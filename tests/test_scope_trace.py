"""`benchmark/harness/scope_trace.py`: the decoder of a trace's `tf_op`s
against the chip traces the benchmark's tests keep (recorded on the v5e by
`benchmark/tests/record_trace*.py`) and against an `XSpace` made here by
hand from the same field numbers; the reduction to phases and scopes
against both."""

import gzip
import os

import pytest

from benchmark.harness import scope_trace, xplane
from ray_tpu.models import layers

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests", "data")
RECORDED = ("tpu1", "tpu4", "tpu1_olmoe", "tpu1_deepseek_v3",
            "tpu1_lfm2_moe")
# the program's scopes, and the two of the split backward that the recorded
# programs (PR 36's) still had: the reader is tested on their names too
VOCABULARY = (*layers.SCOPES, "attention/kernel/bwd_dq",
              "attention/kernel/bwd_dkv")


def recorded(name: str) -> str:
    return os.path.join(DATA, f"{name}.xplane.pb.gz")


# -- the recorded traces -----------------------------------------------------

@pytest.mark.parametrize("name", RECORDED)
def test_events_are_those_profile_data_shows(name):
    """The same operations at the same times as `xplane.load` reads with
    jax's own reader (which rounds both a start and a duration down to a
    nanosecond; here they keep the trace's picoseconds)."""
    ours = scope_trace.events(recorded(name))
    theirs = [(plane, dict(lines)[xplane.OP_LINE])
              for plane, lines in xplane.load(recorded(name))
              if xplane.DEVICE_PLANE.match(plane)]
    assert [plane for plane, _ in ours] == [plane for plane, _ in theirs]
    assert len(ours) == (4 if name == "tpu4" else 1)
    for (_, mine), (_, jax_s) in zip(ours, theirs):
        assert len(mine) == len(jax_s) > 1000
        for ((text, _), start, end), (op, start_ns, end_ns) in zip(
                mine, jax_s):
            assert xplane.op_name(text) == op
            assert int(start) == start_ns
            assert 0 <= end - end_ns < 2


@pytest.mark.parametrize("name,tf_op", [
    ("tpu1_lfm2_moe",
     "jit(train_step)/jvp(operator)/attention/kernel/cond/branch_0_fun/"
     "jit(_pallas_forward)/pallas_call:"),
    ("tpu1_lfm2_moe",
     "jit(train_step)/transpose(jvp(jvp()))/checkpoint/"
     "rematted_computation/operator/attention/kernel/cond/branch_0_fun/"
     "jit(_pallas_forward)/pallas_call:"),
    ("tpu1_lfm2_moe",
     "jit(train_step)/transpose(jvp(jvp()))/checkpoint/operator/attention/"
     "kernel/cond/branch_0_fun/jit(_pallas_backward)/pallas_call:"),
    ("tpu1_lfm2_moe",
     "jit(train_step)/jvp(ffn)/moe/route/jit(take_along_axis)/gather:"),
    ("tpu1_lfm2_moe", "jit(train_step)/optimizer_update/add:"),
    ("tpu1_deepseek_v3",
     "jit(train_step)/routing_bias_update/reduce_sum:"),
    ("tpu1_olmoe", "ragged-dot-none:"),
    ("tpu1", "jit(train_step)/jvp(norm)/mul:"),
    ("tpu1", "jit(train_step)/transpose(jvp(ffn))/dense/dot_general:"),
    ("tpu1", "jit(train_step)/jvp(attention)/qkv/dot_general:"),
])
def test_the_names_are_in_the_file(name, tf_op):
    found = {key[1] for _, line in scope_trace.events(recorded(name))
             for key, _, _ in line}
    assert tf_op in found


@pytest.mark.parametrize("name", RECORDED)
def test_phases_sum_to_busy_and_nothing_counts_twice(name):
    planes = scope_trace.events(recorded(name))
    found = scope_trace.reduce(planes, VOCABULARY, layers.COMPILER_NAMED)
    assert found["devices"] == len(planes)
    assert sum(found["phases"].values()) == pytest.approx(found["busy_s"])
    # busy as the accepted reduction has it (to the nanoseconds it drops)
    accepted = xplane.reduce_file(recorded(name))
    if name != "tpu4":          # there collectives in flight count as busy
        assert found["busy_s"] == pytest.approx(accepted["busy_s"],
                                                rel=2e-3)
    # a conditional's children are not counted again with the conditional:
    # the segments of a device do not overlap, so their sum is the union
    for _, line in planes:
        nested = sum(end - start for _, start, end in line)
        segments = xplane.leaves(line)
        flat = sum(end - start for _, start, end in segments)
        assert flat == pytest.approx(xplane.total(xplane.union(
            (s, e) for _, s, e in segments)))
        assert flat <= nested
    if name in ("tpu1_lfm2_moe", "tpu1_deepseek_v3"):
        # these steps do nest operations (a share's buffer: `lax.cond`)
        assert any(
            sum(e - s for _, s, e in line)
            > 1.001 * sum(e - s for _, s, e in xplane.leaves(line))
            for _, line in planes)
    # top-level scopes hold their sub-scopes' time and sum to `named_s`
    top = sum(s for scope, s in found["scopes"].items() if "/" not in scope)
    assert top == pytest.approx(found["named_s"])
    own = sum(s for by_phase in found["in_scope"].values()
              for s in by_phase.values())
    assert own == pytest.approx(found["named_s"])


def test_lfm2_trace_reads_by_name():
    """The chip trace of the small LFM2 mixture (recorded at PR 34, with
    its `operator/` prefix, which a reader passes over): `remat` on, so
    there is a recomputed forward; every operator of the vocabulary ran."""
    found = scope_trace.reduce(
        scope_trace.events(recorded("tpu1_lfm2_moe")), VOCABULARY,
        layers.COMPILER_NAMED)
    share = lambda s: 100.0 * s / found["busy_s"]
    phases = {k: share(v) for k, v in found["phases"].items()}
    assert phases["fwd"] == pytest.approx(31.9, abs=0.1)
    assert phases["remat_fwd"] == pytest.approx(17.8, abs=0.1)
    assert phases["bwd"] == pytest.approx(34.6, abs=0.1)
    assert phases["optimizer"] == pytest.approx(0.58, abs=0.02)
    scopes = {k: share(v) for k, v in found["scopes"].items()}
    assert scopes["attention"] == pytest.approx(26.8, abs=0.1)
    assert scopes["attention/kernel"] == pytest.approx(20.9, abs=0.1)
    assert scopes["short_conv"] == pytest.approx(5.0, abs=0.1)
    assert scopes["ffn/moe"] > scopes["ffn/dense"] > 0
    # XLA's grouped-matmul kernels, by `COMPILER_NAMED`
    assert found["in_scope"]["ffn/moe/experts"]["other"] > 0
    assert {"short_conv/in_proj", "short_conv/gate_taps",
            "short_conv/out_proj", "attention/qkv", "attention/out",
            "ffn/moe/route", "ffn/moe/dispatch", "ffn/moe/combine",
            "head_and_loss", "optimizer_update",
            "routing_bias_update"} <= set(scopes)
    assert found["unnamed_ops"] and found["unnamed_ops"][0][1] > 0


def test_gpt2_trace_reads_by_name():
    """`tpu1.xplane.pb.gz`: GPT-2 with its scopes on one chip
    (`record_trace.py`, PR 36).  `remat` off: no recomputed forward."""
    found = scope_trace.reduce(scope_trace.events(recorded("tpu1")),
                               VOCABULARY, layers.COMPILER_NAMED)
    share = lambda s: 100.0 * s / found["busy_s"]
    assert found["phases"]["remat_fwd"] == 0.0
    assert share(found["phases"]["fwd"]) == pytest.approx(38.9, abs=0.1)
    assert share(found["phases"]["bwd"]) == pytest.approx(58.2, abs=0.1)
    assert share(found["phases"]["optimizer"]) == pytest.approx(0.88,
                                                                abs=0.02)
    assert share(found["scopes"]["ffn/dense"]) == pytest.approx(27.2,
                                                                abs=0.1)
    assert {"embed", "norm", "attention/qkv", "attention/out",
            "attention/kernel/fwd_lanes",
            "attention/kernel/bwd_fused_lanes", "ffn/dense",
            "head_and_loss", "optimizer_update"} <= set(found["scopes"])
    assert share(found["named_s"]) == pytest.approx(97.2, abs=0.1)


def test_a_program_without_a_vocabulary_still_has_phases():
    found = scope_trace.reduce(scope_trace.events(recorded("tpu1_olmoe")))
    assert found["scopes"] is None and found["named_s"] is None
    assert found["phases"]["bwd"] > found["phases"]["fwd"] > 0


# -- names --------------------------------------------------------------------

@pytest.mark.parametrize("tf_op,phase,scope", [
    ("jit(train_step)/jvp(ffn)/moe/route/jit(take_along_axis)/gather:",
     "fwd", "ffn/moe/route"),
    ("jit(train_step)/jvp(operator)/attention/kernel/cond/branch_0_fun/"
     "jit(_pallas_forward)/fwd_rows/pallas_call:",
     "fwd", "attention/kernel/fwd_rows"),
    ("jit(train_step)/transpose(jvp(jvp()))/checkpoint/"
     "rematted_computation/attention/qkv/norm/mul:",
     "remat_fwd", "attention/qkv"),
    ("jit(train_step)/transpose(jvp(jvp()))/checkpoint/attention/kernel/"
     "cond/branch_0_fun/jit(_pallas_backward)/bwd_dkv/pallas_call:",
     "bwd", "attention/kernel/bwd_dkv"),
    ("jit(train_step)/transpose(jvp(norm))/reduce_sum:", "bwd", "norm"),
    ("jit(train_step)/optimizer_update/add:", "optimizer",
     "optimizer_update"),
    ("jit(train_step)/routing_bias_update/sign:", "optimizer",
     "routing_bias_update"),
    ("jit(train_step)/jvp(head_and_loss)/while/body/closed_call/checkpoint/"
     "rematted_computation/dot_general:", "remat_fwd", "head_and_loss"),
    ("jit(train_step)/jvp()/convert_element_type:", "fwd", ""),
    ("jit(train_step)/jvp(ffn)/moe/shard_map/cond/branch_1_fun/combine/"
     "reduce_sum:", "fwd", "ffn/moe/combine"),
    ("jit(train_step)/iota:", "other", ""),
    ("ragged-dot-none:", "other", "ffn/moe/experts"),
    ("", "unnamed", ""),
])
def test_phase_and_scope_of_a_name(tf_op, phase, scope):
    assert scope_trace.phase_of(tf_op) == phase
    assert scope_trace.scope_of(tf_op, VOCABULARY,
                                layers.COMPILER_NAMED) == scope


# -- an XSpace made by hand --------------------------------------------------

def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def number(field: int, value: int) -> bytes:
    return varint(field << 3) + varint(value)


def message(field: int, body: bytes) -> bytes:
    return varint(field << 3 | 2) + varint(len(body)) + body


def text(field: int, value: str) -> bytes:
    return message(field, value.encode())


def double(field: int) -> bytes:
    return varint(field << 3 | 1) + bytes(8)


def entry(field: int, key: int, value: bytes) -> bytes:
    return message(field, number(1, key) + message(2, value))


def hand_made() -> bytes:
    """One device plane (and a host plane, and a line, that must be passed
    over).  Stat 7 is `tf_op`, stat 8 is a name used through `ref_value`.
    Operations, times in ps from the line's 1,000 ns: a forward matmul
    0-100,000; a conditional 100,000-400,000 with a backward kernel nested
    150,000-350,000; an unnamed copy 400,000-450,000; idle; an optimizer
    fusion 500,000-600,000 whose `tf_op` is a `ref_value`."""
    stats = (entry(5, 7, number(1, 7) + text(2, "tf_op"))
             + entry(5, 8, number(1, 8) + text(
                 2, "jit(train_step)/optimizer_update/add:"))
             + entry(5, 9, number(1, 9) + text(2, "flops")))

    def operation(key, name, tf_op=None, ref=None):
        body = number(1, key) + text(2, name)
        body += message(5, number(1, 9) + number(3, 12345) + double(2))
        if tf_op is not None:
            body += message(5, number(1, 7) + text(5, tf_op))
        if ref is not None:
            body += message(5, number(1, 7) + number(7, ref))
        return entry(4, key, body)

    def event(meta, offset_ps, duration_ps):
        return message(4, number(1, meta) + number(2, offset_ps)
                       + number(3, duration_ps)
                       + message(4, number(1, 9) + number(3, 1)))

    operations = (
        operation(1, "%fusion.1 = bf16[8,8]{1,0} fusion(%p), kind=kOutput",
                  "jit(train_step)/jvp(ffn)/dense/dot_general:")
        + operation(2, "%conditional.1 = bf16[8,8]{1,0} conditional(%p)")
        + operation(3, "%bwd_fused.1 = bf16[8,8]{1,0} custom-call(%p), "
                       'custom_call_target="tpu_custom_call"',
                    "jit(train_step)/transpose(jvp(attention))/kernel/"
                    "jit(_pallas_backward)/bwd_fused/pallas_call:")
        + operation(4, "%copy.1 = bf16[8,8]{1,0} copy(%p)")
        + operation(5, "%fusion.2 = f32[8]{0} fusion(%p), kind=kLoop",
                    ref=8))
    ops_line = (number(1, 1) + text(2, "XLA Ops") + number(3, 1000)
                + event(1, 0, 100_000) + event(2, 100_000, 300_000)
                + event(3, 150_000, 200_000) + event(4, 400_000, 50_000)
                + event(5, 500_000, 100_000))
    modules = (number(1, 2) + text(2, "XLA Modules") + number(3, 1000)
               + event(1, 0, 600_000))
    device = (number(1, 1) + text(2, "/device:TPU:0") + message(3, modules)
              + message(3, ops_line) + operations + stats)
    host = (number(1, 2) + text(2, "/host:CPU")
            + message(3, number(1, 1) + text(2, "XLA Ops")
                      + event(1, 0, 999_000)))
    return message(1, host) + message(1, device) + text(4, "a hostname")


def test_hand_made_xspace(tmp_path):
    (plane, line), = scope_trace.decode(hand_made())
    assert plane == "/device:TPU:0"
    assert [(key[1], start, end) for key, start, end in line] == [
        ("jit(train_step)/jvp(ffn)/dense/dot_general:", 1000.0, 1100.0),
        ("", 1100.0, 1400.0),
        ("jit(train_step)/transpose(jvp(attention))/kernel/"
         "jit(_pallas_backward)/bwd_fused/pallas_call:", 1150.0, 1350.0),
        ("", 1400.0, 1450.0),
        ("jit(train_step)/optimizer_update/add:", 1500.0, 1600.0)]
    assert line[0][0][0].startswith("%fusion.1 = bf16[8,8]")
    path = tmp_path / "made.xplane.pb.gz"
    with gzip.open(path, "wb") as f:
        f.write(hand_made())
    found = scope_trace.reduce_file(str(path))
    ns = 1e-9
    assert found["devices"] == 1
    assert found["busy_s"] == pytest.approx(550 * ns)     # 50 ns idle
    assert found["phases"] == pytest.approx({
        "fwd": 100 * ns, "remat_fwd": 0.0, "bwd": 200 * ns,
        "optimizer": 100 * ns, "other": 0.0,
        # the conditional's own 100 ns around its child, and the copy
        "unnamed": 150 * ns})
    assert found["scopes"] == pytest.approx({
        "ffn": 100 * ns, "ffn/dense": 100 * ns, "attention": 200 * ns,
        "attention/kernel": 200 * ns, "attention/kernel/bwd_fused": 200 * ns,
        "optimizer_update": 100 * ns})
    assert found["named_s"] == pytest.approx(400 * ns)
    assert found["in_scope"]["attention/kernel/bwd_fused"] == pytest.approx(
        {"bwd": 200 * ns})
    assert [op for op, _ in found["unnamed_ops"]] == [
        "conditional_bf16_8_8_", "copy_bf16_8_8_"]
    assert found["unscoped_ops"] == []


def test_not_an_xspace_is_refused():
    with pytest.raises(ValueError):
        scope_trace.decode(b"\x0b\x00")        # a group: never in an XSpace
    assert scope_trace.decode(b"") == []
    assert scope_trace.reduce([]) is None


def test_a_run_without_a_trace_reads_nothing():
    obs = {"trace": None, "peaks": None}
    assert scope_trace.of(obs) is None
    assert scope_trace.share(obs, "attention") is None
    assert scope_trace.phase_share(obs, "fwd") is None
