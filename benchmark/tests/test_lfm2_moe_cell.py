"""The `lfm2_moe` family and its cell: the configuration against the
published `config.json`, the yardstick's counts worked by hand, the
predicates that tell a trace's operations apart, the new readers on a trace
recorded on the chip, and a rehearsal of the cell."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import moe_trace, registry, shortconv_trace, xplane

CONFIG = "lfm2-24b-a2b-ep8-5layer"
CELL = CONFIG + ".resident-8k"
BATCH, SEQ = 2, 8192
TOKENS = BATCH * SEQ
E, H, HKV, D, V = 2048, 32, 8, 64, 8192
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "tpu1_lfm2_moe.xplane.pb.gz")
NEW_METRICS = ("shortconv_share", "shortconv_roofline_share",
               "attn_kv_heads_read_share")

# `LiquidAI/LFM2-24B-A2B`'s config.json, as the catalog of public
# architectures holds it
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv"] + ["full_attention", "conv", "conv",
                                       "conv"] * 9 + ["full_attention",
                                                      "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}
CUT = ["num_hidden_layers", "num_dense_layers", "layer_types", "num_experts",
       "vocab_size"]


@pytest.fixture(scope="module")
def family():
    return registry.family(registry.config(CONFIG))


def test_only_depth_pattern_experts_held_and_vocabulary_are_cut():
    config = registry.config(CONFIG)
    entry = [c for c in registry.benchmark()["configs"]
             if c["name"] == CONFIG][0]
    assert entry["reduced"] == config["reduced"] == CUT
    assert sorted(k for k, v in PUBLISHED.items() if config[k] != v) \
        == sorted(CUT)
    assert len(PUBLISHED["layer_types"]) == 40
    # layers 1-5 of the published list: one dense layer, one whole period
    assert config["layer_types"] == PUBLISHED["layer_types"][1:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (5, 1, 8, 8192)
    assert {k: config["published"][k] for k in CUT if k != "layer_types"} \
        == {k: PUBLISHED[k] for k in CUT if k != "layer_types"}
    # the router keeps its published width and the experts held are said
    assert config["experts_held"] == {
        "first": 0, "of": 64, "why": config["experts_held"]["why"]}
    assert config["vocab_size"] % 128 == 0          # nothing is padded
    assert config["name"] == entry["name"] and config["deployment"]
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    for key in ("tie_word_embeddings", "renorm_eps", "bias_update_speed",
                "auxiliary_loss", "initialisation", "training", "remat",
                "loss_chunk_rows"):
        assert config["assumed"][key]
    for key in ("loss_tolerance", "loss_tolerance_reason", "what"):
        assert config["reference"][key]


def test_the_cell_is_what_the_issue_names():
    cell = registry.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "resident-8k", 1)
    assert len(cell["why"]) <= 200
    traffic = registry.traffic("resident-8k")
    assert (traffic["batch"], traffic["seq"], traffic["source"],
            traffic["loop"]) == (BATCH, SEQ, "resident", "train_steps")
    end = [m["name"] for m in registry.metrics_of(CELL, "end_to_end")]
    assert end == ["tokens_per_s", "setup_s"]
    layer = [m["name"] for m in registry.metrics_of(CELL, "per_layer")]
    for name in NEW_METRICS + ("attn_roofline_share", "attn_kernel_share",
                               "mfu", "hbm_peak_gib", "step_device_ms"):
        assert name in layer
    for name in ("moe_share", "moe_held_share", "mla_proj_share",
                 "moe_rows_buffered_share", "collective_share"):
        assert name not in layer
    new = [m for m in registry.benchmark()["per_layer"]
           if m["name"] in NEW_METRICS]
    assert [m["name"] for m in new] == list(NEW_METRICS)
    # every new metric lists the new cell alone
    assert all(m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
               for m in new)
    # no other cell reports them, and the accepted cells are as they were
    for other in registry.benchmark()["workloads"]:
        if other["name"] != CELL:
            names = [m["name"] for m in registry.metrics_of(
                other["name"], "per_layer")]
            assert not set(names) & set(NEW_METRICS)


def test_counts_by_hand(family):
    conv = 4 * E * E + E * 3                               # 16.78 M
    attn = 2 * E * E + 2 * E * HKV * D                     # 10.49 M
    assert family.shortconv_params() == conv == 16_783_360
    assert family.attention_params() == attn == 10_485_760
    dense, expert = 3 * E * 11776, 3 * E * 1536
    routed = E * 64 + 64 + 8 * expert
    assert family.param_count() == V * E + E + 5 * 2 * E + 4 * conv \
        + attn + 2 * D + dense + 4 * routed
    # ISSUE 34's arithmetic: 469.3 M, 7.51 GB at 16 bytes
    assert round(family.param_count() / 1e6, 1) == 469.3
    assert family.expected_rows_per_token() == 0.5
    n = V * E + 4 * conv + attn + dense + 4 * (E * 64 + 0.5 * expert)
    assert family.multiplying_params_per_token() == n
    assert family.flops_per_token(SEQ) == 6 * n + 6 * SEQ * H * 2 * D
    assert round(family.flops_per_token(SEQ) / 1e9, 2) == 1.32
    # twice the expected load of 8 of 64 at 4 a token: the tokens' count
    assert family.buffered_rows(TOKENS) == 16384


def test_counts_are_the_programs_own(family):
    import jax

    from ray_tpu.models import lfm2_moe as model
    from ray_tpu.ops.moe import buffer_rows

    cfg = family.model_config()
    assert family.flops_per_token(SEQ) == model.count_flops_per_token(
        cfg, SEQ)
    shapes = jax.eval_shape(lambda key: model.init_params(key, cfg),
                            jax.random.PRNGKey(0))
    assert model.num_params(shapes) == family.param_count()
    assert cfg.held == (0, 8) and cfg.n_experts == 64 and cfg.remat
    assert cfg.layer_types == ("conv", "full_attention", "conv", "conv",
                               "conv")
    assert family.buffered_rows(TOKENS) == buffer_rows(TOKENS * 4, 8, 64)


def test_attention_cost_by_hand(family):
    # one attention layer; 32 query heads: six products 64 deep, each
    # 2 B H S^2 D operations, halved for causality; q, o, do, dq at 32
    # heads and k, v (twice) , dk, dv at 8, in bf16; two of B H S in f32
    flops = 6 * 2 * BATCH * H * SEQ * SEQ * D // 2
    nbytes = 6 * BATCH * SEQ * D * (H + HKV) * 2 + 2 * BATCH * H * SEQ * 4
    assert family.attention_cost(BATCH, SEQ) == {"flops": flops,
                                                 "bytes": nbytes}
    peaks = registry.peaks("TPU v5 lite")
    seconds, bound = registry.metric("attn_roofline_share").least_seconds({
        "family": family, "chips": 1, "peaks": peaks,
        "traffic": registry.traffic("resident-8k")})
    assert bound == "compute"
    assert seconds == pytest.approx(flops / 197e12, rel=1e-3)


def test_shortconv_cost_by_hand(family):
    """W_in's forward and its weight gradient alone: what a trace names."""
    cost = family.shortconv_cost(BATCH, SEQ)
    assert cost["flops"] == 4 * 2 * 2 * TOKENS * E * 3 * E
    assert cost["bytes"] == 4 * (2 * 4 * E * TOKENS * 2 + 3 * E * E * 6)
    # under the whole operator's 6 operations a parameter
    assert cost["flops"] < 4 * 6 * family.shortconv_params() * TOKENS
    seconds, bound = registry.metric("shortconv_roofline_share") \
        .least_seconds({"family": family, "chips": 1,
                        "peaks": registry.peaks("TPU v5 lite"),
                        "traffic": registry.traffic("resident-8k")})
    assert bound == "compute"
    assert seconds == pytest.approx(cost["flops"] / 197e12, rel=1e-3)


# names as `harness/xplane.py:op_name` gives them for the full-size step
# (my traced chip run, PR 34)
ATTENTION = [
    "tpu_custom_call__bf16_64_8192_64___f32_64_8192_1__",     # forward
    "tpu_custom_call_bf16_64_8192_64_",                       # dq
    "tpu_custom_call__f32_64_8192_64___f32_64_8192_64__",     # dk, dv parts
]
MOE_MATMULS = [
    "tpu_custom_call_bf16_16384_1536_", "tpu_custom_call_bf16_16384_2048_",
    "tpu_custom_call_bf16_8_2048_1536_", "tpu_custom_call_bf16_8_1536_2048_",
    "tpu_custom_call__s32_9___s32_39___s32_39___s32_1__",     # group layout
]
MOE_OTHER = [
    "fusion:kCustom_f32_65536_", "fusion:kCustom_bf16_16384_2048_",
    "sort__s32_65536___s32_65536__", "sort__f32_16384_64___s32_16384_64__",
    "convert_bf16_8_2048_1536_", "copy_bf16_8_1536_2048_",
]
SHORTCONV = [
    "fusion:kOutput_bf16_2_8192_6144_",                       # W_in
    "fusion:kOutput__f32_2048_6144___f32_2048_6144___f32_2048_6144__",
    "convert_bf16_2048_6144_", "copy-done_bf16_2048_6144_",
    "fusion:kLoop__f32_2048_3___f32_2048_3___f32_2048_3__",   # the taps
    "convert_bf16_2048_3_",
]
NEITHER = [
    "fusion:kOutput_bf16_2_8192_11776_",                      # dense F
    "fusion:kOutput__f32_2048_11776___f32_2048_11776___f32_2048_11776",
    # (B, S, E) results: W_out, the gates and taps, W_in's gradient to u
    "fusion:kOutput__f32_2_8192___bf16_2_8192_2048__",
    "fusion:kOutput__bf16_2048___bf16_2_8192___bf16_2_8192_2048__",
    "fusion:kLoop_bf16_2_8192_2048_",
    # E x E gradients: W_out's, W_q's, W_o's
    "fusion:kOutput__f32_2048_2048___f32_2048_2048___f32_2048_2048__",
    "reduce_bf16_2_8_8192_64_",             # a group's parts of dk, dv summed
    "copy_bf16_2_8192_32_64_", "fusion:kOutput__f32_2048___f32_2048_8192__",
]


def test_operations_are_told_apart_by_shape(family):
    for name in ATTENTION:
        assert family.is_attention_kernel(name), name
        assert not family.is_moe_matmul(name), name
        assert not family.is_shortconv_op(name), name
    for name in MOE_MATMULS:
        assert family.is_moe_matmul(name), name
        assert family.is_moe_op(name, TOKENS), name
        assert not family.is_attention_kernel(name), name
        assert not family.is_shortconv_op(name), name
    for name in MOE_OTHER:
        assert family.is_moe_op(name, TOKENS), name
        assert not family.is_moe_matmul(name), name
        assert not family.is_shortconv_op(name), name
    for name in SHORTCONV:
        assert family.is_shortconv_op(name), name
        assert not family.is_moe_op(name, TOKENS), name
        assert not family.is_attention_kernel(name), name
    for name in NEITHER:
        assert not family.is_shortconv_op(name), name
        assert not family.is_attention_kernel(name), name
        assert not family.is_moe_matmul(name), name


def test_reduction_on_hand_made_events(family):
    """One device, times in ns: W_in 0-100, its weight gradient 100-130, an
    attention kernel 130-200, idle 200-210, W_out's (B, S, E) result
    210-300; two steps."""
    planes = [("/device:TPU:0", [
        (xplane.OP_LINE, [
            ("fusion:kOutput_bf16_2_8192_6144_", 0, 100),
            ("fusion:kOutput__f32_2048_6144___f32_2048_6144__", 100, 130),
            ("tpu_custom_call_bf16_64_8192_64_", 130, 200),
            ("fusion:kOutput_bf16_2_8192_2048_", 210, 300)]),
        (xplane.MODULE_LINE, [("jit_train_step", 0, 150),
                              ("jit_train_step", 150, 300)])])]
    found = moe_trace.reduce(planes, family.is_shortconv_op, lambda op: False)
    assert found["steps"] == 2
    assert found["busy_s"] == pytest.approx(290e-9)
    assert found["moe_s"] == pytest.approx(130e-9)
    whole = xplane.reduce(planes, is_kernel=family.is_attention_kernel)
    assert whole["kernel_s"] == pytest.approx(70e-9)


RECORDED_BUSY_S, RECORDED_SHORTCONV_S = 8.396237e-3, 1.99786e-4
RECORDED_KERNEL_S = 1.649679e-3


@pytest.fixture(scope="module")
def recorded():
    """The trace `record_trace_lfm2_moe.py` recorded on one v5e chip (three
    steps of a dense conv layer, a routed attention layer and a routed
    conv layer, recomputed: hidden 256, four query heads on two key/value
    heads of 64, sixteen experts of which four are held, batch 2 x 2,048),
    with the family of the sizes it ran."""
    import record_trace_lfm2_moe as recorder
    from benchmark.families.lfm2_moe import Family

    return (xplane.load(RECORDED), Family(recorder.CONFIG),
            recorder.BATCH * recorder.SEQ)


def test_recorded_trace_names_the_operations_it_should(recorded):
    """No operation is two things; the attention kernels are the forward
    (o and its statistics), dq, and the float32 parts of dk and dv, one a
    query head; the conv operators' named operations are W_in's."""
    planes, small, tokens = recorded
    kinds, conv = {}, set()
    for name, lines in planes:
        if not xplane.DEVICE_PLANE.match(name):
            continue
        for op, start, end in dict(lines)[xplane.OP_LINE]:
            is_a = (small.is_attention_kernel(op),
                    small.is_moe_op(op, tokens), small.is_shortconv_op(op))
            assert sum(is_a) <= 1, op
            kinds[is_a] = kinds.get(is_a, 0) + 1
            if is_a[2]:
                conv.add(op)
    assert all(kinds.get(k) for k in (
        (True, False, False), (False, True, False), (False, False, True),
        (False, False, False)))
    whole = xplane.reduce(planes, is_kernel=small.is_attention_kernel)
    assert sorted(whole["kernels"]) == [
        "tpu_custom_call__bf16_8_2048_64___f32_8_2048_1__",
        "tpu_custom_call__f32_8_2048_64___f32_8_2048_64__",
        "tpu_custom_call_bf16_8_2048_64_"]
    assert "fusion:kOutput_bf16_2_2048_768_" in conv          # W_in
    assert all("768" in op or "_256_3_" in op or "_3_256_" in op
               for op in conv), conv


def test_recorded_trace_reads_as_it_did(recorded):
    """Values as first reduced (PR 34): a change to the readers or to the
    family's predicates that moves them has changed what the metrics
    mean."""
    planes, small, tokens = recorded
    found = moe_trace.reduce(planes, small.is_shortconv_op, lambda op: False)
    whole = xplane.reduce(planes, is_kernel=small.is_attention_kernel)
    assert found["steps"] == 3
    assert found["busy_s"] == pytest.approx(whole["busy_s"])
    assert found["busy_s"] == pytest.approx(RECORDED_BUSY_S, rel=1e-6)
    assert found["moe_s"] == pytest.approx(RECORDED_SHORTCONV_S, rel=1e-6)
    assert whole["kernel_s"] == pytest.approx(RECORDED_KERNEL_S, rel=1e-6)




def test_readers_on_a_known_reduction(family, monkeypatch):
    obs = {"family": family, "chips": 1,
           "peaks": registry.peaks("TPU v5 lite"),
           "traffic": registry.traffic("resident-8k")}
    monkeypatch.setattr(shortconv_trace, "of", lambda obs: {
        "steps": 4, "busy_s": 1.0, "shortconv_s": 0.125})
    assert registry.metric("shortconv_share").read(obs) \
        == pytest.approx(12.5)
    # four steps' least time over 0.125 s: compute-bound, 4 layers x 2
    # products x 2 T E 3E operations at 197 TFLOP/s
    least = 4 * 2 * 2 * TOKENS * E * 3 * E / 197e12
    assert registry.metric("shortconv_roofline_share").read(obs) \
        == pytest.approx(100 * 4 * least / 0.125, rel=1e-3)
    monkeypatch.setattr(shortconv_trace, "of", lambda obs: None)
    for name in NEW_METRICS[:2]:
        assert registry.metric(name).read(obs) is None


def test_kv_heads_read_share_reads_the_counters():
    from benchmark.harness import timeline

    value = registry.metric("attn_kv_heads_read_share").value
    tl = timeline.Timeline(
        {"spans": [], "counters": {"attention.q_heads": 96,
                                   "attention.kv_heads": 24}},
        {"t_open": 0.0, "window_s": 1.0})
    assert value(tl) == 25.0
    tl.counters["attention.kv_heads"] = 96      # repeated before the call
    assert value(tl) == 100.0
    # the parent's program counts nothing of the kind
    tl.counters = {"attention.tiles": 4}
    assert value(tl) is None


def test_readers_find_nothing_for_other_families_or_without_a_trace(family):
    """No traced run, a rehearsal, a family without the operator, a trace
    older than the run: None, never an exception."""
    base = {"family": family, "chips": 1, "t_fit": 0.0,
            "config": registry.config(CONFIG),
            "traffic": registry.traffic("resident-8k")}
    peaks = registry.peaks("TPU v5 lite")
    others = [registry.family(registry.config(name)) for name in (
        "gpt2-medium", "olmoe-1b-7b-1layer", "kanana-2-30b-a3b-ep8-5layer")]
    cases = [dict(base, peaks=peaks),                             # no trace
             dict(base, peaks=None, trace={"steps": 1}),          # rehearsal
             dict(base, peaks=peaks, trace={"steps": 1}, t_fit=4e9)]
    cases += [dict(base, peaks=peaks, trace={"steps": 1}, family=other)
              for other in others]
    for obs in cases:
        for name in NEW_METRICS[:2]:
            assert registry.metric(name).read(obs) is None
    assert registry.metric("attn_kv_heads_read_share").read(
        dict(base, peaks=None)) is None
    for other in others:
        assert not hasattr(other, "is_shortconv_op")


def run_cell(*args):
    cmd = [sys.executable, os.path.join(registry.ROOT, "benchmark", "run.py"),
           *args, "--rehearse"]
    return subprocess.run(cmd, cwd=registry.ROOT, capture_output=True,
                          text=True, timeout=900,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))


@pytest.mark.parametrize("trace, read", [
    (0, ["setup_s", "tokens_per_s"]),
    (1, ["lower_compile_s", "report_ms", "spawn_s"]),
])
def test_cell_rehearses(trace, read):
    proc = run_cell("--workload", CELL, "--seed", "2147483659", "--seconds",
                    "2", "--trace", str(trace))
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "platform=cpu" in proc.stdout
    assert result["correct"] is True, proc.stdout[-3000:]
    assert result["rehearsal"] is True and result["metrics"] == {}
    assert result["read"] == read
    assert result["attempted"] > 0 and result["failed"] == 0
    # what this PR counts is in the run's own timeline
    with open(os.path.join(registry.ROOT, ".scratch", "benchmark", CELL, CELL,
                           "timeline.json")) as f:
        counters = json.load(f)["counters"]
    # the rehearsal's dense conv layer and its routed conv layers (two,
    # alike and recomputed: traced once) count one layer each; the one
    # attention layer counts a forward and a backward kernel, 4 query heads
    # on 2 key/value heads
    assert counters["shortconv.layers"] == 2
    assert counters["shortconv.taps"] == 6
    assert counters["attention.q_heads"] == 2 * counters["attention.kv_heads"]
    assert counters["moe.experts"] == 2 * 8
    assert counters["moe.experts_held"] == 2 * 4


FAULTS = {
    # three times the learning rate the configuration states
    "wrong_rate": '''\
        from benchmark.families import lfm2_moe
        from benchmark.reference.lfm2_moe import adamw
        from ray_tpu.models.lfm2_moe import trained_by


        class Family(lfm2_moe.Family):
            def optimizer(self):
                settings = dict(self.config["optimizer"])
                settings["learning_rate"] *= 3
                return trained_by(adamw(settings))
        ''',
    # 8-bit floats where the configuration states bfloat16
    "low_precision": '''\
        import dataclasses

        from benchmark.families import lfm2_moe


        class Family(lfm2_moe.Family):
            def model_config(self):
                import jax.numpy as jnp

                return dataclasses.replace(
                    super().model_config(),
                    compute_dtype=jnp.dtype("float8_e4m3fn"))
        ''',
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_reference_check_catches(tmp_path, fault):
    """A family that departs from what the configuration states (a new
    file in a copy of the benchmark) runs, and its run is not `correct`."""
    import shutil
    import textwrap

    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(registry.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(registry.ROOT, "benchmark"),
                    root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "benchmark" / "families" / f"lfm2_moe_{fault}.py").write_text(
        textwrap.dedent(FAULTS[fault]))
    config = registry.load_json("benchmark", "configs", f"{CONFIG}.json")
    config.update(name=f"lfm2-{fault}", family=f"lfm2_moe_{fault}")
    (root / "benchmark" / "configs" / f"lfm2-{fault}.json").write_text(
        json.dumps(config))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": f"lfm2-{fault}", "source": "test", "reduced": [],
        "why": "test", "file": f"benchmark/configs/lfm2-{fault}.json"})
    bench["workloads"].append({
        "name": f"lfm2-{fault}.resident-8k", "config": f"lfm2-{fault}",
        "traffic": "resident-8k", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    proc = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
         f"lfm2-{fault}.resident-8k", "--seed", "5", "--seconds", "1",
         "--trace", "0", "--rehearse"], cwd=root, capture_output=True,
        text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=registry.ROOT))
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is False
    assert "NOT CORRECT: loss at step" in proc.stdout
    if fault == "wrong_rate":
        # the forward pass is right; the first update is not
        assert "NOT CORRECT: loss at step 0" not in proc.stdout
