"""The `deepseek_v3` family and its cell: the configuration against the
published `config.json`, the yardstick's counts worked by hand, the
predicates that tell a trace's operations apart, the new readers on a trace
recorded on the chip, and a rehearsal of the cell."""

import functools
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from benchmark.harness import mla_trace, moe_trace, registry, xplane

CONFIG = "kanana-2-30b-a3b-ep8-5layer"
CELL = CONFIG + ".resident-8k"
BATCH, SEQ = 2, 8192
TOKENS = BATCH * SEQ
E, H, V = 2048, 32, 16128
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "tpu1_deepseek_v3.xplane.pb.gz")
NEW_METRICS = ("mla_proj_share", "moe_held_share",
               "moe_held_matmul_roofline_share", "moe_rows_buffered_share")

# `kakaocorp/kanana-2-30b-a3b-instruct-2601`'s config.json, as the catalog
# of public architectures holds it
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 1000000, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}
CUT = ["num_hidden_layers", "n_routed_experts", "vocab_size"]


@pytest.fixture(scope="module")
def family():
    return registry.family(registry.config(CONFIG))


def test_only_depth_experts_held_and_vocabulary_are_cut():
    config = registry.config(CONFIG)
    entry = [c for c in registry.benchmark()["configs"]
             if c["name"] == CONFIG][0]
    assert entry["reduced"] == config["reduced"] == CUT
    assert [k for k, v in PUBLISHED.items() if config[k] != v] == sorted(
        CUT, key=list(PUBLISHED).index)
    assert config["published"] == {k: PUBLISHED[k] for k in CUT}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 16, 16032)
    # the router keeps its published width and the experts held are said
    assert config["experts_held"]["of"] == 128
    assert config["experts_held"]["first"] == 0
    assert config["padded_vocab_size"] == 16128 == -(-16032 // 128) * 128
    assert config["name"] == entry["name"] and config["deployment"]
    for key in ("initialisation", "training", "bias_update_speed",
                "auxiliary_loss", "padded_vocab_size", "remat",
                "loss_chunk_rows"):
        assert config["assumed"][key]
    for key in ("loss_tolerance", "loss_tolerance_reason", "what"):
        assert config["reference"][key]


def test_the_cell_is_what_the_issue_names():
    cell = registry.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "resident-8k", 1)
    traffic = registry.traffic("resident-8k")
    assert (traffic["batch"], traffic["seq"], traffic["source"],
            traffic["loop"]) == (BATCH, SEQ, "resident", "train_steps")
    assert traffic["warmup_steps"] == 5 and traffic["trace_seconds"] == 1.0
    end = [m["name"] for m in registry.metrics_of(CELL, "end_to_end")]
    assert end == ["tokens_per_s", "setup_s"]
    layer = [m["name"] for m in registry.metrics_of(CELL, "per_layer")]
    for name in NEW_METRICS + ("attn_roofline_share", "attn_kernel_share",
                               "mfu", "hbm_peak_gib", "step_device_ms"):
        assert name in layer
    for name in ("moe_share", "moe_dispatch_share",
                 "moe_matmul_roofline_share", "collective_share"):
        assert name not in layer
    new = [m for m in registry.benchmark()["per_layer"]
           if m["name"] in NEW_METRICS]
    assert [m["name"] for m in new] == list(NEW_METRICS)
    assert all(m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
               for m in new)
    # nothing that was there moved: the new entries are the last ones
    bench = registry.benchmark()
    assert bench["configs"][-1]["name"] == CONFIG
    assert bench["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(NEW_METRICS)


def test_counts_by_hand(family):
    attn = E * 6144 + E * 576 + 512 * 8192 + 4096 * E        # 26.35 M
    assert family.attention_params() == attn == 26_345_472
    shared, expert, dense = 3 * E * 1536, 3 * E * 768, 3 * E * 6144
    routed_layer = attn + 512 + 2 * E + E * 128 + 128 + shared + 16 * expert
    dense_layer = attn + 512 + 2 * E + dense
    assert family.param_count() == 2 * V * E + E + dense_layer \
        + 4 * routed_layer
    # ISSUE 32's table at the 16,032 published rows: 576.0 M (9.2 GB at 16
    # bytes); the 96 spare rows of padding add 0.4 M
    assert round((family.param_count() - 2 * 96 * E) / 1e6, 1) == 576.0
    assert family.expected_rows_per_token() == 0.75
    n = V * E + 5 * attn + dense + 4 * (E * 128 + shared + 0.75 * expert)
    assert family.multiplying_params_per_token() == n
    squares = 6 * 5 * SEQ * H * (192 + 128)
    assert family.flops_per_token(SEQ) == 6 * n + squares
    # the cell's `why`: latent attention (its projections and the causal
    # half of its products) is 73 % of the step's forward operations
    mla = 5 * 2 * attn + squares / 3 / 2
    rest = 2 * dense + 4 * 2 * (E * 128 + shared + 0.75 * expert) + 2 * V * E
    assert round(100 * mla / (mla + rest)) == 73


def test_flops_are_the_programs_own_count(family):
    import jax

    from ray_tpu.models import deepseek_v3 as model

    cfg = family.model_config()
    assert family.flops_per_token(SEQ) == model.count_flops_per_token(
        cfg, SEQ)
    shapes = jax.eval_shape(lambda key: model.init_params(key, cfg),
                            jax.random.PRNGKey(0))
    assert model.num_params(shapes) == family.param_count()
    assert cfg.held == (0, 16) and cfg.n_experts == 128 and cfg.remat


def test_attention_cost_by_hand(family):
    # 32 heads: QK', dQ, dK 192 deep and PV, dV, dP 128 deep, each 2 B H
    # S^2 deep operations, halved for causality; 5 arrays 192 wide and 6
    # 128 wide in bf16 and two of B H S in f32; 5 layers
    square = 2 * BATCH * H * SEQ * SEQ
    flops = 5 * 3 * square * (192 + 128) // 2
    heads = BATCH * SEQ * H
    nbytes = 5 * (heads * (5 * 192 + 6 * 128) * 2 + 2 * heads * 4)
    assert family.attention_cost(BATCH, SEQ) == {"flops": flops,
                                                 "bytes": nbytes}
    peaks = registry.peaks("TPU v5 lite")
    seconds, bound = registry.metric("attn_roofline_share").least_seconds({
        "family": family, "chips": 1, "peaks": peaks,
        "traffic": registry.traffic("resident-8k")})
    assert bound == "compute"
    assert seconds == pytest.approx(flops / 197e12, rel=1e-3)


def test_moe_cost_by_hand(family):
    cost = family.moe_cost(BATCH, SEQ)
    rows = 0.75 * TOKENS                       # expected, a routed layer
    assert cost["flops"] == 4 * 3 * 6 * rows * E * 768
    rows_e, rows_w, weights = rows * E * 2, rows * 768 * 2, 16 * E * 768 * 2
    forward = 2 * (rows_e + rows_w) + (rows_w + rows_e) + 3 * weights
    backward = 3 * (2 * (rows_w + rows_e) + rows_w + rows_e + 2 * weights)
    assert cost["bytes"] == 4 * (forward + backward)


# names as `harness/xplane.py:op_name` gives them for the full-size step
ATTENTION = [
    "tpu_custom_call__bf16_64_8192_128___f32_64_8192_1__",    # forward
    "tpu_custom_call_bf16_64_8192_192_",                      # dq
    "tpu_custom_call__bf16_64_8192_192___bf16_64_8192_128__",  # dk, dv
]
MOE_MATMULS = [
    "tpu_custom_call_bf16_98304_768_",            # gate, up; down's dlhs
    "tpu_custom_call_bf16_98304_2048_",           # down; gate's, up's dlhs
    "tpu_custom_call_bf16_16_2048_768_",          # gate's, up's drhs
    "tpu_custom_call_bf16_16_768_2048_",          # down's drhs
    "tpu_custom_call__s32_17___s32_207___s32_207___s32_1__",   # group layout
]
MOE_OTHER = [
    "fusion:kLoop_bf16_98304_2048_", "sort__s32_98304___s32_98304__",
    "fusion:kOutput__f32_16384_128___f32_16384_128__",
    "sort__f32_16384_128___s32_16384_128__", "fusion:kLoop_f32_16384_6_",
    "fusion:kCustom_bf16_98304_2048_",            # the gathers of rows
    "copy_bf16_16_2048_768_",                     # a transposed stack
    "fusion:kOutput_bf16_16384_1536_",            # the shared experts
]
MLA = [
    "fusion:kOutput_bf16_2_8192_6144_",           # W_q
    "fusion:kOutput_bf16_2_8192_576_",            # W_kv_a
    "fusion:kOutput_bf16_2_8192_8192_",           # W_kv_b
    "fusion:kLoop_bf16_2_8192_512_",              # the latent norm
    "fusion:kLoop_bf16_2_8192_32_192_",           # k assembled
    "copy_bf16_2_32_8192_192_",                   # its transpose, head-major
    "copy_bf16_2_32_8192_128_",                   # a transpose
    "fusion:kLoop_bf16_2_8192_1_64_",             # the shared rotary part
    "fusion:kOutput_f32_2048_6144_",              # W_q's gradient
    "fusion:kOutput_bf16_2_8192_4096_",           # o flat, W_o's operand
    "fusion:kOutput__f32_4096_2048___f32_4096_2048___f32_4096_2048__",
]
NEITHER = [
    "fusion:kOutput_bf16_2_8192_2048_",
    "fusion:kOutput__f32_2048___f32_2048_16128__",      # the head
    # the optimizer's update of the held stacks (the name is cut at 64)
    "fusion:kLoop__f32_16_2048_768___f32_16_2048_768___f32_16_2048_76",
    "fusion:kLoop_bf16_16384_2048_",
    # W_o's result fused with the next norm: (B, S, E), not seen by shape
    "fusion:kOutput__f32_2_8192___bf16_2_8192_2048__",
]


def test_operations_are_told_apart_by_shape(family):
    for name in ATTENTION:
        assert family.is_attention_kernel(name), name
        assert not family.is_moe_matmul(name), name
        assert not family.is_moe_op(name, TOKENS), name
        assert not family.is_mla_op(name, TOKENS), name
    for name in MOE_MATMULS:
        assert family.is_moe_matmul(name), name
        assert family.is_moe_op(name, TOKENS), name
        assert not family.is_attention_kernel(name), name
        assert not family.is_mla_op(name, TOKENS), name
    for name in MOE_OTHER:
        assert family.is_moe_op(name, TOKENS), name
        assert not family.is_moe_matmul(name), name
        assert not family.is_attention_kernel(name), name
        assert not family.is_mla_op(name, TOKENS), name
    for name in MLA:
        assert family.is_mla_op(name, TOKENS), name
        assert not family.is_moe_op(name, TOKENS), name
        assert not family.is_attention_kernel(name), name
    for name in NEITHER:
        assert not family.is_moe_op(name, TOKENS), name
        assert not family.is_attention_kernel(name), name
        assert not family.is_mla_op(name, TOKENS), name


def test_reduction_on_hand_made_events(family):
    """One device, times in ns: a grouped matmul 0-100, a gather of the
    buffered rows 100-130, an attention kernel 130-200, W_q 200-240, idle
    240-250, a fusion that is none of them 250-300; two steps."""
    planes = [("/device:TPU:0", [
        (xplane.OP_LINE, [
            ("tpu_custom_call_bf16_98304_768_", 0, 100),
            ("fusion:kLoop_bf16_98304_2048_", 100, 130),
            ("tpu_custom_call_bf16_64_8192_192_", 130, 200),
            ("fusion:kOutput_bf16_2_8192_6144_", 200, 240),
            ("fusion:kOutput_bf16_2_8192_2048_", 250, 300)]),
        (xplane.MODULE_LINE, [("jit_train_step", 0, 150),
                              ("jit_train_step", 150, 300)])])]
    moe = moe_trace.reduce(
        planes, functools.partial(family.is_moe_op, tokens=TOKENS),
        family.is_moe_matmul)
    assert moe == {"steps": 2, "busy_s": pytest.approx(290e-9),
                   "moe_s": pytest.approx(130e-9),
                   "moe_matmul_s": pytest.approx(100e-9)}
    mla = moe_trace.reduce(
        planes, functools.partial(family.is_mla_op, tokens=TOKENS),
        lambda op: False)
    assert mla["moe_s"] == pytest.approx(40e-9)


@pytest.fixture(scope="module")
def recorded():
    """The trace `record_trace_deepseek_v3.py` recorded on one v5e chip
    (three steps of one dense + one routed layer, recomputed: four heads
    with q/k 192 and v 128, eight experts of which four are held, batch 2
    x 2,048), with the family of the sizes it ran."""
    import record_trace_deepseek_v3 as recorder
    from benchmark.families.deepseek_v3 import Family

    return (xplane.load(RECORDED), Family(recorder.CONFIG),
            recorder.BATCH * recorder.SEQ)


def test_recorded_trace_reads_as_it_did(recorded):
    """Values as first reduced (PR 32): a change to the readers or to the
    family's predicates that moves them has changed what the metrics
    mean."""
    planes, small, tokens = recorded
    moe = moe_trace.reduce(
        planes, functools.partial(small.is_moe_op, tokens=tokens),
        small.is_moe_matmul)
    assert moe["steps"] == 3
    assert moe["busy_s"] == pytest.approx(9.991598e-3, rel=1e-6)
    assert moe["moe_s"] == pytest.approx(1.870439e-3, rel=1e-6)
    assert moe["moe_matmul_s"] == pytest.approx(2.78731e-4, rel=1e-6)
    mla = moe_trace.reduce(
        planes, functools.partial(small.is_mla_op, tokens=tokens),
        lambda op: False)
    assert mla["moe_s"] == pytest.approx(1.543522e-3, rel=1e-6)
    # the same busy time as the loop's own reduction, and attention's
    # kernels alone: the head-major forward (o 128 wide) and the two
    # kernels of the split backward (dq 192 wide; dk 192 and dv 128)
    whole = xplane.reduce(planes, is_kernel=small.is_attention_kernel)
    assert whole["busy_s"] == pytest.approx(moe["busy_s"])
    assert sorted(whole["kernels"]) == [
        "tpu_custom_call__bf16_8_2048_128___f32_8_2048_1__",
        "tpu_custom_call__bf16_8_2048_192___bf16_8_2048_128__",
        "tpu_custom_call_bf16_8_2048_192_"]
    assert whole["kernel_s"] == pytest.approx(4.60631e-3, rel=1e-6)


def test_recorded_trace_tells_the_three_apart(recorded):
    """No operation is two things; the grouped matmuls are the held
    experts' (per step, with the layer recomputed: gate, up twice and
    down's rows' gradient; down twice and gate's, up's rows' gradients;
    the matrices' gradients; three layouts of the groups)."""
    planes, small, tokens = recorded
    matmuls, kinds = {}, {}
    for name, lines in planes:
        if not xplane.DEVICE_PLANE.match(name):
            continue
        for op, start, end in dict(lines)[xplane.OP_LINE]:
            is_a = [small.is_attention_kernel(op), small.is_moe_op(op, tokens),
                    small.is_mla_op(op, tokens)]
            assert sum(is_a) <= 1, op
            kinds[tuple(is_a)] = kinds.get(tuple(is_a), 0) + 1
            if small.is_moe_matmul(op):
                assert small.is_moe_op(op, tokens)
                matmuls[op] = matmuls.get(op, 0) + 1
    assert matmuls == {
        "tpu_custom_call_bf16_8192_160_": 15,
        "tpu_custom_call_bf16_8192_256_": 12,
        "tpu_custom_call_bf16_4_256_160_": 6,
        "tpu_custom_call_bf16_4_160_256_": 3,
        "tpu_custom_call__s32_5___s32_19___s32_19___s32_1__": 9}
    # all four kinds occur: kernels, the mixture, the latent path, the rest
    assert all(kinds.get(k) for k in (
        (True, False, False), (False, True, False), (False, False, True),
        (False, False, False)))


def obs_for(family, monkeypatch, moe=None, mla=None):
    monkeypatch.setattr(moe_trace, "of", lambda obs: moe)
    monkeypatch.setattr(mla_trace, "of", lambda obs: mla)
    return {"family": family, "chips": 1,
            "peaks": registry.peaks("TPU v5 lite"),
            "traffic": registry.traffic("resident-8k")}


def test_readers_on_a_known_reduction(family, monkeypatch):
    moe = {"steps": 4, "busy_s": 1.0, "moe_s": 0.4, "moe_matmul_s": 0.25}
    mla = {"steps": 4, "busy_s": 1.0, "mla_s": 0.1}
    obs = obs_for(family, monkeypatch, moe, mla)
    assert registry.metric("mla_proj_share").read(obs) == pytest.approx(10.0)
    assert registry.metric("moe_held_share").read(obs) == pytest.approx(40.0)
    # four steps' least time over 0.25 s of kernels: compute-bound, 4
    # layers x 18 x 12,288 rows x 2048 x 768 operations at 197 TFLOP/s
    least = 4 * 18 * 12288 * 2048 * 768 / 197e12
    assert registry.metric("moe_held_matmul_roofline_share").read(obs) \
        == pytest.approx(100 * 4 * least / 0.25, rel=1e-3)
    # a family that holds all of its experts has no held share to report
    olmoe = registry.family(registry.config("olmoe-1b-7b-1layer"))
    for name in ("moe_held_share", "moe_held_matmul_roofline_share"):
        assert registry.metric(name).read(
            dict(obs, family=olmoe, t_fit=0.0, trace=None)) is None


def test_rows_buffered_share_reads_the_counters(family):
    from benchmark.harness import timeline

    value = registry.metric("moe_rows_buffered_share").value
    tl = timeline.Timeline(
        {"spans": [], "counters": {"moe.rows_routed": 4 * 98304,
                                   "moe.rows_buffered": 4 * 98304}},
        {"t_open": 0.0, "window_s": 1.0})
    assert value(tl) == 100.0
    tl.counters["moe.rows_buffered"] = 4 * 12288
    assert value(tl) == 12.5
    # the parent's program counts nothing of the kind
    tl.counters = {}
    assert value(tl) is None


def test_readers_find_nothing_without_a_trace(family):
    """No traced run, a rehearsal, a family without the mechanism, the
    parent's program: None, never an exception."""
    base = {"family": family, "chips": 1, "t_fit": 0.0,
            "config": registry.config(CONFIG),
            "traffic": registry.traffic("resident-8k")}
    gpt2 = registry.family(registry.config("gpt2-medium"))
    cases = [
        dict(base, peaks=registry.peaks("TPU v5 lite")),          # no trace
        dict(base, peaks=None, trace={"steps": 1}),               # rehearsal
        dict(base, peaks=registry.peaks("TPU v5 lite"),
             trace={"steps": 1}, family=gpt2),
        # traced, but the trace on disk (if any) is older than this run
        dict(base, peaks=registry.peaks("TPU v5 lite"),
             trace={"steps": 1}, t_fit=4e9),
    ]
    for obs in cases:
        for name in NEW_METRICS[:3]:
            assert registry.metric(name).read(obs) is None


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
    # the mixture's counters are in the run's own timeline
    with open(os.path.join(registry.ROOT, ".scratch", "benchmark", CELL, CELL,
                           "timeline.json")) as f:
        counters = json.load(f)["counters"]
    # 4 of 8 experts held, 128 tokens with 3 experts each; the rehearsal's
    # two routed layers are recomputed (`jax.checkpoint`) and alike, so the
    # step's trace visits the layer once and one layer is counted
    assert counters["moe.experts"] == 8
    assert counters["moe.experts_held"] == 4
    assert counters["moe.rows_routed"] == counters["moe.rows_buffered"] \
        == 128 * 3


FAULTS = {
    # three times the learning rate the configuration states
    "wrong_rate": '''\
        from benchmark.families import deepseek_v3
        from benchmark.reference.deepseek_v3 import adamw
        from ray_tpu.models.deepseek_v3 import trained_by


        class Family(deepseek_v3.Family):
            def optimizer(self):
                settings = dict(self.config["optimizer"])
                settings["learning_rate"] *= 3
                return trained_by(adamw(settings))
        ''',
    # 8-bit floats where the configuration states bfloat16
    "low_precision": '''\
        import dataclasses

        from benchmark.families import deepseek_v3


        class Family(deepseek_v3.Family):
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
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(registry.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(registry.ROOT, "benchmark"),
                    root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "benchmark" / "families" / f"deepseek_v3_{fault}.py").write_text(
        textwrap.dedent(FAULTS[fault]))
    config = registry.load_json("benchmark", "configs", f"{CONFIG}.json")
    config.update(name=f"kanana-{fault}", family=f"deepseek_v3_{fault}")
    (root / "benchmark" / "configs" / f"kanana-{fault}.json").write_text(
        json.dumps(config))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": f"kanana-{fault}", "source": "test", "reduced": [],
        "why": "test", "file": f"benchmark/configs/kanana-{fault}.json"})
    bench["workloads"].append({
        "name": f"kanana-{fault}.resident-8k", "config": f"kanana-{fault}",
        "traffic": "resident-8k", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    proc = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
         f"kanana-{fault}.resident-8k", "--seed", "5", "--seconds", "1",
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
