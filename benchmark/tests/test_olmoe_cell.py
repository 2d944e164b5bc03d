"""The `olmoe` family and its cell: the configuration against the
published `config.json`, the yardstick's counts worked by hand, the
predicates that tell a trace's operations apart, the routed experts'
readers on a trace recorded on the chip, and a rehearsal of the cell."""

import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from benchmark.harness import moe_trace, registry, xplane

CELL = "olmoe-1b-7b-1layer.resident-4k"
BATCH, SEQ = 4, 4096
ROWS = BATCH * SEQ * 8                # (token, expert) rows of a step
E, W, N, V = 2048, 1024, 64, 50304
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "tpu1_olmoe.xplane.pb.gz")

# `allenai/OLMoE-1B-7B-0125-Instruct`'s config.json, as the catalog of
# public architectures holds it
PUBLISHED = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}


@pytest.fixture(scope="module")
def family():
    return registry.family(registry.config("olmoe-1b-7b-1layer"))


def test_only_depth_is_cut():
    config = registry.config("olmoe-1b-7b-1layer")
    entry = [c for c in registry.benchmark()["configs"]
             if c["name"] == "olmoe-1b-7b-1layer"][0]
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    differing = [k for k, v in PUBLISHED.items() if config[k] != v]
    assert differing == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 1
    assert config["name"] == entry["name"]
    for key in ("expert_width", "auxiliary_losses", "qk_norm",
                "initialisation", "training", "remat"):
        assert config["assumed"][key]


def test_the_cell_is_what_the_issue_names():
    cell = registry.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmoe-1b-7b-1layer", "resident-4k", 1)
    traffic = registry.traffic("resident-4k")
    assert (traffic["batch"], traffic["seq"], traffic["source"],
            traffic["loop"]) == (BATCH, SEQ, "resident", "train_steps")
    assert traffic["warmup_steps"] == 5 and traffic["trace_seconds"] == 1.0
    end = [m["name"] for m in registry.metrics_of(CELL, "end_to_end")]
    assert end == ["tokens_per_s", "setup_s"]
    layer = [m["name"] for m in registry.metrics_of(CELL, "per_layer")]
    for name in ("moe_share", "moe_dispatch_share",
                 "moe_matmul_roofline_share", "attn_roofline_share", "mfu",
                 "hbm_peak_gib"):
        assert name in layer
    for m in registry.benchmark()["per_layer"]:
        if m["name"].startswith("moe_"):
            assert m["workloads"] == [CELL]


def test_counts_by_hand(family):
    # embedding and head 2 x 103.0 M; attention 16.8 M + 4 norms; router
    # 0.13 M; 64 experts of three 2048 x 1024 matrices 402.7 M; final norm
    assert family.param_count() == 2 * V * E + (
        4 * E * E + 4 * E + E * N + 3 * N * E * W) + E == 625_616_896
    # 6 x (head + attention + router + 8 experts) + 12 L E S
    n = V * E + 4 * E * E + E * N + 8 * 3 * E * W
    assert n == 170_262_528
    assert family.flops_per_token(SEQ) == 6 * n + 12 * E * SEQ \
        == 1_122_238_464


def test_flops_are_the_programs_own_count(family):
    from ray_tpu.models import olmoe

    assert family.flops_per_token(SEQ) == olmoe.count_flops_per_token(
        olmoe.OlmoeConfig(n_layer=1), SEQ)
    # and the shapes the program builds are the ones counted
    import jax

    shapes = jax.eval_shape(
        lambda key: olmoe.init_params(key, olmoe.OlmoeConfig(n_layer=1)),
        jax.random.PRNGKey(0))
    assert olmoe.num_params(shapes) == family.param_count()


def test_attention_cost_by_hand(family):
    # 16 heads of 128: 6 products of 2 B H S^2 D, halved for causality;
    # 12 tensors of B S H D in bf16 and two of B H S in f32
    flops = 6 * (2 * BATCH * 16 * SEQ * SEQ * 128) // 2
    nbytes = 12 * BATCH * SEQ * 16 * 128 * 2 + 2 * BATCH * 16 * SEQ * 4
    assert family.attention_cost(BATCH, SEQ) == {"flops": flops,
                                                 "bytes": nbytes}
    assert flops == 824_633_720_832


def test_moe_cost_by_hand(family):
    cost = family.moe_cost(BATCH, SEQ)
    # 131,072 rows through three 2048 x 1024 products, forward and twice
    # backward
    assert cost["flops"] == 3 * 6 * ROWS * E * W == 4_947_802_324_992
    rows_e, rows_w, weights = ROWS * E * 2, ROWS * W * 2, N * E * W * 2
    forward = 2 * (rows_e + rows_w) + (rows_w + rows_e) + 3 * weights
    backward = 3 * (2 * (rows_w + rows_e) + rows_w + rows_e + 2 * weights)
    assert cost["bytes"] == forward + backward
    # compute-bound on the v5e: 25.1 ms of arithmetic against 12.9 ms of
    # traffic
    peaks = registry.peaks("TPU v5 lite")
    assert cost["flops"] / peaks["bf16_flops_per_s"] == pytest.approx(
        0.025116, rel=1e-3)
    assert cost["bytes"] / peaks["hbm_bytes_per_s"] < 0.015
    metric = registry.metric("moe_matmul_roofline_share")
    seconds, bound = metric.least_seconds({
        "family": family, "chips": 1, "peaks": peaks,
        "traffic": registry.traffic("resident-4k")})
    assert bound == "compute" and seconds == pytest.approx(0.025116, rel=1e-3)


# names as `harness/xplane.py:op_name` gives them for the full-size step
ATTENTION = [
    # lane-layout forward: (o (B, S, H*D), row statistics)
    "tpu_custom_call__bf16_4_4096_2048___f32_64_4096_1__",
    # transposed two-kernel backward: dq; (dk, dv)
    "tpu_custom_call_bf16_64_4096_128_",
    "tpu_custom_call__bf16_64_4096_128___bf16_64_4096_128__",
]
MOE_MATMULS = [
    "tpu_custom_call_bf16_131072_1024_",          # gate, up; down's dlhs
    "tpu_custom_call_bf16_131072_2048_",          # down; gate's, up's dlhs
    "tpu_custom_call_bf16_64_2048_1024_",         # gate's, up's drhs
    "tpu_custom_call_bf16_64_1024_2048_",         # down's drhs
    "tpu_custom_call__s32_65___s32_319___s32_319___s32_1__",   # group layout
]
MOE_OTHER = [
    "fusion:kLoop_bf16_131072_2048_", "sort__s32_131072___s32_131072__",
    "fusion:kLoop_f32_16384_64_", "fusion:kLoop_f32_16384_8_",
    "fusion:kCustom_bf16_131072_2048_",           # the gathers of rows
    "copy_bf16_64_2048_1024_",                    # a transposed stack
]
NEITHER = [
    "fusion:kOutput_bf16_4_4096_2048_", "convolution_f32_2048_50304_",
    "fusion:kLoop_f32_64_2048_1024_",             # the optimizer's update
    "fusion:kLoop_bf16_16384_2048_",
]


def test_kernels_are_told_apart_by_shape(family):
    tokens = BATCH * SEQ
    for name in ATTENTION:
        assert family.is_attention_kernel(name), name
        assert not family.is_moe_matmul(name), name
        assert not family.is_moe_op(name, tokens), name
    for name in MOE_MATMULS:
        assert family.is_moe_matmul(name), name
        assert family.is_moe_op(name, tokens), name
        assert not family.is_attention_kernel(name), name
    for name in MOE_OTHER:
        assert family.is_moe_op(name, tokens), name
        assert not family.is_moe_matmul(name), name
        assert not family.is_attention_kernel(name), name
    for name in NEITHER:
        assert not family.is_moe_op(name, tokens), name
        assert not family.is_attention_kernel(name), name


def test_reduction_on_hand_made_events(family):
    """One device, times in ns: a grouped matmul 0-100, a gather of the
    routed rows 100-130, an attention kernel 130-200, idle 200-250, a
    fusion that is none of them 250-300; two steps."""
    planes = [("/device:TPU:0", [
        (xplane.OP_LINE, [
            ("tpu_custom_call_bf16_131072_1024_", 0, 100),
            ("fusion:kLoop_bf16_131072_2048_", 100, 130),
            ("tpu_custom_call_bf16_64_4096_128_", 130, 200),
            ("fusion:kOutput_bf16_4_4096_2048_", 250, 300)]),
        (xplane.MODULE_LINE, [("jit_train_step", 0, 150),
                              ("jit_train_step", 150, 300)])])]
    import functools

    found = moe_trace.reduce(
        planes, functools.partial(family.is_moe_op, tokens=BATCH * SEQ),
        family.is_moe_matmul)
    assert found == {"steps": 2, "busy_s": pytest.approx(250e-9),
                     "moe_s": pytest.approx(130e-9),
                     "moe_matmul_s": pytest.approx(100e-9)}


@pytest.fixture(scope="module")
def recorded():
    """The trace `record_trace_olmoe.py` recorded on one v5e chip (three
    steps of a one-layer OLMoE, width 256 in two heads of 128, eight
    experts 128 wide with two a token, batch 2 x 2,048), with the family
    of the sizes it ran."""
    import record_trace_olmoe as recorder
    from benchmark.families.olmoe import Family

    return (xplane.load(RECORDED), Family(recorder.CONFIG),
            recorder.BATCH * recorder.SEQ)


def test_recorded_trace_reads_as_it_did(recorded):
    """Values as first reduced (PR 28): a change to the readers or to the
    family's predicates that moves them has changed what the metrics
    mean."""
    import functools

    planes, small, tokens = recorded
    found = moe_trace.reduce(
        planes, functools.partial(small.is_moe_op, tokens=tokens),
        small.is_moe_matmul)
    assert found["steps"] == 3
    assert found["busy_s"] == pytest.approx(3.161106e-3, rel=1e-6)
    assert found["moe_s"] == pytest.approx(9.37216e-4, rel=1e-6)
    assert found["moe_matmul_s"] == pytest.approx(2.55636e-4, rel=1e-6)
    # the same busy time as the loop's own reduction
    whole = xplane.reduce(planes, is_kernel=small.is_attention_kernel)
    assert whole["busy_s"] == pytest.approx(found["busy_s"])
    # attention's kernels and nothing else: the lane-layout forward and
    # the two kernels of the transposed backward (the sequence is two
    # blocks long), by their shapes
    assert sorted(whole["kernels"]) == [
        "tpu_custom_call__bf16_2_2048_256___f32_4_2048_1__",
        "tpu_custom_call__bf16_4_2048_128___bf16_4_2048_128__",
        "tpu_custom_call_bf16_4_2048_128_"]
    assert whole["kernel_s"] == pytest.approx(6.26728e-4, rel=1e-6)


def test_recorded_trace_holds_every_grouped_matmul(recorded):
    """Per step: gate, up and down forward (3), their rows' gradients (3)
    and their matrices' gradients (3), and two layouts of the groups."""
    planes, small, tokens = recorded
    ops = {}
    for name, lines in planes:
        if xplane.DEVICE_PLANE.match(name):
            for op, start, end in dict(lines)[xplane.OP_LINE]:
                if small.is_moe_matmul(op):
                    ops[op] = ops.get(op, 0) + 1
    assert ops == {
        "tpu_custom_call_bf16_8192_128_": 9,      # gate, up; down's dlhs
        "tpu_custom_call_bf16_8192_256_": 9,      # down; gate's, up's dlhs
        "tpu_custom_call_bf16_8_256_128_": 6,     # gate's, up's drhs
        "tpu_custom_call_bf16_8_128_256_": 3,     # down's drhs
        "tpu_custom_call__s32_9___s32_23___s32_23___s32_1__": 6}
    assert not [op for op in ops if small.is_attention_kernel(op)]


def obs_for(family, found, monkeypatch):
    monkeypatch.setattr(moe_trace, "of", lambda obs: found)
    return {"family": family, "chips": 1,
            "peaks": registry.peaks("TPU v5 lite"),
            "traffic": registry.traffic("resident-4k")}


def test_readers_on_a_known_reduction(family, monkeypatch):
    found = {"steps": 4, "busy_s": 1.0, "moe_s": 0.4, "moe_matmul_s": 0.25}
    obs = obs_for(family, found, monkeypatch)
    assert registry.metric("moe_share").read(obs) == pytest.approx(40.0)
    assert registry.metric("moe_dispatch_share").read(obs) \
        == pytest.approx(15.0)
    # four steps' least time, 25.116 ms each, over 0.25 s of kernels
    assert registry.metric("moe_matmul_roofline_share").read(obs) \
        == pytest.approx(100 * 4 * 0.025116 / 0.25, rel=1e-3)


def test_readers_find_nothing_without_a_trace(family):
    """No traced run, a rehearsal, a family without routed experts, the
    parent's program: None, never an exception."""
    base = {"family": family, "chips": 1, "t_fit": 0.0,
            "config": registry.config("olmoe-1b-7b-1layer"),
            "traffic": registry.traffic("resident-4k")}
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
        for name in ("moe_share", "moe_dispatch_share",
                     "moe_matmul_roofline_share"):
            assert registry.metric(name).read(obs) is None


def run_cell(*args):
    cmd = [sys.executable, os.path.join(registry.ROOT, "benchmark", "run.py"),
           *args, "--rehearse"]
    return subprocess.run(cmd, cwd=registry.ROOT, capture_output=True,
                          text=True, timeout=600,
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


FAULTS = {
    # three times the learning rate the configuration states
    "wrong_rate": '''\
        from benchmark.families import olmoe
        from benchmark.reference.olmoe import adamw


        class Family(olmoe.Family):
            def optimizer(self):
                settings = dict(self.config["optimizer"])
                settings["learning_rate"] *= 3
                return adamw(settings)
        ''',
    # the experts' weights renormalised to sum to one
    "renormalised": '''\
        from benchmark.families import olmoe


        class Family(olmoe.Family):
            def lower_step(self, params, opt_state, batch):
                from ray_tpu.models import olmoe as model

                real = model.moe_dispatch
                model.moe_dispatch = lambda x, weights, *rest: real(
                    x, weights / weights.sum(-1, keepdims=True), *rest)
                try:
                    return super().lower_step(params, opt_state, batch)
                finally:
                    model.moe_dispatch = real
        ''',
    # 8-bit floats where the configuration states bfloat16
    "low_precision": '''\
        import dataclasses

        from benchmark.families import olmoe


        class Family(olmoe.Family):
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
    (root / "benchmark" / "families" / f"olmoe_{fault}.py").write_text(
        textwrap.dedent(FAULTS[fault]))
    config = registry.load_json("benchmark", "configs",
                                "olmoe-1b-7b-1layer.json")
    config.update(name=f"olmoe-{fault}", family=f"olmoe_{fault}")
    (root / "benchmark" / "configs" / f"olmoe-{fault}.json").write_text(
        json.dumps(config))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": f"olmoe-{fault}", "source": "test", "reduced": [],
        "why": "test", "file": f"benchmark/configs/olmoe-{fault}.json"})
    bench["workloads"].append({
        "name": f"olmoe-{fault}.resident-4k", "config": f"olmoe-{fault}",
        "traffic": "resident-4k", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    proc = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
         f"olmoe-{fault}.resident-4k", "--seed", "5", "--seconds", "1",
         "--trace", "0", "--rehearse"], cwd=root, capture_output=True,
        text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=registry.ROOT))
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is False
    assert "NOT CORRECT: loss at step" in proc.stdout
    if fault == "wrong_rate":
        # the forward pass is right; the first update is not
        assert "NOT CORRECT: loss at step 0" not in proc.stdout
