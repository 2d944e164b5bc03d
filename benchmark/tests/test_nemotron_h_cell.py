"""The `nemotron_h` family and its cell: the configuration against the
published `config.json`, the yardstick's counts worked by hand, the four
state-space readers on a trace recorded on the chip, and a rehearsal of the
cell."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import registry, scope_trace

CONFIG = "nemotron-3-nano-30b-a3b-ep16-9layer"
CELL = CONFIG + ".resident-8k"
BATCH, SEQ = 2, 8192
TOKENS = BATCH * SEQ
E, V = 2688, 16384
H, P, G, N, K, Q = 64, 64, 8, 128, 4, 128       # the Mamba-2 mixer
HA, HKV, D = 32, 2, 128                          # attention
W, WS = 1856, 3712                               # routed and shared experts
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "tpu1_nemotron_h.xplane.pb.gz")
NEW_METRICS = ("ssm_scope_share", "ssm_scan_share",
               "ssm_scan_roofline_share", "ssm_glue_share")

# `nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`'s config.json, as the catalog
# of public architectures holds it
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
CUT = ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
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
    pattern = PUBLISHED["hybrid_override_pattern"]
    assert [pattern.count(kind) for kind in "ME*"] == [23, 23, 6]
    # the published layers 35-43 counted from 0: 4 : 4 : 1, beginning with
    # a Mamba-2 layer and ending with a mixture
    assert config["hybrid_override_pattern"] == pattern[35:44] == "MEMEMEM*E"
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (9, 8, 16384)
    assert {k: config["published"][k] for k in CUT
            if k != "hybrid_override_pattern"} \
        == {k: PUBLISHED[k] for k in CUT if k != "hybrid_override_pattern"}
    assert config["published"]["hybrid_override_pattern"].startswith(pattern)
    # the router keeps its published width and the experts held are said
    assert config["experts_held"] == {
        "first": 0, "of": 128, "why": config["experts_held"]["why"]}
    assert config["vocab_size"] % 128 == 0          # nothing is padded
    assert config["name"] == entry["name"] and config["deployment"]
    assert entry["source"] in config["source"]
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    for key in ("no_rotary_positions", "renorm_eps", "bias_update_speed",
                "auxiliary_loss", "initialisation", "training", "remat",
                "loss_chunk_rows"):
        assert config["assumed"][key]
    for key in ("loss_tolerance", "loss_tolerance_reason", "what"):
        assert config["reference"][key]
    # the rehearsal keeps all three kinds of layer and crosses eight chunks
    tiny = registry.config(CONFIG, rehearse=True)
    assert set(tiny["hybrid_override_pattern"]) == set("ME*")
    assert registry.traffic("resident-8k", True)["seq"] \
        == 8 * tiny["chunk_size"]


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
    for name in NEW_METRICS + (
            "attn_roofline_share", "attn_kernel_share", "mfu", "hbm_peak_gib",
            "step_device_ms", "scope_named_share", "fwd_share", "bwd_share",
            "optimizer_share", "attention_scope_share", "ffn_scope_share",
            "head_loss_share", "norm_share"):
        assert name in layer
    for name in ("moe_share", "moe_held_share", "mla_proj_share",
                 "shortconv_share", "collective_share"):
        assert name not in layer
    bench = registry.benchmark()
    new = [m for m in bench["per_layer"] if m["name"] in NEW_METRICS]
    assert [m["name"] for m in new] == list(NEW_METRICS)
    # every new metric lists the new cell alone, and they come last
    assert bench["per_layer"][-4:] == new
    assert all(m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
               and m["source"] == "device_trace" and m["unit"] == "%"
               for m in new)
    assert [m["layer"] for m in new] == ["Model", "Model", "Kernels", "Model"]
    assert bench["configs"][-1]["name"] == CONFIG
    assert bench["workloads"][-1]["name"] == CELL
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert len(bench["workloads"]) == 7
    for other in bench["workloads"]:
        if other["name"] != CELL:
            names = [m["name"] for m in registry.metrics_of(
                other["name"], "per_layer")]
            assert not set(names) & set(NEW_METRICS)


def test_counts_by_hand(family):
    mamba = E * (2 * H * P + 2 * G * N + H) + H * P * E \
        + (H * P + 2 * G * N) * K
    attn = 2 * E * HA * D + 2 * E * HKV * D
    assert family.mamba_matrices() == mamba == 38_731_776
    assert family.attention_params() == attn == 23_396_352
    expert, shared = 2 * E * W, 2 * E * WS
    mixture = E * 128 + 128 + 8 * expert + shared
    small = (H * P + 2 * G * N) + 3 * H + H * P     # bias, A_log D dt_bias,
    assert family.param_count() == 2 * V * E + E + 9 * E \
        + 4 * (mamba + small) + attn + 4 * mixture  # the gated norm's gain
    # ISSUE 38's arithmetic: 666.96 M, 10.67 GB at 16 bytes
    assert round(family.param_count() / 1e6, 2) == 666.96
    assert round(family.param_count() * 16 / 1e9, 2) == 10.67
    assert family.expected_rows_per_token() == 0.375
    n = V * E + 4 * mamba + attn + 4 * (E * 128 + shared + 0.375 * expert)
    assert family.multiplying_params_per_token() == n
    # ISSUE 38 adds the rounded parts and has 318.4
    assert round(n / 1e6, 1) == 318.5
    scan = 2 * Q * N * G // 2 + 2 * Q * P * H // 2 + 2 * N * P * H \
        + 2 * N * P * H
    assert family.scan_flops_per_token() == scan == 2_752_512
    assert family.flops_per_token(SEQ) \
        == 6 * n + 6 * SEQ * HA * 2 * D + 3 * 4 * scan
    assert round(family.flops_per_token(SEQ) / 1e9, 2) == 2.35


def test_counts_are_the_programs_own(family):
    import jax

    from ray_tpu.models import nemotron_h as model
    from ray_tpu.ops.moe import buffer_rows

    cfg = family.model_config()
    assert family.flops_per_token(SEQ) == model.count_flops_per_token(
        cfg, SEQ)
    assert family.scan_flops_per_token() == model.scan_flops_per_token(cfg)
    shapes = jax.eval_shape(lambda key: model.init_params(key, cfg),
                            jax.random.PRNGKey(0))
    assert model.num_params(shapes) == family.param_count()
    assert cfg.held == (0, 8) and cfg.n_experts == 128 and cfg.remat
    assert cfg.pattern == "MEMEMEM*E" and cfg.rescale_depth == 52
    assert (cfg.chunk_size, cfg.mamba_heads, cfg.state_size) == (128, 64, 128)
    # 8 of 128 at top 6: 12,288 of 98,304 rows
    assert buffer_rows(TOKENS * 6, 8, 128) == 12288


def test_attention_and_moe_cost_by_hand(family):
    # one attention layer; 32 query heads: six products 128 deep, each
    # 2 B H S^2 D operations, halved for causality; q, o, do, dq at 32
    # heads and k, v (twice), dk, dv at 2, in bf16; two of B H S in f32
    flops = 6 * 2 * BATCH * HA * SEQ * SEQ * D // 2
    nbytes = 6 * BATCH * SEQ * D * (HA + HKV) * 2 + 2 * BATCH * HA * SEQ * 4
    assert family.attention_cost(BATCH, SEQ) == {"flops": flops,
                                                 "bytes": nbytes}
    # TWO matrices an expert: 2 products of 2 R E W forward, twice that
    # backward, over the 0.375 T rows expected, four mixture layers
    r = 0.375 * TOKENS
    cost = family.moe_cost(BATCH, SEQ)
    assert cost["flops"] == 4 * 2 * 6 * r * E * W
    weights = 8 * E * W
    forward = (2 * (r * E + r * W) + 2 * weights) * 2
    backward = 2 * ((r * W + r * E) * 2 + r * W + r * E + 2 * weights) * 2
    assert cost["bytes"] == 4 * (forward + backward)
    from benchmark.families import deepseek_v3
    assert type(family).moe_cost is not deepseek_v3.Family.moe_cost
    assert family.is_attention_kernel(
        "tpu_custom_call__bf16_64_8192_128___f32_64_8192_1__")
    assert family.is_attention_kernel("tpu_custom_call_bf16_4_8192_128_")
    assert not family.is_attention_kernel("fusion:kOutput_bf16_64_8192_128_")
    assert not family.is_attention_kernel(
        "custom-call_bf16_12288_2688_")            # a grouped matmul


def test_ssd_cost_by_hand(family):
    """ISSUE 38 §4's figures, written out."""
    # operations a token and layer, forward: the causal half of C B' and
    # of (L o C B') x, the chunk's state, the earlier chunks' part
    forward = 128 * 128 * 8 + 128 * 64 * 64 + 2 * 128 * 64 * 64 \
        + 2 * 128 * 64 * 64
    assert forward == 2_752_512                      # 2.75 M at Q = 128
    flops = 4 * TOKENS * 3 * forward                 # backward twice that
    # bytes a token and layer: x 4096, B and C 2 x 1024, y 4096 in bf16,
    # dt 64 in float32
    read = (4096 + 2048) * 2 + 64 * 4
    fwd_bytes = read + 4096 * 2
    bwd_bytes = read + 4096 * 2 + read        # + dy; dx, dB, dC, d dt
    assert fwd_bytes == 20_736
    assert family.ssd_cost(BATCH, SEQ) == {
        "flops": flops, "bytes": 4 * TOKENS * (fwd_bytes + bwd_bytes)}
    # the forward's least time a layer is set by bytes: about 0.4 ms
    assert TOKENS * fwd_bytes / 819e9 == pytest.approx(0.415e-3, rel=0.01)
    assert TOKENS * forward / 197e12 < TOKENS * fwd_bytes / 819e9
    peaks = registry.peaks("TPU v5 lite")
    seconds, bound = registry.metric(
        "ssm_scan_roofline_share").least_seconds({
            "family": family, "chips": 1, "peaks": peaks,
            "traffic": registry.traffic("resident-8k")})
    assert bound == "memory"
    assert seconds == pytest.approx(
        4 * TOKENS * (fwd_bytes + bwd_bytes) / 819e9, rel=1e-6)


# -- the recorded trace -------------------------------------------------------

RECORDED_BUSY_S = 5.40143306399978e-3
RECORDED_SSM_S = 1.0611479640000611e-3
RECORDED_SCAN_S = 5.733150720000789e-4
RECORDED_GLUE_S = 3.3467453199999e-4


@pytest.fixture(scope="module")
def recorded():
    """The trace `record_trace_nemotron_h.py` recorded on one v5e chip
    (three steps of a Mamba-2 layer, an attention layer and a mixture,
    recomputed, batch 2 x 2,048), reduced by the program's scopes, with the
    family of the sizes it ran."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import record_trace_nemotron_h as recorder
    from benchmark.families.nemotron_h import Family

    return (scope_trace.reduce(scope_trace.events(RECORDED),
                               *scope_trace.vocabulary()),
            Family(recorder.CONFIG), recorder)


def test_recorded_trace_reads_as_it_did(recorded):
    found, small, _ = recorded
    scopes = found["scopes"]
    for scope in ("ssm", "ssm/in_proj", "ssm/conv", "ssm/scan",
                  "ssm/gate_norm", "ssm/out_proj", "attention/kernel",
                  "ffn/moe/experts", "ffn/moe/shared", "head_and_loss"):
        assert scopes.get(scope, 0) > 0, scope
    assert scopes["ssm"] == pytest.approx(sum(
        scopes[f"ssm/{part}"] for part in (
            "in_proj", "conv", "scan", "gate_norm", "out_proj")), rel=1e-6)
    assert found["named_s"] / found["busy_s"] > 0.85
    assert found["busy_s"] == pytest.approx(RECORDED_BUSY_S, rel=1e-6)
    assert scopes["ssm"] == pytest.approx(RECORDED_SSM_S, rel=1e-6)
    assert scopes["ssm/scan"] == pytest.approx(RECORDED_SCAN_S, rel=1e-6)
    assert scopes["ssm/conv"] + scopes["ssm/gate_norm"] \
        == pytest.approx(RECORDED_GLUE_S, rel=1e-6)
    # forward, replayed and backward all under the scan's scope
    assert {"fwd", "remat_fwd", "bwd"} <= set(found["in_scope"]["ssm/scan"])


def test_the_four_readers_on_the_recorded_trace(recorded, monkeypatch):
    found, small, recorder = recorded
    traffic = {"batch": recorder.BATCH, "seq": recorder.SEQ}
    obs = {"family": small, "chips": 1, "traffic": traffic,
           "peaks": registry.peaks("TPU v5 lite"), "trace": {"steps": 3}}
    monkeypatch.setattr(scope_trace, "of", lambda obs: found)
    read = {name: registry.metric(name).read(obs) for name in NEW_METRICS}
    busy = found["busy_s"]
    assert read["ssm_scope_share"] == pytest.approx(
        100 * RECORDED_SSM_S / busy, rel=1e-6)
    assert read["ssm_scan_share"] == pytest.approx(
        100 * RECORDED_SCAN_S / busy, rel=1e-6)
    assert read["ssm_glue_share"] == pytest.approx(
        100 * RECORDED_GLUE_S / busy, rel=1e-6)
    cost = small.ssd_cost(recorder.BATCH, recorder.SEQ)
    least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    assert read["ssm_scan_roofline_share"] == pytest.approx(
        100 * 3 * least / RECORDED_SCAN_S, rel=1e-6)
    assert 0 < read["ssm_scan_roofline_share"] < 100
    assert read["ssm_scan_share"] + read["ssm_glue_share"] \
        < read["ssm_scope_share"] < 100


@pytest.mark.parametrize("trace", [
    "tpu1.xplane.pb.gz", "tpu1_olmoe.xplane.pb.gz",
    "tpu1_deepseek_v3.xplane.pb.gz", "tpu1_lfm2_moe.xplane.pb.gz"])
def test_the_readers_find_nothing_in_another_familys_trace(
        trace, family, monkeypatch):
    """The other families' recorded traces hold no state-space scope: each
    of the four readers gives None, for this family and for theirs."""
    path = os.path.join(DATA, trace)
    if not os.path.exists(path):
        pytest.skip(f"no {trace} recorded")
    found = scope_trace.reduce(scope_trace.events(path),
                               *scope_trace.vocabulary())
    assert found and not any(s.startswith("ssm") for s in found["scopes"])
    monkeypatch.setattr(scope_trace, "of", lambda obs: found)
    others = [registry.family(registry.config(name)) for name in (
        "gpt2-medium", "olmoe-1b-7b-1layer", "kanana-2-30b-a3b-ep8-5layer",
        "lfm2-24b-a2b-ep8-5layer")]
    for fam in others:
        assert not hasattr(fam, "ssd_cost")
        obs = {"family": fam, "chips": 1, "trace": {"steps": 3},
               "peaks": registry.peaks("TPU v5 lite"),
               "traffic": registry.traffic("resident-8k")}
        for name in NEW_METRICS:
            assert registry.metric(name).read(obs) is None
    # and this family's roofline reader divides by no time that is not there
    obs = {"family": family, "chips": 1, "trace": {"steps": 3},
           "peaks": registry.peaks("TPU v5 lite"),
           "traffic": registry.traffic("resident-8k")}
    assert registry.metric("ssm_scan_roofline_share").read(obs) is None


def test_readers_find_nothing_without_a_trace(family):
    """No traced run, a rehearsal, a trace older than the run: None, never
    an exception."""
    base = {"family": family, "chips": 1, "t_fit": 0.0,
            "config": registry.config(CONFIG),
            "traffic": registry.traffic("resident-8k")}
    peaks = registry.peaks("TPU v5 lite")
    for obs in (dict(base, peaks=peaks),                          # no trace
                dict(base, peaks=None, trace={"steps": 1}),       # rehearsal
                dict(base, peaks=peaks, trace={"steps": 1}, t_fit=4e9)):
        for name in NEW_METRICS:
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
    # what this PR counts is in the run's own timeline
    with open(os.path.join(registry.ROOT, ".scratch", "benchmark", CELL, CELL,
                           "timeline.json")) as f:
        counters = json.load(f)["counters"]
    tiny = registry.config(CONFIG, rehearse=True)
    assert counters["ssm.layers"] >= 1
    assert counters["ssm.heads"] == tiny["mamba_num_heads"]
    assert counters["ssm.state"] == tiny["ssm_state_size"]
    assert counters["ssm.chunk"] == tiny["chunk_size"]
    assert counters["attention.q_heads"] == 2 * counters["attention.kv_heads"]
    assert counters["moe.experts_held"] * 2 == counters["moe.experts"]


FAULTS = {
    # three times the learning rate the configuration states
    "wrong_rate": '''\
        from benchmark.families import nemotron_h
        from benchmark.reference.nemotron_h import adamw
        from ray_tpu.models.nemotron_h import trained_by


        class Family(nemotron_h.Family):
            def optimizer(self):
                settings = dict(self.config["optimizer"])
                settings["learning_rate"] *= 3
                return trained_by(adamw(settings))
        ''',
    # 8-bit floats where the configuration states bfloat16
    "low_precision": '''\
        import dataclasses

        from benchmark.families import nemotron_h


        class Family(nemotron_h.Family):
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
    (root / "benchmark" / "families" / f"nemotron_h_{fault}.py").write_text(
        textwrap.dedent(FAULTS[fault]))
    config = registry.load_json("benchmark", "configs", f"{CONFIG}.json")
    config.update(name=f"nemotron-{fault}", family=f"nemotron_h_{fault}")
    (root / "benchmark" / "configs" / f"nemotron-{fault}.json").write_text(
        json.dumps(config))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": f"nemotron-{fault}", "source": "test", "reduced": [],
        "why": "test", "file": f"benchmark/configs/nemotron-{fault}.json"})
    bench["workloads"].append({
        "name": f"nemotron-{fault}.resident-8k",
        "config": f"nemotron-{fault}", "traffic": "resident-8k", "chips": 1,
        "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    proc = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
         f"nemotron-{fault}.resident-8k", "--seed", "5", "--seconds", "1",
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
