"""The `laguna` family and its cell: the configuration against the published
`config.json`, the yardstick's counts worked by hand and against
`models/laguna.py`'s own, the two new readers on known reductions and on a
trace and a timeline recorded on the chip, a rehearsal of the cell, and
what the comparison that decides `correct` catches of the seeded faults
(`laguna_faults.py`) at the rehearsal's sizes.  The cell and its entries
are found by NAME, wherever later entries put them."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.harness import registry, scope_trace, timeline

CONFIG = "laguna-xs.2-ep16"
CELL = CONFIG + ".resident-16k"
BATCH, SEQ, WINDOW = 1, 16384, 512
E, HKV, D, ROWS = 2048, 8, 128, 12544
H_FULL, H_SLIDING = 48, 64
FULL_PAIRS = SEQ * (SEQ + 1) // 2               # 134.23 M
WINDOW_PAIRS = WINDOW * (WINDOW + 1) // 2 + (SEQ - WINDOW) * WINDOW  # 8.26 M
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "tpu1_laguna.xplane.pb.gz")
RECORDED_TIMELINE = os.path.join(DATA, "timeline", "timeline_laguna.json")
NEW_METRICS = ("attn_gate_share", "window_head_pairs_attended_share")
SLIDING, FULL = "sliding_attention", "full_attention"
CUT = ["num_hidden_layers", "layer_types", "mlp_layer_types",
       "num_attention_heads_per_layer", "num_experts", "vocab_size"]


def attention_params(heads):
    """W_q, W_o at ``heads``; W_k, W_v at 8; W_g (E, heads)."""
    return 2 * E * D * (heads + HKV) + E * heads


@pytest.fixture(scope="module")
def published():
    """`poolside/Laguna-XS.2`'s config.json, as the catalog of public
    architectures holds it (the catalog is beside the guides, not in the
    repository: where it is absent, what the issue quotes of it)."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                row = json.loads(line)
                if row["name"] == "Laguna-XS.2":
                    return row["config"]
    return {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
        "intermediate_size": 8192, "num_hidden_layers": 40,
        "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 262144, "attention_bias": False,
        "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
        "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                "original_max_position_embeddings": 4096, "beta_slow": 1,
                "beta_fast": 64, "attention_factor": 1.4158883083359672,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                                  "partial_rotary_factor": 1},
            "original_max_position_embeddings": 4096},
        "layer_types": [FULL, SLIDING, SLIDING, SLIDING] * 10,
        "moe_apply_router_weight_on_input": False,
        "partial_rotary_factor": 0.5,
        "mlp_layer_types": ["dense"] + ["sparse"] * 39,
        "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": [48, 64, 64, 64] * 10}


@pytest.fixture(scope="module")
def family():
    return registry.family(registry.config(CONFIG))


def entry_of(kind, name):
    found = [m for m in registry.benchmark()[kind] if m["name"] == name]
    assert len(found) == 1, (kind, name)
    return found[0]


def test_only_depth_experts_held_and_vocabulary_are_cut(published):
    config = registry.config(CONFIG)
    entry = entry_of("configs", CONFIG)
    assert entry["reduced"] == config["reduced"] == CUT
    assert sorted(k for k, v in published.items() if config[k] != v) \
        == sorted(CUT)
    assert {k: config["published"][k] for k in (
        "num_hidden_layers", "num_experts", "vocab_size")} == {
        k: published[k] for k in ("num_hidden_layers", "num_experts",
                                  "vocab_size")}
    # the published layers 0-4: the leading dense layer and the four that
    # follow it, a whole period three to one
    assert config["num_hidden_layers"] == 5
    assert config["layer_types"] == published["layer_types"][:5] \
        == [FULL, SLIDING, SLIDING, SLIDING, FULL]
    assert config["mlp_layer_types"] == published["mlp_layer_types"][:5] \
        == ["dense"] + ["sparse"] * 4
    assert config["num_attention_heads_per_layer"] \
        == published["num_attention_heads_per_layer"][:5] \
        == [48, 64, 64, 64, 48]
    assert config["num_experts"] == 16 >= 8
    assert config["experts_held"] == {
        "first": 0, "of": 256, "why": config["experts_held"]["why"]}
    assert config["vocab_size"] == ROWS == 100352 // 8 and ROWS % 128 == 0
    assert "16 chips share each layer" in config["deployment"]
    assert "512 rows each" in config["deployment"]
    assert "pipeline stages" in config["deployment"]
    assert entry["source"] == ("https://huggingface.co/poolside/Laguna-XS.2/"
                               "blob/main/config.json")
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    # the four choices the config has no key for, each with its evidence
    for key in ("gate", "router", "qk_norm", "shared_expert_gate",
                "intermediate_size", "sliding_window", "yarn",
                "initialisation", "training", "routing_bias", "remat",
                "loss_chunk_rows"):
        assert config["assumed"][key], key
    assert "modeling_laguna.py" in config["assumed"]["gate"]
    for key in ("loss_tolerance", "attention_error_max",
                "loss_tolerance_reason", "what"):
        assert config["reference"][key]
    assert "arguments" in config["reduced_how"] \
        and "scratch" in config["reduced_how"]
    assert config["remat"] is True
    assert (config["param_dtype"], config["compute_dtype"]) == (
        "float32", "bfloat16")


def test_the_cell_is_what_the_issue_names():
    cell = registry.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "resident-16k", 1)
    assert len(cell["why"]) <= 200
    assert [w["name"] for w in registry.benchmark()["workloads"]
            if w["config"] == CONFIG] == [CELL]         # no second cell
    traffic = registry.traffic("resident-16k")
    assert (traffic["batch"], traffic["seq"], traffic["source"]) == (
        BATCH, SEQ, "resident")
    end = [m["name"] for m in registry.metrics_of(CELL, "end_to_end")]
    assert end == ["tokens_per_s", "setup_s"]
    layer = [m["name"] for m in registry.metrics_of(CELL, "per_layer")]
    for name in NEW_METRICS + (
            "attn_roofline_share", "attn_kernel_share", "mfu", "hbm_peak_gib",
            "head_loss_share", "norm_share", "fwd_share", "bwd_share",
            "optimizer_share", "scope_named_share", "attention_scope_share",
            "ffn_scope_share", "step_device_ms", "lower_compile_s",
            "jax_backend_compile_s"):
        assert name in layer
    for name in ("moe_share", "remat_fwd_share", "window_kernel_share",
                 "window_pairs_attended_share", "moe_held_share",
                 "moe_rows_buffered_share", "collective_share"):
        assert name not in layer
    gate, pairs = (entry_of("per_layer", name) for name in NEW_METRICS)
    for m in (gate, pairs):
        assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s" \
            and m["unit"] == "%"
    assert (gate["source"], gate["better"], gate["layer"]) == (
        "device_trace", "lower", "Model")
    assert (pairs["source"], pairs["better"], pairs["layer"]) == (
        "program_counter", "higher", "Kernels")
    four = [w for w in registry.benchmark()["workloads"] if w["chips"] == 4]
    assert len(four) == 1


# -- the counts ---------------------------------------------------------------

def test_counts_by_hand(family):
    assert family.n_layer == 5 and family.n_routed_layers == 4
    assert family.heads == (48, 64, 64, 64, 48)
    assert family.heads_by_kind == {FULL: 48, SLIDING: 64}
    # the issue's arithmetic: 29.46 M and 37.88 M of attention a layer
    assert family.attention_params(48) == attention_params(48) == 29_458_432
    assert family.attention_params(64) == attention_params(64) == 37_879_808
    dense = 3 * E * 8192                            # 50.33 M
    routed = E * 256 + 256 + 3 * E * 512 + 16 * 3 * E * 512     # 54.00 M
    assert family.routed_params() == routed
    assert round(routed / 1e6, 2) == 54.0 and round(dense / 1e6, 2) == 50.33
    layers = (2 * E + attention_params(48) + dense) \
        + 3 * (2 * E + attention_params(64) + routed) \
        + (2 * E + attention_params(48) + routed)
    assert family.param_count() == 2 * ROWS * E + E + layers
    assert round(family.param_count() / 1e6, 1) == 490.3
    # x 16 bytes of training state and 2 of the matrices' bfloat16 copy
    assert round(family.param_count() * 18 / 2 ** 30, 2) == 8.22
    assert family.expected_rows_per_token() == 0.5      # 8 x 16 / 256
    assert family.attended_pairs_by_kind(SEQ) == {
        FULL: FULL_PAIRS, SLIDING: WINDOW_PAIRS}
    assert (FULL_PAIRS, WINDOW_PAIRS) == (134_225_920, 8_257_792)
    head_pairs = 2 * 48 * FULL_PAIRS + 3 * 64 * WINDOW_PAIRS
    assert family.attended_head_pairs_a_pass(SEQ) == head_pairs
    # a window longer than the sequence is the triangle
    assert family.attended_pairs_by_kind(256)[SLIDING] == 256 * 257 // 2
    multiplied = ROWS * E + 2 * attention_params(48) \
        + 3 * attention_params(64) + dense \
        + 4 * (E * 256 + 3 * E * 512 + 0.5 * 3 * E * 512)
    assert family.multiplying_params_per_token() == multiplied
    # the issue's 269.6 M to a rounding: 269.55
    assert round(multiplied / 1e6, 2) == 269.55
    assert family.flops_per_token(SEQ) == pytest.approx(
        6 * multiplied + 6 * head_pairs / SEQ * 2 * D)
    # attention 1.36 G of 2.98 G operations a token: a full layer 0.604 G,
    # a sliding one 0.050 G
    assert round(6 * FULL_PAIRS / SEQ * 48 * 2 * D / 1e9, 3) == 0.604
    assert round(6 * WINDOW_PAIRS / SEQ * 64 * 2 * D / 1e9, 3) == 0.050
    assert round(6 * head_pairs / SEQ * 2 * D / 1e9, 2) == 1.36
    # the issue's 2.98 G is its two rounded parts' sum: 1.617 + 1.357
    assert round(family.flops_per_token(SEQ) / 1e9, 3) == 2.974
    cost = family.attention_cost(BATCH, SEQ)
    assert cost["flops"] == 6 * 2 * BATCH * head_pairs * D
    assert cost["bytes"] == sum(
        6 * BATCH * SEQ * D * (h + HKV) * 2 + 2 * BATCH * h * SEQ * 4
        for h in (48, 64, 64, 64, 48))
    # compute-bound: the attended pairs' time at the chip's peak, a step
    peaks = registry.peaks("TPU v5 lite")
    assert cost["flops"] / peaks["bf16_flops_per_s"] \
        > cost["bytes"] / peaks["hbm_bytes_per_s"]
    # the kernels, head-major at either head count, and no other
    for heads in (48, 64, 8):
        assert family.is_attention_kernel(
            f"tpu_custom_call__bf16_{heads}_16384_128___f32_{heads}_"
            "16384_1__")
    assert not family.is_attention_kernel("fusion.1_bf16_16384_2048_")
    assert not family.is_attention_kernel("tpu_custom_call_bf16_16384_512_")
    assert family.is_moe_matmul("tpu_custom_call_bf16_16384_512_")


def test_counts_are_the_models_own(family):
    import jax

    from ray_tpu.models import laguna

    cfg = family.model_config()
    assert (cfg.n_layer, cfg.vocab_size, cfg.held) == (5, ROWS, (0, 16))
    assert (cfg.sliding_window, cfg.n_experts, cfg.top_k) == (WINDOW, 256, 8)
    assert cfg.layer_types == family.layer_types
    assert cfg.mlp_layer_types == family.ffn_types
    assert cfg.moe_layers == [1, 2, 3, 4]
    assert cfg.yarn == laguna.Yarn(64.0, 4096, 64.0, 1.0,
                                   1.4158883083359672)
    assert (cfg.theta_full, cfg.theta_sliding) == (500000.0, 10000.0)
    assert (cfg.rotary_full, cfg.rotary_sliding) == (64, 128)
    assert (cfg.dense_width, cfg.expert_width, cfg.shared_width) == (
        8192, 512, 512)
    assert cfg.routed_scale == 2.5 and cfg.bias_update_speed == 0.001
    assert family.flops_per_token(SEQ) == pytest.approx(
        laguna.count_flops_per_token(cfg, SEQ), rel=1e-12)
    for kind, window in ((SLIDING, WINDOW), (FULL, None)):
        assert family.attended_pairs_by_kind(SEQ)[kind] \
            == laguna.attended_pairs(SEQ, window)
    shapes = jax.eval_shape(lambda key: laguna.init_params(key, cfg),
                            jax.random.PRNGKey(0))
    assert family.param_count() == laguna.num_params(shapes)
    # the buffer between dispatch and combine is the program's own: twice
    # the 8,192 rows expected
    from ray_tpu.ops.moe import buffer_rows
    assert family.buffered_rows(BATCH * SEQ) == buffer_rows(
        BATCH * SEQ * 8, 16, 256) == 16384
    # the reference is given the layers in three groups
    assert [(kind, ffn, members) for kind, ffn, members
            in family.groups()] == [
        (FULL, "dense", [0]), (SLIDING, "sparse", [1, 2, 3]),
        (FULL, "sparse", [4])]


# -- the readers --------------------------------------------------------------

class Counted:
    def __init__(self, **counters):
        self.counters = {name.replace("_", ".", 1): n
                         for name, n in counters.items()}


T512, T256 = 512 * 512, 256 * 256


@pytest.mark.parametrize("counters, share", [
    # a recomputed stack traces three kernels a SHAPE of layer (its own
    # forward, the forward under the gradient, the backward), 512-tiles: 63
    # tiles a windowed kernel's head
    ({"attention_window_pairs_visited": 3 * 63 * T512,
      "attention_window_kernels": 3}, 100 * WINDOW_PAIRS / (63 * T512)),
    # the same counted a layer (no recomputation): six kernels
    ({"attention_window_pairs_visited": 6 * 63 * T512,
      "attention_window_kernels": 6}, 100 * WINDOW_PAIRS / (63 * T512)),
    # 256-tiles in both passes: 189 tiles
    ({"attention_window_pairs_visited": 3 * 189 * T256,
      "attention_window_kernels": 3}, 100 * WINDOW_PAIRS / (189 * T256)),
    # the forward at 256 and the backward at 512: the mean of the passes
    ({"attention_window_pairs_visited": 2 * 189 * T256 + 63 * T512,
      "attention_window_kernels": 3},
     100 * WINDOW_PAIRS / ((2 * 189 * T256 + 63 * T512) / 3)),
    # a program whose kernels know no window counts none
    ({"attention_pairs_visited": 9 * 528 * T512}, None),
    ({}, None),
])
def test_the_pairs_reader_on_known_counters(family, counters, share,
                                            monkeypatch):
    read = registry.metric("window_head_pairs_attended_share").read
    obs = {"family": family, "peaks": {},
           "traffic": registry.traffic("resident-16k")}
    monkeypatch.setattr(timeline, "of", lambda obs: Counted(**counters))
    assert read(obs) == (share if share is None else pytest.approx(share))
    if share:
        assert 45.0 < read(obs) < 100.0
    # nothing in a rehearsal, nothing without a timeline, nothing for a
    # family whose layers have one head count
    assert read(dict(obs, peaks=None)) is None
    for other in ("olmoe-1b-7b-1layer", "mellum2-12b-a2.5b-ep4"):
        assert read(dict(obs, family=registry.family(
            registry.config(other)))) is None
    monkeypatch.setattr(timeline, "of", lambda obs: None)
    assert read(obs) is None


def test_the_pairs_reader_at_the_cells_tiles(family, monkeypatch):
    """50 with 512-tiles, 67 with 256-tiles, 80 with 128-tiles."""
    read = registry.metric("window_head_pairs_attended_share").read
    obs = {"family": family, "peaks": {},
           "traffic": registry.traffic("resident-16k")}
    for tiles, block, want in ((63, 512, 50.0), (189, 256, 66.7),
                               (630, 128, 80.0)):
        monkeypatch.setattr(timeline, "of", lambda obs: Counted(
            attention_window_kernels=3,
            attention_window_pairs_visited=3 * tiles * block * block))
        assert read(obs) == pytest.approx(want, abs=0.1)


def test_the_gate_share_reader_on_a_known_reduction(family, monkeypatch):
    read = registry.metric("attn_gate_share").read
    obs = {"family": family, "config": family.config, "chips": 1,
           "trace": {"steps": 1}, "peaks": registry.peaks("TPU v5 lite"),
           "traffic": registry.traffic("resident-16k")}
    monkeypatch.setattr(scope_trace, "of", lambda obs: {
        "busy_s": 2.0, "scopes": {"attention": 1.0, "attention/gate": 0.05,
                                  "attention/out": 0.2}})
    assert read(obs) == pytest.approx(2.5)
    # a program that has the scope and spent no time there: 0
    monkeypatch.setattr(scope_trace, "of", lambda obs: {
        "busy_s": 2.0, "scopes": {"attention": 1.0}})
    assert read(obs) == 0.0
    monkeypatch.setattr(scope_trace, "of", lambda obs: None)
    assert read(obs) is None
    # a program whose vocabulary has no such scope (the parent's): nothing
    monkeypatch.setattr(scope_trace, "of", lambda obs: {
        "busy_s": 2.0, "scopes": {"attention": 1.0}})
    monkeypatch.setattr(scope_trace, "vocabulary", lambda: (
        ("attention", "attention/out"), ()))
    assert read(obs) is None
    monkeypatch.setattr(scope_trace, "vocabulary", lambda: (None, ()))
    assert read(obs) is None
    monkeypatch.undo()
    # a family without a gate: nothing
    monkeypatch.setattr(scope_trace, "of", lambda obs: {
        "busy_s": 2.0, "scopes": {"attention/gate": 0.5}})
    other = registry.config("mellum2-12b-a2.5b-ep4")
    assert read(dict(obs, config=other)) is None


# -- the recorded trace and timeline ------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    """What `record_trace_laguna.py` recorded on one v5e chip (three steps
    of five recomputed layers: hidden 256, six and eight heads on two of
    128, four of sixteen experts held beside a shared one, one sequence of
    2,048 tokens under a window of 384), with the family of the sizes it
    ran."""
    if not os.path.exists(RECORDED):
        pytest.skip("no trace of the laguna step recorded")
    import record_trace_laguna as recorder
    from benchmark.families.laguna import Family

    with open(RECORDED_TIMELINE) as f:
        doc = json.load(f)
    return (scope_trace.reduce(scope_trace.events(RECORDED),
                               *scope_trace.vocabulary()),
            Family(recorder.CONFIG), recorder, doc)


def test_recorded_trace_holds_the_gate_and_both_kinds_of_kernel(recorded):
    found, small, _, _ = recorded
    scopes = found["scopes"]
    for scope in ("attention/qkv", "attention/gate", "attention/out", "norm",
                  "head_and_loss", "ffn/dense", "ffn/moe/route",
                  "ffn/moe/experts", "ffn/moe/shared",
                  "attention/kernel/fwd_rows_window",
                  "attention/kernel/bwd_fused_window",
                  "attention/kernel/fwd_rows", "attention/kernel/bwd_fused"):
        assert scopes[scope] > 0, scope
    for scope in ("attention/kernel/fwd_rows_blocks", "diffusion",
                  "attention/indexer", "exit_gate", "attention/latent_down"):
        assert scope not in scopes, scope
    assert found["named_s"] > 0.85 * found["busy_s"]
    # the gate ran forward, in a recomputed layer's replay and backward
    assert set(found["in_scope"]["attention/gate"]) >= {
        "fwd", "remat_fwd", "bwd"}
    assert scopes["attention/gate"] < scopes["attention/kernel"]


def test_the_readers_on_the_recorded_trace_and_timeline(recorded,
                                                        monkeypatch):
    found, small, recorder, doc = recorded
    monkeypatch.setattr(scope_trace, "of", lambda obs: found)
    obs = {"family": small, "config": recorder.CONFIG, "chips": 1,
           "trace": {"steps": 3}, "peaks": registry.peaks("TPU v5 lite"),
           "t_open": 0.0, "window_s": 0.0,
           "traffic": {"batch": recorder.BATCH, "seq": recorder.SEQ}}
    share = registry.metric("attn_gate_share").read(obs)
    assert share == pytest.approx(
        100 * found["scopes"]["attention/gate"] / found["busy_s"])
    assert 0 < share < 15
    # the counters of that step's trace: a recomputed stack traces three
    # kernels a SHAPE of layer (its own forward, the forward under the
    # gradient, the backward): full + dense and full + sparse at 6 query
    # heads, sliding + sparse at 8
    counters = doc["counters"]
    assert counters["attention.q_heads"] == 3 * (6 + 8 + 6)
    assert counters["attention.kv_heads"] == 3 * 3 * 2
    assert counters["attention.window_kernels"] == 3
    assert counters["attention.window"] == 3 * 384
    assert counters["attention.gated"] == 3      # once a shape of layer
    assert counters["rope.partial"] == 2         # the two full shapes
    assert counters["rope.scaled"] == 4          # their q and k
    # under the window 4 x 4 tiles of 512 in all three kernels, 7 visited
    # (the diagonal's 4 and the 3 under it)
    under = 3 * 7 * 512 ** 2
    assert counters["attention.window_pairs_visited"] == under
    monkeypatch.setattr(timeline, "of",
                        lambda obs: timeline.Timeline(doc, obs))
    pairs = registry.metric("window_head_pairs_attended_share").read(obs)
    windowed = 384 * 385 // 2 + (2048 - 384) * 384
    assert pairs == pytest.approx(100 * windowed / (7 * 512 ** 2))
    assert 35 < pairs < 45


@pytest.mark.parametrize("trace", [
    "tpu1_olmoe.xplane.pb.gz", "tpu1_mellum.xplane.pb.gz",
    "tpu1_deepseek_v3.xplane.pb.gz", "tpu1_sdar.xplane.pb.gz"])
def test_other_traces_hold_no_gate(trace):
    path = os.path.join(DATA, trace)
    if not os.path.exists(path):
        pytest.skip(f"no {trace} recorded")
    found = scope_trace.reduce(scope_trace.events(path),
                               *scope_trace.vocabulary())
    assert "attention/gate" not in found["scopes"]


# -- the rehearsal ------------------------------------------------------------

def run_cell(*args, root=registry.ROOT):
    cmd = [sys.executable, os.path.join(root, "benchmark", "run.py"), *args,
           "--rehearse"]
    return subprocess.run(
        cmd, cwd=root, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=registry.ROOT))


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
    assert "laguna reference: L [" in proc.stdout
    assert "layer 0 (full_attention)" in proc.stdout
    assert "layer 1 (sliding_attention)" in proc.stdout
    # what this PR counts is in the run's own timeline: the rehearsal's one
    # sequence of 128 tokens, a tile a sequence, under a window of 48
    run_dir = os.path.join(registry.ROOT, ".scratch", "benchmark", CELL, CELL)
    with open(os.path.join(run_dir, "timeline.json")) as f:
        counters = json.load(f)["counters"]
    # a recomputed stack traces a kernel a SHAPE of layer: a shape's own
    # forward, the forward under the gradient and the one backward; full +
    # dense and full + sparse at 6 query heads, sliding + sparse at 8
    assert counters["attention.q_heads"] == 3 * (6 + 8 + 6)
    assert counters["attention.window_kernels"] == 3
    assert counters["attention.window"] == 3 * 48
    assert counters["attention.gated"] == 3
    assert counters["rope.partial"] == 2
    assert counters["rope.scaled"] == 4
    assert counters["attention.tiles"] == 9
    assert counters["attention.window_pairs_visited"] == 128 * 128 * 3


# -- the seeded faults --------------------------------------------------------

# which limit stops a fault at the rehearsal's sizes: a layer's attention
# or the three losses (none goes unseen here)
SEEN = {
    "gate_dropped": "attention",
    "gate_from_the_stream": "losses",
    "whole_head_on_the_full_layer": "attention",
    "bases_swapped": "attention",
    "attention_factor_dropped": "attention",
    "window_one_more": "attention",
    "window_one_fewer": "attention",
    "window_on_the_full_layer": "attention",
    "full_heads_on_a_sliding_layer": "attention",
    "eight_bit_attention": "attention",
    "routed_scale_dropped": "losses",
    "shared_expert_dropped": "losses",
    "wrong_rate": "losses",
}


@pytest.mark.parametrize("fault", sorted(SEEN))
def test_what_the_reference_check_catches(tmp_path, fault):
    """A family that departs from what the configuration states (a new
    file in a copy of the benchmark) runs, and its run is not `correct`,
    by the limit `SEEN` names.  The faults inside a layer's attention show
    there (0.0073 of the reference's norm and more against a sound 0.0051
    at most, so the rehearsal's limit stands at 0.006); those behind it
    (the gate's input, which `_layer` chooses; the routed scale; the shared
    expert; the learning rate) only through three losses, by 0.0006 and
    more against a sound 0.0003."""
    from laguna_faults import BEHIND_ATTENTION, FAULTS, install

    assert sorted(FAULTS) == sorted(SEEN)
    assert sorted(BEHIND_ATTENTION) == sorted(
        name for name, how in SEEN.items() if how == "losses")
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(registry.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(registry.ROOT, "benchmark"),
                    root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = install(str(root), registry.ROOT, fault)
    proc = run_cell("--workload", cell, "--seed", "5", "--seconds", "1",
                    "--trace", "0", root=str(root))
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is False, proc.stdout[-2000:]
    assert "NOT CORRECT: loss at step" in proc.stdout
    # a layer's attention that is not the reference's withholds its losses
    first = SEEN[fault] == "attention"
    assert ("NOT CORRECT: laguna: a layer's attention" in proc.stdout) \
        is first
    assert ("reference's is nan" in proc.stdout) is first
    if fault == "wrong_rate":
        # the forward pass is right; the first update is not
        assert "NOT CORRECT: loss at step 0" not in proc.stdout
