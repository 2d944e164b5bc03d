"""The `mellum` family and its cell: the configuration against the published
`config.json`, the yardstick's counts worked by hand and against
`models/mellum.py`'s own, the two new readers on known reductions and on a
trace and a timeline recorded on the chip, a rehearsal of the cell, and
what the comparison that decides `correct` catches of the seeded faults
(`mellum_faults.py`) at the rehearsal's sizes.  The cell and its entries
are found by NAME, wherever later entries put them."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.harness import registry, scope_trace, timeline

CONFIG = "mellum2-12b-a2.5b-ep4"
CELL = CONFIG + ".resident-16k"
BATCH, SEQ, WINDOW = 1, 16384, 1024
E, H, HKV, D, W, ROWS = 2304, 32, 4, 128, 896, 24576
ATTN = 2 * E * H * D + 2 * E * HKV * D          # 21.23 M
ROUTED = E * 64 + 2 * 3 * E * W                 # router + two expected experts
FULL_PAIRS = SEQ * (SEQ + 1) // 2               # 134.23 M
WINDOW_PAIRS = WINDOW * (WINDOW + 1) // 2 + (SEQ - WINDOW) * WINDOW  # 16.25 M
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "tpu1_mellum.xplane.pb.gz")
RECORDED_TIMELINE = os.path.join(DATA, "timeline", "timeline_mellum.json")
NEW_METRICS = ("window_pairs_attended_share", "window_kernel_share")
SLIDING, FULL = "sliding_attention", "full_attention"

# `JetBrains/Mellum2-12B-A2.5B-Instruct`'s config.json, as the catalog of
# public architectures holds it
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 7,
    "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
    "max_window_layers": 0, "model_type": "mellum",
    "moe_intermediate_size": 896, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 28, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}
CUT = ["num_hidden_layers", "layer_types", "mlp_layer_types", "num_experts",
       "vocab_size"]


@pytest.fixture(scope="module")
def family():
    return registry.family(registry.config(CONFIG))


def entry_of(kind, name):
    found = [m for m in registry.benchmark()[kind] if m["name"] == name]
    assert len(found) == 1, (kind, name)
    return found[0]


def test_only_depth_experts_held_and_vocabulary_are_cut():
    config = registry.config(CONFIG)
    entry = entry_of("configs", CONFIG)
    assert entry["reduced"] == config["reduced"] == CUT
    assert sorted(k for k, v in PUBLISHED.items() if config[k] != v) \
        == sorted(CUT)
    assert {k: config["published"][k] for k in (
        "num_hidden_layers", "num_experts", "vocab_size")} == {
        k: PUBLISHED[k] for k in ("num_hidden_layers", "num_experts",
                                  "vocab_size")}
    # one whole period, in its published order
    assert config["num_hidden_layers"] == 4
    assert config["layer_types"] == PUBLISHED["layer_types"][:4]
    assert config["mlp_layer_types"] == ["sparse"] * 4
    assert config["num_experts"] == 16 >= 8
    assert config["experts_held"] == {
        "first": 0, "of": 64, "why": config["experts_held"]["why"]}
    assert config["vocab_size"] == ROWS == 98304 // 4 and ROWS % 128 == 0
    assert "4 chips share each layer" in config["deployment"]
    assert "2,048 rows each" in config["deployment"]
    assert "pipeline stages" in config["deployment"]
    assert entry["source"] == ("https://huggingface.co/JetBrains/"
                               "Mellum2-12B-A2.5B-Instruct/blob/main/"
                               "config.json")
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    for key in ("qk_norm", "no_multi_token_head", "intermediate_size",
                "max_window_layers", "initialisation", "auxiliary_loss",
                "training", "remat", "loss_chunk_rows"):
        assert config["assumed"][key], key
    for key in ("loss_tolerance", "attention_error_max",
                "loss_tolerance_reason", "what"):
        assert config["reference"][key]
    assert config["reduced_how"] and config["remat"] is True
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
            "ffn_scope_share", "step_device_ms"):
        assert name in layer
    for name in ("moe_share", "remat_fwd_share", "indexer_scope_share",
                 "loop_gate_share", "collective_share",
                 "attn_pairs_attended_share", "diffusion_glue_share"):
        assert name not in layer
    pairs, kernels = (entry_of("per_layer", name) for name in NEW_METRICS)
    for m in (pairs, kernels):
        assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s" \
            and m["unit"] == "%" and m["layer"] == "Kernels"
    assert (pairs["source"], pairs["better"]) == (
        "program_counter", "higher")
    assert kernels["source"] == "device_trace"
    four = [w for w in registry.benchmark()["workloads"] if w["chips"] == 4]
    assert len(four) == 1


# -- the counts ---------------------------------------------------------------

def test_counts_by_hand(family):
    n = family.n_layer
    assert n == 4 and family.layer_types == (SLIDING,) * 3 + (FULL,)
    assert family.attention_params() == ATTN == 21_233_664
    assert family.expected_rows_per_token() == 2.0      # 8 x 16 / 64
    assert family.attended_pairs_by_kind(SEQ) == {
        FULL: FULL_PAIRS, SLIDING: WINDOW_PAIRS}
    assert (FULL_PAIRS, WINDOW_PAIRS) == (134_225_920, 16_253_440)
    assert family.attended_pairs_a_pass(SEQ) == 3 * WINDOW_PAIRS + FULL_PAIRS
    # a window longer than the sequence is the triangle
    assert family.attended_pairs_by_kind(512)[SLIDING] == 512 * 513 // 2
    layer = 2 * E + ATTN + 2 * D + E * 64 + 16 * 3 * E * W
    assert family.param_count() == 2 * ROWS * E + E + n * layer
    assert round(family.param_count() / 1e6, 1) == 595.2   # 595.1 + norms
    pairs = (3 * WINDOW_PAIRS + FULL_PAIRS) / SEQ
    assert family.flops_per_token(SEQ) == pytest.approx(
        6 * (n * (ATTN + ROUTED) + ROWS * E) + 6 * pairs * H * 2 * D)
    # attention 549 M of 1,699 M operations a token; the windowed layers
    # 146 M of them; 1,611 M for kernels that know the diagonal alone
    attention = 6 * pairs * H * 2 * D
    assert round(attention / 1e6) == 549
    assert round(family.flops_per_token(SEQ) / 1e6) == 1699
    assert round(3 * 6 * WINDOW_PAIRS / SEQ * H * 2 * D / 1e6) == 146
    assert round(4 * 6 * FULL_PAIRS / SEQ * H * 2 * D / 1e6) == 1611
    cost = family.attention_cost(BATCH, SEQ)
    assert cost["flops"] == 6 * 2 * BATCH * (
        3 * WINDOW_PAIRS + FULL_PAIRS) * H * D
    assert cost["bytes"] == n * (
        6 * BATCH * SEQ * D * (H + HKV) * 2 + 2 * BATCH * H * SEQ * 4)
    # compute-bound: the attended pairs' time at the chip's peak, a step
    peaks = registry.peaks("TPU v5 lite")
    assert cost["flops"] / peaks["bf16_flops_per_s"] \
        > cost["bytes"] / peaks["hbm_bytes_per_s"]
    # the kernels, head-major, and no other
    assert family.is_attention_kernel(
        "tpu_custom_call__bf16_32_16384_128___f32_32_16384_1__")
    assert family.is_attention_kernel(
        "tpu_custom_call__bf16_32_16384_128___f32_32_16384_128___f32_32_"
        "16384_128__")
    assert not family.is_attention_kernel("fusion.1_bf16_16384_2304_")
    assert not family.is_attention_kernel("tpu_custom_call_bf16_65536_896_")


def test_counts_are_the_models_own(family):
    import jax

    from ray_tpu.models import mellum

    cfg = family.model_config()
    assert (cfg.n_layer, cfg.vocab_size, cfg.held) == (4, ROWS, (0, 16))
    assert (cfg.sliding_window, cfg.n_experts, cfg.top_k) == (WINDOW, 64, 8)
    assert cfg.layer_types == family.layer_types
    assert cfg.yarn == mellum.Yarn(16.0, 8192, 32.0, 1.0,
                                   1.2772588722239782)
    assert cfg.rope_theta == 500000.0 and cfg.aux_weight \
        == family.config["router_aux_loss_coef"]
    assert family.flops_per_token(SEQ) == pytest.approx(
        mellum.count_flops_per_token(cfg, SEQ), rel=1e-12)
    for kind, window in ((SLIDING, WINDOW), (FULL, None)):
        assert family.attended_pairs_by_kind(SEQ)[kind] \
            == mellum.attended_pairs(SEQ, window)
    shapes = jax.eval_shape(lambda key: mellum.init_params(key, cfg),
                            jax.random.PRNGKey(0))
    assert family.param_count() == mellum.num_params(shapes)
    # the buffer between dispatch and combine is the program's own: twice
    # the 32,768 rows expected
    from ray_tpu.ops.moe import buffer_rows
    assert family.buffered_rows(BATCH * SEQ) == buffer_rows(
        BATCH * SEQ * 8, 16, 64) == 65536


# -- the readers --------------------------------------------------------------

class Counted:
    def __init__(self, **counters):
        self.counters = {name.replace("_", ".", 1): n
                         for name, n in counters.items()}


ATTENDED = 3 * WINDOW_PAIRS + FULL_PAIRS
T512 = 512 * 512


@pytest.mark.parametrize("counters, share", [
    # a recomputed stack traces three kernels a KIND of layer (its own
    # forward, the forward under the gradient, the backward), 512-tiles:
    # 93 tiles a windowed kernel, 528 a full one; the layers weigh 3 to 1
    ({"attention_pairs_visited": 3 * (93 + 528) * T512,
      "attention_window_pairs_visited": 3 * 93 * T512,
      "attention_window_kernels": 3, "attention_q_heads": 6 * 32},
     100 * ATTENDED / ((3 * 93 + 528) * T512)),
    # the same counted a layer (no recomputation): eight kernels
    ({"attention_pairs_visited": 2 * (3 * 93 + 528) * T512,
      "attention_window_pairs_visited": 2 * 3 * 93 * T512,
      "attention_window_kernels": 6, "attention_q_heads": 8 * 32},
     100 * ATTENDED / ((3 * 93 + 528) * T512)),
    # the full layers' forward at 1,024-tiles (136 of them), as
    # `_auto_tiles` has it
    ({"attention_pairs_visited": 3 * 93 * T512
      + (2 * 136 * 4 + 528) * T512,
      "attention_window_pairs_visited": 3 * 93 * T512,
      "attention_window_kernels": 3, "attention_q_heads": 6 * 32},
     100 * ATTENDED / (3 * 93 * T512 + (2 * 136 * 4 + 528) * T512 / 3)),
    # kernels that know the diagonal alone: every layer visits the triangle
    ({"attention_pairs_visited": 6 * 528 * T512,
      "attention_q_heads": 6 * 32}, 100 * ATTENDED / (4 * 528 * T512)),
    ({"attention_q_heads": 192}, None),     # a program that counts no pairs
    ({}, None),
])
def test_the_pairs_reader_on_known_counters(family, counters, share,
                                            monkeypatch):
    read = registry.metric("window_pairs_attended_share").read
    obs = {"family": family, "peaks": {},
           "traffic": registry.traffic("resident-16k")}
    monkeypatch.setattr(timeline, "of", lambda obs: Counted(**counters))
    assert read(obs) == (share if share is None else pytest.approx(share))
    if share:
        assert 30.0 < read(obs) < 100.0
    # nothing in a rehearsal, nothing without a timeline, nothing for a
    # family whose layers are of one kind
    assert read(dict(obs, peaks=None)) is None
    other = registry.family(registry.config("olmoe-1b-7b-1layer"))
    assert read(dict(obs, family=other)) is None
    monkeypatch.setattr(timeline, "of", lambda obs: None)
    assert read(obs) is None


def test_the_pairs_reader_at_the_cells_tiles(family, monkeypatch):
    """86.5 with 512-tiles everywhere, 33 for the diagonal alone."""
    read = registry.metric("window_pairs_attended_share").read
    obs = {"family": family, "peaks": {},
           "traffic": registry.traffic("resident-16k")}
    for counters, want in (
            ({"attention_pairs_visited": 3 * (93 + 528) * T512,
              "attention_window_pairs_visited": 3 * 93 * T512,
              "attention_window_kernels": 3}, 86.5),
            ({"attention_pairs_visited": 6 * 528 * T512}, 33.0)):
        monkeypatch.setattr(timeline, "of", lambda obs: Counted(
            attention_q_heads=192, **counters))
        assert read(obs) == pytest.approx(want, abs=0.3)


def test_the_kernel_share_reader_on_a_known_reduction(family, monkeypatch):
    read = registry.metric("window_kernel_share").read
    obs = {"family": family, "chips": 1, "trace": {"steps": 1},
           "peaks": registry.peaks("TPU v5 lite"),
           "traffic": registry.traffic("resident-16k")}
    kernels = "attention/kernel"
    monkeypatch.setattr(scope_trace, "of", lambda obs: {
        "busy_s": 2.0, "scopes": {
            kernels: 1.0, f"{kernels}/fwd_rows": 0.2,
            f"{kernels}/bwd_fused": 0.3, f"{kernels}/fwd_rows_window": 0.1,
            f"{kernels}/bwd_fused_window": 0.2}})
    assert read(obs) == pytest.approx(37.5)
    # a program that has the forms and ran no windowed kernel: 0
    monkeypatch.setattr(scope_trace, "of", lambda obs: {
        "busy_s": 2.0, "scopes": {kernels: 0.6, f"{kernels}/fwd_rows": 0.5}})
    assert read(obs) == 0.0
    # no attention kernel ran under a form's name: nothing
    monkeypatch.setattr(scope_trace, "of", lambda obs: {
        "busy_s": 2.0, "scopes": {"head_and_loss": 0.5, kernels: 0.1}})
    assert read(obs) is None
    monkeypatch.setattr(scope_trace, "of", lambda obs: None)
    assert read(obs) is None
    # a program whose vocabulary has no such forms (the parent's): nothing
    monkeypatch.setattr(scope_trace, "of", lambda obs: {
        "busy_s": 2.0, "scopes": {kernels: 0.5}})
    monkeypatch.setattr(scope_trace, "vocabulary", lambda: (
        ("attention/kernel", "attention/kernel/fwd_rows"), ()))
    assert read(obs) is None
    monkeypatch.setattr(scope_trace, "vocabulary", lambda: (None, ()))
    assert read(obs) is None
    monkeypatch.undo()
    other = registry.family(registry.config("olmoe-1b-7b-1layer"))
    monkeypatch.setattr(scope_trace, "of", lambda obs: {
        "busy_s": 2.0, "scopes": {kernels: 0.5}})
    assert read(dict(obs, family=other)) is None


# -- the recorded trace and timeline ------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    """What `record_trace_mellum.py` recorded on one v5e chip (three steps
    of one period of four recomputed layers: hidden 256, eight heads on two
    of 128, four of sixteen experts held, one sequence of 2,048 tokens
    under a window of 384), with the family of the sizes it ran."""
    if not os.path.exists(RECORDED):
        pytest.skip("no trace of the mellum step recorded")
    import record_trace_mellum as recorder
    from benchmark.families.mellum import Family

    with open(RECORDED_TIMELINE) as f:
        doc = json.load(f)
    return (scope_trace.reduce(scope_trace.events(RECORDED),
                               *scope_trace.vocabulary()),
            Family(recorder.CONFIG), recorder, doc)


def test_recorded_trace_tells_the_two_kinds_of_layer_apart(recorded):
    found, small, _, _ = recorded
    scopes = found["scopes"]
    for scope in ("attention/qkv", "attention/out", "norm", "head_and_loss",
                  "ffn/moe/route", "ffn/moe/experts",
                  "attention/kernel/fwd_rows_window",
                  "attention/kernel/bwd_fused_window",
                  "attention/kernel/fwd_rows", "attention/kernel/bwd_fused"):
        assert scopes[scope] > 0, scope
    for scope in ("attention/kernel/fwd_rows_blocks", "diffusion",
                  "attention/indexer", "exit_gate"):
        assert scope not in scopes, scope
    assert found["named_s"] > 0.85 * found["busy_s"]
    # the four forms are all the kernels; the transposes and the groups'
    # sums around them stand under `attention/kernel` itself
    forms = sum(scopes[f"attention/kernel/{form}"] for form in (
        "fwd_rows", "bwd_fused", "fwd_rows_window", "bwd_fused_window"))
    assert 0.85 * scopes["attention/kernel"] < forms \
        < scopes["attention/kernel"]


def test_the_readers_on_the_recorded_trace_and_timeline(recorded,
                                                        monkeypatch):
    found, small, recorder, doc = recorded
    monkeypatch.setattr(scope_trace, "of", lambda obs: found)
    obs = {"family": small, "chips": 1, "trace": {"steps": 3},
           "peaks": registry.peaks("TPU v5 lite"), "t_open": 0.0,
           "window_s": 0.0,
           "traffic": {"batch": recorder.BATCH, "seq": recorder.SEQ}}
    share = registry.metric("window_kernel_share").read(obs)
    scopes = found["scopes"]
    windowed = scopes["attention/kernel/fwd_rows_window"] \
        + scopes["attention/kernel/bwd_fused_window"]
    assert share == pytest.approx(100 * windowed / (
        windowed + scopes["attention/kernel/fwd_rows"]
        + scopes["attention/kernel/bwd_fused"]))
    # three windowed layers that attend 0.34 of a full layer's pairs each
    assert 30 < share < 75
    # the counters of that step's trace: a recomputed stack traces three
    # kernels a KIND of layer (its own forward, the forward under the
    # gradient, the backward), 8 query heads each
    counters = doc["counters"]
    assert counters["attention.q_heads"] == 2 * 3 * 8
    assert counters["attention.window_kernels"] == 3
    assert counters["attention.window"] == 3 * 384
    assert counters["rope.scaled"] == 2          # q and k of the full kind
    # under the window 4 x 4 tiles of 512 in all three, 7 visited (the
    # diagonal's 4 and the 3 under it); the full kind's forward 2 x 2 tiles
    # of 1,024, 3 visited, its backward 4 x 4 of 512, 10 visited
    assert counters["attention.tiles"] == 3 * 16 + 2 * 4 + 16
    assert counters["attention.tiles_skipped"] == 3 * 9 + 2 * 1 + 6
    under = 3 * 7 * 512 ** 2
    beside = 2 * 3 * 1024 ** 2 + 10 * 512 ** 2
    assert counters["attention.window_pairs_visited"] == under
    assert counters["attention.pairs_visited"] == under + beside
    monkeypatch.setattr(timeline, "of",
                        lambda obs: timeline.Timeline(doc, obs))
    pairs = registry.metric("window_pairs_attended_share").read(obs)
    windowed = 384 * 385 // 2 + (2048 - 384) * 384
    # the layers weigh three to one, whatever the traces
    assert pairs == pytest.approx(
        100 * (3 * windowed + 2048 * 2049 // 2)
        / (3 * under / 3 + beside / 3))
    assert 45 < pairs < 55


@pytest.mark.parametrize("trace", [
    "tpu1_olmoe.xplane.pb.gz", "tpu1_lfm2_moe.xplane.pb.gz",
    "tpu1_keye_vl.xplane.pb.gz", "tpu1_sdar.xplane.pb.gz"])
def test_other_traces_hold_no_windowed_kernel(trace):
    path = os.path.join(DATA, trace)
    if not os.path.exists(path):
        pytest.skip(f"no {trace} recorded")
    found = scope_trace.reduce(scope_trace.events(path),
                               *scope_trace.vocabulary())
    assert "attention/kernel/fwd_rows_window" not in found["scopes"]
    assert "attention/kernel/bwd_fused_window" not in found["scopes"]


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
    assert "mellum reference: L [" in proc.stdout
    assert "layer 0 (sliding_attention)" in proc.stdout
    assert "layer 3 (full_attention)" in proc.stdout
    # what this PR counts is in the run's own timeline: the rehearsal's one
    # sequence of 128 tokens, a tile a sequence, under a window of 48
    run_dir = os.path.join(registry.ROOT, ".scratch", "benchmark", CELL, CELL)
    with open(os.path.join(run_dir, "timeline.json")) as f:
        counters = json.load(f)["counters"]
    # a recomputed stack traces a kernel a KIND of layer: a kind's own
    # forward, the forward under the gradient and the one backward
    kernels = counters["attention.q_heads"] / 8
    assert kernels == 2 * 3
    assert counters["attention.window_kernels"] == 3
    assert counters["attention.window"] == 3 * 48
    assert counters["rope.scaled"] == 2          # q and k of the full kind
    assert counters["attention.tiles"] == kernels
    assert counters["attention.tiles_skipped"] == 0
    assert counters["attention.pairs_visited"] == 128 * 128 * kernels
    assert counters["attention.window_pairs_visited"] == 128 * 128 * 3


# -- the seeded faults --------------------------------------------------------

# which limit stops a fault at the rehearsal's sizes: a layer's attention
# or the three losses (none goes unseen here)
SEEN = {
    "window_one_more": "attention",
    "window_one_fewer": "attention",
    "window_on_the_full_layer": "attention",
    "no_window": "attention",
    "yarn_on_a_sliding_layer": "attention",
    "plain_rope_on_the_full_layer": "attention",
    "attention_factor_dropped": "attention",
    "eight_bit_attention": "attention",
    "wrong_rate": "losses",
}


@pytest.mark.parametrize("fault", sorted(SEEN))
def test_what_the_reference_check_catches(tmp_path, fault):
    """A family that departs from what the configuration states (a new
    file in a copy of the benchmark) runs, and its run is not `correct`,
    by the limit `SEEN` names.  A window off by one moves one key of a
    row's 48 here, of 1,024 at the cell's sizes, and a layer's attention
    shows it at both (0.08 to 0.10 of the reference's norm here against a
    sound 0.007; 0.027 against 0.009 on the chip);
    `tests/test_mellum.py`'s perturbation case holds it exactly."""
    from mellum_faults import FAULTS, install

    assert sorted(FAULTS) == sorted(SEEN)
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
    assert ("NOT CORRECT: mellum: a layer's attention" in proc.stdout) \
        is first
    assert ("reference's is nan" in proc.stdout) is first
    if fault == "wrong_rate":
        # the forward pass is right; the first update is not
        assert "NOT CORRECT: loss at step 0" not in proc.stdout
