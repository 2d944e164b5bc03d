"""The `sdar` family and its cell: the configuration against the published
`config.json`, the yardstick's counts worked by hand and against
`models/sdar.py`'s own, the two new readers on known reductions and on a
trace and a timeline recorded on the chip, a rehearsal of the cell, and
what the comparison that decides `correct` catches of the seeded faults
(`sdar_faults.py`) at the rehearsal's sizes.  The cell and its entries are
found by NAME, wherever later entries put them."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.harness import registry, scope_trace, timeline

CONFIG = "sdar-30b-a3b-chat-ep8"
CELL = CONFIG + ".resident-8k"
BATCH, SEQ = 2, 8192
E, H, HKV, D, W, ROWS, BLOCK = 2048, 32, 4, 128, 768, 19072, 4
ATTN = 2 * E * H * D + 2 * E * HKV * D          # 18.87 M
KV = 2 * E * HKV * D
ROUTED = E * 128 + 1 * 3 * E * W                # router + one expected expert
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "tpu1_sdar.xplane.pb.gz")
RECORDED_TIMELINE = os.path.join(DATA, "timeline", "timeline_sdar.json")
NEW_METRICS = ("attn_pairs_attended_share", "diffusion_glue_share")

# `JetLM/SDAR-30B-A3B-Chat`'s config.json, as the catalog of public
# architectures holds it
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
CUT = ["num_hidden_layers", "num_experts", "vocab_size"]


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
    assert config["published"] == {k: PUBLISHED[k] for k in CUT}
    assert config["num_hidden_layers"] in (4, 5)        # the floor is four
    assert config["num_experts"] == 16 >= 8
    assert config["experts_held"]["of"] == 128 and \
        config["experts_held"]["first"] == 0
    assert config["vocab_size"] == 18992 == 151936 // 8
    assert config["padded_vocab_size"] == ROWS == 149 * 128
    assert config["block_length"] == BLOCK
    assert "8 chips share each layer" in config["deployment"]
    assert "2,048 rows each" in config["deployment"]
    assert entry["source"] == ("https://huggingface.co/JetLM/"
                               "SDAR-30B-A3B-Chat/blob/main/config.json")
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    for key in ("block_length", "noise_schedule", "no_shift", "positions",
                "mask_row", "intermediate_size", "initialisation",
                "auxiliary_loss", "training", "remat", "loss_chunk_rows"):
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
        CONFIG, "resident-8k", 1)
    assert len(cell["why"]) <= 200
    assert [w["name"] for w in registry.benchmark()["workloads"]
            if w["config"] == CONFIG] == [CELL]         # no second cell
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
                 "loop_gate_share", "collective_share"):
        assert name not in layer
    pairs, glue = (entry_of("per_layer", name) for name in NEW_METRICS)
    for m in (pairs, glue):
        assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s" \
            and m["unit"] == "%"
    assert (pairs["layer"], pairs["source"], pairs["better"]) == (
        "Kernels", "program_counter", "higher")
    assert (glue["layer"], glue["source"], glue["better"]) == (
        "Model", "device_trace", "lower")
    four = [w for w in registry.benchmark()["workloads"] if w["chips"] == 4]
    assert len(four) == 1


# -- the counts ---------------------------------------------------------------

def test_counts_by_hand(family):
    n = family.n_layer
    assert family.attention_params() == ATTN == 18_874_368
    assert family.expected_rows_per_token() == 1.0      # 8 x 16 / 128
    assert family.attended_pairs(SEQ) == SEQ * SEQ + 4 * SEQ == 67_141_632
    assert family.attended_pairs(SEQ) / (2 * SEQ) ** 2 \
        == pytest.approx(0.25, rel=1e-3)
    layer = 2 * E + ATTN + 2 * D + E * 128 + 16 * 3 * E * W
    assert family.param_count() == 2 * ROWS * E + E + n * layer
    # a clean token's two rows through every layer but the last, whose
    # clean row counts W_k and W_v alone; the head once; L + 4 pairs a
    # token, head and layer
    assert family.flops_per_token(SEQ) == pytest.approx(
        6 * ((2 * n - 1) * (ATTN + ROUTED) + KV + ROWS * E)
        + n * 6 * (SEQ + 4) * H * 2 * D)
    cost = family.attention_cost(BATCH, SEQ)
    assert cost["flops"] == n * 6 * 2 * BATCH * (SEQ * SEQ + 4 * SEQ) * H * D
    assert cost["bytes"] == n * (
        6 * BATCH * 2 * SEQ * D * (H + HKV) * 2 + 2 * BATCH * H * 2 * SEQ * 4)
    # compute-bound: the attended pairs' time at the chip's peak, a step
    peaks = registry.peaks("TPU v5 lite")
    assert cost["flops"] / peaks["bf16_flops_per_s"] \
        > cost["bytes"] / peaks["hbm_bytes_per_s"]
    # the kernels over 2 seq rows a sequence, head-major, and no other
    assert family.is_attention_kernel(
        "tpu_custom_call__bf16_64_16384_128___f32_64_16384_1__")
    assert family.is_attention_kernel(
        "tpu_custom_call__bf16_64_16384_128___f32_64_16384_128___f32_64_"
        "16384_128__")
    assert not family.is_attention_kernel("fusion.1_bf16_32768_2048_")
    assert not family.is_attention_kernel("tpu_custom_call_bf16_65536_768_")


def test_counts_are_the_models_own(family):
    import jax

    from ray_tpu.models import sdar

    cfg = family.model_config()
    assert (cfg.n_layer, cfg.vocab_size, cfg.mask_token, cfg.held) == (
        family.n_layer, ROWS, 18992, (0, 16))
    assert (cfg.block_length, cfg.n_experts, cfg.top_k) == (BLOCK, 128, 8)
    assert family.flops_per_token(SEQ) == pytest.approx(
        sdar.count_flops_per_token(cfg, SEQ), rel=1e-12)
    assert family.attended_pairs(SEQ) == sdar.attended_pairs(SEQ, BLOCK)
    shapes = jax.eval_shape(lambda key: sdar.init_params(key, cfg),
                            jax.random.PRNGKey(0))
    assert family.param_count() == sdar.num_params(shapes)
    # the buffer between dispatch and combine is the program's own
    from ray_tpu.ops.moe import buffer_rows
    rows = 2 * BATCH * SEQ * 8
    assert family.buffered_rows(2 * BATCH * SEQ) == buffer_rows(
        rows, 16, 128) == 65536


# -- the readers --------------------------------------------------------------

class Counted:
    def __init__(self, **counters):
        self.counters = {name.replace("_", ".", 1): n
                         for name, n in counters.items()}


@pytest.mark.parametrize("counters, share", [
    # three kernels of 512-tiles at the cell's sizes: 288 tiles each
    ({"attention_pairs_visited": 3 * 288 * 512 * 512,
      "attention_q_heads": 96}, 100 * 67_141_632 / (288 * 512 * 512)),
    # one that visits the square
    ({"attention_pairs_visited": 16384 ** 2, "attention_q_heads": 32},
     100 * 67_141_632 / 16384 ** 2),
    ({"attention_q_heads": 96}, None),      # a program that counts no pairs
    ({}, None),
])
def test_the_pairs_reader_on_known_counters(family, counters, share,
                                            monkeypatch):
    read = registry.metric("attn_pairs_attended_share").read
    obs = {"family": family, "peaks": {},
           "traffic": registry.traffic("resident-8k")}
    monkeypatch.setattr(timeline, "of", lambda obs: Counted(**counters))
    assert read(obs) == (share if share is None else pytest.approx(share))
    if share:
        assert 25.0 < read(obs) < 100.0
    # nothing in a rehearsal, nothing without a timeline, nothing for a
    # family without a rule
    assert read(dict(obs, peaks=None)) is None
    other = registry.family(registry.config("olmoe-1b-7b-1layer"))
    assert read(dict(obs, family=other)) is None
    monkeypatch.setattr(timeline, "of", lambda obs: None)
    assert read(obs) is None


def test_the_glue_reader_on_a_known_reduction(family, monkeypatch):
    read = registry.metric("diffusion_glue_share").read
    obs = {"family": family, "chips": 1, "trace": {"steps": 1},
           "peaks": registry.peaks("TPU v5 lite"),
           "traffic": registry.traffic("resident-8k")}
    monkeypatch.setattr(scope_trace, "of", lambda obs: {
        "busy_s": 2.0, "scopes": {"head_and_loss": 0.5, "diffusion": 0.125}})
    assert read(obs) == 6.25
    # a program that has the scope and spent nothing under it: 0
    monkeypatch.setattr(scope_trace, "of", lambda obs: {
        "busy_s": 2.0, "scopes": {"head_and_loss": 0.5}})
    assert read(obs) == 0.0
    # a program whose vocabulary has no such scope (the parent's): nothing
    monkeypatch.setattr(scope_trace, "vocabulary",
                        lambda: (("embed", "head_and_loss"), ()))
    assert read(obs) is None
    monkeypatch.setattr(scope_trace, "vocabulary", lambda: (None, ()))
    assert read(obs) is None
    monkeypatch.undo()
    monkeypatch.setattr(scope_trace, "of", lambda obs: None)
    assert read(obs) is None
    other = registry.family(registry.config("olmoe-1b-7b-1layer"))
    monkeypatch.setattr(scope_trace, "of", lambda obs: {
        "busy_s": 2.0, "scopes": {"diffusion": 0.125}})
    assert read(dict(obs, family=other)) is None


# -- the recorded trace and timeline ------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    """What `record_trace_sdar.py` recorded on one v5e chip (three steps of
    two recomputed layers: hidden 256, eight heads on two of 128, four of
    sixteen experts held, 2 x 1,024 tokens in blocks of 4, so 2,048 rows a
    sequence), with the family of the sizes it ran."""
    if not os.path.exists(RECORDED):
        pytest.skip("no trace of the sdar step recorded")
    import record_trace_sdar as recorder
    from benchmark.families.sdar import Family

    with open(RECORDED_TIMELINE) as f:
        doc = json.load(f)
    return (scope_trace.reduce(scope_trace.events(RECORDED),
                               *scope_trace.vocabulary()),
            Family(recorder.CONFIG), recorder, doc)


def test_recorded_trace_has_block_diffusions_scopes(recorded):
    """The glue ran under its scope, forward and backward; the kernels
    under the rule under names of their own and the diagonal's not at all;
    nearly all of the step under a name."""
    found, small, _, _ = recorded
    scopes = found["scopes"]
    assert scopes["diffusion"] > 0
    for scope in ("attention/qkv", "attention/out", "norm", "head_and_loss",
                  "ffn/moe/route", "ffn/moe/experts",
                  "attention/kernel/fwd_rows_blocks",
                  "attention/kernel/bwd_fused_blocks"):
        assert scopes[scope] > 0, scope
    for scope in ("attention/kernel/fwd_rows", "attention/kernel/bwd_fused",
                  "attention/indexer", "exit_gate"):
        assert scope not in scopes, scope
    # (at these sizes the copies and casts that carry no name are an
    # eighth of the step; 3 % at the cell's)
    assert found["named_s"] > 0.85 * found["busy_s"]
    # the glue is small beside the head it feeds
    assert scopes["diffusion"] < scopes["head_and_loss"]


def test_the_readers_on_the_recorded_trace_and_timeline(recorded,
                                                        monkeypatch):
    found, small, recorder, doc = recorded
    monkeypatch.setattr(scope_trace, "of", lambda obs: found)
    obs = {"family": small, "chips": 1, "trace": {"steps": 3},
           "peaks": registry.peaks("TPU v5 lite"), "t_open": 0.0,
           "window_s": 0.0,
           "traffic": {"batch": recorder.BATCH, "seq": recorder.SEQ}}
    share = registry.metric("diffusion_glue_share").read(obs)
    assert share == pytest.approx(
        100 * found["scopes"]["diffusion"] / found["busy_s"])
    assert 0 < share < 10
    assert registry.metric("head_loss_share").read(obs) > share
    # the counters of that step's trace: a layer's own forward, the rule's
    # under the gradient and the one backward kernel, 8 query heads each
    counters = doc["counters"]
    assert counters["attention.q_heads"] == 24
    assert (counters["diffusion.rows"], counters["diffusion.rows_noised"],
            counters["diffusion.block_length"]) == (2048, 1024, 4)
    # 4 x 4 tiles of 512 in each of the three (two kinds of row take the
    # backward's tile forward too), 8 visited: 3 + 3 among the clean keys, 2
    # on the noised rows' own diagonal
    assert counters["attention.tiles"] == 3 * 16
    assert counters["attention.tiles_skipped"] == 3 * 8
    assert counters["attention.pairs_visited"] == 3 * 8 * 512 ** 2
    monkeypatch.setattr(timeline, "of",
                        lambda obs: timeline.Timeline(doc, obs))
    pairs = registry.metric("attn_pairs_attended_share").read(obs)
    assert pairs == pytest.approx(
        100 * 3 * 1024 * 1028 / counters["attention.pairs_visited"])


@pytest.mark.parametrize("trace", [
    "tpu1_olmoe.xplane.pb.gz", "tpu1_lfm2_moe.xplane.pb.gz",
    "tpu1_keye_vl.xplane.pb.gz", "tpu1_ouro.xplane.pb.gz"])
def test_other_traces_hold_no_diffusion(trace):
    path = os.path.join(DATA, trace)
    if not os.path.exists(path):
        pytest.skip(f"no {trace} recorded")
    found = scope_trace.reduce(scope_trace.events(path),
                               *scope_trace.vocabulary())
    assert "diffusion" not in found["scopes"]
    assert "attention/kernel/fwd_rows_blocks" not in found["scopes"]


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
    assert "sdar reference: L_D" in proc.stdout
    assert "attention's result under the rule is" in proc.stdout
    # what this PR counts is in the run's own timeline: the rehearsal's
    # sequences of 64 tokens, 128 rows, a tile a kind of row
    run_dir = os.path.join(registry.ROOT, ".scratch", "benchmark", CELL, CELL)
    with open(os.path.join(run_dir, "timeline.json")) as f:
        counters = json.load(f)["counters"]
    assert (counters["diffusion.rows"], counters["diffusion.rows_noised"],
            counters["diffusion.block_length"]) == (128, 64, 4)
    kernels = counters["attention.q_heads"] / 8
    assert counters["attention.tiles"] == 4 * kernels
    assert counters["attention.tiles_skipped"] == 1 * kernels
    assert counters["attention.pairs_visited"] == 3 * 64 * 64 * kernels


# -- the seeded faults --------------------------------------------------------

# which limit stops a fault at the rehearsal's sizes: the first layer's
# attention, the three losses, or, named as not seen here, neither
SEEN = {
    "own_block_left_out": "first layer",
    "leak": "first layer",
    "diagonal_for_the_block": "first layer",
    "eight_bit_attention": "first layer",
    "weight_dropped": "losses",
    "targets_shifted": "losses",
    "wrong_rate": "losses",
}


@pytest.mark.parametrize("fault", sorted(SEEN))
def test_what_the_reference_check_catches(tmp_path, fault):
    """A family that departs from what the configuration states (a new
    file in a copy of the benchmark) runs, and its run is not `correct`,
    by the limit `SEEN` names."""
    from sdar_faults import FAULTS, install

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
    # a first layer that is not the reference's withholds its losses
    first = SEEN[fault] == "first layer"
    assert ("NOT CORRECT: sdar: the first layer" in proc.stdout) is first
    assert ("reference's is nan" in proc.stdout) is first
    if fault == "wrong_rate":
        # the forward pass is right; the first update is not
        assert "NOT CORRECT: loss at step 0" not in proc.stdout
