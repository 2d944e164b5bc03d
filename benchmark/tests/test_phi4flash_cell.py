"""The `phi4flash` family and its cell: the configuration against the
published `config.json`, the yardstick's counts worked by hand and against
`models/phi4flash.py`'s own, the six new readers on known reductions and on
a trace recorded on the chip, the seeded faults at the rehearsal's sizes,
and a rehearsal of the cell."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import registry, scope_trace, timeline

CONFIG = "phi-4-mini-flash-reasoning-vp8"
CELL = CONFIG + ".resident-16k"
BATCH, SEQ = 1, 16384
E, H, HKV, D, W, C, N, R, K = 2560, 40, 20, 64, 10240, 5120, 16, 160, 4
ROWS = 25088
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "tpu1_phi4flash.xplane.pb.gz")
COUNTERS = os.path.join(DATA, "timeline", "timeline_phi4flash.json")
SCOPE_METRICS = {"selective_scan_share": "mamba/scan",
                 "mamba_scope_share": "mamba",
                 "diff_attn_glue_share": "attention/diff",
                 "gmu_share": "gmu"}
NEW_METRICS = ("selective_scan_share", "mamba_scope_share",
               "selective_scan_roofline_share", "diff_attn_glue_share",
               "gmu_share", "shared_state_kept_gib")

# `microsoft/Phi-4-mini-flash-reasoning`'s config.json, as the catalog of
# public architectures holds it
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}
CUT = ["num_hidden_layers", "vocab_size"]
FFN = 3 * E * W
MAMBA = E * 2 * C + C * K + C * (R + 2 * N) + R * C + C * E
ATTENTION = 2 * E * H * D + 2 * E * HKV * D


@pytest.fixture(scope="module")
def family():
    return registry.family(registry.config(CONFIG))


def test_only_depth_and_vocabulary_are_cut():
    config = registry.config(CONFIG)
    entry = [c for c in registry.benchmark()["configs"]
             if c["name"] == CONFIG][0]
    assert entry["reduced"] == config["reduced"] == CUT
    assert sorted(k for k, v in PUBLISHED.items() if config[k] != v) \
        == sorted(CUT)
    assert config["published"] == {"num_hidden_layers": 32,
                                   "vocab_size": 200064}
    n, first = config["num_hidden_layers"], config["first_layer"]
    # every kind of layer, both makers and both readers, above the floor
    assert n >= 4 and first + n == 20 and first in (14, 15)
    assert config["vocab_size"] == ROWS == 196 * 128 >= 200064 / 8
    assert config["assumed"]["sizes"] == {
        "d_state": N, "d_conv": K, "expand": 2, "dt_rank": R}
    assert entry["source"] == (
        "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/"
        "blob/main/config.json")
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    for key in ("sizes", "biases", "head_pairing", "memory", "window",
                "initialisation", "training", "remat", "loss_chunk_rows"):
        assert config["assumed"][key], key
    for key in ("loss_tolerance", "loss_tolerance_reason", "what",
                "state_error_max"):
        assert config["reference"][key]
    assert config["reduced_how"] and config["deployment"]
    assert config["remat"] is True
    assert (config["param_dtype"], config["compute_dtype"]) == (
        "float32", "bfloat16")


def test_the_cell_is_what_the_issue_names():
    cell = registry.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "resident-16k", 1)
    assert len(cell["why"]) <= 200 and "more than its share" in cell["why"]
    end = [m["name"] for m in registry.metrics_of(CELL, "end_to_end")]
    assert end == ["tokens_per_s", "setup_s"]
    layer = [m["name"] for m in registry.metrics_of(CELL, "per_layer")]
    for name in NEW_METRICS + ("attn_roofline_share", "attn_kernel_share",
                               "mfu", "hbm_peak_gib", "head_loss_share",
                               "norm_share", "fwd_share", "bwd_share",
                               "attention_scope_share", "ffn_scope_share",
                               "scope_named_share"):
        assert name in layer
    for name in ("moe_share", "ssm_scan_share", "window_kernel_share",
                 "collective_share"):
        assert name not in layer
    new = registry.benchmark()["per_layer"][-len(NEW_METRICS):]
    assert [m["name"] for m in new] == list(NEW_METRICS)
    assert all(m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
               for m in new)
    assert [m["layer"] for m in new] == [
        "Model", "Model", "Kernels", "Model", "Model", "Model"]
    assert [m["source"] for m in new] == ["device_trace"] * 5 \
        + ["program_counter"]
    # nothing that was there is changed: the new entries come last
    assert registry.benchmark()["workloads"][-1]["name"] == CELL
    assert registry.benchmark()["configs"][-1]["name"] == CONFIG


# -- the counts ---------------------------------------------------------------

def test_counts_by_hand(family):
    from benchmark.families.phi4flash import (CROSS, FULL, GMU,
                                              MAMBA as M, WINDOW)

    kinds = family.kinds
    assert kinds[-5:] == [WINDOW, M, FULL, GMU, CROSS]
    assert family.mixer_matrices(M) == MAMBA
    assert family.mixer_matrices(FULL) == family.mixer_matrices(WINDOW) \
        == ATTENTION
    assert family.mixer_matrices(CROSS) == 2 * E * H * D
    assert family.mixer_matrices(GMU) == 2 * E * C
    # ISSUE 63's arithmetic, a layer with its feed-forward and vectors
    layer = lambda k: round((4 * E + FFN + family.mixer_matrices(k)
                             + family.mixer_vectors(k)) / 1e6, 2)
    assert [layer(k) for k in (M, WINDOW, GMU, CROSS)] == [
        119.90, 98.32, 104.87, 91.77]
    if len(kinds) == 6:
        assert round(family.param_count() / 1e6, 1) == 697.3
        assert round(family.param_count() * 18 / 2 ** 30, 2) == 11.69
    window = 512 * 513 // 2 + (SEQ - 512) * 512
    whole = SEQ * (SEQ + 1) // 2
    assert family.attended_pairs(SEQ, WINDOW) == window
    assert family.attended_pairs(SEQ, FULL) \
        == family.attended_pairs(SEQ, CROSS) == whole
    pairs = window + 2 * whole
    scans = kinds.count(M)
    scan = 6 * C * N + 3 * C
    assert family.scan_flops_per_token() == scan
    n = ROWS * E + sum(family.mixer_matrices(k) + FFN for k in kinds)
    assert family.flops_per_token(SEQ) == pytest.approx(
        6 * n + 6 * pairs / SEQ * (H // 2) * 2 * 3 * D + 3 * scans * scan)
    cost = family.attention_cost(BATCH, SEQ)
    # two calls a layer of 20 query heads: two products a call forward
    # (64 and 128 deep), four backward
    assert cost["flops"] == 2 * pairs * (H // 2) * 3 * 2 * 3 * D
    q, k, v, o = 20 * 64, 10 * 64, 10 * 128, 20 * 128
    assert cost["bytes"] == 6 * (
        SEQ * (3 * (q + k + v) + 3 * o) * 2 + 2 * 20 * SEQ * 4)
    scans_cost = family.selective_scan_cost(BATCH, SEQ)
    assert scans_cost["flops"] == scans * 3 * SEQ * scan
    read = (C + 2 * N) * 2 + C * 4
    assert scans_cost["bytes"] == scans * SEQ * (3 * read + 2 * C * 2)
    assert family.shared_bytes(BATCH, SEQ) == SEQ * (C + 2 * HKV * D) * 2
    # the flash kernels' head-major results, not the convolution's or the
    # scan's
    assert family.is_attention_kernel("custom-call.3_bf16_20_16384_128_")
    assert family.is_attention_kernel("custom-call.9_bf16_10_16384_64_")
    assert not family.is_attention_kernel("custom-call.1_bf16_1_16384_5120_")
    assert not family.is_attention_kernel(
        "custom-call.2_bf16_1_16384_8_640_")
    assert not family.is_attention_kernel("fusion.1_bf16_16384_128_")


def test_counts_are_the_models_own(family):
    import jax

    from ray_tpu.models import phi4flash

    cfg = family.model_config()
    assert (cfg.n_layer, cfg.first_layer, cfg.vocab_size, cfg.channels) == (
        family.n_layer, family.first_layer, ROWS, C)
    assert [cfg.kind(i) for i in range(cfg.n_layer)] == family.kinds
    assert [cfg.lambda_init(i) for i in range(cfg.n_layer)] == [
        family.lambda_init(i) for i in range(cfg.n_layer)]
    assert family.flops_per_token(SEQ) == pytest.approx(
        phi4flash.count_flops_per_token(cfg, SEQ))
    assert family.scan_flops_per_token() \
        == phi4flash.scan_flops_per_token(cfg)
    shapes = jax.eval_shape(lambda key: phi4flash.init_params(key, cfg),
                            jax.random.PRNGKey(0))
    assert family.param_count() == phi4flash.num_params(shapes)
    sizes = family.reference_sizes()
    assert sizes.memory_from == family.kinds.index("gmu") - 2
    assert sizes.keys_from == family.kinds.index("full")


# -- the readers --------------------------------------------------------------

@pytest.mark.parametrize("name,scope", sorted(SCOPE_METRICS.items()))
def test_scope_readers_on_a_known_reduction(family, monkeypatch, name, scope):
    obs = {"family": family, "chips": 1, "trace": {"steps": 1},
           "config": family.config, "peaks": registry.peaks("TPU v5 lite"),
           "traffic": registry.traffic("resident-16k")}
    read = registry.metric(name).read
    monkeypatch.setattr(scope_trace, "of", lambda obs: {
        "busy_s": 2.0, "scopes": {"head_and_loss": 0.5, scope: 0.125}})
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
    # no trace; a family without such layers
    monkeypatch.setattr(scope_trace, "of", lambda obs: None)
    assert read(obs) is None
    other = registry.family(registry.config("olmoe-1b-7b-1layer"))
    assert read(dict(obs, family=other)) is None


def test_the_roofline_reader_on_a_known_reduction(family, monkeypatch):
    obs = {"family": family, "chips": 1, "trace": {"steps": 2},
           "peaks": {"bf16_flops_per_s": 1e14, "hbm_bytes_per_s": 1e12},
           "traffic": registry.traffic("resident-16k")}
    module = registry.metric("selective_scan_roofline_share")
    cost = family.selective_scan_cost(BATCH, SEQ)
    least, bound = module.least_seconds(obs)
    # a pass over its bytes: the recurrence's operations are a tenth of it
    assert bound == "memory" and least == cost["bytes"] / 1e12
    assert cost["flops"] / 1e14 < least
    monkeypatch.setattr(scope_trace, "of", lambda obs: {
        "busy_s": 1.0, "scopes": {"mamba/scan": 8 * least}})
    assert module.read(obs) == pytest.approx(25.0)
    monkeypatch.setattr(scope_trace, "of", lambda obs: {
        "busy_s": 1.0, "scopes": {"mamba": 1.0}})
    assert module.read(obs) is None
    assert module.read(dict(obs, peaks=None)) is None
    assert module.read(dict(obs, trace=None)) is None
    other = registry.family(registry.config("nemotron-3-nano-30b-a3b-"
                                            "ep16-9layer"))
    assert module.read(dict(obs, family=other)) is None


class Counted:
    def __init__(self, **counters):
        self.counters = {name.replace("_", ".", 1): n
                         for name, n in counters.items()}


@pytest.mark.parametrize("counters, gib", [
    ({"shared_bytes_kept": 3 << 28}, 0.75),
    ({"shared_bytes_kept": 0}, 0.0),
    ({"remat_bytes_kept": 5}, None),        # a program that counts no such
    ({}, None),
])
def test_the_kept_reader_on_known_counters(counters, gib, monkeypatch):
    module = registry.metric("shared_state_kept_gib")
    assert module.value(Counted(**counters)) == gib
    # through `read`: nothing in a rehearsal, nothing without a timeline
    assert module.read({"peaks": None}) is None
    monkeypatch.setattr(timeline, "of", lambda obs: None)
    assert module.read({"peaks": {}}) is None
    monkeypatch.setattr(timeline, "of", lambda obs: Counted(**counters))
    assert module.read({"peaks": {}}) == gib


# -- the recorded trace -------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    """The trace `record_trace_phi4flash.py` recorded on one v5e chip (three
    steps of six recomputed layers of every kind: hidden 512, 1,024
    channels with a state of 16, eight heads of 64 on four, one sequence of
    2,048 under a window of 384), with the family of the sizes it ran."""
    if not os.path.exists(RECORDED):
        pytest.skip("no trace of the phi4flash step recorded")
    import record_trace_phi4flash as recorder
    from benchmark.families.phi4flash import Family

    return (scope_trace.reduce(scope_trace.events(RECORDED),
                               *scope_trace.vocabulary()),
            Family(recorder.CONFIG), recorder)


def test_recorded_trace_has_the_new_scopes(recorded):
    found, small, _ = recorded
    scopes = found["scopes"]
    for scope in ("mamba", "mamba/in_proj", "mamba/conv", "mamba/x_proj",
                  "mamba/scan", "mamba/out_proj", "attention/qkv",
                  "attention/cross", "attention/diff", "attention/out",
                  "attention/kernel/fwd_rows_window",
                  "attention/kernel/bwd_fused_window", "gmu", "ffn/dense",
                  "norm", "head_and_loss"):
        assert scopes[scope] > 0, scope
    for scope in ("mamba/scan", "attention/diff", "gmu"):
        assert {"fwd", "remat_fwd", "bwd"} <= set(found["in_scope"][scope])
    assert scopes["mamba"] > scopes["mamba/scan"]
    # at these sizes the weights' casts and the re-laying of u, dt and y
    # to the scan's tiles, which carry no scope, are a tenth of the step
    assert found["named_s"] > 0.85 * found["busy_s"]


def test_the_readers_on_the_recorded_trace(recorded, monkeypatch):
    found, small, recorder = recorded
    monkeypatch.setattr(scope_trace, "of", lambda obs: found)
    obs = {"family": small, "chips": 1, "trace": {"steps": 3},
           "config": small.config, "peaks": registry.peaks("TPU v5 lite"),
           "traffic": {"batch": recorder.BATCH, "seq": recorder.SEQ}}
    for name, scope in SCOPE_METRICS.items():
        share = registry.metric(name).read(obs)
        assert share == pytest.approx(
            100 * found["scopes"][scope] / found["busy_s"])
        assert 0 < share < 60, name
    roofline = registry.metric("selective_scan_roofline_share").read(obs)
    assert 0 < roofline < 100
    with open(COUNTERS) as f:
        counters = json.load(f)["counters"]
    assert counters["sscan.kernels"] == 2 and counters["sscan.fallbacks"] == 0
    assert counters["sscan.positions"] == 2 * recorder.SEQ
    assert counters["shared.memory_readers"] == 1
    assert counters["shared.kv_readers"] == 1
    assert counters["attention.diff_pairs"] == 3 * 4
    assert counters["shared.bytes_kept"] == small.shared_bytes(
        recorder.BATCH, recorder.SEQ)


@pytest.mark.parametrize("trace", [
    "tpu1_olmoe.xplane.pb.gz", "tpu1_nemotron_h.xplane.pb.gz",
    "tpu1_laguna.xplane.pb.gz", "tpu1_ouro.xplane.pb.gz"])
def test_other_traces_hold_none_of_the_new_scopes(trace):
    path = os.path.join(DATA, trace)
    if not os.path.exists(path):
        pytest.skip(f"no {trace} recorded")
    found = scope_trace.reduce(scope_trace.events(path),
                               *scope_trace.vocabulary())
    for scope in ("mamba", "mamba/scan", "attention/diff", "attention/cross",
                  "gmu"):
        assert scope not in found["scopes"]


# -- the seeded faults, at the rehearsal's sizes ------------------------------

def test_the_faults_are_the_issues_twelve():
    from phi4flash_faults import FAULTS, Faulty

    assert sorted(FAULTS) == [
        "cross_reads_window_keys", "eight_bit_matrices", "full_on_window",
        "mean_decay", "memory_after_gate", "no_d_term", "no_dt_bias",
        "no_lambda_init", "no_output_scale", "no_pair_norm", "taps_ahead",
        "window_on_full"]
    assert all(issubclass(f, Faulty) and f.__doc__ for f in FAULTS.values())


# -- the rehearsal ------------------------------------------------------------

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
    # the reference prints how far the system's streams are its own
    assert "phi4flash reference: losses" in proc.stdout
    assert "stream after each published layer" in proc.stdout
    # what this PR counts is in the run's own timeline: the rehearsal's six
    # layers, whose 128 channels the scan kernels decline
    run_dir = os.path.join(registry.ROOT, ".scratch", "benchmark", CELL, CELL)
    with open(os.path.join(run_dir, "timeline.json")) as f:
        doc = json.load(f)
    counters = doc["counters"]
    assert counters["sscan.fallbacks"] == 2
    assert counters.get("sscan.kernels", 0) == 0
    assert counters["sscan.positions"] == 2 * 128
    assert counters["shared.memory_readers"] == 1
    assert counters["shared.kv_readers"] == 1
    assert counters["attention.diff_pairs"] == 3 * 4
    kept = registry.metric("shared_state_kept_gib").value(
        timeline.Timeline(doc, {"t_open": 0.0, "window_s": 0.0}))
    assert kept == 128 * (128 + 2 * 4 * 8) * 2 / 2 ** 30
