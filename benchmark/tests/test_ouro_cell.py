"""The `ouro` family and its cell: the configuration against the published
`config.json`, the yardstick's counts worked by hand and against
`models/ouro.py`'s own, the two new readers on known reductions and on a
trace recorded on the chip, and a rehearsal of the cell."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import registry, scope_trace, timeline

CONFIG = "ouro-2.6b-ut4"
CELL = CONFIG + ".resident-8k"
BATCH, SEQ = 2, 8192
E, H, D, W, ROWS, T = 2048, 16, 128, 5632, 49152, 4
LAYER = 4 * E * E + 3 * E * W                   # a layer's seven matrices
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "tpu1_ouro.xplane.pb.gz")
NEW_METRICS = ("loop_gate_share", "loop_bodies_traced_share")

# `ByteDance/Ouro-2.6B`'s config.json, as the catalog of public
# architectures holds it
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}
CUT = ["num_hidden_layers", "layer_types"]


@pytest.fixture(scope="module")
def family():
    return registry.family(registry.config(CONFIG))


def test_only_depth_is_cut():
    config = registry.config(CONFIG)
    entry = [c for c in registry.benchmark()["configs"]
             if c["name"] == CONFIG][0]
    assert entry["reduced"] == config["reduced"] == CUT
    assert sorted(k for k, v in PUBLISHED.items() if config[k] != v) \
        == sorted(CUT)
    n = config["num_hidden_layers"]
    # a stage of a pipeline: the depth divides the model's, the floor four
    assert n >= 4 and 48 % n == 0
    assert config["layer_types"] == ["full_attention"] * n
    assert config["published"]["num_hidden_layers"] == 48
    assert config["padded_vocab_size"] == ROWS == 384 * 128
    assert config["name"] == entry["name"]
    assert f"{48 // n}-stage" in config["deployment"]
    assert "first and last" in config["deployment"]
    assert entry["source"] == ("https://huggingface.co/ByteDance/Ouro-2.6B/"
                               "blob/main/config.json")
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    for key in ("attention_bias", "gate", "norm_between_walks",
                "entropy_weight", "initialisation", "training",
                "max_window_layers", "remat", "loss_chunk_rows"):
        assert config["assumed"][key]
    for key in ("loss_tolerance", "loss_tolerance_reason", "what",
                "state_error_max", "exit_error_max"):
        assert config["reference"][key]
    assert config["reduced_how"] and config["remat"] is True
    assert (config["param_dtype"], config["compute_dtype"]) == (
        "float32", "bfloat16")


def test_the_cell_is_what_the_issue_names():
    cell = registry.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "resident-8k", 1)
    assert len(cell["why"]) <= 200
    end = [m["name"] for m in registry.metrics_of(CELL, "end_to_end")]
    assert end == ["tokens_per_s", "setup_s"]
    layer = [m["name"] for m in registry.metrics_of(CELL, "per_layer")]
    for name in NEW_METRICS + ("attn_roofline_share", "attn_kernel_share",
                               "mfu", "hbm_peak_gib", "head_loss_share",
                               "norm_share", "fwd_share", "bwd_share",
                               "attention_scope_share", "ffn_scope_share"):
        assert name in layer
    for name in ("moe_share", "moe_scope_share", "remat_fwd_share",
                 "indexer_scope_share", "collective_share"):
        assert name not in layer
    new = [m for m in registry.benchmark()["per_layer"]
           if m["name"] in NEW_METRICS]
    assert [m["name"] for m in new] == list(NEW_METRICS)
    assert all(m["workloads"] == [CELL] and m["layer"] == "Model"
               and m["better"] == "lower" for m in new)
    assert [(m["source"], m["moves"]) for m in new] == [
        ("device_trace", "tokens_per_s"), ("program_counter", "setup_s")]


# -- the counts ---------------------------------------------------------------

def test_counts_by_hand(family):
    n = family.n_layer
    assert family.layer_matrices() == LAYER == 51_380_224
    assert family.layer_calls == T * n
    assert family.param_count() == (n * (LAYER + 4 * E) + 2 * ROWS * E
                                    + E + E + 1)
    # ISSUE 50's formula: four walks and four heads, the causal pairs
    assert family.flops_per_token(SEQ) == pytest.approx(
        6 * (T * n * LAYER + T * ROWS * E)
        + T * n * 6 * ((SEQ + 1) / 2) * H * 2 * D)
    cost = family.attention_cost(BATCH, SEQ)
    assert cost["flops"] == T * n * 6 * (2 * BATCH * H * SEQ * SEQ * D) / 2
    assert cost["bytes"] == T * n * (12 * BATCH * SEQ * H * D * 2
                                     + 2 * BATCH * H * SEQ * 4)
    assert family.is_attention_kernel("custom-call.3_bf16_2_8192_16_128_")
    assert not family.is_attention_kernel("fusion.1_bf16_16384_2048_")


def test_counts_are_the_models_own(family):
    import jax

    from ray_tpu.models import ouro

    cfg = family.model_config()
    assert (cfg.n_walk, cfg.n_layer, cfg.vocab_size) == (
        T, family.n_layer, ROWS)
    assert family.flops_per_token(SEQ) == ouro.count_flops_per_token(cfg, SEQ)
    shapes = jax.eval_shape(lambda key: ouro.init_params(key, cfg),
                            jax.random.PRNGKey(0))
    assert family.param_count() == ouro.num_params(shapes)
    # the work is the model's whatever the form of the walk: four times
    # one walk's
    import dataclasses
    one = dataclasses.replace(cfg, n_walk=1)
    assert ouro.count_flops_per_token(cfg, SEQ) \
        == T * ouro.count_flops_per_token(one, SEQ)


# -- the readers --------------------------------------------------------------

def test_readers_on_a_known_reduction(family, monkeypatch):
    obs = {"family": family, "chips": 1, "trace": {"steps": 1},
           "peaks": registry.peaks("TPU v5 lite"),
           "traffic": registry.traffic("resident-8k")}
    monkeypatch.setattr(scope_trace, "of", lambda obs: {
        "busy_s": 2.0, "scopes": {"head_and_loss": 0.5, "exit_gate": 0.125}})
    assert registry.metric("loop_gate_share").read(obs) == 6.25
    # a program that has the scope and spent nothing under it: 0
    monkeypatch.setattr(scope_trace, "of", lambda obs: {
        "busy_s": 2.0, "scopes": {"head_and_loss": 0.5}})
    assert registry.metric("loop_gate_share").read(obs) == 0.0
    # a program whose vocabulary has no such scope (the parent's): nothing
    monkeypatch.setattr(scope_trace, "vocabulary",
                        lambda: (("embed", "head_and_loss"), ()))
    assert registry.metric("loop_gate_share").read(obs) is None
    monkeypatch.setattr(scope_trace, "vocabulary", lambda: (None, ()))
    assert registry.metric("loop_gate_share").read(obs) is None
    monkeypatch.undo()
    # no trace; a family that walks once
    monkeypatch.setattr(scope_trace, "of", lambda obs: None)
    assert registry.metric("loop_gate_share").read(obs) is None
    other = registry.family(registry.config("olmoe-1b-7b-1layer"))
    assert registry.metric("loop_gate_share").read(
        dict(obs, family=other)) is None


class Counted:
    def __init__(self, **counters):
        self.counters = {name.replace("_", ".", 1): n
                         for name, n in counters.items()}


@pytest.mark.parametrize("counters, share", [
    ({"loop_layer_calls": 24, "loop_layer_traces": 24, "loop_walks": 4},
     100.0),                                            # unrolled
    ({"loop_layer_calls": 24, "loop_layer_traces": 6, "loop_walks": 4},
     25.0),                                             # one walk's bodies
    ({"loop_layer_calls": 24}, 0.0),
    ({"moe_rows_routed": 5}, None),                     # a model walked once
    ({}, None),
])
def test_the_bodies_reader_on_known_counters(counters, share, monkeypatch):
    value = registry.metric("loop_bodies_traced_share").value
    assert value(Counted(**counters)) == share
    # through `read`: nothing in a rehearsal, nothing without a timeline
    read = registry.metric("loop_bodies_traced_share").read
    assert read({"peaks": None}) is None
    monkeypatch.setattr(timeline, "of", lambda obs: None)
    assert read({"peaks": {}}) is None
    monkeypatch.setattr(timeline, "of", lambda obs: Counted(**counters))
    assert read({"peaks": {}}) == share


# -- the recorded trace -------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    """The trace `record_trace_ouro.py` recorded on one v5e chip (three
    steps of two recomputed layers walked three times: hidden 256, two
    heads of 128, three heads over 1,024 rows, batch 2 x 2,048), with the
    family of the sizes it ran."""
    if not os.path.exists(RECORDED):
        pytest.skip("no trace of the ouro step recorded")
    import record_trace_ouro as recorder
    from benchmark.families.ouro import Family

    return (scope_trace.reduce(scope_trace.events(RECORDED),
                               *scope_trace.vocabulary()),
            Family(recorder.CONFIG), recorder)


def test_recorded_trace_has_the_loops_scopes(recorded):
    """The gate ran under its scope, forward and backward; a layer's scopes
    kept their names in every walk; nearly all of the step under a name."""
    found, small, _ = recorded
    scopes = found["scopes"]
    assert scopes["exit_gate"] > 0
    assert {"fwd", "bwd"} <= set(found["in_scope"]["exit_gate"])
    for scope in ("attention/qkv", "attention/out", "ffn/dense", "norm",
                  "head_and_loss", "attention/kernel/fwd_lanes",
                  "attention/kernel/bwd_fused"):
        assert scopes[scope] > 0, scope
    assert "remat_fwd" in found["in_scope"]["ffn/dense"]
    assert found["named_s"] > 0.9 * found["busy_s"]
    # the gate is small beside the heads it weights
    assert scopes["exit_gate"] < scopes["head_and_loss"]


def test_the_readers_on_the_recorded_trace(recorded, monkeypatch):
    found, small, recorder = recorded
    monkeypatch.setattr(scope_trace, "of", lambda obs: found)
    obs = {"family": small, "chips": 1, "trace": {"steps": 3},
           "peaks": registry.peaks("TPU v5 lite"),
           "traffic": {"batch": recorder.BATCH, "seq": recorder.SEQ}}
    share = registry.metric("loop_gate_share").read(obs)
    assert share == pytest.approx(
        100 * found["scopes"]["exit_gate"] / found["busy_s"])
    assert 0 < share < 10
    assert registry.metric("head_loss_share").read(obs) > share


@pytest.mark.parametrize("trace", [
    "tpu1_olmoe.xplane.pb.gz", "tpu1_deepseek_v3.xplane.pb.gz",
    "tpu1_lfm2_moe.xplane.pb.gz", "tpu1_nemotron_h.xplane.pb.gz",
    "tpu1_keye_vl.xplane.pb.gz"])
def test_other_traces_hold_no_gate(trace):
    path = os.path.join(DATA, trace)
    if not os.path.exists(path):
        pytest.skip(f"no {trace} recorded")
    found = scope_trace.reduce(scope_trace.events(path),
                               *scope_trace.vocabulary())
    assert "exit_gate" not in found["scopes"]


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
    # the reference prints how far the system's walks are its own
    assert "ouro reference: losses" in proc.stdout
    assert "normed state after each walk lies" in proc.stdout
    # what this PR counts is in the run's own timeline: the rehearsal's two
    # layers walked three times
    run_dir = os.path.join(registry.ROOT, ".scratch", "benchmark", CELL, CELL)
    with open(os.path.join(run_dir, "timeline.json")) as f:
        doc = json.load(f)
    counters = doc["counters"]
    assert counters["loop.walks"] == 3 and counters["loop.layer_calls"] == 6
    assert counters["loop.layer_traces"] == 6    # the walks are unrolled
    # and the counter's reader finds them there (a rehearsal shows no value,
    # so through `value`)
    obs = {"t_open": 0.0, "window_s": 0.0}
    share = registry.metric("loop_bodies_traced_share").value(
        timeline.Timeline(doc, obs))
    assert share == 100.0 * counters["loop.layer_traces"] / 6
