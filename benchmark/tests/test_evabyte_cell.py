"""The `evabyte` family and its cell: the configuration against the published
`config.json`, the yardstick's counts worked by hand and against
`models/evabyte.py`'s own, the five new readers on known reductions and on a
trace recorded on the chip, the seeded faults' list, a rehearsal of the cell,
and the rehearsal failing under 8-bit matrices."""

import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from benchmark.harness import registry, scope_trace, timeline

CONFIG = "evabyte-6.5b-4layer"
CELL = CONFIG + ".resident-16k"
BATCH, SEQ = 1, 16384
E, H, D, F, C, W, P, V = 4096, 16, 128, 11008, 16, 2048, 8, 320
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "tpu1_evabyte.xplane.pb.gz")
COUNTERS = os.path.join(DATA, "timeline", "timeline_evabyte.json")
SCOPE_METRICS = {"eva_scope_share": "eva", "eva_summary_share": "eva/summary"}
NEW_METRICS = ("eva_scope_share", "eva_summary_share",
               "eva_summary_roofline_share", "eva_attn_roofline_share",
               "eva_pairs_attended_share")
CUT = ["num_hidden_layers", "num_attention_heads", "num_key_value_heads"]


def published():
    """`EvaByte/EvaByte`'s config.json as the catalog of public
    architectures holds it, where this machine has the catalog."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog of public architectures here")
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    (row,) = [r for r in rows if r["name"] == "EvaByte"]
    return row


@pytest.fixture(scope="module")
def family():
    return registry.family(registry.config(CONFIG))


def test_only_depth_and_the_heads_held_are_cut():
    config = registry.config(CONFIG)
    entry = [c for c in registry.benchmark()["configs"]
             if c["name"] == CONFIG][0]
    row = published()
    assert entry["reduced"] == config["reduced"] == CUT
    assert sorted(k for k, v in row["config"].items() if config[k] != v) \
        == sorted(CUT)
    assert config["published"] == {k: row["config"][k] for k in CUT}
    assert entry["source"] == row["source_url"]
    assert (config["num_hidden_layers"], config["first_layer"]) == (4, 14)
    assert config["num_attention_heads"] == config["num_key_value_heads"]
    assert config["num_attention_heads"] in (16, 8)
    assert config["head_dim"] * 32 == config["hidden_size"]
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    for key in ("equations", "pooling_score", "chunks_and_windows", "rope",
                "prediction_heads", "init", "optimizer", "head_dim"):
        assert config["assumed"][key], key
    for key in ("loss_tolerance", "loss_tolerance_reason",
                "state_error_max"):
        assert config["reference"][key]
    assert config["reduced_how"] and config["deployment"] \
        and config["heads_rule"]
    assert config["remat"] is True
    assert (config["param_dtype"], config["compute_dtype"]) == (
        "float32", "bfloat16")


def test_the_cell_is_what_the_issue_names():
    cell = registry.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "resident-16k", 1)
    assert len(cell["why"]) <= 200
    end = [m["name"] for m in registry.metrics_of(CELL, "end_to_end")]
    assert end == ["tokens_per_s", "setup_s"]
    layer = [m["name"] for m in registry.metrics_of(CELL, "per_layer")]
    for name in NEW_METRICS + ("attn_roofline_share", "attn_kernel_share",
                               "mfu", "hbm_peak_gib", "head_loss_share",
                               "norm_share", "fwd_share", "bwd_share",
                               "attention_scope_share", "ffn_scope_share",
                               "scope_named_share"):
        assert name in layer
    for name in ("moe_share", "ssm_scan_share", "kda_scope_share",
                 "window_kernel_share", "collective_share"):
        assert name not in layer
    new = registry.benchmark()["per_layer"][-len(NEW_METRICS):]
    assert [m["name"] for m in new] == list(NEW_METRICS)
    assert all(m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
               and m["unit"] == "%" for m in new)
    assert [m["source"] for m in new] == ["device_trace"] * 4 \
        + ["program_counter"]
    assert [m["layer"] for m in new] == [
        "Model", "Model", "Kernels", "Kernels", "Kernels"]
    # nothing that was there is changed: the new entries come last
    assert registry.benchmark()["workloads"][-1]["name"] == CELL
    assert registry.benchmark()["configs"][-1]["name"] == CONFIG
    assert len(registry.benchmark()["configs"]) == 14
    assert len(registry.benchmark()["workloads"]) == 15


# -- the counts ---------------------------------------------------------------

def test_counts_by_hand(family):
    assert [family.published(i) for i in range(4)] == [14, 15, 16, 17]
    assert family.mixer_matrices() == 4 * E * H * D
    # ISSUE 69's arithmetic
    layer = 4 * E * H * D + 3 * E * F
    assert round(layer / 1e6, 1) == 168.8
    if H == family.n_head:
        assert round(family.param_count() / 1e6, 1) == 687.1
        assert round(family.param_count() * 16 / 2 ** 30, 1) == 10.2
        assert round(family.param_count() * 18 / 2 ** 30, 1) == 11.5
    local = 8 * W * (W + 1) // 2
    remote = W * (W // C) * (8 * 7 // 2)
    assert (family.local_pairs(SEQ), family.remote_pairs(SEQ)) \
        == (local, remote)
    assert (local / SEQ, remote / SEQ) == (1024.5, 448.0)
    n = 4 * layer + P * V * E
    pool = H * 6 * D
    assert family.pool_flops_per_token() == pool
    assert family.flops_per_token(SEQ) == pytest.approx(
        6 * n + 4 * (6 * 1472.5 * H * 2 * D + 3 * pool))
    heads = SEQ * H
    attention = family.attention_cost(BATCH, SEQ)
    assert attention["flops"] == 4 * H * local * 12 * D
    assert attention["bytes"] == 4 * (heads * 11 * D * 2 + 2 * heads * 4)
    remote_cost = family.eva_remote_cost(BATCH, SEQ)
    assert remote_cost["flops"] == 4 * H * remote * 12 * D
    summary = family.eva_summary_cost(BATCH, SEQ)
    assert summary["flops"] == 4 * 3 * SEQ * pool
    summaries = heads // C * D
    assert summary["bytes"] == 4 * (6 * heads * D * 2 + summaries * 12)
    # the flash kernels' head-major results, not the remote pair's nor the
    # pooling's rows
    assert family.is_attention_kernel("custom-call.3_bf16_16_16384_128_")
    assert not family.is_attention_kernel("custom-call.1_bf16_1_16384_2048_")
    assert not family.is_attention_kernel("custom-call.2_bf16_1_1024_2048_")
    assert not family.is_attention_kernel("fusion.1_bf16_16_16384_128_")


def test_counts_are_the_models_own(family):
    import jax

    from ray_tpu.models import evabyte

    cfg = family.model_config()
    assert (cfg.n_layer, cfg.first_layer, cfg.vocab_size, cfg.n_head,
            cfg.n_head_published, cfg.window, cfg.chunk, cfg.n_pred_heads) \
        == (4, 14, V, family.n_head, 32, W, C, P)
    assert family.flops_per_token(SEQ) == pytest.approx(
        evabyte.count_flops_per_token(cfg, SEQ))
    shapes = jax.eval_shape(lambda key: evabyte.init_params(key, cfg),
                            jax.random.PRNGKey(0))
    assert family.param_count() == evabyte.num_params(shapes)


# -- the readers --------------------------------------------------------------

def observed(family, **more):
    return {"family": family, "chips": 1, "trace": {"steps": 1},
            "config": family.config, "peaks": registry.peaks("TPU v5 lite"),
            "traffic": registry.traffic("resident-16k"), **more}


@pytest.mark.parametrize("name,scope", sorted(SCOPE_METRICS.items()))
def test_scope_readers_on_a_known_reduction(family, monkeypatch, name, scope):
    obs = observed(family)
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


@pytest.mark.parametrize("name,cost,scopes", [
    ("eva_summary_roofline_share", "eva_summary_cost", ["eva/summary"]),
    ("eva_attn_roofline_share", "eva_remote_cost",
     ["eva/remote", "eva/merge"])])
def test_the_roofline_readers_on_a_known_reduction(family, monkeypatch, name,
                                                   cost, scopes):
    obs = observed(family, trace={"steps": 2})
    module = registry.metric(name)
    cost = getattr(family, cost)(BATCH, SEQ)
    peaks = obs["peaks"]
    least = max(cost["flops"] / peaks["bf16_flops_per_s"],
                cost["bytes"] / peaks["hbm_bytes_per_s"])
    # the pooling is a pass over its bytes; the remote half's products
    # outweigh its bytes
    bound = "memory" if name == "eva_summary_roofline_share" else "compute"
    assert registry.metric("eva_summary_roofline_share").least_seconds(
        obs, *(() if bound == "memory" else ("eva_remote_cost",))) \
        == (least, bound)
    took = {scope: 4 * least / len(scopes) for scope in scopes}
    monkeypatch.setattr(scope_trace, "of", lambda obs: {
        "busy_s": 1.0, "scopes": dict(took, eva=1.0)})
    assert module.read(obs) == pytest.approx(50.0)
    monkeypatch.setattr(scope_trace, "of", lambda obs: {
        "busy_s": 1.0, "scopes": {"eva": 1.0}})
    assert module.read(obs) is None
    assert module.read(dict(obs, peaks=None)) is None
    assert module.read(dict(obs, trace=None)) is None
    other = registry.family(registry.config("ling-3.0-flash-ep64"))
    assert module.read(dict(obs, family=other)) is None


def test_the_pairs_reader_on_known_counters(monkeypatch):
    module = registry.metric("eva_pairs_attended_share")

    class Known:
        counters = {"eva.pairs_attended": 4 * 24125440,
                    "eva.pairs_visited": 4 * 28311552}

    assert module.value(Known) == pytest.approx(85.2136, abs=1e-3)
    Known.counters = {"attention.pairs_visited": 5}     # the parent's
    assert module.value(Known) is None
    # a rehearsal reads nothing
    assert module.read({"peaks": None}) is None


# -- the recorded trace -------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    """The trace `record_trace_evabyte.py` recorded on one v5e chip (three
    steps of two recomputed layers at hidden 512, four windows of 512 in one
    sequence of 2,048), with the family of the sizes it ran."""
    if not os.path.exists(RECORDED):
        pytest.skip("no trace of the evabyte step recorded")
    import record_trace_evabyte as recorder
    from benchmark.families.evabyte import Family

    return (scope_trace.reduce(scope_trace.events(RECORDED),
                               *scope_trace.vocabulary()),
            Family(recorder.CONFIG), recorder)


def test_recorded_trace_has_the_new_scopes(recorded):
    found, small, _ = recorded
    scopes = found["scopes"]
    for scope in ("eva", "eva/qkv", "eva/summary", "eva/local",
                  "eva/local/fwd_rows_blocks", "eva/local/bwd_fused_blocks",
                  "eva/remote", "eva/merge", "eva/out", "ffn/dense", "norm",
                  "head_and_loss"):
        assert scopes[scope] > 0, scope
    for scope in ("eva/summary", "eva/remote"):
        assert {"fwd", "bwd"} <= set(found["in_scope"][scope])
    assert scopes["eva"] > scopes["eva/local"] + scopes["eva/remote"]
    assert "attention" not in scopes
    assert found["named_s"] > 0.8 * found["busy_s"]


def test_the_readers_on_the_recorded_trace(recorded, monkeypatch):
    found, small, recorder = recorded
    monkeypatch.setattr(scope_trace, "of", lambda obs: found)
    obs = {"family": small, "chips": 1, "trace": {"steps": 3},
           "config": small.config, "peaks": registry.peaks("TPU v5 lite"),
           "traffic": {"batch": recorder.BATCH, "seq": recorder.SEQ}}
    shares = {name: registry.metric(name).read(obs)
              for name in NEW_METRICS[:4]}
    for name, scope in SCOPE_METRICS.items():
        assert shares[name] == pytest.approx(
            100 * found["scopes"][scope] / found["busy_s"])
    assert all(0 < share < 100 for share in shares.values()), shares
    assert shares["eva_scope_share"] > shares["eva_summary_share"]
    with open(COUNTERS) as f:
        counters = json.load(f)["counters"]
    # a trace of the one shape of layer
    assert counters["eva.layers"] == 1 and counters["eva.kernels"] == 3
    assert counters.get("eva.fallbacks", 0) == 0
    assert counters["eva.summaries"] == recorder.SEQ // 16
    share = registry.metric("eva_pairs_attended_share").value(
        type("T", (), {"counters": counters}))
    assert 50 < share <= 100


@pytest.mark.parametrize("trace", [
    "tpu1_olmoe.xplane.pb.gz", "tpu1_bailing_hybrid.xplane.pb.gz",
    "tpu1_mellum.xplane.pb.gz"])
def test_other_traces_hold_none_of_the_new_scopes(trace):
    path = os.path.join(DATA, trace)
    if not os.path.exists(path):
        pytest.skip(f"no {trace} recorded")
    found = scope_trace.reduce(scope_trace.events(path),
                               *scope_trace.vocabulary())
    for scope in ("eva", "eva/summary", "eva/remote"):
        assert scope not in found["scopes"]


# -- the seeded faults --------------------------------------------------------

def test_the_faults_are_the_issues():
    from evabyte_faults import FAULTS, Faulty

    assert sorted(FAULTS) == [
        "bf16_stream", "eight_bit_matrices", "gain_alone", "halves_apart",
        "later_summaries", "local_full_causal", "local_sliding", "no_mu",
        "no_summary_gradient", "own_window_summaries",
        "target_a_byte_early", "uniform_pooling"]
    assert all(issubclass(f, Faulty) and f.__doc__ for f in FAULTS.values())


# -- the rehearsal ------------------------------------------------------------

def run_cell(*args, root=registry.ROOT):
    cmd = [sys.executable, os.path.join(root, "benchmark", "run.py"),
           *args, "--rehearse"]
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
    # the reference prints how far the system's streams are its own
    assert "evabyte reference: losses" in proc.stdout
    assert "stream after each published layer" in proc.stdout
    # what this PR counts is in the run's own timeline: the rehearsal's four
    # windows of four chunks are a shape the kernels decline (the traffic's
    # rehearsal is 128 positions), so the plain form runs, once a traced
    # layer, under its warning
    run_dir = os.path.join(registry.ROOT, ".scratch", "benchmark", CELL, CELL)
    with open(os.path.join(run_dir, "timeline.json")) as f:
        counters = json.load(f)["counters"]
    assert counters["eva.layers"] == counters["eva.fallbacks"] == 1
    assert counters.get("eva.kernels", 0) == 0
    assert "EvaFallbackWarning" in proc.stdout + proc.stderr


LOW_PRECISION = '''\
    import sys

    sys.path.insert(0, "{tests}")
    from evabyte_faults import FAULTS

    Family = FAULTS["eight_bit_matrices"]
    '''


def test_the_reference_check_catches_low_precision(tmp_path):
    """A family that departs from what the configuration states (a new file
    in a copy of the benchmark) runs, and its run is not `correct`: the
    matrices through float8_e4m3fn, the nearest precision below the stated
    bfloat16, by one of the two limits."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(registry.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(registry.ROOT, "benchmark"),
                    root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "benchmark" / "families" / "evabyte_low.py").write_text(
        textwrap.dedent(LOW_PRECISION).format(
            tests=os.path.dirname(os.path.abspath(__file__))))
    config = registry.load_json("benchmark", "configs", f"{CONFIG}.json")
    config.update(name="evabyte-low", family="evabyte_low")
    (root / "benchmark" / "configs" / "evabyte-low.json").write_text(
        json.dumps(config))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "evabyte-low", "source": "test", "reduced": [],
        "why": "test", "file": "benchmark/configs/evabyte-low.json"})
    bench["workloads"].append({
        "name": "evabyte-low.resident-16k", "config": "evabyte-low",
        "traffic": "resident-16k", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    proc = run_cell("--workload", "evabyte-low.resident-16k", "--seed",
                    "5", "--seconds", "1", "--trace", "0", root=str(root))
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is False, proc.stdout[-3000:]
    assert "NOT CORRECT" in proc.stdout
