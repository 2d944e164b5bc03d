"""The `bailing_hybrid` family and its cell: the configuration against the
published `config.json`, the yardstick's counts worked by hand and against
`models/bailing_hybrid.py`'s own, the five new readers on known reductions
and on a trace recorded on the chip, the seeded faults' list, a rehearsal of
the cell, and the rehearsal failing under 8-bit matrices and under a tripled
learning rate."""

import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from benchmark.harness import registry, scope_trace

CONFIG = "ling-3.0-flash-ep64"
CELL = CONFIG + ".resident-16k"
BATCH, SEQ = 1, 16384
E, H, D, C = 2560, 16, 128, 64
ROWS = 19712
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "tpu1_bailing_hybrid.xplane.pb.gz")
COUNTERS = os.path.join(DATA, "timeline", "timeline_bailing_hybrid.json")
SCOPE_METRICS = {"kda_scope_share": "kda", "kda_rule_share": "kda/rule"}
NEW_METRICS = ("kda_scope_share", "kda_rule_share", "kda_rule_roofline_share",
               "kda_glue_share", "route_group_share")
CUT = ["num_hidden_layers", "first_k_dense_replace", "num_attention_heads",
       "num_experts", "vocab_size"]


def published():
    """`inclusionAI/Ling-3.0-flash`'s config.json as the catalog of public
    architectures holds it, where this machine has the catalog."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog of public architectures here")
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    (row,) = [r for r in rows if r["name"] == "Ling-3.0-flash"]
    return row


@pytest.fixture(scope="module")
def family():
    return registry.family(registry.config(CONFIG))


def test_only_depth_heads_experts_and_vocabulary_are_cut():
    config = registry.config(CONFIG)
    entry = [c for c in registry.benchmark()["configs"]
             if c["name"] == CONFIG][0]
    row = published()
    assert entry["reduced"] == config["reduced"] == CUT
    assert sorted(k for k, v in row["config"].items() if config[k] != v) \
        == sorted(CUT)
    assert config["published"] == {k: row["config"][k] for k in CUT}
    assert entry["source"] == row["source_url"]
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["first_layer"]) == (7, 1, 2)
    assert config["num_attention_heads"] in (16, 8)
    assert config["vocab_size"] == ROWS == 154 * 128 >= 157184 / 8
    assert (config["num_experts"], config["experts_held"]["of"]) == (8, 512)
    # no clamp at any layer held: published 0 and 2..7
    held = [0, 2, 3, 4, 5, 6, 7]
    assert not any(config["expert_swiglu_limit_list"][i] for i in held)
    assert not any(config["share_expert_swiglu_limit_list"][i] for i in held)
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    for key in ("equations", "gate", "norms", "mla", "routing", "mtp",
                "swiglu_limits", "initialisation", "training", "remat",
                "loss_chunk_rows", "bias_update_speed"):
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
    for name in ("moe_share", "ssm_scan_share", "selective_scan_share",
                 "collective_share"):
        assert name not in layer
    new = registry.benchmark()["per_layer"][-len(NEW_METRICS):]
    assert [m["name"] for m in new] == list(NEW_METRICS)
    assert all(m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
               and m["source"] == "device_trace" and m["unit"] == "%"
               for m in new)
    assert [m["layer"] for m in new] == [
        "Model", "Model", "Kernels", "Model", "Model"]
    # nothing that was there is changed: the new entries come last
    assert registry.benchmark()["workloads"][-1]["name"] == CELL
    assert registry.benchmark()["configs"][-1]["name"] == CONFIG


# -- the counts ---------------------------------------------------------------

def test_counts_by_hand(family):
    from benchmark.families.bailing_hybrid import KDA, MLA

    assert family.kinds == [KDA] * 4 + [MLA] + [KDA] * 2
    assert [family.published(i) for i in range(7)] == [0, 2, 3, 4, 5, 6, 7]
    kda = 6 * E * H * D + E * H + 3 * H * D * 4
    mla = E * H * 192 + E * 576 + 512 * H * 256 + E * H + H * 128 * E
    assert family.mixer_matrices(KDA) == kda
    assert family.mixer_matrices(MLA) == mla
    # ISSUE 65's arithmetic
    assert round((kda + family.mixer_vectors(KDA)) / 1e6, 1) == 31.5
    assert round((mla + family.mixer_vectors(MLA)) / 1e6, 1) == 16.7
    mixture = E * 512 + 512 + 3 * E * 768 + 8 * 3 * E * 768
    assert round(mixture / 1e6, 1) == 54.4
    assert round(3 * E * 6144 / 1e6, 1) == 47.2
    if H == family.n_head:
        assert round(family.param_count() / 1e6) == 680
        assert round(family.param_count() * 18 / 2 ** 30, 1) == 11.4
    assert family.expected_rows_per_token() == 0.125
    rule = H * (10 * C * D + 20 * C * C + 6 * D * D)
    assert family.rule_flops_per_token() == rule == 16 * 262144
    routed = E * 512 + 3 * E * 768 + 0.125 * 3 * E * 768
    n = ROWS * E + 6 * kda + mla + 3 * E * 6144 + 6 * routed
    assert family.flops_per_token(SEQ) == pytest.approx(
        6 * n + 6 * SEQ * H * (192 + 128) + 3 * 6 * rule)
    cost = family.kda_cost(BATCH, SEQ)
    assert cost["flops"] == 6 * 3 * SEQ * rule
    read = 3 * D * 2 + D * 4 + 4
    assert cost["bytes"] == 6 * SEQ * H * (3 * read + 2 * D * 2)
    attention = family.attention_cost(BATCH, SEQ)
    assert attention["flops"] == 3 * 2 * H * SEQ * SEQ * (192 + 128) / 2
    # the flash kernels' head-major results, not the rule's, the
    # convolution's or the row kernels'
    assert family.is_attention_kernel("custom-call.3_bf16_16_16384_128_")
    assert family.is_attention_kernel("custom-call.9_bf16_16_16384_192_")
    assert not family.is_attention_kernel("custom-call.1_bf16_1_16384_2048_")
    assert not family.is_attention_kernel(
        "custom-call.2_f32_1_16_256_128_128_")
    assert not family.is_attention_kernel("custom-call.4_bf16_2048_2560_")
    assert not family.is_attention_kernel("fusion.1_bf16_16_16384_128_")


def test_counts_are_the_models_own(family):
    import jax

    from ray_tpu.models import bailing_hybrid

    cfg = family.model_config()
    assert (cfg.n_layer, cfg.n_dense_layer, cfg.first_layer, cfg.vocab_size,
            cfg.n_head, cfg.n_head_published, cfg.held) == (
        7, 1, 2, ROWS, family.n_head, 32, (0, 8))
    assert [cfg.kind(i) for i in range(cfg.n_layer)] == family.kinds
    assert family.flops_per_token(SEQ) == pytest.approx(
        bailing_hybrid.count_flops_per_token(cfg, SEQ))
    assert family.rule_flops_per_token() \
        == bailing_hybrid.rule_flops_per_token(cfg)
    shapes = jax.eval_shape(lambda key: bailing_hybrid.init_params(key, cfg),
                            jax.random.PRNGKey(0))
    assert family.param_count() == bailing_hybrid.num_params(shapes)


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


def test_the_glue_and_route_readers_on_a_known_reduction(family,
                                                         monkeypatch):
    obs = observed(family)
    monkeypatch.setattr(scope_trace, "of", lambda obs: {
        "busy_s": 4.0, "scopes": {
            "kda": 2.0, "kda/proj": 0.5, "kda/rule": 1.0,
            "kda/out_proj": 0.125, "kda/conv": 0.25, "ffn/moe/route": 0.1}})
    assert registry.metric("kda_glue_share").read(obs) \
        == pytest.approx(100 * 0.375 / 4.0)
    assert registry.metric("route_group_share").read(obs) \
        == pytest.approx(2.5)
    # a router that picks in one group reads nothing
    kanana = registry.family(registry.config("kanana-2-30b-a3b-ep8-5layer"))
    assert registry.metric("route_group_share").read(
        dict(obs, family=kanana)) is None
    assert registry.metric("kda_glue_share").read(
        dict(obs, family=kanana)) is None
    monkeypatch.setattr(scope_trace, "vocabulary",
                        lambda: (("embed", "ffn/moe/route"), ()))
    assert registry.metric("kda_glue_share").read(obs) is None


def test_the_roofline_reader_on_a_known_reduction(family, monkeypatch):
    obs = observed(family, trace={"steps": 2},
                   peaks={"bf16_flops_per_s": 1e14, "hbm_bytes_per_s": 1e12})
    module = registry.metric("kda_rule_roofline_share")
    cost = family.kda_cost(BATCH, SEQ)
    least, bound = module.least_seconds(obs)
    # at these peaks the rule's operations outweigh its bytes
    assert bound == "compute" and least == cost["flops"] / 1e14
    assert cost["bytes"] / 1e12 < least
    monkeypatch.setattr(scope_trace, "of", lambda obs: {
        "busy_s": 1.0, "scopes": {"kda/rule": 8 * least}})
    assert module.read(obs) == pytest.approx(25.0)
    monkeypatch.setattr(scope_trace, "of", lambda obs: {
        "busy_s": 1.0, "scopes": {"kda": 1.0}})
    assert module.read(obs) is None
    assert module.read(dict(obs, peaks=None)) is None
    assert module.read(dict(obs, trace=None)) is None
    other = registry.family(registry.config("nemotron-3-nano-30b-a3b-"
                                            "ep16-9layer"))
    assert module.read(dict(obs, family=other)) is None
    # at the v5e's own peaks the bytes bound it
    assert module.least_seconds(observed(family))[1] == "memory"


# -- the recorded trace -------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    """The trace `record_trace_bailing_hybrid.py` recorded on one v5e chip
    (three steps of seven recomputed layers, both kinds of mixer and both
    kinds of feed-forward, at hidden 512 and one sequence of 2,048), with
    the family of the sizes it ran."""
    if not os.path.exists(RECORDED):
        pytest.skip("no trace of the bailing_hybrid step recorded")
    import record_trace_bailing_hybrid as recorder
    from benchmark.families.bailing_hybrid import Family

    return (scope_trace.reduce(scope_trace.events(RECORDED),
                               *scope_trace.vocabulary()),
            Family(recorder.CONFIG), recorder)


def test_recorded_trace_has_the_new_scopes(recorded):
    found, small, _ = recorded
    scopes = found["scopes"]
    for scope in ("kda", "kda/proj", "kda/conv", "kda/gate", "kda/rule",
                  "kda/gate_norm", "kda/out_proj", "attention/latent_down",
                  "attention/latent_up", "attention/gate", "attention/out",
                  "attention/kernel/fwd_rows", "ffn/dense", "ffn/moe/route",
                  "ffn/moe/experts", "ffn/moe/shared", "norm",
                  "head_and_loss"):
        assert scopes[scope] > 0, scope
    for scope in ("kda/rule", "kda/gate", "kda/gate_norm"):
        assert {"fwd", "remat_fwd", "bwd"} <= set(found["in_scope"][scope])
    assert scopes["kda"] > scopes["kda/rule"]
    assert found["named_s"] > 0.85 * found["busy_s"]


def test_the_readers_on_the_recorded_trace(recorded, monkeypatch):
    found, small, recorder = recorded
    monkeypatch.setattr(scope_trace, "of", lambda obs: found)
    obs = {"family": small, "chips": 1, "trace": {"steps": 3},
           "config": small.config, "peaks": registry.peaks("TPU v5 lite"),
           "traffic": {"batch": recorder.BATCH, "seq": recorder.SEQ}}
    shares = {name: registry.metric(name).read(obs) for name in NEW_METRICS}
    for name, scope in SCOPE_METRICS.items():
        assert shares[name] == pytest.approx(
            100 * found["scopes"][scope] / found["busy_s"])
    assert all(0 < share < 100 for share in shares.values()), shares
    assert shares["kda_scope_share"] > shares["kda_rule_share"] \
        + shares["kda_glue_share"]
    with open(COUNTERS) as f:
        counters = json.load(f)["counters"]
    # a trace a shape of layer: dense KDA, routed KDA, routed MLA
    assert counters["kda.layers"] == counters["kda.rule_kernel"] == 2
    assert counters["kda.bwd_kernel"] == 2
    assert counters.get("kda.rule_plain", 0) == 0
    assert counters["moe.route_groups"] == 2
    assert counters["attention.gated"] == 1


@pytest.mark.parametrize("trace", [
    "tpu1_olmoe.xplane.pb.gz", "tpu1_nemotron_h.xplane.pb.gz",
    "tpu1_phi4flash.xplane.pb.gz"])
def test_other_traces_hold_none_of_the_new_scopes(trace):
    path = os.path.join(DATA, trace)
    if not os.path.exists(path):
        pytest.skip(f"no {trace} recorded")
    found = scope_trace.reduce(scope_trace.events(path),
                               *scope_trace.vocabulary())
    for scope in ("kda", "kda/rule", "kda/gate_norm"):
        assert scope not in found["scopes"]


# -- the seeded faults --------------------------------------------------------

def test_the_faults_are_the_issues():
    from bailing_hybrid_faults import FAULTS, Faulty

    assert sorted(FAULTS) == [
        "decay_a_head", "eight_bit_matrices", "group_by_largest",
        "no_beta", "no_carry", "no_correction", "no_gate_bound",
        "no_head_norm", "no_l2_norm", "no_latent_gate", "no_output_gate",
        "no_query_scale", "no_routed_scale", "no_shared_expert", "one_group",
        "taps_ahead"]
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
    assert "bailing_hybrid reference: losses" in proc.stdout
    assert "stream after each published layer" in proc.stdout
    # what this PR counts is in the run's own timeline: the rehearsal's
    # heads are as wide as the published ones, so the interpreted kernels
    # run; a trace a shape of layer
    run_dir = os.path.join(registry.ROOT, ".scratch", "benchmark", CELL, CELL)
    with open(os.path.join(run_dir, "timeline.json")) as f:
        counters = json.load(f)["counters"]
    assert counters["kda.layers"] == counters["kda.rule_kernel"] == 2
    assert counters["kda.bwd_kernel"] == 2
    assert counters.get("kda.rule_plain", 0) == 0
    assert counters["moe.route_groups"] == 2
    assert counters["attention.gated"] == 1


WRONG_RATE = '''\
    from benchmark.families import bailing_hybrid
    from benchmark.reference.bailing_hybrid import adamw
    from ray_tpu.models.bailing_hybrid import trained_by


    class Family(bailing_hybrid.Family):
        """Three times the learning rate the configuration states."""

        def optimizer(self):
            settings = dict(self.config["optimizer"])
            settings["learning_rate"] *= 3
            return trained_by(adamw(settings))
    '''
LOW_PRECISION = '''\
    import sys

    sys.path.insert(0, "{tests}")
    from bailing_hybrid_faults import FAULTS

    Family = FAULTS["eight_bit_matrices"]
    '''
CATCHES = {"wrong_rate": (WRONG_RATE, "NOT CORRECT: loss at step"),
           "low_precision": (LOW_PRECISION, "NOT CORRECT")}


@pytest.mark.parametrize("fault", sorted(CATCHES))
def test_the_reference_check_catches(tmp_path, fault):
    """A family that departs from what the configuration states (a new
    file in a copy of the benchmark) runs, and its run is not `correct`:
    three times the learning rate by the losses; the matrices through
    float8_e4m3fn, the nearest precision below the stated bfloat16, by one
    of the two limits."""
    source, said = CATCHES[fault]
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(registry.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(registry.ROOT, "benchmark"),
                    root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "benchmark" / "families" / f"bailing_{fault}.py").write_text(
        textwrap.dedent(source).format(
            tests=os.path.dirname(os.path.abspath(__file__))))
    config = registry.load_json("benchmark", "configs", f"{CONFIG}.json")
    config.update(name=f"ling-{fault}", family=f"bailing_{fault}")
    (root / "benchmark" / "configs" / f"ling-{fault}.json").write_text(
        json.dumps(config))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": f"ling-{fault}", "source": "test", "reduced": [],
        "why": "test", "file": f"benchmark/configs/ling-{fault}.json"})
    bench["workloads"].append({
        "name": f"ling-{fault}.resident-16k", "config": f"ling-{fault}",
        "traffic": "resident-16k", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    proc = run_cell("--workload", f"ling-{fault}.resident-16k", "--seed",
                    "5", "--seconds", "1", "--trace", "0", root=str(root))
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is False, proc.stdout[-3000:]
    assert said in proc.stdout
