"""The `keye_vl` family and its cell: the configuration against the
published `config.json`, the yardstick's counts worked by hand, the
predicates that tell a trace's operations apart, the new readers on a trace
recorded on the chip, a rehearsal of the cell, and seeded faults with what
the three losses see of each."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import registry, scope_trace

CONFIG = "keye-vl-2.0-30b-a3b-ep8"
CELL = CONFIG + ".resident-8k"
BATCH, SEQ = 2, 8192
TOKENS = BATCH * SEQ
E, H, HKV, D, ROWS = 2048, 32, 4, 128, 19072
J, DI, TOPK = 16, 64, 2048
SELECTED, CAUSAL = 14_681_088, 33_558_528       # pairs a sequence
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "tpu1_keye_vl.xplane.pb.gz")
NEW_METRICS = ("indexer_scope_share", "indexer_select_share",
               "indexer_scores_roofline_share", "indexer_loss_share")

# `Kwai-Keye/Keye-VL-2.0-30B-A3B`'s config.json, as the catalog of public
# architectures holds it
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
CUT = ["num_hidden_layers", "num_experts", "vocab_size"]


@pytest.fixture(scope="module")
def family():
    return registry.family(registry.config(CONFIG))


def test_only_depth_experts_held_and_vocabulary_are_cut():
    config = registry.config(CONFIG)
    entry = [c for c in registry.benchmark()["configs"]
             if c["name"] == CONFIG][0]
    assert entry["reduced"] == config["reduced"] == CUT
    assert sorted(k for k, v in PUBLISHED.items() if config[k] != v) \
        == sorted(CUT)
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 16, 18992)
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert config["num_experts"] * 8 == PUBLISHED["num_experts"]
    assert {k: config["published"][k] for k in CUT} \
        == {k: PUBLISHED[k] for k in CUT}
    # the router keeps its published width and the experts held are said
    assert config["experts_held"] == {
        "first": 0, "of": 128, "why": config["experts_held"]["why"]}
    assert config["padded_vocab_size"] == ROWS == -(-18992 // 128) * 128
    assert config["name"] == entry["name"] and config["deployment"]
    assert entry["source"] == ("https://huggingface.co/Kwai-Keye/"
                               "Keye-VL-2.0-30B-A3B/blob/main/config.json")
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    for key in ("language_model_only", "mrope", "indexer_equations",
                "indexer_rope", "indexer_hadamard", "chunk_sizes", "ties",
                "intermediate_size", "auxiliary_loss", "initialisation",
                "training", "remat", "loss_chunk_rows"):
        assert config["assumed"][key]
    for key in ("loss_tolerance", "loss_tolerance_reason", "what",
                "selection_agreement_min", "attention_error_max"):
        assert config["reference"][key]


def test_the_cell_is_what_the_issue_names():
    cell = registry.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "resident-8k", 1)
    assert len(cell["why"]) <= 200
    end = [m["name"] for m in registry.metrics_of(CELL, "end_to_end")]
    assert end == ["tokens_per_s", "setup_s"]
    layer = [m["name"] for m in registry.metrics_of(CELL, "per_layer")]
    for name in NEW_METRICS + ("attn_roofline_share", "attn_kernel_share",
                               "mfu", "hbm_peak_gib", "step_device_ms",
                               "attention_scope_share", "ffn_scope_share"):
        assert name in layer
    for name in ("moe_share", "moe_scope_share", "remat_fwd_share",
                 "moe_rows_buffered_share", "collective_share"):
        assert name not in layer
    new = [m for m in registry.benchmark()["per_layer"]
           if m["name"] in NEW_METRICS]
    assert [m["name"] for m in new] == list(NEW_METRICS)
    # the last four entries, each listing the new cell alone
    assert registry.benchmark()["per_layer"][-4:] == new
    assert all(m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
               for m in new)
    assert [m["layer"] for m in new] == ["Model", "Model", "Kernels",
                                         "Model"]
    # each is read from the device's trace: none is a constant of the
    # configuration
    assert [m["source"] for m in new] == ["device_trace"] * 4
    assert registry.benchmark()["workloads"][-1] == cell
    assert len(registry.benchmark()["workloads"]) == 8
    assert len(registry.benchmark()["configs"]) == 7


def test_counts_by_hand(family):
    attn = 2 * E * H * D + 2 * E * HKV * D                 # 18.87 M
    indexer = E * J * DI + E * DI + E * J                  # 2.26 M
    assert family.attention_params() == attn == 18_874_368
    assert family.indexer_params() == indexer == 2_260_992
    expert = 3 * E * 768
    layer = 2 * E + attn + 2 * D + indexer + 2 * DI + E * 128 + 16 * expert
    assert family.param_count() == 2 * ROWS * E + E + 5 * layer
    # ISSUE 47's arithmetic: 96.90 M a layer, 562.6 M, 9.0 GB at 16 bytes
    assert round(layer / 1e6, 2) == 96.9
    assert round(family.param_count() / 1e6, 1) == 562.6
    assert round(family.param_count() * 16 / 1e9, 1) == 9.0
    assert family.expected_rows_per_token() == 1.0
    assert family.selected_pairs(SEQ) == SELECTED \
        == 2048 * 2049 // 2 + 6144 * 2048
    assert family.causal_pairs(SEQ) == CAUSAL
    n = ROWS * E + 5 * (attn + E * 128 + 1.0 * expert)
    assert family.multiplying_params_per_token() == n
    s, c = SELECTED / SEQ, CAUSAL / SEQ
    pairs = 6 * s * H * 2 * D + 2 * c * J * DI + 4 * s * J * DI \
        + 2 * s * H * D
    assert family.flops_per_token(SEQ) == 6 * n + 5 * (4 * indexer + pairs)
    assert round(family.flops_per_token(SEQ) / 1e9, 2) == 1.59
    # the pair products are 37 % of it
    assert round(100 * 5 * pairs / family.flops_per_token(SEQ)) == 37
    # twice the expected load of 16 of 128 at 8 a token: 2 T rows
    assert family.buffered_rows(TOKENS) == 2 * TOKENS


def test_counts_are_the_programs_own(family):
    import jax

    from ray_tpu.models import keye_vl as model
    from ray_tpu.ops.moe import buffer_rows

    cfg = family.model_config()
    assert family.flops_per_token(SEQ) == model.count_flops_per_token(
        cfg, SEQ)
    shapes = jax.eval_shape(lambda key: model.init_params(key, cfg),
                            jax.random.PRNGKey(0))
    assert model.num_params(shapes) == family.param_count()
    assert cfg.held == (0, 16) and cfg.n_experts == 128 and cfg.remat
    assert (cfg.index_heads, cfg.index_dim, cfg.index_top_k,
            cfg.index_block) == (J, DI, TOPK, 512)
    assert family.buffered_rows(TOKENS) == buffer_rows(TOKENS * 8, 16, 128)
    assert family.selected_pairs(SEQ) == model.selected_pairs(SEQ, TOPK)


def test_attention_cost_is_of_the_selected_pairs(family):
    # five layers; six products 128 deep over the SELECTED pairs of both
    # sequences, 2 D operations a pair and head; q, o, do, dq at 32 heads
    # and k, v (twice), dk, dv at 4, in bf16; two of B H S in f32; the
    # mask a byte a pair, read forward and backward
    flops = 5 * 6 * 2 * BATCH * SELECTED * H * D
    nbytes = 5 * (6 * BATCH * SEQ * D * (H + HKV) * 2
                  + 2 * BATCH * H * SEQ * 4 + 2 * BATCH * SEQ * SEQ)
    assert family.attention_cost(BATCH, SEQ) == {"flops": flops,
                                                 "bytes": nbytes}
    # 43.75 % of what a causal kernel's count would be, 21.9 % of the
    # square's: a kernel that visits every causal tile reads that share
    # of its roofline at best
    assert round(100 * SELECTED / CAUSAL, 2) == 43.75
    peaks = registry.peaks("TPU v5 lite")
    seconds, bound = registry.metric("attn_roofline_share").least_seconds({
        "family": family, "chips": 1, "peaks": peaks,
        "traffic": registry.traffic("resident-8k")})
    assert bound == "compute"
    assert seconds == pytest.approx(flops / 197e12, rel=1e-3)


def test_index_scores_cost_by_hand(family):
    cost = family.index_scores_cost(BATCH, SEQ)
    assert cost["flops"] == 5 * 2 * J * DI * BATCH * (CAUSAL + 2 * SELECTED)
    assert cost["bytes"] == 5 * (4 * BATCH * (CAUSAL + SELECTED)
                                 + 2 * TOKENS * (J * DI + DI + J) * 2)
    seconds, bound = registry.metric("indexer_scores_roofline_share") \
        .least_seconds({"family": family, "chips": 1,
                        "peaks": registry.peaks("TPU v5 lite"),
                        "traffic": registry.traffic("resident-8k")})
    assert bound == "compute"
    assert seconds == pytest.approx(cost["flops"] / 197e12, rel=1e-3)
    assert round(1e3 * seconds, 2) == 6.54         # ms a step, five layers


# names as `harness/xplane.py:op_name` gives them for the full-size step
ATTENTION = [
    "tpu_custom_call__bf16_64_8192_128___f32_64_8192_1__",    # forward
    "tpu_custom_call__bf16_64_8192_128___f32_64_8192_128___f32_64_8192_128__",
]
MOE_MATMULS = [
    "tpu_custom_call_bf16_32768_768_", "tpu_custom_call_bf16_32768_2048_",
    "tpu_custom_call_bf16_16_2048_768_", "tpu_custom_call_bf16_16_768_2048_",
    "tpu_custom_call__s32_17___s32_39___s32_39___s32_1__",    # group layout
]
NEITHER = [
    "fusion:kOutput_f32_16_512_8192_",          # a block's index products
    "fusion:kLoop_s8_2_8192_8192_", "fusion:kLoop_f32_2_8192_8192_",
    "fusion:kOutput_bf16_2_8192_4096_", "copy_s8_2_8_8_1024_1024_",
]


def test_operations_are_told_apart_by_shape(family):
    for name in ATTENTION:
        assert family.is_attention_kernel(name), name
        assert not family.is_moe_matmul(name), name
    for name in MOE_MATMULS:
        assert family.is_moe_matmul(name), name
        assert not family.is_attention_kernel(name), name
    for name in NEITHER:
        assert not family.is_attention_kernel(name), name
        assert not family.is_moe_matmul(name), name


def test_readers_on_a_known_reduction(family, monkeypatch):
    obs = {"family": family, "chips": 1, "trace": {"steps": 4},
           "peaks": registry.peaks("TPU v5 lite"),
           "traffic": registry.traffic("resident-8k")}
    monkeypatch.setattr(scope_trace, "of", lambda obs: {
        "busy_s": 2.0, "scopes": {
            "attention": 1.5, "attention/indexer": 1.0,
            "attention/indexer/scores": 0.5,
            "attention/indexer/select": 0.25,
            "attention/indexer/loss": 0.125}})
    assert registry.metric("indexer_scope_share").read(obs) == 50.0
    assert registry.metric("indexer_select_share").read(obs) == 12.5
    assert registry.metric("indexer_loss_share").read(obs) == 6.25
    least = family.index_scores_cost(BATCH, SEQ)["flops"] / 197e12
    assert registry.metric("indexer_scores_roofline_share").read(obs) \
        == pytest.approx(100 * 4 * least / 0.5, rel=1e-3)
    # a program whose trace has no such scope (the parent's): 0, or nothing
    monkeypatch.setattr(scope_trace, "of", lambda obs: {
        "busy_s": 2.0, "scopes": {"attention": 1.5}})
    assert registry.metric("indexer_scope_share").read(obs) == 0.0
    assert registry.metric("indexer_loss_share").read(obs) == 0.0
    assert registry.metric("indexer_scores_roofline_share").read(obs) is None
    monkeypatch.setattr(scope_trace, "of", lambda obs: None)
    for name in NEW_METRICS:
        assert registry.metric(name).read(obs) is None


def test_no_new_metric_is_a_constant_of_the_configuration():
    """Every new reader reads the device's trace through
    `harness/scope_trace.py`; the pairs a step selects (43.75 % of the
    causal ones here, whatever the program does) are counters of the run
    and no metric."""
    for name in NEW_METRICS:
        with open(os.path.join(registry.BENCH_DIR, "metrics",
                               f"{name}.py")) as f:
            source = f.read()
        assert "scope_trace" in source and "timeline" not in source, name
    assert not os.path.exists(os.path.join(
        registry.BENCH_DIR, "metrics", "attn_selected_pairs_share.py"))
    assert round(100 * SELECTED / CAUSAL, 2) == 43.75


def test_readers_find_nothing_for_other_families_or_without_a_trace(family):
    """No traced run, a rehearsal, a family whose attention selects
    nothing, a trace older than the run: None, never an exception."""
    base = {"family": family, "chips": 1, "t_fit": 0.0,
            "config": registry.config(CONFIG),
            "traffic": registry.traffic("resident-8k")}
    peaks = registry.peaks("TPU v5 lite")
    others = [registry.family(registry.config(name)) for name in (
        "gpt2-medium", "olmoe-1b-7b-1layer", "kanana-2-30b-a3b-ep8-5layer",
        "lfm2-24b-a2b-ep8-5layer")]
    cases = [dict(base, peaks=peaks),                             # no trace
             dict(base, peaks=None, trace={"steps": 1}),          # rehearsal
             dict(base, peaks=peaks, trace={"steps": 1}, t_fit=4e9)]
    cases += [dict(base, peaks=peaks, trace={"steps": 1}, family=other)
              for other in others]
    for obs in cases:
        for name in NEW_METRICS:
            assert registry.metric(name).read(obs) is None
    for other in others:
        assert not hasattr(other, "index_scores_cost")


# -- the recorded trace -------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    """The trace `record_trace_keye_vl.py` recorded on one v5e chip (three
    steps of two recomputed layers: hidden 256, eight query heads on two
    key/value heads of 128, an indexer of four heads of 64, 512 keys a
    query, sixteen experts of which four are held, batch 2 x 2,048), with
    the family of the sizes it ran."""
    if not os.path.exists(RECORDED):
        pytest.skip("no trace of the keye_vl step recorded")
    import record_trace_keye_vl as recorder
    from benchmark.families.keye_vl import Family

    return (scope_trace.reduce(scope_trace.events(RECORDED),
                               *scope_trace.vocabulary()),
            Family(recorder.CONFIG), recorder)


def test_recorded_trace_has_the_indexers_scopes(recorded):
    """Every part of the indexer ran under its scope, forward, replayed and
    backward; the masked kernels under the two head-major forms; and
    nearly all of the step under a name."""
    found, small, _ = recorded
    scopes = found["scopes"]
    for part in ("proj", "scores", "select", "loss"):
        assert scopes[f"attention/indexer/{part}"] > 0, part
    assert scopes["attention/indexer"] == pytest.approx(sum(
        scopes[f"attention/indexer/{part}"]
        for part in ("proj", "scores", "select", "loss")), rel=1e-6)
    assert scopes["attention/kernel/fwd_rows"] > 0
    assert scopes["attention/kernel/bwd_fused"] > 0
    for scope in ("attention/kernel/fwd_lanes",
                  "attention/kernel/bwd_fused_lanes"):
        assert scope not in scopes
    phases = found["in_scope"]["attention/indexer/scores"]
    assert {"fwd", "remat_fwd", "bwd"} <= set(phases)
    # the selection and the loss's target have no backward of their own
    assert "bwd" not in found["in_scope"]["attention/indexer/select"]
    assert found["named_s"] > 0.9 * found["busy_s"]


def test_the_readers_on_the_recorded_trace(recorded, monkeypatch):
    found, small, recorder = recorded
    monkeypatch.setattr(scope_trace, "of", lambda obs: found)
    obs = {"family": small, "chips": 1, "trace": {"steps": 3},
           "peaks": registry.peaks("TPU v5 lite"),
           "traffic": {"batch": recorder.BATCH, "seq": recorder.SEQ}}
    scopes, busy = found["scopes"], found["busy_s"]
    assert registry.metric("indexer_scope_share").read(obs) \
        == pytest.approx(100 * scopes["attention/indexer"] / busy)
    assert registry.metric("indexer_select_share").read(obs) \
        == pytest.approx(100 * scopes["attention/indexer/select"] / busy)
    assert registry.metric("indexer_loss_share").read(obs) \
        == pytest.approx(100 * scopes["attention/indexer/loss"] / busy)
    share = registry.metric("indexer_scores_roofline_share").read(obs)
    least = registry.metric("indexer_scores_roofline_share").least_seconds(
        obs)[0]
    assert share == pytest.approx(
        100 * 3 * least / scopes["attention/indexer/scores"])
    assert 0 < share < 100


@pytest.mark.parametrize("trace", [
    "tpu1_olmoe.xplane.pb.gz", "tpu1_deepseek_v3.xplane.pb.gz",
    "tpu1_lfm2_moe.xplane.pb.gz", "tpu1_nemotron_h.xplane.pb.gz"])
def test_other_traces_hold_no_indexer(trace):
    """The other families' recorded traces hold no indexer's scope: each
    scope reader reads 0 % and the roofline reader nothing."""
    path = os.path.join(DATA, trace)
    if not os.path.exists(path):
        pytest.skip(f"no {trace} recorded")
    found = scope_trace.reduce(scope_trace.events(path),
                               *scope_trace.vocabulary())
    assert not [s for s in found["scopes"] if "indexer" in s]


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
    # the reference prints its indexers' losses and how far the system's
    # selection is its own
    assert "keye_vl reference: L_LM" in proc.stdout
    assert "agree on 9" in proc.stdout
    # what this PR counts is in the run's own timeline
    with open(os.path.join(registry.ROOT, ".scratch", "benchmark", CELL, CELL,
                           "timeline.json")) as f:
        counters = json.load(f)["counters"]
    # the rehearsal's two layers are alike and recomputed: traced once
    # under the gradient, 2 x 64 positions, 16 keys a query
    selected = sum(min(16, t + 1) for t in range(64))
    assert counters["attention.pairs_selected"] * (64 * 65 // 2) \
        == counters["attention.pairs_causal"] * selected
    assert counters["attention.keys_selected"] % 16 == 0
    assert counters["attention.indexer_heads"] % 4 == 0
    assert counters["attention.mask_bytes"] % (2 * 64 * 64) == 0
    assert counters["attention.q_heads"] == 4 * counters["attention.kv_heads"]
    assert counters["moe.experts_held"] * 2 == counters["moe.experts"]


FAULTS = {
    # three times the learning rate the configuration states
    "wrong_rate": '''\
        from benchmark.families import keye_vl
        from benchmark.reference.keye_vl import adamw


        class Family(keye_vl.Family):
            def optimizer(self):
                settings = dict(self.config["optimizer"])
                settings["learning_rate"] *= 3
                return adamw(settings)
        ''',
    # 8-bit floats where the configuration states bfloat16
    "low_precision": '''\
        import dataclasses

        from benchmark.families import keye_vl


        class Family(keye_vl.Family):
            def model_config(self):
                import jax.numpy as jnp

                return dataclasses.replace(
                    super().model_config(),
                    compute_dtype=jnp.dtype("float8_e4m3fn"))
        ''',
    # every key a query sees, none selected: dense causal attention
    "selection_ignored": '''\
        import dataclasses

        from benchmark.families import keye_vl


        class Family(keye_vl.Family):
            def model_config(self):
                return dataclasses.replace(super().model_config(),
                                           index_top_k=1 << 20)
        ''',
    # 2 experts a token for 3
    "one_expert_short": '''\
        import dataclasses

        from benchmark.families import keye_vl


        class Family(keye_vl.Family):
            def model_config(self):
                config = super().model_config()
                return dataclasses.replace(config, top_k=config.top_k - 1)
        ''',
    # the kernels are handed no mask: every key a query sees is attended,
    # while the indexer, its selection and its loss go on as stated
    "mask_ignored": '''\
        from benchmark.families import keye_vl


        class Family(keye_vl.Family):
            def model_config(self):
                from ray_tpu.models import keye_vl as model

                if not hasattr(model, "_sound"):
                    model._sound = attend = model.attention
                    model.attention = lambda q, k, v, mask=None, **kw: \\
                        attend(q, k, v, **kw)
                return super().model_config()
        ''',
    # the index scores alone from 8-bit floats: the indexer's queries and
    # keys rounded through float8_e4m3fn, everything else as stated
    "index_f8": '''\
        from benchmark.families import keye_vl


        class Family(keye_vl.Family):
            def model_config(self):
                import jax.numpy as jnp

                from ray_tpu.models import keye_vl as model

                if not hasattr(model, "_sound"):
                    model._sound = scores = model.index_scores
                    f8 = lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
                    model.index_scores = lambda q, k, w, block: scores(
                        f8(q), f8(k), w, block)
                return super().model_config()
        ''',
    # I = sum_j w_j (q_j . k): the ReLU dropped
    "relu_dropped": '''\
        from benchmark.families import keye_vl


        class Family(keye_vl.Family):
            def model_config(self):
                import jax

                from ray_tpu.models import keye_vl as model

                if not hasattr(model, "_sound"):
                    model._sound = scores = model.index_scores

                    def linear(q, k, w, block):
                        relu, jax.nn.relu = jax.nn.relu, lambda x: x
                        try:
                            return scores(q, k, w, block)
                        finally:
                            jax.nn.relu = relu

                    model.index_scores = linear
                return super().model_config()
        ''',
}
# what of the rehearsal sees each: "losses", L_LM + L_I of three steps
# outside the band (the optimizer); "first layer", the system's first
# layer's attention against the reference's before any step
# (`Family.first_layer`: the selection's agreement, or the operator's
# result under the system's own selection), which is looked at first and
# sees what three losses from random weights see little or nothing of:
# the arithmetic, every key selected, a mask the kernels ignore, index
# scores from 8-bit floats, a ReLU dropped; nothing: one expert of three
# on half the experts (`tests/test_keye_vl.py` sees it at the logits, with
# every other seeded fault)
SEEN = {"wrong_rate": "losses", "low_precision": "first layer",
        "selection_ignored": "first layer", "one_expert_short": None,
        "mask_ignored": "first layer", "index_f8": "first layer",
        "relu_dropped": "first layer"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_what_the_reference_check_catches(tmp_path, fault):
    """A family that departs from what the configuration states (a new
    file in a copy of the benchmark) runs, and its run is `correct` or not
    as `SEEN` says."""
    import shutil
    import textwrap

    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(registry.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(registry.ROOT, "benchmark"),
                    root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "benchmark" / "families" / f"keye_vl_{fault}.py").write_text(
        textwrap.dedent(FAULTS[fault]))
    config = registry.load_json("benchmark", "configs", f"{CONFIG}.json")
    config.update(name=f"keye-{fault}", family=f"keye_vl_{fault}")
    (root / "benchmark" / "configs" / f"keye-{fault}.json").write_text(
        json.dumps(config))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": f"keye-{fault}", "source": "test", "reduced": [],
        "why": "test", "file": f"benchmark/configs/keye-{fault}.json"})
    bench["workloads"].append({
        "name": f"keye-{fault}.resident-8k", "config": f"keye-{fault}",
        "traffic": "resident-8k", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    proc = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
         f"keye-{fault}.resident-8k", "--seed", "5", "--seconds", "1",
         "--trace", "0", "--rehearse"], cwd=root, capture_output=True,
        text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=registry.ROOT))
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is (SEEN[fault] is None), proc.stdout[-2000:]
    assert ("NOT CORRECT: loss at step" in proc.stdout) \
        is (SEEN[fault] is not None)
    # a first layer that is not the reference's withholds its losses
    assert ("NOT CORRECT: keye_vl: the first layer" in proc.stdout) \
        is (SEEN[fault] == "first layer")
    assert ("reference's is nan" in proc.stdout) \
        is (SEEN[fault] == "first layer")
    if fault == "wrong_rate":
        # the forward pass is right; the first update is not
        assert "NOT CORRECT: loss at step 0" not in proc.stdout
