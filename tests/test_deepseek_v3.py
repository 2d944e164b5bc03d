"""The `deepseek_v3` model (`ray_tpu/models/deepseek_v3.py`: latent
attention, a sigmoid bias-corrected mixture with shared experts behind a
leading dense layer) against the plain reference
(`benchmark/reference/deepseek_v3.py`: float32 `jax.numpy`, attention as a
masked softmax, the experts as a loop over those held) at a small size on
the CPU: 1 dense + 2 routed layers, hidden 64, 4 heads with q/k 24 wide
(16 + 8 rotary) and v 16, latent 32, 8 experts 24 wide with 3 a token,
shared 48, dense 96, sequence 64, vocabulary 512, seeded random weights.

The matrices are drawn four times as wide as the assumed 0.02: at 0.02 and
these widths the experts' output is a thousandth of the residual stream
and a routing fault would hide under any tolerance.
"""

import dataclasses

import jax
import jax.numpy as jnp
import model_kit as kit
import numpy as np
import pytest
from model_kit import max_diff

from benchmark.families.deepseek_v3 import to_reference
from benchmark.reference import deepseek_v3 as reference
from ray_tpu.models import deepseek_v3 as model, layers
from ray_tpu.parallel.sharding import infer_param_logical_dims

F32 = dataclasses.replace(model.DEEPSEEK_V3_TINY, compute_dtype=jnp.float32)
BF16 = model.DEEPSEEK_V3_TINY
SIZES = reference.Sizes(n_head=4, kv_lora_rank=32, qk_nope_dim=16,
                        qk_rope_dim=8, v_head_dim=16, top_k=3,
                        routed_scale=2.448, query_block=16)
BATCH, SEQ = 2, 64
OPTIMIZER = {"learning_rate": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
             "weight_decay": 0.1}
BIAS = model.ROUTING_BIAS

# float32 compute: the routing is identical and only summation order
# differs (sorted groups against a loop over experts, flash blocks against
# a whole softmax); measured 1e-6 on logits of size 3, 4e-7 on gradients
F32_TOL = 2e-5
# bfloat16 compute against the float32 reference, logits of size up to 3:
# measured 0.03 to 0.05 over seeds 0-2 on the tokens whose routing is clear
# (bf16 keeps 8 bits: 2^-8 of 3 is 0.012).  The seeded faults below move
# the logits by 0.2 to 2 and fail it.
BF16_LOGITS_TOL = 0.08
# scores + bias closer than this around the k-th are a tie to bf16
ROUTER_GAP = 0.01


pytestmark = pytest.mark.usefixtures("highest_precision")


@kit.once
def make_params(seed=0, cfg=F32, bias=True):
    """Seeded weights, and routing biases that are not 0 (up to 0.05: a
    score is a sigmoid, and the k-th and k+1-th are often closer)."""
    return kit.drawn(
        lambda key: model.init_params(key, cfg), seed,
        [kit.Vector((f"layer_{i}", "moe", "router", BIAS), 0.05, key=77 + n,
                    start=0.0)
         for n, i in enumerate(cfg.moe_layers) if bias])


def make_tokens(seed=0):
    return kit.tokens(1000 + seed, BATCH, SEQ, F32.vocab_size)


def reference_tree(tree):
    return to_reference(tree)[0]


@kit.once
def results(which, sizes=SIZES, fault=None):
    """(logits, loss, rows sent to the experts, gradients in the
    reference's layout) of the system in float32 or of the reference (with
    a seeded fault), each one jitted program, computed once."""
    params, tokens = make_params(), make_tokens()
    with jax.default_matmul_precision("highest"):
        if which == "system":
            def run(params):
                logits, _ = model.forward(params, tokens[:, :-1], F32)
                (loss, parts), grads = jax.value_and_grad(
                    model.loss_fn, has_aux=True)(params, {"tokens": tokens},
                                                 F32)
                return logits, loss, parts["expert_rows"], \
                    reference_tree(grads), grads
            return jax.jit(run)(params)

        ref_params, biases = to_reference(params)
        if fault:
            ref_params, biases = fault(ref_params, biases)

        def run(params, biases):
            logits = reference.logits(params, biases, tokens[:, :-1], sizes)
            (loss, rows), grads = jax.value_and_grad(
                reference.losses, has_aux=True)(params, biases, tokens, sizes)
            return logits, loss, rows, grads
        return jax.jit(run)(ref_params, biases)


def reference_logits(sizes=SIZES, fault=None):
    """The reference's logits alone (with a seeded fault), a program of
    its own a call: the side a fault is in."""
    tokens = make_tokens()[:, :-1]
    ref_params, biases = to_reference(make_params())
    if fault:
        ref_params, biases = fault(ref_params, biases)
    return jax.jit(lambda p, b: reference.logits(p, b, tokens, sizes))(
        ref_params, biases)


@pytest.mark.parametrize("what", ["logits", "loss", "expert_rows"])
def test_the_forward_pass_matches_the_reference_in_float32(what):
    index = ["logits", "loss", "expert_rows"].index(what)
    got, want = results("system")[index], results("reference")[index]
    assert got.shape == want.shape
    if what == "expert_rows":
        assert (np.asarray(got) == np.asarray(want)).all()
        assert int(got.sum()) == 2 * BATCH * SEQ * 3      # nothing dropped
    else:
        assert max_diff(got, want) < F32_TOL


def test_gradients_of_every_leaf_match():
    got, want = results("system")[3], results("reference")[3]
    flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_want = jax.tree_util.tree_flatten_with_path(want)[0]
    assert [p for p, _ in flat_got] == [p for p, _ in flat_want]
    # embed, head, norm_f; a dense layer's 10; the routed stack's 14
    assert len(flat_got) == 3 + 10 + 14
    for (path, g), (_, w) in zip(flat_got, flat_want):
        assert float(jnp.max(jnp.abs(w))) > 0, path     # nothing is dead
        assert max_diff(g, w) < F32_TOL, path


def test_the_bias_gets_no_gradient():
    grads = results("system")[4]
    for i in F32.moe_layers:
        assert not np.asarray(grads[f"layer_{i}"]["moe"]["router"][BIAS]).any()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bfloat16_compute_stays_close_and_routes_alike(seed):
    params, tokens = make_params(seed), make_tokens(seed)
    logits, stats = jax.jit(lambda p: model.forward(
        p, tokens[:, :-1], BF16))(params)
    ref_params, biases = to_reference(params)
    want = jax.jit(lambda p, b: reference.logits(
        p, b, tokens[:, :-1], SIZES))(ref_params, biases)
    # a token whose k-th and k+1-th scores tie in bf16 may take another
    # expert: most tokens do not, and those agree within the band
    diff = jnp.max(jnp.abs(logits - want), axis=-1)
    close = diff < BF16_LOGITS_TOL
    assert float(jnp.mean(close)) > 0.75, float(jnp.mean(close))
    rows = jax.jit(lambda p, b: reference.losses(p, b, tokens, SIZES)[1])(
        ref_params, biases)
    assert int(jnp.sum(jnp.abs(stats["expert_rows"] - rows))) \
        < 0.1 * int(rows.sum())


def system_steps(cfg, steps=3, lr=None):
    params, tokens = make_params(cfg=cfg), make_tokens()
    settings = dict(OPTIMIZER, learning_rate=lr or OPTIMIZER["learning_rate"])
    optimizer = model.trained_by(reference.adamw(settings))
    step = jax.jit(model.make_train_step(cfg, optimizer))
    opt_state = optimizer.init(params)
    losses, outs = [], []
    for _ in range(steps):
        before = params
        params, opt_state, out = step(params, opt_state, {"tokens": tokens})
        losses.append(float(out["loss"]))
        outs.append((before, params, out))
    return losses, outs, opt_state


@kit.once
def reference_steps():
    params, biases = kit.own(to_reference(make_params()))
    tokens = make_tokens()
    with jax.default_matmul_precision("highest"):
        return reference.first_losses(
            params, biases, jnp.stack([tokens] * 3), SIZES, OPTIMIZER)


def test_three_steps_match_the_reference_program_and_the_bias_moves_by_rule():
    losses, outs, opt_state = system_steps(F32)
    assert np.allclose(losses, reference_steps(), atol=F32_TOL), (
        losses, reference_steps())
    assert losses[2] < losses[1] < losses[0]
    # no moments for the bias: AdamW's state holds a leaf for every other
    # leaf twice, and the step count
    n_params = len(jax.tree.leaves(outs[0][0]))
    n_bias = len(F32.moe_layers)
    assert len(jax.tree.leaves(opt_state)) == 2 * (n_params - n_bias) + 1
    for before, after, out in outs:
        for j, i in enumerate(F32.moe_layers):
            b0 = before[f"layer_{i}"]["moe"]["router"][BIAS]
            b1 = after[f"layer_{i}"]["moe"]["router"][BIAS]
            n = np.asarray(out["expert_rows"][j], np.float32)
            # no decay, no gradient: the rule alone
            np.testing.assert_allclose(
                np.asarray(b1 - b0), 0.001 * np.sign(n.mean() - n), atol=1e-7)
        assert float(out["max_routing_bias"]) == pytest.approx(max(
            float(jnp.max(jnp.abs(
                before[f"layer_{i}"]["moe"]["router"][BIAS])))
            for i in F32.moe_layers))
        assert int(out["rows_held"]) == int(out["expert_rows"].sum())
        assert int(out["max_expert_rows"]) == int(out["expert_rows"].max())


def test_bfloat16_train_step_tracks_the_reference_and_a_tripled_rate_does_not():
    """Three bfloat16 steps against the float32 reference.  The second and
    third losses stand behind AdamW's first updates, which divide every
    gradient by its own size, so a rounding anywhere moves them: read here,
    step by step, 0.0009, 0.0083, 0.0062 with the head's logits made twice
    (until PR 53; the CPU's product took d logits in float32, which the
    MXU never did) and 0.0009, 0.0118, 0.0049 since the head rounds
    d logits to bfloat16 itself before its two products."""
    want = reference_steps()
    got, _, _ = system_steps(BF16)
    assert max(abs(g - w) for g, w in zip(got, want)) < 0.015, (got, want)
    tripled, _, _ = system_steps(BF16, lr=3e-3)
    assert max(abs(g - w) for g, w in zip(tripled, want)) > 0.05


def test_the_bias_changes_the_choice_and_not_the_weights():
    cfg = F32
    params = make_params(bias=False)
    router = params["layer_1"]["moe"]["router"]
    xt = jax.random.normal(jax.random.PRNGKey(5), (128, cfg.n_embd))
    scores = jax.nn.sigmoid(xt @ router["kernel"])
    w0, e0 = model._route(cfg)(xt, router)
    # a bias that lifts expert 7 over everything and sinks expert 0
    bias = jnp.zeros(8).at[7].set(2.0).at[0].set(-2.0)
    w1, e1 = model._route(cfg)(xt, {**router, BIAS: bias})
    assert (np.asarray(e1)[:, 0] == 7).all() and not (np.asarray(e1) == 0).any()
    assert (np.asarray(e0) != np.asarray(e1)).any()
    for w, e in ((w0, e0), (w1, e1)):
        s = jnp.take_along_axis(scores, e, axis=-1)
        np.testing.assert_allclose(
            w, 2.448 * s / s.sum(-1, keepdims=True), rtol=1e-6)
        np.testing.assert_allclose(w.sum(-1), 2.448, rtol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts that all the shares of a layer give, plus the
    shared experts once, are the uncut reference's whole layer."""
    params = make_params()
    p = params["layer_1"]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(9), (BATCH, SEQ, F32.n_embd))
    shared = layers.dense_ffn(u, p["shared"], layers.swiglu)
    routed, rows = 0, []
    for first in range(0, 8, 2):
        cfg = dataclasses.replace(F32, held=(first, 2))
        share = {**p, **{k: p[k][first:first + 2]
                         for k in ("wi_gate", "wi_up", "wo")}}
        y, sent = layers.routed_layer(u, share, model._route(cfg),
                                      cfg.n_experts, cfg.held, layers.swiglu)
        routed += y - shared
        rows.append(sent)
    whole, biases = to_reference(params)
    want, want_rows = reference.moe(
        u.reshape(-1, F32.n_embd),
        jax.tree.map(lambda leaf: leaf[0], whole["routed"]), biases[0], SIZES)
    assert max_diff((routed + shared).reshape(want.shape), want) < F32_TOL
    # every share sees the routing over ALL the experts
    for sent in rows:
        assert (np.asarray(sent) == np.asarray(want_rows)).all()
    # and one share alone is not the layer
    assert max_diff((y).reshape(want.shape), want) > 0.01


def _faulty_route(kind):
    def route(x, p, bias, sizes):
        s = jax.nn.softmax(x @ p["router"], -1) if kind == "softmax" \
            else jax.nn.sigmoid(x @ p["router"])
        _, chosen = jax.lax.top_k(s + bias, sizes.top_k)
        chosen = jnp.sum(jax.nn.one_hot(chosen, s.shape[-1]), axis=1)
        picked = s * chosen
        if kind != "not_renormalised":
            picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
        return picked * sizes.routed_scale, chosen
    return route


FAULTS = {
    "scale_left_out": dict(sizes=SIZES._replace(routed_scale=1.0)),
    "two_experts_for_three": dict(sizes=SIZES._replace(top_k=2)),
    "bias_left_out_of_the_selection": dict(
        fault=lambda p, b: (p, jnp.zeros_like(b))),
    "shared_experts_left_out": dict(fault=lambda p, b: ({**p, "routed": {
        **p["routed"], "s_down": jnp.zeros_like(p["routed"]["s_down"])}},
        b)),
    "latent_norm_left_out": dict(patch=("rms_norm", lambda x, gain, eps:
        x * gain if x.shape[-1] == 32 else x / jnp.sqrt(jnp.mean(
            jnp.square(x), axis=-1, keepdims=True) + eps) * gain)),
    "rotary_part_not_turned": dict(patch=("rope_pairs", lambda x, theta: x)),
    "softmax_for_sigmoid": dict(patch=("route", _faulty_route("softmax"))),
    "weights_not_renormalised": dict(
        patch=("route", _faulty_route("not_renormalised"))),
}


@pytest.mark.parametrize("name", sorted(FAULTS))
def test_a_seeded_fault_fails_both_tolerances(monkeypatch, name):
    """The reference with one fault against the system: the logits differ
    by far more than the float32 tolerance and than the bfloat16 band."""
    spec = dict(FAULTS[name])
    if "patch" in spec:
        attr, fn = spec.pop("patch")
        monkeypatch.setattr(reference, attr, fn)
    logits = reference_logits(**spec)   # the faulted side, nothing else
    monkeypatch.undo()
    moved = max_diff(logits, results("system")[0])
    assert moved > BF16_LOGITS_TOL > F32_TOL, (name, moved)


def test_rope_turns_a_part_and_the_one_shared_key_part():
    """`layers.rope(interleaved=True)` on a part of a head against the
    pairwise formula: the same numbers in [evens | odds] order, so every
    q . k is the pairwise one; a key part with H = 1 serves every head."""
    from ray_tpu.models.layers import rope

    S, H, D = 16, 3, 8
    q = jax.random.normal(jax.random.PRNGKey(0), (1, S, H, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, S, 1, D))
    theta = 1e6
    got_q = rope(q, jnp.arange(S), theta, interleaved=True)
    got_k = rope(k, jnp.arange(S), theta, interleaved=True)
    want_q = reference.rope_pairs(q[0], theta)            # (S, H, D)
    want_k = reference.rope_pairs(k[0], theta)
    order = np.r_[0:D:2, 1:D:2]
    np.testing.assert_allclose(got_q[0], want_q[..., order], atol=1e-5)
    np.testing.assert_allclose(got_k[0], want_k[..., order], atol=1e-5)
    # by hand: position m turns (x0, x1) by m * theta^0 = m radians
    m = 5
    x0, x1 = float(q[0, m, 0, 0]), float(q[0, m, 0, 1])
    assert float(want_q[m, 0, 0]) == pytest.approx(
        x0 * np.cos(m) - x1 * np.sin(m), abs=1e-5)
    scores = jnp.einsum("shd,skd->hsk", got_q[0], got_k[0])
    want = jnp.einsum("shd,skd->hsk", want_q, want_k)
    assert max_diff(scores, want) < 1e-4


def test_parameters_carry_the_logical_dimensions_sharding_reads():
    shapes = jax.eval_shape(
        lambda key: model.init_params(key, F32), jax.random.PRNGKey(0))
    dims = {"/".join(str(getattr(k, "key", k)) for k in path):
            infer_param_logical_dims(
                tuple(getattr(k, "key", k) for k in path), leaf.shape)
            for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert dims["embed_tokens/embedding"] == ("vocab", "embed")
    assert dims["lm_head/kernel"] == ("embed", "vocab")
    assert dims["layer_0/attn/q_proj/kernel"] == ("embed", "heads")
    assert dims["layer_0/attn/o_proj/kernel"] == ("heads", "embed")
    assert dims["layer_0/attn/kv_a_norm/scale"] == (None,)
    assert dims["layer_0/mlp/gate_proj/kernel"] == ("embed", "mlp")
    assert dims["layer_0/mlp/down_proj/kernel"] == ("mlp", "embed")
    assert dims["layer_1/moe/wi_gate"] == ("expert", "embed", "mlp")
    assert dims["layer_1/moe/wo"] == ("expert", "mlp", "embed")
    assert dims["layer_1/moe/router/kernel"] == ("embed", None)
    assert dims["layer_1/moe/shared/up_proj/kernel"] == ("embed", "mlp")


def test_counts_at_the_published_widths():
    """kanana-2-30b-a3b whole is the published "30B"; one chip's share of
    the 5-layer cut is 576.0 M parameters, ISSUE 32's table."""
    whole = jax.eval_shape(
        lambda key: model.init_params(key, model.KANANA_2_30B_A3B),
        jax.random.PRNGKey(0))
    assert round(model.num_params(whole) / 1e9, 2) == 30.67
    share = dataclasses.replace(
        model.KANANA_2_30B_A3B, vocab_size=16032, n_layer=5, held=(0, 16))
    shapes = jax.eval_shape(lambda key: model.init_params(key, share),
                            jax.random.PRNGKey(0))
    assert round(model.num_params(shapes) / 1e6, 1) == 576.0
    # MLA 26.35 M a layer; a routed layer 36.05 + 16 x 4.72 = 111.5 M
    attn = 2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    assert round(attn / 1e6, 2) == 26.35
    flops = model.count_flops_per_token(share, 8192)
    n = (16032 * 2048 + 5 * attn + 3 * 2048 * 6144 + 4 * (
        2048 * 128 + 3 * 2048 * 1536 + 0.75 * 3 * 2048 * 768))
    assert flops == 6 * n + 6 * 5 * 8192 * 32 * 320


# -- the group-limited choice (ISSUE 65) ---------------------------------------

def _numpy_group_route(x, kernel, bias, top_k, n_group, topk_group, scale):
    """`sigmoid_route` with groups, written out in numpy float64 a token at a
    time: the two largest picks of each group summed, the best groups kept,
    the top k among their experts, weights over their sum."""
    x, kernel, bias = (np.asarray(a, np.float64) for a in (x, kernel, bias))
    scores = 1.0 / (1.0 + np.exp(-(x @ kernel)))
    weights, experts = [], []
    per = kernel.shape[1] // n_group
    for s in scores:
        picks = s + bias
        by_group = picks.reshape(n_group, per)
        score = np.sort(by_group, axis=1)[:, -2:].sum(axis=1)
        kept = np.argsort(-score, kind="stable")[:topk_group]
        masked = np.full_like(picks, -np.inf)
        for g in kept:
            masked[g * per:(g + 1) * per] = picks[g * per:(g + 1) * per]
        chosen = np.argsort(-masked, kind="stable")[:top_k]
        experts.append(chosen)
        weights.append(s[chosen] / (s[chosen].sum() + 1e-20) * scale)
    return np.asarray(weights), np.asarray(experts)


@pytest.mark.parametrize("n_group, topk_group, top_k", [
    (8, 4, 8), (4, 1, 3), (2, 2, 5)])
def test_the_group_limited_choice_is_the_written_out_one(n_group, topk_group,
                                                        top_k):
    from ray_tpu.ops.moe import sigmoid_route
    from ray_tpu.util import tracing

    T, E, N = 96, 32, 64
    x = jax.random.normal(jax.random.PRNGKey(0), (T, E))
    router = {"kernel": jax.random.normal(jax.random.PRNGKey(1), (E, N)),
              BIAS: 0.3 * jax.random.normal(jax.random.PRNGKey(2), (N,))}
    with tracing.timeline_span("train.fit", root=True) as job:
        weights, experts = sigmoid_route(
            x, router, top_k, 1e-20, 2.5, n_group=n_group,
            topk_group=topk_group)
        assert tracing.counter("moe.route_groups") == 1
    tracing.timeline_take(job.trace_id)
    want_w, want_e = _numpy_group_route(
        x, router["kernel"], router[BIAS], top_k, n_group, topk_group, 2.5)
    np.testing.assert_array_equal(np.asarray(experts), want_e)
    np.testing.assert_allclose(np.asarray(weights), want_w, rtol=1e-5)
    # every choice lies in one of at most `topk_group` groups
    groups = np.asarray(experts) // (N // n_group)
    assert max(len(set(row)) for row in groups) <= topk_group
    # the bias picks and does not weigh, and carries no gradient
    grads = jax.grad(lambda r: jnp.sum(sigmoid_route(
        x, r, top_k, 1e-20, 2.5, n_group=n_group,
        topk_group=topk_group)[0] ** 2))(router)
    assert not np.asarray(grads[BIAS]).any()
    assert np.asarray(grads["kernel"]).any()


def test_one_group_is_the_program_it_always_was():
    """At the defaults `sigmoid_route`'s jaxpr is the parent's, written out
    here as it stood before the groups; kanana's step counts no grouped
    router."""
    from jax.ad_checkpoint import checkpoint_name

    from ray_tpu.ops.moe import ROUTE_NAME, sigmoid_route
    from ray_tpu.util import tracing

    def parent_route(xt, router, top_k, eps, scale):
        scores = jax.nn.sigmoid(checkpoint_name(jnp.matmul(
            xt, router["kernel"].astype(xt.dtype),
            preferred_element_type=jnp.float32), ROUTE_NAME))
        picks = scores
        if BIAS in router:
            picks = scores + jax.lax.stop_gradient(router[BIAS])
        _, experts = jax.lax.top_k(picks, top_k)
        experts = checkpoint_name(experts, ROUTE_NAME)
        weights = checkpoint_name(
            jnp.take_along_axis(scores, experts, axis=-1), ROUTE_NAME)
        if eps is not None:
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                                 + eps)
        return weights * scale, experts

    x = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    for router in ({"kernel": jnp.ones((8, 12))},
                   {"kernel": jnp.ones((8, 12)), BIAS: jnp.zeros((12,))}):
        for eps in (1e-20, None):
            assert str(jax.make_jaxpr(lambda x: sigmoid_route(
                x, router, 3, eps, 2.448))(x)) == str(jax.make_jaxpr(
                    lambda x: parent_route(x, router, 3, eps, 2.448))(x))
    with tracing.timeline_span("train.fit", root=True) as job:
        jax.eval_shape(lambda p: model.forward(
            p, make_tokens()[:, :-1], F32), make_params())
        assert tracing.counter("moe.route_groups") == 0
    tracing.timeline_take(job.trace_id)
