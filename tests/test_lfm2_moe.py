"""The `lfm2_moe` model (`ray_tpu/models/lfm2_moe.py`: gated short
convolutions beside grouped-query attention, a dense feed-forward in the
leading layer and a sigmoid bias-corrected mixture after) against the plain
reference (`benchmark/reference/lfm2_moe.py`: float32 `jax.numpy`, the
convolution as a sum over taps, attention as a masked softmax with the
key/value heads repeated, the experts as a loop over those held) at a small
size on the CPU: a dense conv layer, a routed attention layer and two routed
conv layers, hidden 64, 4 query heads on 2 key/value heads of 16, 8 experts
24 wide with 3 a token, dense 96, sequence 64, vocabulary 512, seeded random
weights.

The matrices are drawn four times as wide as the assumed 0.02: at 0.02 and
these widths an operator's output is a thousandth of the residual stream
and a fault would hide under any tolerance.
"""

import dataclasses

import jax
import jax.numpy as jnp
import model_kit as kit
import numpy as np
import pytest
from model_kit import max_diff

from benchmark.families.lfm2_moe import to_reference
from benchmark.reference import lfm2_moe as reference
from ray_tpu.models import layers, lfm2_moe as model
from ray_tpu.parallel.sharding import infer_param_logical_dims

CONV, ATTN = model.CONV, model.ATTENTION
BF16 = dataclasses.replace(model.LFM2_MOE_TINY,
                           layer_types=(CONV, ATTN, CONV, CONV))
F32 = dataclasses.replace(BF16, compute_dtype=jnp.float32)
SIZES = reference.Sizes(n_head=4, n_kv_head=2, top_k=3, query_block=16)
BATCH, SEQ = 2, 64
OPTIMIZER = {"learning_rate": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
             "weight_decay": 0.1}
BIAS = model.ROUTING_BIAS

# float32 compute: the routing is identical and only summation order
# differs (sorted groups against a loop over experts, flash blocks against
# a whole softmax, shifted copies against the same in another order);
# measured 3e-6 on logits of size 3, 3e-7 on gradients
F32_TOL = 2e-5
# bfloat16 compute against the float32 reference, logits of size up to 3:
# measured under 0.05 over seeds 0-2 on the tokens whose routing is clear.
# The seeded faults below move the logits by 0.2 and more and fail it.
BF16_LOGITS_TOL = 0.08


pytestmark = pytest.mark.usefixtures("highest_precision")


@kit.once
def make_params(seed=0, cfg=F32, bias=True):
    """Seeded weights, and routing biases that are not 0."""
    return kit.drawn(
        lambda key: model.init_params(key, cfg), seed,
        [kit.Vector((f"layer_{i}", "moe", "router", BIAS), 0.05, key=77 + n,
                    start=0.0)
         for n, i in enumerate(cfg.moe_layers) if bias], narrow=("conv",))


def make_tokens(seed=0):
    return kit.tokens(1000 + seed, BATCH, SEQ, F32.vocab_size)


@kit.once
def results(which, sizes=SIZES):
    """(logits, loss, rows sent to the experts, gradients in the
    reference's layout) of the system in float32 or of the reference, each
    one jitted program, computed once."""
    params, tokens = make_params(), make_tokens()
    with jax.default_matmul_precision("highest"):
        if which == "system":
            def run(params):
                logits, _ = model.forward(params, tokens[:, :-1], F32)
                (loss, parts), grads = jax.value_and_grad(
                    model.loss_fn, has_aux=True)(params, {"tokens": tokens},
                                                 F32)
                return logits, loss, parts["expert_rows"], \
                    to_reference(grads)[0], grads
            return jax.jit(run)(params)

        def run(params, biases):
            logits = reference.logits(params, biases, tokens[:, :-1], sizes)
            (loss, rows), grads = jax.value_and_grad(
                reference.losses, has_aux=True)(params, biases, tokens, sizes)
            return logits, loss, rows, grads
        return jax.jit(run)(*to_reference(params))


def reference_logits(sizes=SIZES):
    """The reference's logits alone, a program of its own a call: the side
    a seeded fault is in."""
    tokens = make_tokens()[:, :-1]
    return jax.jit(lambda p, b: reference.logits(p, b, tokens, sizes))(
        *to_reference(make_params()))


@pytest.mark.parametrize("what", ["logits", "loss", "expert_rows"])
def test_the_forward_pass_matches_the_reference_in_float32(what):
    index = ["logits", "loss", "expert_rows"].index(what)
    got, want = results("system")[index], results("reference")[index]
    assert got.shape == want.shape
    if what == "expert_rows":
        assert (np.asarray(got) == np.asarray(want)).all()
        assert int(got.sum()) == 3 * BATCH * SEQ * 3      # nothing dropped
    else:
        assert max_diff(got, want) < F32_TOL


def test_gradients_of_every_leaf_match():
    got, want = results("system")[3], results("reference")[3]
    flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_want = jax.tree_util.tree_flatten_with_path(want)[0]
    assert [p for p, _ in flat_got] == [p for p, _ in flat_want]
    # embed, norm_f; the dense conv layer's 8; the routed attention
    # layer's 12; the routed conv layers' stack of 9
    assert len(flat_got) == 2 + 8 + 12 + 9
    for (path, g), (_, w) in zip(flat_got, flat_want):
        assert float(jnp.max(jnp.abs(w))) > 0, path     # nothing is dead
        assert max_diff(g, w) < F32_TOL, path


def test_the_bias_gets_no_gradient_and_the_head_is_the_embedding():
    grads = results("system")[4]
    for i in F32.moe_layers:
        assert not np.asarray(grads[f"layer_{i}"]["moe"]["router"][BIAS]).any()
    assert "lm_head" not in make_params()


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
        assert int(out["moe_overflow_layers"]) == 0


def test_bfloat16_train_step_tracks_the_reference_and_a_tripled_rate_does_not():
    want = reference_steps()
    got, _, _ = system_steps(BF16)
    assert max(abs(g - w) for g, w in zip(got, want)) < 0.01, (got, want)
    tripled, _, _ = system_steps(BF16, lr=3e-3)
    assert max(abs(g - w) for g, w in zip(tripled, want)) > 0.05


def test_the_flags_of_the_published_config_do_what_they_say():
    """`use_expert_bias` false: no bias leaf, no rule, a plain top-k;
    `norm_topk_prob` false: the chosen scores as they are."""
    cfg = dataclasses.replace(F32, use_expert_bias=False,
                              norm_topk_prob=False)
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    router = params["layer_1"]["moe"]["router"]
    assert set(router) == {"kernel"}
    xt = jax.random.normal(jax.random.PRNGKey(5), (128, cfg.n_embd))
    scores = jax.nn.sigmoid(xt @ router["kernel"])
    top, chosen = jax.lax.top_k(scores, cfg.top_k)
    from ray_tpu.ops.moe import sigmoid_route
    weights, experts = sigmoid_route(xt, router, cfg.top_k, None, 1.0)
    assert (np.asarray(experts) == np.asarray(chosen)).all()
    np.testing.assert_allclose(weights, top, rtol=1e-6)
    renormed, _ = sigmoid_route(xt, router, cfg.top_k, 1e-6, 1.0)
    np.testing.assert_allclose(
        renormed, top / (top.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    tokens = make_tokens()
    optimizer = model.trained_by(reference.adamw(OPTIMIZER))
    params, _, out = jax.jit(model.make_train_step(cfg, optimizer))(
        params, optimizer.init(params), {"tokens": tokens})
    assert float(out["max_routing_bias"]) == 0.0
    assert set(params["layer_1"]["moe"]["router"]) == {"kernel"}


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One routed layer with the router's 64 columns, 4 a token: the parts
    that its eight shares of 8 experts give add up to what the uncut
    reference gives for the whole layer (there is no shared expert to count
    once), every share seeing the routing over all 64."""
    cfg = dataclasses.replace(F32, n_experts=64, top_k=4, expert_width=8)
    params = make_params(cfg=cfg)
    p = params["layer_2"]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(9), (BATCH, SEQ, cfg.n_embd))
    total, rows = 0, []
    for first in range(0, 64, 8):
        share = {**p, **{k: p[k][first:first + 8]
                         for k in ("wi_gate", "wi_up", "wo")}}
        y, sent = layers.routed_layer(u, share, model._route(cfg), 64,
                                      (first, 8), layers.swiglu)
        total += y
        rows.append(sent)
    whole, biases = to_reference(params)
    sizes = SIZES._replace(top_k=4)
    layer = jax.tree.map(lambda leaf: leaf[0], whole["groups"][2])
    want, want_rows = reference.moe(u.reshape(-1, cfg.n_embd), layer,
                                    biases[2][0], sizes)
    assert max_diff(total.reshape(want.shape), want) < F32_TOL
    for sent in rows:
        assert (np.asarray(sent) == np.asarray(want_rows)).all()
    assert int(want_rows.sum()) == BATCH * SEQ * 4
    # and one share alone is not the layer
    assert max_diff(y.reshape(want.shape), want) > 0.01


# -- the seeded faults of the configuration's `loss_tolerance_reason`, here
# in the reference and at the logits

def _no_c_gate(x, p):
    s, e = x.shape
    bcz = x @ p["w_in"]
    g = bcz[:, :e] * bcz[:, 2 * e:]
    taps = p["taps"].shape[1]
    v = sum(p["taps"][:, j] * jnp.concatenate(
        [jnp.zeros((taps - 1 - j, e)), g[:s - (taps - 1 - j)]])
        for j in range(taps))
    return v @ p["w_out"]


def _kv_head_by_remainder(real):
    """Query head h on key/value head h % H_kv: the query heads are put in
    the order whose groups `jnp.repeat` then makes of them."""
    def attention(x, p, sizes):
        h, h_kv = sizes.n_head, sizes.n_kv_head
        d = p["wq"].shape[1] // h
        order = np.argsort(np.arange(h) % h_kv, kind="stable")
        wq = p["wq"].reshape(-1, h, d)[:, order].reshape(p["wq"].shape)
        wo = p["wo"].reshape(h, d, -1)[order].reshape(p["wo"].shape)
        return real(x, {**p, "wq": wq, "wo": wo}, sizes)
    return attention


def _rope_pairs(x, theta):
    """Adjacent pairs (2i, 2i+1) turn together: `rope_interleave`."""
    s, d = x.shape[0], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = (jnp.arange(s, dtype=jnp.float32)[:, None]
             * inv_freq[None])[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                      even * jnp.sin(angle) + odd * jnp.cos(angle)],
                     axis=-1).reshape(x.shape)


def _faulty_route(kind):
    def route(x, p, bias, sizes):
        s = jax.nn.softmax(x @ p["router"], -1) if kind == "softmax" \
            else jax.nn.sigmoid(x @ p["router"])
        _, chosen = jax.lax.top_k(s + bias, sizes.top_k)
        chosen = jnp.sum(jax.nn.one_hot(chosen, s.shape[-1]), axis=1)
        picked = s * chosen
        if kind != "not_renormalised":
            picked = picked / (jnp.sum(picked, -1, keepdims=True)
                               + sizes.renorm_eps)
        return picked * sizes.routed_scale, chosen
    return route


FAULTS = {
    "taps_reversed": ("short_conv", lambda real: lambda x, p: real(
        x, {**p, "taps": p["taps"][:, ::-1]})),
    "taps_looking_ahead": ("short_conv", lambda real: lambda x, p: real(
        x[::-1], p)[::-1]),
    "c_gate_left_out": ("short_conv", lambda real: _no_c_gate),
    "qk_norm_left_out": ("rms_norm", lambda real: lambda x, gain, eps:
                         x if x.ndim == 3 else real(x, gain, eps)),
    "kv_head_by_remainder": ("attention", _kv_head_by_remainder),
    "rope_interleaved": ("rope_halves", lambda real: _rope_pairs),
    "softmax_for_sigmoid": ("route", lambda real: _faulty_route("softmax")),
    "weights_not_renormalised": (
        "route", lambda real: _faulty_route("not_renormalised")),
}


@pytest.mark.parametrize("name", sorted(FAULTS))
def test_a_seeded_fault_fails_both_tolerances(monkeypatch, name):
    """The reference with one fault against the system: the logits differ
    by far more than the float32 tolerance and than the bfloat16 band."""
    attr, make = FAULTS[name]
    monkeypatch.setattr(reference, attr, make(getattr(reference, attr)))
    jax.clear_caches()      # `jax.checkpoint` keeps a layer's trace
    logits = reference_logits()     # the faulted side, and nothing else
    monkeypatch.undo()
    jax.clear_caches()
    moved = max_diff(logits, results("system")[0])
    assert moved > BF16_LOGITS_TOL > F32_TOL, (name, moved)


def test_parameters_carry_the_logical_dimensions_sharding_reads():
    shapes = jax.eval_shape(
        lambda key: model.init_params(key, F32), jax.random.PRNGKey(0))
    dims = {"/".join(str(getattr(k, "key", k)) for k in path):
            infer_param_logical_dims(
                tuple(getattr(k, "key", k) for k in path), leaf.shape)
            for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert dims["embed_tokens/embedding"] == ("vocab", "embed")
    assert dims["layer_0/short_conv/in_proj/kernel"] == ("embed", "mlp")
    assert dims["layer_0/short_conv/conv/kernel"] == ("embed", None)
    assert dims["layer_0/short_conv/out_proj/kernel"] == ("heads", "embed")
    assert dims["layer_1/attn/q_proj/kernel"] == ("embed", "heads")
    # 2 key/value heads of 16: the same logical dims at a quarter the width
    assert dims["layer_1/attn/k_proj/kernel"] == ("embed", "heads")
    assert shapes["layer_1"]["attn"]["k_proj"]["kernel"].shape == (64, 32)
    assert dims["layer_1/attn/o_proj/kernel"] == ("heads", "embed")
    assert dims["layer_1/attn/q_norm/scale"] == (None,)
    assert dims["layer_0/mlp/gate_proj/kernel"] == ("embed", "mlp")
    assert dims["layer_0/mlp/down_proj/kernel"] == ("mlp", "embed")
    assert dims["layer_1/moe/wi_gate"] == ("expert", "embed", "mlp")
    assert dims["layer_1/moe/wo"] == ("expert", "mlp", "embed")
    assert dims["layer_1/moe/router/kernel"] == ("embed", None)


def test_counts_at_the_published_widths():
    """LFM2-24B-A2B whole is the published "24B"; one chip's share of the
    5-layer cut is 469.3 M parameters, ISSUE 34's arithmetic."""
    whole = jax.eval_shape(
        lambda key: model.init_params(key, model.LFM2_24B_A2B),
        jax.random.PRNGKey(0))
    assert round(model.num_params(whole) / 1e9, 1) == 23.8
    assert model.LFM2_24B_A2B.layer_types.count(ATTN) == 10
    assert model.LFM2_24B_A2B.n_layer == 40
    share = dataclasses.replace(
        model.LFM2_24B_A2B, vocab_size=8192, n_dense_layer=1, held=(0, 8),
        layer_types=model.LFM2_24B_A2B.layer_types[1:6])
    assert share.layer_types == (CONV, ATTN, CONV, CONV, CONV)
    shapes = jax.eval_shape(lambda key: model.init_params(key, share),
                            jax.random.PRNGKey(0))
    assert round(model.num_params(shapes) / 1e6, 1) == 469.3
    conv = 4 * 2048 * 2048 + 2048 * 3                     # 16.78 M
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512               # 10.49 M
    assert (round(conv / 1e6, 2), round(attn / 1e6, 2)) == (16.78, 10.49)
    n = (8192 * 2048 + 4 * conv + attn + 3 * 2048 * 11776 + 4 * (
        2048 * 64 + 0.5 * 3 * 2048 * 1536))
    # ISSUE 34 adds the rounded parts and has 186.2
    assert round(n / 1e6, 1) == 186.1
    flops = model.count_flops_per_token(share, 8192)
    assert flops == 6 * n + 6 * 8192 * 32 * 128
    assert round(flops / 1e9, 2) == 1.32
