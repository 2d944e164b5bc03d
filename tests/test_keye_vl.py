"""The `keye_vl` model (`ray_tpu/models/keye_vl.py`: grouped-query attention
that selects its keys by a lightning indexer, beside a softmax-routed
mixture) against the plain reference (`benchmark/reference/keye_vl.py`:
float32 `jax.numpy`, attention and the indexer as one masked softmax, the
selection a stable sort, the experts as a loop over those held) at a small
size on the CPU: two layers, hidden 64, 8 query heads on 1 key/value head of
16 (a group of 8), 4 indexer heads of 8, 16 keys a query of a sequence of
64, 8 experts 24 wide with 3 a token, vocabulary 512, seeded random weights.

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

from benchmark.families.keye_vl import to_reference
from benchmark.reference import keye_vl as reference
from ray_tpu.models import keye_vl as model, layers

BF16 = dataclasses.replace(model.KEYE_VL_TINY, n_kv_head=1, aux_weight=0.1)
F32 = dataclasses.replace(BF16, compute_dtype=jnp.float32)
SIZES = reference.Sizes(n_head=8, n_kv_head=1, top_k=3, index_heads=4,
                        index_top_k=16, aux_weight=0.1, query_block=16)
BATCH, SEQ = 2, 64
OPTIMIZER = {"learning_rate": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
             "weight_decay": 0.1}

# float32 compute: the selection and the routing are identical and only
# summation order differs (flash blocks under a mask against a whole
# softmax, sorted groups against a loop over experts); measured 4e-6 on
# logits of size 3, 2e-7 on gradients
F32_TOL = 2e-5
# bfloat16 compute against the float32 reference, logits of size up to 3:
# measured under 0.06 over seeds 0-2 on the tokens whose routing and
# selection are clear.  The seeded faults below move the logits by 0.15 and
# more and fail it.
BF16_LOGITS_TOL = 0.08


pytestmark = pytest.mark.usefixtures("highest_precision")


def is_indexer(path) -> bool:
    return any(getattr(k, "key", None) == "indexer" for k in path)


@kit.once
def make_params(seed=0, cfg=F32):
    """Seeded weights, the indexer's LayerNorm bias not 0."""
    return kit.drawn(
        lambda key: model.init_params(key, cfg), seed,
        [kit.Vector((f"layer_{i}", "attn", "indexer", "k_norm", "bias"), 0.1,
                    key=77 + i, start=0.0) for i in range(cfg.n_layer)])


def make_tokens(seed=0):
    return kit.tokens(1000 + seed, BATCH, SEQ, F32.vocab_size)


@kit.once
def results(which, sizes=SIZES):
    """(logits, L_LM, L_I, L_B, rows sent to the experts, gradients of the
    objective L_LM + L_I + 0.1 L_B in the reference's layout) of the system
    in float32 or of the reference, each one jitted program, computed
    once."""
    params, tokens = make_params(), make_tokens()
    with jax.default_matmul_precision("highest"):
        if which == "system":
            def run(params):
                logits, _ = model.forward(params, tokens[:, :-1], F32)
                (_, parts), grads = jax.value_and_grad(
                    model.loss_fn, has_aux=True)(params, {"tokens": tokens},
                                                 F32)
                return (logits, parts["lm_loss"], parts["indexer_loss"],
                        parts["aux_loss"], parts["expert_rows"],
                        to_reference(grads))
            return jax.jit(run)(params)

        def run(params):
            logits = reference.logits(params, tokens[:, :-1], sizes)
            (_, (xent, kl, balance, rows)), grads = jax.value_and_grad(
                reference.losses, has_aux=True)(params, tokens, sizes)
            return logits, xent, kl, balance, rows, grads
        return jax.jit(run)(to_reference(params))


PARTS = ["logits", "lm_loss", "indexer_loss", "aux_loss", "expert_rows"]


@pytest.mark.parametrize("what", PARTS)
def test_the_forward_pass_matches_the_reference_in_float32(what):
    index = PARTS.index(what)
    got, want = results("system")[index], results("reference")[index]
    assert got.shape == want.shape
    if what == "expert_rows":
        assert (np.asarray(got) == np.asarray(want)).all()
        assert int(got.sum()) == 2 * BATCH * SEQ * 3      # nothing dropped
    else:
        assert max_diff(got, want) < F32_TOL
    if what == "indexer_loss":
        assert float(want) > 0.05       # two layers' KL: there is a loss
    if what == "aux_loss":
        # two layers, each 1 when the load is even and more when it is not
        assert 2.0 < float(want) < 4.0


def test_gradients_of_every_leaf_match():
    got, want = results("system")[5], results("reference")[5]
    flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_want = jax.tree_util.tree_flatten_with_path(want)[0]
    assert [p for p, _ in flat_got] == [p for p, _ in flat_want]
    # embed, norm_f, head; the layers' stack of 17
    assert len(flat_got) == 3 + 17
    for (path, g), (_, w) in zip(flat_got, flat_want):
        assert float(jnp.max(jnp.abs(w))) > 0, path     # nothing is dead
        assert max_diff(g, w) < F32_TOL, path


@pytest.mark.parametrize("part,trains", [("lm_loss", "the rest"),
                                         ("aux_loss", "the rest"),
                                         ("indexer_loss", "the indexer")])
def test_the_indexers_loss_and_the_others_do_not_mix(part, trains):
    """L_LM's and L_B's gradients on the indexer's leaves are exactly zero,
    L_I's on every other leaf is exactly zero, and each trains its own
    (L_B the routers and what lies before them)."""
    params, tokens = make_params(), make_tokens()
    grads = jax.jit(jax.grad(lambda p: model.loss_fn(
        p, {"tokens": tokens}, F32)[1][part]))(params)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    own = [g for path, g in flat
           if is_indexer(path) == (trains == "the indexer")]
    other = [g for path, g in flat
             if is_indexer(path) != (trains == "the indexer")]
    assert len(own) + len(other) == len(flat) and own and other
    assert len([g for path, g in flat if is_indexer(path)]) == 2 * 5
    for g in other:
        assert not np.asarray(g).any()
    if part == "aux_loss":
        # the last layer's experts and the head lie behind every router
        routers = [g for path, g in flat if any(
            getattr(k, "key", None) == "router" for k in path)]
        assert len(routers) == 2 and all(np.asarray(g).any() for g in routers)
        assert not np.asarray(grads["lm_head"]["kernel"]).any()
    else:
        for g in own:
            assert np.asarray(g).any()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bfloat16_compute_stays_close_and_selects_alike(seed):
    params, tokens = make_params(seed), make_tokens(seed)
    logits, stats = jax.jit(lambda p: model.forward(
        p, tokens[:, :-1], BF16))(params)
    ref_params = to_reference(params)
    want = jax.jit(lambda p: reference.logits(
        p, tokens[:, :-1], SIZES))(ref_params)
    # the first 16 positions attend every key they see, and a token whose
    # routing is clear agrees within the band; past them a query whose 16th
    # and 17th index scores tie in bf16 takes another key (16 of up to 64,
    # from scores of 4 heads of 8: near-ties are common at this size), and
    # most still agree
    diff = jnp.max(jnp.abs(logits - want), axis=-1)
    assert float(jnp.mean(diff[:, :16] < BF16_LOGITS_TOL)) > 0.9
    assert float(jnp.mean(diff < BF16_LOGITS_TOL)) > 0.6
    _, (_, kl, _, rows) = jax.jit(lambda p: reference.losses(
        p, tokens, SIZES))(ref_params)
    assert int(jnp.sum(jnp.abs(stats["expert_rows"] - rows))) \
        < 0.1 * int(rows.sum())
    assert abs(float(stats["indexer_loss"]) - float(kl)) < 0.05 * float(kl)


def system_steps(cfg, steps=3, lr=None):
    params, tokens = make_params(cfg=cfg), make_tokens()
    settings = dict(OPTIMIZER, learning_rate=lr or OPTIMIZER["learning_rate"])
    optimizer = reference.adamw(settings)
    step = jax.jit(model.make_train_step(cfg, optimizer))
    opt_state = optimizer.init(params)
    losses, outs = [], []
    for _ in range(steps):
        params, opt_state, out = step(params, opt_state, {"tokens": tokens})
        losses.append((float(out["lm_loss"]), float(out["indexer_loss"]),
                       float(out["aux_loss"])))
        # what the benchmark's `correct` compares holds the indexer's loss
        assert float(out["loss"]) == pytest.approx(sum(losses[-1][:2]),
                                                   abs=1e-6)
        outs.append(out)
    return losses, outs


@kit.once
def reference_steps():
    with jax.default_matmul_precision("highest"):
        return reference.first_losses(
            kit.own(to_reference(make_params())),
            jnp.stack([make_tokens()] * 3), SIZES, OPTIMIZER)


def test_three_steps_match_the_reference_program():
    """L_LM, L_I and L_B of three AdamW steps on the objective, every leaf
    trained, the indexer's by its own loss."""
    losses, outs = system_steps(F32)
    assert np.allclose(losses, reference_steps(), atol=F32_TOL), (
        losses, reference_steps())
    assert losses[2][0] < losses[1][0] < losses[0][0]
    assert losses[2][1] < losses[1][1] < losses[0][1]
    for out in outs:
        assert int(out["rows_held"]) == int(out["expert_rows"].sum())
        assert int(out["moe_overflow_layers"]) == 0
        assert float(out["max_routing_bias"]) == 0.0    # there is no bias


def test_bfloat16_train_step_tracks_the_reference_and_a_tripled_rate_does_not():
    want = [s[0] for s in reference_steps()]
    got = [s[0] for s in system_steps(BF16)[0]]
    assert max(abs(g - w) for g, w in zip(got, want)) < 0.01, (got, want)
    tripled = [s[0] for s in system_steps(BF16, lr=3e-3)[0]]
    assert max(abs(g - w) for g, w in zip(tripled, want)) > 0.05


def test_a_recomputed_share_of_the_experts_is_the_same_step():
    """`remat` on and 4 of the 8 experts held (the cell's form): the
    losses and every gradient are those of the plain step on the same
    share; the recomputed selection is the forward's."""
    cfg = dataclasses.replace(F32, held=(2, 4))
    params, tokens = make_params(cfg=cfg), make_tokens()

    def run(cfg):
        return jax.jit(jax.value_and_grad(lambda p: model.loss_fn(
            p, {"tokens": tokens}, cfg), has_aux=True))(params)

    (plain, parts), grads = run(cfg)
    (again, parts_r), grads_r = run(dataclasses.replace(cfg, remat=True))
    assert float(plain) == pytest.approx(float(again), abs=1e-6)
    assert float(parts["indexer_loss"]) == pytest.approx(
        float(parts_r["indexer_loss"]), abs=1e-6)
    for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_r)):
        assert max_diff(g, r) < 1e-6
    want = jax.jit(lambda p: reference.losses(
        p, tokens, SIZES._replace(held_first=2)))(to_reference(params))
    assert float(parts["lm_loss"]) == pytest.approx(float(want[1][0]),
                                                    abs=F32_TOL)


def test_every_key_selected_is_causal_attention():
    """`topk` >= S: every query attends every key it sees, and the model is
    the same model without a selection (the reference with all keys
    picked)."""
    cfg = dataclasses.replace(F32, index_top_k=SEQ)
    params, tokens = make_params(), make_tokens()
    logits, stats = jax.jit(lambda p: model.forward(
        p, tokens[:, :-1], cfg))(params)
    want = jax.jit(lambda p: reference.logits(
        p, tokens[:, :-1], SIZES._replace(index_top_k=SEQ)))(
            to_reference(params))
    assert max_diff(logits, want) < F32_TOL
    # and it is not the model that selects 16
    assert max_diff(logits, results("system")[0]) > 0.05


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One routed layer with the router's 64 columns, 4 a token: the parts
    that its eight shares of 8 experts give add up to what the uncut
    reference gives for the whole layer (there is no shared expert to count
    once), every share seeing the routing over all 64."""
    cfg = dataclasses.replace(F32, n_experts=64, top_k=4, expert_width=8)
    params = make_params(cfg=cfg)
    p = params["layer_1"]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(9), (BATCH, SEQ, cfg.n_embd))
    total, rows = 0, []
    for first in range(0, 64, 8):
        share = {**p, **{k: p[k][first:first + 8]
                         for k in ("wi_gate", "wi_up", "wo")}}
        y, sent, _ = layers.routed_layer(u, share, model._route(cfg), 64,
                                      (first, 8), layers.swiglu)
        total += y
        rows.append(sent)
    whole = jax.tree.map(lambda leaf: leaf[1],
                         to_reference(params)["layers"])
    want, want_rows, _ = reference.moe(u.reshape(-1, cfg.n_embd), whole,
                                       SIZES._replace(top_k=4))
    assert max_diff(total.reshape(want.shape), want) < F32_TOL
    for sent in rows:
        assert (np.asarray(sent) == np.asarray(want_rows)).all()
    assert int(want_rows.sum()) == BATCH * SEQ * 4
    # and one share alone is not the layer
    assert max_diff(y.reshape(want.shape), want) > 0.01


def test_counts_are_of_the_work_the_model_asks_for():
    cfg = model.KEYE_VL_2_30B_A3B
    assert model.selected_pairs(8192, 2048) == 14_681_088
    assert model.selected_pairs(64, 100) == 64 * 65 // 2
    per_token = model.count_flops_per_token(
        dataclasses.replace(cfg, n_layer=1, vocab_size=0), 8192)
    s, c = 14_681_088 / 8192, 8193 / 2
    matrices = 6 * (18_874_368 + 2048 * 128 + 8 * 3 * 2048 * 768) \
        + 4 * (2048 * 1024 + 2048 * 64 + 2048 * 16)
    pairs = 6 * s * 32 * 256 + 2 * c * 1024 + 4 * s * 1024 + 2 * s * 4096
    assert per_token == pytest.approx(matrices + pairs)
    # the forward's share, per token and layer, in MFLOP: matrices 115 at
    # all 8 experts; the main attention 29, the index scores 8.4, the
    # loss's target 15
    assert round(4 * s * 4096 / 1e6, 1) == 29.4
    assert round(2 * c * 1024 / 1e6, 1) == 8.4
    assert round(2 * s * 4096 / 1e6, 1) == 14.7


# -- seeded faults, in the reference: which of logits, L_I and the gradients
# sees each

def _faulty_route(kind):
    def route(x, p, sizes):
        g = jax.nn.softmax(x @ p["router"], axis=-1)
        k = sizes.top_k - 1 if kind == "one_expert_short" else sizes.top_k
        _, chosen = jax.lax.top_k(g, k)
        chosen = jnp.sum(jax.nn.one_hot(chosen, g.shape[-1]), axis=1)
        picked = g * chosen
        if kind != "not_renormalised":
            picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
        return picked, chosen
    return route


def _no_relu(q, k, w):
    return jnp.sum(w.T[:, :, None] * (q @ k.T), axis=0)


FAULTS = {
    # dense causal attention: every key a query sees
    "selection_ignored": ("select", lambda real: lambda s, seen, k: seen),
    "one_key_short": ("select", lambda real: lambda s, seen, k: real(
        s, seen, k - 1)),
    "relu_dropped": ("index_scores", lambda real: _no_relu),
    "indexer_rope_on_the_whole_head": (
        "rope_first_half", lambda real: reference.rope_halves),
    "qk_norm_left_out": ("rms_norm", lambda real: lambda x, gain, eps:
                         x if x.ndim == 3 else real(x, gain, eps)),
    "weights_not_renormalised": (
        "route", lambda real: _faulty_route("not_renormalised")),
    "one_expert_short": (
        "route", lambda real: _faulty_route("one_expert_short")),
}


@pytest.mark.parametrize("name", sorted(FAULTS))
def test_a_seeded_fault_fails_both_tolerances(monkeypatch, name):
    """The reference with one fault against the system: the logits differ
    by far more than the float32 tolerance and than the bfloat16 band."""
    attr, make = FAULTS[name]
    monkeypatch.setattr(reference, attr, make(getattr(reference, attr)))
    jax.clear_caches()      # `jax.checkpoint` keeps a layer's trace
    tokens = make_tokens()[:, :-1]      # the faulted side, nothing else
    logits = jax.jit(lambda p: reference.logits(p, tokens, SIZES))(
        to_reference(make_params()))
    monkeypatch.undo()
    jax.clear_caches()
    moved = max_diff(logits, results("system")[0])
    assert moved > BF16_LOGITS_TOL > F32_TOL, (name, moved)


def test_an_indexer_that_is_not_detached_is_seen_in_the_gradients(monkeypatch):
    """The indexer reading its input WITH its gradient: logits and both
    losses are as they were, and L_I's gradient reaches the rest of the
    model, which the comparison of every leaf's gradient sees."""
    monkeypatch.setattr(reference, "detached", lambda x: x)
    jax.clear_caches()
    faulty = results.__wrapped__("reference")
    monkeypatch.undo()
    jax.clear_caches()
    good = results("system")
    for i in (0, 1, 2, 3):
        assert max_diff(faulty[i], good[i]) < F32_TOL
    moved = {jax.tree_util.keystr(path): max_diff(g, w) for (path, g), (_, w)
             in zip(jax.tree_util.tree_flatten_with_path(faulty[5])[0],
                    jax.tree_util.tree_flatten_with_path(good[5])[0])}
    assert moved["['embed']"] > 100 * F32_TOL, moved
    assert moved["['layers']['wq']"] > 100 * F32_TOL, moved
    # the indexer's own leaves get what they got
    assert moved["['layers']['iq']"] < F32_TOL, moved
