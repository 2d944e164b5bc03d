"""OLMoE (`ray_tpu/models/olmoe.py`) against the plain reference
(`benchmark/reference/olmoe.py`: float32 `jax.numpy`, attention as a masked
softmax, the experts as a loop over all of them) at a small size on the
CPU: 2 layers, hidden 64, 4 heads of 16, 8 experts with 2 a token, expert
width 32, sequence 64, vocabulary 512, seeded random weights.

The matrices are drawn four times as wide as the published 0.02: at 0.02
and these widths the experts' output is a thousandth of the residual
stream and a routing fault would hide under any tolerance.
"""

import dataclasses

import jax
import jax.numpy as jnp
import model_kit as kit
import numpy as np
import pytest
from model_kit import max_diff

from benchmark.families.olmoe import to_reference
from benchmark.reference import olmoe as reference
from ray_tpu.models import layers, olmoe
from ray_tpu.parallel.sharding import infer_param_logical_dims

F32 = dataclasses.replace(olmoe.OLMOE_TINY, compute_dtype=jnp.float32)
BF16 = olmoe.OLMOE_TINY
SIZES = reference.Sizes(n_head=4, top_k=2, query_block=16)
BATCH, SEQ = 2, 64
OPTIMIZER = {"learning_rate": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
             "weight_decay": 0.1}

# float32 compute: the routing is identical and only summation order
# differs (sorted groups against a loop over experts, flash blocks against
# a whole softmax); measured 3e-7 on logits of size 2, 2e-7 on gradients
F32_TOL = 1e-5
# bfloat16 compute against the float32 reference on one layer, logits of
# size up to 3: measured 0.023 to 0.036 over seeds 0-7 on the tokens whose
# routing is clear (bf16 keeps 8 bits: 2^-8 of 3 is 0.012).  The seeded
# faults below move the logits by 0.54 (one expert a token for two), 1.0
# (renormalised weights) and 1.4 (no QK-norm, two layers) and fail it;
# float8 compute gives nan.
BF16_LOGITS_TOL = 0.06
# router logits closer than this are a tie to bfloat16 arithmetic
ROUTER_GAP = 0.03


pytestmark = pytest.mark.usefixtures("highest_precision")


@kit.once
def make_params(seed=0, cfg=F32):
    return kit.drawn(lambda key: olmoe.init_params(key, cfg), seed)


def make_tokens(seed=0):
    return kit.tokens(1000 + seed, BATCH, SEQ, F32.vocab_size)


@kit.once
def case(kind="plain"):
    """(params, tokens) of a seeded case, made once."""
    params = imbalanced_params() if kind == "imbalanced" else make_params()
    return params, make_tokens()


@kit.once
def results(which, kind="plain"):
    """(logits, objective, parts, gradients in the reference's layout) of
    the system in float32 or of the reference, each one jitted program,
    computed once for all the tests that read them."""
    params, tokens = case(kind)
    with jax.default_matmul_precision("highest"):
        if which == "system":
            def run(params):
                logits, _ = olmoe.forward(params, tokens[:, :-1], F32)
                (objective, parts), grads = jax.value_and_grad(
                    olmoe.loss_fn, has_aux=True)(params, {"tokens": tokens},
                                                 F32)
                return logits, objective, parts, to_reference(grads)
            return jax.jit(run)(params)

        def run(params):
            logits = reference.logits(params, tokens[:, :-1], SIZES)
            (objective, parts), grads = jax.value_and_grad(
                reference.losses, has_aux=True)(params, tokens, SIZES, 1)
            return logits, objective, parts, grads
        return jax.jit(run)(to_reference(params))


def test_logits_match_the_reference_in_float32():
    logits, want = results("system")[0], results("reference")[0]
    assert float(jnp.max(jnp.abs(want))) > 1.0      # not a comparison of 0s
    assert max_diff(logits, want) < F32_TOL


@pytest.mark.parametrize("term", ["loss", "aux_loss", "z_loss",
                                  "max_expert_rows", "objective"])
def test_every_term_of_the_loss_matches(term):
    _, objective, parts, _ = results("system")
    _, want_objective, want, _ = results("reference")
    got = dict(parts, objective=objective)[term]
    want = dict(want, objective=want_objective)[term]
    assert float(got) == pytest.approx(float(want), abs=F32_TOL)
    if term == "objective":
        assert float(objective) == pytest.approx(float(
            parts["loss"] + 0.01 * parts["aux_loss"]
            + 0.001 * parts["z_loss"]), abs=1e-6)


def test_gradients_of_every_leaf_match():
    grads, want = results("system")[3], results("reference")[3]
    got = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(got) == 3 + 2 * 12
    for (path, g), w in zip(got, jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(w))) > 1e-4, path    # a live gradient
        assert max_diff(g, w) < F32_TOL, jax.tree_util.keystr(path)


def run_steps(cfg, params, tokens, steps=3):
    optimizer = reference.adamw(OPTIMIZER)
    step = jax.jit(olmoe.make_train_step(cfg, optimizer))
    opt_state = optimizer.init(params)
    outs = []
    for _ in range(steps):
        params, opt_state, out = step(params, opt_state, {"tokens": tokens})
        outs.append({k: float(v) for k, v in out.items()})
    return params, outs


@kit.once
def reference_steps():
    """Three steps of the reference on the plain case, run once."""
    return run_reference_steps(*case())


def run_reference_steps(params, tokens, steps=3):
    optimizer = reference.adamw(OPTIMIZER)
    step = jax.jit(reference.make_train_step(SIZES, optimizer, 1))
    params = to_reference(params)
    opt_state = optimizer.init(params)
    outs = []
    for _ in range(steps):
        params, opt_state, out = step(params, opt_state, tokens)
        outs.append({k: float(v) for k, v in out.items()})
    return params, outs


def test_three_optimizer_steps_match():
    got_params, got = run_steps(F32, *case())
    want_params, want = reference_steps()
    assert want[2]["loss"] < want[0]["loss"] - 0.01        # it descends
    for g, w in zip(got, want):
        assert set(g) == {"loss", "aux_loss", "z_loss", "max_expert_rows"}
        for key in g:
            assert g[key] == pytest.approx(w[key], abs=F32_TOL), key
    # after three steps Adam's sign-like first updates have moved every
    # leaf by about 3e-3; the two agree far inside that
    for g, w in zip(jax.tree.leaves(to_reference(got_params)),
                    jax.tree.leaves(want_params)):
        assert max_diff(g, w) < 2e-5


def test_the_reference_program_is_the_same_three_steps():
    """`losses_program`, what the benchmark's `correct` runs."""
    params, tokens = case()
    program = reference.losses_program(SIZES, OPTIMIZER, 1)
    losses = jax.jit(program)(to_reference(params),
                              jnp.stack([tokens] * 3))
    _, want = reference_steps()
    assert [float(v) for v in losses] == pytest.approx(
        [w["loss"] for w in want], abs=1e-6)


def reference_router_gaps(ref_params, inputs):
    """Walks the reference layer by layer: for every layer and token the
    gap between the router's k-th and (k+1)-th logits (L, B, S), and the
    layers' choices (L, B*S, experts)."""
    x = ref_params["embed"][inputs]
    gaps, choices = [], []
    for p in ref_params["layers"]:
        h = x + reference.attention(
            reference.rms_norm(x, p["norm1"], SIZES.rms_eps), p, SIZES)
        normed = reference.rms_norm(h, p["norm2"], SIZES.rms_eps).reshape(
            BATCH * SEQ, -1)
        logits, _, chosen = reference.route(normed, p, SIZES)
        top = jnp.sort(logits, axis=-1)[:, ::-1]
        gaps.append((top[:, SIZES.top_k - 1]
                     - top[:, SIZES.top_k]).reshape(BATCH, SEQ))
        choices.append(chosen)
        x = h + reference.moe(normed, p, SIZES)[0].reshape(h.shape)
    return jnp.stack(gaps), jnp.stack(choices)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bfloat16_compute_stays_close_and_routes_alike(seed):
    """bfloat16 compute (f32 master parameters cast once, as the train
    step does) against the float32 reference, on one layer, so that a
    token's logits depend on no other token's routing.  The router's
    softmax and top-k run in float32 in both, but on logits computed from
    bfloat16 activations and weights: a token whose k-th and (k+1)-th
    router logits are closer than that arithmetic resolves may choose the
    other expert, and then its output differs by an expert's whole
    contribution.  At initialisation there are many such tokens (a tenth
    here).  So a token is `clear` where the reference's gap is above
    ROUTER_GAP: clear tokens must choose the reference's experts and their
    logits are held to the tolerance; the others are only held to a loose
    bound (an expert's contribution).  Two layers in bfloat16, where a
    flip reaches later positions through attention, are held to the
    reference by their losses in the next test."""
    one_layer = dataclasses.replace(BF16, n_layer=1)
    params, tokens = make_params(seed, one_layer), make_tokens(seed)
    inputs = tokens[:, :-1]
    logits, _ = jax.jit(lambda p: olmoe.forward(
        layers.cast_weights(p, jnp.bfloat16), inputs, one_layer))(params)
    ref_params = to_reference(params)
    want = reference.logits(ref_params, inputs, SIZES)
    gaps, choices = jax.jit(reference_router_gaps)(ref_params, inputs)
    clear = gaps[0] > ROUTER_GAP                                 # (B, S)
    assert int(jnp.sum(clear)) > BATCH * SEQ * 3 // 4
    diff = jnp.max(jnp.abs(logits - want), axis=-1)              # (B, S)
    assert float(jnp.max(jnp.where(clear, diff, 0.0))) < BF16_LOGITS_TOL
    assert float(jnp.max(diff)) < 1.0

    # the routing from the same input, in bfloat16
    x = ref_params["embed"][inputs]
    p = ref_params["layers"][0]
    h = x + reference.attention(
        reference.rms_norm(x, p["norm1"], SIZES.rms_eps), p, SIZES)
    normed = reference.rms_norm(h, p["norm2"], SIZES.rms_eps).reshape(
        BATCH * SEQ, -1)
    low = (normed.astype(jnp.bfloat16)
           @ p["router"].astype(jnp.bfloat16)).astype(jnp.float32)
    _, low_experts = jax.lax.top_k(jax.nn.softmax(low, axis=-1), SIZES.top_k)
    low_chosen = jnp.sum(jax.nn.one_hot(low_experts, 8), axis=1)
    same = jnp.all(low_chosen == choices[0], axis=-1)
    assert bool(jnp.all(same | ~clear.reshape(-1)))


def test_bfloat16_train_step_tracks_the_reference():
    """Three AdamW steps in bfloat16 compute: the cross-entropy stays
    within 0.01 of the float32 reference's (measured 0.002 to 0.004 at
    step 2 over seeds 0-3; a step that does not descend is off by 0.05)."""
    _, got = run_steps(BF16, *case())
    _, want = reference_steps()
    for g, w in zip(got, want):
        assert g["loss"] == pytest.approx(w["loss"], abs=0.01)


def imbalanced_params():
    """Every token's first choice is expert 0 and experts 5, 6, 7 are
    never chosen: the embeddings share a large common direction u, the
    router's column 0 points along it and columns 5-7 against it."""
    params = make_params()
    e = F32.n_embd
    u = jnp.ones((e,)) / jnp.sqrt(e)
    params["embed_tokens"]["embedding"] = \
        params["embed_tokens"]["embedding"] + 2.0 * u
    for i in range(F32.n_layer):
        router = params[f"layer_{i}"]["moe"]["router"]["kernel"]
        router = router.at[:, 0].set(2.0 * u)
        router = router.at[:, 5:].set(-2.0 * u[:, None])
        params[f"layer_{i}"]["moe"]["router"]["kernel"] = router
    return params


def test_an_imbalanced_router_loses_no_token():
    logits, _, parts, grads = results("system", "imbalanced")
    want_logits, _, want, want_grads = results("reference", "imbalanced")
    # expert 0 got a row of every token: no capacity, nothing dropped
    assert int(parts["max_expert_rows"]) == BATCH * SEQ
    assert int(want["max_expert_rows"]) == BATCH * SEQ
    assert float(parts["aux_loss"]) > 2.0        # far from balanced (1.0)
    for key in ("loss", "aux_loss", "z_loss"):
        assert float(parts[key]) == pytest.approx(float(want[key]),
                                                  abs=F32_TOL)
    assert max_diff(logits, want_logits) < F32_TOL
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        assert max_diff(g, w) < F32_TOL
    # the experts nobody chose get no gradient at all
    idle = grads["layers"][0]["gate"][5:]
    assert float(jnp.max(jnp.abs(idle))) == 0.0


def dense_experts(x, weights, experts, wi_gate, wi_up, wo):
    """Every expert on every token, weight zero where it was not chosen."""
    n = wi_gate.shape[0]
    per_expert = jnp.sum(jax.nn.one_hot(experts, n) * weights[..., None],
                         axis=1)                                  # (T, n)
    h = jax.nn.silu(jnp.einsum("te,new->ntw", x, wi_gate)) \
        * jnp.einsum("te,new->ntw", x, wi_up)
    return jnp.einsum("ntw,nwe,tn->te", h, wo, per_expert)


@pytest.mark.parametrize("routing", ["uniform", "one_expert_takes_all",
                                     "some_experts_idle"])
def test_dispatch_alone_against_the_dense_loop(routing):
    tokens, k, n, e, w = 96, 2, 8, 64, 32
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    x = jax.random.normal(keys[0], (tokens, e))
    wi_gate = jax.random.normal(keys[1], (n, e, w)) * 0.1
    wi_up = jax.random.normal(keys[2], (n, e, w)) * 0.1
    wo = jax.random.normal(keys[3], (n, w, e)) * 0.1
    weights = jax.random.uniform(keys[4], (tokens, k))
    if routing == "uniform":
        first = jax.random.randint(keys[5], (tokens,), 0, n)
        experts = jnp.stack([first, (first + 3) % n], axis=1)
    elif routing == "one_expert_takes_all":
        experts = jnp.stack([jnp.zeros((tokens,), jnp.int32),
                             1 + jnp.arange(tokens) % (n - 1)], axis=1)
    else:
        experts = jnp.stack([jnp.arange(tokens) % 2,
                             2 + jnp.arange(tokens) % 2], axis=1)
    experts = experts.astype(jnp.int32)

    def got(x, weights, wi_gate, wi_up, wo):
        return olmoe.moe_dispatch(
            x, weights, experts, n, layers.grouped_ffn(
                {"wi_gate": wi_gate, "wi_up": wi_up, "wo": wo},
                layers.swiglu))

    y, group_sizes = got(x, weights, wi_gate, wi_up, wo)
    assert int(jnp.sum(group_sizes)) == tokens * k            # dropless
    np.testing.assert_array_equal(
        np.asarray(group_sizes), np.bincount(np.asarray(experts).ravel(),
                                             minlength=n))
    want = dense_experts(x, weights, experts, wi_gate, wi_up, wo)
    assert max_diff(y, want) < F32_TOL
    # and its gradient, through the permutations' hand-written transposes
    args = (x, weights, wi_gate, wi_up, wo)
    g = jax.grad(lambda *a: jnp.sum(got(*a)[0] ** 2), argnums=range(5))(*args)
    gw = jax.grad(lambda *a: jnp.sum(dense_experts(
        a[0], a[1], experts, *a[2:]) ** 2), argnums=range(5))(*args)
    for a, b in zip(g, gw):
        assert max_diff(a, b) < 1e-4 * max(1.0, float(jnp.max(jnp.abs(b))))


def test_rope_is_the_rotation_of_its_formula():
    """Position m turns the pair (x_i, x_{i+D/2}) by m * theta^(-2i/D)."""
    b, s, h, d = 2, 16, 3, 8
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d)))
    got = np.asarray(layers.rope(jnp.asarray(x), jnp.arange(s), 10000.0))
    want = np.zeros_like(x)
    for m in range(s):
        for i in range(d // 2):
            angle = m * 10000.0 ** (-2.0 * i / d)
            c, sn = np.cos(angle), np.sin(angle)
            a, bb = x[:, m, :, i], x[:, m, :, i + d // 2]
            want[:, m, :, i] = a * c - bb * sn
            want[:, m, :, i + d // 2] = a * sn + bb * c
    np.testing.assert_allclose(got, want, atol=1e-5)
    # it is a rotation: norms are kept, position 0 is untouched
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)
    np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-6)


def test_qk_norm_is_over_the_whole_projection():
    """RMSNorm of q over all heads' dimensions together, with its gain,
    before the split into heads: not a norm per head."""
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (2, 5, 64))) * 3
    gain = np.linspace(0.5, 1.5, 64).astype(np.float32)
    got = np.asarray(layers.rms_norm(jnp.asarray(x),
                                     {"scale": jnp.asarray(gain)}, 1e-5))
    want = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5) * gain
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    per_head = x.reshape(2, 5, 4, 16)
    per_head = (per_head / np.sqrt((per_head ** 2).mean(-1, keepdims=True)
                                   + 1e-5)).reshape(2, 5, 64) * gain
    assert np.abs(per_head - want).max() > 0.1


def with_fault(monkeypatch, params, fault):
    """The system with one piece of the mathematics wrong; -> its config."""
    if fault == "one_expert_for_two":        # the tiny top-7 for top-8
        return dataclasses.replace(F32, top_k=1)
    if fault == "renormalised_weights":
        real = olmoe.moe_dispatch
        monkeypatch.setattr(
            olmoe, "moe_dispatch", lambda x, weights, *rest: real(
                x, weights / jnp.sum(weights, -1, keepdims=True), *rest))
    elif fault == "no_qk_norm":
        skipped = {id(params[f"layer_{i}"]["attn"][name])
                   for i in range(F32.n_layer)
                   for name in ("q_norm", "k_norm")}
        real_norm = olmoe.rms_norm
        monkeypatch.setattr(
            olmoe, "rms_norm", lambda x, p, eps: x if id(p) in skipped
            else real_norm(x, p, eps))
    return F32


@pytest.mark.parametrize("fault", ["one_expert_for_two",
                                   "renormalised_weights", "no_qk_norm"])
def test_a_seeded_fault_fails_both_tolerances(monkeypatch, fault):
    params, tokens = case()
    cfg = with_fault(monkeypatch, params, fault)
    # the parameters are closed over, so their dicts keep their identity
    logits, _ = jax.jit(lambda t: olmoe.forward(params, t, cfg))(
        tokens[:, :-1])
    assert max_diff(logits, results("reference")[0]) \
        > 2 * BF16_LOGITS_TOL > F32_TOL


def test_lower_precision_than_stated_fails_the_bfloat16_tolerance():
    params, tokens = case()
    low = jnp.float8_e4m3fn
    logits, _ = jax.jit(lambda p, t: olmoe.forward(
        layers.cast_weights(p, low), t,
        dataclasses.replace(F32, compute_dtype=low)))(params, tokens[:, :-1])
    # nan at that
    assert not max_diff(logits, results("reference")[0]) \
        < 2 * BF16_LOGITS_TOL


def test_parameters_carry_the_logical_dimensions_sharding_reads():
    shapes = jax.eval_shape(
        lambda key: olmoe.init_params(key, olmoe.OlmoeConfig(n_layer=1)),
        jax.random.PRNGKey(0))
    dims = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        keys = tuple(k.key for k in path)
        dims["/".join(keys)] = infer_param_logical_dims(keys, leaf.shape)
    assert dims == {
        "embed_tokens/embedding": ("vocab", "embed"),
        "lm_head/kernel": ("embed", "vocab"),
        "norm_f/scale": (None,),
        "layer_0/input_norm/scale": (None,),
        "layer_0/post_norm/scale": (None,),
        "layer_0/attn/q_norm/scale": (None,),
        "layer_0/attn/k_norm/scale": (None,),
        "layer_0/attn/q_proj/kernel": ("embed", "heads"),
        "layer_0/attn/k_proj/kernel": ("embed", "heads"),
        "layer_0/attn/v_proj/kernel": ("embed", "heads"),
        "layer_0/attn/o_proj/kernel": ("heads", "embed"),
        "layer_0/moe/router/kernel": ("embed", None),
        "layer_0/moe/wi_gate": ("expert", "embed", "mlp"),
        "layer_0/moe/wi_up": ("expert", "embed", "mlp"),
        "layer_0/moe/wo": ("expert", "mlp", "embed"),
    }


def test_counts_at_the_published_widths():
    """625.6 M parameters in one layer with embedding and head; 1,122
    MFLOP a token at 4,096: 6 x (head 103.0 M + attention 16.8 M + router
    0.13 M + 8 experts of 6.29 M) + 12 x 2048 x 4096."""
    cfg = olmoe.OlmoeConfig(n_layer=1)
    shapes = jax.eval_shape(lambda key: olmoe.init_params(key, cfg),
                            jax.random.PRNGKey(0))
    assert olmoe.num_params(shapes) == 625_616_896
    n = 50304 * 2048 + 4 * 2048 ** 2 + 2048 * 64 + 8 * 3 * 2048 * 1024
    assert olmoe.count_flops_per_token(cfg, 4096) \
        == 6 * n + 12 * 2048 * 4096 == 1_122_238_464


def test_the_step_runs_sharded_over_a_mesh():
    """fsdp=2 x ep=2 on four virtual devices: `param_shardings` lays the
    experts' stacks over ep and the step gives the single-device losses."""
    from ray_tpu.parallel.context import use_mesh
    from ray_tpu.parallel.sharding import ShardingConfig, param_shardings

    layout = ShardingConfig(fsdp=2, ep=2)
    mesh = layout.build_mesh(jax.devices()[:4])
    params, tokens = make_params(), make_tokens()
    _, want = run_steps(F32, params, tokens, steps=2)
    shardings = param_shardings(params, layout, mesh)
    assert shardings["layer_0"]["moe"]["wi_gate"].spec[0] == "ep"
    sharded = jax.device_put(params, shardings)
    optimizer = reference.adamw(OPTIMIZER)
    opt_state = optimizer.init(sharded)
    batch = {"tokens": jax.device_put(
        tokens, layout.named_sharding(mesh, "batch", None))}
    with use_mesh(mesh):
        step = jax.jit(olmoe.make_train_step(F32, optimizer))
        for w in want:
            sharded, opt_state, out = step(sharded, opt_state, batch)
            assert float(out["loss"]) == pytest.approx(w["loss"], abs=1e-4)
