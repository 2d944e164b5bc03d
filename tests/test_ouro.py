"""The `ouro` model (`ray_tpu/models/ouro.py`: one stack of sandwich-normed
layers walked T times over the same weights by `layers.trunk`, an exit gate
after every walk, the T heads' loss weighted by the exit distribution)
against the plain reference (`benchmark/reference/ouro.py`: float32
`jax.numpy`, a Python loop over walks and layers, the gate and the T
cross-entropies written out) at a small size on the CPU: two layers, hidden
64, 4 heads of 16, feed-forward 160, vocabulary 512, T = 3, sequences of
64, seeded random weights.

The matrices are drawn five times as wide as the assumed 0.02 and the
gate's bias is not 0: at 0.02 and these widths an operator's output is a
thousandth of the residual stream, every gate reads a half, and a fault
would hide under any tolerance.
"""

import dataclasses

import jax
import jax.numpy as jnp
import model_kit as kit
import numpy as np
import optax
import pytest
from model_kit import max_diff

from benchmark.families.ouro import from_reference, to_reference
from benchmark.reference import ouro as reference
from ray_tpu.models import layers, ouro as model
from ray_tpu.parallel.sharding import infer_param_logical_dims

BF16 = model.OURO_TINY
F32 = dataclasses.replace(BF16, compute_dtype=jnp.float32)
SIZES = reference.Sizes(n_head=4, n_kv_head=4, n_walk=3, query_block=16)
BATCH, SEQ = 2, 64
OPTIMIZER = {"learning_rate": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
             "weight_decay": 0.1}

# float32 compute: only summation order differs (flash blocks against a
# whole softmax, the chunked loss against a log-softmax, p in logarithms
# against products); measured 2e-6 on states of size 1, 1e-6 of a
# gradient's largest entry
F32_TOL = 2e-5
# bfloat16 compute against the float32 reference, relative Frobenius error
# of a walk's normed state: measured 0.008 to 0.013 over the walks and seeds
# 0-2; the seeded faults below read 0.2 and more where they touch a state
BF16_STATE_TOL = 0.03
# the same of the T means of the exit distribution: measured under 0.002;
# a softmax for the cumulative product reads 0.3
EXIT_TOL = 0.01
# the objective, bfloat16 against float32: measured under 0.001
LOSS_TOL = 0.003


pytestmark = pytest.mark.usefixtures("highest_precision")


@kit.once
def make_params(seed=0, cfg=F32):
    return kit.drawn(lambda key: model.init_params(key, cfg), seed,
                     [kit.Vector(("exit_gate", "bias"), start=0.3)],
                     factor=5.0)


def make_tokens(seed=0):
    return kit.tokens(1000 + seed, BATCH, SEQ, F32.vocab_size)


def relative(got, want):
    """|got - want| / |want|, Frobenius norms over all but the first
    axis."""
    axes = tuple(range(1, jnp.ndim(want)))
    norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(
        jnp.asarray(x, jnp.float32)), axis=axes))
    return np.asarray(norm(jnp.asarray(got, jnp.float32) - want)
                      / norm(want))


def reference_states(params, inputs):
    """-> (states (T, B, S, E), p (T, B, S)) by the reference."""
    walked = [reference.walks(params, row, SIZES) for row in inputs]
    states = jnp.stack([jnp.stack(w) for w in walked], axis=1)
    p = jnp.stack([jnp.stack(reference.exit_distribution(params, w))
                   for w in walked], axis=1)
    return states, p


@kit.once
def results(which):
    """(states, logits, exit distribution, the objective's parts, its
    gradients in the reference's layout) of the system in float32 or of the
    reference, each one jitted program, computed once."""
    params, tokens = make_params(), make_tokens()
    with jax.default_matmul_precision("highest"):
        if which == "system":
            def run(params):
                states, log_p = model.hidden(params, tokens[:, :-1], F32)
                logits, p = model.forward(params, tokens[:, :-1], F32)
                (_, parts), grads = jax.value_and_grad(
                    model.loss_fn, has_aux=True)(params, {"tokens": tokens},
                                                 F32)
                return states, logits, p, parts, to_reference(grads)
            return jax.jit(run)(params)

        def run(params):
            states, p = reference_states(params, tokens[:, :-1])
            (_, parts), grads = jax.value_and_grad(
                reference.losses, has_aux=True)(params, tokens, SIZES)
            return (states, reference.logits(params, tokens[:, :-1], SIZES),
                    p, parts, grads)
        return jax.jit(run)(to_reference(make_params()))


PARTS = ["states", "logits", "exit"]


@pytest.mark.parametrize("what", PARTS)
def test_the_forward_pass_matches_the_reference_in_float32(what):
    index = PARTS.index(what)
    got, want = results("system")[index], results("reference")[index]
    assert got.shape == want.shape and got.shape[0] == F32.n_walk
    assert max_diff(got, want) < F32_TOL * max(
        1.0, float(jnp.max(jnp.abs(want))))


def test_the_exit_distribution_is_one():
    p = results("system")[2]
    np.testing.assert_allclose(np.asarray(jnp.sum(p, axis=0)), 1.0,
                               rtol=1e-6)
    # the gates differ from token to token and from walk to walk
    assert float(jnp.std(p[0])) > 0.01


@pytest.mark.parametrize("part", ["loss", "xent", "exit", "entropy"])
def test_the_objective_matches_the_reference_in_float32(part):
    got, want = results("system")[3][part], results("reference")[3][part]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-6)


def test_the_gradients_match_the_reference_in_float32():
    grads, wants = results("system")[4], results("reference")[4]
    assert jax.tree.structure(grads) == jax.tree.structure(wants)
    for (path, want), got in zip(
            jax.tree_util.tree_flatten_with_path(wants)[0],
            jax.tree.leaves(grads)):
        assert max_diff(got, want) < F32_TOL * max(
            1e-2, float(jnp.max(jnp.abs(want)))), jax.tree_util.keystr(path)
    # the gate is trained by the loss
    assert float(jnp.max(jnp.abs(wants["gate_w"]))) > 1e-5
    assert abs(float(wants["gate_b"])) > 1e-6


def test_the_reference_layout_goes_there_and_back():
    params = make_params()
    back = from_reference(to_reference(params))
    assert jax.tree.structure(back) == jax.tree.structure(params)
    assert max(jax.tree.leaves(jax.tree.map(max_diff, back, params))) == 0.0


def train(step, params, opt_state, batches):
    out = []
    for tokens in batches:
        params, opt_state, parts = step(params, opt_state, tokens)
        out.append(float(parts["loss"]))
    return out


@pytest.mark.parametrize("compute", ["float32", "bfloat16"])
def test_three_adamw_steps_follow_the_reference(compute):
    cfg = F32 if compute == "float32" else BF16
    batches = [make_tokens(seed) for seed in range(3)]
    optimizer = reference.adamw(OPTIMIZER)
    params = make_params()
    step = jax.jit(model.make_train_step(cfg, optimizer))
    got = train(lambda p, o, t: step(p, o, {"tokens": t}), params,
                optimizer.init(params), batches)
    want = reference.first_losses(kit.own(to_reference(make_params())),
                                  jnp.stack(batches), SIZES, OPTIMIZER)
    tolerance = 2e-5 if compute == "float32" else LOSS_TOL
    assert max(abs(g - w) for g, w in zip(got, want)) < tolerance, (got, want)
    assert got[2] < got[0] - 0.01       # it learns the batch's statistics


# -- the walk ----------------------------------------------------------------

def old_trunk(params, tokens, layer, cfg):
    """`layers.trunk` as it was before a walk could be repeated (PR 49),
    word for word."""
    with jax.named_scope("embed"):
        x = params["embed_tokens"]["embedding"][tokens].astype(
            cfg.compute_dtype)
    stack = [params[f"layer_{i}"] for i in range(cfg.n_layer)]
    if cfg.remat:
        layer = layers.checkpoint_layer(
            layer, stack=[(x, p, cfg) for p in stack], static_argnums=(2,),
            behind=jax.ShapeDtypeStruct(
                (cfg.loss_chunk_rows, cfg.vocab_size), jnp.float32))
    seconds = []
    for p in stack:
        x, second = layer(x, p, cfg)
        if second is not None:
            seconds.append(second)
    return layers.rms_norm(x, params["norm_f"], cfg.rms_eps), seconds


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("compute", ["float32", "bfloat16"])
def test_one_walk_is_the_old_single_walk_bit_for_bit(remat, compute):
    cfg = dataclasses.replace(F32 if compute == "float32" else BF16,
                              remat=remat)
    params = layers.cast_weights(make_params(), cfg.compute_dtype)
    tokens = make_tokens()[:, :-1]

    def new(params):
        states, _ = layers.trunk(params, tokens, model._layer, cfg, walks=1)
        return states[0]

    def old(params):
        return old_trunk(params, tokens, model._layer, cfg)[0]

    square = lambda f: lambda p: jnp.sum(jnp.square(f(p).astype(jnp.float32)))
    for f in (new, old):
        f.out = jax.jit(f)(params)
        f.grads = jax.jit(jax.grad(square(f)))(params)
    np.testing.assert_array_equal(np.asarray(new.out, np.float32),
                                  np.asarray(old.out, np.float32))
    for got, want in zip(jax.tree.leaves(new.grads),
                         jax.tree.leaves(old.grads)):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


def test_no_walks_asked_for_is_one_walk_and_counts_nothing(monkeypatch):
    counted = []
    monkeypatch.setattr(layers.tracing, "count",
                        lambda name, n=1: counted.append((name, n)))
    params, tokens = make_params(), make_tokens()[:, :-1]
    x, seconds = layers.trunk(params, tokens, model._layer, F32)
    assert x.shape == (BATCH, SEQ, F32.n_embd) and seconds == []
    assert not [name for name, _ in counted if name.startswith("loop.")]
    states, _ = layers.trunk(params, tokens, model._layer, F32, walks=1)
    assert max_diff(states[0], x) == 0.0


def test_the_walk_counts_itself(monkeypatch):
    counted = {}
    monkeypatch.setattr(
        layers.tracing, "count",
        lambda name, n=1: counted.update({name: counted.get(name, 0) + n}))
    model.hidden(make_params(), make_tokens()[:, :-1], F32)
    assert counted["loop.walks"] == 3 and counted["loop.layer_calls"] == 6
    assert counted["loop.layer_traces"] == 6     # the walks are unrolled
    # one plan over the T x n calls: what three visits of a layer keep
    assert counted["remat.names_declined"] == 3


def test_the_budget_reckons_every_visit_of_a_layer():
    """`keep_plan` over T x n calls: the stream and the kernel's residuals
    of every call, T times what one walk keeps."""
    params, tokens = make_params(cfg=BF16), make_tokens()[:, :-1]
    plans = []
    for walks in (1, 3):
        cfg = dataclasses.replace(BF16, n_walk=walks)
        with layers.assume_memory_limit(1 << 30, plans):
            jax.eval_shape(lambda p: model.hidden(p, tokens, cfg), params)
    one, three = plans
    assert three["already"] == 3 * one["already"] > 0
    assert {k: 3 * v for k, v in one["marked"].items()} == three["marked"]
    assert three["reserve"] == one["reserve"]


# -- the rows' losses ---------------------------------------------------------

@pytest.mark.parametrize("chunk_rows", [8, 12, 1000])
def test_the_rows_losses_are_the_dense_cross_entropies(chunk_rows):
    """The weighted form of the head's loss, as the objective calls it: the
    rows it hands back, and the gradient of their weighted sum with respect
    to the states, the head and the WEIGHTS (which is how the gate learns),
    against dense logits."""
    V, E = 48, 16
    keys = jax.random.split(jax.random.PRNGKey(4), 4)
    x = jax.random.normal(keys[0], (3, 2, 16, E))
    targets = jax.random.randint(keys[1], (3, 2, 16), 0, V)
    head = {"kernel": jax.random.normal(keys[2], (E, V))}
    weights = jax.random.uniform(keys[3], (3, 2, 16))

    def dense(x, head):
        logp = jax.nn.log_softmax(x @ head["kernel"], axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]

    def got(x, head, weights):
        return layers.head_and_weighted_loss(x, head, targets, weights,
                                             chunk_rows)

    rows = got(x, head, weights)[1]
    assert rows.shape == targets.shape
    assert max_diff(rows, dense(x, head)) < 1e-5
    mean = lambda f: lambda x, head, w: f(x, head, w) / targets.size
    for g, want in zip(
            jax.tree.leaves(jax.grad(mean(
                lambda *a: got(*a)[0]), (0, 1, 2))(x, head, weights)),
            jax.tree.leaves(jax.grad(mean(
                lambda x, head, w: jnp.sum(w * dense(x, head))),
                (0, 1, 2))(x, head, weights))):
        assert max_diff(g, want) < 1e-5
    # the rows carry no gradient, and say so: nothing comes through them
    through_rows = jax.grad(lambda x: jnp.sum(got(x, head, weights)[1]))(x)
    assert max_diff(through_rows, jnp.zeros_like(x)) == 0.0
    # summed, they are what `head_and_loss` gives
    assert float(jnp.mean(rows[0])) == pytest.approx(float(
        layers.head_and_loss(x[0], head, targets[0], chunk_rows)), rel=1e-6)
    lowered = jax.jit(got).lower(x, head, weights).as_text(debug_info=True)
    assert "head_and_loss" in lowered


# -- the gradient of a weight used T times ------------------------------------

def test_a_weights_gradient_summed_over_its_uses_in_bfloat16(monkeypatch):
    """`layers.train_step` casts the matrices once, so the cotangents of a
    weight's T uses meet in bfloat16 before the float32 master sees them.
    Against the same step with the cast inside every call of a layer (each
    use's cotangent converted to float32 first, the sum made there), both
    against the float32 reference: the reading is the relative error of
    each layer matrix's gradient, which the sum in bfloat16 does not raise
    beyond the rounding a single use has (measured over the 14 layer
    matrices: 0.0221 to 0.0272 cast once, 0.0221 to 0.0272 cast per use, the
    two alike to three digits matrix by matrix)."""
    tokens = {"tokens": make_tokens()}
    params = make_params()
    want = results("reference")[4]["layers"]

    def errors(loss):
        grads = to_reference(jax.jit(jax.grad(loss))(params))["layers"]
        # the reference's layout stacks the layers: a matrix a layer and name
        return [float(error) for k in sorted(want) if want[k].ndim == 3
                for error in relative(grads[k], want[k])]

    once = errors(lambda params: model.loss_fn(
        layers.cast_weights(params, jnp.bfloat16), tokens, BF16)[0])
    layer = model._layer
    monkeypatch.setattr(model, "_layer", lambda x, p, cfg: layer(
        x, layers.cast_weights(p, jnp.bfloat16), cfg))
    jax.clear_caches()
    per_use = errors(lambda params: model.loss_fn(
        {k: v if k.startswith("layer_")
         else layers.cast_weights(v, jnp.bfloat16)
         for k, v in params.items()}, tokens, BF16)[0])
    jax.clear_caches()
    print("cast once", once, "cast per use", per_use)
    assert len(once) == 14
    assert max(once) < 0.03, once
    assert max(once) < 1.2 * max(per_use), (once, per_use)


# -- seeded faults: each fails at least one check -----------------------------

def unfed_trunk(params, tokens, layer, cfg, walks=None):
    """The final norm read by head and gate and NOT fed back: walk t + 1
    starts from the stream as the layers left it."""
    x = params["embed_tokens"]["embedding"][tokens].astype(cfg.compute_dtype)
    if cfg.remat:       # as `trunk`: the cell's size does not fit without
        layer = layers.checkpoint_layer(layer, static_argnums=(2,))
    states = []
    for _ in range(walks):
        for i in range(cfg.n_layer):
            x, _ = layer(x, params[f"layer_{i}"], cfg)
        states.append(layers.rms_norm(x, params["norm_f"], cfg.rms_eps))
    return jnp.stack(states), []


def unsandwiched_layer(x, p, cfg):
    """`ouro._layer` without the norm on the attention's result."""
    u = layers.rms_norm(x, p["input_norm"], cfg.rms_eps)
    x = x + model._attention(u, p["attn"], cfg)
    u = layers.rms_norm(x, p["post_norm"], cfg.rms_eps)
    f = layers.dense_ffn(u, p["mlp"], layers.swiglu)
    return x + layers.rms_norm(f, p["post_norm_2"], cfg.rms_eps), None


def softmax_exit(states, gate):
    z = jnp.matmul(states, gate["kernel"].astype(states.dtype),
                   preferred_element_type=jnp.float32)[..., 0] + gate["bias"]
    return jax.nn.log_softmax(z, axis=0)


def cast_through_float8(params, dtype, _cast=layers.cast_weights):
    """`layers.cast_weights` with every matrix rounded through
    float8_e4m3fn first."""
    return _cast(jax.tree.map(
        lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
        if x.ndim >= 2 else x, params), dtype)


# what is changed: fields of the configuration, or (module, name, value)
FAULTS = {
    "sound": {},
    "a_walk_short": {"cfg": {"n_walk": -1}},        # one fewer
    "norm_not_fed_back": {"patch": (model, "trunk", unfed_trunk)},
    "sandwich_norm_dropped": {"patch": (model, "_layer",
                                        unsandwiched_layer)},
    "entropy_dropped": {"cfg": {"entropy_weight": 0.0}},
    "softmax_for_the_product": {"patch": (model, "_exit_log_probs",
                                          softmax_exit)},
    "matrices_through_float8": {"patch": (layers, "cast_weights",
                                          cast_through_float8)},
}


def faulty(cfg, fault):
    """``cfg`` with the fault's fields."""
    fields = dict(FAULTS[fault].get("cfg", {}))
    if fields.get("n_walk") == -1:
        fields["n_walk"] = cfg.n_walk - 1
    return dataclasses.replace(cfg, **fields)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_seeded_fault_fails_a_check(fault, monkeypatch):
    """The checks of the cell's `correct` (`benchmark/families/ouro.py`):
    each walk's normed state, the means of the exit distribution, the
    objective, in bfloat16 against the float32 reference.  The sound model
    passes all three; every fault fails one at least."""
    cfg = faulty(BF16, fault)
    if "patch" in FAULTS[fault]:
        monkeypatch.setattr(*FAULTS[fault]["patch"])
        jax.clear_caches()
    tokens = make_tokens()
    params = layers.cast_weights(make_params(), jnp.bfloat16)
    states, log_p = model.hidden(params, tokens[:, :-1], cfg)
    loss, _ = model.loss_fn(params, {"tokens": tokens}, cfg)
    want_states, want_p, want_parts = (results("reference")[i]
                                       for i in (0, 2, 3))
    short = want_states.shape[0] - states.shape[0]
    if short:       # a walk short: its last state stands for the missing one
        states = jnp.concatenate([states, states[-1:]])
        log_p = jnp.concatenate([log_p, log_p[-1:]])
    means = lambda p: jnp.mean(p, axis=(1, 2))
    failed = {
        "states": float(max(relative(states, want_states))) > BF16_STATE_TOL,
        "exit": float(relative(means(jnp.exp(log_p))[None],
                               means(want_p)[None])[0]) > EXIT_TOL,
        "loss": abs(float(loss) - float(want_parts["loss"])) > LOSS_TOL,
    }
    if fault == "sound":
        assert not any(failed.values()), failed
    else:
        assert any(failed.values()), failed
    jax.clear_caches()


# -- the counts of the family -------------------------------------------------

def test_flops_count_the_work_of_every_walk():
    one = dataclasses.replace(model.OURO_2_6B, n_layer=6, n_walk=1)
    four = dataclasses.replace(one, n_walk=4)
    assert model.count_flops_per_token(four, 8192) \
        == 4 * model.count_flops_per_token(one, 8192)
    # ISSUE 50's count: 12.2 GFLOP a token at six layers
    assert model.count_flops_per_token(four, 8192) == pytest.approx(
        12.2e9, rel=0.01)
    assert model.num_params(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), one))) \
        == 6 * (51_380_224 + 4 * 2048) + 2 * 49152 * 2048 + 2048 + 2049


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
    assert dims["layer_1/mlp/gate_proj/kernel"] == ("embed", "mlp")
    assert dims["layer_1/mlp/down_proj/kernel"] == ("mlp", "embed")
    # the sandwich's two more norms a layer, and the gate: (E, 1) is cut
    # along the stream's width alone, its one bias not at all
    for norm in ("input_norm", "input_norm_2", "post_norm", "post_norm_2"):
        assert dims[f"layer_0/{norm}/scale"] == (None,)
    assert dims["exit_gate/kernel"] == ("embed", None)
    assert shapes["exit_gate"]["kernel"].shape == (64, 1)
    assert dims["exit_gate/bias"] == (None,)


def test_one_step_of_optax_moves_every_leaf():
    """Every leaf, the gate's and the four norms' among them, is trained."""
    params = make_params()
    optimizer = optax.adamw(1e-3)
    step = jax.jit(model.make_train_step(F32, optimizer))
    moved, _, _ = step(params, optimizer.init(params),
                       {"tokens": make_tokens()})
    for (path, before), after in zip(
            jax.tree_util.tree_flatten_with_path(params)[0],
            jax.tree.leaves(moved)):
        assert max_diff(before, after) > 0, jax.tree_util.keystr(path)
