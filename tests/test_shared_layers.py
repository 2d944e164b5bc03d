"""What the routed models share since PR 46, each piece alone against a
plain form written here: the routed layer (`models/layers.py:routed_layer`)
against a loop over tokens and their choices, the walk over a decoder's
layers (`layers.trunk`) against the loop it replaces, the routers' account
(`ops/moe.py:routing_account`) on rows made by hand, and the head with its
chunked loss (`layers.head_and_loss`) against dense logits.  Float32, small
sizes, the CPU.  Since PR 50 the walk can be repeated and the chunked loss
can hand back its rows: the five models that walk once are held, bit for
bit, to the walk as it was before.  Since PR 53 the chunked loss is one
rule that forms its gradient as it walks (`layers.chunked_xent`): the six
families are held to the parent's loss to a rounding.
"""

import dataclasses
import functools
import itertools

import jax
import jax.numpy as jnp
import model_kit as kit
import numpy as np
import pytest
from model_kit import max_diff

from ray_tpu.models import (
    deepseek_v3,
    keye_vl,
    layers,
    lfm2_moe,
    mellum,
    nemotron_h,
    olmoe,
    ouro,
    sdar,
)
from ray_tpu.ops import moe
from ray_tpu.ops.moe import ROUTING_BIAS

B, S, E = 2, 16, 16
N, K = 8, 2
WIDTH, SHARED_WIDTH = 24, 40        # no whole number of lane tiles
TOL = 2e-5


pytestmark = pytest.mark.usefixtures("highest_precision")


# -- the routed layer ---------------------------------------------------------

FFNS = {"swiglu": (layers.swiglu, ("wi_gate", "wi_up", "wo"),
                   ("gate_proj", "up_proj", "down_proj")),
        "relu2": (layers.relu2, ("wi_up", "wo"), ("up_proj", "down_proj"))}


def softmax_route(xt, router):
    probs = jax.nn.softmax((xt @ router["kernel"]).astype(jnp.float32), -1)
    return jax.lax.top_k(probs, K)


ROUTES = {
    "sigmoid_bias": functools.partial(moe.sigmoid_route, top_k=K, eps=1e-20,
                                      scale=2.5),
    "sigmoid_plain": functools.partial(moe.sigmoid_route, top_k=K, eps=None,
                                       scale=1.0),
    "softmax": softmax_route,
}


def routed_params(route, ffn, shared, held, width=WIDTH, seed=0):
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 12))
    normal = lambda *shape: jax.random.normal(next(keys), shape) * 0.3
    first, count = held or (0, N)
    p = {"router": {"kernel": normal(E, N)}}
    if route == "sigmoid_bias":
        p["router"][ROUTING_BIAS] = normal(N) * 0.5
    for name in FFNS[ffn][1]:
        p[name] = normal(count, width, E) if name == "wo" \
            else normal(count, E, width)
    if shared:
        p["shared"] = {
            name: {"kernel": normal(SHARED_WIDTH, E) if name == "down_proj"
                   else normal(E, SHARED_WIDTH)} for name in FFNS[ffn][2]}
    return p


def plain_ffn(ffn, x, weights):
    """One token through one expert, as the model files' docstrings write
    it."""
    if ffn == "swiglu":
        gate, up, down = weights
        return (jax.nn.silu(x @ gate) * (x @ up)) @ down
    up, down = weights
    return jnp.square(jnp.maximum(x @ up, 0)) @ down


def scores_of(route, xt, router):
    logits = xt @ router["kernel"]
    return jax.nn.softmax(logits, -1) if route == "softmax" \
        else jax.nn.sigmoid(logits)


def choices(route, xt, router):
    """(T, K) numpy: each token's experts, best first."""
    picks = scores_of(route, xt, router) + router.get(ROUTING_BIAS, 0.0)
    return np.argsort(-np.asarray(picks), axis=-1, kind="stable")[:, :K]


def token_loop(x, p, chosen, route, ffn, held):
    """The layer as a loop over tokens and their choices; ``chosen`` is
    concrete, the weights are differentiated through."""
    xt = x.reshape(-1, E)
    first, count = held or (0, N)
    scores = scores_of(route, xt, p["router"])
    out = []
    for t in range(xt.shape[0]):
        picked = jnp.stack([scores[t, e] for e in chosen[t]])
        if route == "sigmoid_bias":
            picked = picked / (jnp.sum(picked) + 1e-20) * 2.5
        y = jnp.zeros((E,))
        for w, e in zip(picked, chosen[t]):
            if first <= e < first + count:
                y = y + w * plain_ffn(ffn, xt[t], [
                    p[name][e - first] for name in FFNS[ffn][1]])
        if "shared" in p:
            y = y + plain_ffn(ffn, xt[t], [
                p["shared"][name]["kernel"] for name in FFNS[ffn][2]])
        out.append(y)
    return jnp.stack(out).reshape(x.shape)


ROUTED_CASES = list(itertools.product(
    ROUTES, FFNS, ("shared", "alone"), ("all_held", "a_share")))


@pytest.mark.parametrize("route, ffn, shared, share", ROUTED_CASES)
def test_the_routed_layer_is_the_loop_over_tokens(route, ffn, shared, share):
    held = None if share == "all_held" else (2, 4)
    p = routed_params(route, ffn, shared == "shared", held)
    x = jax.random.normal(jax.random.PRNGKey(7), (B, S, E))
    chosen = choices(route, x.reshape(-1, E), p["router"])

    def got(x, p):
        return layers.routed_layer(x, p, ROUTES[route], N, held,
                                   FFNS[ffn][0])

    y, rows = got(x, p)
    assert max_diff(y, token_loop(x, p, chosen, route, ffn, held)) < TOL
    # over ALL the experts, whatever is held
    np.testing.assert_array_equal(
        np.asarray(rows), np.bincount(chosen.ravel(), minlength=N))
    loss = lambda f: lambda x, p: jnp.sum(f(x, p) ** 2)
    grads = jax.grad(loss(lambda x, p: got(x, p)[0]), (0, 1))(x, p)
    wants = jax.grad(loss(lambda x, p: token_loop(
        x, p, chosen, route, ffn, held)), (0, 1))(x, p)
    for (path, want), g in zip(
            jax.tree_util.tree_flatten_with_path(wants)[0],
            jax.tree.leaves(grads)):
        if path[-1] != jax.tree_util.DictKey(ROUTING_BIAS):  # picks only
            assert max_diff(g, want) < 1e-4 * max(
                1.0, float(jnp.max(jnp.abs(want)))), jax.tree_util.keystr(path)


@pytest.mark.parametrize("ffn", sorted(FFNS))
def test_a_widened_stack_gives_the_unpadded_result(ffn, monkeypatch):
    """24 wide runs 128 wide, whole lane tiles and no more
    (`layers._widened`; 16 of hidden width is a shape the grouped kernels
    decline, so the products are `ragged_dot`'s and seen here): the zeros
    add nothing to the output and take no gradient, and the parameters
    keep their shape."""
    p = routed_params("sigmoid_bias", ffn, True, (0, 4))
    x = jax.random.normal(jax.random.PRNGKey(8), (B, S, E))

    def step(x, p):
        def loss(x, p):
            y, _ = layers.routed_layer(x, p, ROUTES["sigmoid_bias"], N,
                                       (0, 4), FFNS[ffn][0])
            return jnp.sum(y ** 2), y
        return jax.value_and_grad(loss, (0, 1), has_aux=True)(x, p)

    seen = []
    real = jax.lax.ragged_dot
    monkeypatch.setattr(jax.lax, "ragged_dot", lambda a, w, sizes: (
        seen.append(w.shape), real(a, w, sizes))[1])
    (_, y), grads = step(x, p)
    assert {128} == {s[2] for s in seen if s[1] == E} \
        == {s[1] for s in seen if s[2] == E}
    seen.clear()
    monkeypatch.setattr(layers, "_widened", lambda w, axis: w)
    (_, y0), grads0 = step(x, p)
    assert {WIDTH} == {s[2] for s in seen if s[1] == E}
    assert max_diff(y, y0) < 1e-6
    assert jax.tree.map(jnp.shape, grads) == jax.tree.map(jnp.shape, grads0)
    assert max(jax.tree.leaves(jax.tree.map(max_diff, grads, grads0))) < 1e-5


@pytest.mark.parametrize("width, run", [
    (768, 768), (896, 896), (1024, 1024), (1536, 1536), (1856, 1920),
    (300, 384)])
def test_a_width_is_widened_to_whole_lane_tiles_and_no_further(width, run):
    """The published widths: those of whole lane tiles are the stack
    itself (mellum2's 896, that XLA's kernel wanted at 1,024: no pad of
    width nothing for XLA to find), nemotron's 1,856 runs 1,920 wide and
    not 2,048."""
    up, down = jnp.ones((2, E, width)), jnp.ones((2, width, E))
    assert layers._widened(up, 2).shape == (2, E, run)
    assert layers._widened(down, 1).shape == (2, run, E)
    assert (layers._widened(up, 2) is up) == (width == run)


# -- the walk over the layers -------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Walked:
    n_layer: int = 4
    vocab_size: int = 32
    remat: bool = False
    compute_dtype: type = jnp.float32
    loss_chunk_rows: int = 8
    rms_eps: float = 1e-5


def walked_layer(x, p, cfg):
    """A toy layer: every other one has a second result."""
    x = x + jnp.tanh(x @ p["w"])
    return x, (jnp.sum(x, axis=(0, 1)) if "counted" in p else None)


def walked_params(cfg):
    keys = jax.random.split(jax.random.PRNGKey(1), cfg.n_layer + 1)
    params = {"embed_tokens": {"embedding": jax.random.normal(
        keys[0], (cfg.vocab_size, E))},
        "norm_f": {"scale": jnp.full((E,), 1.5)}}
    for i in range(cfg.n_layer):
        params[f"layer_{i}"] = {"w": jax.random.normal(keys[1 + i],
                                                       (E, E)) * 0.3}
        if i % 2:
            params[f"layer_{i}"]["counted"] = jnp.zeros(())
    return params


@pytest.mark.parametrize("remat", [False, True])
def test_the_walk_is_the_loop_it_replaces(remat):
    cfg = Walked(remat=remat)
    params = walked_params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, 32)

    def loop(params):
        x = params["embed_tokens"]["embedding"][tokens]
        seconds = []
        for i in range(cfg.n_layer):
            x, second = walked_layer(x, params[f"layer_{i}"], cfg)
            seconds.append(second)
        return layers.rms_norm(x, params["norm_f"], cfg.rms_eps), seconds

    x, seconds = jax.jit(lambda p: layers.trunk(
        p, tokens, walked_layer, cfg))(params)
    want, want_seconds = loop(params)
    # the layers without a second result are left out, the order kept
    assert len(seconds) == 2 and want_seconds[0] is None
    assert max_diff(x, want) < 1e-6
    for got, wanted in zip(seconds, want_seconds[1::2]):
        assert max_diff(got, wanted) < 1e-5
    grad = jax.grad(lambda p: jnp.sum(layers.trunk(
        p, tokens, walked_layer, cfg)[0] ** 2))(params)
    want_grad = jax.grad(lambda p: jnp.sum(loop(p)[0] ** 2))(params)
    assert max(jax.tree.leaves(jax.tree.map(max_diff, grad, want_grad))) \
        < 1e-4


def test_the_walk_recomputes_only_when_asked(monkeypatch):
    """`remat` on: one `checkpoint_layer` over the whole stack, a chunk of
    the head's logits behind it; off: none, the layer itself."""
    asked = []

    def recording(fn, stack=None, behind=(), **kw):
        asked.append((len(stack), behind.shape, kw))
        return fn

    monkeypatch.setattr(layers, "checkpoint_layer", recording)
    tokens = jnp.zeros((B, S), jnp.int32)
    for remat in (False, True):
        cfg = Walked(remat=remat)
        layers.trunk(walked_params(cfg), tokens, walked_layer, cfg)
    assert asked == [(4, (8, 32), {"static_argnums": (2,)})]


# -- the routers' account -----------------------------------------------------

def account_params(biases):
    return {f"layer_{i}": {"moe": {"router": {
        "kernel": jnp.zeros((E, 4)),
        **({ROUTING_BIAS: jnp.asarray(b, jnp.float32)}
           if b is not None else {})}}}
        for i, b in biases.items()}


ROWS = [[40, 0, 8, 16], [10, 30, 14, 10], [4, 5, 50, 5]]     # 64 a layer
ACCOUNTS = {
    # the buffer of a share of 1 of 4 experts is twice 64 / 4 = 32 rows:
    # layers 0 and 2 send experts 0 and 2 more, layer 1 sends expert 1 30
    "first_expert_held": ((0, 1), 40 + 10 + 4, 1),
    "second_expert_held": ((1, 1), 0 + 30 + 5, 0),
    "third_expert_held": ((2, 1), 8 + 14 + 50, 1),
    # half of the experts and more: the buffer is all the rows
    "two_held": ((0, 2), 40 + 40 + 9, 0),
    "all_held": (None, 3 * 64, 0),
}


@pytest.mark.parametrize("case", sorted(ACCOUNTS))
def test_the_account_counts_what_the_held_experts_were_sent(case):
    held, rows_held, overflowed = ACCOUNTS[case]
    params = account_params({1: [0.1, -0.4, 0, 0], 3: [0, 0, 0.2, 0],
                             4: [0.3, 0, 0, -0.1]})
    out = moe.routing_account(
        params, (1, 3, 4), [jnp.asarray(r, jnp.int32) for r in ROWS], 64,
        held)
    np.testing.assert_array_equal(np.asarray(out["expert_rows"]), ROWS)
    assert int(out["rows_held"]) == rows_held
    assert int(out["moe_overflow_layers"]) == overflowed
    assert out["moe_overflow_layers"].dtype == jnp.int32
    assert int(out["max_expert_rows"]) == 50
    assert float(out["max_routing_bias"]) == pytest.approx(0.4)
    assert set(out) == {"expert_rows", "rows_held", "moe_overflow_layers",
                        "max_expert_rows", "max_routing_bias"}


def test_the_account_of_routers_without_a_bias():
    out = moe.routing_account(
        account_params({0: None, 2: None}), (0, 2),
        [jnp.asarray(r, jnp.int32) for r in ROWS[:2]], 64, (0, 1))
    assert float(out["max_routing_bias"]) == 0.0
    assert int(out["rows_held"]) == 50 and int(out["moe_overflow_layers"]) == 1


def test_the_rule_reads_the_account_in_its_order():
    """Row j of `expert_rows` is the j-th of the routed layers, for the
    account and for `routing_bias_rule` alike."""
    layers_routed = (1, 3, 4)
    params = account_params({i: [0.0] * 4 for i in layers_routed})
    out = moe.routing_account(
        params, layers_routed, [jnp.asarray(r, jnp.int32) for r in ROWS],
        64, None)
    moved = moe.routing_bias_rule(layers_routed, 0.5)(params, out)
    for i, rows in zip(layers_routed, ROWS):
        np.testing.assert_allclose(
            moved[f"layer_{i}"]["moe"]["router"][ROUTING_BIAS],
            0.5 * np.sign(16 - np.asarray(rows)))


# -- the head and its loss ----------------------------------------------------

def head_case(dtype=jnp.float32, V=48):
    keys = jax.random.split(jax.random.PRNGKey(4), 4)
    return (jax.random.normal(keys[0], (B, S, E), dtype),
            jax.random.randint(keys[1], (B, S), 0, V),
            jax.random.normal(keys[2], (V, E), dtype),
            jax.random.uniform(keys[3], (B, S)))


def dense_weighted_mean(x, rows, weights, targets):
    """mean_r w_r CE_r from dense logits, float32 whatever comes in."""
    logp = jax.nn.log_softmax(
        x.astype(jnp.float32) @ rows.astype(jnp.float32).T, axis=-1)
    ce = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
    return jnp.mean(ce if weights is None else weights * ce)


def chunked_weighted_mean(head, chunk_rows, targets):
    """-> f(x, rows (V, E), weights): the same through the one chunked
    loss, the head ``"tied"`` or ``"untied"``."""
    def got(x, rows, weights):
        p = {"embedding": rows} if head == "tied" else {"kernel": rows.T}
        total, _ = layers.head_and_weighted_loss(x, p, targets, weights,
                                                 chunk_rows)
        return total / targets.size
    return got


@pytest.mark.parametrize("weighted", [False, True], ids=["ones", "weighted"])
@pytest.mark.parametrize("head", ["tied", "untied"])
@pytest.mark.parametrize("chunk_rows", [8, 12, 1000])
def test_the_head_and_loss_is_the_dense_cross_entropy(head, chunk_rows,
                                                      weighted):
    """The one chunked loss: its value and its gradients with respect to x,
    the head and the rows' weights against dense `log_softmax`; without
    weights it is `head_and_loss`."""
    x, targets, rows, weights = head_case()
    got = chunked_weighted_mean(head, chunk_rows, targets)
    dense = functools.partial(dense_weighted_mean, targets=targets)
    if not weighted:
        weights = None
        p = {"embedding": rows} if head == "tied" else {"kernel": rows.T}
        assert float(layers.head_and_loss(x, p, targets, chunk_rows)) \
            == float(got(x, rows, None))
    argnums = (0, 1, 2) if weighted else (0, 1)
    assert float(got(x, rows, weights)) == pytest.approx(
        float(dense(x, rows, weights)), rel=1e-6)
    for g, want in zip(jax.grad(got, argnums)(x, rows, weights),
                       jax.grad(dense, argnums)(x, rows, weights)):
        assert max_diff(g, want) < 1e-6
    lowered = jax.jit(got).lower(x, rows, weights).as_text(debug_info=True)
    assert "head_and_loss" in lowered


def test_the_head_and_loss_in_bfloat16():
    """bfloat16 operands: the logits are float32 sums of bfloat16 products,
    the gradient with respect to them is rounded to bfloat16 once before
    its two products (as the MXU rounds a float32 cotangent), dW sums the
    chunks in float32.  Against float32 logits of the same operands: the
    loss to 1e-5, the gradients to 2 ** -7 of their largest entry (two
    bfloat16 roundings, of d logits and of the result)."""
    x, targets, rows, weights = head_case(jnp.bfloat16)
    got = chunked_weighted_mean("untied", 8, targets)
    dense = functools.partial(dense_weighted_mean, targets=targets)
    assert float(got(x, rows, weights)) == pytest.approx(
        float(dense(x, rows, weights)), rel=1e-5)
    grads = jax.grad(got, (0, 1, 2))(x, rows, weights)
    assert [g.dtype for g in grads] == [x.dtype, rows.dtype, weights.dtype]
    for g, want in zip(grads, jax.grad(dense, (0, 1, 2))(x, rows, weights)):
        assert max_diff(g, want) < 2 ** -7 * float(jnp.max(jnp.abs(want)))


def count_primitives(jaxpr, name):
    """How many equations of ``jaxpr`` and of every jaxpr among an
    equation's parameters (a scan's body once, whatever its length; a
    rule's) are the primitive ``name``."""
    count = 0
    for eqn in jaxpr.eqns:
        count += eqn.primitive.name == name
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    count += count_primitives(sub, name)
    return count


@pytest.mark.parametrize("weighted", [False, True], ids=["ones", "weighted"])
def test_the_primal_makes_no_gradient_product(weighted, monkeypatch):
    """Nobody differentiates: one product a chunk, the logits'.  Under a
    gradient: three a chunk, in one walk, and none behind it.  Either way
    the walk counts itself once on the job timeline."""
    x, targets, rows, weights = head_case()
    got = chunked_weighted_mean("untied", 8, targets)
    weights = weights if weighted else None
    counted = []
    monkeypatch.setattr(layers.tracing, "count",
                        lambda name, n=1: counted.append((name, n)))
    primal = jax.make_jaxpr(got)(x, rows, weights)
    assert count_primitives(primal.jaxpr, "dot_general") == 1
    assert count_primitives(primal.jaxpr, "scan") == 1
    assert counted == [("loss.chunks", B * S // 8),
                       ("loss.logits_passes", 1)]
    del counted[:]
    grad = jax.make_jaxpr(jax.value_and_grad(got, (0, 1)))(x, rows, weights)
    assert count_primitives(grad.jaxpr, "dot_general") == 3
    assert count_primitives(grad.jaxpr, "scan") == 1
    assert counted == [("loss.chunks", B * S // 8),
                       ("loss.logits_passes", 1)]


# -- what the walk and the chunked loss became (PR 50) ------------------------

def parent_trunk(params, tokens, layer, cfg):
    """`layers.trunk` at PR 49, before a walk could be repeated."""
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


def parent_chunked_xent(x, wte, targets, n_chunks: int):
    """`layers.chunked_xent` at PR 49, before its chunk was shared with the
    rows' form."""
    N, E = x.shape
    n_chunks = max(1, min(n_chunks, N))
    while N % n_chunks:
        n_chunks -= 1
    xc = x.reshape(n_chunks, N // n_chunks, E)
    tc = targets.reshape(n_chunks, N // n_chunks)

    @jax.checkpoint
    def chunk(carry, xt):
        xi, ti = xt
        logits = jnp.matmul(xi, wte.T,
                            preferred_element_type=jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, ti[:, None], axis=-1)[:, 0]
        return carry + jnp.sum(lse - tgt), None

    total, _ = jax.lax.scan(chunk, jnp.zeros((), jnp.float32), (xc, tc))
    return total


def parent_head_and_loss(x, head, targets, chunk_rows):
    """`layers.head_and_loss` at PR 49."""
    B, S, E = x.shape
    with jax.named_scope("head_and_loss"):
        rows = head["embedding"].astype(x.dtype) if "embedding" in head \
            else head["kernel"].astype(x.dtype).T
        total = parent_chunked_xent(
            x.reshape(B * S, E), rows, targets.reshape(B * S),
            -(-B * S // chunk_rows))
        return total / (B * S)


TRUNK_FAMILIES = {
    "olmoe": (olmoe, olmoe.OLMOE_TINY),
    "deepseek_v3": (deepseek_v3, deepseek_v3.DEEPSEEK_V3_TINY),
    "lfm2_moe": (lfm2_moe, lfm2_moe.LFM2_MOE_TINY),
    "nemotron_h": (nemotron_h, nemotron_h.NEMOTRON_H_TINY),
    "keye_vl": (keye_vl, keye_vl.KEYE_VL_TINY),
    "sdar": (sdar, sdar.SDAR_TINY),
    "mellum": (mellum, mellum.MELLUM_TINY),
}
HEAD_FAMILIES = {**TRUNK_FAMILIES, "ouro": (ouro, ouro.OURO_TINY)}


@kit.once
def family_inputs(family):
    """A family's parameters as drawn at its test size, and a batch."""
    module, cfg = HEAD_FAMILIES[family]
    return (module.init_params(jax.random.PRNGKey(3), cfg),
            {"tokens": jax.random.randint(
                jax.random.PRNGKey(4), (B, 65), 0, cfg.vocab_size)})


def family_step(family, cfg):
    """(the loss, every gradient) of a family's objective under ``cfg``, a
    program of its own a call: the side a test patches."""
    module, _ = HEAD_FAMILIES[family]
    params, batch = family_inputs(family)
    # an objective that draws its own noise takes the run's key and the
    # step's number
    noise = (sdar.noise_key(5), 0) if module is sdar else ()
    return jax.jit(jax.value_and_grad(
        lambda p: module.loss_fn(layers.cast_weights(
            p, cfg.compute_dtype), batch, cfg, *noise)[0]))(params)


# the same of the code as it is: once for all the witnesses that read it
as_it_is = kit.once(family_step)


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("family", sorted(TRUNK_FAMILIES))
def test_a_model_that_walks_once_is_what_it_was(family, remat, monkeypatch):
    """The loss and every gradient of the six `trunk` families at their
    test sizes, in their compute type, through `layers.trunk` as it is and
    as the parent had it: bit for bit.  (Until PR 53 the head was the
    parent's bit for bit as well; since then it forms its gradient in its
    forward walk and rounds otherwise:
    `test_the_head_is_what_it_was_to_a_rounding`.)"""
    module, cfg = TRUNK_FAMILIES[family]
    cfg = dataclasses.replace(cfg, remat=remat)
    loss, grads = as_it_is(family, cfg)
    monkeypatch.setattr(module, "trunk", parent_trunk)
    want_loss, want_grads = family_step(family, cfg)
    assert float(loss) == float(want_loss)
    for (path, want), got in zip(
            jax.tree_util.tree_flatten_with_path(want_grads)[0],
            jax.tree.leaves(grads)):
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want),
            err_msg=jax.tree_util.keystr(path))


def parent_head_and_weighted_loss(x, head, targets, weights, chunk_rows):
    """What `models/ouro.py:loss_fn` had of the head at PR 52: the rows'
    losses from a `lax.map` over chunks, each one's logits made again by
    the backward pass (`layers.chunked_xent_rows`), weighted outside."""
    E = x.shape[-1]
    n_chunks = -(-targets.size // chunk_rows)
    while targets.size % n_chunks:
        n_chunks -= 1
    wte = head["kernel"].astype(x.dtype).T

    @jax.checkpoint
    def chunk(xt):
        xi, ti = xt
        logits = jnp.matmul(xi, wte.T, preferred_element_type=jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return lse - jnp.take_along_axis(logits, ti[:, None], axis=-1)[:, 0]

    with jax.named_scope("head_and_loss"):
        rows = jax.lax.map(chunk, (
            x.reshape(n_chunks, -1, E),
            targets.reshape(n_chunks, -1))).reshape(targets.shape)
    return jnp.sum(weights * rows), jax.lax.stop_gradient(rows)


# A gradient against the parent's, as a share of the largest entry of the
# parent's gradient of the same leaf.  In float32 the two differ by the
# order of their sums.  In bfloat16 the head's own results differ by a
# rounding (d logits is rounded to bfloat16 before its products, which the
# CPU's product of a float32 operand does not do and the MXU does; dW sums
# its chunks in float32 where the parent summed them in bfloat16), and the
# layers behind it round every value they make of it again: read here,
# largest leaf a family, 0.012 (nemotron_h) to 0.026 (lfm2_moe), 0.012 to
# 0.021 of the leaf's norm.
HEAD_TOL = {"float32": 1e-5, "bfloat16": 2 ** -4}


@pytest.mark.parametrize("compute", sorted(HEAD_TOL))
@pytest.mark.parametrize("family", sorted(HEAD_FAMILIES))
def test_the_head_is_what_it_was_to_a_rounding(family, compute, monkeypatch):
    """The seven families' losses and gradients through the one chunked loss
    and through the parent's (`jax.checkpoint` a chunk, the logits made
    twice), computing in float32 and in bfloat16: the loss to 1e-6, every
    gradient to `HEAD_TOL`."""
    module, cfg = HEAD_FAMILIES[family]
    cfg = dataclasses.replace(cfg, compute_dtype=jnp.dtype(compute).type)
    loss, grads = as_it_is(family, cfg)
    if family in ("ouro", "sdar"):     # the rows weighted
        monkeypatch.setattr(module, "head_and_weighted_loss",
                            parent_head_and_weighted_loss)
    else:
        monkeypatch.setattr(module, "head_and_loss", parent_head_and_loss)
    want_loss, want_grads = family_step(family, cfg)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    moved = 0
    for (path, want), got in zip(
            jax.tree_util.tree_flatten_with_path(want_grads)[0],
            jax.tree.leaves(grads)):
        scale = float(jnp.max(jnp.abs(want)))
        assert max_diff(got, want) <= HEAD_TOL[compute] * scale, \
            jax.tree_util.keystr(path)
        moved += scale > 0
    assert moved
