"""What the trunk models share since PR 58, each piece alone against a plain
form written here: the softmax route and its balance loss
(`ops/moe.py:softmax_route`, `balance_loss`) against a loop over rows, the
way into and out of an attention operator (`models/layers.py:attention_qkv`,
`attention_out`) around the kernels against dense softmax attention in
numpy, and every model's `init_params` against the leaves the same key gave
before the initialiser's closures were shared.  Float32, small sizes, the
CPU.
"""

import dataclasses
import functools
import hashlib
import importlib
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import layers
from ray_tpu.ops import moe
from ray_tpu.parallel.attention import attention

TOL = 2e-5


pytestmark = pytest.mark.usefixtures("highest_precision")


# -- the softmax route and its balance loss ---------------------------------

T, E, N, K = 24, 16, 8, 3


def route_case(seed=0):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(kx, (T, E)),
            {"kernel": jax.random.normal(kw, (E, N)) * 0.5})


def rows_loop(xt, kernel, renormalise):
    """-> (weights (T, K), experts (T, K), the mean probability (N,), the
    balance loss), a row at a time in float64."""
    xt, kernel = np.asarray(xt, np.float64), np.asarray(kernel, np.float64)
    weights, experts, mean, sent = [], [], np.zeros(N), np.zeros(N)
    for row in xt:
        logits = row @ kernel
        p = np.exp(logits - logits.max())
        p /= p.sum()
        mean += p / len(xt)
        chosen = np.argsort(-p, kind="stable")[:K]
        w = p[chosen]
        weights.append(w / w.sum() if renormalise else w)
        experts.append(chosen)
        for e in chosen:
            sent[e] += 1
    balance = N * np.sum(sent / (len(xt) * K) * mean)
    return np.array(weights), np.array(experts), mean, balance


@pytest.mark.parametrize("renormalise", [False, True],
                         ids=["as_they_are", "over_their_sum"])
def test_the_softmax_route_is_the_loop_over_rows(renormalise):
    xt, router = route_case()
    weights, experts, mean = moe.softmax_route(xt, router, K, renormalise)
    want = rows_loop(xt, router["kernel"], renormalise)
    assert weights.dtype == mean.dtype == jnp.float32
    assert experts.dtype == jnp.int32
    np.testing.assert_array_equal(experts, want[1])
    np.testing.assert_allclose(weights, want[0], atol=TOL)
    np.testing.assert_allclose(mean, want[2], atol=TOL)
    if renormalise:
        np.testing.assert_allclose(jnp.sum(weights, -1), 1.0, atol=TOL)
    rows = jnp.sum(jax.nn.one_hot(experts, N, dtype=jnp.int32), axis=(0, 1))
    np.testing.assert_allclose(moe.balance_loss(rows, mean, T * K), want[3],
                               atol=TOL)


def test_the_balance_loss_of_a_router_in_balance_is_one():
    rows = jnp.full((N,), T * K // N, jnp.int32)
    assert float(moe.balance_loss(rows, jnp.full((N,), 1 / N), T * K)) \
        == pytest.approx(1.0)
    # all the rows to one expert that the softmax prefers as much
    lopsided = jnp.zeros((N,), jnp.int32).at[2].set(T * K)
    assert float(moe.balance_loss(
        lopsided, jnp.zeros((N,)).at[2].set(1.0), T * K)) \
        == pytest.approx(N)


def routed_case():
    width = 24
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T // 2, E))
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    return x, {"router": {"kernel": jax.random.normal(keys[0], (E, N)) * 0.5},
               "wi_gate": jax.random.normal(keys[1], (N, E, width)) * 0.3,
               "wi_up": jax.random.normal(keys[2], (N, E, width)) * 0.3,
               "wo": jax.random.normal(keys[3], (N, width, E)) * 0.3}


def test_a_route_that_gives_two_leaves_the_routed_layer_its_two():
    x, p = routed_case()
    assert len(layers.routed_layer(
        x, p, functools.partial(moe.sigmoid_route, top_k=K, eps=1e-20,
                                scale=1.0), N, None, layers.swiglu)) == 2


def test_the_routed_layer_hands_on_what_a_route_gives_besides():
    """A softmax route's mean comes out of `routed_layer` as its third, and
    the balance loss from it and the rows moves the router alone, by the
    gradient of N sum_e f_e P_e with f a constant."""
    x, p = routed_case()

    def balance(p):
        _, rows, mean = layers.routed_layer(
            x, p, functools.partial(moe.softmax_route, top_k=K,
                                    renormalise=True), N, None,
            layers.swiglu)
        return moe.balance_loss(rows, mean, T * K), rows

    (value, rows), grads = jax.value_and_grad(balance, has_aux=True)(p)
    want = rows_loop(x.reshape(T, E), p["router"]["kernel"], True)
    np.testing.assert_allclose(value, want[3], atol=TOL)
    assert int(jnp.sum(rows)) == T * K

    def plain(kernel):
        probs = jax.nn.softmax(x.reshape(T, E) @ kernel, axis=-1)
        return N * jnp.sum(rows / (T * K) * jnp.mean(probs, axis=0))

    np.testing.assert_allclose(grads["router"]["kernel"],
                               jax.grad(plain)(p["router"]["kernel"]),
                               atol=TOL)
    for name in ("wi_gate", "wi_up", "wo"):
        assert not np.any(np.asarray(grads[name]))


# -- the way into and out of an attention operator ---------------------------

B, S, H, D = 2, 16, 4, 8
EPS, THETA = 1e-6, 100.0


def attention_case(kv_heads, qk_norm, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    normal = lambda key, *shape: jax.random.normal(key, shape) * 0.4
    p = {"q_proj": {"kernel": normal(keys[0], E, H * D)},
         "k_proj": {"kernel": normal(keys[1], E, kv_heads * D)},
         "v_proj": {"kernel": normal(keys[2], E, kv_heads * D)},
         "o_proj": {"kernel": normal(keys[3], H * D, E)}}
    if qk_norm:
        p["q_norm"] = {"scale": 1 + normal(keys[4], D)}
        p["k_norm"] = {"scale": 1 + normal(keys[5], D)}
    return jax.random.normal(keys[6], (B, S, E)), p


def dense_attention(x, p, positions):
    """The operator in numpy, float64: projections, an RMSNorm a head where
    there are gains, rotate-half RoPE where there are positions, causal
    softmax at D^-1/2 with query head h on key/value head h // group,
    W_o."""
    f64 = lambda a: np.asarray(a, np.float64)
    x = f64(x)

    def heads(name, norm=None):
        """v: no norm, no RoPE."""
        h = (x @ f64(p[name]["kernel"])).reshape(B, S, -1, D)
        if norm in p:
            h = h / np.sqrt((h ** 2).mean(-1, keepdims=True) + EPS) \
                * f64(p[norm]["scale"])
        if norm and positions is not None:
            angle = f64(positions)[:, None] \
                * THETA ** (-np.arange(D // 2) / (D // 2))
            cos, sin = (turn(angle)[None, :, None] for turn in
                        (np.cos, np.sin))
            h1, h2 = h[..., :D // 2], h[..., D // 2:]
            h = np.concatenate([h1 * cos - h2 * sin, h1 * sin + h2 * cos],
                               axis=-1)
        return h

    q, k, v = heads("q_proj", "q_norm"), heads("k_proj", "k_norm"), \
        heads("v_proj")
    group = H // k.shape[2]
    out = np.zeros((B, S, H, D))
    for b, h, t in itertools.product(range(B), range(H), range(S)):
        scores = k[b, :t + 1, h // group] @ q[b, t, h] * D ** -0.5
        probs = np.exp(scores - scores.max())
        out[b, t, h] = probs / probs.sum() @ v[b, :t + 1, h // group]
    return out.reshape(B, S, H * D) @ f64(p["o_proj"]["kernel"])


ROPES = {"no_rope": None, "rope": jnp.arange,
         "rope_folded": lambda S: jnp.arange(S) % (S // 2)}


@pytest.mark.parametrize("rope", sorted(ROPES))
@pytest.mark.parametrize("qk_norm", [False, True], ids=["plain", "qk_norm"])
@pytest.mark.parametrize("kv_heads", [H, 2], ids=["mha", "gqa"])
def test_the_projected_attention_is_dense_softmax_attention(kv_heads,
                                                            qk_norm, rope):
    x, p = attention_case(kv_heads, qk_norm)
    positions = ROPES[rope]
    q, k, v = layers.attention_qkv(x, p, D, EPS, positions, THETA)
    assert q.shape == (B, S, H, D)
    assert k.shape == v.shape == (B, S, kv_heads, D)
    got = layers.attention_out(attention(q, k, v), p)
    want = dense_attention(
        x, p, None if positions is None else np.asarray(positions(S)))
    np.testing.assert_allclose(got, want, atol=TOL)


def test_the_two_ends_stand_under_their_scopes_and_mark_their_products():
    """`attention/qkv` and `attention/out` are what a reader sums device
    time by and what a recomputed layer may keep: the products carry the
    marks, the norms' and RoPE's results none."""
    x, p = attention_case(2, True)

    def operator(x):
        with jax.named_scope("attention"):
            q, k, v = layers.attention_qkv(x, p, D, EPS, jnp.arange, THETA)
            return layers.attention_out(
                attention(q, k, v, variant="dense"), p)

    jaxpr = jax.make_jaxpr(operator)(x)
    marks = [(str(eqn.source_info.name_stack), eqn.params["name"])
             for eqn in jaxpr.eqns if eqn.primitive.name == "name"]
    assert marks == [("attention/qkv", "attention/qkv")] * 3 \
        + [("attention/out", "attention/out")]
    products = [str(eqn.source_info.name_stack) for eqn in jaxpr.eqns
                if eqn.primitive.name == "dot_general"]
    assert products[:3] == ["attention/qkv"] * 3
    assert products[-1] == "attention/out"


# -- the initialisers ---------------------------------------------------------

def test_the_two_initialisers():
    key = jax.random.PRNGKey(3)
    drawn = layers.normal_kernel(key, 5, 7)
    assert set(drawn) == {"kernel"} and drawn["kernel"].dtype == jnp.float32
    np.testing.assert_array_equal(
        drawn["kernel"], jax.random.normal(key, (5, 7), jnp.float32) * 0.02)
    np.testing.assert_array_equal(
        layers.normal_kernel(key, 2, 5, 7, std=0.5)["kernel"],
        jax.random.normal(key, (2, 5, 7), jnp.float32) * 0.5)
    gain = layers.unit_scale(6)
    assert set(gain) == {"scale"} and gain["scale"].dtype == jnp.float32
    np.testing.assert_array_equal(gain["scale"], np.ones(6))


def leaves_digest(tree):
    """Every leaf's path, type, shape and bytes in one digest."""
    digest = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        leaf = np.asarray(leaf)
        digest.update(f"{jax.tree_util.keystr(path)} {leaf.dtype} "
                      f"{leaf.shape}".encode())
        digest.update(leaf.tobytes())
    return digest.hexdigest()[:16]


# case -> (the module, its tiny configuration, fields replaced, the digest of
# `init_params(PRNGKey(7), ...)` on the parent of PR 58, when every file
# still defined its own closures): a seed's parameters are the benchmark's
# data, and a `split` that moves or a shape that changes moves them
PARENT_LEAVES = {
    "deepseek_v3": ("deepseek_v3", "DEEPSEEK_V3_TINY", {},
                    "5084c799290be3e0"),
    "gpt2": ("gpt2", "GPT2_TINY", {}, "6797ff35c1c0ff51"),
    "gpt2_moe": ("gpt2", "GPT2_TINY", {"moe_experts": 4},
                 "dbc26f6bcc8363fa"),
    "keye_vl": ("keye_vl", "KEYE_VL_TINY", {}, "ca583b3929abb4a8"),
    "lfm2_moe": ("lfm2_moe", "LFM2_MOE_TINY", {}, "1d757c0dae24060b"),
    "mellum": ("mellum", "MELLUM_TINY", {}, "3d8c0398c8f53369"),
    "nemotron_h": ("nemotron_h", "NEMOTRON_H_TINY", {}, "85b29c99616e8286"),
    "olmoe": ("olmoe", "OLMOE_TINY", {}, "8b0fdcea75597c6f"),
    "ouro": ("ouro", "OURO_TINY", {}, "b2b40c10eb6b4046"),
    "sdar": ("sdar", "SDAR_TINY", {}, "62639408b0b90bc7"),
}


@pytest.mark.parametrize("case", sorted(PARENT_LEAVES))
def test_a_seed_gives_the_leaves_it_gave(case):
    name, tiny, fields, want = PARENT_LEAVES[case]
    module = importlib.import_module(f"ray_tpu.models.{name}")
    cfg = dataclasses.replace(getattr(module, tiny), **fields)
    assert leaves_digest(module.init_params(jax.random.PRNGKey(7), cfg)) \
        == want
