"""What latent attention and a held share of the experts ask of the shared
code: attention whose q and k have one width and v another, in both forms
of the kernels; `moe_dispatch` told which experts are held; and that
neither moved what equal widths and all-held calls get."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops.moe import moe_dispatch
from ray_tpu.parallel.attention import attention


def _qkv(B, S, H, D, Dv, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(S + D), 3)
    return (jax.random.normal(ks[0], (B, S, H, D), dtype),
            jax.random.normal(ks[1], (B, S, H, D), dtype),
            jax.random.normal(ks[2], (B, S, H, Dv), dtype))


def _tr(x):
    return x.transpose(0, 2, 1, 3)


def _value_and_grads(f, q, k, v):
    def loss(q, k, v):
        o = f(q, k, v)
        return jnp.sum(jnp.sin(o.astype(jnp.float32))), o
    (_, o), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(q, k, v)
    return (o, *grads)


def _kernels(f, *args):
    """Names of the kernel functions of the `pallas_call`s in f's jaxpr,
    nested ones (jit, the platform's branches, custom_vjp) included."""
    from jax.extend import core as jex_core

    def walk(jaxpr, found):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.add(eqn.params["jaxpr"].debug_info.func_name)
            for value in eqn.params.values():
                for item in value if isinstance(value, (tuple, list)) \
                        else (value,):
                    if isinstance(item, jex_core.ClosedJaxpr):
                        walk(item.jaxpr, found)
                    elif isinstance(item, jex_core.Jaxpr):
                        walk(item, found)
        return found

    return walk(jax.make_jaxpr(f)(*args).jaxpr, set())


def _grad_of_attention(q, k, v):
    return jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention_bshd(
        q, k, v, True).astype(jnp.float32)), (0, 1, 2))(q, k, v)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("form", ["whole", "long"])
@pytest.mark.parametrize("dims", [(48, 32), (24, 16), (32, 64)])
def test_attention_with_two_widths_matches_the_reference(
        dims, form, causal, monkeypatch):
    """q, k `D` wide and v, o `Dv` wide (MLA: 192 and 128) against
    `reference_attention`: o and all three gradients, through the model's
    entry (`parallel/attention.py`: (B, S, H, D), head_dim^-1/2 of q's
    width), in the form a grid step takes the whole sequence in (S <=
    `_WHOLE_SEQ_MAX`) and in the long one (lowered here so that an
    interpretable size passes it: q tiles looping over k blocks, the
    two-kernel backward)."""
    D, Dv = dims
    if form == "long":
        monkeypatch.setattr(fa, "_WHOLE_SEQ_MAX", 128)
    q, k, v = _qkv(1, 256, 2, D, Dv)

    def kernel(q, k, v):
        return fa.flash_attention_bshd(q, k, v, causal, None, 128, 128)

    def reference(q, k, v):
        o, _ = fa.reference_attention(_tr(q), _tr(k), _tr(v), D ** -0.5,
                                      causal)
        return _tr(o)

    got = _value_and_grads(kernel, q, k, v)
    want = _value_and_grads(reference, q, k, v)
    assert got[0].shape == (1, 256, 2, Dv) and got[3].shape == v.shape
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert float(jnp.max(jnp.abs(g - w))) < 2e-5, name
    if causal:
        entry = _value_and_grads(lambda q, k, v: attention(q, k, v), q, k, v)
        for g, w in zip(entry, want):
            assert float(jnp.max(jnp.abs(g - w))) < 2e-5


def test_two_widths_take_the_head_major_kernels_and_no_fallback():
    """Widths that differ cannot share lanes: the call goes head-major
    (one head a grid step), never to the O(S^2) reference."""
    import warnings

    q, k, v = _qkv(1, 256, 2, 128, 64, jnp.bfloat16)
    with warnings.catch_warnings():
        warnings.simplefilter("error", fa.AttentionFallbackWarning)
        found = _kernels(_grad_of_attention, q, k, v)
    assert found == {"_fwd_kernel", "_bwd_fused_kernel"}


def test_equal_widths_get_what_they_got():
    """The lane layout for heads that fill lanes, the default compiler
    parameters at every shape a cell had, and a higher VMEM limit only
    where the sequence-long operands need it (S = 8,192 at 192 / 128)."""
    q, k, v = _qkv(1, 256, 2, 64, 64, jnp.bfloat16)
    assert _kernels(_grad_of_attention, q, k, v) == {
        "_fwd_kernel_lanes", "_bwd_fused_kernel_lanes"}
    assert fa._bshd_lanes_ok(q, 256, 128, 128)
    for S, D in ((1024, 64), (2048, 128), (4096, 128)):
        assert fa._compiler_params(S, D, D, jnp.bfloat16) \
            is fa._COMPILER_PARAMS
    raised = fa._compiler_params(8192, 192, 128, jnp.bfloat16)
    assert raised.vmem_limit_bytes == (16 + 4 * 12) << 20
    assert raised.dimension_semantics == \
        fa._COMPILER_PARAMS.dimension_semantics
    # the tiles are a function of S and causal alone, as before
    assert fa._auto_tiles(1024, True) == ((512, 512), (256, 256))
    assert fa._auto_tiles(4096, True) == ((1024, 1024), (1024, 1024))


def _experts(n=8, e=16, w=8):
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    gate = jax.random.normal(ks[0], (n, e, w))
    down = jax.random.normal(ks[1], (n, w, e))

    def run(gate, down):
        return lambda xs, sizes: jax.lax.ragged_dot(
            jax.nn.silu(jax.lax.ragged_dot(xs, gate, sizes)), down, sizes)
    return gate, down, run


def _routing(T=64, n=8, k=3, skew=0.0):
    logits = jax.random.normal(jax.random.PRNGKey(4), (T, n))
    logits = logits + skew * jnp.arange(n)        # the last experts fill up
    return jax.lax.top_k(jax.nn.softmax(logits), k)


@pytest.mark.parametrize("skew", [0.0, 3.0])
def test_dispatch_to_held_experts_alone(skew):
    """Each share computes every row sent to its experts and nothing for
    the others; the shares' sum is the whole; the counts are over ALL the
    experts in every share; an absent expert's matrices get no gradient
    and the rows sent to it bring none back."""
    gate, down, run = _experts()
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 16))
    weights, experts = _routing(skew=skew)
    whole, sizes = moe_dispatch(x, weights, experts, 8, run(gate, down))
    assert int(sizes.sum()) == 64 * 3

    def share(x, weights, gate, down, first, count=2):
        return moe_dispatch(
            x, weights, experts, 8,
            run(gate[first:first + count], down[first:first + count]),
            held=(first, count))

    parts = [share(x, weights, gate, down, first) for first in range(0, 8, 2)]
    assert float(jnp.max(jnp.abs(sum(y for y, _ in parts) - whole))) < 1e-4
    for _, sent in parts:
        assert (np.asarray(sent) == np.asarray(sizes)).all()

    first = 6 if skew else 2        # under the skew the last two fill up
    grads = jax.grad(lambda *a: jnp.sum(share(*a, first)[0] ** 2),
                     (0, 1, 2, 3))(x, weights, gate, down)
    held = np.zeros(8, bool)
    held[first:first + 2] = True
    for g in grads[2:]:
        per_expert = np.abs(np.asarray(g)).reshape(8, -1).max(axis=1)
        assert (per_expert[~held] == 0).all() and (per_expert[held] > 0).all()
    # a choice of an absent expert weighs nothing here
    absent = ~np.isin(np.asarray(experts), (first, first + 1))
    assert not np.asarray(grads[1])[absent].any()
    assert np.asarray(grads[1])[~absent].any()
    # a token none of whose choices is held gets nothing and gives nothing
    nothing = absent.all(axis=1)
    if nothing.any():
        y = share(x, weights, gate, down, first)[0]
        assert not np.asarray(y)[nothing].any()
        assert not np.asarray(grads[0])[nothing].any()


def test_all_held_is_the_call_it_was_bit_for_bit():
    gate, down, run = _experts()
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 16))
    weights, experts = _routing()
    plain = jax.jit(lambda x: moe_dispatch(
        x, weights, experts, 8, run(gate, down)))
    told = jax.jit(lambda x: moe_dispatch(
        x, weights, experts, 8, run(gate, down), held=(0, 8)))
    (y0, n0), (y1, n1) = plain(x), told(x)
    assert (np.asarray(y0) == np.asarray(y1)).all()
    assert (np.asarray(n0) == np.asarray(n1)).all()
    # and a call that names no share has no mask and no second key
    count = lambda f: str(jax.make_jaxpr(f)(x)).count("select_n")
    assert count(plain) < count(told)
