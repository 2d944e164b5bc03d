"""Grouped-query attention inside the flash kernels: k and v with fewer
heads than q (query head h on key/value head h // group), in every form a
call can take, against `reference_attention` with k and v repeated; what
a call with equal heads gets is what it got; and the entry under a mesh."""

import hashlib
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_attention as fa
from ray_tpu.parallel.attention import _flash_sharded, attention
from ray_tpu.parallel.mesh import create_mesh
from ray_tpu.util import tracing

H, D, S = 8, 16, 256


def _tr(x):
    return x.transpose(0, 2, 1, 3)


def _qkv(kv_heads, dtype=jnp.float32, B=1, S=S, H=H, D=D):
    """(B, S, H, D) q and (B, S, kv_heads, D) k, v."""
    ks = jax.random.split(jax.random.PRNGKey(kv_heads), 3)
    return (jax.random.normal(ks[0], (B, S, H, D), dtype),
            jax.random.normal(ks[1], (B, S, kv_heads, D), dtype),
            jax.random.normal(ks[2], (B, S, kv_heads, D), dtype))


def _value_and_grads(f, q, k, v):
    def loss(q, k, v):
        o = f(q, k, v)
        return jnp.sum(jnp.sin(o.astype(jnp.float32))), o
    (_, o), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(q, k, v)
    return (o, *grads)


def _repeated_reference(causal):
    """`reference_attention` given k and v with q's heads: each key/value
    head repeated for its group, so autodiff sums a group's gradients."""
    def reference(q, k, v):
        group = q.shape[2] // k.shape[2]
        o, _ = fa.reference_attention(
            _tr(q), jnp.repeat(_tr(k), group, axis=1),
            jnp.repeat(_tr(v), group, axis=1), q.shape[-1] ** -0.5, causal)
        return _tr(o)
    return reference


def _kernels(f, *args):
    """Names of the kernel functions of the `pallas_call`s in f's jaxpr."""
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


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("layout", ["lane", "head_major"])
@pytest.mark.parametrize("form", ["whole", "long"])
@pytest.mark.parametrize("group", [1, 4, 8])
def test_gqa_kernels_match_the_reference_with_kv_repeated(
        group, form, layout, causal, monkeypatch):
    """o, dq, dk, dv of the interpreted kernels, in the form a grid step
    takes the whole sequence in and in the long one (`_WHOLE_SEQ_MAX`
    lowered so that an interpretable size passes it: q tiles looping over
    k blocks forward, k tiles looping over q blocks in the one backward
    kernel), through the (B, S, H, D) entry (the
    lane layout where the heads are equal, head-major where they are not)
    and the (B, H, S, D) one, at H / H_kv of 1, 4 and 8."""
    if form == "long":
        monkeypatch.setattr(fa, "_WHOLE_SEQ_MAX", 128)
    q, k, v = _qkv(H // group)
    if layout == "lane":
        def kernel(q, k, v):
            return fa.flash_attention_bshd(q, k, v, causal, None, 128, 128)
    else:
        def kernel(q, k, v):
            return _tr(fa.flash_attention(_tr(q), _tr(k), _tr(v), causal,
                                          None, 128, 128))
    with jax.default_matmul_precision("highest"):
        got = _value_and_grads(kernel, q, k, v)
        want = _value_and_grads(_repeated_reference(causal), q, k, v)
    assert got[2].shape == k.shape and got[3].shape == v.shape
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert float(jnp.max(jnp.abs(g - w))) < 5e-5, name


@pytest.mark.parametrize("form", ["whole", "long"])
def test_a_group_of_sixteen_heads_of_128(form, monkeypatch):
    """Nemotron-H's attention: 32 query heads on 2 key/value heads of 128
    (H / H_kv = 16), through the head-major kernels in both forms, against
    `reference_attention` with k and v repeated."""
    if form == "long":
        monkeypatch.setattr(fa, "_WHOLE_SEQ_MAX", 128)
    q, k, v = _qkv(2, H=32, D=128)
    kernel = lambda q, k, v: fa.flash_attention_bshd(
        q, k, v, True, None, 128, 128)
    assert _kernels(kernel, q, k, v), "no kernel ran"
    with jax.default_matmul_precision("highest"):
        got = _value_and_grads(kernel, q, k, v)
        want = _value_and_grads(_repeated_reference(True), q, k, v)
    assert got[2].shape == k.shape == (1, S, 2, 128)
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert float(jnp.max(jnp.abs(g - w))) < 2e-4 * max(
            1.0, float(jnp.max(jnp.abs(w)))), name


def test_the_reference_paths_take_grouped_queries_too():
    """Beyond the interpreter's size off the TPU a call runs
    `reference_attention` and `_reference_backward`: they repeat k and v
    themselves and sum a group's gradients."""
    q, k, v = _qkv(2)
    with jax.default_matmul_precision("highest"):
        def reference(q, k, v):
            o, _ = fa.reference_attention(_tr(q), _tr(k), _tr(v),
                                          D ** -0.5, True)
            return _tr(o)
        got = _value_and_grads(reference, q, k, v)
        want = _value_and_grads(_repeated_reference(True), q, k, v)
        o, lse = fa.reference_attention(_tr(q), _tr(k), _tr(v), D ** -0.5,
                                        True)
        do = jnp.cos(o)
        delta = jnp.sum(do * o, axis=-1)
        back = fa._reference_backward(_tr(q), _tr(k), _tr(v), lse, do, delta,
                                      D ** -0.5, True)
    for g, w in zip(got, want):
        assert float(jnp.max(jnp.abs(g - w))) < 2e-5
    for g, w in zip(back, want[1:]):
        assert g.shape == _tr(w).shape
        assert float(jnp.max(jnp.abs(g - _tr(w)))) < 2e-5


def test_grouped_queries_take_the_head_major_kernels_and_no_fallback():
    """The lane layout slices every operand's heads out of the same lanes
    and declines the call; it goes head-major, never to the O(S^2)
    reference; dk and dv leave the kernel in float32."""
    q, k, v = _qkv(2, jnp.bfloat16)

    def grad(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention_bshd(
            q, k, v, True).astype(jnp.float32)), (0, 1, 2))(q, k, v)

    with warnings.catch_warnings():
        warnings.simplefilter("error", fa.AttentionFallbackWarning)
        assert _kernels(grad, q, k, v) == {"_fwd_kernel", "_bwd_fused_kernel"}
        text = str(jax.make_jaxpr(grad)(q, k, v))
    # the parts of dk and dv: one a query head, float32
    assert f"f32[{H},{S},{D}]" in text
    dq, dk, dv = grad(q, k, v)
    assert dk.dtype == dv.dtype == jnp.bfloat16 and dk.shape == k.shape
    # equal heads that fill lanes keep the lane kernels
    q, k, v = _qkv(H, jnp.bfloat16)
    assert _kernels(grad, q, k, v) == {"_fwd_kernel_lanes",
                                       "_bwd_fused_kernel_lanes"}


def _normalised_jaxpr(f, *args):
    """The jaxpr's text without source paths and line numbers."""
    return re.sub(r"(/[\w./-]+\.py|<[\w ]+>):\d+(:\d+)?", "",
                  str(jax.make_jaxpr(f)(*args)))


# sha256 (16 hex digits) of `_normalised_jaxpr` of the gradient of causal
# attention at the accepted cells' shapes, taken on the parent of the PR
# that brought grouped queries (ab23c31): (B, S, H, D), v's width, through
# `parallel/attention.py:attention`.  A PR that changes the kernels on
# purpose changes these with them.  The names the forward rules give o and
# lse (`KEPT_RESIDUALS`, two identity equations a call) are taken out first.
# Since PR 35 the transposing route (XL, kanana) transposes the kernel's o to
# (B, S, H, D) once, for the result and the residual alike, where the parent
# wrote the same transpose twice (XLA merged them): one equation fewer; and
# up to `_WHOLE_SEQ_MAX` (XL) lse is named in the kernels' own (B*H, S, 1),
# two reshapes that cancel.  PR 45 made the backward past `_WHOLE_SEQ_MAX`
# one kernel: the three long shapes' gradients are that PR's, and the last
# hash is of their FORWARD alone, which is still the parent's (taken on
# fbcf05f).
PARENT = {
    "gpt2-medium": ((16, 1024, 16, 64), None, False, "58052d16e0f69720",
                    "ba812a7dd7dc3012"),
    "gpt2-xl a chip": ((4, 1024, 25, 64), None, False, "46a839a5b15db2c7",
                       "6fc25eb3b821d90b"),
    "olmoe": ((4, 4096, 16, 128), None, False, "e89b7c629b743aaf",
              "08228a281a8c16ae"),
    "kanana": ((2, 8192, 32, 192), 128, True, "57e8b2a48718df99",
               "46beb62f7c8cade5"),
    "lanes, long forward": ((2, 2048, 32, 64), None, False,
                            "7889c1a6dc334d4e", "8c506a32eee08969"),
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_equal_heads_lower_to_the_jaxpr_they_lowered_to(name, monkeypatch):
    """`H_kv == H`: every `pallas_call`, index map and shape is the
    parent's, so the accepted cells run the parent's kernels: both passes
    up to `_WHOLE_SEQ_MAX`, the forward past it."""
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    shape, v_dim, entry, want, want_forward = PARENT[name]
    B, S, heads, D = shape
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    v = jax.ShapeDtypeStruct((B, S, heads, v_dim or D), jnp.bfloat16)

    def loss(q, k, v):
        o = attention(q, k, v) if entry \
            else fa.flash_attention_bshd(q, k, v, True, None, None, None)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    for f, digest in ((jax.grad(loss, (0, 1, 2)), want),
                      (loss, want_forward)):
        text = _normalised_jaxpr(f, q, q, v)
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


@pytest.mark.parametrize("shape,kv_heads,kernels", [
    ((2, 8192, 32, 64), 8, 2), ((2, 1024, 32, 64), 8, 2)])
def test_grouped_queries_lower_to_mosaic_for_tpu(shape, kv_heads, kernels):
    """LFM2-24B-A2B's attention (32 query heads on 8 key/value heads of 64
    at S = 8,192: the head-major forward by q tiles and the one backward
    kernel by k tiles) exported for a TPU from this CPU host: Mosaic custom
    calls, no interpreted kernel body and no O(S^2) reference."""
    B, S, heads, D = shape
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    k = jax.ShapeDtypeStruct((B, S, kv_heads, D), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(attention(q, k, v).astype(jnp.float32) ** 2)

    module = jax.export.export(
        jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
        platforms=["tpu"])(q, k, k).mlir_module()
    assert module.count("stablehlo.custom_call @tpu_custom_call") == kernels
    assert "stablehlo.dot_general" not in module
    assert "stablehlo.while" not in module


@pytest.mark.parametrize("kv_heads", [8, 2])
def test_flash_sharded_cuts_k_and_v_by_their_own_heads(kv_heads):
    """Under a mesh of four devices along the heads' axis, 8 query heads on
    8 or 2 key/value heads: with 8 each device gets 2 of each; 2 key/value
    heads do not divide by 4, so the heads stay whole on every device (the
    batch is still cut).  The result is the unsharded call's."""
    mesh = create_mesh({"dp": 2, "tp": 4})
    q, k, v = _qkv(kv_heads, B=2, S=128)
    with jax.default_matmul_precision("highest"):
        want = _value_and_grads(_repeated_reference(True), q, k, v)
        got = _value_and_grads(
            lambda q, k, v: _flash_sharded(q, k, v, mesh, True), q, k, v)
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert g.shape == w.shape
        assert float(jnp.max(jnp.abs(g - w))) < 5e-5, name
    text = str(jax.make_jaxpr(
        lambda q, k, v: _flash_sharded(q, k, v, mesh, True))(q, k, v))
    specs = re.search(r"in_specs=\((.*?)\)\)", text).group(1)
    assert specs.count("'dp'") == 3
    assert specs.count("'tp'") == (3 if kv_heads == 8 else 0)


def test_the_entry_refuses_what_it_cannot_do():
    q, k, v = _qkv(2, S=128)
    mesh = create_mesh({"sp": 8})
    from ray_tpu.parallel.context import use_mesh

    for variant in ("ring", "ulysses"):
        with use_mesh(mesh), pytest.raises(
                NotImplementedError, match="grouped queries"):
            attention(q, k, v, variant=variant)
    with pytest.raises(ValueError, match="divide the query heads"):
        attention(q, k[:, :, :1].repeat(3, axis=2), v[:, :, :1].repeat(
            3, axis=2))
    # the dense variant repeats k and v itself
    with jax.default_matmul_precision("highest"):
        o = attention(q, k, v, variant="dense")
        want = _repeated_reference(True)(q, k, v)
    assert float(jnp.max(jnp.abs(o - want))) < 2e-5


def test_the_kernels_count_the_heads_they_read():
    """`attention.q_heads` / `attention.kv_heads` on the job timeline, once
    per kernel as it is traced: a quarter where four query heads share a
    key/value head, equal where k and v were repeated before the call."""
    names = ("attention.q_heads", "attention.kv_heads")

    def traced(q_shape, kv_heads):
        q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16)
        k = jax.ShapeDtypeStruct(
            (*q_shape[:2], kv_heads, q_shape[3]), jnp.bfloat16)
        before = [tracing.counter(name) for name in names]
        jax.eval_shape(jax.grad(lambda q, k, v: jnp.sum(
            attention(q, k, v).astype(jnp.float32)), (0, 1, 2)), q, k, k)
        return [tracing.counter(name) - b for name, b in zip(names, before)]

    assert traced((2, 1024, 32, 64), 8) == [0, 0]          # no job, no count
    with tracing.timeline_span("train.fit", root=True):
        # whole sequence: a forward and a one-kernel backward
        assert traced((2, 1024, 32, 64), 8) == [64, 16]
        # past `_WHOLE_SEQ_MAX` the same: a forward and ONE backward kernel
        assert traced((2, 2048, 32, 64), 8) == [64, 16]
        assert traced((2, 2048, 32, 64), 32) == [64, 64]


# (query heads, key/value heads, q/k width, v width, S, tile): every shape
# stays within what a non-TPU backend interprets (`ops.INTERPRET_MAX_ELEMS`)
LONG_SHAPES = {
    "heads of 64": (2, 2, 64, 64, 256, 128),
    "heads of 128": (2, 2, 128, 128, 256, 128),
    "q, k 192 and v 128": (1, 1, 192, 128, 256, 128),
    "a group of 4": (4, 1, 64, 64, 256, 128),
    "a group of 16": (16, 1, 16, 16, 256, 128),
    # 512 does not divide 640: `_auto_tiles` falls to the 128 that does
    "a length 512 does not divide": (2, 2, 32, 32, 640, None),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("name", sorted(LONG_SHAPES))
def test_the_long_backward_is_the_reference_s_and_the_whole_form_s(
        name, causal, dtype, monkeypatch):
    """dq, dk and dv of the one backward kernel with a slice's k tiles on
    the grid (`_WHOLE_SEQ_MAX` lowered to 128) against `_reference_backward`
    on the same residuals, and against the same kernel with a grid step the
    whole slice (`_WHOLE_SEQ_MAX` as it is): one body, two walks."""
    heads, kv_heads, D, Dv, S, tile = LONG_SHAPES[name]
    ks = jax.random.split(jax.random.PRNGKey(S + heads), 4)
    q = jax.random.normal(ks[0], (1, heads, S, D), dtype)
    k = jax.random.normal(ks[1], (1, kv_heads, S, D), dtype)
    v = jax.random.normal(ks[2], (1, kv_heads, S, Dv), dtype)
    do = jax.random.normal(ks[3], (1, heads, S, Dv), dtype)
    scale = D ** -0.5
    with jax.default_matmul_precision("highest"):
        _, res = fa._flash_fwd(q, k, v, causal, scale, tile, tile)
        o, lse = res[3:]
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
        want = fa._reference_backward(q, k, v, lse, do, delta, scale, causal)
        whole = fa._flash_bwd(causal, scale, tile, tile, res, do)
        monkeypatch.setattr(fa, "_WHOLE_SEQ_MAX", 128)
        steps = S // (tile or fa._auto_tiles(S, causal)[1][1])
        assert steps == (2 if tile else 5)
        with warnings.catch_warnings():
            warnings.simplefilter("error", fa.AttentionFallbackWarning)
            long = fa._flash_bwd(causal, scale, tile, tile, res, do)
    tol = 2e-5 if dtype == jnp.float32 else 6e-2
    for what, g, w, other in zip(("dq", "dk", "dv"), long, want, whole):
        assert g.shape == w.shape and g.dtype == w.dtype == dtype, what
        for against in (w, other):
            err = jnp.max(jnp.abs(g.astype(jnp.float32)
                                  - against.astype(jnp.float32)))
            assert float(err) < tol * max(1.0, float(jnp.max(jnp.abs(
                w.astype(jnp.float32))))), what


def test_a_slice_that_does_not_fit_vmem_falls_back(monkeypatch):
    """The long backward holds a (b, h) slice's q, do, statistics and dq in
    VMEM while its k tiles pass (`_bwd_held_bytes`): S = 16,384 fits at the
    widest heads a cell has, S = 32,768 at none, and Mosaic would refuse it
    ("Ran out of memory in memory space vmem").  `_tiling_problem` names
    it first and the call takes the reference with the warning every
    untileable shape gets, with the reference's gradients."""
    bf16 = jnp.bfloat16
    assert fa._bwd_held_bytes(8192, 192, 128, bf16) == 8192 * 5632
    assert fa._bwd_held_bytes(4096, 128, 128, bf16) == 4096 * 4096
    for S, D, Dv, fits in ((16384, 192, 128, True), (16384, 64, 64, True),
                           (32768, 64, 64, False), (32768, 128, 128, False)):
        problem = fa._tiling_problem(S, 512, 512,
                                     fa._bwd_held_bytes(S, D, Dv, bf16))
        assert (problem is None) == fits, (S, D)
        assert fits or "VMEM" in problem
    # at an interpretable size: the chip's room lowered to under the slice's
    monkeypatch.setattr(fa, "_WHOLE_SEQ_MAX", 128)
    monkeypatch.setattr(fa, "_VMEM_MAX", fa._TILE_VMEM + (1 << 20))
    q, k, v = (_tr(x) for x in _qkv(2, H=4, D=32))
    assert fa._bwd_held_bytes(S, 32, 32, q.dtype) > 1 << 20

    def grads(attend):
        return jax.grad(lambda q, k, v: jnp.sum(jnp.sin(attend(q, k, v))),
                        (0, 1, 2))(q, k, v)

    with jax.default_matmul_precision("highest"):
        with pytest.warns(fa.AttentionFallbackWarning, match="VMEM") as seen:
            got = grads(lambda q, k, v: fa.flash_attention(
                q, k, v, True, None, 128, 128))
        # the forward holds no slice and keeps its kernel
        assert len(seen) == 1
        want = grads(lambda q, k, v: fa.reference_attention(
            q, k, v, 32 ** -0.5, True)[0])
    for g, w in zip(got, want):
        assert float(jnp.max(jnp.abs(g - w))) < 2e-5


# -- a mask that is data ------------------------------------------------------

# (query heads, key/value heads, q/k width, v width): a group of 8 at one
# width and at two (q, k wider than v: latent attention's), and equal heads
MASKED_SHAPES = {
    "a group of 8": (8, 1, 16, 16),
    "a group of 8, q, k 24 and v 16": (8, 1, 24, 16),
    "equal heads of 64": (2, 2, 64, 64),
}


def _random_mask(S, seed=0, empty_tile=None):
    """(1, S, S) int8: a third of the pairs and every query's own position;
    ``empty_tile`` (q tile, k tile) of 128 x 128 holds nothing."""
    mask = np.array(jax.random.uniform(jax.random.PRNGKey(seed), (1, S, S))
                    < 0.3) | np.eye(S, dtype=bool)
    if empty_tile is not None:
        i, j = empty_tile
        mask[:, 128 * i:128 * (i + 1), 128 * j:128 * (j + 1)] = False
    return jnp.asarray(mask, jnp.int8)


def _masked_dense(q, k, v, mask, causal):
    """A masked softmax by einsum, k and v repeated: float32, no kernel."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    seen = mask[:, None] != 0
    if causal:
        seen = seen & jnp.tril(jnp.ones(s.shape[-2:], bool))
    return jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("form", ["whole", "long"])
@pytest.mark.parametrize("name", sorted(MASKED_SHAPES))
def test_masked_kernels_match_a_masked_dense_einsum(name, form, causal,
                                                    monkeypatch):
    """o, dq, dk, dv of the interpreted forward and one-kernel backward
    under a mask that is data, a grid step the whole sequence and the tiles
    on the grid (`_WHOLE_SEQ_MAX` lowered; 2 x 2 tiles of 128, one of them
    without an attended pair, which adds nothing), with and without `causal`
    beside it."""
    heads, kv_heads, D, Dv = MASKED_SHAPES[name]
    S = 256
    if form == "long":
        monkeypatch.setattr(fa, "_WHOLE_SEQ_MAX", 128)
    ks = jax.random.split(jax.random.PRNGKey(heads + D), 3)
    q = jax.random.normal(ks[0], (1, heads, S, D))
    k = jax.random.normal(ks[1], (1, kv_heads, S, D))
    v = jax.random.normal(ks[2], (1, kv_heads, S, Dv))
    mask = _random_mask(S, empty_tile=(1, 0))

    def grads(attend):
        return _value_and_grads(attend, q, k, v)

    with jax.default_matmul_precision("highest"), warnings.catch_warnings():
        warnings.simplefilter("error", fa.AttentionFallbackWarning)
        got = grads(lambda q, k, v: fa.flash_attention(
            q, k, v, causal, None, 128, 128, mask))
        want = grads(lambda q, k, v: _masked_dense(q, k, v, mask, causal))
    for what, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert g.shape == w.shape, what
        assert float(jnp.max(jnp.abs(g - w))) < 3e-5, what
    kernels = _kernels(jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
        q, k, v, causal, None, 128, 128, mask)), (0, 1, 2)), q, k, v)
    assert kernels == {"_fwd_kernel", "_bwd_fused_kernel"}


def test_a_mask_of_every_causal_pair_is_the_causal_kernel():
    """`topk` >= S: the selection is the causal triangle
    (`ops/sparse_index.py:select_top_k`), and the kernels under it give what
    the causal kernels give without one, to the last bit; through the
    (B, S, H, D) entry, which hands a mask to the head-major kernels."""
    from ray_tpu.ops.sparse_index import select_top_k

    q, k, v = _qkv(1)                            # 8 heads on 1: a group of 8
    mask = select_top_k(jnp.zeros((1, S, S)), S, block=64)
    assert np.array_equal(np.asarray(mask[0]), np.tril(np.ones((S, S))))
    got = _value_and_grads(lambda q, k, v: attention(q, k, v, mask=mask),
                           q, k, v)
    want = _value_and_grads(lambda q, k, v: attention(q, k, v), q, k, v)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))
    o, lse = attention(q, k, v, mask=mask, with_lse=True)
    assert np.array_equal(np.asarray(o), np.asarray(want[0]))
    _, want_lse = fa.reference_attention(_tr(q), _tr(k), _tr(v), D ** -0.5,
                                         True)
    assert lse.shape == (1, H, S)
    assert float(jnp.max(jnp.abs(lse - want_lse))) < 1e-4


def test_the_lane_layout_and_the_ring_decline_a_mask():
    """Equal heads of 64 take the lane layout without a mask and the
    head-major kernels with one; `ring` and `ulysses` refuse it as they
    refuse unequal heads; the dense variant applies it."""
    q, k, v = _qkv(H, D=64)
    mask = _random_mask(S, seed=3)
    plain = _kernels(lambda q, k, v: attention(q, k, v), q, k, v)
    masked = _kernels(lambda q, k, v: attention(q, k, v, mask=mask), q, k, v)
    assert plain == {"_fwd_kernel_lanes"} and masked == {"_fwd_kernel"}
    from ray_tpu.parallel.context import use_mesh

    for variant in ("ring", "ulysses"):
        with use_mesh(create_mesh({"sp": 8})), pytest.raises(
                NotImplementedError, match="takes no mask"):
            attention(q, k, v, variant=variant, mask=mask)
    with pytest.raises(NotImplementedError, match="with_lse"):
        attention(q, k, v, variant="dense", with_lse=True)
    with jax.default_matmul_precision("highest"):
        dense = attention(q, k, v, variant="dense", mask=mask)
        flash = attention(q, k, v, mask=mask)
        want = _tr(_masked_dense(_tr(q), _tr(k), _tr(v), mask, True))
    assert float(jnp.max(jnp.abs(dense - want))) < 2e-5
    assert float(jnp.max(jnp.abs(flash - want))) < 2e-5


@pytest.mark.parametrize("kv_heads", [8, 2])
def test_flash_sharded_cuts_a_mask_with_the_batch(kv_heads):
    """Under a mesh of several devices the mask goes into the `shard_map`
    cut as the batch is and whole for every head; o and the row statistics
    are the unsharded call's."""
    mesh = create_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    q, k, v = _qkv(kv_heads, B=2, S=128)
    mask = jnp.concatenate([_random_mask(128, seed=s) for s in (1, 2)])
    with jax.default_matmul_precision("highest"):
        o, lse = jax.jit(lambda q, k, v: _flash_sharded(
            q, k, v, mesh, True, mask, True))(q, k, v)
        want, want_lse = attention(q, k, v, mask=mask, with_lse=True)
    assert float(jnp.max(jnp.abs(o - want))) < 2e-5
    assert float(jnp.max(jnp.abs(lse - want_lse))) < 2e-5


def test_a_masked_call_counts_what_is_known_when_it_is_traced():
    """`attention.tiles` and `attention.tiles_skipped` are of the causal
    square, with or without a mask: every causal tile is visited whatever
    the mask holds of it."""
    names = ("attention.tiles", "attention.tiles_skipped")
    q = jax.ShapeDtypeStruct((1, 2048, 8, 64), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 2048, 2, 64), jnp.bfloat16)
    mask = jax.ShapeDtypeStruct((1, 2048, 2048), jnp.int8)

    def traced(*extra):
        before = [tracing.counter(name) for name in names]
        jax.eval_shape(jax.grad(lambda q, k, v, *m: jnp.sum(attention(
            q, k, v, **dict(zip(("mask",), m))).astype(jnp.float32)),
            (0, 1, 2)), q, k, k, *extra)
        return [tracing.counter(name) - b for name, b in zip(names, before)]

    with tracing.timeline_span("train.fit", root=True):
        assert traced(mask) == traced() == [4 + 16, 1 + 6]


@pytest.mark.parametrize("shape,kv_heads", [((2, 8192, 32, 128), 4),
                                            ((1, 1024, 8, 64), 8)])
def test_masked_kernels_lower_to_mosaic_for_tpu(shape, kv_heads):
    """The cell's shape (32 query heads on 4 key/value heads of 128 over
    8,192 positions: the mask tile-major) and a sequence a
    grid step takes whole, exported for a TPU from this CPU host: two
    Mosaic custom calls, no interpreted kernel body and no O(S^2)
    reference."""
    B, S, heads, D = shape
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    k = jax.ShapeDtypeStruct((B, S, kv_heads, D), jnp.bfloat16)
    mask = jax.ShapeDtypeStruct((B, S, S), jnp.int8)
    module = jax.export.export(
        jax.jit(jax.grad(lambda q, k, v, mask: jnp.sum(attention(
            q, k, v, mask=mask).astype(jnp.float32) ** 2), (0, 1, 2))),
        platforms=["tpu"])(q, k, k, mask).mlir_module()
    assert module.count("stablehlo.custom_call @tpu_custom_call") == 2
    assert "stablehlo.dot_general" not in module
    assert "stablehlo.while" not in module
