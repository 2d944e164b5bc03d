"""Tensor-plane tests on the virtual 8-device CPU mesh: mesh/sharding,
flash attention, ring attention, ulysses, collectives."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.ops.flash_attention import flash_attention, reference_attention
from ray_tpu.parallel.attention import attention
from ray_tpu.parallel.context import use_mesh
from ray_tpu.parallel.mesh import create_mesh
from ray_tpu.parallel.ring_attention import ulysses_attention
from ray_tpu.parallel.sharding import ShardingConfig, shard_params

TOL = 2e-2  # CPU backend matmuls are low-precision by default

def _qkv(B=2, H=4, S=128, D=32, dtype=jnp.float32):
    key = jax.random.PRNGKey(0)
    return tuple(
        jax.random.normal(jax.random.fold_in(key, i), (B, H, S, D), dtype)
        for i in range(3)
    )


def _ring(q, k, v, mesh, causal):
    """`attention(variant="ring")` under `mesh`, on head-major arrays."""
    tr = lambda x: x.transpose(0, 2, 1, 3)
    with use_mesh(mesh):
        return tr(attention(tr(q), tr(k), tr(v), causal=causal,
                            variant="ring"))


def test_device_count():
    assert len(jax.devices()) == 8


def test_create_mesh_axes():
    mesh = create_mesh({"dp": 2, "sp": 2, "tp": 2})
    assert mesh.shape == {"dp": 2, "sp": 2, "tp": 2}
    mesh2 = create_mesh({"dp": -1, "tp": 2})
    assert mesh2.shape["dp"] == 4


def test_flash_attention_matches_reference():
    q, k, v = _qkv()
    for causal in (False, True):
        o = flash_attention(q, k, v, causal)
        ref, _ = reference_attention(q, k, v, q.shape[-1] ** -0.5, causal)
        np.testing.assert_allclose(o, ref, atol=TOL)


def test_flash_attention_backward_matches_reference():
    """The pallas dq/dk/dv kernels (interpret mode on CPU) must match the
    dense-attention gradients."""
    q, k, v = _qkv(B=1, H=2, S=128, D=32)

    def loss_flash(q, k, v, causal, bq, bk):
        return jnp.sum(flash_attention(q, k, v, causal, None, bq, bk) ** 2)

    def loss_ref(q, k, v, causal):
        o, _ = reference_attention(q, k, v, q.shape[-1] ** -0.5, causal)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    # block 128 = single-block path; block 32 = 4x4 blocks, exercising the
    # inner fori loops and the causal start/last block arithmetic.
    for block in (128, 32):
        for causal in (False, True):
            gf = jax.grad(loss_flash, argnums=(0, 1, 2))(
                q, k, v, causal, block, block)
            gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v, causal)
            for a, b, name in zip(gf, gr, ("dq", "dk", "dv")):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), atol=TOL,
                    err_msg=f"{name} causal={causal} block={block}",
                )


@pytest.mark.parametrize("bq,bk", [(128, 128), (128, 256), (256, 128),
                                   (256, 256)])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("whole", [False, True])
def test_pallas_kernels_direct_multiblock(bq, bk, causal, whole):
    """Exercise _pallas_forward/_pallas_backward directly (interpret mode)
    at S=256 with mixed block sizes, in both forms a call can take.  Not
    ``whole`` (what a sequence past `_WHOLE_SEQ_MAX` runs): the grid walks
    the forward's q tiles, each with its first_diag/last two-phase fori
    loops over k blocks, and the backward's k tiles (a grid step takes
    ``bk`` rows of k), each with the same loops over q blocks, dq summed
    over the k tiles in scratch.  ``whole``: a grid step takes the
    sequence, the forward walks its q tiles and the backward its k tiles
    over merged spans.  One backward kernel either way."""
    from ray_tpu.ops.flash_attention import (
        _pallas_backward,
        _pallas_forward,
    )

    B, H, S, D = 1, 2, 256, 32
    q, k, v = _qkv(B, H, S, D)
    scale = D ** -0.5

    o, lse = _pallas_forward(q, k, v, scale, causal, bq, bk, whole=whole,
                             interpret=True)
    ref_o, ref_lse = reference_attention(q, k, v, scale, causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref_o), atol=TOL)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               atol=TOL)

    def loss_ref(q, k, v):
        o, _ = reference_attention(q, k, v, scale, causal)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    do = (2.0 * o).astype(q.dtype)  # d/do of sum(o^2)
    dq, dk, dv = _pallas_backward(q, k, v, o, lse, do, scale, causal,
                                  bq, bk, k_rows=S if whole else bk,
                                  interpret=True)
    for a, b, name in zip((dq, dk, dv), gr, ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-2,
            err_msg=f"{name} causal={causal} bq={bq} bk={bk} whole={whole}")


def test_ring_attention_matches_dense():
    B, H, S, D = 2, 4, 128, 32
    q, k, v = _qkv(B, H, S, D)
    mesh = create_mesh({"sp": 8})
    for causal in (False, True):
        out = _ring(q, k, v, mesh, causal)
        ref, _ = reference_attention(q, k, v, D ** -0.5, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=TOL)


def test_ring_attention_grad():
    B, H, S, D = 1, 2, 64, 16
    q, k, v = _qkv(B, H, S, D)
    mesh = create_mesh({"sp": 8})

    def loss_ring(q, k, v):
        return (_ring(q, k, v, mesh, True) ** 2).sum()

    def loss_ref(q, k, v):
        o, _ = reference_attention(q, k, v, D ** -0.5, True)
        return (o ** 2).sum()

    # all three grads: dq exercises the local accumulation, dk/dv the
    # rotating ring accumulators of the hand-written backward
    g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-2,
                                   err_msg=name)


def test_ulysses_attention_matches_dense():
    B, H, S, D = 2, 8, 128, 32
    q, k, v = _qkv(B, H, S, D)
    mesh = create_mesh({"sp": 8})
    spec = P(None, None, "sp", None)

    out = jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, "sp", True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
    ref, _ = reference_attention(q, k, v, D ** -0.5, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=TOL)


def test_sharding_config_specs():
    cfg = ShardingConfig(dp=2, fsdp=2, tp=2)
    mesh = cfg.build_mesh()
    assert cfg.spec(mesh, "batch", "embed") == P(("dp", "fsdp"), None)
    # embed rule maps to fsdp for params
    assert cfg.spec(mesh, "embed", "mlp") == P("fsdp", "tp")
    # absent axes collapse to replication
    cfg2 = ShardingConfig(dp=8)
    mesh2 = cfg2.build_mesh()
    assert cfg2.spec(mesh2, "embed", "mlp") == P(None, None)


def test_shard_params_places_leaves():
    cfg = ShardingConfig(fsdp=2, tp=4)
    mesh = cfg.build_mesh()
    params = {
        "wte": {"embedding": jnp.zeros((1024, 256))},
        "h_0": {"attn": {"c_attn": {"kernel": jnp.zeros((256, 768))}},
                "ln_1": {"scale": jnp.zeros((256,))}},
    }
    sharded = shard_params(params, cfg, mesh)
    emb = sharded["wte"]["embedding"]
    assert emb.sharding.spec == P("tp", "fsdp")
    qkv = sharded["h_0"]["attn"]["c_attn"]["kernel"]
    assert qkv.sharding.spec == P("fsdp", "tp")


def test_xla_collectives():
    from ray_tpu.collective import xla

    mesh = create_mesh({"dp": 8})
    x = jnp.arange(8.0)

    out = jax.shard_map(
        lambda x: xla.allreduce(x, "dp"),
        mesh=mesh, in_specs=P("dp"), out_specs=P("dp"), check_vma=False,
    )(x)
    assert np.asarray(out).tolist() == [28.0] * 8

    out = jax.shard_map(
        lambda x: xla.broadcast(x, "dp", root=3),
        mesh=mesh, in_specs=P("dp"), out_specs=P("dp"), check_vma=False,
    )(x)
    assert np.asarray(out).tolist() == [3.0] * 8


def test_host_collectives(ray_shared):
    ray = ray_shared

    @ray.remote
    def rank_fn(world, rank):
        from ray_tpu import collective as col

        col.init_collective_group(world, rank, backend="host",
                                  group_name=f"g{world}")
        total = col.allreduce(np.array([rank + 1.0]), group_name=f"g{world}")
        col.barrier(group_name=f"g{world}")
        got = col.broadcast(np.array([rank * 10.0]), root=2,
                            group_name=f"g{world}")
        return float(total[0]), float(got[0])

    results = ray.get([rank_fn.remote(4, r) for r in range(4)], timeout=120)
    assert all(t == 10.0 for t, _ in results)
    assert all(g == 20.0 for _, g in results)


def test_moe_ep_sharded_matches_single_device():
    """Expert-parallel MoE: loss on an ep-sharded mesh matches the
    unsharded computation (XLA SPMD dispatches via all_to_all)."""
    from dataclasses import replace

    from ray_tpu.models import gpt2
    from ray_tpu.parallel.context import use_mesh

    cfg = replace(gpt2.GPT2_TINY, moe_experts=4, attention="dense",
                  compute_dtype=jnp.float32)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                                cfg.vocab_size)
    ref = float(gpt2.loss_fn(params, {"tokens": tokens}, cfg))

    scfg = ShardingConfig(ep=2, tp=2, dp=2)
    mesh = scfg.build_mesh()
    sharded = shard_params(params, scfg, mesh)
    with use_mesh(mesh):
        got = float(jax.jit(lambda p, b: gpt2.loss_fn(p, b, cfg))(
            sharded, {"tokens": tokens}))
    assert abs(got - ref) < 1e-3, (got, ref)


def test_pipeline_matches_sequential():
    """pp=2 pipelined blocks produce the same loss as the sequential
    single-device model (the GPipe schedule only reorders work)."""
    from dataclasses import replace

    from ray_tpu.models import gpt2
    from ray_tpu.parallel.context import use_mesh

    cfg = replace(gpt2.GPT2_TINY, attention="dense",
                  compute_dtype=jnp.float32)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                                cfg.vocab_size)
    ref = float(gpt2.loss_fn(params, {"tokens": tokens}, cfg))

    scfg = ShardingConfig(pp=2, tp=2, dp=2)
    mesh = scfg.build_mesh()
    pp_params = shard_params(gpt2.to_pipeline_params(params, cfg),
                             scfg, mesh)
    with use_mesh(mesh):
        got = float(jax.jit(
            lambda p, b: gpt2.loss_fn(p, b, cfg, 2))(
                pp_params, {"tokens": tokens}))
    assert abs(got - ref) < 1e-3, (got, ref)


def test_pipeline_moe_train_step_learns():
    """Full fwd+bwd+adamw on a pp x ep x tp mesh: grads flow through the
    ppermute schedule and the expert dispatch; loss decreases."""
    from dataclasses import replace

    import optax

    from ray_tpu.models import gpt2
    from ray_tpu.parallel.context import use_mesh

    cfg = replace(gpt2.GPT2_TINY, moe_experts=2, attention="dense",
                  compute_dtype=jnp.float32)
    params = gpt2.to_pipeline_params(
        gpt2.init_params(jax.random.PRNGKey(0), cfg), cfg)
    scfg = ShardingConfig(pp=2, ep=2, tp=2)
    mesh = scfg.build_mesh()
    params = shard_params(params, scfg, mesh)
    opt = optax.adamw(1e-3)
    ost = opt.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                                cfg.vocab_size)
    step = jax.jit(gpt2.make_train_step(cfg, opt, pp_microbatches=2))
    with use_mesh(mesh):
        p, o, m1 = step(params, ost, {"tokens": tokens})
        _, _, m2 = step(p, o, {"tokens": tokens})
    assert float(m2["loss"]) < float(m1["loss"])


@pytest.mark.parametrize("H,D", [(4, 32), (2, 64), (1, 128)])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_bshd_lane_path(H, D, causal):
    """The (B, S, H, D) lane-layout kernels (head slices from 128-wide lane
    blocks, fused whole-S backward) must match the dense reference — this is
    the models' default attention path.  hpb = 128//D covers 4/2/1 heads per
    lane block; fused single-pass bwd runs since S <= 1024."""
    from ray_tpu.ops.flash_attention import (
        _bshd_lanes_ok,
        flash_attention_bshd,
    )

    B, S = 2, 128
    key = jax.random.PRNGKey(1)
    q, k, v = (
        jax.random.normal(jax.random.fold_in(key, i), (B, S, H, D),
                          jnp.float32) * 0.5
        for i in range(3)
    )
    assert _bshd_lanes_ok(q, S, S, S)
    tr = lambda x: x.transpose(0, 2, 1, 3)

    o = flash_attention_bshd(q, k, v, causal)
    ref, _ = reference_attention(tr(q), tr(k), tr(v), D ** -0.5, causal)
    np.testing.assert_allclose(np.asarray(tr(o)), np.asarray(ref), atol=TOL)

    def loss_lane(q, k, v):
        return jnp.sum(flash_attention_bshd(q, k, v, causal) ** 2)

    def loss_ref(q, k, v):
        o, _ = reference_attention(tr(q), tr(k), tr(v), D ** -0.5, causal)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    gl = jax.grad(loss_lane, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gl, gr, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-2,
                                   err_msg=f"{name} causal={causal} D={D}")


# (B, S, H, D) -> Mosaic kernels in forward + backward: the lane kernels
# with the one-kernel backward, the transposing bhsd kernels where heads do
# not pair up into 128 lanes (25, 3), and past _WHOLE_SEQ_MAX the forward
# over q tiles and the one backward kernel over k tiles; last, the medium
# cell's own shape with 256-tiles forced in both passes
@pytest.mark.parametrize("shape,kernels,block", [
    ((16, 1024, 12, 64), 2, None), ((8, 1024, 16, 64), 2, None),
    ((4, 1024, 25, 64), 2, None), ((16, 1024, 3, 64), 2, None),
    ((2, 2048, 32, 128), 2, None), ((16, 1024, 16, 64), 2, 256)])
def test_flash_attention_lowers_to_mosaic_for_tpu(shape, kernels, block):
    """Exported for a TPU from this CPU host, forward + backward are Mosaic
    custom calls and nothing else: no interpreted kernel body and no O(S^2)
    reference (either would show up as dots in the module).  Catches a
    change that breaks Mosaic lowering before it costs chip time."""
    from ray_tpu.ops.flash_attention import flash_attention_bshd

    def loss(q, k, v):
        o = flash_attention_bshd(q, k, v, True, None, block, block)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    module = jax.export.export(
        jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
        platforms=["tpu"])(x, x, x).mlir_module()
    assert module.count("stablehlo.custom_call @tpu_custom_call") == kernels
    assert "stablehlo.dot_general" not in module
    assert "stablehlo.while" not in module


def test_flash_attention_reference_fallback_warns():
    """A sequence the kernels cannot tile runs the O(S^2) reference on every
    platform — visibly."""
    from ray_tpu.ops.flash_attention import AttentionFallbackWarning

    q, k, v = _qkv(B=1, H=1, S=1032, D=8)  # 1032 = 8 * 129: blocks fall to 8
    with pytest.warns(AttentionFallbackWarning, match=r"\(1, 1, 1032, 8\)"):
        o = flash_attention(q, k, v, True)
    ref, _ = reference_attention(q, k, v, 8 ** -0.5, True)
    np.testing.assert_allclose(o, ref, atol=TOL)


def test_flash_attention_fused_bwd_mixed_dtypes():
    """dk/dv must come back in k/v's dtype on the fused single-block paths
    (regression: out_shape used q.dtype for all three)."""
    B, H, S, D = 1, 2, 128, 32
    q, k, v = _qkv(B, H, S, D)
    k = k.astype(jnp.bfloat16)
    v = v.astype(jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True).astype(jnp.float32))

    dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert dq.dtype == jnp.float32
    assert dk.dtype == jnp.bfloat16
    assert dv.dtype == jnp.bfloat16


def _pallas_calls(jaxpr):
    """The `pallas_call` equations of a jaxpr, nested ones (jit, the
    platform's branches, custom_vjp) included."""
    from jax.extend import core as jex_core

    def subjaxprs(value):
        if isinstance(value, jex_core.ClosedJaxpr):
            yield value.jaxpr
        elif isinstance(value, jex_core.Jaxpr):
            yield value
        elif isinstance(value, (tuple, list)):
            for item in value:
                yield from subjaxprs(item)

    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for value in eqn.params.values():
            for sub in subjaxprs(value):
                yield from _pallas_calls(sub)


def _pallas_kernels(jaxpr):
    """{kernel function's name: its body holds a loop} over the
    `pallas_call`s of a jaxpr (`_pallas_calls`)."""
    found = {}
    for eqn in _pallas_calls(jaxpr):
        name = eqn.params["jaxpr"].debug_info.func_name
        # fori_loop: `while` under traced bounds, `scan` under static
        body = str(eqn.params["jaxpr"])
        found[name] = (found.get(name, False) or "while[" in body
                       or "scan[" in body)
    return found


def _bshd_against_reference(q, k, v, causal, block_q, block_k, kernels):
    """Forward and dq / dk / dv of `flash_attention_bshd` with these tiles
    against the dense reference, at the tolerances of the tests above; and
    that the kernels that ran were ``kernels`` (`_pallas_kernels`)."""
    from ray_tpu.ops.flash_attention import flash_attention_bshd

    D = q.shape[-1]
    tr = lambda x: x.transpose(0, 2, 1, 3)

    def loss_flash(q, k, v):
        o = flash_attention_bshd(q, k, v, causal, None, block_q, block_k)
        return jnp.sum(o.astype(jnp.float32) ** 2), o

    def loss_ref(q, k, v):
        o, _ = reference_attention(tr(q), tr(k), tr(v), D ** -0.5, causal)
        return jnp.sum(o.astype(jnp.float32) ** 2), tr(o)

    grad = lambda f: jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)
    assert _pallas_kernels(
        jax.make_jaxpr(grad(loss_flash))(q, k, v).jaxpr) == kernels
    (_, o), gf = grad(loss_flash)(q, k, v)
    (_, ref), gr = grad(loss_ref)(q, k, v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=TOL)
    for a, b, name in zip(gf, gr, ("dq", "dk", "dv")):
        assert a.dtype == b.dtype, name
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=5e-2,
            err_msg=f"{name} causal={causal} tiles=({block_q}, {block_k})")


# {kernel: its body loops} of a gradient in the two forms (`_pallas_kernels`)
_WHOLE_LANES = {"_fwd_kernel_lanes": False, "_bwd_fused_kernel_lanes": False}
_WHOLE_BHSD = {"_fwd_kernel": False, "_bwd_fused_kernel": False}
# past `_WHOLE_SEQ_MAX`: the same head-major kernel, a k tile a grid step
_LOOPED_FUSED = {"_bwd_fused_kernel": True}


def _bshd_qkv(S, H, D, dtype=jnp.float32):
    key = jax.random.PRNGKey(2)
    return tuple(
        jax.random.normal(jax.random.fold_in(key, i), (1, S, H, D), dtype)
        * 0.5 for i in range(3))


# heads x head_dim: two heads a lane block, one, and 3 heads of 64, which
# fill no lane block and take the transposing head-major kernels
@pytest.mark.parametrize("H,D", [(2, 64), (1, 128), (3, 64)])
@pytest.mark.parametrize("bq,bk", [(128, 128), (128, 256), (256, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_tiled(H, D, bq, bk, causal):
    """Several tiles inside the kernels that take a short sequence whole
    (forward walking its q tiles, one-kernel backward): spans merged below
    the diagonal, masked on it, nothing above it."""
    from ray_tpu.ops.flash_attention import _bshd_lanes_ok

    q, k, v = _bshd_qkv(256, H, D)
    assert _bshd_lanes_ok(q, 256, bq, bk) == (H != 3)
    _bshd_against_reference(q, k, v, causal, bq, bk,
                            _WHOLE_BHSD if H == 3 else _WHOLE_LANES)


@pytest.mark.parametrize("tiles", [2, 4])
def test_flash_attention_tiled_diagonal_only_rows(tiles):
    """`tiles` square tiles a side: the first row tile has only its masked
    diagonal tile (no interior span), the last k tile only its diagonal q
    tile (no span below it)."""
    q, k, v = _bshd_qkv(128 * tiles, 2, 64)
    _bshd_against_reference(q, k, v, True, 128, 128, _WHOLE_LANES)


def test_flash_attention_tiled_mixed_dtypes():
    """k/v in bf16 under an f32 q through the tiled one-kernel backward:
    dk/dv come back in k/v's dtype (the regression of
    test_flash_attention_fused_bwd_mixed_dtypes, several tiles)."""
    q, k, v = _bshd_qkv(256, 2, 64)
    _bshd_against_reference(q, k.astype(jnp.bfloat16),
                            v.astype(jnp.bfloat16), True, 128, 128,
                            _WHOLE_LANES)


@pytest.mark.parametrize("H,D", [(2, 64), (3, 64)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_long_sequence_forms(H, D, causal, monkeypatch):
    """Past `_WHOLE_SEQ_MAX` (lowered here so that an interpretable size
    passes it) the grid walks the forward's q tiles, each looping over its
    k blocks, and the backward's k tiles, each looping over its q blocks:
    two kernels with loops (the lane layout declines the long backward,
    which goes head-major), whatever an earlier test traced at these
    shapes (what a grid step takes is a static argument of the jitted
    kernel calls)."""
    from ray_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_WHOLE_SEQ_MAX", 128)
    q, k, v = _bshd_qkv(256, H, D)
    forward = "_fwd_kernel" if H == 3 else "_fwd_kernel_lanes"
    _bshd_against_reference(q, k, v, causal, 128, 128,
                            {forward: True, **_LOOPED_FUSED})


@pytest.mark.parametrize("bq,bk", [(128, 128), (256, 128), (128, 256)])
@pytest.mark.parametrize("causal", [True, False])
def test_long_backward_sums_dq_over_its_k_tiles(bq, bk, causal, monkeypatch):
    """S = 512 past a lowered `_WHOLE_SEQ_MAX`: a (b, h) slice's backward
    is four grid steps (two at 256), each adding its k tile's part of dq
    into the f32 scratch, which is written once at the last.  dq, dk and dv
    in float32 against the reference's, tightly: a part dropped, doubled or
    written early would be a whole tile's worth off."""
    from ray_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_WHOLE_SEQ_MAX", 128)
    q, k, v = (x.transpose(0, 2, 1, 3) for x in _bshd_qkv(512, 3, 32))

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v) ** 2)

    flash = loss(lambda q, k, v: flash_attention(q, k, v, causal, None,
                                                 bq, bk))
    dense = loss(lambda q, k, v: reference_attention(
        q, k, v, 32 ** -0.5, causal)[0])
    assert _pallas_kernels(jax.make_jaxpr(jax.grad(flash, (0, 1, 2)))(
        q, k, v).jaxpr) == {"_fwd_kernel": True, **_LOOPED_FUSED}
    with jax.default_matmul_precision("highest"):
        got = jax.grad(flash, (0, 1, 2))(q, k, v)
        want = jax.grad(dense, (0, 1, 2))(q, k, v)
    for name, g, w in zip(("dq", "dk", "dv"), got, want):
        assert float(jnp.max(jnp.abs(g - w))) < 2e-5, name


@pytest.mark.parametrize("group", [1, 2])
def test_long_backward_takes_the_ring_s_delta(group, monkeypatch):
    """What `ring_attention` does with a rotating chunk: the backward of
    one chunk of k and v under the lse and delta of the WHOLE row
    (`_flash_bwd(..., delta=)`: o here is not this chunk's output, so the
    kernel may not make delta from it), a causal chunk and a non-causal
    one, in the long form.  dq summed over the chunks and dk, dv side by
    side are the gradients of attention over both."""
    from ray_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_WHOLE_SEQ_MAX", 128)
    S, H, D = 256, 2, 32
    q, _, _ = (x.transpose(0, 2, 1, 3) for x in _bshd_qkv(S, H, D))
    k, v = (x.transpose(0, 2, 1, 3)[:, :H // group]
            for x in _bshd_qkv(2 * S, H, D)[1:])
    scale = D ** -0.5

    def whole_row(q, k, v):
        # the second half of a causal sequence's rows: every key of the
        # first chunk, the keys up to its own position of the second
        kr, vr = (jnp.repeat(x, group, axis=1) for x in (k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kr) * scale
        mask = jnp.arange(S)[:, None] + S >= jnp.arange(2 * S)[None, :]
        s = jnp.where(mask, s, -1e30)
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", jnp.exp(s - lse[..., None]),
                          vr), lse

    with jax.default_matmul_precision("highest"):
        (o, lse), vjp = jax.vjp(whole_row, q, k, v)
        do = 2.0 * o
        want = vjp((do, jnp.zeros_like(lse)))
        delta = jnp.sum(do * o, axis=-1)
        parts = [fa._flash_bwd(causal, scale, 128, 128,
                               (q, k[:, :, rows], v[:, :, rows], o, lse), do,
                               delta=delta)
                 for causal, rows in ((False, slice(0, S)),
                                      (True, slice(S, 2 * S)))]
    got = (parts[0][0] + parts[1][0],
           jnp.concatenate([parts[0][1], parts[1][1]], axis=2),
           jnp.concatenate([parts[0][2], parts[1][2]], axis=2))
    for name, g, w in zip(("dq", "dk", "dv"), got, want):
        assert g.shape == w.shape, name
        assert float(jnp.max(jnp.abs(g - w))) < 2e-5, name


def _backward_call(S, causal, block=128):
    """The `pallas_call` equation of `flash_attention`'s backward at
    (1, 2, S, 32) with square tiles of ``block``."""
    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, None, block, block))

    x = jax.ShapeDtypeStruct((1, 2, S, 32), jnp.float32)
    found = [eqn for eqn in _pallas_calls(jax.make_jaxpr(
        jax.grad(loss, (0, 1, 2)))(x, x, x).jaxpr)
        if eqn.params["jaxpr"].debug_info.func_name == "_bwd_fused_kernel"]
    # the platform's two branches (compiled, interpreted) hold the same call
    assert len({str(eqn.params["jaxpr"]) for eqn in found}) == 1
    return found[0]


def _score_rows(call):
    """The rows of every (rows, block_k) score tile a backward kernel's
    body makes: the first operand's of each product contracted over the
    head's width."""
    import re

    return sorted({int(rows) for rows in re.findall(
        r"= dot_general\[\s*dimension_numbers=\(\(\[1\], \[1\]\)"
        r".*?f32\[(\d+),32\]", str(call.params["jaxpr"]), re.S)})


@pytest.mark.parametrize("S,steps", [(256, 1), (512, 4)])
@pytest.mark.parametrize("causal", [True, False])
def test_the_backward_s_k_tiles_walk_on_either_side_of_the_gate(
        S, steps, causal, monkeypatch):
    """The two sides of `_WHOLE_SEQ_MAX` (lowered to 256) in the ONE
    backward kernel.  Up to it a grid step holds the (b, h) slice's k, its
    tiles are unrolled and nothing loops or branches, and the q tiles of a
    kind are merged into one span: S rows at most, which is the spans' cap
    (a non-causal k tile scores all 256 rows at once; a causal one its
    diagonal tile's 128, and the first also the 128 below).  Past it the k
    tiles are the grid's second axis, last to first, each loops over q
    tiles of 128 rows, never a span of more, and dq is zeroed and written
    under `pl.when`."""
    from ray_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_WHOLE_SEQ_MAX", 256)
    call = _backward_call(S, causal)
    body = str(call.params["jaxpr"])
    grid = call.params["grid_mapping"].grid
    assert tuple(grid) == (2, steps)
    looped = "while[" in body or "scan[" in body
    assert looped == (steps > 1)
    assert ("cond[" in body) == (steps > 1)
    assert ("program_id" in body) == (steps > 1)
    if steps == 1:
        assert _score_rows(call) == ([128] if causal else [256])
    else:
        assert _score_rows(call) == [128]
    # k, v, dk and dv blocks: the whole sequence, or one tile of it
    held = [int(getattr(spec.block_shape[1], "block_size",
                        spec.block_shape[1]))
            for spec in call.params["grid_mapping"].block_mappings]
    k_rows = S if steps == 1 else 128
    #               q  k       v       do lse delta dq dk      dv
    assert held == [S, k_rows, k_rows, S, S, S, S, k_rows, k_rows]


def test_the_long_backward_s_k_tiles_pass_last_to_first():
    """`_kv_rows`: grid step i of a slice takes k tile ``last - i`` of the
    key/value head its query head reads (a causal slice's longest step, its
    first k tile's, comes last and hides the next slice's fetch); with the
    whole sequence a step the index maps are the ones they were."""
    from ray_tpu.ops import flash_attention as fa

    assert [fa._kv_rows(1, 3)(5, i) for i in range(4)] == [
        (5, 3, 0), (5, 2, 0), (5, 1, 0), (5, 0, 0)]
    assert [fa._kv_rows(4, 3)(5, i) for i in (0, 3)] == [(1, 3, 0), (1, 0, 0)]
    assert fa._kv_rows(1)(5, 0) == (5, 0, 0)
    assert fa._kv_rows(4)(5, 0) == (1, 0, 0)


@pytest.mark.parametrize("S,steps,limit", [
    # a step a slice: the default, as the kernels up to 1,024 always had
    (1024, 1, None),
    # k tiles on the grid: 16 MB + what the slice holds meanwhile, 4 KB a
    # row at D = 128 and 5.5 at 192 / 128 (q, k and dq take 256 lanes)
    (4096, 8, (16 << 20) + 4096 * 4096),
    (8192, 16, (16 << 20) + 8192 * 4096),
    (16384, 32, (16 << 20) + 16384 * 4096),
    # past the v5e's room: the limit stops at 100 MB; Mosaic would refuse
    # it, and `_tiling_problem` hands such a call to the reference first
    (32768, 64, 100 << 20),
])
def test_the_backward_s_vmem_budget_on_either_side_of_its_gates(
        S, steps, limit):
    from ray_tpu.ops import flash_attention as fa

    params = fa._compiler_params(S, 128, 128, jnp.bfloat16, bwd_steps=steps)
    if limit is None:
        assert params is fa._COMPILER_PARAMS
        return
    assert params.vmem_limit_bytes == limit
    assert params.dimension_semantics == ("parallel", "arbitrary")
    wide = fa._compiler_params(S, 192, 128, jnp.bfloat16, bwd_steps=steps)
    assert wide.vmem_limit_bytes == min(100 << 20, (16 << 20) + S * 5632)


def test_xl_s_own_backward_under_a_checkpointed_layer(monkeypatch):
    """The XL cell's form of the kernels at an interpretable size: 25 heads
    of 64 (no lane block: the head-major kernels through the (B, S, H, D)
    entry's transposes), a grid step a (b, h) slice with two tiles a side,
    under `checkpoint_layer`, which keeps o and the row statistics in the
    kernels' own (B*H, S, 1) so that the replay runs no forward kernel.
    Gradients against the dense reference's."""
    from ray_tpu.models.layers import checkpoint_layer
    from ray_tpu import ops
    from ray_tpu.ops import flash_attention as fa

    monkeypatch.setattr(ops, "INTERPRET_MAX_ELEMS", 1 << 20)
    B, S, H, D = 1, 256, 25, 64
    q, k, v = (x.astype(jnp.bfloat16) for x in _bshd_qkv(S, H, D))
    tr = lambda x: x.transpose(0, 2, 1, 3)

    def flash_layer(q, k, v):
        o = fa.flash_attention_bshd(q, k, v, True, None, 128, 128)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def dense_layer(q, k, v):
        o, _ = reference_attention(tr(q), tr(k), tr(v), D ** -0.5, True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    layer = checkpoint_layer(flash_layer)
    jaxpr = jax.make_jaxpr(jax.grad(layer, (0, 1, 2)))(q, k, v)
    assert _pallas_kernels(jaxpr.jaxpr) == _WHOLE_BHSD
    # what the layer keeps besides its arguments: o as the caller has it,
    # and lse a row a position, as the kernels write and read it
    from jax._src.ad_checkpoint import saved_residuals

    kept = sorted(tuple(x.shape) for x, where in
                  saved_residuals(layer, q, k, v)
                  if "from the argument" not in where)
    assert kept == [(B, S, H, D), (B * H, S, 1)]
    got = jax.jit(jax.grad(layer, (0, 1, 2)))(q, k, v)
    want = jax.grad(dense_layer, (0, 1, 2))(q, k, v)
    for name, g, w in zip(("dq", "dk", "dv"), got, want):
        assert g.dtype == w.dtype == jnp.bfloat16, name
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32), atol=5e-2,
            err_msg=name)


def test_flash_attention_counts_its_tiles():
    """`attention.tiles` / `attention.tiles_skipped` on the job timeline:
    once per kernel as it is traced, not per head slice."""
    from ray_tpu.ops.flash_attention import flash_attention_bshd
    from ray_tpu.util import tracing

    x = jax.ShapeDtypeStruct((2, 1024, 4, 64), jnp.bfloat16)

    def traced(causal, block):
        def loss(q, k, v):
            o = flash_attention_bshd(q, k, v, causal, None, block, block)
            return jnp.sum(o.astype(jnp.float32))

        before = [tracing.counter(name) for name in names]
        jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)
        return [tracing.counter(name) - b for name, b in zip(names, before)]

    names = ("attention.tiles", "attention.tiles_skipped")
    assert traced(True, 256) == [0, 0]           # no job, no count
    with tracing.timeline_span("train.fit", root=True) as job:
        # forward 16 tiles of which 6 above the diagonal, backward the same
        assert traced(True, 256) == [32, 12]
        assert traced(False, 256) == [32, 0]
        # what a call that names no tiles takes: 512 forward, 256 backward
        assert traced(True, None) == [4 + 16, 1 + 6]
    tracing.timeline_take(job.trace_id)


def test_pipeline_moe_aux_collected_under_pp():
    """The MoE load-balancing aux must ride the pp stage handoff: the
    pp-pipelined loss equals the sequential loss WITH its aux term (to the
    microbatch-mean-vs-batch-mean tolerance), and strictly exceeds the
    sequential cross-entropy-only loss."""
    import warnings
    from dataclasses import replace

    from ray_tpu.models import gpt2
    from ray_tpu.parallel.context import use_mesh

    cfg = replace(gpt2.GPT2_TINY, moe_experts=4, moe_aux_weight=0.5,
                  attention="dense", compute_dtype=jnp.float32)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens}
    ref_with_aux = float(gpt2.loss_fn(params, batch, cfg))
    ref_no_aux = float(gpt2.loss_fn(params, batch,
                                    replace(cfg, moe_aux_weight=0.0)))
    assert ref_with_aux > ref_no_aux + 1e-4  # aux term is material

    scfg = ShardingConfig(pp=2, ep=2, tp=2)
    mesh = scfg.build_mesh()
    pp_params = shard_params(gpt2.to_pipeline_params(params, cfg),
                             scfg, mesh)
    with use_mesh(mesh), warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        got = float(jax.jit(
            lambda p, b: gpt2.loss_fn(p, b, cfg, 2))(pp_params, batch))
    # the old "aux loss not collected" warning must be gone
    assert not [w for w in caught if "aux loss" in str(w.message)]
    # microbatch-mean vs full-batch-mean of the Switch aux differ slightly
    assert abs(got - ref_with_aux) < 1e-3, (got, ref_with_aux)
    assert got > ref_no_aux + 1e-4


def test_pipeline_schedule_utilization():
    """The fill-drain schedule runs M+S-1 stage-body ticks per device with
    M useful — the best any non-interleaved schedule (GPipe or 1F1B)
    achieves; assert the accounting and the output sharding that replaces
    the old full-buffer psum gather."""
    from ray_tpu.parallel.pipeline import (
        pipeline_apply,
        schedule_info,
        stack_layer_params,
    )

    info = schedule_info(num_microbatches=8, n_stages=2)
    assert info["ticks"] == 9
    assert info["utilization"] == 8 / 9
    assert info["bubble_fraction"] == 1 / 9
    # more microbatches amortize the fill/drain bubble
    assert (schedule_info(16, 2)["utilization"] > info["utilization"]
            > schedule_info(2, 2)["utilization"])

    mesh = create_mesh({"dp": 4, "pp": 2})
    layers = stack_layer_params([{"w": jnp.eye(8) * (i + 1)}
                                 for i in range(4)])

    def block(p, h):
        return h @ p["w"], jnp.sum(p["w"][0, 0])

    x = jnp.ones((8, 8, 8))
    out, aux = pipeline_apply(block, layers, x, mesh, num_microbatches=4)
    # sequential reference
    ref = x
    for i in range(4):
        ref = ref @ (jnp.eye(8) * (i + 1))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)
    assert abs(float(aux) - (1 + 2 + 3 + 4)) < 1e-5
    # M % S == 0: the output comes back pp-sharded on the batch dim
    spec = out.sharding.spec
    assert spec and spec[0] == "pp", spec
