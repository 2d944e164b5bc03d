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
    loops over k blocks, and the backward is the two-kernel split with the
    same loops.  ``whole``: a grid step takes the sequence, the forward
    walks its q tiles over merged spans and the backward is one kernel."""
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
                                  bq, bk, whole=whole, interpret=True)
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
# not pair up into 128 lanes (25, 3), and past _WHOLE_SEQ_MAX the dq and
# dk/dv kernels of the two-kernel backward; last, the medium cell's own
# shape with 256-tiles forced in both passes
@pytest.mark.parametrize("shape,kernels,block", [
    ((16, 1024, 12, 64), 2, None), ((8, 1024, 16, 64), 2, None),
    ((4, 1024, 25, 64), 2, None), ((16, 1024, 3, 64), 2, None),
    ((2, 2048, 32, 128), 3, None), ((16, 1024, 16, 64), 2, 256)])
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


def _pallas_kernels(jaxpr, found=None):
    """{kernel function's name: its body holds a loop} over the
    `pallas_call`s of a jaxpr, nested ones (jit, the platform's branches,
    custom_vjp) included."""
    def subjaxprs(value):
        if isinstance(value, jex_core.ClosedJaxpr):
            yield value.jaxpr
        elif isinstance(value, jex_core.Jaxpr):
            yield value
        elif isinstance(value, (tuple, list)):
            for item in value:
                yield from subjaxprs(item)

    from jax.extend import core as jex_core

    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["jaxpr"].debug_info.func_name
            # fori_loop: `while` under traced bounds, `scan` under static
            body = str(eqn.params["jaxpr"])
            found[name] = (found.get(name, False) or "while[" in body
                           or "scan[" in body)
        for value in eqn.params.values():
            for sub in subjaxprs(value):
                _pallas_kernels(sub, found)
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
_LOOPED_SPLIT = {"_bwd_dq_kernel": True, "_bwd_dkv_kernel": True}


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
    k blocks, and the backward is the two-kernel split: three kernels with
    loops, whatever an earlier test traced at these shapes (the form is a
    static argument of the jitted kernel calls)."""
    from ray_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_WHOLE_SEQ_MAX", 128)
    q, k, v = _bshd_qkv(256, H, D)
    forward = "_fwd_kernel" if H == 3 else "_fwd_kernel_lanes"
    _bshd_against_reference(q, k, v, causal, 128, 128,
                            {forward: True, **_LOOPED_SPLIT})


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
