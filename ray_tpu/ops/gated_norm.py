"""Mamba-2's `MambaRMSNormGated`: the gate first, g = y * silu(z), THEN an
RMSNorm over each of G runs of C / G channels, times the gain.  A Pallas
(Mosaic) kernel a pass, forward and backward, under one `jax.custom_vjp`.

    out = g * rsqrt(mean_group(g^2) + eps) * scale,   g = y * silu(z)

y and z (..., C); scale (C,).  Statistics and products in float32; the
result in y's type.  z may be handed over as the first C columns of a wider
array (Mamba-2's z is the head of W_in's result [z | xBC | dt]): the kernels
read those columns where they lie, and XLA slices nothing out for them.

**The kernels.**  A grid over tiles of whole rows of the (rows, C) arrays
as they lie: nothing is re-laid to (..., G, C / G) and nothing float32 goes
through HBM, so a pass reads its operands once and writes its results once.
Inside a tile a loop takes `_SUB` rows at a time and, unrolled, each
group's C / G lanes of them (a static slice, whole 128-lane tiles): at the
published widths (512 lanes a group) what a group's rows need fits the
vector registers, and the v5e's vector unit, which has no bfloat16 and
little to spare beside such a pass's bytes, is not spent on spills (on the
chip the forward takes its bytes' time at the HBM's peak over 0.81, the
backward over 0.82: PERF.md §6, PR 55).

The backward is a kernel of its own over the same grid.  Its residuals are
the INPUTS only (y, z, the gain), as `ops/ssd.py` keeps them: g, the
group's r = rsqrt(.) and n = g r are made again in VMEM.  With
dn = d out * scale:

    dg = r (dn - n mean_group(dn n)),   dy = dg silu(z),
    dz = dg y silu'(z),                 d scale = sum_rows d out n

the gain's gradient summed in float32 over the row tiles in a block that
stays in VMEM (the grid's one axis `arbitrary`), eight rows of partial sums
that XLA adds up.  A wider z's gradient is dz with zeros behind it, padded
in z's own shape so that XLA folds it into whatever sums the array's other
parts' gradients.

**What the shape decides** (`_row_tile`).  The kernels take the calls whose
group fills whole 128-lane tiles and whose rows divide into a tile of a
multiple of `_SUB`: the published Mamba-2 and Nemotron-H shapes.  Every
other shape runs `_reference`, the plain jax the kernels are tested and
timed beside, which is also what any platform but a TPU runs beyond the
interpreter's sizes (`ops.by_platform`).

Counts itself on the job timeline as the step is traced:
`ssm.gate_norm_rows_fused`, the rows whose gate and norm the kernels make
(0 where the shape took the plain form).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import by_platform, interpreted
from ray_tpu.util import tracing

_LANE = 128
_F32 = jnp.float32
# rows the kernels' inner loop takes at a time: one tile of bfloat16 rows,
# two of float32; (16, 512) float32 is 8 vector registers a value
_SUB = 16
# rows of a grid step's blocks.  On a v5e at (2 x 8192, 4096) bfloat16 in 8
# groups (`tools/chip_kernels.py --cases gatenorm_8k`, PR 55), forward /
# forward + backward kernels ms a layer, and the seconds both compiled in:
# 64 rows 0.645 / 1.647 (0.69 s), 128 0.620 / 1.619 (0.57), 256 0.604 /
# 1.604 (0.57), 512 0.597 / 1.603 (0.54); 1,024 want 80 MiB of VMEM and
# are refused.  The bytes alone take 0.492 / 1.311.  256: level with 512
# at half its VMEM, 20 MiB for the backward's five blocks twice over, which
# is more than the 16 MiB a kernel gets unasked
_ROW_TILE = 256

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary",), vmem_limit_bytes=48 << 20)


def _reference(y, z, scale, groups, eps):
    """The rule in plain jax, y (..., C) and z its like or wider: what the
    kernels are held to and what a shape they decline runs."""
    g = y.astype(_F32) * jax.nn.silu(z[..., :y.shape[-1]].astype(_F32))
    parts = g.reshape(*g.shape[:-1], groups, g.shape[-1] // groups)
    parts = parts * jax.lax.rsqrt(
        jnp.mean(jnp.square(parts), axis=-1, keepdims=True) + eps)
    return (parts.reshape(g.shape) * scale).astype(y.dtype)


def _row_tile(rows, C, groups) -> Optional[int]:
    """The rows of a grid step's blocks, or None for a shape the kernels
    decline: a group that is no whole number of 128-lane tiles, or rows
    that do not divide into tiles of a multiple of `_SUB`."""
    tile = min(_ROW_TILE, rows)
    if C % groups or (C // groups) % _LANE or rows % tile or tile % _SUB:
        return None
    return tile


def _over_rows(rows, body):
    """``body(rows' slice)`` for each `_SUB` rows of a block, in a loop
    that is not unrolled: the groups inside it are."""
    def step(i, carry):
        body(pl.ds(pl.multiple_of(i * _SUB, _SUB), _SUB))
        return carry

    jax.lax.fori_loop(0, rows // _SUB, step, 0)


def _gate(y, z):
    """-> (g, silu(z), sigmoid(z)) in float32."""
    s = jax.nn.sigmoid(z)
    silu = z * s
    return y * silu, silu, s


def _forward_kernel(y_ref, z_ref, scale_ref, out_ref, *, groups, eps):
    rows, C = y_ref.shape
    width = C // groups

    def body(at):
        for k in range(groups):
            cols = pl.ds(k * width, width)
            g, _, _ = _gate(y_ref[at, cols].astype(_F32),
                            z_ref[at, cols].astype(_F32))
            r = jax.lax.rsqrt(
                jnp.mean(g * g, axis=-1, keepdims=True) + eps)
            out_ref[at, cols] = (g * r * scale_ref[:, cols]).astype(
                out_ref.dtype)

    _over_rows(rows, body)


def _backward_kernel(y_ref, z_ref, scale_ref, dout_ref, dy_ref, dz_ref,
                     dscale_ref, *, groups, eps):
    rows, C = y_ref.shape
    width = C // groups

    @pl.when(pl.program_id(0) == 0)
    def _():
        dscale_ref[...] = jnp.zeros_like(dscale_ref)

    def body(at):
        for k in range(groups):
            cols = pl.ds(k * width, width)
            y = y_ref[at, cols].astype(_F32)
            z = z_ref[at, cols].astype(_F32)
            dout = dout_ref[at, cols].astype(_F32)
            g, silu, s = _gate(y, z)
            r = jax.lax.rsqrt(
                jnp.mean(g * g, axis=-1, keepdims=True) + eps)
            n = g * r
            # the gain's gradient: `_SUB` rows folded onto 8 by whole
            # registers, the 8 left to XLA
            dn_rows = dout * n
            dscale_ref[:, cols] += sum(
                dn_rows[i:i + 8] for i in range(0, _SUB, 8))
            dn = dout * scale_ref[:, cols]
            dg = r * (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True))
            dy_ref[at, cols] = (dg * silu).astype(dy_ref.dtype)
            dz_ref[at, cols] = (
                dg * y * (s * (1 + z * (1 - s)))).astype(dz_ref.dtype)

    _over_rows(rows, body)


def _specs(y, z, scale, tile):
    """The grid, a block of ``tile`` whole rows (of z: the first C columns
    of however many it has), the gain's block, and y, z and the gain as the
    blocks cut them: (rows, C), (rows, C or more), (1, C) float32."""
    C = y.shape[-1]
    rows = y.size // C
    block = pl.BlockSpec((tile, C), lambda i: (i, 0))
    return ((rows // tile,), block, pl.BlockSpec((1, C), lambda i: (0, 0)),
            (y.reshape(rows, C), z.reshape(rows, z.shape[-1]),
             scale.astype(_F32).reshape(1, C)))


@functools.partial(jax.jit,
                   static_argnames=("groups", "eps", "tile", "interpret"))
def _norm_forward(y, z, scale, *, groups, eps, tile, interpret=False):
    """y (..., C), z (..., C or more), scale (C,) -> out in y's shape and
    type, by blocks of ``tile`` rows."""
    grid, block, gain, operands = _specs(y, z, scale, tile)
    return pl.pallas_call(
        functools.partial(_forward_kernel, groups=groups, eps=eps),
        grid=grid, in_specs=[block, block, gain], out_specs=block,
        out_shape=jax.ShapeDtypeStruct(operands[0].shape, y.dtype),
        compiler_params=_COMPILER_PARAMS, interpret=interpret,
    )(*operands).reshape(y.shape)


@functools.partial(jax.jit,
                   static_argnames=("groups", "eps", "tile", "interpret"))
def _norm_backward(y, z, scale, dout, *, groups, eps, tile, interpret=False):
    """-> (dy, dz, d scale), each in its primal's shape and type: a wider
    z's gradient is 0 past its first C columns."""
    C = y.shape[-1]
    grid, block, gain, operands = _specs(y, z, scale, tile)
    flat = operands[0].shape
    dy, dz, dscale = pl.pallas_call(
        functools.partial(_backward_kernel, groups=groups, eps=eps),
        grid=grid, in_specs=[block, block, gain, block],
        out_specs=[block, block, pl.BlockSpec((8, C), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct(flat, y.dtype),
                   jax.ShapeDtypeStruct(flat, z.dtype),
                   jax.ShapeDtypeStruct((8, C), _F32)],
        compiler_params=_COMPILER_PARAMS, interpret=interpret,
    )(*operands, dout.reshape(flat))
    # padded in z's own shape, as the cotangent of a slice of it is: XLA
    # then folds it into the sum with the other parts' (padded as (rows,
    # width) it was an operation of its own, 0.82 ms a layer on nemotron)
    dz = jnp.pad(dz.reshape(*z.shape[:-1], C),
                 ((0, 0),) * (z.ndim - 1) + ((0, z.shape[-1] - C),))
    return (dy.reshape(y.shape), dz,
            jnp.sum(dscale, axis=0).astype(scale.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _kernels(y, z, scale, static):
    return _kernels_fwd(y, z, scale, static)[0]


def _kernels_fwd(y, z, scale, static):
    groups, eps, tile = static
    out = by_platform(
        functools.partial(_norm_forward, groups=groups, eps=eps, tile=tile),
        functools.partial(_reference, groups=groups, eps=eps), y, z, scale)
    return out, (y, z, scale)


def _kernels_bwd(static, inputs, dout):
    groups, eps, tile = static

    def reference(*a):
        *inputs, dout = a
        return jax.vjp(functools.partial(
            _reference, groups=groups, eps=eps), *inputs)[1](dout)

    return by_platform(
        functools.partial(_norm_backward, groups=groups, eps=eps, tile=tile),
        reference, *inputs, dout)


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def gated_rms_norm(y, z, scale, groups, eps):
    """-> out (..., C) in y's type: the rule above, by the kernels where
    the shape lets them (`_row_tile`) and by `_reference` elsewhere.  z:
    (..., C), or wider with the gate in its first C columns."""
    C = y.shape[-1]
    rows = y.size // C
    tile = _row_tile(rows, C, groups)
    runs = tile and (interpreted(y) or jax.default_backend() == "tpu")
    # on every timeline that has a gated norm, a 0 too
    tracing.count("ssm.gate_norm_rows_fused", rows if runs else 0)
    if tile is None:
        return _reference(y, z, scale, groups, eps)
    return _kernels(y, z, scale, (groups, eps, tile))
