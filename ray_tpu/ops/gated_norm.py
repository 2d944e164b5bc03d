"""Two rules of a gate and an RMSNorm over runs of a row's channels, each a
Pallas (Mosaic) kernel a pass, forward and backward, under one
`jax.custom_vjp`, over the (rows, C) arrays as they lie.  They share the
grid, the blocks, the row loop and the choice of where a kernel runs; the
arithmetic is each rule's own kernel bodies, and which runs is which function
a model calls.

**`gated_rms_norm`**, Mamba-2's `MambaRMSNormGated`: the gate first, g = y *
silu(z), THEN an RMSNorm over each of G runs of C / G channels, times the
gain.

    out = g * rsqrt(mean_group(g^2) + eps) * scale,   g = y * silu(z)

**`head_rms_norm`**, a Kimi Delta Attention mixer's norms
(`models/bailing_hybrid.py`): an RMSNorm over each of H heads' C / H lanes
FIRST, times a gain, and an optional gate BEHIND it, a sigmoid:

    out = x * rsqrt(mean_head(x^2) + eps) * gain [* sigmoid(z)]

the head's norm with its output gate, and with a constant gain and no gate an
L2 norm (x rsqrt(sum x^2 + e) = x rsqrt(mean x^2 + e / D) D^-1/2).  Its
kernels follow the first rule's below in everything but the arithmetic: the
residuals are the inputs, n = x r is made again, and with dn = d out * gain
[* s], s = sigmoid(z):

    dx = r (dn - n mean_head(dn n)),   dz = d out (n gain) s (1 - s),
    d gain = sum_rows d out n [s]

A constant gain is a number in the kernel: no block, no gradient.  A head
here is ONE 128-lane tile, so a row takes 16 sums across lanes where
Mamba-2's takes 8 over four tiles each; on the chip (`tools/chip_kernels.py
--cases headnorm_16k`, PR 66) the gated forward still takes its bytes' time
over 0.76 and both kernels over 0.88, and the sums as products with a (128,
128) block of ones on the idle MXU were slower (float32 `HIGHEST` 0.49 ms
forward for 0.32, three bfloat16 passes by hand 0.39; backward 2.3 for
0.43).

The first rule in full.  y and z (..., C); scale (C,).  Statistics and
products in float32; the result in y's type.  z may be handed over as the first C columns of a wider
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

Each rule counts itself on the job timeline as the step is traced:
`ssm.gate_norm_rows_fused` and `kda.head_norm_rows_fused`, the rows whose
norm the kernels make (0 where the shape took the plain form).
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
# is more than the 16 MiB a kernel gets unasked.  The second rule at
# (16384, 2048) bfloat16 in 16 heads (`--cases headnorm_16k`, PR 66),
# gated / as an L2 norm, forward ms and both kernels' ms: 128 rows 0.337 and
# 0.776 / 0.233 and 0.507, 256 0.323 and 0.748 / 0.212 and 0.484, 512 0.316
# and 0.739 / 0.210 and 0.484, 1,024 0.316 and 0.747 / 0.214 and 0.494; the
# bytes alone 0.246 and 0.655 / 0.164 and 0.410.  256 there too: 512 is 1 %
# ahead on one rule and level on the other
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
    blocks cut them: (rows, C), (rows, C or more), (1, C) float32; a z or a
    gain that is None stays None."""
    C = y.shape[-1]
    rows = y.size // C
    block = pl.BlockSpec((tile, C), lambda i: (i, 0))
    return ((rows // tile,), block, pl.BlockSpec((1, C), lambda i: (0, 0)),
            (y.reshape(rows, C),
             None if z is None else z.reshape(rows, z.shape[-1]),
             None if scale is None else scale.astype(_F32).reshape(1, C)))


def _padded(dz, z, C):
    """dz (rows, C) in z's own shape, zeros behind its first C columns, as
    the cotangent of a slice of it is: XLA then folds it into the sum with
    the other parts' (padded as (rows, width) it was an operation of its
    own, 0.82 ms a layer on nemotron)."""
    return jnp.pad(dz.reshape(*z.shape[:-1], C),
                   ((0, 0),) * (z.ndim - 1) + ((0, z.shape[-1] - C),))


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
    return (dy.reshape(y.shape), _padded(dz, z, C),
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


# ---------------------------------------------------------------------------
# the second rule: an RMSNorm a head, the gate BEHIND it
# ---------------------------------------------------------------------------

def _head_reference(x, z, gain, heads, eps):
    """The second rule in plain jax, x (..., C), z its like, wider or None,
    gain (C,) or a number: what its kernels are held to and what a shape
    they decline runs."""
    v = x.astype(_F32)
    parts = v.reshape(*v.shape[:-1], heads, v.shape[-1] // heads)
    parts = parts * jax.lax.rsqrt(
        jnp.mean(jnp.square(parts), axis=-1, keepdims=True) + eps)
    out = parts.reshape(v.shape) * gain
    if z is not None:
        out = out * jax.nn.sigmoid(z[..., :x.shape[-1]].astype(_F32))
    return out.astype(x.dtype)


def _head_mean(v):
    """v (rows, a head's lanes) float32 -> the mean over the lanes a row,
    (rows, 1)."""
    return jnp.mean(v, axis=-1, keepdims=True)


def _head_refs(refs, gated, const):
    """A kernel's leading refs as (x, z or None, the gain or None, the
    rest): z is there where the rule is gated, the gain's block where the
    gain is no constant."""
    x_ref, *rest = refs
    z_ref = rest.pop(0) if gated else None
    gain_ref = rest.pop(0) if const is None else None
    return x_ref, z_ref, gain_ref, rest


def _head_forward_kernel(*refs, heads, eps, gated, const):
    x_ref, z_ref, gain_ref, (out_ref,) = _head_refs(refs, gated, const)
    rows, C = x_ref.shape
    width = C // heads

    def body(at):
        for k in range(heads):
            cols = pl.ds(k * width, width)
            x = x_ref[at, cols].astype(_F32)
            n = x * jax.lax.rsqrt(_head_mean(x * x) + eps)
            out = n * (const if gain_ref is None else gain_ref[:, cols])
            if gated:
                out = out * jax.nn.sigmoid(z_ref[at, cols].astype(_F32))
            out_ref[at, cols] = out.astype(out_ref.dtype)

    _over_rows(rows, body)


def _head_backward_kernel(*refs, heads, eps, gated, const):
    x_ref, z_ref, gain_ref, (dout_ref, dx_ref, *rest) = _head_refs(
        refs, gated, const)
    dz_ref = rest.pop(0) if gated else None
    rows, C = x_ref.shape
    width = C // heads

    if gain_ref is not None:
        dgain_ref, = rest

        @pl.when(pl.program_id(0) == 0)
        def _():
            dgain_ref[...] = jnp.zeros_like(dgain_ref)

    def body(at):
        for k in range(heads):
            cols = pl.ds(k * width, width)
            x = x_ref[at, cols].astype(_F32)
            dout = dout_ref[at, cols].astype(_F32)
            r = jax.lax.rsqrt(_head_mean(x * x) + eps)
            n = x * r
            gain = const if gain_ref is None else gain_ref[:, cols]
            dgain_rows = dout * n
            dn = dout * gain
            if gated:
                s = jax.nn.sigmoid(z_ref[at, cols].astype(_F32))
                dz_ref[at, cols] = (
                    dn * n * (s * (1 - s))).astype(dz_ref.dtype)
                dgain_rows, dn = dgain_rows * s, dn * s
            if gain_ref is not None:
                dgain_ref[:, cols] += sum(
                    dgain_rows[i:i + 8] for i in range(0, _SUB, 8))
            dx_ref[at, cols] = (
                r * (dn - n * _head_mean(dn * n))).astype(dx_ref.dtype)

    _over_rows(rows, body)


_HEAD_STATIC = ("heads", "eps", "tile", "const", "interpret")


def _head_specs(x, z, gain, tile):
    """`_specs` with what is None left out: the grid, a rows' block, the
    blocks of the operands that are there, and those operands."""
    grid, block, gain_block, operands = _specs(x, z, gain, tile)
    there = [(spec, a) for spec, a in zip(
        (block, block, gain_block), operands) if a is not None]
    return grid, block, [spec for spec, _ in there], [a for _, a in there]


@functools.partial(jax.jit, static_argnames=_HEAD_STATIC)
def _head_forward(x, z, gain, *, heads, eps, tile, const, interpret=False):
    """x (..., C), z (..., C or more) or None, gain (C,) or None where
    ``const`` is the gain -> out in x's shape and type."""
    grid, block, in_specs, operands = _head_specs(x, z, gain, tile)
    return pl.pallas_call(
        functools.partial(_head_forward_kernel, heads=heads, eps=eps,
                          gated=z is not None, const=const),
        grid=grid, in_specs=in_specs, out_specs=block,
        out_shape=jax.ShapeDtypeStruct(operands[0].shape, x.dtype),
        compiler_params=_COMPILER_PARAMS, interpret=interpret,
    )(*operands).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=_HEAD_STATIC)
def _head_backward(x, z, gain, dout, *, heads, eps, tile, const,
                   interpret=False):
    """-> (dx, dz, d gain), each in its primal's shape and type, None for
    a z or a gain that is None."""
    C = x.shape[-1]
    grid, block, in_specs, operands = _head_specs(x, z, gain, tile)
    flat = operands[0].shape
    results = [(block, jax.ShapeDtypeStruct(flat, x.dtype))]
    if z is not None:
        results.append((block, jax.ShapeDtypeStruct(flat, z.dtype)))
    if gain is not None:
        results.append((pl.BlockSpec((8, C), lambda i: (0, 0)),
                        jax.ShapeDtypeStruct((8, C), _F32)))
    dx, *rest = pl.pallas_call(
        functools.partial(_head_backward_kernel, heads=heads, eps=eps,
                          gated=z is not None, const=const),
        grid=grid, in_specs=in_specs + [block],
        out_specs=[spec for spec, _ in results],
        out_shape=[shape for _, shape in results],
        compiler_params=_COMPILER_PARAMS, interpret=interpret,
    )(*operands, dout.reshape(flat))
    dz = None if z is None else _padded(rest.pop(0), z, C)
    dgain = None if gain is None else jnp.sum(
        rest.pop(0), axis=0).astype(gain.dtype)
    return dx.reshape(x.shape), dz, dgain


def _head_plain(static):
    """`_head_reference` over a kernel's operands (x, z or None, the gain or
    None where it is the constant)."""
    heads, eps, _, const = static
    return lambda x, z, gain: _head_reference(
        x, z, const if gain is None else gain, heads, eps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _head_kernels(x, z, gain, static):
    return _head_kernels_fwd(x, z, gain, static)[0]


def _head_kernels_fwd(x, z, gain, static):
    heads, eps, tile, const = static
    out = by_platform(
        functools.partial(_head_forward, heads=heads, eps=eps, tile=tile,
                          const=const),
        _head_plain(static), x, z, gain)
    return out, (x, z, gain)


def _head_kernels_bwd(static, inputs, dout):
    heads, eps, tile, const = static

    def reference(*a):
        *inputs, dout = a
        return jax.vjp(_head_plain(static), *inputs)[1](dout)

    return by_platform(
        functools.partial(_head_backward, heads=heads, eps=eps, tile=tile,
                          const=const),
        reference, *inputs, dout)


_head_kernels.defvjp(_head_kernels_fwd, _head_kernels_bwd)


def head_rms_norm(x, gain, heads, eps, z=None):
    """-> out (..., C) in x's type: the second rule, by its kernels where
    the shape lets them (`_row_tile`) and by `_head_reference` elsewhere.
    gain: (C,) a lane, or a number for every lane, which is a constant of
    the rule and has no gradient; z: None for no gate, (..., C), or wider
    with the gate in its first C columns."""
    C = x.shape[-1]
    rows = x.size // C
    tile = _row_tile(rows, C, heads)
    runs = tile and (interpreted(x) or jax.default_backend() == "tpu")
    # on every timeline that has such a norm, a 0 too
    tracing.count("kda.head_norm_rows_fused", rows if runs else 0)
    if tile is None:
        return _head_reference(x, z, gain, heads, eps)
    const = float(gain) if isinstance(gain, (int, float)) else None
    return _head_kernels(x, z, None if const is not None else gain,
                         (heads, eps, tile, const))
