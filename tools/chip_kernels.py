"""Each flash-attention kernel family, compiled on the chip, against the
reference, with the device time of its forward and of its backward.

    python tools/chip_kernels.py            # the cases, default tiles
    python tools/chip_kernels.py --sweep    # tile -> ms at the cells' shapes
    python tools/chip_kernels.py --sweep s512-d64   # at the named shapes only

One case on each side of the gates in ``ops/flash_attention.py``: the lane
kernels with the one-kernel backward (GPT-2 124M's heads), the transposing
bhsd kernels with the one-kernel backward (25 heads: no lane tiling; XL's
share of a batch on one chip), the two-kernel backward past
``_WHOLE_SEQ_MAX`` (S=2048), OLMoE's shape (S=4096, D=128), and latent
attention's two widths at S=8192 (a fifth number in a shape is v's width:
q and k 192, v 128).  This process holds the chip, so run it alone.  Exits non-zero unless every case
ran as compiled Mosaic kernels on a TPU and agrees with
``reference_attention``.  ``--sweep`` times forced square tiles instead
(what ``_auto_tiles`` is set from) and compares nothing.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import warnings

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (B, S, H, D[, Dv]) -> Mosaic kernels in forward + backward
CASES = {
    "lanes_fused_bwd": ((16, 1024, 12, 64), 2),
    "bhsd_fused_bwd": ((4, 1024, 25, 64), 2),
    "two_kernel_bwd": ((2, 2048, 32, 128), 3),
    "olmoe_4k": ((4, 4096, 16, 128), 3),
    "mla_8k": ((2, 8192, 32, 192, 128), 3),
}
# the benchmark's cells: medium's step, XL's on one chip of four, OLMoE's
SWEEP = {
    "gpt2-medium": ((16, 1024, 16, 64), (
        (128, 128), (256, 256), (512, 512), (1024, 1024))),
    "gpt2-xl-fsdp4": ((4, 1024, 25, 64), (
        (256, 256), (512, 512), (1024, 1024))),
    "olmoe-1b-7b": ((4, 4096, 16, 128), (
        (256, 256), (512, 512), (1024, 1024))),
    # no cell: heads of 128 on a short sequence (llama's prefill), and
    # medium's tokens a step at half the sequence
    "d128-1k": ((4, 1024, 16, 128), (
        (128, 128), (256, 256), (512, 512), (1024, 1024))),
    "s512-d64": ((32, 512, 16, 64), ((128, 128), (256, 256), (512, 512))),
    # kanana-2-30b-a3b's latent attention, multiplied out: q, k 192, v 128
    "mla-8k": ((2, 8192, 32, 192, 128), (
        (256, 256), (512, 512), (1024, 1024))),
}
TOLERANCE = 0.05


# elements of the reference's S x S scores alive at once: 1 GiB in float32
REFERENCE_SCORES = 1 << 28


def _qkv(shape, dtype):
    """q, k of (B, S, H, D) and v of (B, S, H, Dv), Dv = D if not given."""
    import jax

    *bsh, d = shape[:4]
    widths = (d, d, shape[4] if len(shape) > 4 else d)
    return tuple(jax.random.normal(jax.random.PRNGKey(i), (*bsh, w), dtype)
                 for i, w in enumerate(widths))


def kernel_ms(f, *args, calls=5):
    """Device ms of the Mosaic kernels in one call of jitted ``f``: a
    profiler trace of ``calls`` calls, the durations of every
    ``tpu_custom_call`` on the first chip's ``XLA Ops`` line summed and
    divided by the calls."""
    import jax

    jax.block_until_ready(f(*args))
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(calls):
                out = f(*args)
            jax.block_until_ready(out)
        found = glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(found[0])
    for plane in data.planes:
        if plane.name == "/device:TPU:0":
            ns = sum(e.duration_ns for line in plane.lines
                     if line.name == "XLA Ops" for e in line.events
                     if "tpu_custom_call" in e.name)
            return round(ns / calls / 1e6, 4)
    raise ValueError("the trace holds no /device:TPU:0 plane")


def time_passes(shape, dtype, block_q=None, block_k=None):
    """(forward ms, backward ms) on the device of the kernels of causal
    ``flash_attention_bshd`` at ``shape`` with these tiles (None:
    ``_auto_tiles``)."""
    import jax

    from ray_tpu.ops import flash_attention as fa

    q, k, v = _qkv(shape, dtype)
    forward = jax.jit(lambda q, k, v: fa._flash_fwd_bshd(
        q, k, v, True, None, block_q, block_k))
    backward = jax.jit(lambda res, do: fa._flash_bwd_bshd(
        True, None, block_q, block_k, res, do))
    o, res = forward(q, k, v)
    return kernel_ms(forward, q, k, v), kernel_ms(backward, res, o)


def compare_with_reference(shape, dtype):
    """Causal ``flash_attention_bshd`` at ``shape`` (B, S, H, D[, Dv]),
    forward and backward, on the default device: (largest error of o, dq,
    dk, dv relative to ``reference_attention``'s, the worst of the batch's
    rows, Mosaic kernels in the compiled program — 0 where the kernels are
    interpreted).  The O(S^2) reference runs one batch row at a time, and
    of a row as many heads as keep its scores under `REFERENCE_SCORES`
    (OLMoE's whole batch would hold several 4 GB score arrays, one row of
    32 heads at S = 8,192 several of 8.6 GB); the loss is a sum over rows
    and heads, so a slice's gradients are the batch's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import flash_attention as fa
    from ray_tpu.parallel.attention import attention

    q, k, v = _qkv(shape, dtype)

    def kernel(q, k, v):
        o = fa.flash_attention_bshd(q, k, v, True)
        return jnp.sum(o.astype(jnp.float32) ** 2), o

    def reference(q, k, v):
        o = attention(q, k, v, variant="dense")
        return jnp.sum(o.astype(jnp.float32) ** 2), o

    def grad(f):
        return jax.jit(jax.value_and_grad(f, (0, 1, 2), has_aux=True))

    compiled = grad(kernel).lower(q, k, v).compile()
    (_, o_k), g_k = compiled(q, k, v)
    reference = grad(reference)
    errs = dict.fromkeys(("o", "dq", "dk", "dv"), 0.0)
    B, S, H = shape[:3]
    heads = max(1, min(H, REFERENCE_SCORES // (S * S)))
    for row in range(B):
        for first in range(0, H, heads):
            part = (slice(row, row + 1), slice(None),
                    slice(first, first + heads))
            (_, o_r), g_r = reference(q[part], k[part], v[part])
            for what, a, b in zip(errs, (o_k, *g_k), (o_r, *g_r)):
                a = np.asarray(a[part], np.float32)
                b = np.asarray(b, np.float32)
                errs[what] = max(errs[what], round(
                    float(np.max(np.abs(a - b)) / np.max(np.abs(b))), 5))
    return errs, compiled.as_text().count(
        'custom_call_target="tpu_custom_call"')


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--sweep", nargs="*", metavar="SHAPE",
                        help="time forced tiles at these of SWEEP's shapes "
                             f"({', '.join(SWEEP)}; none named: at all)")
    args = parser.parse_args()
    if args.sweep and set(args.sweep) - set(SWEEP):
        parser.error(f"--sweep: no such shape in {sorted(SWEEP)}")

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import (
        AttentionFallbackWarning,
        _auto_tiles,
    )
    from ray_tpu.util.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    warnings.simplefilter("error", AttentionFallbackWarning)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"no TPU: jax found {dev.platform!r}")

    if args.sweep is not None:
        for name in args.sweep or SWEEP:
            shape, blocks = SWEEP[name]
            for block in ((None, None),) + blocks:
                fwd_ms, bwd_ms = time_passes(shape, jnp.bfloat16, *block)
                print(json.dumps({
                    "sweep": name, "shape": shape,
                    "tile": block if block[0] else _auto_tiles(
                        shape[1], True),
                    "fwd_ms": fwd_ms, "bwd_ms": bwd_ms,
                    "device_kind": dev.device_kind}), flush=True)
        return

    failed = []
    for name, (shape, n_kernels) in CASES.items():
        errs, found = compare_with_reference(shape, jnp.bfloat16)
        fwd_ms, bwd_ms = time_passes(shape, jnp.bfloat16)
        ok = found == n_kernels and max(errs.values()) < TOLERANCE
        if not ok:
            failed.append(name)
        print(json.dumps({"case": name, "shape": shape, "ok": ok,
                          "mosaic_kernels": found, "rel_err": errs,
                          "fwd_ms": fwd_ms, "bwd_ms": bwd_ms,
                          "device_kind": dev.device_kind}), flush=True)
    if failed:
        sys.exit(f"failed: {failed}")


if __name__ == "__main__":
    main()
