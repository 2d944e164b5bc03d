"""Each flash-attention kernel family, compiled on the chip, against the
reference.

    python tools/chip_kernels.py

One case on each side of the gates in ``ops/flash_attention.py``: the lane
kernels with the fused backward (GPT-2 124M's heads), the transposing bhsd
kernels with the fused backward (25 heads: no lane tiling), and the
two-kernel backward past ``_LANES_MAX_SEQ`` (S=2048).  This process holds
the chip, so run it alone.  Exits non-zero unless every case ran as
compiled Mosaic kernels on a TPU and agrees with ``reference_attention``.
"""

from __future__ import annotations

import json
import os
import sys
import warnings

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (B, S, H, D) -> Mosaic kernels in forward + backward
CASES = {
    "lanes_fused_bwd": ((16, 1024, 12, 64), 2),
    "bhsd_fused_bwd": ((4, 1024, 25, 64), 2),
    "two_kernel_bwd": ((2, 2048, 32, 128), 3),
}
TOLERANCE = 0.05


def compare_with_reference(shape, dtype):
    """Causal ``flash_attention_bshd`` at ``shape`` (B, S, H, D), forward and
    backward, on the default device: (largest error of o, dq, dk, dv
    relative to ``reference_attention``'s, Mosaic kernels in the compiled
    program — 0 where the kernels are interpreted)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import flash_attention as fa
    from ray_tpu.parallel.attention import attention

    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), shape, dtype)
               for i in range(3))

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
    (_, o_r), g_r = grad(reference)(q, k, v)
    errs = {}
    for what, a, b in zip(("o", "dq", "dk", "dv"), (o_k, *g_k), (o_r, *g_r)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        errs[what] = round(float(np.max(np.abs(a - b)) / np.max(np.abs(b))),
                           5)
    return errs, compiled.as_text().count(
        'custom_call_target="tpu_custom_call"')


def main():
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import AttentionFallbackWarning
    from ray_tpu.util.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    warnings.simplefilter("error", AttentionFallbackWarning)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"no TPU: jax found {dev.platform!r}")

    failed = []
    for name, (shape, n_kernels) in CASES.items():
        errs, found = compare_with_reference(shape, jnp.bfloat16)
        ok = found == n_kernels and max(errs.values()) < TOLERANCE
        if not ok:
            failed.append(name)
        print(json.dumps({"case": name, "shape": shape, "ok": ok,
                          "mosaic_kernels": found, "rel_err": errs,
                          "device_kind": dev.device_kind}))
    if failed:
        sys.exit(f"failed: {failed}")


if __name__ == "__main__":
    main()
