"""The device kernels, and the one rule they share about where a kernel
runs: compiled where a call is lowered for a TPU, elsewhere interpreted up
to the tests' sizes and the plain reference beyond them."""

import functools

import jax

# elements of a call's first operand up to which a platform that is no TPU
# interprets a kernel (the tests' sizes); beyond it interpretation is too
# slow to be worth it
INTERPRET_MAX_ELEMS = 1 << 16


def interpreted(x) -> bool:
    """Whether a platform that is no TPU interprets a kernel whose first
    operand is ``x``."""
    return x.size <= INTERPRET_MAX_ELEMS


def by_platform(kernel, reference, q, *rest):
    """``kernel(q, *rest, interpret=False)`` — the compiled Mosaic kernel —
    wherever the computation is lowered for a TPU; on any other platform
    the same kernel interpreted at test sizes and ``reference(q, *rest)``
    beyond them.  The choice is made per lowering platform, not from the
    devices of the tracing process, so an export for a TPU from a CPU host
    carries the kernel and nothing on a TPU is ever interpreted."""
    other = functools.partial(kernel, interpret=True) if interpreted(q) \
        else reference
    return jax.lax.platform_dependent(
        q, *rest, tpu=functools.partial(kernel, interpret=False),
        default=other)


# Mamba-1's scan, by the name its callers know (behind the rule above, which
# its module reads from here).  The function takes the module's place as this
# package's attribute: the module is `sys.modules[__name__ + ".selective_scan"]`
# (`importlib.import_module`), and `from ray_tpu.ops.selective_scan import ...`
# reads it as ever.
from ray_tpu.ops.selective_scan import selective_scan  # noqa: E402
