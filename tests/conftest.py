"""Test fixtures.

Multi-chip sharding tests run on a virtual 8-device CPU mesh
(``xla_force_host_platform_device_count``), the single-machine analogue of the
reference's fake multi-node cluster (`python/ray/cluster_utils.py:99`).
These env vars must be set before jax is first imported, hence conftest.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"  # force: env may pin the TPU platform
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Keep worker subprocesses on CPU too (workers inherit the driver env).
os.environ.setdefault("RAY_TPU_OBJECT_STORE_MEMORY_MB", "256")
# Continuous profiling defaults ON in production; in the suite the
# 19Hz sampler thread per process is pure wakeup tax on the loaded
# 2-core CI hosts (hundreds of short-lived clusters), so default it off
# here — the profiling tests opt back in explicitly (setdefault: an
# operator's env still wins).
os.environ.setdefault("RAY_TPU_PROFILE", "0")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture
def clean_host():
    """Leaked-process audit around cluster-heavy tests: snapshot the
    host's ray_tpu runtime processes / shm segments before the test,
    assert everything above the baseline is gone after (teardown is
    async, so the check polls with a grace window).  Apply per-module
    with ``pytestmark = pytest.mark.usefixtures("clean_host")``."""
    from ray_tpu.util import chaos

    baseline = chaos.snapshot_host()
    yield
    chaos.assert_clean_host(baseline)


@pytest.fixture(scope="module")
def clean_host_module():
    """Module-scoped variant of :func:`clean_host` for modules that share
    ONE live cluster across their tests (e.g. a module-scoped ``cluster``
    fixture): a per-test audit would flag the shared cluster's warm
    worker pool — processes that legitimately appear mid-module and
    outlive individual tests — so the baseline/check pair brackets the
    whole module instead."""
    from ray_tpu.util import chaos

    baseline = chaos.snapshot_host()
    yield
    chaos.assert_clean_host(baseline)


@pytest.fixture
def ray_start_regular():
    import ray_tpu

    ray_tpu.init(num_cpus=4, ignore_reinit_error=False)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def ray_shared():
    """Shared runtime for a whole test module (cheaper than per-test)."""
    import ray_tpu

    ray_tpu.init(num_cpus=8)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def ray_local_mode():
    import ray_tpu

    ray_tpu.init(local_mode=True)
    yield ray_tpu
    ray_tpu.shutdown()
