"""Test fixtures.

Multi-chip sharding tests run on a virtual 8-device CPU mesh
(``xla_force_host_platform_device_count``), the single-machine analogue of the
reference's fake multi-node cluster (`python/ray/cluster_utils.py:99`).
These env vars must be set before jax is first imported, hence conftest.
"""

import os
import shutil
import sys
import tempfile

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
# Every program jax finds in its compilation cache (see `compile_cache`
# below) makes XLA:CPU log, at its ERROR level, that the pseudo-features
# `+prefer-no-scatter`/`+prefer-no-gather` it compiled with are not reported
# by the very host it compiled on: two KiB a program on a failing test's
# captured stderr.  Nothing below FATAL is logged by the C++ side here; what
# fails still raises in Python with its message (setdefault: an operator's
# env still wins).
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

# -- programs are built once a session ----------------------------------------
# Most of what the device-side files spend on the CPU is XLA compiling small
# programs that the same file, or a sibling on another xdist worker, has
# compiled before.  One run keeps one compilation cache: the controller (or
# a run without xdist) makes the directory and names it in the environment
# its workers inherit, every test process points jax at it, and the end of
# the run removes it.  A constant of the tests' set-up: `ray_tpu` has no
# flag for it, and the processes the tests spawn do not read it.
COMPILE_CACHE_ENV = "TESTS_JAX_COMPILE_CACHE"
# modules whose tests read the cache's own state, left without it
NO_COMPILE_CACHE = {
    # test_cache_reads_miss_then_hit_and_off_without_a_directory: a compile
    # with no directory set is booked "off"
    "test_train_timeline",
}


def pytest_configure(config):
    # a cache entry another worker is still writing reads as an error, which
    # jax answers by compiling: nothing a test should hear of
    config.addinivalue_line(
        "filterwarnings",
        "ignore:Error reading persistent compilation cache entry")
    if hasattr(config, "workerinput") or os.environ.get(COMPILE_CACHE_ENV):
        return          # an xdist worker, or a run inside a run: it is named
    # where the environment places jax's cache, that is the directory, and
    # it is not this run's to remove
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = placed or tempfile.mkdtemp(prefix="ray_tpu_tests_jax_cache_")
    os.environ[COMPILE_CACHE_ENV] = path

    def forget():
        os.environ.pop(COMPILE_CACHE_ENV, None)
        if not placed:
            shutil.rmtree(path, ignore_errors=True)
    config.add_cleanup(forget)


@pytest.fixture(scope="module", autouse=True)
def compile_cache(request):
    """jax's persistent compilation cache at the run's directory, every
    program kept whatever it took to compile, for every module but those
    of `NO_COMPILE_CACHE`."""
    path = os.environ.get(COMPILE_CACHE_ENV)
    if not path:
        yield
        return
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    if request.module.__name__ in NO_COMPILE_CACHE:
        path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or None
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
        compilation_cache.reset_cache()
    yield


@pytest.fixture
def highest_precision():
    """float32 products in full: what a comparison with a float32 reference
    to 1e-5 needs of the CPU's matmul.  A module of such comparisons takes
    it for all its tests with ``pytestmark = pytest.mark.usefixtures(
    "highest_precision")``."""
    import jax

    with jax.default_matmul_precision("highest"):
        yield


def audit_scope():
    """What tells this xdist worker's clusters from its neighbours' on the
    same host: xdist puts both into a worker's environment before a test
    runs, every process a test starts inherits them, and an orphan keeps
    them.  Empty outside xdist, where the audit counts the whole host."""
    return {name: os.environ[name]
            for name in ("PYTEST_XDIST_WORKER", "PYTEST_XDIST_TESTRUNUID")
            if name in os.environ}


@pytest.fixture
def clean_host():
    """Leaked-process audit around cluster-heavy tests: snapshot the
    host's ray_tpu runtime processes / shm segments before the test,
    assert everything above the baseline is gone after (teardown is
    async, so the check polls with a grace window).  Apply per-module
    with ``pytestmark = pytest.mark.usefixtures("clean_host")``."""
    from ray_tpu.util import chaos

    baseline = chaos.snapshot_host(audit_scope())
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

    baseline = chaos.snapshot_host(audit_scope())
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
