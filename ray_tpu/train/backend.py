"""Training backends — the tensor-plane bootstrap.

Reference analogue: `python/ray/train/backend.py` (``Backend``/
``BackendConfig``) + `python/ray/train/torch/config.py:69-170`
(``_setup_torch_process_group``: rank-0 address broadcast →
``dist.init_process_group(nccl|gloo)``).

TPU-native replacement: the worker group elects rank 0 as the JAX
coordination-service host and every worker calls
``jax.distributed.initialize(coordinator, num_processes, process_id)`` —
after which ``jax.devices()`` is the GLOBAL device list and a single
``jax.sharding.Mesh`` spans every chip of every worker; XLA inserts the
collectives (psum/all-gather over ICI/DCN) that NCCL provided in the
reference.  On CPU (tests) the cross-process data plane is gloo
(``jax_cpu_collectives_implementation``) with
``--xla_force_host_platform_device_count`` virtual devices per worker —
the single-machine analogue of the reference's fake multi-node cluster.
"""

from __future__ import annotations

import errno
import glob
import logging
import math
import os
import socket
import sys
import threading
import time
from dataclasses import dataclass
from typing import Dict, Optional

from ray_tpu.train.worker_group import WorkerGroup
from ray_tpu.util import tracing

logger = logging.getLogger(__name__)


class BackendConfig:
    """Base config; subclasses name their backend class."""

    @property
    def backend_cls(self):
        return Backend


class Backend:
    def on_start(self, worker_group: WorkerGroup,
                 backend_config: BackendConfig):
        pass

    def on_training_start(self, worker_group: WorkerGroup,
                          backend_config: BackendConfig):
        pass

    def on_shutdown(self, worker_group: WorkerGroup,
                    backend_config: BackendConfig):
        pass


# ---------------------------------------------------------------------------
# JAX backend


@dataclass
class JaxConfig(BackendConfig):
    """Bootstrap a multi-process JAX runtime over the worker group.

    ``distributed=False`` skips ``jax.distributed.initialize`` (single-worker
    training or externally-initialized runtimes).  ``platform`` pins
    JAX_PLATFORMS in the workers ("cpu" for the virtual-device test path;
    None = what the raylet sets from the worker's grant: the CPU, or the
    TPU chips it was granted).  ``devices_per_worker`` sets
    ``--xla_force_host_platform_device_count`` (CPU testing only).
    """

    distributed: bool = True
    platform: Optional[str] = None
    devices_per_worker: Optional[int] = None
    coordinator_port: Optional[int] = None

    @property
    def backend_cls(self):
        return JaxBackend

    def worker_env(self) -> Dict[str, str]:
        """Env vars that must be staged BEFORE the worker process first
        imports jax (they are read at import/backend-init time)."""
        env: Dict[str, str] = {}
        if self.platform:
            env["JAX_PLATFORMS"] = self.platform
        if self.devices_per_worker:
            env["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count="
                f"{self.devices_per_worker}"
            )
        return env


def _find_free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get_host_and_port(port: Optional[int]):
    return socket.gethostname(), (port or _find_free_port())


def _init_jax_distributed(coordinator: str, world_size: int, rank: int,
                          platform: Optional[str]):
    """Runs inside each training worker process."""
    # the span takes in the import: a worker that holds no chip has not
    # imported jax before this
    with tracing.timeline_span("train.jax_distributed_init", rank=rank,
                               world_size=world_size):
        import jax

        # NOTE: must not touch jax.devices()/default_backend() before
        # distributed.initialize — that would create the backend early and
        # the process would never see the global mesh.
        env_platform = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
        if platform == "cpu" or (platform is None and env_platform == "cpu"):
            # Cross-process CPU collectives need gloo (the CPU analogue of
            # the ICI/DCN data plane).
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=world_size,
            process_id=rank,
        )
    return {
        "process_index": jax.process_index(),
        "global_devices": jax.device_count(),
        "local_devices": jax.local_device_count(),
    }


def _shutdown_jax_distributed():
    import jax

    try:
        jax.distributed.shutdown()
    except Exception:  # noqa: BLE001
        pass


# One device node a chip from v5e on (`core/worker.py:count_local_tpu_chips`
# counts the same ones), and how long a granted worker waits for one that
# another process is still giving back.
_VFIO_NODES = "/dev/vfio/[0-9]*"
_CHIP_BUSY_LIMIT_S = 60.0
_CHIP_BUSY_POLL_S = 0.25


def _granted_chip_nodes():
    """The device nodes this worker's libtpu is about to open: those of
    `TPU_VISIBLE_CHIPS` where the raylet granted part of the host (chip i
    is the i-th node in numeric order), else every one.  None on a host
    whose chips are `/dev/accel<N>` (up to v4) or that has no TPU."""
    if glob.glob("/dev/accel[0-9]*"):
        return []
    nodes = sorted((n for n in glob.glob(_VFIO_NODES)
                    if os.path.basename(n).isdigit()),
                   key=lambda n: int(os.path.basename(n)))
    visible = os.environ.get("TPU_VISIBLE_CHIPS", "")
    try:
        return [nodes[int(i)] for i in visible.split(",")] if visible \
            else nodes
    except (ValueError, IndexError):
        return nodes


def _chip_node_busy(node: str) -> bool:
    """Whether another process holds the node: it is opened and closed at
    once.  Any other refusal is libtpu's to report."""
    try:
        os.close(os.open(node, os.O_RDWR))
    except OSError as e:
        return e.errno == errno.EBUSY
    return False


def _wait_for_chips(granted: int):
    """Make sure the grant's device nodes can be opened before libtpu
    tries: a node opens for one process at a time, and the worker of a job
    that has just ended (SIGKILLed, or of a program that did not wait for
    it) holds its nodes for seconds more while the kernel takes back the
    chips' mappings.  libtpu fails on the first busy node and jax's backend
    does not start a second time in one process, so each node is tried
    here first and a busy one waited for."""
    nodes = _granted_chip_nodes()
    if not nodes:
        return
    start = time.monotonic()
    tries = 0
    with tracing.timeline_span("train.chip_wait", granted=granted,
                               nodes=len(nodes)) as sp:
        for node in nodes:
            while _chip_node_busy(node):
                waited = time.monotonic() - start
                if waited >= _CHIP_BUSY_LIMIT_S:
                    sp.set_attrs(tries=tries, waited_s=waited)
                    raise RuntimeError(
                        f"training worker was granted TPU: {granted} but "
                        f"open({node}) still answers 'Device or resource "
                        f"busy' after {waited:.0f} s: another process holds "
                        "the chip")
                tries += 1
                time.sleep(_CHIP_BUSY_POLL_S)
        sp.set_attrs(tries=tries, waited_s=time.monotonic() - start)
    tracing.count("train.chip_busy_retries", tries)


def _check_granted_chips(granted: int):
    """Runs inside a training worker that was granted TPU chips: it must
    see platform ``tpu`` and exactly the chips of its grant, or the group
    does not start — a worker that was promised a chip and has none would
    otherwise train on the CPU and report success.  A chip that the worker
    before it is still letting go is waited for (`_wait_for_chips`), not
    died on."""
    # paid here unless the worker imported jax when it started (it does
    # where `ensure_compile_cache` has to place the cache itself)
    with tracing.timeline_span("train.jax_import",
                               imported="jax" in sys.modules):
        import jax

    # after the import, which gives a leaving worker seconds more
    _wait_for_chips(granted)
    # the first look at the devices: libtpu opens the chips here
    with tracing.timeline_span("train.chip_claim", granted=granted) as sp:
        devices = jax.local_devices()
        platform = devices[0].platform
        sp.set_attrs(platform=platform, devices=len(devices))
    if platform != "tpu" or len(devices) != granted:
        raise RuntimeError(
            f"training worker was granted TPU: {granted} but jax sees "
            f"platform {platform!r} with {len(devices)} local device(s) "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}, "
            f"TPU_VISIBLE_CHIPS={os.environ.get('TPU_VISIBLE_CHIPS')!r})")


# jax.monitoring's durations, by event, as spans of the job timeline, and
# the kind each is booked under in the set-up account
_JAX_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax.cache_read",
}
_KINDS = {"jax.trace": "trace", "jax.lower": "lower",
          "jax.backend_compile": "compile", "jax.cache_read": "cache_read"}
# jax's plain events: (the job's counter, what the event says of the compile
# it fell in).  A compile asks the cache wherever caching is not switched
# off, directory or none, and only an entry that was written counts as one
# of jax's misses: asked and not served is a miss where there is a directory
_JAX_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": (None, "miss"),
    "/jax/compilation_cache/cache_hits": ("jax.cache_hits", "hit"),
    "/jax/compilation_cache/cache_misses": ("jax.cache_misses", None),
}
# `models/layers.py:train_step`'s function as jax.monitoring names it, traced,
# and lowered or compiled: what jax takes for a function of this name is the
# step's in the set-up account, whoever wrote it
STEP_NAMES = ("train_step", "jit(train_step)")
# jax reports every function traced inside another's trace, thousands a
# step: a trace shorter than this is counted (`jax.traces`), not spanned
_SHORT_TRACE_S = 0.005
_jax_listening = False
_compiles: Dict[str, int] = {}     # backend compiles, by trace id + function
_recompile_warned = set()


class _JaxEvents:
    """One thread's open jax events, and what the events it has closed for
    one job took: the set-up account.

    jax reports a trace, a lowering or a compile twice on the thread that
    makes it, as it starts and as it ends, and they nest (a kernel traced
    inside a lowering, a cache read inside a compile, thousands of small
    jitted functions inside the step's trace).  Between two such reports the
    thread's time is the OWN time of the innermost open event: its duration
    less the durations of the events that closed inside it.  Own time is
    booked by kind and by whose function the OUTERMOST open event is: the
    step's (`STEP_NAMES`) or another's.  jax keeps a jitted function's trace
    by its arguments' shapes, so a function called at two sites is booked
    where it was traced, the first: the account says what the tracer paid,
    not what a site would cost alone."""

    __slots__ = ("trace_id", "open", "step", "last", "traces", "own",
                 "step_cache", "listen")

    def __init__(self, trace_id):
        self.trace_id = trace_id
        self.open = []      # [span, fun_name, start, own s, cache]
        self.step = False   # the outermost open event is the step's
        self.last = 0.0     # when jax last reported on this thread
        self.traces = 0     # closed under the outermost open event
        self.own = {}       # (span, the step's) -> seconds
        self.step_cache = None      # the `cache` of the step's last compile
        self.listen = 0.0   # seconds the listeners themselves took

    def report(self, now):
        """jax reports at ``now``: the time since its last report is the
        innermost open event's own."""
        if self.open:
            self.open[-1][3] += now - self.last
        self.last = now

    def start(self, span, fun_name, start):
        self.report(start)
        if not self.open:
            self.step = fun_name in STEP_NAMES
        self.open.append([span, fun_name, start, 0.0, "off"])

    def end(self, span, fun_name, seconds):
        """The open event this end belongs to, closed and booked."""
        stack = self.open
        for at in range(len(stack) - 1, -1, -1):
            if stack[at][0] == span and stack[at][1] == fun_name:
                if at + 1 < len(stack):     # what an exception left open
                    del stack[at + 1:]
                break
        else:           # jax reported no start: it ends now
            self.start(span, fun_name, time.time() - seconds)
        event = stack[-1]
        self.report(event[2] + seconds)
        stack.pop()
        booked = span, self.step
        self.own[booked] = self.own.get(booked, 0.0) + event[3]
        return event


_jax_thread = threading.local()


def _jax_events(ctx) -> _JaxEvents:
    """The calling thread's account of ``ctx``'s job: another job's is
    left."""
    mine = getattr(_jax_thread, "events", None)
    if mine is None or mine.trace_id != ctx["trace_id"]:
        mine = _jax_thread.events = _JaxEvents(ctx["trace_id"])
    return mine


def setup_account(ctx) -> dict:
    """What the calling thread's closed jax events took so far under
    ``ctx``'s job, as `train.setup` carries it (`train/session.py`)."""
    mine = _jax_events(ctx)
    return {
        "own_us": {f"{kind}/{whose}": int(mine.own.get((span, step), 0) * 1e6)
                   for step, whose in ((True, "step"), (False, "other"))
                   for span, kind in _KINDS.items()},
        "step_cache": mine.step_cache,
        "listen_us": int(mine.listen * 1e6),
    }


def _listen_to_jax():
    """Runs inside each training worker, once a process: what this process
    pays JAX's tracer, its lowering (Mosaic included), its compiler and
    its compile cache becomes spans and counters of the job whose context
    is active when jax reports it (a cache read lies inside its
    `jax.backend_compile`, a function traced inside another's trace inside
    that one's span), and every event's own time an entry of the thread's
    set-up account (`_JaxEvents`), spanned or not.  A function that
    compiles a second time after the job's first `train.report` is named in
    one WARNING line: the step recompiled."""
    global _jax_listening
    if _jax_listening:
        return
    _jax_listening = True
    import jax

    def on_start(event, start, **kwargs):
        span = _JAX_SPANS.get(event)
        ctx = span and tracing.timeline_ctx()
        if not ctx:
            return
        t0 = time.perf_counter()
        mine = _jax_events(ctx)
        mine.start(span, str(kwargs.get("fun_name", "")), start)
        mine.listen += time.perf_counter() - t0

    def on_duration(event, seconds, **kwargs):
        span = _JAX_SPANS.get(event)
        ctx = span and tracing.timeline_ctx()
        if not ctx:
            return
        t0 = time.perf_counter()
        mine, fun_name = _jax_events(ctx), str(kwargs.get("fun_name", ""))
        _, _, start, own, cache = mine.end(span, fun_name, seconds)
        if span == "jax.trace":
            mine.traces += 1
        if mine.traces and not mine.open:   # a lock a root, not an event
            tracing.count("jax.traces", mine.traces)
            mine.traces = 0
        if span != "jax.trace" or seconds >= _SHORT_TRACE_S:
            attrs = {"own_us": int(own * 1e6), "step": mine.step}
            if span == "jax.backend_compile":
                if cache == "miss" \
                        and not jax.config.jax_compilation_cache_dir:
                    cache = "off"
                if mine.step:
                    mine.step_cache = cache
                attrs["cache"] = cache
            _record(span, ctx, fun_name, start, seconds, attrs)
        mine.listen += time.perf_counter() - t0

    def on_event(event, **_):
        counter, cache = _JAX_EVENTS.get(event, (None, None))
        if counter:
            tracing.count(counter)
        mine = cache and getattr(_jax_thread, "events", None)
        if mine and mine.open and mine.open[-1][4] != "hit" \
                and mine.open[-1][0] == "jax.backend_compile":
            mine.open[-1][4] = cache

    jax.monitoring.register_scalar_listener(on_start)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def _record(span, ctx, fun_name, start, seconds, attrs):
    """A closed jax event as a span of the job's timeline; a compile is
    counted, and warned of where it is a second one after the job's first
    report."""
    if fun_name:
        attrs["fun_name"] = fun_name
    tracing.timeline_hop(span, ctx, start, start + seconds, **attrs)
    if span != "jax.backend_compile":
        return
    tracing.count("jax.compiles")
    key = ctx["trace_id"] + fun_name
    seen = _compiles[key] = _compiles.get(key, 0) + 1
    if (fun_name and seen > 1 and key not in _recompile_warned
            and tracing.counter("train.reports")):
        _recompile_warned.add(key)
        logger.warning(
            "%s compiled again after the job's first train.report: "
            "a shape, dtype or static argument of it changes between "
            "steps", fun_name)


class JaxBackend(Backend):
    def on_start(self, worker_group: WorkerGroup, backend_config: JaxConfig):
        if backend_config.distributed and len(worker_group) > 1:
            self._bootstrap_distributed(worker_group, backend_config)
        # after the bootstrap: looking at the devices creates the backend
        granted = math.ceil(worker_group.resources_per_worker.get("TPU", 0))
        if granted:
            worker_group.execute(_check_granted_chips, granted)

    def _bootstrap_distributed(self, worker_group: WorkerGroup,
                               backend_config: JaxConfig):
        # Elect rank 0's host as coordinator (reference broadcasts rank-0's
        # address the same way, `train/torch/config.py:102-136`).
        host, port = worker_group.execute_single(
            0, _get_host_and_port, backend_config.coordinator_port
        )
        coordinator = f"{host}:{port}"
        results = [None] * len(worker_group)
        futures = []
        for rank, w in enumerate(worker_group.workers):
            futures.append(w.execute.remote(
                _init_jax_distributed, coordinator, len(worker_group), rank,
                backend_config.platform,
            ))
        import ray_tpu

        results = ray_tpu.get(futures, timeout=300)
        expect = results[0]["global_devices"]
        for rank, r in enumerate(results):
            if r["global_devices"] != expect:
                raise RuntimeError(
                    f"worker {rank} sees {r['global_devices']} global devices"
                    f", rank 0 sees {expect}"
                )

    def on_training_start(self, worker_group: WorkerGroup,
                          backend_config: JaxConfig):
        worker_group.execute(_listen_to_jax)

    def on_shutdown(self, worker_group: WorkerGroup,
                    backend_config: JaxConfig):
        if backend_config.distributed and len(worker_group) > 1:
            try:
                worker_group.execute(_shutdown_jax_distributed)
            except Exception:  # noqa: BLE001
                pass
