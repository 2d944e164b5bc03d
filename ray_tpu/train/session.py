"""Worker-side training session.

Reference analogue: `python/ray/train/_internal/session.py:84` — the user's
``train_loop_per_worker`` runs in a daemon thread; ``report(metrics,
checkpoint)`` hands results to the driver through a rendezvous queue (the
training thread blocks until the driver consumes, keeping workers in
lockstep the way the reference's result queue does at `session.py:147,287`).

Inside a job the session also keeps the step as the program sees it
(`_StepWatch`): every interval between two reports of the rank is a
`train.step` record of the job timeline with what this process did in it,
and an interval far over the running median is a `train.stall` that names
where the loop's thread stood.  The rank's first report closes `train.setup`:
the loop's start to there, by what jax's tracer, lowering, compiler and cache
took of it and for whose function.
"""

from __future__ import annotations

import gc
import logging
import queue
import resource
import statistics
import sys
import threading
import time
import traceback
from collections import deque
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

from ray_tpu.air.checkpoint import Checkpoint
from ray_tpu.util import profiling, tracing

logger = logging.getLogger(__name__)

REPORT = "report"
FINISHED = "finished"
ERROR = "error"


@dataclass
class TrainContext:
    world_rank: int = 0
    world_size: int = 1
    local_rank: int = 0
    local_world_size: int = 1
    node_rank: int = 0
    experiment_name: str = ""
    trial_id: str = ""

# A stall: an interval that, once STALL_AFTER have been seen, is longer than
# STALL_FACTOR x the median of the last INTERVALS_KEPT, and by STALL_OVER_US
# or more.
INTERVALS_KEPT = 32
STALL_AFTER = 8
STALL_FACTOR = 1.5
STALL_OVER_US = 50_000
STALL_SAMPLES = 8       # stacks the watcher takes of one late step, at most
STALL_FRAMES = 12       # frames a stack keeps, from the leaf


def _profiler_on() -> bool:
    """Whether a `jax.profiler` session is running, asked only where this
    process has imported jax (this module never does)."""
    jax = sys.modules.get("jax")
    try:
        return bool(jax and jax.profiler.TraceAnnotation.is_enabled())
    except AttributeError:  # jax is still being imported
        return False


def _data_wait_us() -> int:
    """This thread's time under `data.block_wait` so far, where Data is
    in use."""
    dataset = sys.modules.get("ray_tpu.data.dataset")
    return dataset.block_wait_us() if dataset else 0


def stall_text(record: dict) -> str:
    """A `train.stall` record as the worker's warning words it."""
    a = record["attributes"]
    where = ""
    if a["stack"]:
        stack, seen = max(a["stack"].items(), key=lambda kv: kv[1])
        where = (f"; loop thread in {';'.join(stack.split(';')[-3:])} "
                 f"({seen} of {sum(a['stack'].values())} samples)")
    ms = lambda us: f"{us / 1e3:,.3g}" if us < 1e4 else f"{us / 1e3:,.0f}"
    return (f"train: step {a['n']} took {ms(record['duration_us'])} ms, "
            f"median {ms(a['median_us'])}{where}; "
            f"report {ms(a['report_us'])} ms, data {ms(a['data_us'])}, "
            f"gc {ms(a['gc_us'])}, thread cpu {ms(a['thread_cpu_us'])} ms, "
            f"process cpu {ms(a['process_cpu_us'])} ms, "
            f"{a['nivcsw']} pre-emptions, {a['majflt']} major faults"
            + (", in a profiler session" if a["profiled"] else ""))


def setup_text(record: dict) -> str:
    """A `train.setup` record as the worker's line words it, in seconds."""
    a = record["attributes"]
    s = lambda *keys: f"{sum(a['own_us'][key] for key in keys) / 1e6:.1f}"
    others = [key for key in a["own_us"] if key.endswith("/other")]
    return (f"train: set-up {record['duration_us'] / 1e6:.1f} s to the "
            f"first report: step trace {s('trace/step')}, lower "
            f"{s('lower/step')}, compile "
            f"{s('compile/step', 'cache_read/step')} "
            f"({a['step_cache'] or 'none'}); other functions "
            f"{s(*others)}; running {a['run_us'] / 1e6:.1f}")


class _StepWatch:
    """The step as the loop's process sees it, on in every job.

    `step()` runs on the loop's thread at the end of every report: the
    interval since the previous report becomes a `train.step` record whose
    attributes are this process's deltas over it, and an overrun a
    `train.stall` (a lifecycle record, so a long job keeps it), the
    counters `train.stalls` / `train.stall_us` and a warning.  A watcher
    thread waits for each report; only when one is late does it take the
    loop thread's stack, so the stall can say where the time went."""

    def __init__(self, rank: int):
        self._rank = rank
        self._intervals: deque = deque(maxlen=INTERVALS_KEPT)  # us
        self._median = None         # of them, once STALL_AFTER are seen
        self._gc_t0 = self._gc_ns = self._gc_runs = 0
        self._prev = None           # the readings at the last report's end
        # what the watcher reads: the open interval's number and start, and
        # the lengths (s) at which it is late and between two samples
        self._open = (0, 0.0, None, None)
        self._stacks = (0, {})      # step number, folded stack -> samples
        self._tick = threading.Event()
        self._done = False
        self._loop_ident = None

    def start(self, loop_thread: threading.Thread):
        self._loop_ident = loop_thread.ident
        gc.callbacks.append(self._on_gc)
        threading.Thread(target=self._watch, name="train-step-watch",
                         daemon=True).start()

    def stop(self):
        if self._done:
            return
        self._done = True
        self._tick.set()
        try:
            gc.callbacks.remove(self._on_gc)
        except ValueError:
            pass

    def _on_gc(self, phase, info):
        now = time.perf_counter_ns()
        if phase == "start":
            self._gc_t0 = now
        elif self._gc_t0:
            self._gc_ns += now - self._gc_t0
            self._gc_runs += 1
            self._gc_t0 = 0

    def _readings(self):
        usage = resource.getrusage(resource.RUSAGE_SELF)
        return (time.thread_time_ns() // 1000,
                time.process_time_ns() // 1000, self._gc_ns // 1000,
                self._gc_runs, usage.ru_nivcsw, usage.ru_majflt,
                tracing.counter("jax.compiles"), _data_wait_us())

    def step(self, ctx, n: int, put: float):
        """Report `n`, put at `put`, has just been consumed: it closes
        step `n`."""
        end, now, profiled = time.time(), self._readings(), _profiler_on()
        prev, self._prev = self._prev, (end, now, profiled)
        if prev is None:            # the first report opens the series
            self._open = (n + 1, end, None, None)
            return
        start, then, was_profiled = prev
        took = int((end - start) * 1e6)
        attrs = dict(zip(
            ("thread_cpu_us", "process_cpu_us", "gc_us", "gc_runs",
             "nivcsw", "majflt", "compiles", "data_us"),
            (a - b for a, b in zip(now, then))))
        attrs.update(n=n, rank=self._rank,
                     report_us=int((end - put) * 1e6),
                     profiled=profiled or was_profiled)
        tracing.timeline_hop("train.step", ctx, start, end, **attrs)
        tracing.count("train.steps")
        median = self._median       # of the intervals before this one
        if median is not None and took > STALL_FACTOR * median \
                and took - median >= STALL_OVER_US:
            at, stacks = self._stacks
            attrs.update(median_us=int(median), over_us=int(took - median),
                         stack=stacks if at == n else {})
            tracing.timeline_hop("train.stall", ctx, start, end, **attrs)
            tracing.count("train.stalls")
            tracing.count("train.stall_us", attrs["over_us"])
            if not attrs["profiled"]:
                logger.warning(stall_text(
                    {"duration_us": took, "attributes": attrs}))
        self._intervals.append(took)
        late_s = every_s = None
        if len(self._intervals) >= STALL_AFTER:
            median = self._median = statistics.median(self._intervals)
            late_s = max(STALL_FACTOR * median, median + STALL_OVER_US) / 1e6
            every_s = median / 2e6
        self._open = (n + 1, end, late_s, every_s)
        self._tick.set()

    def _watch(self):
        """Sleeps until the open interval is late; then samples the loop
        thread's stack every half median until its report comes."""
        while not self._done:
            n, start, late_s, every_s = self._open
            if self._tick.wait(None if late_s is None
                               else max(0.0, start + late_s - time.time())):
                self._tick.clear()
                continue
            stacks = {}
            for _ in range(STALL_SAMPLES):
                frame = sys._current_frames().get(self._loop_ident)
                if frame is None:
                    break
                stack = profiling._fold(frame, STALL_FRAMES)
                del frame
                stacks[stack] = stacks.get(stack, 0) + 1
                self._stacks = (n, dict(stacks))
                if self._tick.wait(every_s):
                    break
            self._tick.wait()       # the late report, or the session's end
            self._tick.clear()


class _TrainSession:
    def __init__(self, train_fn: Callable[[Optional[dict]], None],
                 config: Optional[dict], context: TrainContext,
                 checkpoint: Optional[Checkpoint]):
        self.context = context
        self.checkpoint = checkpoint
        self._result_q: "queue.Queue" = queue.Queue(maxsize=1)
        self._consumed = threading.Event()
        self._dataset_shards: Dict[str, Any] = {}
        # the session is made inside the trainer's `start_session` call:
        # the loop's thread parents its spans under that call's context
        self._trace_ctx = tracing.timeline_ctx()
        self._reports = 0
        self._loop_start = 0.0      # `train.loop` entered
        # outside a job there is no timeline to keep the steps in
        self._steps = (_StepWatch(context.world_rank) if self._trace_ctx
                       else None)
        self._thread = threading.Thread(
            target=self._run, args=(train_fn, config),
            name=f"train-session-rank{context.world_rank}", daemon=True,
        )

    def start(self):
        self._thread.start()
        if self._steps:
            self._steps.start(self._thread)

    def _run(self, train_fn, config):
        try:
            # Reference semantics (`construct_train_func`): a loop that
            # accepts a parameter receives the config dict ({} if none given).
            import inspect

            takes_config = False
            try:
                takes_config = len(inspect.signature(
                    train_fn).parameters) >= 1
            except (TypeError, ValueError):
                pass
            with tracing.timeline_span("train.loop", parent=self._trace_ctx,
                                       rank=self.context.world_rank):
                self._loop_start = time.time()
                if takes_config:
                    train_fn(config if config is not None else {})
                else:
                    train_fn()
        except BaseException as e:  # noqa: BLE001
            outcome = (ERROR, (e, traceback.format_exc()))
        else:
            outcome = (FINISHED, None)
        self._end_steps()
        self._result_q.put(outcome)

    def _end_steps(self):
        if self._steps:
            self._steps.stop()

    # ---------------------------------------------------------------- worker API

    def report(self, metrics: Dict[str, Any],
               checkpoint: Optional[Checkpoint] = None):
        # put -> consumed: the time the loop is blocked on the trainer
        put = time.time()
        with tracing.timeline_span("train.report", n=self._reports,
                                   checkpoint=checkpoint is not None):
            self._consumed.clear()
            self._result_q.put((REPORT, (metrics, checkpoint)))
            # Lockstep: wait until the driver drained this round before
            # producing the next (reference blocks on a bounded queue too).
            self._consumed.wait()
        tracing.count("train.reports")
        ctx = self._steps and tracing.timeline_ctx()
        if ctx:
            if not self._reports:
                self._setup_done(ctx)
            self._steps.step(ctx, self._reports, put)
        self._reports += 1

    def _setup_done(self, ctx):
        """The rank's first report has returned: `train.setup`, the loop's
        start to now, with what jax's tracer, lowering, compiler and cache
        took of it on this thread (`backend.py:setup_account`) and, as
        `run_us`, what is left: the thread executing, waiting on the device
        or in Python that is no trace.  One INFO line words it."""
        from ray_tpu.train.backend import setup_account  # imports this file

        end = time.time()
        took_us = int((end - self._loop_start) * 1e6)
        attrs = setup_account(ctx)
        attrs.update(rank=self.context.world_rank,
                     run_us=took_us - sum(attrs["own_us"].values()))
        tracing.timeline_hop("train.setup", ctx, self._loop_start, end,
                             **attrs)
        logger.info(setup_text({"duration_us": took_us,
                                "attributes": attrs}))

    # ---------------------------------------------------------------- driver side

    def get_next(self):
        """Blocks until the next report/finish/error event."""
        kind, payload = self._result_q.get()
        if kind == REPORT:
            self._consumed.set()
        return kind, payload

    def finish(self, timeout: Optional[float] = 10):
        self._consumed.set()
        self._thread.join(timeout=timeout)
        self._end_steps()           # also of a loop that never returned


_session: Optional[_TrainSession] = None
_session_lock = threading.Lock()


def _init_session(session: _TrainSession):
    global _session
    with _session_lock:
        _session = session


def _shutdown_session():
    global _session
    with _session_lock:
        _session = None


def get_session() -> Optional[_TrainSession]:
    return _session


# ------------------------------------------------------------------ public API
# (reference: ``ray.air.session`` / ``ray.train`` free functions)


def report(metrics: Dict[str, Any], checkpoint: Optional[Checkpoint] = None,
           **_):
    """Report metrics (and optionally a checkpoint) to the trainer driver."""
    s = get_session()
    if s is None:
        raise RuntimeError("session.report() called outside a train session")
    if checkpoint is not None and not isinstance(checkpoint, Checkpoint):
        checkpoint = Checkpoint.from_dict(dict(checkpoint))
    s.report(dict(metrics), checkpoint)


def get_checkpoint() -> Optional[Checkpoint]:
    """The checkpoint to resume from (None on a fresh start)."""
    s = get_session()
    return s.checkpoint if s else None


def get_context() -> TrainContext:
    s = get_session()
    return s.context if s else TrainContext()


def get_world_rank() -> int:
    return get_context().world_rank


def get_world_size() -> int:
    return get_context().world_size


def get_local_rank() -> int:
    return get_context().local_rank


def get_dataset_shard(name: str = "train"):
    """The per-worker shard of a dataset passed to the trainer
    (reference: `session.get_dataset_shard`)."""
    s = get_session()
    if s is None:
        return None
    return s._dataset_shards.get(name)
