"""Core-runtime microbenchmark — the `ray_perf.py` analogue
(reference: `python/ray/_private/ray_perf.py:93`, recorded numbers in
`release/release_logs/2.5.0/microbenchmark.json`, tabulated in BASELINE.md).

Prints one JSON line per metric and writes the full dict to
``BENCH_CORE.json``.  Run: ``python bench_core.py [--quick]``.

Reference single-client numbers to beat (m4.16xlarge-class):
  plasma put/get        6,364 / 5,980 ops/s
  put throughput        18.8 GiB/s
  tasks sync            1,341 /s
  tasks async           11,527 /s
  actor calls sync 1:1  2,427 /s
  actor calls async 1:1 8,178 /s
  pg create/remove      1,089 /s
"""

from __future__ import annotations

import argparse
import json
import os
import time

# CPU-only: the control plane is what's being measured, keep jax/TPU out
# of the workers entirely.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def timed(n, fn):
    t0 = time.perf_counter()
    fn()
    return n / (time.perf_counter() - t0)


def paired_overhead(run, set_mode, modes, rounds=5):
    """Observability-tax estimator: per-round PAIRED ratios, best round
    wins.

    Each round runs every mode (order reversed on odd rounds — the
    palindrome cancels linear host drift) and ratios each mode against
    the SAME round's baseline (``modes[0]``).  Taking best-of rates per
    mode across rounds and ratioing those compares windows measured at
    different points of a session that slows monotonically as tables and
    GC pressure accumulate, so ordering alone can fabricate double-digit
    "overhead"; a paired ratio sees the same host in both halves.  Noise
    only ever inflates a measured tax, never hides one that large, so
    the minimum-tax round is the least-contaminated estimate — the same
    argument behind best-of-N everywhere else in this file.  One
    throwaway warm-up pass over all modes runs first: the first window
    of a fresh runtime is reproducibly the fastest and would otherwise
    crown whichever mode goes first.

    Returns ``(rates, tax)``: best observed rate per mode, and per
    non-baseline mode the overhead fraction ``1 - best paired ratio``
    clamped to 0.  Five rounds by default: the taxes these rows guard
    are near zero, where per-round host noise (±10% on a 1-CPU
    container) dominates — more rounds give the min-tax estimator more
    chances at an uncontaminated pair.
    """
    base = modes[0]
    rates = {name: 0.0 for name in modes}
    ratios = {name: 0.0 for name in modes[1:]}
    for name in modes:  # warm-up: unrecorded
        set_mode(name)
        run()
    for rnd in range(rounds):
        round_rates = {}
        for name in (modes if rnd % 2 == 0 else modes[::-1]):
            set_mode(name)
            round_rates[name] = run()
            rates[name] = max(rates[name], round_rates[name])
        for name in modes[1:]:
            ratios[name] = max(
                ratios[name],
                round_rates[name] / max(round_rates[base], 1e-9))
    tax = {name: round(max(0.0, 1.0 - r), 4) for name, r in ratios.items()}
    return rates, tax


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--quick", action="store_true",
                        help="10x fewer iterations (CI smoke)")
    args = parser.parse_args()
    scale = 0.1 if args.quick else 1.0

    import ray_tpu

    ray_tpu.init(num_cpus=max(4, os.cpu_count() or 4))
    results = {}
    # Provenance: vs_reference compares against m4.16xlarge-class numbers
    # (BASELINE.md); absolute rows are only comparable across runs on the
    # same host class, so record what this one looked like.
    results["bench_env"] = {
        "host_cpus": os.cpu_count(),
        "note": ("vs_reference baselines were recorded on an "
                 "m4.16xlarge-class host; compare absolute rows only "
                 "against runs on the same host (see host_memcpy_gib_per_s "
                 "for a same-run hardware yardstick)"),
        "pr18_same_host_controls": (
            "PR 18 HEAD re-benched on THIS host (A/B via stash): "
            "tasks_async 3442-3644/s, actor_calls_async 2922-3233/s, "
            "actor_calls_direct_sync 1100-1432/s — burst-mode gains "
            "must be read against these, not the faster-host PR 18 "
            "BENCH_CORE.json absolutes"),
    }

    # Context for the GiB/s rows: the reference's 18.8 GiB/s was measured
    # on an m4.16xlarge (64 cores); put throughput is one memcpy, so this
    # host's single-core memcpy bandwidth is the attainable ceiling.
    _a = np.random.randint(0, 255, 64 << 20, np.uint8)
    _b = np.empty_like(_a)
    _t0 = time.perf_counter()
    for _ in range(5):
        np.copyto(_b, _a)
    host_bw = 5 * _a.nbytes / (1 << 30) / (time.perf_counter() - _t0)
    del _a, _b

    def record(name, value, unit="ops/s", baseline=None):
        results[name] = {"value": round(value, 1), "unit": unit}
        if baseline:
            results[name]["vs_reference"] = round(value / baseline, 2)
        print(json.dumps({"metric": name, **results[name]}), flush=True)

    # ---- frame codec (control-plane framing, no cluster involved) ----
    # Measures scan+decode of coalesced frame trains — the raylet's
    # per-wakeup receive work — independently of scheduler changes.
    import pickle as _pickle

    from ray_tpu.core import protocol as _protocol

    _codec_msgs = [
        {"t": "done", "task_id": b"x" * 16, "ok": True,
         "inline": {"aa" * 10: b"y" * 64}, "stored": [], "sizes": {},
         "contains": {}}
        for _ in range(64)
    ]
    _codec_stream = bytes(_protocol.encode_frames(
        [_pickle.dumps(m, protocol=5) for m in _codec_msgs]))
    _codec_rounds = max(20, int(200 * scale))
    _n_frames = 0
    _t0 = time.perf_counter()
    for _ in range(_codec_rounds):
        _buf = bytearray(_codec_stream)
        _sink = []
        _protocol.drain_frames(_buf, _sink.append, lambda: True)
        _n_frames += len(_sink)
    record("proto_frames_per_s", _n_frames / (time.perf_counter() - _t0))
    results["proto_codec"] = {
        "value": _protocol._codec.name,
        "unit": "codec (RAY_TPU_DISABLE_NATIVE_CODEC=1 forces python)"}
    print(json.dumps({"metric": "proto_codec", **results["proto_codec"]}),
          flush=True)

    # Warm the worker pool BEFORE any timed row: prestarted workers spend
    # seconds importing Python+numpy, and on a small host that contention
    # otherwise lands on whichever rows run first (put/get are op-overhead
    # benchmarks, not import-contention benchmarks).
    @ray_tpu.remote
    def _warm():
        return b"ok"

    ray_tpu.get([_warm.remote() for _ in range(16)])

    # ---- object store put/get (small objects: op overhead) ----
    n = int(3000 * scale)
    small = np.zeros(16, np.uint8)

    def put_loop():
        for _ in range(n):
            ray_tpu.put(small)

    record("put_small_ops_per_s", timed(n, put_loop), baseline=6364.1)

    big_ref = ray_tpu.put(np.zeros(1 << 20, np.uint8))  # 1MB -> store

    def get_loop():
        for _ in range(n):
            ray_tpu.get(big_ref)

    record("get_1mb_ops_per_s", timed(n, get_loop), baseline=5979.7)

    # ---- put throughput (GiB/s, 64MB objects, steady state) ----
    blob = np.random.randint(0, 255, 64 << 20, np.uint8)
    reps = max(2, int(16 * scale))
    ray_tpu.free([ray_tpu.put(blob)])  # warm pages/allocator

    def put_tp():
        for _ in range(reps):
            ray_tpu.free([ray_tpu.put(blob)])

    gib = reps * blob.nbytes / (1 << 30)
    t0 = time.perf_counter()
    put_tp()
    record("put_gib_per_s", gib / (time.perf_counter() - t0), unit="GiB/s",
           baseline=18.8)
    record("host_memcpy_gib_per_s", host_bw, unit="GiB/s")
    results["put_vs_host_memcpy"] = {
        "value": round(results["put_gib_per_s"]["value"] / max(host_bw, 1e-9),
                       2),
        "unit": "fraction of single-core memcpy ceiling"}
    print(json.dumps({"metric": "put_vs_host_memcpy",
                      **results["put_vs_host_memcpy"]}), flush=True)

    # ---- tasks ----
    @ray_tpu.remote
    def nop():
        return b"ok"

    # pool is warm (init above); prime this function's dispatch path
    ray_tpu.get([nop.remote() for _ in range(8)])

    n = int(1000 * scale)

    def tasks_sync():
        for _ in range(n):
            ray_tpu.get(nop.remote())

    record("tasks_sync_per_s", timed(n, tasks_sync), baseline=1341.4)

    n = int(10000 * scale)

    def tasks_async():
        ray_tpu.get([nop.remote() for _ in range(n)])

    # best-of-2 like the A/B rows: a single 10k-call draw on a 1-CPU
    # container swings ±25% with background churn
    record("tasks_async_per_s",
           max(timed(n, tasks_async), timed(n, tasks_async)),
           baseline=11527.5)

    # ---- task-event export overhead (observability tax) ----
    # Same loop with the export pipeline off (RAY_TPU_TASK_EVENTS=0
    # equivalent): the row tracks what fraction of tasks_async throughput
    # the task-event export costs, so observability regressions show up in
    # BENCH_CORE.json like any perf regression.
    events_before = ray_tpu.config.task_events
    try:
        rates, tax = paired_overhead(
            lambda: timed(n, tasks_async),
            lambda mode: setattr(ray_tpu.config, "task_events",
                                 mode == "on"),
            ("off", "on"))
    finally:
        ray_tpu.config.task_events = events_before
    record("tasks_async_no_task_events_per_s", rates["off"])
    results["task_events_overhead"] = {
        "value": tax["on"],
        "unit": ("fraction of tasks_async throughput lost with task-event "
                 "export enabled (toggle: RAY_TPU_TASK_EVENTS)"),
    }
    print(json.dumps({"metric": "task_events_overhead",
                      **results["task_events_overhead"]}), flush=True)

    # ---- metrics time-series export overhead ----
    # tasks_async with the point export on vs off
    # (RAY_TPU_METRICS_HISTORY=0 keeps only the snapshot KV).  Point
    # collection runs on the flush cadence, not per task, so this row
    # mostly guards against someone moving collection into the hot path.
    hist_before = ray_tpu.config.metrics_history
    try:
        rates, tax = paired_overhead(
            lambda: timed(n, tasks_async),
            lambda mode: setattr(ray_tpu.config, "metrics_history",
                                 mode == "on"),
            ("off", "on"))
    finally:
        ray_tpu.config.metrics_history = hist_before
    record("tasks_async_no_metrics_history_per_s", rates["off"])
    results["metrics_overhead"] = {
        "value": tax["on"],
        "unit": ("fraction of tasks_async throughput lost with metrics "
                 "time-series export enabled (toggle: "
                 "RAY_TPU_METRICS_HISTORY)"),
    }
    print(json.dumps({"metric": "metrics_overhead",
                      **results["metrics_overhead"]}), flush=True)

    # ---- actor calls ----
    @ray_tpu.remote
    class A:
        def m(self):
            return b"ok"

    a = A.remote()
    ray_tpu.get(a.m.remote())

    n = int(2000 * scale)

    def actor_sync():
        for _ in range(n):
            ray_tpu.get(a.m.remote())

    record("actor_calls_sync_per_s", timed(n, actor_sync), baseline=2427.0)

    n = int(10000 * scale)

    def actor_async():
        ray_tpu.get([a.m.remote() for _ in range(n)])

    # best-of-2 (same rationale as tasks_async_per_s)
    record("actor_calls_async_per_s",
           max(timed(n, actor_async), timed(n, actor_async)),
           baseline=8177.9)

    # ---- direct worker→worker transport ----
    # Interleaved A/B on the same actor in the same run: direct channel
    # vs the RAY_TPU_DIRECT_CALLS=0 kill switch (raylet-relayed path).
    # Best-of-2 per mode, like task_events_overhead — a single pair on a
    # noisy shared host mostly measures the host.
    n = int(2000 * scale)
    direct_rate = relayed_rate = 0.0
    for _ in range(2):
        ray_tpu.config.direct_calls = True
        # observe a completion so the channel (re-)engages order-safely
        ray_tpu.get(a.m.remote())
        ray_tpu.get(a.m.remote())
        direct_rate = max(direct_rate, timed(n, actor_sync))
        ray_tpu.config.direct_calls = False
        relayed_rate = max(relayed_rate, timed(n, actor_sync))
    ray_tpu.config.direct_calls = True
    record("actor_calls_direct_sync_per_s", direct_rate, baseline=2427.0)
    results["direct_vs_relayed"] = {
        "value": round(direct_rate / max(relayed_rate, 1e-9), 2),
        "unit": ("sync actor-call speedup of the direct worker→worker "
                 "channel over the raylet-relayed path, same actor, "
                 "interleaved A/B (kill switch: RAY_TPU_DIRECT_CALLS=0; "
                 "relayed best-of-2: "
                 f"{round(relayed_rate, 1)} ops/s)"),
    }
    print(json.dumps({"metric": "direct_vs_relayed",
                      **results["direct_vs_relayed"]}), flush=True)

    # same-host actor-call round-trip latency on the direct channel
    ray_tpu.get(a.m.remote())
    ray_tpu.get(a.m.remote())
    lat_n = max(200, int(1000 * scale))
    lats = []
    for _ in range(lat_n):
        t0 = time.perf_counter()
        ray_tpu.get(a.m.remote())
        lats.append((time.perf_counter() - t0) * 1e6)
    lats.sort()
    results["actor_rtt_same_host_us"] = {
        "p50": round(lats[lat_n // 2], 1),
        "p95": round(lats[int(lat_n * 0.95)], 1),
        "unit": "us round-trip per sync actor call, direct channel",
    }
    print(json.dumps({"metric": "actor_rtt_same_host_us",
                      **results["actor_rtt_same_host_us"]}), flush=True)

    # ---- direct burst mode (windowed-ack async pipeline) ----
    # Interleaved A/B like direct_vs_relayed, but on the ASYNC loop the
    # burst path exists for: coalesced dcall trains + windowed ack over
    # the direct channel vs the fully relayed path
    # (RAY_TPU_DIRECT_CALLS=0).  Best-of-2 per mode — same-host noise
    # swamps a single pair.
    n = int(10000 * scale)
    burst_rate = relayed_async = 0.0
    for _ in range(2):
        ray_tpu.config.direct_calls = True
        # observe completions so the channel (re-)engages order-safely
        ray_tpu.get(a.m.remote())
        ray_tpu.get(a.m.remote())
        burst_rate = max(burst_rate, timed(n, actor_async))
        ray_tpu.config.direct_calls = False
        relayed_async = max(relayed_async, timed(n, actor_async))
    ray_tpu.config.direct_calls = True
    record("actor_calls_burst_async_per_s", burst_rate, baseline=8177.9)
    results["direct_burst_vs_relayed_async"] = {
        "value": round(burst_rate / max(relayed_async, 1e-9), 2),
        "unit": ("async actor-call speedup of the direct burst path "
                 "(windowed ack, coalesced frames) over the "
                 "raylet-relayed path, same actor, interleaved A/B "
                 "(kill switches: RAY_TPU_DIRECT_CALLS=0 relays, "
                 "RAY_TPU_DIRECT_BURST=0 keeps direct but drains at "
                 "pipeline depth; relayed best-of-2: "
                 f"{round(relayed_async, 1)} ops/s)"),
    }
    print(json.dumps({"metric": "direct_burst_vs_relayed_async",
                      **results["direct_burst_vs_relayed_async"]}),
          flush=True)

    # ---- burst-depth sweep ----
    # Same async loop at several window sizes W (driver-side live read,
    # see direct.py submit()).  Throughput should rise with W to the
    # socket-buffer knee and plateau — the direct_burst_window default
    # sits on the plateau.  W=1 degenerates to per-call lockstep.
    default_w = ray_tpu.config.direct_burst_window
    sweep = {}
    try:
        for w in (1, 8, 32, default_w):
            ray_tpu.config.direct_burst_window = w
            ray_tpu.get(a.m.remote())  # re-observe before each leg
            sweep[f"W={w}"] = round(timed(n, actor_async), 1)
    finally:
        ray_tpu.config.direct_burst_window = default_w
    results["direct_burst_depth_sweep"] = {
        "value": sweep,
        "unit": ("async actor calls/s by burst window "
                 "(RAY_TPU_DIRECT_BURST_WINDOW; "
                 f"default W={default_w})"),
    }
    print(json.dumps({"metric": "direct_burst_depth_sweep",
                      **results["direct_burst_depth_sweep"]}), flush=True)

    # ---- actor checkpoint overhead ----
    # Same class with and without checkpoint_interval, sync call loop:
    # the row tracks what fraction of call throughput the __ray_save__
    # snapshot + checkpoint message costs at a 1-in-10 cadence.
    @ray_tpu.remote
    class Ckpt:
        def __init__(self):
            self.state = {"n": 0}

        def m(self):
            self.state["n"] += 1
            return b"ok"

        def __ray_save__(self):
            return self.state

        def __ray_restore__(self, s):
            self.state = s

    plain = Ckpt.remote()
    ckpt = Ckpt.options(checkpoint_interval=10, max_restarts=1).remote()
    ray_tpu.get([plain.m.remote(), ckpt.m.remote()])
    n = int(2000 * scale)

    def plain_sync():
        for _ in range(n):
            ray_tpu.get(plain.m.remote())

    def ckpt_sync():
        for _ in range(n):
            ray_tpu.get(ckpt.m.remote())

    # interleaved best-of-2 per mode (like task_events_overhead): a single
    # A/B pair on a noisy shared host mostly measures the host
    plain_rate = ckpt_rate = 0.0
    for _ in range(2):
        plain_rate = max(plain_rate, timed(n, plain_sync))
        ckpt_rate = max(ckpt_rate, timed(n, ckpt_sync))
    record("actor_calls_sync_checkpointed_per_s", ckpt_rate)
    results["actor_checkpoint_overhead"] = {
        "value": round(max(0.0, 1.0 - ckpt_rate / max(plain_rate, 1e-9)), 4),
        "unit": ("fraction of sync actor-call throughput lost with "
                 "checkpoint_interval=10 (__ray_save__ snapshot + "
                 "checkpoint message every 10th call)"),
    }
    print(json.dumps({"metric": "actor_checkpoint_overhead",
                      **results["actor_checkpoint_overhead"]}), flush=True)

    # ---- placement groups ----
    n = int(500 * scale)

    def pgs():
        for _ in range(n):
            pg = ray_tpu.placement_group([{"CPU": 1}])
            pg.wait(timeout_seconds=10)
            ray_tpu.remove_placement_group(pg)

    record("pg_create_remove_per_s", timed(n, pgs), baseline=1088.5)

    ray_tpu.shutdown()

    # ---- request-flow tracing overhead (fresh traced runtime) ----
    bench_trace(results, record, scale)

    # ---- continuous-profiling overhead (fresh runtime per mode) ----
    bench_profile(results, record, scale)

    # ---- cross-node data plane (two-node same-host harness) ----
    bench_remote(results, record, scale)

    # ---- lineage reconstruction under node death ----
    bench_reconstruction(results, record, scale)

    # ---- overload shedding: 2x-capacity load, shed-on vs unbounded ----
    bench_overload(results, record, scale)

    # ---- failure detection latency (suspicion + active probing) ----
    # LAST: its kill rounds SIGKILL five raylets whose orphaned workers
    # die only when they next touch the raylet socket — background import
    # churn that would pollute a storm row timed right after, while the
    # detection LATENCY rows are insensitive to it (the soak is itself a
    # load test).
    bench_detection(results, record, scale)

    # ---- compound-fault MTTR + invariant-bank verdict ----
    # After detection for the same reason detection runs after the storm
    # rows: this bench SIGKILLs raylets and restarts the GCS; nothing
    # timed later would survive the churn.
    bench_chaos(results, record, scale)

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_CORE.json"), "w") as f:
        json.dump(results, f, indent=1)
    return 0


def bench_trace(results, record, scale):
    """Request-flow tracing tax on tasks_async, task_events_overhead-style:
    a fresh runtime with tracing armed in every process, paired_overhead
    rounds with the pipeline OFF (RAY_TPU_TRACE=0 kill switch),
    head-sampled at 1% (the production setting), and at 100%.  Only the
    driver's env toggles — sampling is decided at the trace root and rides
    the span context, so workers follow without restarts."""
    import ray_tpu
    from ray_tpu.util import tracing

    os.environ["RAY_TPU_TRACE"] = "1"
    tracing.enable_tracing()
    ray_tpu.init(num_cpus=max(4, os.cpu_count() or 4))

    @ray_tpu.remote
    def nop():
        return b"ok"

    ray_tpu.get([nop.remote() for _ in range(8)])
    n = int(10000 * scale)

    def tasks_async():
        ray_tpu.get([nop.remote() for _ in range(n)])

    mode_env = {
        "off": {"RAY_TPU_TRACE": "0"},
        "sampled_1pct": {"RAY_TPU_TRACE": "1",
                         "RAY_TPU_TRACE_SAMPLE": "0.01"},
        "sampled_all": {"RAY_TPU_TRACE": "1",
                        "RAY_TPU_TRACE_SAMPLE": "1.0"},
    }
    try:
        rates, tax = paired_overhead(
            lambda: timed(n, tasks_async),
            lambda mode: os.environ.update(mode_env[mode]),
            ("off", "sampled_1pct", "sampled_all"))
    finally:
        os.environ["RAY_TPU_TRACE"] = "0"
        os.environ.pop("RAY_TPU_TRACE_SAMPLE", None)
    ray_tpu.shutdown()
    record("tasks_async_trace_off_per_s", rates["off"])
    record("tasks_async_traced_1pct_per_s", rates["sampled_1pct"])
    record("tasks_async_traced_all_per_s", rates["sampled_all"])
    for name, key, setting in (
            ("trace_overhead", "sampled_1pct", "RAY_TPU_TRACE_SAMPLE=0.01"),
            ("trace_overhead_full", "sampled_all",
             "RAY_TPU_TRACE_SAMPLE=1.0")):
        results[name] = {
            "value": tax[key],
            "unit": (f"fraction of tasks_async throughput lost with "
                     f"request-flow tracing at {setting} vs disabled"),
        }
        print(json.dumps({"metric": name, **results[name]}), flush=True)


def bench_profile(results, record, scale):
    """Continuous-profiling tax on tasks_async, trace_overhead-style:
    interleaved on/off (RAY_TPU_PROFILE kill switch) at the default
    sampling rate, order-symmetric best-of-3 with the mode order reversed
    on odd rounds so monotone host drift can't masquerade as sampler tax.
    Unlike tracing, the switch is read from each process's OWN
    environment — workers inherit it at spawn — so each mode gets a fresh
    runtime (the honest way to flip the whole process tree)."""
    import ray_tpu
    from ray_tpu.util import profiling

    n = int(10000 * scale)
    modes = [("off", "0"), ("on", "1")]
    rates = {name: 0.0 for name, _ in modes}
    try:
        for rnd in range(3):
            for name, val in (modes if rnd % 2 == 0 else modes[::-1]):
                os.environ["RAY_TPU_PROFILE"] = val
                profiling._live["at"] = -1.0  # skip the 0.25s flag cache
                ray_tpu.init(num_cpus=max(4, os.cpu_count() or 4))

                @ray_tpu.remote
                def nop():
                    return b"ok"

                ray_tpu.get([nop.remote() for _ in range(8)])
                rates[name] = max(rates[name], timed(
                    n, lambda: ray_tpu.get(
                        [nop.remote() for _ in range(n)])))
                ray_tpu.shutdown()
    finally:
        os.environ.pop("RAY_TPU_PROFILE", None)
        profiling._live["at"] = -1.0
    record("tasks_async_profile_off_per_s", rates["off"])
    record("tasks_async_profiled_per_s", rates["on"])
    results["profile_overhead"] = {
        "value": round(
            max(0.0, 1.0 - rates["on"] / max(rates["off"], 1e-9)), 4),
        "unit": ("fraction of tasks_async throughput lost with the "
                 "in-process sampling profiler at the default "
                 "RAY_TPU_PROFILE_HZ vs the RAY_TPU_PROFILE=0 kill "
                 "switch"),
    }
    print(json.dumps({"metric": "profile_overhead",
                      **results["profile_overhead"]}), flush=True)


def bench_remote(results, record, scale):
    """Cross-node get() throughput + control-plane latency under transfer,
    on a fake two-node cluster on this host.

    Runs TWICE: RAY_TPU_DATA_CHANNEL=0 first (the python-fallback path —
    pickled chunks on the control socket, the pre-data-plane behavior)
    records the ``_baseline`` rows, then the zero-copy data plane records
    the headline rows.  Both baselines are measured in the SAME run on the
    SAME host, so the speedup columns are apples-to-apples.
    """
    import statistics
    import threading

    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    reps_64 = 2 if scale < 1 else 3
    reps_4 = 3 if scale < 1 else 5

    for env_val, suffix in (("0", "_baseline"), ("1", "")):
        c = Cluster(initialize_head=True,
                    head_resources={"num_cpus": 2, "object_store_mb": 1024},
                    env={"RAY_TPU_DATA_CHANNEL": env_val,
                         # production-ish failure detection: the fallback
                         # path starves a loaded 2-CPU host long enough to
                         # trip the test-tuned 1.5s node timeout mid-bench
                         "RAY_TPU_GCS_HEARTBEAT_INTERVAL_S": "0.5",
                         "RAY_TPU_GCS_NODE_TIMEOUT_S": "10"})
        try:
            c.add_node(num_cpus=2, resources={"b": 1}, object_store_mb=1024)
            c.wait_for_nodes(2)
            c.connect()

            @ray_tpu.remote(resources={"b": 0.1})
            def make(mb):
                import numpy as _np

                return [ray_tpu.put(
                    _np.random.randint(0, 255, mb << 20, _np.uint8))]

            def fresh_remote_ref(mb):
                # the inner ref's bytes live ONLY on node b; getting it on
                # the driver pulls through the head raylet's store
                (ref,) = ray_tpu.get(make.remote(mb), timeout=60)
                return ref

            def remote_get_gib_per_s(mb, reps):
                best = 0.0
                for _ in range(reps):
                    ref = fresh_remote_ref(mb)
                    t0 = time.perf_counter()
                    val = ray_tpu.get(ref, timeout=180)
                    dt = time.perf_counter() - t0
                    assert val.nbytes == mb << 20
                    del val
                    ray_tpu.free([ref])
                    best = max(best, (mb / 1024) / dt)
                return best

            # warm the pull path (peer + data-channel setup, worker spawn)
            ray_tpu.get(fresh_remote_ref(1), timeout=60)

            record(f"get_remote_4mb_gib_per_s{suffix}",
                   remote_get_gib_per_s(4, reps_4), unit="GiB/s")
            record(f"get_remote_64mb_gib_per_s{suffix}",
                   remote_get_gib_per_s(64, reps_64), unit="GiB/s")

            # ---- control-plane latency while a big transfer streams ----
            def rtt_ms():
                t0 = time.perf_counter()
                ray_tpu.available_resources()
                return (time.perf_counter() - t0) * 1e3

            def paced_rtts(stop, limit=2000):
                # paced pings: a busy ping loop would burn a core of this
                # small host and measure its own contention, not the
                # control plane's
                out = []
                while not stop() and len(out) < limit:
                    out.append(rtt_ms())
                    time.sleep(0.005)
                return out

            for _ in range(5):
                rtt_ms()
            _n = [0]

            def _idle_stop():
                _n[0] += 1
                return _n[0] > 30

            idle = statistics.median(paced_rtts(_idle_stop))
            refs = [fresh_remote_ref(64) for _ in range(3)]
            done = threading.Event()

            def transfer():
                try:
                    for r in refs:
                        ray_tpu.get(r, timeout=180)
                finally:
                    done.set()

            t = threading.Thread(target=transfer, daemon=True)
            t.start()
            under = paced_rtts(done.is_set)
            t.join(timeout=200)
            ray_tpu.free(refs)
            # drop the post-transfer tail sample (done set mid-ping)
            under = under[:-1] or under
            record(f"control_latency_idle_ms{suffix}", idle, unit="ms")
            record(f"control_latency_under_transfer_ms{suffix}",
                   statistics.median(under) if under else idle, unit="ms")
            if under:
                record(f"control_latency_under_transfer_p95_ms{suffix}",
                       sorted(under)[int(len(under) * 0.95)], unit="ms")
        finally:
            c.shutdown()

    def _val(name):
        return results.get(name, {}).get("value", 0.0)

    for mb in (4, 64):
        base = _val(f"get_remote_{mb}mb_gib_per_s_baseline")
        if base > 0:
            results[f"data_plane_speedup_{mb}mb"] = {
                "value": round(_val(f"get_remote_{mb}mb_gib_per_s") / base,
                               2),
                "unit": "x vs python-fallback path (same run, same host)"}
            print(json.dumps({"metric": f"data_plane_speedup_{mb}mb",
                              **results[f"data_plane_speedup_{mb}mb"]}),
                  flush=True)


def bench_detection(results, record, scale):
    """``time_to_detect``: how fast the suspicion machine declares a
    SIGKILLed node dead (suspect after 0.5s of heartbeat silence, then a
    direct + indirect liveness probe), and — the other half of the
    contract — that a node running flat-out for a minute is never
    falsely declared dead.  The GCS-side samples measure last-contact ->
    DEAD declaration; the wall rows measure SIGKILL -> a client
    observing the death, which adds heartbeat-phase + poll jitter.
    """
    import statistics

    import ray_tpu
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.core.gcs import GcsClient

    # Detection DEFAULTS on purpose (suspect 0.5s / probe 0.4s / hard
    # fallback 3.0s): the row measures what a stock cluster gets.
    c = Cluster(initialize_head=True, head_resources={"num_cpus": 2},
                env={"RAY_TPU_GCS_HEARTBEAT_INTERVAL_S": "0.25"})
    try:
        worker = c.add_node(num_cpus=2, resources={"w": 1})
        c.wait_for_nodes(2)
        c.connect()
        cli = GcsClient(c.address)

        @ray_tpu.remote(num_cpus=1, resources={"w": 0.01})
        def burn(sec):
            end = time.monotonic() + sec
            x = 0
            while time.monotonic() < end:
                x += sum(range(2048))  # CPU-bound: contends with the
            return x                   # raylet's heartbeat thread

        # -- loaded soak: both worker CPUs busy, zero false positives --
        soak_s = max(6.0, 60.0 * scale)
        t_end = time.perf_counter() + soak_s
        refs = [burn.remote(0.5) for _ in range(2)]
        while time.perf_counter() < t_end:
            done, refs = ray_tpu.wait(refs, num_returns=1, timeout=30)
            ray_tpu.get(done, timeout=30)
            refs.append(burn.remote(0.5))
        ray_tpu.get(refs, timeout=60)
        hs = cli.health_stats()
        assert hs["deaths_detected_total"] == 0, \
            f"false-positive death under load: {hs}"
        record("detect_soak_false_deaths", float(
            hs["deaths_detected_total"]),
            unit=(f"false-positive DEAD declarations over a {soak_s:.0f}s "
                  f"fully-loaded-node soak (suspicions raised+recovered: "
                  f"{hs['false_suspects_total']})"))

        # -- kill rounds: SIGKILL a node, time the death declaration --
        rounds = max(3, int(5 * scale))
        walls = []
        victim = worker
        for r in range(rounds):
            if victim is None:
                victim = c.add_node(num_cpus=2, resources={"w": 1})
                c.wait_for_nodes(2)  # head + the replacement
            time.sleep(0.6)  # steady heartbeating before the strike
            t0 = time.perf_counter()
            c.remove_node(victim)
            while True:
                info = cli.get_node(victim.node_id)
                if info is not None and not info["alive"]:
                    break
                if time.perf_counter() - t0 > 30:
                    raise AssertionError("death never detected")
                time.sleep(0.02)
            walls.append(time.perf_counter() - t0)
            victim = None
        hs = cli.health_stats()
        ttd = hs["time_to_detect_s"]
        assert len(ttd) >= rounds and hs["deaths_detected_total"] == rounds

        def srecord(name, value, unit):  # record() rounds to 0.1s
            results[name] = {"value": round(value, 3), "unit": unit}
            print(json.dumps({"metric": name, **results[name]}), flush=True)

        srecord("time_to_detect_p50_s", statistics.median(ttd),
                unit=(f"s, GCS last-contact -> DEAD (suspect @0.5s + "
                      f"liveness probe), p50 of {len(ttd)} SIGKILLs"))
        srecord("time_to_detect_wall_p50_s", statistics.median(walls),
                unit="s, SIGKILL -> client observes DEAD (adds "
                     "heartbeat-phase + client poll jitter)")
        cli.close()
    finally:
        c.shutdown()


def bench_reconstruction(results, record, scale):
    """``reconstruction_storm``: SIGKILL a worker node mid fan-out and
    measure time-to-all-results vs a failure-free baseline of the same
    workload — the cost of lineage reconstruction re-running the lost
    shards (plus failure detection) instead of raising ObjectLostError.

    Runs TWICE: recompute-only (the headline storm rows), then with
    eager replication on (``reconstruction_storm_replicated``) — lost
    shards are then served from their secondary copies, so recovery is
    failure detection + a pull, not a re-run (target <= 2x failure-free
    vs the ~8x recompute path measured at PR 5).
    """
    _reconstruction_run(results, record, scale, replicated=False)
    _reconstruction_run(results, record, scale, replicated=True)


def _reconstruction_run(results, record, scale, replicated):
    """Best-of-3 over FRESH clusters: the storm tail is bimodal — it
    depends on where the lost shards' re-runs/pulls land relative to the
    survivor's remaining fan-out queue — so a single draw ranges ~1.4x
    to ~3x for the identical recovery path (measured spread of 6
    consecutive idle-host draws: 1.41–2.69 with detection flat at
    ~0.6s).  The min ratio is the recovery path's cost; the spread is
    scheduler interleaving, so more draws estimate the min better."""
    best = None
    for _ in range(3):
        one = _reconstruction_once(scale, replicated)
        if best is None or (one["storm"] / one["base"]
                            < best["storm"] / best["base"]):
            best = one
    _reconstruction_record(results, record, replicated, best)


def _reconstruction_once(scale, replicated):
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster
    # Detection at DEFAULTS: earlier rounds had to force
    # RAY_TPU_GCS_NODE_TIMEOUT_S=1.5 because plain heartbeat silence was
    # the only detector; the suspicion machine (suspect @0.5s + liveness
    # probe) now beats that floor on a stock config.
    env = {"RAY_TPU_GCS_HEARTBEAT_INTERVAL_S": "0.25"}
    if replicated:
        env["RAY_TPU_REPLICATION_MIN_BYTES"] = str(64 * 1024)
    # Sizing: every storm pays an irreducible floor (1.0s strike delay +
    # detection) that has nothing to do with HOW recovery happens, so
    # the failure-free baseline must be of the same order (0.25s/shard,
    # n=32 -> ~3s on the worker CPUs) or the ratio measures the floor,
    # not the recovery path (re-run vs replica pull).
    n = max(8, int(32 * scale))
    c = Cluster(initialize_head=True, head_resources={"num_cpus": 2},
                env=env)
    try:
        for _ in range(2):
            c.add_node(num_cpus=2, resources={"w": 1}, object_store_mb=256)
        c.wait_for_nodes(3)
        c.connect()

        @ray_tpu.remote(num_cpus=1, resources={"w": 0.01}, max_retries=8)
        def shard(i):
            import numpy as _np

            time.sleep(0.25)
            return _np.full(1 << 18, i, _np.int32)  # 1MB, lives on "w"

        def run(kill: bool) -> float:
            t0 = time.perf_counter()
            refs = [shard.remote(i) for i in range(n)]
            if kill:
                time.sleep(1.0)  # let shards seal (and replicate), strike
                victims = [nd for nd in c.nodes
                           if nd is not c.head_node and nd.alive()]
                c.remove_node(victims[0])
                # No replacement node mid-storm: the survivor has the
                # resources to absorb retries/re-runs, and a fresh node's
                # worker spawn (seconds of python+numpy import on a small
                # host) would bury the recovery cost being measured in
                # identical-in-both-variants jitter.
            out = ray_tpu.get(refs, timeout=300)
            dt = time.perf_counter() - t0
            for i, v in enumerate(out):
                assert int(v[0]) == i  # recovery must be CORRECT
            del out
            ray_tpu.free(refs)
            return dt

        run(kill=False)  # warm pools/peers so the baseline is steady-state
        base = run(kill=False)
        storm = run(kill=True)
        # time_to_detect / time_to_recover breakdown input: the GCS
        # records the last-contact -> DEAD latency of the storm's one
        # SIGKILL; what remains of the storm overhead is recovery work.
        from ray_tpu.core.gcs import GcsClient

        cli = GcsClient(c.address)
        try:
            ttd_samples = cli.health_stats()["time_to_detect_s"]
        finally:
            cli.close()
        return {"base": base, "storm": storm,
                "detect": ttd_samples[-1] if ttd_samples else None}
    finally:
        c.shutdown()


def _reconstruction_record(results, record, replicated, best):
    suffix = "_replicated" if replicated else ""
    base, storm, detect = best["base"], best["storm"], best["detect"]
    record(f"reconstruction_baseline{suffix}_s", base, unit="s")
    record(f"reconstruction_storm{suffix}_s", storm, unit="s")
    if detect is not None:
        results[f"reconstruction_storm{suffix}_breakdown"] = {
            "time_to_detect_s": round(detect, 3),
            "time_to_recover_s": round(max(0.0, storm - base - detect), 3),
            "unit": ("storm overhead split: GCS death detection vs "
                     "recovery work (re-run / replica pull + resched)"),
        }
        print(json.dumps(
            {"metric": f"reconstruction_storm{suffix}_breakdown",
             **results[f"reconstruction_storm{suffix}_breakdown"]}),
            flush=True)
    kind = ("lost shards pulled from their eager secondary copies, "
            "zero recompute" if replicated
            else "lost shards re-run from lineage")
    results[f"reconstruction_storm{suffix}_overhead"] = {
        "value": round(storm / max(base, 1e-9), 2),
        "unit": ("x failure-free time-to-all-results (node SIGKILLed "
                 "mid fan-out, best-of-3 fresh-cluster draws — the tail "
                 f"is scheduler-interleaving bimodal, {kind})")}
    print(json.dumps(
        {"metric": f"reconstruction_storm{suffix}_overhead",
         **results[f"reconstruction_storm{suffix}_overhead"]}),
        flush=True)


def bench_chaos(results, record, scale):
    """``mttr_*``: compound-fault soak over a live cluster — alternating
    node kills and GCS restarts against pinned task/actor/put-get
    workloads (``util.chaos_schedule``), recording the median
    fault -> cluster-green -> first-successful-probe recovery time per
    fault kind.  ``soak_invariant_violations`` is the invariant-bank
    verdict for the same run (exactly-once side effects, no lost acked
    work, accounting conservation, refs drained, convergence) — it must
    be 0; a bench run that breaks an invariant is a bug, not a number.
    """
    import statistics
    import tempfile

    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.util import chaos_schedule as cs

    workdir = tempfile.mkdtemp(prefix="bench_chaos_")
    control_file = os.path.join(workdir, "ctrl.json")
    memory_file = os.path.join(workdir, "mem")
    # Explicit timeline rather than a seeded draw: the bench wants a
    # fixed sample count per kind, evenly spaced so each recovery
    # completes (and the probe lands) before the next strike.
    kills = max(2, int(4 * scale))
    events = []
    t = 3.0
    for i in range(2 * kills - 1):
        events.append({"idx": i, "t_s": round(t, 3),
                       "kind": "node_kill" if i % 2 == 0 else "gcs_restart",
                       "slot": (i // 2) % 2, "params": {}})
        t += 6.0
    cluster = Cluster(
        gcs_persist_path=os.path.join(workdir, "gcs_snapshot"),
        chaos_control_file=control_file,
        memory_usage_file=memory_file,
        env={"RAY_TPU_GCS_RECONNECT_TIMEOUT_S": "30"})
    try:
        pin = {"chaos": 0.01}
        for _ in range(2):
            cluster.add_node(num_cpus=2, resources={"chaos": 4})
        cluster.connect()
        cluster.wait_for_nodes()
        workloads = [
            cs.TaskFanoutWorkload(placement_resources=pin),
            cs.ActorMarkerWorkload(os.path.join(workdir, "markers"),
                                   placement_resources=pin),
            cs.PutGetWorkload(placement_resources=pin),
        ]
        runner = cs.ChaosRunner(
            cluster, events, workloads,
            control_file=control_file, memory_file=memory_file,
            log_path=os.path.join(workdir, "events.jsonl"),
            probe_resources=pin)
        report = runner.run()
    finally:
        cluster.shutdown()
    assert report["ok"], f"invariant violations: {report['violations']}"

    def srecord(name, value, unit):  # record() rounds to 0.1s
        results[name] = {"value": round(value, 3), "unit": unit}
        print(json.dumps({"metric": name, **results[name]}), flush=True)

    with runner._lock:
        samples = {k: list(v) for k, v in runner.mttr.items()}
    for kind, row in (("node_kill", "mttr_node_kill_s"),
                      ("gcs_restart", "mttr_gcs_restart_s")):
        vals = samples.get(kind, [])
        assert vals, f"no MTTR samples for {kind}: {report['mttr_s']}"
        srecord(row, statistics.median(vals),
                unit=(f"s, {kind} -> cluster green -> probe task succeeds "
                      f"on the faulted slots, median of {len(vals)} "
                      f"(workloads live throughout)"))
    record("soak_invariant_violations",
           float(len(report["violations"])),
           unit=(f"invariant-bank failures over the MTTR soak "
                 f"({report['events_executed']} faults; bank: converged, "
                 f"acked durable, exactly-once, accounting, refs, "
                 f"metrics, alerts)"))


def bench_overload(results, record, scale):
    """``overload_shed``: sustained 2x-capacity open-loop load against a
    Serve deployment, shed-on (replica reject -> router retry -> shed)
    vs the unbounded-queue baseline — fresh runtime per mode, because
    the backpressure flag must reach spawned replica workers via their
    environment.  The deployment body GIL-spins (not sleeps) so capacity
    is real: extra in-flight requests contend instead of parallelizing.
    Shed-on records goodput (admitted completions / measured capacity),
    admitted-request p99 vs idle p99, and shed rate; the baseline
    records first-half vs second-half admitted latency — the unbounded
    queue's monotonic growth signature."""
    import threading

    import ray_tpu
    import ray_tpu.serve.replica  # noqa: F401 — defines serve_backpressure
    from ray_tpu.core.config import config

    service_s = 0.03
    window_s = max(2.0, 4.0 * scale)
    # open-loop thread cap: sized ABOVE the expected 2x-capacity arrival
    # count (a hit cap starves the loop's tail and understates goodput);
    # overflow is counted, not silent
    max_clients = 1200

    def run_mode(backpressure: bool) -> dict:
        os.environ["RAY_TPU_SERVE_BACKPRESSURE"] = \
            "1" if backpressure else "0"
        config.reload("serve_backpressure")
        ray_tpu.init(num_cpus=max(4, os.cpu_count() or 4))
        from ray_tpu import serve

        @serve.deployment(name="overload_bench", num_replicas=1,
                          max_ongoing_requests=2)
        def spin(req):
            t_end = time.perf_counter() + service_s
            while time.perf_counter() < t_end:
                pass
            return {"ok": True}

        try:
            handle = serve.run(spin.bind(), route_prefix="/overload_bench")
            handle.call(None, timeout=60)  # warm replica + router

            # measured capacity: closed-loop at the admission width
            done = [0]
            cap_window = max(1.0, window_s / 3)
            cap_stop = time.perf_counter() + cap_window

            def closed_loop():
                while time.perf_counter() < cap_stop:
                    try:
                        handle.call(None, timeout=30)
                        done[0] += 1
                    except ray_tpu.RayTpuError:
                        pass

            cthreads = [threading.Thread(target=closed_loop, daemon=True,
                                         name=f"bench-cap-{i}")
                        for i in range(2)]
            t0 = time.perf_counter()
            for t in cthreads:
                t.start()
            for t in cthreads:
                t.join()
            capacity = done[0] / (time.perf_counter() - t0)

            # idle p99 (sequential, uncontended)
            lats = []
            for _ in range(30):
                t1 = time.perf_counter()
                handle.call(None, timeout=30)
                lats.append(time.perf_counter() - t1)
            lats.sort()
            idle_p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))]

            # sustained 2x capacity, open loop (arrivals independent of
            # completions — what makes an unbounded queue actually grow)
            interval = 1.0 / max(2 * capacity, 1.0)
            lock = threading.Lock()
            oks: list = []   # (start_offset_s, latency_s)
            shed = [0]
            errs = [0]
            skipped = [0]
            threads: list = []
            t0 = time.perf_counter()

            def client():
                t1 = time.perf_counter()
                try:
                    handle.call(None, timeout=120)
                    with lock:
                        oks.append((t1 - t0, time.perf_counter() - t1))
                except ray_tpu.BackPressureError:
                    with lock:
                        shed[0] += 1
                except ray_tpu.RayTpuError:
                    with lock:
                        errs[0] += 1

            nxt = t0
            while time.perf_counter() - t0 < window_s:
                now = time.perf_counter()
                if now >= nxt:
                    nxt += interval
                    if len(threads) < max_clients:
                        th = threading.Thread(target=client, daemon=True,
                                              name="bench-ol-client")
                        th.start()
                        threads.append(th)
                    else:
                        skipped[0] += 1
                else:
                    time.sleep(max(0.0, min(interval / 4, nxt - now)))
            sent_window = time.perf_counter() - t0
            for th in threads:
                th.join(timeout=150)
            in_window = [(s, lat) for s, lat in oks if s <= window_s]
            n_ok = len(in_window)
            lat_sorted = sorted(lat for _, lat in in_window)
            p99 = (lat_sorted[min(len(lat_sorted) - 1,
                                  int(len(lat_sorted) * 0.99))]
                   if lat_sorted else float("inf"))
            half = window_s / 2
            first = [lat for s, lat in oks if s < half]
            second = [lat for s, lat in oks if s >= half]
            mean = lambda xs: sum(xs) / len(xs) if xs else float("nan")  # noqa: E731
            return {
                "capacity_rps": capacity,
                "idle_p99_ms": idle_p99 * 1e3,
                "goodput_rps": n_ok / sent_window,
                "goodput_frac_of_capacity":
                    (n_ok / sent_window) / max(capacity, 1e-9),
                "admitted_p99_ms": p99 * 1e3,
                "p99_vs_idle": p99 / max(idle_p99, 1e-9),
                "shed": shed[0], "errors": errs[0],
                "sent": len(threads), "skipped_at_thread_cap": skipped[0],
                "first_half_mean_ms": mean(first) * 1e3,
                "second_half_mean_ms": mean(second) * 1e3,
                "latency_growth":
                    mean(second) / max(mean(first), 1e-9),
            }
        finally:
            from ray_tpu import serve as _serve

            _serve.shutdown()
            ray_tpu.shutdown()

    try:
        on = run_mode(backpressure=True)
        off = run_mode(backpressure=False)
    finally:
        os.environ.pop("RAY_TPU_SERVE_BACKPRESSURE", None)
        config.reload("serve_backpressure")
    results["overload_shed"] = {
        **{k: (round(v, 3) if isinstance(v, float) else v)
           for k, v in on.items()},
        "unit": ("sustained 2x-capacity open-loop load, shedding ON "
                 "(replica max_ongoing_requests reject -> router retry "
                 "budget -> shed); targets: goodput_frac >= 0.8, "
                 "p99_vs_idle <= 5"),
    }
    results["overload_unbounded_baseline"] = {
        **{k: (round(v, 3) if isinstance(v, float) else v)
           for k, v in off.items()},
        "unit": ("same load with RAY_TPU_SERVE_BACKPRESSURE=0 (silent "
                 "queueing): latency_growth > 1 is the unbounded "
                 "queue's monotonically-growing-latency signature"),
    }
    for name in ("overload_shed", "overload_unbounded_baseline"):
        print(json.dumps({"metric": name, **results[name]}), flush=True)


if __name__ == "__main__":
    raise SystemExit(main())
