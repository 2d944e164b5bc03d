"""Request-flow tracing: every hop spanned, cluster-collected, attributed.

Reference behaviors: `python/ray/util/tracing/tracing_helper.py` (task
invocation + in-function spans sharing one trace via propagated span
context), grown here into hop-level spans (inbox/queue/dispatch/exec/
result), a GCS trace table, and critical-path attribution.
"""

import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster
from ray_tpu.core.config import config
from ray_tpu.util import state, trace_analysis, tracing


def _reset_tracing():
    """Return the tracing module to its untraced, bufferless state."""
    tracing.set_flush_target(None)
    tracing.drain_pending()
    tracing._enabled = False
    tracing._trace_dir = None
    with tracing._file_lock:
        tracing._close_file_locked()
    os.environ.pop("RAY_TPU_TRACE", None)


@pytest.fixture
def traced(tmp_path, monkeypatch):
    monkeypatch.setenv("RAY_TPU_TRACE_DIR", str(tmp_path / "traces"))
    monkeypatch.setenv("RAY_TPU_TRACE", "1")
    tracing.enable_tracing(str(tmp_path / "traces"))
    # fresh runtime so workers inherit the trace dir
    ray_tpu.init(num_cpus=2)
    yield str(tmp_path / "traces")
    ray_tpu.shutdown()
    _reset_tracing()


@pytest.fixture
def traced_gcs(monkeypatch):
    """GCS-table-only export (no trace dir): the production shape."""
    monkeypatch.setenv("RAY_TPU_TRACE", "1")
    monkeypatch.setenv("RAY_TPU_TRACE_SAMPLE", "1.0")
    tracing.enable_tracing()
    ray_tpu.init(num_cpus=2)
    yield
    ray_tpu.shutdown()
    _reset_tracing()


def _wait_spans(trace_dir, pred, timeout=15):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        spans = tracing.read_spans(trace_dir)
        if pred(spans):
            return spans
        time.sleep(0.2)
    return tracing.read_spans(trace_dir)


def _trace_id_for(task_name, timeout=15, last=False):
    """Trace id of a task-event row for ``task_name``; ``last=True``
    picks the most recent matching row (e.g. the call AFTER the direct
    channel engaged, not the relayed warm-up)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        rows = [row for row in state.list_tasks()
                if row.get("name") == task_name and row.get("trace_id")]
        if rows:
            rows.sort(key=lambda r: r.get("time", 0))
            return rows[-1 if last else 0]["trace_id"]
        time.sleep(0.2)
    raise AssertionError(f"no traced task-event row for {task_name}")


def _wait_trace(trace_id, pred, timeout=15):
    deadline = time.monotonic() + timeout
    tr = {}
    while time.monotonic() < deadline:
        tr = state.get_trace(trace_id)
        if pred(tr):
            return tr
        time.sleep(0.2)
    return tr


def _hops(tr):
    return {str(s.get("name", "")).split(" ")[0] for s in tr["spans"]}


# ------------------------------------------- spans on the profiler's clock


def test_span_is_a_trace_annotation_on_the_profilers_clock(tmp_path):
    """A span open during a `jax.profiler` session sits, under its own
    name, on `/host:CPU` of the `.xplane.pb` the session writes."""
    import glob

    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.timeline_span("train.fit", root=True) as job:
            with tracing.timeline_span("train.report", n=0):
                jnp.ones(8).sum().block_until_ready()
        with tracing.span("plain.span"):
            pass
    finally:
        jax.profiler.stop_trace()
    tracing.timeline_take(job.trace_id)
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    with open(path, "rb") as f:
        space = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    host = {e.name for plane in space.planes
            if plane.name.startswith("/host:CPU")
            for line in plane.lines for e in line.events}
    assert {"train.fit", "train.report", "plain.span"} <= host


def test_tracing_never_imports_jax():
    """The benchmark's parent and the raylet must stay off jax: a process
    that imports the tracing layer and opens every kind of span ends
    without it."""
    code = (
        "import sys\n"
        "from ray_tpu.util import tracing\n"
        "tracing.enable_tracing()\n"
        "with tracing.span('a') as sp:\n"
        "    with tracing.maybe_span('b'):\n"
        "        pass\n"
        "with tracing.timeline_span('train.fit', root=True) as job:\n"
        "    with tracing.timeline_span('train.report', n=0):\n"
        "        tracing.count('train.reports')\n"
        "    tracing.timeline_hop('raylet.worker_spawn', job.ctx, 0, 1)\n"
        "part = tracing.timeline_take(job.trace_id)\n"
        "assert len(part['spans']) == 3, part\n"
        "assert 'jax' not in sys.modules\n"
        "print('NOJAX')\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], cwd=root,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "NOJAX" in proc.stdout


def _many_ops(x):
    import jax.numpy as jnp

    for _ in range(400):        # long enough to trace to be spanned
        x = jnp.sin(x) + 1.0
    return x


def test_compile_listener_spans_a_first_call_and_not_a_second(caplog):
    """What a process pays JAX's tracer, lowering and compiler becomes
    `jax.trace` / `jax.lower` / `jax.backend_compile` spans of the job; a
    call served from jit's own cache leaves none; a function that
    compiles again after the first `train.report` is named once."""
    import logging

    import jax
    import jax.numpy as jnp

    from ray_tpu.train import backend

    backend._listen_to_jax()
    backend._listen_to_jax()              # once a process
    # the inputs' own little programs compile outside any job
    x, x16, x32 = jnp.ones(8), jnp.ones(16), jnp.ones(32)
    step = jax.jit(_many_ops)
    with tracing.timeline_span("train.fit", root=True) as job:
        step(x).block_until_ready()
        first = tracing.timeline_take(job.trace_id)
        step(x).block_until_ready()
        second = tracing.timeline_take(job.trace_id)
        tracing.count("train.reports")
        with caplog.at_level(logging.WARNING, logger=backend.__name__):
            step(x16).block_until_ready()      # a new shape: compiles
            step(x32).block_until_ready()
        third = tracing.timeline_take(job.trace_id)
    tracing.timeline_take(job.trace_id)
    names = [r["name"] for r in first["spans"]]
    assert {"jax.trace", "jax.lower", "jax.backend_compile"} <= set(names)
    assert names.count("jax.backend_compile") == 1
    compile_span = [r for r in first["spans"]
                    if r["name"] == "jax.backend_compile"][0]
    assert "_many_ops" in compile_span["attributes"]["fun_name"]
    assert compile_span["trace_id"] == job.trace_id
    assert compile_span["parent_id"] == job.span_id
    assert compile_span["duration_us"] > 0
    assert first["counters"]["jax.compiles"] == 1
    assert first["counters"]["jax.traces"] >= 1
    assert second == {"spans": [], "counters": {}, "dropped": 0}
    assert third["counters"]["jax.compiles"] == 2
    warned = [r for r in caplog.records if "compiled again" in r.message]
    assert len(warned) == 1 and "_many_ops" in warned[0].getMessage()
    # outside a job the listener records nothing
    jax.jit(_many_ops)(jnp.ones(4)).block_until_ready()
    assert tracing.timeline_take(job.trace_id)["spans"] == []


def test_job_timeline_with_the_master_switch_on(traced_gcs, tmp_path):
    """With `RAY_TPU_TRACE=1` the generic RPC spans parent under the job's
    trace in the GCS trace table (`ray_tpu trace export` shows the whole
    job), and the file still holds the job's own spans only."""
    from ray_tpu.train import JaxConfig, JaxTrainer, RunConfig, ScalingConfig

    def loop(config):
        from ray_tpu.train import session

        session.report({"step": 0})

    result = JaxTrainer(
        loop, train_loop_config={},
        jax_config=JaxConfig(platform="cpu", devices_per_worker=2),
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="switched_on",
                             storage_path=str(tmp_path))).fit()
    with open(os.path.join(result.path, "timeline.json")) as f:
        doc = json.load(f)
    in_file = {s["name"] for s in doc["spans"]}
    assert {"train.fit", "train.loop", "train.report",
            "raylet.worker_spawn"} <= in_file
    assert not any(n.startswith(("task.", "worker.")) for n in in_file)
    trace_id = [s for s in doc["spans"]
                if s["name"] == "train.fit"][0]["trace_id"]
    want = {"task.submit", "worker.exec", "train.fit", "train.loop",
            "train.report", "raylet.worker_spawn"}
    tr = _wait_trace(trace_id, lambda tr: want <= _hops(tr))
    assert want <= _hops(tr), _hops(tr)
    assert len({s["span_id"] for s in tr["spans"]}) == len(tr["spans"])


# ------------------------------------------------------- legacy two-span


def test_task_spans_share_a_trace(traced):
    @ray_tpu.remote
    def traced_fn(x):
        return x + 1

    assert ray_tpu.get(traced_fn.remote(1), timeout=30) == 2

    spans = _wait_spans(
        traced,
        lambda s: any(x["name"] == "task.run traced_fn" for x in s)
        and any(x["name"] == "task.submit traced_fn" for x in s))
    submit = next(x for x in spans if x["name"] == "task.submit traced_fn")
    run = next(x for x in spans if x["name"] == "task.run traced_fn")
    # one distributed trace: the run span is a CHILD of the submit span
    assert run["trace_id"] == submit["trace_id"]
    assert run["parent_id"] == submit["span_id"]
    assert run["pid"] != submit["pid"]
    assert run["status"] == "OK"
    assert run["proc"] == "worker" and submit["proc"] == "driver"


def test_actor_method_spans_and_error_status(traced):
    @ray_tpu.remote
    class A:
        def ok(self):
            return 1

        def boom(self):
            raise ValueError("nope")

    a = A.remote()
    assert ray_tpu.get(a.ok.remote(), timeout=30) == 1
    with pytest.raises(Exception):
        ray_tpu.get(a.boom.remote(), timeout=30)

    spans = _wait_spans(
        traced, lambda s: any(x["name"] == "task.run A.boom" for x in s))
    ok_run = next(x for x in spans if x["name"] == "task.run A.ok")
    assert ok_run["status"] == "OK"
    boom_run = next(x for x in spans if x["name"] == "task.run A.boom")
    assert boom_run["status"] == "ERROR"


def test_nested_spans_inherit(traced):
    with tracing.span("outer") as outer:
        with tracing.span("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
    spans = tracing.read_spans(traced)
    names = [s["name"] for s in spans]
    assert "outer" in names and "inner" in names


# ------------------------------------------- acceptance: full span tree


def test_sync_actor_call_full_span_tree_and_critical_path(traced_gcs):
    """A traced same-host sync actor call reassembles into ONE span tree
    with >= 6 distinct hop spans whose summed critical path lands within
    20% of the measured end-to-end latency (acceptance criterion).

    A warmed actor call rides the DIRECT worker→worker channel, so the
    expected hop set is the direct topology — the raylet inbox/queue/
    dispatch/result hops must be GONE from the critical path (that they
    vanish, not merely shrink, is the direct-transport acceptance
    criterion), replaced by the two transport hops worker.direct_send /
    worker.direct_result."""
    @ray_tpu.remote
    class A:
        def m(self, x):
            return x + 1

    a = A.remote()
    assert ray_tpu.get(a.m.remote(0), timeout=30) == 1  # warm the path
    assert ray_tpu.get(a.m.remote(0), timeout=30) == 1  # engage direct

    t0 = time.perf_counter()
    assert ray_tpu.get(a.m.remote(1), timeout=30) == 2
    e2e_us = (time.perf_counter() - t0) * 1e6

    want = {"task.submit", "worker.direct_send", "worker.exec",
            "worker.result_push", "worker.direct_result"}
    # Poll for the DIRECT call's trace: its task-event row (direct_done,
    # batched) can land after the relayed warm-ups', so re-pick the
    # newest row until its trace carries the direct hops plus the
    # caller-wakeup span that closes the trace window.
    tr = {}
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        trace_id = _trace_id_for("A.m", last=True)
        tr = state.get_trace(trace_id)
        if (want | {"task.get"}) <= _hops(tr):
            break
        time.sleep(0.2)
    hops = _hops(tr)
    assert want <= hops, hops
    assert len(hops) >= 6
    # the raylet hops left the critical path entirely
    assert not hops & {"raylet.inbox", "raylet.queue", "raylet.dispatch",
                       "raylet.result"}, hops

    # ONE tree: every span shares the trace id, the driver's submit span
    # is the single root, and the worker spans nest under task.run
    assert {s["trace_id"] for s in tr["spans"]} == {trace_id}
    by_name = {}
    for s in tr["spans"]:
        by_name.setdefault(str(s["name"]).split(" ")[0], []).append(s)
    run = by_name["task.run"][0]
    exec_sp = by_name["worker.exec"][0]
    assert exec_sp["parent_id"] == run["span_id"]
    submit = by_name["task.submit"][0]
    assert run["parent_id"] == submit["span_id"]
    assert submit["parent_id"] is None
    assert len(tr["tree"]) == 1 and tr["tree"][0]["name"].startswith(
        "task.submit")

    # critical path: hop self-times sum EXACTLY to the trace window, and
    # the window explains the measured latency to within 20% — with a
    # 300us absolute floor: a DIRECT call's e2e is sub-millisecond, so a
    # pure ratio would demand cross-process time.time() agreement finer
    # than real clock skew
    cp = tr["critical_path"]
    assert sum(cp["by_hop"].values()) == cp["total_us"]
    assert abs(cp["total_us"] - e2e_us) <= max(0.20 * e2e_us, 300.0), (
        cp["total_us"], e2e_us)
    # the waterfall rows carry attribution for every span
    assert {r["hop"] for r in cp["rows"]} >= want


def test_trace_export_chrome_loadable(traced_gcs, tmp_path):
    """state.export_trace writes chrome://tracing-loadable JSON."""
    @ray_tpu.remote
    def expo(x):
        return x * 2

    assert ray_tpu.get(expo.remote(21), timeout=30) == 42
    trace_id = _trace_id_for("expo")
    _wait_trace(trace_id, lambda t: len(t["spans"]) >= 4)

    out = str(tmp_path / "trace.json")
    n = state.export_trace(out, trace_id=trace_id)
    assert n > 0
    with open(out) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    # chrome://tracing essentials: complete events with ts/dur/pid/tid,
    # process_name metadata naming each lane
    xs = [e for e in events if e["ph"] == "X"]
    ms = [e for e in events if e["ph"] == "M"]
    assert xs and ms
    assert all(e["ph"] in ("X", "M") for e in events)
    for e in xs:
        assert {"ts", "dur", "pid", "tid", "name"} <= set(e)
    assert any(e["name"] == "process_name" for e in ms)


def test_serve_route_and_ttft_spans(traced_gcs):
    """Serve handle calls open a serve.route root (replica pick + submit
    parent under it) and streaming responses get a time-to-first-token
    sub-span."""
    from ray_tpu import serve

    serve.start()

    @serve.deployment
    def streamy(req):
        def gen():
            for i in range(3):
                yield i
        return gen()

    h = serve.run(streamy.bind(), name="s", route_prefix="/s")
    gen = h.options(stream=True).remote("x")
    assert [ray_tpu.get(r, timeout=30) for r in gen] == [0, 1, 2]

    deadline = time.monotonic() + 15
    spans = []
    while time.monotonic() < deadline:
        spans = state.list_trace_spans()
        kinds = {str(s["name"]).split(" ")[0] for s in spans}
        if {"serve.route", "serve.ttft"} <= kinds:
            break
        time.sleep(0.2)
    kinds = {str(s["name"]).split(" ")[0] for s in spans}
    assert {"serve.route", "serve.ttft"} <= kinds, kinds
    route = next(s for s in spans
                 if str(s["name"]).startswith("serve.route"))
    submits = [s for s in spans if s.get("parent_id") == route["span_id"]
               and str(s["name"]).startswith("task.submit")]
    assert submits, "task.submit did not parent under serve.route"
    ttft = next(s for s in spans if s["name"] == "serve.ttft")
    assert ttft["trace_id"] == route["trace_id"]


# -------------------------------------------------------------- sampling


def test_head_sampling_deterministic():
    ids = [tracing._new_trace_id() for _ in range(400)]
    # pure function of the id: every process agrees, repeat calls agree
    for tid in ids[:50]:
        assert tracing.trace_sampled(tid, 0.5) == \
            tracing.trace_sampled(tid, 0.5)
    hit = sum(tracing.trace_sampled(t, 0.5) for t in ids)
    assert 100 < hit < 300  # ~50% +- wide slack
    assert all(tracing.trace_sampled(t, 1.0) for t in ids)
    assert not any(tracing.trace_sampled(t, 0.0) for t in ids)
    # monotone: sampled at rate r => sampled at every r' > r
    for tid in ids[:100]:
        if tracing.trace_sampled(tid, 0.1):
            assert tracing.trace_sampled(tid, 0.5)


def test_sampled_out_requests_export_only_errors(monkeypatch):
    """RAY_TPU_TRACE_SAMPLE=0: OK requests export nothing, but an errored
    request always exports its spans (failures are never invisible)."""
    monkeypatch.setenv("RAY_TPU_TRACE", "1")
    monkeypatch.setenv("RAY_TPU_TRACE_SAMPLE", "0.0")
    tracing.enable_tracing()
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote
        def fine():
            return 1

        @ray_tpu.remote
        def busted():
            raise RuntimeError("traced failure")

        assert ray_tpu.get(fine.remote(), timeout=30) == 1
        with pytest.raises(Exception):
            ray_tpu.get(busted.remote(), timeout=30)

        deadline = time.monotonic() + 15
        spans = []
        while time.monotonic() < deadline:
            spans = state.list_trace_spans()
            if any("busted" in str(s.get("name", "")) for s in spans):
                break
            time.sleep(0.2)
        assert spans, "errored request exported no spans"
        assert all(s.get("status") == "ERROR" for s in spans), spans
        assert not any("fine" in str(s.get("name", "")) for s in spans)
    finally:
        ray_tpu.shutdown()
        _reset_tracing()


# --------------------------------------------------- critical-path math


def _mk(name, trace, span_id, parent, start_ms, dur_ms, **kw):
    return {"name": name, "trace_id": trace, "span_id": span_id,
            "parent_id": parent, "start_us": int(start_ms * 1000),
            "duration_us": int(dur_ms * 1000), "status": "OK", **kw}


def test_critical_path_attribution_synthetic():
    """Hand-built span tree: nested children steal their interval from the
    enclosing span, uncovered instants count as (untraced), and the by-hop
    totals sum exactly to the trace window."""
    spans = [
        _mk("task.get", "t", "g", None, 0, 100),
        _mk("raylet.queue q", "t", "q", "g", 10, 20),
        _mk("task.run f", "t", "r", "g", 30, 40),
        _mk("worker.exec", "t", "e", "r", 35, 20),
    ]
    cp = trace_analysis.critical_path(spans)
    assert cp["total_us"] == 100000
    assert sum(cp["by_hop"].values()) == 100000
    by = cp["by_hop"]
    # get owns only what no later-started span covers: 0-10 + 70-100
    assert by["task.get"] == 40000
    assert by["raylet.queue"] == 20000
    # run loses its middle to the nested exec child
    assert by["task.run"] == 20000
    assert by["worker.exec"] == 20000
    assert trace_analysis.UNTRACED not in by

    # a gap no span covers is attributed as (untraced)
    gap = [_mk("a", "t", "a", None, 0, 10),
           _mk("b", "t", "b", "a", 50, 10)]
    cp = trace_analysis.critical_path(gap)
    assert cp["by_hop"][trace_analysis.UNTRACED] == 40000
    assert sum(cp["by_hop"].values()) == cp["total_us"] == 60000


def test_build_tree_orphans_float_as_roots():
    spans = [
        _mk("root", "t", "r", None, 0, 10),
        _mk("child", "t", "c", "r", 1, 5),
        _mk("orphan", "t", "o", "missing-parent", 2, 3),
    ]
    roots = trace_analysis.build_tree(spans)
    names = {n["name"] for n in roots}
    assert names == {"root", "orphan"}  # orphan NOT dropped
    root = next(n for n in roots if n["name"] == "root")
    assert [c["name"] for c in root["children"]] == ["child"]


def test_aggregate_by_hop_table():
    spans = []
    for i in range(10):
        t = f"t{i}"
        spans += [_mk("task.get", t, f"g{i}", None, 0, 10),
                  _mk("task.run f", t, f"r{i}", f"g{i}", 2, 6)]
    agg = trace_analysis.aggregate(spans)
    assert agg["requests"] == 10
    assert agg["errored"] == 0
    assert set(agg["by_hop"]) == {"task.get", "task.run"}
    assert agg["by_hop"]["task.run"]["requests"] == 10
    assert agg["by_hop"]["task.run"]["p50_us"] == 6000
    shares = sum(r["share"] for r in agg["by_hop"].values())
    assert abs(shares - 1.0) < 0.01


# ------------------------------------------------- table + file lifecycle


def test_gcs_trace_table_drop_counter(traced_gcs):
    """The bounded per-job trace table evicts oldest spans and COUNTS the
    evictions (plus any producer-side export-buffer sheds)."""
    old = config.trace_table_max
    config.trace_table_max = 40
    try:
        @ray_tpu.remote
        def burst():
            return 1

        ray_tpu.get([burst.remote() for _ in range(30)], timeout=60)
        deadline = time.monotonic() + 15
        table = {}
        while time.monotonic() < deadline:
            table = state.trace_summary().get("table", {})
            if table.get("num_dropped", 0) > 0:
                break
            time.sleep(0.2)
        assert table.get("num_dropped", 0) > 0, table
        assert table.get("num_spans", 0) <= 40, table
    finally:
        config.trace_table_max = old


def test_trace_file_rotation(tmp_path, monkeypatch):
    """The per-process JSONL export rotates at the size cap (one .1
    generation kept) and read_spans sees both generations."""
    monkeypatch.setenv("RAY_TPU_TRACE_EXPORT", "0")  # file-only
    monkeypatch.setenv("RAY_TPU_TRACE", "1")
    old = config.trace_file_max_mb
    config.trace_file_max_mb = 1
    tracing.enable_tracing(str(tmp_path))
    try:
        pad = "x" * 400
        for i in range(3000):  # ~1.4MB of records: crosses the 1MB cap
            tracing.emit_span(f"filler{i % 7}", tracing._new_trace_id(),
                              None, 0.0, 0.001, pad=pad)
        rotated = [n for n in os.listdir(tmp_path)
                   if n.endswith(".jsonl.1")]
        assert rotated, os.listdir(tmp_path)
        live = str(tmp_path / f"{os.getpid()}.jsonl")
        assert os.path.getsize(live) < 1 << 20
        spans = tracing.read_spans(str(tmp_path))
        assert len(spans) > 2000  # both generations read back
    finally:
        config.trace_file_max_mb = old
        _reset_tracing()


def test_enable_tracing_idempotent(tmp_path, monkeypatch):
    monkeypatch.setenv("RAY_TPU_TRACE_EXPORT", "0")
    monkeypatch.setenv("RAY_TPU_TRACE", "1")
    try:
        d1 = tracing.enable_tracing(str(tmp_path / "a"))
        tracing.emit_span("one", tracing._new_trace_id(), None, 0.0, 0.1)
        handle = tracing._file
        # same dir: keeps the open file; no dir: keeps everything
        assert tracing.enable_tracing(str(tmp_path / "a")) == d1
        assert tracing.enable_tracing() == d1
        assert tracing._file is handle
        tracing.emit_span("two", tracing._new_trace_id(), None, 0.0, 0.1)
        assert {s["name"] for s in tracing.read_spans(d1)} == \
            {"one", "two"}
        # a NEW dir rotates the export target
        d2 = tracing.enable_tracing(str(tmp_path / "b"))
        assert d2 != d1
        tracing.emit_span("three", tracing._new_trace_id(), None, 0.0, 0.1)
        assert {s["name"] for s in tracing.read_spans(d2)} == {"three"}
    finally:
        _reset_tracing()


# ------------------------------------------------------------- two-node


@pytest.fixture(scope="module")
def traced_cluster():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    os.environ["RAY_TPU_TRACE"] = "1"
    os.environ["RAY_TPU_TRACE_SAMPLE"] = "1.0"
    tracing.enable_tracing()
    c = Cluster(initialize_head=True, head_resources={"num_cpus": 2},
                env={"RAY_TPU_TRACE": "1", "RAY_TPU_TRACE_SAMPLE": "1.0"})
    c.add_node(num_cpus=2, resources={"remote_res": 4})
    c.wait_for_nodes(2)
    c.connect()
    yield c
    c.shutdown()
    _reset_tracing()
    os.environ.pop("RAY_TPU_TRACE_SAMPLE", None)


@pytest.fixture(scope="module")
def trace_dashboard(traced_cluster):
    from ray_tpu.dashboard import DashboardHead

    d = DashboardHead(traced_cluster.address)
    yield d
    d.shutdown()


def _http(url: str) -> str:
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.read().decode()


def test_two_node_trace_propagation(traced_cluster):
    """A forwarded task's trace crosses three processes and two nodes:
    the driver's submit, both raylets' hop spans (forward on the gateway,
    inbox/queue on the executor), the data-channel arg pull as a child
    span, and the remote worker's execution spans."""
    blob = b"q" * (2 << 20)  # store-sized: the executor must PULL it
    ref = ray_tpu.put(blob)

    @ray_tpu.remote(resources={"remote_res": 1})
    def far(x):
        return len(x)

    assert ray_tpu.get(far.remote(ref), timeout=60) == len(blob)
    trace_id = _trace_id_for("far", timeout=30)
    tr = _wait_trace(
        trace_id,
        lambda t: {"task.run", "pull.fetch"} <= _hops(t), timeout=30)
    hops = _hops(tr)
    assert "task.run" in hops, hops
    # the gateway raylet forwarded (either directly or via spillback)
    assert "raylet.forward" in hops, hops
    # arg pull shows as a child span, attributed to the data plane
    pulls = [s for s in tr["spans"]
             if str(s["name"]).startswith("pull.fetch")]
    assert pulls, hops
    assert pulls[0]["attributes"].get("bytes", 0) >= len(blob)
    # spans came from more than one node, all in ONE trace
    nodes = {s.get("node") for s in tr["spans"]}
    assert len(nodes) >= 2, nodes
    assert {s["trace_id"] for s in tr["spans"]} == {trace_id}


def test_two_node_actor_call_trace(traced_cluster):
    @ray_tpu.remote(resources={"remote_res": 1})
    class R:
        def m(self):
            return os.getpid()

    r = R.remote()
    assert ray_tpu.get(r.m.remote(), timeout=60)
    trace_id = _trace_id_for("R.m", timeout=30)
    tr = _wait_trace(
        trace_id,
        lambda t: {"task.submit", "task.run", "raylet.dispatch"}
        <= _hops(t), timeout=30)
    by_name = {str(s["name"]).split(" ")[0]: s for s in tr["spans"]}
    submit, run = by_name["task.submit"], by_name["task.run"]
    assert run["trace_id"] == submit["trace_id"]
    assert run["parent_id"] == submit["span_id"]
    assert run["node"] != submit["node"]


def test_trace_cli_export_and_summary(traced_cluster, tmp_path):
    @ray_tpu.remote
    def cli_task():
        return 1

    ray_tpu.get([cli_task.remote() for _ in range(3)], timeout=60)
    _trace_id_for("cli_task", timeout=30)
    out = str(tmp_path / "cli_trace.json")
    env = {**os.environ, "RAY_TPU_TRACE": "0"}  # reader needs no tracing
    r = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts", "trace", "export",
         "--address", traced_cluster.address, "--out", out],
        capture_output=True, text=True, timeout=120, env=env)
    assert r.returncode == 0, r.stderr
    with open(out) as f:
        doc = json.load(f)
    assert doc["traceEvents"], doc

    r = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts", "trace", "summary",
         "--address", traced_cluster.address],
        capture_output=True, text=True, timeout=120, env=env)
    assert r.returncode == 0, r.stderr
    assert "hop" in r.stdout and "task.submit" in r.stdout, r.stdout


def test_dashboard_trace_endpoints(traced_cluster, trace_dashboard):
    @ray_tpu.remote
    def dash_task():
        return 1

    ray_tpu.get(dash_task.remote(), timeout=60)
    trace_id = _trace_id_for("dash_task", timeout=30)
    _wait_trace(trace_id, lambda t: len(t["spans"]) >= 3, timeout=30)

    doc = json.loads(_http(trace_dashboard.url + f"/api/trace/{trace_id}"))
    assert doc["trace_id"] == trace_id
    assert doc["num_spans"] >= 3
    assert doc["tree"] and doc["critical_path"]["total_us"] > 0

    summary = json.loads(_http(trace_dashboard.url + "/api/trace_summary"))
    assert summary["requests"] >= 1
    assert summary["by_hop"]
    assert "num_dropped" in summary["table"]


def test_dashboard_health_series_reach_metrics(traced_cluster,
                                               trace_dashboard):
    """The PR 8 GCS-side health series are scrapeable from /metrics, and
    /api/health exposes health_stats (satellite)."""
    deadline = time.monotonic() + 20
    text = ""
    while time.monotonic() < deadline:
        text = _http(trace_dashboard.url + "/metrics")
        if "ray_tpu_internal_node_drains" in text:
            break
        time.sleep(0.5)
    assert "ray_tpu_internal_node_drains" in text, text[-2000:]

    health = json.loads(_http(trace_dashboard.url + "/api/health"))
    for key in ("suspects_total", "fenced_frames_total",
                "time_to_detect_s", "drains"):
        assert key in health, health


def test_timeline_slices_carry_trace_id(traced_cluster):
    @ray_tpu.remote
    def tl_task():
        return 1

    ray_tpu.get(tl_task.remote(), timeout=60)
    trace_id = _trace_id_for("tl_task", timeout=30)
    deadline = time.monotonic() + 20
    tagged = []
    while time.monotonic() < deadline:
        tl = ray_tpu.timeline()
        tagged = [s for s in tl
                  if s.get("args", {}).get("trace_id") == trace_id]
        if tagged:
            break
        time.sleep(0.25)
    assert tagged, "no timeline slice carried the trace id"
