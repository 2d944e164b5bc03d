"""Cluster-wide continuous profiling + live stack introspection.

Covers the in-process sampling profiler (folded stacks, task/trace/actor
attribution, kill switch), the GCS profile table (bounds, fencing), the
speedscope/collapsed exports, and — on a two-node cluster — the
acceptance paths: ``ray_tpu stack`` returning all-thread stacks from a
live remote actor's worker process, and ``state.profile(duration_s)``
yielding a speedscope-loadable capture whose samples carry task/trace
attribution.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster
from ray_tpu.util import profiling, state, tracing


def _force_flags():
    profiling._live["at"] = -1.0  # take env changes now, not at cache TTL


def _drain_all():
    profiling.drain_samples()


# ----------------------------------------------------------------- units


def busy_probe_fn(stop):
    prev = profiling.set_task_tags(task_id="feedc0de" * 2,
                                   trace_id="ab" * 16,
                                   actor_id="ac" * 8, name="probe")
    try:
        while not stop.is_set():
            sum(i * i for i in range(500))
    finally:
        profiling.reset_task_tags(prev)


def test_sampler_folds_tagged_stacks(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PROFILE", "1")
    monkeypatch.setenv("RAY_TPU_PROFILE_HZ", "97")
    _force_flags()
    assert profiling.ensure_profiler()
    _drain_all()
    stop = threading.Event()
    t = threading.Thread(target=busy_probe_fn, args=(stop,),
                         name="busy-probe", daemon=True)
    t.start()
    # until a sample of the busy thread is there, not for a fixed 0.6 s: on
    # a host whose cores are all taken (six xdist workers compiling) the
    # sampler's thread may not get its first tick in that time
    records, deadline = [], time.monotonic() + 30
    try:
        while time.monotonic() < deadline and not any(
                "busy_probe_fn" in r["stack"] for r in records):
            time.sleep(0.2)
            records += profiling.drain_samples()[0]
    finally:
        stop.set()
        t.join()
    assert records, "sampler produced nothing in 30s at 97Hz"
    tagged = [r for r in records if "busy_probe_fn" in r["stack"]]
    assert tagged, [r["stack"] for r in records]
    rec = tagged[0]
    # attribution rides every record: task, trace, actor, task name
    assert rec["task"] == "feedc0de" * 2
    assert rec["trace"] == "ab" * 16
    assert rec["actor"] == "ac" * 8
    assert rec["name"] == "probe"
    assert rec["thread"] == "busy-probe"
    assert rec["count"] >= 1 and rec["t1"] >= rec["t0"]
    # folded shape: root-first, ;-separated
    assert rec["stack"].split(";")[-1].startswith(("<genexpr>",
                                                   "busy_probe_fn"))


def test_kill_switch_stops_sampling(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PROFILE", "0")
    _force_flags()
    assert not profiling.profiling_enabled()
    profiling.ensure_profiler()
    time.sleep(0.35)  # let an already-in-flight sampler tick finish
    _drain_all()
    time.sleep(0.5)
    records, dropped = profiling.drain_samples()
    assert records == [] and dropped == 0
    monkeypatch.setenv("RAY_TPU_PROFILE", "1")
    _force_flags()
    assert profiling.profiling_enabled()


def test_dump_threads_sees_all_threads():
    stop = threading.Event()
    t = threading.Thread(target=busy_probe_fn, args=(stop,),
                         name="dumpee", daemon=True)
    t.start()
    try:
        time.sleep(0.05)
        dump = profiling.dump_threads(proc="testproc")
    finally:
        stop.set()
        t.join()
    by_name = {d["name"]: d for d in dump}
    assert "dumpee" in by_name and "MainThread" in by_name
    d = by_name["dumpee"]
    assert d["proc"] == "testproc" and d["pid"] == os.getpid()
    assert any("busy_probe_fn" in fr for fr in d["frames"])
    assert d.get("task") == "feedc0de" * 2  # tags ride the dump too
    me = by_name["MainThread"]
    assert any("test_dump_threads_sees_all_threads" in fr
               for fr in me["frames"])
    # the CLI renderer handles the dump shape
    text = profiling.format_stacks(dump)
    assert "dumpee" in text and "busy_probe_fn" in text


SAMPLES = [
    {"thread": "t1", "proc": "worker", "stack": "a (f.py:1);b (f.py:2)",
     "count": 3, "t0": 10.0, "t1": 11.0, "task": "abc"},
    {"thread": "t1", "proc": "worker", "stack": "a (f.py:1);c (f.py:3)",
     "count": 1, "t0": 10.0, "t1": 11.0},
    {"thread": "t2", "proc": "raylet", "stack": "a (f.py:1);b (f.py:2)",
     "count": 2, "t0": 10.0, "t1": 11.0},
]


def test_speedscope_export_shape():
    doc = profiling.to_speedscope(SAMPLES, name="test")
    # speedscope-loadable: schema pointer, shared frame table, one
    # sampled profile whose rows index into it with matching weights
    assert doc["$schema"].endswith("file-format-schema.json")
    prof = doc["profiles"][0]
    assert prof["type"] == "sampled" and prof["unit"] == "none"
    assert len(prof["samples"]) == len(prof["weights"]) == 3
    assert prof["endValue"] == sum(prof["weights"]) == 6
    nframes = len(doc["shared"]["frames"])
    for row in prof["samples"]:
        assert row and all(0 <= i < nframes for i in row)
    json.dumps(doc)  # serializable as-is


def test_collapsed_export_merges_counts():
    text = profiling.to_collapsed(SAMPLES, include_thread=False)
    lines = dict(ln.rsplit(" ", 1) for ln in text.strip().splitlines())
    assert lines["a (f.py:1);b (f.py:2)"] == "5"  # merged across threads
    assert lines["a (f.py:1);c (f.py:3)"] == "1"


def test_summarize_self_vs_inclusive():
    out = profiling.summarize(SAMPLES)
    assert out["total_samples"] == 6
    self_counts = {r["frame"]: r["samples"] for r in out["top_self"]}
    total_counts = {r["frame"]: r["samples"] for r in out["top_total"]}
    assert self_counts["b (f.py:2)"] == 5
    assert "a (f.py:1)" not in self_counts  # never a leaf
    assert total_counts["a (f.py:1)"] == 6  # on every stack
    assert out["by_proc"] == {"worker": 4, "raylet": 2}
    assert out["num_tagged_tasks"] == 1


def test_gcs_profile_table_bounds_and_fencing(monkeypatch):
    from ray_tpu.core.config import config
    from ray_tpu.core.gcs import GcsCore

    core = GcsCore()
    # assign through the config object, not the _Flag: non-live flags are
    # materialized as instance attributes and only __setattr__ re-syncs
    old = config.profile_table_max
    config.profile_table_max = 5
    try:
        recs = [{"stack": f"s{i}", "count": 1, "t0": float(i),
                 "t1": float(i) + 1} for i in range(8)]
        core.add_profile_samples("nodeA", recs, dropped=2)
        stats = core.profile_table_stats()
        assert stats["num_records"] == 5
        # 2 producer drops + 3 cap evictions
        assert stats["num_dropped"] == 5
        assert stats["nodes"] == ["nodeA"]
        # since-filter keeps only windows ending at/after the cut
        # (retained: s3..s7 with t1 = 4..8 -> two at/after 6.5)
        assert len(core.list_profile_samples(since=6.5)) == 2
        # node prefix filter
        assert core.list_profile_samples(node_id="node")
        assert core.list_profile_samples(node_id="zzz") == []
        # a stamped batch from an unknown/fenced incarnation is rejected
        core.add_profile_samples("ghost", recs, incarnation=3)
        assert "ghost" not in core.profile_table_stats()["nodes"]
    finally:
        config.profile_table_max = old
        core.stop()


# ------------------------------------------------------------ two-node


@pytest.fixture(scope="module")
def profiled_cluster():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    os.environ["RAY_TPU_TRACE"] = "1"
    os.environ["RAY_TPU_TRACE_SAMPLE"] = "1.0"
    os.environ["RAY_TPU_PROFILE"] = "1"
    tracing.enable_tracing()
    _force_flags()
    c = Cluster(initialize_head=True, head_resources={"num_cpus": 2},
                env={"RAY_TPU_TRACE": "1", "RAY_TPU_TRACE_SAMPLE": "1.0",
                     "RAY_TPU_PROFILE": "1"})
    c.add_node(num_cpus=2, resources={"remote_res": 4})
    c.wait_for_nodes(2)
    c.connect()
    yield c
    c.shutdown()
    os.environ.pop("RAY_TPU_TRACE_SAMPLE", None)
    os.environ["RAY_TPU_TRACE"] = "0"
    os.environ["RAY_TPU_PROFILE"] = "0"  # back to the suite default
    _force_flags()


@ray_tpu.remote(resources={"remote_res": 1})
class _Spinner:
    def ping(self):
        return os.getpid()

    def spin_marker_method(self, secs):
        t_end = time.time() + secs
        n = 0
        while time.time() < t_end:
            n += sum(i for i in range(400))
        return n

    def spin_stop(self):
        # queued behind a running spin: returning means the spin ended
        return True


def test_remote_actor_stack_dump(profiled_cluster):
    """Acceptance: all-thread stacks from a live remote actor's worker
    process on a 2-node cluster, targeted by actor id, while the actor
    is busy executing — no cooperation from the stuck method needed."""
    a = _Spinner.remote()
    pid = ray_tpu.get(a.ping.remote(), timeout=60)
    ref = a.spin_marker_method.remote(12.0)
    time.sleep(0.5)

    aid = state.list_actors()[0]["actor_id"]
    # retry the dump: the 0.5s sleep usually suffices for the call to
    # dispatch, but a fully-loaded suite host can stretch it a lot
    deadline = time.monotonic() + 30.0
    while True:
        out = state.list_stacks(target=aid[:12], timeout_s=5.0)
        procs = [p for ps in out["nodes"].values() for p in ps]
        assert len(procs) == 1, out
        proc = procs[0]
        spinning = [t for t in proc["threads"]
                    if any("spin_marker_method" in fr
                           for fr in t["frames"])]
        if spinning or time.monotonic() > deadline:
            break
        time.sleep(0.5)
    assert proc["pid"] == pid and proc["actor_id"] == aid
    # every thread of the worker reports, not just the executor
    names = {t["name"] for t in proc["threads"]}
    assert "MainThread" in names and "worker-reader" in names
    assert spinning, proc["threads"]
    # the executing thread is tagged with the in-flight call
    assert spinning[0].get("task") and spinning[0].get("trace")
    assert spinning[0].get("actor") == aid

    # untargeted dump covers both nodes (and the raylet processes)
    full = state.list_stacks(timeout_s=5.0)
    assert len(full["nodes"]) == 2 and not full["missing"]
    kinds = {p["proc"] for ps in full["nodes"].values() for p in ps}
    assert "raylet" in kinds and "worker" in kinds

    # CLI: ray_tpu stack <actor-prefix>
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts", "stack", aid[:12],
         "--address", profiled_cluster.address],
        capture_output=True, text=True, env=env, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "spin_marker_method" in r.stdout
    ray_tpu.get(ref, timeout=60)


def test_profile_capture_speedscope_with_attribution(profiled_cluster):
    """Acceptance: ``state.profile(2.0)`` returns a speedscope-loadable
    flamegraph whose samples carry task/trace attribution."""
    a = _Spinner.remote()
    ray_tpu.get(a.ping.remote(), timeout=60)
    ref = a.spin_marker_method.remote(9.0)
    time.sleep(0.2)
    prof = state.profile(2.0)
    ray_tpu.get(ref, timeout=60)
    assert prof["num_samples"] > 0
    spin = [r for r in prof["samples"]
            if "spin_marker_method" in r["stack"]]
    assert spin, f"{len(prof['samples'])} records, none in the spin"
    assert spin[0].get("task") and spin[0].get("trace"), spin[0]
    # capture window honored: every record overlaps [t0, t0+duration]
    t0, end = prof["t0"], prof["t0"] + prof["duration_s"]
    assert all(r["t1"] >= t0 and r["t0"] <= end for r in prof["samples"])
    # speedscope-loadable document
    doc = prof["speedscope"]
    json.dumps(doc)
    assert doc["$schema"].endswith("file-format-schema.json")
    sampled = doc["profiles"][0]
    assert sampled["samples"] and len(sampled["samples"]) == \
        len(sampled["weights"])
    nframes = len(doc["shared"]["frames"])
    assert all(0 <= i < nframes for row in sampled["samples"]
               for i in row)
    # both nodes contributed (raylets sample themselves too)
    nodes = {r["node"] for r in prof["samples"]}
    assert len(nodes) >= 2, nodes
    # collapsed export round-trips
    assert "spin_marker_method" in prof["collapsed"]


@pytest.mark.slow
def test_profile_summary_and_cli_export(profiled_cluster, tmp_path):
    summary = state.profile_summary()
    assert summary["total_samples"] > 0
    assert summary["top_self"] and summary["table"]["num_records"] > 0
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = tmp_path / "prof.speedscope.json"
    r = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts", "profile", "export",
         "--address", profiled_cluster.address, "--out", str(out)],
        capture_output=True, text=True, env=env, timeout=60)
    assert r.returncode == 0, r.stderr
    doc = json.loads(out.read_text())
    assert doc["profiles"][0]["weights"]
    r = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts", "profile", "summary",
         "--address", profiled_cluster.address],
        capture_output=True, text=True, env=env, timeout=60)
    assert r.returncode == 0 and "samples:" in r.stdout, r.stderr


@pytest.mark.slow
def test_dashboard_stacks_and_profile(profiled_cluster):
    from ray_tpu.dashboard import DashboardHead

    d = DashboardHead(profiled_cluster.address)
    try:
        def get(u):
            with urllib.request.urlopen(d.url + u, timeout=15) as resp:
                return resp.read().decode()

        stacks = json.loads(get("/api/stacks"))
        assert len(stacks["nodes"]) == 2 and not stacks["missing"]
        assert stacks.get("gcs")  # standalone GCS dumps itself too
        prof = json.loads(get("/api/profile"))
        assert prof["total_samples"] > 0 and "top_self" in prof
        ss = json.loads(get("/api/profile?format=speedscope"))
        assert ss["profiles"][0]["weights"]
        collapsed = get("/api/profile?format=collapsed")
        assert collapsed.strip().rsplit(" ", 1)[-1].isdigit()
    finally:
        d.shutdown()


@pytest.mark.slow
def test_gcs_process_profiles_itself(profiled_cluster):
    """The standalone GCS feeds its own sampler output into the table
    under the reserved "gcs" producer key — control-plane CPU is never a
    blind spot."""
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if any(r.get("proc") == "gcs"
               for r in state.list_profile_samples(node_id="gcs")):
            break
        time.sleep(0.5)
    else:
        pytest.fail("no gcs-process samples reached the profile table")
