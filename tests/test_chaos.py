"""Chaos / fault injection: node kills mid-workload, OOM worker killing,
lineage reconstruction under node death, network-fault injection.

Reference behaviors: `python/ray/tests/test_chaos.py` (NodeKillerActor
workloads survive node churn), ObjectRecoveryManager lineage
reconstruction (`object_recovery_manager.cc`), MemoryMonitor +
retriable-FIFO worker killing (`src/ray/common/memory_monitor.h:52`,
`worker_killing_policy_retriable_fifo.cc`).
"""

import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest
from conftest import audit_scope

import ray_tpu
from ray_tpu.cluster_utils import Cluster
from ray_tpu.util import chaos
from ray_tpu.util.chaos import NetworkChaos, NodeKiller

# Every test here spawns real cluster processes — audit for leaked
# raylets/GCS/shm after each one (conftest.clean_host).
pytestmark = pytest.mark.usefixtures("clean_host")


def _wait_until(predicate, timeout=30.0, interval=0.2, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            if predicate():
                return
        except Exception:  # noqa: BLE001 — transient during recovery
            pass
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


@pytest.mark.slow
def test_tasks_survive_node_churn():
    """Retriable tasks all complete while worker nodes are being
    SIGKILLed and replaced under them."""
    c = Cluster(initialize_head=True, head_resources={"num_cpus": 1})
    try:
        for _ in range(2):
            c.add_node(num_cpus=2)
        c.wait_for_nodes(3)
        c.connect()

        @ray_tpu.remote(num_cpus=1, max_retries=8)
        def work(i):
            time.sleep(0.3)
            return i * i

        killer = NodeKiller(c, kill_interval_s=0.8, respawn=True,
                            seed=7, max_kills=3).start()
        try:
            refs = [work.remote(i) for i in range(24)]
            out = ray_tpu.get(refs, timeout=180)
        finally:
            killer.stop()
        assert sorted(out) == sorted(i * i for i in range(24))
        assert killer.killed, "chaos never fired"
    finally:
        c.shutdown()


@pytest.mark.slow
def test_named_actor_survives_node_kill():
    """A restartable named actor fails over when its node is killed
    mid-call-stream (reference: chaos + actor FT suites)."""
    c = Cluster(initialize_head=True, head_resources={"num_cpus": 1})
    try:
        c.add_node(num_cpus=1, resources={"slot": 1})
        c.add_node(num_cpus=1, resources={"slot": 1})
        c.wait_for_nodes(3)
        c.connect()

        @ray_tpu.remote(max_restarts=4, resources={"slot": 0.5})
        class Svc:
            def ping(self):
                import os

                return os.getpid()

        svc = Svc.options(name="chaos_svc").remote()
        pid1 = ray_tpu.get(svc.ping.remote(), timeout=30)
        # find and kill the node hosting the actor (not the head)
        victim = None
        for node in c.nodes[1:]:
            if node.alive():
                victim = node
                break
        c.remove_node(victim)
        deadline = time.time() + 60
        pid2 = None
        while time.time() < deadline:
            try:
                pid2 = ray_tpu.get(svc.ping.remote(), timeout=10)
                break
            except ray_tpu.ActorDiedError:
                time.sleep(0.5)
        assert pid2 is not None
    finally:
        c.shutdown()


def test_reconstruction_two_node():
    """Deterministic lineage reconstruction: kill the SOLE holder of a
    >1MB task result; get() transparently re-runs the creating task on a
    replacement node instead of raising ObjectLostError.  Also asserts
    the observability surface: ray_tpu_internal_reconstruction_* metric
    series reach the metrics KV and RECONSTRUCTING task events reach the
    cluster-wide task-event table."""
    c = Cluster(initialize_head=True, head_resources={"num_cpus": 1})
    try:
        victim = c.add_node(num_cpus=2, resources={"data": 1})
        c.wait_for_nodes(2)
        c.connect()

        @ray_tpu.remote(resources={"data": 0.1})
        def make():
            return np.full(1 << 19, 7, np.int32)  # 2MB, sole copy on "data"

        @ray_tpu.remote(resources={"data": 0.1})
        def probe(x):
            return int(x[123])

        ref = make.remote()
        # confirm the object sealed on the data node WITHOUT pulling it
        # to the head (probe runs next to the data)
        assert ray_tpu.get(probe.remote(ref), timeout=60) == 7

        c.remove_node(victim)  # SIGKILL the only holder
        c.add_node(num_cpus=2, resources={"data": 1})  # replacement

        val = ray_tpu.get(ref, timeout=120)  # reconstructed, not lost
        assert val.shape == (1 << 19,) and int(val[0]) == 7

        # metrics: the reconstruction series reaches the GCS time-series
        # table.  query_metrics force-flushes the raylet's pending points
        # on every call, so this poll converges as soon as the counter is
        # bumped — no fixed sleep racing the background flush cadence.
        from ray_tpu.util.state import query_metrics

        _wait_until(
            lambda: (query_metrics(
                name="ray_tpu_internal_reconstruction_attempts_total")
                or {}).get("count", 0) > 0,
            timeout=30, msg="reconstruction series in the metrics table")
        # task events: RECONSTRUCTING (and the terminal RECONSTRUCTED)
        # are visible through the cluster-wide state API — the raw event
        # log records the transition, and list_tasks surfaces the
        # recovered task by state
        from ray_tpu.util.state import list_tasks, raw_task_events

        _wait_until(
            lambda: {"RECONSTRUCTING", "RECONSTRUCTED"} <= {
                ev.get("state") for ev in raw_task_events()},
            timeout=15, msg="RECONSTRUCTING/RECONSTRUCTED task events")
        _wait_until(
            lambda: any(t.get("name") == "make"
                        for t in list_tasks(state="RECONSTRUCTED")),
            timeout=15, msg="reconstructed task visible via list_tasks")
    finally:
        c.shutdown()


def test_reconstruction_budget_exhausted():
    """With the reconstruction budget zeroed, losing the sole holder still
    raises ObjectLostError — and the message reports the budget/count so
    the failure is diagnosable."""
    c = Cluster(initialize_head=True, head_resources={"num_cpus": 1},
                env={"RAY_TPU_MAX_OBJECT_RECONSTRUCTIONS": "0"})
    try:
        victim = c.add_node(num_cpus=2, resources={"data": 1})
        c.wait_for_nodes(2)
        c.connect()

        @ray_tpu.remote(resources={"data": 0.1})
        def make():
            return np.full(1 << 19, 9, np.int32)

        @ray_tpu.remote(resources={"data": 0.1})
        def probe(x):
            return int(x[0])

        ref = make.remote()
        assert ray_tpu.get(probe.remote(ref), timeout=60) == 9
        c.remove_node(victim)
        with pytest.raises(ray_tpu.ObjectLostError) as ei:
            ray_tpu.get(ref, timeout=60)
        assert "reconstruction budget exhausted" in str(ei.value)
        assert "0 reconstruction(s)" in str(ei.value)
    finally:
        c.shutdown()


def test_lineage_chaos_correctness():
    """Chaos WITH correctness: a lineage-heavy two-stage task graph keeps
    returning the right answers while worker nodes are SIGKILLed and
    replaced under it — every value exact, zero ObjectLostErrors (get()
    would raise one)."""
    c = Cluster(initialize_head=True, head_resources={"num_cpus": 1})
    try:
        for _ in range(2):
            c.add_node(num_cpus=2)
        c.wait_for_nodes(3)
        c.connect()

        @ray_tpu.remote(num_cpus=1, max_retries=16)
        def stage1(i):
            time.sleep(0.2)
            return np.full(60_000, i, np.int32)  # 240KB -> store object

        @ray_tpu.remote(num_cpus=1, max_retries=16)
        def stage2(x):
            time.sleep(0.1)
            return x * 2

        killer = NodeKiller(c, kill_interval_s=0.8, respawn=True,
                            seed=11, max_kills=3).start()
        try:
            mids = [stage1.remote(i) for i in range(14)]
            refs = [stage2.remote(m) for m in mids]
            out = ray_tpu.get(refs, timeout=240)
        finally:
            killer.stop()
        assert killer.killed, "chaos never fired"
        for i, v in enumerate(out):
            assert v.shape == (60_000,)
            assert int(v[0]) == 2 * i and int(v[-1]) == 2 * i
    finally:
        c.shutdown()


def test_data_plane_survives_net_chaos():
    """Seeded network-fault injection (RAY_TPU_CHAOS_NET_*): with 15% of
    data-channel frames dropped on every raylet, cross-node pulls stall,
    rotate, and retry — and still deliver exact bytes."""
    c = Cluster(
        initialize_head=True, head_resources={"num_cpus": 1},
        env={"RAY_TPU_CHAOS_NET_DROP_P": "0.15",
             "RAY_TPU_CHAOS_NET_CHANNELS": "data",
             "RAY_TPU_CHAOS_NET_SEED": "42",
             "RAY_TPU_PULL_RANGE_TIMEOUT_S": "1"})
    try:
        c.add_node(num_cpus=2, resources={"data": 1})
        c.wait_for_nodes(2)
        c.connect()

        @ray_tpu.remote(resources={"data": 0.1})
        def make():
            rng = np.random.default_rng(0)
            return rng.integers(0, 255, 4 << 20, np.uint8)  # 4MB

        ref = make.remote()
        val = ray_tpu.get(ref, timeout=120)
        expect = np.random.default_rng(0).integers(0, 255, 4 << 20, np.uint8)
        assert np.array_equal(val, expect)
    finally:
        c.shutdown()


def test_network_chaos_deterministic():
    """The fault sequence is fully determined by the seed (unit)."""
    a = NetworkChaos(drop_p=0.3, delay_p=0.2, blackhole_p=0.05, seed=123,
                     channels=["peer", "data"])
    b = NetworkChaos(drop_p=0.3, delay_p=0.2, blackhole_p=0.05, seed=123,
                     channels=["peer", "data"])
    seq_a = [a.decide("peer") for _ in range(200)]
    seq_b = [b.decide("peer") for _ in range(200)]
    assert seq_a == seq_b
    assert any(f == "drop" for f in seq_a)
    # channel gating: undeclared channels never fault — and the DEFAULT
    # afflicts only the data channel (peer control frames have no
    # per-frame retry, so faulting them is an explicit opt-in)
    gated = NetworkChaos(drop_p=1.0, seed=1)
    assert gated.decide("peer") is None
    assert gated.decide("data") == "drop"


def test_backoff_policy_deterministic():
    """Unified retry policy: seeded jitter replays; delays grow
    exponentially to the cap (unit)."""
    from ray_tpu.util.retry import BackoffPolicy

    p1 = BackoffPolicy(base_s=0.1, max_s=2.0, multiplier=2.0,
                       jitter=0.2, seed=7)
    p2 = BackoffPolicy(base_s=0.1, max_s=2.0, multiplier=2.0,
                       jitter=0.2, seed=7)
    d1 = [p1.delay(i) for i in range(10)]
    d2 = [p2.delay(i) for i in range(10)]
    assert d1 == d2
    nojit = BackoffPolicy(base_s=0.1, max_s=2.0, multiplier=2.0, jitter=0.0)
    assert nojit.delay(0) == pytest.approx(0.1)
    assert nojit.delay(3) == pytest.approx(0.8)
    assert nojit.delay(50) == pytest.approx(2.0)  # capped
    # every jittered delay stays within +/- jitter of the ideal curve
    for i, d in enumerate(d1):
        ideal = min(2.0, 0.1 * (2.0 ** i))
        assert 0.8 * ideal <= d <= 1.2 * ideal


def test_replicated_object_zero_recompute(tmp_path):
    """Eager availability: with replication on, killing a sealed object's
    producing node costs a pull from the replica — ZERO lineage
    recompute.  Proof is cluster-wide: the creating task's side-effect
    marker shows exactly one run, the reconstruction_attempts metric
    series never appears in the metrics KV, and the replication series
    does."""
    c = Cluster(initialize_head=True, head_resources={"num_cpus": 1},
                env={"RAY_TPU_REPLICATION_MIN_BYTES": str(64 * 1024)})
    try:
        victim = c.add_node(num_cpus=2, resources={"data": 1})
        c.add_node(num_cpus=2, resources={"spare": 1})
        c.wait_for_nodes(3)
        c.connect()
        marker = tmp_path / "runs"

        @ray_tpu.remote(resources={"data": 0.1})
        def make(path):
            with open(path, "a") as f:
                f.write("x")
            return np.full(1 << 19, 7, np.int32)  # 2MB -> store + replica

        ref = make.remote(str(marker))
        from ray_tpu.core.gcs import GcsClient
        from ray_tpu.core.worker import global_worker

        w = global_worker()
        cli = GcsClient(c.address)
        try:
            _wait_until(
                lambda: len(cli.get_object_locations(ref.hex())["nodes"])
                >= 2, timeout=30, msg="secondary copy in the directory")
            loc = cli.get_object_locations(ref.hex())
            assert loc["replicas"], "directory did not mark the replica"
            # The push counter lives on the PRODUCING raylet — assert its
            # metrics flush BEFORE killing it (soft KV survives the node;
            # waiting afterwards races the victim's last 1s flush window,
            # and the survivor's repair can legitimately push 0 copies
            # when every remaining node already holds the bytes).
            _wait_until(
                lambda: any(b"ray_tpu_internal_replication_pushes_total"
                            in k for k in w.kv_keys(b"",
                                                    namespace="metrics")),
                timeout=20, msg="replication metric series in metrics KV")

            c.remove_node(victim)  # SIGKILL the producer / primary holder
            val = ray_tpu.get(ref, timeout=120)  # served from the replica
            assert val.shape == (1 << 19,) and int(val[0]) == 7
            assert marker.read_text().count("x") == 1, "task was re-run"
            # no raylet attempted a recompute: the reconstruction series
            # never reaches the metrics KV
            assert not any(
                b"ray_tpu_internal_reconstruction_attempts_total" in k
                for k in w.kv_keys(b"", namespace="metrics"))
        finally:
            cli.close()
    finally:
        c.shutdown()


def test_re_replication_after_holder_death(tmp_path):
    """After a replica holder dies, a surviving holder restores the
    target copy count (directory back to >= replication_factor nodes).
    Also covers the explicit put(..., _replicate=True) flag (worker-side
    register_stored path) — the object is small enough that the
    auto-threshold alone would not replicate it."""
    c = Cluster(initialize_head=True, head_resources={"num_cpus": 1})
    try:
        c.add_node(num_cpus=2, resources={"data": 1})
        c.add_node(num_cpus=2, resources={"spare": 1})
        c.wait_for_nodes(3)
        c.connect()

        @ray_tpu.remote(resources={"data": 0.1})
        def make():
            return [ray_tpu.put(np.full(1 << 17, 3, np.int32),
                                _replicate=True)]

        (ref,) = ray_tpu.get(make.remote(), timeout=60)
        from ray_tpu.core.gcs import GcsClient

        cli = GcsClient(c.address)
        try:
            _wait_until(
                lambda: len(cli.get_object_locations(ref.hex())["nodes"])
                >= 2, timeout=30, msg="flagged put replicated")
            # kill whichever holder is not the head, then expect repair
            loc = cli.get_object_locations(ref.hex())
            holders = set(loc["nodes"])
            victims = [nd for nd in c.nodes
                       if nd is not c.head_node and nd.node_id in holders]
            assert victims, (holders, [nd.node_id for nd in c.nodes])
            c.remove_node(victims[0])
            _wait_until(
                lambda: len(cli.get_object_locations(ref.hex())["nodes"])
                >= 2, timeout=60,
                msg="copy count restored after holder death")
            val = ray_tpu.get(ref, timeout=60)
            assert int(val[0]) == 3
        finally:
            cli.close()
    finally:
        c.shutdown()


def test_actor_checkpoint_survives_node_death():
    """Checkpoint-restore round trip under chaos: kill the node an actor
    executes on mid call-stream; the restart restores the latest
    __ray_save__ state (no cold start, no call replay)."""
    c = Cluster(initialize_head=True, head_resources={"num_cpus": 1})
    try:
        c.add_node(num_cpus=1, resources={"slot": 1})
        c.add_node(num_cpus=1, resources={"slot": 1})
        c.wait_for_nodes(3)
        c.connect()

        @ray_tpu.remote(max_restarts=4, resources={"slot": 0.5},
                        checkpoint_interval=1)
        class Svc:
            def __init__(self):
                self.n = 0
                self.restored = False

            def incr(self):
                self.n += 1
                return self.n

            def value(self):
                return (self.n, self.restored)

            def __ray_save__(self):
                return self.n

            def __ray_restore__(self, n):
                self.n = n
                self.restored = True

        svc = Svc.remote()
        for i in range(5):
            assert ray_tpu.get(svc.incr.remote(), timeout=30) == i + 1
        time.sleep(1.0)  # let the checkpoint relay + owner-side pull land
        victim = next(nd for nd in c.nodes[1:] if nd.alive())
        c.remove_node(victim)
        deadline = time.time() + 90
        val = None
        while time.time() < deadline:
            try:
                val = ray_tpu.get(svc.value.remote(), timeout=10)
                break
            except (ray_tpu.ActorDiedError, ray_tpu.GetTimeoutError):
                time.sleep(0.5)
        # n == 5 (restored state, incr calls NOT replayed); restored flag
        # proves the warm path ran, not a cold __init__
        assert val == (5, True), val
    finally:
        c.shutdown()


def test_partition_fence_resurrect(tmp_path):
    """The acceptance scenario for suspicion + fencing: partition a
    two-node cluster (SIGSTOP freezes the victim — heartbeats stop,
    probes time out — exactly what a network partition looks like to the
    detector) until the victim is declared dead, heal it, and assert:

      (a) no actor call executed twice (marker-file count — the fenced
          raylet killed its workers before the stale actor instance could
          run anything post-heal);
      (b) fenced-frame rejections observed (the resurrected node's first
          heartbeat carried the dead incarnation);
      (c) the node rejoins under a STRICTLY greater incarnation and
          serves work again."""
    c = Cluster(initialize_head=True, head_resources={"num_cpus": 1},
                env={"RAY_TPU_GCS_NODE_SUSPECT_S": "0.4",
                     "RAY_TPU_GCS_PROBE_TIMEOUT_S": "0.3"})
    try:
        victim = c.add_node(num_cpus=2, resources={"slot": 1, "v": 1})
        c.wait_for_nodes(2)
        c.connect()
        marker = tmp_path / "calls"

        @ray_tpu.remote(max_restarts=2, resources={"slot": 0.5})
        class Svc:
            def bump(self, path):
                with open(path, "a") as f:
                    f.write("x")
                return True

        svc = Svc.remote()
        for _ in range(3):
            assert ray_tpu.get(svc.bump.remote(str(marker)), timeout=30)
        assert marker.read_text().count("x") == 3

        # restart target joins before the strike, so the actor can fail
        # over while the victim is partitioned
        c.add_node(num_cpus=2, resources={"slot": 1})
        c.wait_for_nodes(3)

        from ray_tpu.core.gcs import GcsClient

        cli = GcsClient(c.address)
        try:
            old_inc = cli.get_node(victim.node_id)["incarnation"]
            t0 = time.monotonic()
            c.pause_node(victim)  # the "partition"
            _wait_until(
                lambda: not cli.get_node(victim.node_id)["alive"],
                timeout=10, msg="victim declared dead")
            assert time.monotonic() - t0 < 3.5, \
                "suspicion+probe should beat the 3s-class heartbeat floor"

            # while partitioned: calls fail over to the restarted instance
            deadline = time.time() + 60
            served = 0
            while served < 3 and time.time() < deadline:
                try:
                    if ray_tpu.get(svc.bump.remote(str(marker)),
                                   timeout=10):
                        served += 1
                except (ray_tpu.ActorDiedError, ray_tpu.GetTimeoutError):
                    time.sleep(0.3)
            assert served == 3, "actor never failed over"

            c.resume_node(victim)  # heal the partition
            _wait_until(
                lambda: (cli.get_node(victim.node_id) or {}).get("alive")
                and cli.get_node(victim.node_id)["incarnation"] > old_inc,
                timeout=30, msg="victim rejoined under a new incarnation")

            # (a) every call executed exactly once
            time.sleep(1.0)  # grace: any stale double-execution would land
            assert marker.read_text().count("x") == 6, \
                "an actor call executed twice across the partition"
            # (b) the stale incarnation was fenced on the way back in
            hs = cli.health_stats()
            assert hs["fenced_frames_total"] >= 1
            assert hs["deaths_detected_total"] >= 1
            # (c) the resurrected node serves work again
            @ray_tpu.remote(resources={"v": 0.5})
            def on_victim():
                return "ok"

            assert ray_tpu.get(on_victim.remote(), timeout=60) == "ok"
        finally:
            cli.close()
    finally:
        c.shutdown()


def test_asymmetric_partition_heal_data_channel(tmp_path):
    """Scriptable asymmetric partition (NetworkChaos control file): the
    holder stops serving data-channel requests from everyone (inbound
    blackhole), a cross-node get() stalls on the pull watchdog — then the
    driver heals the partition by rewriting the file and the same get()
    completes with exact bytes."""
    import json as _json

    ctl = tmp_path / "partition.json"
    c = Cluster(
        initialize_head=True, head_resources={"num_cpus": 1},
        env={"RAY_TPU_CHAOS_NET_PARTITION_FILE": str(ctl),
             "RAY_TPU_PULL_RANGE_TIMEOUT_S": "1"})
    try:
        c.add_node(num_cpus=2, resources={"data": 1})
        c.wait_for_nodes(2)
        c.connect()

        @ray_tpu.remote(resources={"data": 0.1})
        def make():
            rng = np.random.default_rng(3)
            return rng.integers(0, 255, 4 << 20, np.uint8)  # 4MB

        @ray_tpu.remote(resources={"data": 0.1})
        def probe(x):
            return int(x[0])

        ref = make.remote()
        # confirm the seal WITHOUT pulling the bytes to the driver (the
        # probe runs next to the data) — a local prefetch would dodge the
        # partition entirely
        expect = np.random.default_rng(3).integers(0, 255, 4 << 20,
                                                   np.uint8)
        assert ray_tpu.get(probe.remote(ref), timeout=60) == int(expect[0])
        # partition: every process drops inbound data-channel requests
        ctl.write_text(_json.dumps({"partitions": {"*": "in"}}))
        time.sleep(0.1)
        with pytest.raises(ray_tpu.GetTimeoutError):
            ray_tpu.get(ref, timeout=3.0)
        # heal and the SAME pull path recovers on its own
        ctl.write_text(_json.dumps({"partitions": {}}))
        val = ray_tpu.get(ref, timeout=120)
        assert np.array_equal(val, expect)
    finally:
        c.shutdown()


@pytest.mark.slow
def test_oom_killer_retriable_fifo(tmp_path):
    """With the memory monitor reading a test-seam usage file, crossing
    the threshold kills the most-recently-started retriable worker; the
    task retries and completes once pressure clears."""
    usage = tmp_path / "usage"
    usage.write_text("0.1")
    c = Cluster(initialize_head=True, head_resources={"num_cpus": 2},
                env={"RAY_TPU_MEMORY_MONITOR_INTERVAL_S": "0.1",
                     "RAY_TPU_MEMORY_USAGE_THRESHOLD": "0.9",
                     "RAY_TPU_MEMORY_USAGE_FILE": str(usage)})
    try:
        c.wait_for_nodes(1)
        c.connect()
        marker = tmp_path / "attempts"

        @ray_tpu.remote(num_cpus=1, max_retries=4)
        def hog(path):
            with open(path, "a") as f:
                f.write("x")
            time.sleep(3.0)
            return "done"

        ref = hog.remote(str(marker))
        # let the task start, then simulate memory pressure
        deadline = time.time() + 30
        while time.time() < deadline and not marker.exists():
            time.sleep(0.05)
        assert marker.exists()
        usage.write_text("0.99")
        time.sleep(0.6)   # monitor fires, kills the worker
        usage.write_text("0.1")  # pressure clears; retry succeeds
        assert ray_tpu.get(ref, timeout=60) == "done"
        assert marker.read_text().count("x") >= 2  # it really was killed
    finally:
        c.shutdown()


# -- the audit itself: whose strays it counts ---------------------------------

def _orphaned_stray(env):
    """-> the pid of a process that reads as a runtime worker to the audit
    (``ray_tpu.core.worker_main`` an element of its argv) and only sleeps,
    started under ``env`` by a parent that is gone: re-parented, as a worker
    whose raylet died."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import subprocess, sys\n"
         "quiet = subprocess.DEVNULL\n"
         "print(subprocess.Popen([sys.executable, '-c', "
         "'import time; time.sleep(120)', 'ray_tpu.core.worker_main'], "
         "stdin=quiet, stdout=quiet, stderr=quiet).pid)"],
        env=env, capture_output=True, text=True, timeout=60, check=True)
    return int(out.stdout)


@pytest.mark.parametrize("whose", ["its_own", "a_neighbours"])
def test_the_audit_counts_the_strays_of_its_own_scope(whose, monkeypatch):
    """Two xdist workers on one host: an orphan (and the segment named by
    it) started under THIS worker's scope fails this worker's audit; one
    started under a neighbour's is the neighbour's to report, and counts
    here only where no scope is given, as it did before there was one."""
    scope = audit_scope() or {"PYTEST_XDIST_WORKER": "gw0",
                              "PYTEST_XDIST_TESTRUNUID": "a-run-alone"}
    started = dict(scope) if whose == "its_own" else dict(
        scope, PYTEST_XDIST_WORKER=scope["PYTEST_XDIST_WORKER"] + "-next")
    baseline = chaos.snapshot_host(scope)
    pid = _orphaned_stray({**os.environ, **started})
    segment = f"/dev/shm/rt_store_{pid}_a5d17e"
    try:
        open(segment, "wb").close()
        names = f"pid {pid} \\(ray_tpu.core.worker_main\\).*" \
            + os.path.basename(segment)
        if whose == "its_own":
            with pytest.raises(chaos.HostLeakError, match=names) as caught:
                chaos.assert_clean_host(baseline, grace_s=0.5)
            # and nothing of a neighbour's at work beside it
            assert str(caught.value).count("pid ") == 1
        else:
            chaos.assert_clean_host(baseline, grace_s=0.5)
            with pytest.raises(chaos.HostLeakError, match=names):
                chaos.assert_clean_host(dict(baseline, scope=None),
                                        grace_s=0.5)
        # a segment whose maker is gone is a leak, whoever made it (read
        # from a listing of this test's own: on a shared host the next
        # driver to start sweeps a dead maker's file, `_gc_stale_stores`)
        os.kill(pid, signal.SIGKILL)
        deadline = time.monotonic() + 10
        while os.path.exists(f"/proc/{pid}") and time.monotonic() < deadline:
            time.sleep(0.05)
        monkeypatch.setattr(chaos.os, "listdir",
                            lambda path: [os.path.basename(segment)])
        assert chaos._shm_segments(scope) == [os.path.basename(segment)]
        monkeypatch.undo()
    finally:
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        if os.path.exists(segment):
            os.unlink(segment)
