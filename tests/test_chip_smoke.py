"""The chip bring-up contract, as far as a CPU can check it: the smoke
command runs end to end at the tiny size, a worker that was promised a chip
and has none stops the trainer, the driver never opens the device, and each
TPU worker is told which chips are its own."""

import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run_smoke(tmp_path, *args):
    # foreign cwd: worker import paths must not depend on the repo root
    return subprocess.run([sys.executable, SMOKE, *args], cwd=tmp_path,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("layout", [(), ("--chips", "4", "--tp", "2")])
def test_tiny_smoke_passes_from_foreign_cwd(tmp_path, layout):
    """One device, and the sharded step (tp2·fsdp2, shard_map'd attention,
    shardings kept through the step) on four virtual ones."""
    proc = _run_smoke(tmp_path, "--tiny", *layout)
    assert proc.returncode == 0, proc.stderr[-2000:]
    verdict = json.loads(proc.stdout.strip().splitlines()[-1])
    assert verdict["ok"] is True
    # --tiny is never mistaken for the real thing
    assert verdict["device"]["platform"] == "cpu"
    assert 'platform="cpu"' in proc.stdout


def test_smoke_without_a_chip_fails_and_prints_no_verdict(tmp_path):
    from ray_tpu.core.worker import count_local_tpu_chips

    if count_local_tpu_chips():
        pytest.skip("this host has a TPU")
    proc = _run_smoke(tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.fixture
def four_chip_node():
    import ray_tpu

    ray_tpu.init(num_cpus=4, num_tpus=4)
    yield ray_tpu
    ray_tpu.shutdown()


def test_worker_granted_a_chip_but_on_cpu_stops_the_trainer(four_chip_node):
    """The bring-up's founding failure: a worker granted ``TPU: 1`` that
    comes up on platform cpu used to train there and report success."""
    from ray_tpu.train import JaxConfig, JaxTrainer, ScalingConfig

    ran = []
    trainer = JaxTrainer(
        lambda: ran.append(1),
        jax_config=JaxConfig(platform="cpu"),  # forces the worker off the chip
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
    )
    with pytest.raises(Exception, match="granted TPU: 1 but jax sees "
                                        "platform 'cpu'"):
        trainer.fit()
    assert not ran


def test_tpu_workers_are_told_their_own_chips(four_chip_node):
    """One process for each chip: a worker that does not take the whole
    host gets the chip indices of its grant and a topology of that size, and
    no CPU pin from its parent."""
    ray_tpu = four_chip_node

    @ray_tpu.remote
    class Holder:
        def env(self):
            return {k: os.environ.get(k) for k in (
                "JAX_PLATFORMS", "TPU_VISIBLE_CHIPS",
                "TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_PROCESS_BOUNDS")}

    assert os.environ["JAX_PLATFORMS"] == "cpu"  # what a worker would inherit
    one, other, pair = (Holder.options(num_tpus=n, num_cpus=0).remote()
                        for n in (1, 1, 2))
    envs = ray_tpu.get([a.env.remote() for a in (one, other, pair)],
                       timeout=60)
    assert [e["JAX_PLATFORMS"] for e in envs] == ["tpu,cpu"] * 3
    assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"1,1,1"}
    # whichever worker registered first took the first actor
    assert sorted((e["TPU_VISIBLE_CHIPS"], e["TPU_CHIPS_PER_PROCESS_BOUNDS"])
                  for e in envs) == [("0", "1,1,1"), ("1", "1,1,1"),
                                     ("2,3", "1,2,1")]


def test_chip_claims_follow_process_liveness(capsys):
    """Claims are aligned groups, last as long as the process that opened
    the chips, and a count no topology carries is refused out loud."""
    from ray_tpu.core.raylet import Raylet

    node = types.SimpleNamespace(
        resources_total={"TPU": 4.0}, _chip_procs={}, _idle={}, _workers={},
        _chip_counts_refused=set())
    claim = lambda n: Raylet._claim_chips(node, n)

    class Proc:
        code = None

        def poll(self):
            return self.code

    def hold(chips):
        proc = Proc()
        node._chip_procs.update(dict.fromkeys(chips, proc))
        return proc

    first = hold(claim(1))
    assert claim(2) == [2, 3]   # aligned past the held chip 0
    hold([2, 3])
    assert claim(1) == [1]
    assert claim(2) is None and claim(4) is None
    first.code = 0              # the process exited: its chip is free again
    assert claim(1) == [0]
    assert claim(3) is None
    assert "no TPU worker can open 3 of this host's 4 chips" \
        in capsys.readouterr().err


def test_driver_never_asks_jax_for_devices(monkeypatch):
    """init(), then a second init() in the same process after jax has been
    imported: counting chips must not open the device in the driver."""
    import jax

    import ray_tpu

    calls = []
    monkeypatch.setattr(jax, "devices",
                        lambda *a, **k: calls.append(a) or [])
    for _ in range(2):
        ray_tpu.init(num_cpus=1)
        ray_tpu.shutdown()
    assert not calls


# ---------------------------------------------------------------------------
# a chip on its way back: the worker waits for it, and a node's shutdown
# returns only when its chip workers are gone


@pytest.fixture
def chip_nodes(tmp_path, monkeypatch):
    """A stand-in /dev/vfio of four chips' nodes (and the container node
    beside them) for `_check_granted_chips`; ``busy[node] = n`` makes that
    node's next n opens answer EBUSY.  Returns (nodes, busy, opened)."""
    import errno

    from ray_tpu.train import backend

    nodes = [str(tmp_path / str(i)) for i in range(4)]
    for path in nodes + [str(tmp_path / "vfio")]:
        open(path, "w").close()
    busy, opened, real_open = {}, [], os.open

    def fake_open(path, flags, *rest):
        if os.path.dirname(str(path)) == str(tmp_path):
            opened.append(str(path))
            if busy.get(str(path), 0) > 0:
                busy[str(path)] -= 1
                raise OSError(errno.EBUSY, "Device or resource busy",
                              str(path))
        return real_open(path, flags, *rest)

    monkeypatch.setattr(os, "open", fake_open)
    monkeypatch.setattr(backend, "_VFIO_NODES", str(tmp_path / "[0-9]*"))
    monkeypatch.setattr(backend, "_CHIP_BUSY_POLL_S", 0.01)
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    return nodes, busy, opened


def _claim(granted):
    """`_check_granted_chips` under a job of its own: (what it raised, the
    job's spans by name, its counters).  Without a chip the claim itself
    ends in "jax sees platform 'cpu'": it was reached."""
    from ray_tpu.train import backend
    from ray_tpu.util import tracing

    with tracing.timeline_span("train.fit", root=True) as job:
        with pytest.raises(RuntimeError) as raised:
            backend._check_granted_chips(granted)
    part = tracing.timeline_take(job.trace_id)
    return (str(raised.value), {r["name"]: r for r in part["spans"]},
            part["counters"])


@pytest.mark.parametrize("visible, granted, probed", [
    ("2,3", 2, [2, 3]),      # part of the host: the grant's own nodes
    (None, 4, [0, 1, 2, 3]),  # the whole host: every node
    ("7", 1, [0, 1, 2, 3]),  # an index no node has: every node
])
def test_granted_worker_waits_for_a_busy_chip_node(
        chip_nodes, monkeypatch, visible, granted, probed):
    """A node that answers EBUSY twice and then opens: the worker tried
    three times, went on to its claim, and the wait is a span of its own
    between the import and the claim, with its tries."""
    nodes, busy, opened = chip_nodes
    if visible is not None:
        monkeypatch.setenv("TPU_VISIBLE_CHIPS", visible)
    last = nodes[probed[-1]]
    busy[last] = 2
    error, spans, counters = _claim(granted)
    assert f"granted TPU: {granted} but jax sees platform 'cpu'" in error
    assert opened == [nodes[i] for i in probed] + [last] * 2
    wait = spans["train.chip_wait"]
    assert wait["status"] == "OK"
    assert wait["attributes"]["tries"] == 2
    assert wait["attributes"]["nodes"] == len(probed)
    assert wait["attributes"]["waited_s"] >= 0.02
    assert counters["train.chip_busy_retries"] == 2
    starts = [spans[name]["start_us"] for name in (
        "train.jax_import", "train.chip_wait", "train.chip_claim")]
    assert starts == sorted(starts)
    # the claim's span still begins where libtpu would open the chips
    assert spans["train.chip_claim"]["start_us"] >= \
        wait["start_us"] + wait["duration_us"]


def test_free_chip_nodes_cost_one_open_each(chip_nodes, monkeypatch):
    nodes, busy, opened = chip_nodes
    monkeypatch.setattr("time.sleep", lambda s: pytest.fail("slept"))
    _, spans, counters = _claim(4)
    assert opened == nodes
    assert spans["train.chip_wait"]["attributes"]["tries"] == 0
    assert counters["train.chip_busy_retries"] == 0


def test_chip_node_busy_past_the_limit_is_named(chip_nodes, monkeypatch):
    from ray_tpu.train import backend

    nodes, busy, opened = chip_nodes
    monkeypatch.setattr(backend, "_CHIP_BUSY_LIMIT_S", 0.05)
    busy[nodes[2]] = 1 << 30
    error, spans, _ = _claim(4)
    assert f"open({nodes[2]}) still answers 'Device or resource busy'" \
        in error and "granted TPU: 4" in error
    assert opened.count(nodes[2]) >= 2 and nodes[3] not in opened
    assert spans["train.chip_wait"]["status"] == "ERROR"
    assert spans["train.chip_wait"]["attributes"]["tries"] >= 1
    assert "train.chip_claim" not in spans      # jax was not asked


@pytest.mark.parametrize("host", ["no_tpu", "accel", "not_ours"])
def test_hosts_without_vfio_chips_do_not_wait(chip_nodes, monkeypatch, host):
    """No node at all, a host whose chips are /dev/accel<N>, and a node
    that refuses for another reason than EBUSY (libtpu's to report): the
    worker goes to its claim as it always did."""
    import errno
    import glob

    from ray_tpu.train import backend

    nodes, busy, opened = chip_nodes
    monkeypatch.setattr("time.sleep", lambda s: pytest.fail("slept"))
    if host == "no_tpu":
        monkeypatch.setattr(backend, "_VFIO_NODES",
                            os.path.join(os.path.dirname(nodes[0]),
                                         "none", "[0-9]*"))
    elif host == "accel":
        real_glob = glob.glob
        monkeypatch.setattr(glob, "glob", lambda pattern: (
            ["/dev/accel0"] if pattern.startswith("/dev/accel")
            else real_glob(pattern)))
    else:
        real_open = os.open

        def denied(path, flags, *rest):
            if str(path) in nodes:
                raise OSError(errno.EACCES, "Permission denied", str(path))
            return real_open(path, flags, *rest)

        monkeypatch.setattr(os, "open", denied)
    error, spans, counters = _claim(4)
    assert "jax sees platform 'cpu'" in error
    assert not opened
    if host == "not_ours":
        assert spans["train.chip_wait"]["attributes"]["tries"] == 0
    else:
        assert "train.chip_wait" not in spans
        assert "train.chip_busy_retries" not in counters


class _SlowToDie(subprocess.Popen):
    """A child that ignores SIGTERM and is gone only ``LINGER_S`` after
    SIGKILL: a worker whose chips' mappings the kernel is still taking
    back."""

    LINGER_S = 0.8

    def __init__(self):
        super().__init__([sys.executable, "-c", (
            "import signal, time; "
            "signal.signal(signal.SIGTERM, signal.SIG_IGN); "
            "print('up', flush=True); time.sleep(60)")],
            stdout=subprocess.PIPE)
        assert self.stdout.readline() == b"up\n"   # the handler is set
        self.killed_at = None

    def kill(self):
        import threading
        import time

        self.killed_at = time.monotonic()
        threading.Timer(self.LINGER_S, super().kill).start()


def _node_of(child, chips):
    """What `Raylet.shutdown` touches of a node whose one worker process
    is ``child``, started on ``chips``."""
    quiet = types.SimpleNamespace(
        unregister_node=lambda node_id: None, send=lambda data: None,
        join=lambda timeout: None)
    return types.SimpleNamespace(
        gcs=quiet, node_id="n", _wake_w=quiet, _thread=quiet,
        _procs=[child], _chip_procs=dict.fromkeys(chips, child))


@pytest.mark.parametrize("holds_chips", [True, False])
def test_shutdown_returns_when_the_chip_workers_are_gone(
        monkeypatch, capsys, holds_chips):
    """`Raylet.shutdown` waits out a killed worker that opened chips, and
    gives one that opened none its grace and no more."""
    import time

    from ray_tpu.core import raylet

    monkeypatch.setattr(raylet, "_WORKER_EXIT_GRACE_S", 0.2)
    child = _SlowToDie()
    node = _node_of(child, (0, 1) if holds_chips else ())
    try:
        start = time.monotonic()
        raylet.Raylet.shutdown(node)
        took = time.monotonic() - start
        assert child.killed_at - start >= 0.2      # SIGTERM had its grace
        if holds_chips:
            assert child.poll() is not None        # gone before the return
            assert took >= 0.2 + _SlowToDie.LINGER_S
        else:
            assert child.poll() is None            # killed, not waited for
            assert took < 0.2 + _SlowToDie.LINGER_S
        assert node._shutdown
        assert not capsys.readouterr().err
    finally:
        child.wait(timeout=10)
        child.stdout.close()


def test_shutdown_says_so_when_a_chip_worker_outlasts_the_limit(
        monkeypatch, capsys):
    from ray_tpu.core import raylet

    monkeypatch.setattr(raylet, "_WORKER_EXIT_GRACE_S", 0.05)
    monkeypatch.setattr(raylet, "_CHIP_RELEASE_LIMIT_S", 0.1)
    child = _SlowToDie()
    node = _node_of(child, (0,))
    try:
        raylet.Raylet.shutdown(node)
        assert child.poll() is None
        err = capsys.readouterr().err
        assert f"worker {child.pid} still holds its TPU chips" in err
        assert err.count("\n") == 1
    finally:
        child.wait(timeout=10)
        child.stdout.close()
