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
