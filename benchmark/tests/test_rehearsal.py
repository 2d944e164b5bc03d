"""The harness end to end at the rehearsal sizes on the CPU: every cell's
path on one and on four virtual devices; new files found with no file
edited; the reference check catching a wrong update and a lower precision;
and no way to a result without a chip."""

import hashlib
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from benchmark.harness import registry

ROOT = registry.ROOT


def run_cell(root, *args, rehearse=True, env=None):
    cmd = [sys.executable, os.path.join(root, "benchmark", "run.py"), *args]
    if rehearse:
        cmd.append("--rehearse")
    full_env = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run(cmd, cwd=root, capture_output=True, text=True,
                          timeout=600, env=full_env)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell, trace, devices, read", [
    ("gpt2-medium.resident", 0, 1,
     ["setup_s", "step_ms_p90", "tokens_per_s"]),
    ("gpt2-medium.streamed", 1, 1,
     ["data_wait_ms", "lower_compile_s", "report_ms", "spawn_s"]),
    ("gpt2-xl-fsdp4.resident", 0, 4, ["setup_s", "tokens_per_s"]),
    ("gpt2-xl-fsdp4.resident", 1, 4,
     ["lower_compile_s", "report_ms", "spawn_s"]),
])
def test_cell_rehearses(cell, trace, devices, read):
    proc = run_cell(ROOT, "--workload", cell, "--seed", "2147483659",
                    "--seconds", "2", "--trace", str(trace))
    result = last_line(proc)
    assert "platform=cpu" in proc.stdout
    assert result["correct"] is True, proc.stdout[-3000:]
    assert result["rehearsal"] is True
    assert result["device"] == {"platform": "cpu", "kind": "cpu",
                                "count": devices}
    # never a value: which readers found something to read, by name
    assert result["metrics"] == {}
    assert result["read"] == read
    assert result["attempted"] > 0 and result["failed"] == 0


def test_same_seed_same_inputs():
    losses = []
    for _ in range(2):
        proc = run_cell(ROOT, "--workload", "gpt2-medium.streamed", "--seed",
                        "7", "--seconds", "1", "--trace", "0")
        last_line(proc)
        losses.append([l for l in proc.stdout.splitlines()
                       if l.startswith("losses of the first steps")][0]
                      .split(" (float32")[0])
    assert losses[0] == losses[1]


def test_without_rehearsal_and_without_a_chip_there_is_no_result():
    from ray_tpu.core.worker import count_local_tpu_chips

    if count_local_tpu_chips():
        pytest.skip("this host has a TPU")
    proc = run_cell(ROOT, "--workload", "gpt2-medium.resident", "--seed",
                    "1", "--seconds", "1", "--trace", "0", rehearse=False)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


@pytest.fixture
def copy(tmp_path):
    """BENCHMARK.json and the benchmark's directory alone, as the driver's
    bare directory has them."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


def test_a_bare_directory_gives_no_result(copy):
    proc = run_cell(str(copy), "--workload", "gpt2-medium.resident",
                    "--seed", "1", "--seconds", "1", "--trace", "0",
                    env={"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


def digest(root):
    out = {}
    for base, _, files in os.walk(os.path.join(root, "benchmark")):
        if "__pycache__" in base:
            continue
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def add_entries(root, **lists):
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for key, entries in lists.items():
        bench[key].extend(entries)
    with open(path, "w") as f:
        json.dump(bench, f)


def write(root, relative, text):
    path = os.path.join(root, relative)
    assert not os.path.exists(path)
    with open(path, "w") as f:
        f.write(textwrap.dedent(text))


def derived_config(root, name, **changes):
    config = registry.load_json("benchmark", "configs", "gpt2-medium.json")
    config.update(name=name, **changes)
    write(root, f"benchmark/configs/{name}.json", json.dumps(config))
    return {"name": name, "source": "test", "reduced": [], "why": "test",
            "file": f"benchmark/configs/{name}.json"}


def test_new_files_are_found_and_no_file_is_edited(copy):
    """A configuration, a traffic mix, a cell and a per-layer metric come
    as new files and new entries of BENCHMARK.json."""
    root = str(copy)
    before = digest(root)
    traffic = registry.load_json("benchmark", "traffic", "resident.json")
    traffic.update(name="resident-b8")
    traffic["rehearsal"]["batch"] = 8
    write(root, "benchmark/traffic/resident-b8.json", json.dumps(traffic))
    write(root, "benchmark/metrics/sync_ms.py", '''\
        """Device: median host time waiting for a step's loss."""
        import numpy as np


        def read(obs):
            return 1e3 * float(np.median(obs["spans"]["sync"]))
        ''')
    add_entries(
        root,
        configs=[derived_config(root, "gpt2-three-layers",
                                rehearsal={"n_layer": 3, "n_head": 2,
                                           "n_embd": 64, "n_positions": 128,
                                           "vocab_size": 500,
                                           "padded_vocab_size": 512})],
        workloads=[{"name": "gpt2-three-layers.resident-b8",
                    "config": "gpt2-three-layers", "traffic": "resident-b8",
                    "chips": 1, "why": "test"}],
        per_layer=[{"name": "sync_ms", "unit": "ms", "better": "lower",
                    "source": "program_span", "layer": "Device",
                    "moves": "tokens_per_s",
                    "workloads": ["gpt2-three-layers.resident-b8"]}])
    proc = run_cell(root, "--workload", "gpt2-three-layers.resident-b8",
                    "--seed", "11", "--seconds", "1", "--trace", "1",
                    env={"PYTHONPATH": ROOT})
    result = last_line(proc)
    assert result["correct"] is True, proc.stdout[-3000:]
    assert "sync_ms" in result["read"]
    after = digest(root)
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "benchmark/configs/gpt2-three-layers.json",
        "benchmark/metrics/sync_ms.py",
        "benchmark/traffic/resident-b8.json"]


PERTURBED = {
    # the update applied with the wrong sign: the loss climbs
    "wrong_sign": '''\
        import optax

        from benchmark.families import gpt2


        class Family(gpt2.Family):
            def optimizer(self):
                return optax.chain(super().optimizer(), optax.scale(-1.0))
        ''',
    # three times the learning rate the configuration states
    "wrong_rate": '''\
        from benchmark.families import gpt2
        from benchmark.reference.gpt2 import adamw


        class Family(gpt2.Family):
            def optimizer(self):
                settings = dict(self.config["optimizer"])
                settings["learning_rate"] *= 3
                return adamw(settings)
        ''',
    # 8-bit floats where the configuration states bfloat16
    "low_precision": '''\
        import dataclasses

        from benchmark.families import gpt2


        class Family(gpt2.Family):
            def model_config(self):
                import jax.numpy as jnp

                return dataclasses.replace(
                    super().model_config(),
                    compute_dtype=jnp.dtype("float8_e4m3fn"))
        ''',
}


@pytest.mark.parametrize("fault", sorted(PERTURBED))
def test_the_reference_check_catches(copy, fault):
    """A family that departs from what its configuration states (a new
    file, as any family is) runs, and its run is not `correct`."""
    root = str(copy)
    write(root, f"benchmark/families/gpt2_{fault}.py", PERTURBED[fault])
    add_entries(
        root,
        configs=[derived_config(root, f"gpt2-{fault}",
                                family=f"gpt2_{fault}")],
        workloads=[{"name": f"gpt2-{fault}.resident",
                    "config": f"gpt2-{fault}", "traffic": "resident",
                    "chips": 1, "why": "test"}])
    proc = run_cell(root, "--workload", f"gpt2-{fault}.resident", "--seed",
                    "5", "--seconds", "1", "--trace", "0",
                    env={"PYTHONPATH": ROOT})
    result = last_line(proc)
    assert result["correct"] is False
    assert "NOT CORRECT: loss at step" in proc.stdout
    if fault != "low_precision":
        # the forward pass is right; the first update is not
        assert "NOT CORRECT: loss at step 0" not in proc.stdout
