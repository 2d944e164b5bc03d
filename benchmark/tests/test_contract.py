"""`BENCHMARK.json` against the parts of its contract a file can be held
to, and against the files it names: everything it names is found by name."""

import json
import os
import re

import pytest

from benchmark.harness import registry

BENCH = registry.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark"]
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check of 24 cells fits the driver's 43200 s
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    size = os.path.getsize(os.path.join(registry.ROOT, "BENCHMARK.json"))
    assert size <= 64 * 1024


def test_names_and_whys():
    entries = (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
               + BENCH["per_layer"])
    for entry in entries:
        assert NAME.match(entry["name"]), entry["name"]
        for key in ("why", "layer", "source"):
            if key in entry:
                assert 1 <= len(entry[key]) <= 200, (entry["name"], key)
                assert "\n" not in entry[key] and "\t" not in entry[key]
    for kind in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[kind]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))


def test_end_to_end_metrics_are_what_the_issue_fixed():
    names = [m["name"] for m in BENCH["end_to_end"]]
    assert names == ["tokens_per_s", "step_ms_p90", "setup_s"]
    assert not [n for n in names if "loss" in n or "mfu" in n]
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1


def test_every_cell_reports_what_it_must():
    for cell in BENCH["workloads"]:
        end = [m["name"] for m in registry.metrics_of(cell["name"],
                                                      "end_to_end")]
        assert "setup_s" in end and len(end) >= 2
        layer = registry.metrics_of(cell["name"], "per_layer")
        assert layer
        for m in layer:
            # what a per-layer metric moves is reported where it is
            assert m["moves"] in end, (cell["name"], m["name"])


def test_every_configuration_is_used_and_resolves():
    used = {w["config"] for w in BENCH["workloads"]}
    for entry in BENCH["configs"]:
        assert entry["name"] in used
        assert entry["file"].startswith("benchmark/")
        config = registry.config(entry["name"])
        assert config["name"] == entry["name"]
        assert config["reduced"] == entry["reduced"] == []
        assert config["reference"]["loss_tolerance_reason"]
        assert config["reference"]["steps"] >= 2
        registry.family(config)


def test_every_cell_finds_its_files():
    for cell in BENCH["workloads"]:
        traffic = registry.traffic(cell["traffic"])
        assert traffic["why"]
        loop = registry.loop(traffic)
        assert callable(loop.drive) and callable(loop.train_loop)
        assert registry.config(cell["config"])["chips"] == cell["chips"]


def test_every_metric_has_a_reader():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(registry.metric(m["name"]).read)
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_published_widths():
    """openai-community/gpt2-medium and gpt2-xl `config.json`."""
    medium = registry.config("gpt2-medium")
    xl = registry.config("gpt2-xl-fsdp4")
    assert (medium["n_layer"], medium["n_head"], medium["n_embd"]) \
        == (24, 16, 1024)
    assert (xl["n_layer"], xl["n_head"], xl["n_embd"]) == (48, 25, 1600)
    for config in (medium, xl):
        assert config["n_positions"] == 1024
        assert config["vocab_size"] == 50257


def test_rehearsal_sizes_never_leak_into_a_real_run():
    real = registry.config("gpt2-medium")
    tiny = registry.config("gpt2-medium", rehearse=True)
    assert "rehearsal" not in real and "rehearsal" not in tiny
    assert real["n_embd"] == 1024 and tiny["n_embd"] == 64
    # a nested group is laid over, not replaced
    assert tiny["reference"]["steps"] == real["reference"]["steps"]
    assert tiny["reference"]["loss_tolerance"] \
        != real["reference"]["loss_tolerance"]
