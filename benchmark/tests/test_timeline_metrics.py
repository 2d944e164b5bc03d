"""The per-layer metrics that read the program's own timeline
(`harness/timeline.py`, one reader each): their values on a small recorded
`timeline.json` checked by hand, a stale file read as nothing, and the
timeline a rehearsal leaves read by the six that need no chip."""

import json
import os

import pytest

from benchmark.harness import registry, timeline
from benchmark.tests.test_rehearsal import last_line, run_cell

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "timeline")
NAMES = ["worker_start_s", "chip_claim_s", "jax_trace_lower_s",
         "jax_backend_compile_s", "train_report_ms", "data_block_wait_ms",
         "data_prefetch_hit_share"]
# the fixture: `fit()` called at 1000 s, the window 1050 s .. 1060 s
OBS = {"config": {"name": "gpt2-medium"}, "traffic": {"name": "streamed"},
       "t_fit": 1000.0, "t_open": 1050.0, "window_s": 10.0,
       "peaks": {"bf16_flops_per_s": 1.0}}


@pytest.fixture
def recorded(monkeypatch):
    monkeypatch.setattr(timeline, "run_dir", lambda obs: DATA)
    return timeline.of(OBS)


@pytest.mark.parametrize("name, value", [
    # the spawn of pid 7, which ran rank 0's loop; not pid 8's or pid 9's
    ("worker_start_s", 6.5),
    # the longest rank's
    ("chip_claim_s", 4.5),
    # trace 1020-1030 (one nested in it counted once) + lower 1030-1034;
    # another process's and the one after the opening left out
    ("jax_trace_lower_s", 14.0),
    # compile 1034-1040 (its cache read inside it) + 1041-1041.5; the one
    # that ends after the window opened left out
    ("jax_backend_compile_s", 6.5),
    # of 0.3, 0.5 and 0.4 ms; the 9 ms one ended before the window opened,
    # the 0.9 ms one after it closed
    ("train_report_ms", 0.4),
    # (0.8 + 1.2) ms over the two blocks of the window; the 50 ms not
    ("data_block_wait_ms", 1.0),
    # 6 of 8 blocks
    ("data_prefetch_hit_share", 75.0),
])
def test_value_on_the_recorded_timeline(recorded, name, value):
    reader = registry.metric(name)
    assert reader.value(recorded) == pytest.approx(value, abs=1e-9)
    assert reader.read(OBS) == pytest.approx(value, abs=1e-9)


def test_every_new_reader_is_in_benchmark_json_and_has_a_file():
    entries = {m["name"]: m for m in registry.benchmark()["per_layer"]}
    assert list(entries)[-7:] == NAMES
    for name in NAMES:
        assert callable(registry.metric(name).read)
        assert callable(registry.metric(name).value)
    assert entries["data_block_wait_ms"]["workloads"] == [
        "gpt2-medium.streamed"]
    assert "workloads" not in entries["worker_start_s"]


def test_a_stale_or_missing_file_or_span_reads_as_nothing(
        recorded, monkeypatch, tmp_path):
    # another run's file: its `train.fit` is not this run's `fit()`
    for t_fit in (990.0, 1000.3, 2000.0):
        stale = dict(OBS, t_fit=t_fit)
        assert timeline.of(stale) is None
        assert [registry.metric(n).read(stale) for n in NAMES] == [None] * 7
    # a rehearsal never gives a metric's value
    rehearsal = dict(OBS, peaks=None)
    assert [registry.metric(n).read(rehearsal) for n in NAMES] == [None] * 7
    # a program that wrote no timeline (the parent of the PR that brought
    # it), and one that wrote an empty one: nothing, and no exception
    monkeypatch.setattr(timeline, "run_dir", lambda obs: str(tmp_path))
    assert [registry.metric(n).read(OBS) for n in NAMES] == [None] * 7
    fit = [r for r in recorded.spans["train.fit"]]
    (tmp_path / "timeline.json").write_text(json.dumps(
        {"spans": fit, "counters": {}, "dropped": 0}))
    assert timeline.of(OBS) is not None
    assert [registry.metric(n).read(OBS) for n in NAMES] == [None] * 7
    (tmp_path / "timeline.json").write_text("{not json")
    assert [registry.metric(n).read(OBS) for n in NAMES] == [None] * 7


def test_run_dir_is_found_by_the_names_obs_carries():
    assert timeline.run_dir(OBS) == os.path.join(
        registry.ROOT, ".scratch", "benchmark", "gpt2-medium.streamed",
        "gpt2-medium.streamed")
    with pytest.raises(SystemExit):
        timeline.run_dir(dict(OBS, traffic={"name": "no_such_mix"}))


def test_covered_seconds_count_nested_spans_once():
    span = lambda start, dur: {"start_us": start, "duration_us": dur}
    assert timeline.covered_s([]) == 0
    assert timeline.covered_s([span(0, 10), span(2, 3), span(8, 4),
                               span(20, 5)]) == pytest.approx(17e-6)


def test_a_rehearsal_leaves_a_timeline_the_readers_can_read():
    """After a traced rehearsal of the streamed cell, the run's own `read`
    list is what `test_rehearsal.py` expects (the new readers stay out of
    it), and the six that need no chip give a number from the
    `timeline.json` that run left."""
    proc = run_cell(registry.ROOT, "--workload", "gpt2-medium.streamed",
                    "--seed", "2147483659", "--seconds", "2", "--trace", "1")
    result = last_line(proc)
    assert result["correct"] is True, proc.stdout[-3000:]
    assert result["read"] == ["data_wait_ms", "lower_compile_s",
                              "report_ms", "spawn_s"]
    obs = {"config": {"name": "gpt2-medium"},
           "traffic": {"name": "streamed"}}
    with open(os.path.join(timeline.run_dir(obs), "timeline.json")) as f:
        doc = json.load(f)
    assert os.path.getsize(f.name) < 1 << 20
    names = {r["name"] for r in doc["spans"]}
    fit = [r for r in doc["spans"] if r["name"] == "train.fit"][0]
    reports = sorted((r for r in doc["spans"] if r["name"] == "train.report"),
                     key=lambda r: r["start_us"])
    # as a run on the chip would: the window opens after the warm-up steps
    obs.update(t_fit=fit["start_us"] / 1e6 - 0.05,
               t_open=reports[4]["start_us"] / 1e6 + 0.001, window_s=2.0,
               peaks={"bf16_flops_per_s": 1.0})
    values = {n: registry.metric(n).read(obs) for n in NAMES}
    assert values.pop("chip_claim_s") is None and \
        "train.chip_claim" not in names           # no chip, no claim
    assert all(isinstance(v, float) and v >= 0 for v in values.values()), \
        values
    assert 0 < values["worker_start_s"] < 60
    assert values["jax_trace_lower_s"] > 0.1      # the step was traced
    assert values["train_report_ms"] < 100
    assert 50 <= values["data_prefetch_hit_share"] <= 100
