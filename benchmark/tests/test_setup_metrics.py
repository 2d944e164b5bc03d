"""The six readers of `train.setup` (PR 67: the set-up account the
program writes at the loop's first report, `ray_tpu/train/session.py`):
their values on a small recorded `timeline.json` checked by hand, and a
file from before the record, or a record without an attribute, read as
nothing."""

import json
import os

import pytest

from benchmark.harness import registry, timeline

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "setup")
NAMES = ["step_trace_s", "step_lower_s", "step_compile_s",
         "step_cache_served_share", "other_trace_compile_s", "setup_run_s"]
# the fixture: `fit()` called at 1000 s, the loop entered at 1010 s, its
# first report back at 1048 s, the window 1050 s .. 1060 s
OBS = {"config": {"name": "gpt2-medium"}, "traffic": {"name": "resident"},
       "t_fit": 1000.0, "t_open": 1050.0, "window_s": 10.0,
       "peaks": {"bf16_flops_per_s": 1.0}}


@pytest.fixture
def recorded(monkeypatch):
    monkeypatch.setattr(timeline, "run_dir", lambda obs: DATA)
    return timeline.of(OBS)


@pytest.mark.parametrize("name, value", [
    # rank 0's record (pid 7 ran the first loop), not rank 1's
    ("step_trace_s", 12.0),
    ("step_lower_s", 2.5),
    # compiled for 1 s besides the 4 s the cache took to hand it over
    ("step_compile_s", 5.0),
    ("step_cache_served_share", 100.0),
    # 3 + 1.5 + 5 + 0.5
    ("other_trace_compile_s", 10.0),
    ("setup_run_s", 8.5),
])
def test_value_on_the_recorded_timeline(recorded, name, value):
    reader = registry.metric(name)
    assert reader.value(recorded) == pytest.approx(value, abs=1e-9)
    assert reader.read(OBS) == pytest.approx(value, abs=1e-9)


def test_the_five_times_are_the_records_duration(recorded):
    setup = recorded.set_up("train.setup")[-1]
    times = [registry.metric(name).value(recorded) for name in NAMES
             if name.endswith("_s")]
    assert len(times) == 5
    assert sum(times) == pytest.approx(setup["duration_us"] / 1e6)


def test_each_of_the_six_is_an_entry_with_its_file():
    entries = {m["name"]: m for m in registry.benchmark()["per_layer"]}
    for name in NAMES:
        entry = entries[name]
        assert entry["source"] == "program_span"
        assert entry["moves"] == "setup_s" and "workloads" not in entry
        assert callable(registry.metric(name).read)
        assert callable(registry.metric(name).value)


def _rewritten(tmp_path, change):
    with open(os.path.join(DATA, "timeline.json")) as f:
        doc = json.load(f)
    doc["spans"] = [r for r in (change(r) for r in doc["spans"])
                    if r is not None]
    with open(tmp_path / "timeline.json", "w") as f:
        json.dump(doc, f)
    return str(tmp_path)


def test_a_file_without_the_record_reads_as_nothing(monkeypatch, tmp_path):
    """The parent of the PR that brought `train.setup`: every other span
    is there, the six find nothing and raise nothing."""
    where = _rewritten(
        tmp_path, lambda r: None if r["name"] == "train.setup" else r)
    monkeypatch.setattr(timeline, "run_dir", lambda obs: where)
    assert timeline.of(OBS).named("jax.trace")
    assert [registry.metric(n).read(OBS) for n in NAMES] == [None] * 6
    # and no file at all, a rehearsal, another run's file
    monkeypatch.setattr(timeline, "run_dir", lambda obs: str(tmp_path / "x"))
    assert [registry.metric(n).read(OBS) for n in NAMES] == [None] * 6
    monkeypatch.setattr(timeline, "run_dir", lambda obs: DATA)
    for other in (dict(OBS, peaks=None), dict(OBS, t_fit=990.0)):
        assert [registry.metric(n).read(other) for n in NAMES] == [None] * 6


@pytest.mark.parametrize("gone, silent", [
    ("step_cache", ["step_cache_served_share"]),
    ("run_us", ["setup_run_s"]),
    ("own_us", ["step_trace_s", "step_lower_s", "step_compile_s",
                "other_trace_compile_s"]),
])
def test_a_record_without_an_attribute_silences_its_readers_alone(
        monkeypatch, tmp_path, gone, silent):
    def without(record):
        if record["name"] == "train.setup":
            record["attributes"].pop(gone)
        return record

    where = _rewritten(tmp_path, without)
    monkeypatch.setattr(timeline, "run_dir", lambda obs: where)
    for name in NAMES:
        value = registry.metric(name).read(OBS)
        assert (value is None) == (name in silent), (name, value)
