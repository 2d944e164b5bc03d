"""The four per-layer metrics that read the program's `train.step` records
(`step_stall_share`, `step_interval_max_ms`, `loop_cpu_share`,
`profiler_pause_ms`) on timelines written out here by hand, every value
worked out beside its case."""

import json

import pytest

from benchmark.harness import registry, timeline

NAMES = ["step_stall_share", "step_interval_max_ms", "loop_cpu_share",
         "profiler_pause_ms"]
# `fit()` called at 1000 s, the window 1050 s .. 1100 s
OBS = {"config": {"name": "gpt2-medium"}, "traffic": {"name": "resident"},
       "t_fit": 1000.0, "t_open": 1050.0, "window_s": 50.0,
       "peaks": {"bf16_flops_per_s": 1.0}}
LOOP_PID = 7


def span(name, start_s, seconds, pid=LOOP_PID, **attributes):
    return {"name": name, "start_us": int(1e6 * start_s),
            "duration_us": int(1e6 * seconds), "pid": pid,
            "attributes": attributes}


def doc_of(steps, t0=1050.0):
    """A job whose loop's process made `steps`, (seconds, process cpu in
    us, profiled) each, one after another from `t0` on."""
    spans = [span("train.fit", 1000.2, 120), span("train.loop", 1010, 100)]
    for n, (seconds, cpu_us, profiled) in enumerate(steps, 1):
        spans.append(span("train.step", t0, seconds, n=n,
                          process_cpu_us=cpu_us, profiled=profiled))
        t0 += seconds
    return {"spans": spans, "counters": {}, "dropped": 0}


def values(doc):
    tl = timeline.Timeline(doc, OBS)
    return [registry.metric(name).value(tl) for name in NAMES]


def test_a_clean_window_reads_no_stall_and_its_median_as_its_longest():
    # 20 steps of 0.5 s, each with 10 ms of the process on a core
    assert values(doc_of([(0.5, 10_000, False)] * 20)) == [
        0.0, 500.0, pytest.approx(2.0), None]


def test_one_long_step_is_the_stall_and_the_longest():
    # 49 steps of 0.5 s and one of 3 s: m = 0.5 s, 2.5 s over, of 27.5 s
    steps = [(0.5, 5_000, False)] * 50
    steps[30] = (3.0, 5_000, False)
    stall, longest, cpu, pause = values(doc_of(steps))
    assert stall == pytest.approx(100 * 2.5 / 27.5)
    assert longest == 3000.0
    assert cpu == pytest.approx(100 * 50 * 0.005 / 27.5)
    assert pause is None


def test_an_overrun_short_of_either_limit_is_no_stall():
    # m = 0.02 s: 0.06 s is over 1.5 m but by 40 ms only; m = 0.5 s:
    # 0.7 s is 200 ms over but under 1.5 m
    for m, d in ((0.02, 0.06), (0.5, 0.7)):
        steps = [(m, 0, False)] * 20 + [(d, 0, False)]
        stall, longest, _, _ = values(doc_of(steps))
        assert stall == 0.0 and longest == pytest.approx(1e3 * d)


def test_profiled_steps_leave_three_readers_and_make_the_fourth():
    # 20 clean steps of 0.5 s; the steps that held the profiler's start
    # (1.5 s) and stop (0.8 s), each burning a core: m = 0.5 s over all 22
    steps = [(0.5, 10_000, False)] * 20
    steps[5:5] = [(1.5, 1_500_000, True)]
    steps[9:9] = [(0.8, 800_000, True)]
    stall, longest, cpu, pause = values(doc_of(steps))
    assert (stall, longest) == (0.0, 500.0)
    assert cpu == pytest.approx(2.0)
    assert pause == pytest.approx(1000.0 + 300.0)
    # and a window whose every step is profiled has nothing clean to read
    assert values(doc_of([(0.5, 0, True)] * 4)) == [None, None, None, 0.0]


def test_only_the_loops_process_inside_the_window_counts():
    doc = doc_of([(0.5, 10_000, False)] * 10)
    doc["spans"] += [
        # another rank's process; a warm-up step; one past the close
        span("train.step", 1052, 9.0, pid=8, n=1, process_cpu_us=0,
             profiled=False),
        span("train.step", 1040, 9.5, n=0, process_cpu_us=0,
             profiled=False),
        span("train.step", 1095, 9.0, n=99, process_cpu_us=0,
             profiled=False)]
    assert values(doc) == [0.0, 500.0, pytest.approx(2.0), None]


def test_no_step_record_reads_as_nothing(monkeypatch, tmp_path):
    """The parent of the PR that brought `train.step` writes a timeline
    without it; a rehearsal never gives a value."""
    monkeypatch.setattr(timeline, "run_dir", lambda obs: str(tmp_path))
    assert [registry.metric(n).read(OBS) for n in NAMES] == [None] * 4
    doc = doc_of([])
    assert values(doc) == [None] * 4
    (tmp_path / "timeline.json").write_text(json.dumps(doc))
    assert timeline.of(OBS) is not None
    assert [registry.metric(n).read(OBS) for n in NAMES] == [None] * 4
    (tmp_path / "timeline.json").write_text(json.dumps(
        doc_of([(0.5, 10_000, False)] * 20)))
    assert [registry.metric(n).read(OBS) for n in NAMES] == [
        0.0, 500.0, pytest.approx(2.0), None]
    assert [registry.metric(n).read(dict(OBS, peaks=None))
            for n in NAMES] == [None] * 4


def test_each_reader_has_its_file_and_its_entry_at_the_end_of_per_layer():
    entries = registry.benchmark()["per_layer"]
    # appended after the last entry the benchmark had, in this order (a
    # later PR's entries follow them)
    first = [m["name"] for m in entries].index("loop_bodies_traced_share") + 1
    added = entries[first:first + 4]
    assert [m["name"] for m in added] == NAMES
    for entry, unit in zip(added, ("%", "ms", "%", "ms")):
        assert entry == {"name": entry["name"], "unit": unit,
                         "better": "lower", "source": "program_span",
                         "layer": "Train", "moves": "tokens_per_s"}
        reader = registry.metric(entry["name"])
        assert callable(reader.read) and callable(reader.value)
    cells = [cell["name"] for cell in registry.benchmark()["workloads"]]
    for name in NAMES:     # no `workloads` list: every cell reports them
        assert [m["name"] for cell in cells
                for m in registry.metrics_of(cell, "per_layer")
                ].count(name) == len(cells)
