"""The step as the program sees it: every interval between two
`train.report`s of a rank is a `train.step` record of the job timeline
with what the loop's process did in it, and an overrun is a `train.stall`
that names where the loop's thread stood (`ray_tpu/train/session.py`,
`_StepWatch`).  Every pause here is one the test makes: half a second
against steps of 20 ms."""

import gc
import json
import logging
import os
import re
import threading
import time

import pytest

from ray_tpu.train import session as session_mod
from ray_tpu.train.session import REPORT, TrainContext, _TrainSession
from ray_tpu.util import tracing

STEP_ATTRIBUTES = {
    "n", "rank", "report_us", "data_us", "thread_cpu_us", "process_cpu_us",
    "gc_us", "gc_runs", "nivcsw", "majflt", "compiles", "profiled"}
STEP_S = 0.02
PAUSE_S = 0.5


def _run_session(loop, slow_round=None):
    """`loop(report)` on a session's thread inside a job of its own, this
    thread as the trainer that consumes its reports (`slow_round`: the
    report it leaves waiting for PAUSE_S).  Returns the session, the
    reports' metrics and what the job's timeline holds."""
    reports = []
    with tracing.timeline_span("train.fit", root=True) as job:
        session = _TrainSession(lambda: loop(session.report), None,
                                TrainContext(world_rank=3), None)
        session.start()
        while True:
            if len(reports) == slow_round:
                time.sleep(PAUSE_S)
            kind, payload = session.get_next()
            if kind != REPORT:
                break
            reports.append(payload[0])
        session.finish()
    part = tracing.timeline_take(job.trace_id)
    by_name = {}
    for record in part["spans"]:
        by_name.setdefault(record["name"], []).append(record)
    return session, reports, kind, by_name, part["counters"]


def _loop_with(pause, at=12, steps=16):
    """A loop of `steps` reports, 20 ms apart, that calls `pause` once
    before report `at` and reports how long it took."""
    def loop(report):
        took = 0.0
        for i in range(steps):
            time.sleep(STEP_S)
            if i == at and pause is not None:
                t0 = time.perf_counter()
                pause()
                took = time.perf_counter() - t0
            report({"i": i, "pause_s": took})
    return loop


def a_nap_in_a_named_function():
    time.sleep(PAUSE_S)


def _spin():
    until = time.perf_counter() + PAUSE_S
    while time.perf_counter() < until:
        pass


class _SlowToFree:
    """Garbage in a cycle whose finalizer takes 20 ms: a heap that keeps
    the collector for PAUSE_S at no cost in memory."""

    def __init__(self):
        self.me = self

    def __del__(self):
        time.sleep(PAUSE_S / 25)


def _collect_a_slow_heap():
    gc.disable()                # the forced collection finds them, no other
    try:
        for _ in range(25):
            _SlowToFree()
        gc.collect()
    finally:
        gc.enable()


@pytest.fixture(scope="module")
def ray_train():
    import ray_tpu

    ray_tpu.init(num_cpus=4)
    yield ray_tpu
    ray_tpu.shutdown()


def _ten_reports(config):
    import time

    from ray_tpu.train import session

    for i in range(10):
        time.sleep(0.01)
        session.report({"i": i})


def test_a_fit_of_n_reports_leaves_n_minus_1_contiguous_steps(
        ray_train, tmp_path):
    from ray_tpu.train import JaxConfig, JaxTrainer, RunConfig, ScalingConfig

    result = JaxTrainer(
        _ten_reports, jax_config=JaxConfig(platform="cpu"),
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="steps", storage_path=str(tmp_path))).fit()
    assert result.error is None
    with open(os.path.join(result.path, "timeline.json")) as f:
        doc = json.load(f)
    steps = [r for r in doc["spans"] if r["name"] == "train.step"]
    reports = [r for r in doc["spans"] if r["name"] == "train.report"]
    loop = next(r for r in doc["spans"] if r["name"] == "train.loop")
    assert [r["attributes"]["n"] for r in steps] == list(range(1, 10))
    assert doc["counters"]["train.steps"] == 9
    assert doc["counters"]["train.reports"] == len(reports) == 10
    assert "train.stalls" not in doc["counters"]
    for before, step in zip(steps, steps[1:]):
        # each starts where the last ended (to the microsecond's rounding)
        assert abs(before["start_us"] + before["duration_us"]
                   - step["start_us"]) <= 1
    for step, report in zip(steps, reports[1:]):
        assert step["attributes"].keys() == STEP_ATTRIBUTES
        assert step["attributes"]["rank"] == 0
        assert step["parent_id"] == loop["span_id"]
        assert step["pid"] == loop["pid"]
        assert step["duration_us"] >= 10_000
        # the report that closes the step is the one it carries
        assert report["attributes"]["n"] == step["attributes"]["n"]
        assert abs(report["duration_us"]
                   - step["attributes"]["report_us"]) < 10_000
    # the far side of a report: the driver's round of the same number
    rounds = [r for r in doc["spans"] if r["name"] == "train.round"]
    assert [r["attributes"]["n"] for r in rounds] == list(range(11))


@pytest.mark.parametrize("how", ["sleep", "spin", "gc", "slow_trainer"])
def test_one_pause_leaves_exactly_one_stall(how):
    pause = {"sleep": a_nap_in_a_named_function, "spin": _spin,
             "gc": _collect_a_slow_heap, "slow_trainer": None}[how]
    session, reports, kind, by_name, counters = _run_session(
        _loop_with(pause), slow_round=12 if how == "slow_trainer" else None)
    assert kind == session_mod.FINISHED and len(reports) == 16
    assert len(by_name["train.step"]) == counters["train.steps"] == 15
    assert len(by_name["train.stall"]) == counters["train.stalls"] == 1
    stall = by_name["train.stall"][0]
    attrs = stall["attributes"]
    paused_us = 1e6 * (PAUSE_S if how == "slow_trainer"
                       else reports[-1]["pause_s"])
    assert paused_us >= 0.4e6
    assert attrs["over_us"] == pytest.approx(paused_us, rel=0.2)
    assert counters["train.stall_us"] == attrs["over_us"]
    assert attrs.keys() == STEP_ATTRIBUTES | {"median_us", "over_us",
                                              "stack"}
    assert attrs["n"] == 12 and attrs["rank"] == 3
    assert 15_000 < attrs["median_us"] < 80_000
    assert stall["duration_us"] - attrs["median_us"] \
        == pytest.approx(attrs["over_us"], abs=2)
    # it is the step record of the same interval, and more
    step = next(r for r in by_name["train.step"]
                if r["attributes"]["n"] == 12)
    assert (step["start_us"], step["duration_us"]) == (
        stall["start_us"], stall["duration_us"])
    assert attrs["profiled"] is False
    frames = ";".join(attrs["stack"])
    if how == "sleep":
        assert "a_nap_in_a_named_function" in frames
        assert sum(attrs["stack"].values()) == session_mod.STALL_SAMPLES
        assert attrs["thread_cpu_us"] < 0.1 * stall["duration_us"]
        assert attrs["report_us"] < 0.1 * stall["duration_us"]
    elif how == "spin":
        assert "_spin" in frames
        assert attrs["thread_cpu_us"] > 0.6 * paused_us
        assert attrs["process_cpu_us"] >= attrs["thread_cpu_us"]
    elif how == "gc":
        assert attrs["gc_runs"] >= 1
        assert attrs["gc_us"] == pytest.approx(paused_us, rel=0.2)
    else:
        # the loop stood in `report`, waiting to be consumed
        assert "report (session.py" in frames and "wait" in frames
        assert attrs["report_us"] == pytest.approx(paused_us, rel=0.2)


def test_a_steady_loop_leaves_no_stall(caplog):
    with caplog.at_level(logging.WARNING, logger="ray_tpu.train.session"):
        _, _, _, by_name, counters = _run_session(_loop_with(None))
    assert len(by_name["train.step"]) == 15
    assert "train.stall" not in by_name
    assert "train.stalls" not in counters and "train.stall_us" not in counters
    assert not caplog.records


def test_the_first_eight_intervals_never_stall():
    """The pause is in the eighth interval: seven have been seen."""
    _, _, _, by_name, counters = _run_session(
        _loop_with(a_nap_in_a_named_function, at=8, steps=12))
    assert max(r["duration_us"] for r in by_name["train.step"]) > 0.4e6
    assert "train.stall" not in by_name and "train.stalls" not in counters


def test_the_warning_says_what_an_operator_can_act_on(caplog):
    with caplog.at_level(logging.WARNING, logger="ray_tpu.train.session"):
        _, _, _, by_name, _ = _run_session(
            _loop_with(a_nap_in_a_named_function))
    [warning] = [r for r in caplog.records if r.levelno == logging.WARNING]
    text = warning.getMessage()
    assert text == session_mod.stall_text(by_name["train.stall"][0])
    assert text.startswith("train: step 12 took 5")
    assert "; loop thread in " in text
    assert "a_nap_in_a_named_function (test_train_steps.py:" in text
    assert re.search(r"\(8 of 8 samples\); report [\d.]+ ms, data 0, gc 0, "
                     r"thread cpu [\d.]+ ms, process cpu [\d.,]+ ms, "
                     r"\d+ pre-emptions, 0 major faults$", text)
    assert " ms, median " in text


def test_a_profiler_session_marks_its_steps_and_logs_nothing(
        tmp_path, caplog):
    """`jax.profiler.start_trace` before report 12 and `stop_trace` before
    report 14: the steps that held them, and the one between, are
    `profiled`; the pause beside the start is a stall so marked, and no
    warning."""
    import jax

    def loop(report):
        for i in range(17):
            time.sleep(STEP_S)
            if i == 12:
                jax.profiler.start_trace(str(tmp_path))
                time.sleep(PAUSE_S)
            if i == 14:
                jax.profiler.stop_trace()
            report({"i": i})

    with caplog.at_level(logging.WARNING, logger="ray_tpu.train.session"):
        _, _, _, by_name, counters = _run_session(loop)
    profiled = [r["attributes"]["n"] for r in by_name["train.step"]
                if r["attributes"]["profiled"]]
    assert profiled == [12, 13, 14]
    stalls = by_name["train.stall"]
    assert stalls[0]["attributes"]["n"] == 12
    assert all(r["attributes"]["profiled"] for r in stalls)
    assert stalls[0]["attributes"]["over_us"] >= 0.4e6
    assert counters["train.stalls"] == len(stalls)
    assert not [r for r in caplog.records
                if r.name == "ray_tpu.train.session"]
    assert "in a profiler session" in session_mod.stall_text(stalls[0])


def _watchers():
    return [t for t in threading.enumerate() if t.name == "train-step-watch"]


def _watchers_gone():
    """A stopped watcher is woken and returns: give it a moment."""
    deadline = time.time() + 5
    while _watchers() and time.time() < deadline:
        time.sleep(0.01)
    return not _watchers()


def _boom(report):
    report({"i": 0})
    assert len(_watchers()) == 1 and len(gc.callbacks) == _boom.hooks + 1
    raise ValueError("boom in the watched loop")


def _quiet(report):
    report({"i": 0})
    assert len(_watchers()) == 1 and len(gc.callbacks) == _quiet.hooks + 1


@pytest.mark.parametrize("loop, ends", [(_quiet, session_mod.FINISHED),
                                        (_boom, session_mod.ERROR)])
def test_watcher_and_gc_hook_end_with_the_session(loop, ends):
    assert _watchers_gone()     # an earlier test's
    loop.hooks = len(gc.callbacks)
    session, reports, kind, _, _ = _run_session(loop)
    assert kind == ends and len(reports) == 1
    assert _watchers_gone()
    assert len(gc.callbacks) == loop.hooks


def test_outside_a_job_the_session_keeps_no_steps():
    assert _watchers_gone()     # an earlier test's
    held = len(tracing._tl_steps) + len(tracing._tl_lifecycle)
    counted = {job: dict(c) for job, c in tracing._tl_counters.items()}
    hooks = len(gc.callbacks)
    seen = []

    def loop():
        for i in range(3):
            session.report({"i": i})
        seen.append((len(_watchers()), len(gc.callbacks)))

    session = _TrainSession(loop, None, TrainContext(), None)
    session.start()
    while session.get_next()[0] == REPORT:
        pass
    session.finish()
    assert seen == [(0, hooks)]
    assert len(tracing._tl_steps) + len(tracing._tl_lifecycle) == held
    assert tracing._tl_counters == counted
    assert session._steps is None


def test_a_stall_survives_a_ring_that_shed_its_steps(monkeypatch):
    monkeypatch.setattr(tracing, "TIMELINE_STEP_CAP", 8)
    with tracing._tl_lock:      # what earlier jobs of this process left
        tracing._tl_steps.clear()
    _, _, _, by_name, counters = _run_session(
        _loop_with(a_nap_in_a_named_function, at=9, steps=40))
    # the ring holds the newest 8 of 40 `train.report`s and 39 steps
    kept = by_name["train.step"] + by_name["train.report"]
    assert len(kept) == 8
    assert min(r["attributes"]["n"] for r in by_name["train.step"]) > 30
    assert counters["train.steps"] == 39
    [stall] = by_name["train.stall"]
    assert stall["attributes"]["n"] == 9
    assert stall["attributes"]["over_us"] >= 0.4e6


def test_dump_steps_prints_a_files_steps_and_its_stall(tmp_path, capsys,
                                                       monkeypatch):
    from tools import dump_steps

    _, _, _, by_name, counters = _run_session(
        _loop_with(a_nap_in_a_named_function))
    path = tmp_path / "timeline.json"
    path.write_text(json.dumps({
        "spans": [r for records in by_name.values() for r in records],
        "counters": counters, "dropped": 0}))
    monkeypatch.setattr("sys.argv", ["dump_steps.py", str(path)])
    dump_steps.main()
    out = capsys.readouterr().out
    assert "15 train.step" in out and '"train.stalls": 1' in out
    assert "rank 3: steps 1..15, 15 outside a profiler session" in out
    assert "(step 12); loop_cpu_share " in out
    assert session_mod.stall_text(by_name["train.stall"][0]) in out
    assert "8 x " in out and "a_nap_in_a_named_function" in out
