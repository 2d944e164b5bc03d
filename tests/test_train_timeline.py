"""The Train job's own timeline: every `fit()` leaves `timeline.json` in
its run directory, whatever `RAY_TPU_TRACE` says: `train.fit` at the root
and, beneath it through parent ids across the driver, the raylet and the
workers, what the job spent its wall time on (README, "Tracing")."""

import functools
import json
import os

import numpy as np
import pytest

from ray_tpu.util import trace_analysis, tracing

SETUP_SPANS = ("train.placement", "train.workers_up", "raylet.worker_spawn",
               "train.jax_distributed_init", "train.jax_import",
               "train.chip_wait", "train.chip_claim", "train.start_session")


@pytest.fixture(scope="module")
def ray_train():
    import ray_tpu

    ray_tpu.init(num_cpus=8)
    yield ray_tpu
    ray_tpu.shutdown()


def _block(index):
    return {"x": np.full((8, 4), index, np.int32)}


def _two_report_loop(config):
    """Draws every batch of its shard; reports after the second batch
    (with a checkpoint) and after the last."""
    import jax

    from ray_tpu.train import session

    double = jax.jit(lambda x: (x * 2).sum())
    shard = session.get_dataset_shard("train")
    total, batches = 0.0, 0
    for batch in shard.iter_jax_batches(batch_size=4):
        total += float(double(batch["x"]))
        batches += 1
        if batches == 2:
            session.report(
                {"batches": batches},
                checkpoint=session.Checkpoint.from_dict({"batches": 2}))
    session.report({"batches": batches, "total": total})


def _fit(tmp_path, loop, name, workers=1, config=None, max_failures=0,
         datasets=True):
    from ray_tpu.data.dataset import Dataset
    from ray_tpu.train import (
        FailureConfig,
        JaxConfig,
        JaxTrainer,
        RunConfig,
        ScalingConfig,
    )

    ds = Dataset.from_read_fns(
        [functools.partial(_block, i) for i in range(4)])
    return JaxTrainer(
        loop, train_loop_config=config or {},
        jax_config=JaxConfig(platform="cpu", devices_per_worker=2),
        scaling_config=ScalingConfig(num_workers=workers),
        datasets={"train": ds} if datasets else None,
        run_config=RunConfig(
            name=name, storage_path=str(tmp_path),
            failure_config=FailureConfig(max_failures=max_failures)))


def _timeline(path):
    with open(os.path.join(path, "timeline.json")) as f:
        doc = json.load(f)
    by_name = {}
    for record in doc["spans"]:
        by_name.setdefault(record["name"], []).append(record)
    return doc, by_name


@pytest.fixture(scope="module")
def two_report_job(ray_train, tmp_path_factory):
    """One two-worker, two-report fit with a small `from_read_fns` shard
    each; the tests below read the timeline it left."""
    result = _fit(tmp_path_factory.mktemp("job"), _two_report_loop,
                  "two_reports", workers=2).fit()
    assert result.error is None
    return result, *_timeline(result.path)


def test_fit_leaves_a_timeline_with_every_span_of_the_table(two_report_job):
    result, doc, by_name = two_report_job
    assert set(doc) == {"spans", "counters", "dropped"}
    # every span of ISSUE 25's table that can occur without a chip
    assert set(by_name) >= {
        "train.fit", "train.placement", "train.workers_up",
        "raylet.worker_spawn", "train.jax_distributed_init",
        "train.start_session", "train.loop", "jax.lower",
        "jax.backend_compile", "train.report", "train.round",
        "train.checkpoint_register", "data.block_wait", "data.to_device"}
    assert "train.chip_claim" not in by_name      # no chip was granted
    assert len(by_name["train.fit"]) == 1
    assert len(by_name["train.loop"]) == 2        # one a rank
    assert len(by_name["train.jax_distributed_init"]) == 2
    # records as the tracing layer makes them
    assert set(by_name["train.report"][0]) >= {
        "name", "trace_id", "span_id", "parent_id", "start_us",
        "duration_us", "pid", "proc", "status", "attributes"}
    assert by_name["train.report"][0]["attributes"].keys() == {
        "n", "checkpoint"}
    assert {r["attributes"]["ready"] for r in by_name["data.block_wait"]} \
        <= {True, False}
    assert all(r["attributes"]["bytes"] == 128
               for r in by_name["data.block_wait"])


def test_timeline_is_one_tree_across_driver_raylet_and_workers(
        two_report_job):
    result, doc, by_name = two_report_job
    fit = by_name["train.fit"][0]
    by_id = {r["span_id"]: r for r in doc["spans"]}
    assert {r["trace_id"] for r in doc["spans"]} == {fit["trace_id"]}
    for record in doc["spans"]:
        hops = 0
        while record["parent_id"] is not None:
            record = by_id[record["parent_id"]]
            hops += 1
            assert hops < 16
        assert record is fit
    procs = {r["proc"] for r in doc["spans"]}
    assert procs == {"driver", "raylet", "worker"}
    worker_pids = {r["pid"] for r in by_name["train.loop"]}
    assert len(worker_pids) == 2
    # the raylet's hop names the worker it started, and lies in the
    # driver's wait for the workers
    spawned = {r["attributes"]["pid"] for r in by_name["raylet.worker_spawn"]}
    assert worker_pids <= spawned
    up = by_name["train.workers_up"][0]
    for spawn in by_name["raylet.worker_spawn"]:
        if spawn["attributes"]["pid"] in worker_pids:
            assert spawn["parent_id"] == up["span_id"]
    # the loop's thread parents under the call that started its session
    start = by_name["train.start_session"][0]
    assert {r["parent_id"] for r in by_name["train.loop"]} == {
        start["span_id"]}
    loops = {r["span_id"] for r in by_name["train.loop"]}
    assert {r["parent_id"] for r in by_name["train.report"]} <= loops
    assert {r["parent_id"] for r in by_name["data.block_wait"]} <= loops


def test_named_spans_cover_fit_to_loop(two_report_job):
    """fit() called -> the loop entered: at least 90 % of it lies under a
    named span, so what `spawn_s` times from outside is accounted for."""
    result, doc, by_name = two_report_job
    t0 = by_name["train.fit"][0]["start_us"]
    t1 = min(r["start_us"] for r in by_name["train.loop"])
    covered, reach = 0, t0
    for record in sorted((r for name in SETUP_SPANS
                          for r in by_name.get(name, ())),
                         key=lambda r: r["start_us"]):
        start = max(record["start_us"], reach)
        end = min(record["start_us"] + record["duration_us"], t1)
        if end > start:
            covered += end - start
            reach = end
    assert covered >= 0.9 * (t1 - t0), (covered, t1 - t0)


def test_counters_equal_the_calls_made(two_report_job):
    result, doc, by_name = two_report_job
    counters = doc["counters"]
    # two reports a rank, seen by the trainer as two rounds of reports
    assert counters["train.reports"] == 4
    assert len(by_name["train.report"]) == 4
    assert len(result.metrics_history) == 2
    assert len(by_name["train.round"]) == 3          # and the finish
    # the two shards cover the four blocks once, two batches a block
    assert counters["data.blocks"] == len(by_name["data.block_wait"]) == 4
    assert 0 <= counters["data.blocks_ready"] <= counters["data.blocks"]
    assert counters["data.blocks_ready"] == sum(
        r["attributes"]["ready"] for r in by_name["data.block_wait"])
    assert counters["data.block_bytes"] == 4 * 128
    assert counters["data.batches"] == len(by_name["data.to_device"]) == 8
    assert counters["jax.compiles"] == len(by_name["jax.backend_compile"])
    assert doc["dropped"] == 0


def test_timeline_opens_as_a_perfetto_file_unchanged(two_report_job):
    result, doc, by_name = two_report_job
    chrome = trace_analysis.to_chrome_trace(doc["spans"])
    events = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
    assert len(events) == len(doc["spans"])
    lanes = {e["args"]["name"] for e in chrome["traceEvents"]
             if e["ph"] == "M"}
    assert any(l.startswith("driver") for l in lanes)
    assert sum(l.startswith("worker") for l in lanes) == 2
    json.dumps(chrome)
    tree = trace_analysis.build_tree(doc["spans"])
    assert len(tree) == 1 and tree[0]["name"] == "train.fit"


def _raising_loop(config):
    from ray_tpu.train import session

    session.report({"step": 0})
    raise ValueError("boom in the timeline's loop")


def test_a_loop_that_raises_still_leaves_the_file(ray_train, tmp_path):
    trainer = _fit(tmp_path, _raising_loop, "raises", datasets=False)
    with pytest.raises(Exception, match="boom in the timeline's loop"):
        trainer.fit()
    doc, by_name = _timeline(os.path.join(str(tmp_path), "raises"))
    fit, loop = by_name["train.fit"][0], by_name["train.loop"][0]
    assert fit["status"] == "ERROR" and "boom" in fit["error"]
    # the worker still answered `end_session`: its part is there
    assert loop["status"] == "ERROR" and "boom" in loop["error"]
    assert doc["counters"]["train.reports"] == 1
    assert len(by_name["train.report"]) == 1


def _crash_once_loop(config):
    import os

    from ray_tpu.train import session

    session.report({"step": 0})
    if not os.path.exists(config["marker"]):
        open(config["marker"], "w").close()
        os._exit(1)                     # the worker dies: a group restart
    session.report({"step": 1})


def test_a_forced_restart_shows_one_train_restart(ray_train, tmp_path):
    marker = str(tmp_path / "crashed")
    result = _fit(tmp_path, _crash_once_loop, "restarts", datasets=False,
                  config={"marker": marker}, max_failures=1).fit()
    assert result.error is None and os.path.exists(marker)
    doc, by_name = _timeline(result.path)
    assert len(by_name["train.restart"]) == 1
    restart = by_name["train.restart"][0]
    assert restart["attributes"]["failures"] == 1
    assert restart["attributes"]["cause"]
    assert restart["parent_id"] == by_name["train.fit"][0]["span_id"]
    # both groups' starts are kept; the dead worker's own part is lost
    assert len(by_name["train.workers_up"]) == 2
    assert len(by_name["train.start_session"]) == 2
    assert len(by_name["raylet.worker_spawn"]) == 2
    assert len(by_name["train.loop"]) == 1
    assert len(by_name["train.setup"]) == 1     # the new group's own
    # the second group's start lies inside the restart
    second = by_name["train.workers_up"][1]
    assert second["parent_id"] == restart["span_id"]


def test_buffers_are_bounded_and_count_what_they_drop(monkeypatch):
    monkeypatch.setattr(tracing, "TIMELINE_STEP_CAP", 8)
    monkeypatch.setattr(tracing, "TIMELINE_LIFECYCLE_CAP", 4)
    with tracing._tl_lock:      # what earlier jobs of this process left
        tracing._tl_lifecycle.clear()
        tracing._tl_steps.clear()
        tracing._tl_dropped = 0
    with tracing.timeline_span("train.fit", root=True) as job:
        for i in range(20):
            with tracing.timeline_span("train.report", n=i):
                pass
        for i in range(6):
            with tracing.timeline_span("train.restart", failures=i):
                pass
        tracing.count("train.reports", 20)
    part = tracing.timeline_take(job.trace_id)
    names = [r["name"] for r in part["spans"]]
    # the newest per-step spans, and the lifecycle list at its cap
    assert [r["attributes"]["n"] for r in part["spans"]
            if r["name"] == "train.report"] == list(range(12, 20))
    assert names.count("train.restart") + names.count("train.fit") == 4
    assert part["dropped"] == 12 + 3
    assert part["counters"] == {"train.reports": 20}
    # taken means gone
    assert tracing.timeline_take(job.trace_id) == {
        "spans": [], "counters": {}, "dropped": 0}
    # the merge of the parts cuts the per-step spans again, and counts
    merged = tracing.timeline_merge([part, part, {"spans": [dict(
        part["spans"][-1], span_id=f"s{i}", name="train.round",
        start_us=i) for i in range(10)], "dropped": 2}])
    assert len([r for r in merged["spans"]
                if r["name"].startswith(("train.report", "train.round"))]) \
        == 8
    assert merged["dropped"] == 2 * 15 + 2 + 10
    assert merged["counters"] == {"train.reports": 40}


def test_outside_a_job_nothing_is_recorded(ray_train):
    """Data and Train code paths run outside `fit()` too: no job, no
    record, and `timeline_span` is a no-op span."""
    from ray_tpu.data.dataset import Dataset

    def held():
        # what this test's calls could write; the fixture's own raylet
        # records its `raylet.worker_spawn` hops while the test runs
        with tracing._tl_lock:
            return [r for r in (*tracing._tl_steps, *tracing._tl_lifecycle)
                    if r["name"].startswith(("data.", "train."))]

    before = held()
    # other files' tests in this process may have left a job's counters
    counted = {job: dict(c) for job, c in tracing._tl_counters.items()}
    ds = Dataset.from_read_fns(
        [functools.partial(_block, i) for i in range(2)])
    assert sum(len(b["x"]) for b in ds.iter_batches(batch_size=4)) == 16
    assert tracing.timeline_span("data.block_wait") is tracing._NULL_SPAN
    tracing.count("data.blocks")
    assert held() == before
    assert tracing._tl_counters == counted


def _trace_a_mixture_step(config):
    """Traces (never runs) a tiny `deepseek_v3` step that holds
    ``config["held"]`` of its 8 experts, as `fit()`'s loop would lower it,
    and reports."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import deepseek_v3 as model
    from ray_tpu.train import session

    cfg = dataclasses.replace(model.DEEPSEEK_V3_TINY,
                              held=tuple(config["held"]))
    optimizer = model.trained_by(optax.adamw(1e-3))
    params = jax.eval_shape(lambda k: model.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    state = jax.eval_shape(optimizer.init, params)
    tokens = jax.ShapeDtypeStruct((2, 65), jnp.int32)
    jax.jit(model.make_train_step(cfg, optimizer)).lower(
        params, state, {"tokens": tokens})
    session.report({"done": 1})


@pytest.mark.parametrize("held, buffered", [(4, 128 * 3), (2, 192)])
def test_a_fit_counts_the_mixture_in_its_timeline(ray_train, tmp_path, held,
                                                  buffered):
    """`moe.experts`, `moe.experts_held`, `moe.rows_routed` and
    `moe.rows_buffered` (`ops/moe.py:moe_dispatch`, as the step is traced):
    two routed layers, each traced once: 8 experts of which 4 are held, 2
    x 64 tokens x 3 rows routed and a buffer of all of them; of which 2
    are held, a buffer of twice the 96 rows a balanced router sends them
    (`ops/moe.py:buffer_rows`)."""
    result = _fit(tmp_path, _trace_a_mixture_step, "moe_counters",
                  config={"held": (2, held)}, datasets=False).fit()
    assert result.error is None
    counters = _timeline(result.path)[0]["counters"]
    assert counters["moe.experts"] == 2 * 8
    assert counters["moe.experts_held"] == 2 * held
    assert counters["moe.rows_routed"] == 2 * 128 * 3
    assert counters["moe.rows_buffered"] == 2 * buffered


# -- the set-up account: what jax's tracer, lowering, compiler and cache take
# before the first step, booked by the program (`train/backend.py`) ---------

EIGHT = {f"{kind}/{whose}" for whose in ("step", "other")
         for kind in ("trace", "lower", "compile", "cache_read")}


@pytest.fixture
def listening(monkeypatch):
    """The listeners on, every trace spanned however short, and what this
    thread booked for earlier jobs forgotten."""
    from ray_tpu.train import backend

    backend._listen_to_jax()
    monkeypatch.setattr(backend, "_SHORT_TRACE_S", 0.0)
    backend._jax_thread.__dict__.pop("events", None)
    return backend


def _nested_step():
    """A function whose trace holds a nested jitted call, a `jax.checkpoint`
    and a `custom_vjp` whose backward calls a jitted function."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def inner(x):                   # `sin` and `cos` trace inside it
        return jnp.cos(jnp.sin(x))

    triple = jax.jit(lambda g: g * 3.0)
    triple.__wrapped__.__name__ = "triple"

    @jax.custom_vjp
    def scaled(x):
        return x * 3.0

    scaled.defvjp(lambda x: (x * 3.0, None), lambda _, g: (triple(g),))

    def layer(x, w):
        return jnp.tanh(inner(x)) @ w

    def loss(w, x):
        return jnp.sum(scaled(jax.checkpoint(layer)(x, w)) ** 2)

    return jax.grad(loss), (jnp.ones((8, 8)), jnp.ones((4, 8)))


def _jax_spans(part, name="jax.trace"):
    return {r["attributes"]["fun_name"]: r for r in part["spans"]
            if r["name"] == name}


def test_own_times_sum_to_the_root_and_nested_traces_keep_their_own(
        listening):
    import jax

    fn, args = _nested_step()
    with tracing.timeline_span("train.fit", root=True) as job:
        jax.jit(fn).trace(*args)
    part = tracing.timeline_take(job.trace_id)
    traces = [r for r in part["spans"] if r["name"] == "jax.trace"]
    by_fun = _jax_spans(part)
    root = by_fun[fn.__name__]
    # every trace of the run lies in the root's span: their own times are
    # its duration, each cut to a whole microsecond
    assert part["counters"]["jax.traces"] == len(traces) > 8
    own = sum(r["attributes"]["own_us"] for r in traces)
    assert 0 <= root["duration_us"] - own < 1000, (root["duration_us"], own)
    assert 0 < root["attributes"]["own_us"] < root["duration_us"]
    assert all(r["attributes"]["own_us"] <= r["duration_us"] for r in traces)
    # `sin` and `cos` closed inside `inner`: its own time is the rest
    inner, sin, cos = by_fun["inner"], by_fun["sin"], by_fun["cos"]
    assert inner["start_us"] <= sin["start_us"] <= cos["start_us"]
    assert inner["attributes"]["own_us"] <= inner["duration_us"] \
        - sin["duration_us"] - cos["duration_us"] + 2
    # the backward's jitted call is traced in the root's span too
    assert "triple" in by_fun
    # no function here is the step's
    assert not any(r["attributes"]["step"] for r in traces)
    assert not any("scope" in r["attributes"] for r in traces)


def test_train_step_books_to_the_step_and_any_other_function_to_other(
        listening):
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import layers

    backend = listening

    def objective(params, batch):
        loss = jnp.sum(jnp.tanh(batch @ params["w"]) ** 2)
        return loss, {"loss": loss}

    optimizer = optax.sgd(1e-2)
    params = {"w": jnp.ones((8, 8))}
    state, batch = optimizer.init(params), jnp.ones((4, 8))
    step = layers.train_step(objective, optimizer, jnp.float32)
    assert step.__name__ in backend.STEP_NAMES
    with tracing.timeline_span("train.fit", root=True) as job:
        jax.jit(step).lower(params, state, batch).compile()
        only_step = backend.setup_account(tracing.timeline_ctx())
        jax.jit(lambda x: x * 2 + 1)(batch).block_until_ready()
        account = backend.setup_account(tracing.timeline_ctx())
    part = tracing.timeline_take(job.trace_id)
    assert set(account["own_us"]) == EIGHT
    assert all(only_step["own_us"][f"{kind}/step"] > 0
               for kind in ("trace", "lower", "compile"))
    assert not any(only_step["own_us"][f"{kind}/other"]
                   for kind in ("trace", "lower", "compile"))
    # the second function added to `other` alone
    assert {k: v for k, v in account["own_us"].items() if "/step" in k} \
        == {k: v for k, v in only_step["own_us"].items() if "/step" in k}
    assert all(account["own_us"][f"{kind}/other"] > 0
               for kind in ("lower", "compile"))
    assert set(account) == {"own_us", "step_cache", "listen_us"}
    # on the spans: `step` by the outermost function, whatever their own
    for name in ("jax.trace", "jax.lower", "jax.backend_compile"):
        spans = [r for r in part["spans"] if r["name"] == name]
        mine = [r for r in spans if r["attributes"]["step"]]
        assert mine and len(mine) < len(spans)
        assert {"own_us", "step"} <= set(mine[0]["attributes"])
    compiled = _jax_spans(part, "jax.backend_compile")
    assert compiled["jit(train_step)"]["attributes"]["step"]
    assert not compiled["jit(<lambda>)"]["attributes"]["step"]
    assert account["step_cache"] == \
        compiled["jit(train_step)"]["attributes"]["cache"]


def test_cache_reads_miss_then_hit_and_off_without_a_directory(
        listening, tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    x = jnp.ones(8)         # its own little program compiles outside

    def compiles(fn):
        with tracing.timeline_span("train.fit", root=True) as job:
            jax.jit(fn).lower(x).compile()
        part = tracing.timeline_take(job.trace_id)
        span, = [r for r in part["spans"]
                 if r["name"] == "jax.backend_compile"]
        reads = [r for r in part["spans"] if r["name"] == "jax.cache_read"]
        return span, reads

    def served():
        """A new function each call (jit's own caches miss) of one module
        (the cache's key is the same)."""
        def served(x):
            return jnp.cos(x) * 5.0 + 2.0
        return served

    was = {name: getattr(jax.config, name) for name in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    assert not was["jax_compilation_cache_dir"]
    span, reads = compiles(served())
    assert span["attributes"]["cache"] == "off" and not reads
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()
        first, reads = compiles(served())
        assert first["attributes"]["cache"] == "miss" and not reads
        second, (read,) = compiles(served())
        assert second["attributes"]["cache"] == "hit"
        # the read lies inside its compile, whose own time is the rest
        assert second["start_us"] <= read["start_us"]
        assert second["attributes"]["own_us"] \
            <= second["duration_us"] - read["duration_us"] + 1
    finally:
        for name, value in was.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()


def test_outside_a_job_the_jax_listeners_record_nothing(listening):
    import jax
    import jax.numpy as jnp

    backend = listening
    with tracing._tl_lock:
        held = len(tracing._tl_lifecycle)
    jax.jit(lambda x: jnp.sin(x) + 4.0)(jnp.ones(4)).block_until_ready()
    with tracing._tl_lock:
        assert not [r for r in list(tracing._tl_lifecycle)[held:]
                    if r["name"].startswith("jax.")]
    assert not hasattr(backend._jax_thread, "events")


def test_train_setup_is_written_once_a_rank_at_its_first_report(
        two_report_job):
    from ray_tpu.train.session import setup_text

    result, doc, by_name = two_report_job
    setups = by_name["train.setup"]
    assert sorted(r["attributes"]["rank"] for r in setups) == [0, 1]
    for setup in setups:
        loop, = [r for r in by_name["train.loop"] if r["pid"] == setup["pid"]]
        first, = [r for r in by_name["train.report"]
                  if r["pid"] == setup["pid"] and r["attributes"]["n"] == 0]
        a = setup["attributes"]
        # the loop's start to the first report's return, under the loop
        assert setup["parent_id"] == loop["span_id"]
        assert 0 <= setup["start_us"] - loop["start_us"] < 10_000
        end = setup["start_us"] + setup["duration_us"]
        assert 0 <= end - (first["start_us"] + first["duration_us"]) < 50_000
        assert set(a["own_us"]) == EIGHT
        assert a["run_us"] >= 0
        assert a["run_us"] + sum(a["own_us"].values()) == setup["duration_us"]
        # `double` is no step of `layers.train_step`: all of it is `other`
        assert a["own_us"]["compile/other"] > 0
        assert not any(us for key, us in a["own_us"].items()
                       if key.endswith("/step"))
        assert a["step_cache"] is None
        assert 0 < a["listen_us"] < 1_000_000
        line = setup_text(setup)
        assert line.startswith("train: set-up ") and "other functions" in line


def _traces_a_round(n, x):
    """``n`` small functions traced (two a turn: the function and the `add`
    inside it) inside one function's trace, as a step's are."""
    import jax

    def body(x):
        for _ in range(n // 2):
            x = jax.jit(lambda x: x + 1)(x)     # a new function: a trace
        return x

    jax.jit(body).trace(x)


def test_the_listeners_cost_microseconds_a_trace(listening, monkeypatch):
    """What 2,000 small traces cost in a job, where the listeners keep their
    stack and book, by what the listeners time of themselves (`listen_us`):
    a few microseconds a trace on an idle machine.  The limit is generous,
    25 us, so that a loaded machine does not fail it; the wall clock's
    difference to a run outside a job swings by tens of microseconds a trace
    either way and is asserted nowhere."""
    import jax.numpy as jnp

    backend = listening
    monkeypatch.setattr(backend, "_SHORT_TRACE_S", 0.005)
    n, x = 2000, jnp.ones(4)
    _traces_a_round(200, x)
    listened = []
    for _ in range(2):
        with tracing.timeline_span("train.fit", root=True) as job:
            _traces_a_round(n, x)
            listened.append(backend.setup_account(
                tracing.timeline_ctx())["listen_us"])
        part = tracing.timeline_take(job.trace_id)
        assert part["counters"]["jax.traces"] == n + 1      # and `body`
    print(f"listen_us a trace {min(listened) / n:.2f}")
    assert 0 < min(listened) / n < 25, listened
