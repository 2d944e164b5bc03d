"""The `train_steps` loop: a training job through the system's normal path.

`drive` runs in the benchmark's parent process, which never imports jax:
`ray_tpu.init` -> raylet -> one worker granted the cell's chips ->
`JaxTrainer.fit`.  `train_loop` runs in that worker: the plain reference
first, then the system's state, its one compiled step, warm-up, and the
measured window.  Closed loop by nature: a step follows a step.  The loop
dispatches step i and only then fetches step i-1's loss, so the device
always has its next step queued and every completion gets a host
timestamp; `train.report` is called for every step, as a real job does.

What comes back is observations (times, spans, losses, memory, the trace
reduction); the metric readers in `benchmark/metrics/` turn them into
numbers.  A traffic file names this loop; a checkpointing, resuming or
serving loop is a new file beside it.
"""

from __future__ import annotations

import functools
import os
import time


def block_tokens(seed: int, index: int, rows: int, width: int, vocab: int):
    """One block of the streamed data set, from (seed, block index)."""
    import numpy as np

    rng = np.random.default_rng([seed, index])
    return {"tokens": rng.integers(0, vocab, (rows, width), dtype=np.int32)}


def drive(spec: dict) -> dict:
    """Parent side.  `spec` holds the cell, its configuration and traffic
    (rehearsal sizes already applied), seed, seconds and trace flag."""
    import ray_tpu
    from ray_tpu.train import JaxConfig, JaxTrainer, RunConfig, ScalingConfig

    chips, traffic = spec["chips"], spec["traffic"]
    rehearse = spec["rehearse"]
    ray_tpu.init(num_cpus=4, num_tpus=0 if rehearse else chips)
    try:
        datasets = None
        if traffic["source"] == "dataset":
            from ray_tpu.data.dataset import Dataset

            ds = traffic["dataset"]
            datasets = {"train": Dataset.from_read_fns([
                functools.partial(block_tokens, spec["seed"], i,
                                  ds["block_rows"], traffic["seq"] + 1,
                                  spec["config"]["vocab_size"])
                for i in range(ds["rows"] // ds["block_rows"])])}
        if rehearse:
            scaling = ScalingConfig(num_workers=1)
        else:
            scaling = ScalingConfig(
                num_workers=1, use_tpu=True,
                resources_per_worker={"CPU": 1, "TPU": chips})
        spec = dict(spec, t_fit=time.time())
        trainer = JaxTrainer(
            train_loop, train_loop_config=spec,
            # a rehearsal stands in virtual CPU devices for the chips
            jax_config=JaxConfig(
                devices_per_worker=chips if rehearse else None),
            scaling_config=scaling, datasets=datasets,
            run_config=RunConfig(name=spec["cell"],
                                 storage_path=spec["work_dir"]))
        result = trainer.fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        raise result.error
    obs = result.metrics["observations"]
    obs["reports_seen"] = len(result.metrics_history) - 1
    return obs


def train_loop(spec: dict):
    """Worker side: runs in the process that holds the chips."""
    t_enter = time.time()
    import math
    import shutil
    import warnings

    import jax
    import numpy as np

    from benchmark.harness import registry, xplane
    from benchmark.harness.spans import Spans
    from ray_tpu import train
    from ray_tpu.ops.flash_attention import AttentionFallbackWarning

    config, traffic = spec["config"], spec["traffic"]
    seed, chips = spec["seed"], spec["chips"]
    batch, seq = traffic["batch"], traffic["seq"]
    ref_steps = config["reference"]["steps"]
    warmup = max(traffic["warmup_steps"], ref_steps)

    compiles = []       # (perf_counter, event): every trace and compile
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: "compile" in event
        and compiles.append((time.perf_counter(), event)))
    cache_hits = []
    jax.monitoring.register_event_listener(
        lambda event, **_: event == "/jax/compilation_cache/cache_hits"
        and cache_hits.append(event))

    devices = jax.devices()[:chips]
    obs = {
        "t_fit": spec["t_fit"],
        "t_enter": t_enter,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": jax.device_count()},
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
    }
    family = registry.family(config)
    family.bind(devices)
    spans = Spans()

    # -- the batches, as the traffic delivers them --------------------------
    if traffic["source"] == "resident":
        rng = np.random.default_rng([seed, 7])
        resident = family.place_batch(rng.integers(
            0, config["vocab_size"], (batch, seq + 1), dtype=np.int32))
        next_batch = lambda: resident
    else:
        shard = train.get_dataset_shard("train")
        stream = iter(())

        def next_batch():
            nonlocal stream
            while True:
                try:
                    return family.place_batch(next(stream)["tokens"])
                except StopIteration:     # a new pass over the data set
                    stream = shard.iter_jax_batches(batch_size=batch)

    # the first batches go to the reference and then to the system
    first = [next_batch() for _ in range(ref_steps)]

    # -- the plain reference, before the system's state exists --------------
    t0 = time.perf_counter()
    obs["reference_losses"] = family.reference_losses(
        seed, [np.asarray(b["tokens"]) for b in first])
    obs["reference_s"] = time.perf_counter() - t0
    obs["reference_peak_bytes"] = _peak_bytes(devices)

    # -- the system's state and its one step --------------------------------
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        t0 = time.perf_counter()
        params, opt_state = family.init_state(seed)
        jax.block_until_ready((params, opt_state))
        obs["init_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        hits = len(cache_hits)
        compiled = family.lower_step(params, opt_state, first[0]).compile()
        obs["lower_compile_s"] = time.perf_counter() - t0
        obs["step_served_from_cache"] = len(cache_hits) > hits
        # the step's scratch space on each device, which this runtime's
        # `peak_bytes_in_use` leaves out (it counts live arrays only)
        obs["step_temp_bytes"] = compiled.memory_analysis().temp_size_in_bytes
    obs["attention_fallbacks"] = [
        str(w.message) for w in caught
        if issubclass(w.category, AttentionFallbackWarning)]

    # -- warm-up, then the window, in one loop ------------------------------
    done = []           # (host time step i was seen complete, its loss)
    pending = None      # the loss of the step in flight
    t_open = deadline = None

    def complete():
        nonlocal pending, t_open, deadline
        with spans("sync"):
            loss = float(pending)
        pending = None
        done.append((time.perf_counter(), loss))
        with spans("report"):
            train.report({"step": len(done) - 1, "loss": loss})
        if len(done) == warmup:
            t_open = done[-1][0]
            obs["t_open"] = time.time()
            deadline = t_open + spec["seconds"]

    # a traced run drains the device, traces whole steps dispatched as ever
    # until `trace_seconds` are through, and drains again
    trace_dir = os.path.join(spec["work_dir"], "trace")
    trace_at = warmup + 1 if spec["trace"] else None
    trace_until = None      # perf_counter at which the trace has enough
    i = 0
    while deadline is None or done[-1][0] <= deadline:
        if i == trace_at:
            complete()
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            trace_until = time.perf_counter() + traffic["trace_seconds"]
        elif trace_until is not None and time.perf_counter() >= trace_until:
            complete()
            jax.profiler.stop_trace()
            trace_until = None
        with spans("data_wait"):
            data = first[i] if i < ref_steps else next_batch()
        with spans("dispatch"):
            params, opt_state, out = compiled(params, opt_state, data)
        if pending is not None:
            complete()
        pending = out["loss"]
        i += 1
    complete()          # the step in flight ends before anything is read
    if trace_until is not None:
        raise RuntimeError(
            f"the window closed before the trace's "
            f"{traffic['trace_seconds']} s were through")

    in_window = [(t, l) for t, l in done[warmup:] if t <= deadline]
    ends = [t_open] + [t for t, _ in in_window]
    t_close = ends[-1]
    obs.update({
        "losses_first": [l for _, l in done[:ref_steps]],
        "warmup_steps": warmup,
        "window_s": t_close - t_open,
        "window_steps": len(in_window),
        "tokens_per_step": batch * seq,
        "step_intervals_s": [b - a for a, b in zip(ends, ends[1:])],
        "window_nonfinite": sum(not math.isfinite(l) for _, l in in_window),
        "loss_open": done[warmup - 1][1],
        "loss_close": in_window[-1][1] if in_window else None,
        "spans": spans.durations(t_open, t_close),
        "compiles_in_window": [e for t, e in compiles
                               if t_open < t <= t_close],
        "peak_bytes": _peak_bytes(devices),
    })
    if obs["peak_bytes"][0] is not None:
        # the fullest chip: its live arrays at their most, and the scratch
        # space the step holds while it runs
        obs["memory_peak_bytes"] = (max(obs["peak_bytes"])
                                    + obs["step_temp_bytes"])
    if spec["trace"] and not spec["rehearse"]:   # a CPU trace has no device
        obs["trace"] = xplane.reduce_file(
            xplane.newest_trace(trace_dir), spans=tuple(obs["spans"]),
            is_kernel=family.is_attention_kernel)
    train.report({"step": len(done), "loss": done[-1][1],
                  "observations": obs})


def _peak_bytes(devices):
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]
