"""Seeded faults of the `mellum` family: each a family that departs from
what the configuration states in one way, for `test_mellum_cell.py` (at the
rehearsal's sizes) and for the readings on the chip that the configuration's
limits are set between (`reference.loss_tolerance_reason`).  A fault is a
patch of one name of the program for as long as the family traces its step
or its layers' attention; the reference is never touched.

    FAULTS[name] -> the family's class

A benchmark checkout gets one as `benchmark/families/mellum_<name>.py`:
`from benchmark.tests.mellum_faults import FAULTS; Family = FAULTS[<name>]`
(`install`).
"""

from __future__ import annotations

import contextlib
import json
import os

from benchmark.families import mellum

SLIDING, FULL = mellum.SLIDING, mellum.FULL


@contextlib.contextmanager
def patched(module, name, replacement):
    """``module.name`` replaced while a trace runs; `jax.checkpoint` and
    `jax.jit` cache a trace by function and shapes, not by what its globals
    are, so the caches go before and after."""
    import jax

    original = getattr(module, name)
    jax.clear_caches()
    setattr(module, name, replacement(original))
    try:
        yield
    finally:
        setattr(module, name, original)
        jax.clear_caches()


class Faulty(mellum.Family):
    """A family whose step and layers' attention are traced under
    `patch()`."""

    def patch(self):
        return contextlib.nullcontext()

    def lower_step(self, params, opt_state, batch):
        with self.patch():
            return super().lower_step(params, opt_state, batch)

    def first_layer(self, params, tokens):
        with self.patch():
            return super().first_layer(params, tokens)


def _model_fault(name, change, doc):
    """A family under which `ray_tpu.models.mellum.<name>` is
    ``change(original)``."""
    class Family(Faulty):
        __doc__ = doc

        def patch(self):
            from ray_tpu.models import mellum as model

            return patched(model, name, change)
    return Family


def _window(width_of):
    """The sliding layers' window ``width_of(W)`` wide (None: none); the
    full layers as they are."""
    def change(original):
        def rule(cfg, kind):
            if kind != SLIDING:
                return original(cfg, kind)
            return original(cfg, kind)._replace(
                window=width_of(cfg.sliding_window))
        return rule
    return change


def _window_on_full(original):
    return lambda cfg, kind: original(cfg, SLIDING)


def _table_of(kind_for):
    """Each kind of layer turns by the table of ``kind_for[kind]``."""
    def change(original):
        return lambda cfg, kind: original(cfg, kind_for.get(kind, kind))
    return change


def _no_attention_factor(original):
    def rotary(cfg, kind):
        theta, scale = original(cfg, kind)
        return theta, None if kind == FULL else scale
    return rotary


def _eight_bit(original):
    def attention(q, k, v, **kw):
        import jax.numpy as jnp

        low = lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
        return original(low(q), low(k), low(v), **kw)
    return attention


class WrongRate(mellum.Family):
    """Three times the learning rate the configuration states."""

    def optimizer(self):
        from benchmark.reference.mellum import adamw

        settings = dict(self.config["optimizer"])
        settings["learning_rate"] *= 3
        return adamw(settings)


FAULTS = {
    "window_one_more": _model_fault(
        "rule", _window(lambda w: w + 1), "W + 1 keys a sliding row."),
    "window_one_fewer": _model_fault(
        "rule", _window(lambda w: w - 1), "W - 1 keys a sliding row."),
    "window_on_the_full_layer": _model_fault(
        "rule", _window_on_full, "The full layers windowed too."),
    "no_window": _model_fault(
        "rule", _window(lambda w: None),
        "A sliding layer attends every earlier key."),
    "yarn_on_a_sliding_layer": _model_fault(
        "rotary", _table_of({SLIDING: FULL}),
        "YaRN's frequencies and factor in the sliding layers."),
    "plain_rope_on_the_full_layer": _model_fault(
        "rotary", _table_of({FULL: SLIDING}),
        "The base's frequencies, c = 1, in the full layers."),
    "attention_factor_dropped": _model_fault(
        "rotary", _no_attention_factor,
        "YaRN's frequencies with c = 1."),
    "eight_bit_attention": _model_fault(
        "attention", _eight_bit,
        "q, k and v through `float8_e4m3fn` before the kernels."),
    "wrong_rate": WrongRate,
}


def install(root: str, source_root: str, fault: str,
            config_name: str = "mellum2-12b-a2.5b-ep4") -> str:
    """Into the benchmark checkout at ``root`` (a copy of BENCHMARK.json and
    benchmark/): the family `mellum_<fault>`, a configuration of it and a
    cell under `resident-16k` -> the cell's name."""
    with open(os.path.join(root, "benchmark", "families",
                           f"mellum_{fault}.py"), "w") as f:
        f.write("from benchmark.tests.mellum_faults import FAULTS\n\n"
                f"Family = FAULTS[{fault!r}]\n")
    with open(os.path.join(source_root, "benchmark", "configs",
                           f"{config_name}.json")) as f:
        config = json.load(f)
    name = f"mellum-{fault.replace('_', '-')}"
    config.update(name=name, family=f"mellum_{fault}")
    with open(os.path.join(root, "benchmark", "configs", f"{name}.json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": name, "source": "test", "reduced": [], "why": "test",
        "file": f"benchmark/configs/{name}.json"})
    bench["workloads"].append({
        "name": f"{name}.resident-16k", "config": name,
        "traffic": "resident-16k", "chips": 1, "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return f"{name}.resident-16k"
