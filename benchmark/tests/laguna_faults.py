"""Seeded faults of the `laguna` family: each a family that departs from what
the configuration states in one way, for `test_laguna_cell.py` (at the
rehearsal's sizes) and for the readings on the chip that the configuration's
limits are set between (`reference.loss_tolerance_reason`).  A fault is a
patch of one name of the program for as long as the family traces its step
or its layers' attention; the reference is never touched.

    FAULTS[name] -> the family's class

A benchmark checkout gets one as `benchmark/families/laguna_<name>.py`:
`from benchmark.tests.laguna_faults import FAULTS; Family = FAULTS[<name>]`
(`install`).
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os

from benchmark.families import laguna
# what a window's and a precision's faults are does not go by the family:
# the rule's window a key more, fewer or on every layer, q, k and v through
# eight bits, and the patch of a module's name while a trace runs
from benchmark.tests.mellum_faults import (
    _eight_bit,
    _window,
    _window_on_full,
    patched,
)

SLIDING, FULL = laguna.SLIDING, laguna.FULL


class Faulty(laguna.Family):
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
    """A family under which `ray_tpu.models.laguna.<name>` is
    ``change(original)``."""
    class Family(Faulty):
        __doc__ = doc

        def patch(self):
            from ray_tpu.models import laguna as model

            return patched(model, name, change)
    return Family


# -- the gate -----------------------------------------------------------------

def _no_gate(original):
    return lambda o, p, gate_input=None: original(o, p)


def _gate_from_the_stream(original):
    """`_layer` with the gate reading x, the residual stream, and not its
    norm u: the layer's own lines, the gate's input apart."""
    def _layer(x, p, cfg):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import laguna as model
        from ray_tpu.models import layers

        kind = SLIDING if SLIDING in p else FULL
        u = layers.rms_norm(x, p["input_norm"], cfg.rms_eps)
        with jax.named_scope("attention"):
            q, k, v = layers.attention_qkv(
                u, p[kind], cfg.head_dim, None, jnp.arange,
                *model.rotary(cfg, kind))
            with jax.named_scope("kernel"):
                o = model.attention(q, k, v, causal=model.rule(cfg, kind))
            x = x + layers.attention_out(o, p[kind], gate_input=x)
        u = layers.rms_norm(x, p["post_norm"], cfg.rms_eps)
        with jax.named_scope("ffn"):
            if "mlp" in p:
                with jax.named_scope("dense"):
                    return x + layers.dense_ffn(u, p["mlp"],
                                                layers.swiglu), None
            with jax.named_scope("moe"):
                y, rows = model.routed_layer(
                    u, p["moe"], model._route(cfg), cfg.n_experts, cfg.held,
                    layers.swiglu)
        return x + y, rows
    return _layer


# -- the rotary tables --------------------------------------------------------

def _whole_head_on_the_full_layer(original):
    def rotary(cfg, kind):
        if kind != FULL:
            return original(cfg, kind)
        return original(dataclasses.replace(
            cfg, rotary_full=cfg.head_dim), kind)
    return rotary


def _bases_swapped(original):
    return lambda cfg, kind: original(dataclasses.replace(
        cfg, theta_full=cfg.theta_sliding, theta_sliding=cfg.theta_full),
        kind)


def _no_attention_factor(original):
    def rotary(cfg, kind):
        theta, scale, width = original(cfg, kind)
        return theta, None if kind == FULL else scale, width
    return rotary


# -- the heads ----------------------------------------------------------------

def _full_layers_heads_on_a_sliding_layer(original):
    """A sliding layer run with the full layers' head count: the first 48
    of its 64 heads' weights."""
    def _attention(u, p, cfg, kind):
        if kind != SLIDING:
            return original(u, p, cfg, kind)
        h, d = cfg.n_head_full, cfg.head_dim
        cut = lambda name, rows, cols: {
            "kernel": p[name]["kernel"][:rows, :cols]}
        return original(u, {
            **p, "q_proj": cut("q_proj", None, h * d),
            "g_proj": cut("g_proj", None, h),
            "o_proj": cut("o_proj", h * d, None)}, cfg, kind)
    return _attention


# -- the mixture --------------------------------------------------------------

def _no_routed_scale(original):
    return lambda cfg: original(dataclasses.replace(cfg, routed_scale=1.0))


def _no_shared_expert(original):
    def routed_layer(x, p, *args):
        return original(x, {name: leaf for name, leaf in p.items()
                            if name != "shared"}, *args)
    return routed_layer


class WrongRate(laguna.Family):
    """Three times the learning rate the configuration states."""

    def optimizer(self):
        from benchmark.reference.laguna import adamw
        from ray_tpu.models.laguna import trained_by

        settings = dict(self.config["optimizer"])
        settings["learning_rate"] *= 3
        return trained_by(adamw(settings))


FAULTS = {
    "gate_dropped": _model_fault(
        "attention_out", _no_gate, "No gate before W_o."),
    "gate_from_the_stream": _model_fault(
        "_layer", _gate_from_the_stream,
        "The gate reads x, not its norm u."),
    "whole_head_on_the_full_layer": _model_fault(
        "rotary", _whole_head_on_the_full_layer,
        "A full layer turns all 128 dims, YaRN at dim 128."),
    "bases_swapped": _model_fault(
        "rotary", _bases_swapped, "Each kind turns by the other's base."),
    "attention_factor_dropped": _model_fault(
        "rotary", _no_attention_factor, "YaRN's frequencies with c = 1."),
    "window_one_more": _model_fault(
        "rule", _window(lambda w: w + 1), "W + 1 keys a sliding row."),
    "window_one_fewer": _model_fault(
        "rule", _window(lambda w: w - 1), "W - 1 keys a sliding row."),
    "window_on_the_full_layer": _model_fault(
        "rule", _window_on_full, "The full layers windowed too."),
    "full_heads_on_a_sliding_layer": _model_fault(
        "_attention", _full_layers_heads_on_a_sliding_layer,
        "A sliding layer with the first 48 of its 64 heads."),
    "eight_bit_attention": _model_fault(
        "attention", _eight_bit,
        "q, k and v through `float8_e4m3fn` before the kernels."),
    "routed_scale_dropped": _model_fault(
        "_route", _no_routed_scale, "The chosen weights not times 2.5."),
    "shared_expert_dropped": _model_fault(
        "routed_layer", _no_shared_expert, "No shared expert."),
    "wrong_rate": WrongRate,
}
# the faults a layer's attention cannot show: they stand outside it, or (the
# gate's input) in what a layer hands its attention
BEHIND_ATTENTION = ("gate_from_the_stream", "routed_scale_dropped",
                    "shared_expert_dropped", "wrong_rate")


def install(root: str, source_root: str, fault: str,
            config_name: str = "laguna-xs.2-ep16") -> str:
    """Into the benchmark checkout at ``root`` (a copy of BENCHMARK.json and
    benchmark/): the family `laguna_<fault>`, a configuration of it and a
    cell under `resident-16k` -> the cell's name."""
    with open(os.path.join(root, "benchmark", "families",
                           f"laguna_{fault}.py"), "w") as f:
        f.write("from benchmark.tests.laguna_faults import FAULTS\n\n"
                f"Family = FAULTS[{fault!r}]\n")
    with open(os.path.join(source_root, "benchmark", "configs",
                           f"{config_name}.json")) as f:
        config = json.load(f)
    name = f"laguna-{fault.replace('_', '-')}"
    config.update(name=name, family=f"laguna_{fault}")
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
