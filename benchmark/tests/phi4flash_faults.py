"""Seeded faults of the `phi4flash` family: each a family that departs from
what the configuration states in one way, for `test_phi4flash_cell.py` and
`tests/test_phi4flash.py` (at small sizes) and for the readings on the chip
that the configuration's limits are set between
(`reference.loss_tolerance_reason`).  A fault is a patch of one name of the
program for as long as the family traces its step or its walk; the reference
is never touched.

    FAULTS[name] -> the family's class

On the chip, all of them in one process, the reference run once:

    python benchmark/tests/phi4flash_faults.py --seed N [--faults NAME ...]
        [--no-losses]

prints, a fault (and first for the program as it is, `sound`), the stream's
error after each layer held on the first sequence and the three losses'
distances from the reference's.
"""

from __future__ import annotations

import contextlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.families import phi4flash  # noqa: E402
# the patch of a module's name while a trace runs does not go by the family
from benchmark.tests.mellum_faults import patched  # noqa: E402


class Faulty(phi4flash.Family):
    """A family whose step and walk are traced under `patch()`."""

    def patch(self):
        return contextlib.nullcontext()

    def lower_step(self, params, opt_state, batch):
        with self.patch():
            return super().lower_step(params, opt_state, batch)

    def first_streams(self, params, inputs, want=None):
        with self.patch():
            return super().first_streams(params, inputs, want)


def _fault(module_name, name, change, doc):
    """A family under which `<module_name>.<name>` is ``change(original)``."""
    class Family(Faulty):
        __doc__ = doc

        def patch(self):
            import importlib

            return patched(importlib.import_module(module_name), name, change)
    return Family


MODEL = "ray_tpu.models.phi4flash"


def _with_mamba_leaf(change):
    """`_mamba` over parameters of which ``change(p) -> {name: leaf}`` are
    replaced."""
    def wrap(original):
        return lambda h, p, cfg: original(h, {**p, **change(p)}, cfg)
    return wrap


def _no_d(original):
    import jax.numpy as jnp

    return lambda u, dt, A, B, C, D: original(u, dt, A, B, C,
                                              jnp.zeros_like(D))


def _no_dt_bias(p):
    import jax.numpy as jnp

    return {"dt_proj": {**p["dt_proj"],
                        "bias": jnp.zeros_like(p["dt_proj"]["bias"])}}


def _mean_a(p):
    """A's N columns replaced by their mean: one decay a channel, which is
    what a scan with one decay a head (`ops/ssd.py`) computes."""
    import jax.numpy as jnp

    a = jnp.exp(p["A_log"])
    return {"A_log": jnp.log(jnp.broadcast_to(
        jnp.mean(a, axis=1, keepdims=True), a.shape))}


def _taps_ahead(original):
    """The convolution run over the sequence turned round: position t reads
    t .. t + K - 1."""
    def causal_conv(v, p, activation=None, start=0, widths=None):
        import jax.numpy as jnp

        out = original(jnp.flip(v, axis=1), p, activation, start, widths)
        flip = lambda x: jnp.flip(x, axis=1)
        return flip(out) if widths is None else tuple(map(flip, out))
    return causal_conv


def _swap_rule(which):
    def wrap(original):
        def rule(cfg, kind):
            from ray_tpu.ops.flash_attention import BlockRule

            if kind != which:
                return original(cfg, kind)
            return BlockRule(window=cfg.window
                             if which == phi4flash.FULL else None)
        return rule
    return wrap


def through_e4m3(x):
    """x rounded to the nearest float8_e4m3fn value (3 bits of mantissa,
    subnormals below 2^-6 in steps of 2^-9, at most 448), written out in
    float32 arithmetic: XLA:TPU drops a convert to float8 and back as excess
    precision it may keep, on the chip the round trip reads as the sound
    program to the last digit (PERF.md section 6, PR 63)."""
    import jax.numpy as jnp

    exponent = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(x), 2.0 ** -6)))
    step = jnp.exp2(exponent - 3.0)
    return jnp.clip(jnp.round(x / step) * step, -448.0, 448.0)


def _eight_bit_matrices(original):
    """`cast_weights` over matrices rounded through float8_e4m3fn, the
    nearest precision below the stated bfloat16."""
    def cast_weights(params, dtype):
        import jax
        import jax.numpy as jnp

        return original(jax.tree.map(
            lambda x: through_e4m3(x)
            if x.dtype == jnp.float32 and x.ndim >= 2 else x, params), dtype)
    return cast_weights


FAULTS = {
    "no_d_term": _fault(
        MODEL, "selective_scan", _no_d, "y without D u"),
    "no_dt_bias": _fault(
        MODEL, "_mamba", _with_mamba_leaf(_no_dt_bias),
        "dt = softplus(r W_dt) without its bias"),
    "mean_decay": _fault(
        MODEL, "_mamba", _with_mamba_leaf(_mean_a),
        "A's columns replaced by their mean"),
    "taps_ahead": _fault(
        MODEL, "causal_conv", _taps_ahead, "the taps looking ahead"),
    "no_lambda_init": _fault(
        MODEL, "_lambda", lambda original: lambda p, l0: original(p, 0.0),
        "lambda without lambda0"),
    "no_output_scale": _fault(
        MODEL, "_scale", lambda original: lambda l0: 1.0,
        "the (1 - lambda0) left out"),
    "no_pair_norm": _fault(
        MODEL, "_pair_norm", lambda original: lambda o, eps: o,
        "the norm over a pair's 2 d left out"),
    "window_on_full": _fault(
        MODEL, "_rule", _swap_rule(phi4flash.FULL),
        "the full layer under the window"),
    "full_on_window": _fault(
        MODEL, "_rule", _swap_rule(phi4flash.WINDOW),
        "the window layer without its window"),
    "cross_reads_window_keys": _fault(
        MODEL, "_hands_on_keys",
        lambda original: lambda kind: kind == phi4flash.WINDOW,
        "the cross layer reading the window layer's keys and values"),
    "memory_after_gate": _fault(
        MODEL, "_memory", lambda original: lambda y, gated: gated,
        "the memory taken after the gate"),
    "eight_bit_matrices": _fault(
        "ray_tpu.models.layers", "cast_weights", _eight_bit_matrices,
        "the matrices through float8_e4m3fn"),
}


def readings(config: dict, seed: int, names, losses: bool = True,
             batch: int = 1, seq: int = 16384):
    """Yield (name, the streams' errors, |system - reference| of the first
    losses or None) for the program as it is (`sound`) and under each fault
    of ``names``: the reference's streams and steps run once, on the first
    device; every family's walk and step on the cell's first batches."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import phi4flash as reference

    steps = config["reference"]["steps"]
    rng = np.random.default_rng([seed, 7])
    tokens = rng.integers(0, config["vocab_size"], (batch, seq + 1),
                          dtype=np.int32)
    families = {"sound": Faulty(config)}
    families.update({name: FAULTS[name](config) for name in names})
    for family in families.values():
        family.bind(jax.devices()[:1])
    sound = families["sound"]
    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda key: phi4flash.to_reference(sound._init(key)))(
            jax.device_put(jax.random.PRNGKey(seed), sound.devices[0]))
    inputs = jnp.asarray(tokens[0, :-1])
    want = sound.reference_streams(params, inputs)
    errors = {name: family.first_streams(params, inputs, want)
              for name, family in families.items()}
    del want
    if not losses:
        for name in families:
            yield name, errors[name], None
        return
    with jax.default_matmul_precision("highest"):
        ref_losses = reference.first_losses(
            params, jnp.asarray(np.stack([tokens] * steps)),
            sound.reference_sizes(), config["optimizer"])
    for name, family in families.items():
        state = family.init_state(seed)
        data = family.place_batch(tokens)
        compiled = family.lower_step(*state, data).compile()
        got = []
        for _ in range(steps):
            *state, out = compiled(*state, data)
            got.append(float(out["loss"]))
        for leaf in jax.tree.leaves(state):
            leaf.delete()
        yield name, errors[name], [abs(g - w)
                                   for g, w in zip(got, ref_losses)]


def main():
    import argparse
    import json

    from benchmark.harness import registry

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--faults", nargs="*", default=sorted(FAULTS))
    parser.add_argument("--no-losses", action="store_true")
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()
    config = registry.config("phi-4-mini-flash-reasoning-vp8", args.rehearse)
    sizes = {"seq": 128} if args.rehearse else {}
    for name, errors, losses in readings(config, args.seed, args.faults,
                                         not args.no_losses, **sizes):
        print(json.dumps({"fault": name, "seed": args.seed,
                          "stream_errors": errors, "loss_errors": losses}),
              flush=True)


if __name__ == "__main__":
    main()
