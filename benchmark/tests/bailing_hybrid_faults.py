"""Seeded faults of the `bailing_hybrid` family: each a family that departs
from what the configuration states in one way, for
`test_bailing_hybrid_cell.py` and `tests/test_bailing_hybrid.py` (at small
sizes) and for the readings on the chip that the configuration's limits are
set between (`reference.loss_tolerance_reason`).  A fault is a patch of one
name of the program for as long as the family traces its step or its walk;
the reference is never touched.

    FAULTS[name] -> the family's class

On the chip, all of them in one process, the reference run once:

    python benchmark/tests/bailing_hybrid_faults.py --seed N
        [--faults NAME ...] [--no-losses]

prints, a fault (and first for the program as it is, `sound`), the stream's
error after each layer held on the first sequence and the three losses'
distances from the reference's.
"""

from __future__ import annotations

import contextlib
import functools
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.families import bailing_hybrid  # noqa: E402
# the patch of a module's name while a trace runs does not go by the family
from benchmark.tests.mellum_faults import patched  # noqa: E402
from benchmark.tests.phi4flash_faults import (  # noqa: E402
    _eight_bit_matrices,
    _taps_ahead,
)


class Faulty(bailing_hybrid.Family):
    """A family whose step and walk are traced under `patch()`."""

    def patch(self):
        return contextlib.nullcontext()

    def lower_step(self, params, opt_state, batch):
        with self.patch():
            return super().lower_step(params, opt_state, batch)

    def first_streams(self, params, biases, inputs, want=None):
        with self.patch():
            return super().first_streams(params, biases, inputs, want)


def _fault(module_name, name, change, doc):
    """A family under which `<module_name>.<name>` is ``change(original)``."""
    class Family(Faulty):
        __doc__ = doc

        def patch(self):
            import importlib

            return patched(importlib.import_module(module_name), name, change)
    return Family


MODEL = "ray_tpu.models.bailing_hybrid"


def _decay_a_head(original):
    """The decay one factor a head: its channels' logs replaced by their
    mean, which is what a rule with a scalar decay a head computes."""
    def decay(f, p, cfg):
        import jax.numpy as jnp

        g = original(f, p, cfg)
        return jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    return decay


def _unbounded_decay(original):
    """The paper's form without the bound: g = -exp(A_log) softplus(.)."""
    def decay(f, p, cfg):
        import jax
        import jax.numpy as jnp

        B, S, _ = f.shape
        x = (f.astype(jnp.float32) + p["dt_bias"]).reshape(
            B, S, cfg.n_head, cfg.head_dim)
        return -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(x)
    return decay


def _no_correction(original):
    """The rule without its k k' term: S_t = Diag(alpha_t) S_{t-1} + beta_t
    k_t v_t', a gated linear attention, position by position."""
    def rule(q, k, v, g, beta, chunk=64):
        import jax
        import jax.numpy as jnp

        f32 = lambda x: jnp.moveaxis(x.astype(jnp.float32), 1, 0)

        def position(state, t):
            qt, kt, vt, gt, bt = t
            state = jnp.exp(gt)[..., None] * state \
                + (bt[..., None] * kt)[..., None] * vt[..., None, :]
            return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

        B, _, H, K = q.shape
        _, o = jax.lax.scan(
            position, jnp.zeros((B, H, K, v.shape[-1]), jnp.float32),
            tuple(f32(x) for x in (q, k, v, g, beta)))
        return jnp.moveaxis(o, 0, 1).astype(q.dtype)
    return rule


def _no_carry(original):
    """Every chunk starts from a state of zeros."""
    def chunk_forward(q, k, kb, vb, g, state, want_o=True):
        import jax.numpy as jnp

        return original(q, k, kb, vb, g, jnp.zeros_like(state), want_o)
    return chunk_forward


def _ungated_latent(original):
    return lambda x, p, cfg, gated=False: original(x, p, cfg, gated=False)


def _route_with(**changed):
    def wrap(original):
        return lambda cfg: functools.partial(original(cfg), **changed)
    return wrap


def _largest_alone(original):
    """A group's score its largest pick, not the sum of its two largest."""
    def in_best_groups(picks, n_group, topk_group):
        import jax
        import jax.numpy as jnp

        T, N = picks.shape
        best = jnp.max(picks.reshape(T, n_group, N // n_group), axis=-1)
        _, groups = jax.lax.top_k(best, topk_group)
        kept = jnp.any(groups[:, :, None] == jnp.arange(n_group)[None, None],
                       axis=1)
        return jnp.where(jnp.repeat(kept, N // n_group, axis=1), picks,
                         -jnp.inf)
    return in_best_groups


def _no_shared_expert(original):
    def routed_layer(x, p, *rest):
        return original(x, {k: v for k, v in p.items() if k != "shared"},
                        *rest)
    return routed_layer


def _ones_like_result(original):
    def ones(x):
        import jax.numpy as jnp

        return jnp.ones_like(original(x))
    return ones


FAULTS = {
    "decay_a_head": _fault(
        MODEL, "_decay", _decay_a_head,
        "the decay a head instead of a key channel"),
    "no_gate_bound": _fault(
        MODEL, "_decay", _unbounded_decay, "the decay without its bound"),
    "no_beta": _fault(MODEL, "_beta", _ones_like_result, "beta = 1"),
    "no_correction": _fault(
        MODEL, "kda", _no_correction, "the rule without its k k' term"),
    "no_query_scale": _fault(
        MODEL, "_query_scale", lambda original: lambda cfg: 1.0,
        "q without its 128^-1/2"),
    "taps_ahead": _fault(
        MODEL, "causal_conv", _taps_ahead, "the taps looking ahead"),
    "no_l2_norm": _fault(
        MODEL, "_l2", lambda original: lambda x, eps: x.astype("float32"),
        "q and k not normalised"),
    "no_head_norm": _fault(
        MODEL, "_head_norm",
        lambda original: lambda o, gain, eps: o.astype("float32"),
        "o without the norm over a head"),
    "no_output_gate": _fault(
        MODEL, "_out_gate", _ones_like_result, "o without sigmoid(u W_g)"),
    "no_latent_gate": _fault(
        MODEL, "latent_attention", _ungated_latent,
        "MLA's heads without their gate"),
    "one_group": _fault(
        MODEL, "_route", _route_with(n_group=1, topk_group=1),
        "the top k over all the experts, no groups"),
    "group_by_largest": _fault(
        "ray_tpu.ops.moe", "_in_best_groups", _largest_alone,
        "a group scored by its largest pick alone"),
    "no_routed_scale": _fault(
        MODEL, "_route", _route_with(scale=1.0), "the 2.5 left out"),
    "no_shared_expert": _fault(
        MODEL, "routed_layer", _no_shared_expert,
        "the shared expert left out"),
    "no_carry": _fault(
        "ray_tpu.ops.kda", "_chunk_forward", _no_carry,
        "the state not carried from chunk to chunk"),
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

    from benchmark.reference import bailing_hybrid as reference

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
        params, biases = jax.jit(
            lambda key: bailing_hybrid.to_reference(sound._init(key)))(
                jax.device_put(jax.random.PRNGKey(seed), sound.devices[0]))
    inputs = jnp.asarray(tokens[0, :-1])
    want = sound.reference_streams(params, biases, inputs)
    errors = {name: family.first_streams(params, biases, inputs, want)
              for name, family in families.items()}
    del want
    if not losses:
        for name in families:
            yield name, errors[name], None
        return
    with jax.default_matmul_precision("highest"):
        ref_losses = reference.first_losses(
            params, biases, jnp.asarray(np.stack([tokens] * steps)),
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
    config = registry.config("ling-3.0-flash-ep64", args.rehearse)
    sizes = {"seq": 128} if args.rehearse else {}
    for name, errors, losses in readings(config, args.seed, args.faults,
                                         not args.no_losses, **sizes):
        print(json.dumps({"fault": name, "seed": args.seed,
                          "stream_errors": errors, "loss_errors": losses}),
              flush=True)


if __name__ == "__main__":
    main()
