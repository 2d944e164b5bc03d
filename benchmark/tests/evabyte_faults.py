"""Seeded faults of the `evabyte` family: each a family that departs from
what the configuration states in one way, for `test_evabyte_cell.py` and
`tests/test_evabyte.py` (at small sizes) and for the readings on the chip
that the configuration's limits are set between
(`reference.loss_tolerance_reason`).  A fault is a patch of one name of the
program for as long as the family traces its step or its walk; the reference
is never touched.

    FAULTS[name] -> the family's class

On the chip, all of them in one process, the reference run once:

    python benchmark/tests/evabyte_faults.py --seed N
        [--faults NAME ...] [--no-losses]

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

from benchmark.families import evabyte  # noqa: E402
# the patch of a module's name while a trace runs does not go by the family
from benchmark.tests.mellum_faults import patched  # noqa: E402
from benchmark.tests.phi4flash_faults import _eight_bit_matrices  # noqa: E402


class Faulty(evabyte.Family):
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


MODEL = "ray_tpu.models.evabyte"
OPS = "ray_tpu.ops.eva"


def _with_vector(which, value):
    """EVA attention with phi or mu replaced: mu = 0 drops it, phi = 0 makes
    the pooling's softmax uniform."""
    def wrap(original):
        def eva_attention(q, k, v, phi, mu, **sizes):
            import jax.numpy as jnp

            vectors = {"phi": phi, "mu": mu}
            vectors[which] = jnp.full_like(vectors[which], value)
            return original(q, k, v, vectors["phi"], vectors["mu"], **sizes)
        return eva_attention
    return wrap


def _masked(remote="earlier", apart=False, block=128):
    """EVA attention as masks over all keys and all summaries, ``block``
    query rows at a time, with one departure.  ``remote``: which summaries a
    query attends: those of "earlier" windows (sound), of earlier windows
    and its "own", or of "all" windows, the later ones among them.
    ``apart``: the two sources normalised apart and their results averaged
    (window 0's queries, which have no summary, keep their local result)."""
    def wrap(original):
        def eva_attention(q, k, v, phi, mu, *, window, chunk):
            import jax
            import jax.numpy as jnp

            B, S, H, D = q.shape
            f32 = lambda x: x.astype(jnp.float32)
            N = S // chunk
            kc, vc = (f32(x).reshape(B, N, chunk, H, D) for x in (k, v))
            a = jax.nn.softmax(jnp.einsum("bnchd,hd->bnch", kc, f32(phi)),
                               axis=2)[..., None]
            ks = jnp.sum(a * kc, axis=2) + f32(mu)
            vs = jnp.sum(a * vc, axis=2)
            rows = min(block, S)

            @jax.checkpoint
            def some(start):
                i = (start + jnp.arange(rows))[:, None]
                j, n = jnp.arange(S)[None], jnp.arange(N)[None]
                own = (j // window == i // window) & (j <= i)
                of = (n * chunk) // window
                seen = {"earlier": of < i // window, "own": of <= i // window,
                        "all": of >= 0}[remote]
                qb = f32(jax.lax.dynamic_slice_in_dim(q, start, rows, axis=1))
                s1 = jnp.where(own, jnp.einsum(
                    "bqhd,bkhd->bhqk", qb, f32(k)) * D ** -0.5, -jnp.inf)
                s2 = jnp.where(seen, jnp.einsum(
                    "bqhd,bnhd->bhqn", qb, ks) * D ** -0.5, -jnp.inf)
                if apart:
                    o1 = jnp.einsum("bhqk,bkhd->bqhd",
                                    jax.nn.softmax(s1, -1), f32(v))
                    p2 = jnp.where(seen, jax.nn.softmax(s2, -1), 0.0)
                    o2 = jnp.einsum("bhqn,bnhd->bqhd", p2, vs)
                    alone = (i // window == 0)[None, :, :, None]
                    return jnp.where(alone, o1, 0.5 * (o1 + o2))
                p = jax.nn.softmax(jnp.concatenate([s1, s2], -1), -1)
                return jnp.einsum("bhqk,bkhd->bqhd", p[..., :S], f32(v)) \
                    + jnp.einsum("bhqn,bnhd->bqhd", p[..., S:], vs)

            out = jax.lax.map(some, jnp.arange(0, S, rows))
            return jnp.moveaxis(out, 0, 1).reshape(q.shape).astype(q.dtype)
        return eva_attention
    return wrap


def _local_rule(make):
    """The local half under another rule of the flash kernels:
    ``make(window)`` in place of `BlockRule(aligned=window)`."""
    def wrap(original):
        return lambda aligned: make(original, aligned)
    return wrap


def _no_summary_gradient(original):
    def pool(*args):
        import jax

        return jax.lax.stop_gradient(original(*args))
    return pool


def _gain_alone(original):
    """A norm's gain w and not 1 + w (w starts at 0: the norm gives 0)."""
    def norm(x, p, cfg):
        import dataclasses

        return original(x, p, dataclasses.replace(cfg,
                                                  norm_unit_offset=False))
    return norm


def _target_a_byte_early(original):
    """Head p reads the byte at i + p: head 0 its own input."""
    def targets(tokens, p):
        import jax.numpy as jnp

        S = tokens.shape[1] - 1
        at = jnp.arange(S)
        return (jnp.take(tokens, jnp.minimum(at + p, S), axis=1),
                original(tokens, p)[1])
    return targets


class Bf16Stream(Faulty):
    """The residual stream in bfloat16, not float32 (`fp32_skip_add` off)."""

    def model_config(self):
        import dataclasses

        cfg = super().model_config()
        return dataclasses.replace(cfg, stream_dtype=cfg.compute_dtype)


FAULTS = {
    "no_mu": _fault(MODEL, "eva_attention", _with_vector("mu", 0.0),
                    "the summaries' keys without mu"),
    "uniform_pooling": _fault(
        MODEL, "eva_attention", _with_vector("phi", 0.0),
        "a chunk's mean in place of softmax(k . phi)"),
    "own_window_summaries": _fault(
        MODEL, "eva_attention", _masked(remote="own"),
        "the query's own window's summaries also attended"),
    "later_summaries": _fault(
        MODEL, "eva_attention", _masked(remote="all"),
        "the summaries of every window attended, the later ones too"),
    "local_full_causal": _fault(
        OPS, "BlockRule", _local_rule(lambda rule, w: rule()),
        "the local half causal over the whole sequence"),
    "local_sliding": _fault(
        OPS, "BlockRule", _local_rule(lambda rule, w: rule(window=w)),
        "the local half a sliding window of 2,048"),
    "halves_apart": _fault(
        MODEL, "eva_attention", _masked(apart=True),
        "the two halves normalised apart and averaged"),
    "no_summary_gradient": _fault(
        OPS, "_pool", _no_summary_gradient,
        "no gradient through the summaries (the streams are sound: the "
        "losses from step 1 on)"),
    "gain_alone": _fault(MODEL, "_norm", _gain_alone,
                         "a norm's gain w, not 1 + w"),
    "target_a_byte_early": _fault(
        MODEL, "_targets", _target_a_byte_early,
        "head p reading the byte at i + p (the streams are sound: the "
        "losses)"),
    "bf16_stream": Bf16Stream,
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

    from benchmark.reference import evabyte as reference

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
        params = jax.jit(lambda key: evabyte.to_reference(sound._init(key)))(
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
    config = registry.config("evabyte-6.5b-4layer", args.rehearse)
    sizes = {"seq": 128} if args.rehearse else {}
    for name, errors, losses in readings(config, args.seed, args.faults,
                                         not args.no_losses, **sizes):
        print(json.dumps({"fault": name, "seed": args.seed,
                          "stream_errors": errors, "loss_errors": losses}),
              flush=True)


if __name__ == "__main__":
    main()
