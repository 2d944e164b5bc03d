"""Seeded faults of the `sdar` family: each a family that departs from what
the configuration states in one way, for `test_sdar_cell.py` (at the
rehearsal's sizes) and for the readings on the chip that the configuration's
limits are set between (`reference.loss_tolerance_reason`).  A fault is a
patch of one name of the program for as long as the family traces its step
or its first layer; the reference is never touched.

    FAULTS[name] -> the family's class

A benchmark checkout gets one as `benchmark/families/sdar_<name>.py`:
`from benchmark.tests.sdar_faults import FAULTS; Family = FAULTS[<name>]`
(`install`).
"""

from __future__ import annotations

import contextlib
import json
import os

from benchmark.families import sdar


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


class Faulty(sdar.Family):
    """A family whose step and first layer are traced under `patch()`."""

    def patch(self):
        return contextlib.nullcontext()

    def lower_step(self, params, opt_state, batch):
        with self.patch():
            return super().lower_step(params, opt_state, batch)

    def first_layer(self, params, tokens, seed):
        with self.patch():
            return super().first_layer(params, tokens, seed)


def _mask_fault(change):
    """A family whose kernels' predicate is ``change(original)``."""
    class Family(Faulty):
        def patch(self):
            from ray_tpu.ops import flash_attention

            return patched(flash_attention, "_rule_mask", change)
    return Family


def _own_block_left_out(original):
    """A noised row blind to the noised rows of its own block."""
    def mask(s, rule, q_start, k_start, how, strict=0):
        if how == "own":
            return s * 0 - 1e30
        return original(s, rule, q_start, k_start, how, strict)
    return mask


def _leak(original):
    """A noised row sees the CLEAN rows of its own block: block(j) <=
    block(i) where the rule says <."""
    def mask(s, rule, q_start, k_start, how, strict=0):
        return original(s, rule, q_start, k_start, how, 0)
    return mask


def _diagonal_for_the_block(original):
    """A clean row blind to the later clean rows of its own block: the
    diagonal where the rule says the block."""
    def mask(s, rule, q_start, k_start, how, strict=0):
        import jax
        import jax.numpy as jnp

        if how == "own":
            return original(s, rule, q_start, k_start, how, strict)
        q_at = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_at = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        diagonal = jnp.where(q_at >= k_at, s, -1e30)
        return jnp.where(jnp.asarray(strict) == 1,
                         original(s, rule, q_start, k_start, how, strict),
                         diagonal)
    return mask


class WeightDropped(Faulty):
    """A masked row weighs 1 where the objective says 1 / t."""

    def patch(self):
        from ray_tpu.models import sdar as model

        def unweighted(original):
            def draw(*args):
                masked, _ = original(*args)
                return masked, masked.astype("float32")
            return draw
        return patched(model, "draw_noise", unweighted)


class EightBitAttention(Faulty):
    """q, k and v through `float8_e4m3fn` before the kernels."""

    def patch(self):
        from ray_tpu.models import sdar as model

        def rounded(original):
            def attention(q, k, v, **kw):
                import jax.numpy as jnp

                low = lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
                return original(low(q), low(k), low(v), **kw)
            return attention
        return patched(model, "attention", rounded)


class TargetsShifted(Faulty):
    """Row n_i is held to x_{i+1}: the shift a next-token model makes."""

    def patch(self):
        from ray_tpu.models import sdar as model

        def shifted(original):
            def loss(x, head, targets, weights, chunk_rows):
                import jax.numpy as jnp

                return original(x, head, jnp.roll(targets, -1, axis=1),
                                weights, chunk_rows)
            return loss
        return patched(model, "head_and_weighted_loss", shifted)


class WrongRate(sdar.Family):
    """Three times the learning rate the configuration states."""

    def optimizer(self):
        from benchmark.reference.sdar import adamw

        settings = dict(self.config["optimizer"])
        settings["learning_rate"] *= 3
        return adamw(settings)


FAULTS = {
    "own_block_left_out": _mask_fault(_own_block_left_out),
    "leak": _mask_fault(_leak),
    "diagonal_for_the_block": _mask_fault(_diagonal_for_the_block),
    "weight_dropped": WeightDropped,
    "eight_bit_attention": EightBitAttention,
    "targets_shifted": TargetsShifted,
    "wrong_rate": WrongRate,
}


def install(root: str, source_root: str, fault: str,
            config_name: str = "sdar-30b-a3b-chat-ep8") -> str:
    """Into the benchmark checkout at ``root`` (a copy of BENCHMARK.json and
    benchmark/): the family `sdar_<fault>`, a configuration of it and a
    cell under `resident-8k` -> the cell's name."""
    with open(os.path.join(root, "benchmark", "families",
                           f"sdar_{fault}.py"), "w") as f:
        f.write("from benchmark.tests.sdar_faults import FAULTS\n\n"
                f"Family = FAULTS[{fault!r}]\n")
    with open(os.path.join(source_root, "benchmark", "configs",
                           f"{config_name}.json")) as f:
        config = json.load(f)
    name = f"sdar-{fault.replace('_', '-')}"
    config.update(name=name, family=f"sdar_{fault}")
    with open(os.path.join(root, "benchmark", "configs", f"{name}.json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": name, "source": "test", "reduced": [], "why": "test",
        "file": f"benchmark/configs/{name}.json"})
    bench["workloads"].append({
        "name": f"{name}.resident-8k", "config": name,
        "traffic": "resident-8k", "chips": 1, "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return f"{name}.resident-8k"
