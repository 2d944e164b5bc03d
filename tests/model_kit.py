"""What the model test files share (`test_<model>.py` against its plain
reference, `test_shared_layers.py`): the one measure of a difference, seeded
tokens, the recipe that makes seeded weights a fault cannot hide under, and
`once`, through which a file gets its sound side (the parameters, the
reference's results of them, the system's, the jitted forward) a single time
whatever the number of tests that read it.  A plain module the test files
import (pytest puts `tests/` on the path), not a plugin; the
`highest_precision` fixture they run under is `tests/conftest.py`'s.

Everything here runs on the CPU at the tests' small sizes.  A test's cost
there is the number of programs it builds, not their arithmetic: an eager
`x + 0.2 * normal(key, x.shape)` a leaf is a hundred programs, the same
under one `jax.jit` is one.
"""

import collections
import contextlib
import functools
import inspect
import sys
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


def max_diff(a, b):
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                 - jnp.asarray(b, jnp.float32))))


# -- computed once ------------------------------------------------------------

# "module.function" -> the times `once` had to compute it
COMPUTED = collections.Counter()


def once(fn):
    """``fn`` computed once for equal arguments (hashable; a default and
    the same value given count as equal) and again for unequal ones, kept
    for the session: what the model files keep is of their small sizes (a
    model's parameters under a MiB, its logits a quarter of one).  What is
    served is a new tree around the same leaves, so a test that sets a key
    of its parameters changes its own copy; a caller whose callee DONATES
    them (every reference's `first_losses` does) hands over copies, `own`.
    ``fn.__wrapped__`` computes and keeps nothing; `COMPUTED` counts the
    computations."""
    kept = {}
    name = f"{fn.__module__}.{fn.__qualname__}"
    signature = inspect.signature(fn)

    @functools.wraps(fn)
    def served(*args, **kwargs):
        bound = signature.bind(*args, **kwargs)
        bound.apply_defaults()
        key = tuple(bound.arguments.items())
        if key not in kept:
            COMPUTED[name] += 1
            kept[key] = fn(*args, **kwargs)
        return jax.tree.map(lambda leaf: leaf, kept[key])

    served.name = name
    return served


def own(tree):
    """``tree`` with leaves of the caller's own, for a callee that donates
    what it is given."""
    return jax.tree.map(jnp.copy, tree)


@once
def tokens(seed, batch, seq, vocab):
    """(batch, seq + 1) seeded tokens: the inputs and, shifted, their
    targets."""
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0,
                              vocab)


@contextlib.contextmanager
def patches_undone(package="ray_tpu."):
    """On leaving the block, every function and class of the package's
    loaded modules is the object it was on entering: what a seeded fault's
    patch owes the tests behind it."""
    before = {name: {k: v for k, v in vars(module).items() if callable(v)}
              for name, module in list(sys.modules.items())
              if name.startswith(package) and module is not None}
    yield
    for name, was in before.items():
        now = vars(sys.modules[name])
        changed = [k for k, v in was.items() if now.get(k) is not v]
        assert not changed, (name, changed)


# -- seeded weights a fault cannot hide under ---------------------------------

class Vector(NamedTuple):
    """A leaf that is not left as it starts, or every leaf of a branch in
    jax's order: it becomes ``start + plus + scale * normal(key)``, where
    ``start`` is the leaf itself unless given.  ``key`` seeds a key of the
    vector's own; without one it takes the next key of `widened`'s
    ``sequence``, a leaf a key."""
    path: tuple
    scale: float = 0.0
    key: Optional[int] = None
    start: Optional[float] = None
    plus: float = 0.0


def widened(params, vectors=(), *, factor=4.0, narrow=(), sequence=None):
    """``params`` with every matrix ``factor`` times as wide as drawn and
    the ``vectors`` moved: at the assumed 0.02 and the tests' widths a
    mixer's output is a thousandth of the residual stream, and gains of 1
    and biases of 0 make many a fault no fault.

    ``narrow`` names the matrices left as drawn (a leaf so named, or held
    by a branch so named: a convolution's taps).  ``sequence`` is (seed,
    count) of the split that gives the vectors without a key of their own
    theirs, in the order of ``vectors`` (a split's keys depend on its
    count).

    Three jitted programs whatever the number of leaves: the draws, their
    products with the scales, and the sums with the widening.  Each
    operation rounds on its own, as it did when every one was an eager
    program of its own and the files' margins were set: in ONE program XLA
    folds ``scale * (sqrt(2) * erfinv(u))`` into one constant and LLVM fuses
    the multiply-add, and a gain comes out an ulp away."""
    leaf_of = lambda tree, path: functools.reduce(
        lambda node, name: node[name], path, tree)
    moved = [(v.path + tuple(k.key for k in path), v) for v in vectors
             for path, _ in jax.tree_util.tree_flatten_with_path(
                 leaf_of(params, v.path))[0]]
    noisy = [(path, v) for path, v in moved if v.scale]
    given = iter(()) if sequence is None else iter(jax.random.split(
        jax.random.PRNGKey(sequence[0]), sequence[1]))
    keys = [next(given) if v.key is None else jax.random.PRNGKey(v.key)
            for _, v in noisy]
    shapes = [leaf_of(params, path).shape for path, _ in noisy]
    draws = jax.jit(lambda keys: [
        jax.random.normal(key, shape) for key, shape in zip(keys, shapes)])(
            keys)
    products = jax.jit(lambda draws: [
        v.scale * n for (_, v), n in zip(noisy, draws)])(draws)

    def rest(params, products):
        def wide(path, x):
            names = {getattr(k, "key", k) for k in path[-2:]}
            return factor * x if x.ndim >= 2 and not names & set(narrow) \
                else x

        params = jax.tree_util.tree_map_with_path(wide, params)
        noise = dict(zip((path for path, _ in noisy), products))
        for path, v in moved:
            holder = leaf_of(params, path[:-1])
            x = holder[path[-1]]
            x = x if v.start is None else jnp.full_like(x, v.start)
            x = x + v.plus if v.plus else x
            holder[path[-1]] = x + noise[path] if path in noise else x
        return params

    return jax.jit(rest)(params, products)


def drawn(init, seed, vectors=(), **recipe):
    """``init(jax.random.PRNGKey(seed))`` `widened`.  The drawing stays
    eager for the same reason: an initialiser's ``std * normal`` under a
    jit folds its constants and rounds otherwise, and its programs are one
    a shape, found again by every later call."""
    return widened(init(jax.random.PRNGKey(seed)), tuple(vectors), **recipe)
