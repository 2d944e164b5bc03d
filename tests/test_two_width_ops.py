"""What latent attention and a held share of the experts ask of the shared
code: attention whose q and k have one width and v another, in both forms
of the kernels; `moe_dispatch` told which experts are held, over a buffer
of the share's rows where the share is small and over all the routed rows
where a step overflows it; and that neither moved what equal widths and
all-held calls get."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_attention as fa
from ray_tpu.models import layers
from ray_tpu.ops import moe
from ray_tpu.ops.moe import moe_dispatch
from ray_tpu.parallel.attention import attention


def _qkv(B, S, H, D, Dv, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(S + D), 3)
    return (jax.random.normal(ks[0], (B, S, H, D), dtype),
            jax.random.normal(ks[1], (B, S, H, D), dtype),
            jax.random.normal(ks[2], (B, S, H, Dv), dtype))


def _tr(x):
    return x.transpose(0, 2, 1, 3)


def _value_and_grads(f, q, k, v):
    def loss(q, k, v):
        o = f(q, k, v)
        return jnp.sum(jnp.sin(o.astype(jnp.float32))), o
    (_, o), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(q, k, v)
    return (o, *grads)


def _kernels(f, *args):
    """Names of the kernel functions of the `pallas_call`s in f's jaxpr,
    nested ones (jit, the platform's branches, custom_vjp) included."""
    from jax.extend import core as jex_core

    def walk(jaxpr, found):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.add(eqn.params["jaxpr"].debug_info.func_name)
            for value in eqn.params.values():
                for item in value if isinstance(value, (tuple, list)) \
                        else (value,):
                    if isinstance(item, jex_core.ClosedJaxpr):
                        walk(item.jaxpr, found)
                    elif isinstance(item, jex_core.Jaxpr):
                        walk(item, found)
        return found

    return walk(jax.make_jaxpr(f)(*args).jaxpr, set())


def _grad_of_attention(q, k, v):
    return jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention_bshd(
        q, k, v, True).astype(jnp.float32)), (0, 1, 2))(q, k, v)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("form", ["whole", "long"])
@pytest.mark.parametrize("dims", [(48, 32), (24, 16), (32, 64)])
def test_attention_with_two_widths_matches_the_reference(
        dims, form, causal, monkeypatch):
    """q, k `D` wide and v, o `Dv` wide (MLA: 192 and 128) against
    `reference_attention`: o and all three gradients, through the model's
    entry (`parallel/attention.py`: (B, S, H, D), head_dim^-1/2 of q's
    width), in the form a grid step takes the whole sequence in (S <=
    `_WHOLE_SEQ_MAX`) and in the long one (lowered here so that an
    interpretable size passes it: q tiles looping over k blocks, the
    one backward kernel's k tiles looping over q blocks)."""
    D, Dv = dims
    if form == "long":
        monkeypatch.setattr(fa, "_WHOLE_SEQ_MAX", 128)
    q, k, v = _qkv(1, 256, 2, D, Dv)

    def kernel(q, k, v):
        return fa.flash_attention_bshd(q, k, v, causal, None, 128, 128)

    def reference(q, k, v):
        o, _ = fa.reference_attention(_tr(q), _tr(k), _tr(v), D ** -0.5,
                                      causal)
        return _tr(o)

    got = _value_and_grads(kernel, q, k, v)
    want = _value_and_grads(reference, q, k, v)
    assert got[0].shape == (1, 256, 2, Dv) and got[3].shape == v.shape
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert float(jnp.max(jnp.abs(g - w))) < 2e-5, name
    if causal:
        entry = _value_and_grads(lambda q, k, v: attention(q, k, v), q, k, v)
        for g, w in zip(entry, want):
            assert float(jnp.max(jnp.abs(g - w))) < 2e-5


def test_two_widths_take_the_head_major_kernels_and_no_fallback():
    """Widths that differ cannot share lanes: the call goes head-major
    (one head a grid step), never to the O(S^2) reference."""
    import warnings

    q, k, v = _qkv(1, 256, 2, 128, 64, jnp.bfloat16)
    with warnings.catch_warnings():
        warnings.simplefilter("error", fa.AttentionFallbackWarning)
        found = _kernels(_grad_of_attention, q, k, v)
    assert found == {"_fwd_kernel", "_bwd_fused_kernel"}


def test_equal_widths_get_what_they_got():
    """The lane layout for heads that fill lanes, the default compiler
    parameters at every shape a cell had, and a higher VMEM limit only
    where the sequence-long operands need it (S = 8,192 at 192 / 128)."""
    q, k, v = _qkv(1, 256, 2, 64, 64, jnp.bfloat16)
    assert _kernels(_grad_of_attention, q, k, v) == {
        "_fwd_kernel_lanes", "_bwd_fused_kernel_lanes"}
    assert fa._bshd_lanes_ok(q, 256, 128, 128)
    for S, D in ((1024, 64), (2048, 128), (4096, 128)):
        assert fa._compiler_params(S, D, D, jnp.bfloat16) \
            is fa._COMPILER_PARAMS
    raised = fa._compiler_params(8192, 192, 128, jnp.bfloat16)
    assert raised.vmem_limit_bytes == (16 + 4 * 12) << 20
    assert raised.dimension_semantics == \
        fa._COMPILER_PARAMS.dimension_semantics
    # the backward past `_WHOLE_SEQ_MAX` holds a slice's q, do, statistics,
    # dq and its scratch while the k tiles pass, in order: 5.5 KB a row
    long = fa._compiler_params(8192, 192, 128, jnp.bfloat16,
                               bwd_steps=16)
    assert long.vmem_limit_bytes == (16 << 20) + 8192 * 5632
    assert long.dimension_semantics == ("parallel", "arbitrary")
    # the tiles are a function of S and causal alone, as before
    assert fa._auto_tiles(1024, True) == ((512, 512), (256, 256))
    assert fa._auto_tiles(4096, True) == ((1024, 1024), (512, 512))
    assert fa._auto_tiles(8192, False) == ((1024, 1024), (512, 512))


def _experts(n=8, e=16, w=8):
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    gate = jax.random.normal(ks[0], (n, e, w))
    down = jax.random.normal(ks[1], (n, w, e))

    def run(gate, down):
        return lambda xs, sizes: jax.lax.ragged_dot(
            jax.nn.silu(jax.lax.ragged_dot(xs, gate, sizes)), down, sizes)
    return gate, down, run


def _routing(T=64, n=8, k=3, skew=0.0):
    logits = jax.random.normal(jax.random.PRNGKey(4), (T, n))
    logits = logits + skew * jnp.arange(n)        # the last experts fill up
    return jax.lax.top_k(jax.nn.softmax(logits), k)


@pytest.mark.parametrize("skew", [0.0, 3.0])
def test_dispatch_to_held_experts_alone(skew):
    """Each share computes every row sent to its experts and nothing for
    the others; the shares' sum is the whole; the counts are over ALL the
    experts in every share; an absent expert's matrices get no gradient
    and the rows sent to it bring none back."""
    gate, down, run = _experts()
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 16))
    weights, experts = _routing(skew=skew)
    whole, sizes = moe_dispatch(x, weights, experts, 8, run(gate, down))
    assert int(sizes.sum()) == 64 * 3

    def share(x, weights, gate, down, first, count=2):
        return moe_dispatch(
            x, weights, experts, 8,
            run(gate[first:first + count], down[first:first + count]),
            held=(first, count))

    parts = [share(x, weights, gate, down, first) for first in range(0, 8, 2)]
    assert float(jnp.max(jnp.abs(sum(y for y, _ in parts) - whole))) < 1e-4
    for _, sent in parts:
        assert (np.asarray(sent) == np.asarray(sizes)).all()

    first = 6 if skew else 2        # under the skew the last two fill up
    grads = jax.grad(lambda *a: jnp.sum(share(*a, first)[0] ** 2),
                     (0, 1, 2, 3))(x, weights, gate, down)
    held = np.zeros(8, bool)
    held[first:first + 2] = True
    for g in grads[2:]:
        per_expert = np.abs(np.asarray(g)).reshape(8, -1).max(axis=1)
        assert (per_expert[~held] == 0).all() and (per_expert[held] > 0).all()
    # a choice of an absent expert weighs nothing here
    absent = ~np.isin(np.asarray(experts), (first, first + 1))
    assert not np.asarray(grads[1])[absent].any()
    assert np.asarray(grads[1])[~absent].any()
    # a token none of whose choices is held gets nothing and gives nothing
    nothing = absent.all(axis=1)
    if nothing.any():
        y = share(x, weights, gate, down, first)[0]
        assert not np.asarray(y)[nothing].any()
        assert not np.asarray(grads[0])[nothing].any()


def test_all_held_is_the_call_it_was_bit_for_bit():
    gate, down, run = _experts()
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 16))
    weights, experts = _routing()
    plain = jax.jit(lambda x: moe_dispatch(
        x, weights, experts, 8, run(gate, down)))
    told = jax.jit(lambda x: moe_dispatch(
        x, weights, experts, 8, run(gate, down), held=(0, 8)))
    (y0, n0), (y1, n1) = plain(x), told(x)
    assert (np.asarray(y0) == np.asarray(y1)).all()
    assert (np.asarray(n0) == np.asarray(n1)).all()
    # and a call that names no share has no mask and no second key
    count = lambda f: str(jax.make_jaxpr(f)(x)).count("select_n")
    assert count(plain) < count(told)


def _dispatch_over_all_rows(x, weights, experts, n_experts, run_experts,
                            held=None):
    """`moe_dispatch` as it was before a share had a buffer of its own
    (PR 32), to the letter but for the counters: every call over all T*k
    rows.  What the buffered path is held to, in values and gradients, and
    the program that all-held calls and large shares must still be."""
    _permute_rows = moe._permute_rows
    T, k = experts.shape
    first, count = held or (0, n_experts)
    flat = experts.reshape(T * k)
    rows = jnp.arange(T * k, dtype=jnp.int32)
    keys = flat
    if held:
        here = (flat >= first) & (flat < first + count)
        keys = jnp.where(here, flat, n_experts)
    _, order = jax.lax.sort((keys, rows), num_keys=1)
    _, inverse = jax.lax.sort((order, rows), num_keys=1)
    group_sizes = jnp.sum(
        flat[:, None] == jnp.arange(n_experts, dtype=flat.dtype)[None],
        axis=0, dtype=jnp.int32)
    xs = _permute_rows(jnp.repeat(x, k, axis=0), order, inverse)
    if held:
        sizes = group_sizes[first:first + count]
        grouped = (rows < jnp.sum(sizes))[:, None]
        ys = jnp.where(grouped, run_experts(
            jnp.where(grouped, xs, 0), sizes), 0)
    else:
        ys = run_experts(xs, group_sizes)
    ys = _permute_rows(ys, inverse, order).reshape(T, k, -1)
    y = jnp.sum(ys.astype(jnp.float32) * weights[..., None], axis=1)
    return y.astype(x.dtype), group_sizes


def _sent_to_first_two(n_rows, T=64, k=3):
    """A routing (T, k) over 8 experts that sends experts 0 and 1 exactly
    ``n_rows`` rows between them: the first tokens choose both (and one of
    the others), one more chooses expert 0 if the count is odd, the rest
    choose among 2..7."""
    experts = np.stack([2 + (np.arange(T) + j) % 6 for j in range(k)], axis=1)
    experts[:n_rows // 2, :2] = (0, 1)
    if n_rows % 2:
        experts[n_rows // 2, 1] = 0
    assert np.isin(experts, (0, 1)).sum() == n_rows
    weights = jax.nn.softmax(
        jax.random.normal(jax.random.PRNGKey(6), (T, k)))
    return weights, jnp.asarray(experts, jnp.int32)


def _share_of(dispatch, experts, first, count=2):
    """(x, weights, gate, down) -> (sum of sin y, (y, rows sent)) of the
    share (first, count) of 8 experts, by ``dispatch``."""
    _, _, run = _experts()

    def loss(x, weights, gate, down):
        y, sent = dispatch(
            x, weights, experts, 8,
            run(gate[first:first + count], down[first:first + count]),
            held=(first, count))
        return jnp.sum(jnp.sin(y)), (y, sent)
    return loss


@pytest.mark.parametrize("routing", [
    "balanced", "skewed", "full_buffer", "one_row_over", "nothing_held"])
def test_a_small_share_over_its_own_buffer_is_the_whole_buffer_exactly(
        routing):
    """2 of 8 experts held, 64 tokens x 3: the buffer is 96 rows of 192.
    y, the rows sent and the gradients in x, the weights and both expert
    matrices against the T*k path in float32: on balanced routing (the
    buffer holds the share's rows: the compact branch runs); under a skew
    that sends the last two experts more than the buffer holds (the
    overflow branch runs, on the device, in the same program); with
    exactly the buffer's 96 rows and with 97; and for a share that is sent
    nothing at all."""
    gate, down, _ = _experts()
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 16))
    first = 0
    if routing == "balanced":
        (weights, experts), first = _routing(), 2
    elif routing == "skewed":
        (weights, experts), first = _routing(skew=3.0), 6
    else:
        weights, experts = _sent_to_first_two(
            {"full_buffer": 96, "one_row_over": 97, "nothing_held": 0}[
                routing])
    C = moe.buffer_rows(64 * 3, 2, 8)
    assert C == 96
    sent_here = int(np.isin(np.asarray(experts), (first, first + 1)).sum())
    overflows = routing in ("skewed", "one_row_over")
    assert (sent_here > C) == overflows
    if routing == "skewed":
        assert sent_here > 120          # about 128 of the 192

    def value_and_grads(dispatch):
        return jax.jit(jax.value_and_grad(
            _share_of(dispatch, experts, first), (0, 1, 2, 3),
            has_aux=True))(x, weights, gate, down)

    (_, (y, sent)), grads = value_and_grads(moe_dispatch)
    (_, (y0, sent0)), grads0 = value_and_grads(_dispatch_over_all_rows)
    assert (np.asarray(sent) == np.asarray(sent0)).all()
    assert float(jnp.max(jnp.abs(y - y0))) < 1e-5
    for name, g, g0 in zip(("x", "weights", "gate", "down"), grads, grads0):
        # float32 sums in another order: relative to the largest entry
        assert float(jnp.max(jnp.abs(g - g0))) < 1e-5 * (
            1 + float(jnp.max(jnp.abs(g0)))), name
    if sent_here:
        assert np.asarray(grads[0]).any() and np.asarray(grads[2]).any()
    else:
        assert not np.asarray(y).any()
        assert not any(np.asarray(g).any() for g in grads)
    # a token none of whose choices is held gets nothing and gives nothing
    nothing = ~np.isin(np.asarray(experts), (first, first + 1)).any(axis=1)
    assert nothing.any() or routing == "skewed"   # there every token does
    assert not np.asarray(y)[nothing].any()
    assert not np.asarray(grads[0])[nothing].any()


def _avals(jaxpr, found):
    """Shapes of every variable of a jaxpr, nested ones included."""
    from jax.extend import core as jex_core

    for eqn in jaxpr.eqns:
        found.update(v.aval.shape for v in eqn.outvars)
        for value in eqn.params.values():
            for item in value if isinstance(value, (tuple, list)) \
                    else (value,):
                if isinstance(item, jex_core.ClosedJaxpr):
                    _avals(item.jaxpr, found)
                elif isinstance(item, jex_core.Jaxpr):
                    _avals(item, found)
    return found


def test_the_buffered_branch_holds_no_array_of_all_the_routed_rows():
    """Forward and backward of the branch that runs when the share's rows
    fit: nothing with T*k rows is E or W wide, and nothing is (T, k, E);
    the other branch, today's path, has them (so the search finds what it
    looks for).  In the whole call both are branches of one `cond`."""
    gate, down, run = _experts()                    # E 16, W 8
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 16))
    weights, experts = _routing()
    T, k, held = 64, 3, (2, 2)
    C = moe.buffer_rows(T * k, 2, 8)

    sort = moe._sort_by_expert(experts, 8, held)    # not the branches'

    def branch(over):
        def loss(x, weights, gate, down):
            return jnp.sum(over(x, weights, *sort, held,
                                run(gate[2:4], down[2:4])))
        return _avals(jax.make_jaxpr(jax.grad(loss, (0, 1, 2, 3)))(
            x, weights, gate, down).jaxpr, set())

    wide = lambda shapes: {s for s in shapes if len(s) > 1 and (
        (s[0] == T * k and s[-1] in (16, 8)) or s[:2] == (T, k))
        and s != (T, k)}
    assert not wide(branch(functools.partial(moe._over_held_rows, C)))
    assert (C, 16) in branch(functools.partial(moe._over_held_rows, C))
    assert {(T * k, 16), (T * k, 8), (T, k, 16)} <= wide(
        branch(moe._over_all_rows))
    whole = str(jax.make_jaxpr(lambda x: moe_dispatch(
        x, weights, experts, 8, run(gate[2:4], down[2:4]), held=held))(x))
    assert whole.count("cond[") == 1


@pytest.mark.parametrize("held", [None, (0, 8), (2, 4), (0, 5)])
def test_all_held_and_large_shares_lower_to_the_program_they_were(
        held, monkeypatch):
    """No share, all the experts as a share, half of them and more: the
    buffer is all the rows and the jaxpr, forward and backward, is the one
    the call had before a small share got a buffer of its own (but for the
    marks on the sorts' results, `moe.ROUTE_NAME`, which are no
    instruction: `tests/test_remat_keeps_attention.py`)."""
    monkeypatch.setattr(moe, "checkpoint_name", lambda v, name: v)
    gate, down, run = _experts()
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 16))
    weights, experts = _routing()
    first, count = held or (0, 8)
    assert moe.buffer_rows(64 * 3, count, 8) == 64 * 3

    def text(dispatch):
        def loss(x, weights, gate, down):
            return jnp.sum(dispatch(
                x, weights, experts, 8,
                run(gate[first:first + count], down[first:first + count]),
                held=held)[0])
        return (str(jax.make_jaxpr(loss)(x, weights, gate, down)),
                str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2, 3)))(
                    x, weights, gate, down)))

    assert text(moe_dispatch) == text(_dispatch_over_all_rows)
    assert "cond[" not in text(moe_dispatch)[0]


@pytest.mark.parametrize("rows, count, n_experts, want", [
    (98304, 16, 128, 24576),      # kanana's share: a quarter of the rows
    (192, 2, 8, 96), (192, 4, 8, 192), (192, 8, 8, 192),
    (384, 2, 8, 192), (30, 1, 8, 8), (30, 3, 8, 24), (24, 1, 64, 8)])
def test_buffer_rows_is_twice_the_expected_load_in_whole_tiles(
        rows, count, n_experts, want):
    assert moe.buffer_rows(rows, count, n_experts) == want


@pytest.mark.parametrize("favoured", [0.0, 10.0])
def test_the_model_says_which_layers_overflowed_and_stays_exact(
        favoured, monkeypatch):
    """`deepseek_v3` holding 2 of its 8 experts: `out["moe_overflow_layers"]`
    is 0 at initialisation, and both routed layers once the routing bias
    sends every token to the two held experts (2 T rows against a buffer
    of 1.5 T); either way loss, `rows_held` and every gradient are those
    of the step whose every call runs over all T*k rows."""
    import dataclasses

    from ray_tpu.models import deepseek_v3 as model

    cfg = dataclasses.replace(model.DEEPSEEK_V3_TINY, held=(0, 2),
                              compute_dtype=jnp.float32)
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    for i in cfg.moe_layers:
        router = params[f"layer_{i}"]["moe"]["router"]
        router[model.ROUTING_BIAS] = router[model.ROUTING_BIAS].at[:2].set(
            favoured)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, 512)

    def step():
        return jax.value_and_grad(
            lambda p: model.loss_fn(p, {"tokens": tokens}, cfg),
            has_aux=True)(params)

    (loss, out), grads = step()
    buffer = moe.buffer_rows(64 * 3, 2, 8)
    held = np.asarray(out["expert_rows"])[:, :2].sum(axis=1)
    assert int(out["moe_overflow_layers"]) == (2 if favoured else 0)
    assert ((held > buffer) == bool(favoured)).all()
    monkeypatch.setattr(layers, "moe_dispatch", _dispatch_over_all_rows)
    (loss0, out0), grads0 = step()
    assert abs(float(loss) - float(loss0)) < 1e-5
    assert int(out["rows_held"]) == int(out0["rows_held"]) == held.sum()
    worst = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         grads, grads0)
    assert max(jax.tree.leaves(worst)) < 1e-5
