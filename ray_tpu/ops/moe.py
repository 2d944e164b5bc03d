"""Dropless routing of tokens to experts.

A routed mixture multiplies each token by the few experts its router
chose.  Here no token is ever dropped and no expert has a capacity: the
T*k (token, expert) rows are sorted by expert, so that each expert's rows
are one contiguous group of whatever size the router made it, the experts
run over the ragged groups (`ops/grouped_matmul.py`: the repo's grouped
matmul kernels), and the rows go back to their tokens and are summed with
their weights.  Shared by `models/layers.py:routed_layer`
(`models/deepseek_v3.py`, `models/lfm2_moe.py` and `models/nemotron_h.py`:
a chip's share of the experts), `models/olmoe.py` (all of them) and
`models/gpt2.py`'s mixture (GELU experts).  Behind the dispatch, the two
routers the models of `routed_layer` share and what a step says of them
(`routing_account`): sigmoid scores with a routing bias that picks and does
not weigh and moves by a rule of its own (`sigmoid_route`,
`routing_bias_rule`, `trained_by`: the first three), and a softmax with the
load-balancing loss that is read off its mean and the rows the dispatch
counted (`softmax_route`, `balance_loss`: `models/keye_vl.py`,
`models/sdar.py`, `models/mellum.py`).

Where all the experts live here, the buffer between dispatch and combine
is the T*k rows.  Where a share of under half of them does (``held``), it
is `buffer_rows` long, twice what a balanced router sends the share, and
the rows of held experts alone are gathered into it and summed back out
of it (`_take`, `_put`); a step whose router sends the share more than
that takes the T*k path instead, inside the same compiled program
(`lax.cond`), so the result is exact under any imbalance.

What moves a row.  Over a held share's buffer, the two Mosaic kernels of
`ops/moe_rows.py`, forward and backward (`_take`'s transpose is `_put`
without weights, and the other way round): a row moves by one DMA and only
if it exists, so the half of the buffer that belongs to no group is
written as zeros and never fetched, and of a token's k choices only those
whose expert is held are read back; a shape the kernels decline takes the
XLA forms they stand for (a gather and a mask; a gather a choice and a
sum).  Over all T*k rows (`_over_all_rows`: every expert held, and the
overflow branch), XLA's gathers by a permutation, as before.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops import moe_rows
from ray_tpu.util import tracing

# The name a router's product, its choices and their order by expert carry
# for a recomputed layer (`models/layers.py:KEPT_NAMES`, the first of them):
# kilobytes a token, and with them kept a replay runs no router and no sort.
# OLMoE's router, in its model file, marks its logits with the same word.
ROUTE_NAME = "ffn/moe/route"

# A share's buffer over the rows a balanced router sends it.  Twice: a
# router at its initialisation, or one a bias rule or an auxiliary loss
# keeps balanced, sends a share its expected rows within a few per cent,
# and a share that is sent double has lost its balance altogether; that
# step is still exact (the T*k path), only slower.  Not an option: a caller
# says which experts it holds, and nothing about buffers.
_HEADROOM = 2


def buffer_rows(rows: int, count: int, n_experts: int) -> int:
    """Rows of the buffer between dispatch and combine when ``count`` of
    ``n_experts`` are held and ``rows`` = T*k are routed: `_HEADROOM` times
    the expected load, to a whole sublane tile of 8, and never more than
    all the rows (half of the experts and more: T*k)."""
    need = -(-_HEADROOM * rows * count // n_experts)
    return min(rows, -(-need // 8) * 8)


@jax.custom_vjp
def _permute_rows(x, perm, inverse):
    """x[perm] for a permutation whose inverse is known: the cotangent is
    a gather by the inverse, where autodiff of a gather would scatter-add
    (slow on the chip, and needless: no row is taken twice)."""
    return x[perm]


_permute_rows.defvjp(
    lambda x, perm, inverse: (x[perm], inverse),
    lambda inverse, g: (g[inverse], None, None))


def _sum_into_tokens(rows, weights, where):
    """(C, E) buffered rows -> (T, E): each token's rows, times its
    choices' ``weights`` (T, k) if given, summed in float32 in the order
    of its choices (`moe_rows.sum_rows`: only the choices that are in the
    buffer are fetched, a row a copy; nothing with T*k rows exists)."""
    return moe_rows.sum_rows(rows, where[2], where[1], weights)


@jax.custom_vjp
def _take(x, where):
    """(T, E) -> the (C, E) buffer: each buffered row's token's row, zero
    for the rows of the buffer that belong to no group, which nobody
    fetches (`moe_rows.take_rows`).  Its transpose is `_put` without
    weights."""
    return moe_rows.take_rows(x, where[0], where[2])


_take.defvjp(lambda x, where: (_take(x, where), where),
             lambda where, g: (_sum_into_tokens(g, None, where), None))


@jax.custom_vjp
def _put(rows, weights, where):
    """The (C, E) buffer, each row times its (token, choice)'s weight
    (T, k), summed into the (T, E) tokens; the rows of no group add
    nothing.  Its transpose in ``rows`` is `_take` times the weights."""
    return _sum_into_tokens(rows, weights, where)


def _put_bwd(res, g):
    rows, weights, where = res
    slot, place = where[1], where[3]
    C = rows.shape[0]
    g = _take(g, where).astype(jnp.float32)
    by_row = jnp.sum(g * rows.astype(jnp.float32), axis=1)      # (C,)
    return ((g * weights.reshape(-1)[place][:, None]).astype(rows.dtype),
            jnp.where(slot < C, by_row[jnp.minimum(slot, C - 1).reshape(-1)]
                      .reshape(slot.shape), 0), None)


_put.defvjp(lambda rows, weights, where: (
    _sum_into_tokens(rows, weights, where), (rows, weights, where)),
    _put_bwd)


def _sort_by_expert(experts, n_experts, held):
    """-> ((0..T*k-1, the order that sorts the (token, choice) rows by
    expert, its inverse), rows sent to each of all the experts)."""
    T, k = experts.shape
    flat = experts.reshape(T * k)
    rows = jnp.arange(T * k, dtype=jnp.int32)
    keys = flat
    if held:
        # the absent experts' rows go last, behind every held group
        first, count = held
        here = (flat >= first) & (flat < first + count)
        keys = jnp.where(here, flat, n_experts)
    # a stable sort keeps a token's rows in token order inside a group
    _, order = jax.lax.sort((keys, rows), num_keys=1)
    _, inverse = jax.lax.sort((order, rows), num_keys=1)
    group_sizes = jnp.sum(
        flat[:, None] == jnp.arange(n_experts, dtype=flat.dtype)[None],
        axis=0, dtype=jnp.int32)
    return (rows, order, inverse), group_sizes


def _buffer_index(C, k, by_expert, n_held):
    """-> `where` for `_take` and `_put`: each buffered row's token, each
    (token, choice)'s place in the buffer (T, k), C for those not in it,
    the rows the held experts were sent (the buffer's first ``n_held``),
    and each buffered row's (token, choice), in expert order."""
    _, order, inverse = by_expert
    rows = order[:C]
    return (rows // k, jnp.where(inverse < n_held, inverse, C).reshape(-1, k),
            n_held, rows)


def _over_all_rows(x, weights, by_expert, group_sizes, held, run_experts):
    """Dispatch, experts and combine over a buffer of all T*k rows;
    ``by_expert`` = (0..T*k-1, the sort's order, its inverse)."""
    T, k = weights.shape
    rows, order, inverse = by_expert
    with jax.named_scope("dispatch"):
        xs = _permute_rows(jnp.repeat(x, k, axis=0), order, inverse)
    with jax.named_scope("experts"):
        if held:
            first, count = held
            sizes = group_sizes[first:first + count]
            # whatever a grouped matmul makes of the rows of no group, or
            # its transposes of their cotangents, stays inside
            grouped = (rows < jnp.sum(sizes))[:, None]
            ys = jnp.where(grouped, run_experts(
                jnp.where(grouped, xs, 0), sizes), 0)
        else:
            ys = run_experts(xs, group_sizes)
    with jax.named_scope("combine"):
        ys = _permute_rows(ys, inverse, order).reshape(T, k, -1)
        y = jnp.sum(ys.astype(jnp.float32) * weights[..., None], axis=1)
    return y.astype(x.dtype)


def _over_held_rows(C, x, weights, by_expert, group_sizes, held,
                    run_experts):
    """The same over a buffer of the first C rows in expert order, which
    must hold every row sent to a held expert.  Nothing here has T*k rows
    and a width."""
    T, k = weights.shape
    first, count = held
    sizes = group_sizes[first:first + count]
    with jax.named_scope("dispatch"):
        where = _buffer_index(C, k, by_expert, jnp.sum(sizes))
        xs = _take(x, where)
    with jax.named_scope("experts"):
        # whatever a grouped matmul makes of the rows of no group stays
        # there: `_put` fetches none of them, and its transpose hands
        # their cotangents over as the zeros `_take` wrote
        ys = run_experts(xs, sizes)
    with jax.named_scope("combine"):
        return _put(ys, weights, where)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _one_of(compact, full, fits, index, operands):
    """`compact(index, *operands)` if ``fits`` else `full(...)`,
    differentiable in ``operands``, with no residual but the arguments:
    reverse mode of a plain `lax.cond` would have each branch write zeros
    for the other's residuals, the T*k-row arrays among them; here the
    backward pass chooses again and the branch taken differentiates
    itself."""
    return jax.lax.cond(fits, compact, full, index, *operands)


def _one_of_bwd(compact, full, res, g):
    def back(branch):
        return lambda index, g, *operands: jax.vjp(
            functools.partial(branch, index), *operands)[1](g)
    fits, index, operands = res
    return None, None, jax.lax.cond(fits, back(compact), back(full),
                                    index, g, *operands)


_one_of.defvjp(
    lambda compact, full, fits, index, operands: (
        _one_of(compact, full, fits, index, operands),
        (fits, index, operands)),
    _one_of_bwd)


def _over_either(C, x, weights, by_expert, group_sizes, held, run_experts):
    """`_over_held_rows` when the share was sent at most C rows, else
    `_over_all_rows`, chosen on the device."""
    (T, E), k = x.shape, weights.shape[1]
    first, count = held
    # what `run_experts` closes over has to cross the custom rule as
    # arguments, at each of the two buffer lengths
    runs, consts = [], {}
    for n in (C, T * k):
        run, closed = jax.closure_convert(
            run_experts, jax.ShapeDtypeStruct((n, E), x.dtype),
            jax.ShapeDtypeStruct((count,), group_sizes.dtype))
        for c in closed:
            consts.setdefault(id(c), c)
        runs.append((run, [list(consts).index(id(c)) for c in closed]))

    def branch(body, which):
        run, at = runs[which]

        def over(index, x, weights, *hoisted):
            return body(x, weights, *index, held,
                        lambda xs, sizes: run(xs, sizes,
                                              *(hoisted[i] for i in at)))
        return over

    return _one_of(
        branch(functools.partial(_over_held_rows, C), 0),
        branch(_over_all_rows, 1),
        jnp.sum(group_sizes[first:first + count]) <= C,
        (by_expert, group_sizes), (x, weights, *consts.values()))


def moe_dispatch(x, weights, experts, n_experts, run_experts, held=None):
    """x (T, E); weights, experts (T, k): each token's k experts, of ALL
    ``n_experts``, and what each one's output is multiplied by.
    `run_experts(rows, group_sizes)` gets the buffered rows in expert
    order (R, E) with the rows of each expert it runs and returns their
    outputs (R, E), row for row.  Whatever the imbalance, every row is
    computed.  Returns (y (T, E), rows sent to each of all the experts
    (n_experts,) int32).  Differentiable in x, weights and whatever
    `run_experts` closes over.

    ``held`` = (first, count): only that contiguous range of the experts
    lives here (one chip's share of an expert-parallel layer).  Routing is
    still over all of them; the rows sent to a held expert come first in
    expert order and `run_experts` gets the group sizes of the held experts
    alone (count,); a row sent to an absent expert is computed by nobody
    and adds nothing to y (its part of the sum is another chip's), and no
    gradient comes back through it.  The buffer is R = `buffer_rows` long:
    all T*k rows for a share of half the experts and more, twice the
    share's expected rows for a smaller one, and then a step that sends it
    more runs over all T*k rows instead (`run_experts` is traced at both
    lengths, and called again by the backward pass).  Nothing is dropped
    under any imbalance.  In the job timeline `moe.rows_buffered` against
    `moe.rows_routed` is what the buffer costs the grouped matmuls and the
    passes that write it, no longer what the gathers cost: dispatch
    fetches the rows the share was sent and combine the choices that are
    held (`ops/moe_rows.py`; `moe.row_kernel_passes` counts the passes its
    kernels took as the step was traced and `moe.row_kernel_declined`
    those a shape sent back to XLA's gathers, which fetch a row for every
    buffered row and every choice).  `moe.overflow_passes` is how many
    passes (forward, recomputed, backward) took the long way.
    None: all are held."""
    T, k = experts.shape
    first, count = held or (0, n_experts)
    C = buffer_rows(T * k, count, n_experts)
    tracing.count("moe.experts", n_experts)
    tracing.count("moe.experts_held", count)
    tracing.count("moe.rows_routed", T * k)
    tracing.count("moe.rows_buffered", C)
    with jax.named_scope("dispatch"):
        # the sorts' results are the router's choices in another order:
        # kept with them, a replay sorts nothing
        by_expert, group_sizes = jax.tree.map(
            lambda v: checkpoint_name(v, ROUTE_NAME),
            _sort_by_expert(experts, n_experts, held))
    over = _over_all_rows if C == T * k \
        else functools.partial(_over_either, C)
    return over(x, weights, by_expert, group_sizes, held,
                run_experts), group_sizes


# -- the sigmoid router with a routing bias (DeepSeek-V3's `noaux_tc`) ------

ROUTING_BIAS = "e_score_correction_bias"


def _in_best_groups(picks, n_group, topk_group):
    """picks (T, N) -> picks with -inf outside each row's ``topk_group`` best
    of ``n_group`` groups of N / n_group consecutive experts, a group's score
    the sum of its two largest picks (DeepSeek-V3's group-limited choice)."""
    T, N = picks.shape
    best_two, _ = jax.lax.top_k(picks.reshape(T, n_group, N // n_group), 2)
    _, groups = jax.lax.top_k(jnp.sum(best_two, axis=-1), topk_group)
    kept = jnp.any(groups[:, :, None] == jnp.arange(n_group)[None, None],
                   axis=1)                                      # (T, n_group)
    return jnp.where(jnp.repeat(kept, N // n_group, axis=1), picks, -jnp.inf)


def sigmoid_route(xt, router, top_k, eps, scale, n_group=1, topk_group=1):
    """xt (T, E) -> (weights (T, k) f32, experts (T, k) int32) over all the
    experts ``router["kernel"]`` (E, N) scores: s = sigmoid(xt W) in
    float32; the top k of s + b, b the leaf `ROUTING_BIAS` where the router
    has one (it picks and does not weigh, and nothing differentiates
    through it); weights s at the chosen, over their sum + ``eps`` unless
    ``eps`` is None (weights not renormalised), times ``scale``.
    ``n_group`` > 1: the top k are taken inside the ``topk_group`` best of
    ``n_group`` groups of consecutive experts (`_in_best_groups`); such a
    call is counted on the job timeline as the step is traced
    (`moe.route_groups`).  One group: every expert stands, and the program
    is what it always was."""
    # the product and not its sigmoid, whose backward reads its own result
    scores = jax.nn.sigmoid(checkpoint_name(jnp.matmul(
        xt, router["kernel"].astype(xt.dtype),
        preferred_element_type=jnp.float32), ROUTE_NAME))     # (T, N)
    picks = scores
    if ROUTING_BIAS in router:
        picks = scores + jax.lax.stop_gradient(router[ROUTING_BIAS])
    if n_group > 1:
        tracing.count("moe.route_groups")
        picks = _in_best_groups(jax.lax.stop_gradient(picks), n_group,
                                topk_group)
    _, experts = jax.lax.top_k(picks, top_k)
    experts = checkpoint_name(experts, ROUTE_NAME)
    # and the scores at the chosen: (T, k), and a gather a replay would run
    weights = checkpoint_name(
        jnp.take_along_axis(scores, experts, axis=-1), ROUTE_NAME)
    if eps is not None:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + eps)
    return weights * scale, experts


def routing_account(params, routed_layers, rows, routed, held):
    """What a step's `out` says of its routers, device values that cost
    nothing unless fetched.  ``rows``: what each routed layer sent each of
    all the experts (N,), one entry a layer in the order the layers were
    walked, which is ``routed_layers``' (the i of ``params[f"layer_{i}"]
    ["moe"]["router"]``); ``routed`` = T*k rows a layer routes; ``held`` =
    (first, count) as `moe_dispatch`'s.  -> {"expert_rows": (routed layers,
    N), row j the j-th of ``routed_layers``; "rows_held": rows the held
    experts computed, over the layers; "moe_overflow_layers": routed layers
    whose held experts were sent more than `buffer_rows` and ran over all
    the routed rows instead (exact, and slower); "max_expert_rows";
    "max_routing_bias": |b| as the step used it, 0 where no router has a
    bias}."""
    rows = jnp.stack(rows)
    n_experts = rows.shape[1]
    first, count = held or (0, n_experts)
    sent = jnp.sum(rows[:, first:first + count], axis=1)
    routers = [params[f"layer_{i}"]["moe"]["router"] for i in routed_layers]
    biases = [r[ROUTING_BIAS] for r in routers if ROUTING_BIAS in r]
    biases = jnp.stack(biases) if biases else jnp.zeros((1,), jnp.float32)
    return {
        "expert_rows": rows,
        "rows_held": jnp.sum(sent),
        "moe_overflow_layers": jnp.sum(
            sent > buffer_rows(routed, count, n_experts), dtype=jnp.int32),
        "max_expert_rows": jnp.max(rows),
        "max_routing_bias": jnp.max(jnp.abs(biases)),
    }


def routing_bias_rule(routed_layers, speed):
    """rule(params, out) -> params for `models/layers.py:train_step`: the
    bias of each routed layer (``routed_layers`` and `out["expert_rows"]`
    as `routing_account` orders them) moves ``speed`` towards the experts
    that were sent fewer rows than the mean:
    b_e += speed * sign(mean(n) - n_e) (arXiv:2412.19437)."""
    def rule(params, out):
        with jax.named_scope("routing_bias_update"):
            params = dict(params)
            for j, i in enumerate(routed_layers):
                n = out["expert_rows"][j].astype(jnp.float32)
                layer = params[f"layer_{i}"]
                router = layer["moe"]["router"]
                bias = router[ROUTING_BIAS] + speed \
                    * jnp.sign(jnp.mean(n) - n)
                params[f"layer_{i}"] = {**layer, "moe": {
                    **layer["moe"], "router": {**router, ROUTING_BIAS: bias}}}
            return params
    return rule


def trained_by(optimizer):
    """``optimizer`` over every leaf but the routing biases, which it
    neither moves nor decays and keeps no moments for."""
    import optax

    def labels(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, _: "rule" if path[-1].key == ROUTING_BIAS
            else "optimizer", params)

    return optax.multi_transform(
        {"optimizer": optimizer, "rule": optax.set_to_zero()}, labels)


# -- the softmax router with a load-balancing loss (Qwen3-MoE's) -------------

def softmax_route(xt, router, top_k, renormalise):
    """xt (T, E) -> (weights (T, k) f32, experts (T, k) int32, the mean over
    the rows of the softmax (N,) f32, which `balance_loss` reads) over all
    the experts ``router["kernel"]`` (E, N) scores: p = softmax(xt W) in
    float32; the top k of p; weights p at the chosen, over their sum if
    ``renormalise``."""
    # the logits: a softmax's and a top-k's backward read their own
    # results, which a replay makes from these
    logits = checkpoint_name(jnp.matmul(
        xt, router["kernel"].astype(xt.dtype),
        preferred_element_type=jnp.float32), ROUTE_NAME)          # (T, N)
    probs = jax.nn.softmax(logits, axis=-1)
    mean = jnp.mean(probs, axis=0)
    weights, experts = jax.lax.top_k(probs, top_k)
    if renormalise:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, checkpoint_name(experts, ROUTE_NAME), mean


def balance_loss(rows, mean_probs, routed):
    """A router's load-balancing loss (Switch Transformer's), N sum_e f_e
    P_e, under `route`: f_e the share of the ``routed`` = T*k assignments
    that went to expert e, from the ``rows`` (N,) `moe_dispatch` counted (a
    count: no gradient), P_e the batch's mean probability
    (`softmax_route`'s third).  1 for a router in balance."""
    with jax.named_scope("route"):
        share = rows.astype(jnp.float32) / routed
        return rows.shape[0] * jnp.sum(share * mean_probs)
