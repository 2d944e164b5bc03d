"""The scope vocabulary (`models/layers.py:SCOPES`) on every Train-path
model: in the lowered step's text with debug info, every matmul, grouped
matmul, Mosaic kernel and convolution has an `op_name` under a scope of the
vocabulary, no other scope appears, `rematted_computation` appears exactly
when `remat` is on, the chunked loss makes a chunk's three products in its
forward walk and none again, and the flash kernels' four forms are told apart.

The step is lowered for the platform `tpu` (no chip needed: the Mosaic
kernels become `tpu_custom_call`s at lowering time), so the text holds what
the chip's program holds.  jax lowers a jitted function called inside the
step (`_pallas_forward`, the loss's chunk) once, as a private function
whose operations carry names relative to it; XLA's inliner puts the call's
name in front, and `full_names` here does the same.  Names are read as the
benchmark's reader reads a trace's `tf_op` (`scope_trace.scope_of`).
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import optax
import pytest

from benchmark.harness import scope_trace
from ray_tpu.models import (
    bailing_hybrid,
    deepseek_v3,
    evabyte,
    gpt2,
    keye_vl,
    laguna,
    layers,
    lfm2_moe,
    mellum,
    nemotron_h,
    olmoe,
    ouro,
    phi4flash,
    sdar,
)
from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops.moe import trained_by

MODELS = {
    "gpt2": (gpt2, gpt2.GPT2_TINY),
    "gpt2_moe": (gpt2, dataclasses.replace(gpt2.GPT2_TINY, moe_experts=4)),
    "olmoe": (olmoe, olmoe.OLMOE_TINY),
    "deepseek_v3": (deepseek_v3, deepseek_v3.DEEPSEEK_V3_TINY),
    "lfm2_moe": (lfm2_moe, lfm2_moe.LFM2_MOE_TINY),
    "nemotron_h": (nemotron_h, nemotron_h.NEMOTRON_H_TINY),
    "keye_vl": (keye_vl, keye_vl.KEYE_VL_TINY),
    "ouro": (ouro, ouro.OURO_TINY),
    "sdar": (sdar, sdar.SDAR_TINY),
    "mellum": (mellum, mellum.MELLUM_TINY),
    "laguna": (laguna, laguna.LAGUNA_TINY),
    "phi4flash": (phi4flash, phi4flash.PHI4FLASH_TINY),
    # heads as wide as the published ones: the rule's kernels, not its
    # plain form
    "bailing_hybrid": (bailing_hybrid, dataclasses.replace(
        bailing_hybrid.BAILING_HYBRID_TINY, head_dim=128, kda_chunk=64)),
    # heads, windows and chunks the EVA kernels take, not the plain form
    "evabyte": (evabyte, dataclasses.replace(
        evabyte.EVABYTE_TINY, head_dim=128, window=128, chunk=8)),
}
# the positions of a model's sequence: two of evabyte's windows
SEQ = {"evabyte": 256}
CASES = [(name, remat) for name in MODELS for remat in (False, True)]
# what every model's step must have a matmul under
EXPECTED = {
    "gpt2": {"attention/qkv", "attention/out", "ffn/dense",
             "head_and_loss", "attention/kernel/fwd_rows",
             "attention/kernel/bwd_fused"},
    "gpt2_moe": {"attention/qkv", "attention/out", "ffn/moe/route",
                 "ffn/moe/experts", "head_and_loss"},
    "olmoe": {"attention/qkv", "attention/out", "ffn/moe/route",
              "ffn/moe/experts", "head_and_loss"},
    "deepseek_v3": {"attention/latent_down", "attention/latent_up",
                    "attention/out", "ffn/dense", "ffn/moe/route",
                    "ffn/moe/experts", "ffn/moe/shared", "head_and_loss"},
    "lfm2_moe": {"short_conv/in_proj", "short_conv/out_proj",
                 "attention/qkv", "attention/out", "ffn/dense",
                 "ffn/moe/route", "ffn/moe/experts", "head_and_loss"},
    "nemotron_h": {"ssm/in_proj", "ssm/scan", "ssm/out_proj",
                   "attention/qkv", "attention/out", "ffn/moe/route",
                   "ffn/moe/experts", "ffn/moe/shared", "head_and_loss"},
    "keye_vl": {"attention/qkv", "attention/indexer/proj",
                "attention/indexer/scores", "attention/indexer/loss",
                "attention/kernel/fwd_rows", "attention/kernel/bwd_fused",
                "attention/out", "ffn/moe/route", "ffn/moe/experts",
                "head_and_loss"},
    "ouro": {"attention/qkv", "attention/out", "ffn/dense", "head_and_loss",
             "exit_gate"},
    "sdar": {"attention/qkv", "attention/kernel/fwd_rows_blocks",
             "attention/kernel/bwd_fused_blocks", "attention/out",
             "ffn/moe/route", "ffn/moe/experts", "head_and_loss"},
    "mellum": {"attention/qkv", "attention/kernel/fwd_rows_window",
               "attention/kernel/bwd_fused_window",
               "attention/kernel/fwd_rows", "attention/kernel/bwd_fused",
               "attention/out", "ffn/moe/route", "ffn/moe/experts",
               "head_and_loss"},
    "laguna": {"attention/qkv", "attention/kernel/fwd_rows_window",
               "attention/kernel/bwd_fused_window",
               "attention/kernel/fwd_rows", "attention/kernel/bwd_fused",
               "attention/gate", "attention/out", "ffn/dense",
               "ffn/moe/route", "ffn/moe/experts", "ffn/moe/shared",
               "head_and_loss"},
    "phi4flash": {"mamba/in_proj", "mamba/x_proj", "mamba/out_proj",
                  "mamba/conv", "attention/qkv", "attention/cross",
                  "attention/kernel/fwd_rows_window",
                  "attention/kernel/bwd_fused_window",
                  "attention/kernel/fwd_rows", "attention/kernel/bwd_fused",
                  "attention/out", "gmu", "ffn/dense", "head_and_loss"},
    "bailing_hybrid": {"kda/proj", "kda/conv", "kda/rule", "kda/out_proj",
                       "attention/latent_down", "attention/latent_up",
                       "attention/kernel/fwd_rows",
                       "attention/kernel/bwd_fused", "attention/gate",
                       "attention/out", "ffn/dense", "ffn/moe/route",
                       "ffn/moe/experts", "ffn/moe/shared", "head_and_loss"},
    "evabyte": {"eva/qkv", "eva/summary", "eva/local/fwd_rows_blocks",
                "eva/local/bwd_fused_blocks", "eva/remote", "eva/out",
                "ffn/dense", "head_and_loss"},
}
# components of an `op_name` that jax puts there itself (`jnp.einsum` its
# subscripts: `ops/ssd.py`'s products)
JAX_WRAPPERS = re.compile(
    r"^(checkpoint|rematted_computation|shard_map|cond|branch_\d+_fun|"
    r"while|body|closed_call:?|custom_vjp_call|custom_jvp_call|pjit|"
    r"[a-z]+(,[a-z]+)*->[a-z]*)$")
OPS = re.compile(r"stablehlo\.dot_general|chlo\.ragged_dot|"
                 r"stablehlo\.convolution|"
                 r"stablehlo\.custom_call @tpu_custom_call")
LOC_DEF = re.compile(r'^(#loc\d+) = loc\("([^"]*)"\((#loc\d+)?', re.M)
LOC_USE = re.compile(r"loc\((#loc\d+)\)\s*$")
FUNC = re.compile(r"^\s*func\.func (?:public|private) @([\w.]+)\(")
CALL = re.compile(r"\bcall @([\w.]+)\(")


@functools.lru_cache(maxsize=None)
def lowered_text(name: str, remat: bool) -> str:
    module, cfg = MODELS[name]
    cfg = dataclasses.replace(cfg, remat=remat)
    optimizer = optax.adamw(1e-4)
    if module in (deepseek_v3, lfm2_moe, nemotron_h, laguna,
                  bailing_hybrid):
        optimizer = trained_by(optimizer)
    # a step that draws its own noise is built with the run's seed
    step = module.make_train_step(cfg, optimizer,
                                  *((0,) if module is sdar else ()))
    params = jax.eval_shape(lambda key: module.init_params(key, cfg),
                            jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(optimizer.init, params)
    batch = {"tokens": jax.ShapeDtypeStruct((2, SEQ.get(name, 64) + 1),
                                            jnp.int32)}
    traced = jax.jit(step).trace(params, opt_state, batch)
    return traced.lower(lowering_platforms=("tpu",)).as_text(debug_info=True)


def full_names(text: str, wanted=OPS) -> list:
    """[(what matched ``wanted``, the operation's `op_name` as XLA's
    inliner will make it: every chain of call sites that reaches its
    function, in front of its own)]."""
    named = {loc: (name, inner) for loc, name, inner in LOC_DEF.findall(text)}

    def resolve(loc):
        # a call of a function jax lowered for a `closed_call` (a `lax.map`
        # in a checkpointed layer's own forward pass) is located
        # "closed_call:" AROUND the location that has its path, and XLA
        # names the inlined operations by that one
        name, inner = named[loc]
        while name == "closed_call:" and inner in named:
            name, inner = named[inner]
        return name

    names = {loc: resolve(loc) for loc in named}
    calls, found, function = {}, [], None      # callee -> [(caller, name)]
    for line in text.splitlines():
        start = FUNC.match(line)
        if start:
            function = start[1]
            continue
        used = LOC_USE.search(line)
        name = names.get(used[1]) if used else None
        called = CALL.search(line)
        if called:
            calls.setdefault(called[1], []).append((function, name))
        op = wanted.search(line)
        if op:
            found.append((op[0], function, name))

    def prefixes(function, seen=()):
        if function == "main":
            return [""]
        return [f"{front}/{site}" if front else site
                for caller, site in calls.get(function, ())
                if caller not in seen and site is not None
                for front in prefixes(caller, seen + (function,))]

    return [(op, f"{front}/{name}" if front else name)
            for op, function, name in found
            for front in (prefixes(function) if name is not None else [])]


def scope(name: str) -> str:
    return scope_trace.scope_of(name, tuple(layers.SCOPES))


def test_the_vocabulary_is_one_tuple_of_paths():
    assert len(set(layers.SCOPES)) == len(layers.SCOPES)
    for path in layers.SCOPES:
        # a scope's parent is a scope
        parent = path.rpartition("/")[0]
        assert not parent or parent in layers.SCOPES, path
    for form in fa.KERNEL_FORMS:
        assert f"attention/kernel/{form}" in layers.SCOPES


@pytest.mark.parametrize("name,remat", CASES)
def test_every_matmul_and_kernel_is_under_a_scope(name, remat):
    found = full_names(lowered_text(name, remat))
    assert found, "the lowered text holds no matmul?"
    kinds = {op for op, _ in found}
    assert "stablehlo.dot_general" in kinds
    assert "stablehlo.custom_call @tpu_custom_call" in kinds
    bare = [(op, full) for op, full in found if not scope(full)]
    assert not bare, bare[:5]
    scopes = {scope(full) for _, full in found}
    assert EXPECTED[name] <= scopes, EXPECTED[name] - scopes
    # XLA:TPU renames the grouped matmuls, and `COMPILER_NAMED` says they
    # are the experts': so every one of them must be
    (renamed, experts), = layers.COMPILER_NAMED
    assert {scope(full) for op, full in found
            if op == "chlo.ragged_dot"} <= {experts}
    assert scope_trace.scope_of(f"{renamed}-none:", tuple(layers.SCOPES),
                                layers.COMPILER_NAMED) == experts
    # the optimizer's and the norms' operations are named too
    every = {scope(full) for _, full in full_names(
        lowered_text(name, remat), re.compile(r"stablehlo\.\w+"))}
    assert {"optimizer_update", "norm", "embed"} <= every
    if name in ("deepseek_v3", "lfm2_moe", "nemotron_h", "laguna",
                "bailing_hybrid"):
        assert "routing_bias_update" in every
    if name == "lfm2_moe":
        assert "short_conv/gate_taps" in every
    if name == "nemotron_h":
        assert {"ssm/conv", "ssm/gate_norm"} <= every
    if name == "keye_vl":
        # the threshold search has no matmul: compares and counts
        assert "attention/indexer/select" in every
    if name != "ouro":
        assert "exit_gate" not in every
    # the draw, the masking and the rows' weights of block diffusion; the
    # diagonal's kernels are not in its step, nor the rule's in another's
    assert ("diffusion" in every) is (name == "sdar")
    assert ("attention/kernel/fwd_rows_blocks" in every) is (name == "sdar")
    if name == "sdar":
        assert "attention/kernel/fwd_rows" not in every
    # a windowed layer's kernels under names of their own, in the models
    # that have such layers (beside their full layers' plain ones)
    assert ("attention/kernel/fwd_rows_window" in every) \
        is ("attention/kernel/bwd_fused_window" in every) \
        is (name in ("mellum", "laguna", "phi4flash"))
    # a Mamba-1 mixer's scan and what differential attention adds behind
    # its kernels, in the one model that has them
    assert ({"mamba/scan", "attention/diff"} <= every) \
        is (name == "phi4flash")
    # a gate on attention's result, in the models that have one
    assert ("attention/gate" in every) \
        is (name in ("laguna", "bailing_hybrid"))
    # a delta-rule mixer's parts that hold no matmul, and the rule's
    # kernels, in the one model that has them
    assert ({"kda/gate", "kda/gate_norm", "kda/rule"} <= every) \
        is (name == "bailing_hybrid")
    # an EVA mixer's merge holds no matmul; its kernels stand under the
    # mixer's scopes (the flash pair under `eva/local`, not `attention`)
    assert ({"eva/merge", "eva/local/fwd_rows_blocks", "eva/remote",
             "eva/summary"} <= every) is (name == "evabyte")
    if name == "evabyte":
        assert not {s for s in every if s.startswith("attention")}


@pytest.mark.parametrize("remat", [False, True])
def test_the_gate_is_a_scope_of_its_own(remat):
    """A gated attention's product with the layer's normed input, its
    sigmoid and the multiply over the kernels' result stand under
    `attention/gate`, forward and backward (and a recomputed layer's
    replay), and W_o's product stays under `attention/out`: one product a
    pass under the gate in each of the five layers, and no kernel."""
    found = full_names(lowered_text("laguna", remat),
                       re.compile(r"stablehlo\.\w+"))
    gate = [(op, full) for op, full in found
            if scope(full) == "attention/gate"]
    phases = {scope_trace.phase_of(full) for _, full in gate}
    assert phases >= {"fwd", "bwd"}
    assert ("remat_fwd" in phases) is remat
    ops = {op for op, _ in gate}
    # u W_g, the sigmoid, the multiply a head; no kernel, no reshape to W_o
    assert {"stablehlo.dot_general", "stablehlo.multiply"} <= ops, ops
    assert ops & {"stablehlo.logistic", "stablehlo.exponential"}, ops
    assert "stablehlo.custom_call" not in ops
    products = [full for op, full in gate if op == "stablehlo.dot_general"]
    forward = [full for full in products
               if scope_trace.phase_of(full) == "fwd"]
    backward = [full for full in products
                if scope_trace.phase_of(full) == "bwd"]
    # one body a shape of layer when recomputed: three shapes, five layers
    assert len(forward) == 5
    assert len(backward) == 2 * 5         # du and dW_g a layer
    out = [full for op, full in found if op == "stablehlo.dot_general"
           and scope(full) == "attention/out"]
    assert out and not [full for full in out if "gate" in full]


@pytest.mark.parametrize("remat", [False, True])
def test_the_exit_gate_is_a_scope_of_its_own(remat):
    """A looped model's gate, exit distribution, entropy and the weighting
    of the rows' losses stand under `exit_gate`, forward and backward, and
    not under the head's scope; the scopes of a layer keep their names in
    every walk (whatever a loop over the walks puts in front of them)."""
    found = full_names(lowered_text("ouro", remat),
                       re.compile(r"stablehlo\.\w+"))
    gate = [(op, full) for op, full in found if scope(full) == "exit_gate"]
    assert {scope_trace.phase_of(full) for _, full in gate} >= {"fwd", "bwd"}
    # the gate's product, its sigmoid in logarithms, the entropy's exp
    assert {"stablehlo.dot_general", "stablehlo.exponential"} \
        <= {op for op, _ in gate}
    assert not [full for _, full in gate if "head_and_loss" in full]
    walked = {scope(full) for _, full in found}
    assert {"attention/qkv", "attention/kernel/fwd_rows", "ffn/dense",
            "norm", "head_and_loss"} <= walked


@pytest.mark.parametrize("name,remat", CASES)
def test_no_scope_outside_the_vocabulary(name, remat):
    parts = {part for path in layers.SCOPES for part in path.split("/")}
    text = lowered_text(name, remat)
    for _, full in full_names(text, re.compile(r"stablehlo\.\w+|chlo\.\w+")):
        inside = []
        for part in scope_trace.components(full):
            if JAX_WRAPPERS.match(part):
                continue
            assert part in parts, (part, full)
            inside.append(part)
        # and in the vocabulary's order: what is left IS a scope's path
        # (a norm inside an operator stands in the operator's scope)
        if len(inside) > 1 and inside[-1] == "norm":
            inside.pop()
        assert "/".join(inside) in layers.SCOPES + ("",), full


@pytest.mark.parametrize("name,remat", CASES)
def test_rematted_computation_exactly_under_remat(name, remat):
    """A recomputed layer's operations say so, which is what the reader's
    `remat_fwd` phase goes by.  The chunked loss replays nothing, whatever
    `remat` says: a chunk's logits are made once, and the two products of
    their gradient stand beside them in the forward walk (PR 53)."""
    found = full_names(lowered_text(name, remat),
                       re.compile(r"stablehlo\.dot_general"))
    replayed = {scope(full) for _, full in found
                if scope_trace.phase_of(full) == "remat_fwd"}
    # an indexer makes a block's products again in its own backward pass
    # (`ops/sparse_index.py:index_scores`), whatever `remat` says
    layer_scopes = replayed - {"attention/indexer/scores"}
    if remat:
        # a layer of ONE mixer ends in W_o's product, which no backward
        # reads: its replay stops at the kernel
        last = {"nemotron_h": "attention/qkv",
                "evabyte": "eva/out"}.get(name, "attention/out")
        assert layer_scopes >= {last}, replayed
    else:
        assert not layer_scopes, replayed
    assert "head_and_loss" not in replayed
    phases = {scope_trace.phase_of(full) for _, full in found}
    assert {"fwd", "bwd"} <= phases
    if name in ("gpt2", "gpt2_moe"):
        return      # dense logits: their product, and its two transposes
    # the six families of the chunked loss: one loop over the chunks, whose
    # body holds a chunk's three products, all of the forward pass
    head = [full for _, full in found if scope(full) == "head_and_loss"]
    # (a walk a prediction head where a model has several)
    walks = MODELS[name][1].n_pred_heads if name == "evabyte" else 1
    assert len(head) == 3 * walks, head
    assert {scope_trace.phase_of(full) for full in head} == {"fwd"}
    assert all("while/body" in full for full in head)


def _kernel_names(fn, *shapes, dtype=jnp.bfloat16):
    args = [jax.ShapeDtypeStruct(shape, dtype) for shape in shapes]

    def loss(*xs):
        with jax.named_scope("attention"), jax.named_scope("kernel"):
            return jnp.sum(fn(*xs).astype(jnp.float32))

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    text = grad.trace(*args).lower(lowering_platforms=("tpu",)).as_text(
        debug_info=True)
    return sorted(scope(full) for _, full in full_names(
        text, re.compile(r"@tpu_custom_call")))


@pytest.mark.parametrize("fn,shape,forms", [
    # head-major, a grid step the whole sequence: the one-kernel backward
    (fa.flash_attention, (1, 2, 256, 64), ("fwd_rows", "bwd_fused")),
    # head-major past `_WHOLE_SEQ_MAX`: the same two, a tile a grid step
    (fa.flash_attention, (1, 2, 2048, 64), ("fwd_rows", "bwd_fused")),
    # (B, S, H, D) with two heads to a lane block: the lane layout
    (fa.flash_attention_bshd, (1, 256, 4, 64),
     ("fwd_lanes", "bwd_fused_lanes")),
    # the lane layout past `_WHOLE_SEQ_MAX`: its forward, and the
    # head-major backward it hands the long sequence to
    (fa.flash_attention_bshd, (1, 2048, 4, 64), ("fwd_lanes", "bwd_fused")),
])
def test_the_four_kernel_forms_are_told_apart(fn, shape, forms):
    """One `pallas_call` a pass on either side of `_WHOLE_SEQ_MAX`, in
    both layouts: the backward is one kernel at every length."""
    causal = functools.partial(fn, causal=True)
    assert _kernel_names(causal, shape, shape, shape) == sorted(
        f"attention/kernel/{form}" for form in forms)


@pytest.mark.parametrize("fn,shape", [
    (fa.flash_attention, (1, 2, 256, 64)),
    (fa.flash_attention, (1, 2, 2048, 64)),
    # the lane layout would take these heads; a window goes head-major
    (fa.flash_attention_bshd, (1, 256, 4, 64)),
    (fa.flash_attention_bshd, (1, 2048, 4, 64)),
])
def test_a_windows_kernels_have_form_names_of_their_own(fn, shape):
    """Under `BlockRule(window=W)` the two head-major kernels stand under
    `fwd_rows_window` and `bwd_fused_window`, at every length and from
    either entry, so a trace tells a windowed layer's kernels from a full
    one's."""
    windowed = functools.partial(fn, causal=fa.BlockRule(window=100))
    assert _kernel_names(windowed, shape, shape, shape) == [
        "attention/kernel/bwd_fused_window",
        "attention/kernel/fwd_rows_window"]


def test_no_split_backward_remains():
    """PR 45 deleted the dq and dk/dv kernels of the long backward: no
    form, scope, kernel body or gate of theirs is left in the program."""
    import inspect

    assert fa.KERNEL_FORMS == ("fwd_rows", "fwd_lanes", "bwd_fused",
                               "bwd_fused_lanes", "fwd_rows_blocks",
                               "bwd_fused_blocks", "fwd_rows_window",
                               "bwd_fused_window")
    assert [s for s in layers.SCOPES if s.startswith("attention/kernel/")] \
        == [f"attention/kernel/{form}" for form in fa.KERNEL_FORMS]
    source = inspect.getsource(fa) + inspect.getsource(layers)
    for gone in ("bwd_dq", "bwd_dkv", "_SPLIT_BWD_MAX_BLOCK"):
        assert gone not in source, gone


def _experts_ops(E, W, held, ffn):
    """[(operation, scope)] of the grouped products and kernels of one
    routed layer's value and gradient, lowered for a TPU: 64 rows of 2,
    8 experts of hidden width W on a hidden size of E."""
    from ray_tpu.ops.moe import sigmoid_route

    n, count = 8, held[1] if held else 8
    stack = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    p = {"router": {"kernel": stack(E, n)}, "wi_up": stack(count, E, W),
         "wo": stack(count, W, E)}
    if ffn is layers.swiglu:
        p["wi_gate"] = stack(count, E, W)

    def loss(x, p):
        with jax.named_scope("ffn"), jax.named_scope("moe"):
            y, _ = layers.routed_layer(
                x, p, lambda xt, router: sigmoid_route(xt, router, 2, 1e-20,
                                                       1.0), n, held, ffn)
        return jnp.sum(y.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1))).trace(stack(1, 64, E), p).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    return [(op, scope(full)) for op, full in full_names(
        text, re.compile(r"chlo\.ragged_dot|@tpu_custom_call"))
        if "dispatch" not in full and "combine" not in full]


@pytest.mark.parametrize("ffn", [layers.swiglu, layers.relu2])
@pytest.mark.parametrize("held", [None, (2, 2)])
@pytest.mark.parametrize("width", [128, 100])
def test_the_experts_lower_to_the_grouped_kernels_under_their_scope(
        width, held, ffn):
    """A hidden size of whole lane tiles (every routed cell's), whatever
    the experts' width (`layers._widened`): each of a layer's products is
    the repo's Mosaic kernel, forward and both gradients, under
    `ffn/moe/experts` by the caller's name, and no `ragged_dot` is left
    (a held share traces its products at both buffer lengths, and its
    backward what it needs of the forward again)."""
    found = _experts_ops(128, width, held, ffn)
    assert {op for op, _ in found} == {"@tpu_custom_call"}
    assert {where for _, where in found} == {"ffn/moe/experts"}
    stacks = 3 if ffn is layers.swiglu else 2
    assert len(found) == stacks * 3 if held is None \
        else len(found) >= 2 * stacks * 3


@pytest.mark.parametrize("ffn", [layers.swiglu, layers.relu2])
def test_a_declined_width_lowers_to_ragged_dot_under_the_same_scope(ffn):
    """A hidden size of half a lane tile: `ragged_dot`, still the
    experts'."""
    found = _experts_ops(64, 128, None, ffn)
    assert {op for op, _ in found} == {"chlo.ragged_dot"}
    assert {where for _, where in found} == {"ffn/moe/experts"}
