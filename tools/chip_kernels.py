"""Each flash-attention kernel family, compiled on the chip, against the
reference, with the device time of its forward and of its backward.

    python tools/chip_kernels.py            # the cases, default tiles
    python tools/chip_kernels.py --sweep    # tile -> ms at the cells' shapes
    python tools/chip_kernels.py --sweep s512-d64   # at the named shapes only
    python tools/chip_kernels.py --cases moe_held_8k   # the named cases only
    python tools/chip_kernels.py --cases moe_all_4k    # the grouped products alone
    python tools/chip_kernels.py --cases head_loss_8k  # the head's loss alone

One case on each side of the gates in ``ops/flash_attention.py``: the lane
kernels with the one-kernel backward (GPT-2 124M's heads), the transposing
bhsd kernels with the one-kernel backward (25 heads: no lane tiling; XL's
share of a batch on one chip), the long form past ``_WHOLE_SEQ_MAX``
(S=2048: a forward over q tiles and the one-kernel backward over k tiles),
OLMoE's shape (S=4096, D=128), and latent
attention's two widths at S=8192 (a fifth number in a shape is v's width:
q and k 192, v 128), and grouped queries at S=8192 (`gqa_8k`: 32 query
heads on 8 key/value heads of 64, `KV_HEADS`; beside it the same call with
k and v repeated to 32 heads first; `gqa16_8k`: 32 query heads on 2
key/value heads of 128, a group of 16).  This process holds the chip, so run it alone.  Exits non-zero unless every case
ran as compiled Mosaic kernels on a TPU and agrees with
``reference_attention``.  ``--sweep`` times forced tiles instead (what
``_auto_tiles`` is set from) and compares nothing: square ones, and past
``_WHOLE_SEQ_MAX`` also a q tile of another size than the backward's k tile
(``LONG_TILES``; on a v5e at PR 44 (the kernels of PR 45) the one-kernel backward read, ms a layer
at 256 / 512 / 1,024: OLMoE's shape 7.10 / 4.83 / 5.07, ``mla-8k`` 34.53 /
28.12 / 27.99, ``gqa-8k`` 27.07 / 17.62 / 17.59, ``gqa16-8k`` 26.78 / 17.56
/ 17.57, and no mixed pair beat square 512 by more than 0.3 %).

``mellum_16k`` and ``mellum_8k`` are the calls under a WINDOW
(`ops/flash_attention.py:BlockRule(window=1024)`; `WINDOW_CASES`): one
sequence of 16,384 and of 8,192 rows, 32 query heads on 4 key/value heads
of 128, then the same call with no window; a line a rule and a tile
(`_auto_tiles`' own, then 256, 512 and 1,024) with the forward's and
forward + backward's device ms, the rows of the band each pass takes
(`ops/flash_attention.py:_band`; null where it walks its tiles: at 1,024
here), the share of the visited pairs the rule attends, and the largest
error of o, dq, dk and dv relative to a float32 masked softmax taken 1,024
query rows at a time; under the window a last line is the WALK of
512-tiles (`walking`: the module's `_band` saying no), what the band
replaced.  ``laguna_16k`` is Laguna-XS.2's pair: 64 query heads on 8 under
a window of 512 (tiles of 128, 256 and 512), then its full layers' 48 on 8
with no window; ``phi4_16k`` one call of Phi-4-mini-flash's differential
attention (20 on 10, q and k 64 wide on v 128, W = 512).  ``--sweep
laguna-16k``, ``mellum-16k`` and ``phi4-16k`` time the windowed call alone,
forward and backward apart: `_auto_tiles`' own, the band at tiles of 128,
256 and 512 (``BAND_TILES``) and the walk at 512 (``WALK_TILE``), which is
what `_auto_tiles` takes under a window is set from (a minute a shape).

``moe_held_8k`` is no attention case: one routed layer of kanana's share
(`ops/moe.py`: dispatch, the held experts, combine) over all the routed
rows, over the held rows' buffer, and as `moe_dispatch` chooses between
them, all of it against a float32 loop over the held experts (a line a
form: forward and forward + backward device ms, the custom calls' part of
it and the longest operations by name); then the two movements alone
(`ops/moe_rows.py`), each way tried beside the least time of its bytes:
the buffer taken (XLA's gather and mask; the row kernel at each of
``MOE_TAKE_TILES``) and summed back into its tokens (XLA's gather a choice;
the row kernel at each of ``MOE_SUM_TILES``; the buffer sorted by token; a
scatter-add), with the kernels' own ms and the largest difference to the
XLA form, which is 0.  ``moe_held_16k`` is the same at mellum2's share (16
of 64 experts, 8 choices a token, hidden 2,304, experts 896 wide), whose
buffer of 65,536 rows is too large for XLA to keep in VMEM.  About six
minutes a case: the float32 loop is most of it.  Both end with the grouped
products alone (`grouped_case`; ``moe_all_4k`` is nothing else, at OLMoE's
64 groups of 2,048 rows with every expert held): `swiglu` over the three
stacks of the share at a random router's ragged groups, each product
`jax.lax.ragged_dot` at the width XLA:TPU's kernel wants (whole 256s) and
the repo's kernels (`ops/grouped_matmul.py`) at each of ``GROUPED_TILES``
rows a tile (``kept``: the one `_row_tile` chooses), a line a form: forward
and forward + backward device ms beside the least time of the operations
and of the bytes, and the largest error of the result and of the four
gradients relative to `ragged_dot` over float32 operands.

``shortconv_8k`` is no attention case either: one gated short convolution
(`models/layers.py:short_conv`) at (2, 8192, 2048) with 3 taps, the whole
operator and its gates and taps alone, in each form tried for the taps,
against a float32 sum over taps and against the least time of the gates'
and taps' bytes.

``target_8k`` is the indexer's loss alone (`ops/sparse_index.py:_pallas_loss`)
at the keye cell's shape (2 x 8,192, 32 query heads on 4 key heads of 128,
blocks of 512 queries, 2,048 keys a query): one layer's rows' losses and
gradient to the scores, the one Mosaic kernel (the target, its sums and the
gradient, a q tile's whole row in VMEM) beside `_loss_reference` (the plain XLA
form by blocks: a block's target and every pass after it through HBM), device
ms of every operation, the seconds each took to compile and the largest error
of the losses and of the gradient relative to the reference on float32
operands.  ``--sweep target-8k`` times the kernel at other tiles.

``scores_8k`` is the index scores alone (`ops/sparse_index.py:index_scores`'s
two kernels, `_pallas_scores` and `_pallas_scores_bwd`) at the keye cell's
shape (2 x 8,192, 16 heads of 64 on one key head, blocks of 512 queries): the
Mosaic kernels beside `_scores_reference` and `_scores_reference_bwd` (the
blocked XLA form), forward and backward device ms of every operation, the
seconds each took to compile, and the largest error of the scores and of the gradients to q, k and
w relative to the reference on float32 operands at the highest precision; the
cotangent is zero off 2,048 selected keys a query, as the loss's is.
``--sweep scores-8k`` times the kernels at other tiles.

``select_8k`` is the selection alone (`ops/sparse_index.py:select_top_k`'s
kernel, `_pallas_select`) at the same cell's shape (2 x 8,192 rows of 8,192
scores, the kernels' own of random bfloat16 queries and keys, 2,048 keys a
query): the one Mosaic kernel beside `_select_reference` (the threshold
search in plain XLA by blocks of 512 queries), device ms of every operation,
the seconds each took to compile, and the bytes of the mask in which the two
differ, which is 0.  ``--sweep select-8k`` times the kernel at other tiles
and at two bits of a threshold a pass, each against the reference's bytes.

``head_loss_8k`` is the head and its chunked loss alone
(`models/layers.py:head_and_loss`, an untied head) at the cells' shape: 16,384
rows of 2,048, chunks of 2,048, a vocabulary of 49,152 (ouro's a walk) and of
50,304 (OLMoE's): device ms of the loss alone (nobody differentiates: one
product a chunk) and of its value and gradient (three products a chunk in one
walk), beside the least time of those products' operations at the chip's peak,
the longest operations of the gradient by name, and the largest error of the
loss and of both gradients relative to float32 logits at the highest
precision.  No cell runs it: it is the instrument for what the head's walk
costs beyond its three products.

``ssd_8k`` is the chunked state-space scan alone (`ops/ssd.py:ssd_scan`) at
(2, 8192, 64 heads of 64) with 8 groups and a state of 128, in bfloat16 and
in float32: the Pallas kernels as `ssd_scan` calls them, and the `einsum`
form with each form of the carry over the chunks, all against the
recurrence run position by position in float32
(`benchmark/reference/nemotron_h.py:recurrence`), y and the six gradients,
device ms forward and forward + backward, beside the least time of the
scan's operations and bytes (`families/nemotron_h.py:ssd_cost`'s count for
one layer).  ``--sweep ssd-chunk`` times all three at chunks of 64, 128 and
256 (the published 128 is what the timed path runs) and compares nothing.

``gatenorm_8k`` is Mamba-2's gated group norm alone
(`ops/gated_norm.py:gated_rms_norm`) at the nemotron cell's shape, (2, 8192,
4096) in 8 groups: the plain jax form (`_reference`), the groups' mean of
squares as a product of g^2 with a (4096, 8) matrix of 0 and 1 and back (no
re-laying to (..., 8, 512); tried and not kept), and the two Mosaic kernels
at each row tile of ``GATENORM_TILES`` (``kept``: the one `_row_tile`
chooses): device ms forward and forward + backward (one `jax.grad` in y, z
and the gain), the seconds both took to compile, the least time of the
bytes, and the largest error of the result and of the three gradients
relative to the plain form on float32 operands.

``headnorm_16k`` is the same file's second rule alone
(`ops/gated_norm.py:head_rms_norm`: an RMSNorm a head, the gate behind it) at
the ling cell's shape, (1, 16384, 2048) in 16 heads of one lane tile, as a
KDA mixer calls it: ``gated`` (the head's norm with its gain and the output
gate) and ``l2`` (q's and k's L2 norm: a constant gain, no gate).  A line a
rule for the plain jax form (`_head_reference`, the (..., 16, 128) view XLA
re-tiles the rows for) and one for the two Mosaic kernels at each row tile of
``HEADNORM_TILES`` (``kept``: `_row_tile`'s), with what ``gatenorm_8k``'s
lines hold.

``conv_8k`` is Mamba-2's causal convolution alone
(`ops/causal_conv.py:causal_conv`) at the nemotron cell's shape: 6,144
channels of 4 taps with a bias and a SiLU, read out of a (2, 8192, 10304)
source at column 4,096.  The plain jax form (`_reference`, the slice
included) beside the two Mosaic kernels at each pair of ``CONV_TILES``
(the forward's and the backward's (row tile, channel block, turns of the
inner loop written out)), as one result and, at the kept pair, cut into x,
B and C (``kept``: `_FORWARD` and `_BACKWARD` of the module): device ms of
the forward alone and of a `jax.vjp` under a given cotangent (forward,
backward and the padding of v's gradient), the kernels' own ms in both,
the seconds both took to trace and lower and to compile, the least time of
the bytes (forward reads and writes the channels, backward reads twice and writes once), and
the largest error of the result and of the three gradients relative to the
plain form on float32 operands.

``ling_16k`` is Kimi Delta Attention's rule alone (`ops/kda.py`) at the ling
cell's shape, one sequence of 16,384, 16 heads of 128, chunks of 64, g over
the whole of (-5, 0) with a sixteenth of the positions at the bound
(``KDA_CASES``): a line for the float32 recurrence position by position (the
benchmark's reference's), one for the plain chunked form and one for the
Mosaic kernels as the module plans them, with the heads a grid step takes
(``Hb``; see `kda_case`; a minute).  ``--sweep ling-16k`` times the kernels
alone at each count of heads a grid step of ``KDA_SWEEP``, ``kept`` the
module's own plan.

``evabyte_16k`` is EVA attention alone (`ops/eva.py`) at the evabyte cell's
shape, one sequence of 16,384, 16 heads of 128, windows of 2,048 in chunks of
16 (``EVA_CASES``): a line for the float32 definition (every key and every
summary scored under boolean masks, 256 query rows at a time: the
benchmark's reference's), one for the plain masked form by windows and one
for the kernels (the flash pair under `BlockRule(aligned=2048)`, the remote
pair, the pooling pair): forward and forward + backward ms, the Mosaic
kernels' own ms, the longest operations, and the error of o and the five
gradients against the definition (see `eva_case`; two minutes).
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import glob
import importlib
import json
import os
import sys
import tempfile
import warnings

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (B, S, H, D[, Dv]) -> Mosaic kernels in forward + backward
CASES = {
    "lanes_fused_bwd": ((16, 1024, 12, 64), 2),
    "bhsd_fused_bwd": ((4, 1024, 25, 64), 2),
    "long_fused_bwd": ((2, 2048, 32, 128), 2),
    "olmoe_4k": ((4, 4096, 16, 128), 2),
    "mla_8k": ((2, 8192, 32, 192, 128), 2),
    "gqa_8k": ((2, 8192, 32, 64), 2),
    "gqa16_8k": ((2, 8192, 32, 128), 2),
}
# key/value heads of the cases and sweeps whose k and v have fewer than q
KV_HEADS = {"laguna-16k": 8, "mellum-16k": 4, "phi4-16k": 10,
            "gqa_8k": 8, "gqa-8k": 8, "gqa16_8k": 2, "gqa16-8k": 2,
            "blocks-16k": 4}
# (block, kinds) of the sweeps under a rule that is not the diagonal
# (`ops/flash_attention.py:BlockRule`)
RULES = {"blocks-16k": (4, 2), "laguna-16k": (1, 1, 512),
         "mellum-16k": (1, 1, 1024), "phi4-16k": (1, 1, 512)}
# the tiles a window's band is swept at (`ops/flash_attention.py:_band`: the
# forward reads its q tile alone and the backward its k tile alone, so
# square ones say everything), then the walk of tiles at `WALK_TILE`
BAND_TILES = ((128, 128), (256, 256), (512, 512))
WALK_TILE = (512, 512)
# (shape, key/value heads, window[, the query heads of the call with no
# window]) of the calls under a window (`BlockRule(window=W)`): Mellum 2's
# sliding layers at the cell's length and at half of it, each beside the
# same call with no window; Laguna-XS.2's sliding layers (64 query heads on
# 8, a window narrower than a pair of 512-tiles) beside its full layers'
# call (48 on 8, no window)
WINDOW_CASES = {
    "mellum_16k": ((1, 16384, 32, 128), 4, 1024),
    "mellum_8k": ((1, 8192, 32, 128), 4, 1024),
    "laguna_16k": ((1, 16384, 64, 128), 8, 512, 48),
    # Phi-4-mini-flash's differential attention, one of a layer's two
    # calls: 20 query heads on 10, q and k 64 wide on v 128 wide
    "phi4_16k": ((1, 16384, 20, 64, 128), 10, 512),
}
WINDOW_TILES = ((None, None), (256, 256), (512, 512), (1024, 1024))
# a window of 512 is swept a tile further down
NARROW_TILES = ((None, None), (128, 128), (256, 256), (512, 512))
# (B, S, C, N) of one Mamba-1 selective scan
SSCAN_CASES = {
    "sscan_16k": (1, 16384, 5120, 16),
}
# (B, S, H, P, G, N, chunk) of one state-space scan
# (B, S, H, K = V, chunk): the delta rule of the ling cell's KDA layers
KDA_CASES = {
    "ling_16k": (1, 16384, 16, 128, 64),
}
# (case, heads a grid step) `--sweep ling-16k` times a case of KDA_CASES at
KDA_SWEEP = {
    "ling-16k": ("ling_16k", (1, 2, 4, 8, 16)),
}
# (B, S, H, D, window, chunk): EVA attention of the evabyte cell's layers
EVA_CASES = {
    "evabyte_16k": (1, 16384, 16, 128, 2048, 16),
}

SSD_CASES = {
    "ssd_8k": (2, 8192, 64, 64, 8, 128, 128),
}
# the chunks `--sweep ssd-chunk` times a case of SSD_CASES at
SSD_SWEEP = {
    "ssd-chunk": ("ssd_8k", (64, 128, 256)),
}
# (B, S, E, taps) of one conv operator
SHORTCONV_CASES = {
    "shortconv_8k": (2, 8192, 2048, 3),
}
# (B, S, C, groups) of one gated group norm, and the row tiles its kernels
# are timed at (1,024 rows want 80 MiB of VMEM for the backward's five
# blocks twice over, and the compiler refuses them under the kernels' 48)
GATENORM_CASES = {
    "gatenorm_8k": (2, 8192, 4096, 8),
}
GATENORM_TILES = (64, 128, 256, 512)
# (B, S, C, heads) of one norm a head, and the row tiles its kernels are
# timed at
HEADNORM_CASES = {
    "headnorm_16k": (1, 16384, 2048, 16),
}
HEADNORM_TILES = (128, 256, 512, 1024)
# (B, S, source's width, first column, (x, B, C) widths, taps) of one causal
# convolution, and the forward's and the backward's (row tile, channel block,
# turns written out) its kernels are timed at
CONV_CASES = {
    "conv_8k": (2, 8192, 10304, 4096, (4096, 1024, 1024), 4),
}
CONV_TILES = tuple(((512, 1024, 1), bwd) for bwd in (
    (2048, 256, 4), (1024, 256, 4), (512, 256, 4), (2048, 256, 2),
    (2048, 256, 8), (2048, 512, 2), (2048, 128, 8))) + tuple(
    (fwd, (2048, 256, 4)) for fwd in (
        (2048, 1024, 1), (512, 512, 2), (1024, 256, 4)))
# (T, k, held, experts, E, W): tokens, choices a token, experts held of the
# router's, hidden and expert widths
MOE_CASES = {
    "moe_held_8k": (16384, 6, 16, 128, 2048, 768),
    # mellum2's share: 16 of 64, 8 choices a token, hidden 2,304
    "moe_held_16k": (16384, 8, 16, 64, 2304, 896),
}
# the result rows of a grid step the two row kernels (`ops/moe_rows.py`)
# are timed at, beside the ones `_tile` chooses
# every expert held: OLMoE's 64 groups of 2,048 rows (`grouped_case` only)
MOE_ALL_CASES = {
    "moe_all_4k": (16384, 8, 64, 64, 2048, 1024),
}
# the rows of a tile the grouped kernels (`ops/grouped_matmul.py`) are
# timed at, and the multiple XLA:TPU's own kernel wants of a width
GROUPED_TILES = (128, 256, 512)
XLA_GROUPED_WIDTH = 256
MOE_TAKE_TILES = (128, 256, 512)
MOE_SUM_TILES = (128, 256)
# (B, S, H, H_kv, D, block, top_k) of one indexer loss's target
TARGET_CASES = {
    "target_8k": (2, 8192, 32, 4, 128, 512, 2048),
}
# the (q tile, k tile) `--sweep target-8k` times a case of TARGET_CASES at
TARGET_SWEEP = {
    "target-8k": ("target_8k", ((128, 512), (128, 1024), (256, 256),
                                (256, 512), (256, 1024))),
}
# (B, S, J, D_I, block, top_k) of one layer's index scores
SCORES_CASES = {
    "scores_8k": (2, 8192, 16, 64, 512, 2048),
}
# the (q tile, k tile) `--sweep scores-8k` times a case of SCORES_CASES at
SCORES_SWEEP = {
    "scores-8k": ("scores_8k", ((128, 512), (256, 256), (256, 512),
                                (256, 1024), (512, 512), (512, 1024))),
}
# (B, S, J, D_I, block, top_k) of the index scores one layer's selection reads
SELECT_CASES = {
    "select_8k": SCORES_CASES["scores_8k"],
}
# the (q tile, k tile, bits a pass) `--sweep select-8k` times a case of
# SELECT_CASES at
SELECT_SWEEP = {
    "select-8k": ("select_8k", (
        (64, 1024, 1), (128, 256, 1), (128, 512, 1), (128, 1024, 1),
        (128, 2048, 1), (256, 512, 1), (256, 1024, 1), (512, 512, 1),
        (64, 1024, 2), (128, 1024, 2), (256, 1024, 2), (512, 512, 2))),
}
# the tiles of a sequence past `_WHOLE_SEQ_MAX`: square, and the backward's
# k tile (a grid step) beside another q tile (its loop's step)
LONG_TILES = ((256, 256), (512, 512), (1024, 1024), (256, 512), (512, 1024),
              (1024, 512))
# the benchmark's cells: medium's step, XL's on one chip of four, OLMoE's
SWEEP = {
    "gpt2-medium": ((16, 1024, 16, 64), (
        (128, 128), (256, 256), (512, 512), (1024, 1024))),
    "gpt2-xl-fsdp4": ((4, 1024, 25, 64), (
        (256, 256), (512, 512), (1024, 1024))),
    "olmoe-1b-7b": ((4, 4096, 16, 128), LONG_TILES),
    # no cell: heads of 128 on a short sequence (llama's prefill), and
    # medium's tokens a step at half the sequence
    "d128-1k": ((4, 1024, 16, 128), (
        (128, 128), (256, 256), (512, 512), (1024, 1024))),
    "s512-d64": ((32, 512, 16, 64), ((128, 128), (256, 256), (512, 512))),
    # kanana-2-30b-a3b's latent attention, multiplied out: q, k 192, v 128
    "mla-8k": ((2, 8192, 32, 192, 128), LONG_TILES),
    # LFM2-24B-A2B's attention layers: 32 query heads on 8 key/value heads
    "gqa-8k": ((2, 8192, 32, 64), LONG_TILES),
    # Nemotron-3-Nano's: 32 query heads on 2 key/value heads of 128
    "gqa16-8k": ((2, 8192, 32, 128), LONG_TILES),
    # SDAR-30B-A3B's block diffusion: 2 x 8,192 clean rows and their noised
    # copies under the rule of blocks of 4, 32 query heads on 4 of 128
    "blocks-16k": ((2, 16384, 32, 128), ((256, 256), (512, 512),
                                         (1024, 1024), (1024, 512))),
    # Laguna-XS.2's sliding layers: a window of 512, 64 query heads on 8 of
    # 128, one sequence; Mellum 2's: 1,024, 32 on 4; one call of
    # Phi-4-mini-flash's: 512, 20 on 10, q and k 64 wide on v 128
    "laguna-16k": ((1, 16384, 64, 128), BAND_TILES),
    "mellum-16k": ((1, 16384, 32, 128), BAND_TILES),
    "phi4-16k": ((1, 16384, 20, 64, 128), BAND_TILES),
}
# (rows, E, vocabularies, rows a chunk) of one head and its loss
HEAD_CASES = {
    "head_loss_8k": (16384, 2048, (49152, 50304), 2048),
}
TOLERANCE = 0.05


# elements of the reference's S x S scores alive at once: 1 GiB in float32
REFERENCE_SCORES = 1 << 28


def _qkv(shape, dtype, kv_heads=None):
    """q, k of (B, S, H, D) and v of (B, S, H, Dv), Dv = D if not given; k
    and v with ``kv_heads`` heads if given."""
    import jax

    b, s, h, d = shape[:4]
    widths = (d, d, shape[4] if len(shape) > 4 else d)
    heads = (h, kv_heads or h, kv_heads or h)
    return tuple(jax.random.normal(jax.random.PRNGKey(i), (b, s, n, w), dtype)
                 for i, (n, w) in enumerate(zip(heads, widths)))


def _device_events(f, args, calls):
    """[(name, start ns, duration ns)] of the first chip's ``XLA Ops`` line
    over ``calls`` calls of jitted ``f`` under the profiler."""
    import jax

    jax.block_until_ready(f(*args))
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(calls):
                out = f(*args)
            jax.block_until_ready(out)
        found = glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(found[0])
    for plane in data.planes:
        if plane.name == "/device:TPU:0":
            return [(e.name, e.start_ns, e.duration_ns)
                    for line in plane.lines if line.name == "XLA Ops"
                    for e in line.events]
    raise ValueError("the trace holds no /device:TPU:0 plane")


def kernel_ms(f, *args, calls=5):
    """Device ms of the Mosaic kernels in one call of jitted ``f``: the
    durations of every ``tpu_custom_call`` summed and divided by the
    calls."""
    ns = sum(duration for name, _, duration in _device_events(f, args, calls)
             if "tpu_custom_call" in name)
    return round(ns / calls / 1e6, 4)


def busy_ms(f, *args, calls=3):
    """Device ms of one call of jitted ``f``, every operation counted: the
    union of the events' intervals (a conditional's event covers its
    branch's), divided by the calls."""
    busy, until = 0.0, 0.0
    for _, start, duration in sorted(
            _device_events(f, args, calls), key=lambda e: e[1]):
        busy += max(0.0, start + duration - max(start, until))
        until = max(until, start + duration)
    return round(busy / calls / 1e6, 4)


def longest_ops(f, *args, calls=3, top=6):
    """{operation: device ms a call} of the ``top`` longest operations of
    jitted ``f`` by name, a name's events summed.  A name here is the
    instruction's whole text: its own name and its result's type, the
    first 96 characters, tell it apart."""
    by_name = {}
    for name, _, duration in _device_events(f, args, calls):
        by_name[name[:96]] = by_name.get(name[:96], 0) + duration
    return {name: round(ns / calls / 1e6, 4) for name, ns in sorted(
        by_name.items(), key=lambda item: -item[1])[:top]}


def head_case(name, dtype):
    """The head and its chunked loss at ``HEAD_CASES[name]``: a line for
    each vocabulary (loss-only ms; value-and-gradient ms in x and the
    head; the least time of one and of three products of rows x E x V at
    the chip's peak; the gradient's longest operations; errors against
    float32 logits made chunk by chunk at the highest precision)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import layers

    N, E, vocabularies, chunk_rows = HEAD_CASES[name]
    for V in vocabularies:
        ks = jax.random.split(jax.random.PRNGKey(V), 3)
        x = jax.random.normal(ks[0], (1, N, E), dtype)
        head = {"kernel": (0.02 * jax.random.normal(ks[1], (E, V))).astype(
            dtype)}
        targets = jax.random.randint(ks[2], (1, N), 0, V)
        loss = jax.jit(lambda x, head: layers.head_and_loss(
            x, head, targets, chunk_rows))
        grad = jax.jit(jax.value_and_grad(lambda x, head: layers.head_and_loss(
            x, head, targets, chunk_rows), (0, 1)))

        @jax.jit
        def exact(x, head):
            """float32 operands, the highest precision, a chunk's dense
            logits at a time: its gradients summed by jax."""
            def chunk(xi, ti, kernel):
                logp = jax.nn.log_softmax(jnp.matmul(
                    xi, kernel, precision="highest"), axis=-1)
                return -jnp.sum(jnp.take_along_axis(
                    logp, ti[:, None], -1)) / N
            xf = x.astype(jnp.float32).reshape(-1, chunk_rows, E)
            kernel = head["kernel"].astype(jnp.float32)
            total, dx, dk = 0.0, [], jnp.zeros_like(kernel)
            for xi, ti in zip(xf, targets.reshape(-1, chunk_rows)):
                part, (dxi, dki) = jax.value_and_grad(chunk, (0, 2))(
                    xi, ti, kernel)
                total, dk = total + part, dk + dki
                dx.append(dxi)
            return total, (jnp.concatenate(dx).reshape(x.shape),
                           {"kernel": dk})

        rel = lambda g, w: round(float(
            np.max(np.abs(np.asarray(g, np.float32) - np.asarray(w)))
            / np.max(np.abs(np.asarray(w)))), 5)
        got, want = grad(x, head), exact(x, head)
        product_ms = 2 * N * E * V / 197e12 * 1e3
        yield {"case": name, "form": f"vocab_{V}", "rows": N, "chunk_rows":
               chunk_rows,
               "loss_ms": busy_ms(loss, x, head),
               "loss_and_grad_ms": busy_ms(grad, x, head),
               "least_loss_ms": round(product_ms, 4),
               "least_loss_and_grad_ms": round(3 * product_ms, 4),
               "longest_ops_ms": longest_ops(grad, x, head),
               "rel_err": {what: rel(g, w) for what, g, w in zip(
                   ("loss", "dx", "dhead"), jax.tree.leaves(got),
                   jax.tree.leaves(want))}}


def moe_case(name, dtype):
    """One routed layer of a share of the experts at ``MOE_CASES[name]``:
    yields a line for each form (forward ms; forward and backward ms of
    one `jax.grad` in x, the weights and the three matrices; largest
    error of y and of each gradient relative to a float32 loop over the
    held experts), then a line for each way tried to sum the buffer into
    its tokens (forward ms alone)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.layers import swiglu
    from ray_tpu.ops import grouped_matmul, moe, moe_rows

    T, k, count, n_experts, E, W = MOE_CASES[name]
    held = (0, count)
    C = moe.buffer_rows(T * k, count, n_experts)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (T, E), dtype)
    scores = jax.nn.sigmoid(jax.random.normal(ks[1], (T, n_experts)))
    weights, experts = jax.lax.top_k(scores, k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    gate, up, down = (
        (0.02 * jax.random.normal(key, shape)).astype(dtype)
        for key, shape in zip(ks[2:5], ((count, E, W), (count, E, W),
                                        (count, W, E))))
    seed = jax.random.normal(ks[5], (T, E), jnp.float32)   # d loss / d y

    def routed(over):
        """The layer with ``over`` between the sorts and the result."""
        def y(experts, x, weights, gate, up, down):
            def run(xs, sizes):
                return swiglu(xs, gate, up, down,
                              matmul=grouped_matmul.over(sizes, xs.shape[0]))
            if over is None:
                return moe.moe_dispatch(x, weights, experts, n_experts, run,
                                        held=held)[0]
            return over(x, weights,
                        *moe._sort_by_expert(experts, n_experts, held),
                        held, run)
        return y

    def reference(experts, x, weights, gate, up, down):
        """Every held expert over every token in float32, weighted by the
        token's choice of it (0 where it chose another)."""
        y = jnp.zeros((T, E), jnp.float32)
        with jax.default_matmul_precision("highest"):
            for e in range(count):
                w = jnp.sum(jnp.where(experts == e, weights, 0), axis=1)
                y = y + w[:, None] * swiglu(x, gate[e], up[e], down[e])
        return y

    def both(y):
        forward = jax.jit(y)
        grad = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(y(*a).astype(jnp.float32) * seed),
            (1, 2, 3, 4, 5)))
        return forward, grad

    def in_f32(args):
        return (args[0], *(a.astype(jnp.float32) for a in args[1:]))

    args = (experts, x, weights, gate, up, down)
    exact = both(reference)
    want = (exact[0](*in_f32(args)), *exact[1](*in_f32(args))[1])
    forms = {"all_rows": moe._over_all_rows,
             "held_rows": functools.partial(moe._over_held_rows, C),
             "moe_dispatch": None,
             # a router that sends every token to held experts, more than
             # the buffer holds: `moe_dispatch` runs over all the rows
             "moe_dispatch_overflowed": None}
    for form, over in forms.items():
        if form == "moe_dispatch_overflowed":
            most, favoured = jax.lax.top_k(
                scores + (jnp.arange(n_experts) < count), k)
            args = (favoured, x,
                    most / jnp.sum(most, axis=-1, keepdims=True), *args[3:])
            want = (exact[0](*in_f32(args)), *exact[1](*in_f32(args))[1])
        forward, grad = both(routed(over))
        got = (forward(*args), *grad(*args)[1])
        errs = {what: round(float(
            np.max(np.abs(np.asarray(g, np.float32) - np.asarray(w)))
            / np.max(np.abs(np.asarray(w)))), 5) for what, g, w in zip(
                ("y", "dx", "dweights", "dgate", "dup", "ddown"), got, want)}
        yield {"case": name, "form": form, "buffer_rows": C,
               "rows_held": int(jnp.sum(args[0] < count)),
               "fwd_ms": busy_ms(forward, *args),
               "fwd_bwd_ms": busy_ms(grad, *args),
               "custom_calls_ms": kernel_ms(grad, *args),
               "longest_ops_ms": longest_ops(grad, *args, top=8),
               "rel_err": errs}

    # the two movements alone, each way tried, beside the least time of
    # their bytes: the rows that exist read, the result written
    tokens, scale, where, by_token, same, start, some = jax.jit(
        lambda: _buffer_of(experts, weights, held, n_experts, C))()
    ys = jax.random.normal(ks[0], (C, E), dtype)
    slot, n_held = where[1:3]
    valid = jnp.arange(C) < n_held
    size = jnp.dtype(dtype).itemsize

    def least_ms(written):
        return round((int(n_held) + written) * E * size / 819e9 * 1e3, 4)

    takes = {"gather_and_mask": moe_rows._take_reference, **{
        f"row_kernel_{tile}": functools.partial(moe_rows._take, tile=tile)
        for tile in MOE_TAKE_TILES}}
    kept = None
    for form, take in takes.items():
        take = jax.jit(take)
        out = np.asarray(take(x, tokens, n_held), np.float32)
        kept = out if kept is None else kept
        yield {"case": name, "take": form, "rows": C,
               "fwd_ms": busy_ms(take, x, tokens, n_held),
               "kernel_ms": kernel_ms(take, x, tokens, n_held),
               "least_ms": least_ms(C),
               "max_abs_diff_to_xla": round(float(
                   np.max(np.abs(out - kept))), 5)}

    def sorted_neighbours(ys):
        """The buffer in token order (a sort of C keys, a gather of C
        rows), to each row the up to k-1 after it of the same token
        added, each token's first row (or nothing) gathered."""
        z = ys[by_token].astype(jnp.float32) * scale[by_token][:, None]
        after = jnp.concatenate([z, jnp.zeros((k - 1, E), z.dtype)])
        for d, same_token in enumerate(same, 1):
            z = z + jnp.where(same_token[:, None], after[d:d + C], 0)
        return jnp.where(some[:, None], z.astype(dtype)[start], 0)

    by_choice = scale[jnp.minimum(slot, C - 1)]          # (T, k)
    puts = {
        # what the kernel stands for (`ops/moe_rows.py:_sum_reference`):
        # a gather of T rows for each of the k choices, summed
        "gather_per_choice": lambda ys: moe_rows._sum_reference(
            ys, n_held, slot, by_choice),
        **{f"row_kernel_{tile}": functools.partial(
            lambda ys, tile: moe_rows._sum(ys, n_held, slot, by_choice,
                                           tile=tile),
            tile=tile) for tile in MOE_SUM_TILES},
        "sorted_neighbours": sorted_neighbours,
        "scatter_add": lambda ys: jnp.zeros((T, E), jnp.float32).at[
            tokens].add(jnp.where(valid[:, None], ys.astype(jnp.float32)
                                  * scale[:, None], 0)).astype(dtype),
    }
    kept = None
    for form, put in puts.items():
        put = jax.jit(put)
        out = np.asarray(put(ys), np.float32)
        kept = out if kept is None else kept
        yield {"case": name, "put": form, "fwd_ms": busy_ms(put, ys),
               "kernel_ms": kernel_ms(put, ys), "least_ms": least_ms(T),
               "max_abs_diff_to_xla": round(float(
                   np.max(np.abs(out - kept))), 5)}


def grouped_case(name, dtype):
    """The grouped products of one layer's experts alone, at the case's
    share and a random router's ragged groups: `swiglu` over the three
    stacks with each product `jax.lax.ragged_dot` at the width XLA:TPU's
    kernel wants (whole `XLA_GROUPED_WIDTH`s) and with the repo's kernels
    (`ops/grouped_matmul.py`) at each of ``GROUPED_TILES`` (``kept``: the
    one `_row_tile` chooses): a line a form with forward and forward +
    backward device ms (one `jax.grad` in the rows and the three stacks),
    the least time of the operations (6 and 18 rows E W at the bf16 peak)
    and of the bytes, and the largest error of the result and of each
    gradient relative to `ragged_dot` over float32 operands."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.layers import swiglu
    from ray_tpu.ops import grouped_matmul, moe

    T, k, count, n_experts, E, W = {**MOE_CASES, **MOE_ALL_CASES}[name]
    R = moe.buffer_rows(T * k, count, n_experts)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    scores = jax.nn.sigmoid(jax.random.normal(ks[1], (T, n_experts)))
    _, experts = jax.lax.top_k(scores, k)
    sizes = moe._sort_by_expert(experts, n_experts, (0, count))[1][:count]
    rows = int(jnp.sum(sizes))
    held = (jnp.arange(R) < rows)[:, None]
    xs = jnp.where(held, jax.random.normal(ks[0], (R, E), dtype), 0)
    stacks = [(0.02 * jax.random.normal(key, shape)).astype(dtype)
              for key, shape in zip(ks[2:5], ((count, E, W), (count, E, W),
                                              (count, W, E)))]
    seed = jnp.where(held, jax.random.normal(ks[5], (R, E), jnp.float32), 0)

    def both(matmul, widen=0):
        def y(xs, gate, up, down):
            pad = lambda w, axis: jnp.pad(w, [
                (0, -w.shape[a] % widen if widen and a == axis else 0)
                for a in range(3)])
            return jnp.where(held, swiglu(
                xs, pad(gate, 2), pad(up, 2), pad(down, 1),
                matmul=matmul), 0)
        # the cotangent an argument: a constant of a gigabyte would be
        # compiled into every form's program
        return jax.jit(y), jax.jit(jax.grad(
            lambda *a: jnp.sum(y(*a[:4]).astype(jnp.float32) * a[4]),
            (0, 1, 2, 3)))

    ragged = lambda a, w: jax.lax.ragged_dot(a, w, sizes)
    args = (xs, *stacks)
    exact = both(ragged)
    in_f32 = [a.astype(jnp.float32) for a in args]
    want = (exact[0](*in_f32), *exact[1](*in_f32, seed))
    size = jnp.dtype(dtype).itemsize
    # the rows read and written by each product and the stacks once
    moved = size * (rows * (2 * (E + W) + W + E) + 3 * count * E * W)
    kept = grouped_matmul._row_tile(R)
    forms = {"ragged_dot": both(ragged, XLA_GROUPED_WIDTH), **{
        f"kernel_{tile}": both(grouped_matmul.over(sizes, R, tile))
        for tile in GROUPED_TILES}}
    for form, (forward, grad) in forms.items():
        got = (forward(*args), *grad(*args, seed))
        yield {"case": name, "form": form, "buffer_rows": R,
               "rows_held": rows, "groups": count,
               "kept": form == f"kernel_{kept}",
               "fwd_ms": busy_ms(forward, *args),
               "fwd_bwd_ms": busy_ms(grad, *args, seed),
               "least_ops_ms": [round(n * rows * E * W / 197e12 * 1e3, 4)
                                for n in (6, 18)],
               "least_bytes_ms": [round(n * moved / 819e9 * 1e3, 4)
                                  for n in (1, 3)],
               "rel_err": {what: round(float(
                   np.max(np.abs(np.asarray(g, np.float32) - np.asarray(w)))
                   / np.max(np.abs(np.asarray(w)))), 5)
                   for what, g, w in zip(
                       ("y", "dxs", "dgate", "dup", "ddown"), got, want)}}


def _buffer_of(experts, weights, held, n_experts, C):
    """What `ops/moe.py:_over_held_rows` derives from a routing, and what
    the other forms of the sum need: (each buffered row's token, its
    weight, `where`; the buffer's rows in token order, whether each of the
    k-1 rows after one is the same token's, each token's first row there,
    whether it has one)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    T, k = experts.shape
    by_expert, sizes = moe._sort_by_expert(experts, n_experts, held)
    n_held = jnp.sum(sizes[held[0]:held[0] + held[1]])
    where = moe._buffer_index(C, k, by_expert, n_held)
    first = where[3]
    in_order, by_token = jax.lax.sort(
        (jnp.where(jnp.arange(C) < n_held, first, T * k),
         jnp.arange(C, dtype=jnp.int32)), num_keys=1)
    token = jnp.concatenate([in_order // k, jnp.full((k - 1,), -1, jnp.int32)])
    same = [token[d:d + C] == token[:C] for d in range(1, k)]
    mine = jnp.sum(where[1] < C, axis=1, dtype=jnp.int32)
    return (where[0], weights.reshape(T * k)[first], where, by_token, same,
            jnp.cumsum(mine) - mine, mine > 0)


@contextlib.contextmanager
def walking():
    """The windowed calls made inside keep the walk of tiles: no call takes
    a band (`ops/flash_attention.py:_band` says None; the kernels are jitted
    on their static arguments, so the caches go before and after)."""
    import jax

    from ray_tpu.ops import flash_attention as fa

    band, fa._band = fa._band, lambda *args, **kwargs: None
    jax.clear_caches()
    try:
        yield
    finally:
        fa._band = band
        jax.clear_caches()


def then_the_walk(blocks, windowed):
    """[(tile, the context to run it in)] of a windowed call's lines: each
    of ``blocks`` as the module takes it and then, under a window,
    `WALK_TILE` with the band taken out (`walking`)."""
    return [(block, contextlib.nullcontext) for block in blocks] \
        + [(WALK_TILE, walking)] * bool(windowed)


def time_passes(shape, dtype, block_q=None, block_k=None, kv_heads=None,
                repeated=False, every_op=False, causal=True):
    """(forward ms, backward ms) on the device of the kernels of causal
    ``flash_attention_bshd`` (or under the rule ``causal`` is) at ``shape``
    with these tiles (None: ``_auto_tiles``); k and v with ``kv_heads``
    heads, ``repeated`` to q's before the call.  ``every_op``: of every operation of the two passes
    (the transposes to the head-major kernels, the sums of a group's parts
    of dk and dv), not of the kernels alone."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import flash_attention as fa

    q, k, v = _qkv(shape, dtype, kv_heads)
    if repeated:
        k, v = (jnp.repeat(t, shape[2] // kv_heads, axis=2) for t in (k, v))
    forward = jax.jit(lambda q, k, v: fa._flash_fwd_bshd(
        q, k, v, causal, None, block_q, block_k))
    backward = jax.jit(lambda res, do: fa._flash_bwd_bshd(
        causal, None, block_q, block_k, res, do))
    o, res = forward(q, k, v)
    ms = busy_ms if every_op else kernel_ms
    return ms(forward, q, k, v), ms(backward, res, o)


def shortconv_case(name, dtype):
    """One conv operator at ``SHORTCONV_CASES[name]``: a line for each form
    of the gates and taps (forward ms; forward and backward ms of one
    `jax.grad` in [b c z] and the taps; largest error of the result and of
    both gradients relative to a float32 sum over taps; the least time of
    the bytes: forward reads 3E and writes E a token, backward reads 4E
    and writes 3E), then a line for the whole operator with the form
    `layers.short_conv` has."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import layers

    B, S, E, L = SHORTCONV_CASES[name]
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    bcz = jax.random.normal(ks[0], (B, S, 3 * E), dtype)
    taps = jax.random.uniform(ks[1], (E, L), jnp.float32, -L ** -0.5,
                              L ** -0.5)
    seed = jax.random.normal(ks[2], (B, S, E), jnp.float32)   # d loss / d y

    def by_convolution(bcz, w):
        """`lax.conv_general_dilated`, one group a channel, in the same
        (B, S, E) layout; the gates around it left to XLA."""
        b, c, z = (bcz[..., i * E:(i + 1) * E] for i in range(3))
        v = jax.lax.conv_general_dilated(
            b * z, w.T[:, None, :].astype(bcz.dtype), (1,), [(L - 1, 0)],
            dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=E)
        return c * v

    def padded_float32(bcz, w):
        """The first form `layers._gate_taps` had: g = b * z in float32,
        padded once, three slices of it; its backward by autodiff."""
        b, c, z = (bcz[..., i * E:(i + 1) * E] for i in range(3))
        g = jnp.pad((b * z).astype(jnp.float32),
                    ((0, 0), (L - 1, 0), (0, 0)))
        v = sum(w[:, j].astype(jnp.float32) * g[:, j:j + S]
                for j in range(L))
        return (c.astype(jnp.float32) * v).astype(bcz.dtype)

    def in_float32(bcz, w):
        bcz = bcz.astype(jnp.float32)
        b, c, z = (bcz[..., i * E:(i + 1) * E] for i in range(3))
        g = b * z
        v = jnp.zeros_like(g)
        for j in range(L):
            back = L - 1 - j
            v = v + w[:, j] * jnp.concatenate(
                [jnp.zeros((B, back, E)), g[:, :S - back]], axis=1)
        return c * v

    def both(y):
        return jax.jit(y), jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(y(*a).astype(jnp.float32) * seed), (0, 1)))

    rel = lambda g, w: round(float(
        np.max(np.abs(np.asarray(g, np.float32) - np.asarray(w)))
        / np.max(np.abs(np.asarray(w)))), 5)
    exact = both(in_float32)
    want = (exact[0](bcz, taps), *exact[1](bcz, taps)[1])
    peak = 819e9                       # HBM bytes a second, TPU v5e
    width = jnp.dtype(dtype).itemsize
    least = {"fwd": 4 * E * B * S * width / peak * 1e3,
             "bwd": 7 * E * B * S * width / peak * 1e3}
    for form, y in (("one_pass_shifted_gates", layers._gate_taps),  # kept
                    ("padded_float32", padded_float32),
                    ("conv_general_dilated", by_convolution)):
        forward, grad = both(y)
        w = taps.astype(dtype)
        got = (forward(bcz, w), *grad(bcz, w)[1])
        yield {"case": name, "form": form,
               "fwd_ms": busy_ms(forward, bcz, w),
               "fwd_bwd_ms": busy_ms(grad, bcz, w),
               "least_fwd_ms": round(least["fwd"], 4),
               "least_fwd_bwd_ms": round(least["fwd"] + least["bwd"], 4),
               "rel_err": {what: rel(g, t) for what, g, t in zip(
                   ("y", "dbcz", "dtaps"), got, want)}}
    # the whole operator as the model calls it
    u = jax.random.normal(ks[3], (B, S, E), dtype)
    p = {"in_proj": {"kernel": (0.02 * jax.random.normal(
             ks[4], (E, 3 * E))).astype(dtype)},
         "conv": {"kernel": taps.astype(dtype)},
         "out_proj": {"kernel": (0.02 * jax.random.normal(
             ks[5], (E, E))).astype(dtype)}}
    forward, grad = both(layers.short_conv)
    flops = 2 * B * S * 4 * E * E
    yield {"case": name, "form": "short_conv",
           "fwd_ms": busy_ms(forward, u, p),
           "fwd_bwd_ms": busy_ms(grad, u, p),
           "least_fwd_ms": round(flops / 197e12 * 1e3, 4),
           "least_fwd_bwd_ms": round(4 * flops / 197e12 * 1e3, 4)}


def gatenorm_case(name, dtype):
    """One gated group norm at ``GATENORM_CASES[name]``: a line for each
    form of it (forward ms; forward and backward ms of one `jax.grad` in
    y, z and the gain; the seconds both jits took to compile; the Mosaic
    kernels in them; the least time of the bytes: forward reads y and z
    and writes the result, backward reads three and writes two; largest
    error of the result and of the gradients relative to the plain form on
    float32 operands)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import gated_norm as gn

    B, S, C, G = GATENORM_CASES[name]
    eps = 1e-5
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    y, z = (jax.random.normal(k, (B, S, C), dtype) for k in ks[:2])
    gain = 1 + 0.3 * jax.random.normal(ks[2], (C,), jnp.float32)
    seed = jax.random.normal(ks[3], (B, S, C), dtype)   # d loss / d out

    def ones_matrix(y, z, gain):
        """ROADMAP Queue 1 item 7 (a)'s other form: the statistics on the
        MXU, nothing re-laid."""
        g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        ones = jnp.repeat(jnp.eye(G, dtype=jnp.float32), C // G, axis=0)
        r = jax.lax.rsqrt(jnp.dot(
            g * g, ones, precision=jax.lax.Precision.HIGHEST) / (C // G)
            + eps)
        return (g * (r @ ones.T) * gain).astype(y.dtype)

    def kernels(tile):
        return lambda y, z, gain: gn._kernels(
            y.reshape(B * S, C), z.reshape(B * S, C), gain,
            (G, eps, tile)).reshape(y.shape)

    plain = lambda y, z, gain: gn._reference(y, z, gain, G, eps)

    def both(f):
        return jax.jit(f), jax.jit(jax.value_and_grad(
            lambda y, z, gain, seed: jnp.sum(
                f(y, z, gain).astype(jnp.float32)
                * seed.astype(jnp.float32)), (0, 1, 2)))

    rel = lambda g, w: round(float(
        np.max(np.abs(np.asarray(g, np.float32) - np.asarray(w)))
        / np.max(np.abs(np.asarray(w)))), 5)
    exact = both(plain)
    f32 = (y.astype(jnp.float32), z.astype(jnp.float32), gain)
    want = (exact[0](*f32), *exact[1](*f32, seed)[1])
    kept = gn._row_tile(B * S, C, G)
    peak = 819e9                       # HBM bytes a second, TPU v5e
    array = B * S * C * jnp.dtype(dtype).itemsize
    forms = [("plain", plain, None), ("ones_matrix", ones_matrix, None)] + [
        ("kernel", kernels(tile), tile) for tile in GATENORM_TILES]
    for form, f, tile in forms:
        forward, grad = both(f)
        began = time.perf_counter()
        compiled = [forward.lower(y, z, gain).compile(),
                    grad.lower(y, z, gain, seed).compile()]
        line = {"case": name, "form": form,
                "compile_s": round(time.perf_counter() - began, 2),
                "mosaic_kernels": sum(c.as_text().count(
                    'custom_call_target="tpu_custom_call"')
                    for c in compiled),
                "fwd_ms": busy_ms(forward, y, z, gain),
                "fwd_bwd_ms": busy_ms(grad, y, z, gain, seed),
                "least_fwd_ms": round(3 * array / peak * 1e3, 4),
                "least_fwd_bwd_ms": round(8 * array / peak * 1e3, 4)}
        if tile:
            # the two kernels alone, without the loss that reads the result
            line.update(tile=tile, kept=tile == kept,
                        fwd_bwd_kernels_ms=kernel_ms(grad, y, z, gain, seed))
        got = (forward(y, z, gain), *grad(y, z, gain, seed)[1])
        line["rel_err"] = {what: rel(g, w) for what, g, w in zip(
            ("out", "dy", "dz", "dgain"), got, want)}
        yield line


def headnorm_case(name, dtype):
    """One norm a head at ``HEADNORM_CASES[name]``, gated and as an L2 norm:
    a line a rule and a form (forward ms; forward and backward ms of one
    `jax.grad` in every operand that has a gradient; the seconds both jits
    took to compile; the Mosaic kernels in them; the least time of the
    bytes: the gated rule's forward reads x and z and writes the result, its
    backward reads three and writes two; the L2 norm's read one and write
    one, read two and write one; largest error of the result and of the
    gradients relative to the plain form on float32 operands)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import gated_norm as gn

    B, S, C, H = HEADNORM_CASES[name]
    D = C // H
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x, z = (jax.random.normal(k, (B, S, C), dtype) for k in ks[:2])
    gain = 1 + 0.3 * jax.random.normal(ks[2], (C,), jnp.float32)
    seed = jax.random.normal(ks[3], (B, S, C), dtype)   # d loss / d out
    # rule -> (operands, eps, the constant gain, arrays a forward and a
    # forward + backward move at the least)
    rules = {"gated": ((x, z, gain), 1e-6, None, 3, 8),
             "l2": ((x,), 1e-6 / D, D ** -0.5, 2, 5)}
    rel = lambda g, w: round(float(
        np.max(np.abs(np.asarray(g, np.float32) - np.asarray(w)))
        / np.max(np.abs(np.asarray(w)))), 5)
    peak = 819e9                       # HBM bytes a second, TPU v5e
    array = B * S * C * jnp.dtype(dtype).itemsize
    for rule, (operands, eps, const, moved, moved_both) in rules.items():
        def plain(x, z=None, gain=const):
            return gn._head_reference(x, z, gain, H, eps)

        def kernels(tile):
            return lambda x, z=None, gain=None: gn._head_kernels(
                x, z, gain, (H, eps, tile, const))

        def both(f):
            return jax.jit(f), jax.jit(jax.value_and_grad(
                lambda *a: jnp.sum(f(*a[:-1]).astype(jnp.float32)
                                   * a[-1].astype(jnp.float32)),
                tuple(range(len(operands)))))

        exact = both(plain)
        f32 = tuple(a.astype(jnp.float32) for a in operands)
        want = (exact[0](*f32), *exact[1](*f32, seed)[1])
        kept = gn._row_tile(B * S, C, H)
        forms = [("plain", plain, None)] + [
            ("kernel", kernels(tile), tile) for tile in HEADNORM_TILES]
        for form, f, tile in forms:
            forward, grad = both(f)
            began = time.perf_counter()
            compiled = [forward.lower(*operands).compile(),
                        grad.lower(*operands, seed).compile()]
            line = {"case": name, "rule": rule, "form": form,
                    "compile_s": round(time.perf_counter() - began, 2),
                    "mosaic_kernels": sum(c.as_text().count(
                        'custom_call_target="tpu_custom_call"')
                        for c in compiled),
                    "fwd_ms": busy_ms(forward, *operands),
                    "fwd_bwd_ms": busy_ms(grad, *operands, seed),
                    "least_fwd_ms": round(moved * array / peak * 1e3, 4),
                    "least_fwd_bwd_ms": round(
                        moved_both * array / peak * 1e3, 4)}
            if tile:
                # the two kernels alone, without the loss that reads the
                # result
                line.update(tile=tile, kept=tile == kept,
                            fwd_bwd_kernels_ms=kernel_ms(
                                grad, *operands, seed))
            got = (forward(*operands), *grad(*operands, seed)[1])
            line["rel_err"] = {what: rel(g, w) for what, g, w in zip(
                ("out", "dx", "dz", "dgain"), got, want)}
            yield line


def conv_case(name, dtype):
    """One causal convolution at ``CONV_CASES[name]``: a line for each form
    of it, as the module's text says."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import causal_conv as cc

    B, S, W, start, widths, K = CONV_CASES[name]
    C = sum(widths)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    v = jax.random.normal(ks[0], (B, S, W), dtype)
    w = jax.random.uniform(ks[1], (C, K), jnp.float32, -0.5, 0.5)
    b = jax.random.uniform(ks[2], (C,), jnp.float32, -0.5, 0.5)
    seed = jax.random.normal(ks[3], (B, S, C), dtype)   # d loss / d out

    def plain(v, w, b):
        return cc._reference(v, w, b, start=start, activation="silu")

    def kernels(cut):
        def f(v, w, b):
            outs, at = [], 0
            for width in cut:
                outs.append(cc._kernels(
                    v, w[at:at + width], b[at:at + width], start + at,
                    "silu"))
                at += width
            return jnp.concatenate(outs, axis=-1)
        return f

    def both(f):
        return jax.jit(f), jax.jit(
            lambda v, w, b, seed: jax.vjp(f, v, w, b)[1](seed))

    rel = lambda g, w: round(float(
        np.max(np.abs(np.asarray(g, np.float32) - np.asarray(w)))
        / np.max(np.abs(np.asarray(w)))), 5)
    exact = both(plain)
    v32 = v.astype(jnp.float32)
    want = (exact[0](v32, w, b),
            *exact[1](v32, w, b, seed.astype(jnp.float32)))
    del v32
    kept = (cc._FORWARD, cc._BACKWARD)
    peak = 819e9                       # HBM bytes a second, TPU v5e
    array = B * S * C * jnp.dtype(dtype).itemsize
    forms = [("plain", plain, None, None)] + [
        ("kernel", kernels(cut), at, len(cut))
        for at in CONV_TILES
        for cut in ((C,), widths)[:1 + (at == kept)]]
    for form, f, at, results in forms:
        if at:
            cc._FORWARD, cc._BACKWARD = at
            jax.clear_caches()
        forward, vjp = both(f)
        began = time.perf_counter()
        lowered = [forward.lower(v, w, b), vjp.lower(v, w, b, seed)]
        traced = time.perf_counter()
        compiled = [low.compile() for low in lowered]
        line = {"case": name, "form": form,
                "trace_lower_s": round(traced - began, 2),
                "compile_s": round(time.perf_counter() - traced, 2),
                "mosaic_kernels": sum(c.as_text().count(
                    'custom_call_target="tpu_custom_call"')
                    for c in compiled),
                "fwd_ms": busy_ms(forward, v, w, b),
                "vjp_ms": busy_ms(vjp, v, w, b, seed),
                "least_fwd_ms": round(2 * array / peak * 1e3, 4),
                "least_bwd_ms": round(3 * array / peak * 1e3, 4)}
        if at:
            line.update(forward=at[0], backward=at[1], results=results,
                        kept=at == kept,
                        fwd_kernels_ms=kernel_ms(forward, v, w, b),
                        vjp_kernels_ms=kernel_ms(vjp, v, w, b, seed))
        got = (forward(v, w, b), *vjp(v, w, b, seed))
        line["rel_err"] = {what: rel(g, x) for what, g, x in zip(
            ("out", "dv", "dw", "db"), got, want)}
        if form == "plain" or at == kept:
            line["fwd_ops"] = longest_ops(forward, v, w, b, top=4)
            line["vjp_ops"] = longest_ops(vjp, v, w, b, seed, top=6)
        yield line
    cc._FORWARD, cc._BACKWARD = kept
    jax.clear_caches()


def target_case(name, dtype, tiles=None):
    """The indexer's loss at ``TARGET_CASES[name]``, one layer's of both
    sequences: a line for the fused Mosaic kernel (``tiles``: at these
    instead of `_target_tiles`', and nothing compared), the rows' losses
    and the gradient, and one for `_loss_reference`, the plain XLA form by
    blocks: device ms of every operation, the seconds the form took to
    compile, and the largest error of the rows' losses and of the (B, S, S)
    gradient relative to the reference on float32 operands."""
    import time

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import sparse_index as si
    from ray_tpu.parallel.attention import attention

    B, S, H, Hkv, D, block, top_k = TARGET_CASES[name]
    q, k, v = _qkv((B, S, H, D), dtype, Hkv)
    scores = jnp.where(jnp.tril(jnp.ones((S, S), bool)), jax.random.normal(
        jax.random.PRNGKey(3), (B, S, S)), -jnp.inf)
    mask = jax.jit(lambda s: si.select_top_k(s, top_k, block))(scores)
    _, lse = jax.jit(lambda q, k, v, m: attention(
        q, k, v, mask=m, with_lse=True))(q, k, v, mask)
    del v
    sizes = dict(scale=D ** -0.5, inv_rows=1.0 / (B * S))
    reference = functools.partial(si._loss_reference, block=block, **sizes)
    bq, bk = tiles or si._target_tiles(q, k)
    forms = {"kernel": functools.partial(
        si._pallas_loss, block_q=bq, block_k=bk, interpret=False, **sizes)}
    exact = None
    if not tiles:
        forms["reference"] = reference
        exact = jax.jit(reference)(q.astype(jnp.float32),
                                   k.astype(jnp.float32), lse, mask, scores)
    rel = lambda got, want: round(float(
        jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))), 6)
    for form, fn in forms.items():
        f = jax.jit(fn)
        began = time.perf_counter()
        compiled = f.lower(q, k, lse, mask, scores).compile()
        line = {"case": name, "form": form, "tile": [bq, bk],
                "compile_s": round(time.perf_counter() - began, 2),
                "mosaic_kernels": compiled.as_text().count(
                    'custom_call_target="tpu_custom_call"'),
                "every_op_ms": busy_ms(f, q, k, lse, mask, scores)}
        if exact is not None:
            line["rel_err"] = {what: rel(a, b) for what, a, b in zip(
                ("kl", "grad"), f(q, k, lse, mask, scores), exact)}
        yield line


def scores_case(name, dtype, tiles=None):
    """One layer's index scores at ``SCORES_CASES[name]``: a line for the
    Mosaic kernels (``tiles``: at these instead of `_scores_tiles`', and
    nothing compared) and one for the blocked XLA form they are held to:
    forward and backward device ms of every operation, the seconds each
    took to compile, and the largest error of the scores and of each
    gradient relative to the reference on float32 operands at the highest
    precision."""
    import time

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import sparse_index as si

    B, S, J, D, block, top_k = SCORES_CASES[name]
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, S, J, D), dtype)
    k = jax.random.normal(ks[1], (B, S, D), dtype)
    w = jax.random.normal(ks[2], (B, S, J), jnp.float32) * (J * D) ** -0.5
    bq, bk = tiles or si._scores_tiles(q, block)
    forms = {"kernel": (
        functools.partial(si._pallas_scores, block_q=bq, block_k=bk,
                          interpret=False),
        functools.partial(si._pallas_scores_bwd, block_q=bq, block_k=bk,
                          interpret=False))}
    if not tiles:
        forms["reference"] = (
            functools.partial(si._scores_reference, block=block),
            functools.partial(si._scores_reference_bwd, block=block))
    forward, backward = map(jax.jit, forms.get("reference", forms["kernel"]))
    scores = forward(q, k, w)
    g = jnp.where(
        jax.jit(lambda s: si.select_top_k(s, top_k, block))(scores) != 0,
        jax.random.normal(ks[3], (B, S, S)), 0.0)
    exact = None
    if not tiles:
        with jax.default_matmul_precision("highest"):
            f32 = q.astype(jnp.float32), k.astype(jnp.float32), w
            exact = forward(*f32), *backward(*f32, g)
    del scores
    rel = lambda got, want: round(float(jnp.max(jnp.abs(jnp.where(
        jnp.isfinite(want), got.astype(jnp.float32) - want, 0.0)))
        / jnp.max(jnp.where(jnp.isfinite(want), jnp.abs(want), 0.0))), 5)
    for form, fns in forms.items():
        line = {"case": name, "form": form, "tile": [bq, bk]}
        got = []
        for what, fn, args in zip(("fwd", "bwd"), map(jax.jit, fns),
                                  ((q, k, w), (q, k, w, g))):
            began = time.perf_counter()
            compiled = fn.lower(*args).compile()
            line[f"{what}_compile_s"] = round(time.perf_counter() - began, 2)
            line[f"{what}_mosaic_kernels"] = compiled.as_text().count(
                'custom_call_target="tpu_custom_call"')
            line[f"{what}_ms"] = busy_ms(fn, *args)
            if exact is not None:
                out = fn(*args)
                got += [out] if what == "fwd" else list(out)
        if exact is not None:
            line["rel_err"] = {what: rel(a, b) for what, a, b in zip(
                ("scores", "dq", "dk", "dw"), got, exact)}
            line["above_diagonal_is_neg_inf"] = bool(jnp.all(
                jnp.isneginf(got[0]) == jnp.isneginf(exact[0])))
        yield line


def select_case(name, dtype, tiles=None):
    """One layer's selection at ``SELECT_CASES[name]``: a line for the
    Mosaic kernel (``tiles``: at this (q tile, k tile, bits a pass) instead
    of `_select_tiles`' and `_SELECT_BITS`) and, without ``tiles``, one for
    `_select_reference`: device ms of every operation, the seconds the form
    took to compile, and the bytes of the mask that differ from the
    reference's."""
    import time

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import sparse_index as si

    B, S, J, D, block, top_k = SELECT_CASES[name]
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    scores = jax.jit(functools.partial(si.index_scores, block=block))(
        jax.random.normal(ks[0], (B, S, J, D), dtype),
        jax.random.normal(ks[1], (B, S, D), dtype),
        jax.random.normal(ks[2], (B, S, J), jnp.float32) * (J * D) ** -0.5)
    reference = jax.jit(functools.partial(
        si._select_reference, top_k=top_k, block=block))
    bq, bk, bits = tiles or (*si._select_tiles(S), si._SELECT_BITS)
    forms = {"kernel": jax.jit(functools.partial(
        si._pallas_select, top_k=top_k, block_q=bq, block_k=bk, bits=bits,
        interpret=False))}
    if not tiles:
        forms["reference"] = reference
    compiled, seconds = {}, {}
    for form, f in forms.items():       # before the reference's first call
        began = time.perf_counter()
        compiled[form] = f.lower(scores).compile()
        seconds[form] = round(time.perf_counter() - began, 2)
    want = reference(scores)
    for form, f in forms.items():
        yield {"case": name, "form": form, "tile": [bq, bk], "bits": bits,
               "compile_s": seconds[form],
               "mosaic_kernels": compiled[form].as_text().count(
                   'custom_call_target="tpu_custom_call"'),
               "every_op_ms": busy_ms(f, scores),
               "bytes_differ": int(jnp.sum(f(scores) != want)),
               "selected": int(jnp.sum(want, dtype=jnp.int32))}


def ssd_case(name, dtype, chunk=None, compare=True):
    """One state-space scan at ``SSD_CASES[name]`` (``chunk`` given: at
    that chunk): a line for each form of it (`ops/ssd.py`) -- the `einsum`
    form with either carry over the chunks, and the Pallas kernels as
    `ssd_scan` calls them: forward ms, forward and backward ms of one
    `jax.grad` in all six operands, the least time of the scan's operations
    and bytes, and (``compare``) the largest error of y and of the six
    gradients relative to the float32 recurrence run position by
    position."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import nemotron_h as reference
    from ray_tpu.ops import ssd

    B, S, H, P, G, N, Q = SSD_CASES[name]
    Q = chunk or Q
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    # x, B and C flat over heads and groups, as `_mamba` splits them off the
    # conv's result: a 4-D array on the chip is laid out by its last two
    # dimensions, and a jit that took one would time re-laying it
    x = jax.random.normal(ks[0], (B, S, H * P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)) - 4.0)
    A = -jnp.arange(1, H + 1, dtype=jnp.float32)
    Bm = jax.random.normal(ks[2], (B, S, G * N), dtype) * N ** -0.5
    Cm = jax.random.normal(ks[3], (B, S, G * N), dtype) * N ** -0.5
    D = jnp.ones((H,), jnp.float32)
    seed = jax.random.normal(ks[4], (B, S, H * P), jnp.float32)
    args = (x, dt, A, Bm, Cm, D)

    def both(scan):
        def y(x, dt, A, Bm, Cm, D):
            return scan(x.reshape(B, S, H, P), dt, A, Bm.reshape(B, S, G, N),
                        Cm.reshape(B, S, G, N), D).reshape(B, S, H * P)

        return jax.jit(y), jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(y(*a).astype(jnp.float32) * seed),
            tuple(range(6))))

    def by_positions(x, dt, A, Bm, Cm, D):
        f32 = lambda v: v.astype(jnp.float32)
        rep = lambda v: jnp.repeat(f32(v), H // G, axis=1)
        return jax.lax.map(lambda a: reference.recurrence(
            f32(a[0]), a[1], A, rep(a[2]), rep(a[3]), D, 64),
            (x, dt, Bm, Cm))

    rel = lambda g, w: round(float(
        np.max(np.abs(np.asarray(g, np.float32) - np.asarray(w)))
        / np.max(np.abs(np.asarray(w)))), 5)
    if compare:
        exact = both(by_positions)
        want = (exact[0](*args), *exact[1](*args)[1])
    tokens, width = B * S, jnp.dtype(dtype).itemsize
    flops = tokens * (Q * N * G + Q * P * H + 4 * N * P * H)
    read = (H * P + 2 * G * N) * width + 4 * H
    least = lambda f, b: max(f / 197e12, tokens * b / 819e9) * 1e3
    def carry_by_scan(states, total):
        """The form `ops/ssd.py` does not keep: a `lax.scan` over the
        chunks, h_c = exp(total_c) h_{c-1} + S_c."""
        def step(h, chunk):
            own, decay = chunk
            return jnp.exp(decay)[..., None, None] * h + own, h

        _, before = jax.lax.scan(
            step, jnp.zeros_like(states[:, 0]),
            (jnp.moveaxis(states, 1, 0), jnp.moveaxis(total, 1, 0)))
        return jnp.moveaxis(before, 0, 1)

    kept = ssd._carry
    einsums = lambda *a: ssd._ssd_einsum(*a, Q)
    for form, carry, scan in (
            ("einsum_carry_by_scan", carry_by_scan, einsums),
            ("einsum_carry_by_product", kept, einsums),
            ("kernels", kept, lambda *a: ssd.ssd_scan(*a, Q))):   # what runs
        ssd._carry = carry
        forward, grad = both(scan)
        line = {"case": name, "form": form, "chunk": Q,
                "dtype": jnp.dtype(dtype).name,
                "fwd_ms": busy_ms(forward, *args),
                "fwd_bwd_ms": busy_ms(grad, *args),
                "least_fwd_ms": round(least(flops, read + H * P * width), 4),
                "least_fwd_bwd_ms": round(least(
                    3 * flops, 3 * read + 2 * H * P * width), 4)}
        if form == "kernels":
            line["mosaic_kernels"] = grad.lower(*args).compile().as_text(
                ).count('custom_call_target="tpu_custom_call"')
        if compare:
            got = (forward(*args), *grad(*args)[1])
            line["rel_err"] = {what: rel(g, t) for what, g, t in zip(
                ("y", "dx", "ddt", "dA", "dB", "dC", "dD"), got, want)}
        yield line
    ssd._carry = kept


def sscan_case(name, dtype):
    """One Mamba-1 selective scan at ``SSCAN_CASES[name]``
    (`ops/selective_scan.py`): a line for the float32 recurrence run
    position by position in recomputed blocks of 64
    (`benchmark/reference/phi4flash.py:recurrence`; the plain `lax.scan`
    `ops/selective_scan.py:_reference` keeps every position's state under a
    gradient, 5.4 GB at this shape, and does not fit the chip) and one for
    the Mosaic kernels at each block of positions of `_TIME_BLOCKS`
    (`kept`: the one `_blocks` chooses): forward ms and forward + backward
    ms of one `jax.grad` in all six operands, every operation counted (the
    re-laying of u, dt and y to the kernels' tiles among them) and the
    kernels alone, beside the least time of the scan's bytes, and the
    largest error of y and of the six gradients relative to the
    recurrence's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import phi4flash as reference

    sscan = importlib.import_module("ray_tpu.ops.selective_scan")
    B, S, C, N = SSCAN_CASES[name]
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    u = jax.random.normal(ks[0], (B, S, C), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, C)) - 4.0)
    A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (C, N))
    Bm = jax.random.normal(ks[2], (B, S, N), dtype)
    Cm = jax.random.normal(ks[3], (B, S, N), dtype)
    D = jnp.ones((C,), jnp.float32)
    seed = jax.random.normal(ks[4], (B, S, C), jnp.float32)
    args = (u, dt, A, Bm, Cm, D)

    def by_positions(u, dt, A, Bm, Cm, D):
        f32 = lambda v: v.astype(jnp.float32)
        return jax.lax.map(lambda a: reference.recurrence(
            f32(a[0]), a[1], A, f32(a[2]), f32(a[3]), D, 64),
            (u, dt, Bm, Cm))

    def both(scan):
        return jax.jit(scan), jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(scan(*a).astype(jnp.float32) * seed),
            tuple(range(6))))

    rel = lambda g, w: round(float(
        np.max(np.abs(np.asarray(g, np.float32) - np.asarray(w, np.float32)))
        / np.max(np.abs(np.asarray(w, np.float32)))), 5)
    width = jnp.dtype(dtype).itemsize
    read = (C + 2 * N) * width + C * 4
    least = lambda b: round(B * S * b / 819e9 * 1e3, 4)
    exact = both(by_positions)
    want = (exact[0](*args), *exact[1](*args)[1])
    chosen = sscan._blocks(S, C, N)[0]
    kept = sscan._TIME_BLOCKS
    forms = [("recurrence", None)] + [("kernels", (t,)) for t in kept
                                      if t <= chosen and S % t == 0]
    for form, blocks in forms:
        if blocks:
            sscan._TIME_BLOCKS = blocks
            jax.clear_caches()
        forward, grad = both(sscan.selective_scan) if blocks else exact
        line = {"case": name, "form": form, "dtype": jnp.dtype(dtype).name,
                "positions": blocks and blocks[0],
                "kept": bool(blocks) and blocks[0] == chosen,
                "fwd_ms": busy_ms(forward, *args),
                "fwd_bwd_ms": busy_ms(grad, *args),
                "fwd_kernel_ms": kernel_ms(forward, *args),
                "fwd_bwd_kernel_ms": kernel_ms(grad, *args),
                "least_fwd_ms": least(read + C * width),
                "least_fwd_bwd_ms": least(3 * read + 2 * C * width),
                "mosaic_kernels": grad.lower(*args).compile().as_text(
                    ).count('custom_call_target="tpu_custom_call"')}
        got = (forward(*args), *grad(*args)[1])
        line["rel_err"] = {what: rel(g, t) for what, g, t in zip(
            ("y", "du", "ddt", "dA", "dB", "dC", "dD"), got, want)}
        yield line
    sscan._TIME_BLOCKS = kept
    jax.clear_caches()


def kda_case(name, dtype, plans=None):
    """One gated delta rule at ``KDA_CASES[name]`` (`ops/kda.py`): a line for
    the float32 recurrence run position by position in recomputed blocks of
    64 (`benchmark/reference/bailing_hybrid.py:delta_rule`), one for the
    plain chunked form (`_plain`: what a declined shape runs) and one for the
    Mosaic kernels as the module plans them (`Hb`: the heads a grid step
    takes): forward ms and forward + backward ms of one `jax.grad` in all
    five operands, every operation counted (beta k and beta v among them)
    and the kernels alone (`fwd_states_kernel_ms`:
    the forward kernel as differentiation runs it, the entering states and
    the chunks' T behind o), beside the least time of the rule's bytes, and
    the largest error of o and of the five gradients relative to the
    recurrence's.  g is drawn over the whole of (-5, 0) and a sixteenth of
    the positions stand AT the bound.  With ``plans``, counts of heads a
    grid step, the kernels' lines alone, one a count forced on the module
    (`kept`: the count is the module's own plan; one it cannot take, by H or
    VMEM, is left out)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import bailing_hybrid as reference

    kda = importlib.import_module("ray_tpu.ops.kda")
    B, S, H, D, C = KDA_CASES[name]
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = (unit(jax.random.normal(ks[0], (B, S, H, D))) * D ** -0.5).astype(dtype)
    k = unit(jax.random.normal(ks[1], (B, S, H, D))).astype(dtype)
    v = jax.nn.silu(jax.random.normal(ks[2], (B, S, H, D))).astype(dtype)
    g = -5.0 * jax.nn.sigmoid(4.0 * jax.random.normal(ks[3], (B, S, H, D)))
    g = jnp.where(jax.random.uniform(ks[4], (B, S, 1, 1)) < 1 / 16, -5.0, g)
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (B, S, H)))
    seed = jax.random.normal(ks[6], (B, S, H, D), jnp.float32)
    args = (q, k, v, g, beta)

    def by_positions(q, k, v, g, beta):
        f32 = lambda x: x.astype(jnp.float32)
        return jax.lax.map(lambda a: reference.delta_rule(
            f32(a[0]), f32(a[1]), f32(a[2]), a[3], a[4], 64),
            (q, k, v, g, beta))

    def plain(q, k, v, g, beta):
        return kda._plain(q, k, kda._scaled(k, beta), kda._scaled(v, beta),
                          g, C)[0]

    def both(rule):
        return jax.jit(rule), jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(rule(*a).astype(jnp.float32) * seed),
            tuple(range(5))))

    planned = lambda: kda._step_heads(H, C, dtype)
    rel = lambda got, want: round(float(
        np.max(np.abs(np.asarray(got, np.float32)
                      - np.asarray(want, np.float32)))
        / np.max(np.abs(np.asarray(want, np.float32)))), 5)
    width = jnp.dtype(dtype).itemsize
    read = 3 * D * width + D * 4 + 4
    least = lambda b: round(B * S * H * b / 819e9 * 1e3, 4)
    exact = both(by_positions)
    # traced here: its einsums in float32, not in bfloat16 passes
    with jax.default_matmul_precision("highest"):
        want = (exact[0](*args), *exact[1](*args)[1])
    kept, most = planned(), kda._STEP_HEADS
    forms = [("kernels", plan) for plan in plans] if plans else [
        ("recurrence", None), ("plain", None), ("kernels", kept)]
    for form, plan in forms:
        if plan:
            kda._STEP_HEADS = plan
            jax.clear_caches()
            if planned() != plan:
                continue
        forward, grad = exact if form == "recurrence" else both(
            plain if form == "plain" else
            lambda *a: kda.kda(*a, chunk=C))
        line = {"case": name, "form": form, "dtype": jnp.dtype(dtype).name,
                "Hb": plan, "kept": plan == kept,
                "fwd_ms": busy_ms(forward, *args),
                "fwd_bwd_ms": busy_ms(grad, *args),
                "fwd_kernel_ms": kernel_ms(forward, *args),
                "fwd_bwd_kernel_ms": kernel_ms(grad, *args),
                "least_fwd_ms": least(read + D * width),
                "least_fwd_bwd_ms": least(3 * read + 2 * D * width),
                "mosaic_kernels": grad.lower(*args).compile().as_text(
                    ).count('custom_call_target="tpu_custom_call"')}
        if form == "kernels":
            line["fwd_states_kernel_ms"] = kernel_ms(
                jax.jit(functools.partial(kda._forward, C=C, states=True)),
                q, k, kda._scaled(k, beta), kda._scaled(v, beta), g)
        got = (forward(*args), *grad(*args)[1])
        line["rel_err"] = {what: rel(g_, w_) for what, g_, w_ in zip(
            ("o", "dq", "dk", "dv", "dg", "dbeta"), got, want)}
        yield line
    kda._STEP_HEADS = most
    jax.clear_caches()


def eva_case(name, dtype):
    """EVA attention at ``EVA_CASES[name]`` (`ops/eva.py`): a line for the
    float32 definition (`benchmark/reference/evabyte.py`: the summaries by a
    reshape and a softmax, a block of 256 query rows scored against ALL keys
    and ALL summaries under the masks of A_i and B_i), one for the plain
    masked form by windows (`_plain`: what a declined shape runs) and one for
    the kernels: forward ms and forward + backward ms of one `jax.grad` in
    all five operands, every operation counted (the transposes to head-major,
    the merge, delta and the sums of the cotangents among them) and the
    Mosaic kernels alone, the longest operations, the pairs attended over
    those the forward kernels visit, and the largest error of o and of the
    five gradients relative to the definition's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import evabyte as reference

    eva = importlib.import_module("ray_tpu.ops.eva")
    B, S, H, D, window, chunk = EVA_CASES[name]
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q, k, v = (jax.random.normal(key, (B, S, H, D)).astype(dtype)
               for key in ks[:3])
    phi, mu = (jnp.clip(jax.random.normal(key, (H, D)), -1, 1) * D ** -0.5
               for key in ks[3:5])
    phi, mu = phi.astype(dtype), mu.astype(dtype)
    seed = jax.random.normal(ks[5], (B, S, H, D), jnp.float32)
    args = (q, k, v, phi, mu)
    sizes = reference.Sizes(H, chunk, window, 1, 1e5, 1e-5, 256, 2048)

    def definition(q, k, v, phi, mu):
        """`reference.eva` between its projections: one sequence a time."""
        f32 = lambda x: x.astype(jnp.float32)

        def one(q, k, v):
            ks, vs = reference.summaries(k, v, f32(phi), f32(mu), chunk)
            keys = jnp.concatenate([k, ks], 0).transpose(1, 0, 2)
            values = jnp.concatenate([v, vs], 0).transpose(1, 0, 2)
            qh = q.transpose(1, 0, 2)

            @jax.checkpoint
            def some(start):
                seen = jnp.concatenate(reference.attended(
                    start + jnp.arange(256), S, sizes), axis=1)
                qb = jax.lax.dynamic_slice_in_dim(qh, start, 256, axis=1)
                scores = qb @ keys.transpose(0, 2, 1) / jnp.sqrt(
                    jnp.float32(D))
                return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf),
                                      axis=-1) @ values

            out = jax.lax.map(some, jnp.arange(0, S, 256))
            return out.transpose(0, 2, 1, 3).reshape(S, H, D)

        return jax.lax.map(lambda a: one(*a), (f32(q), f32(k), f32(v)))

    def both(rule):
        return jax.jit(rule), jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(rule(*a).astype(jnp.float32) * seed),
            tuple(range(5))))

    rel = lambda got, want: round(float(
        np.max(np.abs(np.asarray(got, np.float32)
                      - np.asarray(want, np.float32)))
        / np.max(np.abs(np.asarray(want, np.float32)))), 5)
    exact = both(definition)
    with jax.default_matmul_precision("highest"):
        want = (exact[0](*args), *exact[1](*args)[1])
    local, remote = eva.attended_pairs(S, window, chunk)
    for form in ("definition", "plain", "kernels"):
        forward, grad = exact if form == "definition" else both(
            (lambda *a: eva._plain(*a, window, chunk)) if form == "plain"
            else lambda *a: eva.eva_attention(*a, window=window, chunk=chunk))
        line = {"case": name, "form": form, "dtype": jnp.dtype(dtype).name,
                "fwd_ms": busy_ms(forward, *args),
                "fwd_bwd_ms": busy_ms(grad, *args),
                "fwd_kernel_ms": kernel_ms(forward, *args),
                "fwd_bwd_kernel_ms": kernel_ms(grad, *args),
                # the attended pairs' operations at the matrix unit's peak
                "least_fwd_ms": round(
                    B * H * (local + remote) * 4 * D / 197e12 * 1e3, 4),
                "least_fwd_bwd_ms": round(
                    B * H * (local + remote) * 12 * D / 197e12 * 1e3, 4),
                "mosaic_kernels": grad.lower(*args).compile().as_text(
                    ).count('custom_call_target="tpu_custom_call"')}
        if form == "kernels":
            line["longest_ops"] = longest_ops(grad, *args, top=10)
        got = (forward(*args), *grad(*args)[1])
        line["rel_err"] = {what: rel(g_, w_) for what, g_, w_ in zip(
            ("o", "dq", "dk", "dv", "dphi", "dmu"), got, want)}
        yield line


def window_case(name, dtype):
    """One line a rule (the window, then none) and a tile (`_auto_tiles`',
    then `WINDOW_TILES`) of ``flash_attention_bshd`` at the case's shape:
    device ms of the forward kernel and of forward + backward, the share
    of the visited pairs the rule attends, and the largest error of o, dq,
    dk and dv relative to a float32 masked softmax taken 1,024 query rows
    at a time (the rule written out as a comparison of positions)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.mellum import attended_pairs
    from ray_tpu.ops import flash_attention as fa

    shape, kv_heads, window, *full_heads = WINDOW_CASES[name]
    tiles = WINDOW_TILES if window >= 1024 else NARROW_TILES

    def reference(q, k, v, width):
        """(B, S, H, D) float32, by blocks of query rows (1,024 of up to
        32 heads: 2 GiB of scores alive)."""
        B, S, H, D = q.shape
        block = 1024 if H <= 32 else 512
        qf, kf, vf = (t.astype(jnp.float32).transpose(0, 2, 1, 3)
                      for t in (q, k, v))
        kf, vf = (jnp.repeat(t, H // kv_heads, axis=1) for t in (kf, vf))

        @jax.checkpoint
        def some(start):
            rows = start + jnp.arange(block)
            behind = rows[:, None] - jnp.arange(S)[None]
            seen = (behind >= 0) & (behind < (width or S))
            qb = jax.lax.dynamic_slice_in_dim(qf, start, block, axis=2)
            scores = jnp.einsum("bhqd,bhkd->bhqk", qb, kf) * D ** -0.5
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return jnp.einsum("bhqk,bhkd->bhqd", probs, vf)

        out = jax.lax.map(some, jnp.arange(0, S, block))  # (n, B, H, ., D)
        return out.transpose(1, 0, 3, 2, 4).reshape(B, S, H, v.shape[-1])

    def grad(f):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: (lambda o: (jnp.sum(o.astype(jnp.float32) ** 2),
                                        o))(f(q, k, v)),
            (0, 1, 2), has_aux=True))

    for width in (window, None):
        if width is None and full_heads:
            shape = (*shape[:2], *full_heads, shape[3])
        S = shape[1]
        q, k, v = _qkv(shape, dtype, kv_heads)
        rule = fa.BlockRule(window=width)
        with jax.default_matmul_precision("highest"):
            (_, o_r), g_r = grad(lambda q, k, v: reference(q, k, v, width))(
                q, k, v)
        want = [np.asarray(t, np.float32) for t in (o_r, *g_r)]
        del o_r, g_r
        for block, how in then_the_walk(
                tiles if width else WINDOW_TILES, width):
            with how():
                (_, o_k), g_k = grad(lambda q, k, v: fa.flash_attention_bshd(
                    q, k, v, rule, None, *block))(q, k, v)
                fwd_ms, bwd_ms = time_passes(shape, dtype, *block,
                                             kv_heads=kv_heads, causal=rule)
                (bq, bk), (cq, ck) = [
                    block if block[0] else t for t in fa._auto_tiles(S, rule)]
                bands = [fa._band(rule, S, bq, False),
                         fa._band(rule, S, ck, False)]
            errs = {what: round(float(
                np.max(np.abs(np.asarray(a, np.float32) - b))
                / np.max(np.abs(b))), 5)
                for what, a, b in zip(("o", "dq", "dk", "dv"),
                                      (o_k, *g_k), want)}
            # what a pass multiplies: every row by its band, or the tiles
            # its walk visits
            visited = [S * band if band else fa._tiles_visited(
                rule, S, *tile) * tile[0] * tile[1]
                for band, tile in zip(bands, ((bq, bk), (cq, ck)))]
            attended = attended_pairs(S, width)
            yield {"case": name, "shape": shape, "kv_heads": kv_heads,
                   "window": width, "tile": block if block[0] else "auto",
                   "tiles": [[bq, bk], [cq, ck]], "band": bands,
                   "fwd_ms": fwd_ms, "fwd_bwd_ms": round(fwd_ms + bwd_ms, 4),
                   "attended_over_visited": [
                       round(attended / n, 3) for n in visited],
                   "rel_err": errs}


def compare_with_reference(shape, dtype, kv_heads=None):
    """Causal ``flash_attention_bshd`` at ``shape`` (B, S, H, D[, Dv]),
    forward and backward, on the default device: (largest error of o, dq,
    dk, dv relative to ``reference_attention``'s, the worst of the batch's
    rows, Mosaic kernels in the compiled program — 0 where the kernels are
    interpreted).  The O(S^2) reference runs one batch row at a time, and
    of a row as many heads as keep its scores under `REFERENCE_SCORES`
    (OLMoE's whole batch would hold several 4 GB score arrays, one row of
    32 heads at S = 8,192 several of 8.6 GB); the loss is a sum over rows
    and heads, so a slice's gradients are the batch's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import flash_attention as fa
    from ray_tpu.parallel.attention import attention

    q, k, v = _qkv(shape, dtype, kv_heads)
    group = shape[2] // (kv_heads or shape[2])

    def kernel(q, k, v):
        o = fa.flash_attention_bshd(q, k, v, True)
        return jnp.sum(o.astype(jnp.float32) ** 2), o

    def reference(q, k, v):
        o = attention(q, k, v, variant="dense")
        return jnp.sum(o.astype(jnp.float32) ** 2), o

    def grad(f):
        return jax.jit(jax.value_and_grad(f, (0, 1, 2), has_aux=True))

    compiled = grad(kernel).lower(q, k, v).compile()
    (_, o_k), g_k = compiled(q, k, v)
    reference = grad(reference)
    errs = dict.fromkeys(("o", "dq", "dk", "dv"), 0.0)
    B, S, H = shape[:3]
    # whole groups of query heads, with the key/value heads they read
    heads = max(group, min(H, REFERENCE_SCORES // (S * S)) // group * group)
    for row in range(B):
        for first in range(0, H, heads):
            part = (slice(row, row + 1), slice(None),
                    slice(first, first + heads))
            kv_part = (*part[:2], slice(first // group,
                                        (first + heads) // group))
            (_, o_r), g_r = reference(q[part], k[kv_part], v[kv_part])
            for what, a, b in zip(errs, (o_k, *g_k), (o_r, *g_r)):
                a = np.asarray(a[part if what in ("o", "dq") else kv_part],
                               np.float32)
                b = np.asarray(b, np.float32)
                errs[what] = max(errs[what], round(
                    float(np.max(np.abs(a - b)) / np.max(np.abs(b))), 5))
    return errs, compiled.as_text().count(
        'custom_call_target="tpu_custom_call"')


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--sweep", nargs="*", metavar="SHAPE",
                        help="time forced tiles at these of SWEEP's shapes "
                             f"({', '.join(SWEEP)}; none named: at all)")
    parser.add_argument("--cases", nargs="+", metavar="CASE",
                        default=[*CASES, *MOE_CASES, *MOE_ALL_CASES,
                                 *SHORTCONV_CASES,
                                 *SSD_CASES, *TARGET_CASES, *SCORES_CASES,
                                 *SELECT_CASES, *HEAD_CASES,
                                 *GATENORM_CASES, *HEADNORM_CASES,
                                 *CONV_CASES, *WINDOW_CASES,
                                 *SSCAN_CASES, *KDA_CASES, *EVA_CASES],
                        help=f"run these only ({', '.join(CASES)}, "
                             f"{', '.join(WINDOW_CASES)}, "
                             f"{', '.join((*MOE_CASES, *MOE_ALL_CASES))}, "
                             f"{', '.join(SHORTCONV_CASES)}, "
                             f"{', '.join(SSD_CASES)}, "
                             f"{', '.join(TARGET_CASES)}, "
                             f"{', '.join(SCORES_CASES)}, "
                             f"{', '.join(SELECT_CASES)}, "
                             f"{', '.join(HEAD_CASES)}, "
                             f"{', '.join(GATENORM_CASES)}, "
                             f"{', '.join(HEADNORM_CASES)}, "
                             f"{', '.join(CONV_CASES)}, "
                             f"{', '.join(SSCAN_CASES)}, "
                             f"{', '.join(KDA_CASES)}, "
                             f"{', '.join(EVA_CASES)}; default: all)")
    args = parser.parse_args()
    swept = [*SWEEP, *SSD_SWEEP, *TARGET_SWEEP, *SCORES_SWEEP, *SELECT_SWEEP,
             *KDA_SWEEP]
    if args.sweep and set(args.sweep) - set(swept):
        parser.error(f"--sweep: no such shape in {sorted(swept)}")
    known = [*CASES, *MOE_CASES, *MOE_ALL_CASES, *SHORTCONV_CASES,
             *SSD_CASES, *TARGET_CASES, *SCORES_CASES, *SELECT_CASES,
             *HEAD_CASES, *GATENORM_CASES, *HEADNORM_CASES, *CONV_CASES,
             *WINDOW_CASES, *SSCAN_CASES, *KDA_CASES, *EVA_CASES]
    if set(args.cases) - set(known):
        parser.error(f"--cases: no such case in {known}")

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import (
        AttentionFallbackWarning,
        BlockRule,
        _auto_tiles,
    )
    from ray_tpu.ops import flash_attention as fa
    from ray_tpu.ops.eva import EvaFallbackWarning
    from ray_tpu.ops.kda import KdaFallbackWarning
    from ray_tpu.ops.ssd import SsdFallbackWarning
    from ray_tpu.util.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    warnings.simplefilter("error", AttentionFallbackWarning)
    warnings.simplefilter("error", SsdFallbackWarning)
    warnings.simplefilter("error", KdaFallbackWarning)
    warnings.simplefilter("error", EvaFallbackWarning)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"no TPU: jax found {dev.platform!r}")

    if args.sweep is not None:
        for name in args.sweep or swept:
            for table, one in ((TARGET_SWEEP, target_case),
                               (SCORES_SWEEP, scores_case),
                               (SELECT_SWEEP, select_case)):
                case, tiles = table.get(name, (None, ()))
                for tile in tiles:
                    for line in one(case, jnp.bfloat16, tile):
                        print(json.dumps({
                            "sweep": name, **line,
                            "device_kind": dev.device_kind}), flush=True)
            if name in (*TARGET_SWEEP, *SCORES_SWEEP, *SELECT_SWEEP):
                continue
            if name in KDA_SWEEP:
                case, heads = KDA_SWEEP[name]
                for line in kda_case(case, jnp.bfloat16, heads):
                    print(json.dumps({
                        "sweep": name, **line,
                        "device_kind": dev.device_kind}), flush=True)
                continue
            if name in SSD_SWEEP:
                case, chunks = SSD_SWEEP[name]
                for chunk in chunks:
                    for line in ssd_case(case, jnp.bfloat16, chunk, False):
                        print(json.dumps({
                            "sweep": name, **line,
                            "device_kind": dev.device_kind}), flush=True)
                continue
            shape, blocks = SWEEP[name]
            causal = BlockRule(*RULES[name]) if name in RULES else True
            # under a window: each tile's band, then the walk at one tile
            windowed = getattr(causal, "window", None) is not None
            for block, how in then_the_walk(((None, None),) + blocks,
                                            windowed):
                with how():
                    fwd_ms, bwd_ms = time_passes(
                        shape, jnp.bfloat16, *block,
                        kv_heads=KV_HEADS.get(name), causal=causal)
                    tiles = [block if block[0] else t
                             for t in _auto_tiles(shape[1], causal)]
                    line = {"sweep": name, "shape": shape,
                            "tile": block if block[0] else tiles,
                            "fwd_ms": fwd_ms, "bwd_ms": bwd_ms}
                    if windowed:
                        # the rows of the forward's band of keys and of the
                        # backward's of query rows, None on the walk
                        line["band"] = [
                            fa._band(causal, shape[1], tiles[0][0], False),
                            fa._band(causal, shape[1], tiles[1][1], False)]
                print(json.dumps({**line, "device_kind": dev.device_kind}),
                      flush=True)
        return

    failed = []
    for name, (shape, n_kernels) in CASES.items():
        if name not in args.cases:
            continue
        kv_heads = KV_HEADS.get(name)
        errs, found = compare_with_reference(shape, jnp.bfloat16, kv_heads)
        fwd_ms, bwd_ms = time_passes(shape, jnp.bfloat16, kv_heads=kv_heads)
        ok = found == n_kernels and max(errs.values()) < TOLERANCE
        if not ok:
            failed.append(name)
        line = {"case": name, "shape": shape, "ok": ok,
                "mosaic_kernels": found, "rel_err": errs,
                "fwd_ms": fwd_ms, "bwd_ms": bwd_ms,
                "device_kind": dev.device_kind}
        if kv_heads:
            # every operation of the passes, beside the same call with k
            # and v repeated to q's heads first (the repeat not timed)
            line["kv_heads"] = kv_heads
            line["every_op_ms"] = time_passes(
                shape, jnp.bfloat16, kv_heads=kv_heads, every_op=True)
            line["repeated_kernel_ms"] = time_passes(
                shape, jnp.bfloat16, kv_heads=kv_heads, repeated=True)
            line["repeated_every_op_ms"] = time_passes(
                shape, jnp.bfloat16, kv_heads=kv_heads, repeated=True,
                every_op=True)
        print(json.dumps(line), flush=True)
    for name, case in [(n, moe_case) for n in MOE_CASES] + [
            (n, grouped_case) for n in (*MOE_CASES, *MOE_ALL_CASES)]:
        for line in case(name, jnp.bfloat16) \
                if name in args.cases else ():
            ok = max(line.get("rel_err", {"": 0}).values()) < TOLERANCE
            if not ok:
                failed.append(f"{name}:{line.get('form')}")
            print(json.dumps({**line, "ok": ok,
                              "device_kind": dev.device_kind}), flush=True)
    for name, case, dtype in [
            (n, shortconv_case, jnp.bfloat16) for n in SHORTCONV_CASES] + [
            (n, ssd_case, t) for n in SSD_CASES
            for t in (jnp.bfloat16, jnp.float32)]:
        for line in case(name, dtype) \
                if name in args.cases else ():
            # the scan's forward kernel, and its backward's two
            ok = max(line.get("rel_err", {"": 0}).values()) < TOLERANCE \
                and line.get("mosaic_kernels", 3) == 3
            if not ok:
                failed.append(f"{name}:{line['form']}")
            print(json.dumps({**line, "ok": ok,
                              "device_kind": dev.device_kind}), flush=True)
    for name in TARGET_CASES:
        for line in target_case(name, jnp.bfloat16) \
                if name in args.cases else ():
            ok = max(line["rel_err"].values()) < TOLERANCE \
                and line["mosaic_kernels"] == (line["form"] == "kernel")
            if not ok:
                failed.append(f"{name}:{line['form']}")
            print(json.dumps({**line, "ok": ok,
                              "device_kind": dev.device_kind}), flush=True)
    for name in SCORES_CASES:
        for line in scores_case(name, jnp.bfloat16) \
                if name in args.cases else ():
            ok = max(line["rel_err"].values()) < TOLERANCE \
                and line["above_diagonal_is_neg_inf"] \
                and line["fwd_mosaic_kernels"] + line["bwd_mosaic_kernels"] \
                == (2 if line["form"] == "kernel" else 0)
            if not ok:
                failed.append(f"{name}:{line['form']}")
            print(json.dumps({**line, "ok": ok,
                              "device_kind": dev.device_kind}), flush=True)
    for name in SELECT_CASES:
        for line in select_case(name, jnp.bfloat16) \
                if name in args.cases else ():
            ok = line["bytes_differ"] == 0 \
                and line["mosaic_kernels"] == (line["form"] == "kernel")
            if not ok:
                failed.append(f"{name}:{line['form']}")
            print(json.dumps({**line, "ok": ok,
                              "device_kind": dev.device_kind}), flush=True)
    for name in HEAD_CASES:
        for line in head_case(name, jnp.bfloat16) \
                if name in args.cases else ():
            ok = max(line["rel_err"].values()) < TOLERANCE
            if not ok:
                failed.append(f"{name}:{line['form']}")
            print(json.dumps({**line, "ok": ok,
                              "device_kind": dev.device_kind}), flush=True)
    for name in GATENORM_CASES:
        for line in gatenorm_case(name, jnp.bfloat16) \
                if name in args.cases else ():
            # a kernel forward; forward and backward under the gradient
            ok = max(line["rel_err"].values()) < TOLERANCE \
                and line["mosaic_kernels"] == (
                    3 if line["form"] == "kernel" else 0)
            if not ok:
                failed.append(f"{name}:{line['form']}:{line.get('tile')}")
            print(json.dumps({**line, "ok": ok,
                              "device_kind": dev.device_kind}), flush=True)
    for name in HEADNORM_CASES:
        for line in headnorm_case(name, jnp.bfloat16) \
                if name in args.cases else ():
            # a kernel forward; forward and backward under the gradient
            ok = max(line["rel_err"].values()) < TOLERANCE \
                and line["mosaic_kernels"] == (
                    3 if line["form"] == "kernel" else 0)
            if not ok:
                failed.append(
                    f"{name}:{line['rule']}:{line['form']}:"
                    f"{line.get('tile')}")
            print(json.dumps({**line, "ok": ok,
                              "device_kind": dev.device_kind}), flush=True)
    for name in CONV_CASES:
        for line in conv_case(name, jnp.bfloat16) \
                if name in args.cases else ():
            # a kernel a result forward; the vjp runs no forward of its own
            ok = max(line["rel_err"].values()) < TOLERANCE \
                and line["mosaic_kernels"] == 2 * line.get("results", 0)
            if not ok:
                failed.append(
                    f"{name}:{line['form']}:{line.get('forward')}:"
                    f"{line.get('backward')}")
            print(json.dumps({**line, "ok": ok,
                              "device_kind": dev.device_kind}), flush=True)
    for name in WINDOW_CASES:
        for line in window_case(name, jnp.bfloat16) \
                if name in args.cases else ():
            ok = max(line["rel_err"].values()) < TOLERANCE
            if not ok:
                failed.append(f"{name}:{line['window']}:{line['tile']}")
            print(json.dumps({**line, "ok": ok,
                              "device_kind": dev.device_kind}), flush=True)
    for name in SSCAN_CASES:
        for line in sscan_case(name, jnp.bfloat16) \
                if name in args.cases else ():
            # a kernel forward (with the entering states), one backward
            ok = max(line["rel_err"].values()) < TOLERANCE \
                and line["mosaic_kernels"] == (
                    2 if line["form"] == "kernels" else 0)
            if not ok:
                failed.append(f"{name}:{line['form']}:{line['positions']}")
            print(json.dumps({**line, "ok": ok,
                              "device_kind": dev.device_kind}), flush=True)
    for name in KDA_CASES:
        for line in kda_case(name, jnp.bfloat16) \
                if name in args.cases else ():
            # the forward kernel (o and the entering states) and the backward
            ok = max(line["rel_err"].values()) < TOLERANCE \
                and line["mosaic_kernels"] == (
                    2 if line["form"] == "kernels" else 0)
            if not ok:
                failed.append(f"{name}:{line['form']}:{line['Hb']}")
            print(json.dumps({**line, "ok": ok,
                              "device_kind": dev.device_kind}), flush=True)
    for name in EVA_CASES:
        for line in eva_case(name, jnp.bfloat16) \
                if name in args.cases else ():
            # forward and backward of the flash pair, the remote pair and
            # the pooling pair
            ok = max(line["rel_err"].values()) < TOLERANCE \
                and line["mosaic_kernels"] == (
                    6 if line["form"] == "kernels" else 0)
            if not ok:
                failed.append(f"{name}:{line['form']}")
            print(json.dumps({**line, "ok": ok,
                              "device_kind": dev.device_kind}), flush=True)
    if failed:
        sys.exit(f"failed: {failed}")


if __name__ == "__main__":
    main()
