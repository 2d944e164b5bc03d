"""A windowed layer's flash kernels past `_WHOLE_SEQ_MAX` take a tile's keys
(the backward: a k tile's query rows) as ONE band (`ops/flash_attention.py:
_band`): the band against `reference_attention`, its clamped ends, the gate
between band and walk, what the counters say of it, and that a call with no
window has nothing of it."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from model_kit import max_diff

import ray_tpu.ops
from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops.flash_attention import BlockRule
from ray_tpu.util import tracing
from tools.chip_kernels import walking


@pytest.fixture(autouse=True)
def interpret_these_sizes(monkeypatch):
    """The kernels interpreted at S = 4,096 too (`ops.by_platform` takes the
    reference past the tests' usual sizes)."""
    monkeypatch.setattr(ray_tpu.ops, "INTERPRET_MAX_ELEMS", 1 << 24)


def _qkv(S, H, Hkv, D, Dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (1, H, S, D), jnp.float32),
            jax.random.normal(ks[1], (1, Hkv, S, D), jnp.float32),
            jax.random.normal(ks[2], (1, Hkv, S, Dv), jnp.float32),
            jax.random.normal(ks[3], (1, H, S, Dv), jnp.float32))


def _both_passes(q, k, v, do, rule, block):
    """(o, lse, dq, dk, dv) of the kernels, interpreted, no fallback."""
    with warnings.catch_warnings():
        warnings.simplefilter("error", fa.AttentionFallbackWarning)
        _, res = fa._flash_fwd(q, k, v, rule, None, block, block)
        o, lse = res[3], res[4]
        grads = fa._flash_bwd(rule, None, block, block, res, do)
    return (o, lse, *grads)


def _reference(q, k, v, do, rule):
    scale = q.shape[-1] ** -0.5
    o, lse = fa.reference_attention(q, k, v, scale, rule)
    delta = jnp.sum(do * o, -1)
    return (o, lse, *fa._reference_backward(q, k, v, lse, do, delta, scale,
                                            rule))


# (S, window, block, query heads, key/value heads, q/k width, v width):
# windows of 512 and 1,024 (the cells'), of 384 (no whole tiles of 256 or
# 512) and of 200 (no whole lanes: W' = 256), at tiles of 128, 256 and 512
# and the tiles `_auto_tiles` takes (None); groups of 1, 6 and 8 query heads
# a key/value head; q and k 64 wide on v 128 wide (phi4's differential
# attention); and windows as long as the sequence or longer, which keep
# the walk
BAND_CASES = [
    (2048, 512, 128, 1, 1, 32, 32),
    (2048, 512, 256, 1, 1, 32, 32),
    (2048, 512, 512, 1, 1, 32, 32),
    (2048, 512, None, 1, 1, 32, 32),
    (2048, 1024, 128, 1, 1, 32, 32),
    (2048, 1024, 256, 1, 1, 32, 32),
    (2048, 1024, 512, 1, 1, 32, 32),
    (2048, 384, 128, 1, 1, 32, 32),
    (2048, 384, 256, 1, 1, 32, 32),
    (2048, 384, 512, 1, 1, 32, 32),
    (2048, 200, 256, 1, 1, 32, 32),
    (2048, 512, 256, 6, 1, 16, 16),
    (2048, 512, 256, 8, 2, 16, 16),
    (2048, 512, 256, 2, 1, 64, 128),
    (2048, 2048, 256, 1, 1, 32, 32),
    (2048, 5000, 256, 1, 1, 32, 32),
    (4096, 512, 256, 1, 1, 16, 16),
    (4096, 512, 512, 6, 1, 16, 16),
    (4096, 1024, 128, 1, 1, 16, 16),
    (4096, 1024, 256, 8, 1, 16, 16),
    (4096, 1024, None, 1, 1, 16, 16),
    (4096, 384, 128, 8, 1, 16, 16),
    (4096, 512, 128, 2, 1, 64, 128),
    (4096, 4096, 512, 1, 1, 16, 16),
]


@pytest.mark.parametrize("S,window,block,H,Hkv,D,Dv", BAND_CASES)
def test_the_band_matches_the_reference(S, window, block, H, Hkv, D, Dv):
    """o, each row's lse and all three gradients, interpreted, against
    `reference_attention` under the same rule; the band is taken exactly
    where `_band` says."""
    q, k, v, do = _qkv(S, H, Hkv, D, Dv)
    rule = BlockRule(window=window)
    tile = block or 256
    took = window + tile <= S
    assert (fa._band(rule, S, tile, False) is not None) == took
    if block is None:
        assert fa._auto_tiles(S, rule) == ((256, 256), (256, 256))
    names = ("attention.window_kernels", "attention.window_band_kernels")
    with tracing.timeline_span("train.fit", root=True) as job:
        before = [tracing.counter(name) for name in names]
        got = _both_passes(q, k, v, do, rule, block)
        kernels, bands = (tracing.counter(name) - b
                          for name, b in zip(names, before))
    tracing.timeline_take(job.trace_id)
    assert kernels == 2 and bands == (2 if took else 0)
    want = _reference(q, k, v, do, rule)
    for name, g, w, limit in zip(("o", "lse", "dq", "dk", "dv"), got, want,
                                 (1e-5, 1e-5, 2e-5, 5e-5, 5e-5)):
        assert max_diff(g, w) < limit, name


@pytest.mark.parametrize("S,window,block", [
    (2048, 512, 256), (2048, 384, 128), (2048, 200, 512), (4096, 1024, 512)])
def test_the_clamped_ends(S, window, block):
    """The first q tiles' band starts at key 0 and holds keys past their rows;
    the last k tiles' band ends at row S and holds rows before their keys:
    those rows' o and lse and those keys' dk and dv are the reference's, and
    the band's starts are what `_k_spans` and `_q_spans` say."""
    rule = BlockRule(window=window)
    band = fa._band(rule, S, block, False)
    lead = band - block                         # W'
    for i in range(S // block):
        (start, rows, how), = fa._k_spans(rule, i, block, block, S, band)[2]
        assert (start, rows, how) == (max(i * block - lead, 0), band, "both")
        assert start % 128 == 0 and start + rows <= S
        # every key a row of the tile attends is in the band
        assert start <= max(i * block - window + 1, 0)
        assert start + rows >= (i + 1) * block
        (start, rows, how, noised), = fa._q_spans(
            rule, i, block, block, S, band)[1]
        assert (start, rows, how, noised) == (
            min(i * block, S - band), band, "both", 0)
        assert start <= i * block
        assert start + rows >= min((i + 1) * block + window - 1, S)
    q, k, v, do = _qkv(S, 1, 1, 16, 16, seed=1)
    o, lse, dq, dk, dv = _both_passes(q, k, v, do, rule, block)
    o_r, lse_r, dq_r, dk_r, dv_r = _reference(q, k, v, do, rule)
    head, tail = slice(0, band), slice(S - band, S)
    assert max_diff(o[:, :, head], o_r[:, :, head]) < 1e-5
    assert max_diff(lse[:, :, head], lse_r[:, :, head]) < 1e-5
    assert max_diff(dq[:, :, head], dq_r[:, :, head]) < 2e-5
    assert max_diff(dk[:, :, tail], dk_r[:, :, tail]) < 5e-5
    assert max_diff(dv[:, :, tail], dv_r[:, :, tail]) < 5e-5
    # a row's first key alone: the first row attends itself
    assert float(lse[0, 0, 0]) == pytest.approx(
        float(jnp.sum(q[0, 0, 0] * k[0, 0, 0]) * 16 ** -0.5), abs=1e-5)


# (window, S, block, a grid step the whole sequence, a mask that is data,
# the band's rows or None, why)
GATE_CASES = [
    (512, 16384, 128, False, False, 640, "laguna's window at 128"),
    (512, 16384, 256, False, False, 768, "at 256: what `_auto_tiles` takes"),
    (512, 16384, 512, False, False, 1024, "at 512"),
    (1024, 16384, 256, False, False, 1280, "mellum2's window at 256"),
    (1024, 16384, 512, False, False, 1536, "at 512"),
    (384, 2048, 128, False, False, 512, "whole lanes, no whole tiles"),
    (200, 2048, 256, False, False, 512, "W' = 256: rounded up to lanes"),
    (1, 2048, 128, False, False, 256, "a row's own key: W' = 128"),
    (1152, 16384, 512, False, False, 1664, "the widest at 512-tiles"),
    (1153, 16384, 512, False, False, None, "its temporaries pass _TILE_VMEM"),
    (3072, 16384, 256, False, False, 3328, "the widest at 256-tiles"),
    (3073, 16384, 256, False, False, None, "its temporaries pass _TILE_VMEM"),
    (512, 16384, 1024, False, False, None, "1,024-tiles: 18 MiB a band"),
    (2048, 2048, 256, False, False, None, "the band is longer than S"),
    (1793, 2048, 256, False, False, None, "W' + 256 > S"),
    (1792, 2048, 256, False, False, 2048, "the band is all S rows"),
    (512, 1024, 256, True, False, None, "a grid step the whole sequence"),
    (512, 16384, 256, False, True, None, "a mask comes a tile a visit"),
    (None, 16384, 256, False, False, None, "no window"),
    (512, 16384, 192, False, False, None, "a tile that is no whole lanes"),
]


@pytest.mark.parametrize("window,S,block,whole,masked,rows,why", GATE_CASES)
def test_the_gate_between_band_and_walk(window, S, block, whole, masked,
                                        rows, why):
    assert fa._band(BlockRule(window=window), S, block, whole, masked) \
        == rows, why


def test_the_gate_reads_nothing_but_the_call():
    assert fa._band(True, 16384, 256, False) is None
    assert fa._band(False, 16384, 256, False) is None
    assert fa._band(BlockRule(4, 2), 16384, 256, False) is None
    # a grid step takes `_BAND_STEP` rows where they divide S
    assert fa._band_step(16384, 256, 768) == fa._BAND_STEP == 1024
    assert fa._band_step(16384, 256, None) == 256
    assert fa._band_step(2048 + 512, 256, 768) == 256
    assert fa._band_step(16384, 2048, 2560) == 2048


@pytest.mark.parametrize("S,window,tiles", [
    (16384, 512, ((256, 256), (256, 256))),
    (16384, 1024, ((256, 256), (256, 256))),
    (8192, 1024, ((256, 256), (256, 256))),
    (16384, 3072, ((256, 256), (256, 256))),
    (16384, 4096, ((512, 512), (512, 512))),    # too wide: the walk's tiles
    (2048, 2048, ((512, 512), (512, 512))),
    (1024, 512, ((512, 512), (256, 256))),      # whole: the diagonal's
    (16384, None, ((1024, 1024), (512, 512))),
])
def test_auto_tiles_under_a_band(S, window, tiles):
    assert fa._auto_tiles(S, BlockRule(window=window)) == tiles


NAMES = ("attention.tiles", "attention.tiles_skipped",
         "attention.pairs_visited", "attention.window_kernels",
         "attention.window", "attention.window_pairs_visited",
         "attention.window_band_kernels")


def _traced(S, rule, block, backward=False):
    """What one call's trace adds to `NAMES`: the forward kernel's, or
    with ``backward`` the forward's and the backward's."""
    q = jax.ShapeDtypeStruct((1, 8, S, 32), jnp.float32)
    k = jax.ShapeDtypeStruct((1, 2, S, 32), jnp.float32)
    call = lambda q, k, v: fa.flash_attention(q, k, v, rule, None, block,
                                              block)
    if backward:
        f = lambda q, k, v: jax.grad(lambda *a: jnp.sum(call(*a)))(q, k, v)
    else:
        f = call
    before = [tracing.counter(name) for name in NAMES]
    jax.eval_shape(f, q, k, k)
    return [tracing.counter(name) - b for name, b in zip(NAMES, before)]


@pytest.mark.parametrize("S,window,block,band", [
    (16384, 512, None, 768), (16384, 512, 128, 640), (16384, 512, 512, 1024),
    (16384, 1024, None, 1280), (16384, 1024, 512, 1536),
    (2048, 384, 256, 640), (16384, 512, 1024, None), (16384, 4096, None, None),
])
def test_the_counters_count_what_the_band_multiplies(S, window, block, band):
    """Every row of the sequence by a band's rows, the clamped ends as they
    are; `attention.window_band_kernels` beside `attention.window_kernels`;
    a walk counts its tiles as it did."""
    rule = BlockRule(window=window)
    tile = block or fa._auto_tiles(S, rule)[0][0]
    assert fa._band(rule, S, tile, False) == band
    tiles = (S // tile) ** 2
    if band:
        pairs = S * band
        visited = -(-pairs // tile ** 2)
    else:
        visited = fa._tiles_visited(rule, S, tile, tile)
        pairs = visited * tile * tile
    with tracing.timeline_span("train.fit", root=True) as job:
        assert _traced(S, rule, block) == [
            tiles, tiles - visited, pairs, 1, window, pairs, int(bool(band))]
        # the backward's k tile is the forward's q tile here: the same again
        assert _traced(S, rule, block, backward=True) == [
            2 * tiles, 2 * (tiles - visited), 2 * pairs, 2, 2 * window,
            2 * pairs, 2 * int(bool(band))]
        assert _traced(S, True, block)[3:] == [0, 0, 0, 0]
    tracing.timeline_take(job.trace_id)


def test_the_share_of_the_visited_pairs_a_band_attends():
    """What `window_head_pairs_attended_share` and
    `window_pairs_attended_share` will read at the cells' sizes: 65.6 % at
    W = 512 and 77.5 % at W = 1,024 with 256-tiles (0.667 and 0.80 less the
    first rows' triangle), where the walk of 512-tiles read 50.0 and 66.7."""
    for window, share, walked in ((512, 0.6563, 0.500), (1024, 0.7750, 0.667)):
        S = 16384
        attended = window * (window + 1) // 2 + (S - window) * window
        band = fa._band(BlockRule(window=window), S, 256, False)
        assert attended / (S * band) == pytest.approx(share, abs=5e-4)
        walk = fa._tiles_visited(BlockRule(window=window), S, 512, 512)
        assert attended / (walk * 512 * 512) == pytest.approx(walked,
                                                              abs=5e-4)


def _jaxpr(causal, S=2048, block=None, backward=False):
    q = jax.ShapeDtypeStruct((1, 2, S, 32), jnp.float32)
    call = lambda q, k, v: fa.flash_attention(q, k, v, causal, None, block,
                                              block)
    f = (lambda q, k, v: jax.grad(lambda *a: jnp.sum(call(*a)), (0, 1, 2))(
        q, k, v)) if backward else call
    return str(jax.make_jaxpr(f)(q, q, q))


@pytest.mark.parametrize("backward", [False, True])
@pytest.mark.parametrize("causal", [True, False, BlockRule(4, 2),
                                    BlockRule(window=None)])
def test_a_call_with_no_window_has_nothing_of_the_band(causal, backward):
    """Its program, kernels' bodies included, is the same with the band
    taken out of the module (`tools/chip_kernels.py:walking`): no
    `multiple_of`, a tile a grid step."""
    with_band = _jaxpr(causal, backward=backward)
    assert "multiple_of" not in with_band
    with walking():
        assert _jaxpr(causal, backward=backward) == with_band


def test_a_windowed_call_is_another_program_under_a_band():
    """The same comparison does tell a band from a walk: the windowed call's
    program changes when the band is taken out, and names its kernels
    `fwd_rows_window` and `bwd_fused_window` either way."""
    rule = BlockRule(window=512)
    with_band = _jaxpr(rule, backward=True)
    assert "multiple_of" in with_band
    lowered = lambda: jax.jit(jax.grad(lambda q: jnp.sum(fa.flash_attention(
        q, q, q, rule)))).lower(jax.ShapeDtypeStruct(
            (1, 2, 2048, 32), jnp.float32)).as_text(debug_info=True)
    text = lowered()
    assert "fwd_rows_window" in text and "bwd_fused_window" in text
    with walking():
        walked = _jaxpr(rule, backward=True)
    assert walked != with_band and "multiple_of" not in walked


def test_a_window_as_long_as_the_sequence_is_causal_under_a_band_too():
    """W >= S keeps the walk, whose result is the diagonal's bit for bit
    (`tests/test_mellum.py` holds the short form to that); the band just
    under it agrees with the diagonal's to rounding on the rows it leaves
    whole (the first W)."""
    S = 2048
    q, k, v, do = _qkv(S, 1, 1, 32, 32)
    causal = _both_passes(q, k, v, do, True, 256)
    walked = _both_passes(q, k, v, do, BlockRule(window=S), 256)
    for a, b in zip(causal, walked):
        assert (np.asarray(a) == np.asarray(b)).all()
    band = _both_passes(q, k, v, do, BlockRule(window=1792), 256)
    assert max_diff(band[0][:, :, :1792], causal[0][:, :, :1792]) < 1e-5
    assert max_diff(band[1][:, :, :1792], causal[1][:, :, :1792]) < 1e-5


# -- aligned windows (PR 69): the diagonal inside windows that do not slide ---

ALIGNED_CASES = [
    # S, aligned, block (None: `_auto_tiles`'), H, Hkv, D
    (512, 256, None, 2, 2, 64),         # a grid step the whole sequence
    (1024, 128, 128, 2, 1, 32),         # a tile a window, grouped queries
    (2048, 512, None, 1, 1, 32),        # the walk of tiles, traced bounds
    (2048, 1024, 256, 2, 1, 32),        # four tiles a window
]


@pytest.mark.parametrize("S,aligned,block,H,Hkv,D", ALIGNED_CASES)
def test_aligned_windows_match_the_reference(S, aligned, block, H, Hkv, D):
    """Key j iff j <= i and j // A == i // A, forward and backward, against
    the masked softmax; the tiles of earlier windows are never visited."""
    rule = BlockRule(aligned=aligned)
    q, k, v, do = _qkv(S, H, Hkv, D, D)
    got = _both_passes(q, k, v, do, rule, block)
    row = jnp.arange(S)
    seen = (row[None] <= row[:, None]) \
        & (row[None] // aligned == row[:, None] // aligned)
    np.testing.assert_array_equal(np.asarray(fa._attended(rule, S)),
                                  np.asarray(seen))
    for g, w in zip(got, _reference(q, k, v, do, rule)):
        assert max_diff(g, w) < 2e-5
    # a window's triangle is every pair attended, and its tiles all that is
    # visited: S / A squares of (A / b) (A / b + 1) / 2 tiles
    b = block or fa._auto_tiles(S, rule)[0][0]
    assert fa._tiles_visited(rule, S, b, b) \
        == (S // aligned) * (aligned // b) * (aligned // b + 1) // 2
    assert int(seen.sum()) == (S // aligned) * aligned * (aligned + 1) // 2


@pytest.mark.parametrize("rule", [
    BlockRule(aligned=256, window=64), BlockRule(4, 1, None, 256),
    BlockRule(1, 2, None, 256), BlockRule(4, 2, None, 256),
    BlockRule(aligned=0)])
def test_aligned_windows_are_refused_beside_the_other_rules(rule):
    """Beside a sliding window, blocks or two kinds of row: not written
    until a model asks."""
    q = jnp.zeros((1, 1, 512, 32))
    with pytest.raises(NotImplementedError, match="aligned windows"):
        fa.flash_attention(q, q, q, rule)
    with pytest.raises(NotImplementedError, match="aligned windows"):
        fa.flash_attention_bshd(_tr(q), _tr(q), _tr(q), rule)


def _tr(x):
    return x.transpose(0, 2, 1, 3)


def test_tiles_that_do_not_divide_a_window_take_the_reference():
    q = jnp.zeros((1, 1, 1024, 32))
    with pytest.warns(fa.AttentionFallbackWarning, match="aligned windows"):
        fa.flash_attention(q, q, q, BlockRule(aligned=384), None, 256, 256)
    # and windows that do not divide the sequence
    with pytest.warns(fa.AttentionFallbackWarning, match="aligned windows"):
        fa.flash_attention(q, q, q, BlockRule(aligned=768), None, 256, 256)


# what `_auto_tiles` gave every other rule before aligned windows came
# (the parent of PR 69), (forward, backward) tiles by sequence length
TILES_BEFORE = {
    True: {512: (512, 256), 1024: (512, 256), 2048: (1024, 512),
           16384: (1024, 512)},
    False: {512: (512, 512), 1024: (1024, 1024), 2048: (1024, 512),
            16384: (1024, 512)},
    BlockRule(4, 2): {512: (256, 256), 1024: (512, 256), 2048: (512, 512),
                      16384: (512, 512)},
    BlockRule(window=1024): {512: (512, 256), 1024: (512, 256),
                             2048: (256, 256), 16384: (256, 256)},
    BlockRule(window=4096): {512: (512, 256), 2048: (512, 512),
                             16384: (512, 512)},
}


@pytest.mark.parametrize("causal", list(TILES_BEFORE), ids=str)
def test_every_other_rule_keeps_its_tiles_and_its_runs(causal):
    """The new field changes nothing where it is None: the tiles a call takes
    and the runs of tiles a kernel walks are the parent's."""
    for S, (fwd, bwd) in TILES_BEFORE[causal].items():
        assert fa._auto_tiles(S, causal) == ((fwd, fwd), (bwd, bwd))
    assert fa._k_spans(fa.CAUSAL, 3, 512, 512, 4096) \
        == (3, 0, [(0, 3, None), (3, 4, "upto")])
    assert fa._q_spans(fa.CAUSAL, 3, 512, 512, 4096) \
        == (3, [(3, 4, "upto", 0), (4, 8, None, 0)])
    # under aligned windows of 2,048 a row of tiles starts, and a column of
    # them ends, with its own window's
    rule = BlockRule(aligned=2048)
    assert fa._k_spans(rule, 6, 512, 512, 4096) \
        == (6, 0, [(4, 6, None), (6, 7, "upto")])
    assert fa._q_spans(rule, 1, 512, 512, 4096) \
        == (1, [(1, 2, "upto", 0), (2, 4, None, 0)])
    assert fa._auto_tiles(16384, rule) == ((512, 512), (512, 512))
    assert fa._auto_tiles(512, BlockRule(aligned=128)) \
        == ((128, 128), (128, 128))
    assert fa.BlockRule()._fields == ("block", "kinds", "window", "aligned")
    assert fa._form("fwd_rows", rule) == "fwd_rows_blocks"
    assert fa._band(rule, 16384, 256, False) is None
