"""The `nemotron_h` model (`ray_tpu/models/nemotron_h.py`: one mixer a
layer, a Mamba-2 state-space mixer, grouped-query attention without
positions, or a mixture of experts that are not gated beside a shared one)
against the plain reference (`benchmark/reference/nemotron_h.py`: float32
`jax.numpy`, the recurrence position by position, the convolution as a sum
over taps, attention as a masked softmax with the key/value heads repeated,
the experts as a loop over those held) at a small size on the CPU: pattern
`MEM*E`, hidden 64, 8 state-space heads of 8 in 2 groups with a state of 16
and chunks of 8, 4 query heads on 2 key/value heads of 16, 8 experts 24
wide with 3 a token and a shared one 48 wide, sequence 64 (eight chunks),
vocabulary 512, seeded random weights.

The matrices are drawn four times as wide as the assumed 0.02, and the
matrices `rescale_prenorm_residual` shrinks are not shrunk: at 0.02 and
these widths a mixer's output is a thousandth of the residual stream and a
fault would hide under any tolerance.
"""

import dataclasses

import jax
import jax.numpy as jnp
import model_kit as kit
import numpy as np
import pytest
from model_kit import max_diff

from benchmark.families.nemotron_h import to_reference
from benchmark.reference import nemotron_h as reference
from ray_tpu.models import layers, nemotron_h as model
from ray_tpu.parallel.context import use_mesh
from ray_tpu.parallel.sharding import (
    ShardingConfig,
    infer_param_logical_dims,
    shard_params,
)

BF16 = dataclasses.replace(model.NEMOTRON_H_TINY, rescale_depth=1)
F32 = dataclasses.replace(BF16, compute_dtype=jnp.float32)
SIZES = reference.Sizes(mamba_heads=8, mamba_head_dim=8, n_groups=2,
                        state_size=16, n_head=4, n_kv_head=2, top_k=3,
                        query_block=16, scan_block=16)
BATCH, SEQ = 2, 64
OPTIMIZER = {"learning_rate": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
             "weight_decay": 0.1}
BIAS = model.ROUTING_BIAS

# float32 compute: the routing is identical and only summation order
# differs (chunks against positions, sorted groups against a loop over
# experts, flash blocks against a whole softmax)
F32_TOL = 5e-5
# bfloat16 compute against the float32 reference, logits of size up to 3.
# The seeded faults below move the logits by more and fail it.
BF16_LOGITS_TOL = 0.08


pytestmark = pytest.mark.usefixtures("highest_precision")


def vectors(cfg):
    """Routing biases that are not 0, and a D and a gain that are not all
    1 (one key for both)."""
    for n, i in enumerate(cfg.moe_layers):
        yield kit.Vector((f"layer_{i}", "moe", "router", BIAS), 0.05,
                         key=77 + n, start=0.0)
    for i, kind in enumerate(cfg.pattern):
        if kind == model.MAMBA:
            for leaf in (("D",), ("norm", "scale")):
                yield kit.Vector((f"layer_{i}", "mamba") + leaf, 0.5,
                                 key=99 + i, start=1.0)


@kit.once
def make_params(seed=0, cfg=F32):
    """Seeded weights, routing biases that are not 0, and a D and a gain
    that are not all 1."""
    return kit.drawn(lambda key: model.init_params(key, cfg), seed,
                     vectors(cfg), narrow=("conv",))


def make_tokens(seed=0):
    return kit.tokens(1000 + seed, BATCH, SEQ, F32.vocab_size)


@kit.once
def results(which, sizes=SIZES):
    """(logits, loss, rows sent to the experts, gradients in the
    reference's layout) of the system in float32 or of the reference, each
    one jitted program, computed once."""
    params, tokens = make_params(), make_tokens()
    with jax.default_matmul_precision("highest"):
        if which == "system":
            def run(params):
                logits, _ = model.forward(params, tokens[:, :-1], F32)
                (loss, parts), grads = jax.value_and_grad(
                    model.loss_fn, has_aux=True)(params, {"tokens": tokens},
                                                 F32)
                return logits, loss, parts["expert_rows"], \
                    to_reference(grads)[0], grads
            return jax.jit(run)(params)

        def run(params, biases):
            logits = reference.logits(params, biases, tokens[:, :-1], sizes)
            (loss, rows), grads = jax.value_and_grad(
                reference.losses, has_aux=True)(params, biases, tokens, sizes)
            return logits, loss, rows, grads
        return jax.jit(run)(*to_reference(params))


def reference_logits(sizes=SIZES):
    """The reference's logits alone, a program of its own a call: the side
    a seeded fault is in."""
    tokens = make_tokens()[:, :-1]
    return jax.jit(lambda p, b: reference.logits(p, b, tokens, sizes))(
        *to_reference(make_params()))


@pytest.mark.parametrize("what", ["logits", "loss", "expert_rows"])
def test_the_forward_pass_matches_the_reference_in_float32(what):
    index = ["logits", "loss", "expert_rows"].index(what)
    got, want = results("system")[index], results("reference")[index]
    assert got.shape == want.shape
    if what == "expert_rows":
        assert (np.asarray(got) == np.asarray(want)).all()
        assert int(got.sum()) == 2 * BATCH * SEQ * 3      # nothing dropped
    else:
        assert max_diff(got, want) < F32_TOL


def test_gradients_of_every_leaf_match():
    got, want = results("system")[3], results("reference")[3]
    flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_want = jax.tree_util.tree_flatten_with_path(want)[0]
    assert [p for p, _ in flat_got] == [p for p, _ in flat_want]
    # embed, head, norm_f; two Mamba-2 layers of 9; two mixtures of 6; the
    # attention layer's 5
    assert len(flat_got) == 3 + 2 * 9 + 2 * 6 + 5
    for (path, g), (_, w) in zip(flat_got, flat_want):
        assert float(jnp.max(jnp.abs(w))) > 0, path     # nothing is dead
        assert max_diff(g, w) < F32_TOL * max(
            1.0, float(jnp.max(jnp.abs(w)))), path


def test_the_bias_gets_no_gradient_and_the_head_is_its_own():
    grads = results("system")[4]
    for i in F32.moe_layers:
        assert not np.asarray(grads[f"layer_{i}"]["moe"]["router"][BIAS]).any()
    params = make_params()
    assert params["lm_head"]["kernel"].shape == (64, 512)
    assert np.asarray(grads["lm_head"]["kernel"]).any()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bfloat16_compute_stays_close_and_routes_alike(seed):
    params, tokens = make_params(seed), make_tokens(seed)
    logits, stats = jax.jit(lambda p: model.forward(
        p, tokens[:, :-1], BF16))(params)
    ref_params, biases = to_reference(params)
    want = jax.jit(lambda p, b: reference.logits(
        p, b, tokens[:, :-1], SIZES))(ref_params, biases)
    # a token whose k-th and k+1-th scores tie in bf16 may take another
    # expert: most tokens do not, and those agree within the band
    diff = jnp.max(jnp.abs(logits - want), axis=-1)
    close = diff < BF16_LOGITS_TOL
    assert float(jnp.mean(close)) > 0.75, float(jnp.mean(close))
    rows = jax.jit(lambda p, b: reference.losses(p, b, tokens, SIZES)[1])(
        ref_params, biases)
    assert int(jnp.sum(jnp.abs(stats["expert_rows"] - rows))) \
        < 0.1 * int(rows.sum())


def system_steps(cfg, steps=3, lr=None):
    params, tokens = make_params(cfg=cfg), make_tokens()
    settings = dict(OPTIMIZER, learning_rate=lr or OPTIMIZER["learning_rate"])
    optimizer = model.trained_by(reference.adamw(settings))
    step = jax.jit(model.make_train_step(cfg, optimizer))
    opt_state = optimizer.init(params)
    losses, outs = [], []
    for _ in range(steps):
        before = params
        params, opt_state, out = step(params, opt_state, {"tokens": tokens})
        losses.append(float(out["loss"]))
        outs.append((before, params, out))
    return losses, outs, opt_state


@kit.once
def reference_steps():
    params, biases = kit.own(to_reference(make_params()))
    tokens = make_tokens()
    with jax.default_matmul_precision("highest"):
        return reference.first_losses(
            params, biases, jnp.stack([tokens] * 3), SIZES, OPTIMIZER)


def test_three_steps_match_the_reference_program_and_the_bias_moves_by_rule():
    losses, outs, opt_state = system_steps(F32)
    assert np.allclose(losses, reference_steps(), atol=F32_TOL), (
        losses, reference_steps())
    assert losses[2] < losses[1] < losses[0]
    # no moments for the bias: AdamW's state holds a leaf for every other
    # leaf twice, and the step count
    n_params = len(jax.tree.leaves(outs[0][0]))
    n_bias = len(F32.moe_layers)
    assert len(jax.tree.leaves(opt_state)) == 2 * (n_params - n_bias) + 1
    for before, after, out in outs:
        for j, i in enumerate(F32.moe_layers):
            b0 = before[f"layer_{i}"]["moe"]["router"][BIAS]
            b1 = after[f"layer_{i}"]["moe"]["router"][BIAS]
            n = np.asarray(out["expert_rows"][j], np.float32)
            # no decay, no gradient: the rule alone
            np.testing.assert_allclose(
                np.asarray(b1 - b0), 0.001 * np.sign(n.mean() - n), atol=1e-7)
        assert float(out["max_routing_bias"]) == pytest.approx(max(
            float(jnp.max(jnp.abs(
                before[f"layer_{i}"]["moe"]["router"][BIAS])))
            for i in F32.moe_layers))
        assert int(out["rows_held"]) == int(out["expert_rows"].sum())
        assert int(out["moe_overflow_layers"]) == 0


def test_bfloat16_train_step_tracks_the_reference_and_a_tripled_rate_does_not():
    want = reference_steps()
    got, _, _ = system_steps(BF16)
    assert max(abs(g - w) for g, w in zip(got, want)) < 0.01, (got, want)
    tripled, _, _ = system_steps(BF16, lr=3e-3)
    assert max(abs(g - w) for g, w in zip(tripled, want)) > 0.05


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """One mixture layer with the router's 128 columns, 6 a token: the
    parts that its sixteen shares of 8 experts give, the shared expert
    counted once, add up to what the uncut reference gives for the whole
    layer, every share seeing the routing over all 128."""
    cfg = dataclasses.replace(F32, n_experts=128, top_k=6, expert_width=8)
    params = make_params(cfg=cfg)
    p = params["layer_1"]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(9), (BATCH, SEQ, cfg.n_embd))
    shared = layers.dense_ffn(u, p["shared"], layers.relu2)
    total, rows = shared, []
    for first in range(0, 128, 8):
        share = {**p, **{k: p[k][first:first + 8] for k in ("wi_up", "wo")}}
        y, sent = layers.routed_layer(u, share, model._route(cfg), 128,
                                      (first, 8), layers.relu2)
        total += y - shared          # every chip has the shared expert whole
        rows.append(sent)
    whole, biases = to_reference(params)
    sizes = SIZES._replace(top_k=6)
    want, want_rows = reference.moe(u.reshape(-1, cfg.n_embd),
                                    whole["layers"][1], biases[1], sizes)
    assert max_diff(total.reshape(want.shape), want) < F32_TOL
    for sent in rows:
        assert (np.asarray(sent) == np.asarray(want_rows)).all()
    assert int(want_rows.sum()) == BATCH * SEQ * 6
    # and one share alone is not the layer
    assert max_diff(y.reshape(want.shape), want) > 0.01


# -- the seeded faults of the configuration's `loss_tolerance_reason`, here
# in the reference and at the logits

def _state_not_carried(chunk):
    def make(real):
        def recurrence(x, dt, a, b, c, d, block):
            cut = lambda v: v.reshape(-1, chunk, *v.shape[1:])
            return jax.lax.map(
                lambda v: real(*v[:2], a, *v[2:], d, chunk),
                (cut(x), cut(dt), cut(b), cut(c))).reshape(x.shape)
        return recurrence
    return make


def _kv_head_by_remainder(real):
    """Query head h on key/value head h % H_kv: the query heads are put in
    the order whose groups `jnp.repeat` then makes of them."""
    def attention(u, p, sizes):
        h, h_kv = sizes.n_head, sizes.n_kv_head
        d = p["wq"].shape[1] // h
        order = np.argsort(np.arange(h) % h_kv, kind="stable")
        wq = p["wq"].reshape(-1, h, d)[:, order].reshape(p["wq"].shape)
        wo = p["wo"].reshape(h, d, -1)[order].reshape(p["wo"].shape)
        return real(u, {**p, "wq": wq, "wo": wo}, sizes)
    return attention


def _rope(x, theta=10000.0):
    """x (seq, heads, d): rotate-half, the whole head
    (`partial_rotary_factor` 1, `rope_theta` 10000)."""
    s, d = x.shape[0], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = (jnp.arange(s, dtype=jnp.float32)[:, None]
             * inv_freq[None])[:, None, :]
    first, second = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [first * jnp.cos(angle) - second * jnp.sin(angle),
         first * jnp.sin(angle) + second * jnp.cos(angle)], axis=-1)


def _with_rope(real):
    def heads(u, p, sizes):
        q, k, v = real(u, p, sizes)
        return _rope(q), _rope(k), v
    return heads


def _norm_before_gate(y, z, gain, groups, eps):
    s, hp = y.shape
    y = reference.rms_norm(y.reshape(s, groups, hp // groups), 1.0, eps)
    return y.reshape(s, hp) * gain * jax.nn.silu(z)


def faults(chunk):
    """{name: (the reference's function to replace, real -> the faulty
    one)}; ``chunk``: where `state_not_carried` drops the state."""
    with_p = lambda **change: lambda real: lambda u, p, *rest: real(
        u, {**p, **{k: f(p[k]) for k, f in change.items()}}, *rest)
    return {
        "state_not_carried": ("recurrence", _state_not_carried(chunk)),
        "taps_looking_ahead": ("conv", lambda real: lambda v, taps, bias:
                               real(v[::-1], taps, bias)[::-1]),
        "dt_bias_left_out": ("mamba", with_p(dt_bias=jnp.zeros_like)),
        "d_x_left_out": ("mamba", with_p(d=jnp.zeros_like)),
        "group_by_remainder": (
            "heads_of_groups", lambda real: lambda v, heads: jnp.tile(
                v, (1,) * (v.ndim - 2) + (heads // v.shape[-2], 1))),
        "norm_before_gate": ("gated_norm", lambda real: _norm_before_gate),
        "one_norm_for_all_groups": (
            "gated_norm", lambda real: lambda y, z, gain, groups, eps:
            real(y, z, gain, 1, eps)),
        "relu_for_relu2": ("relu2", lambda real: lambda x, up, down:
                           jax.nn.relu(x @ up) @ down),
        "shared_expert_left_out": ("moe", with_p(s_down=jnp.zeros_like)),
        "routed_scale_left_out": (
            "moe", lambda real: lambda u, p, bias, sizes: real(
                u, p, bias, sizes._replace(routed_scale=1.0))),
        "rope_applied": ("heads", _with_rope),
        "kv_head_by_remainder": ("attention", _kv_head_by_remainder),
    }


FAULTS = faults(8)


@pytest.mark.parametrize("name", sorted(FAULTS))
def test_a_seeded_fault_fails_both_tolerances(monkeypatch, name):
    """The reference with one fault against the system: the logits differ
    by far more than the float32 tolerance and than the bfloat16 band."""
    attr, make = FAULTS[name]
    monkeypatch.setattr(reference, attr, make(getattr(reference, attr)))
    jax.clear_caches()      # `jax.checkpoint` keeps a layer's trace
    logits = reference_logits()     # the faulted side, and nothing else
    monkeypatch.undo()
    jax.clear_caches()
    moved = max_diff(logits, results("system")[0])
    assert moved > BF16_LOGITS_TOL > F32_TOL, (name, moved)


def test_four_chips_under_fsdp_give_the_one_device_loss():
    params, batch = make_params(), {"tokens": jnp.tile(make_tokens(), (2, 1))}
    want, _ = jax.jit(lambda p, b: model.loss_fn(p, b, F32))(params, batch)
    layout = ShardingConfig(fsdp=4)
    mesh = layout.build_mesh(jax.devices()[:4])
    with use_mesh(mesh):
        placed = {"tokens": jax.device_put(
            batch["tokens"], layout.named_sharding(mesh, "batch", None))}
        got, _ = jax.jit(lambda p, b: model.loss_fn(p, b, F32))(
            shard_params(params, layout, mesh), placed)
    assert abs(float(got) - float(want)) < F32_TOL


def test_parameters_carry_the_logical_dimensions_sharding_reads():
    shapes = jax.eval_shape(
        lambda key: model.init_params(key, F32), jax.random.PRNGKey(0))
    dims = {"/".join(str(getattr(k, "key", k)) for k in path):
            infer_param_logical_dims(
                tuple(getattr(k, "key", k) for k in path), leaf.shape)
            for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert dims["embed_tokens/embedding"] == ("vocab", "embed")
    assert dims["lm_head/kernel"] == ("embed", "vocab")
    assert dims["layer_0/mamba/in_proj/kernel"] == ("embed", "mlp")
    # the conv's channels are cut, its taps never; the heads' vectors whole
    assert dims["layer_0/mamba/conv/kernel"] == ("embed", None)
    assert dims["layer_0/mamba/conv/bias"] == ("embed",)
    for leaf in ("A_log", "D", "dt_bias", "norm/scale"):
        assert dims[f"layer_0/mamba/{leaf}"] == (None,)
    assert dims["layer_0/mamba/out_proj/kernel"] == ("heads", "embed")
    assert dims["layer_3/attn/q_proj/kernel"] == ("embed", "heads")
    assert dims["layer_3/attn/k_proj/kernel"] == ("embed", "heads")
    assert shapes["layer_3"]["attn"]["k_proj"]["kernel"].shape == (64, 32)
    assert dims["layer_3/attn/o_proj/kernel"] == ("heads", "embed")
    assert dims["layer_1/moe/wi_up"] == ("expert", "embed", "mlp")
    assert dims["layer_1/moe/wo"] == ("expert", "mlp", "embed")
    assert dims["layer_1/moe/router/kernel"] == ("embed", None)
    assert dims["layer_1/moe/shared/up_proj/kernel"] == ("embed", "mlp")
    assert dims["layer_1/moe/shared/down_proj/kernel"] == ("mlp", "embed")
    assert set(shapes["layer_1"]) == {"norm", "moe"}


def test_the_initialisation_is_the_assumed_one():
    cfg = dataclasses.replace(F32, rescale_depth=52)
    params = model.init_params(jax.random.PRNGKey(3), cfg)
    mamba = params["layer_0"]["mamba"]
    np.testing.assert_allclose(mamba["A_log"], np.log(np.arange(1, 9)),
                               rtol=1e-6)
    assert (np.asarray(mamba["D"]) == 1).all()
    dt = np.asarray(jax.nn.softplus(mamba["dt_bias"]))
    assert (dt >= 0.001 - 1e-6).all() and (dt <= 0.1 + 1e-6).all()
    assert float(jnp.max(jnp.abs(mamba["conv"]["kernel"]))) <= 0.5
    std = lambda x: float(jnp.std(x))
    assert std(mamba["in_proj"]["kernel"]) == pytest.approx(0.02, rel=0.1)
    for shrunk in (mamba["out_proj"]["kernel"],
                   params["layer_3"]["attn"]["o_proj"]["kernel"],
                   params["layer_1"]["moe"]["wo"],
                   params["layer_1"]["moe"]["shared"]["down_proj"]["kernel"]):
        assert std(shrunk) == pytest.approx(0.02 / 52 ** 0.5, rel=0.15)


def test_the_new_names_change_no_other_models_plan():
    """`KEPT_NAMES` with the state-space mixer's names in it plans the
    other models' stacks as it did without them, at every room."""
    from ray_tpu.models import deepseek_v3, gpt2, lfm2_moe, olmoe
    before = tuple(n for n in layers.KEPT_NAMES if not n.startswith("ssm/"))
    assert len(before) == len(layers.KEPT_NAMES) - 2
    x = jnp.zeros((2, 64, 64), jnp.bfloat16)
    for module, cfg in ((gpt2, gpt2.GPT2_TINY),
                        (olmoe, olmoe.OLMOE_TINY),
                        (deepseek_v3, deepseek_v3.DEEPSEEK_V3_TINY),
                        (lfm2_moe, lfm2_moe.LFM2_MOE_TINY)):
        params = jax.eval_shape(lambda key: module.init_params(key, cfg),
                                jax.random.PRNGKey(0))
        block = "h_{}" if module is gpt2 else "layer_{}"
        fn = module._block if module is gpt2 else module._layer
        calls = [(x, params[block.format(i)], cfg)
                 for i in range(cfg.n_layer)]
        marked = layers.keep_plan(fn, calls, (2,), room=0)["marked"]
        assert marked and not any(n.startswith("ssm/") for n in marked)
        for room in (0, max(marked.values()), sum(marked.values()) // 2,
                     sum(marked.values())):
            now = layers.keep_plan(fn, calls, (2,), room=room)
            names = ()
            kept = 0
            for name in before:       # the plan as the parent made it
                need = marked.get(name, 0)
                if need and kept + need <= room:
                    names += (name,)
                    kept += need
            assert now["names"] == names, (module.__name__, room)
            assert now["bytes_kept"] == kept


def test_counts_at_the_published_widths():
    """Nemotron-3-Nano-30B-A3B whole is the published 31.6 B; one chip's
    share of the 9-layer cut is 666.96 M parameters, ISSUE 38's
    arithmetic."""
    big = model.NEMOTRON_3_NANO_30B
    whole = jax.eval_shape(lambda key: model.init_params(key, big),
                           jax.random.PRNGKey(0))
    assert round(model.num_params(whole) / 1e9, 2) == 31.58
    assert [big.pattern.count(k) for k in "ME*"] == [23, 23, 6]
    assert big.n_layer == 52 and big.mamba_width == 4096
    assert big.conv_width == 6144
    share = dataclasses.replace(big, vocab_size=16384, held=(0, 8),
                                pattern=big.pattern[35:44])
    assert share.pattern == "MEMEMEM*E"
    shapes = jax.eval_shape(lambda key: model.init_params(key, share),
                            jax.random.PRNGKey(0))
    assert round(model.num_params(shapes) / 1e6, 2) == 666.96
    mamba = model.num_params(shapes["layer_0"]["mamba"])
    attn = model.num_params(shapes["layer_7"]["attn"])
    mixture = model.num_params(shapes["layer_1"]["moe"])
    assert [round(n / 1e6, 2) for n in (mamba, attn, mixture)] \
        == [38.74, 23.40, 100.12]
    assert model.scan_flops_per_token(share) == 131072 + 524288 + 2097152
    flops = model.count_flops_per_token(share, 8192)
    assert round(flops / 1e9, 2) == 2.35
