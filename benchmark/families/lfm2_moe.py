"""The `lfm2_moe` family: how an LFM2-MoE configuration file (the keys of
the model's published `config.json`) becomes the system under test
(`ray_tpu.models.lfm2_moe` under a `ShardingConfig`), the counts the
yardstick needs (operations per token; the attention kernels', the conv
operators' and the held experts' grouped matmuls' operations and bytes;
which of a trace's operations are which), and the run of the plain
reference it is judged against.

A configuration of this family is one chip's share of an expert-parallel
deployment: `num_experts` counts the experts HELD here, `experts_held.of`
the router's width, `vocab_size` the slice of the vocabulary the tokens
are drawn from (the rows of the tied embedding held here).

Nothing at module level imports jax: the parent process reads the counts
and must stay off the chip.

Telling a trace's operations apart (`harness/xplane.py:op_name` names an
operation by its opcode and its results' shapes; named scopes do not reach
the trace: PERF.md §7), by shape.  Attention's Mosaic kernels give
head-major arrays, (B*H, S, D) or (B*H_kv, S, D), and (B*H, S, 1)
statistics.  The grouped matmuls give one array of the buffered rows, E or
W wide, or the held experts' matrices.  A conv operator is every other
operation one of whose results has a dimension only it makes: the 3E of
[b | c | z] (W_in's result, the gradient of the gates and taps, W_in itself
and its gradient) or a taps-shaped (E, L).  Not seen by shape, here as in
the other families: results of (B, S, E) (the gates' and taps' forward,
W_out's result, W_in's gradient towards u) and the E x E gradient of W_out,
which W_q's and W_o's have too (PERF.md §7 says what that leaves out).
"""

from __future__ import annotations

from benchmark.families import deepseek_v3, gpt2, olmoe

CONV, ATTENTION = "conv", "full_attention"


class Family:
    def __init__(self, config: dict):
        self.config = c = config
        self.layer_types = tuple(c["layer_types"])
        self.n_layer = c["num_hidden_layers"]
        assert len(self.layer_types) == self.n_layer, "layer_types"
        self.n_dense = c["num_dense_layers"]
        self.n_head = c["num_attention_heads"]
        self.n_kv_head = c["num_key_value_heads"]
        self.n_embd = c["hidden_size"]
        self.head_dim = self.n_embd // self.n_head
        self.taps = c["conv_L_cache"]
        self.dense_width = c["intermediate_size"]
        self.width = c["moe_intermediate_size"]        # of one routed expert
        self.n_held = c["num_experts"]
        self.held_first = c["experts_held"]["first"]
        self.n_experts = c["experts_held"]["of"]       # the router's width
        self.top_k = c["num_experts_per_tok"]
        self.rows = c["vocab_size"]
        self.mesh = None

    @property
    def n_routed_layers(self) -> int:
        return self.n_layer - self.n_dense

    @property
    def n_conv_layers(self) -> int:
        return self.layer_types.count(CONV)

    @property
    def n_attention_layers(self) -> int:
        return self.layer_types.count(ATTENTION)

    # -- counts: pure functions of the shapes, no jax ----------------------

    def shortconv_params(self) -> int:
        """W_in (E x 3E), W_out (E x E) and the taps (E x L)."""
        e = self.n_embd
        return 4 * e * e + e * self.taps

    def attention_params(self) -> int:
        """W_q and W_o at H heads, W_k and W_v at H_kv."""
        e, d = self.n_embd, self.head_dim
        return 2 * e * self.n_head * d + 2 * e * self.n_kv_head * d

    def param_count(self) -> int:
        """Every leaf held here, norms and routing biases included; the
        head is the embedding and is counted once."""
        e = self.n_embd
        biased = 1 if self.config["use_expert_bias"] else 0
        routed = (e * self.n_experts + biased * self.n_experts
                  + self.n_held * 3 * e * self.width)
        return (self.rows * e + e + self.n_layer * 2 * e
                + self.n_conv_layers * self.shortconv_params()
                + self.n_attention_layers * (self.attention_params()
                                             + 2 * self.head_dim)
                + self.n_dense * 3 * e * self.dense_width
                + self.n_routed_layers * routed)

    def expected_rows_per_token(self) -> float:
        """Rows a token sends to the experts held here under a balanced
        router: top_k x held / experts (4 x 8 / 64 = 0.5)."""
        return self.top_k * self.n_held / self.n_experts

    def multiplying_params_per_token(self) -> float:
        """The parameters one token multiplies HERE: the tied head's rows
        held, a conv operator's three leaves, an attention operator's four
        matrices, a dense layer's feed-forward, and in a routed layer the
        router and the expected rows of held experts (three matrices
        each)."""
        e = self.n_embd
        routed = (e * self.n_experts
                  + self.expected_rows_per_token() * 3 * e * self.width)
        return (self.rows * e
                + self.n_conv_layers * self.shortconv_params()
                + self.n_attention_layers * self.attention_params()
                + self.n_dense * 3 * e * self.dense_width
                + self.n_routed_layers * routed)

    def flops_per_token(self, seq: int) -> float:
        """6 N + the score squares of the attention layers only: N as
        above; the squares 6 S heads (D + D), QK' and PV forward once and
        backward twice, the whole S x S as PaLM's formula counts it.
        Copied from `ray_tpu.models.lfm2_moe.count_flops_per_token`."""
        return (6 * self.multiplying_params_per_token()
                + 6 * self.n_attention_layers * seq * self.n_head
                * 2 * self.head_dim)

    def attention_cost(self, batch: int, seq: int) -> dict:
        """As `families/gpt2.py:attention_cost` with grouped queries:
        causal attention needs half of each S x S product, six products of
        H heads D deep; what a kernel recomputes is not counted.  Bytes: q,
        o, do and dq have H heads (forward reads q and writes o; backward
        reads q, o, do and writes dq: six arrays), k, v, dk and dv H_kv
        (read twice, written once: six arrays); the row statistics
        (B, H, S) in f32 once each way.  The float32 parts of dk and dv a
        query head writes before they are summed are the kernels' own
        doing and are not counted."""
        d = self.head_dim
        product = 2 * batch * self.n_head * seq * seq * d
        elems = 6 * batch * seq * d * (self.n_head + self.n_kv_head)
        stats = batch * self.n_head * seq * 4
        return {"flops": self.n_attention_layers * 6 * product / 2,
                "bytes": self.n_attention_layers * (
                    elems * self._width_bytes() + 2 * stats)}

    def shortconv_cost(self, batch: int, seq: int) -> dict:
        """What one training step's conv operators must do IN THE
        OPERATIONS A TRACE NAMES (`is_shortconv_op`), over all conv layers:
        W_in's forward product and its weight gradient, 2 T E 3E operations
        each; recomputation not counted.  W_in's gradient towards u, W_out
        forward and backward and the gates and taps are not told from other
        layers' operations by shape, so neither their time nor their work
        is here: counted with them, the least time would be set against the
        device time of half the operator and read over 100 %.  Bytes, in
        the compute type: the forward reads u (E a token) and W_in and
        writes [b c z] (3E); the weight gradient reads u and the gradient
        of [b c z] and writes W_in's in float32."""
        tokens = batch * seq
        e, b = self.n_embd, self._width_bytes()
        w_in = 3 * e * e
        return {"flops": self.n_conv_layers * 2 * 2 * tokens * w_in,
                "bytes": self.n_conv_layers * (
                    2 * 4 * e * tokens * b + w_in * (b + 4))}

    # the grouped matmuls over the rows the held experts are EXPECTED to
    # be sent (0.5 T a routed layer), and which custom calls they are, are
    # the `deepseek_v3` family's, word for word: they read the widths, the
    # experts held and `expected_rows_per_token`, which this family has
    moe_cost = deepseek_v3.Family.moe_cost
    is_moe_matmul = deepseek_v3.Family.is_moe_matmul
    _width_bytes = deepseek_v3.Family._width_bytes
    _shapes = olmoe.Family._shapes
    _is_custom_call = staticmethod(olmoe.Family._is_custom_call)

    def is_attention_kernel(self, op_name: str) -> bool:
        """A Mosaic kernel whose first result is a head-major array of the
        heads' activations or gradients: (B*H, S, D), or (B*H_kv, S, D)."""
        if not self._is_custom_call(op_name) or self.is_moe_matmul(op_name):
            return False
        shapes = self._shapes(op_name)
        return bool(shapes) and len(shapes[0]) == 3 \
            and shapes[0][0] % self.n_kv_head == 0 \
            and shapes[0][2] == self.head_dim

    def buffered_rows(self, tokens: int) -> int:
        """`ray_tpu/ops/moe.py:buffer_rows` for this share, copied (no jax
        here): twice the expected rows, to a whole tile of 8, and never
        more than the T*k routed rows."""
        rows = tokens * self.top_k
        need = -(-2 * rows * self.n_held // self.n_experts)
        return min(rows, -(-need // 8) * 8)

    def is_moe_op(self, op_name: str, tokens: int) -> bool:
        """An operation of route, dispatch, the held experts or combine: a
        grouped matmul, a copy of the held stacks in the compute type, or
        any operation one of whose results has the rows of the buffer
        between dispatch and combine (`buffered_rows`), the T*k routed
        rows, or the router's (T, experts) or (T, k)."""
        if self.is_moe_matmul(op_name):
            return True
        buffered = self.buffered_rows(tokens)
        e, w, n = self.n_embd, self.width, self.n_held
        if f"bf16_{n}_{e}_{w}_" in op_name or f"bf16_{n}_{w}_{e}_" in op_name:
            return True
        for shape in self._shapes(op_name):
            if buffered in shape or tokens * self.top_k in shape:
                return True
            if len(shape) >= 2 and shape[0] == tokens and \
                    shape[1] in (self.n_experts, self.top_k):
                return True
        return False

    def is_shortconv_op(self, op_name: str) -> bool:
        """An operation of a conv operator that its shapes give away: not a
        kernel, with a result that has the 3E of [b | c | z] as a dimension
        (W_in's result, the gradient that the gates and taps send back,
        W_in in the compute type, its gradient and AdamW's update of it)
        or is shaped as the taps, (E, L) or (L, E).  The operator's
        (B, S, E) results and W_out's E x E gradient are not told from
        other layers' and are not counted."""
        if self._is_custom_call(op_name):
            return False
        e = self.n_embd
        for shape in self._shapes(op_name):
            if 3 * e in shape:
                return True
            if shape in ((e, self.taps), (self.taps, e)):
                return True
        return False

    # -- the system under test: runs in the worker that holds the chips ----

    bind = gpt2.Family.bind
    init_state = gpt2.Family.init_state
    place_batch = gpt2.Family.place_batch

    def model_config(self):
        import jax.numpy as jnp

        from ray_tpu.models.lfm2_moe import Lfm2MoeConfig

        c = self.config
        return Lfm2MoeConfig(
            vocab_size=self.rows, layer_types=self.layer_types,
            n_dense_layer=self.n_dense, n_head=self.n_head,
            n_kv_head=self.n_kv_head, n_embd=self.n_embd,
            conv_taps=self.taps, dense_width=self.dense_width,
            expert_width=self.width, n_experts=self.n_experts,
            held=(self.held_first, self.n_held), top_k=self.top_k,
            norm_topk_prob=c["norm_topk_prob"],
            use_expert_bias=c["use_expert_bias"],
            routed_scale=float(c["routed_scaling_factor"]),
            rope_theta=float(c["rope_parameters"]["rope_theta"]),
            rms_eps=c["norm_eps"],
            bias_update_speed=c["bias_update_speed"],
            compute_dtype=jnp.dtype(c["compute_dtype"]), remat=c["remat"],
            loss_chunk_rows=c["loss_chunk_rows"])

    def optimizer(self):
        """AdamW over every leaf but the routing biases."""
        from benchmark.reference.lfm2_moe import adamw
        from ray_tpu.models.lfm2_moe import trained_by

        return trained_by(adamw(self.config["optimizer"]))

    def _init(self, key):
        from ray_tpu.models import lfm2_moe

        return lfm2_moe.init_params(key, self.model_config())

    def lower_step(self, params, opt_state, batch):
        """The jitted train step, traced and lowered for this state."""
        import jax

        from ray_tpu.models import lfm2_moe
        from ray_tpu.parallel.context import use_mesh

        with use_mesh(self.mesh):
            kept = jax.tree.map(lambda x: x.sharding, (params, opt_state))
            step = jax.jit(
                lfm2_moe.make_train_step(self.model_config(),
                                         self.optimizer()),
                donate_argnums=(0, 1), out_shardings=(*kept, None))
            return step.lower(params, opt_state, batch)

    # -- the plain reference on the same seed and batches ------------------

    def reference_sizes(self):
        from benchmark.reference.lfm2_moe import Sizes

        c = self.config
        return Sizes(
            n_head=self.n_head, n_kv_head=self.n_kv_head, top_k=self.top_k,
            routed_scale=float(c["routed_scaling_factor"]),
            renorm_eps=c["renorm_eps"], held_first=self.held_first,
            rope_theta=float(c["rope_parameters"]["rope_theta"]),
            rms_eps=c["norm_eps"],
            bias_update_speed=c["bias_update_speed"],
            query_block=c["reference"]["query_block"])

    def reference_losses(self, seed: int, batches) -> list:
        """Cross-entropies of the first len(batches) steps by
        `benchmark/reference/lfm2_moe.py`, from the parameters the system's
        own init draws from `seed` (the same held experts and rows of the
        vocabulary), on the first bound device.  All of it is freed on
        return."""
        import jax
        import numpy as np

        from benchmark.reference import lfm2_moe as reference

        device = self.devices[0]
        with jax.default_matmul_precision("highest"):
            # the parameters are born on the device in the reference's
            # layout, so no second copy of them waits beside it
            params, biases = jax.jit(
                lambda key: to_reference(self._init(key)))(
                    jax.device_put(jax.random.PRNGKey(seed), device))
            return reference.first_losses(
                params, biases, jax.device_put(np.stack(batches), device),
                self.reference_sizes(), self.config["optimizer"])


def to_reference(params):
    """The system's parameter tree (`ray_tpu.models.lfm2_moe.init_params`)
    as `benchmark/reference/lfm2_moe.py` reads it: (parameters, with each
    run of alike layers stacked; the runs' routing biases, (layers, experts)
    for a routed run and None for a dense one).  A router without a bias
    (`use_expert_bias` false) gets zeros, which pick nothing."""
    import jax
    import jax.numpy as jnp

    runs = []                   # [(the layers' names, [layer], [bias])]
    i = 0
    while f"layer_{i}" in params:
        p = params[f"layer_{i}"]
        layer = {"norm1": p["operator_norm"]["scale"],
                 "norm2": p["ffn_norm"]["scale"]}
        if "short_conv" in p:
            conv = p["short_conv"]
            layer.update({"w_in": conv["in_proj"]["kernel"],
                          "taps": conv["conv"]["kernel"],
                          "w_out": conv["out_proj"]["kernel"]})
        else:
            attn = p["attn"]
            layer.update({"wq": attn["q_proj"]["kernel"],
                          "wk": attn["k_proj"]["kernel"],
                          "wv": attn["v_proj"]["kernel"],
                          "wo": attn["o_proj"]["kernel"],
                          "q_norm": attn["q_norm"]["scale"],
                          "k_norm": attn["k_norm"]["scale"]})
        bias = None
        if "mlp" in p:
            layer.update({k: p["mlp"][f"{k}_proj"]["kernel"]
                          for k in ("gate", "up", "down")})
        else:
            moe = p["moe"]
            router = dict(moe["router"])
            layer["router"] = router.pop("kernel")
            bias = next(iter(router.values()), None)
            if bias is None:
                bias = jnp.zeros(layer["router"].shape[1:], jnp.float32)
            layer.update({"e_gate": moe["wi_gate"], "e_up": moe["wi_up"],
                          "e_down": moe["wo"]})
        names = tuple(sorted(layer))
        if not runs or runs[-1][0] != names:
            runs.append((names, [], []))
        runs[-1][1].append(layer)
        runs[-1][2].append(bias)
        i += 1
    stack = lambda *leaves: jnp.stack(leaves)
    return ({"embed": params["embed_tokens"]["embedding"],
             "norm_f": params["norm_f"]["scale"],
             "groups": [jax.tree.map(stack, *layers)
                        for _, layers, _ in runs]},
            [None if biases[0] is None else jnp.stack(biases)
             for _, _, biases in runs])
