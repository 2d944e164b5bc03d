"""The `deepseek_v3` family: how a DeepSeek-V3-shaped configuration file
(the keys of the model's published `config.json`) becomes the system under
test (`ray_tpu.models.deepseek_v3` under a `ShardingConfig`), the counts
the yardstick needs (operations per token; the attention kernels' and the
held experts' grouped matmuls' operations and bytes; which of a trace's
operations are which), and the run of the plain reference it is judged
against.

A configuration of this family is one chip's share of an expert-parallel
deployment: `n_routed_experts` counts the experts HELD here,
`experts_held.of` the router's width, `vocab_size` the slice of the
vocabulary the tokens are drawn from.

Nothing at module level imports jax: the parent process reads the counts
and must stay off the chip.

Telling a trace's operations apart (`harness/xplane.py:op_name` names an
operation by its opcode and its results' shapes; named scopes do not reach
the trace: PERF.md §7), by shape.  Attention's Mosaic kernels give
(B*H, S, 192) / (B*H, S, 128) arrays and (B*H, S, 1) statistics.  The
grouped matmuls give one array of the T*k buffered rows, 2048 or 768 wide,
or the held experts' matrices.  The latent path outside the kernels is
every other operation one of whose results is as wide as something only
MLA makes: heads x 192, heads x 256, heads x 128 (q, kv, o and v flat), the
latent 576 / 512 / 64, or a head-shaped rank-4 array.  The mixture is told
by the buffered rows, the router's (T, experts) and (T, k), the held
stacks, and the shared experts' 1536.
"""

from __future__ import annotations

from benchmark.families import gpt2, olmoe


class Family:
    def __init__(self, config: dict):
        self.config = c = config
        self.n_layer = c["num_hidden_layers"]
        self.n_dense = c["first_k_dense_replace"]
        self.n_head = c["num_attention_heads"]
        self.n_embd = c["hidden_size"]
        self.latent = c["kv_lora_rank"]
        self.nope, self.rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
        self.qk_dim, self.v_dim = c["qk_head_dim"], c["v_head_dim"]
        self.dense_width = c["intermediate_size"]
        self.width = c["moe_intermediate_size"]        # of one routed expert
        self.shared_width = c["n_shared_experts"] * self.width
        self.n_held = c["n_routed_experts"]
        self.held_first = c["experts_held"]["first"]
        self.n_experts = c["experts_held"]["of"]       # the router's width
        self.top_k = c["num_experts_per_tok"]
        self.rows = c["padded_vocab_size"]
        self.mesh = None

    @property
    def n_routed_layers(self) -> int:
        return self.n_layer - self.n_dense

    # -- counts: pure functions of the shapes, no jax ----------------------

    def attention_params(self) -> int:
        e, h = self.n_embd, self.n_head
        return (e * h * self.qk_dim + e * (self.latent + self.rope)
                + self.latent * h * (self.nope + self.v_dim)
                + h * self.v_dim * e)

    def param_count(self) -> int:
        """Every leaf held here, norms and routing biases included."""
        e = self.n_embd
        attn = self.attention_params() + self.latent + 2 * e
        dense = 3 * e * self.dense_width
        routed = (e * self.n_experts + self.n_experts
                  + 3 * e * self.shared_width
                  + self.n_held * 3 * e * self.width)
        return (2 * self.rows * e + e + self.n_layer * attn
                + self.n_dense * dense + self.n_routed_layers * routed)

    def expected_rows_per_token(self) -> float:
        """Rows a token sends to the experts held here under a balanced
        router: top_k x held / experts (6 x 16 / 128 = 0.75)."""
        return self.top_k * self.n_held / self.n_experts

    def multiplying_params_per_token(self) -> float:
        """The parameters one token multiplies HERE: the head's rows held,
        per layer MLA's four matrices, in a routed layer the router, the
        shared experts and the expected rows of held experts (three
        matrices each), in a dense layer its MLP."""
        e = self.n_embd
        routed = (e * self.n_experts + 3 * e * self.shared_width
                  + self.expected_rows_per_token() * 3 * e * self.width)
        return (self.rows * e + self.n_layer * self.attention_params()
                + self.n_dense * 3 * e * self.dense_width
                + self.n_routed_layers * routed)

    def flops_per_token(self, seq: int) -> float:
        """6 N + the full score squares: N as above (what this chip's
        share multiplies, the absent experts' rows not counted: nobody
        here computes them); the squares 6 L S heads (192 + 128), QK' 192
        deep and PV 128, forward once and backward twice, the whole S x S
        as PaLM's formula counts it.  Copied from
        `ray_tpu.models.deepseek_v3.count_flops_per_token`."""
        return (6 * self.multiplying_params_per_token()
                + 6 * self.n_layer * seq * self.n_head
                * (self.qk_dim + self.v_dim))

    def _width_bytes(self) -> int:
        return {"bfloat16": 2, "float32": 4}[self.config["compute_dtype"]]

    def attention_cost(self, batch: int, seq: int) -> dict:
        """As `families/gpt2.py:attention_cost` at two widths: causal
        attention needs half of each S x S product; forward QK' (192 deep)
        and PV (128), backward dQ and dK (192) and dV and dP (128); what a
        kernel recomputes is not counted.  Bytes: forward reads q, k (192
        wide), v and writes o (128); backward reads q, k, v, o, do and
        writes dq, dk, dv: five arrays 192 wide and six 128 wide; the row
        statistics (B, H, S) in f32 once each way."""
        heads = batch * seq * self.n_head
        square = 2 * batch * self.n_head * seq * seq
        flops = 3 * square * (self.qk_dim + self.v_dim) / 2
        elems = heads * (5 * self.qk_dim + 6 * self.v_dim)
        return {"flops": self.n_layer * flops,
                "bytes": self.n_layer * (elems * self._width_bytes()
                                         + 2 * heads * 4)}

    def moe_cost(self, batch: int, seq: int) -> dict:
        """As `families/olmoe.py:moe_cost` for the rows the held experts
        are EXPECTED to be sent, 0.75 T a routed layer (a run's own count
        is `out["rows_held"]`): 3 products of 2 E W forward and twice that
        backward a row; bytes of the rows, their activations and the held
        experts' matrices, forward and backward.  The rows buffered beyond
        those (`moe.rows_buffered`) are no work anyone asked for, so they
        are not counted and show as a lower share."""
        r = batch * seq * self.expected_rows_per_token()
        e, w, n = self.n_embd, self.width, self.n_held
        b = self._width_bytes()
        weights = n * e * w
        forward = (2 * (r * e + r * w) + (r * w + r * e) + 3 * weights) * b
        backward = 3 * ((r * w + r * e) * 2 + r * w + r * e + 2 * weights) * b
        return {"flops": self.n_routed_layers * 3 * 6 * r * e * w,
                "bytes": self.n_routed_layers * (forward + backward)}

    _shapes = olmoe.Family._shapes
    _is_custom_call = staticmethod(olmoe.Family._is_custom_call)

    def is_moe_matmul(self, op_name: str) -> bool:
        """A grouped matmul over the held experts or the kernel that lays
        out its groups: a custom call whose one result is rows x E or rows
        x W (rank 2), the held stacks (held, E, W) / (held, W, E), or a
        tuple of s32 vectors."""
        if not self._is_custom_call(op_name):
            return False
        shapes = self._shapes(op_name)
        if not shapes:
            return False
        e, w, n = self.n_embd, self.width, self.n_held
        if "_s32_" in op_name and all(len(s) == 1 for s in shapes):
            return True
        first = shapes[0]
        if len(first) == 2:
            return first[1] in (e, w)
        return first in ((n, e, w), (n, w, e))

    def is_attention_kernel(self, op_name: str) -> bool:
        """A Mosaic kernel whose first result is a head-major array of the
        heads' activations, (B*H, S, 192) or (B*H, S, 128)."""
        if not self._is_custom_call(op_name) or self.is_moe_matmul(op_name):
            return False
        shapes = self._shapes(op_name)
        return bool(shapes) and len(shapes[0]) == 3 \
            and shapes[0][0] % self.n_head == 0 \
            and shapes[0][2] in (self.qk_dim, self.v_dim)

    def is_moe_op(self, op_name: str, tokens: int) -> bool:
        """An operation of route, dispatch, the held experts, combine or
        the shared experts: a grouped matmul, a copy of the held stacks in
        the compute type, or any operation one of whose results has the
        T*k buffered rows, the router's (T, experts) or (T, k), or the
        shared experts' width.  Not seen by shape: the weighted sum's and
        the shared experts' (T, E) results."""
        if self.is_moe_matmul(op_name):
            return True
        e, w, n = self.n_embd, self.width, self.n_held
        if f"bf16_{n}_{e}_{w}_" in op_name or f"bf16_{n}_{w}_{e}_" in op_name:
            return True
        rows = tokens * self.top_k
        for shape in self._shapes(op_name):
            if rows in shape or self.shared_width in shape[-2:]:
                return True
            if len(shape) >= 2 and shape[0] == tokens and \
                    shape[1] in (self.n_experts, self.top_k):
                return True
        return False

    def is_mla_op(self, op_name: str, tokens: int) -> bool:
        """An operation of the latent path outside the kernels: W_q,
        W_kv_a, the latent norm, W_kv_b, the RoPE parts, assembling k, the
        transposes to and from the kernels' layout, W_o, and their
        backward.  Not a kernel and not the mixture's, with a result
        shaped as only MLA's arrays are: activations (rank 3 and up, or
        (tokens, width)) as wide as q, kv, v and o flat (heads x 192, x 256,
        x 128) or the latent (576 / 512 / 64); rank 4 with the heads (or the
        one shared key part) and a head's width; or one of its four matrices (their gradients and
        AdamW's update).  A dense layer's gate and up have W_q's shapes
        where intermediate_size = heads x 192 (kanana: 6144) and are
        counted here: one layer's, of a model's 48."""
        if self._is_custom_call(op_name) or self.is_moe_op(op_name, tokens):
            return False
        h, e, r = self.n_head, self.n_embd, self.latent
        flat = {h * self.qk_dim, h * (self.nope + self.v_dim), h * self.v_dim,
                r + self.rope, r, self.rope}
        head = {self.qk_dim, self.v_dim, self.nope + self.v_dim, self.rope,
                self.rope // 2}
        matrices = {(e, h * self.qk_dim), (e, r + self.rope),
                    (r, h * (self.nope + self.v_dim)), (h * self.v_dim, e)}
        for shape in self._shapes(op_name):
            if len(shape) == 2:
                if shape in matrices or shape[::-1] in matrices or (
                        shape[0] == tokens and shape[1] in flat):
                    return True
            elif len(shape) >= 3 and shape[-1] in flat:
                return True
            elif len(shape) >= 4 and shape[-1] in head and (
                    h in shape[:-1] or shape[-2] == 1):
                return True
        return False

    # -- the system under test: runs in the worker that holds the chips ----

    bind = gpt2.Family.bind
    init_state = gpt2.Family.init_state
    place_batch = gpt2.Family.place_batch

    def model_config(self):
        import jax.numpy as jnp

        from ray_tpu.models.deepseek_v3 import DeepseekV3Config

        c = self.config
        return DeepseekV3Config(
            vocab_size=self.rows, n_layer=self.n_layer,
            n_dense_layer=self.n_dense, n_head=self.n_head,
            n_embd=self.n_embd, kv_lora_rank=self.latent,
            qk_nope_dim=self.nope, qk_rope_dim=self.rope,
            v_head_dim=self.v_dim, dense_width=self.dense_width,
            expert_width=self.width, shared_width=self.shared_width,
            n_experts=self.n_experts, held=(self.held_first, self.n_held),
            top_k=self.top_k, routed_scale=c["routed_scaling_factor"],
            rope_theta=float(c["rope_theta"]), rms_eps=c["rms_norm_eps"],
            bias_update_speed=c["bias_update_speed"],
            compute_dtype=jnp.dtype(c["compute_dtype"]), remat=c["remat"],
            loss_chunk_rows=c["loss_chunk_rows"])

    def optimizer(self):
        """AdamW over every leaf but the routing biases."""
        from benchmark.reference.deepseek_v3 import adamw
        from ray_tpu.models.deepseek_v3 import trained_by

        return trained_by(adamw(self.config["optimizer"]))

    def _init(self, key):
        from ray_tpu.models import deepseek_v3

        return deepseek_v3.init_params(key, self.model_config())

    def lower_step(self, params, opt_state, batch):
        """The jitted train step, traced and lowered for this state."""
        import jax

        from ray_tpu.models import deepseek_v3
        from ray_tpu.parallel.context import use_mesh

        with use_mesh(self.mesh):
            kept = jax.tree.map(lambda x: x.sharding, (params, opt_state))
            step = jax.jit(
                deepseek_v3.make_train_step(self.model_config(),
                                            self.optimizer()),
                donate_argnums=(0, 1), out_shardings=(*kept, None))
            return step.lower(params, opt_state, batch)

    # -- the plain reference on the same seed and batches ------------------

    def reference_sizes(self):
        from benchmark.reference.deepseek_v3 import Sizes

        c = self.config
        return Sizes(
            n_head=self.n_head, kv_lora_rank=self.latent,
            qk_nope_dim=self.nope, qk_rope_dim=self.rope,
            v_head_dim=self.v_dim, top_k=self.top_k,
            routed_scale=c["routed_scaling_factor"],
            held_first=self.held_first, rope_theta=float(c["rope_theta"]),
            rms_eps=c["rms_norm_eps"],
            bias_update_speed=c["bias_update_speed"],
            query_block=c["reference"]["query_block"])

    def reference_losses(self, seed: int, batches) -> list:
        """Cross-entropies of the first len(batches) steps by
        `benchmark/reference/deepseek_v3.py`, from the parameters the
        system's own init draws from `seed` (the same held experts and
        rows of the vocabulary), on the first bound device.  All of it is
        freed on return."""
        import jax
        import numpy as np

        from benchmark.reference import deepseek_v3 as reference

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
    """The system's parameter tree
    (`ray_tpu.models.deepseek_v3.init_params`) as
    `benchmark/reference/deepseek_v3.py` reads it: (parameters, with the
    routed layers' leaves stacked; the routing biases (routed layers,
    experts))."""
    import jax
    import jax.numpy as jnp

    dense, routed, biases = [], [], []
    i = 0
    while f"layer_{i}" in params:
        p = params[f"layer_{i}"]
        attn = p["attn"]
        layer = {
            "norm1": p["input_norm"]["scale"],
            "norm2": p["post_norm"]["scale"],
            "wq": attn["q_proj"]["kernel"],
            "wkv_a": attn["kv_a_proj"]["kernel"],
            "kv_norm": attn["kv_a_norm"]["scale"],
            "wkv_b": attn["kv_b_proj"]["kernel"],
            "wo": attn["o_proj"]["kernel"],
        }
        if "mlp" in p:
            layer.update({k: p["mlp"][f"{k}_proj"]["kernel"]
                          for k in ("gate", "up", "down")})
            dense.append(layer)
        else:
            moe = p["moe"]
            router = dict(moe["router"])
            layer["router"] = router.pop("kernel")
            (bias,) = router.values()
            biases.append(bias)
            layer.update({"e_gate": moe["wi_gate"], "e_up": moe["wi_up"],
                          "e_down": moe["wo"]})
            layer.update({f"s_{k}": moe["shared"][f"{k}_proj"]["kernel"]
                          for k in ("gate", "up", "down")})
            routed.append(layer)
        i += 1
    return ({"embed": params["embed_tokens"]["embedding"],
             "head": params["lm_head"]["kernel"],
             "norm_f": params["norm_f"]["scale"], "dense": dense,
             "routed": jax.tree.map(lambda *leaves: jnp.stack(leaves),
                                    *routed)},
            jnp.stack(biases))
