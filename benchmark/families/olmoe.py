"""The `olmoe` family: how an OLMoE configuration file (the keys of the
model's published `config.json`) becomes the system under test
(`ray_tpu.models.olmoe` under a `ShardingConfig`), the counts the yardstick
needs (operations per token; the attention kernels' and the grouped
matmuls' operations and bytes; which of a trace's operations are which),
and the run of the plain reference it is judged against.

Nothing at module level imports jax: the parent process reads the counts
and must stay off the chip.

Telling a trace's operations apart.  `harness/xplane.py:op_name` names an
operation by its opcode (a custom call's target) and its result's shape,
and this family's step has two kinds of Mosaic kernel, both
`tpu_custom_call`: flash attention's, and the grouped matmuls XLA:TPU makes
of `jax.lax.ragged_dot` (with the small kernel that lays out their groups).
So they are told apart by shape: attention's results are (B, S, H*D) or
(B*H, S, D) arrays and row statistics; a grouped matmul's is one array of
the T*k routed rows or of one matrix per expert.  The shapes depend on the
step's tokens, so the predicates take them.
"""

from __future__ import annotations

import re

from benchmark.families import gpt2

DIMS = re.compile(r"(?:bf16|f32|s32|u32|pred|f16|s8|u8)_((?:\d+_)+)")


class Family:
    def __init__(self, config: dict):
        self.config = config
        self.n_layer = config["num_hidden_layers"]
        self.n_head = config["num_attention_heads"]
        self.n_embd = config["hidden_size"]
        self.width = config["intermediate_size"]      # of one expert
        self.n_experts = config["num_experts"]
        self.top_k = config["num_experts_per_tok"]
        self.rows = config["padded_vocab_size"]
        self.mesh = None

    # -- counts: pure functions of the shapes, no jax ----------------------

    def param_count(self) -> int:
        e, w, n = self.n_embd, self.width, self.n_experts
        per_layer = 4 * e * e + 4 * e + e * n + 3 * n * e * w
        return 2 * self.rows * e + self.n_layer * per_layer + e

    def multiplying_params_per_token(self) -> int:
        """The parameters one token multiplies: the head, and per layer
        the four attention matrices, the router, and three matrices of
        each of the token's `top_k` experts (not of all of them)."""
        e = self.n_embd
        per_layer = (4 * e * e + e * self.n_experts
                     + self.top_k * 3 * e * self.width)
        return self.rows * e + self.n_layer * per_layer

    def flops_per_token(self, seq: int) -> float:
        """6 N + 12 L E S (PaLM, appendix B) with N as above; the
        attention term counts the full S x S products, as that formula
        does.  Copied from `ray_tpu.models.olmoe.count_flops_per_token`."""
        return (6 * self.multiplying_params_per_token()
                + 12 * self.n_layer * self.n_embd * seq)

    def _width_bytes(self) -> int:
        return {"bfloat16": 2, "float32": 4}[self.config["compute_dtype"]]

    def attention_cost(self, batch: int, seq: int) -> dict:
        """As `families/gpt2.py:attention_cost`, for `n_head` heads of
        `hidden_size / n_head`: causal attention needs half of each S x S
        product; forward two products, backward four; what a kernel
        recomputes is not counted.  Bytes: forward reads q, k, v and
        writes o; backward reads q, k, v, o, do and writes dq, dk, dv;
        the row statistics (B, H, S) in f32 once each way."""
        d = self.n_embd // self.n_head
        elems = batch * seq * self.n_head * d
        product = 2 * batch * self.n_head * seq * seq * d
        stats = batch * self.n_head * seq * 4
        return {
            "flops": self.n_layer * 6 * product / 2,
            "bytes": self.n_layer * (12 * elems * self._width_bytes()
                                     + 2 * stats),
        }

    def moe_cost(self, batch: int, seq: int) -> dict:
        """What one step's grouped matmuls must do, over all layers: each
        of the T*k routed rows goes through gate, up (E x W) and down
        (W x E): 3 products of 2 E W forward, and twice that backward
        (the rows' gradient and the matrices').  Bytes, in the compute
        type: forward, gate and up read the rows (R x E) and write R x W
        each, down reads R x W and writes R x E, each reads its N
        matrices once; backward, each of the three reads the gradient of
        what it wrote, its matrices and what it read, and writes the
        gradient of what it read and of its matrices.  Whatever the
        routing, the count is the same: no row is dropped."""
        r = batch * seq * self.top_k
        e, w, n = self.n_embd, self.width, self.n_experts
        b = self._width_bytes()
        weights = n * e * w
        forward = (2 * (r * e + r * w) + (r * w + r * e) + 3 * weights) * b
        # each matmul: dlhs reads dout and the matrices, writes dlhs;
        # drhs reads lhs and dout, writes the matrices' gradient
        backward = 3 * ((r * w + r * e) * 2 + r * w + r * e + 2 * weights) * b
        return {"flops": self.n_layer * 3 * 6 * r * e * w,
                "bytes": self.n_layer * (forward + backward)}

    def _shapes(self, op_name: str) -> list:
        return [tuple(int(d) for d in dims.strip("_").split("_"))
                for dims in DIMS.findall(op_name)]

    @staticmethod
    def _is_custom_call(op_name: str) -> bool:
        low = op_name.lower()
        return "custom_call" in low or "custom-call" in low

    def is_attention_kernel(self, op_name: str) -> bool:
        """A Mosaic kernel whose first result is shaped like the heads'
        activations: (B, S, H*D) on the lane layout, (B*H, S, D)
        transposed; neither the grouped matmuls (one array of rank 2, or
        of rank 3 with the experts leading and a matrix's shape after)
        nor their metadata (s32 vectors)."""
        if not self._is_custom_call(op_name):
            return False
        shapes = self._shapes(op_name)
        if not shapes or len(shapes[0]) != 3:
            return False
        d = self.n_embd // self.n_head
        first = shapes[0]
        lanes = first[2] == self.n_embd
        transposed = first[2] == d and first[0] % self.n_head == 0
        return (lanes or transposed) and not self.is_moe_matmul(op_name)

    def is_moe_matmul(self, op_name: str) -> bool:
        """A grouped matmul (XLA:TPU's kernel for `ragged_dot`), or the
        kernel that lays out its groups: a custom call whose one result
        is rows x E or rows x W (rank 2), or the experts' matrices
        (N, E, W) / (N, W, E), or, the metadata, a tuple of s32
        vectors."""
        if not self._is_custom_call(op_name):
            return False
        shapes = self._shapes(op_name)
        if not shapes:
            return False
        e, w, n = self.n_embd, self.width, self.n_experts
        first = shapes[0]
        if "_s32_" in op_name and all(len(s) == 1 for s in shapes):
            return True
        if len(first) == 2:
            return first[1] in (e, w)
        return first in ((n, e, w), (n, w, e))

    def is_moe_op(self, op_name: str, tokens: int) -> bool:
        """An operation of route, dispatch, experts or combine: a grouped
        matmul, a copy of the experts' stacks in the compute type, or any
        operation one of whose results has the T*k routed rows, the
        (T, experts) router's shape or the (T, k) choices'.  Not seen by
        shape, and so not counted: the weighted sum's (T, E) result where
        XLA fuses it into the residual add."""
        if self.is_moe_matmul(op_name):
            return True
        e, w, n = self.n_embd, self.width, self.n_experts
        if f"bf16_{n}_{e}_{w}_" in op_name or f"bf16_{n}_{w}_{e}_" in op_name:
            # the experts' stacks in the compute type: their cast and the
            # transposed copies the backward grouped matmuls read (the
            # f32 ones are the optimizer's update, and not counted)
            return True
        rows = tokens * self.top_k
        for shape in self._shapes(op_name):
            if rows in shape:
                return True
            if len(shape) >= 2 and shape[0] == tokens and \
                    shape[1] in (self.n_experts, self.top_k):
                return True
        return False

    # -- the system under test: runs in the worker that holds the chips ----

    # binding the devices, the state born sharded from the seed and the
    # placing of a batch are the `gpt2` family's, word for word: they read
    # `config["layout"]`, `_init` and `optimizer`, which this family has
    bind = gpt2.Family.bind
    init_state = gpt2.Family.init_state
    place_batch = gpt2.Family.place_batch

    def model_config(self):
        import jax.numpy as jnp

        from ray_tpu.models.olmoe import OlmoeConfig

        c = self.config
        return OlmoeConfig(
            vocab_size=c["padded_vocab_size"],
            max_seq=c["max_position_embeddings"], n_layer=self.n_layer,
            n_head=self.n_head, n_embd=self.n_embd, expert_width=self.width,
            n_experts=self.n_experts, top_k=self.top_k,
            rope_theta=float(c["rope_theta"]), rms_eps=c["rms_norm_eps"],
            aux_weight=c["router_aux_loss_coef"],
            z_weight=c["router_z_loss_coef"],
            compute_dtype=jnp.dtype(c["compute_dtype"]), remat=c["remat"])

    def optimizer(self):
        from benchmark.reference.olmoe import adamw

        return adamw(self.config["optimizer"])

    def _init(self, key):
        from ray_tpu.models import olmoe

        return olmoe.init_params(key, self.model_config())

    def lower_step(self, params, opt_state, batch):
        """The jitted train step, traced and lowered for this state."""
        import jax

        from ray_tpu.models import olmoe
        from ray_tpu.parallel.context import use_mesh

        with use_mesh(self.mesh):
            kept = jax.tree.map(lambda x: x.sharding, (params, opt_state))
            step = jax.jit(
                olmoe.make_train_step(self.model_config(), self.optimizer()),
                donate_argnums=(0, 1), out_shardings=(*kept, None))
            return step.lower(params, opt_state, batch)

    # -- the plain reference on the same seed and batches ------------------

    def reference_sizes(self):
        from benchmark.reference.olmoe import Sizes

        c = self.config
        return Sizes(n_head=self.n_head, top_k=self.top_k,
                     rope_theta=float(c["rope_theta"]),
                     rms_eps=c["rms_norm_eps"],
                     aux_weight=c["router_aux_loss_coef"],
                     z_weight=c["router_z_loss_coef"])

    def reference_losses(self, seed: int, batches) -> list:
        """Cross-entropies of the first len(batches) steps by
        `benchmark/reference/olmoe.py`, from the parameters the system's
        own init draws from `seed`, on the first bound device (this
        family's cells hold their state on one chip).  All of it is freed
        on return."""
        import jax
        import numpy as np

        from benchmark.reference import olmoe as reference

        program = reference.losses_program(
            self.reference_sizes(), self.config["optimizer"],
            self.config["reference"]["micro_batch"])

        def from_seed(key, tokens):
            # the parameters are born inside the program, so no second
            # copy of them waits outside it
            return program(to_reference(self._init(key)), tokens)

        device = self.devices[0]
        with jax.default_matmul_precision("highest"):
            losses = jax.jit(from_seed)(
                jax.device_put(jax.random.PRNGKey(seed), device),
                jax.device_put(np.stack(batches), device))
        return [float(v) for v in losses]


def to_reference(params) -> dict:
    """The system's parameter tree (`ray_tpu.models.olmoe.init_params`) in
    the layout `benchmark/reference/olmoe.py` reads."""
    layers = []
    i = 0
    while f"layer_{i}" in params:
        p = params[f"layer_{i}"]
        attn, moe = p["attn"], p["moe"]
        layers.append({
            "norm1": p["input_norm"]["scale"],
            "norm2": p["post_norm"]["scale"],
            "q_norm": attn["q_norm"]["scale"],
            "k_norm": attn["k_norm"]["scale"],
            "wq": attn["q_proj"]["kernel"], "wk": attn["k_proj"]["kernel"],
            "wv": attn["v_proj"]["kernel"], "wo": attn["o_proj"]["kernel"],
            "router": moe["router"]["kernel"],
            "gate": moe["wi_gate"], "up": moe["wi_up"], "down": moe["wo"],
        })
        i += 1
    return {"embed": params["embed_tokens"]["embedding"],
            "head": params["lm_head"]["kernel"],
            "norm_f": params["norm_f"]["scale"], "layers": layers}
