"""The `nemotron_h` family: how a Nemotron-H configuration file (the keys
of the model's published `config.json`) becomes the system under test
(`ray_tpu.models.nemotron_h` under a `ShardingConfig`), the counts the
yardstick needs (operations per token; the attention kernels', the held
experts' grouped matmuls' and the state-space scans' operations and bytes;
which of a trace's kernels are attention's), and the run of the plain
reference it is judged against.

A configuration of this family is one chip's share of an expert-parallel
deployment: `n_routed_experts` counts the experts HELD here,
`experts_held.of` the router's width, `vocab_size` the slice of the
vocabulary the tokens are drawn from (the rows of the embedding and of the
head held here).

Nothing at module level imports jax: the parent process reads the counts
and must stay off the chip.

A layer has ONE mixer; `hybrid_override_pattern` says which: `M` a Mamba-2
mixer, `*` attention, `E` a mixture.  Device time by part is read under the
program's own scopes (`harness/scope_trace.py`: `ssm/...`); only the
attention kernels are told by shape, for the readers that predate the
scopes.
"""

from __future__ import annotations

from benchmark.families import deepseek_v3, gpt2, olmoe

MAMBA, ATTENTION, MOE = "M", "*", "E"


class Family:
    def __init__(self, config: dict):
        self.config = c = config
        self.pattern = c["hybrid_override_pattern"]
        self.n_layer = c["num_hidden_layers"]
        assert len(self.pattern) == self.n_layer, "hybrid_override_pattern"
        self.n_embd = c["hidden_size"]
        self.mamba_heads = c["mamba_num_heads"]
        self.mamba_head_dim = c["mamba_head_dim"]
        self.n_groups = c["n_groups"]
        self.state = c["ssm_state_size"]
        self.taps = c["conv_kernel"]
        self.chunk = c["chunk_size"]
        self.n_head = c["num_attention_heads"]
        self.n_kv_head = c["num_key_value_heads"]
        self.head_dim = c["head_dim"]
        self.width = c["moe_intermediate_size"]        # of one routed expert
        self.shared_width = c["moe_shared_expert_intermediate_size"]
        self.n_held = c["n_routed_experts"]
        self.held_first = c["experts_held"]["first"]
        self.n_experts = c["experts_held"]["of"]       # the router's width
        self.top_k = c["num_experts_per_tok"]
        self.rows = c["vocab_size"]
        self.mesh = None

    @property
    def n_mamba_layers(self) -> int:
        return self.pattern.count(MAMBA)

    @property
    def n_attention_layers(self) -> int:
        return self.pattern.count(ATTENTION)

    @property
    def n_routed_layers(self) -> int:
        return self.pattern.count(MOE)

    @property
    def mamba_width(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_width(self) -> int:
        """[x | B | C]."""
        return self.mamba_width + 2 * self.n_groups * self.state

    # -- counts: pure functions of the shapes, no jax ----------------------

    def mamba_matrices(self) -> int:
        """W_in (E x (HP + [x B C] + H)), W_out (HP x E) and the taps."""
        e = self.n_embd
        return (e * (self.mamba_width + self.conv_width + self.mamba_heads)
                + self.mamba_width * e + self.conv_width * self.taps)

    def attention_params(self) -> int:
        """W_q and W_o at H heads, W_k and W_v at H_kv."""
        e, d = self.n_embd, self.head_dim
        return 2 * e * self.n_head * d + 2 * e * self.n_kv_head * d

    def param_count(self) -> int:
        """Every leaf held here, norms and routing biases included: the
        mixer's conv bias, A_log, D, dt_bias and its gated norm's gain; the
        embedding and the head each."""
        e = self.n_embd
        mamba = (self.mamba_matrices() + self.conv_width
                 + 3 * self.mamba_heads + self.mamba_width)
        mixture = (e * self.n_experts + self.n_experts
                   + self.n_held * 2 * e * self.width
                   + 2 * e * self.shared_width)
        return (2 * self.rows * e + e + self.n_layer * e
                + self.n_mamba_layers * mamba
                + self.n_attention_layers * self.attention_params()
                + self.n_routed_layers * mixture)

    def expected_rows_per_token(self) -> float:
        """Rows a token sends to the experts held here under a balanced
        router: top_k x held / experts (6 x 8 / 128 = 0.375)."""
        return self.top_k * self.n_held / self.n_experts

    def multiplying_params_per_token(self) -> float:
        """The parameters one token multiplies HERE: the head's rows held
        (the embedding is a gather), a Mamba-2 mixer's W_in, W_out and
        taps, an attention mixer's four matrices, and in a mixture the
        router, the shared expert and the expected rows of held experts
        (two matrices each)."""
        e = self.n_embd
        mixture = (e * self.n_experts + 2 * e * self.shared_width
                   + self.expected_rows_per_token() * 2 * e * self.width)
        return (self.rows * e + self.n_mamba_layers * self.mamba_matrices()
                + self.n_attention_layers * self.attention_params()
                + self.n_routed_layers * mixture)

    def scan_flops_per_token(self) -> float:
        """Forward operations a token of ONE layer's chunked scan: C B' and
        (L o C B') x over the causal half of a chunk's square, a chunk's
        own state, what earlier chunks add."""
        q, h, p = self.chunk, self.mamba_heads, self.mamba_head_dim
        g, n = self.n_groups, self.state
        return 2 * q * n * g / 2 + 2 * q * p * h / 2 + 2 * 2 * n * p * h

    def flops_per_token(self, seq: int) -> float:
        """6 N + the score squares of the attention layers, 6 S heads
        (D + D), the whole S x S as PaLM's formula counts it + the scans'
        four products forward once and backward twice.  Copied from
        `ray_tpu.models.nemotron_h.count_flops_per_token`."""
        return (6 * self.multiplying_params_per_token()
                + 6 * self.n_attention_layers * seq * self.n_head
                * 2 * self.head_dim
                + 3 * self.n_mamba_layers * self.scan_flops_per_token())

    def attention_cost(self, batch: int, seq: int) -> dict:
        """As `families/lfm2_moe.py:attention_cost`: causal attention needs
        half of each S x S product, six products of H heads D deep; q, o,
        do and dq have H heads (six arrays read or written), k, v, dk and
        dv H_kv (six); the row statistics (B, H, S) in f32 once each way.
        What a kernel recomputes, and the float32 parts of dk and dv a
        query head writes before they are summed, are not counted."""
        d = self.head_dim
        product = 2 * batch * self.n_head * seq * seq * d
        elems = 6 * batch * seq * d * (self.n_head + self.n_kv_head)
        stats = batch * self.n_head * seq * 4
        return {"flops": self.n_attention_layers * 6 * product / 2,
                "bytes": self.n_attention_layers * (
                    elems * self._width_bytes() + 2 * stats)}

    def moe_cost(self, batch: int, seq: int) -> dict:
        """As `families/deepseek_v3.py:moe_cost` for experts of TWO
        matrices: over the rows the held experts are EXPECTED to be sent
        (0.375 T a mixture layer), 2 products of 2 E W forward and twice
        that backward a row; bytes of the rows, their activations and the
        held experts' matrices, forward and backward."""
        r = batch * seq * self.expected_rows_per_token()
        e, w, n = self.n_embd, self.width, self.n_held
        b = self._width_bytes()
        weights = n * e * w
        forward = (2 * (r * e + r * w) + 2 * weights) * b
        backward = 2 * ((r * w + r * e) * 2 + r * w + r * e + 2 * weights) * b
        return {"flops": self.n_routed_layers * 2 * 6 * r * e * w,
                "bytes": self.n_routed_layers * (forward + backward)}

    def ssd_cost(self, batch: int, seq: int) -> dict:
        """What one training step's state-space scans must do, over the
        Mamba-2 layers, whatever implements them; recomputation not
        counted.  Operations: `scan_flops_per_token` forward, twice that
        backward.  Bytes a token and layer: the forward reads x (HP), B and
        C (2 G N) and dt (H, float32) and writes y (HP); the backward reads
        those and dy and writes dx, dB, dC and d dt: the compute type's
        width but for dt."""
        tokens = batch * seq
        b = self._width_bytes()
        hp, gn, h = self.mamba_width, self.n_groups * self.state, \
            self.mamba_heads
        read = (hp + 2 * gn) * b + h * 4
        forward = read + hp * b
        backward = read + hp * b + read
        return {"flops": self.n_mamba_layers * 3 * tokens
                * self.scan_flops_per_token(),
                "bytes": self.n_mamba_layers * tokens * (forward + backward)}

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

    # -- the system under test: runs in the worker that holds the chips ----

    bind = gpt2.Family.bind
    init_state = gpt2.Family.init_state
    place_batch = gpt2.Family.place_batch

    def model_config(self):
        import jax.numpy as jnp

        from ray_tpu.models.nemotron_h import NemotronHConfig

        c = self.config
        return NemotronHConfig(
            vocab_size=self.rows, pattern=self.pattern, n_embd=self.n_embd,
            mamba_heads=self.mamba_heads,
            mamba_head_dim=self.mamba_head_dim, n_groups=self.n_groups,
            state_size=self.state, conv_taps=self.taps,
            chunk_size=self.chunk, time_step_min=c["time_step_min"],
            time_step_max=c["time_step_max"],
            time_step_floor=c["time_step_floor"], n_head=self.n_head,
            n_kv_head=self.n_kv_head, head_dim=self.head_dim,
            expert_width=self.width, shared_width=self.shared_width,
            n_experts=self.n_experts, held=(self.held_first, self.n_held),
            top_k=self.top_k, norm_topk_prob=c["norm_topk_prob"],
            routed_scale=float(c["routed_scaling_factor"]),
            rms_eps=c["layer_norm_epsilon"],
            bias_update_speed=c["bias_update_speed"],
            rescale_depth=c["published"]["num_hidden_layers"],
            compute_dtype=jnp.dtype(c["compute_dtype"]), remat=c["remat"],
            loss_chunk_rows=c["loss_chunk_rows"])

    def optimizer(self):
        """AdamW over every leaf but the routing biases."""
        from benchmark.reference.nemotron_h import adamw
        from ray_tpu.models.nemotron_h import trained_by

        return trained_by(adamw(self.config["optimizer"]))

    def _init(self, key):
        from ray_tpu.models import nemotron_h

        return nemotron_h.init_params(key, self.model_config())

    def lower_step(self, params, opt_state, batch):
        """The jitted train step, traced and lowered for this state."""
        import jax

        from ray_tpu.models import nemotron_h
        from ray_tpu.parallel.context import use_mesh

        with use_mesh(self.mesh):
            kept = jax.tree.map(lambda x: x.sharding, (params, opt_state))
            step = jax.jit(
                nemotron_h.make_train_step(self.model_config(),
                                           self.optimizer()),
                donate_argnums=(0, 1), out_shardings=(*kept, None))
            return step.lower(params, opt_state, batch)

    # -- the plain reference on the same seed and batches ------------------

    def reference_sizes(self):
        from benchmark.reference.nemotron_h import Sizes

        c = self.config
        return Sizes(
            mamba_heads=self.mamba_heads,
            mamba_head_dim=self.mamba_head_dim, n_groups=self.n_groups,
            state_size=self.state, n_head=self.n_head,
            n_kv_head=self.n_kv_head, top_k=self.top_k,
            routed_scale=float(c["routed_scaling_factor"]),
            renorm_eps=c["renorm_eps"], held_first=self.held_first,
            rms_eps=c["layer_norm_epsilon"],
            bias_update_speed=c["bias_update_speed"],
            query_block=c["reference"]["query_block"],
            scan_block=c["reference"]["scan_block"])

    def reference_losses(self, seed: int, batches) -> list:
        """Cross-entropies of the first len(batches) steps by
        `benchmark/reference/nemotron_h.py`, from the parameters the
        system's own init draws from `seed` (the same held experts and rows
        of the vocabulary), on the first bound device.  All of it is freed
        on return."""
        import jax
        import numpy as np

        from benchmark.reference import nemotron_h as reference

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
    """The system's parameter tree (`ray_tpu.models.nemotron_h.init_params`)
    as `benchmark/reference/nemotron_h.py` reads it: (parameters, one dict
    a layer; the layers' routing biases, (experts,) for a mixture and None
    for the others)."""
    layers, biases = [], []
    i = 0
    while f"layer_{i}" in params:
        p = params[f"layer_{i}"]
        layer, bias = {"norm": p["norm"]["scale"]}, None
        if "mamba" in p:
            m = p["mamba"]
            layer.update({
                "w_in": m["in_proj"]["kernel"], "taps": m["conv"]["kernel"],
                "conv_bias": m["conv"]["bias"], "a_log": m["A_log"],
                "d": m["D"], "dt_bias": m["dt_bias"],
                "gate_norm": m["norm"]["scale"],
                "w_out": m["out_proj"]["kernel"]})
        elif "attn" in p:
            layer.update({k: p["attn"][f"{k[1]}_proj"]["kernel"]
                          for k in ("wq", "wk", "wv", "wo")})
        else:
            moe = p["moe"]
            router = dict(moe["router"])
            layer["router"] = router.pop("kernel")
            (bias,) = router.values()
            layer.update({"e_up": moe["wi_up"], "e_down": moe["wo"],
                          "s_up": moe["shared"]["up_proj"]["kernel"],
                          "s_down": moe["shared"]["down_proj"]["kernel"]})
        layers.append(layer)
        biases.append(bias)
        i += 1
    return ({"embed": params["embed_tokens"]["embedding"],
             "head": params["lm_head"]["kernel"],
             "norm_f": params["norm_f"]["scale"], "layers": layers}, biases)
