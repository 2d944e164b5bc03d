"""The `bailing_hybrid` family: how a Ling-3.0-flash configuration file (the
keys of the model's published `config.json`) becomes the system under test
(`ray_tpu.models.bailing_hybrid` under a `ShardingConfig`), the counts the
yardstick needs (operations per token; the latent-attention kernels' and the
delta rule's operations and bytes; which of a trace's kernels are
attention's), and the run of the plain reference it is judged against.

A configuration of this family is one chip's share of an expert-parallel
deployment: `num_experts` counts the experts HELD here (`experts_held.of` the
router's width), `num_attention_heads` the heads of BOTH mixers held here
(`published.num_attention_heads` the model's), `vocab_size` the slice of the
vocabulary the tokens are drawn from, `first_k_dense_replace` the leading
dense layers held (published 0 ..) and `first_layer` the published index of
the first routed layer held; a layer's mixer goes by its published index
(`ray_tpu.models.bailing_hybrid.BailingHybridConfig.kind`).

Nothing at module level imports jax: the parent process reads the counts
and must stay off the chip.
"""

from __future__ import annotations

from benchmark.families import deepseek_v3, gpt2, olmoe

KDA, MLA = "kda", "attn"


class Family:
    def __init__(self, config: dict):
        self.config = c = config
        self.n_layer = c["num_hidden_layers"]
        self.n_dense = c["first_k_dense_replace"]
        self.first_layer = c["first_layer"]
        self.layer_group = c["layer_group_size"]
        self.n_head = c["num_attention_heads"]
        self.n_embd = c["hidden_size"]
        self.head_dim = c["head_dim"]
        self.taps = c["short_conv_kernel_size"]
        self.chunk = c["kda_chunk"]
        self.latent = c["kv_lora_rank"]
        self.nope, self.rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
        self.qk_dim, self.v_dim = c["qk_head_dim"], c["v_head_dim"]
        self.dense_width = c["intermediate_size"]
        self.width = c["moe_intermediate_size"]        # of one routed expert
        self.shared_width = c["num_shared_experts"] \
            * c["moe_shared_expert_intermediate_size"]
        self.n_held = c["num_experts"]
        self.held_first = c["experts_held"]["first"]
        self.n_experts = c["experts_held"]["of"]       # the router's width
        self.top_k = c["num_experts_per_tok"]
        self.rows = c["vocab_size"]
        self.mesh = None

    def published(self, i: int) -> int:
        return i if i < self.n_dense else self.first_layer + i - self.n_dense

    def kind(self, i: int) -> str:
        """The mixer of the layer held at ``i`` (as the model's config)."""
        return MLA if (self.published(i) + 1) % self.layer_group == 0 else KDA

    @property
    def kinds(self) -> list:
        return [self.kind(i) for i in range(self.n_layer)]

    @property
    def n_routed_layers(self) -> int:
        return self.n_layer - self.n_dense

    # -- counts: pure functions of the shapes, no jax ----------------------

    def mixer_matrices(self, kind: str) -> int:
        """The parameters a token multiplies in a layer's mixer."""
        e, h, d = self.n_embd, self.n_head, self.head_dim
        if kind == KDA:         # W_q, W_k, W_v, W_f, W_g, W_o; W_b; the taps
            return 6 * e * h * d + e * h + 3 * h * d * self.taps
        return (e * h * self.qk_dim + e * (self.latent + self.rope)
                + self.latent * h * (self.nope + self.v_dim)
                + e * h + h * self.v_dim * e)

    def mixer_vectors(self, kind: str) -> int:
        """A mixer's leaves that are no matrix: A_log, dt_bias and the gain
        over a head; the latent's gain."""
        if kind == KDA:
            return self.n_head + self.n_head * self.head_dim + self.head_dim
        return self.latent

    def param_count(self) -> int:
        """Every leaf held here, norms and routing biases included."""
        e = self.n_embd
        routed = (e * self.n_experts + self.n_experts
                  + 3 * e * self.shared_width
                  + self.n_held * 3 * e * self.width)
        return (2 * self.rows * e + e + sum(
            2 * e + self.mixer_matrices(k) + self.mixer_vectors(k)
            for k in self.kinds)
            + self.n_dense * 3 * e * self.dense_width
            + self.n_routed_layers * routed)

    def expected_rows_per_token(self) -> float:
        """Rows a token sends to the experts held here under a balanced
        router: top_k x held / experts (8 x 8 / 512 = 0.125)."""
        return self.top_k * self.n_held / self.n_experts

    def rule_flops_per_token(self) -> float:
        """Forward operations a token of ONE KDA layer's rule as the chunked
        form at C = `kda_chunk` makes them, a multiply and an add two, K = V
        = D a head: the pair products A and P (2 x 2 C D a row, the whole
        square), the triangle's inverse as 10 products of (C, C) (2 C^2
        each a row), T on beta V and on beta K exp(G) (2 x 2 C D), W S_0 and
        Q S_0 (2 x 2 D^2), P U (2 C D) and the state's K' U (2 D^2).  Copied
        from `ray_tpu.models.bailing_hybrid.rule_flops_per_token`."""
        c, d = self.chunk, self.head_dim
        return self.n_head * (10 * c * d + 20 * c * c + 6 * d * d)

    def flops_per_token(self, seq: int) -> float:
        """6 N + MLA's full score squares + the rules: N what a token
        multiplies HERE (the head's rows held; each mixer's matrices; in a
        routed layer the router, the shared expert and the expected rows of
        held experts, three matrices each; in a dense layer its MLP); the
        squares 6 S heads (192 + 128) an MLA layer, the whole S x S as
        PaLM's formula counts it; the rules forward once and backward twice.
        Recomputation not counted.  Copied from
        `ray_tpu.models.bailing_hybrid.count_flops_per_token`."""
        e = self.n_embd
        kinds = self.kinds
        routed = (e * self.n_experts + 3 * e * self.shared_width
                  + self.expected_rows_per_token() * 3 * e * self.width)
        n = (self.rows * e + sum(self.mixer_matrices(k) for k in kinds)
             + self.n_dense * 3 * e * self.dense_width
             + self.n_routed_layers * routed)
        return (6 * n + 6 * kinds.count(MLA) * seq * self.n_head
                * (self.qk_dim + self.v_dim)
                + 3 * kinds.count(KDA) * self.rule_flops_per_token())

    _width_bytes = deepseek_v3.Family._width_bytes

    def attention_cost(self, batch: int, seq: int) -> dict:
        """`families/deepseek_v3.py:attention_cost` for the MLA layers held
        (one of seven) at the heads held: causal attention needs half of
        each S x S product; forward QK' (192 deep) and PV (128), backward dQ
        and dK (192) and dV and dP (128); bytes: five arrays 192 wide and six
        128 wide a head and position, the row statistics in f32 once each
        way.  What a kernel recomputes is not counted."""
        layers = self.kinds.count(MLA)
        heads = batch * seq * self.n_head
        square = 2 * batch * self.n_head * seq * seq
        flops = 3 * square * (self.qk_dim + self.v_dim) / 2
        elems = heads * (5 * self.qk_dim + 6 * self.v_dim)
        return {"flops": layers * flops,
                "bytes": layers * (elems * self._width_bytes()
                                   + 2 * heads * 4)}

    def kda_cost(self, batch: int, seq: int) -> dict:
        """What one training step's delta rules must do, whatever implements
        them; recomputation not counted, and blind to a kernel's own tiles: a
        formula in B, S, H, K = V = D and the chunk alone.  Operations:
        `rule_flops_per_token` forward, twice that backward.  Bytes a token,
        head and layer: the forward reads q, k, v (3 D in the compute type),
        g (D float32) and beta (one float32) and writes o (D); the backward
        reads those and do (D) and writes dq, dk, dv (3 D), dg (D float32)
        and d beta."""
        tokens = batch * seq
        b, d, h = self._width_bytes(), self.head_dim, self.n_head
        read = 3 * d * b + d * 4 + 4
        forward = read + d * b
        backward = read + d * b + read
        layers = self.kinds.count(KDA)
        return {"flops": layers * 3 * tokens * self.rule_flops_per_token(),
                "bytes": layers * tokens * h * (forward + backward)}

    _shapes = olmoe.Family._shapes
    _is_custom_call = staticmethod(olmoe.Family._is_custom_call)

    def is_attention_kernel(self, op_name: str) -> bool:
        """A Mosaic kernel whose first result is a head-major array of the
        MLA heads' activations, (B * H, S, 192) or (B * H, S, 128): neither
        the rule's (B, S, H 128) and (B, H, chunks, 128, 128), the
        convolution's (B, S, C) nor the row kernels' (rows, E)."""
        if not self._is_custom_call(op_name):
            return False
        shapes = self._shapes(op_name)
        return bool(shapes) and len(shapes[0]) == 3 \
            and shapes[0][0] % self.n_head == 0 \
            and shapes[0][2] in (self.qk_dim, self.v_dim)

    # -- the system under test: runs in the worker that holds the chips ----

    bind = gpt2.Family.bind
    init_state = gpt2.Family.init_state
    place_batch = gpt2.Family.place_batch

    def model_config(self):
        import jax.numpy as jnp

        from ray_tpu.models.bailing_hybrid import BailingHybridConfig

        c = self.config
        return BailingHybridConfig(
            vocab_size=self.rows, n_layer=self.n_layer,
            n_dense_layer=self.n_dense, first_layer=self.first_layer,
            layer_group=self.layer_group, n_embd=self.n_embd,
            n_head=self.n_head,
            n_head_published=c["published"]["num_attention_heads"],
            head_dim=self.head_dim, conv_taps=self.taps,
            gate_bound=float(c["kda_lower_bound"]), kda_chunk=self.chunk,
            kv_lora_rank=self.latent, qk_nope_dim=self.nope,
            qk_rope_dim=self.rope, v_head_dim=self.v_dim,
            dense_width=self.dense_width, expert_width=self.width,
            shared_width=self.shared_width, n_experts=self.n_experts,
            held=(self.held_first, self.n_held), top_k=self.top_k,
            n_group=c["n_group"], topk_group=c["topk_group"],
            routed_scale=c["routed_scaling_factor"],
            rope_theta=float(c["rope_theta"]), rms_eps=c["rms_norm_eps"],
            bias_update_speed=c["bias_update_speed"],
            compute_dtype=jnp.dtype(c["compute_dtype"]), remat=c["remat"],
            loss_chunk_rows=c["loss_chunk_rows"])

    def optimizer(self):
        """AdamW over every leaf but the routing biases."""
        from benchmark.reference.bailing_hybrid import adamw
        from ray_tpu.models.bailing_hybrid import trained_by

        return trained_by(adamw(self.config["optimizer"]))

    def _init(self, key):
        from ray_tpu.models import bailing_hybrid

        return bailing_hybrid.init_params(key, self.model_config())

    def lower_step(self, params, opt_state, batch):
        """The jitted train step, traced and lowered for this state."""
        import jax

        from ray_tpu.models import bailing_hybrid
        from ray_tpu.parallel.context import use_mesh

        with use_mesh(self.mesh):
            kept = jax.tree.map(lambda x: x.sharding, (params, opt_state))
            step = jax.jit(
                bailing_hybrid.make_train_step(self.model_config(),
                                               self.optimizer()),
                donate_argnums=(0, 1), out_shardings=(*kept, None))
            return step.lower(params, opt_state, batch)

    # -- the plain reference on the same seed and batches ------------------

    def reference_sizes(self):
        from benchmark.reference.bailing_hybrid import Sizes

        c = self.config
        return Sizes(
            kinds=tuple(self.kinds), n_head=self.n_head,
            head_dim=self.head_dim, kv_lora_rank=self.latent,
            qk_nope_dim=self.nope, qk_rope_dim=self.rope,
            v_head_dim=self.v_dim, top_k=self.top_k, n_group=c["n_group"],
            topk_group=c["topk_group"],
            routed_scale=c["routed_scaling_factor"],
            held_first=self.held_first,
            gate_bound=float(c["kda_lower_bound"]),
            rope_theta=float(c["rope_theta"]), rms_eps=c["rms_norm_eps"],
            bias_update_speed=c["bias_update_speed"],
            query_block=c["reference"]["query_block"],
            scan_block=c["reference"]["scan_block"],
            row_block=c["reference"]["row_block"])

    def reference_losses(self, seed: int, batches) -> list:
        """What the system's `out["loss"]` is held to, the first
        len(batches) steps: the cross-entropy of
        `benchmark/reference/bailing_hybrid.py` from the parameters the
        system's own init draws from `seed` (the same heads, experts and rows
        of the vocabulary), on the first bound device.

        Before the steps, the system's own walk
        (`ray_tpu.models.bailing_hybrid.hidden`: `layers.trunk`, the kernels,
        the matrices cast once, `remat` as configured) is held to the
        reference's on the first batch's first sequence (`first_streams`),
        because three losses from random weights on uniform random tokens see
        little of a state carried wrongly from chunk to chunk: the stream
        after each of the layers held may lie `reference.state_error_max` of
        the norm of the reference's from it at most.  `harness/verdict.py`
        compares losses and nothing else, so a breach is handed to it as
        reference losses that are not numbers, which no loss is within the
        tolerance of; the line printed here says which limit was passed.  All
        of it is freed on return."""
        import jax
        import numpy as np

        from benchmark.reference import bailing_hybrid as reference

        device = self.devices[0]
        batches = jax.device_put(np.stack(batches), device)
        with jax.default_matmul_precision("highest"):
            # the parameters are born on the device in the reference's
            # layout, so no second copy of them waits beside it
            params, biases = jax.jit(
                lambda key: to_reference(self._init(key)))(
                    jax.device_put(jax.random.PRNGKey(seed), device))
        errors = self.first_streams(params, biases, batches[0, 0, :-1])
        with jax.default_matmul_precision("highest"):
            losses = reference.first_losses(
                params, biases, batches, self.reference_sizes(),
                self.config["optimizer"])
        limit = self.config["reference"]["state_error_max"]
        told = ", ".join(f"{self.published(i)} ({kind}) {error:.5f}"
                         for i, (kind, error) in
                         enumerate(zip(self.kinds, errors)))
        print(f"bailing_hybrid reference: losses {losses}; sequence 0: the "
              f"system's stream after each published layer, of the norm of "
              f"the reference's from it: {told} (at most {limit})",
              flush=True)
        if not max(errors) <= limit:
            print("NOT CORRECT: bailing_hybrid: the system's streams are not "
                  "the reference's (the line above): the reference's losses "
                  "are withheld", flush=True)
            return [float("nan")] * len(losses)
        return losses

    def reference_streams(self, params, biases, inputs):
        """The reference's stream after each layer held on one sequence,
        (layers, seq, E) float32."""
        import jax
        import jax.numpy as jnp

        from benchmark.reference import bailing_hybrid as reference

        sizes = self.reference_sizes()
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda p, b, t: jnp.stack(
                reference.streams(p, b, t, sizes)[0]))(params, biases, inputs)

    def first_streams(self, params, biases, inputs, want=None) -> list:
        """The system's walk against the reference's on one sequence ->
        [|system - reference| / |reference| of the stream (seq, E) after
        each layer held, Frobenius norms].  ``params`` and ``biases`` in the
        reference's layout; ``inputs`` (seq,) int32; ``want``:
        `reference_streams` of them, where a caller has it already.  The
        system's side is traced as its step is (no matmul precision asked
        for, the matrices cast to the compute type once, `remat` as
        configured)."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import bailing_hybrid, layers
        from ray_tpu.util import tracing

        cfg = self.model_config()

        def system(params, biases, inputs):
            _, streams = bailing_hybrid.hidden(
                layers.cast_weights(from_reference(params, biases),
                                    cfg.compute_dtype), inputs[None], cfg,
                streams=True)
            return jnp.stack([s[0].astype(jnp.float32) for s in streams])

        norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x), axis=(1, 2)))
        # the step's counters are of the step: these traces add nothing
        with tracing.outside_job():
            if want is None:
                want = self.reference_streams(params, biases, inputs)
            got = jax.jit(system)(params, biases, inputs)
            errors = norm(got - want) / norm(want)
        return [float(e) for e in errors]


_FFN = (("gate_proj", "gate"), ("up_proj", "up"), ("down_proj", "down"))
_MLA = (("q_proj", "wq"), ("kv_a_proj", "wkv_a"), ("kv_b_proj", "wkv_b"),
        ("g_proj", "wgate"), ("o_proj", "wo"))
_KDA = (("f_proj", "wf"), ("b_proj", "wb"), ("g_proj", "wg"),
        ("o_proj", "wo"))
_BIAS = "e_score_correction_bias"


def to_reference(params):
    """The system's parameter tree
    (`ray_tpu.models.bailing_hybrid.init_params`) as
    `benchmark/reference/bailing_hybrid.py` reads it: (parameters; the
    routing biases, one (N,) a routed layer)."""
    layers, biases = [], []
    i = 0
    while f"layer_{i}" in params:
        p = params[f"layer_{i}"]
        layer = {"norm1": p["input_norm"]["scale"],
                 "norm2": p["post_norm"]["scale"]}
        if KDA in p:
            m = p[KDA]
            width = m["qkv_proj"]["kernel"].shape[1] // 3
            for j, name in enumerate("qkv"):
                part = slice(j * width, (j + 1) * width)
                layer[f"w{name}"] = m["qkv_proj"]["kernel"][:, part]
                layer[f"taps_{name}"] = m["conv"]["kernel"][part]
            layer.update({ours: m[theirs]["kernel"] for theirs, ours in _KDA})
            layer.update(a_log=m["A_log"], dt_bias=m["dt_bias"],
                         gain=m["head_norm"]["scale"])
        else:
            m = p[MLA]
            layer.update({ours: m[theirs]["kernel"] for theirs, ours in _MLA})
            layer["kv_norm"] = m["kv_a_norm"]["scale"]
        if "mlp" in p:
            layer.update({ours: p["mlp"][theirs]["kernel"]
                          for theirs, ours in _FFN})
        else:
            moe = p["moe"]
            layer["router"] = moe["router"]["kernel"]
            biases.append(moe["router"][_BIAS])
            layer.update({"e_gate": moe["wi_gate"], "e_up": moe["wi_up"],
                          "e_down": moe["wo"]})
            layer.update({f"s_{ours}": moe["shared"][theirs]["kernel"]
                          for theirs, ours in _FFN})
        layers.append(layer)
        i += 1
    return ({"embed": params["embed_tokens"]["embedding"],
             "head": params["lm_head"]["kernel"],
             "norm_f": params["norm_f"]["scale"], "layers": layers}, biases)


def from_reference(params, biases):
    """`to_reference` back: the reference's layout as the system's tree."""
    import jax.numpy as jnp

    tree = {"embed_tokens": {"embedding": params["embed"]},
            "lm_head": {"kernel": params["head"]},
            "norm_f": {"scale": params["norm_f"]}}
    biases = list(biases)
    for i, p in enumerate(params["layers"]):
        layer = {"input_norm": {"scale": p["norm1"]},
                 "post_norm": {"scale": p["norm2"]}}
        if "taps_q" in p:
            m = {theirs: {"kernel": p[ours]} for theirs, ours in _KDA}
            m["qkv_proj"] = {"kernel": jnp.concatenate(
                [p["wq"], p["wk"], p["wv"]], axis=1)}
            m["conv"] = {"kernel": jnp.concatenate(
                [p["taps_q"], p["taps_k"], p["taps_v"]], axis=0)}
            m.update(A_log=p["a_log"], dt_bias=p["dt_bias"],
                     head_norm={"scale": p["gain"]})
            layer[KDA] = m
        else:
            m = {theirs: {"kernel": p[ours]} for theirs, ours in _MLA}
            m["kv_a_norm"] = {"scale": p["kv_norm"]}
            layer[MLA] = m
        if "router" in p:
            layer["moe"] = {
                "router": {"kernel": p["router"], _BIAS: biases.pop(0)},
                "wi_gate": p["e_gate"], "wi_up": p["e_up"], "wo": p["e_down"],
                "shared": {theirs: {"kernel": p[f"s_{ours}"]}
                           for theirs, ours in _FFN}}
        else:
            layer["mlp"] = {theirs: {"kernel": p[ours]}
                            for theirs, ours in _FFN}
        tree[f"layer_{i}"] = layer
    return tree
