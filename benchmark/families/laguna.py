"""The `laguna` family: how a Laguna configuration file (the keys of the
model's published `config.json`, `model_type` `laguna`) becomes the system
under test (`ray_tpu.models.laguna` under a `ShardingConfig`), the counts
the yardstick needs (operations per token; the attention kernels'
operations and bytes; the pairs each kind of layer attends and the heads
it attends them with; which of a trace's operations are the kernels), and
the run of the plain reference it is judged against.

A configuration of this family is one chip's share of an expert-parallel
deployment: `num_experts` counts the experts HELD here, `experts_held.of`
the router's width, `vocab_size` the slice of the vocabulary the tokens are
drawn from and the rows of embedding and head held (a multiple of 128: no
padding).

Its layers are of two kinds (`layer_types`) that differ in MORE than their
rule: a `full_attention` layer attends every earlier key with 48 query
heads, half of each head turned by YaRN's table; a `sliding_attention`
layer the `sliding_window` latest keys with 64, the whole head turned by a
base of its own (`num_attention_heads_per_layer`, `rope_parameters`).  So
every count here goes layer by layer: a layer's pairs times ITS heads.  The
counts are of the work the MODEL asks for, whatever implements it:
attention over the pairs each layer's own kind attends, never the tiles a
kernel happens to visit.  So `mfu` and `attn_roofline_share` read the same
work whatever tiles `_auto_tiles` takes under the window.

Nothing at module level imports jax: the parent process reads the counts
and must stay off the chip.
"""

from __future__ import annotations

from benchmark.families import deepseek_v3, lfm2_moe

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"


class Family:
    def __init__(self, config: dict):
        self.config = c = config
        self.layer_types = tuple(c["layer_types"])
        self.ffn_types = tuple(c["mlp_layer_types"])
        self.heads = tuple(c["num_attention_heads_per_layer"])
        self.n_layer = c["num_hidden_layers"]
        assert len(self.layer_types) == len(self.ffn_types) \
            == len(self.heads) == self.n_layer, "a kind, a feed-forward " \
            "and a head count a layer"
        assert set(self.layer_types) <= {FULL, SLIDING}, self.layer_types
        assert set(self.ffn_types) <= {DENSE, SPARSE}, self.ffn_types
        # a kind has one head count: the model's table of parameters goes
        # by the kind
        self.heads_by_kind = dict(zip(self.layer_types, self.heads))
        assert all(self.heads_by_kind[kind] == h for kind, h in
                   zip(self.layer_types, self.heads)), self.heads
        assert c["gating"] is True
        self.window = c["sliding_window"]
        self.n_kv_head = c["num_key_value_heads"]
        self.head_dim = c["head_dim"]
        self.n_embd = c["hidden_size"]
        self.dense_width = c["intermediate_size"]
        self.width = c["moe_intermediate_size"]        # of one routed expert
        self.shared_width = c["shared_expert_intermediate_size"]
        self.n_held = c["num_experts"]
        self.held_first = c["experts_held"]["first"]
        self.n_experts = c["experts_held"]["of"]       # the router's width
        self.top_k = c["num_experts_per_tok"]
        self.rows = c["vocab_size"]
        self.mesh = None
        # a program from before this family's model cannot run its cells:
        # said as soon as the worker that holds the chip builds its family,
        # before a batch, a reference or a state exists (the module is
        # looked for, not imported: no jax here)
        import importlib.util
        if importlib.util.find_spec("ray_tpu.models.laguna") is None:
            raise ImportError("this program has no ray_tpu/models/laguna.py: "
                              "it cannot run a cell of the laguna family")

    @property
    def n_routed_layers(self) -> int:
        return self.ffn_types.count(SPARSE)

    # -- counts: pure functions of the shapes, no jax ----------------------

    def attention_params(self, heads: int) -> int:
        """A layer's at ``heads`` query heads: W_q and W_o, W_k and W_v at
        H_kv, the gate's W_g (E, heads)."""
        e, d = self.n_embd, self.head_dim
        return 2 * e * d * (heads + self.n_kv_head) + e * heads

    def routed_params(self) -> int:
        """A sparse feed-forward's leaves held here: the router with its
        bias, the shared expert, the held experts."""
        e = self.n_embd
        return (e * self.n_experts + self.n_experts
                + 3 * e * self.shared_width
                + self.n_held * 3 * e * self.width)

    def param_count(self) -> int:
        """Every leaf held here: embedding and head, the final norm; a
        layer's two norms, its attention at its own heads, its dense MLP
        or its mixture."""
        e = self.n_embd
        layers = sum(
            2 * e + self.attention_params(h)
            + (3 * e * self.dense_width if ffn == DENSE
               else self.routed_params())
            for h, ffn in zip(self.heads, self.ffn_types))
        return 2 * self.rows * e + e + layers

    def attended_pairs_by_kind(self, seq: int) -> dict:
        """{kind: (query, key) pairs a sequence attends, a head, in ONE
        layer of that kind}: the triangle seq (seq + 1) / 2; under a window
        the triangle of its first W rows and W a row after."""
        w = min(self.window, seq)
        return {FULL: seq * (seq + 1) // 2,
                SLIDING: w * (w + 1) // 2 + (seq - w) * w}

    def attended_head_pairs_a_pass(self, seq: int, kinds=(FULL, SLIDING)):
        """The attended (pair, head) products of one pass over the stack,
        a sequence: each layer's pairs by its own kind times its own query
        heads; ``kinds``: of the layers of these kinds alone."""
        pairs = self.attended_pairs_by_kind(seq)
        return sum(pairs[kind] * h for kind, h in
                   zip(self.layer_types, self.heads) if kind in kinds)

    def multiplying_params_per_token(self) -> float:
        """The parameters a token multiplies HERE: a layer's attention
        matrices at its heads (the gate's among them); a dense layer's
        MLP; in a sparse layer the router, the shared expert and the
        expected rows of held experts (three matrices each); the head's
        rows held."""
        e = self.n_embd
        routed = (e * self.n_experts + 3 * e * self.shared_width
                  + self.expected_rows_per_token() * 3 * e * self.width)
        return self.rows * e + sum(
            self.attention_params(h)
            + (3 * e * self.dense_width if ffn == DENSE else routed)
            for h, ffn in zip(self.heads, self.ffn_types))

    def flops_per_token(self, seq: int) -> float:
        """6 N + the attention products over the pairs each layer's own
        kind attends at its own heads: QK' and PV forward once and backward
        twice, 2 D operations a pair and head each.  Recomputation not
        counted.  Copied from
        `ray_tpu.models.laguna.count_flops_per_token`."""
        return 6 * self.multiplying_params_per_token() \
            + 6 * self.attended_head_pairs_a_pass(seq) / seq \
            * 2 * self.head_dim

    def attention_cost(self, batch: int, seq: int) -> dict:
        """What attention must do over the ATTENDED pairs, each layer by
        its kind and heads, whatever tiles a kernel visits: six products D
        deep, 2 D operations a pair and head each.  Bytes a layer: q, o,
        do and dq have H_l heads (six arrays read or written), k, v, dk and
        dv H_kv (six); the row statistics (B, H_l, seq) in f32 once each
        way.  The rule is no operand: no mask bytes.  The gate is outside
        the kernels and not counted."""
        d = self.head_dim
        product = 2 * batch * self.attended_head_pairs_a_pass(seq) * d
        elems = sum(6 * batch * seq * d * (h + self.n_kv_head)
                    for h in self.heads)
        stats = sum(batch * h * seq * 4 for h in self.heads)
        return {"flops": 6 * product,
                "bytes": elems * self._width_bytes() + 2 * stats}

    # the grouped matmuls over the rows the held experts are EXPECTED to be
    # sent (0.5 T a sparse layer) and which custom calls they are: the
    # `deepseek_v3` family's, word for word (they read the widths, the
    # experts held, `n_routed_layers` and `expected_rows_per_token`); the
    # attention kernels (head-major arrays of whole groups of heads of
    # `head_dim`: 48, 64 and 8 are all multiples of H_kv) and the mixture's
    # other operations by the buffer's rows: the `lfm2_moe` family's.  The
    # kernels' forms, `fwd_rows_window` and `bwd_fused_window` beside
    # `fwd_rows` and `bwd_fused`, are in their `tf_op`
    # (`harness/scope_trace.py`)
    expected_rows_per_token = deepseek_v3.Family.expected_rows_per_token
    moe_cost = deepseek_v3.Family.moe_cost
    is_moe_matmul = deepseek_v3.Family.is_moe_matmul
    buffered_rows = lfm2_moe.Family.buffered_rows
    is_moe_op = lfm2_moe.Family.is_moe_op
    is_attention_kernel = lfm2_moe.Family.is_attention_kernel
    _width_bytes = deepseek_v3.Family._width_bytes
    _shapes = deepseek_v3.Family._shapes
    _is_custom_call = staticmethod(deepseek_v3.Family._is_custom_call)

    # -- the system under test: runs in the worker that holds the chips ----

    bind = lfm2_moe.Family.bind
    place_batch = lfm2_moe.Family.place_batch
    init_state = lfm2_moe.Family.init_state

    def _rope(self, kind: str) -> dict:
        section = self.config["rope_parameters"][kind]
        assert section["rope_type"] == (
            "yarn" if kind == FULL else "default"), section
        return section

    def _rotary_dim(self, kind: str) -> int:
        dims = self.head_dim * self._rope(kind)["partial_rotary_factor"]
        assert dims == int(dims) and int(dims) % 2 == 0, dims
        return int(dims)

    def model_config(self):
        import jax.numpy as jnp

        from ray_tpu.models.laguna import LagunaConfig, Yarn

        c = self.config
        yarn = self._rope(FULL)
        assert c["moe_apply_router_weight_on_input"] is False
        return LagunaConfig(
            vocab_size=self.rows, layer_types=self.layer_types,
            mlp_layer_types=self.ffn_types,
            n_head_full=self.heads_by_kind.get(FULL, 0),
            n_head_sliding=self.heads_by_kind.get(SLIDING, 0),
            n_kv_head=self.n_kv_head, head_dim=self.head_dim,
            n_embd=self.n_embd, sliding_window=self.window,
            dense_width=self.dense_width, expert_width=self.width,
            shared_width=self.shared_width, n_experts=self.n_experts,
            held=(self.held_first, self.n_held), top_k=self.top_k,
            routed_scale=c["moe_routed_scaling_factor"],
            theta_full=float(yarn["rope_theta"]),
            theta_sliding=float(self._rope(SLIDING)["rope_theta"]),
            rotary_full=self._rotary_dim(FULL),
            rotary_sliding=self._rotary_dim(SLIDING),
            yarn=Yarn(float(yarn["factor"]),
                      yarn["original_max_position_embeddings"],
                      float(yarn["beta_fast"]), float(yarn["beta_slow"]),
                      yarn.get("attention_factor")),
            rms_eps=c["rms_norm_eps"],
            bias_update_speed=c["bias_update_speed"],
            compute_dtype=jnp.dtype(c["compute_dtype"]), remat=c["remat"],
            loss_chunk_rows=c["loss_chunk_rows"])

    def optimizer(self):
        """AdamW over every leaf but the routing biases."""
        from benchmark.reference.laguna import adamw
        from ray_tpu.models.laguna import trained_by

        return trained_by(adamw(self.config["optimizer"]))

    def _init(self, key):
        from ray_tpu.models import laguna

        return laguna.init_params(key, self.model_config())

    def lower_step(self, params, opt_state, batch):
        """The jitted train step, traced and lowered for this state."""
        import jax

        from ray_tpu.models import laguna
        from ray_tpu.parallel.context import use_mesh

        with use_mesh(self.mesh):
            kept = jax.tree.map(lambda x: x.sharding, (params, opt_state))
            step = jax.jit(
                laguna.make_train_step(self.model_config(), self.optimizer()),
                donate_argnums=(0, 1), out_shardings=(*kept, None))
            return step.lower(params, opt_state, batch)

    # -- the plain reference on the same seed and batches ------------------

    def reference_sizes(self):
        from benchmark.reference import laguna as reference

        c = self.config
        yarn = self._rope(FULL)
        return reference.Sizes(
            n_kv_head=self.n_kv_head, head_dim=self.head_dim,
            top_k=self.top_k,
            groups=tuple(reference.FULL if kind == FULL else reference.SLIDING
                         for kind, _, _ in self.groups()),
            window=self.window, routed_scale=c["moe_routed_scaling_factor"],
            held_first=self.held_first,
            theta_full=float(yarn["rope_theta"]),
            theta_sliding=float(self._rope(SLIDING)["rope_theta"]),
            rotary_full=self._rotary_dim(FULL),
            rotary_sliding=self._rotary_dim(SLIDING),
            yarn_factor=float(yarn["factor"]),
            yarn_original=yarn["original_max_position_embeddings"],
            yarn_beta_fast=float(yarn["beta_fast"]),
            yarn_beta_slow=float(yarn["beta_slow"]),
            yarn_attention_factor=yarn.get("attention_factor"),
            rms_eps=c["rms_norm_eps"],
            bias_update_speed=c["bias_update_speed"],
            query_block=c["reference"]["query_block"])

    def groups(self) -> list:
        """[(kind, feed-forward, the layers)] of the runs of neighbouring
        layers with the same leaves, in order: what the reference scans
        (`to_reference` stacks them so)."""
        return groups_of(self.layer_types, self.ffn_types)

    def reference_losses(self, seed: int, batches) -> list:
        """What the system's `out["loss"]` is held to, the first
        len(batches) steps: the cross-entropy of
        `benchmark/reference/laguna.py` from the parameters the system's own
        init draws from `seed` (the same held experts and rows of the
        vocabulary), on the first bound device.

        Before the steps, the system's own attention of the first layer of
        EACH kind (`ray_tpu.models.laguna._attention`: the kernels under
        each rule at each kind's heads, each rotary table over its part of
        a head, the gate, W_o) is held to the reference's float32 masked
        softmax on the first batch's first sequence (`first_layer`),
        because three losses from random weights see little of which keys
        a row attends or of how a head is turned: each operator's result
        may lie `reference.attention_error_max` of the norm of the
        reference's from it at most.  `harness/verdict.py` compares losses
        and nothing else, so a breach is handed to it as reference losses
        that are not numbers, which no loss is within the tolerance of; the
        line printed here says which limit was passed.  All of it is freed
        on return."""
        import jax
        import numpy as np

        from benchmark.reference import laguna as reference

        device = self.devices[0]
        batches = jax.device_put(np.stack(batches), device)
        with jax.default_matmul_precision("highest"):
            # the parameters are born on the device in the reference's
            # layout, so no second copy of them waits beside it
            params, biases = jax.jit(
                lambda key: to_reference(self._init(key)))(
                    jax.device_put(jax.random.PRNGKey(seed), device))
        errors = self.first_layer(params, batches[0, :1, :-1])
        with jax.default_matmul_precision("highest"):
            steps = reference.first_losses(
                params, biases, batches, self.reference_sizes(),
                self.config["optimizer"])
        limit = self.config["reference"]["attention_error_max"]
        told = ", ".join(f"layer {i} ({kind}) {error:.5f}"
                         for kind, (i, error) in errors.items())
        print(f"laguna reference: L {steps}; sequence 0: the gated "
              f"attention's result under each kind's rule, of the "
              f"reference's norm from it: {told} (at most {limit})",
              flush=True)
        if not all(error <= limit for _, error in errors.values()):
            print("NOT CORRECT: laguna: a layer's attention is not the "
                  "reference's (the line above): the reference's losses are "
                  "withheld", flush=True)
            return [float("nan")] * len(steps)
        return steps

    def first_layer(self, params, tokens) -> dict:
        """The system's attention against the reference's in the first
        layer of each kind, on one sequence -> {kind: (the layer,
        |system - reference| / |reference| of the operator's result
        (seq, E), Frobenius norms)}: the kernels under the kind's rule at
        the kind's heads, the kind's rotary table over its part of a head,
        the gate and W_o, traced as the step traces them, against a float32
        softmax under the rule written out.  Every layer is given the
        embedded tokens (normed by its own gain): what is judged is the
        operator, and a deeper layer's input would carry the layers before
        it.  ``params`` in the reference's layout; ``tokens`` (1, seq)
        int32."""
        import jax
        import jax.numpy as jnp

        from benchmark.reference import laguna as reference
        from ray_tpu.models import laguna
        from ray_tpu.util import tracing

        sizes, cfg = self.reference_sizes(), self.model_config()
        where = {}                 # layer -> (its group, its place in it)
        for g, (_, _, members) in enumerate(self.groups()):
            where.update({i: (g, at) for at, i in enumerate(members)})

        def of(params, tokens, i):
            """-> (layer i's leaves, its normed input (seq, E))."""
            g, at = where[i]
            p = jax.tree.map(lambda leaf: leaf[at], params["groups"][g])
            return p, reference.rms_norm(params["embed"][tokens[0]],
                                         p["norm1"], sizes.rms_eps)

        def system(params, tokens, i, kind):
            """The system's side, traced as its step is (no matmul
            precision asked for) -> (seq, E) float32."""
            p, u = of(params, tokens, i)
            attn = {"q_proj": {"kernel": p["wq"]},
                    "k_proj": {"kernel": p["wk"]},
                    "v_proj": {"kernel": p["wv"]},
                    "g_proj": {"kernel": p["wg"]},
                    "o_proj": {"kernel": p["wo"]}}
            out = laguna._attention(u[None].astype(cfg.compute_dtype), attn,
                                    cfg, kind)
            return out[0].astype(jnp.float32)

        def compare(params, tokens, got, i):
            p, u = of(params, tokens, i)
            want = reference.attention(u, p, sizes.groups[where[i][0]],
                                       sizes)
            return jnp.linalg.norm(got - want) / jnp.linalg.norm(want)

        errors = {}
        # the step's counters are of the step: these traces add nothing
        with tracing.outside_job():
            for kind in dict.fromkeys(self.layer_types):
                i = self.layer_types.index(kind)
                got = jax.jit(system, static_argnums=(2, 3))(
                    params, tokens, i, kind)
                with jax.default_matmul_precision("highest"):
                    errors[kind] = (i, float(jax.jit(
                        compare, static_argnums=3)(params, tokens, got, i)))
        return errors


def groups_of(layer_types, ffn_types) -> list:
    """[(kind, feed-forward, [the layers])]: the runs of neighbouring
    layers of one kind and one feed-forward."""
    out = []
    for i, key in enumerate(zip(layer_types, ffn_types)):
        if out and out[-1][:2] == key:
            out[-1][2].append(i)
        else:
            out.append((*key, [i]))
    return out


def to_reference(params):
    """The system's parameter tree (`ray_tpu.models.laguna.init_params`) as
    `benchmark/reference/laguna.py` reads it -> (parameters, the layers in
    groups of neighbours with the same leaves, each group's leaves stacked;
    the routing biases, a group's (layers, N), None for a dense group)."""
    import jax
    import jax.numpy as jnp

    layers, biases, kinds, ffns = [], [], [], []
    i = 0
    while f"layer_{i}" in params:
        p = params[f"layer_{i}"]
        kind = SLIDING if SLIDING in p else FULL
        attn = p[kind]
        layer = {
            "norm1": p["input_norm"]["scale"],
            "norm2": p["post_norm"]["scale"],
            "wq": attn["q_proj"]["kernel"], "wk": attn["k_proj"]["kernel"],
            "wv": attn["v_proj"]["kernel"], "wg": attn["g_proj"]["kernel"],
            "wo": attn["o_proj"]["kernel"]}
        if "mlp" in p:
            layer.update({k: p["mlp"][f"{k}_proj"]["kernel"]
                          for k in ("gate", "up", "down")})
            biases.append(None)
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
        layers.append(layer)
        kinds.append(kind)
        ffns.append(DENSE if "mlp" in p else SPARSE)
        i += 1
    stack = lambda leaves: jax.tree.map(lambda *xs: jnp.stack(xs), *leaves)
    groups = groups_of(kinds, ffns)
    return ({"embed": params["embed_tokens"]["embedding"],
             "norm_f": params["norm_f"]["scale"],
             "head": params["lm_head"]["kernel"],
             "groups": [stack([layers[i] for i in members])
                        for _, _, members in groups]},
            [None if ffn == DENSE else jnp.stack([biases[i] for i in members])
             for _, ffn, members in groups])
