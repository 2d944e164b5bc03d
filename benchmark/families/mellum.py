"""The `mellum` family: how a Mellum 2 configuration file (the keys of the
model's published `config.json`, `model_type` `mellum`) becomes the system
under test (`ray_tpu.models.mellum` under a `ShardingConfig`), the counts
the yardstick needs (operations per token; the attention kernels'
operations and bytes; the pairs each kind of layer attends; which of a
trace's operations are the kernels), and the run of the plain reference it
is judged against.

A configuration of this family is one chip's share of an expert-parallel
deployment: `num_experts` counts the experts HELD here, `experts_held.of`
the router's width, `vocab_size` the slice of the vocabulary the tokens are
drawn from and the rows of embedding and head held (a multiple of 128: no
padding).

Its layers are of two kinds (`layer_types`): a `sliding_attention` layer
attends the `sliding_window` latest keys, a `full_attention` layer every
earlier key.  The counts are of the work the MODEL asks for, whatever
implements it: attention over the pairs each layer's own kind attends,
never the tiles a kernel happens to visit.  So `mfu` and
`attn_roofline_share` read the same work before and after a kernel learns
to skip, and rise when it does; a kernel that knew the diagonal alone
would read a third of what one that knows the window reads.

Nothing at module level imports jax: the parent process reads the counts
and must stay off the chip.
"""

from __future__ import annotations

from benchmark.families import lfm2_moe

SLIDING, FULL = "sliding_attention", "full_attention"


class Family:
    def __init__(self, config: dict):
        self.config = c = config
        self.layer_types = tuple(c["layer_types"])
        self.n_layer = c["num_hidden_layers"]
        assert len(self.layer_types) == self.n_layer \
            and set(self.layer_types) <= {SLIDING, FULL}, self.layer_types
        assert set(c["mlp_layer_types"]) == {"sparse"} \
            and len(c["mlp_layer_types"]) == self.n_layer, \
            "every layer's feed-forward is the mixture"
        self.window = c["sliding_window"]
        self.n_head = c["num_attention_heads"]
        self.n_kv_head = c["num_key_value_heads"]
        self.head_dim = c["head_dim"]
        self.n_embd = c["hidden_size"]
        self.width = c["moe_intermediate_size"]        # of one routed expert
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
        if importlib.util.find_spec("ray_tpu.models.mellum") is None:
            raise ImportError("this program has no ray_tpu/models/mellum.py: "
                              "it cannot run a cell of the mellum family")

    n_routed_layers = property(lambda self: self.n_layer)

    # -- counts: pure functions of the shapes, no jax ----------------------

    def attention_params(self) -> int:
        """W_q and W_o at H heads, W_k and W_v at H_kv."""
        return 2 * self.n_embd * self.head_dim * (self.n_head
                                                  + self.n_kv_head)

    def param_count(self) -> int:
        """Every leaf held here: embedding and head, the final norm; a
        layer's two norms, attention with its two head norms, the router
        and the held experts."""
        e = self.n_embd
        layer = (2 * e + self.attention_params() + 2 * self.head_dim
                 + e * self.n_experts + self.n_held * 3 * e * self.width)
        return 2 * self.rows * e + e + self.n_layer * layer

    def attended_pairs_by_kind(self, seq: int) -> dict:
        """{kind: (query, key) pairs a sequence attends, a head, in ONE
        layer of that kind}: the triangle seq (seq + 1) / 2; under a window
        the triangle of its first W rows and W a row after."""
        w = min(self.window, seq)
        return {FULL: seq * (seq + 1) // 2,
                SLIDING: w * (w + 1) // 2 + (seq - w) * w}

    def attended_pairs_a_pass(self, seq: int) -> int:
        """The attended pairs of one pass over the stack, a head: each
        layer's by its own kind."""
        pairs = self.attended_pairs_by_kind(seq)
        return sum(pairs[kind] for kind in self.layer_types)

    def multiplying_params_per_token(self) -> float:
        """The parameters a token multiplies HERE: a layer's four attention
        matrices, the router and the expected rows of held experts (three
        matrices each); the head's rows held."""
        e = self.n_embd
        routed = (e * self.n_experts
                  + self.expected_rows_per_token() * 3 * e * self.width)
        return self.n_layer * (self.attention_params() + routed) \
            + self.rows * e

    def flops_per_token(self, seq: int) -> float:
        """6 N + the attention products over the pairs each layer's own
        kind attends: QK' and PV forward once and backward twice, 2 D
        operations a pair and head each.  Recomputation not counted.
        Copied from `ray_tpu.models.mellum.count_flops_per_token`."""
        return 6 * self.multiplying_params_per_token() \
            + 6 * self.attended_pairs_a_pass(seq) / seq \
            * self.n_head * 2 * self.head_dim

    def attention_cost(self, batch: int, seq: int) -> dict:
        """What attention must do over the ATTENDED pairs by kind, whatever
        tiles a kernel visits: six products of H heads D deep, 2 D
        operations a pair and head each.  Bytes a layer: q, o, do and dq
        have H heads (six arrays read or written), k, v, dk and dv H_kv
        (six); the row statistics (B, H, seq) in f32 once each way.  The
        rule is no operand: no mask bytes."""
        d = self.head_dim
        product = 2 * batch * self.attended_pairs_a_pass(seq) \
            * self.n_head * d
        elems = 6 * batch * seq * d * (self.n_head + self.n_kv_head)
        stats = batch * self.n_head * seq * 4
        return {"flops": 6 * product,
                "bytes": self.n_layer * (elems * self._width_bytes()
                                         + 2 * stats)}

    # which custom calls are the grouped matmuls and which the attention
    # kernels (head-major arrays of heads of `head_dim`: a trace's event
    # names carry shapes; the kernels' forms, `fwd_rows_window` and
    # `bwd_fused_window` beside `fwd_rows` and `bwd_fused`, are in their
    # `tf_op`, which `harness/scope_trace.py` reads and
    # `metrics/window_kernel_share.py` tells the kinds of layer by) are the
    # `lfm2_moe` family's, word for word
    expected_rows_per_token = lfm2_moe.Family.expected_rows_per_token
    buffered_rows = lfm2_moe.Family.buffered_rows
    is_moe_matmul = lfm2_moe.Family.is_moe_matmul
    is_attention_kernel = lfm2_moe.Family.is_attention_kernel
    _width_bytes = lfm2_moe.Family._width_bytes
    _shapes = lfm2_moe.Family._shapes
    _is_custom_call = staticmethod(lfm2_moe.Family._is_custom_call)

    # -- the system under test: runs in the worker that holds the chips ----

    bind = lfm2_moe.Family.bind
    place_batch = lfm2_moe.Family.place_batch
    init_state = lfm2_moe.Family.init_state

    def model_config(self):
        import jax.numpy as jnp

        from ray_tpu.models.mellum import MellumConfig, Yarn

        c = self.config
        plain, yarn = (c["rope_parameters"][kind] for kind in (SLIDING, FULL))
        assert plain["rope_type"] == "default" and yarn["rope_type"] == "yarn"
        assert plain["rope_theta"] == yarn["rope_theta"]
        return MellumConfig(
            vocab_size=self.rows, layer_types=self.layer_types,
            sliding_window=self.window, n_head=self.n_head,
            n_kv_head=self.n_kv_head, head_dim=self.head_dim,
            n_embd=self.n_embd, expert_width=self.width,
            n_experts=self.n_experts, held=(self.held_first, self.n_held),
            top_k=self.top_k, norm_topk_prob=c["norm_topk_prob"],
            aux_weight=c["router_aux_loss_coef"],
            rope_theta=float(plain["rope_theta"]),
            yarn=Yarn(float(yarn["factor"]),
                      yarn["original_max_position_embeddings"],
                      float(yarn["beta_fast"]), float(yarn["beta_slow"]),
                      yarn.get("attention_factor")),
            rms_eps=c["rms_norm_eps"],
            compute_dtype=jnp.dtype(c["compute_dtype"]), remat=c["remat"],
            loss_chunk_rows=c["loss_chunk_rows"])

    def optimizer(self):
        from benchmark.reference.mellum import adamw

        return adamw(self.config["optimizer"])

    def _init(self, key):
        from ray_tpu.models import mellum

        return mellum.init_params(key, self.model_config())

    def lower_step(self, params, opt_state, batch):
        """The jitted train step, traced and lowered for this state."""
        import jax

        from ray_tpu.models import mellum
        from ray_tpu.parallel.context import use_mesh

        with use_mesh(self.mesh):
            kept = jax.tree.map(lambda x: x.sharding, (params, opt_state))
            step = jax.jit(
                mellum.make_train_step(self.model_config(), self.optimizer()),
                donate_argnums=(0, 1), out_shardings=(*kept, None))
            return step.lower(params, opt_state, batch)

    # -- the plain reference on the same seed and batches ------------------

    def reference_sizes(self):
        from benchmark.reference import mellum as reference

        c = self.config
        yarn = c["rope_parameters"][FULL]
        return reference.Sizes(
            n_head=self.n_head, n_kv_head=self.n_kv_head, top_k=self.top_k,
            kinds=tuple(reference.FULL if kind == FULL else reference.SLIDING
                        for kind in self.layer_types),
            window=self.window, norm_topk_prob=c["norm_topk_prob"],
            held_first=self.held_first,
            rope_theta=float(yarn["rope_theta"]),
            yarn_factor=float(yarn["factor"]),
            yarn_original=yarn["original_max_position_embeddings"],
            yarn_beta_fast=float(yarn["beta_fast"]),
            yarn_beta_slow=float(yarn["beta_slow"]),
            yarn_attention_factor=yarn.get("attention_factor"),
            rms_eps=c["rms_norm_eps"], aux_weight=c["router_aux_loss_coef"],
            query_block=c["reference"]["query_block"])

    def reference_losses(self, seed: int, batches) -> list:
        """What the system's `out["loss"]` is held to, the first
        len(batches) steps: the cross-entropy of
        `benchmark/reference/mellum.py` from the parameters the system's own
        init draws from `seed` (the same held experts and rows of the
        vocabulary), on the first bound device.

        Before the steps, the system's own attention of the first layer of
        EACH kind (`ray_tpu.models.mellum._attention`: the kernels under
        each rule with each rotary table, W_o) is held to the reference's
        float32 masked softmax on the first batch's first sequence
        (`first_layer`), because three losses from random weights see
        little of which keys a row attends: each operator's result may lie
        `reference.attention_error_max` of the norm of the reference's from
        it at most.  `harness/verdict.py` compares losses and nothing else,
        so a breach is handed to it as reference losses that are not
        numbers, which no loss is within the tolerance of; the line printed
        here says which limit was passed.  All of it is freed on return."""
        import jax
        import numpy as np

        from benchmark.reference import mellum as reference

        device = self.devices[0]
        batches = jax.device_put(np.stack(batches), device)
        with jax.default_matmul_precision("highest"):
            # the parameters are born on the device in the reference's
            # layout, so no second copy of them waits beside it
            params = jax.jit(lambda key: to_reference(self._init(key)))(
                jax.device_put(jax.random.PRNGKey(seed), device))
        errors = self.first_layer(params, batches[0, :1, :-1])
        with jax.default_matmul_precision("highest"):
            steps = reference.first_losses(
                params, batches, self.reference_sizes(),
                self.config["optimizer"])
        limit = self.config["reference"]["attention_error_max"]
        told = ", ".join(f"layer {i} ({kind}) {error:.5f}"
                         for kind, (i, error) in errors.items())
        print(f"mellum reference: L {[s[0] for s in steps]} L_B "
              f"{[s[1] for s in steps]}; sequence 0: the attention's result "
              f"under each kind's rule, of the reference's norm from it: "
              f"{told} (at most {limit})", flush=True)
        if not all(error <= limit for _, error in errors.values()):
            print("NOT CORRECT: mellum: a layer's attention is not the "
                  "reference's (the line above): the reference's losses are "
                  "withheld", flush=True)
            return [float("nan")] * len(steps)
        return [s[0] for s in steps]

    def first_layer(self, params, tokens) -> dict:
        """The system's attention against the reference's in the first
        layer of each kind, on one sequence -> {kind: (the layer,
        |system - reference| / |reference| of the operator's result
        (seq, E), Frobenius norms)}: the kernels under the kind's rule with
        the kind's rotary table, traced as the step traces them, against a
        float32 softmax under the rule written out.  Every layer is given
        the embedded tokens (normed by its own gain): what is judged is the
        operator, and a deeper layer's input would carry the layers before
        it.  ``params`` in the reference's layout; ``tokens`` (1, seq)
        int32."""
        import jax
        import jax.numpy as jnp

        from benchmark.reference import mellum as reference
        from ray_tpu.models import mellum
        from ray_tpu.util import tracing

        sizes, cfg = self.reference_sizes(), self.model_config()

        def of(params, tokens, i):
            """-> (layer i's leaves, its normed input (seq, E))."""
            p = jax.tree.map(lambda leaf: leaf[i], params["layers"])
            return p, reference.rms_norm(params["embed"][tokens[0]],
                                         p["norm1"], sizes.rms_eps)

        def system(params, tokens, i, kind):
            """The system's side, traced as its step is (no matmul
            precision asked for) -> (seq, E) float32."""
            p, u = of(params, tokens, i)
            attn = {"q_proj": {"kernel": p["wq"]},
                    "k_proj": {"kernel": p["wk"]},
                    "v_proj": {"kernel": p["wv"]},
                    "o_proj": {"kernel": p["wo"]},
                    "q_norm": {"scale": p["q_norm"]},
                    "k_norm": {"scale": p["k_norm"]}}
            out = mellum._attention(u[None].astype(cfg.compute_dtype), attn,
                                    cfg, kind)
            return out[0].astype(jnp.float32)

        def compare(params, tokens, got, i):
            p, u = of(params, tokens, i)
            want = reference.attention(u, p, sizes.kinds[i], sizes)
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


def to_reference(params):
    """The system's parameter tree (`ray_tpu.models.mellum.init_params`) as
    `benchmark/reference/mellum.py` reads it: the layers' leaves stacked,
    each layer's attention from the subtree its kind names."""
    import jax
    import jax.numpy as jnp

    layers = []
    i = 0
    while f"layer_{i}" in params:
        p = params[f"layer_{i}"]
        attn, moe = p[SLIDING if SLIDING in p else FULL], p["moe"]
        layers.append({
            "norm1": p["input_norm"]["scale"],
            "norm2": p["post_norm"]["scale"],
            "wq": attn["q_proj"]["kernel"], "wk": attn["k_proj"]["kernel"],
            "wv": attn["v_proj"]["kernel"], "wo": attn["o_proj"]["kernel"],
            "q_norm": attn["q_norm"]["scale"],
            "k_norm": attn["k_norm"]["scale"],
            "router": moe["router"]["kernel"],
            "e_gate": moe["wi_gate"], "e_up": moe["wi_up"],
            "e_down": moe["wo"]})
        i += 1
    return {"embed": params["embed_tokens"]["embedding"],
            "norm_f": params["norm_f"]["scale"],
            "head": params["lm_head"]["kernel"],
            "layers": jax.tree.map(lambda *leaves: jnp.stack(leaves),
                                   *layers)}
