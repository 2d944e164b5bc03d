"""The `keye_vl` family: how a Keye-VL-2.0 configuration file (the keys of
the model's published `config.json`, the language model's) becomes the
system under test (`ray_tpu.models.keye_vl` under a `ShardingConfig`), the
counts the yardstick needs (operations per token; the attention kernels',
the index scores' and the held experts' grouped matmuls' operations and
bytes; which of a trace's operations are which), and the run of the plain
reference it is judged against.

A configuration of this family is one chip's share of an expert-parallel
deployment: `num_experts` counts the experts HELD here, `experts_held.of`
(the published `num_local_experts`) the router's width, `vocab_size` the
slice of the vocabulary the tokens are drawn from, `padded_vocab_size` the
rows of embedding and head held.

The counts are of the work the MODEL asks for, whatever implements it: the
main attention over the sum_t min(t + 1, topk) selected pairs of a
sequence, the index scores over every causal pair (they are what selects),
never the tiles a kernel happens to visit.  So `mfu` and
`attn_roofline_share` read the same work before and after a kernel learns
to skip, and rise when it does.

Nothing at module level imports jax: the parent process reads the counts
and must stay off the chip.
"""

from __future__ import annotations

from benchmark.families import lfm2_moe


class Family:
    def __init__(self, config: dict):
        self.config = c = config
        self.n_layer = c["num_hidden_layers"]
        self.n_head = c["num_attention_heads"]
        self.n_kv_head = c["num_key_value_heads"]
        self.head_dim = c["head_dim"]
        self.n_embd = c["hidden_size"]
        self.width = c["moe_intermediate_size"]        # of one routed expert
        self.n_held = c["num_experts"]
        self.held_first = c["experts_held"]["first"]
        self.n_experts = c["experts_held"]["of"]       # the router's width
        assert self.n_experts == c["num_local_experts"], "router width"
        self.top_k = c["num_experts_per_tok"]
        sa = c["sa_config"]
        assert sa["indexer_num_kv_heads"] == 1, "one indexer key head"
        self.index_heads = sa["indexer_num_heads"]
        self.index_dim = sa["indexer_head_dim"]
        self.index_top_k = sa["topk"]
        self.index_block = sa["q_chunk_size"]
        self.rows = c["padded_vocab_size"]
        self.mesh = None

    n_routed_layers = property(lambda self: self.n_layer)

    # -- counts: pure functions of the shapes, no jax ----------------------

    def attention_params(self) -> int:
        """W_q and W_o at H heads, W_k and W_v at H_kv."""
        e, d = self.n_embd, self.head_dim
        return 2 * e * self.n_head * d + 2 * e * self.n_kv_head * d

    def indexer_params(self) -> int:
        """W_Iq (E x J D_I), W_Ik (E x D_I) and W_Iw (E x J): the matrices;
        the key's LayerNorm has 2 D_I more."""
        e = self.n_embd
        return e * self.index_heads * self.index_dim + e * self.index_dim \
            + e * self.index_heads

    def param_count(self) -> int:
        """Every leaf held here: embedding and head, the final norm; a
        layer's two norms, attention with its two head norms, the indexer
        with its LayerNorm, the router and the held experts."""
        e = self.n_embd
        layer = (2 * e + self.attention_params() + 2 * self.head_dim
                 + self.indexer_params() + 2 * self.index_dim
                 + e * self.n_experts + self.n_held * 3 * e * self.width)
        return 2 * self.rows * e + e + self.n_layer * layer

    def selected_pairs(self, seq: int) -> int:
        """(query, key) pairs a sequence attends: sum_t min(t + 1, topk)."""
        full = min(self.index_top_k, seq)
        return full * (full + 1) // 2 + (seq - full) * self.index_top_k

    @staticmethod
    def causal_pairs(seq: int) -> int:
        return seq * (seq + 1) // 2

    def multiplying_params_per_token(self) -> float:
        """The parameters one token multiplies HERE outside the indexer:
        the head's rows held, the four attention matrices, the router and
        the expected rows of held experts (three matrices each)."""
        e = self.n_embd
        routed = (e * self.n_experts
                  + self.expected_rows_per_token() * 3 * e * self.width)
        return self.rows * e + self.n_layer * (self.attention_params()
                                               + routed)

    def flops_per_token(self, seq: int) -> float:
        """6 N + per layer 4 x the indexer's matrices (forward and their
        own gradient; nothing goes back to the indexer's input) + the pair
        products, c causal and s selected pairs a token: the main
        attention over the selected pairs, QK' and PV forward once and
        backward twice (6 s H 2D); the index scores over every causal pair
        forward (2 c J D_I) and their two backward products over the
        selected (4 s J D_I); the loss's target, every head's QK' over the
        selected pairs once (2 s H D).  Copied from
        `ray_tpu.models.keye_vl.count_flops_per_token`."""
        h, d = self.n_head, self.head_dim
        j, di = self.index_heads, self.index_dim
        c = (seq + 1) / 2
        s = self.selected_pairs(seq) / seq
        pairs = 6 * s * h * 2 * d + 2 * c * j * di + 4 * s * j * di \
            + 2 * s * h * d
        return 6 * self.multiplying_params_per_token() \
            + self.n_layer * (4 * self.indexer_params() + pairs)

    def attention_cost(self, batch: int, seq: int) -> dict:
        """What the main attention must do over the SELECTED pairs, whatever
        tiles a kernel visits: six products of H heads D deep, 2 D
        operations a pair and head each.  Bytes: q, o, do and dq have H
        heads (six arrays read or written), k, v, dk and dv H_kv (six); the
        row statistics (B, H, S) in f32 once each way; the mask, a byte a
        (query, key) pair, read once each way."""
        d = self.head_dim
        product = 2 * batch * self.selected_pairs(seq) * self.n_head * d
        elems = 6 * batch * seq * d * (self.n_head + self.n_kv_head)
        stats = batch * self.n_head * seq * 4
        mask = batch * seq * seq
        return {"flops": self.n_layer * 6 * product,
                "bytes": self.n_layer * (elems * self._width_bytes()
                                         + 2 * stats + 2 * mask)}

    def index_scores_cost(self, batch: int, seq: int) -> dict:
        """What one training step's index scores must do, over all layers:
        forward the J heads' products over every causal pair (2 D_I
        operations a pair and head), backward the two products (towards
        the queries and towards the keys) over the selected pairs, the only
        ones whose score the loss reads; recomputation not counted.  Bytes:
        the float32 score of every causal pair written, the gradient of
        every selected pair read; the indexer's queries, keys and weights
        read and their gradients written, in the compute type."""
        j, di = self.index_heads, self.index_dim
        causal = batch * self.causal_pairs(seq)
        selected = batch * self.selected_pairs(seq)
        rows = 2 * batch * seq * (j * di + di + j) * self._width_bytes()
        return {"flops": self.n_layer * 2 * j * di * (causal + 2 * selected),
                "bytes": self.n_layer * (4 * (causal + selected) + rows)}

    # the grouped matmuls over the rows the held experts are EXPECTED to be
    # sent (T a layer: 8 x 16 / 128 a token), which custom calls they are
    # and which the attention kernels (head-major arrays of heads of
    # `head_dim`) are the `lfm2_moe` family's, word for word: they read the
    # widths, the heads, the experts held and `expected_rows_per_token`
    expected_rows_per_token = lfm2_moe.Family.expected_rows_per_token
    buffered_rows = lfm2_moe.Family.buffered_rows
    moe_cost = lfm2_moe.Family.moe_cost
    is_moe_matmul = lfm2_moe.Family.is_moe_matmul
    is_attention_kernel = lfm2_moe.Family.is_attention_kernel
    _width_bytes = lfm2_moe.Family._width_bytes
    _shapes = lfm2_moe.Family._shapes
    _is_custom_call = staticmethod(lfm2_moe.Family._is_custom_call)

    # -- the system under test: runs in the worker that holds the chips ----

    bind = lfm2_moe.Family.bind
    init_state = lfm2_moe.Family.init_state
    place_batch = lfm2_moe.Family.place_batch

    def model_config(self):
        import jax.numpy as jnp

        from ray_tpu.models.keye_vl import KeyeVlConfig

        c = self.config
        return KeyeVlConfig(
            vocab_size=self.rows, n_layer=self.n_layer, n_head=self.n_head,
            n_kv_head=self.n_kv_head, head_dim=self.head_dim,
            n_embd=self.n_embd, expert_width=self.width,
            n_experts=self.n_experts, held=(self.held_first, self.n_held),
            top_k=self.top_k, norm_topk_prob=c["norm_topk_prob"],
            rope_theta=float(c["rope_theta"]), rms_eps=c["rms_norm_eps"],
            index_heads=self.index_heads, index_dim=self.index_dim,
            index_top_k=self.index_top_k, index_block=self.index_block,
            aux_weight=c["router_aux_loss_coef"],
            compute_dtype=jnp.dtype(c["compute_dtype"]), remat=c["remat"],
            loss_chunk_rows=c["loss_chunk_rows"])

    def optimizer(self):
        """AdamW over every leaf, the indexer's among them."""
        from benchmark.reference.keye_vl import adamw

        return adamw(self.config["optimizer"])

    def _init(self, key):
        from ray_tpu.models import keye_vl

        return keye_vl.init_params(key, self.model_config())

    def lower_step(self, params, opt_state, batch):
        """The jitted train step, traced and lowered for this state."""
        import jax

        from ray_tpu.models import keye_vl
        from ray_tpu.parallel.context import use_mesh

        with use_mesh(self.mesh):
            kept = jax.tree.map(lambda x: x.sharding, (params, opt_state))
            step = jax.jit(
                keye_vl.make_train_step(self.model_config(),
                                        self.optimizer()),
                donate_argnums=(0, 1), out_shardings=(*kept, None))
            return step.lower(params, opt_state, batch)

    # -- the plain reference on the same seed and batches ------------------

    def reference_sizes(self):
        from benchmark.reference.keye_vl import Sizes

        c = self.config
        return Sizes(
            n_head=self.n_head, n_kv_head=self.n_kv_head, top_k=self.top_k,
            index_heads=self.index_heads, index_top_k=self.index_top_k,
            norm_topk_prob=c["norm_topk_prob"], held_first=self.held_first,
            rope_theta=float(c["rope_theta"]), rms_eps=c["rms_norm_eps"],
            aux_weight=c["router_aux_loss_coef"],
            query_block=c["reference"]["query_block"])

    def reference_losses(self, seed: int, batches) -> list:
        """What the system's `out["loss"]` is held to, the first
        len(batches) steps: L_LM + L_I of `benchmark/reference/keye_vl.py`
        (the cross-entropy and the indexers' loss: the indexer's leaves
        reach a loss only through L_I), from the parameters the system's
        own init draws from `seed` (the same held experts and rows of the
        vocabulary), on the first bound device.

        Before the steps, the system's own first layer's attention
        (`ray_tpu.models.keye_vl._select` and `_attention`: the indexer,
        the selection, the masked kernel, W_o) is held to the reference's
        on the first batch's first sequence (`first_layer`), because three
        losses from random weights see little of which keys a query
        attends: the share of causal pairs both select alike must reach
        `reference.selection_agreement_min`, and the operator's result may
        lie `reference.attention_error_max` of the norm of the reference's
        (its softmax over the keys the SYSTEM selected) from it at most.
        `harness/verdict.py` compares losses and nothing else, so a breach
        is handed to it as reference losses that are not numbers, which no
        loss is within the tolerance of; the line printed here says which
        limit was passed.  All of it is freed on return."""
        import jax
        import numpy as np

        from benchmark.reference import keye_vl as reference

        device = self.devices[0]
        batches = jax.device_put(np.stack(batches), device)
        with jax.default_matmul_precision("highest"):
            # the parameters are born on the device in the reference's
            # layout, so no second copy of them waits beside it
            params = jax.jit(lambda key: to_reference(self._init(key)))(
                jax.device_put(jax.random.PRNGKey(seed), device))
        agreement, error = self.first_layer(params, batches[0, 0, :-1])
        with jax.default_matmul_precision("highest"):
            steps = reference.first_losses(
                params, batches, self.reference_sizes(),
                self.config["optimizer"])
        limits = self.config["reference"]
        print(f"keye_vl reference: L_LM {[s[0] for s in steps]} L_I "
              f"{[s[1] for s in steps]} L_B {[s[2] for s in steps]}; "
              f"layer 0, sequence 0: system and reference agree on "
              f"{100 * agreement:.4f} % of the causal pairs (at least "
              f"{100 * limits['selection_agreement_min']} %), the "
              f"attention's result is {error:.5f} of the reference's norm "
              f"from it (at most {limits['attention_error_max']})",
              flush=True)
        if not (agreement >= limits["selection_agreement_min"]
                and error <= limits["attention_error_max"]):
            print("NOT CORRECT: keye_vl: the first layer's attention is not "
                  "the reference's (the line above): the reference's losses "
                  "are withheld", flush=True)
            return [float("nan")] * len(steps)
        return [s[0] + s[1] for s in steps]

    def first_layer(self, params, inputs):
        """The system's first layer's attention against the reference's on
        one sequence -> (of its causal (query, key) pairs, the share that
        the system's selection and the reference's mark alike: float32
        scores and a stable sort against `ray_tpu/ops/sparse_index.py` in
        the configuration's compute type; |system - reference| / |reference|
        of the operator's result (seq, E), Frobenius norms: the system's
        masked kernel under its own selection against the reference's
        float32 softmax over the same keys, so that the first number judges
        the selection and the second what is done with it).  ``params`` in
        the reference's layout; ``inputs`` (seq,) int32."""
        import jax
        import jax.numpy as jnp

        from benchmark.reference import keye_vl as reference
        from ray_tpu.models import keye_vl
        from ray_tpu.util import tracing

        sizes, cfg = self.reference_sizes(), self.model_config()

        def first(params, inputs):
            """-> (the first layer's leaves, its normed input (seq, E))."""
            p = jax.tree.map(lambda leaf: leaf[0], params["layers"])
            return p, reference.rms_norm(params["embed"][inputs], p["norm1"],
                                         sizes.rms_eps)

        def system(params, inputs):
            """The system's side, traced as its step is (no matmul
            precision asked for) -> (the selection (seq, seq) bool, the
            operator's result (seq, E) float32)."""
            p, u = first(params, inputs)
            indexer = {"q_proj": {"kernel": p["iq"]},
                       "k_proj": {"kernel": p["ik"]},
                       "weights_proj": {"kernel": p["iw"]},
                       "k_norm": {"scale": p["ik_gain"],
                                  "bias": p["ik_bias"]}}
            attn = {"q_proj": {"kernel": p["wq"]},
                    "k_proj": {"kernel": p["wk"]},
                    "v_proj": {"kernel": p["wv"]},
                    "o_proj": {"kernel": p["wo"]},
                    "q_norm": {"scale": p["q_norm"]},
                    "k_norm": {"scale": p["k_norm"]}, "indexer": indexer}
            x = u[None].astype(cfg.compute_dtype)
            _, mask = keye_vl._select(x, indexer, cfg)
            out, _ = keye_vl._attention(x, attn, cfg)
            return mask[0] != 0, out[0].astype(jnp.float32)

        def compare(params, inputs, got, got_out):
            p, u = first(params, inputs)
            seq = u.shape[0]
            block = min(sizes.query_block, seq)
            q, k, w = reference.indexer(u, p, sizes)

            def rows(start):
                seen = (start + jnp.arange(block))[:, None] \
                    >= jnp.arange(seq)[None]
                cut = lambda x, axis: jax.lax.dynamic_slice_in_dim(
                    x, start, block, axis=axis)
                return reference.select(
                    reference.index_scores(cut(q, 1), k, cut(w, 0)), seen,
                    sizes.index_top_k)

            want = jax.lax.map(rows, jnp.arange(0, seq, block)).reshape(
                seq, seq)
            want_out, _ = reference.attention(u, p, sizes, got)
            causal = jnp.tril(jnp.ones((seq, seq), bool))
            return (jnp.sum((want == got) & causal) / jnp.sum(causal),
                    jnp.linalg.norm(got_out - want_out)
                    / jnp.linalg.norm(want_out))

        # the step's counters are of the step: these traces add nothing
        with tracing.outside_job():
            got, got_out = jax.jit(system)(params, inputs)
            with jax.default_matmul_precision("highest"):
                agreement, error = jax.jit(compare)(params, inputs, got,
                                                    got_out)
        return float(agreement), float(error)


def to_reference(params):
    """The system's parameter tree (`ray_tpu.models.keye_vl.init_params`)
    as `benchmark/reference/keye_vl.py` reads it: the layers' leaves
    stacked."""
    import jax
    import jax.numpy as jnp

    layers = []
    i = 0
    while f"layer_{i}" in params:
        p = params[f"layer_{i}"]
        attn, moe = p["attn"], p["moe"]
        indexer = attn["indexer"]
        layers.append({
            "norm1": p["input_norm"]["scale"],
            "norm2": p["post_norm"]["scale"],
            "wq": attn["q_proj"]["kernel"], "wk": attn["k_proj"]["kernel"],
            "wv": attn["v_proj"]["kernel"], "wo": attn["o_proj"]["kernel"],
            "q_norm": attn["q_norm"]["scale"],
            "k_norm": attn["k_norm"]["scale"],
            "iq": indexer["q_proj"]["kernel"],
            "ik": indexer["k_proj"]["kernel"],
            "iw": indexer["weights_proj"]["kernel"],
            "ik_gain": indexer["k_norm"]["scale"],
            "ik_bias": indexer["k_norm"]["bias"],
            "router": moe["router"]["kernel"],
            "e_gate": moe["wi_gate"], "e_up": moe["wi_up"],
            "e_down": moe["wo"]})
        i += 1
    return {"embed": params["embed_tokens"]["embedding"],
            "norm_f": params["norm_f"]["scale"],
            "head": params["lm_head"]["kernel"],
            "layers": jax.tree.map(lambda *leaves: jnp.stack(leaves),
                                   *layers)}
