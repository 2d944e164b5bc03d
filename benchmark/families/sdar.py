"""The `sdar` family: how an SDAR configuration file (the keys of the model's
published `config.json`, `model_type` `sdar_moe`) becomes the system under
test (`ray_tpu.models.sdar` under a `ShardingConfig`), the counts the
yardstick needs (operations per token; the attention kernels' operations
and bytes; the pairs the rule attends; which of a trace's operations are
the kernels), and the run of the plain reference it is judged against.

A configuration of this family is one chip's share of an expert-parallel
deployment: `num_experts` counts the experts HELD here, `experts_held.of`
the router's width, `vocab_size` the slice of the vocabulary the tokens are
drawn from, `padded_vocab_size` the rows of embedding and head held, of
which row `vocab_size`, the first spare one, is MASK: embedded, never
drawn by the traffic and never a target.

It is trained as a block-diffusion model: a step lays a noised copy of
every sequence beside the clean one, so a step of `batch` x `seq` CLEAN
tokens (what `tokens_per_s` counts, as such a job's users do) runs 2 x
`batch` x `seq` rows through the trunk.  The counts are of the work the
MODEL asks for, whatever implements it: attention over the L (L + b) pairs
a sequence's rule attends, never the tiles a kernel happens to visit, and
of the last layer's clean rows only the keys and values the noised rows
read.  So `mfu` and `attn_roofline_share` read the same work before and
after a kernel learns to skip or a layer to leave rows out, and rise when
it does.

Nothing at module level imports jax: the parent process reads the counts
and must stay off the chip.
"""

from __future__ import annotations

from benchmark.families import lfm2_moe


class Family:
    def __init__(self, config: dict):
        self.config = c = config
        assert c["decoder_sparse_step"] == 1 and not c["mlp_only_layers"], \
            "every layer's feed-forward is the mixture"
        self.n_layer = c["num_hidden_layers"]
        self.n_head = c["num_attention_heads"]
        self.n_kv_head = c["num_key_value_heads"]
        self.head_dim = c["head_dim"]
        self.n_embd = c["hidden_size"]
        self.width = c["moe_intermediate_size"]        # of one routed expert
        self.n_held = c["num_experts"]
        self.held_first = c["experts_held"]["first"]
        self.n_experts = c["experts_held"]["of"]       # the router's width
        self.top_k = c["num_experts_per_tok"]
        self.block_length = c["block_length"]
        self.rows = c["padded_vocab_size"]
        self.mask_token = c["vocab_size"]              # the first spare row
        assert self.mask_token < self.rows, "MASK needs a spare row"
        self.mesh = None
        self.seed = None
        # a program from before this family's model cannot run its cells:
        # said as soon as the worker that holds the chip builds its family,
        # before a batch, a reference or a state exists (the module is
        # looked for, not imported: no jax here)
        import importlib.util
        if importlib.util.find_spec("ray_tpu.models.sdar") is None:
            raise ImportError("this program has no ray_tpu/models/sdar.py: "
                              "it cannot run a cell of the sdar family")

    n_routed_layers = property(lambda self: self.n_layer)

    # -- counts: pure functions of the shapes, no jax ----------------------

    def attention_params(self) -> int:
        """W_q and W_o at H heads, W_k and W_v at H_kv."""
        return 2 * self.n_embd * self.n_head * self.head_dim \
            + self.kv_params()

    def kv_params(self) -> int:
        return 2 * self.n_embd * self.n_kv_head * self.head_dim

    def param_count(self) -> int:
        """Every leaf held here: embedding and head, the final norm; a
        layer's two norms, attention with its two head norms, the router
        and the held experts."""
        e = self.n_embd
        layer = (2 * e + self.attention_params() + 2 * self.head_dim
                 + e * self.n_experts + self.n_held * 3 * e * self.width)
        return 2 * self.rows * e + e + self.n_layer * layer

    def attended_pairs(self, seq: int) -> int:
        """(query, key) pairs a sequence attends, a head, over its 2 seq
        rows: the clean rows seq (seq + b) / 2, the noised rows' clean keys
        seq (seq - b) / 2 and their own blocks seq b."""
        return seq * (seq + self.block_length)

    def multiplying_params_per_token(self) -> float:
        """The parameters a CLEAN token's two rows multiply HERE: in every
        layer but the last both rows through the four attention matrices,
        the router and the expected rows of held experts (three matrices
        each); in the last the noised row through all of it and the clean
        row through W_k and W_v alone; the head's rows held, once."""
        e = self.n_embd
        routed = (e * self.n_experts
                  + self.expected_rows_per_token() * 3 * e * self.width)
        return (2 * self.n_layer - 1) * (self.attention_params() + routed) \
            + self.kv_params() + self.rows * e

    def flops_per_token(self, seq: int) -> float:
        """6 N + per layer the attention products over the attended pairs,
        seq + b a token: QK' and PV forward once and backward twice, 2 D
        operations a pair and head each.  Recomputation not counted.
        Copied from `ray_tpu.models.sdar.count_flops_per_token`."""
        pairs = self.attended_pairs(seq) / seq
        return 6 * self.multiplying_params_per_token() \
            + self.n_layer * 6 * pairs * self.n_head * 2 * self.head_dim

    def attention_cost(self, batch: int, seq: int) -> dict:
        """What attention must do over the ATTENDED pairs, whatever tiles a
        kernel visits: six products of H heads D deep, 2 D operations a pair
        and head each.  Bytes, over the 2 seq rows a sequence has: q, o, do
        and dq have H heads (six arrays read or written), k, v, dk and dv
        H_kv (six); the row statistics (B, H, 2 seq) in f32 once each way.
        The rule is no operand: no mask bytes."""
        d = self.head_dim
        product = 2 * batch * self.attended_pairs(seq) * self.n_head * d
        elems = 6 * batch * 2 * seq * d * (self.n_head + self.n_kv_head)
        stats = batch * self.n_head * 2 * seq * 4
        return {"flops": self.n_layer * 6 * product,
                "bytes": self.n_layer * (elems * self._width_bytes()
                                         + 2 * stats)}

    # which custom calls are the grouped matmuls and which the attention
    # kernels (head-major arrays of heads of `head_dim`, here over 2 seq
    # rows: a trace's event names carry shapes, not the kernels' scopes
    # `fwd_rows_blocks` / `bwd_fused_blocks`, which `scope_trace` reads)
    # are the `lfm2_moe` family's, word for word
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

    def init_state(self, seed: int):
        """As every family's, and the seed kept: the step draws its noise
        from it (`lower_step`)."""
        self.seed = seed
        return lfm2_moe.Family.init_state(self, seed)

    def model_config(self):
        import jax.numpy as jnp

        from ray_tpu.models.sdar import SdarConfig

        c = self.config
        return SdarConfig(
            vocab_size=self.rows, mask_token=self.mask_token,
            block_length=self.block_length, n_layer=self.n_layer,
            n_head=self.n_head, n_kv_head=self.n_kv_head,
            head_dim=self.head_dim, n_embd=self.n_embd,
            expert_width=self.width, n_experts=self.n_experts,
            held=(self.held_first, self.n_held), top_k=self.top_k,
            norm_topk_prob=c["norm_topk_prob"],
            rope_theta=float(c["rope_theta"]), rms_eps=c["rms_norm_eps"],
            aux_weight=c["router_aux_loss_coef"],
            compute_dtype=jnp.dtype(c["compute_dtype"]), remat=c["remat"],
            loss_chunk_rows=c["loss_chunk_rows"])

    def optimizer(self):
        from benchmark.reference.sdar import adamw

        return adamw(self.config["optimizer"])

    def _init(self, key):
        from ray_tpu.models import sdar

        return sdar.init_params(key, self.model_config())

    def lower_step(self, params, opt_state, batch):
        """The jitted train step, traced and lowered for this state; its
        noise is a function of the seed `init_state` was given and the
        optimizer's count of updates (0 for a state nobody seeded: the
        no-chip compile, `tools/aot_collectives.py`)."""
        import jax

        from ray_tpu.models import sdar
        from ray_tpu.parallel.context import use_mesh

        with use_mesh(self.mesh):
            kept = jax.tree.map(lambda x: x.sharding, (params, opt_state))
            step = jax.jit(
                sdar.make_train_step(self.model_config(), self.optimizer(),
                                     self.seed or 0),
                donate_argnums=(0, 1), out_shardings=(*kept, None))
            return step.lower(params, opt_state, batch)

    # -- the plain reference on the same seed and batches ------------------

    def reference_sizes(self):
        from benchmark.reference.sdar import Sizes

        c = self.config
        return Sizes(
            n_head=self.n_head, n_kv_head=self.n_kv_head, top_k=self.top_k,
            mask_token=self.mask_token, block_length=self.block_length,
            norm_topk_prob=c["norm_topk_prob"], held_first=self.held_first,
            rope_theta=float(c["rope_theta"]), rms_eps=c["rms_norm_eps"],
            aux_weight=c["router_aux_loss_coef"],
            query_block=c["reference"]["query_block"])

    def reference_losses(self, seed: int, batches) -> list:
        """What the system's `out["loss"]` is held to, the first
        len(batches) steps: L_D of `benchmark/reference/sdar.py` under the
        noise of (seed, step), from the parameters the system's own init
        draws from `seed` (the same held experts and rows of the
        vocabulary), on the first bound device.

        Before the steps, the system's own first layer's attention
        (`ray_tpu.models.sdar._attention`: the kernels under the rule, W_o)
        is held to the reference's float32 masked softmax on the first
        batch's first sequence under step 0's noise (`first_layer`),
        because three losses from random weights see little of which keys a
        row attends: the operator's result may lie
        `reference.attention_error_max` of the norm of the reference's from
        it at most.  `harness/verdict.py` compares losses and nothing else,
        so a breach is handed to it as reference losses that are not
        numbers, which no loss is within the tolerance of; the line printed
        here says which limit was passed.  All of it is freed on return."""
        import jax
        import numpy as np

        from benchmark.reference import sdar as reference

        device = self.devices[0]
        batches = jax.device_put(np.stack(batches), device)
        with jax.default_matmul_precision("highest"):
            # the parameters are born on the device in the reference's
            # layout, so no second copy of them waits beside it
            params = jax.jit(lambda key: to_reference(self._init(key)))(
                jax.device_put(jax.random.PRNGKey(seed), device))
        error = self.first_layer(params, batches[0, :1, :-1], seed)
        with jax.default_matmul_precision("highest"):
            steps = reference.first_losses(
                params, batches, seed, self.reference_sizes(),
                self.config["optimizer"])
        limit = self.config["reference"]["attention_error_max"]
        print(f"sdar reference: L_D {[s[0] for s in steps]} L_B "
              f"{[s[1] for s in steps]}; layer 0, sequence 0: the "
              f"attention's result under the rule is {error:.5f} of the "
              f"reference's norm from it (at most {limit})", flush=True)
        if not error <= limit:
            print("NOT CORRECT: sdar: the first layer's attention is not "
                  "the reference's (the line above): the reference's losses "
                  "are withheld", flush=True)
            return [float("nan")] * len(steps)
        return [s[0] for s in steps]

    def first_layer(self, params, tokens, seed: int) -> float:
        """The system's first layer's attention against the reference's on
        one sequence's 2 seq rows under step 0's noise ->
        |system - reference| / |reference| of the operator's result
        (2 seq, E), Frobenius norms: the kernels under the rule, traced as
        the step traces them, against a float32 softmax under the rule
        written out.  ``params`` in the reference's layout; ``tokens``
        (1, seq) int32."""
        import jax
        import jax.numpy as jnp

        from benchmark.reference import sdar as reference
        from ray_tpu.models import sdar
        from ray_tpu.util import tracing

        sizes, cfg = self.reference_sizes(), self.model_config()

        def first(params, tokens):
            """-> (the first layer's leaves, its normed input (2 seq, E))."""
            m, _ = reference.noise(seed, 0, *tokens.shape, sizes.block_length)
            rows = jnp.concatenate(
                [tokens[0], jnp.where(m[0], sizes.mask_token, tokens[0])])
            p = jax.tree.map(lambda leaf: leaf[0], params["layers"])
            return p, reference.rms_norm(params["embed"][rows], p["norm1"],
                                         sizes.rms_eps)

        def system(params, tokens):
            """The system's side, traced as its step is (no matmul
            precision asked for) -> (2 seq, E) float32."""
            p, u = first(params, tokens)
            attn = {"q_proj": {"kernel": p["wq"]},
                    "k_proj": {"kernel": p["wk"]},
                    "v_proj": {"kernel": p["wv"]},
                    "o_proj": {"kernel": p["wo"]},
                    "q_norm": {"scale": p["q_norm"]},
                    "k_norm": {"scale": p["k_norm"]}}
            out = sdar._attention(u[None].astype(cfg.compute_dtype), attn,
                                  cfg)
            return out[0].astype(jnp.float32)

        def compare(params, tokens, got):
            p, u = first(params, tokens)
            want = reference.attention(u, p, sizes)
            return jnp.linalg.norm(got - want) / jnp.linalg.norm(want)

        # the step's counters are of the step: these traces add nothing
        with tracing.outside_job():
            got = jax.jit(system)(params, tokens)
            with jax.default_matmul_precision("highest"):
                return float(jax.jit(compare)(params, tokens, got))


def to_reference(params):
    """The system's parameter tree (`ray_tpu.models.sdar.init_params`) as
    `benchmark/reference/sdar.py` reads it: the layers' leaves stacked."""
    import jax
    import jax.numpy as jnp

    layers = []
    i = 0
    while f"layer_{i}" in params:
        p = params[f"layer_{i}"]
        attn, moe = p["attn"], p["moe"]
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
