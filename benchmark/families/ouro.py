"""The `ouro` family: how an Ouro configuration file (the keys of the
model's published `config.json`) becomes the system under test
(`ray_tpu.models.ouro` under a `ShardingConfig`), the counts the yardstick
needs (operations per token, the attention kernels' operations and bytes,
which of a trace's operations are those kernels), and the run of the plain
reference it is judged against.

A configuration of this family is one stage of a pipeline that a
micro-batch goes round `total_ut_steps` times: `num_hidden_layers` counts
the layers held here, and every one of them is called `total_ut_steps`
times a step over the same parameters.

The counts are of the work the MODEL asks for, T walks and T heads,
whatever implements them (a loop unrolled or scanned, a head read once or T
times): so `mfu` reads the same work whichever form the walk has.

Nothing at module level imports jax: the parent process reads the counts
and must stay off the chip.
"""

from __future__ import annotations

from benchmark.families import gpt2, lfm2_moe


class Family:
    def __init__(self, config: dict):
        self.config = c = config
        self.n_layer = c["num_hidden_layers"]
        assert set(c["layer_types"]) == {"full_attention"}, "layer_types"
        self.n_walk = c["total_ut_steps"]
        self.n_head = c["num_attention_heads"]
        self.n_kv_head = c["num_key_value_heads"]
        self.head_dim = c["head_dim"]
        self.n_embd = c["hidden_size"]
        self.dense_width = c["intermediate_size"]
        self.rows = c["padded_vocab_size"]
        self.mesh = None

    @property
    def layer_calls(self) -> int:
        """Calls of a layer a step: every layer held, every walk."""
        return self.n_walk * self.n_layer

    # -- counts: pure functions of the shapes, no jax ----------------------

    def layer_matrices(self) -> int:
        """W_q and W_o at H heads, W_k and W_v at H_kv; gate, up, down."""
        e, d = self.n_embd, self.head_dim
        return (2 * e * self.n_head * d + 2 * e * self.n_kv_head * d
                + 3 * e * self.dense_width)

    def param_count(self) -> int:
        """Every leaf held here: embedding and head, the final norm, the
        gate with its bias; a layer's seven matrices and four norms."""
        e = self.n_embd
        return (2 * self.rows * e + e + e + 1
                + self.n_layer * (self.layer_matrices() + 4 * e))

    def flops_per_token(self, seq: int) -> float:
        """6 N + the attention products: N the parameters a token
        multiplies over ALL its walks (T x n calls of a layer's seven
        matrices, T heads; the gate's 2,048 are left out); the products of
        the T x n calls over the causal pairs, (S + 1) / 2 keys a query:
        QK' and PV forward once and backward twice, 2 D operations a pair
        and head each.  Copied from
        `ray_tpu.models.ouro.count_flops_per_token`."""
        n = self.layer_calls * self.layer_matrices() \
            + self.n_walk * self.rows * self.n_embd
        return 6 * n + self.layer_calls * 6 * ((seq + 1) / 2) \
            * self.n_head * 2 * self.head_dim

    def attention_cost(self, batch: int, seq: int) -> dict:
        """As `families/lfm2_moe.py:attention_cost`, over the T x n calls of
        a step: causal attention needs half of each S x S product, six
        products of H heads D deep; what a kernel recomputes is not
        counted.  Bytes: q, o, do and dq have H heads (six arrays read or
        written), k, v, dk and dv H_kv (six); the row statistics (B, H, S)
        in f32 once each way."""
        d = self.head_dim
        product = 2 * batch * self.n_head * seq * seq * d
        elems = 6 * batch * seq * d * (self.n_head + self.n_kv_head)
        stats = batch * self.n_head * seq * 4
        return {"flops": self.layer_calls * 6 * product / 2,
                "bytes": self.layer_calls * (
                    elems * self._width_bytes() + 2 * stats)}

    # the step's only Mosaic kernels are flash attention's
    is_attention_kernel = staticmethod(gpt2.Family.is_attention_kernel)
    _width_bytes = lfm2_moe.Family._width_bytes

    # -- the system under test: runs in the worker that holds the chips ----

    bind = gpt2.Family.bind
    init_state = gpt2.Family.init_state
    place_batch = gpt2.Family.place_batch

    def model_config(self):
        import jax.numpy as jnp

        from ray_tpu.models.ouro import OuroConfig

        c = self.config
        return OuroConfig(
            vocab_size=self.rows, n_layer=self.n_layer, n_head=self.n_head,
            n_kv_head=self.n_kv_head, head_dim=self.head_dim,
            n_embd=self.n_embd, dense_width=self.dense_width,
            n_walk=self.n_walk, rope_theta=float(c["rope_theta"]),
            rms_eps=c["rms_norm_eps"], entropy_weight=c["entropy_weight"],
            compute_dtype=jnp.dtype(c["compute_dtype"]), remat=c["remat"],
            loss_chunk_rows=c["loss_chunk_rows"])

    def optimizer(self):
        """AdamW over every leaf, the gate's among them."""
        from benchmark.reference.ouro import adamw

        return adamw(self.config["optimizer"])

    def _init(self, key):
        from ray_tpu.models import ouro

        return ouro.init_params(key, self.model_config())

    def lower_step(self, params, opt_state, batch):
        """The jitted train step, traced and lowered for this state."""
        import jax

        from ray_tpu.models import ouro
        from ray_tpu.parallel.context import use_mesh

        with use_mesh(self.mesh):
            kept = jax.tree.map(lambda x: x.sharding, (params, opt_state))
            step = jax.jit(
                ouro.make_train_step(self.model_config(), self.optimizer()),
                donate_argnums=(0, 1), out_shardings=(*kept, None))
            return step.lower(params, opt_state, batch)

    # -- the plain reference on the same seed and batches ------------------

    def reference_sizes(self):
        from benchmark.reference.ouro import Sizes

        c = self.config
        return Sizes(
            n_head=self.n_head, n_kv_head=self.n_kv_head,
            n_walk=self.n_walk, rope_theta=float(c["rope_theta"]),
            rms_eps=c["rms_norm_eps"], entropy_weight=c["entropy_weight"],
            query_block=c["reference"]["query_block"])

    def reference_losses(self, seed: int, batches) -> list:
        """What the system's `out["loss"]` is held to, the first
        len(batches) steps: the objective (sum_t p_t CE_t - beta H, the mean
        over the tokens) of `benchmark/reference/ouro.py`, from the
        parameters the system's own init draws from `seed`, on the first
        bound device.

        Before the steps, the system's own walk (`ray_tpu.models.ouro.
        hidden`: `layers.trunk` over the T walks, and the gate) is held to
        the reference's on the first batch's first sequence (`first_walks`),
        because three losses from random weights see little of how often
        the trunk was walked (every CE_t starts near the logarithm of the
        vocabulary): each of the T normed states may lie
        `reference.state_error_max` of the norm of the reference's from it
        at most, and the T means of the exit distribution
        `reference.exit_error_max` of the reference's.
        `harness/verdict.py` compares losses and nothing else, so a breach
        is handed to it as reference losses that are not numbers, which no
        loss is within the tolerance of; the line printed here says which
        limit was passed.  All of it is freed on return."""
        import jax
        import numpy as np

        from benchmark.reference import ouro as reference

        device = self.devices[0]
        batches = jax.device_put(np.stack(batches), device)
        with jax.default_matmul_precision("highest"):
            # the parameters are born on the device in the reference's
            # layout, so no second copy of them waits beside it
            params = jax.jit(lambda key: to_reference(self._init(key)))(
                jax.device_put(jax.random.PRNGKey(seed), device))
        states, exits = self.first_walks(params, batches[0, 0, :-1])
        with jax.default_matmul_precision("highest"):
            losses = reference.first_losses(
                params, batches, self.reference_sizes(),
                self.config["optimizer"])
        limits = self.config["reference"]
        print(f"ouro reference: losses {losses}; sequence 0: the system's "
              f"normed state after each walk lies {states} of the norm of "
              f"the reference's from it (at most "
              f"{limits['state_error_max']}), the means of its exit "
              f"distribution {exits} of the reference's (at most "
              f"{limits['exit_error_max']})", flush=True)
        if not (max(states) <= limits["state_error_max"]
                and max(exits) <= limits["exit_error_max"]):
            print("NOT CORRECT: ouro: the system's walks are not the "
                  "reference's (the line above): the reference's losses are "
                  "withheld", flush=True)
            return [float("nan")] * len(losses)
        return losses

    def first_walks(self, params, inputs):
        """The system's walks against the reference's on one sequence ->
        ([|system - reference| / |reference| of each walk's normed state
        (seq, E), Frobenius norms], [|system - reference| / |reference| of
        the T means over the sequence of the exit distribution, one
        number]).  ``params`` in the reference's layout; ``inputs`` (seq,)
        int32.  The system's side is traced as its step is (no matmul
        precision asked for, the matrices cast to the compute type once,
        `remat` as configured)."""
        import jax
        import jax.numpy as jnp

        from benchmark.reference import ouro as reference
        from ray_tpu.models import ouro
        from ray_tpu.models.layers import cast_weights
        from ray_tpu.util import tracing

        sizes, cfg = self.reference_sizes(), self.model_config()

        def system(params, inputs):
            states, log_p = ouro.hidden(
                cast_weights(from_reference(params), cfg.compute_dtype),
                inputs[None], cfg)
            return states[:, 0].astype(jnp.float32), jnp.exp(log_p[:, 0])

        def compare(params, inputs, got, got_p):
            want = reference.walks(params, inputs, sizes)
            want_p = jnp.stack([jnp.mean(p) for p in
                                reference.exit_distribution(params, want)])
            want = jnp.stack(want)
            norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x), axis=(1, 2)))
            return (norm(got - want) / norm(want),
                    jnp.linalg.norm(jnp.mean(got_p, axis=1) - want_p)
                    / jnp.linalg.norm(want_p))

        # the step's counters are of the step: these traces add nothing
        with tracing.outside_job():
            got, got_p = jax.jit(system)(params, inputs)
            with jax.default_matmul_precision("highest"):
                states, exits = jax.jit(compare)(params, inputs, got, got_p)
        return [float(s) for s in states], [float(exits)]


def to_reference(params):
    """The system's parameter tree (`ray_tpu.models.ouro.init_params`) as
    `benchmark/reference/ouro.py` reads it: the layers' leaves stacked on a
    leading axis."""
    import jax
    import jax.numpy as jnp

    layers = []
    i = 0
    while f"layer_{i}" in params:
        p = params[f"layer_{i}"]
        attn, mlp = p["attn"], p["mlp"]
        layers.append({
            "norm1": p["input_norm"]["scale"],
            "norm2": p["input_norm_2"]["scale"],
            "norm3": p["post_norm"]["scale"],
            "norm4": p["post_norm_2"]["scale"],
            "wq": attn["q_proj"]["kernel"], "wk": attn["k_proj"]["kernel"],
            "wv": attn["v_proj"]["kernel"], "wo": attn["o_proj"]["kernel"],
            "gate": mlp["gate_proj"]["kernel"],
            "up": mlp["up_proj"]["kernel"],
            "down": mlp["down_proj"]["kernel"]})
        i += 1
    return {"embed": params["embed_tokens"]["embedding"],
            "norm_f": params["norm_f"]["scale"],
            "head": params["lm_head"]["kernel"],
            "gate_w": params["exit_gate"]["kernel"][:, 0],
            "gate_b": params["exit_gate"]["bias"][0],
            "layers": jax.tree.map(lambda *leaves: jnp.stack(leaves),
                                   *layers)}


def from_reference(params):
    """`to_reference` back: the reference's layout as the system's tree."""
    kernel = lambda w: {"kernel": w}
    scale = lambda g: {"scale": g}
    tree = {"embed_tokens": {"embedding": params["embed"]},
            "norm_f": scale(params["norm_f"]),
            "lm_head": kernel(params["head"]),
            "exit_gate": {"kernel": params["gate_w"][:, None],
                          "bias": params["gate_b"][None]}}
    stacked = params["layers"]
    for i in range(stacked["norm1"].shape[0]):
        p = {name: leaf[i] for name, leaf in stacked.items()}
        tree[f"layer_{i}"] = {
            "input_norm": scale(p["norm1"]),
            "input_norm_2": scale(p["norm2"]),
            "post_norm": scale(p["norm3"]),
            "post_norm_2": scale(p["norm4"]),
            "attn": {"q_proj": kernel(p["wq"]), "k_proj": kernel(p["wk"]),
                     "v_proj": kernel(p["wv"]), "o_proj": kernel(p["wo"])},
            "mlp": {"gate_proj": kernel(p["gate"]),
                    "up_proj": kernel(p["up"]),
                    "down_proj": kernel(p["down"])}}
    return tree
