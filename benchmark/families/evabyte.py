"""The `evabyte` family: how an EvaByte configuration file (the keys of the
model's published `config.json`) becomes the system under test
(`ray_tpu.models.evabyte` under a `ShardingConfig`), the counts the yardstick
needs (operations per token; the attention kernels', the remote half's and
the pooling's operations and bytes; which of a trace's kernels are the flash
kernels), and the run of the plain reference it is judged against.

A configuration of this family is one chip's share of a deployment in which
several chips share each layer: `num_attention_heads` counts the heads HELD
here (`published.num_attention_heads` the model's; `num_key_value_heads`
follows), `first_layer` the published index of the first layer held.  The
feed-forward, the norms, the embedding and the eight heads' matrix are whole.

Nothing at module level imports jax: the parent process reads the counts
and must stay off the chip.
"""

from __future__ import annotations

from benchmark.families import deepseek_v3, gpt2, olmoe


class Family:
    def __init__(self, config: dict):
        self.config = c = config
        self.n_layer = c["num_hidden_layers"]
        self.first_layer = c["first_layer"]
        self.n_head = c["num_attention_heads"]
        self.n_embd = c["hidden_size"]
        self.head_dim = c["head_dim"]
        self.width = c["intermediate_size"]
        self.chunk = c["chunk_size"]
        self.window = c["window_size"]
        self.n_pred = c["num_pred_heads"]
        self.rows = c["vocab_size"]
        self.mesh = None

    def published(self, i: int) -> int:
        return self.first_layer + i

    # -- counts: pure functions of the shapes, no jax ----------------------

    def mixer_matrices(self) -> int:
        """W_q, W_k, W_v and W_o at the heads held."""
        return 4 * self.n_embd * self.n_head * self.head_dim

    def param_count(self) -> int:
        """Every leaf held here: the matrices, phi and mu, the norms' w."""
        e = self.n_embd
        layer = (self.mixer_matrices() + 2 * self.n_head * self.head_dim
                 + 3 * e * self.width + 2 * e)
        return self.n_layer * layer + (1 + self.n_pred) * self.rows * e + e

    def local_pairs(self, seq: int) -> int:
        """(query, key) pairs of a sequence and head inside the queries'
        own windows: |A_i| summed, a triangle a window."""
        return (seq // self.window) * self.window * (self.window + 1) // 2

    def remote_pairs(self, seq: int) -> int:
        """(query, summary) pairs of a sequence and head: |B_i| summed, the
        earlier windows' `window / chunk` summaries each."""
        n = seq // self.window
        return self.window * (self.window // self.chunk) * n * (n - 1) // 2

    def pool_flops_per_token(self) -> float:
        """Forward operations a token of ONE layer's summaries: k . phi, a k
        and a v, 2 D a head each.  Copied from
        `ray_tpu.models.evabyte.pool_flops_per_token`."""
        return self.n_head * 6 * self.head_dim

    def flops_per_token(self, seq: int) -> float:
        """6 N + the attended pairs + the pooling: N what a token multiplies
        HERE (a layer's four attention matrices at the heads held, the
        feed-forward's three whole; the eight heads' matrix); per layer the
        products over |A_i| + |B_i| pairs (1,024.5 + 448 a query at S =
        16,384; QK' and PV forward once and backward twice, 2 D a pair and
        head each) and the summaries forward once and backward twice.
        Recomputation not counted.  Copied from
        `ray_tpu.models.evabyte.count_flops_per_token`."""
        e, h, d = self.n_embd, self.n_head, self.head_dim
        n = self.n_layer * (self.mixer_matrices() + 3 * e * self.width) \
            + self.n_pred * self.rows * e
        pairs = (self.local_pairs(seq) + self.remote_pairs(seq)) / seq
        return 6 * n + self.n_layer * (6 * pairs * h * 2 * d
                                       + 3 * self.pool_flops_per_token())

    _width_bytes = deepseek_v3.Family._width_bytes

    def _pairs_cost(self, batch: int, pairs: int) -> float:
        """Operations of attention over ``pairs`` (query, key) pairs a head
        and sequence, all layers: forward QK' and PV, backward dQ, dK, dV
        and dP, 2 D each a pair; what a kernel recomputes is not counted."""
        return self.n_layer * batch * self.n_head * pairs * 12 * self.head_dim

    def attention_cost(self, batch: int, seq: int) -> dict:
        """What one step's LOCAL halves must do (the flash kernels under
        `BlockRule(aligned=window)`, which `is_attention_kernel` finds): the
        operations over the windows' triangles; bytes: forward reads q, k, v
        and writes o, backward reads q, k, v, do and writes dq, dk, dv (the
        merged o is read outside the kernel, for delta), the row statistics
        in f32 once each way."""
        heads = batch * seq * self.n_head
        return {"flops": self._pairs_cost(batch, self.local_pairs(seq)),
                "bytes": self.n_layer * (
                    heads * 11 * self.head_dim * self._width_bytes()
                    + 2 * heads * 4)}

    def eva_remote_cost(self, batch: int, seq: int) -> dict:
        """The REMOTE halves (`ray_tpu/ops/eva.py`'s kernel pair) and the
        merge: the operations over (query, summary) pairs; bytes: forward
        reads q and the summaries and writes o2 and its statistics, the
        merge reads o1 and o2 and writes o; backward reads q, do, the
        statistics and the summaries and writes dq and the summaries'
        float32 gradients."""
        heads = batch * seq * self.n_head
        d, b = self.head_dim, self._width_bytes()
        summaries = heads // self.chunk * d
        return {"flops": self._pairs_cost(batch, self.remote_pairs(seq)),
                "bytes": self.n_layer * (
                    heads * 8 * d * b + 4 * heads * 4
                    + summaries * (4 * b + 2 * 4))}

    def eva_summary_cost(self, batch: int, seq: int) -> dict:
        """The pooling kernels (`ray_tpu/ops/eva.py:_pool`): forward k and v
        read once and the summaries written; backward k, v and the
        summaries' float32 gradients read, dk and dv written.  Operations:
        `pool_flops_per_token` forward, twice that backward."""
        tokens = batch * seq
        heads = tokens * self.n_head
        d, b = self.head_dim, self._width_bytes()
        summaries = heads // self.chunk * d
        return {"flops": self.n_layer * 3 * tokens
                * self.pool_flops_per_token(),
                "bytes": self.n_layer * (6 * heads * d * b
                                         + summaries * (2 * b + 2 * 4))}

    _shapes = olmoe.Family._shapes
    _is_custom_call = staticmethod(olmoe.Family._is_custom_call)

    def is_attention_kernel(self, op_name: str) -> bool:
        """A Mosaic kernel whose first result is a head-major array of the
        heads' activations, (B * H, S, D): the flash kernels of the local
        half.  Not the remote half's nor the pooling's, whose results are
        rows as the projections wrote them, (B, S, H D) or (B, S / c, H D)."""
        if not self._is_custom_call(op_name):
            return False
        shapes = self._shapes(op_name)
        return bool(shapes) and len(shapes[0]) == 3 \
            and shapes[0][0] % self.n_head == 0 \
            and shapes[0][2] == self.head_dim

    # -- the system under test: runs in the worker that holds the chips ----

    bind = gpt2.Family.bind
    init_state = gpt2.Family.init_state
    place_batch = gpt2.Family.place_batch

    def model_config(self):
        import jax.numpy as jnp

        from ray_tpu.models.evabyte import EvaByteConfig

        c = self.config
        return EvaByteConfig(
            vocab_size=self.rows, n_layer=self.n_layer,
            first_layer=self.first_layer, n_embd=self.n_embd,
            n_head=self.n_head,
            n_head_published=c["published"]["num_attention_heads"],
            head_dim=self.head_dim, ffn_width=self.width, chunk=self.chunk,
            window=self.window, n_pred_heads=self.n_pred,
            rope_theta=float(c["rope_theta"]), rms_eps=c["rms_norm_eps"],
            init_std=c["init_std"],
            norm_unit_offset=c["norm_add_unit_offset"],
            stream_dtype=jnp.dtype(
                "float32" if c["fp32_skip_add"] else c["compute_dtype"]),
            compute_dtype=jnp.dtype(c["compute_dtype"]), remat=c["remat"],
            loss_chunk_rows=c["loss_chunk_rows"])

    def optimizer(self):
        from benchmark.reference.evabyte import adamw

        return adamw(self.config["optimizer"])

    def _init(self, key):
        from ray_tpu.models import evabyte

        return evabyte.init_params(key, self.model_config())

    def lower_step(self, params, opt_state, batch):
        """The jitted train step, traced and lowered for this state."""
        import jax

        from ray_tpu.models import evabyte
        from ray_tpu.parallel.context import use_mesh

        with use_mesh(self.mesh):
            kept = jax.tree.map(lambda x: x.sharding, (params, opt_state))
            step = jax.jit(
                evabyte.make_train_step(self.model_config(),
                                        self.optimizer()),
                donate_argnums=(0, 1), out_shardings=(*kept, None))
            return step.lower(params, opt_state, batch)

    # -- the plain reference on the same seed and batches ------------------

    def reference_sizes(self):
        from benchmark.reference.evabyte import Sizes

        c = self.config
        return Sizes(
            n_head=self.n_head, chunk=self.chunk, window=self.window,
            n_pred_heads=self.n_pred, rope_theta=float(c["rope_theta"]),
            rms_eps=c["rms_norm_eps"],
            query_block=c["reference"]["query_block"],
            row_block=c["reference"]["row_block"])

    def reference_losses(self, seed: int, batches) -> list:
        """What the system's `out["loss"]` is held to, the first
        len(batches) steps: the eight heads' mean cross-entropy of
        `benchmark/reference/evabyte.py` from the parameters the system's own
        init draws from `seed` (the same heads), on the first bound device.

        Before the steps, the system's own walk
        (`ray_tpu.models.evabyte.hidden`: `layers.trunk`, the kernels, the
        matrices cast once, `remat` as configured) is held to the reference's
        on the first batch's first sequence (`first_streams`), because three
        losses from random weights on uniform random bytes see little of a
        summary attended wrongly: the stream after each of the layers held
        may lie `reference.state_error_max` of the norm of the reference's
        from it at most.  `harness/verdict.py` compares losses and nothing
        else, so a breach is handed to it as reference losses that are not
        numbers, which no loss is within the tolerance of; the line printed
        here says which limit was passed.  All of it is freed on return."""
        import jax
        import numpy as np

        from benchmark.reference import evabyte as reference

        device = self.devices[0]
        batches = jax.device_put(np.stack(batches), device)
        with jax.default_matmul_precision("highest"):
            # the parameters are born on the device in the reference's
            # layout, so no second copy of them waits beside it
            params = jax.jit(lambda key: to_reference(self._init(key)))(
                jax.device_put(jax.random.PRNGKey(seed), device))
        errors = self.first_streams(params, batches[0, 0, :-1])
        with jax.default_matmul_precision("highest"):
            losses = reference.first_losses(
                params, batches, self.reference_sizes(),
                self.config["optimizer"])
        limit = self.config["reference"]["state_error_max"]
        told = ", ".join(f"{self.published(i)} {error:.5f}"
                         for i, error in enumerate(errors))
        print(f"evabyte reference: losses {losses}; sequence 0: the "
              f"system's stream after each published layer, of the norm of "
              f"the reference's from it: {told} (at most {limit})",
              flush=True)
        if not max(errors) <= limit:
            print("NOT CORRECT: evabyte: the system's streams are not the "
                  "reference's (the line above): the reference's losses are "
                  "withheld", flush=True)
            return [float("nan")] * len(losses)
        return losses

    def reference_streams(self, params, inputs):
        """The reference's stream after each layer held on one sequence,
        (layers, seq, E) float32."""
        import jax

        from benchmark.reference import evabyte as reference

        sizes = self.reference_sizes()
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda p, t: reference.streams(p, t, sizes))(
                params, inputs)

    def first_streams(self, params, inputs, want=None) -> list:
        """The system's walk against the reference's on one sequence ->
        [|system - reference| / |reference| of the stream (seq, E) after
        each layer held, Frobenius norms].  ``params`` in the reference's
        layout; ``inputs`` (seq,) int32; ``want``: `reference_streams` of
        them, where a caller has it already.  The system's side is traced as
        its step is (no matmul precision asked for, the matrices cast to the
        compute type once, `remat` as configured)."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import evabyte, layers
        from ray_tpu.util import tracing

        cfg = self.model_config()

        def system(params, inputs):
            _, streams = evabyte.hidden(
                layers.cast_weights(from_reference(params),
                                    cfg.compute_dtype), inputs[None], cfg,
                streams=True)
            return jnp.stack([s[0].astype(jnp.float32) for s in streams])

        norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x), axis=(1, 2)))
        # the step's counters are of the step: these traces add nothing
        with tracing.outside_job():
            if want is None:
                want = self.reference_streams(params, inputs)
            got = jax.jit(system)(params, inputs)
            errors = norm(got - want) / norm(want)
        return [float(e) for e in errors]


_EVA = (("q_proj", "wq"), ("k_proj", "wk"), ("v_proj", "wv"),
        ("o_proj", "wo"))
_FFN = (("gate_proj", "gate"), ("up_proj", "up"), ("down_proj", "down"))


def to_reference(params):
    """The system's parameter tree (`ray_tpu.models.evabyte.init_params`) as
    `benchmark/reference/evabyte.py` reads it: the layers' leaves stacked, a
    leading dim a layer."""
    import jax
    import jax.numpy as jnp

    layers = []
    i = 0
    while f"layer_{i}" in params:
        p = params[f"layer_{i}"]
        layer = {"norm1": p["input_norm"]["scale"],
                 "norm2": p["post_norm"]["scale"],
                 "phi": p["eva"]["phi"], "mu": p["eva"]["mu"]}
        layer.update({ours: p["eva"][theirs]["kernel"]
                      for theirs, ours in _EVA})
        layer.update({ours: p["mlp"][theirs]["kernel"]
                      for theirs, ours in _FFN})
        layers.append(layer)
        i += 1
    return {"embed": params["embed_tokens"]["embedding"],
            "head": params["lm_head"]["kernel"],
            "norm_f": params["norm_f"]["scale"],
            "layers": jax.tree.map(lambda *leaves: jnp.stack(leaves),
                                   *layers)}


def from_reference(params):
    """`to_reference` back: the reference's layout as the system's tree."""
    tree = {"embed_tokens": {"embedding": params["embed"]},
            "lm_head": {"kernel": params["head"]},
            "norm_f": {"scale": params["norm_f"]}}
    stacked = params["layers"]
    for i in range(stacked["norm1"].shape[0]):
        p = {name: leaf[i] for name, leaf in stacked.items()}
        eva = {theirs: {"kernel": p[ours]} for theirs, ours in _EVA}
        eva.update(phi=p["phi"], mu=p["mu"])
        tree[f"layer_{i}"] = {
            "input_norm": {"scale": p["norm1"]},
            "post_norm": {"scale": p["norm2"]},
            "eva": eva,
            "mlp": {theirs: {"kernel": p[ours]} for theirs, ours in _FFN}}
    return tree
