"""The `phi4flash` family: how a Phi-4-mini-flash configuration file (the
keys of the model's published `config.json`) becomes the system under test
(`ray_tpu.models.phi4flash` under a `ShardingConfig`), the counts the
yardstick needs (operations per token; the attention kernels' and the
selective scans' operations and bytes; which of a trace's kernels are
attention's), and the run of the plain reference it is judged against.

A configuration of this family is one pipeline stage's layers,
`num_hidden_layers` of them from the published index `first_layer` on; a
layer's kind and lambda0 go by its published index
(`ray_tpu.models.phi4flash.Phi4FlashConfig.kind`).  `vocab_size` is the
slice of the tied vocabulary the tokens are drawn from (the rows of the
embedding held here, which is also the head).

Nothing at module level imports jax: the parent process reads the counts
and must stay off the chip.
"""

from __future__ import annotations

import math

from benchmark.families import deepseek_v3, gpt2, olmoe

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"
ATTENDING = (WINDOW, FULL, CROSS)


class Family:
    def __init__(self, config: dict):
        self.config = c = config
        self.n_layer = c["num_hidden_layers"]
        self.first_layer = c["first_layer"]
        self.n_published = c["published"]["num_hidden_layers"]
        self.n_embd = c["hidden_size"]
        self.n_head = c["num_attention_heads"]
        self.n_kv_head = c["num_key_value_heads"]
        self.head_dim = self.n_embd // self.n_head
        self.dense_width = c["intermediate_size"]
        self.window = c["sliding_window"]
        assert c["mb_per_layer"] == 2, "a Mamba-1 mixer every second layer"
        s = c["assumed"]["sizes"]
        self.d_state, self.d_conv = s["d_state"], s["d_conv"]
        self.expand, self.dt_rank = s["expand"], s["dt_rank"]
        self.channels = self.expand * self.n_embd
        self.rows = c["vocab_size"]
        self.mesh = None

    def kind(self, i: int) -> str:
        """The kind of the layer held at ``i`` (as the model's config)."""
        at, middle = self.first_layer + i, self.n_published // 2
        if at % 2 == 0:
            return MAMBA if at <= middle else GMU
        return WINDOW if at < middle + 1 else \
            FULL if at == middle + 1 else CROSS

    @property
    def kinds(self) -> list:
        return [self.kind(i) for i in range(self.n_layer)]

    def lambda_init(self, i: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * (self.first_layer + i))

    # -- counts: pure functions of the shapes, no jax ----------------------

    def mixer_matrices(self, kind: str) -> int:
        """The parameters a token multiplies in a layer's mixer."""
        e, c, d = self.n_embd, self.channels, self.head_dim
        if kind == MAMBA:
            return (e * 2 * c + c * self.d_conv
                    + c * (self.dt_rank + 2 * self.d_state)
                    + self.dt_rank * c + c * e)
        if kind == GMU:
            return 2 * e * c
        if kind == CROSS:
            return 2 * e * self.n_head * d
        return 2 * e * self.n_head * d + 2 * e * self.n_kv_head * d

    def mixer_vectors(self, kind: str) -> int:
        """A mixer's leaves that are no matrix: biases, A_log, D, the lambda
        vectors and the gain over a pair."""
        e, c, d = self.n_embd, self.channels, self.head_dim
        if kind == MAMBA:       # the conv's bias, dt's, A_log, D
            return c + c + c * self.d_state + c
        if kind == GMU:
            return 0
        diff = 4 * d + 2 * d + e                # lambdas, gain, W_o's bias
        if kind == CROSS:
            return diff + self.n_head * d
        return diff + (self.n_head + 2 * self.n_kv_head) * d

    def param_count(self) -> int:
        """Every leaf held here: the tied embedding once, the final norm's
        gain and bias, a layer's two norms, feed-forward and mixer."""
        e = self.n_embd
        return self.rows * e + 2 * e + sum(
            4 * e + 3 * e * self.dense_width + self.mixer_matrices(k)
            + self.mixer_vectors(k) for k in self.kinds)

    def attended_pairs(self, seq: int, kind: str) -> int:
        """(query, key) pairs a sequence and head under a layer's rule."""
        w = self.window if kind == WINDOW else None
        if w is None or w >= seq:
            return seq * (seq + 1) // 2
        return w * (w + 1) // 2 + (seq - w) * w

    def scan_flops_per_token(self) -> float:
        """Forward operations a token of ONE layer's recurrence, a multiply
        and an add two: dt A, the decay times the state and the input's
        outer product added, the sum with C_t (C N pairs each), dt u and
        D u.  Copied from `ray_tpu.models.phi4flash.scan_flops_per_token`."""
        return 6 * self.channels * self.d_state + 3 * self.channels

    def flops_per_token(self, seq: int) -> float:
        """6 N + the attention products over the pairs each layer's rule
        attends (two score maps a pair of heads at d, two value products at
        2 d, forward once and backward twice) + the recurrences forward
        once and backward twice.  Recomputation not counted.  Copied from
        `ray_tpu.models.phi4flash.count_flops_per_token`."""
        e, d = self.n_embd, self.head_dim
        n = self.rows * e + sum(
            self.mixer_matrices(k) + 3 * e * self.dense_width
            for k in self.kinds)
        pairs = sum(self.attended_pairs(seq, k) / seq
                    for k in self.kinds if k in ATTENDING)
        return (6 * n + 6 * pairs * (self.n_head // 2) * 2 * (d + 2 * d)
                + 3 * self.kinds.count(MAMBA) * self.scan_flops_per_token())

    def attention_cost(self, batch: int, seq: int) -> dict:
        """What the flash kernels must do over the ATTENDED pairs, two
        calls a layer of H / 2 query heads each, whatever tiles a kernel
        visits: a call's QK' (d deep) and PV (2 d) forward, dQ and dK (d)
        and dV and dP (2 d) backward, 2 x depth operations a pair and head.
        Bytes a call: forward reads q (H / 2 heads of d), k (H_kv / 2 of d)
        and v (H_kv / 2 of 2 d) and writes o (H / 2 of 2 d); backward reads
        those and do and writes dq, dk, dv; the row statistics (B, H / 2,
        seq) float32 once each way.  A cross layer's k and v are read as a
        self layer's.  What a kernel recomputes is not counted."""
        d, h, hkv = self.head_dim, self.n_head // 2, self.n_kv_head // 2
        pairs = sum(self.attended_pairs(seq, k)
                    for k in self.kinds if k in ATTENDING)
        calls = 2 * sum(k in ATTENDING for k in self.kinds)
        q, o = h * d, h * 2 * d
        k, v = hkv * d, hkv * 2 * d
        elems = batch * seq * ((q + k + v + o)             # forward
                               + (q + k + v + o + o)       # backward reads
                               + (q + k + v))              # and writes
        stats = batch * h * seq * 4
        return {"flops": 2 * batch * pairs * h * 3 * 2 * (d + 2 * d),
                "bytes": calls * (elems * self._width_bytes() + 2 * stats)}

    def selective_scan_cost(self, batch: int, seq: int) -> dict:
        """What one training step's Mamba-1 recurrences must do, whatever
        implements them; recomputation not counted.  Operations:
        `scan_flops_per_token` forward, twice that backward.  Bytes a token
        and layer: the forward reads u (C) and B and C (2 N) in the compute
        type and dt (C, float32) and writes y (C); the backward reads those
        and dy and writes du, d dt, dB and dC."""
        tokens = batch * seq
        b = self._width_bytes()
        c, n = self.channels, self.d_state
        read = (c + 2 * n) * b + c * 4
        forward = read + c * b
        backward = read + c * b + read
        layers = self.kinds.count(MAMBA)
        return {"flops": layers * 3 * tokens * self.scan_flops_per_token(),
                "bytes": layers * tokens * (forward + backward)}

    def shared_bytes(self, batch: int, seq: int) -> int:
        """What the makers held here hand on, in the compute type: the
        middle layer's y (C a token), the full layer's k and v."""
        kinds = self.kinds
        per_token = (self.channels if GMU in kinds else 0) \
            + (2 * self.n_kv_head * self.head_dim if CROSS in kinds else 0)
        return batch * seq * per_token * self._width_bytes()

    _width_bytes = deepseek_v3.Family._width_bytes
    _shapes = olmoe.Family._shapes
    _is_custom_call = staticmethod(olmoe.Family._is_custom_call)

    def is_attention_kernel(self, op_name: str) -> bool:
        """A Mosaic kernel whose first result is a head-major array of the
        heads' activations or gradients, (B * heads, S, d or 2 d): neither
        the convolution's (B, S, C) nor the scan's (B, S, 8, C / 8)."""
        if not self._is_custom_call(op_name):
            return False
        shapes = self._shapes(op_name)
        return bool(shapes) and len(shapes[0]) == 3 \
            and shapes[0][2] in (self.head_dim, 2 * self.head_dim)

    # -- the system under test: runs in the worker that holds the chips ----

    bind = gpt2.Family.bind
    init_state = gpt2.Family.init_state
    place_batch = gpt2.Family.place_batch

    def model_config(self):
        import jax.numpy as jnp

        from ray_tpu.models.phi4flash import Phi4FlashConfig

        c = self.config
        return Phi4FlashConfig(
            vocab_size=self.rows, n_layer=self.n_layer,
            first_layer=self.first_layer, n_published=self.n_published,
            n_embd=self.n_embd, n_head=self.n_head,
            n_kv_head=self.n_kv_head, head_dim=self.head_dim,
            dense_width=self.dense_width, window=self.window,
            d_state=self.d_state, d_conv=self.d_conv, expand=self.expand,
            dt_rank=self.dt_rank, norm_eps=c["layer_norm_eps"],
            rms_eps=c["layer_norm_eps"],
            compute_dtype=jnp.dtype(c["compute_dtype"]), remat=c["remat"],
            loss_chunk_rows=c["loss_chunk_rows"])

    def optimizer(self):
        """AdamW over every leaf."""
        from benchmark.reference.phi4flash import adamw

        return adamw(self.config["optimizer"])

    def _init(self, key):
        from ray_tpu.models import phi4flash

        return phi4flash.init_params(key, self.model_config())

    def lower_step(self, params, opt_state, batch):
        """The jitted train step, traced and lowered for this state."""
        import jax

        from ray_tpu.models import phi4flash
        from ray_tpu.parallel.context import use_mesh

        with use_mesh(self.mesh):
            kept = jax.tree.map(lambda x: x.sharding, (params, opt_state))
            step = jax.jit(
                phi4flash.make_train_step(self.model_config(),
                                          self.optimizer()),
                donate_argnums=(0, 1), out_shardings=(*kept, None))
            return step.lower(params, opt_state, batch)

    # -- the plain reference on the same seed and batches ------------------

    def reference_sizes(self):
        from benchmark.reference.phi4flash import Sizes

        c = self.config
        return Sizes(
            kinds=tuple(self.kinds),
            lambdas=tuple(self.lambda_init(i) for i in range(self.n_layer)),
            n_head=self.n_head, n_kv_head=self.n_kv_head,
            window=self.window, d_state=self.d_state, dt_rank=self.dt_rank,
            norm_eps=c["layer_norm_eps"], rms_eps=c["layer_norm_eps"],
            query_block=c["reference"]["query_block"],
            scan_block=c["reference"]["scan_block"],
            row_block=c["reference"]["row_block"])

    def reference_losses(self, seed: int, batches) -> list:
        """What the system's `out["loss"]` is held to, the first
        len(batches) steps: the cross-entropy of
        `benchmark/reference/phi4flash.py` from the parameters the system's
        own init draws from `seed`, on the first bound device.

        Before the steps, the system's own walk (`ray_tpu.models.phi4flash.
        hidden`: `layers.trunk` with what the layers hand on, the kernels,
        the matrices cast once, `remat` as configured) is held to the
        reference's on the first batch's first sequence (`first_streams`),
        because three losses from random weights see little of one mixer of
        six: the stream after each of the layers held may lie
        `reference.state_error_max` of the norm of the reference's from it
        at most.  `harness/verdict.py` compares losses and nothing else, so
        a breach is handed to it as reference losses that are not numbers,
        which no loss is within the tolerance of; the line printed here says
        which limit was passed.  All of it is freed on return."""
        import jax
        import numpy as np

        from benchmark.reference import phi4flash as reference

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
        told = ", ".join(f"{self.first_layer + i} ({kind}) {error:.5f}"
                         for i, (kind, error) in
                         enumerate(zip(self.kinds, errors)))
        print(f"phi4flash reference: losses {losses}; sequence 0: the "
              f"system's stream after each published layer, of the norm of "
              f"the reference's from it: {told} (at most {limit})",
              flush=True)
        if not max(errors) <= limit:
            print("NOT CORRECT: phi4flash: the system's streams are not the "
                  "reference's (the line above): the reference's losses are "
                  "withheld", flush=True)
            return [float("nan")] * len(losses)
        return losses

    def reference_streams(self, params, inputs):
        """The reference's stream after each layer held on one sequence,
        (layers, seq, E) float32."""
        import jax
        import jax.numpy as jnp

        from benchmark.reference import phi4flash as reference

        sizes = self.reference_sizes()
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda p, t: jnp.stack(
                reference.streams(p, t, sizes)))(params, inputs)

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

        from ray_tpu.models import layers, phi4flash
        from ray_tpu.util import tracing

        kinds, cfg = tuple(self.kinds), self.model_config()

        def system(params, inputs):
            _, streams = phi4flash.hidden(
                layers.cast_weights(from_reference(params, kinds),
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


_NORMS = (("norm1", "ln1"), ("norm2", "ln2"))
_FFN = (("gate_proj", "w_gate"), ("up_proj", "w_up"), ("down_proj", "w_down"))
_LAMBDAS = (("lambda_q1", "lq1"), ("lambda_k1", "lk1"),
            ("lambda_q2", "lq2"), ("lambda_k2", "lk2"))


def to_reference(params):
    """The system's parameter tree (`ray_tpu.models.phi4flash.init_params`)
    as `benchmark/reference/phi4flash.py` reads it."""
    norm = lambda p: {"g": p["scale"], "b": p["bias"]}
    layers = []
    i = 0
    while f"layer_{i}" in params:
        p = params[f"layer_{i}"]
        layer = {ours: norm(p[theirs]) for theirs, ours in _NORMS}
        layer.update({ours: p["mlp"][theirs]["kernel"]
                      for theirs, ours in _FFN})
        if MAMBA in p:
            m = p[MAMBA]
            layer.update({
                "w_in": m["in_proj"]["kernel"], "taps": m["conv"]["kernel"],
                "conv_bias": m["conv"]["bias"],
                "w_x": m["x_proj"]["kernel"], "w_dt": m["dt_proj"]["kernel"],
                "dt_bias": m["dt_proj"]["bias"], "a_log": m["A_log"],
                "d": m["D"], "w_out": m["out_proj"]["kernel"]})
        elif GMU in p:
            layer.update({"w_1": p[GMU]["in_proj"]["kernel"],
                          "w_2": p[GMU]["out_proj"]["kernel"]})
        else:
            (kind,) = set(p) & set(ATTENDING)
            a = p[kind]
            for name in "qkvo" if kind != CROSS else "qo":
                layer[f"w{name}"] = a[f"{name}_proj"]["kernel"]
                layer[f"b{name}"] = a[f"{name}_proj"]["bias"]
            layer.update({ours: a[theirs] for theirs, ours in _LAMBDAS})
            layer["gain"] = a["diff_norm"]["scale"]
        layers.append(layer)
        i += 1
    return {"embed": params["embed_tokens"]["embedding"],
            "norm_f": norm(params["norm_f"]), "layers": layers}


def from_reference(params, kinds):
    """`to_reference` back: the reference's layout as the system's tree;
    ``kinds`` names each layer's mixer."""
    norm = lambda p: {"scale": p["g"], "bias": p["b"]}
    tree = {"embed_tokens": {"embedding": params["embed"]},
            "norm_f": norm(params["norm_f"])}
    for i, (p, kind) in enumerate(zip(params["layers"], kinds)):
        layer = {theirs: norm(p[ours]) for theirs, ours in _NORMS}
        layer["mlp"] = {theirs: {"kernel": p[ours]} for theirs, ours in _FFN}
        if kind == MAMBA:
            layer[MAMBA] = {
                "in_proj": {"kernel": p["w_in"]},
                "conv": {"kernel": p["taps"], "bias": p["conv_bias"]},
                "x_proj": {"kernel": p["w_x"]},
                "dt_proj": {"kernel": p["w_dt"], "bias": p["dt_bias"]},
                "A_log": p["a_log"], "D": p["d"],
                "out_proj": {"kernel": p["w_out"]}}
        elif kind == GMU:
            layer[GMU] = {"in_proj": {"kernel": p["w_1"]},
                          "out_proj": {"kernel": p["w_2"]}}
        else:
            a = {f"{name}_proj": {"kernel": p[f"w{name}"],
                                  "bias": p[f"b{name}"]}
                 for name in ("qkvo" if kind != CROSS else "qo")}
            a.update({theirs: p[ours] for theirs, ours in _LAMBDAS})
            a["diff_norm"] = {"scale": p["gain"]}
            layer[kind] = a
        tree[f"layer_{i}"] = layer
    return tree
