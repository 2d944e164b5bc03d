"""The `gpt2` family: how a GPT-2 configuration file becomes the system
under test (`ray_tpu.models.gpt2` under a `ShardingConfig`), the counts the
yardstick needs (operations per token, the attention kernels' operations
and bytes), and the run of the plain reference it is judged against.

Nothing at module level imports jax: the parent process reads the counts
and must stay off the chip.  A configuration names its family in its file;
a new architecture brings a new file here and edits none.
"""

from __future__ import annotations

import math


class Family:
    def __init__(self, config: dict):
        self.config = config
        self.n_layer = config["n_layer"]
        self.n_head = config["n_head"]
        self.n_embd = config["n_embd"]
        self.rows = config["padded_vocab_size"]
        self.mesh = None

    # -- counts: pure functions of the shapes, no jax ----------------------

    def param_count(self) -> int:
        e, l = self.n_embd, self.n_layer
        per_block = 12 * e * e + 13 * e       # 4 matrices, their biases, 2 LN
        return (self.rows * e + self.config["n_positions"] * e
                + l * per_block + 2 * e)

    def flops_per_token(self, seq: int) -> float:
        """Operations the forward and backward passes need per token:
        6 N + 12 L E S (PaLM, appendix B), N the parameters that multiply
        (the blocks' four matrices and the tied head over the rows the
        system holds; the embedding lookup multiplies nothing).  The
        attention term counts the full S x S products, as that formula
        does.  Copied from `ray_tpu.models.gpt2.count_flops_per_token`."""
        n = 12 * self.n_layer * self.n_embd ** 2 + self.rows * self.n_embd
        return 6 * n + 12 * self.n_layer * self.n_embd * seq

    def attention_cost(self, batch: int, seq: int) -> dict:
        """What one training step's attention kernels must do, over all
        layers and all chips: causal attention needs half of each S x S
        product; forward two products (QK', PV), backward four (dV, dP,
        dQ, dK); what a kernel recomputes is not counted.  Bytes: forward
        reads q, k, v and writes o; backward reads q, k, v, o, do and
        writes dq, dk, dv; the compute type's width; the row statistics
        (B, H, S) in f32 once each way."""
        d = self.n_embd // self.n_head
        elems = batch * seq * self.n_head * d
        product = 2 * batch * self.n_head * seq * seq * d
        width = {"bfloat16": 2, "float32": 4}[self.config["compute_dtype"]]
        stats = batch * self.n_head * seq * 4
        return {
            "flops": self.n_layer * 6 * product / 2,
            "bytes": self.n_layer * (12 * elems * width + 2 * stats),
        }

    @staticmethod
    def is_attention_kernel(op_name: str) -> bool:
        """The step's only Mosaic kernels are flash attention's."""
        low = op_name.lower()
        return "custom_call" in low or "custom-call" in low

    # -- the system under test: runs in the worker that holds the chips ----

    def bind(self, devices):
        from ray_tpu.parallel.sharding import ShardingConfig

        self.devices = list(devices)
        self.layout = ShardingConfig(**self.config["layout"])
        self.mesh = self.layout.build_mesh(self.devices)

    def model_config(self):
        import jax.numpy as jnp

        from ray_tpu.models.gpt2 import GPT2Config

        c = self.config
        return GPT2Config(
            vocab_size=c["padded_vocab_size"], block_size=c["n_positions"],
            n_layer=c["n_layer"], n_head=c["n_head"], n_embd=c["n_embd"],
            compute_dtype=jnp.dtype(c["compute_dtype"]), attention="flash",
            remat=c["remat"])

    def optimizer(self):
        from benchmark.reference.gpt2 import adamw

        return adamw(self.config["optimizer"])

    def _init(self, key):
        from ray_tpu.models import gpt2

        return gpt2.init_params(key, self.model_config())

    def init_state(self, seed: int):
        """Parameters born sharded from the seed, and the optimizer's
        state beside them (as `chip_smoke.py`)."""
        import jax

        from ray_tpu.parallel.sharding import param_shardings

        key = jax.random.PRNGKey(seed)
        shardings = param_shardings(jax.eval_shape(self._init, key),
                                    self.layout, self.mesh)
        params = jax.jit(self._init, out_shardings=shardings)(key)
        everywhere = self.layout.named_sharding(self.mesh)
        opt_state = jax.tree.map(
            lambda x: x if x.ndim else jax.device_put(x, everywhere),
            self.optimizer().init(params))
        return params, opt_state

    def place_batch(self, tokens):
        import jax

        return {"tokens": jax.device_put(
            tokens, self.layout.named_sharding(self.mesh, "batch", None))}

    def lower_step(self, params, opt_state, batch):
        """The jitted train step, traced and lowered for this state."""
        import jax

        from ray_tpu.models import gpt2
        from ray_tpu.parallel.context import use_mesh

        with use_mesh(self.mesh):
            kept = jax.tree.map(lambda x: x.sharding, (params, opt_state))
            step = jax.jit(
                gpt2.make_train_step(self.model_config(), self.optimizer()),
                donate_argnums=(0, 1), out_shardings=(*kept, None))
            return step.lower(params, opt_state, batch)

    # -- the plain reference on the same seed and batches ------------------

    def reference_losses(self, seed: int, batches) -> list:
        """Losses of the first len(batches) steps by
        `benchmark/reference/gpt2.py`, from the parameters the system's
        own init draws from `seed`, restacked to the reference's layout
        and spread over the bound devices so that XL's 24.9 GB of float32
        state fit four chips.  All of it is freed on return."""
        import jax
        import numpy as np

        from benchmark.reference import gpt2 as reference

        restacked, shardings, everywhere = self.reference_layout()
        program = reference.losses_program(
            self.n_head, self.config["optimizer"],
            self.config["reference"]["micro_batch"])

        def from_seed(key, tokens):
            # the parameters are born inside the program, so no second
            # copy of them waits outside it
            return program(jax.lax.with_sharding_constraint(
                restacked(key), shardings), tokens)

        with jax.default_matmul_precision("highest"):
            losses = jax.jit(from_seed)(
                jax.device_put(jax.random.PRNGKey(seed), everywhere),
                jax.device_put(np.stack(batches), everywhere))
        return [float(v) for v in losses]

    def reference_layout(self):
        """(key -> the system's initial parameters in the reference's
        stacked layout, where each leaf lives, where the batches live)."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        n = len(self.devices)
        mesh = Mesh(np.array(self.devices), ("d",))

        def spread(leaf, stacked):
            # the widest dimension the devices divide, never the layers'
            dims = [(size, i) for i, size in enumerate(leaf.shape)
                    if size % n == 0 and not (stacked and i == 0)]
            spec = [None] * leaf.ndim
            if n > 1 and dims and leaf.ndim > 1 + stacked:
                spec[max(dims)[1]] = "d"
            return NamedSharding(mesh, P(*spec))

        def restacked(key):
            p = self._init(key)
            blocks = [p[f"h_{i}"] for i in range(self.n_layer)]

            def stack(*path):
                def leaf(b):
                    for k in path:
                        b = b[k]
                    return b
                return jnp.stack([leaf(b) for b in blocks])

            return {
                "wte": p["wte"]["embedding"], "wpe": p["wpe"]["embedding"],
                "lnf_g": p["ln_f"]["scale"], "lnf_b": p["ln_f"]["bias"],
                "blocks": {
                    "ln1_g": stack("ln_1", "scale"),
                    "ln1_b": stack("ln_1", "bias"),
                    "attn_w": stack("attn", "c_attn", "kernel"),
                    "attn_b": stack("attn", "c_attn", "bias"),
                    "proj_w": stack("attn", "c_proj", "kernel"),
                    "proj_b": stack("attn", "c_proj", "bias"),
                    "ln2_g": stack("ln_2", "scale"),
                    "ln2_b": stack("ln_2", "bias"),
                    "fc_w": stack("mlp", "c_fc", "kernel"),
                    "fc_b": stack("mlp", "c_fc", "bias"),
                    "out_w": stack("mlp", "c_proj", "kernel"),
                    "out_b": stack("mlp", "c_proj", "bias"),
                },
            }

        shapes = jax.eval_shape(restacked, jax.random.PRNGKey(0))
        shardings = {k: spread(v, False) for k, v in shapes.items()
                     if k != "blocks"}
        shardings["blocks"] = {k: spread(v, True)
                               for k, v in shapes["blocks"].items()}
        return restacked, shardings, NamedSharding(mesh, P())
