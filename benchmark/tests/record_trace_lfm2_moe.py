"""Records the small trace that `test_lfm2_moe_cell.py` checks the conv
operators' and the attention kernels' readers against.  Run on the machine
with the chip (not under pytest):

    python benchmark/tests/record_trace_lfm2_moe.py [out_dir]

One process, one chip: three steps of an `lfm2_moe` step at small sizes
that keep every kernel of the full-size step (a dense conv layer, a routed
attention layer and a routed conv layer, recomputed; hidden 256 with four
query heads on two key/value heads of 64; three taps; sixteen experts 160
wide of which this chip holds four, two a token, so that the buffer is
shorter than the routed rows; a dense layer 640 wide; sequence 2,048 in
two blocks so that the flash kernels are the long form and the two-kernel
backward; batch 2; 3E = 768 is no other width, as at full size), so the
trace holds what the cell's trace holds in a few hundred kilobytes.  It
also writes `trace_dump_lfm2_moe.txt`: every distinct operation name with
its count and time and what the family takes it for, which is how the
family's predicates were chosen.
"""

import glob
import gzip
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CONFIG = {
    "num_hidden_layers": 3, "num_dense_layers": 1,
    "layer_types": ["conv", "full_attention", "conv"], "hidden_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 2, "conv_L_cache": 3,
    "intermediate_size": 640, "moe_intermediate_size": 160,
    "num_experts": 4, "experts_held": {"first": 4, "of": 16},
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "use_expert_bias": True, "routed_scaling_factor": 1, "vocab_size": 1024,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "norm_eps": 1e-5, "renorm_eps": 1e-6, "bias_update_speed": 0.001,
    "loss_chunk_rows": 2048, "compute_dtype": "bfloat16", "remat": True,
    "layout": {"fsdp": 1},
    "optimizer": {"learning_rate": 1e-4, "b1": 0.9, "b2": 0.999,
                  "eps": 1e-8, "weight_decay": 0.1}}
BATCH, SEQ = 2, 2048


def main():
    out = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        ROOT, "chiprun_out", "trace_fixture")
    os.makedirs(out, exist_ok=True)
    import jax
    import numpy as np

    from benchmark.families.lfm2_moe import Family

    devices = jax.devices()[:1]
    family = Family(CONFIG)
    family.bind(devices)
    params, opt_state = family.init_state(0)
    batch = family.place_batch(np.random.default_rng(0).integers(
        0, 1024, (BATCH, SEQ + 1), dtype=np.int32))
    step = family.lower_step(params, opt_state, batch).compile()
    for _ in range(2):
        params, opt_state, m = step(params, opt_state, batch)
    float(m["loss"])
    raw = os.path.join(out, "raw")
    shutil.rmtree(raw, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(raw, profiler_options=options)
    for _ in range(3):
        params, opt_state, m = step(params, opt_state, batch)
        float(m["loss"])
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(raw, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    name = f"{devices[0].platform}1_lfm2_moe.xplane.pb.gz"
    with open(path, "rb") as src, gzip.open(os.path.join(out, name),
                                            "wb") as dst:
        shutil.copyfileobj(src, dst)
    dump_ops(path, os.path.join(out, "trace_dump_lfm2_moe.txt"), family,
             BATCH * SEQ)
    shutil.rmtree(raw)
    print(name, os.path.getsize(os.path.join(out, name)), "bytes",
          "rows_held", int(m["rows_held"]), "overflowed",
          int(m["moe_overflow_layers"]))


def dump_ops(path, to, family, tokens):
    """Every distinct operation of the first device with count, seconds
    and what the family takes it for."""
    from benchmark.harness import xplane

    ops = {}
    for plane, lines in xplane.load(path):
        if not xplane.DEVICE_PLANE.match(plane):
            continue
        for op, start, end in xplane.leaves(dict(lines).get(
                xplane.OP_LINE, [])):
            n, s = ops.get(op, (0, 0))
            ops[op] = (n + 1, s + end - start)
        break
    with open(to, "w") as f:
        for op, (n, s) in sorted(ops.items(), key=lambda kv: -kv[1][1]):
            kind = "attn" if family.is_attention_kernel(op) else \
                "moe_matmul" if family.is_moe_matmul(op) else \
                "moe" if family.is_moe_op(op, tokens) else \
                "shortconv" if family.is_shortconv_op(op) else ""
            print(f"{s * 1e-6:10.3f} ms {n:5d} {kind:10s} {op}", file=f)


if __name__ == "__main__":
    main()
