"""ZeRO-Offload scale demo: a GPT-2-1.3B-class model training on ONE 16 GB
chip (reference claim: 13B on one 32 GB V100,
/root/reference/docs/_pages/training.md:77 — same params-per-HBM-byte
class).

Device holds only bf16 params + grads + (full-remat) activations; the fp32
master and Adam moments live in host RAM and the native C++ host optimizer
(csrc/host_ops.cpp) steps them.  Prints ONE JSON line:
  {"params", "steps", "losses", "device_ms", "grad_d2h_ms",
   "host_optimizer_ms", "param_h2d_ms"}

The per-phase breakdown separates device compute from the host link.
"""
from __future__ import annotations

import argparse
import json


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default="1.3b")
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--micro", type=int, default=4)
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args()

    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models import Transformer, gpt2_config

    cfg = gpt2_config(args.size, max_seq_len=args.seq, dtype=jnp.bfloat16,
                      remat=True, tiled_loss_shards=8)
    model = Transformer(cfg)
    engine = dstpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": args.micro,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "zero_optimization": {
            "stage": 2,
            "offload_optimizer": {"device": "cpu"},
        },
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "steps_per_print": 0,
        "activation_checkpointing": {},
    })
    gbs = engine.config.train_batch_size
    rng = np.random.RandomState(0)
    batch = {"input_ids": rng.randint(
        0, cfg.vocab_size, (gbs, args.seq + 1)).astype(np.int32)}

    losses = []
    timings = None
    for _ in range(args.steps):
        m = engine.train_batch(batch)
        losses.append(round(float(m["loss"]), 3))
        timings = dict(engine.last_step_timings)

    row = {"params": model.num_params(), "steps": args.steps,
           "losses": losses}
    row.update({k: round(v, 1) for k, v in (timings or {}).items()})
    print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
