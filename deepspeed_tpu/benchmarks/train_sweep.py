"""One-config training-throughput probe for the bench sweep.

Run ONE configuration per fresh process (a chip belongs to one process,
and an OOM ends it), print ONE JSON line on stdout:

    python -u -m deepspeed_tpu.benchmarks.train_sweep \
        --micro 8 --policy save_attn_proj --state-dtype bf16 \
        --grad-dtype bf16 [--size large] [--seq 1024] [--steps 10]

Used to find the bench.py config; see bench.py module docstring for the
sweep history.
"""
from __future__ import annotations

import argparse
import json
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", default="gpt2")  # gpt2 | llama
    ap.add_argument("--size", default="large")
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--micro", type=int, default=8)
    ap.add_argument("--gas", type=int, default=1)
    ap.add_argument("--policy", default="none")  # none = full remat
    ap.add_argument("--state-dtype", default=None)
    ap.add_argument("--grad-dtype", default=None)
    ap.add_argument("--tiled-loss", type=int, default=8)
    ap.add_argument("--unroll", type=int, default=1)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--heads", type=int, default=None)    # override: D=h/heads
    ap.add_argument("--kv-heads", type=int, default=None)
    ap.add_argument("--attn-impl", default="auto")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models import Transformer, gpt2_config, llama_config

    kw = dict(max_seq_len=args.seq, dtype=jnp.bfloat16, remat=True,
              tiled_loss_shards=args.tiled_loss, scan_unroll=args.unroll,
              attn_impl=args.attn_impl)
    if args.heads:
        kw["num_heads"] = args.heads
    if args.kv_heads:
        kw["num_kv_heads"] = args.kv_heads
    mk = {"gpt2": gpt2_config, "llama": llama_config}[args.family]
    cfg = mk(args.size, **kw)
    model = Transformer(cfg)
    opt_params = {"lr": 1e-4, "weight_decay": 0.1}
    if args.state_dtype:
        opt_params["state_dtype"] = args.state_dtype
    ds_config = {
        "train_micro_batch_size_per_gpu": args.micro,
        "gradient_accumulation_steps": args.gas,
        "optimizer": {"type": "adamw", "params": opt_params},
        "zero_optimization": {"stage": 1},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "steps_per_print": 0,
        "activation_checkpointing": {"policy": args.policy},
    }
    if args.grad_dtype:
        ds_config["data_types"] = {"grad_accum_dtype": args.grad_dtype}
    engine = dstpu.initialize(model=model, config=ds_config)

    gbs = engine.config.train_batch_size
    rng = np.random.RandomState(0)
    batch = {"input_ids": rng.randint(
        0, cfg.vocab_size, (gbs, args.seq + 1)).astype(np.int32)}

    for _ in range(3):
        float(engine.train_batch(batch)["loss"])
    t0 = time.perf_counter()
    for _ in range(args.steps):
        m = engine.train_batch(batch)
    jax.block_until_ready(m["loss"])
    dt = time.perf_counter() - t0

    tok_s = gbs * args.seq * args.steps / dt / len(jax.devices())
    n_params = model.num_params()
    flops_per_token = 6 * n_params + 12 * cfg.num_layers * cfg.hidden_size * args.seq
    from ..utils.device import device_peaks
    mfu = tok_s * flops_per_token / device_peaks()["bf16_flops"]
    print(json.dumps({
        "family": args.family, "size": args.size,
        "heads": cfg.num_heads, "head_dim": cfg.hidden_size // cfg.num_heads,
        "micro": args.micro, "policy": args.policy,
        "state_dtype": args.state_dtype, "grad_dtype": args.grad_dtype,
        "seq": args.seq, "gas": args.gas, "params": model.num_params(),
        "tok_s_chip": round(tok_s, 1), "mfu": round(mfu, 4),
    }), flush=True)


if __name__ == "__main__":
    main()
