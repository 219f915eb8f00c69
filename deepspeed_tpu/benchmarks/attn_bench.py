"""Microbench: flash-attention kernel efficiency at the training-bench
geometry (GPT-2-large: NH=20, D=64; micro 8, seq 1024 by default).

Times fwd and fwd+bwd for impl=pallas vs impl=jnp (dense XLA) and prints
achieved TFLOP/s and fraction of the v5e bf16 peak, so the training-MFU
decomposition can attribute step time to the attention kernels precisely.

Measurement note: one host dispatch costs more than one attention call, so
the N timed iterations run INSIDE one compiled program as a lax.scan whose
carry feeds q (serializing the calls); wall time / N is then kernel time
plus only 1/N of the dispatch cost.

Usage: python -m deepspeed_tpu.benchmarks.attn_bench [--seq 1024] [--batch 8]
"""
from __future__ import annotations

import argparse
import json
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--heads", type=int, default=20)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--iters", type=int, default=100)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.attention import causal_attention

    B, S, N, D = args.batch, args.seq, args.heads, args.dim
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, N, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, N, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, N, D), jnp.bfloat16)

    # causal attention does ~half the full S^2 work; count the work the
    # kernel actually performs (0.5 * 4*S^2*D per head-batch fwd) so the
    # efficiency number reflects the kernel, not the convention.
    fwd_flops = 0.5 * 4 * B * N * S * S * D
    from ..utils.device import device_peaks
    peak = device_peaks()["bf16_flops"]

    sync = jax.block_until_ready

    def timed_once(prog, *xs):
        sync(prog(*xs))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            sync(prog(*xs))
            best = min(best, time.perf_counter() - t0)
        return best

    # time at two scan lengths, min-of-3 each, and take the difference, so
    # the fixed per-execution cost cancels
    N_SHORT, N_LONG = 10, 10 + args.iters

    def timed(make_prog, *xs):
        ts = {}
        for n in (N_SHORT, N_LONG):
            ts[n] = timed_once(jax.jit(make_prog(n)), *xs)
        return (ts[N_LONG] - ts[N_SHORT]) / (N_LONG - N_SHORT)

    rows = []
    for impl in ("pallas", "jnp", "jax_flash", "jax_splash"):
        if impl == "jax_flash":
            import math
            from jax.experimental.pallas.ops.tpu.flash_attention import (
                flash_attention as jf)

            def attn(qq, kk, vv):
                o = jf(qq.transpose(0, 2, 1, 3), kk.transpose(0, 2, 1, 3),
                       vv.transpose(0, 2, 1, 3), causal=True,
                       sm_scale=1.0 / math.sqrt(D))
                return o.transpose(0, 2, 1, 3)
        elif impl == "jax_splash":
            import math
            from jax.experimental.pallas.ops.tpu.splash_attention import (
                splash_attention_kernel as sk,
                splash_attention_mask as sm)

            mask = sm.MultiHeadMask(
                [sm.CausalMask((S, S)) for _ in range(N)])
            kern = sk.make_splash_mha(
                mask=mask, head_shards=1, q_seq_shards=1)

            def attn(qq, kk, vv):
                scale = 1.0 / math.sqrt(D)
                o = jax.vmap(kern)((qq * scale).transpose(0, 2, 1, 3),
                                   kk.transpose(0, 2, 1, 3),
                                   vv.transpose(0, 2, 1, 3))
                return o.transpose(0, 2, 1, 3)
        else:
            def attn(qq, kk, vv, impl=impl):
                return causal_attention(qq, kk, vv, impl=impl)

        def fwd_many(n):
            def prog(q, k, v):
                def body(c, _):
                    o = attn(c, k, v)
                    return (q + 0.01 * o).astype(q.dtype), ()
                c, _ = jax.lax.scan(body, q, None, length=n)
                return c
            return prog

        def g_many(n):
            def prog(q, k, v):
                def loss(qq, kk, vv):
                    return attn(qq, kk, vv).astype(jnp.float32).sum()
                def body(c, _):
                    # differentiate wrt ALL inputs: grad wrt q alone lets
                    # DCE drop the dk/dv kernel and under-reports the
                    # backward; fold every grad into the carry so none is
                    # dead
                    gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(c, k, v)
                    upd = gq + gk + gv
                    return (q + 1e-6 * upd).astype(q.dtype), ()
                c, _ = jax.lax.scan(body, q, None, length=n)
                return c
            return prog

        try:
            t_f = timed(fwd_many, q, k, v)
            t_g = timed(g_many, q, k, v)
        except Exception as e:  # pallas unavailable off-TPU
            rows.append({"impl": impl, "error": str(e)[:120]})
            continue
        rows.append({
            "impl": impl,
            "fwd_ms": round(t_f * 1e3, 3),
            "fwd_tflops": round(fwd_flops / t_f / 1e12, 1),
            "fwd_pct_peak": round(fwd_flops / t_f / peak * 100, 1),
            "fwdbwd_ms": round(t_g * 1e3, 3),
            "fwdbwd_tflops": round(3.5 * fwd_flops / t_g / 1e12, 1),
            "fwdbwd_pct_peak": round(3.5 * fwd_flops / t_g / peak * 100, 1),
        })
    print(json.dumps({"geom": [B, S, N, D], "rows": rows}), flush=True)


if __name__ == "__main__":
    main()
