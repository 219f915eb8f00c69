"""Probe the chip's achievable matmul throughput (the real MFU ceiling).

Prints device_kind and measured TFLOP/s for dense bf16/fp32 matmuls at
model-like shapes, a transformer-layer-like matmul chain, and elementwise/
exp VPU passes — the numbers every attention-kernel and MFU analysis in
this repo should be calibrated against (peak specs assume v5e: 197 bf16
TFLOP/s, 819 GB/s HBM).

Usage: python -m deepspeed_tpu.benchmarks.mxu_probe
"""
from __future__ import annotations

import json
import time


def main():
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]

    def sync(out):
        float(jax.tree.leaves(out)[0].ravel()[0].astype(jnp.float32))

    def timed_once(prog, *xs):
        sync(prog(*xs))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            sync(prog(*xs))
            best = min(best, time.perf_counter() - t0)
        return best

    # time each op at two scan lengths, min-of-3 each, and difference
    # them so the fixed per-execution cost cancels; the long scan keeps
    # the signal well above the jitter.
    N_SHORT, N_LONG = 10, 510

    def timed(op, *xs):
        ts = {}
        for n in (N_SHORT, N_LONG):
            def prog(x, *cs, n=n):
                def body(c, _):
                    return op(c, *cs), ()
                c, _ = jax.lax.scan(body, x, None, length=n)
                return c
            ts[n] = timed_once(jax.jit(prog), *xs)
        return (ts[N_LONG] - ts[N_SHORT]) / (N_LONG - N_SHORT)

    rows = []

    # dense matmul, bf16 and fp32, square-ish model shapes
    for dtype, name in ((jnp.bfloat16, "bf16"), (jnp.float32, "fp32")):
        M, K, N = 8192, 1280, 5120
        a = jax.random.normal(jax.random.PRNGKey(0), (M, K), dtype)
        b = jax.random.normal(jax.random.PRNGKey(1), (K, N), dtype)

        def mm(a, b):
            out = jax.lax.dot_general(
                a, b, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return out[:, :K].astype(a.dtype)  # feed back, keep shape

        t = timed(mm, a, b)
        rows.append({"op": f"matmul_{name}_{M}x{K}x{N}",
                     "ms": round(t * 1e3, 3),
                     "tflops": round(2 * M * K * N / t / 1e12, 1)})

    # attention-shaped matmuls: [512,64]x[64,512] (QK^T) and
    # [512,512]x[512,64] (PV) chained, bf16
    bq = bk = 512
    D = 64
    q = jax.random.normal(jax.random.PRNGKey(2), (bq, D), jnp.bfloat16)
    kT = jax.random.normal(jax.random.PRNGKey(3), (D, bk), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(4), (bk, D), jnp.bfloat16)

    def attn_mm(q, kT, v):
        s = jax.lax.dot_general(q, kT, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        o = jax.lax.dot_general(s.astype(jnp.bfloat16), v,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return o.astype(jnp.bfloat16)

    t = timed(attn_mm, q, kT, v)
    fl = 2 * bq * D * bk + 2 * bq * bk * D
    rows.append({"op": f"attn_pair_bf16_{bq}x{D}x{bk}",
                 "ms": round(t * 1e3, 4),
                 "tflops": round(fl / t / 1e12, 1)})

    # VPU: exp over [8192, 512] fp32 (softmax-like traffic)
    x = jax.random.normal(jax.random.PRNGKey(5), (8192, 512), jnp.float32)

    def expop(x):
        return jnp.exp(x) * 1e-3

    t = timed(expop, x)
    rows.append({"op": "exp_8192x512_fp32", "ms": round(t * 1e3, 3),
                 "gelem_s": round(x.size / t / 1e9, 1)})

    # HBM: big copy-scale (bandwidth probe), 256 MB fp32
    y = jax.random.normal(jax.random.PRNGKey(6), (64 * 1024 * 1024,),
                          jnp.float32)

    def scale(y):
        return y * 1.0000001

    t = timed(scale, y)
    rows.append({"op": "scale_256MB_fp32", "ms": round(t * 1e3, 3),
                 "gb_s": round(2 * y.nbytes / t / 1e9, 1)})

    print(json.dumps({"device_kind": dev.device_kind,
                      "platform": dev.platform, "rows": rows}), flush=True)


if __name__ == "__main__":
    main()
