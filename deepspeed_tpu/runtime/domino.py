"""Domino — tensor parallelism with communication hidden behind compute.

Reference: `runtime/domino/transformer.py` — `DominoTransformer` :411 splits
each batch into two μ-batches and interleaves their execution so the TP
AllReduce of μ-batch 0's attention overlaps μ-batch 1's attention compute
(and so on through the MLP), hiding up to the ~43% of iteration time TP
comm costs on the reference hardware (blogs/deepspeed-domino).

TPU-first: the same interleaving, expressed as *dataflow* instead of CUDA
streams.  Inside `shard_map`, each μ-batch's row-parallel matmul ends in its
own `psum`; because the two μ-batches share no data edges, XLA's
latency-hiding scheduler turns each psum into async collective-start /
collective-done pairs and slides the other μ-batch's matmuls between them —
the scheduler does what Domino's hand-rolled `no_operation_+_cuda_sync`
stream juggling does, provably deadlock-free.

Layout notes: weights arrive TP-pre-sharded ([H, O/tp] column, [I/tp, H]
row) as shard_map sees local shards; qkv column-parallel means NH % tp == 0.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional

import jax
from jax import shard_map
from jax.lax import axis_size
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any


def _dense(x, w, b=None):
    y = jnp.einsum("bsh,hd->bsd", x, w.astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)
    if b is not None:
        y = y + b.astype(x.dtype)
    return y


def _layernorm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * scale + bias
    return out.astype(x.dtype)


def _attn_local(x, lp, num_heads_local: int):
    """Local-TP attention: column-parallel qkv (local heads), causal SDPA,
    row-parallel out-proj partial product (psum'd by the caller)."""
    B, S, H = x.shape
    q = _dense(x, lp["wq"])
    k = _dense(x, lp["wk"])
    v = _dense(x, lp["wv"])
    D = q.shape[-1] // num_heads_local
    q = q.reshape(B, S, num_heads_local, D)
    k = k.reshape(B, S, num_heads_local, D)
    v = v.reshape(B, S, num_heads_local, D)
    s = jnp.einsum("bqnd,bknd->bnqk", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    mask = jnp.tril(jnp.ones((S, S), jnp.bool_))
    s = jnp.where(mask[None, None], s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bnqk,bknd->bqnd", p, v).reshape(B, S, -1)
    return _dense(o, lp["wo"])          # partial: needs psum over tp


def _mlp_local(x, lp):
    h = _dense(x, lp["w_up"])           # column-parallel
    h = jax.nn.gelu(h.astype(jnp.float32), approximate=True).astype(x.dtype)
    return _dense(h, lp["w_down"])      # partial: needs psum over tp


def domino_layer(x, lp, axis_name: str, num_heads: int,
                 num_micro: int = 2):
    """One TP transformer block over `num_micro` interleaved μ-batches.

    x: [B, S, H] local (B replicated or dp-sharded outside); weights are the
    *local TP shards*.  Returns [B, S, H]."""
    tp = axis_size(axis_name)
    nh_local = num_heads // tp
    B = x.shape[0]
    assert B % num_micro == 0, (B, num_micro)
    chunks = jnp.split(x, num_micro, axis=0)

    # --- attention phase: launch each μ-batch's psum, then immediately
    # start the next μ-batch's compute; XLA overlaps the in-flight
    # collectives with it (the Domino interleave) ---
    normed = [_layernorm(c, lp["ln1_scale"], lp["ln1_bias"]) for c in chunks]
    partials = []
    for i in range(num_micro):
        part = _attn_local(normed[i], lp, nh_local)
        partials.append(jax.lax.psum(part, axis_name))
    attn_out = [chunks[i] + partials[i] for i in range(num_micro)]

    # --- mlp phase, same interleave ---
    normed2 = [_layernorm(c, lp["ln2_scale"], lp["ln2_bias"]) for c in attn_out]
    out = []
    for i in range(num_micro):
        part = _mlp_local(normed2[i], lp)
        out.append(attn_out[i] + jax.lax.psum(part, axis_name))
    return jnp.concatenate(out, axis=0)


class DominoTransformer:
    """Stacked Domino TP transformer (reference class name, :411).

    Owns TP-sharded stacked-layer weights and a jitted forward that runs
    every layer via `domino_layer` under shard_map over the `tp` mesh axis.
    """

    def __init__(self, mesh: Mesh, num_layers: int, hidden: int,
                 num_heads: int, ffn: Optional[int] = None,
                 num_micro: int = 2, tp_axis: str = "tp",
                 dtype=jnp.bfloat16):
        self.mesh = mesh
        self.num_layers = num_layers
        self.hidden = hidden
        self.num_heads = num_heads
        self.ffn = ffn or 4 * hidden
        self.num_micro = num_micro
        self.tp_axis = tp_axis
        self.dtype = dtype

    def init_params(self, key) -> PyTree:
        L, H, F = self.num_layers, self.hidden, self.ffn
        ks = jax.random.split(key, 6)
        std = 0.02

        def rnd(k, shape, s=std):
            return jax.random.normal(k, shape, jnp.float32) * s

        p = {
            "ln1_scale": jnp.ones((L, H)), "ln1_bias": jnp.zeros((L, H)),
            "ln2_scale": jnp.ones((L, H)), "ln2_bias": jnp.zeros((L, H)),
            "wq": rnd(ks[0], (L, H, H)), "wk": rnd(ks[1], (L, H, H)),
            "wv": rnd(ks[2], (L, H, H)),
            "wo": rnd(ks[3], (L, H, H), std / math.sqrt(2 * L)),
            "w_up": rnd(ks[4], (L, H, F)),
            "w_down": rnd(ks[5], (L, F, H), std / math.sqrt(2 * L)),
        }
        specs = self.param_specs()
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
            p, specs)

    def param_specs(self) -> Dict[str, P]:
        t = self.tp_axis
        return {
            "ln1_scale": P(None, None), "ln1_bias": P(None, None),
            "ln2_scale": P(None, None), "ln2_bias": P(None, None),
            "wq": P(None, None, t), "wk": P(None, None, t),
            "wv": P(None, None, t), "wo": P(None, t, None),
            "w_up": P(None, None, t), "w_down": P(None, t, None),
        }

    def __call__(self, params: PyTree, x) -> jax.Array:
        t = self.tp_axis
        nm, nh = self.num_micro, self.num_heads

        def body(params, x):
            def layer_step(carry, lp):
                return domino_layer(carry, lp, t, nh, nm), None
            out, _ = jax.lax.scan(layer_step, x, params)
            return out

        in_specs = ({k: v for k, v in self.param_specs().items()}, P())
        f = shard_map(body, mesh=self.mesh, in_specs=in_specs,
                          out_specs=P(), check_vma=False)
        return jax.jit(f)(params, x.astype(self.dtype))
