"""Seeded weights of the dense decoder-only block (Qwen2, OPT, ...), made on
the device, for the program and for the reference.  This is the family's own
library: `references/transformer.py` is what the harness finds by name, and
nothing shared (kinds, systems, readers) imports this file.

A model is its weights: both sides of the `correct` comparison must hold the
same ones without either handing them to the other.  So the benchmark owns
the generator.  `make_params` builds the whole tree in the program's layout
(layers stacked on a leading axis) in ONE jitted call whose seed is a traced
argument — every seed after the first is served from the compile cache.
`layer_params` / `top_param` regenerate a single layer or top-level leaf from
the same seed, so the plain reference can walk the model layer by layer
without ever holding it whole.

Every leaf is random, biases and norm scales included, so a path that drops
one shows in the comparison.  Values are rounded to the type the
configuration stores (`dtype`); the reference widens those same values to
float32.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The sizes a dense decoder-only transformer is made of, read from the
    configuration file (`sizes_from_config`)."""
    layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    norm: str            # "rms" | "ln"
    eps: float
    act: str             # "swiglu" | "relu"
    pos: str             # "rope" | "learned"
    rope_theta: float
    max_pos: int
    qkv_bias: bool
    dense_bias: bool     # biases on wo / up / down and on the norms (OPT)
    tied: bool


def sizes_from_config(cfg: dict) -> Sizes:
    """The configuration file's `sizes` section -> Sizes.  Each field is a
    literal (`"norm": "rms"`) or names the published key it is read from
    (`"hidden": {"key": "hidden_size"}`), so the file itself says how its
    family's `config.json` maps onto this block and no code here knows a
    family by name.  `head_dim` defaults to hidden / heads."""
    fields = {name: cfg[v["key"]] if isinstance(v, dict) else v
              for name, v in cfg["sizes"].items()}
    fields.setdefault("head_dim", fields["hidden"] // fields["heads"])
    fields["rope_theta"] = float(fields["rope_theta"])
    return Sizes(**fields)


STD = 0.02

# kind -> (mean, std factor); "out" leaves get the GPT-2 1/sqrt(2L) damping
_KINDS = {"w": (0.0, STD), "out": (0.0, STD), "bias": (0.0, STD),
          "scale": (1.0, 0.1), "pos": (0.0, 0.01)}


def layer_leaves(s: Sizes) -> List[Tuple[str, Tuple[int, ...], str]]:
    """(name, per-layer shape, kind) in the program's parameter names."""
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    out = [("attn_norm_scale", (s.hidden,), "scale"),
           ("mlp_norm_scale", (s.hidden,), "scale"),
           ("wq", (s.hidden, q), "w"), ("wk", (s.hidden, kv), "w"),
           ("wv", (s.hidden, kv), "w"), ("wo", (q, s.hidden), "out")]
    if s.qkv_bias:
        out += [("bq", (q,), "bias"), ("bk", (kv,), "bias"),
                ("bv", (kv,), "bias")]
    if s.act == "swiglu":
        out += [("w_gate", (s.hidden, s.ffn), "w")]
    out += [("w_up", (s.hidden, s.ffn), "w"),
            ("w_down", (s.ffn, s.hidden), "out")]
    if s.dense_bias:
        out += [("attn_norm_bias", (s.hidden,), "bias"),
                ("mlp_norm_bias", (s.hidden,), "bias"),
                ("bo", (s.hidden,), "bias"), ("b_up", (s.ffn,), "bias"),
                ("b_down", (s.hidden,), "bias")]
    return out


def top_leaves(s: Sizes) -> List[Tuple[str, Tuple[int, ...], str]]:
    out = [("tok_embed", (s.vocab, s.hidden), "w"),
           ("final_norm_scale", (s.hidden,), "scale")]
    if s.norm == "ln":
        out += [("final_norm_bias", (s.hidden,), "bias")]
    if s.pos == "learned":
        out += [("pos_embed", (s.max_pos, s.hidden), "pos")]
    if not s.tied:
        out += [("lm_head", (s.hidden, s.vocab), "w")]
    return out


def seed_key(seed) -> jax.Array:
    """A key from the run's seed; `seed` may be traced (uint32)."""
    return jax.random.fold_in(jax.random.PRNGKey(20260928),
                              jnp.asarray(seed, jnp.uint32))


def seed_arg(seed: int) -> np.uint32:
    """The driver's seeds pass 2**31: fold into the 32 bits a key takes."""
    return np.uint32(int(seed) % (1 << 32))


def _leaf(key, slot: int, layer, shape, kind: str, s: Sizes, dtype):
    mean, std = _KINDS[kind]
    if kind == "out":
        std = std / math.sqrt(2 * s.layers)
    k = jax.random.fold_in(jax.random.fold_in(key, slot), layer)
    x = mean + std * jax.random.normal(k, shape, jnp.float32)
    return x.astype(dtype)


def layer_params(key, layer, s: Sizes, dtype) -> Dict[str, jax.Array]:
    """The leaves of one layer (`layer` may be traced)."""
    return {name: _leaf(key, i, layer, shape, kind, s, dtype)
            for i, (name, shape, kind) in enumerate(layer_leaves(s))}


def top_param(key, name: str, s: Sizes, dtype) -> jax.Array:
    for i, (n, shape, kind) in enumerate(top_leaves(s)):
        if n == name:
            return _leaf(key, 1000 + i, 0, shape, kind, s, dtype)
    raise KeyError(name)


@functools.partial(jax.jit, static_argnames=("s", "dtype"))
def make_params(seed, *, s: Sizes, dtype):
    """The whole tree, program layout, one call.  Layers are generated in
    sequence (`lax.map`) so the float32 intermediates of one layer, not of
    the stack, are what the call holds beside its result."""
    key = seed_key(seed)
    params = {n: top_param(key, n, s, dtype) for n, _, _ in top_leaves(s)}
    params["layers"] = jax.lax.map(
        lambda l: layer_params(key, l, s, dtype),
        jnp.arange(s.layers, dtype=jnp.uint32))
    return params


def param_count(s: Sizes) -> int:
    per_layer = sum(int(np.prod(sh)) for _, sh, _ in layer_leaves(s))
    top = sum(int(np.prod(sh)) for _, sh, _ in top_leaves(s))
    return s.layers * per_layer + top
