"""Trace-time handoff of per-layer qwZ gathers from the ZeRO++ quantized
path to scan-over-layers models.

Problem: `runtime/zero/quantized.py` gathered every
sharded leaf at the top of the loss, so qwZ peak memory was ZeRO-1/2-like —
a model that NEEDS stage-3 residency couldn't use qwZ.  The reference
quantizes the same per-module gathers stage 3 already does
(partition_parameters.py:824 + the coordinator), so the two compose.

TPU formulation: the engine cannot reach inside an opaque `loss_fn`, but the
in-tree Transformer (models/transformer.py) scans stacked [L, ...] layer
leaves with `lax.scan`.  The quantized path leaves those leaves SHARDED,
publishes a pytree of per-leaf gather callables here, and the model's scan
body applies them to each layer SLICE — so only one layer's weights are
ever gathered at a time (per-module fetch), while the cotangent flowing
back through each gather's vjp is the quantized reduce-scatter, exactly as
in the eager path.

The handoff is trace-time only: the context is set around the loss trace
inside the shard_map body; `jax.checkpoint`/custom-vjp replay jaxprs, not
Python, so backward recomputation never needs the context again.  Any model
whose layer scan calls `apply_layer_gathers(lp)` participates; models that
never consult the context keep the whole-model eager gather.
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Any, Optional

import jax

_CURRENT: Optional[Any] = None  # pytree of callables, or None


@contextmanager
def layer_gather_context(gathers):
    """Install the per-layer gather tree for the duration of a loss trace."""
    global _CURRENT
    prev = _CURRENT
    _CURRENT = gathers
    try:
        yield
    finally:
        _CURRENT = prev


def apply_layer_gathers(layer_params):
    """Called from a model's layer-scan body with one layer's param slice;
    returns the slice with sharded leaves gathered (identity when no
    quantized per-layer context is active)."""
    if _CURRENT is None:
        return layer_params
    return jax.tree.map(lambda f, x: f(x), _CURRENT, layer_params)
