"""Activation checkpointing (rematerialisation) subsystem.

TPU-native analog of DeepSpeed's Megatron-compatible activation checkpointing
(reference: runtime/activation_checkpointing/checkpointing.py —
`CheckpointFunction`:488, `partition_activations`:377, `checkpoint`:948,
`CudaRNGStatesTracker`:124, `configure`:906).

Design inversion: the reference re-runs forward subgraphs eagerly inside
autograd Functions, manually slicing/partitioning saved activations across TP
ranks and copying them to pinned CPU buffers.  On TPU all four of its memory
levers map onto `jax.checkpoint` (remat) machinery that the XLA scheduler then
overlaps for free:

- plain checkpointing      -> `jax.checkpoint(fn, policy=nothing_saveable)`
- `partition_activations`  -> saved residuals carry a sharding constraint over
                              the TP axis, so each device stores 1/tp of every
                              checkpoint (reference :377 slices tensors by
                              `mp_rank`; here the SPMD partitioner does it)
- `cpu_checkpointing`      -> residual offload to host memory via
                              `save_and_offload_only_these_names` (reference
                              :420 copies partitioned activations to CPU)
- `number_checkpoints` /
  selective checkpointing  -> `remat_scan` applies remat to every layer of a
                              scanned stack; selective policies
                              (`dots_saveable` etc.) keep matmul outputs.

The RNG-state tracker keeps dropout patterns identical between the first
forward and the rematerialised forward — with functional PRNG keys this is
automatic (the same key is an input to both executions), so the tracker here
only has to manage *named* key streams (model-parallel vs data-parallel seeds,
reference `model_parallel_cuda_manual_seed`:242).
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Callable, Dict, Iterable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name as _jax_checkpoint_name
from jax.sharding import PartitionSpec

__all__ = [
    "CheckpointingOptions", "configure", "is_configured", "reset",
    "checkpoint", "checkpoint_wrapper", "checkpoint_name", "remat_policy",
    "partition_activation", "remat_scan", "RNGStatesTracker",
    "get_rng_tracker", "model_parallel_reseed",
]

# name tag used for offloadable / partitionable residuals
_CKPT_NAME = "ds_tpu_ckpt"
_ATTN_NAME = "ds_tpu_attn"
# flash-attention logsumexp residual: without it saved alongside the
# attention output, the remat backward must re-run the O(S^2) forward
# kernel just to regenerate lse for the flash backward kernels
_LSE_NAME = "ds_tpu_attn_lse"
# q/k/v/out projection outputs (models/transformer.py tags them)
_PROJ_NAME = "ds_tpu_proj"
# MLP up-projection output (the gelu input — the biggest single matmul
# recompute in a transformer layer backward)
_MLP_UP_NAME = "ds_tpu_mlp_up"


class CheckpointingOptions:
    """Resolved global options (reference `configure`:906 stores module-level
    state: mp_rank/size, partition flags, num_layers)."""

    def __init__(self,
                 partition_activations: bool = False,
                 cpu_checkpointing: bool = False,
                 contiguous_memory_optimization: bool = False,
                 number_checkpoints: Optional[int] = None,
                 synchronize_checkpoint_boundary: bool = False,
                 profile: bool = False,
                 policy: Optional[str] = None):
        self.partition_activations = partition_activations
        self.cpu_checkpointing = cpu_checkpointing
        # contiguous buffers are an XLA allocator concern; accepted for config
        # parity, no-op (the reference pre-allocates one big buffer, :430)
        self.contiguous_memory_optimization = contiguous_memory_optimization
        self.number_checkpoints = number_checkpoints
        self.synchronize_checkpoint_boundary = synchronize_checkpoint_boundary
        self.profile = profile
        self.policy = policy


_options = CheckpointingOptions()
_configured = False


def configure(cfg=None, **kwargs) -> CheckpointingOptions:
    """Install global checkpointing options (reference `configure`:906).

    Accepts an `ActivationCheckpointingConfig` (config/config.py) or kwargs.
    """
    global _options, _configured
    if cfg is not None:
        _options = CheckpointingOptions(
            partition_activations=getattr(cfg, "partition_activations", False),
            cpu_checkpointing=getattr(cfg, "cpu_checkpointing", False),
            contiguous_memory_optimization=getattr(
                cfg, "contiguous_memory_optimization", False),
            number_checkpoints=getattr(cfg, "number_checkpoints", None),
            synchronize_checkpoint_boundary=getattr(
                cfg, "synchronize_checkpoint_boundary", False),
            profile=getattr(cfg, "profile", False),
            policy=getattr(cfg, "policy", None),
        )
    else:
        _options = CheckpointingOptions(**kwargs)
    _configured = True
    return _options


def is_configured() -> bool:
    """Reference: checkpointing.py `is_configured`."""
    return _configured


def reset():
    """Reference: checkpointing.py `reset` (frees contiguous buffers; here
    just restores defaults)."""
    global _options, _configured
    _options = CheckpointingOptions()
    _configured = False


# ---------------------------------------------------------------------------
# policies
# ---------------------------------------------------------------------------

def remat_policy(name: Optional[str] = None,
                 options: Optional[CheckpointingOptions] = None):
    """Resolve a named remat policy into a `jax.checkpoint` policy callable.

    Names: nothing_saveable (default / full remat), everything_saveable,
    dots_saveable, dots_with_no_batch_dims (selective: keep matmul outputs),
    offload (cpu_checkpointing: move tagged residuals to host),
    save_named (keep only `checkpoint_name`-tagged residuals on device).
    """
    opts = options or _options
    name = name or opts.policy
    if name == "none":  # config default sentinel
        name = None
    cp = jax.ad_checkpoint.checkpoint_policies
    if name is None:
        if opts.cpu_checkpointing:
            name = "offload"
        elif opts.partition_activations:
            name = "save_named"
        else:
            name = "nothing_saveable"
    table = {
        "nothing_saveable": cp.nothing_saveable,
        "everything_saveable": cp.everything_saveable,
        "dots_saveable": cp.dots_saveable,
        "checkpoint_dots": cp.dots_saveable,
        "dots_with_no_batch_dims": cp.dots_with_no_batch_dims_saveable,
        "save_named": cp.save_only_these_names(_CKPT_NAME),
        # full remat EXCEPT attention outputs (+ the flash lse residual —
        # without lse saved too the backward re-runs the O(S^2) forward
        # kernel just to regenerate it, which is why the round-2 save_attn
        # gained nothing): ~2 bytes/token/layer/width + 4B/token/head
        "save_attn": cp.save_only_these_names(_ATTN_NAME, _LSE_NAME),
        # (what is left to recompute: the q/k/v/output projections and
        # the MLP up-projection, 8H^2 of a layer's 12H^2 forward matmul
        # parameters; the down-projection's output feeds no gradient and is
        # recomputed under no policy: tests/test_activation_checkpointing.py
        # counts them in the step's HLO)
        # save_attn + the q/k/v/attn-out projection outputs: the layer
        # backward recomputes only norms/rope/gelu and the mlp-up matmul
        # (4H^2 of 12H^2)
        "save_attn_proj": cp.save_only_these_names(
            _ATTN_NAME, _LSE_NAME, _PROJ_NAME),
        # + the MLP up-projection output: no weight matmul is recomputed,
        # for an extra 2*ffn_size bytes/token/layer of saved residuals
        "save_attn_proj_up": cp.save_only_these_names(
            _ATTN_NAME, _LSE_NAME, _PROJ_NAME, _MLP_UP_NAME),
        "offload": cp.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
            names_which_can_be_offloaded=[_CKPT_NAME],
            offload_src="device", offload_dst="pinned_host"),
    }
    if name not in table:
        raise ValueError(f"unknown remat policy {name!r}; one of {sorted(table)}")
    return table[name]


def attn_checkpoint_name(x):
    """Tag an attention output for the "save_attn*" remat policies (no-op
    under every other policy — names are only consulted by name-keyed
    policies)."""
    return _jax_checkpoint_name(x, _ATTN_NAME)


def lse_checkpoint_name(x):
    """Tag a flash-attention logsumexp residual (see _LSE_NAME)."""
    return _jax_checkpoint_name(x, _LSE_NAME)


def proj_checkpoint_name(x):
    """Tag a q/k/v/out projection output for "save_attn_proj*"."""
    return _jax_checkpoint_name(x, _PROJ_NAME)


def mlp_up_checkpoint_name(x):
    """Tag an MLP up-projection output for "save_attn_proj_up"."""
    return _jax_checkpoint_name(x, _MLP_UP_NAME)


def checkpoint_name(x, name: str = _CKPT_NAME):
    """Tag a value as a named residual for save/offload policies."""
    return _jax_checkpoint_name(x, name)


def maybe_checkpoint_name(x):
    """Tag `x` only when the configured policy keys off names
    (partition_activations / cpu_checkpointing / save_named); identity
    otherwise.  Model code calls this at layer-boundary residuals so those
    config options are never a silent no-op."""
    if _options.cpu_checkpointing:
        return checkpoint_name(x)
    if _options.partition_activations:
        return partition_activation(x)
    if _options.policy in ("save_named", "offload"):
        return checkpoint_name(x)
    return x


def partition_activation(x, mesh=None, axis: str = "tp"):
    """Mark an activation as a TP-partitioned checkpoint (reference
    `partition_activations`:377 slices saved tensors across model-parallel
    ranks; here a sharding constraint on the tagged residual makes the SPMD
    partitioner store 1/tp per device).

    Shards the last dim if divisible, else the sequence dim.
    """
    from ...parallel.context import get_current_topology
    topo = get_current_topology()
    if topo is None or topo.axis_sizes.get(axis, 1) <= 1:
        return checkpoint_name(x)
    size = topo.axis_sizes[axis]
    spec = [None] * x.ndim
    if x.shape[-1] % size == 0:
        spec[-1] = axis
    elif x.ndim >= 2 and x.shape[1] % size == 0:
        spec[1] = axis
    x = jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(topo.mesh, PartitionSpec(*spec)))
    return checkpoint_name(x)


# ---------------------------------------------------------------------------
# checkpoint API
# ---------------------------------------------------------------------------

def checkpoint(function: Callable, *args, policy: Optional[str] = None,
               static_argnums=(), **kwargs):
    """Checkpoint a forward function: re-run it during backward instead of
    storing intermediates (reference `checkpoint`:948 — the Megatron-style
    `deepspeed.checkpointing.checkpoint(function, *args)` call).

    Immediate-call form. For a reusable wrapped function use
    `checkpoint_wrapper`.
    """
    return checkpoint_wrapper(function, policy=policy,
                              static_argnums=static_argnums)(*args, **kwargs)


def checkpoint_wrapper(function: Callable, policy: Optional[str] = None,
                       static_argnums=()) -> Callable:
    """Return a rematerialising version of `function` honoring the global
    options (policy / partition / offload)."""
    pol = remat_policy(policy)
    return jax.checkpoint(function, policy=pol,
                          static_argnums=static_argnums)


def remat_scan(layer_fn: Callable, stacked_params, x0, *,
               policy: Optional[str] = None, unroll: int = 1,
               extra_args: tuple = ()):
    """Run a stack of identical layers under `lax.scan` with per-layer remat
    (the TPU idiom for `number_checkpoints = num_layers`: activation memory
    O(L * sizeof(boundary)) instead of O(L * all intermediates); compile time
    O(1) in depth).

    layer_fn: (params_i, x, *extra_args) -> x
    stacked_params: pytree whose leaves have leading dim L.
    """
    fn = checkpoint_wrapper(
        lambda p, x: layer_fn(p, x, *extra_args), policy=policy)

    def body(x, p):
        return fn(p, x), None

    out, _ = jax.lax.scan(body, x0, stacked_params, unroll=unroll)
    return out


# ---------------------------------------------------------------------------
# RNG state tracker
# ---------------------------------------------------------------------------

class RNGStatesTracker:
    """Named PRNG-key streams (reference `CudaRNGStatesTracker`:124).

    The reference snapshots/restores CUDA RNG state so dropout inside a
    checkpointed block replays identically in the recomputed forward.  With
    functional keys replay-identity is automatic; the tracker's remaining job
    is Megatron semantics: a `model-parallel-rng` stream seeded differently
    per TP rank (so dropout differs across TP shards of one tensor) and a
    default stream seeded identically everywhere.
    """

    def __init__(self):
        self._states: Dict[str, jax.Array] = {}

    def reset(self):
        self._states.clear()

    def get_states(self) -> Dict[str, jax.Array]:
        return dict(self._states)

    def set_states(self, states: Dict[str, jax.Array]):
        self._states = dict(states)

    def add(self, name: str, seed: int):
        if name in self._states:
            raise ValueError(f"rng state {name} already exists")
        self._states[name] = jax.random.PRNGKey(seed)

    @contextlib.contextmanager
    def fork(self, name: str = "model-parallel-rng"):
        """Yield a fresh key from the named stream and advance it
        (reference :180 swaps the device generator inside the context)."""
        if name not in self._states:
            raise KeyError(f"rng state {name} not added")
        self._states[name], sub = jax.random.split(self._states[name])
        yield sub


_RNG_TRACKER = RNGStatesTracker()


def get_rng_tracker() -> RNGStatesTracker:
    """Reference: `get_cuda_rng_tracker`:236."""
    return _RNG_TRACKER


def model_parallel_reseed(seed: int, tp_rank: int = 0):
    """Reference: `model_parallel_cuda_manual_seed`:242 — default stream gets
    `seed`, model-parallel stream gets `seed + 2718 + tp_rank`."""
    _RNG_TRACKER.reset()
    _RNG_TRACKER.add("default", seed)
    _RNG_TRACKER.add("model-parallel-rng", seed + 2718 + tp_rank)
    return _RNG_TRACKER
