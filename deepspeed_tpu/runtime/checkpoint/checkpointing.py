"""Checkpoint save/load.

Reference: `save_checkpoint` engine.py:3369 / `load_checkpoint` engine.py:3023
and the pluggable engines under runtime/checkpoint_engine/.  Layout parity:

    <save_dir>/<tag>/            # tag defaults to global_step{N}
        state.msgpack-like .npz shards + metadata.json
    <save_dir>/latest             # tag file (reference writes `latest`)

TPU-native mechanics: arrays are saved from their *sharded* global form.  On
a multi-host pod each host saves only its addressable shards (the reference's
per-rank `mp_rank_XX_model_states.pt` files map to per-host shard files);
single-host saves full arrays.  Loading re-places arrays with the engine's
current sharding rules, so a checkpoint written under one topology can be
loaded under another — the semantics of the reference's *universal
checkpoint* (deepspeed/checkpoint/ds_to_universal.py) fall out naturally
because we always store the logical (unpartitioned) array per leaf.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...utils.logging import log_dist

PyTree = Any

LATEST_FILE = "latest"


def _flatten_with_names(tree: PyTree, prefix: str = "", is_leaf=None):
    flat = {}
    leaves_with_paths = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_leaf)[0]
    for path, leaf in leaves_with_paths:
        name = prefix + "/".join(_key_str(p) for p in path)
        # a bare-array "tree" has an empty path: drop the dangling slash so
        # save and per-subtree load agree on the name
        flat[name.rstrip("/")] = leaf
    return flat


def _is_spec(x) -> bool:
    from jax.sharding import PartitionSpec
    return isinstance(x, PartitionSpec)


def _key_str(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    if hasattr(p, "name"):
        return str(p.name)
    return str(p)


def _checkpoint_io(engine):
    """Per-engine pluggable IO engine (reference: checkpoint_engine factory
    selected by config, runtime/checkpoint_engine/)."""
    io = getattr(engine, "_ckpt_io", None)
    if io is None:
        from ..checkpoint_engine import make_checkpoint_engine
        kind = getattr(engine.config.checkpoint, "engine", "sync")
        if kind in ("native", "orbax"):
            kind = "sync"
        io = make_checkpoint_engine(
            kind, async_save=getattr(engine.config.checkpoint,
                                     "async_save", False))
        engine._ckpt_io = io
    return io


def save_checkpoint(engine, save_dir: str, tag: Optional[str] = None,
                    client_state: Optional[Dict] = None) -> str:
    """Write engine state.  Returns checkpoint path."""
    state = engine.state
    tag = tag or f"global_step{int(state.step)}"
    ckpt_dir = os.path.join(save_dir, tag)
    os.makedirs(ckpt_dir, exist_ok=True)

    trees = {
        "params": state.params,
        "opt_state": state.opt_state,
    }
    if state.master is not None:
        trees["master"] = state.master

    arrays: Dict[str, np.ndarray] = {}
    for tree_name, tree in trees.items():
        for name, leaf in _flatten_with_names(tree, f"{tree_name}/").items():
            # Gather the logical array (universal-checkpoint semantics: store
            # the unpartitioned tensor, topology-independent).  bfloat16 has
            # no native numpy representation — store widened to fp32
            # (lossless) and re-cast on load.
            arr = jax.device_get(leaf)
            if arr.dtype == jnp.bfloat16:
                arr = np.asarray(arr, dtype=np.float32)
            arrays[name] = np.asarray(arr)

    if jax.process_index() == 0:
        io = _checkpoint_io(engine)

        def _mark_durable():
            # flip `latest` only once array data is durable (for async
            # engines this runs on the writer thread after a good write —
            # a failed/crashed save never becomes the resume point)
            with open(os.path.join(save_dir, LATEST_FILE), "w") as f:
                f.write(tag)

        io.save(arrays, ckpt_dir, on_durable=_mark_durable)
        meta = {
            "step": int(state.step),
            "loss_scale": float(state.loss_scale),
            "good_steps": int(state.good_steps),
            "skipped_steps": int(state.skipped_steps),
            "zero_stage": engine.config.zero.stage,
            "dtype": str(engine.compute_dtype.__name__),
            "world_size": jax.device_count(),
            "client_state": client_state or {},
            "format_version": 1,
        }
        with open(os.path.join(ckpt_dir, "metadata.json"), "w") as f:
            json.dump(meta, f, indent=2)
        # ship the consolidation script into the dir (reference parity:
        # save_checkpoint injects zero_to_fp32.py, engine.py:3369 area)
        _inject_zero_to_fp32(ckpt_dir)
    log_dist(f"saved checkpoint {ckpt_dir}", ranks=[0])
    return ckpt_dir


def commit_checkpoint(engine, tag: str = "") -> bool:
    """Fence any async checkpoint writes (reference: checkpoint_engine
    commit at the GAS boundary, engine.py:2454).  Call before relying on an
    `async_save` checkpoint being durable."""
    return _checkpoint_io(engine).commit(tag)


def _inject_zero_to_fp32(ckpt_dir: str):
    script = os.path.join(ckpt_dir, "zero_to_fp32.py")
    with open(script, "w") as f:
        f.write(
            "#!/usr/bin/env python\n"
            '"""Offline consolidation: checkpoint shards -> fp32 state dict '
            '(reference: utils/zero_to_fp32.py, shipped into every checkpoint '
            'dir)."""\n'
            "import sys\n"
            "from deepspeed_tpu.utils.zero_to_fp32 import main\n"
            "if __name__ == '__main__':\n"
            "    sys.exit(main())\n")


def load_checkpoint(engine, load_dir: str, tag: Optional[str] = None):
    """Restore engine state in-place; returns (ckpt_dir, client_state).
    Reference behavior parity: reads `latest` when no tag is given
    (engine.py:3064); re-shards onto the *current* topology, which is the
    universal-checkpoint elastic-resume property (SURVEY §5.4)."""
    if tag is None:
        latest_path = os.path.join(load_dir, LATEST_FILE)
        if not os.path.exists(latest_path):
            return None, {}
        with open(latest_path) as f:
            tag = f.read().strip()
    ckpt_dir = os.path.join(load_dir, tag)
    io = _checkpoint_io(engine)
    io.wait()  # fence an in-flight async save of this same dir
    data = io.load(ckpt_dir)
    with open(os.path.join(ckpt_dir, "metadata.json")) as f:
        meta = json.load(f)

    state = engine.state

    def restore_tree(tree, prefix):
        # each existing state leaf was materialized under the *current*
        # topology's sharding rules, so its .sharding is exactly the target
        # placement — re-sharding a checkpoint written under a different
        # topology happens here (universal-checkpoint elastic resume).
        flat_names = _flatten_with_names(tree, prefix)
        missing = [n for n in flat_names if n not in data]
        if missing:      # before any leaf of the live state is given up
            raise KeyError(f"checkpoint {ckpt_dir} lacks {missing[:5]}")
        restored = {}
        for name, leaf in flat_names.items():
            arr = data[name]
            if isinstance(leaf, np.ndarray):
                # host-resident leaf (ZeRO-Offload master/moments): stays
                # in host RAM, no device placement
                restored[name] = np.asarray(arr, dtype=leaf.dtype)
            else:
                # cast on the host and place each shard straight from
                # there (never the whole leaf on one device first); the
                # leaf being replaced is freed before its replacement
                # lands — a 1B-class state does not fit one chip twice
                host = np.asarray(arr).astype(leaf.dtype)
                sharding = leaf.sharding
                leaf.delete()
                restored[name] = jax.device_put(host, sharding)
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        names = list(flat_names.keys())
        return jax.tree_util.tree_unflatten(treedef, [restored[n] for n in names])

    new_params = restore_tree(state.params, "params/")
    new_opt = {}
    for k, sub in state.opt_state.items():
        new_opt[k] = restore_tree(sub, f"opt_state/{k}/")
    new_master = None
    if state.master is not None:
        new_master = restore_tree(state.master, "master/")

    from ..engine import TrainState
    engine.state = TrainState(
        step=engine._scalar(meta["step"], jnp.int32),
        params=new_params,
        master=new_master,
        opt_state=new_opt,
        loss_scale=engine._scalar(meta["loss_scale"], jnp.float32),
        good_steps=engine._scalar(meta["good_steps"], jnp.int32),
        skipped_steps=engine._scalar(meta["skipped_steps"], jnp.int32),
    )
    engine.global_steps = meta["step"]
    log_dist(f"loaded checkpoint {ckpt_dir}", ranks=[0])
    return ckpt_dir, meta.get("client_state", {})
