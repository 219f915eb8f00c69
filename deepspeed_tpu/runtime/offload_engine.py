"""ZeRO-Offload / ZeRO-Infinity engine: optimizer states on host (or NVMe),
updates by the native C++ host optimizer.

Reference semantics (SURVEY §2.3 ZeRO-Offload row): grads are computed on
device, moved to host, the vectorized CPU optimizer (csrc/adam/cpu_adam.cpp
analog — ours is csrc/host_ops.cpp `dstpu_adam_step`, OpenMP+SIMD) updates
the fp32 master copy + moments in host RAM, and the bf16 params are copied
back to device.  With ``offload_optimizer.device="nvme"`` the states live on
NVMe and are paged through the pipelined optimizer swapper
(runtime/swap_tensor/optimizer_swapper.py), double-buffering the next
leaf's read behind the current leaf's update — the reference's
pipelined_optimizer_swapper discipline.

Device side stays one jitted program (fwd+bwd+reduce+clip); only the
optimizer update leaves the XLA graph, which is exactly the boundary the
reference draws.
"""
from __future__ import annotations

import os
import tempfile
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from ..ops import native
from ..utils import tree as tu
from .engine import TrainEngine, TrainState
from .zero.sharding import grad_specs, param_specs

PyTree = Any

_STATE_NAMES = {
    "adam": ("exp_avg", "exp_avg_sq"),
    "adamw": ("exp_avg", "exp_avg_sq"),
    "adagrad": ("acc",),
    "lion": ("exp_avg",),
}


def _leaf_key(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return ".".join(parts)


class ZeroOffloadEngine(TrainEngine):
    """TrainEngine with host/NVMe-offloaded optimizer (ZeRO-Offload)."""

    supports_compression = False  # own step path; see TrainEngine.__init__

    def __init__(self, loss_fn, params, config, **kw):
        off = config.zero.offload_optimizer
        self._offload_device = off.device
        self._opt_type = (config.optimizer.type or "adamw").lower()
        if self._opt_type not in _STATE_NAMES:
            raise ValueError(
                f"offload_optimizer supports {sorted(_STATE_NAMES)}, "
                f"got {self._opt_type!r} (reference: cpu_adam/cpu_adagrad/cpu_lion)")
        self._swapper = None
        if off.device == "nvme":
            swap_dir = off.nvme_path or os.path.join(
                tempfile.gettempdir(), "dstpu_nvme_swap")
            from .swap_tensor import OptimizerStateSwapper
            self._swapper = OptimizerStateSwapper(
                os.path.join(swap_dir, "optimizer"),
                buffer_count=max(2, off.buffer_count))
        # ZeRO-Infinity param residence (reference: offload_param +
        # partitioned_param_swapper): bf16 params live on host ("cpu") or
        # NVMe between steps; each train_batch pages them onto the chip
        off_p = config.zero.offload_param
        self._param_offload = off_p.device
        self._param_swapper = None
        if self._param_offload == "nvme":
            swap_dir = off_p.nvme_path or os.path.join(
                tempfile.gettempdir(), "dstpu_nvme_swap")
            from .swap_tensor import PartitionedParamSwapper
            self._param_swapper = PartitionedParamSwapper(
                os.path.join(swap_dir, "param"))
        super().__init__(loss_fn, params, config, **kw)

    # ------------------------------------------------------------------
    # state: params on device, master+moments on host (or NVMe)
    # ------------------------------------------------------------------
    def _init_state(self, params: PyTree) -> TrainState:
        if callable(params):
            self._rng, init_key = jax.random.split(self._rng)
            params = params(init_key)
        mesh = self.topology.mesh
        p_specs = param_specs(self.rules, params)
        params = jax.tree.map(
            lambda x, s: jax.device_put(
                jnp.asarray(x, dtype=self.compute_dtype), NamedSharding(mesh, s)),
            params, p_specs)

        names = _STATE_NAMES[self._opt_type]
        # np.asarray of a jax array is a read-only view; copy=True makes the
        # host master writable (numpy fancy-assignment checks WRITEABLE even
        # though the native kernel writes through raw pointers)
        host_master = jax.tree.map(
            lambda x: np.array(x, np.float32, copy=True), params)
        host_opt = {n: jax.tree.map(lambda x: np.zeros(x.shape, np.float32), params)
                    for n in names}

        if self._swapper is not None:
            leaves, _ = jax.tree_util.tree_flatten_with_path(host_master)
            for path, m in leaves:
                key = _leaf_key(path)
                states = {"master": m}
                for n in names:
                    states[n] = np.zeros(m.shape, np.float32)
                self._swapper.init_leaf(key, states)
            # NVMe is authoritative; host trees become empty placeholders
            host_master = jax.tree.map(lambda x: None, host_master,
                                       is_leaf=lambda x: isinstance(x, np.ndarray))
            host_opt = {}

        self._host_master = host_master
        self._host_opt = host_opt

        # offload_param: bf16 params leave the device between steps
        # (reference ZeRO-Infinity partitioned_param_swapper residence)
        params = self._to_residence(params)

        pc = self.config.precision
        init_scale = (2.0 ** pc.initial_scale_power
                      if pc.fp16_enabled and pc.loss_scale == 0 else
                      (pc.loss_scale if pc.fp16_enabled else 1.0))
        return TrainState(
            step=jnp.zeros((), jnp.int32), params=params, master=None,
            opt_state={}, loss_scale=jnp.asarray(init_scale, jnp.float32),
            good_steps=jnp.zeros((), jnp.int32),
            skipped_steps=jnp.zeros((), jnp.int32))

    # ------------------------------------------------------------------
    # offload_param paging
    # ------------------------------------------------------------------
    def _to_residence(self, params: PyTree) -> PyTree:
        """Move a params tree to its between-step residence: numpy (cpu),
        NVMe + shape placeholders (nvme), or unchanged (none)."""
        if self._param_offload == "cpu":
            return jax.tree.map(lambda x: np.asarray(x), params)
        if self._param_offload == "nvme":
            leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
            ph = []
            for path, x in leaves:
                arr = np.asarray(x)
                self._param_swapper.swap_out(_leaf_key(path), arr)
                ph.append(jax.ShapeDtypeStruct(arr.shape, arr.dtype))
            return jax.tree_util.tree_unflatten(treedef, ph)
        return params

    def _device_params(self) -> PyTree:
        """Page the bf16 params onto the chip for one step."""
        if self._param_offload == "none":
            return self.state.params
        leaves, treedef = jax.tree_util.tree_flatten_with_path(
            self.state.params)
        specs = jax.tree_util.tree_leaves(
            self._named(param_specs(self.rules, self.state.params)),
            is_leaf=lambda x: isinstance(x, NamedSharding))
        out = []
        if self._param_swapper is not None:
            keys = [_leaf_key(p) for p, _ in leaves]
            if keys:
                self._param_swapper.prefetch(keys[0])
            for i, ((path, ph), sh) in enumerate(zip(leaves, specs)):
                if i + 1 < len(keys):
                    self._param_swapper.prefetch(keys[i + 1])
                host = self._param_swapper.fetch(keys[i])
                out.append(jax.device_put(host, sh))
                self._param_swapper.release(keys[i])
        else:
            for (path, host), sh in zip(leaves, specs):
                out.append(jax.device_put(host, sh))
        return jax.tree_util.tree_unflatten(treedef, out)

    def _store_params(self, new_host: Dict[str, np.ndarray]) -> PyTree:
        """Persist updated bf16 params to their offload residence; returns
        the state.params representation."""
        leaves, treedef = jax.tree_util.tree_flatten_with_path(
            self.state.params)
        out = []
        for path, old in leaves:
            host = new_host[_leaf_key(path)].reshape(old.shape).astype(
                np.dtype(self.compute_dtype))
            if self._param_swapper is not None:
                self._param_swapper.swap_out(_leaf_key(path), host)
                out.append(jax.ShapeDtypeStruct(old.shape, old.dtype))
            else:
                out.append(host)
        return jax.tree_util.tree_unflatten(treedef, out)

    # ------------------------------------------------------------------
    # device side: grads only
    # ------------------------------------------------------------------
    def _build_train_step(self):
        cfg = self.config
        rules = self.rules
        loss_fn = self.loss_fn
        gas = cfg.gradient_accumulation_steps
        clip = cfg.gradient_clipping
        fp16 = cfg.precision.fp16_enabled

        def call_loss(params, batch, rng):
            out = loss_fn(params, batch, rng)
            return (out[0], out[1]) if isinstance(out, tuple) else (out, {})

        def grad_step(params, batch, rng, loss_scale):
            def micro_grads(micro, k):
                def scaled(p):
                    loss, aux = call_loss(p, micro, k)
                    return loss * loss_scale.astype(loss.dtype), (loss, aux)
                (_, (loss, aux)), grads = jax.value_and_grad(
                    scaled, has_aux=True)(params)
                return loss, aux, grads

            accum0 = tu.tree_zeros_like(params, jnp.float32)

            def body(carry, micro):
                acc, aux_acc, loss_sum, i = carry
                loss, aux, g = micro_grads(micro, jax.random.fold_in(rng, i))
                acc = jax.tree.map(lambda a, x: a + x.astype(jnp.float32), acc, g)
                aux_acc = jax.tree.map(
                    lambda a, v: a + v.astype(jnp.float32), aux_acc, aux)
                return (acc, aux_acc, loss_sum + loss.astype(jnp.float32),
                        i + 1), None

            if gas > 1:
                from .engine import aux_zeros
                first_micro = jax.tree.map(lambda x: x[0], batch)
                aux0 = aux_zeros(lambda m: micro_grads(m, rng)[1], first_micro)
                (grads, aux_sum, loss_sum, _), _ = jax.lax.scan(
                    body, (accum0, aux0, jnp.zeros((), jnp.float32),
                           jnp.zeros((), jnp.int32)), batch)
                aux = jax.tree.map(lambda a: a / gas, aux_sum)
                loss = loss_sum / gas
            else:
                micro = jax.tree.map(lambda x: x[0], batch)
                loss, aux, g = micro_grads(micro, rng)
                grads = jax.tree.map(lambda x: x.astype(jnp.float32), g)
                loss = loss.astype(jnp.float32)

            inv = 1.0 / (loss_scale * gas)
            grads = jax.tree.map(lambda g: g * inv, grads)
            grads = jax.lax.with_sharding_constraint(
                grads, self._named(grad_specs(rules, params)))
            finite = tu.tree_finite(grads) if fp16 else jnp.asarray(True)
            gnorm = tu.global_norm(grads)
            if clip and clip > 0:
                scale = jnp.minimum(1.0, clip / (gnorm + 1e-6))
                grads = jax.tree.map(lambda g: g * scale, grads)
            from .engine import surface_aux
            metrics = surface_aux({"loss": loss, "grad_norm": gnorm,
                                   "overflow": jnp.logical_not(finite)}, aux)
            return grads, metrics

        self._built_with_grads = True
        return jax.jit(grad_step)

    # ------------------------------------------------------------------
    # host side: native optimizer over leaves
    # ------------------------------------------------------------------
    def _host_update_leaf(self, key: str, master: np.ndarray,
                          states: Dict[str, np.ndarray], grad: np.ndarray,
                          lr: float, step: int) -> np.ndarray:
        o = self.config.optimizer
        m2, g2 = master.reshape(-1), np.ascontiguousarray(grad, np.float32).reshape(-1)
        b1, b2 = o.betas
        if self._opt_type in ("adam", "adamw"):
            native.adam_step(m2, states["exp_avg"].reshape(-1),
                             states["exp_avg_sq"].reshape(-1), g2, lr,
                             beta1=b1, beta2=b2, eps=o.eps,
                             weight_decay=o.weight_decay,
                             adam_w=self._opt_type == "adamw", step=step)
        elif self._opt_type == "adagrad":
            native.adagrad_step(m2, states["acc"].reshape(-1), g2, lr,
                                eps=o.eps, weight_decay=o.weight_decay)
        else:  # lion
            native.lion_step(m2, states["exp_avg"].reshape(-1), g2, lr,
                             beta1=b1, beta2=b2,
                             weight_decay=o.weight_decay)
        return master

    def train_batch(self, batch: PyTree) -> Dict[str, Any]:
        import time
        if self._tput_t0 is None:
            self._tput_t0 = time.time()
        timings: Dict[str, float] = {}
        t0 = time.perf_counter()
        sharded = self._shard_batch(batch)
        grads, metrics = self._train_step(
            self._device_params(), sharded, self.next_rng(),
            self.state.loss_scale)
        # bound the device fwd+bwd
        jax.block_until_ready(metrics["loss"])
        timings["device_ms"] = (time.perf_counter() - t0) * 1e3

        overflow = bool(metrics["overflow"])
        step_num = int(self.state.step) + 1
        lr = float(self.lr_fn(self.state.step))

        if not overflow:
            g_leaves, treedef = jax.tree_util.tree_flatten_with_path(grads)
            keys = [_leaf_key(p) for p, _ in g_leaves]
            new_host: Dict[str, np.ndarray] = {}

            if self._swapper is not None:
                # pipelined: prefetch leaf i+1 while updating leaf i
                if keys:
                    self._swapper.prefetch(keys[0])
                for i, (key, (_, g)) in enumerate(zip(keys, g_leaves)):
                    states = self._swapper.swap_in(key)
                    if i + 1 < len(keys):
                        self._swapper.prefetch(keys[i + 1])
                    master = states.pop("master")
                    g_host = np.asarray(g)
                    self._host_update_leaf(key, master, states, g_host, lr, step_num)
                    states["master"] = master
                    self._swapper.swap_out(key, states)
                    new_host[key] = master
                self._swapper.flush()
            else:
                # sequential over leaves: the native kernel already spans
                # the host cores via its internal parallel_for
                # (csrc/host_ops.cpp:87), so a leaf-level thread pool would
                # only oversubscribe.
                m_leaves = jax.tree_util.tree_flatten_with_path(self._host_master)[0]
                o_leaves = {n: jax.tree_util.tree_flatten_with_path(t)[0]
                            for n, t in self._host_opt.items()}
                t1 = time.perf_counter()
                g_host = [np.asarray(g) for _, g in g_leaves]  # one D2H sync
                timings["grad_d2h_ms"] = (time.perf_counter() - t1) * 1e3
                t1 = time.perf_counter()
                for i, key in enumerate(keys):
                    master = m_leaves[i][1]
                    states = {n: o_leaves[n][i][1] for n in o_leaves}
                    self._host_update_leaf(key, master, states, g_host[i],
                                           lr, step_num)
                    new_host[key] = master
                timings["host_optimizer_ms"] = (time.perf_counter()
                                                - t1) * 1e3

            if self._param_offload != "none":
                # params stay off-device between steps (ZeRO-Infinity)
                params = self._store_params(new_host)
            else:
                # copy updated bf16 params back to device, resharded
                t1 = time.perf_counter()
                p_leaves, pdef = jax.tree_util.tree_flatten_with_path(
                    self.state.params)
                spec_leaves = jax.tree_util.tree_leaves(
                    self._named(param_specs(self.rules, self.state.params)),
                    is_leaf=lambda x: isinstance(x, NamedSharding))
                new_params = []
                for (path, old), sh in zip(p_leaves, spec_leaves):
                    host = new_host[_leaf_key(path)].reshape(old.shape)
                    new_params.append(
                        jax.device_put(host.astype(self.compute_dtype), sh))
                params = jax.tree_util.tree_unflatten(pdef, new_params)
                jax.block_until_ready(new_params)
                timings["param_h2d_ms"] = (time.perf_counter() - t1) * 1e3
        else:
            params = self.state.params

        if self.store_gradients and not overflow:
            self._last_grads = grads
        else:
            self._last_grads = None

        # dynamic loss-scale update, host-side mirror of engine.py:308-315
        pc = self.config.precision
        scale = float(self.state.loss_scale)
        good = int(self.state.good_steps)
        if pc.fp16_enabled and pc.loss_scale == 0:
            if overflow:
                scale = max(scale / 2.0, pc.min_loss_scale)
                good = 0
            else:
                good += 1
                if good >= pc.loss_scale_window:
                    scale *= 2.0
                    good = 0

        self.state = TrainState(
            step=jnp.asarray(step_num if not overflow else int(self.state.step), jnp.int32),
            params=params, master=None, opt_state={},
            loss_scale=jnp.asarray(scale, jnp.float32),
            good_steps=jnp.asarray(good, jnp.int32),
            skipped_steps=self.state.skipped_steps + (1 if overflow else 0))
        metrics = dict(metrics)
        metrics["lr"] = lr
        # step-phase decomposition for benchmarks/diagnostics (the host
        # link can dwarf device time — report both)
        self.last_step_timings = timings
        self._finish_step(metrics)
        return metrics

    def eval_batch(self, batch: PyTree):
        if self._param_offload == "none":
            return super().eval_batch(batch)
        import dataclasses as _dc
        placeholder = self.state
        self.state = _dc.replace(placeholder, params=self._device_params())
        try:
            return super().eval_batch(batch)
        finally:
            self.state = placeholder

    # -- checkpointing: host/NVMe states go through engine.state ---------
    def save_checkpoint(self, save_dir: str, tag=None, client_state=None):
        """Materialize the offloaded fp32 master + moments into
        engine.state so the common checkpoint writer persists them
        (reference: _save_zero_checkpoint engine.py:3812 writes the CPU
        optimizer shards the same way)."""
        import dataclasses as _dc
        master, opt = self.materialize_host_states()
        placeholder = self.state
        params = placeholder.params
        fetched_keys = []
        if self._param_swapper is not None:
            # NVMe-resident params: page in for the writer (cpu residence
            # already holds real numpy leaves)
            leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
            fetched_keys = [_leaf_key(p) for p, _ in leaves]
            params = jax.tree_util.tree_unflatten(
                treedef, [self._param_swapper.fetch(k)
                          for k in fetched_keys])
        self.state = _dc.replace(placeholder, params=params, master=master,
                                 opt_state=opt)
        try:
            return super().save_checkpoint(save_dir, tag=tag,
                                           client_state=client_state)
        finally:
            self.state = _dc.replace(self.state, params=placeholder.params,
                                     master=None, opt_state={})
            # drop the paged-in host copies — an end-of-run checkpoint must
            # not leave the whole model pinned in swapper RAM
            for k in fetched_keys:
                self._param_swapper.release(k)

    def load_checkpoint(self, load_dir: str, tag=None):
        """Restore, then re-seed the host/NVMe stores from the loaded
        trees — otherwise the next step would overwrite the restored params
        with the stale pre-load master."""
        import dataclasses as _dc
        master, opt = self.materialize_host_states()
        params_proto = self.state.params
        if self._param_swapper is not None:
            # restore host-side: numpy proto leaves route the checkpoint
            # reader's host path, avoiding a device round trip (and, on a
            # sharded mesh, an unsharded device materialization) of params
            # that are about to be swapped back to NVMe anyway
            params_proto = jax.tree.map(
                lambda x: np.zeros(x.shape, np.dtype(x.dtype)), params_proto,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        self.state = _dc.replace(self.state, params=params_proto,
                                 master=master, opt_state=opt)
        out = super().load_checkpoint(load_dir, tag=tag)
        st = self.state
        new_master = jax.tree.map(
            lambda x: np.ascontiguousarray(np.asarray(x, np.float32)), st.master)
        new_opt = {k: jax.tree.map(
            lambda x: np.ascontiguousarray(np.asarray(x, np.float32)), v)
            for k, v in st.opt_state.items()}
        if self._swapper is not None:
            m_leaves, _ = jax.tree_util.tree_flatten_with_path(new_master)
            o_leaves = {n: jax.tree_util.tree_leaves(t)
                        for n, t in new_opt.items()}
            for i, (path, m) in enumerate(m_leaves):
                states = {"master": m}
                states.update({n: ls[i] for n, ls in o_leaves.items()})
                self._swapper.init_leaf(_leaf_key(path), states)
        else:
            self._host_master, self._host_opt = new_master, new_opt
        self.state = _dc.replace(st, params=self._to_residence(st.params),
                                 master=None, opt_state={})
        return out

    # -- materialize NVMe states on demand ------------------------------
    def materialize_host_states(self) -> Tuple[PyTree, Dict[str, PyTree]]:
        """Return (master_tree, opt_state_trees) as host numpy, paging from
        NVMe when offloaded there (used by save_checkpoint / zero_to_fp32)."""
        if self._swapper is None:
            return self._host_master, self._host_opt
        proto = self.state.params
        names = _STATE_NAMES[self._opt_type]

        def fetch(path, x):
            key = _leaf_key(path)
            return self._swapper.read_only(key, "master").reshape(x.shape)
        leaves, treedef = jax.tree_util.tree_flatten_with_path(proto)
        master = jax.tree_util.tree_unflatten(
            treedef, [fetch(p, x) for p, x in leaves])
        opt = {}
        for n in names:
            opt[n] = jax.tree_util.tree_unflatten(
                treedef,
                [self._swapper.read_only(_leaf_key(p), n).reshape(x.shape)
                 for p, x in leaves])
        return master, opt
