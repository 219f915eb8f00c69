"""Core training engine.

TPU-native analog of `DeepSpeedEngine` (reference: runtime/engine.py:198 —
`forward`:2114, `backward`:2286, `step`:2422, `_take_model_step`:2356,
`allreduce_gradients`:2181, checkpointing :3023/:3369).

Design inversion vs the reference: DeepSpeed wraps an eager nn.Module and
injects communication via hooks during autograd; here the whole training step
— forward, backward, gradient reduction, optimizer update, LR schedule, loss
scaling — is ONE jitted program over global arrays.  ZeRO partitioning,
gradient reduce-scatter, and parameter allgather are expressed as sharding
constraints (runtime/zero/sharding.py) and inserted by the XLA SPMD
partitioner at compile time, which also overlaps them with compute (the
`overlap_comm` behavior of stage_1_and_2.py:1136 falls out for free).

Gradient accumulation runs as a `lax.scan` over micro-batches inside the same
program (reference: GAS boundary logic engine.py:2451), accumulating fp32
grads; the collective reduction happens once per global step, like the
reference's `contiguous_gradients` bucketing path.

User contract (mirrors deepspeed.initialize):

    engine = deepspeed_tpu.initialize(
        loss_fn=loss_fn,        # (params, batch, rng) -> loss | (loss, aux)
        params=params,          # pytree (or init_fn(rng) -> pytree)
        config=ds_config,       # dict / path, DeepSpeed JSON keys
    )
    for batch in loader:
        metrics = engine.train_batch(batch)   # one optimizer step

`forward/backward/step` compat shims are provided for the reference's 3-call
loop; they drive the same jitted program.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..config.config import ConfigError, DeepSpeedTPUConfig
from ..parallel.mesh import MeshTopology, make_mesh
from ..utils.device import on_tpu
from ..utils.logging import log_dist, logger
from ..utils.spans import span
from ..utils import tree as tu
from . import lr_schedules, optimizers
from .zero.sharding import ZeroShardingRules, param_specs, opt_state_specs, grad_specs

__all__ = ["TrainEngine", "TrainState", "initialize"]

PyTree = Any


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    """All mutable training state; a single pytree so the whole step can
    donate and re-emit it."""

    step: jax.Array                      # int32 scalar, completed optimizer steps
    params: PyTree                       # compute-dtype params (bf16/fp16/fp32)
    master: Optional[PyTree]             # fp32 master copy (None when fp32 compute)
    opt_state: Dict[str, PyTree]         # optimizer moments, mirrors params
    loss_scale: jax.Array                # f32 scalar (1.0 when not fp16)
    good_steps: jax.Array                # int32: consecutive non-overflow steps
    skipped_steps: jax.Array             # int32 (reference: engine.skipped_steps)


def aux_zeros(micro_aux_fn, *args):
    """fp32 zeros matching the aux structure of one abstract micro step —
    the scan-carry accumulator init shared by the train engines."""
    shapes = jax.eval_shape(micro_aux_fn, *args)
    return jax.tree.map(lambda sh: jnp.zeros(sh.shape, jnp.float32), shapes)


_aux_collisions_warned: set = set()


def surface_aux(metrics: Dict[str, Any], aux) -> Dict[str, Any]:
    """Merge a loss_fn's aux outputs into the step metrics without shadowing
    the engine's reserved keys; non-dict aux (tuple/namedtuple) lands under
    one "aux" key rather than vanishing.  Shared by TrainEngine and
    ZeroOffloadEngine (one contract, one implementation).  A collision with
    a reserved metric name (loss, grad_norm, lr, ...) keeps the engine's
    value and warns once per key — silent discard hid user aux before."""
    if isinstance(aux, dict):
        for k, v in aux.items():
            if k in metrics:
                if k not in _aux_collisions_warned:
                    _aux_collisions_warned.add(k)
                    log_dist(
                        f"loss_fn aux key {k!r} collides with a reserved "
                        f"step-metric name and is dropped; rename it "
                        f"(e.g. 'aux_{k}') to surface it", ranks=[0],
                        level=logging.WARNING)
            else:
                metrics[k] = v
    elif aux is not None and jax.tree.leaves(aux):
        if "aux" in metrics and "aux" not in _aux_collisions_warned:
            _aux_collisions_warned.add("aux")
            log_dist("non-dict loss_fn aux collides with an existing 'aux' "
                     "metric and is dropped", ranks=[0],
                     level=logging.WARNING)
        metrics.setdefault("aux", aux)
    return metrics


class LossHandle:
    """Lazily-resolved scalar loss from the `forward()` compat shim.

    Resolves for free (to that micro-batch's unscaled loss) when the GAS
    boundary fires in `step()`.  `float(handle)` / `handle.item()` before
    the boundary forces one extra grad-free forward pass at current params
    — correct but paying a forward; prefer reading after `step()`.
    """

    __slots__ = ("_engine", "_batch", "_value")

    def __init__(self, engine, batch):
        self._engine = engine
        self._batch = batch
        self._value = None

    def _resolve(self, value) -> None:
        self._value = value
        self._engine = None
        self._batch = None

    @property
    def resolved(self) -> bool:
        return self._value is not None

    def item(self) -> float:
        if self._value is None:
            self._value = self._engine._eval_loss(self._batch)
            self._engine = None
            self._batch = None
        return float(self._value)

    def __float__(self) -> float:
        return self.item()

    def __repr__(self) -> str:
        if self._value is None:
            return "LossHandle(pending)"
        return f"LossHandle({float(self._value):.6g})"


class TrainEngine:
    """See module docstring.  Construction mirrors
    `DeepSpeedEngine.__init__` (engine.py:198): configure topology, wrap
    optimizer per ZeRO stage, build the compiled step."""

    def __init__(
        self,
        loss_fn: Callable,
        params: PyTree,
        config: DeepSpeedTPUConfig,
        topology: Optional[MeshTopology] = None,
        tp_rules: Optional[Callable] = None,
        eval_fn: Optional[Callable] = None,
    ):
        self.config = config
        self.loss_fn = loss_fn
        self.eval_fn = eval_fn or loss_fn
        # hpZ / MiCS carve the data dimension into dp×fsdp = (world/k)×k
        # (reference: groups.py:702 _create_zero_param_parallel_group,
        # mics.py:64).  The knob DRIVES the mesh — a k the device count
        # can't honour is a config error, not a silent no-op.
        shard_k, shard_knob = None, None
        if config.zero.mics_shard_size > 0:
            shard_k, shard_knob = config.zero.mics_shard_size, "mics_shard_size"
        elif config.zero.zero_hpz_partition_size > 1:
            shard_k = config.zero.zero_hpz_partition_size
            shard_knob = "zero_hpz_partition_size"
        if topology is not None:
            self.topology = topology
            if shard_k is not None and topology.fsdp_size != shard_k:
                raise ConfigError(
                    f"{shard_knob}={shard_k} conflicts with the explicit "
                    f"topology's fsdp={topology.fsdp_size}: the shard "
                    f"sub-group IS the fsdp axis — drop the knob or build "
                    f"the mesh with fsdp={shard_k}")
        else:
            try:
                self.topology = make_mesh(
                    fsdp=shard_k or 1,
                    tp=config.parallel.tensor_parallel_size,
                    pp=config.parallel.pipeline_parallel_size,
                    sp=max(config.parallel.sequence_parallel_size,
                           config.parallel.context_parallel_size),
                    ep=config.parallel.expert_parallel_size,
                )
            except ValueError as e:
                if shard_k is not None:
                    raise ConfigError(
                        f"{shard_knob}={shard_k} does not divide the "
                        f"data-parallel world: {e}") from e
                raise
        if config.zero.mics_hierarchical_params_gather \
                and config.zero.mics_shard_size > 0:
            # reference mics.py two-hop (intra- then inter-node) allgather:
            # under GSPMD the compiler already lowers the fsdp gather to a
            # hierarchical ICI/DCN schedule from the mesh's device order, so
            # the flag is honoured by construction rather than by a
            # hand-written two-hop
            log_dist("mics_hierarchical_params_gather: XLA lowers the fsdp "
                     "allgather hierarchically from mesh locality; no "
                     "manual two-hop needed", ranks=[0])
        config.reconcile_topology(self.topology.dp_size)
        from ..parallel.context import set_current_topology
        set_current_topology(self.topology)
        self.rules = ZeroShardingRules(
            config.zero.stage, self.topology, tp_rules=tp_rules,
            mics_shard_size=config.zero.mics_shard_size,
            leaf_paths=getattr(config, "z3_leaf_paths", None),
            hpz=config.zero.zero_hpz_partition_size > 1)
        self.optimizer = optimizers.build_optimizer(config.optimizer)
        base_lr = config.optimizer.lr if config.optimizer else 1e-3
        self.lr_fn = lr_schedules.build_scheduler(config.scheduler, base_lr)
        self.compute_dtype = config.precision.dtype
        self._rng = jax.random.PRNGKey(config.seed)

        # activation checkpointing global options (reference: engine wires
        # deepspeed.checkpointing.configure from config, engine.py:375 area)
        from .activation_checkpointing import configure as _ac_configure
        _ac_configure(config.activation_checkpointing)

        # monitor sinks (reference: engine emits loss/lr/samples-per-sec to
        # MonitorMaster, engine.py:2213-2221)
        self.monitor = None
        if config.monitor.enabled:
            from ..monitor.monitor import MonitorMaster
            self.monitor = MonitorMaster(config.monitor)

        if config.sparse_gradients:
            # reference engine.py:361-366 swaps embedding allreduce for a
            # sparse gather; under SPMD the dense grad is already
            # reduce-scattered (never fully materialized per rank), so the
            # flag maps to the row-sparse API rather than an engine rewrite
            logger.warning(
                "sparse_gradients=true: SPMD grads are reduce-scattered, so "
                "the dense embedding gradient is never replicated; for "
                "row-sparse gradient exchange in custom loops use "
                "deepspeed_tpu.runtime.sparse_tensor (sparse_lookup_vjp / "
                "allgather_sparse / apply_rows)")

        # retain last step's full grads for safe_get_full_grad
        # (utils/tensor_fragment.py; costs a param-sized fp32 buffer)
        self.store_gradients = False
        self._built_with_grads = False
        self._last_grads = None

        self.compression = None

        self.state = self._init_state(params)

        # compression training (reference: engine applies init_compression
        # when a compression_training section is present; the spec's QAT /
        # mask transforms run inside the jitted step — compression/compress.py)
        if config.compression.enabled:
            if not getattr(self, "supports_compression", True):
                log_dist(
                    f"WARNING: compression_training is ignored by "
                    f"{type(self).__name__} (mirrors the reference: 1-bit/"
                    f"offload engines run their own optimizer paths)",
                    ranks=[0])
            else:
                from ..compression import init_compression, compression_scheduler
                spec = init_compression(
                    self.state.params,
                    {"compression_training": config.compression.raw})
                if spec.enabled:
                    self.compression = compression_scheduler(spec, self.state.params)

        self._train_step = self._build_train_step()
        self._eval_step = None
        # forward/backward/step compat shim state
        self._pending_batches = []
        self._pending_handles = []
        self._loss_probe = None      # jitted loss-only forward (lazy)
        self._last_grad_norm = None  # device scalar from the last step
        self.global_steps = 0
        self._tput_t0 = None
        self._tput_samples = 0

        log_dist(
            f"engine up: zero_stage={config.zero.stage} dtype={self.compute_dtype.__name__} "
            f"mesh={dict(self.topology.axis_sizes)} "
            f"micro_bs={config.train_micro_batch_size_per_gpu} "
            f"gas={config.gradient_accumulation_steps} "
            f"global_bs={config.train_batch_size} "
            f"params={tu.count_params(self.state.master or self.state.params):,}",
            ranks=[0])

    # ------------------------------------------------------------------
    # state construction
    # ------------------------------------------------------------------
    def _named(self, spec_tree: PyTree) -> PyTree:
        """Specs -> NamedShardings, each spec in the form the compiled
        step hands its outputs back in (size-1 mesh axes dropped, trailing
        Nones trimmed).  Sharding equality is textual: state placed under
        P(None, 'fsdp', 'tp') and returned as the equivalent
        P(None, 'fsdp') made the SECOND train_batch compile the whole
        step again (11.8 s for ZeRO-3 fsdp=4 at 1.1B on the chip)."""
        mesh = self.topology.mesh

        def canonical(spec: PartitionSpec) -> PartitionSpec:
            out = []
            for entry in spec:
                names = entry if isinstance(entry, tuple) else (entry,)
                names = tuple(n for n in names
                              if n is not None and mesh.shape[n] > 1)
                out.append(None if not names else
                           names[0] if len(names) == 1 else names)
            while out and out[-1] is None:
                out.pop()
            return PartitionSpec(*out)

        return jax.tree.map(
            lambda s: NamedSharding(mesh, canonical(s)), spec_tree,
            is_leaf=lambda x: isinstance(x, PartitionSpec))

    def _init_state(self, params: PyTree) -> TrainState:
        if callable(params):  # init_fn(rng) -> pytree
            self._rng, init_key = jax.random.split(self._rng)
            params = params(init_key)
        fp32 = self.compute_dtype == jnp.float32

        p_specs = param_specs(self.rules, params)
        o_specs = opt_state_specs(self.rules, params)

        mesh = self.topology.mesh
        # place compute params THROUGH a non-donating jit: device_put can
        # alias the caller's buffer when sharding/dtype already match, and
        # the compiled step donates state — an aliased leaf would leave the
        # caller (or a second engine built from the same params) holding
        # deleted arrays. jit without donation must emit fresh buffers.
        dt = self.compute_dtype
        params = jax.jit(
            lambda t: jax.tree.map(lambda x: jnp.asarray(x, dt), t),
            out_shardings=self._named(p_specs))(params)
        if fp32:
            master = None
        else:
            master = jax.tree.map(
                lambda x, sh: jax.device_put(
                    jnp.asarray(x, dtype=jnp.float32), sh),
                params, self._named(o_specs))
        # optimizer moments, sharded like master (ZeRO>=1 partitioned)
        opt_state = jax.jit(
            self.optimizer.init,
            out_shardings=self._opt_tree_shardings(params, o_specs),
        )(master if master is not None else params)

        pc = self.config.precision
        init_scale = (2.0 ** pc.initial_scale_power
                      if pc.fp16_enabled and pc.loss_scale == 0 else
                      (pc.loss_scale if pc.fp16_enabled else 1.0))
        return TrainState(
            step=self._scalar(0, jnp.int32),
            params=params,
            master=master,
            opt_state=opt_state,
            loss_scale=self._scalar(init_scale, jnp.float32),
            good_steps=self._scalar(0, jnp.int32),
            skipped_steps=self._scalar(0, jnp.int32),
        )

    def _scalar(self, value, dtype) -> jax.Array:
        """A TrainState scalar placed on the mesh like the compiled step
        returns it: the mesh is part of an array's type, so a scalar built
        off the mesh makes the second train_batch retrace and recompile
        the whole step (17 s at 1.1B on the chip)."""
        return jax.device_put(
            jnp.asarray(value, dtype),
            NamedSharding(self.topology.mesh, PartitionSpec()))

    def _opt_tree_shardings(self, params, o_specs):
        """Optimizer state is {name: tree-like-params}; build matching
        sharding dict for each moment.  Quantized-moment scale trees
        ("*_scale", per-row fp32 absmax factors ~1/row-len the payload
        size) are replicated: their trailing size-1 dim cannot carry the
        payload's partitioning and they are too small to matter."""
        mesh = self.topology.mesh
        probe = jax.eval_shape(self.optimizer.init, params)
        named = self._named(o_specs)
        repl = jax.tree.map(
            lambda _: NamedSharding(mesh, PartitionSpec()), params)
        from .optimizers import is_scale_key
        return {k: (repl if is_scale_key(k) else named)
                for k in probe.keys()}

    # ------------------------------------------------------------------
    # the compiled train step
    # ------------------------------------------------------------------
    def _build_train_step(self):
        cfg = self.config
        opt = self.optimizer
        rules = self.rules
        lr_fn = self.lr_fn
        loss_fn = self.loss_fn
        gas = cfg.gradient_accumulation_steps
        clip = cfg.gradient_clipping
        fp16 = cfg.precision.fp16_enabled
        pc = cfg.precision
        mesh = self.topology.mesh

        comp_spec = self.compression.spec if self.compression else None

        def call_loss(params, batch, rng):
            out = loss_fn(params, batch, rng)
            if isinstance(out, tuple):
                return out[0], out[1]
            return out, {}
        # forwarded marker: the loss's layer scan consults
        # layer_gather.apply_layer_gathers (quantized per-layer fetch)
        call_loss.supports_layer_gather = getattr(
            loss_fn, "supports_layer_gather", False)

        def micro_grads(params, micro, rng, loss_scale, comp_masks, step):
            def scaled_loss(p):
                if comp_spec is not None:
                    from ..compression import CompressionState, compress_params
                    p = compress_params(
                        comp_spec, CompressionState(masks=comp_masks), p, step,
                        rng=rng)
                loss, aux = call_loss(p, micro, rng)
                return loss * loss_scale.astype(loss.dtype), (loss, aux)
            (_, (loss, aux)), grads = jax.value_and_grad(
                scaled_loss, has_aux=True)(params)
            return loss, aux, grads

        # ZeRO++ qwZ/qgZ/2-hop + EQuARX quantized all-reduce: route the
        # param gather / grad reduction through block-quantized collectives
        # (explicit shard_map region; reference partition_parameters.py:824
        # + coalesced_collectives.py:31; arxiv 2306.10209 / 2506.17615);
        # flag/stage compatibility is validated at config parse time
        # (config.py ZeroConfig)
        zc = cfg.zero
        quantized_path = (zc.zero_quantized_weights
                          or zc.zero_quantized_gradients
                          or zc.zero_quantized_allreduce)
        # T3 overlap (arxiv 2401.16677): microstep double-buffering defers
        # each microstep's grad reduction into the next scan iteration
        # (only meaningful with accumulation); layer mode moves stage<3
        # per-layer grad all-reduce into the backward scan
        overlap_micro = "microstep" in zc.overlap_mode and gas > 1
        if "microstep" in zc.overlap_mode and gas <= 1:
            log_dist(
                "overlap_mode='microstep' needs gradient_accumulation_"
                "steps > 1 to double-buffer; running the serialized step",
                ranks=[0], level=logging.WARNING)
        if quantized_path:
            from .zero.quantized import build_quantized_micro_grads
            from .zero.sharding import resolve_hierarchy
            hier = resolve_hierarchy(
                zc.zero_quantized_gradients_hierarchy, rules)
            micro_grads = build_quantized_micro_grads(
                call_loss, rules, self.topology, self.state.params,
                qwz=zc.zero_quantized_weights,
                qgz=zc.zero_quantized_gradients,
                qgz_bits=zc.zero_quantized_gradients_bits,
                comp_spec=comp_spec,
                qar=zc.zero_quantized_allreduce,
                hier=hier,
                intra_bits=zc.zero_quantized_gradients_intra_bits,
                bucket_size=zc.zero_quantized_bucket_size,
                layer_ar="layer" in zc.overlap_mode and zc.stage < 3,
                defer_finish=overlap_micro)
        elif overlap_micro:
            # no quantized path: the raw/finish split is the unconstrained
            # grads vs the grad-layout constraint — issuing the constraint
            # per microstep (one iteration late) hands GSPMD a per-
            # microstep reduction it can schedule under the next
            # microstep's compute instead of one bulk reduction after the
            # whole accumulation scan
            def _finish_constrain(g):
                return jax.lax.with_sharding_constraint(
                    g, self._named(grad_specs(rules, self.state.params)))
            micro_grads.finish = _finish_constrain
            micro_grads.raw = micro_grads

        # grad residence dtype between backward and optimizer update
        # (reference: data_types.grad_accum_dtype, runtime/config.py:850).
        # fp32 default; bf16 halves the resident grad buffer — the update
        # itself always computes in fp32 (optimizers.py casts per leaf)
        gad = {None: jnp.float32, "fp32": jnp.float32,
               "float32": jnp.float32, "bf16": jnp.bfloat16,
               "bfloat16": jnp.bfloat16, "fp16": jnp.float16,
               "float16": jnp.float16}.get(cfg.grad_accum_dtype, "bad")
        if gad == "bad":
            raise ConfigError(
                f"data_types.grad_accum_dtype {cfg.grad_accum_dtype!r} "
                f"not supported (fp32 | bf16 | fp16)")

        # a model whose loss adds its layer stack's gradient into a sink
        # (models/transformer.py `_grads_into`) accumulates where the
        # backward scan produces it: the accumulator is the only stacked
        # tree alive, and the pass that added a micro-batch's whole tree to
        # it is gone.  The paths that rework a micro-batch's gradient tree
        # before it is added keep the tree
        sink_key = getattr(loss_fn, "grad_sink", None)
        if quantized_path or overlap_micro or comp_spec is not None:
            sink_key = None

        def add(acc, grads):
            return jax.tree.map(lambda a, g: a + g.astype(gad), acc, grads)

        def micro_accumulate(acc, params, micro, rng, loss_scale, comp_masks,
                             step):
            """One micro-batch: (loss, aux, `acc` + its gradient), every
            leaf added once, as `acc + g`."""
            if sink_key is None:
                loss, aux, grads = micro_grads(params, micro, rng, loss_scale,
                                               comp_masks, step)
                return loss, aux, add(acc, grads)

            def scaled_loss(p, sink):
                loss, aux, sink = loss_fn(p, micro, rng, grad_sink=sink)
                return (loss * loss_scale.astype(loss.dtype), sink), (loss, aux)
            (scaled, _), vjp, (loss, aux) = jax.vjp(
                scaled_loss, params, acc[sink_key], has_aux=True)
            grads, sunk = vjp((jnp.ones_like(scaled), acc[sink_key]))
            return loss, aux, {
                k: sunk if k == sink_key else add(a, grads[k])
                for k, a in acc.items()}

        def train_step(state: TrainState, batch: PyTree, rng,
                       comp_masks) -> Tuple[TrainState, Dict]:
            params = state.params
            g_specs = grad_specs(rules, params)
            o_specs = opt_state_specs(rules, params)

            # the three scopes name the step's device work in a trace
            # (docs/OBSERVABILITY.md): metadata only
            with jax.named_scope("forward_backward"):
                # ---- gradient accumulation over micro-batches (lax.scan) ----
                # batch leaves: [gas, micro_global, ...]
                accum0 = tu.tree_zeros_like(params, gad)

                def body(carry, micro):
                    acc, aux_acc, loss_sum, i = carry
                    k = jax.random.fold_in(rng, i)
                    loss, aux, acc = micro_accumulate(
                        acc, params, micro, k, state.loss_scale, comp_masks,
                        state.step)
                    aux_acc = jax.tree.map(
                        lambda a, v: a + v.astype(jnp.float32), aux_acc, aux)
                    return (acc, aux_acc, loss_sum + loss.astype(jnp.float32),
                            i + 1), loss.astype(jnp.float32)

                if gas > 1 and overlap_micro:
                    # ---- T3 microstep double-buffering (overlap_mode=
                    # "microstep"): microstep 0 is peeled and its RAW grads
                    # ride the scan carry; each iteration issues the PREVIOUS
                    # microstep's reductions FIRST — no data dependency on
                    # this microstep's forward/backward, so XLA's async
                    # collective scheduler can hide them under its compute —
                    # then runs its own fwd/bwd and hands its raw grads to the
                    # next iteration.  The last microstep's reduction runs
                    # after the scan.  Costs one raw-grad tree of carry (the
                    # double buffer); reassociates the accumulation order, so
                    # it is opt-in (the default path stays bit-exact). ----
                    first_micro = jax.tree.map(lambda x: x[0], batch)
                    rest = jax.tree.map(lambda x: x[1:], batch)
                    # the accumulator adds FINISHED grads (already in the
                    # grad layout); pin it there so GSPMD does not reshard
                    # the carry against each iteration's addend
                    accum0 = jax.lax.with_sharding_constraint(
                        accum0, self._named(g_specs))
                    k0 = jax.random.fold_in(rng, 0)
                    loss0, aux0v, raw0 = micro_grads.raw(
                        params, first_micro, k0, state.loss_scale, comp_masks,
                        state.step)
                    aux0 = jax.tree.map(
                        lambda v: v.astype(jnp.float32), aux0v)
                    loss0 = loss0.astype(jnp.float32)

                    def body_overlap(carry, micro):
                        acc, raw_prev, aux_acc, loss_sum, i = carry
                        finished = micro_grads.finish(raw_prev)
                        acc = add(acc, finished)
                        k = jax.random.fold_in(rng, i)
                        loss, aux, raw = micro_grads.raw(
                            params, micro, k, state.loss_scale, comp_masks,
                            state.step)
                        aux_acc = jax.tree.map(
                            lambda a, v: a + v.astype(jnp.float32), aux_acc, aux)
                        return (acc, raw, aux_acc,
                                loss_sum + loss.astype(jnp.float32),
                                i + 1), loss.astype(jnp.float32)

                    (acc, raw_last, aux_sum, loss_sum, _), rest_losses = \
                        jax.lax.scan(
                            body_overlap,
                            (accum0, raw0, aux0, loss0,
                             jnp.ones((), jnp.int32)), rest)
                    grads = add(acc, micro_grads.finish(raw_last))
                    micro_losses = jnp.concatenate([loss0[None], rest_losses])
                    aux = jax.tree.map(lambda a: a / gas, aux_sum)
                    loss = loss_sum / gas
                elif gas > 1:
                    # aux accumulates in the carry (constant memory) — its
                    # structure comes from an abstract trace of one micro step
                    first_micro = jax.tree.map(lambda x: x[0], batch)
                    aux0 = aux_zeros(
                        lambda p, m: micro_grads(p, m, rng, state.loss_scale,
                                                 comp_masks, state.step)[1],
                        params, first_micro)
                    (grads, aux_sum, loss_sum, _), micro_losses = jax.lax.scan(
                        body, (accum0, aux0, jnp.zeros((), jnp.float32),
                               jnp.zeros((), jnp.int32)), batch)
                    aux = jax.tree.map(lambda a: a / gas, aux_sum)
                    loss = loss_sum / gas
                else:
                    micro = jax.tree.map(lambda x: x[0], batch)
                    loss, aux, g = micro_grads(params, micro, rng, state.loss_scale,
                                               comp_masks, state.step)
                    grads = jax.tree.map(lambda x: x.astype(gad), g)
                    loss = loss.astype(jnp.float32)
                    micro_losses = loss[None]

            with jax.named_scope("grad_reduce"):
                # ---- unscale + average over accumulation (reference:
                # _backward_prologue scale_wrt_gas engine.py:2199).  When the
                # optimizer supports grad_scale, the unscale AND the clip
                # multiplies FOLD into its update pass as one scalar — the
                # global norm is homogeneous (norm(raw)*inv == norm(unscaled))
                # so nothing needs the rewritten grads, and two full
                # read+write passes over the grad tree (~12 GB at the 1.3B
                # bench) disappear from the step tail ----
                # fp16 keeps the unscale BEFORE the cross-device reduction:
                # folding would sum still-loss-scaled grads over dp, costing
                # log2(dp_size) bits of fp16 headroom (overflow -> permanent
                # step-skipping under a static scale).  bf16/fp32 have the
                # exponent range to reduce first.
                inv = 1.0 / (state.loss_scale * gas)
                fold_scale = getattr(opt, "supports_grad_scale", False) \
                    and self.compression is None and not fp16
                if not fold_scale:
                    grads = jax.tree.map(lambda g: g * inv, grads)

                # ---- ZeRO gradient sharding constraint: stage>=2 this forces a
                # ReduceScatter; stage<2 an AllReduce (sharding.py docstring) ----
                grads = jax.lax.with_sharding_constraint(grads, self._named(g_specs))

                # ---- overflow check (reference: CheckOverflow + DynamicLossScaler
                # fp16/loss_scaler.py:93). bf16/fp32 skip the check — at TRACE
                # time, not with a constant-True select: a traced
                # where(finite, new, old) over master + every moment is an
                # extra full read+select+write of ~9 GB of optimizer state at
                # the 774M bench (XLA cannot fold a select on a runtime
                # scalar), measured in the step-vs-grad decomposition gap ----
                if fp16:
                    finite = tu.tree_finite(grads)
                else:
                    finite = jnp.asarray(True)

                # ---- grad clip by global norm (engine config gradient_clipping;
                # reference: runtime/utils.py clip_grad_norm_) ----
                if fold_scale:
                    gnorm = tu.global_norm(grads) * inv
                    gscale = inv
                    if clip and clip > 0:
                        gscale = inv * jnp.minimum(1.0, clip / (gnorm + 1e-6))
                else:
                    gnorm = tu.global_norm(grads)
                    gscale = None
                    if clip and clip > 0:
                        scale = jnp.minimum(1.0, clip / (gnorm + 1e-6))
                        grads = jax.tree.map(lambda g: g * scale, grads)

            with jax.named_scope("optimizer"):
                # ---- optimizer update on fp32 master (BF16_Optimizer semantics,
                # runtime/bf16_optimizer.py:274) ----
                master = state.master if state.master is not None else params
                step_num = state.step + 1
                lr = lr_fn(state.step)
                # fused single-pass update (Pallas; optimizers.update_fused)
                # emits the compute-dtype params from the same VMEM pass —
                # TPU only, and only when a cast is wanted (master mode)
                use_fused = (opt.update_fused is not None
                             and state.master is not None
                             and on_tpu())
                new_params_cast = None
                fold_kw = {"grad_scale": gscale} if fold_scale else {}
                if use_fused:
                    new_master, new_params_cast, new_opt = opt.update_fused(
                        grads, state.opt_state, master, lr,
                        step_num.astype(jnp.float32), self.compute_dtype,
                        **fold_kw)
                else:
                    new_master, new_opt = opt.update(
                        grads, state.opt_state, master, lr,
                        step_num.astype(jnp.float32), **fold_kw)
                new_master = jax.lax.with_sharding_constraint(new_master, self._named(o_specs))
                # the moments (and their scale trees) come back placed as
                # _init_state placed them: left to propagation, the replicated
                # scales returned sharded and the second step recompiled
                new_opt = jax.lax.with_sharding_constraint(
                    new_opt, self._opt_tree_shardings(master, o_specs))

                # skip update on overflow (reference: step skipping engine.py:2400)
                if fp16:
                    new_master = tu.tree_where(finite, new_master, master)
                    new_opt = {k: tu.tree_where(finite, v, state.opt_state[k])
                               for k, v in new_opt.items()}
                    if new_params_cast is not None:
                        # params IS cast(master) from the previous step — no
                        # per-step recast just to feed the overflow branch
                        new_params_cast = tu.tree_where(
                            finite, new_params_cast, params)

                if state.master is not None:
                    p_specs = param_specs(rules, params)
                    cast = (new_params_cast if new_params_cast is not None
                            else tu.tree_cast(new_master, self.compute_dtype))
                    new_params = jax.lax.with_sharding_constraint(
                        cast, self._named(p_specs))
                    new_state_master = new_master
                else:
                    # no master copy: params ARE the optimizer's target, but
                    # their resident layout must stay param_specs — under hpZ
                    # o_specs span dp×fsdp while the param gather domain is
                    # fsdp-only, and inheriting the opt layout here would
                    # silently widen every later gather to the full world
                    new_params = jax.lax.with_sharding_constraint(
                        new_master, self._named(param_specs(rules, params)))
                    new_state_master = None

            # ---- dynamic loss scale update ----
            if fp16 and pc.loss_scale == 0:
                window = pc.loss_scale_window
                good = jnp.where(finite, state.good_steps + 1, 0)
                grow = jnp.logical_and(finite, good >= window)
                new_scale = jnp.where(
                    grow, state.loss_scale * 2.0,
                    jnp.where(finite, state.loss_scale,
                              jnp.maximum(state.loss_scale / 2.0, pc.min_loss_scale)))
                good = jnp.where(grow, 0, good)
            else:
                new_scale = state.loss_scale
                good = state.good_steps

            new_state = TrainState(
                step=jnp.where(finite, step_num, state.step) if fp16
                else step_num,
                params=new_params,
                master=new_state_master,
                opt_state=new_opt,
                loss_scale=new_scale,
                good_steps=good,
                skipped_steps=state.skipped_steps + (
                    jnp.where(finite, 0, 1) if fp16 else 0),
            )
            metrics = {
                "loss": loss,
                "grad_norm": gnorm,
                "lr": lr,
                "loss_scale": state.loss_scale,
                "overflow": jnp.logical_not(finite),
                # per-micro unscaled losses, [gas] — lets the 3-call compat
                # loop hand each forward() its own loss (reference:
                # engine.forward returns the micro loss, engine.py:1847)
                "micro_losses": micro_losses,
            }
            # engine-owned keys land first so surface_aux's collision
            # warning fires for user aux that would shadow them
            if self.store_gradients:
                # contract (safe_get_full_grad): unscaled, post-clip grads
                metrics["grads"] = (
                    jax.tree.map(lambda g: g * gscale, grads)
                    if fold_scale else grads)
            # loss_fn aux outputs (ppl_log/moe_aux/custom kl...) -> metrics
            surface_aux(metrics, aux)
            return new_state, metrics

        self._built_with_grads = self.store_gradients
        return jax.jit(train_step, donate_argnums=(0,))

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def _shard_batch(self, batch: PyTree) -> PyTree:
        """Reshape a global batch [train_batch_size, ...] to
        [gas, micro_global, ...] and shard micro dim over the data axes."""
        gas = self.config.gradient_accumulation_steps
        mesh = self.topology.mesh
        data_axes = self.topology.data_axes

        expected = self.config.train_batch_size

        def leaf(x):
            x = np.asarray(x) if not isinstance(x, jax.Array) else x
            n = x.shape[0]
            if n != expected:
                raise ValueError(
                    f"batch leading dim {n} != train_batch_size {expected} "
                    f"(= micro {self.config.train_micro_batch_size_per_gpu} * gas {gas}"
                    f" * dp {self.config.data_parallel_size})")
            micro_global = n // gas
            x = x.reshape((gas, micro_global) + x.shape[1:])
            # SP: additionally shard the sequence dim (reference:
            # UlyssesSPDataLoaderAdapter ulysses_sp.py:428 shards each batch
            # on the sequence dim across the SP group)
            from ..parallel.mesh import AXIS_SP
            sp_axis = (AXIS_SP,) if (self.topology.sp_size > 1 and x.ndim >= 3
                                     and x.shape[2] % self.topology.sp_size == 0) \
                else (None,)
            # truncate to the leaf's rank: a [B]-shaped leaf (per-sample
            # scalars — advantages, rewards, seq lens) reshapes to rank 2
            # and takes just (None, data_axes); shorter-than-rank specs
            # leave trailing dims replicated
            dims = (None, data_axes) + sp_axis
            spec = PartitionSpec(*dims[:x.ndim])
            sharding = NamedSharding(mesh, spec)
            return jax.device_put(x, sharding)

        return jax.tree.map(leaf, batch)

    def next_rng(self) -> jax.Array:
        self._rng, k = jax.random.split(self._rng)
        return k

    def train_batch(self, batch: PyTree) -> Dict[str, Any]:
        """One global optimizer step over a full [train_batch_size, ...] batch
        (reference: PipelineEngine.train_batch engine.py:337 is the analogous
        whole-batch API; for the plain engine this folds the reference's
        forward/backward x gas + step loop into one call)."""
        if self._tput_t0 is None:
            self._tput_t0 = time.time()
        if self._no_sync_depth > 0 and not self._warned_no_sync_fused:
            # fused train_batch reduces at the boundary by construction;
            # no_sync cannot suppress that (see no_sync docstring)
            self._warned_no_sync_fused = True
            logger.warning(
                "train_batch() called inside no_sync(): the fused step "
                "always syncs gradients at the boundary; no_sync only "
                "affects the forward/backward/step compat loop")
        # host spans in the profiler's trace (utils/spans.py): the step is
        # dispatched, not awaited, so `train.step` is the host's share
        with span("train.step", step=self.global_steps + 1):
            if self.store_gradients != self._built_with_grads:
                self._train_step = self._build_train_step()
            with span("train.shard_batch"):
                sharded = self._shard_batch(batch)
            comp_masks = {}
            if self.compression is not None:
                comp_masks = dict(self.compression.step(
                    self.state.params, self.global_steps).masks)
            with span("train.dispatch"):
                self.state, metrics = self._train_step(
                    self.state, sharded, self.next_rng(), comp_masks)
            if self.store_gradients:
                self._last_grads = metrics.pop("grads")
            else:
                self._last_grads = None  # never serve stale grads
            self._finish_step(metrics)
        return metrics

    def _finish_step(self, metrics: Dict[str, Any]) -> None:
        """Shared per-step bookkeeping: counters, steps_per_print log,
        monitor events (reference: engine step path 2419-2482).  Lives
        here (not in train_batch) so the offload/zenflow train_batch
        overrides feed the same get_global_grad_norm surface."""
        self._last_grad_norm = metrics.get("grad_norm")
        self.global_steps += 1
        self._tput_samples += self.config.train_batch_size
        if self.config.steps_per_print and self.global_steps % self.config.steps_per_print == 0:
            m = {k: float(v) for k, v in metrics.items()
                 if np.ndim(v) == 0}
            elapsed = time.time() - self._tput_t0
            sps = self._tput_samples / max(elapsed, 1e-9)
            log_dist(
                f"step={self.global_steps} loss={m['loss']:.4f} lr={m['lr']:.3e} "
                f"gnorm={m['grad_norm']:.3f} samples/sec={sps:.1f}", ranks=[0])
            if self.monitor is not None and self.monitor.enabled:
                step = self.global_steps
                self.monitor.write_events([
                    ("Train/loss", m["loss"], step),
                    ("Train/lr", m["lr"], step),
                    ("Train/grad_norm", m["grad_norm"], step),
                    ("Train/samples_per_sec", sps, step),
                ])

    # -- reference-style 3-call loop compat (engine.forward/backward/step) --
    def forward(self, batch: PyTree):
        """Compat shim: queue a micro-batch and return a `LossHandle` — a
        lazily-resolved scalar loss.  The reference's 3-call loop does
        `loss = engine(batch)` and logs/uses that loss
        (reference: engine.forward engine.py:1847, used at 2114); here the
        loss is computed inside the fused compiled step at the GAS
        boundary, so the handle resolves for free when `step()` fires.
        Coercing it to float *before* the boundary forces one extra
        (grad-free) forward pass at the current params."""
        handle = LossHandle(self, batch)
        self._pending_batches.append(batch)
        self._pending_handles.append(handle)
        return handle

    def backward(self, loss=None):
        """Compat shim (reference: engine.backward:2286): grads accumulate
        inside the compiled step at the boundary; no-op here."""
        return None

    def step(self):
        """Compat shim (reference: engine.step:2422): when
        len(pending) == gradient_accumulation_steps, run the fused step.
        Under an active no_sync() context micro-batches keep queueing past
        the boundary (reference semantics: accumulation without sync)."""
        if self._no_sync_depth > 0:
            return None
        gas = self.config.gradient_accumulation_steps
        if len(self._pending_batches) < gas:
            return None
        if len(self._pending_batches) > gas and not self._warned_extended_gas:
            self._warned_extended_gas = True
            logger.warning(
                "%d micro-batches queued, more than one "
                "gradient_accumulation_steps=%d window (extra forward() "
                "calls, or accumulation under no_sync()); step() runs each "
                "complete window as its own sequential optimizer update, NOT "
                "one combined large-batch update — raise "
                "gradient_accumulation_steps for exact big-batch semantics",
                len(self._pending_batches), gas)
        out = None
        while len(self._pending_batches) >= gas:
            window, self._pending_batches = (
                self._pending_batches[:gas], self._pending_batches[gas:])
            handles, self._pending_handles = (
                self._pending_handles[:gas], self._pending_handles[gas:])
            batch = jax.tree.map(
                lambda *xs: np.concatenate([np.asarray(x) for x in xs],
                                           axis=0), *window)
            out = self.train_batch(batch)
            micro_losses = out.get("micro_losses")
            for i, h in enumerate(handles):
                h._resolve(micro_losses[i] if micro_losses is not None
                           else out["loss"])
        if self._pending_batches and not self._warned_partial_window:
            self._warned_partial_window = True
            logger.warning(
                "%d queued micro-batch(es) did not fill a "
                "gradient_accumulation_steps=%d window and remain pending; "
                "they will be folded into the NEXT accumulation window (or "
                "silently unused if training stops here)",
                len(self._pending_batches), gas)
        return out

    _no_sync_depth = 0            # class defaults; set by no_sync()/step()
    _warned_extended_gas = False
    _warned_no_sync_fused = False
    _warned_partial_window = False

    def no_sync(self):
        """Reference API (engine.py:2265): suppress gradient sync so
        accumulation can extend past the configured GAS window.  In the
        forward/backward/step compat loop this defers the boundary firing
        (micro-batches keep queueing) until the context exits.  Inside a
        fused `train_batch` call reduction happens at the boundary by
        construction, so there is nothing to suppress there (a warning is
        logged if tried)."""
        engine = self

        class _NoSync:
            def __enter__(self):
                # depth-counted so nested no_sync contexts compose (the
                # inner exit must not re-enable boundary firing)
                engine._no_sync_depth += 1
                return self

            def __exit__(self, *exc):
                engine._no_sync_depth = max(0, engine._no_sync_depth - 1)
                return False

        return _NoSync()

    def eval_batch(self, batch: PyTree):
        if self._eval_step is None:
            def ev(params, batch, rng):
                out = self.eval_fn(params, batch, rng)
                return out[0] if isinstance(out, tuple) else out
            self._eval_step = jax.jit(ev)
        micro = jax.tree.map(lambda x: jnp.asarray(x), batch)
        return self._eval_step(self.state.params, micro, self.next_rng())

    # -- checkpointing (see runtime/checkpoint) -------------------------
    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[Dict] = None):
        from .checkpoint.checkpointing import save_checkpoint as _save
        return _save(self, save_dir, tag=tag, client_state=client_state or {})

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None):
        from .checkpoint.checkpointing import load_checkpoint as _load
        return _load(self, load_dir, tag=tag)

    def commit_checkpoint(self, tag: str = "") -> bool:
        """Fence async checkpoint writes (reference: checkpoint_engine
        commit at the GAS boundary, engine.py:2454)."""
        from .checkpoint.checkpointing import commit_checkpoint as _commit
        return _commit(self, tag)

    def load_universal_checkpoint(self, universal_dir: str):
        """Resume from UCP atoms under the current topology (reference:
        `load_universal` flag → _load_universal_checkpoint)."""
        from ..checkpoint.universal import load_universal_checkpoint as _lu
        return _lu(self, universal_dir)

    # -- state offload API (reference: runtime/zero/offload_states.py:90
    # engine.offload_states/reload_states free HBM between training phases,
    # e.g. during the RLHF generation phase) ----------------------------
    def offload_states(self, include=("opt_state", "master")) -> None:
        """Move the named state trees to host RAM, freeing device HBM."""
        st = self.state
        repl = {}
        for name in include:
            tree = getattr(st, name)
            if tree is None or (isinstance(tree, dict) and not tree):
                continue
            host = jax.tree.map(lambda x: np.asarray(x), tree)
            jax.tree.map(lambda x: x.delete() if isinstance(x, jax.Array) else None,
                         tree)
            repl[name] = host
        self.state = dataclasses.replace(st, **repl)
        self._offloaded = tuple(repl)

    def reload_states(self) -> None:
        """Undo offload_states: re-place host trees on device, resharded."""
        names = getattr(self, "_offloaded", ())
        if not names:
            return
        st = self.state
        o_specs = self._named(opt_state_specs(self.rules, st.params))
        # quantized-moment scale trees are replicated, exactly as at init
        # (_opt_tree_shardings): their trailing size-1 dim cannot carry
        # the payload's partitioning
        repl_spec = jax.tree.map(
            lambda _: NamedSharding(self.topology.mesh, PartitionSpec()),
            st.params)
        from .optimizers import is_scale_key
        repl = {}
        for name in names:
            tree = getattr(st, name)
            if name == "opt_state":
                repl[name] = {
                    k: jax.tree.map(
                        jax.device_put, v,
                        repl_spec if is_scale_key(k) else o_specs)
                    for k, v in tree.items()}
            else:
                repl[name] = jax.tree.map(jax.device_put, tree, o_specs)
        self.state = dataclasses.replace(st, **repl)
        self._offloaded = ()

    # -- introspection --------------------------------------------------
    @property
    def params(self) -> PyTree:
        return self.state.params

    def get_lr(self):
        return float(self.lr_fn(self.state.step))

    def get_global_grad_norm(self):
        """Global (pre-clip) gradient norm of the last optimizer step, or
        None before the first step (reference: engine.get_global_grad_norm
        property engine.py:508)."""
        if self._last_grad_norm is None:
            return None
        return float(self._last_grad_norm)

    def _eval_loss(self, micro: PyTree):
        """Grad-free loss forward for early LossHandle coercion.  Applies
        the same compression/pruning masks as the fused step's micro_grads
        so the early reading agrees with the boundary resolution."""
        if self._loss_probe is None:
            comp_spec = self.compression.spec if self.compression else None

            def probe(params, batch, rng, comp_masks, step):
                if comp_spec is not None:
                    from ..compression import CompressionState, compress_params
                    params = compress_params(
                        comp_spec, CompressionState(masks=comp_masks),
                        params, step, rng=rng)
                out = self.loss_fn(params, batch, rng)
                return out[0] if isinstance(out, tuple) else out
            self._loss_probe = jax.jit(probe)
        comp_masks = {}
        if self.compression is not None:
            comp_masks = dict(
                self.compression.step(self.state.params, self.global_steps).masks)
        micro = jax.tree.map(jnp.asarray, micro)
        return self._loss_probe(self.state.params, micro, self._rng,
                                comp_masks, self.state.step)

    @property
    def loss_scale(self):
        return float(self.state.loss_scale)


def initialize(
    loss_fn: Callable = None,
    params: PyTree = None,
    config=None,
    topology: Optional[MeshTopology] = None,
    tp_rules: Optional[Callable] = None,
    eval_fn: Optional[Callable] = None,
    model=None,
    mpu=None,
    optimizer=None,
    lr_scheduler=None,
    training_data=None,
) -> TrainEngine:
    """Entry point mirroring `deepspeed.initialize` (deepspeed/__init__.py:69).

    Returns the engine only (optimizer/scheduler live inside it; the
    reference returns them as a tuple for torch idiom — here they are
    engine-internal by functional design).

    `model` may be a deepspeed_tpu.models.Model (bundles init/loss/tp rules);
    otherwise pass `loss_fn` + `params` explicitly.
    """
    # set-up's `train.build` span: config, state initialised and sharded,
    # the step built (it compiles at the first `train_batch`)
    with span("train.build"):
        return _initialize(loss_fn, params, config, topology, tp_rules,
                           eval_fn, model, mpu, optimizer, lr_scheduler,
                           training_data)


def _initialize(loss_fn, params, config, topology, tp_rules, eval_fn, model,
                mpu, optimizer, lr_scheduler, training_data) -> TrainEngine:
    if model is not None:
        # a block that is served only says here what training would take
        getattr(model, "refuse_serving_only", lambda what: None)(
            "deepspeed_tpu.initialize (training)")
        if loss_fn is None:
            loss_fn = model.loss_fn
            markers = {k: getattr(model, k) for k in
                       ("supports_layer_gather", "grad_sink")
                       if getattr(model, k, None)}
            if markers:
                # bound methods refuse attributes — wrap to carry the
                # markers the quantized per-layer gather path and the
                # accumulation loop check
                base_loss = loss_fn

                def loss_fn(p, b, rng=None, _f=base_loss, **kw):
                    return _f(p, b, rng, **kw)
                for k, v in markers.items():
                    setattr(loss_fn, k, v)
        params = params if params is not None else model.init_params
        tp_rules = tp_rules or getattr(model, "tp_rules", None)
    if loss_fn is None or params is None:
        raise ValueError("initialize() needs loss_fn+params or model=")
    if mpu is not None and topology is None:
        # Megatron-style external model-parallel unit (reference:
        # deepspeed/__init__.py:103 accepts mpu and takes its groups):
        # carry over its tp (and pp when exposed) degrees into the mesh
        def _mpu_size(*names):
            for n in names:
                fn = getattr(mpu, n, None)
                if fn is not None:
                    return int(fn())
            return 1
        topology = make_mesh(
            tp=_mpu_size("get_tensor_model_parallel_world_size",
                         "get_model_parallel_world_size"),
            pp=_mpu_size("get_pipeline_model_parallel_world_size"))
    cfg = DeepSpeedTPUConfig.from_json(config or {}, world_size=jax.device_count())
    if optimizer is not None:
        # client-constructed optimizer (reference: deepspeed.initialize's
        # `optimizer=` arg with FusedAdam/DeepSpeedCPUAdam instances);
        # accepts the ops.* shim classes, an OptimizerConfig, or a config
        # dict — takes precedence over the JSON "optimizer" block, like the
        # reference's client optimizer does
        from ..config.config import OptimizerConfig
        if hasattr(optimizer, "ds_config"):
            cfg.optimizer = optimizer.ds_config
        elif isinstance(optimizer, OptimizerConfig):
            cfg.optimizer = optimizer
        elif isinstance(optimizer, dict):
            cfg.optimizer = OptimizerConfig(
                type=optimizer.get("type", "adamw"),
                params=optimizer.get("params", {}))
        else:
            raise TypeError(
                f"optimizer= expects a deepspeed_tpu.ops optimizer shim "
                f"(ops.adam.FusedAdam, ops.lamb.FusedLamb, ...), an "
                f"OptimizerConfig, or a config dict — got "
                f"{type(optimizer).__name__} (torch optimizer instances "
                f"cannot drive the jitted step)")
    if lr_scheduler is not None:
        # fail before the (expensive, globally side-effecting) engine build.
        # The functional engine needs a traceable step -> lr callable — not
        # a torch scheduler object, and not the reference's other documented
        # form (a factory `lambda optimizer: scheduler`), which would only
        # explode with an opaque tracer error inside the first compiled
        # step.  A probe call catches both up front.
        _sched_err = TypeError(
            f"lr_scheduler= expects a callable step -> learning rate "
            f"(jax-traceable; it runs inside the compiled step), got "
            f"{type(lr_scheduler).__name__!s} — torch scheduler objects / "
            f"`lambda optimizer: ...` factories cannot drive the jitted "
            f"program; use the config 'scheduler' block or write the "
            f"schedule as a function of the step")
        if not callable(lr_scheduler):
            raise _sched_err
        try:
            probe = lr_scheduler(jnp.zeros((), jnp.int32))
            jnp.asarray(probe) + 0.0
        except Exception as e:
            raise _sched_err from e
    if model is not None and getattr(model, "_z3_leaf_paths", None):
        # set_z3_leaf_modules marks (runtime/zero/init_context.py); the
        # sharding rules keep these subtrees out of fsdp partitioning
        cfg.z3_leaf_paths = list(model._z3_leaf_paths)
    compile_decisions: Dict[str, Any] = {}
    if model is not None and (cfg.raw or {}).get("compile", {}).get("deepcompile"):
        # DeepCompile analog: profiling-driven persistent-param selection +
        # remat policy, applied before the engine compiles its step
        from ..compile import apply_compile_config
        compile_decisions = apply_compile_config(
            cfg, model, world_size=jax.device_count())
    engine_cls = TrainEngine
    if cfg.optimizer is not None:
        from .onebit import OnebitEngine, is_onebit_optimizer
        if is_onebit_optimizer(cfg.optimizer.type):
            engine_cls = OnebitEngine
    _any_offload = (cfg.zero.offload_optimizer.device in ("cpu", "nvme")
                    or cfg.zero.offload_param.device in ("cpu", "nvme"))
    if _any_offload:
        if engine_cls is not TrainEngine:
            raise ValueError(
                "1-bit optimizers do not compose with cpu/nvme offload "
                "(the compressed exchange needs device-resident states)")
        # offload_param implies the host-optimizer engine: the update runs
        # where the master weights live (ZeRO-Infinity residence)
        from .offload_engine import ZeroOffloadEngine
        engine_cls = ZeroOffloadEngine
        if getattr(cfg.zero, "zenflow", None):
            if cfg.zero.offload_param.device in ("cpu", "nvme"):
                raise ValueError(
                    "zenflow does not compose with offload_param residence "
                    "(its selective upload path assumes device-resident "
                    "params); use offload_optimizer only")
            from .zenflow import ZenFlowEngine
            engine_cls = ZenFlowEngine
    hybrid = (getattr(cfg, "raw", None) or {}).get("hybrid_engine", {})
    if hybrid.get("enabled"):
        # reference: deepspeed.initialize picks DeepSpeedHybridEngine when
        # the config enables hybrid_engine (deepspeed/__init__.py:181)
        if engine_cls is not TrainEngine:
            raise ValueError("hybrid_engine does not compose with 1-bit/"
                             "offload engines (as in the reference)")
        from .hybrid_engine import DeepSpeedHybridEngine
        engine = DeepSpeedHybridEngine(loss_fn, params, cfg, model=model,
                                       topology=topology, tp_rules=tp_rules,
                                       eval_fn=eval_fn)
    else:
        engine = engine_cls(loss_fn, params, cfg, topology=topology,
                            tp_rules=tp_rules, eval_fn=eval_fn)
    engine.compile_decisions = compile_decisions

    if lr_scheduler is not None:
        # client LR scheduler (reference: deepspeed.initialize's
        # lr_scheduler= arg); validated up front, applied here
        engine.lr_fn = lr_scheduler
        engine._train_step = engine._build_train_step()

    if training_data is not None:
        # reference: initialize(training_data=dataset) returns a
        # DeepSpeedDataLoader over the global batch size (engine.py:318
        # deepspeed_io); here it is attached as engine.training_dataloader
        from .dataloader import DeepSpeedDataLoader
        engine.training_dataloader = DeepSpeedDataLoader(
            training_data, batch_size=engine.config.train_batch_size,
            # reference deepspeed_io samples through a shuffling
            # DistributedSampler — fixed-order epochs would silently hurt
            # convergence on order-correlated datasets
            shuffle=True, seed=cfg.seed)

    return engine
