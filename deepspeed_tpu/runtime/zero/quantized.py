"""ZeRO++ quantized collectives wired into the training step.

Reference:
- qwZ — quantized weight allgather: `CUDAQuantizer` +
  `all_gather_coalesced` (runtime/zero/partition_parameters.py:824) gather
  stage-3 param shards as int8 blocks, halving allgather bytes.
- qgZ — quantized gradient reduction: `all_to_all_quant_reduce`
  (runtime/comm/coalesced_collectives.py:31, kernels in
  csrc/quantization/quant_reduce.cu) replaces the grad reduce-scatter with
  quantize -> all-to-all -> dequant -> local reduce.  The reference ships
  int4 on the wire; `zero_quantized_gradients_bits` selects 8 (default,
  tightest trajectory parity) or 4 (the reference width, half the bytes
  again).
- 2-hop qgZ (ZeRO++ hierarchical partitioning, arxiv 2306.10209): the
  grad reduction rides a factored (intra, inter) axis pair — full- (or
  int8-) precision reduce-scatter over the ICI-like intra axis, then a
  quantized hop over the DCN-like inter axis, so only 1/intra of the
  data crosses the slow links, quantized.
- EQuARX quantized all-reduce (arxiv 2506.17615): the data-axis grad psum
  (replicated-grad leaves; the replica-axis reduction) becomes quantized
  reduce-scatter + quantized all-gather with ONE fused payload+scales
  launch per hop.  Small leaves can additionally be coalesced into flat
  BUCKETS before quantization (`zero_quantized_bucket_size`), so tiny
  params stop paying per-leaf launch + block padding.

TPU formulation: under GSPMD the param allgather and grad reduce-scatter
are compiler-inserted, so there is no call site to swap a quantized
kernel into.  Instead the whole micro-batch value_and_grad runs inside a
`jax.shard_map` that is MANUAL over the ZeRO data axes (auto over
tp/sp/ep, which GSPMD keeps partitioning as usual).  Each stage-3 sharded
leaf flows through a custom-vjp gather primitive:

    forward:  p_full  = quantized_all_gather(p_shard)      # qwZ, int8 wire
    backward: g_shard = quantized_reduce_scatter(ct)       # qgZ, int8 wire

i.e. the qgZ reduction IS the vjp of the qwZ gather (straight-through
the quantizer, as the reference trains w.r.t. the unquantized master).
The gather is wrapped in `jax.checkpoint` so autodiff keeps the SHARDED
leaf as the residual and re-gathers in the backward — the reference's
fetch-again-in-backward discipline, trading a second (int8) gather for
not holding gathered weights across fwd+bwd.

Residency: leaves under a top-level "layers" subtree (the in-tree
Transformer's stacked [L, ...] scan convention) stay SHARDED at the top
of the loss; the model's scan body gathers ONE layer's slice at a time
through `layer_gather.apply_layer_gathers`, so qwZ composes with
stage-3 per-module residency (reference: quantized per-module gathers,
partition_parameters.py:824).  Every other sharded leaf (embeddings,
head, norms — and the whole tree for models that never consult the
context) is gathered eagerly at the top of the loss, the r3 behavior.
Set PER_LAYER_GATHER = False to force the eager whole-model path
(used by the residency regression test).

Overlap (T3, arxiv 2401.16677):
- layer-granular: the per-layer gather vjp puts layer L's grad collective
  INSIDE the backward scan, overlapping layer L-1's backward math (free
  at stage 3).  At stage < 3 `layer_ar=True` installs an identity
  custom-vjp hook per layer whose backward is the quantized all-reduce,
  getting the same in-backward placement for replicated-param grads.
- microstep: `defer_finish=True` splits the pipeline into
  ``micro_grads.raw`` (fwd/bwd only; grads leave the region pre-finish)
  and ``micro_grads.finish`` (the cross-device reductions), so the
  engine's accumulation scan can issue microstep i's reduction alongside
  microstep i+1's compute (engine.py `overlap_mode="microstep"`).

The quantized primitives live in comm/compressed.py (block-wise
int8/int4, ops/quantization.py codecs; fused payload+scales launches).
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
from jax import shard_map
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from ...comm.compressed import (hierarchical_quantized_reduce_scatter,
                                quantized_all_gather,
                                quantized_all_reduce,
                                quantized_reduce_scatter)
from ...parallel.mesh import MeshTopology
from .layer_gather import layer_gather_context
from .sharding import ZeroShardingRules, grad_specs, param_specs

PyTree = Any

# module switch for the per-layer gather of "layers" subtrees (see
# module docstring); tests force False to measure the eager baseline
PER_LAYER_GATHER = True


def _filter_manual(spec: PartitionSpec, manual: frozenset) -> PartitionSpec:
    """Keep only manual-axis entries of a spec (auto axes are GSPMD's
    business and must not appear in shard_map specs)."""
    out = []
    for entry in tuple(spec):
        if entry is None:
            out.append(None)
        elif isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in manual)
            out.append(kept if kept else None)
        else:
            out.append(entry if entry in manual else None)
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def _shard_dim(spec: PartitionSpec, shard_axis: str) -> Optional[int]:
    """Dimension index that `shard_axis` partitions, or None."""
    for i, entry in enumerate(tuple(spec)):
        if entry == shard_axis or (
                isinstance(entry, (tuple, list)) and shard_axis in entry):
            return i
    return None


def _spec_axes(spec: PartitionSpec) -> frozenset:
    """All mesh axes a spec mentions."""
    out = set()
    for entry in tuple(spec):
        if entry is None:
            continue
        out.update(entry if isinstance(entry, (tuple, list)) else (entry,))
    return frozenset(out)


def build_quantized_micro_grads(
    call_loss: Callable,
    rules: ZeroShardingRules,
    topo: MeshTopology,
    params_template: PyTree,
    *,
    qwz: bool,
    qgz: bool,
    qwz_bits: int = 8,
    qgz_bits: int = 8,
    block_size: int = 256,
    comp_spec=None,
    qar: bool = False,
    hier: Optional[Tuple[str, str]] = None,
    intra_bits: int = 0,
    bucket_size: int = 0,
    layer_ar: bool = False,
    defer_finish: bool = False,
) -> Callable:
    """Drop-in replacement for the engine's `micro_grads` closure
    (engine.py _build_train_step) routing ZeRO collectives through the
    quantized primitives.  Signature and contract match: returns
    (unscaled_loss, aux, grads) with grads scaled by `loss_scale` and
    laid out per `grad_specs` (sharded leaves arrive sharded).

    New collective modes (module docstring): `qar` quantizes the data-axis
    grad psum (EQuARX), `hier=(intra, inter)` factors the reduction into
    the 2-hop topology, `bucket_size` coalesces small psum-path leaves,
    `layer_ar` moves stage<3 per-layer grad all-reduce into the backward
    scan, `defer_finish` exposes `.raw`/`.finish` for the engine's
    microstep double-buffering."""
    mesh = topo.mesh
    shard_axis = rules.shard_axes[0]
    group = topo.size(shard_axis)
    # manual over every >1 data axis: the batch is sharded over all of
    # them, so per-device partial grads only exist w.r.t. all of them
    data_axes = tuple(a for a in topo.data_axes if topo.size(a) > 1) \
        or (shard_axis,)
    manual = frozenset(data_axes)
    other_axes = tuple(a for a in data_axes if a != shard_axis)
    data_size = int(np.prod([topo.size(a) for a in data_axes]))

    # 2-hop hierarchy: resolve_hierarchy (sharding.py) guarantees intra is
    # the shard axis and both sizes > 1; a degenerate mesh arrives as None
    if hier is not None:
        assert hier[0] == shard_axis and hier[1] in other_axes, (hier,
                                                                 data_axes)
    hier_inter = hier[1] if hier is not None else None

    p_specs = param_specs(rules, params_template)
    g_specs = grad_specs(rules, params_template)
    p_manual = jax.tree.map(lambda s: _filter_manual(s, manual), p_specs,
                            is_leaf=lambda s: isinstance(s, PartitionSpec))
    g_manual = jax.tree.map(lambda s: _filter_manual(s, manual), g_specs,
                            is_leaf=lambda s: isinstance(s, PartitionSpec))
    batch_spec = PartitionSpec(data_axes)

    # ---- first-hop reduce-scatter over the shard axis ----------------
    def _shard_hop(ct, dim):
        """Reduce-scatter a cotangent over the shard axis along `dim` —
        the qgZ hop.  Under hierarchy this is the INTRA hop: full
        precision by default (the reference's intra-node choice) or
        intra_bits-quantized; the inter hop is applied by the finisher."""
        if qgz and hier is None:
            ct = jnp.moveaxis(ct, dim, 0)
            g = quantized_reduce_scatter(ct, shard_axis, group,
                                         bits=qgz_bits,
                                         block_size=block_size)
            return jnp.moveaxis(g, 0, dim)
        if qgz and intra_bits:
            ct = jnp.moveaxis(ct, dim, 0)
            g = quantized_reduce_scatter(ct, shard_axis, group,
                                         bits=intra_bits,
                                         block_size=block_size)
            return jnp.moveaxis(g, 0, dim)
        return jax.lax.psum_scatter(ct, shard_axis, scatter_dimension=dim,
                                    tiled=True)

    def _inter_scatter(g, dim, axis):
        """hpZ-refined scatter over a non-shard data axis: plain
        psum_scatter, or the quantized a2a hop when this is the
        hierarchy's inter (DCN-like) axis."""
        if qgz and axis == hier_inter:
            g = jnp.moveaxis(g, dim, 0)
            g = quantized_reduce_scatter(g, axis, topo.size(axis),
                                         bits=qgz_bits,
                                         block_size=block_size)
            return jnp.moveaxis(g, 0, dim)
        return jax.lax.psum_scatter(g, axis, scatter_dimension=dim,
                                    tiled=True)

    def _psum_axis(g, axis):
        """Replica-axis reduction: EQuARX quantized all-reduce when the
        flag is on or this is the hierarchy's inter hop; plain psum
        otherwise."""
        if qar or axis == hier_inter:
            return quantized_all_reduce(g, axis, topo.size(axis),
                                        bits=qgz_bits,
                                        block_size=block_size)
        return jax.lax.psum(g, axis)

    def _psum_full(g):
        """Full data-axes reduction for replicated-grad leaves.  Under
        hierarchy: 2-hop — exact (or intra_bits) psum over the ICI-like
        intra axis, quantized all-reduce over the DCN-like inter axis."""
        if hier is not None:
            if intra_bits:
                g = quantized_all_reduce(g, hier[0], topo.size(hier[0]),
                                         bits=intra_bits,
                                         block_size=block_size)
            else:
                g = jax.lax.psum(g, hier[0])
            g = quantized_all_reduce(g, hier[1], topo.size(hier[1]),
                                     bits=qgz_bits, block_size=block_size)
            # hierarchy names only (intra, inter); any remaining data axis
            # (not representable on this 2-axis factoring) reduces exactly
            rest = tuple(a for a in data_axes if a not in hier)
            return jax.lax.psum(g, rest) if rest else g
        if qar:
            return quantized_all_reduce(g, data_axes, data_size,
                                        bits=qgz_bits,
                                        block_size=block_size)
        return jax.lax.psum(g, data_axes)

    def _local_slice(g, g_spec: PartitionSpec):
        """Extract this device's shard of a fully-reduced (replicated-
        value) gradient per its grad spec — the layout half of a
        reduce-scatter with the comm already paid (layer_ar leaves)."""
        for i, entry in enumerate(tuple(g_spec)):
            if entry is None:
                continue
            axes = tuple(a for a in (entry if isinstance(entry, (tuple, list))
                                     else (entry,)) if a in manual)
            if not axes:
                continue
            size = int(np.prod([topo.size(a) for a in axes]))
            shard = g.shape[i] // size
            idx = jnp.zeros((), jnp.int32)
            for a in axes:          # major-to-minor per spec tuple order
                idx = idx * topo.size(a) + jax.lax.axis_index(a)
            g = jax.lax.dynamic_slice_in_dim(g, idx * shard, shard, axis=i)
        return g

    def _make_gather(dim: int) -> Callable:
        """custom-vjp gather for one sharded leaf: quantized (or plain
        tiled) all-gather forward; (quantized) reduce-scatter of the
        cotangent backward.  The cotangent arriving here is this device's
        PARTIAL grad of the gathered value; summing slices over the shard
        group is exactly reduce-scatter — qgZ drops in as the vjp."""

        def _gather_impl(p):
            if qwz:
                return quantized_all_gather(p, shard_axis, bits=qwz_bits,
                                            block_size=block_size,
                                            gather_axis=dim)
            return jax.lax.all_gather(p, shard_axis, axis=dim, tiled=True)

        @jax.custom_vjp
        def gather(p):
            return _gather_impl(p)

        def fwd(p):
            return _gather_impl(p), None

        def bwd(_, ct):
            return (_shard_hop(ct, dim),)

        gather.defvjp(fwd, bwd)
        # checkpoint: keep the SHARDED leaf as the autodiff residual and
        # re-gather in backward (reference stage-3 re-fetch) — without this
        # every gathered weight is pinned across fwd+bwd as a matmul
        # residual
        return jax.checkpoint(gather)

    def _make_layer_ar() -> Callable:
        """Identity custom-vjp whose backward is the full data-axes
        quantized all-reduce — applied to each layer SLICE inside the
        model's scan, so layer L's grad collective is issued inside the
        backward scan where it overlaps layer L-1's backward math (the
        stage<3 analog of the per-layer gather vjp)."""

        @jax.custom_vjp
        def hook(p):
            return p

        def fwd(p):
            return p, None

        def bwd(_, ct):
            return (_psum_full(ct),)

        hook.defvjp(fwd, bwd)
        return hook

    # per-leaf gather primitives, built once from the static specs
    # (identity for unsharded leaves — a None leaf would vanish from the
    # pytree structure).  Leaves under a top-level "layers" subtree whose
    # shard dim is not the layer dim get gathered PER SCAN STEP inside the
    # model (layer_gather module docstring) instead of eagerly — composes
    # qwZ with stage-3 residency; disabled under compression (masks are
    # built against full leaves).  GATED on the loss fn declaring it calls
    # apply_layer_gathers (initialize() forwards the model's
    # supports_layer_gather marker) — a user model whose params merely
    # HAVE a "layers" key must keep the eager whole-model gather, else
    # its sharded leaves would never be gathered at all.
    layers_hooked = (comp_spec is None
                     and getattr(call_loss, "supports_layer_gather", False)
                     and isinstance(params_template, dict)
                     and "layers" in params_template)
    per_layer = PER_LAYER_GATHER and layers_hooked
    # stage<3 in-backward per-layer all-reduce: only when no leaf under
    # "layers" is param-sharded (else the gather hooks own the subtree)
    layer_ar = (layer_ar and layers_hooked and not any(
        _shard_dim(s, shard_axis) is not None
        for s in jax.tree.leaves(
            p_specs["layers"] if isinstance(p_specs, dict)
            and "layers" in p_specs else {},
            is_leaf=lambda s: isinstance(s, PartitionSpec))))

    def _eager_leaf(path, s):
        d = _shard_dim(s, shard_axis)
        if d is None:
            return lambda p: p
        if per_layer and path and str(getattr(path[0], "key", "")) == "layers" \
                and d >= 1:
            return lambda p: p  # gathered per layer inside the scan
        return _make_gather(d)

    gathers = jax.tree_util.tree_map_with_path(
        _eager_leaf, p_specs, is_leaf=lambda s: isinstance(s, PartitionSpec))

    layer_gathers = None
    if layer_ar:
        hook = _make_layer_ar()
        layer_gathers = jax.tree.map(
            lambda s: hook, p_specs["layers"],
            is_leaf=lambda s: isinstance(s, PartitionSpec))
    elif per_layer:
        def _layer_leaf(s):
            d = _shard_dim(s, shard_axis)
            if d is None or d == 0:  # unsharded / sharded on the layer dim
                return lambda p: p
            return _make_gather(d - 1)  # slice drops the leading layer dim
        layer_gathers = jax.tree.map(
            _layer_leaf, p_specs["layers"],
            is_leaf=lambda s: isinstance(s, PartitionSpec))

    def _is_layer_ar_path(path) -> bool:
        return layer_ar and bool(path) and \
            str(getattr(path[0], "key", "")) == "layers"

    # ---- grad finishing: the cross-device reductions -----------------
    def finish_leaf(path, g, p_spec: PartitionSpec, g_spec: PartitionSpec):
        """Post-vjp grad finishing: GATHERED leaves (param sharded, stage
        3) were already reduce-scattered over the shard axis by the
        gather vjp; ungathered leaves whose grad spec shards (stage 2)
        reduce-scatter here — quantized under qgZ, 2-hop under hier.
        Remaining data axes then either psum (replica axis — quantized
        under qar/hier) or psum_scatter (hpZ: the grad spec refines the
        gather dim with dp — ZeroShardingRules.opt_spec orders it
        (fsdp, dp), matching this fsdp-then-dp scatter order; the dp hop
        is the hierarchy's quantized inter hop when configured).
        layer_ar leaves arrive fully reduced from the in-backward hook
        and only need their local slice.  Normalization to the global
        mean happens once in `finish_tree`."""
        if _is_layer_ar_path(path):
            return _local_slice(g, g_spec)
        gathered = _shard_dim(p_spec, shard_axis) is not None
        d = _shard_dim(g_spec, shard_axis)
        if d is not None and not gathered:
            g = _shard_hop(g, d)
        if d is not None or gathered:
            for a in other_axes:
                da = _shard_dim(g_spec, a)
                if da is not None:
                    g = _inter_scatter(g, da, a)
                else:
                    g = _psum_axis(g, a)
        else:
            g = _psum_full(g)
        return g

    # bucketing: psum-path leaves (replicated grad spec, never gathered)
    # coalesce into flat buckets before quantization — one launch and one
    # block-quant padding per BUCKET instead of per leaf
    def _bucket_path(path, p_spec, g_spec) -> bool:
        # fully-replicated grad specs only: a tp/sp-sharded leaf in the
        # flat concat would make GSPMD reshard the whole bucket
        return (bucket_size > 0
                and not _is_layer_ar_path(path)
                and _shard_dim(p_spec, shard_axis) is None
                and not _spec_axes(g_spec))

    bucket_paths = []
    jax.tree_util.tree_map_with_path(
        lambda path, p_s, g_s: bucket_paths.append(tuple(path))
        if _bucket_path(path, p_s, g_s) else None,
        p_specs, g_specs, is_leaf=lambda s: isinstance(s, PartitionSpec))
    bucket_set = frozenset(bucket_paths)

    def finish_tree(grads):
        """All cross-device grad reductions + the global-mean normalize.
        Separated from the fwd/bwd so the engine can defer it by one
        microstep (T3 double-buffering)."""
        finished = jax.tree_util.tree_map_with_path(
            lambda path, g, p_s, g_s: g if tuple(path) in bucket_set
            else finish_leaf(path, g, p_s, g_s),
            grads, p_specs, g_specs)
        if bucket_set:
            leaves = {tuple(p): g for p, g in
                      jax.tree_util.tree_flatten_with_path(grads)[0]}
            flat = [leaves[p].astype(jnp.float32).reshape(-1)
                    for p in bucket_paths]
            cat = jnp.concatenate(flat) if len(flat) > 1 else flat[0]
            out = []
            for start in range(0, cat.shape[0], bucket_size):
                out.append(_psum_full(cat[start:start + bucket_size]))
            cat = jnp.concatenate(out) if len(out) > 1 else out[0]
            offs = 0
            reduced = {}
            for p in bucket_paths:
                leaf = leaves[p]
                n = int(np.prod(leaf.shape)) if leaf.shape else 1
                reduced[p] = cat[offs:offs + n].reshape(leaf.shape).astype(
                    leaf.dtype)
                offs += n
            finished = jax.tree_util.tree_map_with_path(
                lambda path, g: reduced.get(tuple(path), g), finished)
        return jax.tree.map(lambda g: g / data_size, finished)

    def run_fwd_bwd(params, micro, rng, loss_scale, comp_masks, step):
        """One microstep's forward + backward inside the manual region;
        grads are post-vjp (shard-hop applied for gathered leaves,
        layer_ar leaves pre-reduced) but NOT finished."""
        # distinct per-device randomness, stable across qwz/qgz settings
        for a in data_axes:
            rng = jax.random.fold_in(rng, jax.lax.axis_index(a))

        def scaled_loss(p_shard):
            full = jax.tree.map(lambda p, gth: gth(p), p_shard, gathers)
            if comp_spec is not None:
                from ...compression import CompressionState, compress_params
                full = compress_params(
                    comp_spec, CompressionState(masks=comp_masks),
                    full, step, rng=rng)
            with layer_gather_context(layer_gathers):
                loss, aux = call_loss(full, micro, rng)
            return loss * loss_scale.astype(loss.dtype), (loss, aux)

        (_, (loss, aux)), grads = jax.value_and_grad(
            scaled_loss, has_aux=True)(params)
        loss = jax.lax.pmean(loss, data_axes)
        aux = jax.tree.map(lambda v: jax.lax.pmean(v, data_axes), aux)
        return loss, aux, grads

    def body(params, micro, rng, loss_scale, comp_masks, step):
        loss, aux, grads = run_fwd_bwd(params, micro, rng, loss_scale,
                                       comp_masks, step)
        return loss, aux, finish_tree(grads)

    wrapped = shard_map(
        body, mesh=mesh,
        in_specs=(p_manual, batch_spec, PartitionSpec(), PartitionSpec(),
                  PartitionSpec(), PartitionSpec()),
        out_specs=(PartitionSpec(), PartitionSpec(), g_manual),
        axis_names=manual, check_vma=False)

    def micro_grads(params, micro, rng, loss_scale, comp_masks, step):
        return wrapped(params, micro, rng, loss_scale, comp_masks, step)

    if defer_finish:
        # T3 microstep double-buffering support: RAW grads round-trip the
        # manual-region boundary as globally-stacked partials — each leaf
        # gains a leading dim carrying the data axes its own layout does
        # not (a full-size partial over (dp, fsdp) is represented as the
        # global stack [world, ...] of which this device holds [1, ...];
        # per-device memory equals the partial itself).  `finish` takes
        # that representation back in and runs the deferred reductions.
        def _raw_spec(pm: PartitionSpec) -> PartitionSpec:
            lead = tuple(a for a in data_axes if a not in _spec_axes(pm))
            return PartitionSpec(lead if lead else None, *tuple(pm))

        raw_specs = jax.tree.map(
            _raw_spec, p_manual, is_leaf=lambda s: isinstance(s, PartitionSpec))

        def body_raw(params, micro, rng, loss_scale, comp_masks, step):
            loss, aux, grads = run_fwd_bwd(params, micro, rng, loss_scale,
                                           comp_masks, step)
            return loss, aux, jax.tree.map(lambda g: g[None], grads)

        raw_wrapped = shard_map(
            body_raw, mesh=mesh,
            in_specs=(p_manual, batch_spec, PartitionSpec(), PartitionSpec(),
                      PartitionSpec(), PartitionSpec()),
            out_specs=(PartitionSpec(), PartitionSpec(), raw_specs),
            axis_names=manual, check_vma=False)

        def body_finish(raw):
            return finish_tree(jax.tree.map(lambda g: g[0], raw))

        finish_wrapped = shard_map(
            body_finish, mesh=mesh, in_specs=(raw_specs,),
            out_specs=g_manual, axis_names=manual, check_vma=False)

        micro_grads.raw = raw_wrapped
        micro_grads.finish = finish_wrapped

    return micro_grads
