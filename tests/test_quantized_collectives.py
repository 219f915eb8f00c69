"""Overlapped + hierarchical quantized training collectives (ISSUE 6).

Covers the three tentpole legs end-to-end on the 8-virtual-device mesh:

- T3-style microstep double-buffering (`overlap_mode="microstep"`): the
  GAS scan carries the previous microstep's raw grads and issues their
  reduction before the next microstep's fwd/bwd — asserted structurally
  (the while loop carries the double buffer) and numerically (same
  trajectory as the serialized schedule; the overlap itself is not
  lossy, only reassociated).
- Hierarchical 2-hop qgZ (`zero_quantized_gradients_hierarchy`): intra
  hop over fsdp (exact or int8), quantized inter hop over dp — primitive
  layout vs the exact sum, plus engine loss parity on a factored mesh.
- EQuARX quantized all-reduce + bucketing
  (`zero_quantized_allreduce` / `zero_quantized_bucket_size` /
  `overlap_mode="layer"`): fused payload+scales launch counts, loss
  parity for every lossy mode, and the acceptance-criterion wire-byte
  cut (>= 2x) for the overlapped+hierarchical+quantized config.

The bit-exact contract is locked the other way: a default-config engine
compiles to a program with NO quantized collectives and NO double
buffer, and is deterministic run to run.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu as dstpu
from deepspeed_tpu.benchmarks.hlo_census import (async_overlap_report,
                                                 collective_census,
                                                 collective_wire_bytes)
from deepspeed_tpu.comm.compressed import (
    hierarchical_quantized_reduce_scatter, quantized_all_reduce)
from deepspeed_tpu.parallel.mesh import make_mesh
from jax import shard_map

pytestmark = pytest.mark.slow


# ----------------------------------------------------------------------
# primitives
# ----------------------------------------------------------------------
def test_hier_2hop_matches_exact_sum_intra_major(devices8):
    """2-hop (intra=chip/fsdp, inter=node/dp) reduce-scatter must equal
    the exact sum scattered with the INTRA axis major — the layout the
    sharding specs record for hpZ's (fsdp, dp) refinement."""
    mesh = make_mesh(dp=2, fsdp=4).mesh
    rng = np.random.RandomState(0)
    g = rng.randn(8, 16, 6).astype(np.float32)
    for intra_bits, atol in [(0, 0.3), (8, 0.6)]:
        f = shard_map(
            lambda x, ib=intra_bits: hierarchical_quantized_reduce_scatter(
                x[0], "fsdp", "dp", 4, 2, bits=8, intra_bits=ib),
            mesh=mesh, in_specs=(P(("dp", "fsdp"), None, None),),
            out_specs=P(("fsdp", "dp"), None), check_vma=False)
        np.testing.assert_allclose(np.asarray(f(jnp.asarray(g))),
                                   g.sum(axis=0), atol=atol)


def test_quantized_all_reduce_fused_two_launches(devices8):
    """EQuARX shape: ONE fused payload+scales a2a + ONE fused all-gather
    — not the 3 collectives per hop the unfused wire would pay — and
    both ride s8."""
    mesh = make_mesh().mesh
    x = jnp.ones((8, 4096), jnp.float32)
    f = jax.jit(shard_map(
        lambda v: quantized_all_reduce(v[0], "dp", 8, bits=8),
        mesh=mesh, in_specs=(P("dp", None),), out_specs=P("dp"),
        check_vma=False))
    txt = f.lower(x).compile().as_text()
    census = collective_census(txt)
    assert census["all-to-all"] == 1 and census["all-gather"] == 1, census
    assert sum(census.values()) == 2, census
    for line in txt.splitlines():
        m = re.search(r"%(all-to-all|all-gather)(?:-start)?[.\d]* = (\S+)",
                      line)
        if m:
            assert re.search(r"\bs8\[", m.group(2)), line


def test_quantized_all_reduce_group_order_tuple_axes(devices8):
    """Joint-group qAR over ('dp','fsdp'): a rank-order mismatch between
    the a2a and the all-gather would permute chunks — every device must
    still see the true sum."""
    mesh = make_mesh(dp=4, fsdp=2).mesh
    rng = np.random.RandomState(3)
    vals = rng.randn(8, 1000).astype(np.float32)
    f = shard_map(
        lambda v: quantized_all_reduce(v[0], ("dp", "fsdp"), 8,
                                       bits=8)[None],
        mesh=mesh, in_specs=(P(("dp", "fsdp"), None),),
        out_specs=P(("dp", "fsdp"), None), check_vma=False)
    out = np.asarray(f(jnp.asarray(vals)))
    for r in range(8):
        np.testing.assert_allclose(out[r], vals.sum(axis=0), atol=0.6)


# ----------------------------------------------------------------------
# engine-level loss parity — every lossy mode
# ----------------------------------------------------------------------
def _params():
    k = jax.random.PRNGKey(0)
    p = {f"w{i}": jax.random.normal(jax.random.fold_in(k, i),
                                    (64, 64)) * 0.1
         for i in range(4)}
    # a small odd leaf rides the bucketed psum path
    p["bias"] = jax.random.normal(jax.random.fold_in(k, 9), (7,)) * 0.1
    return p


def _loss_fn(p, batch, rng=None):
    x = batch["x"]
    for i in range(4):
        x = jnp.tanh(x @ p[f"w{i}"])
    x = x + jnp.pad(p["bias"], (0, 57))
    return jnp.mean((x - batch["y"]) ** 2)


def _engine(zero, gas=1, topo=None):
    return dstpu.initialize(loss_fn=_loss_fn, params=_params(), config={
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": zero, "steps_per_print": 0}, topology=topo)


def _batch(gas=1):
    rng = np.random.RandomState(0)
    n = 16 * gas
    return {"x": rng.randn(n, 64).astype(np.float32),
            "y": rng.randn(n, 64).astype(np.float32)}


def _losses(eng, b, n=8):
    return [float(eng.train_batch(b)["loss"]) for _ in range(n)]


@pytest.mark.parametrize("zero,topo_axes", [
    # EQuARX quantized all-reduce at stage 1 (the stage<3 psum path)
    ({"stage": 1, "zero_quantized_allreduce": True}, None),
    # + bucketing of small leaves
    ({"stage": 1, "zero_quantized_allreduce": True,
      "zero_quantized_bucket_size": 2048}, None),
    # 2-hop hierarchy at stage 2 on the factored mesh, exact intra hop
    ({"stage": 2, "zero_quantized_gradients": True,
      "zero_quantized_gradients_hierarchy": "auto"}, (2, 4)),
    # 2-hop with the intra hop quantized too (int8) + quantized psum
    ({"stage": 2, "zero_quantized_gradients": True,
      "zero_quantized_allreduce": True,
      "zero_quantized_gradients_hierarchy": "auto",
      "zero_quantized_gradients_intra_bits": 8}, (2, 4)),
    # hpZ stage 3: the dp hop of the (fsdp, dp)-refined scatter is the
    # hierarchy's quantized inter hop
    ({"stage": 3, "zero_hpz_partition_size": 4,
      "zero_quantized_gradients": True,
      "zero_quantized_gradients_hierarchy": "auto"}, (2, 4)),
    # int4 inter hop — the ZeRO++ reference wire width
    ({"stage": 2, "zero_quantized_gradients": True,
      "zero_quantized_gradients_bits": 4,
      "zero_quantized_gradients_hierarchy": "auto"}, (2, 4)),
])
def test_lossy_mode_loss_parity(devices8, zero, topo_axes):
    """Every lossy collective mode must track the exact trajectory
    within block-quantization tolerance AND actually train."""
    base = _losses(_engine({"stage": 2}), _batch())
    topo = make_mesh(dp=topo_axes[0], fsdp=topo_axes[1]) if topo_axes \
        else None
    q = _losses(_engine(zero, topo=topo), _batch())
    assert q[-1] < q[0] * 0.7, (zero, q)
    rtol = 0.3 if zero.get("zero_quantized_gradients_bits") == 4 else 0.15
    np.testing.assert_allclose(q[-1], base[-1], rtol=rtol)


def test_layer_mode_in_backward_allreduce_parity(devices8):
    """overlap_mode='layer' at stage<3: per-layer grads all-reduce
    INSIDE the backward scan via the identity-fwd/quantized-AR-bwd hook
    — needs the in-tree Transformer's layer-scan hook."""
    from deepspeed_tpu.models import Transformer, TransformerConfig
    cfg = TransformerConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        max_seq_len=64, pos_emb="rope", norm="rmsnorm",
        activation="swiglu", dtype=jnp.float32, attn_impl="jnp")

    def eng(zero):
        return dstpu.initialize(model=Transformer(cfg), config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
            "zero_optimization": zero, "steps_per_print": 0})

    ids = np.random.RandomState(0).randint(0, 128, (16, 64)).astype(np.int32)
    b = {"input_ids": ids}
    base = [float(eng({"stage": 2}).train_batch(b)["loss"])]
    e0 = eng({"stage": 2})
    base = [float(e0.train_batch(b)["loss"]) for _ in range(6)]
    e1 = eng({"stage": 2, "zero_quantized_allreduce": True,
              "overlap_mode": "layer"})
    layer = [float(e1.train_batch(b)["loss"]) for _ in range(6)]
    assert layer[-1] < layer[0], layer
    np.testing.assert_allclose(layer[-1], base[-1], rtol=0.1)


@pytest.mark.parametrize("zero", [
    {"stage": 2},                                        # plain GSPMD path
    {"stage": 2, "zero_quantized_gradients": True},      # quantized path
])
def test_microstep_overlap_trajectory_parity(devices8, zero):
    """Double-buffered microsteps are NOT lossy — only the accumulation
    order reassociates — so the overlap engine must track its serialized
    twin tightly, microstep losses included."""
    b = _batch(gas=2)
    ref = _engine(dict(zero), gas=2)
    ov = _engine(dict(zero, overlap_mode="microstep"), gas=2)
    for _ in range(6):
        mr = ref.train_batch(b)
        mo = ov.train_batch(b)
        np.testing.assert_allclose(float(mo["loss"]), float(mr["loss"]),
                                   rtol=1e-4)
        np.testing.assert_allclose(np.asarray(mo["micro_losses"]),
                                   np.asarray(mr["micro_losses"]),
                                   rtol=1e-4)


# ----------------------------------------------------------------------
# program structure: double buffer, wire bytes, bit-exact default
# ----------------------------------------------------------------------
def _lowered_txt(eng, gas=1):
    b = eng._shard_batch(_batch(gas))
    return eng._train_step.lower(eng.state, b, jax.random.PRNGKey(0), {})


def test_microstep_overlap_carries_double_buffer(devices8):
    """Structural evidence of the T3 double buffer: the overlap build's
    accumulation while-loop carries the raw-grad tree (more iterArgs
    than the serialized build) and still issues the quantized
    collectives (s8) inside the loop body."""
    zero = {"stage": 2, "zero_quantized_gradients": True}
    ref = _lowered_txt(_engine(dict(zero), gas=3), gas=3).as_text()
    ov_l = _lowered_txt(
        _engine(dict(zero, overlap_mode="microstep"), gas=3), gas=3)
    ov = ov_l.as_text()

    def carry_arity(txt):
        return max((line.count("iterArg")
                    for line in txt.splitlines() if "while" in line),
                   default=0)

    a_ref, a_ov = carry_arity(ref), carry_arity(ov)
    assert a_ov > a_ref, (
        f"overlap scan does not carry the raw-grad double buffer: "
        f"iterArgs {a_ref} -> {a_ov}")
    # the deferred reductions still happen — and on a backend with a
    # latency-hiding scheduler they show up as async start/done pairs
    # with compute between (the CPU backend schedules synchronously)
    compiled = ov_l.compile().as_text()
    census = collective_census(compiled)
    assert census["all-to-all"] > 0, census
    pairs = async_overlap_report(compiled)
    if pairs:  # only a TPU/GPU-class scheduler emits async pairs
        assert any(has_compute for _, _, has_compute in pairs), pairs


def test_grad_path_wire_bytes_cut_2x(devices8):
    """ACCEPTANCE: >= 2x reduction in measured grad-path wire bytes.

    Measured at the grad-reduction primitive level with a realistic
    (1M-element) grad payload, where attribution is unambiguous — the
    engine-level census on the 64x64 toy is dominated by per-use param
    gathers and block-padding floors that vanish at real sizes (the
    model-level ratios are locked by test_zeropp_wire_bytes_measured:
    3.1x int8 / 4.1x int4):

    1. EQuARX quantized all-reduce (the stage<3 data-axis grad psum
       replacement) vs the f32 psum it replaces.
    2. The hierarchical claim proper: 2-hop qgZ must cut the bytes
       crossing the slow INTER (node) axis >= 2x vs single-hop, read
       from each collective's replica groups (a group confined to one
       node's devices is intra; anything else crosses nodes).
    """
    from deepspeed_tpu.comm.compressed import quantized_reduce_scatter
    mesh = make_mesh(dp=2, fsdp=4).mesh   # dp = node-like outer axis
    n = 1 << 20
    x = jnp.ones((8, n // 8), jnp.float32)

    def wire(fn, out_spec):
        f = jax.jit(shard_map(fn, mesh=mesh,
                              in_specs=(P(("dp", "fsdp"), None),),
                              out_specs=out_spec, check_vma=False))
        return f.lower(x).compile().as_text()

    # 1. quantized vs plain all-reduce of the same grad payload
    base_txt = wire(lambda v: jax.lax.psum(v[0], ("dp", "fsdp")),
                    P(("dp", "fsdp"), None))
    qar_txt = wire(
        lambda v: quantized_all_reduce(v[0], ("dp", "fsdp"), 8, bits=8),
        P(("dp", "fsdp"), None))
    base_b = collective_wire_bytes(base_txt, 8)
    qar_b = collective_wire_bytes(qar_txt, 8)
    assert qar_b <= base_b / 2.0, (base_b, qar_b)

    # 2. inter-node bytes: single-hop qgZ vs 2-hop (int8 both) — node r
    # is the set of device ids along the mesh's dp row; a collective
    # whose every replica group stays inside one node is intra (ICI),
    # anything else crosses nodes (DCN)
    from deepspeed_tpu.benchmarks.hlo_census import _DEF_RE, _type_bytes
    nodes = [frozenset(d.id for d in np.asarray(mesh.devices)[r].ravel())
             for r in range(2)]

    def inter_bytes(txt):
        total = 0.0
        for line in txt.splitlines():
            dm = _DEF_RE.search(line)
            if not dm:
                continue
            groups = [frozenset(int(i) for i in g.split(","))
                      for g in re.findall(r"\{([\d,]+)\}", line)]
            if groups and all(any(g <= node for node in nodes)
                              for g in groups):
                continue                      # intra-node only: ICI
            total += _type_bytes(dm.group(3))
        return total

    flat_txt = wire(
        lambda v: quantized_reduce_scatter(
            v[0].reshape(8, -1).reshape(-1), ("fsdp", "dp"), 8, bits=8),
        P(("fsdp", "dp"), None))
    hop2_txt = wire(
        lambda v: hierarchical_quantized_reduce_scatter(
            v[0], "fsdp", "dp", 4, 2, bits=8, intra_bits=8),
        P(("fsdp", "dp"), None))
    flat_inter = inter_bytes(flat_txt)
    hop2_inter = inter_bytes(hop2_txt)
    assert flat_inter > 0, "single-hop program shows no inter-node traffic"
    assert hop2_inter <= flat_inter / 2.0, (flat_inter, hop2_inter)


def test_default_config_stays_bit_exact(devices8):
    """The default path must not change: no quantized collectives, no
    double buffer, and bit-for-bit deterministic across fresh engines."""
    eng = _engine({"stage": 2})
    txt = _lowered_txt(eng).compile().as_text()
    assert not re.search(
        r"%(?:all-gather|all-to-all|all-reduce|reduce-scatter)"
        r"(?:-start)?[.\d]* = [^\n]*\bs8\[", txt), \
        "default path ships quantized collectives"
    b = _batch()
    l1 = [float(eng.train_batch(b)["loss"]) for _ in range(4)]
    eng2 = _engine({"stage": 2})
    l2 = [float(eng2.train_batch(b)["loss"]) for _ in range(4)]
    assert l1 == l2, (l1, l2)


def test_full_stack_multichip_config_trains(devices8):
    """The dryrun regime-9 config (2-hop qgZ + EQuARX AR + bucketing +
    microstep+layer overlap, bf16, gas 2) on the (node, chip) factored
    mesh — one train step, finite loss, and s8 collectives on the wire."""
    from deepspeed_tpu.models import Transformer, TransformerConfig
    topo = make_mesh(dp=2, fsdp=4)
    cfg = TransformerConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        max_seq_len=64, pos_emb="rope", norm="rmsnorm",
        activation="swiglu", dtype=jnp.bfloat16, attn_impl="jnp")
    eng = dstpu.initialize(model=Transformer(cfg), config={
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {
            "stage": 2, "zero_quantized_gradients": True,
            "zero_quantized_gradients_hierarchy": "auto",
            "zero_quantized_allreduce": True,
            "zero_quantized_bucket_size": 16384,
            "overlap_mode": "microstep+layer"},
        "bf16": {"enabled": True}, "steps_per_print": 0}, topology=topo)
    ids = np.random.RandomState(9).randint(
        0, 128, (eng.config.train_batch_size, 64)).astype(np.int32)
    losses = [float(eng.train_batch({"input_ids": ids})["loss"])
              for _ in range(4)]
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], losses
    b = eng._shard_batch({"input_ids": ids})
    txt = eng._train_step.lower(eng.state, b, jax.random.PRNGKey(0),
                                {}).compile().as_text()
    assert re.search(r"%(?:all-to-all|all-gather)(?:-start)?[.\d]* = "
                     r"[^\n]*\bs8\[", txt), "no s8 collectives on the wire"
