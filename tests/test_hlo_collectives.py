"""Compiled-lowering regression tests: the ZeRO/PP/SP/EP designs rest on
sharding constraints nudging GSPMD into the right collectives
(runtime/engine.py train_step's with_sharding_constraint on grads/master).
Numeric tests cannot catch a rule regression that silently replicates
state — every value would still be correct, only multichip memory/perf
would collapse.  These tests lock the lowering:

- the staged grad/master sharding CONSTRAINTS appear in the lowered IR
  (Shardy `sdy.sharding_constraint`; the thing our code emits),
- the compiled executable's OUTPUT shardings place optimizer state and
  params per ZeRO stage,
- the compiled HLO contains the structural collectives each parallelism
  mode implies: stage-3 per-use all-gather, PP collective-permute,
  Ulysses/MoE all-to-all, ring-CP collective-permute.

Backend note: the CPU backend lowers a sharded-grad sum to
all-reduce+dynamic-slice (it lacks the TPU/GPU reduce-scatter-creator
rewrite), so asserting literal `reduce-scatter` text would test XLA's
backend choice, not our design — the constraint+placement assertions
above are the backend-stable invariant.  Reference analog: SURVEY §4.4
(the reference unit-tests partitioning decisions, not NCCL bytes).
"""
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dstpu
from jax.sharding import PartitionSpec


# ----------------------------------------------------------------------
# helpers
# ----------------------------------------------------------------------
def _toy_engine(stage, dtype_block=None):
    k = jax.random.PRNGKey(0)
    params = {f"w{i}": jax.random.normal(jax.random.fold_in(k, i),
                                         (32, 32)) * 0.1
              for i in range(4)}

    def loss_fn(p, batch, rng=None):
        x = batch["x"]
        for i in range(4):
            x = jnp.tanh(x @ p[f"w{i}"].astype(x.dtype))
        return jnp.mean((x.astype(jnp.float32) - batch["y"]) ** 2)

    cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": stage},
        "steps_per_print": 0,
    }
    if dtype_block:
        cfg.update(dtype_block)
    return dstpu.initialize(loss_fn=loss_fn, params=params, config=cfg)


pytestmark = pytest.mark.slow


def _lower(engine):
    b = {"x": np.random.randn(16, 32).astype(np.float32),
         "y": np.random.randn(16, 32).astype(np.float32)}
    sharded = engine._shard_batch(b)
    return engine._train_step.lower(engine.state, sharded,
                                    jax.random.PRNGKey(0), {})


def _count_sharded_constraints(ir_txt, axis, shape="32x32"):
    """Constraints that shard a `shape` tensor over `axis` in the lowered
    IR.  Matches the Shardy dialect first; a jax without it
    lowers with_sharding_constraint to GSPMD-V1 `custom_call @Sharding`
    annotations instead, which carry a devices=[...] assignment but no
    axis NAMES — there, any non-replicated constraint on a `shape` tensor
    counts (the toy engines only exercise one data axis, so the weaker
    match locks the same invariant).  If both dialects move, this returns
    0 and the stage>=2 test fails loudly — the right outcome, since the
    invariant would be unverified."""
    pat = (rf'sdy\.sharding_constraint[^\n]*\{{"{axis}"\}}[^\n]*'
           rf'tensor<{shape}x')
    n = len(re.findall(pat, ir_txt))
    if n:
        return n
    pat_v1 = (rf'custom_call @Sharding\([^\n]*devices=\[[^\]]*\][^\n]*'
              rf'tensor<{shape}x')
    return len(re.findall(pat_v1, ir_txt))


def _collectives(compiled_txt):
    ops = ["all-reduce", "reduce-scatter", "all-gather",
           "collective-permute", "all-to-all"]
    return {op: len(re.findall(rf"\b{op}\b(?!-)", compiled_txt))
            for op in ops}


def _transformer_engine(devices8, *, stage=3, pp=1, sp=None, sp_mode=None,
                        moe=False, fsdp=1, tp=1):
    from deepspeed_tpu.models import Transformer, TransformerConfig
    from deepspeed_tpu.parallel.mesh import make_mesh

    used = pp * (2 if sp else 1) * fsdp * tp * (2 if moe else 1)
    dp = max(1, 8 // max(used, 1))
    topo = make_mesh(dp=dp, fsdp=fsdp, tp=tp, pp=pp,
                     sp=2 if sp else 1, ep=2 if moe else 1,
                     devices=devices8)
    cfg = TransformerConfig(
        vocab_size=128, hidden_size=64, num_layers=2 * max(pp, 1),
        num_heads=4, max_seq_len=64, pos_emb="rope", norm="rmsnorm",
        activation="swiglu", dtype=jnp.bfloat16, attn_impl="jnp",
        sp_axis="sp" if sp else None, sp_mode=sp_mode or "ulysses",
        pp_axis="pp" if pp > 1 else None, pp_microbatches=2,
        pp_schedule="1f1b",
        moe_experts=4 if moe else 0, moe_top_k=2 if moe else 0)
    eng = dstpu.initialize(model=Transformer(cfg), config={
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": stage},
        "bf16": {"enabled": True},
        "steps_per_print": 0,
    }, topology=topo)
    ids = np.random.RandomState(0).randint(
        0, 128, (eng.config.train_batch_size, 65)).astype(np.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    sharded = eng._shard_batch(batch)
    return eng._train_step.lower(eng.state, sharded,
                                 jax.random.PRNGKey(0), {})


# ----------------------------------------------------------------------
# ZeRO grad/state sharding constraints (the engine's own emissions)
# ----------------------------------------------------------------------
class TestZeroShardingLowering:
    def test_stage0_no_dp_sharded_state(self, devices8):
        lowered = _lower(_toy_engine(0))
        assert _count_sharded_constraints(lowered.as_text(), "dp") == 0
        st_sh, _ = lowered.compile().output_shardings
        for leaf in jax.tree.leaves(st_sh.opt_state["m"]):
            assert leaf.spec == PartitionSpec(), leaf
        for leaf in jax.tree.leaves(st_sh.params):
            assert leaf.spec == PartitionSpec(), leaf

    def test_stage1_opt_sharded_grads_replicated(self, devices8):
        lowered = _lower(_toy_engine(1))
        txt = lowered.as_text()
        # master/opt constraints only: 4 leaves -> 4 dp-sharded constraints
        # (grads are NOT constrained to dp at stage 1)
        n = _count_sharded_constraints(txt, "dp")
        assert n == 4, f"expected 4 master constraints, found {n}"
        st_sh, _ = lowered.compile().output_shardings
        for leaf in jax.tree.leaves(st_sh.opt_state["m"]):
            assert "dp" in str(leaf.spec), leaf

    @pytest.mark.parametrize("stage", [2, 3])
    def test_stage23_grads_constrained_to_dp(self, devices8, stage):
        lowered = _lower(_toy_engine(stage))
        txt = lowered.as_text()
        # 4 grad constraints + 4 master constraints; a regression that
        # silently replicates grads (the failure numeric tests cannot see)
        # drops this below 8
        n = _count_sharded_constraints(txt, "dp")
        assert n >= 8, (
            f"stage {stage}: expected >=8 dp-sharded constraints "
            f"(4 grads + 4 master), found {n} — grads may have silently "
            f"reverted to replicated")
        st_sh, _ = lowered.compile().output_shardings
        for leaf in jax.tree.leaves(st_sh.opt_state["m"]):
            assert "dp" in str(leaf.spec), leaf

    def test_stage3_params_sharded_and_gathered(self, devices8):
        lowered = _lower(_toy_engine(3))
        compiled = lowered.compile()
        st_sh, _ = compiled.output_shardings
        # ZeRO-3: params leave the step sharded...
        for leaf in jax.tree.leaves(st_sh.params):
            assert "dp" in str(leaf.spec), leaf
        # ...and every forward use re-gathers them
        counts = _collectives(compiled.as_text())
        assert counts["all-gather"] > 0, counts

    def test_stage2_bf16_params_replicated_master_sharded(self, devices8):
        """bf16-with-fp32-master mode: compute params stay replicated at
        stage 2 (only master/opt shard) — the ZeRO-2 contract."""
        eng = _toy_engine(2, dtype_block={"bf16": {"enabled": True}})
        lowered = _lower(eng)
        st_sh, _ = lowered.compile().output_shardings
        for leaf in jax.tree.leaves(st_sh.params):
            assert leaf.spec == PartitionSpec(), leaf
        for leaf in jax.tree.leaves(st_sh.master):
            assert "dp" in str(leaf.spec), leaf


# ----------------------------------------------------------------------
# overlapped + quantized collectives (ISSUE 6): wire dtype + overlap
# evidence in the compiled step
# ----------------------------------------------------------------------
class TestQuantizedOverlapLowering:
    def _quant_engine(self, overlap, gas=2):
        import deepspeed_tpu as _d
        k = jax.random.PRNGKey(0)
        params = {f"w{i}": jax.random.normal(jax.random.fold_in(k, i),
                                             (32, 32)) * 0.1
                  for i in range(4)}

        def loss_fn(p, batch, rng=None):
            x = batch["x"]
            for i in range(4):
                x = jnp.tanh(x @ p[f"w{i}"].astype(x.dtype))
            return jnp.mean((x.astype(jnp.float32) - batch["y"]) ** 2)

        return _d.initialize(loss_fn=loss_fn, params=params, config={
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": gas,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {
                "stage": 2, "zero_quantized_gradients": True,
                "zero_quantized_allreduce": True,
                "overlap_mode": overlap},
            "steps_per_print": 0})

    def _compiled(self, eng, gas=2):
        b = {"x": np.random.randn(16 * gas, 32).astype(np.float32),
             "y": np.random.randn(16 * gas, 32).astype(np.float32)}
        sharded = eng._shard_batch(b)
        return eng._train_step.lower(eng.state, sharded,
                                     jax.random.PRNGKey(0), {}).compile()

    def test_quantized_payloads_are_s8_on_the_wire(self, devices8):
        """Every grad-path collective the quantized primitives launch
        must carry s8/u8 payload operands — a quantized mode whose flags
        parse but whose wire stays f32/bf16 would pass loss tests and
        save nothing.  Grad-path ops are identified by their op metadata
        (source_file = comm/compressed.py); the partitioner is free to
        add f32 layout gathers of its own (e.g. re-materializing the
        loop-invariant params), which are not the quantized wire."""
        txt = self._compiled(self._quant_engine("microstep")).as_text()
        grad_path = [l for l in txt.splitlines()
                     if re.search(r"%(all-to-all|all-gather|all-reduce)"
                                  r"(-start)?[.\d]* =", l)
                     and "comm/compressed.py" in l]
        assert any("all-to-all" in l for l in grad_path), (
            "no quantized reduce-scatter a2a attributed to compressed.py")
        for l in grad_path:
            assert re.search(r"\b[su]8\[", l) or re.search(r"\bf32\[\]", l), \
                f"non-quantized wire on the grad path: {l}"

    def test_microstep_overlap_schedule_evidence(self, devices8):
        """Overlap evidence, backend-portable: the double-buffered build
        must (a) carry the raw-grad tree through the accumulation loop
        (more iterArgs than the serialized build) and (b) on a backend
        with async collectives, schedule compute between start/done
        pairs.  The CPU backend is synchronous, so (b) is asserted only
        when pairs exist (what the TPU's scheduler does with them waits
        for a four-chip cell)."""
        from deepspeed_tpu.benchmarks.hlo_census import (
            async_overlap_report, collective_census)
        ser = self._quant_engine("none", gas=3)
        ovl = self._quant_engine("microstep", gas=3)

        def arity(eng):
            txt = eng._train_step.lower(
                eng.state, eng._shard_batch(
                    {"x": np.random.randn(48, 32).astype(np.float32),
                     "y": np.random.randn(48, 32).astype(np.float32)}),
                jax.random.PRNGKey(0), {}).as_text()
            return max((l.count("iterArg") for l in txt.splitlines()
                        if "while" in l), default=0)

        assert arity(ovl) > arity(ser), "no raw-grad double buffer in carry"
        compiled = self._compiled(ovl, gas=3).as_text()
        census = collective_census(compiled)
        assert census["all-to-all"] > 0, census
        pairs = async_overlap_report(compiled)
        if pairs:
            assert any(c for _, _, c in pairs), (
                f"async pairs exist but none hide compute: {pairs}")


# ----------------------------------------------------------------------
# slow-tier env-rot gating (ROADMAP): the container's jaxlib regressed
# between 2026-08-01 (all green) and 08-02 — its SPMD
# partitioner now refuses the PartitionId instruction that
# partial-manual shard_map programs (pp pipeline, ring-CP) lower to
# ("UNIMPLEMENTED: PartitionId instruction is not supported"), and
# XLA:CPU SIGABRTS the whole process compiling the ulysses sp step.
# Each gate is a lazy cached capability probe (the test_pp_inference
# precedent): the refusal skips, ANY other failure stays loud, and the
# tests re-enable themselves on a fixed jaxlib.
# ----------------------------------------------------------------------
_PARTITION_ID_MSG = "PartitionId instruction is not supported"
_partition_id_rot = None        # None = unprobed; set by first compile


def _compile_or_skip_partition_id(lowered):
    """Compile a lowered step, downgrading ONLY the known PartitionId
    refusal to a skip (and caching the verdict for the drift gate)."""
    global _partition_id_rot
    try:
        compiled = lowered.compile()
    except Exception as e:              # noqa: BLE001 - filtered below
        if _PARTITION_ID_MSG not in str(e):
            raise
        _partition_id_rot = True
        pytest.skip(
            "this jaxlib's SPMD partitioner refuses the PartitionId "
            "instruction partial-manual shard_map programs lower to "
            "(UNIMPLEMENTED; green on the 2026-08-01 image — ROADMAP "
            "slow-tier env rot)")
    _partition_id_rot = False
    return compiled


def _skip_if_partitioner_rotten(devices8):
    """Gate for assertion DRIFT (not refusal): the same jaxlib swap that
    brought the PartitionId refusal also re-groups hpZ's param gathers
    ({2: 3, 4: 4, 8: 4} where every per-use gather used to ride the
    size-2 fsdp sub-group).  Probe the refusal once (cheap pp=2 compile,
    reused from any earlier gated test) and skip the drift-sensitive
    assertions on the rotten partitioner; on a fixed jaxlib the probe
    passes and the assertions run — and must hold — again."""
    global _partition_id_rot
    if _partition_id_rot is None:
        try:
            _transformer_engine(devices8, pp=2).compile()
            _partition_id_rot = False
        except Exception as e:          # noqa: BLE001 - filtered below
            if _PARTITION_ID_MSG not in str(e):
                raise
            _partition_id_rot = True
    if _partition_id_rot:
        pytest.skip(
            "this jaxlib's partitioner drifts the hpZ gather "
            "replica-grouping (same regression as its PartitionId "
            "refusal, probed; green on the 2026-08-01 image — ROADMAP "
            "slow-tier env rot)")


# ----------------------------------------------------------------------
# structural collectives per parallelism mode
# ----------------------------------------------------------------------
class TestParallelismCollectives:
    def test_pipeline_emits_collective_permute(self, devices8):
        txt = _compile_or_skip_partition_id(
            _transformer_engine(devices8, pp=2)).as_text()
        counts = _collectives(txt)
        assert counts["collective-permute"] > 0, counts

    def test_ulysses_emits_all_to_all(self, devices8):
        if os.environ.get("_DSTPU_ULYSSES_CHILD") == "1":
            # child branch: actually compile — a SIGABRT kills only the
            # child interpreter, never the suite
            txt = _transformer_engine(devices8, sp=True,
                                      sp_mode="ulysses").compile().as_text()
            counts = _collectives(txt)
            assert counts["all-to-all"] > 0, counts
            return
        # parent branch: XLA:CPU on this jaxlib ABORTS the process
        # ("Fatal Python error: Aborted" inside backend_compile) on this
        # program — uncatchable in-process, so re-exec this one test in
        # a child pytest and translate only an abort into a skip
        r = subprocess.run(
            [sys.executable, "-m", "pytest",
             f"{os.path.abspath(__file__)}::TestParallelismCollectives"
             f"::test_ulysses_emits_all_to_all",
             "-q", "-p", "no:cacheprovider"],
            env={**os.environ, "_DSTPU_ULYSSES_CHILD": "1"},
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            capture_output=True, timeout=900)
        if r.returncode == 0:
            return
        blob = r.stdout + r.stderr
        if r.returncode < 0 or r.returncode == 134 \
                or b"Fatal Python error: Aborted" in blob:
            pytest.skip(
                "XLA:CPU aborts the process compiling the ulysses sp "
                "train step on this jaxlib (green on the 2026-08-01 "
                "image — ROADMAP slow-tier env rot)")
        pytest.fail(f"ulysses child run failed (rc={r.returncode}):\n"
                    f"{blob.decode(errors='replace')[-2000:]}")

    def test_ring_cp_emits_collective_permute(self, devices8):
        txt = _compile_or_skip_partition_id(
            _transformer_engine(devices8, stage=2, sp=True,
                                sp_mode="ring")).as_text()
        counts = _collectives(txt)
        assert counts["collective-permute"] > 0, counts

    def test_moe_ep_emits_all_to_all(self, devices8):
        txt = _transformer_engine(devices8, moe=True).compile().as_text()
        counts = _collectives(txt)
        assert counts["all-to-all"] > 0, counts

    def test_tp_emits_reduction_collective(self, devices8):
        """Row-parallel matmul partial sums must reduce over tp."""
        txt = _transformer_engine(devices8, stage=1, tp=2).compile().as_text()
        counts = _collectives(txt)
        assert counts["all-reduce"] + counts["reduce-scatter"] > 0, counts

    def test_hpz_gathers_ride_intra_group_only(self, devices8):
        """ZeRO++ hpZ with partition size 2 on the 4x2 dp x fsdp mesh:
        the param gathers in the compiled step must ride SIZE-2 replica
        groups (the fsdp sub-group — the whole point of the secondary
        partition: backward gathers never cross the group), while at
        least one reduction spans a LARGER group (grads reduce over the
        full dp x fsdp world)."""
        _skip_if_partitioner_rotten(devices8)
        k = jax.random.PRNGKey(0)
        params = {f"w{i}": jax.random.normal(jax.random.fold_in(k, i),
                                             (32, 32)) * 0.1
                  for i in range(4)}

        def loss_fn(p, batch, rng=None):
            x = batch["x"]
            for i in range(4):
                x = jnp.tanh(x @ p[f"w{i}"].astype(x.dtype))
            return jnp.mean((x.astype(jnp.float32) - batch["y"]) ** 2)

        eng = dstpu.initialize(loss_fn=loss_fn, params=params, config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 3, "zero_hpz_partition_size": 2},
            "steps_per_print": 0})
        txt = _lower(eng).compile().as_text()

        def group_sizes(op):
            """replica-group size -> instruction count for `op` (both the
            iota form [n,g]<=[...] and explicit {{...}} lists)."""
            sizes = {}
            for line in txt.splitlines():
                if not re.search(rf"%{op}[.\d]* =", line):
                    continue
                m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
                if m:
                    s = int(m.group(2))
                else:
                    m = re.search(r"replica_groups=\{(\{[\d,]+\})", line)
                    if not m:
                        continue
                    s = len(m.group(1).strip("{}").split(","))
                sizes[s] = sizes.get(s, 0) + 1
            return sizes

        ag = group_sizes("all-gather")
        assert ag, "hpZ step compiled without param all-gathers"
        # the per-USE gathers (forward + backward re-fetch, the traffic
        # hpZ exists to localize) must ride the 2-device fsdp sub-group;
        # the single update-path gather (world-sharded new master ->
        # fsdp-resident params) legitimately crosses dp — it must stay a
        # minority
        assert ag.get(2, 0) >= 4, f"too few intra-group gathers: {ag}"
        assert sum(c for s, c in ag.items() if s > 2) <= ag[2], (
            f"cross-group gathers dominate — hpZ gather domain "
            f"regressed: {ag}")
        red = group_sizes("all-reduce") | group_sizes("reduce-scatter")
        assert any(s > 2 for s in red), (
            f"grad reduction should span more than the fsdp sub-group "
            f"(dp x fsdp world); reduction group sizes: {red}")
