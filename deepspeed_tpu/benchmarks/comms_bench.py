"""Collective bandwidth sweep — the `ds_bench` analog.

Reference: `bin/ds_bench` drives the DeepSpeed comms benchmarks
(all_reduce/all_gather/all_to_all/broadcast/pt2pt over sizes, reporting
algbw/busbw — utils/comms_logging.py:67 get_bw computes the same numbers the
summary table prints).

TPU-first: the collectives are XLA ops over the device mesh (ICI on a real
slice), launched via shard_map and timed with blocking host sync.  busbw
follows the standard ring-model corrections: allreduce 2(n-1)/n, allgather /
reducescatter / alltoall (n-1)/n of the payload.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Callable, Dict, List

import numpy as np

import jax
from jax import shard_map
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

__all__ = ["run_sweep", "run_quant_sweep", "run_tp_inference_sweep",
           "run_moe_sweep", "main"]

_AX = "bench"


def _ops(world: int) -> Dict[str, Callable]:
    P = PartitionSpec(_AX)
    R = PartitionSpec()

    def all_reduce(x):
        return jax.lax.psum(x, _AX)

    def all_gather(x):
        return jax.lax.all_gather(x, _AX, tiled=True)

    def reduce_scatter(x):
        return jax.lax.psum_scatter(x, _AX, tiled=True)

    def all_to_all(x):
        return jax.lax.all_to_all(x, _AX, split_axis=0, concat_axis=0,
                                  tiled=True)

    def broadcast(x):
        # root's shard to everyone; XLA lowers this via AllGather on the
        # mesh, so bandwidth accounting matches all_gather below
        full = jax.lax.all_gather(x, _AX)
        return full[0]

    return {
        "all_reduce": (all_reduce, P, P),
        "all_gather": (all_gather, P, R),
        "reduce_scatter": (reduce_scatter, P, P),
        "all_to_all": (all_to_all, P, P),
        "broadcast": (broadcast, P, R),
    }


def _busbw_factor(op: str, n: int) -> float:
    if op == "all_reduce":
        return 2.0 * (n - 1) / n
    # broadcast is AllGather-backed here (each device receives (n-1)/n of
    # the buffer), so it uses the same correction — not the NCCL root-push
    # model whose payload this implementation does not match
    if op in ("all_gather", "reduce_scatter", "all_to_all", "broadcast"):
        return (n - 1) / n
    return 1.0


def run_sweep(ops: List[str] = None, min_bytes: int = 1 << 15,
              max_bytes: int = 1 << 26, dtype=jnp.bfloat16,
              trials: int = 5, warmups: int = 2, mesh: Mesh = None) -> List[dict]:
    devices = mesh.devices.reshape(-1) if mesh is not None else jax.devices()
    world = len(devices)
    mesh = mesh or Mesh(np.array(devices), (_AX,))
    table = _ops(world)
    ops = ops or list(table)
    itemsize = jnp.dtype(dtype).itemsize
    results = []
    for op in ops:
        fn, in_spec, out_spec = table[op]
        size = min_bytes
        while size <= max_bytes:
            n_elem = max(size // itemsize, world) // world * world
            x = jnp.ones((n_elem,), dtype)
            shx = jax.device_put(
                x, jax.sharding.NamedSharding(mesh, PartitionSpec(_AX)))
            run = jax.jit(shard_map(fn, mesh=mesh, in_specs=in_spec,
                                        out_specs=out_spec, check_vma=False))
            for _ in range(warmups):
                jax.block_until_ready(run(shx))
            t0 = time.perf_counter()
            for _ in range(trials):
                jax.block_until_ready(run(shx))
            dt = (time.perf_counter() - t0) / trials
            payload = n_elem * itemsize
            algbw = payload / dt / 1e9
            results.append({
                "op": op, "bytes": payload, "time_ms": dt * 1e3,
                "algbw_GBps": algbw,
                "busbw_GBps": algbw * _busbw_factor(op, world),
                "world": world,
            })
            size <<= 2
    return results


def run_quant_sweep(n_bytes: int = 1 << 22, dtype=jnp.bfloat16,
                    trials: int = 5, warmups: int = 2,
                    n_leaves: int = 32) -> List[dict]:
    """Quantized-collective rows (ISSUE 6): hierarchical 2-hop qgZ vs
    single-hop, EQuARX quantized all-reduce vs psum, and bucketed vs
    per-leaf reduction of many small leaves.  Each row reports measured
    wall time AND measured wire bytes (from the compiled HLO census), so
    the quantization/hierarchy saving is a number, not a dtype claim."""
    from ..comm.compressed import (hierarchical_quantized_reduce_scatter,
                                   quantized_all_reduce,
                                   quantized_reduce_scatter)
    devices = jax.devices()
    world = len(devices)
    assert world % 2 == 0, "quant sweep needs an even device count"
    mesh_flat = Mesh(np.array(devices), (_AX,))
    # (node, chip)-factored mesh for the 2-hop rows: the outer axis plays
    # the DCN-like inter hop, the inner the ICI-like intra hop
    mesh_fac = Mesh(np.array(devices).reshape(2, world // 2),
                    ("node", "chip"))
    itemsize = jnp.dtype(dtype).itemsize
    n_elem = max(n_bytes // itemsize // world, 256) * world
    P, R = PartitionSpec(_AX), PartitionSpec()
    Pf = PartitionSpec(("node", "chip"))

    def _time(run, *args):
        for _ in range(warmups):
            jax.block_until_ready(run(*args))
        t0 = time.perf_counter()
        for _ in range(trials):
            jax.block_until_ready(run(*args))
        return (time.perf_counter() - t0) / trials

    from .hlo_census import collective_wire_bytes
    rows = []

    def _row(op, fn, in_spec, out_spec, mesh, x, note="",
             logical_bytes=None):
        run = jax.jit(shard_map(fn, mesh=mesh, in_specs=(in_spec,),
                                out_specs=out_spec, check_vma=False))
        # one compile: the lowered executable is timed AND censused
        compiled = run.lower(x).compile()
        dt = _time(run, x)
        wire = collective_wire_bytes(compiled.as_text(), world)
        rows.append({
            "op": op,
            "bytes": int(logical_bytes if logical_bytes is not None
                         else n_elem * itemsize),
            "wire_bytes": int(wire), "time_ms": dt * 1e3,
            "world": world, "note": note,
        })

    x = jnp.ones((n_elem,), dtype)
    shx = jax.device_put(x, jax.sharding.NamedSharding(mesh_flat, P))
    shxf = jax.device_put(x, jax.sharding.NamedSharding(mesh_fac, Pf))

    # gradient reduce-scatter family: bf16 baseline, int8/int4 single
    # hop, 2-hop hierarchical (bf16 intra + int8 inter)
    _row("psum_scatter_bf16",
         lambda v: jax.lax.psum_scatter(v, _AX, scatter_dimension=0,
                                        tiled=True),
         P, P, mesh_flat, shx)
    for bits in (8, 4):
        _row(f"qgz_rs_int{bits}",
             lambda v, b=bits: quantized_reduce_scatter(v, _AX, world,
                                                        bits=b),
             P, P, mesh_flat, shx)
    _row("qgz_rs_2hop_int8",
         lambda v: hierarchical_quantized_reduce_scatter(
             v, "chip", "node", world // 2, 2, bits=8),
         Pf, PartitionSpec(("chip", "node")), mesh_fac, shxf,
         note="bf16 intra (chip) + int8 inter (node)")

    # all-reduce family: psum baseline vs EQuARX quantized
    _row("psum_bf16", lambda v: jax.lax.psum(v, _AX), P, P, mesh_flat, shx)
    for bits in (8, 4):
        _row(f"quant_allreduce_int{bits}",
             lambda v, b=bits: quantized_all_reduce(v, _AX, world, bits=b),
             P, P, mesh_flat, shx)

    # bucketing: n_leaves small leaves reduced per-leaf vs coalesced into
    # one flat bucket (per-leaf pays launch + block padding per leaf)
    leaf = max(n_elem // n_leaves // 64, 32)
    xs = jnp.ones((n_leaves, leaf), dtype)
    shxs = jax.device_put(xs, jax.sharding.NamedSharding(mesh_flat, R))

    def per_leaf(vs):
        return jnp.stack([quantized_all_reduce(vs[i], _AX, world, bits=8)
                          for i in range(n_leaves)])

    def bucketed(vs):
        return quantized_all_reduce(vs.reshape(-1), _AX, world,
                                    bits=8).reshape(vs.shape)

    small_bytes = n_leaves * leaf * itemsize
    _row("quant_allreduce_per_leaf", per_leaf, R, R, mesh_flat, shxs,
         note=f"{n_leaves} leaves x {leaf} elems, one launch each",
         logical_bytes=small_bytes)
    _row("quant_allreduce_bucketed", bucketed, R, R, mesh_flat, shxs,
         note=f"same {n_leaves} leaves coalesced into one flat bucket",
         logical_bytes=small_bytes)
    return rows


def run_moe_sweep(experts: int = 16, capacity: int = 512,
                  hidden: int = 1024, dtype=jnp.float32,
                  trials: int = 5, warmups: int = 2) -> List[dict]:
    """Expert-parallel a2a rows (ISSUE 20): the MoE dispatch+combine
    round trip (`moe/sharded.py moe_dispatch_a2a` / `moe_combine_a2a`)
    plain vs int8 block-quantized wire, at the [E, C, H] dispatch-buffer
    shape a capacity-factor router produces.  Each row reports measured
    wall time AND the CommsLogger wire bytes the hop recorded — the same
    accounting the training regime asserts — so the quantized dispatch's
    wire saving is a measured number; the int8 row is asserted at
    >= 2x fewer bytes than the raw row.  The default dtype is fp32 (the
    dryrun regimes' model dtype; ~3.9x on the wire) — a bf16 baseline
    lands at ~1.97x, the block scales eating the last percent."""
    from ..comm.comm import comms_logger
    from ..moe.sharded import moe_combine_a2a, moe_dispatch_a2a

    devices = jax.devices()
    world = len(devices)
    if world < 2:
        raise RuntimeError(
            "the --moe rows need >= 2 devices (run with --platform cpu "
            "--devices 8 for a virtual mesh)")
    mesh = Mesh(np.array(devices), (_AX,))
    E = max(experts // world, 1) * world   # owner-major buffer needs E % ep == 0
    itemsize = jnp.dtype(dtype).itemsize
    R = PartitionSpec()

    def _time(run, *args):
        for _ in range(warmups):
            jax.block_until_ready(run(*args))
        t0 = time.perf_counter()
        for _ in range(trials):
            jax.block_until_ready(run(*args))
        return (time.perf_counter() - t0) / trials

    x = jnp.asarray(np.random.RandomState(11).randn(E, capacity, hidden),
                    dtype)
    rows: List[dict] = []
    wire_by_bits: Dict[object, int] = {}
    for bits in (None, 8, 4):
        def hop(v, b=bits):
            d = moe_dispatch_a2a(v, _AX, bits=b)
            return moe_combine_a2a(d, _AX, bits=b)

        # full-manual shard_map (the _moe_layer_a2a discipline) with a
        # replicated input: every rank ships its whole [E, C, H] buffer
        run = jax.jit(shard_map(hop, mesh=mesh, in_specs=(R,),  # dstpu: noqa[DST004] each iteration IS a distinct benched program (plain vs int8/int4 wire arm), compiled exactly once and timed
                                out_specs=R, check_vma=False))
        # wire bytes are recorded at TRACE time (the logger hook sits in
        # the hop builders), so one enabled lower() captures exactly one
        # invocation's bytes
        comms_logger.configure(enabled=True)
        comms_logger.comms_dict.clear()
        try:
            compiled = run.lower(x).compile()
            wire = sum(size * sum(counts)
                       for op, sizes in comms_logger.comms_dict.items()
                       if op.startswith("moe_")
                       for size, counts in sizes.items())
        finally:
            comms_logger.configure(enabled=False)
        del compiled
        dt = _time(run, x)
        tag = "raw" if bits is None else f"int{bits}"
        wire_by_bits[bits] = int(wire)
        rows.append({
            "op": f"moe_a2a_{tag}",
            "bytes": int(E * capacity * hidden * itemsize),
            "wire_bytes": int(wire), "time_ms": dt * 1e3,
            "world": world,
            "note": (f"dispatch+combine round trip, [E={E}, C={capacity}, "
                     f"H={hidden}] {'raw' if bits is None else 'block-quant'} wire"),
        })
    assert wire_by_bits[8] * 2 <= wire_by_bits[None], (
        f"int8 a2a wire {wire_by_bits[8]} is not >= 2x smaller than the "
        f"raw wire {wire_by_bits[None]} — the quantized dispatch is "
        f"not saving bytes")
    return rows


def run_tp_inference_sweep(hidden: int = 1024, ffn: int = 4096,
                           decode_rows: int = 64,
                           prefill_rows: int = 2048, dtype=jnp.bfloat16,
                           trials: int = 10, warmups: int = 3) -> List[dict]:
    """TP-inference matmul-collective rows (ISSUE 12): the fused ring
    kernels (`ops/tp_matmul.py` ag_matmul / matmul_rs — the exact
    per-block composition `inference/v2/tp_ragged.py` serves) vs their
    monolithic XLA twins (all_gather-then-GEMM / GEMM-then-psum_scatter),
    at the decode (skinny batch) and prefill (chunk-flat batch) shapes.
    Each row reports measured wall time AND `hlo_census` wire bytes per
    step, so "fused is free on the wire and hides the hops" is a
    number, not a schedule claim.  On a 1-hop CPU mesh wall times mostly
    document parity — the overlap would show on ICI.  `decode_rows` defaults to 64 so per-chunk GEMMs keep
    rows/world >= 8 on an 8-wide mesh — below the 8-row sublane tile the
    Pallas kernel auto-falls back to jnp.dot and the decode rows would
    time the wrong GEMM on TPU."""
    from ..ops.tp_matmul import (ag_matmul, ag_matmul_xla, matmul_rs,
                                 matmul_rs_xla, tile_matmul)
    from .hlo_census import collective_wire_bytes

    devices = jax.devices()
    world = len(devices)
    if world < 2:
        raise RuntimeError(
            "the --tp-inference rows need >= 2 devices (run with "
            "--platform cpu --devices 8 for a virtual mesh)")
    mesh = Mesh(np.array(devices), (_AX,))
    itemsize = jnp.dtype(dtype).itemsize
    P = PartitionSpec(_AX)
    Pc = PartitionSpec(None, _AX)

    def _time(run, *args):
        for _ in range(warmups):
            jax.block_until_ready(run(*args))
        t0 = time.perf_counter()
        for _ in range(trials):
            jax.block_until_ready(run(*args))
        return (time.perf_counter() - t0) / trials

    rows: List[dict] = []

    def _pair(stage: str, rows_n: int, K: int, N: int, op: str):
        """One fused + one unfused row for a (rows_n, K) x (K, N)
        matmul-collective: op="ag" gathers the row-sharded activation
        into the GEMM, op="rs" reduce-scatters the GEMM's partials."""
        rng = np.random.RandomState(7)
        if op == "ag":
            x = jnp.asarray(rng.randn(rows_n, K), dtype)
            w = jnp.asarray(rng.randn(K, N // world), dtype)
            x_spec, w_spec, o_spec = P, PartitionSpec(), PartitionSpec(None, None)
            mk = lambda fused: (lambda xv, wv: (ag_matmul if fused else ag_matmul_xla)(
                xv, _AX, world,
                lambda c: tile_matmul(c, wv, impl="auto").astype(dtype)))
        else:
            x = jnp.asarray(rng.randn(rows_n, K), dtype)
            w = jnp.asarray(rng.randn(K // world, N), dtype)
            x_spec, w_spec, o_spec = Pc, PartitionSpec(), P
            mk = lambda fused: (lambda xv, wv: (matmul_rs if fused else matmul_rs_xla)(
                xv, _AX, world,
                lambda c: tile_matmul(c, wv, impl="auto")).astype(dtype))
        shx = jax.device_put(x, jax.sharding.NamedSharding(mesh, x_spec))
        shw = jax.device_put(w, jax.sharding.NamedSharding(mesh, w_spec))
        for fused in (True, False):
            run = jax.jit(shard_map(mk(fused), mesh=mesh,  # dstpu: noqa[DST004] each iteration IS a distinct benched program (fused vs xla arm), compiled exactly once and timed
                                    in_specs=(x_spec, w_spec),
                                    out_specs=o_spec, check_vma=False))
            compiled = run.lower(shx, shw).compile()
            dt = _time(run, shx, shw)
            rows.append({
                "op": f"tp_{stage}_{op}_{'fused' if fused else 'xla'}",
                "bytes": int(rows_n * K * itemsize),
                "wire_bytes": int(collective_wire_bytes(
                    compiled.as_text(), world)),
                "time_ms": dt * 1e3, "world": world,
                "note": (f"[{rows_n},{K}]x[{K},{N}] "
                         f"{'ring matmul-collective' if fused else 'monolithic collective + GEMM'}"),
            })

    # decode: the skinny [max_seqs] batch; prefill: a flat 2048-token chunk
    _pair("decode", decode_rows, hidden, ffn, "ag")
    _pair("decode", decode_rows, ffn, hidden, "rs")
    _pair("prefill", prefill_rows, hidden, ffn, "ag")
    _pair("prefill", prefill_rows, ffn, hidden, "rs")
    return rows


def main(argv=None) -> int:
    import sys
    argv = list(sys.argv[1:]) if argv is None else list(argv)
    p = argparse.ArgumentParser(
        "dstpu_bench",
        description="XLA collective bandwidth sweep (ds_bench)")
    p.add_argument("--ops", nargs="*", default=None,
                   help="subset of: all_reduce all_gather reduce_scatter "
                        "all_to_all broadcast")
    p.add_argument("--quant", action="store_true",
                   help="run the quantized-collective rows (hierarchical "
                        "qgZ, quantized all-reduce, bucketed-vs-per-leaf) "
                        "with measured wire bytes")
    p.add_argument("--tp-inference", action="store_true",
                   help="run the TP-inference matmul-collective rows "
                        "(fused ring ag_matmul/matmul_rs vs monolithic "
                        "XLA collective+GEMM, decode + prefill shapes) "
                        "with measured wire bytes")
    p.add_argument("--moe", action="store_true",
                   help="run the MoE expert-parallel a2a rows "
                        "(dispatch+combine round trip, plain vs int8/int4 "
                        "block-quantized wire) with CommsLogger wire bytes")
    p.add_argument("--minbytes", type=int, default=1 << 15)
    p.add_argument("--maxbytes", type=int, default=1 << 26)
    p.add_argument("--trials", type=int, default=5)
    p.add_argument("--json", action="store_true", help="one JSON line per row")
    p.add_argument("--platform", default=None,
                   help="force backend (e.g. cpu) before device init")
    p.add_argument("--devices", type=int, default=0,
                   help="with --platform cpu: number of virtual devices")
    args = p.parse_args(argv)
    if args.platform:
        # backends init lazily; setting config before first device use works
        # even though jax is already imported (same trick as tests/conftest)
        jax.config.update("jax_platforms", args.platform)
        if args.devices:
            import os
            os.environ["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={args.devices} "
                + os.environ.get("XLA_FLAGS", ""))
    from ..utils.device import place_compile_cache
    place_compile_cache()
    if args.moe:
        rows = run_moe_sweep(trials=args.trials)
        if args.json:
            for r in rows:
                print(json.dumps(r))
        else:
            hdr = (f"{'op':<26}{'bytes':>12}{'wire bytes':>12}"
                   f"{'time(ms)':>12}  note")
            print(hdr)
            print("-" * len(hdr))
            for r in rows:
                print(f"{r['op']:<26}{r['bytes']:>12}{r['wire_bytes']:>12}"
                      f"{r['time_ms']:>12.3f}  {r['note']}")
        return 0
    if args.tp_inference:
        rows = run_tp_inference_sweep(trials=args.trials)
        if args.json:
            for r in rows:
                print(json.dumps(r))
        else:
            hdr = (f"{'op':<26}{'bytes':>12}{'wire bytes':>12}"
                   f"{'time(ms)':>12}  note")
            print(hdr)
            print("-" * len(hdr))
            for r in rows:
                print(f"{r['op']:<26}{r['bytes']:>12}{r['wire_bytes']:>12}"
                      f"{r['time_ms']:>12.3f}  {r['note']}")
        return 0
    if args.quant:
        rows = run_quant_sweep(n_bytes=args.maxbytes, trials=args.trials)
        if args.json:
            for r in rows:
                print(json.dumps(r))
        else:
            hdr = (f"{'op':<26}{'bytes':>12}{'wire bytes':>12}"
                   f"{'time(ms)':>12}  note")
            print(hdr)
            print("-" * len(hdr))
            for r in rows:
                print(f"{r['op']:<26}{r['bytes']:>12}{r['wire_bytes']:>12}"
                      f"{r['time_ms']:>12.3f}  {r['note']}")
        return 0
    rows = run_sweep(args.ops, args.minbytes, args.maxbytes,
                     trials=args.trials)
    if args.json:
        for r in rows:
            print(json.dumps(r))
    else:
        hdr = f"{'op':<16}{'bytes':>12}{'time(ms)':>12}{'algbw GB/s':>14}{'busbw GB/s':>14}"
        print(hdr)
        print("-" * len(hdr))
        for r in rows:
            print(f"{r['op']:<16}{r['bytes']:>12}{r['time_ms']:>12.3f}"
                  f"{r['algbw_GBps']:>14.2f}{r['busbw_GBps']:>14.2f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
