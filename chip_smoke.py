"""Quickest proof that today's program starts, compiles and computes right
answers on the attached TPU.

    python chip_smoke.py            # one chip: train phase, then serve phase
    python chip_smoke.py --chips 4  # four chips: ZeRO-3 fsdp=4 and tp=4 only

One process drives everything (a chip belongs to one process), through the
entry points a user calls — `deepspeed_tpu.initialize` / `train_batch` /
`save_checkpoint` / `load_checkpoint`, and `inference.v2.build_engine` /
`serving.ServeLoop` — on TinyLlama-1.1B at its full published size
(`llama_config("1b")`: hidden 2048, 22 layers, 32/4 heads, ffn 5632, vocab
32000), bf16, sequence 2048.  Weights and prompts are made from a seed.

It fails (non-zero exit, no result line) when JAX reports no TPU, and when
any phase raises; nothing is caught and downgraded.  Phase reports go on
earlier lines; the last line of stdout is the one JSON object the driver
reads: {"ok": true, "device": {"platform", "kind", "count"}}.

The persistent compile cache goes where `JAX_COMPILATION_CACHE_DIR` says,
else `<checkout>/.cache/xla` (utils.device.place_compile_cache).

The phases are functions of a `Size`; tests/test_chip_smoke.py calls them
at `TINY` on the CPU.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import shutil
import time
from typing import Dict, List, Sequence, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Size:
    model: str                    # llama_config preset
    seq: int                      # training sequence length
    micro: int                    # one-chip micro batch
    prompts: Tuple[int, ...]      # serve prompt lengths
    short: Tuple[int, ...]        # prompt lengths of the kernel-vs-dense check
    new_tokens: int
    num_blocks: int               # KV arena blocks (block_size 64)
    max_seqs: int                 # decode batch width
    model_kw: Tuple = ()          # preset overrides, as (key, value) pairs


FULL = Size(model="1b", seq=2048, micro=4,
            prompts=(64, 128, 200, 384, 512, 640, 1024, 96),
            short=(64, 128, 640), new_tokens=32, num_blocks=256,
            max_seqs=32)
# head_dim 64, so the paged kernels' shape gates admit the tiny model too
TINY = Size(model="tiny", seq=256, micro=2, prompts=(64, 640),
            short=(640,), new_tokens=4, num_blocks=32, max_seqs=4,
            model_kw=(("num_heads", 4), ("num_kv_heads", 2)))

SEED = 0
TRAIN_STEPS = 4
# a device may hold this much more than the mean after set-up before the
# four-chip phases call it "the whole model landed on one device"
SHARE_MARGIN = 1.25
CKPT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        ".cache", "chip_smoke_ckpt")


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + json.dumps(fields, default=str), flush=True)


def count_kernels(compiled_text: str) -> int:
    """Mosaic kernels in a compiled program's text."""
    return compiled_text.count("tpu_custom_call")


def hbm(device=None) -> Dict[str, int]:
    """bytes_in_use / peak_bytes_in_use of one device ({} where the backend
    keeps no statistics, i.e. the CPU the tests run on)."""
    import jax
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return {k: int(stats[k]) for k in ("bytes_in_use", "peak_bytes_in_use",
                                       "bytes_limit") if k in stats}


def free(*trees) -> None:
    """Delete the device arrays of engines that are done (two 1.1B
    training states do not fit one chip together)."""
    import jax
    for leaf in jax.tree.leaves(trees):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()
    gc.collect()


# ----------------------------------------------------------------------
# train
# ----------------------------------------------------------------------
def train_config(micro: int, stage: int, gas: int = 1) -> dict:
    # int8 Adam moments + bf16 grad accumulation + save_attn remat is
    # what fits a 1B-class state in 16 GB
    return {
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "adamw",
                      "params": {"lr": 1e-4, "weight_decay": 0.1,
                                 "state_dtype": "int8f"}},
        "data_types": {"grad_accum_dtype": "bf16"},
        "zero_optimization": {"stage": stage},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "steps_per_print": 0,
        "seed": SEED,
        "activation_checkpointing": {"policy": "save_attn"},
    }


def _model(size: Size):
    import jax.numpy as jnp
    from deepspeed_tpu.models import Transformer, llama_config
    kw = dict(max_seq_len=size.seq, dtype=jnp.bfloat16, remat=True,
              tiled_loss_shards=8)
    kw.update(size.model_kw)
    return Transformer(llama_config(size.model, **kw))


def _batch(size: Size, rows: int, vocab: int) -> dict:
    rng = np.random.RandomState(SEED)
    return {"input_ids": rng.randint(
        0, vocab, (rows, size.seq + 1)).astype(np.int32)}


def _step_program(engine, batch):
    """The engine's compiled train step, lowered from the arguments
    `train_batch` would pass (the persistent cache then serves the jit
    call itself)."""
    import jax
    return engine._train_step.lower(
        engine.state, engine._shard_batch(batch), jax.random.PRNGKey(0),
        {}).compile()


def _run_steps(engine, batch, n: int) -> Tuple[List[float], List[float]]:
    import jax
    losses, secs = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        loss = jax.block_until_ready(engine.train_batch(batch)["loss"])
        secs.append(round(time.perf_counter() - t0, 3))
        losses.append(float(loss))
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"non-finite training loss: {losses}")
    return losses, secs


def train_phase(size: Size, ckpt_dir: str = CKPT_DIR) -> dict:
    """initialize -> train_batch x4 -> save -> load into a second engine ->
    one more step.  ZeRO-1 on the chip(s) JAX reports."""
    import deepspeed_tpu as ds
    model = _model(size)
    cfg = train_config(size.micro, stage=1)
    engine = ds.initialize(model=model, config=cfg)
    batch = _batch(size, engine.config.train_batch_size,
                   model.cfg.vocab_size)
    t0 = time.perf_counter()
    program = _step_program(engine, batch)
    compile_s = round(time.perf_counter() - t0, 2)
    kernels = count_kernels(program.as_text())
    mem = program.memory_analysis()
    say("train", params=model.num_params(), seq=size.seq, micro=size.micro,
        step_compile_s=compile_s, flash_kernels_in_step=kernels,
        program_bytes={"arguments": mem.argument_size_in_bytes,
                       "temps": mem.temp_size_in_bytes,
                       "aliased": mem.alias_size_in_bytes})
    if not kernels:
        raise AssertionError("no flash kernel (tpu_custom_call) in the "
                             "compiled train step")
    losses, secs = _run_steps(engine, batch, TRAIN_STEPS)
    say("train", losses=losses, step_s=secs, hbm=hbm())
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall on a repeated batch: "
                             f"{losses}")

    shutil.rmtree(ckpt_dir, ignore_errors=True)
    try:
        t0 = time.perf_counter()
        engine.save_checkpoint(ckpt_dir)
        save_s = round(time.perf_counter() - t0, 1)
        free(engine.state)
        resumed = ds.initialize(model=model, config=cfg)
        t0 = time.perf_counter()
        resumed.load_checkpoint(ckpt_dir)
        load_s = round(time.perf_counter() - t0, 1)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    step = int(resumed.state.step)
    (after,), _ = _run_steps(resumed, batch, 1)
    say("train", checkpoint_save_s=save_s, checkpoint_load_s=load_s,
        resumed_at_step=step, loss_after_resume=after, hbm=hbm())
    if step != TRAIN_STEPS or resumed.global_steps != TRAIN_STEPS + 1:
        raise AssertionError(f"resumed at step {step}, expected "
                             f"{TRAIN_STEPS}")
    # continuous: nearer to where training stood than to where it began,
    # and no further from the last loss than steps have moved before
    # (without warm-up the first steps at lr 1e-4 are not monotone)
    jumps = np.abs(np.diff(losses))
    if not (after < (losses[0] + losses[-1]) / 2
            and abs(after - losses[-1]) <= 1.5 * jumps.max()):
        raise AssertionError(f"loss not continuous across the resume: "
                             f"{losses} then {after}")
    free(resumed.state)
    return {"losses": losses + [after], "kernels": kernels}


def train_sharded_phase(size: Size, n_dev: int = 4) -> dict:
    """ZeRO-3 over fsdp=n_dev against the one-device trajectory of the same
    global batch and seed (one device first, by gradient accumulation, and
    freed before the sharded engine is built)."""
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.parallel.mesh import make_mesh
    model = _model(size)
    batch = _batch(size, n_dev, model.cfg.vocab_size)
    ref = ds.initialize(
        model=model, config=train_config(1, stage=3, gas=n_dev),
        topology=make_mesh(devices=jax.devices()[:1]))
    ref_losses, ref_s = _run_steps(ref, batch, TRAIN_STEPS)
    say("train4", one_device_losses=ref_losses, step_s=ref_s)
    free(ref.state)

    engine = ds.initialize(
        model=model, config=train_config(1, stage=3),
        topology=make_mesh(fsdp=n_dev, devices=jax.devices()[:n_dev]))
    per_device = _per_device_in_use(n_dev)
    program = _step_program(engine, batch)
    kernels = count_kernels(program.as_text())
    losses, secs = _run_steps(engine, batch, TRAIN_STEPS)
    say("train4", zero3_fsdp=n_dev, losses=losses, step_s=secs,
        flash_kernels_in_step=kernels,
        bytes_in_use_after_setup=per_device,
        temp_bytes_per_device=program.memory_analysis().temp_size_in_bytes)
    _check_share("train4", per_device)
    # step 1 is a pure forward of identical weights; later steps carry the
    # bf16 accumulation-order and int8-moment rounding of two different
    # reduction trees
    if abs(losses[0] - ref_losses[0]) > 5e-3 * abs(ref_losses[0]):
        raise AssertionError(f"first loss differs: sharded {losses[0]} vs "
                             f"one device {ref_losses[0]}")
    if not np.allclose(losses, ref_losses, rtol=3e-2):
        raise AssertionError(f"ZeRO-3 trajectory {losses} left the "
                             f"one-device trajectory {ref_losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"sharded loss did not fall: {losses}")
    free(engine.state)
    return {"losses": losses, "ref_losses": ref_losses}


def _per_device_in_use(n_dev: int) -> List[int]:
    import jax
    return [hbm(d).get("bytes_in_use", 0) for d in jax.devices()[:n_dev]]


def _check_share(phase: str, per_device: Sequence[int]) -> None:
    """After set-up no device may hold (much) more than its share: an
    engine that builds the whole model on device 0 and keeps it shows
    here."""
    mean = sum(per_device) / len(per_device)
    if mean and max(per_device) > SHARE_MARGIN * mean:
        raise AssertionError(
            f"[{phase}] a device holds {max(per_device)} bytes after "
            f"set-up, over {SHARE_MARGIN}x the mean {int(mean)}: "
            f"{list(per_device)}")


# ----------------------------------------------------------------------
# serve
# ----------------------------------------------------------------------
def _prompts(size: Size, vocab: int) -> List[np.ndarray]:
    rng = np.random.RandomState(SEED + 1)
    return [rng.randint(0, vocab, n).astype(np.int32) for n in size.prompts]


def _engine(size: Size, params=None, serving_config=None, **cfg_kw):
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2 import (RaggedInferenceEngineConfig,
                                            build_engine)
    kw = dict(dtype=jnp.bfloat16, max_seq_len=2048)
    kw.update(size.model_kw)
    kw.update(cfg_kw)
    return build_engine(
        "llama", size.model, params=params, serving_config=serving_config,
        engine_config=RaggedInferenceEngineConfig(
            num_blocks=size.num_blocks, max_seqs=size.max_seqs), **kw)


@contextlib.contextmanager
def spied_programs(eng, seen: Dict[str, object]):
    """Keep, for the first call of each serving program, the executable
    compiled from that call's own arguments (so its text and memory
    analysis can be read); the call then goes through unchanged."""
    from deepspeed_tpu.inference.v2 import ragged_ops

    def spy(name, fn, lower):
        def call(*a, **k):
            if name not in seen:
                seen[name] = lower(*a, **k).compile()
            return fn(*a, **k)
        return call

    bound = {n: getattr(eng._programs, n)
             for n in ("prefill_chunks", "decode_step", "decode_multi_step")}
    full = ragged_ops.prefill_full   # step() imports it per call
    for name, p in bound.items():     # functools.partial of a jitted fn
        setattr(eng._programs, name, spy(
            name, p, lambda *a, _p=p, **k: _p.func.lower(
                *_p.args, *a, **{**_p.keywords, **k})))
    ragged_ops.prefill_full = spy("prefill_full", full, full.lower)
    try:
        yield seen
    finally:
        ragged_ops.prefill_full = full
        for name, p in bound.items():
            setattr(eng._programs, name, p)


def _serve(eng, serving_config, prompts, new_tokens: int) -> dict:
    """submit -> run_until_idle; every request must finish, none lost or
    starved, and the block audit must come back clean."""
    from deepspeed_tpu.serving import ServeLoop
    from deepspeed_tpu.serving.request import RequestState
    loop = ServeLoop(eng, serving_config)
    t0 = time.perf_counter()
    reqs = [loop.submit(p, max_new_tokens=new_tokens) for p in prompts]
    loop.run_until_idle(max_steps=2000)   # raises on a starved request
    wall = time.perf_counter() - t0
    bad = [(r.uid, r.state.value, len(r.generated)) for r in reqs
           if r.state is not RequestState.DONE
           or len(r.generated) != new_tokens]
    s = loop.telemetry.summary()
    if bad or s["completed"] != len(reqs) or s["timed_out"] \
            or s["cancelled"]:
        raise AssertionError(f"requests lost or unfinished: {bad}; {s}")
    audit = eng.audit_blocks()            # raises on a leaked block
    return {"tokens": [list(map(int, r.generated)) for r in reqs],
            "wall_s": round(wall, 2), "audit": audit,
            "ttft_p50_s": s["ttft_p50_s"]}


# bf16 carries 8 significant bits; 22 layers of it leave two correct paths
# this far apart at most, relative to the largest logit
LOGIT_TOL = 5e-2


def _probe_prompts(size: Size, vocab: int) -> List[np.ndarray]:
    rng = np.random.RandomState(SEED + 2)
    return [rng.randint(0, vocab, n).astype(np.int32) for n in size.short]


def _probe_logits(eng, probes) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Per probe prompt: its last token's logits (prefill program) and the
    logits of the next position after feeding the argmax (decode program),
    through the engine's put/step/flush."""
    out = []
    for uid, prompt in enumerate(probes, start=10_000):
        got = eng.put([uid], [prompt])
        while uid not in got:
            got = eng.step()
        first = np.asarray(got[uid], np.float32)
        second = np.asarray(
            eng.put([uid], [np.array([first.argmax()], np.int32)])[uid],
            np.float32)
        eng.flush(uid)
        out.append((first, second))
    return out


def _check_logits(phase: str, got, want, lens, **fields) -> float:
    """`got` against `want` (same weights, another path): logits must be
    finite and agree to LOGIT_TOL.  Greedy streams are compared by the
    callers and only reported — random weights give nearly flat logits, so
    a bf16 near-tie may flip an argmax without either side being wrong."""
    def gap(a, b):
        return round(float(np.max(np.abs(a - b)) / np.max(np.abs(b))), 5)

    if not all(np.all(np.isfinite(x)) for pair in got for x in pair):
        raise AssertionError(f"[{phase}] non-finite logits")
    gaps = [{"len": len_, "prefill_gap": gap(a[0], b[0]),
             "decode_gap": gap(a[1], b[1]),
             "same_first_token": bool(a[0].argmax() == b[0].argmax())}
            for len_, a, b in zip(lens, got, want)]
    say(phase, compared="first- and second-token logits, max|a-b|/max|b|",
        tolerance=LOGIT_TOL, per_prompt=gaps, **fields)
    worst = max(max(g["prefill_gap"], g["decode_gap"]) for g in gaps)
    if worst > LOGIT_TOL:
        raise AssertionError(f"[{phase}] logits disagree by {worst} "
                             f"(> {LOGIT_TOL}): {gaps}")
    return worst


def _agreement(a: List[List[int]], b: List[List[int]]) -> List[int]:
    """Per request: length of the common prefix of two token streams."""
    out = []
    for x, y in zip(a, b):
        n = 0
        while n < len(x) and n < len(y) and x[n] == y[n]:
            n += 1
        out.append(n)
    return out


def serve_phase(size: Size) -> dict:
    """build_engine -> ServeLoop -> submit -> run_until_idle, with the
    ServingConfig defaults and with multi_step=8; the kernel path checked
    against attn_impl="jnp" on the same weights."""
    from deepspeed_tpu import ServingConfig
    eng = _engine(size)
    vocab = eng.cfg.vocab_size
    prompts = _prompts(size, vocab)
    say("serve", prompts=list(size.prompts), new_tokens=size.new_tokens,
        arena_blocks=size.num_blocks, hbm_after_setup=hbm())
    seen: Dict[str, object] = {}
    with spied_programs(eng, seen):
        default = _serve(eng, ServingConfig(), prompts, size.new_tokens)
        multi = _serve(eng, ServingConfig(multi_step=8), prompts,
                       size.new_tokens)
    kernels = {n: count_kernels(c.as_text()) for n, c in seen.items()}
    temps = {n: c.memory_analysis().temp_size_in_bytes
             for n, c in seen.items()}
    say("serve", default_wall_s=default["wall_s"],
        multi_step8_wall_s=multi["wall_s"], audit=multi["audit"],
        kernels_in_program=kernels, program_temp_bytes=temps,
        multi_step_agrees_for=_agreement(multi["tokens"],
                                         default["tokens"]),
        hbm=hbm())
    # prefill_full is reported, not required: at head_dim 64 its flash
    # gate (ops.attention shapes_ok) opens from S=1024, past the 512-token
    # step budget that bounds the prompts it takes
    for name in ("prefill_chunks", "decode_step", "decode_multi_step"):
        if not kernels.get(name):
            raise AssertionError(f"no tpu_custom_call in the compiled "
                                 f"{name} program: {kernels}")
    # dense reference on the same weights, short prompts only (the gather
    # path materialises [C, max_kv] scores)
    ref = _engine(size, params=eng.params, attn_impl="jnp")
    probes = _probe_prompts(size, vocab)
    worst = _check_logits("serve", _probe_logits(eng, probes),
                          _probe_logits(ref, probes), lens=size.short)
    short = [i for i, n in enumerate(size.prompts) if n <= 128]
    dense = _serve(ref, ServingConfig(), [prompts[i] for i in short],
                   size.new_tokens)
    say("serve", greedy_tokens_agree_with_dense_for=_agreement(
        [default["tokens"][i] for i in short], dense["tokens"]),
        of=size.new_tokens, short_prompts=len(short))
    free(eng.params, eng.arena, ref.arena)
    return {"worst_gap": worst, "kernels": kernels,
            "tokens": default["tokens"]}


def serve_tp_phase(size: Size, tp: int = 4) -> dict:
    """tp=1 first (freed before the sharded engines are built), then
    tensor_parallel_size=tp under the default tp_collectives and under
    "fused" (same weights), each against the tp=1 logits."""
    from deepspeed_tpu import ServingConfig
    one = _engine(size)                   # weights from the engine's seed
    vocab = one.cfg.vocab_size
    prompts = _prompts(size, vocab)
    base = _serve(one, ServingConfig(), prompts, size.new_tokens)
    probes = _probe_prompts(size, vocab)
    base_logits = _probe_logits(one, probes)
    free(one.params, one.arena)

    out = {}
    params = None                         # the engine's own seeded init
    for mode in ("xla", "fused"):
        scfg = ServingConfig(tensor_parallel_size=tp, tp_collectives=mode)
        eng = _engine(size, params=params, serving_config=scfg)
        per_device = _per_device_in_use(tp)
        run = _serve(eng, scfg, prompts, size.new_tokens)
        out[mode] = _check_logits(
            "serve4", _probe_logits(eng, probes), base_logits,
            lens=size.short, tp=tp, tp_collectives=mode,
            wall_s=run["wall_s"], audit=run["audit"],
            bytes_in_use_after_setup=per_device,
            greedy_tokens_agree_with_tp1_for=_agreement(
                run["tokens"], base["tokens"]), of=size.new_tokens)
        _check_share("serve4", per_device)
        params = eng.params               # same weights, already sharded
        free(eng.arena)
    free(params)
    return out


# ----------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the ZeRO-3 fsdp=4 and tp=4 phases "
                         "(and what they are compared with)")
    args = ap.parse_args(argv)

    import jax
    from deepspeed_tpu.utils.device import (CompileCounter,
                                            place_compile_cache)
    from deepspeed_tpu.utils.tpu_claim import require_tpu
    require_tpu()
    devices = jax.devices()
    if len(devices) != args.chips:
        raise RuntimeError(f"--chips {args.chips} but JAX reports "
                           f"{len(devices)} device(s)")
    cache_dir = place_compile_cache()
    counter = CompileCounter()
    t0 = time.perf_counter()
    say("start", device_kind=devices[0].device_kind, count=len(devices),
        jax=jax.__version__, compile_cache=cache_dir, hbm=hbm())
    phases = ((train_phase, serve_phase) if args.chips == 1
              else (train_sharded_phase, serve_tp_phase))
    for phase in phases:
        t1 = time.perf_counter()
        phase(FULL)
        say(phase.__name__, seconds=round(time.perf_counter() - t1, 1),
            programs=counter.snapshot(), hbm=hbm())
    say("done", seconds=round(time.perf_counter() - t0, 1),
        programs=counter.snapshot())
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
