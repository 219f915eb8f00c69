"""The only place the benchmark touches the program: the entry points a user
calls, built from a configuration file's `program` section.

Serving: `inference.v2.build_engine` -> `serving.ServeLoop`.
Training: `deepspeed_tpu.initialize` -> `engine.train_batch`.
Weights come from the configuration's reference module (`make_params`:
seeded, on the device, in the stored type), never from the program's own
initialiser.
"""
from __future__ import annotations

import gc
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def stored_dtype(config: dict):
    return DTYPES[config["program"]["dtype"]]


def free(*trees) -> None:
    """Delete device arrays that are done with, so what follows fits."""
    for leaf in jax.tree.leaves(trees):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()
    gc.collect()


def build_serving(config: dict, seed: int, reference):
    """(engine, ServeLoop on the benchmark's clock); `reference` is the
    configuration's reference module, which makes the weights."""
    from deepspeed_tpu import ServingConfig
    from deepspeed_tpu.inference.v2 import (RaggedInferenceEngineConfig,
                                            build_engine)
    from deepspeed_tpu.serving import ServeLoop
    prog = config["program"]
    dtype = stored_dtype(config)
    params = reference.make_params(seed, reference.sizes(config), dtype)
    engine = build_engine(
        prog["arch"], prog["size"], params=params,
        engine_config=RaggedInferenceEngineConfig(**prog["engine"]),
        dtype=dtype, **prog["overrides"])
    loop = ServeLoop(engine, ServingConfig.from_dict(prog["serving"])
                     if prog["serving"] else ServingConfig(),
                     clock=time.perf_counter)
    return engine, loop


def _pow2_at_least(n: int, floor: int = 1) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


def warm_serving(engine, prompt_range, vocab: int) -> List[str]:
    """Run once every program shape this prompt range can reach, through
    the engine's own put/flush (the shapes follow `engine_v2.step`'s
    bucketing: a fresh batch is one power-of-two length bucket S >= 128 and
    a power-of-two row count NS under the step's token budget; what it
    leaves, and everything while a prompt is mid-prefill, goes through
    power-of-two chunk-slot counts NC; the decode program has one shape).  Returns the shapes warmed."""
    lo, hi = prompt_range
    budget = engine.config.max_prefill_tokens_per_step
    rng = np.random.RandomState(0)
    uid = [10_000_000]

    def run(lengths) -> None:
        uids = list(range(uid[0], uid[0] + len(lengths)))
        uid[0] += len(lengths)
        prompts = [rng.randint(0, vocab, n).astype(np.int32)
                   for n in lengths]
        got = engine.put(uids, prompts) if uids else engine.step()
        while not all(u in got for u in uids):   # a partial chunk finishes
            got.update(engine.step())
        for u in uids:
            engine.flush(u)

    warmed = []
    buckets = sorted({_pow2_at_least(n, 128) for n in (lo, hi)}
                     | {b for b in (128, 256, 512, 1024, 2048)
                        if lo < b < hi})
    shortest = {}
    for S in buckets:
        n_min = max(lo, S // 2 + 1 if S > 128 else lo)
        shortest[S] = n_min
        max_rows = min(engine.config.max_seqs, budget // n_min)
        NS = 1
        while min(NS, max_rows) > NS // 2:
            run([n_min] * min(NS, max_rows))
            warmed.append(f"prefill_full[{NS},{S}]")
            NS *= 2
    # chunk slots: a prompt a little over the budget is chunked and left
    # with a short tail; while it is mid-prefill the fresh batch is
    # suspended, so the next step chunks the tail and every new arrival
    tail = 8
    most = min(1 + -(-(budget - tail) // lo),  # tail, whole prompts, a partial
               engine.config.max_seqs)
    NC = 1
    while min(NC, most) > NC // 2:
        u = uid[0]
        uid[0] += 1
        engine.put([u], [rng.randint(0, vocab, budget + tail)
                         .astype(np.int32)])
        run([lo] * (min(NC, most) - 1))
        while engine.query(u) is None:
            engine.step()
        engine.flush(u)
        warmed.append(f"prefill_chunks[{NC}]")
        NC *= 2
    # decode: one shape
    u = uid[0]
    uid[0] += 1
    logits = engine.put([u], [rng.randint(0, vocab, lo).astype(np.int32)])
    engine.put([u], [np.array([int(np.argmax(logits[u]))], np.int32)])
    engine.flush(u)
    warmed.append("decode_step")
    return warmed


def build_training(config: dict, seed: int, devices, reference):
    """The TrainEngine over `devices`, holding weights made from the seed
    by `reference`, the configuration's reference module."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import Transformer, get_model_config
    from deepspeed_tpu.parallel.mesh import make_mesh
    prog = config["program"]
    dtype = stored_dtype(config)
    model = Transformer(get_model_config(
        prog["arch"], prog["size"], dtype=dtype, **prog["overrides"]))
    params = reference.make_params(seed, reference.sizes(config), dtype)
    engine = ds.initialize(model=model, params=params,
                           config=dict(prog["training"]),
                           topology=make_mesh(devices=list(devices)))
    free(params)
    return engine
