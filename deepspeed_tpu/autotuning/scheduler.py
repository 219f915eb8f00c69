"""Launcher-driven experiment scheduling for the autotuner.

Reference: `deepspeed/autotuning/scheduler.py:32` `ResourceManager` — every
experiment runs as its own launched job, so a failing config (OOM, invalid
topology) cannot take down the tuner, and resources are handed back between
trials.

On TPU this isolation is not optional: the device grant is per-process and
an HBM OOM kills the process, so an in-process tuner can only ever observe
the first OOM.  Fresh-process trials are also the methodology the perf
sweeps on this repo's own benches use (one config per process, one JSON
line per run).  The child entry (`python -m
deepspeed_tpu.autotuning.scheduler`) rebuilds the model from a registry
spec — or the caller supplies a training script that accepts
``--deepspeed_config`` and prints a JSON result line, the reference's
user-script contract.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

__all__ = ["ModelSpec", "ResourceManager"]


@dataclass
class ModelSpec:
    """Registry recipe the child process rebuilds the model from.

    steps/warmup None means "inherit the Autotuner's steps_per_trial /
    warmup_steps"; setting them here overrides per-spec."""
    family: str
    size: Optional[str] = None
    kw: Dict[str, Any] = field(default_factory=dict)
    seq_len: int = 128
    steps: Optional[int] = None
    warmup: Optional[int] = None

    def as_dict(self):
        return {"family": self.family, "size": self.size, "kw": self.kw,
                "seq_len": self.seq_len, "steps": self.steps,
                "warmup": self.warmup}


class ResourceManager:
    """Run tuning experiments in fresh subprocesses.

    Either `model_spec` (built-in probe: engine over a registry model with
    a random batch) or `train_script` (invoked with --deepspeed_config
    <path>; must print a JSON line containing "time_per_step" and
    optionally "samples_per_s") must be provided per run.
    """

    def __init__(self, timeout_s: float = 900.0,
                 env: Optional[Dict[str, str]] = None):
        self.timeout_s = timeout_s
        self.env = env

    def run(self, config: Dict, model_spec: Optional[ModelSpec] = None,
            train_script: Optional[str] = None,
            mem_budget_bytes: Optional[int] = None) -> Dict[str, Any]:
        """Returns {"time_per_step", "samples_per_s"} or {"error": ...}.
        `mem_budget_bytes`: the built-in probe child refuses (error
        "pruned: ...") a config whose estimated model states exceed it,
        before it builds an engine."""
        if (model_spec is None) == (train_script is None):
            raise ValueError("provide exactly one of model_spec / "
                             "train_script")
        env = dict(os.environ)
        if self.env:
            env.update(self.env)
        with tempfile.TemporaryDirectory(prefix="dstpu_tune_") as td:
            cfg_path = os.path.join(td, "ds_config.json")
            with open(cfg_path, "w") as f:
                json.dump(config, f)
            if train_script is not None:
                cmd = [sys.executable, "-u", train_script,
                       "--deepspeed_config", cfg_path]
            else:
                spec_path = os.path.join(td, "model_spec.json")
                with open(spec_path, "w") as f:
                    json.dump(dict(model_spec.as_dict(),
                                   mem_budget_bytes=mem_budget_bytes), f)
                cmd = [sys.executable, "-u", "-m",
                       "deepspeed_tpu.autotuning.scheduler",
                       "--config", cfg_path, "--model-spec", spec_path]
            try:
                proc = subprocess.run(cmd, env=env, capture_output=True,
                                      text=True, timeout=self.timeout_s)
            except subprocess.TimeoutExpired:
                return {"error": f"trial timed out after {self.timeout_s}s"}
        for line in reversed(proc.stdout.strip().splitlines()):
            try:
                out = json.loads(line)
            except json.JSONDecodeError:
                continue
            if "time_per_step" in out or "error" in out:
                return out
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()[-3:]
        return {"error": f"trial exited rc={proc.returncode} without a "
                         f"JSON result line; tail: {' | '.join(tail)}"}


def _child_main(argv: Optional[List[str]] = None) -> int:
    """Child entry: build the spec'd model + engine, time a few steps,
    print ONE JSON line.  OOM/invalid configs become an error line (rc 0 —
    a failed trial is a RESULT, not a scheduler failure)."""
    import argparse

    p = argparse.ArgumentParser("deepspeed_tpu.autotuning.scheduler")
    p.add_argument("--config", required=True)
    p.add_argument("--model-spec", required=True)
    args = p.parse_args(argv)
    with open(args.config) as f:
        config = json.load(f)
    with open(args.model_spec) as f:
        spec = json.load(f)
    try:
        import jax
        import numpy as np
        import deepspeed_tpu as dstpu
        from ..models import Transformer, get_model_config
        from ..utils.device import place_compile_cache
        from .autotuner import estimate_model_states_mem

        place_compile_cache()
        cfg = get_model_config(spec["family"], spec["size"], **spec["kw"]) \
            if spec.get("size") else get_model_config(spec["family"],
                                                      **spec["kw"])
        model = Transformer(cfg)
        budget = spec.get("mem_budget_bytes")
        if budget is not None:
            # memory-arithmetic pruning, here because this process may
            # touch the device and the tuner parent may not
            shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
            need = estimate_model_states_mem(
                sum(int(x.size) for x in jax.tree.leaves(shapes)),
                config.get("zero_optimization", {}).get("stage", 0),
                jax.device_count())
            if need > budget:
                print(json.dumps({"error": (
                    f"pruned: est model states {need/1e9:.2f} GB > "
                    f"budget {budget/1e9:.2f} GB")}))
                return 0
        engine = dstpu.initialize(model=model, config=config)
        S = spec["seq_len"]
        rng = np.random.RandomState(0)
        batch = {"input_ids": rng.randint(
            0, cfg.vocab_size,
            (engine.config.train_batch_size, S)).astype(np.int32)}
        for _ in range(spec["warmup"] if spec["warmup"] is not None else 2):
            float(engine.train_batch(batch)["loss"])
        steps = spec["steps"] if spec["steps"] is not None else 5
        t0 = time.perf_counter()
        for _ in range(steps):
            m = engine.train_batch(batch)
        float(m["loss"])
        dt = (time.perf_counter() - t0) / steps
        print(json.dumps({
            "time_per_step": dt,
            "samples_per_s": engine.config.train_batch_size / dt}))
    except Exception as e:  # OOM (RESOURCE_EXHAUSTED), bad config, ...
        print(json.dumps({"error": f"{type(e).__name__}: {e}"}))
    return 0


if __name__ == "__main__":
    sys.exit(_child_main())
