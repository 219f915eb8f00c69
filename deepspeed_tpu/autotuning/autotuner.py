"""Autotuning: search ZeRO stage / micro-batch / config space.

Reference: `deepspeed/autotuning/` — `Autotuner` autotuner.py:42 builds a
tuning space (zero stage, micro batch, offload flags), prunes it with a
model-memory estimate from a profiling run (engine.py:2120-2137 model-info
hook), schedules short experiments through `ResourceManager` scheduler.py:32,
and ranks them by a metric (latency / throughput / FLOPS); tuners in
`tuner/{index_based,model_based}.py`.

Two execution modes:
- **in-process** (default): under JAX each trial is just a fresh jitted
  program — build an engine with the candidate config, time a few steps,
  catch XLA RESOURCE_EXHAUSTED as the OOM signal.  Fast (no interpreter
  restart), right for CPU-mesh searches and configs that fail softly.
- **process isolation** (`isolation="process"`, reference ResourceManager
  scheduler.py:32): each trial is a fresh subprocess via
  `autotuning/scheduler.py`.  Required on real TPU — the device grant is
  per-process and an HBM OOM kills the process, so an in-process tuner can
  only ever observe its first OOM.

Memory-based pruning uses the same model-states arithmetic
(params × bytes-per-element × optimizer multiplier ÷ shard factor).
"""
from __future__ import annotations

import itertools
import json
import os
import time
import traceback
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

from ..utils.logging import logger

__all__ = ["Autotuner", "Experiment", "estimate_model_states_mem"]

DEFAULT_TUNING_SPACE = {
    "zero_optimization.stage": [0, 1, 2, 3],
    "train_micro_batch_size_per_gpu": [1, 2, 4, 8, 16],
}

METRICS = ("throughput", "latency")


def estimate_model_states_mem(num_params: int, zero_stage: int,
                              dp_size: int, bytes_per_param: int = 2,
                              optimizer_mult: int = 12) -> int:
    """Bytes per chip for params+grads+optimizer states (the reference's
    ZeRO memory arithmetic used for pruning, autotuner.py `_get_*_mem`).
    optimizer_mult=12: fp32 master + 2 Adam moments, 4 bytes each."""
    param_b = num_params * bytes_per_param
    grad_b = num_params * 4  # fp32 grad accumulators
    opt_b = num_params * optimizer_mult
    if zero_stage >= 3:
        param_b //= dp_size
    if zero_stage >= 2:
        grad_b //= dp_size
    if zero_stage >= 1:
        opt_b //= dp_size
    return param_b + grad_b + opt_b


@dataclass
class Experiment:
    """One scheduled trial (reference: autotuning/scheduler.py experiments)."""
    exp_id: int
    overrides: Dict[str, Any]
    metric_val: Optional[float] = None
    time_per_step: Optional[float] = None
    error: Optional[str] = None
    pruned: bool = False

    def as_dict(self):
        return {"exp_id": self.exp_id, "overrides": self.overrides,
                "metric_val": self.metric_val,
                "time_per_step": self.time_per_step,
                "error": self.error, "pruned": self.pruned}


def _set_path(d: Dict, dotted: str, value):
    parts = dotted.split(".")
    cur = d
    for p in parts[:-1]:
        cur = cur.setdefault(p, {})
    cur[parts[-1]] = value


class Autotuner:
    """In-process config search.

    Args:
      model: a deepspeed_tpu.models model object (init_params/loss_fn), or
        pass loss_fn=, params= like `initialize`.
      base_config: the user's DeepSpeed-style JSON config; tuned knobs are
        overridden per trial.
      tuning_space: {dotted.config.key: [candidates]}; defaults to
        zero-stage × micro-batch like the reference's core space.
      batch_fn: candidate_config -> batch dict for `train_batch`; required
        to run trials (it must honor train_batch_size of the trial config).
    """

    def __init__(self, model=None, base_config: Optional[Dict] = None,
                 tuning_space: Optional[Dict[str, Sequence]] = None,
                 batch_fn: Optional[Callable[[Any], Dict]] = None,
                 loss_fn=None, params=None,
                 steps_per_trial: int = 5, warmup_steps: int = 2,
                 mem_budget_bytes: Optional[int] = None,
                 results_dir: Optional[str] = None,
                 tuner_type: str = "gridsearch",
                 max_trials: Optional[int] = None, seed: int = 0,
                 isolation: str = "in_process",
                 model_spec=None, train_script: Optional[str] = None,
                 trial_timeout_s: float = 900.0,
                 trial_env: Optional[Dict[str, str]] = None):
        self.model = model
        self.loss_fn = loss_fn
        self.params = params
        self.base_config = dict(base_config or {})
        self.tuning_space = dict(tuning_space or DEFAULT_TUNING_SPACE)
        self.batch_fn = batch_fn
        self.steps_per_trial = steps_per_trial
        self.warmup_steps = warmup_steps
        self.mem_budget_bytes = mem_budget_bytes
        self.results_dir = results_dir
        # search strategy (reference: autotuning/tuner/{index_based,
        # model_based}.py behind the `tuner_type` config knob)
        self.tuner_type = tuner_type
        self.max_trials = max_trials
        self.seed = seed
        if isolation not in ("in_process", "process"):
            raise ValueError(f"isolation must be in_process|process, "
                             f"got {isolation!r}")
        if isolation == "process" and (model_spec is None) == \
                (train_script is None):
            raise ValueError("isolation='process' needs exactly one of "
                             "model_spec= (autotuning.scheduler.ModelSpec) "
                             "or train_script=")
        self.isolation = isolation
        self.model_spec = model_spec
        self.train_script = train_script
        self.trial_timeout_s = trial_timeout_s
        self.trial_env = trial_env
        self.experiments: List[Experiment] = []

    # -- space construction (reference: _generate_experiments) -----------
    def _candidates(self) -> List[Dict[str, Any]]:
        keys = list(self.tuning_space.keys())
        out = []
        for combo in itertools.product(*(self.tuning_space[k] for k in keys)):
            out.append(dict(zip(keys, combo)))
        return out

    def _trial_config(self, overrides: Dict[str, Any]) -> Dict:
        cfg = json.loads(json.dumps(self.base_config))  # deep copy
        for k, v in overrides.items():
            _set_path(cfg, k, v)
        cfg["steps_per_print"] = 0
        return cfg

    def _num_params(self) -> Optional[int]:
        try:
            import jax
            src = self.params if self.params is not None else \
                (self.model.init_params if self.model is not None else None)
            if src is None and self.model_spec is not None:
                # process mode carries a registry spec, not a live model —
                # memory pruning must still work
                from ..models import Transformer, get_model_config
                sp = self.model_spec
                mc = (get_model_config(sp.family, sp.size, **sp.kw)
                      if sp.size else get_model_config(sp.family, **sp.kw))
                src = Transformer(mc).init_params
            if callable(src):
                shapes = jax.eval_shape(src, jax.random.PRNGKey(0))
                return sum(int(x.size) for x in jax.tree_util.tree_leaves(shapes))
            if src is not None:
                return sum(int(x.size) for x in jax.tree_util.tree_leaves(src))
        except Exception:
            return None
        return None

    def _prune(self, exp: Experiment) -> bool:
        """Memory-arithmetic pruning before paying a compile.  Under
        process isolation the trial child does it (scheduler._child_main):
        a chip belongs to one process, so this parent must never touch
        JAX — counting parameters or devices here would take the chip the
        children need."""
        if self.mem_budget_bytes is None or self.isolation == "process":
            return False
        n = self._num_params()
        if n is None:
            return False
        import jax
        stage = exp.overrides.get("zero_optimization.stage",
                                  self.base_config.get(
                                      "zero_optimization", {}).get("stage", 0))
        need = estimate_model_states_mem(n, stage, max(jax.device_count(), 1))
        if need > self.mem_budget_bytes:
            exp.pruned = True
            exp.error = (f"pruned: est model states {need/1e9:.2f} GB > "
                         f"budget {self.mem_budget_bytes/1e9:.2f} GB")
            return True
        return False

    # -- experiment execution --------------------------------------------
    def run_experiment(self, exp: Experiment) -> Experiment:
        if self.isolation == "process":
            return self._run_experiment_subprocess(exp)
        return self._run_experiment_inprocess(exp)

    def _run_experiment_subprocess(self, exp: Experiment) -> Experiment:
        """Fresh-process trial via the scheduler (reference:
        ResourceManager.run_job — OOM/crash cannot take down the tuner)."""
        import dataclasses

        from .scheduler import ResourceManager
        rm = ResourceManager(timeout_s=self.trial_timeout_s,
                             env=self.trial_env)
        spec = self.model_spec
        if spec is not None:
            # unset spec fields inherit the Autotuner's trial-length knobs;
            # explicitly-set ones win
            spec = dataclasses.replace(
                spec,
                steps=(spec.steps if spec.steps is not None
                       else self.steps_per_trial),
                warmup=(spec.warmup if spec.warmup is not None
                        else self.warmup_steps))
        out = rm.run(self._trial_config(exp.overrides),
                     model_spec=spec,
                     train_script=self.train_script,
                     mem_budget_bytes=self.mem_budget_bytes)
        if "error" in out:
            exp.error = out["error"]
            exp.pruned = exp.error.startswith("pruned:")
            logger.info(f"trial {exp.exp_id} failed: "
                        f"{exp.error.splitlines()[0]}")
        else:
            exp.time_per_step = float(out["time_per_step"])
            if "samples_per_s" in out:
                exp.metric_val = float(out["samples_per_s"])
            else:
                exp.metric_val = 1.0 / exp.time_per_step
        return exp

    def _run_experiment_inprocess(self, exp: Experiment) -> Experiment:
        import deepspeed_tpu as dstpu
        try:
            cfg = self._trial_config(exp.overrides)
            engine = dstpu.initialize(model=self.model, loss_fn=self.loss_fn,
                                      params=self.params, config=cfg)
            batch = self.batch_fn(engine.config)
            for _ in range(self.warmup_steps):
                float(engine.train_batch(batch)["loss"])
            t0 = time.perf_counter()
            for _ in range(self.steps_per_trial):
                m = engine.train_batch(batch)
            float(m["loss"])  # sync
            dt = (time.perf_counter() - t0) / self.steps_per_trial
            exp.time_per_step = dt
            exp.metric_val = engine.config.train_batch_size / dt  # samples/s
        except Exception as e:  # OOM (RESOURCE_EXHAUSTED) or invalid config
            exp.error = f"{type(e).__name__}: {e}"
            logger.info(f"trial {exp.exp_id} failed: {exp.error.splitlines()[0]}")
        return exp

    def tune(self, metric: str = "throughput") -> Dict:
        """Run the search; returns {"best_overrides", "best_config",
        "metric_val", "experiments"} and writes results json when
        `results_dir` is set (reference writes autotuning_results/)."""
        assert metric in METRICS, f"metric must be one of {METRICS}"
        if self.isolation == "in_process" and self.batch_fn is None:
            raise ValueError("Autotuner needs batch_fn to run in-process "
                             "trials (process isolation builds its own "
                             "batch from model_spec)")
        from .tuner import make_tuner
        candidates = self._candidates()
        strategy = make_tuner(self.tuner_type, candidates, seed=self.seed)
        history: List = []          # (candidate_idx, metric or None)
        trials = 0
        while self.max_trials is None or trials < self.max_trials:
            i = strategy.next(history)
            if i is None:
                break
            overrides = candidates[i]
            exp = Experiment(exp_id=i, overrides=overrides)
            self.experiments.append(exp)
            if not self._prune(exp):
                self.run_experiment(exp)
            if exp.pruned:
                history.append((i, None))
                continue
            trials += 1
            # feed the strategy the OBJECTIVE it should optimize — for
            # latency that is -time/step, not samples/s, else the surrogate
            # routes the trial budget toward throughput configs
            if exp.metric_val is None:
                obj = None
            elif metric == "latency":
                obj = -exp.time_per_step
            else:
                obj = exp.metric_val
            history.append((i, obj))
            if exp.metric_val is not None:
                # logger, not log_dist: the rank lookup initialises the
                # backend, and this process must stay off the chip
                logger.info(f"trial {i} {overrides}: "
                            f"{exp.metric_val:.1f} samples/s "
                            f"({exp.time_per_step*1e3:.0f} ms/step)")

        ok = [e for e in self.experiments if e.metric_val is not None]
        if not ok:
            raise RuntimeError(
                "no successful trials; errors: "
                + "; ".join(f"{e.overrides}: {e.error}" for e in self.experiments))
        key = ((lambda e: e.metric_val) if metric == "throughput"
               else (lambda e: -e.time_per_step))
        best = max(ok, key=key)
        result = {
            "best_overrides": best.overrides,
            "best_config": self._trial_config(best.overrides),
            "metric": metric,
            "metric_val": best.metric_val,
            "experiments": [e.as_dict() for e in self.experiments],
        }
        if self.results_dir:
            os.makedirs(self.results_dir, exist_ok=True)
            with open(os.path.join(self.results_dir,
                                   "autotuning_results.json"), "w") as f:
                json.dump(result, f, indent=2)
        return result
