"""Tests: elastic batch math (reference: tests/unit/elasticity/) and the
in-process autotuner."""
import json
import subprocess
import sys
import os

import numpy as np
import pytest

from deepspeed_tpu.elasticity import (
    ElasticityConfig, ElasticityError, ElasticityIncompatibleWorldSize,
    compute_elastic_config, elasticity_enabled,
    ensure_immutable_elastic_config)
from deepspeed_tpu.elasticity.elasticity import ELASTICITY_ENV


BASE = {"elasticity": {"enabled": True,
                       "max_train_batch_size": 2000,
                       "micro_batch_sizes": [2, 4, 6],
                       "min_gpus": 1, "max_gpus": 10000,
                       "version": 0.1}}


pytestmark = pytest.mark.slow


class TestElasticity:
    def test_basic_v01(self):
        batch, valid = compute_elastic_config(BASE)
        assert batch <= 2000
        # every valid world size divides batch/micro for some micro
        for w in valid:
            assert any(batch % (m * w) == 0
                       for m in [2, 4, 6]), (batch, w)
        # the canonical result from the reference's own unit test:
        # max 2000 with micros [2,4,6] → batch 1680 (HCN-scaled LCM 12)
        assert batch == 1680
        assert 1 in valid and 840 in valid

    def test_deterministic(self):
        a = compute_elastic_config(BASE)
        b = compute_elastic_config(BASE)
        assert a == b

    def test_world_size_check(self):
        batch, valid, micro = compute_elastic_config(
            BASE, world_size=valid_world(BASE), return_microbatch=True)
        assert micro in [2, 4, 6]
        with pytest.raises(ElasticityIncompatibleWorldSize):
            compute_elastic_config(BASE, world_size=valid_world(BASE) + 10**6)

    def test_v02_host_granularity(self):
        cfg = {"elasticity": {**BASE["elasticity"], "version": 0.2}}
        batch, valid, micro = compute_elastic_config(
            cfg, world_size=8, return_microbatch=True,
            chips_per_host=4, model_parallel_size=2)
        # dp worlds are multiples of chips_per_host/tp = 2
        assert all(v % 2 == 0 for v in valid)
        assert batch > 0 and micro in [2, 4, 6]

    def test_v02_tp_divisibility_error(self):
        cfg = {"elasticity": {**BASE["elasticity"], "version": 0.2}}
        with pytest.raises(ElasticityError):
            compute_elastic_config(cfg, world_size=9, chips_per_host=3,
                                   model_parallel_size=2)

    def test_micro_batch_validation(self):
        bad = {"elasticity": {"enabled": True, "max_train_batch_size": 4,
                              "micro_batch_sizes": [8]}}
        with pytest.raises(ElasticityError):
            compute_elastic_config(bad)

    def test_enabled_flag(self):
        assert elasticity_enabled(BASE)
        assert not elasticity_enabled({})

    def test_immutable_config_guard(self, monkeypatch):
        monkeypatch.setenv(ELASTICITY_ENV, json.dumps(BASE["elasticity"]))
        ensure_immutable_elastic_config(BASE["elasticity"])  # same → ok
        drifted = {**BASE["elasticity"], "max_train_batch_size": 999}
        with pytest.raises(ElasticityError):
            ensure_immutable_elastic_config(drifted)


def valid_world(cfg) -> int:
    _, valid = compute_elastic_config(cfg)
    return valid[len(valid) // 2]


class TestAutotuner:
    def test_tune_picks_runnable_config(self, tmp_path):
        from deepspeed_tpu.autotuning import Autotuner
        from deepspeed_tpu.models import Transformer, llama_config

        cfg = llama_config("tiny", max_seq_len=32)
        model = Transformer(cfg)

        def batch_fn(trial_cfg):
            rng = np.random.RandomState(0)
            return {"input_ids": rng.randint(
                0, cfg.vocab_size,
                (trial_cfg.train_batch_size, 33)).astype(np.int32)}

        tuner = Autotuner(
            model=model,
            base_config={"optimizer": {"type": "adamw",
                                       "params": {"lr": 1e-3}},
                         "bf16": {"enabled": True}},
            tuning_space={"zero_optimization.stage": [0, 2],
                          "train_micro_batch_size_per_gpu": [1, 2]},
            batch_fn=batch_fn, steps_per_trial=2, warmup_steps=1,
            results_dir=str(tmp_path))
        result = tuner.tune()
        assert result["metric_val"] > 0
        assert result["best_overrides"]["zero_optimization.stage"] in (0, 2)
        assert len(result["experiments"]) == 4
        assert os.path.exists(os.path.join(str(tmp_path),
                                           "autotuning_results.json"))

    def test_memory_pruning(self):
        from deepspeed_tpu.autotuning import (Autotuner,
                                              estimate_model_states_mem)
        # stage 3 shards everything; stage 0 replicates
        full = estimate_model_states_mem(10**9, 0, 8)
        sharded = estimate_model_states_mem(10**9, 3, 8)
        assert sharded < full / 4

        from deepspeed_tpu.models import Transformer, llama_config
        model = Transformer(llama_config("tiny", max_seq_len=32))
        tuner = Autotuner(model=model, base_config={},
                          tuning_space={"zero_optimization.stage": [0]},
                          batch_fn=lambda c: {},
                          mem_budget_bytes=1)  # nothing fits
        with pytest.raises(RuntimeError, match="no successful trials"):
            tuner.tune()
        assert tuner.experiments[0].pruned


def test_autotuner_process_isolation():
    """Fresh-subprocess trials via the ResourceManager (reference:
    autotuning/scheduler.py:32): an OOM/invalid config is a failed RESULT,
    not a tuner crash, and surviving configs report timings."""
    from deepspeed_tpu.autotuning import Autotuner
    from deepspeed_tpu.autotuning.scheduler import ModelSpec
    tuner = Autotuner(
        base_config={"optimizer": {"type": "adamw",
                                   "params": {"lr": 1e-3}},
                     "zero_optimization": {"stage": 1}},
        tuning_space={"train_micro_batch_size_per_gpu": [1, 2]},
        isolation="process",
        model_spec=ModelSpec(family="gpt2", size="tiny", seq_len=32,
                             steps=2, warmup=1),
        trial_env={"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""},
        trial_timeout_s=300)
    result = tuner.tune()
    assert result["best_overrides"]["train_micro_batch_size_per_gpu"] in (1, 2)
    ok = [e for e in result["experiments"] if e["metric_val"] is not None]
    assert len(ok) == 2


def test_scheduler_reports_bad_config_as_error():
    from deepspeed_tpu.autotuning.scheduler import ModelSpec, ResourceManager
    rm = ResourceManager(timeout_s=300,
                         env={"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""})
    out = rm.run({"optimizer": {"type": "not_an_optimizer"},
                  "train_micro_batch_size_per_gpu": 1},
                 model_spec=ModelSpec(family="gpt2", size="tiny",
                                      seq_len=16, steps=1, warmup=0))
    assert "error" in out and "not_an_optimizer" in out["error"]


def test_elastic_agent_restarts_and_recovers(tmp_path):
    """DSElasticAgent (reference: elastic_agent.py:32): a training process
    that dies mid-run is restarted with the recomputed elastic batch env;
    the 'checkpoint' (a progress file here) carries recovery across the
    restart, and the restart counter is visible to the script."""
    from deepspeed_tpu.elasticity import DSElasticAgent
    marker = tmp_path / "progress.txt"
    script = tmp_path / "train.py"
    script.write_text(
        "import os, sys\n"
        f"p = {str(marker)!r}\n"
        "restart = int(os.environ['DSTPU_ELASTIC_RESTART'])\n"
        "batch = os.environ['DSTPU_ELASTIC_BATCH']\n"
        "done = os.path.exists(p)\n"
        "with open(p, 'a') as f:\n"
        "    f.write(f'attempt restart={restart} batch={batch}\\n')\n"
        "if not done:\n"
        "    sys.exit(17)      # simulated chip failure on the cold start\n"
        "sys.exit(0)\n")
    agent = DSElasticAgent(
        [sys.executable, str(script)],
        elastic_config={"elasticity": {
            "enabled": True, "max_train_batch_size": 64,
            "micro_batch_sizes": [2, 4], "min_gpus": 1, "max_gpus": 32,
            "version": 0.1}},
        world_size_fn=lambda: 8, max_restarts=2, restart_delay_s=0.0)
    assert agent.run() == 0
    lines = marker.read_text().strip().splitlines()
    assert len(lines) == 2
    assert lines[0].startswith("attempt restart=0")
    assert lines[1].startswith("attempt restart=1")
    assert "batch=" in lines[0] and agent.attempts == [17, 0]


def test_elastic_agent_gives_up_after_max_restarts(tmp_path):
    from deepspeed_tpu.elasticity import DSElasticAgent
    script = tmp_path / "always_fail.py"
    script.write_text("import sys; sys.exit(3)\n")
    agent = DSElasticAgent([sys.executable, str(script)],
                           world_size_fn=lambda: 4, max_restarts=2,
                           restart_delay_s=0.0)
    assert agent.run() == 3
    assert agent.attempts == [3, 3, 3]


def test_elastic_agent_fast_first_failure_not_retried(tmp_path):
    from deepspeed_tpu.elasticity import DSElasticAgent
    script = tmp_path / "bad_config.py"
    script.write_text("import sys; sys.exit(2)\n")
    agent = DSElasticAgent([sys.executable, str(script)],
                           world_size_fn=lambda: 4, max_restarts=3,
                           restart_delay_s=0.0, min_uptime_s=60.0)
    assert agent.run() == 2
    assert agent.attempts == [2]        # no retries for a config error


def test_elastic_agent_incompatible_world_gives_up_cleanly(tmp_path):
    from deepspeed_tpu.elasticity import DSElasticAgent
    script = tmp_path / "dies.py"
    script.write_text("import sys; sys.exit(9)\n")
    worlds = iter([8, 5])               # restart sees 5 chips: incompatible
    agent = DSElasticAgent(
        [sys.executable, str(script)],
        elastic_config={"elasticity": {
            "enabled": True, "max_train_batch_size": 64,
            "micro_batch_sizes": [2, 4], "min_gpus": 1, "max_gpus": 32,
            "version": 0.1}},
        world_size_fn=lambda: next(worlds), max_restarts=3,
        restart_delay_s=0.0)
    rc = agent.run()
    assert rc == 9 and agent.attempts == [9]


# ----------------------------------------------------------------------
# pod-level elasticity
# ----------------------------------------------------------------------
class _FakeRunner:
    """Stands in for SSHRunner: scripted per-attempt outcomes."""

    def __init__(self, hosts, extra_env, outcomes, log):
        self.hosts = dict(hosts)
        self.extra_env = dict(extra_env)
        self._outcomes = outcomes
        self._log = log
        self.last_failed_hosts = []

    def launch(self, cmd):
        rc, failed = self._outcomes.pop(0)
        self.last_failed_hosts = [h for h in failed if h in self.hosts]
        self._log.append({"hosts": sorted(self.hosts),
                          "env": dict(self.extra_env), "rc": rc,
                          "failed": list(self.last_failed_hosts)})
        return rc


def _pod_agent(outcomes, log, hosts=None, **kw):
    from deepspeed_tpu.elasticity import PodElasticAgent
    hosts = hosts or {f"host{i}": 4 for i in range(4)}   # 16 chips
    return PodElasticAgent(
        ["python", "train.py"], hosts,
        elastic_config={"elasticity": {
            "enabled": True, "max_train_batch_size": 480,
            "micro_batch_sizes": [2, 4], "min_gpus": 1, "max_gpus": 64,
            "version": 0.1}},
        runner_factory=lambda h, env: _FakeRunner(h, env, outcomes, log),
        restart_delay_s=0.0, **kw)


def test_pod_agent_excludes_dead_host_and_recomputes_world():
    """host2 dies on attempt 0 -> the fan-out restarts over the three
    survivors with the elastic batch recomputed for 12 chips (reference:
    elastic_agent.py membership change -> new WORLD_SIZE restart)."""
    log = []
    agent = _pod_agent([(1, ["host2"]), (0, [])], log)
    assert agent.run() == 0
    assert log[0]["hosts"] == ["host0", "host1", "host2", "host3"]
    assert log[0]["env"]["DSTPU_ELASTIC_WORLD"] == "16"
    assert log[1]["hosts"] == ["host0", "host1", "host3"]   # host2 gone
    assert log[1]["env"]["DSTPU_ELASTIC_WORLD"] == "12"
    assert log[1]["env"]["DSTPU_ELASTIC_RESTART"] == "1"
    # recomputed batch is compatible with the 12-chip world
    assert int(log[1]["env"]["DSTPU_ELASTIC_BATCH"]) % 12 == 0


def test_pod_agent_health_probe_readmits_flapping_host():
    log = []
    agent = _pod_agent([(1, ["host1"]), (0, [])], log,
                       health_fn=lambda h: True)   # probe says healthy
    assert agent.run() == 0
    assert log[1]["hosts"] == ["host0", "host1", "host2", "host3"]
    assert log[1]["env"]["DSTPU_ELASTIC_WORLD"] == "16"


def test_pod_agent_gives_up_below_min_hosts():
    log = []
    agent = _pod_agent([(1, ["host0"]), (1, ["host1"]), (1, ["host2"])],
                       log, min_hosts=2, max_restarts=5)
    rc = agent.run()
    assert rc == 1
    # third attempt leaves one host < min_hosts=2: no fourth launch
    assert len(log) == 3


def test_pod_agent_exhausts_restarts():
    log = []
    agent = _pod_agent([(7, []), (7, []), (7, [])], log, max_restarts=2)
    assert agent.run() == 7
    assert len(log) == 3
    # no hosts failed -> membership never shrinks
    assert all(e["hosts"] == log[0]["hosts"] for e in log)


def test_ssh_runner_carries_extra_env():
    from deepspeed_tpu.launcher.multinode_runner import SSHRunner
    r = SSHRunner({"a": 4, "b": 4},
                  extra_env={"DSTPU_ELASTIC_WORLD": "8"})
    cmds = r.commands(["python", "t.py"])
    assert len(cmds) == 2
    for _host, argv in cmds:
        assert "DSTPU_ELASTIC_WORLD=8" in argv[-1]


def test_autotuner_process_parent_stays_off_the_device(tmp_path):
    """A chip belongs to one process: under isolation="process" the tuner
    parent must never initialise a JAX backend (parameter count, device
    count and rank lookups all used to) or its trial children cannot hold
    the chip.  Pruning happens in the child."""
    script = tmp_path / "tune.py"
    script.write_text(
        "from deepspeed_tpu.autotuning import Autotuner\n"
        "from deepspeed_tpu.autotuning.scheduler import ModelSpec\n"
        "t = Autotuner(base_config={'zero_optimization': {'stage': 1}},\n"
        "    tuning_space={'train_micro_batch_size_per_gpu': [1]},\n"
        "    isolation='process', mem_budget_bytes=1,\n"
        "    model_spec=ModelSpec(family='gpt2', size='tiny', seq_len=16,\n"
        "                         steps=1, warmup=0),\n"
        "    trial_env={'JAX_PLATFORMS': 'cpu', 'XLA_FLAGS': ''})\n"
        "try:\n"
        "    t.tune()\n"
        "except RuntimeError as e:\n"
        "    assert 'no successful trials' in str(e), e\n"
        "assert t.experiments[0].pruned, t.experiments[0].error\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "print('PARENT_OFF_DEVICE')\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, str(script)], cwd=root,
                       capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu",
                                PYTHONPATH=root))
    assert "PARENT_OFF_DEVICE" in r.stdout, r.stderr[-2000:]
