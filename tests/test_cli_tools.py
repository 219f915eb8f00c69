"""Tests: CLI tool surfaces — ds_bench / ds_nvme_tune / ds_io / ds_report /
ds_elastic analogs (reference: bin/* entry points, tests/unit/launcher/)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

BIN = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "bin")


def test_comms_bench_sweep(devices8):
    from deepspeed_tpu.benchmarks.comms_bench import run_sweep
    rows = run_sweep(ops=["all_reduce", "all_gather", "reduce_scatter",
                          "all_to_all", "broadcast"],
                     min_bytes=1 << 14, max_bytes=1 << 14, trials=1,
                     warmups=1)
    assert len(rows) == 5
    for r in rows:
        assert r["world"] == 8
        assert r["algbw_GBps"] > 0
        if r["op"] == "all_reduce":
            assert r["busbw_GBps"] == pytest.approx(
                r["algbw_GBps"] * 2 * 7 / 8)


def test_nvme_sweep(tmp_path):
    from deepspeed_tpu.nvme.tune import sweep, run_io_bench
    out = sweep(str(tmp_path), total_mb=2, block_kbs=[256], inflights=[2, 4])
    assert len(out["results"]) == 2
    assert out["best_read"]["read_GBps"] > 0
    assert out["aio_config"]["block_size"] == 256 << 10
    one = run_io_bench(str(tmp_path / "x.bin"), total_mb=1, block_kb=128,
                       inflight=2)
    assert one["write_GBps"] > 0 and one["read_GBps"] > 0


def test_env_report_contains_ops():
    from deepspeed_tpu.env_report import report
    txt = report()
    assert "deepspeed_tpu version" in txt
    assert "flash_attention" in txt


def test_elastic_cli_script(tmp_path):
    cfg = {"elasticity": {"enabled": True, "max_train_batch_size": 64,
                          "micro_batch_sizes": [2, 4], "min_gpus": 1,
                          "max_gpus": 8, "version": 0.1}}
    p = tmp_path / "cfg.json"
    p.write_text(json.dumps(cfg))
    out = subprocess.run(
        [sys.executable, os.path.join(BIN, "dstpu_elastic"), "-c", str(p),
         "-w", "4"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert 4 in res["compatible_world_sizes"]
    assert res["global_batch"] % 4 == 0
    assert res["micro_batch"] in (2, 4)


def test_bin_scripts_exist_and_executable():
    for name in ("dstpu", "dstpu_report", "dstpu_bench", "dstpu_nvme_tune",
                 "dstpu_io", "dstpu_elastic", "dstpu_ssh", "dstpu_lint"):
        path = os.path.join(BIN, name)
        assert os.path.exists(path), name
        assert os.access(path, os.X_OK), name


def test_dstpu_ssh_fanout(tmp_path):
    """dstpu_ssh (reference: bin/ds_ssh): runs the command once per hostfile
    host with host-prefixed output; local fallback without a hostfile."""
    hf = tmp_path / "hostfile"
    hf.write_text("hostA slots=4\nhostB slots=4\nhostC slots=4\n")
    out = subprocess.run(
        [sys.executable, os.path.join(BIN, "dstpu_ssh"), "-f", str(hf),
         "--exclude", "hostC", "--ssh", "echo", "--", "hostname"],
        capture_output=True, text=True)
    assert out.returncode == 0
    lines = sorted(out.stdout.splitlines())
    assert lines == ["hostA: hostA hostname", "hostB: hostB hostname"]
    # no hostfile -> run locally
    out = subprocess.run(
        [sys.executable, os.path.join(BIN, "dstpu_ssh"), "-f",
         str(tmp_path / "missing"), "--", "echo", "local-ok"],
        capture_output=True, text=True)
    assert out.returncode == 0 and "local-ok" in out.stdout


def _console_scripts():
    import tomllib
    with open(os.path.join(os.path.dirname(BIN), "pyproject.toml"), "rb") as f:
        scripts = tomllib.load(f)["project"]["scripts"]
    return [pytest.param(target, id=name)
            for name, target in sorted(scripts.items())]


@pytest.mark.parametrize("target", _console_scripts())
def test_console_scripts_resolve(target):
    """Every `[project.scripts]` entry of pyproject.toml names a module
    that imports and an attribute that can be called: what `pip install`
    would wire the script to exists."""
    import importlib
    module, _, attr = target.partition(":")
    assert callable(getattr(importlib.import_module(module), attr)), target
