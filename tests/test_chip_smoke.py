"""chip_smoke.py's phases at TINY on the CPU mesh: the rehearsal the
on-chip-measurement guide asks for before chip time is spent, kept as a
test.  The test — not an option of the program — puts the Pallas kernels
in interpret mode and reports the platform as "tpu" so the kernel gates
open; interpret mode leaves no `tpu_custom_call` in a CPU program, so
the kernel count is steered here too."""
import dataclasses
import functools
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402


@pytest.fixture
def as_on_chip(monkeypatch):
    import jax.experimental.pallas as pl
    import deepspeed_tpu.utils.device as device_mod
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    monkeypatch.setattr(device_mod, "platform", lambda: "tpu")
    monkeypatch.setattr(chip_smoke, "count_kernels", lambda text: 1)


def test_train_phase_tiny(as_on_chip, tmp_path, capsys):
    out = chip_smoke.train_phase(chip_smoke.TINY, str(tmp_path / "ckpt"))
    assert len(out["losses"]) == chip_smoke.TRAIN_STEPS + 1
    assert out["losses"][-1] < out["losses"][0]
    assert not (tmp_path / "ckpt").exists()      # cleaned up
    assert "[train]" in capsys.readouterr().out


def test_serve_phase_tiny(as_on_chip):
    out = chip_smoke.serve_phase(chip_smoke.TINY)
    assert set(out["kernels"]) == {"prefill_full", "prefill_chunks",
                                   "decode_step", "decode_multi_step"}
    assert out["worst_gap"] <= chip_smoke.LOGIT_TOL
    assert all(len(t) == chip_smoke.TINY.new_tokens for t in out["tokens"])


def test_train_sharded_phase_tiny(as_on_chip, devices8):
    out = chip_smoke.train_sharded_phase(chip_smoke.TINY, n_dev=4)
    assert out["losses"][-1] < out["losses"][0]


def test_serve_tp_phase_tiny(devices8):
    # the mesh and sharding rehearsal; the per-shard kernel wiring has its
    # interpret-mode tests in test_tp_inference.py.  float32: XLA:CPU
    # aborts ("Invalid binary instruction opcode copy") compiling the
    # fused-TP programs in bf16; the chip's compiler does not
    size = dataclasses.replace(
        chip_smoke.TINY,
        model_kw=chip_smoke.TINY.model_kw + (("dtype", jnp.float32),))
    out = chip_smoke.serve_tp_phase(size, tp=2)
    assert set(out) == {"xla", "fused"}


def test_share_check_names_the_heavy_device():
    chip_smoke._check_share("x", [10, 10, 10, 10])
    chip_smoke._check_share("x", [0, 0, 0, 0])    # CPU: no statistics
    with pytest.raises(AssertionError, match="holds 40"):
        chip_smoke._check_share("x", [40, 10, 10, 10])


def test_main_refuses_the_cpu():
    """`JAX_PLATFORMS=cpu python chip_smoke.py` exits non-zero, names the
    platform it found and never prints the result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok": true' not in r.stdout
