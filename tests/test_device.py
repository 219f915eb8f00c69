"""The one platform helper, the TPU guard and the compile cache's
placement (deepspeed_tpu/utils/device.py, utils/tpu_claim.py) —
and the kernel dispatch that hangs on them: no fallback anywhere."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu.utils.device as device_mod
from deepspeed_tpu.utils.tpu_claim import require_tpu

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_platform_answers_and_propagates(monkeypatch):
    assert device_mod.platform() == "cpu" and not device_mod.on_tpu()

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", no_backend)
    for probe in (device_mod.platform, device_mod.on_tpu):
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            probe()
    # the accelerator auto-detect asks the same helper: it does not decide
    # "cpu" because the device could not be reached
    from deepspeed_tpu.accelerator import real_accelerator
    monkeypatch.setattr(real_accelerator, "_accelerator", None)
    monkeypatch.delenv("DSTPU_ACCELERATOR", raising=False)
    monkeypatch.delenv("DS_ACCELERATOR", raising=False)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        real_accelerator.get_accelerator()


def test_require_tpu_raises_on_cpu_and_names_it(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")     # no loophole
    with pytest.raises(RuntimeError, match="found platform 'cpu'"):
        require_tpu()
    monkeypatch.setattr(device_mod, "platform", lambda: "tpu")
    require_tpu()


def test_auto_attention_reraises_a_kernel_error_on_tpu(monkeypatch):
    """impl="auto" on a TPU: a flash kernel that cannot compile is an
    error, not a reason to run the dense reference."""
    from deepspeed_tpu.ops import attention, flash_attention as flash_mod

    def refused(*a, **k):
        raise NotImplementedError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(flash_mod, "flash_attention", refused)
    q = jnp.zeros((1, 128, 2, 128), jnp.bfloat16)
    out = attention.causal_attention(q, q, q, impl="auto")   # cpu: dense
    assert out.shape == q.shape
    monkeypatch.setattr(device_mod, "platform", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="Mosaic failed"):
        attention.causal_attention(q, q, q, impl="auto")
    # the explicit dense route stays, and shape conditions still choose it
    assert attention.causal_attention(q, q, q, impl="jnp").shape == q.shape
    odd = jnp.zeros((1, 96, 2, 128), jnp.bfloat16)           # S % 128 != 0
    assert attention.causal_attention(odd, odd, odd).shape == odd.shape


_PROBE = (
    "import os, sys, jax\n"
    "from deepspeed_tpu.utils.device import place_compile_cache\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "used = place_compile_cache()\n"
    "print('USED', used)\n"
    "print('CONFIG_CHANGED', jax.config.jax_compilation_cache_dir != before)\n")


def _probe(cwd, **env):
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    r = subprocess.run([sys.executable, "-c", _PROBE], cwd=cwd, text=True,
                       capture_output=True, timeout=120,
                       env=dict(base, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
                                **env))
    assert r.returncode == 0, r.stderr[-2000:]
    return dict(line.split(" ", 1) for line in r.stdout.splitlines()
                if line.startswith(("USED", "CONFIG_CHANGED")))


def test_compile_cache_same_path_from_two_working_directories(tmp_path):
    a = _probe(str(tmp_path))
    b = _probe(ROOT)
    assert a["USED"] == b["USED"] == os.path.join(ROOT, ".cache", "xla")
    assert a["CONFIG_CHANGED"] == "True"


def test_compile_cache_leaves_jax_alone_when_the_variable_is_set(tmp_path):
    """JAX reads JAX_COMPILATION_CACHE_DIR itself: the function sets no
    directory in code and writes nothing under <checkout>/.cache/xla."""
    out = _probe(str(tmp_path), JAX_COMPILATION_CACHE_DIR=str(tmp_path / "x"))
    assert out["USED"] == str(tmp_path / "x")
    assert out["CONFIG_CHANGED"] == "False"
