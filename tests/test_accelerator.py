"""Tests: accelerator abstraction + comm discovery helpers (reference:
tests/unit/accelerator/ and comm env-discovery tests)."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.accelerator import (
    DeepSpeedAccelerator, TPU_Accelerator, CPU_Accelerator,
    get_accelerator, set_accelerator)
from deepspeed_tpu.comm.comm import initialize_mesh_device, mpi_discovery


@pytest.fixture(autouse=True)
def _reset_accel():
    import deepspeed_tpu.accelerator.real_accelerator as ra
    old = ra._accelerator
    ra._accelerator = None
    yield
    ra._accelerator = old


def test_autodetect_matches_backend():
    acc = get_accelerator()
    assert isinstance(acc, DeepSpeedAccelerator)
    assert acc._name == jax.devices()[0].platform
    # singleton
    assert get_accelerator() is acc


def test_env_override(monkeypatch):
    monkeypatch.setenv("DS_ACCELERATOR", "cpu")
    acc = get_accelerator()
    assert isinstance(acc, CPU_Accelerator)
    monkeypatch.setenv("DS_ACCELERATOR", "bogus")
    import deepspeed_tpu.accelerator.real_accelerator as ra
    ra._accelerator = None
    with pytest.raises(ValueError):
        get_accelerator()


def test_device_surface(devices8):
    acc = set_accelerator(CPU_Accelerator())
    assert acc.device_count() == 8
    assert acc.device_name(3) == "cpu:3"
    assert acc.is_available()
    assert acc.is_synchronized_device()   # XLA: no user streams
    assert acc.communication_backend_name() == "xla"
    # stream API degrades to no-ops, as the reference CPU accelerator does
    with acc.stream(acc.Stream()):
        pass
    acc.manual_seed(1234)
    assert acc.initial_seed() == 1234
    assert jnp.bfloat16 in acc.supported_dtypes()


def test_memory_stats_shape():
    acc = get_accelerator()
    stats = acc.memory_stats()
    assert isinstance(stats, dict)
    assert acc.memory_allocated() >= 0
    assert acc.total_memory() >= 0


def test_on_accelerator():
    acc = get_accelerator()
    assert acc.on_accelerator(jnp.ones(3))
    assert not acc.on_accelerator(np.ones(3))


def test_initialize_mesh_device(devices8):
    mesh = initialize_mesh_device((2, 4), ("dp", "sp"))
    assert mesh.shape == {"dp": 2, "sp": 4}
    with pytest.raises(ValueError):
        initialize_mesh_device((4, 4))


def test_mpi_discovery_env(monkeypatch):
    assert mpi_discovery() == {}   # no launcher env
    monkeypatch.setenv("OMPI_COMM_WORLD_RANK", "2")
    monkeypatch.setenv("OMPI_COMM_WORLD_SIZE", "4")
    monkeypatch.setenv("MASTER_ADDR", "10.0.0.1")
    got = mpi_discovery()
    assert got == {"coordinator_address": "10.0.0.1:29500",
                   "num_processes": 4, "process_id": 2}
    monkeypatch.delenv("MASTER_ADDR")
    with pytest.raises(RuntimeError):
        mpi_discovery()


def test_graph_capture_module():
    from deepspeed_tpu.model_implementations import GraphCaptureModule
    import jax.numpy as jnp
    calls = []

    def fn(params, x):
        calls.append(1)       # traced once per capture
        return params * x

    m = GraphCaptureModule(fn, params=jnp.float32(2.0))
    a = m(jnp.ones((4,)))
    b = m(jnp.ones((4,)))
    np.testing.assert_allclose(np.array(b), 2.0)
    assert m.capture_count == 1 and m.replay_count == 1
    assert len(calls) == 1    # replay did not retrace
    m(jnp.ones((8,)))         # new shape -> new capture
    assert m.capture_count == 2

    # Python-scalar args are weakly typed: value changes must NOT count as
    # new captures (jit compiles once per type)
    m2 = GraphCaptureModule(lambda p, x, t: x * t, params=jnp.float32(1.0))
    for t in (0.1, 0.2, 0.3):
        m2(jnp.ones((4,)), t)
    assert m2.capture_count == 1 and m2.replay_count == 2


class TestDiffusionWrappers:
    """DSUNet/DSVAE/DSClipEncoder (reference:
    model_implementations/diffusers/{unet,vae,clip_encoder}.py) exercised
    against a REAL tiny diffusion stack written in jax — the diffusers
    package is absent from this environment, so torch-diffusers weight
    conversion is explicitly out of scope;
    what the reference wrappers ADD — capture-once-per-shape, replay
    thereafter — is what these tests pin down."""

    def _tiny_unet(self):
        import numpy as np
        rng = np.random.RandomState(0)
        params = {
            "temb": jnp.asarray(rng.randn(1, 8) * 0.1, jnp.float32),
            "down": jnp.asarray(rng.randn(3 * 3 * 4 * 8) * 0.1,
                                jnp.float32).reshape(3, 3, 4, 8),
            "up": jnp.asarray(rng.randn(3 * 3 * 8 * 4) * 0.1,
                              jnp.float32).reshape(3, 3, 8, 4),
        }

        def apply(p, x, t):
            # [B, H, W, 4] latents + scalar timestep: conv down, timestep
            # bias, conv up — the structural skeleton of a UNet block
            temb = jnp.sin(t[:, None].astype(jnp.float32)) @ p["temb"]
            h = jax.lax.conv_general_dilated(
                x, p["down"], (2, 2), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            h = jax.nn.silu(h + temb[:, None, None, :])
            h = jax.image.resize(h, (x.shape[0], x.shape[1], x.shape[2],
                                     h.shape[-1]), "nearest")
            return jax.lax.conv_general_dilated(
                h, p["up"], (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))

        return apply, params

    def test_dsunet_capture_replay_semantics(self):
        from deepspeed_tpu.model_implementations import DSUNet
        apply, params = self._tiny_unet()
        unet = DSUNet(apply, params=params)
        x8 = jnp.ones((2, 8, 8, 4))
        t = jnp.asarray([3, 7], jnp.int32)
        y1 = unet(x8, t)
        y2 = unet(x8, t)                       # same shapes -> replay
        x16 = jnp.ones((2, 16, 16, 4))
        y3 = unet(x16, t)                      # new shape -> capture
        assert unet.capture_count == 2
        assert unet.replay_count == 1
        assert y1.shape == (2, 8, 8, 4) and y3.shape == (2, 16, 16, 4)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2))

    def test_dsvae_and_clip_roundtrip(self):
        from deepspeed_tpu.model_implementations import (DSClipEncoder,
                                                         DSVAE)
        import numpy as np
        rng = np.random.RandomState(1)
        w_enc = jnp.asarray(rng.randn(48, 8) * 0.1, jnp.float32)
        w_dec = jnp.asarray(rng.randn(8, 48) * 0.1, jnp.float32)

        def vae_apply(p, x, mode):
            flat = x.reshape(x.shape[0], -1)
            if mode == "encode":
                return flat @ p["enc"]
            return (flat[:, :8] @ p["dec"]).reshape(x.shape[0], 4, 4, 3)

        vae = DSVAE(vae_apply, params={"enc": w_enc, "dec": w_dec})
        x = jnp.ones((2, 4, 4, 3))
        z = vae(x, "encode")
        assert z.shape == (2, 8)
        y = vae(jnp.ones((2, 4, 4, 3)), "decode")
        assert y.shape == (2, 4, 4, 3)
        assert vae.capture_count == 2          # one per static mode

        emb = jnp.asarray(rng.randn(32, 16) * 0.1, jnp.float32)
        clip = DSClipEncoder(lambda p, ids: jnp.take(p, ids, axis=0).mean(1),
                             params=emb)
        e = clip(jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32))
        assert e.shape == (2, 16)
        clip(jnp.asarray([[7, 8, 9], [1, 1, 1]], jnp.int32))
        assert clip.replay_count == 1
