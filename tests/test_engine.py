"""End-to-end engine tests over ZeRO stages on the 8-device CPU mesh.

Reference analogs: tests/unit/runtime/zero/test_zero.py (stage semantics),
tests/unit/runtime/half_precision (loss scaling), simple_model.py fixtures.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dstpu


def _toy_params(key, din=16, dh=32, dout=8):
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (din, dh)) * 0.1,
        "b1": jnp.zeros((dh,)),
        "w2": jax.random.normal(k2, (dh, dout)) * 0.1,
        "b2": jnp.zeros((dout,)),
    }


def _toy_loss(params, batch, rng=None):
    x, y = batch["x"], batch["y"]
    h = jnp.tanh(x @ params["w1"].astype(x.dtype) + params["b1"].astype(x.dtype))
    out = h @ params["w2"].astype(x.dtype) + params["b2"].astype(x.dtype)
    return jnp.mean((out.astype(jnp.float32) - y) ** 2)


def _make_batch(n=16, din=16, dout=8, seed=0):
    rng = np.random.RandomState(seed)
    return {"x": rng.randn(n, din).astype(np.float32),
            "y": rng.randn(n, dout).astype(np.float32)}


def _engine(stage=0, extra=None, dtype_block=None, gas=1, micro=2):
    cfg = {
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": stage},
        "steps_per_print": 0,
    }
    if dtype_block:
        cfg.update(dtype_block)
    if extra:
        cfg.update(extra)
    params = _toy_params(jax.random.PRNGKey(0))
    return dstpu.initialize(loss_fn=_toy_loss, params=params, config=cfg)


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_zero_stages_loss_decreases(devices8, stage):
    eng = _engine(stage=stage)
    batch = _make_batch(n=eng.config.train_batch_size)
    losses = [float(eng.train_batch(batch)["loss"]) for _ in range(10)]
    assert losses[-1] < losses[0] * 0.9, losses


@pytest.mark.parametrize("stage", [1, 2, 3])
def test_zero_stage_matches_ddp(devices8, stage):
    """All ZeRO stages must produce the SAME training trajectory as stage 0
    (reference contract: ZeRO is an exact-optimizer rearrangement)."""
    b = _make_batch(n=16)
    eng0 = _engine(stage=0)
    engN = _engine(stage=stage)
    for i in range(5):
        l0 = float(eng0.train_batch(b)["loss"])
        lN = float(engN.train_batch(b)["loss"])
        np.testing.assert_allclose(l0, lN, rtol=2e-5, atol=1e-6)
    # params match too
    p0 = jax.device_get(eng0.state.params)
    pN = jax.device_get(engN.state.params)
    for k in p0:
        np.testing.assert_allclose(np.asarray(p0[k]), np.asarray(pN[k]),
                                   rtol=1e-4, atol=1e-6)


def test_zero1_opt_state_is_sharded(devices8):
    eng = _engine(stage=1)
    m = eng.state.opt_state["m"]["w1"]
    assert not m.sharding.is_fully_replicated
    # params stay replicated at stage 1
    assert eng.state.params["w1"].sharding.is_fully_replicated


def test_zero3_params_sharded(devices8):
    eng = _engine(stage=3)
    assert not eng.state.params["w1"].sharding.is_fully_replicated


def test_gradient_accumulation_equivalence(devices8):
    """gas=4 with the same total batch must match gas=1 (reference:
    scale_wrt_gas semantics engine.py:2199)."""
    b = _make_batch(n=16)
    e1 = _engine(stage=0, gas=1, micro=2)      # tb = 16
    e4 = _engine(stage=0, gas=4, micro=2)      # tb = 64 -> use a 64 batch
    b4 = _make_batch(n=64)
    # same data repeated 4x so the average grad matches
    b4 = {k: np.concatenate([b[k]] * 4, axis=0) for k in b}
    l1 = float(e1.train_batch(b)["loss"])
    l4 = float(e4.train_batch(b4)["loss"])
    np.testing.assert_allclose(l1, l4, rtol=1e-5)
    p1 = jax.device_get(e1.state.params)
    p4 = jax.device_get(e4.state.params)
    for k in p1:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p4[k]),
                                   rtol=1e-4, atol=1e-6)


def test_bf16_optimizer_state_parity(devices8):
    """state_dtype=bf16 stores Adam moments in bfloat16 (half the state
    memory — the lever that lets selective remat fit next to Adam state on
    a 16 GB chip).  The update still computes in fp32; over a short run
    the loss trajectory must track the fp32-state run closely."""
    def run(state_dtype):
        eng = _engine(stage=0, extra={
            "optimizer": {"type": "adamw",
                          "params": ({"lr": 1e-2, "state_dtype": state_dtype}
                                     if state_dtype else {"lr": 1e-2})}})
        b = _make_batch()
        losses = [float(eng.train_batch(b)["loss"]) for _ in range(60)]
        return eng, losses

    e32, l32 = run(None)
    e16, l16 = run("bf16")
    for leaf in jax.tree.leaves(e16.state.opt_state):
        assert leaf.dtype == jnp.bfloat16
    for leaf in jax.tree.leaves(e32.state.opt_state):
        assert leaf.dtype == jnp.float32
    assert l16[-1] < l16[0] * 0.2            # it actually trains
    # trajectories track: same order of magnitude throughout, close at end
    np.testing.assert_allclose(l16[-1], l32[-1], rtol=0.15)
    assert abs(np.log10(max(l16[-1], 1e-9) / max(l32[-1], 1e-9))) < 0.5


def test_int8_optimizer_state_parity(devices8):
    """state_dtype=int8 stores Adam moments in 8 bits (quarter the fp32
    state memory — frees the HBM that lets the save_attn_proj_up remat
    policy fit the training bench): signed linear-absmax int8 for m,
    log-map uint8 for the heavy-tailed v (Dettmers' 8-bit-Adam recipe,
    arXiv:2110.02861).  The trajectory must track fp32 state."""
    def run(state_dtype):
        eng = _engine(stage=0, extra={
            "optimizer": {"type": "adamw",
                          "params": ({"lr": 1e-2, "state_dtype": state_dtype}
                                     if state_dtype else {"lr": 1e-2})}})
        b = _make_batch()
        losses = [float(eng.train_batch(b)["loss"]) for _ in range(60)]
        return eng, losses

    e32, l32 = run(None)
    e8, l8 = run("int8")
    st = e8.state.opt_state
    for leaf in jax.tree.leaves(st["m"]):
        assert leaf.dtype == jnp.int8
    for leaf in jax.tree.leaves(st["v"]):
        assert leaf.dtype == jnp.uint8
    for key in ("m_scale", "v_scale"):
        for leaf in jax.tree.leaves(st[key]):
            assert leaf.dtype == jnp.float32
    assert l8[-1] < l8[0] * 0.2              # it actually trains
    np.testing.assert_allclose(l8[-1], l32[-1], rtol=0.2)
    assert abs(np.log10(max(l8[-1], 1e-9) / max(l32[-1], 1e-9))) < 0.5


def test_int8f_optimizer_state_parity(devices8):
    """state_dtype=int8f: the single-pass codec (predicted scale bounds +
    sqrt-domain codes — no fp32 moment HBM round-trip, see optimizers.py
    _q8_sq_signed block).  Must track the fp32 trajectory like int8 does,
    and its scales must be valid UPPER BOUNDS of the row maxima."""
    def run(state_dtype):
        eng = _engine(stage=0, extra={
            "optimizer": {"type": "adamw",
                          "params": ({"lr": 1e-2, "state_dtype": state_dtype}
                                     if state_dtype else {"lr": 1e-2})}})
        b = _make_batch()
        losses = [float(eng.train_batch(b)["loss"]) for _ in range(60)]
        return eng, losses

    e32, l32 = run(None)
    e8, l8 = run("int8f")
    st = e8.state.opt_state
    for leaf in jax.tree.leaves(st["m"]):
        assert leaf.dtype == jnp.int8
    for leaf in jax.tree.leaves(st["v"]):
        assert leaf.dtype == jnp.uint8
    assert l8[-1] < l8[0] * 0.2              # it actually trains
    np.testing.assert_allclose(l8[-1], l32[-1], rtol=0.2)
    assert abs(np.log10(max(l8[-1], 1e-9) / max(l32[-1], 1e-9))) < 0.5
    # bound validity: decode(q) <= bound everywhere (q <= 127/255 by
    # construction) AND the fp32 reference moments are <= bound too
    m32, v32 = e32.state.opt_state["m"], e32.state.opt_state["v"]
    for k in m32:
        bound = np.asarray(st["m_scale"][k])
        ref = np.max(np.abs(np.asarray(m32[k])), axis=-1, keepdims=True)
        assert (bound >= ref * 0.5).all(), k  # same scale class
    # safe_get returns DEQUANTIZED floats close to the fp32 moments
    from deepspeed_tpu.utils.tensor_fragment import (
        safe_get_full_optimizer_state)
    got = safe_get_full_optimizer_state(e8, "w1", "exp_avg_sq")
    ref = np.asarray(v32["w1"])
    assert got.shape == ref.shape and got.dtype == np.float32
    np.testing.assert_allclose(got.mean(), ref.mean(), rtol=0.5)


def test_int8_state_sharded_zero2(devices8):
    """int8 moment payloads shard under ZeRO (param-shaped leaves reuse the
    opt specs); the tiny per-row scale trees are replicated.  Must compile
    and train on the 8-device mesh."""
    eng = _engine(stage=2, extra={
        "optimizer": {"type": "adamw",
                      "params": {"lr": 1e-2, "state_dtype": "int8"}}})
    batch = _make_batch(n=eng.config.train_batch_size)
    losses = [float(eng.train_batch(batch)["loss"]) for _ in range(10)]
    assert losses[-1] < losses[0] * 0.9, losses


def test_int8_v_moment_set_rejects_negative(devices8):
    """safe_set_full_optimizer_state on the log-quantized (non-negative)
    v moment must reject negative entries instead of silently encoding
    them as zero codes (the codebook has no sign)."""
    from deepspeed_tpu.utils.tensor_fragment import (
        safe_get_full_optimizer_state, safe_set_full_optimizer_state)
    eng = _engine(stage=0, extra={
        "optimizer": {"type": "adamw",
                      "params": {"lr": 1e-2, "state_dtype": "int8"}}})
    eng.train_batch(_make_batch(n=eng.config.train_batch_size))
    good = np.abs(np.random.RandomState(0).randn(16, 32)).astype(np.float32)
    safe_set_full_optimizer_state(eng, "w1", "exp_avg_sq", good)
    back = safe_get_full_optimizer_state(eng, "w1", "exp_avg_sq")
    np.testing.assert_allclose(back, good, rtol=0.2, atol=1e-7)
    with pytest.raises(ValueError, match="negative"):
        safe_set_full_optimizer_state(eng, "w1", "exp_avg_sq", -good)


def test_int8_state_rejects_lamb():
    from deepspeed_tpu.config.config import OptimizerConfig
    from deepspeed_tpu.runtime.optimizers import build_optimizer
    with pytest.raises(ValueError, match="adam"):
        build_optimizer(OptimizerConfig(
            type="lamb", params={"state_dtype": "int8"})).init({
                "w": jnp.zeros((2,))})


def test_bf16_state_rejects_fp16():
    from deepspeed_tpu.config.config import OptimizerConfig
    from deepspeed_tpu.runtime.optimizers import build_optimizer
    with pytest.raises(ValueError, match="state_dtype"):
        build_optimizer(OptimizerConfig(
            type="adamw", params={"state_dtype": "fp16"})).init({
                "w": jnp.zeros((2,))})


def test_grad_accum_dtype_bf16(devices8):
    """data_types.grad_accum_dtype=bf16 halves the resident grad buffer;
    step results must track fp32 accumulation closely on a toy problem."""
    b = _make_batch(n=16)

    def run(block):
        eng = _engine(stage=0, gas=4, micro=2, dtype_block=block)
        b4 = {k: np.concatenate([b[k]] * 4, axis=0) for k in b}
        return [float(eng.train_batch(b4)["loss"]) for _ in range(5)]

    l32 = run(None)
    l16 = run({"data_types": {"grad_accum_dtype": "bf16"}})
    np.testing.assert_allclose(l16, l32, rtol=0.05)


def test_grad_accum_dtype_invalid_raises():
    from deepspeed_tpu.config.config import ConfigError
    with pytest.raises(ConfigError, match="grad_accum_dtype"):
        _engine(stage=0, dtype_block={
            "data_types": {"grad_accum_dtype": "int8"}})


def test_bf16_master_weights(devices8):
    eng = _engine(stage=1, dtype_block={"bf16": {"enabled": True}})
    assert eng.state.params["w1"].dtype == jnp.bfloat16
    assert eng.state.master["w1"].dtype == jnp.float32
    batch = _make_batch(n=eng.config.train_batch_size)
    losses = [float(eng.train_batch(batch)["loss"]) for _ in range(10)]
    assert losses[-1] < losses[0]


def test_fp16_dynamic_loss_scale_overflow_skip(devices8):
    eng = _engine(stage=0, dtype_block={"fp16": {"enabled": True}})
    scale0 = eng.loss_scale
    batch = _make_batch(n=eng.config.train_batch_size)
    # poison one batch to force inf grads
    bad = {k: v.copy() for k, v in batch.items()}
    bad["y"][:] = 1e38  # loss ~ (out - 1e38)^2 overflows fp32 grads * scale
    p_before = jax.device_get(eng.state.params)
    m = eng.train_batch(bad)
    assert bool(m["overflow"])
    p_after = jax.device_get(eng.state.params)
    for k in p_before:
        np.testing.assert_array_equal(np.asarray(p_before[k]), np.asarray(p_after[k]))
    assert eng.loss_scale < scale0  # backoff
    assert int(eng.state.skipped_steps) == 1
    # normal batch trains
    m = eng.train_batch(batch)
    assert not bool(m["overflow"])


def test_gradient_clipping(devices8):
    eng = _engine(stage=0, extra={"gradient_clipping": 1e-6})
    batch = _make_batch(n=eng.config.train_batch_size)
    p_before = jax.device_get(eng.state.params)
    eng.train_batch(batch)
    p_after = jax.device_get(eng.state.params)
    # clipped to tiny norm -> param movement bounded by lr * small update
    delta = max(np.abs(np.asarray(p_after[k]) - np.asarray(p_before[k])).max()
                for k in p_before)
    assert delta < 1e-2


def test_forward_backward_step_compat(devices8):
    eng = _engine(stage=0, gas=2, micro=1)
    b = _make_batch(n=8)
    eng.forward(b)
    eng.backward()
    assert eng.step() is None  # not at boundary yet
    eng.forward(b)
    eng.backward()
    out = eng.step()
    assert out is not None and np.isfinite(float(out["loss"]))


def test_forward_returns_usable_loss(devices8):
    """Ported 3-call loops use the loss forward() returns (reference:
    engine.py:2114 `loss = model_engine(batch)` then logs it)."""
    eng = _engine(stage=0, gas=2, micro=1)
    b1, b2 = _make_batch(n=8, seed=1), _make_batch(n=8, seed=2)
    l1 = eng.forward(b1)
    eng.backward(l1)
    eng.step()
    l2 = eng.forward(b2)
    eng.backward(l2)
    out = eng.step()
    # resolved for free at the boundary, per-micro values
    assert l1.resolved and l2.resolved
    v1, v2 = float(l1), float(l2)
    assert np.isfinite(v1) and np.isfinite(v2)
    assert v1 != v2  # distinct micro-batches, distinct losses
    # window mean of the per-micro losses == reported step loss
    assert np.isclose((v1 + v2) / 2, float(out["loss"]), rtol=1e-5)


def test_forward_loss_early_coercion(devices8):
    """float(handle) before the boundary forces a grad-free forward."""
    eng = _engine(stage=0, gas=2, micro=1)
    b = _make_batch(n=8, seed=3)
    h = eng.forward(b)
    assert not h.resolved
    v = float(h)  # before step(): eager probe at current params
    assert np.isfinite(v)
    # matches the direct loss at current params (no dropout in toy model)
    ref = float(_toy_loss(eng.params, {k: jnp.asarray(x) for k, x in b.items()}))
    assert np.isclose(v, ref, rtol=1e-4)


def test_get_global_grad_norm(devices8):
    eng = _engine(stage=0)
    assert eng.get_global_grad_norm() is None  # before first step
    out = eng.train_batch(_make_batch(n=eng.config.train_batch_size))
    gn = eng.get_global_grad_norm()
    assert gn is not None and np.isfinite(gn) and gn > 0
    assert np.isclose(gn, float(out["grad_norm"]), rtol=1e-6)


def test_lr_schedule_applied(devices8):
    eng = _engine(stage=0, extra={
        "scheduler": {"type": "WarmupLR",
                      "params": {"warmup_min_lr": 0.0, "warmup_max_lr": 1e-2,
                                 "warmup_num_steps": 10, "warmup_type": "linear"}}})
    batch = _make_batch(n=eng.config.train_batch_size)
    m1 = eng.train_batch(batch)
    m5 = None
    for _ in range(4):
        m5 = eng.train_batch(batch)
    assert float(m5["lr"]) > float(m1["lr"])


def test_checkpoint_save_load_roundtrip(devices8, tmp_path):
    eng = _engine(stage=2, dtype_block={"bf16": {"enabled": True}})
    batch = _make_batch(n=eng.config.train_batch_size)
    for _ in range(3):
        eng.train_batch(batch)
    loss_before = float(eng.train_batch(batch)["loss"])
    eng.save_checkpoint(str(tmp_path), tag="t1", client_state={"foo": 1})

    eng2 = _engine(stage=2, dtype_block={"bf16": {"enabled": True}})
    path, client = eng2.load_checkpoint(str(tmp_path))
    assert client == {"foo": 1}
    assert int(eng2.state.step) == int(eng.state.step)
    l2 = float(eng2.train_batch(batch)["loss"])
    l1 = float(eng.train_batch(batch)["loss"])
    np.testing.assert_allclose(l1, l2, rtol=1e-5)


def test_checkpoint_topology_change(devices8, tmp_path):
    """Save under stage 2, load under stage 3 — universal-checkpoint
    semantics (reference: checkpoint/ds_to_universal.py round trip)."""
    eng = _engine(stage=2)
    batch = _make_batch(n=eng.config.train_batch_size)
    for _ in range(2):
        eng.train_batch(batch)
    eng.save_checkpoint(str(tmp_path), tag="u1")

    eng3 = _engine(stage=3)
    eng3.load_checkpoint(str(tmp_path), tag="u1")
    l_a = float(eng.train_batch(batch)["loss"])
    l_b = float(eng3.train_batch(batch)["loss"])
    np.testing.assert_allclose(l_a, l_b, rtol=2e-5, atol=1e-6)


def test_no_sync_defers_compat_loop():
    """no_sync(): micro-batches queue past the GAS boundary; step() after
    exit consumes them window by window (reference engine.no_sync:2265)."""
    import deepspeed_tpu as dstpu

    def loss_fn(params, batch, rng=None):
        return jnp.mean((batch["x"] @ params["w"]) ** 2), {}

    engine = dstpu.initialize(
        loss_fn=loss_fn, params={"w": jnp.ones((4, 2))},
        config={"train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 2,
                "optimizer": {"type": "sgd", "params": {"lr": 0.1}},
                "steps_per_print": 0})
    dp = engine.topology.dp_size
    micro = {"x": np.ones((dp, 4), np.float32)}
    with engine.no_sync():
        for _ in range(4):           # 2 windows worth of micro-batches
            engine.forward(micro)
            engine.backward()
            assert engine.step() is None      # deferred inside the context
    assert len(engine._pending_batches) == 4
    before = int(engine.global_steps)
    out = engine.step()              # consumes both windows
    assert out is not None
    assert engine._pending_batches == []
    assert int(engine.global_steps) == before + 2


def test_no_sync_nested_contexts_compose():
    """Exiting an inner nested no_sync() must not re-enable boundary firing
    while the outer context is still active (depth-counted, like the
    reference's guard)."""
    import deepspeed_tpu as dstpu

    def loss_fn(params, batch, rng=None):
        return jnp.mean((batch["x"] @ params["w"]) ** 2), {}

    engine = dstpu.initialize(
        loss_fn=loss_fn, params={"w": jnp.ones((4, 2))},
        config={"train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 2,
                "optimizer": {"type": "sgd", "params": {"lr": 0.1}},
                "steps_per_print": 0})
    dp = engine.topology.dp_size
    micro = {"x": np.ones((dp, 4), np.float32)}
    with engine.no_sync():
        with engine.no_sync():
            pass
        for _ in range(4):
            engine.forward(micro)
            engine.backward()
            assert engine.step() is None   # outer context still active
    assert int(engine.global_steps) == 0
    engine.step()
    assert int(engine.global_steps) == 2


def test_client_optimizer_shims():
    """initialize(optimizer=FusedAdam(...)) — the reference's client-optimizer
    path (deepspeed.ops.adam/lamb/lion/adagrad classes; engine
    _configure_basic_optimizer)."""
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.ops.adam import FusedAdam, DeepSpeedCPUAdam
    from deepspeed_tpu.ops.lamb import FusedLamb
    from deepspeed_tpu.ops.lion import FusedLion
    from deepspeed_tpu.ops.adagrad import DeepSpeedCPUAdagrad

    def loss_fn(params, batch, rng=None):
        return jnp.mean((batch["x"] @ params["w"]) ** 2), {}

    for shim, expect in ((FusedAdam(lr=0.05), "adamw"),
                         (FusedAdam(lr=0.05, adam_w_mode=False), "adam"),
                         (DeepSpeedCPUAdam(lr=0.05), "adamw"),
                         (FusedLamb(lr=0.05), "lamb"),
                         (FusedLion(lr=0.01), "lion"),
                         (DeepSpeedCPUAdagrad(lr=0.05), "adagrad")):
        engine = dstpu.initialize(
            loss_fn=loss_fn, params={"w": jnp.ones((4, 2))}, optimizer=shim,
            config={"train_micro_batch_size_per_gpu": 1,
                    "steps_per_print": 0})
        assert engine.optimizer.name == expect, (shim, engine.optimizer.name)
        batch = {"x": np.ones((engine.topology.dp_size, 4), np.float32)}
        l0 = float(engine.train_batch(batch)["loss"])
        l1 = float(engine.train_batch(batch)["loss"])
        assert l1 < l0
    with pytest.raises(TypeError, match="optimizer="):
        dstpu.initialize(loss_fn=loss_fn, params={"w": jnp.ones((4, 2))},
                         optimizer=object(),
                         config={"train_micro_batch_size_per_gpu": 1})


def test_aux_metrics_and_scalar_batch_leaves():
    """loss_fn aux outputs surface in train_batch metrics (averaged over the
    GAS window), and per-sample scalar batch leaves ([B]-shaped — advantages,
    rewards) shard correctly."""
    import deepspeed_tpu as dstpu

    def loss_fn(params, batch, rng=None):
        pred = batch["x"] @ params["w"]                      # [b, 2]
        loss = jnp.mean(batch["weight"][:, None] * pred ** 2)
        return loss, {"my_aux": jnp.mean(batch["weight"]), "kl": loss * 0.5}

    engine = dstpu.initialize(
        loss_fn=loss_fn, params={"w": jnp.ones((4, 2))},
        config={"train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 2,
                "optimizer": {"type": "sgd", "params": {"lr": 0.05}},
                "steps_per_print": 0})
    B = engine.config.train_batch_size
    batch = {"x": np.ones((B, 4), np.float32),
             "weight": np.linspace(1.0, 2.0, B).astype(np.float32)}
    m = engine.train_batch(batch)
    assert "my_aux" in m and "kl" in m
    np.testing.assert_allclose(float(m["my_aux"]), float(np.mean(batch["weight"])),
                               rtol=1e-5)
    # reserved engine keys are not shadowed by aux
    def bad_aux(params, batch, rng=None):
        return jnp.mean((batch["x"] @ params["w"]) ** 2), {"loss": jnp.zeros(())}
    engine2 = dstpu.initialize(
        loss_fn=bad_aux, params={"w": jnp.ones((4, 2))},
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "sgd", "params": {"lr": 0.05}},
                "steps_per_print": 0})
    m2 = engine2.train_batch({"x": np.ones((engine2.config.train_batch_size, 4),
                                           np.float32)})
    assert float(m2["loss"]) > 0.0   # the real loss, not the aux zero


def test_client_lr_scheduler_and_training_data():
    """initialize(lr_scheduler=callable, training_data=dataset) — the
    reference's client-scheduler/dataloader args; the callable drives the
    compiled step's lr and the dataset is wrapped at the global batch size."""
    import deepspeed_tpu as dstpu

    def loss_fn(params, batch, rng=None):
        return jnp.mean((batch["x"] @ params["w"]) ** 2), {}

    data = {"x": np.random.RandomState(0).randn(32, 4).astype(np.float32)}
    engine = dstpu.initialize(
        loss_fn=loss_fn, params={"w": jnp.ones((4, 2))},
        lr_scheduler=lambda step: 0.1 * jnp.minimum((step + 1) / 4.0, 1.0),
        training_data=data,
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "sgd", "params": {"lr": 999.0}},
                "steps_per_print": 0})
    assert len(engine.training_dataloader) == 32 // engine.config.train_batch_size
    for i, batch in enumerate(engine.training_dataloader):
        m = engine.train_batch(batch)
        np.testing.assert_allclose(float(m["lr"]),
                                   0.1 * min((i + 1) / 4.0, 1.0), rtol=1e-6)
        if i >= 5:
            break
    with pytest.raises(TypeError, match="lr_scheduler="):
        dstpu.initialize(loss_fn=loss_fn, params={"w": jnp.ones((4, 2))},
                         lr_scheduler=object(),
                         config={"train_micro_batch_size_per_gpu": 1})


@pytest.mark.parametrize("fsdp", [1, 4])
def test_train_step_compiles_once(fsdp, devices8, caplog, monkeypatch):
    """The state `_init_state` builds is placed exactly as the compiled
    step returns it — scalars on the mesh, canonical PartitionSpecs,
    constrained optimizer state (int8 moments carry replicated scale
    trees).  Otherwise the SECOND train_batch recompiles the whole step:
    17 s at 1.1B on one chip, 11.8 s under ZeRO-3 fsdp=4 (PR 23)."""
    import logging
    # XLA:CPU alone: it schedules a program's independent collectives
    # side by side, the virtual devices start them in different orders,
    # and under load (xdist's other workers) this four-device step's
    # all-gather and all-reduce wait for each other in the in-process
    # rendezvous until the process aborts 40 s later, in whatever test
    # runs then (beside five copies of itself: 7 of 24 runs; 0 of 24
    # compiled like this: PR 30).  A TPU orders collectives itself.
    jit = jax.jit
    monkeypatch.setattr(jax, "jit", lambda f, **kw: jit(
        f, compiler_options={
            "xla_cpu_enable_concurrency_optimized_scheduler": False}, **kw))
    from deepspeed_tpu.models import Transformer, llama_config
    model = Transformer(llama_config("tiny", max_seq_len=32,
                                     dtype=jnp.bfloat16))
    from deepspeed_tpu.parallel.mesh import make_mesh
    engine = dstpu.initialize(
        model=model,
        topology=make_mesh(fsdp=fsdp, devices=jax.devices()[:fsdp]),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "adamw",
                              "params": {"lr": 1e-3,
                                         "state_dtype": "int8f"}},
                "zero_optimization": {"stage": 3},
                "bf16": {"enabled": True}, "steps_per_print": 0})
    batch = {"input_ids": np.random.RandomState(0).randint(
        0, 1000, (engine.config.train_batch_size, 33)).astype(np.int32)}
    with caplog.at_level(logging.WARNING), jax.log_compiles(True):
        for _ in range(3):
            engine.train_batch(batch)
    compiles = [r for r in caplog.records
                if "Finished XLA compilation of jit(train_step)"
                in r.getMessage()]
    assert len(compiles) == 1, [r.getMessage()[:80] for r in compiles]


def _tiny_opt():
    from deepspeed_tpu.models import Transformer, get_model_config
    model = Transformer(get_model_config("opt", "tiny", dtype=jnp.float32,
                                         remat=True))
    return model, model.init_params(jax.random.PRNGKey(0))


@functools.lru_cache(maxsize=None)
def _one_step(gas: int, given: str, clip=1.0, micro=2, seq=32):
    """One `train_batch` of a tiny OPT, the engine given the `Transformer`
    or its loss as a plain function: (engine, batch, what the step
    returned, the gradients the optimizer got, the updated parameters)."""
    from deepspeed_tpu.parallel.mesh import make_mesh
    model, params = _tiny_opt()
    kw = ({"model": model} if given == "model" else
          {"loss_fn": lambda p, b, rng=None: model.loss_fn(p, b, rng)})
    eng = dstpu.initialize(
        params=params, topology=make_mesh(devices=jax.devices()[:1]),
        config={"train_micro_batch_size_per_gpu": micro,
                "gradient_accumulation_steps": gas,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 1},
                "gradient_clipping": clip, "steps_per_print": 0,
                "activation_checkpointing": {"policy": "save_attn"}}, **kw)
    eng.store_gradients = True
    ids = np.random.RandomState(gas).randint(
        0, model.cfg.vocab_size, (gas * micro, seq)).astype(np.int32)
    got = eng.train_batch({"input_ids": ids})
    return eng, ids.reshape(gas, micro, seq), jax.device_get(
        ((got["loss"], got["micro_losses"], got["grad_norm"]),
         eng._last_grads, eng.state.params))


@pytest.mark.parametrize("given", ["model", "loss_fn"])
@pytest.mark.parametrize("gas", [1, 2, 8])
def test_accumulation_is_the_reference_sum_bit_for_bit(gas, given):
    """Losses, gradient norm and the gradients the optimizer got against
    an accumulation written here: every micro-batch's gradient tree added
    whole, in order, as `acc + g`.  Given a `Transformer` the engine adds
    each layer's gradient into the accumulator inside the backward layer
    scan (`grad_sink`); given the same loss as a plain `loss_fn` it adds
    the tree itself.  Both are the same sum, bit for bit, and the updated
    parameters of the two are the same bits too (against an optimizer call
    compiled outside the step they are one ulp apart: fused otherwise)."""
    from deepspeed_tpu.utils import tree as tu
    model, params = _tiny_opt()
    eng, micros, (scalars, grads, updated) = _one_step(gas, given)
    assert getattr(eng.loss_fn, "grad_sink", None) == (
        "layers" if given == "model" else None)
    clip = eng.config.gradient_clipping

    @jax.jit
    def reference(params, micros):
        def body(carry, ids):
            acc, total = carry
            loss, g = jax.value_and_grad(
                lambda p: model.loss_fn(p, {"input_ids": ids})[0])(params)
            return (jax.tree.map(lambda a, g: a + g, acc, g),
                    total + loss), loss
        (acc, total), losses = jax.lax.scan(
            body, (jax.tree.map(jnp.zeros_like, params), jnp.zeros(())),
            micros)
        norm = tu.global_norm(acc) * (1.0 / gas)
        scale = (1.0 / gas) * jnp.minimum(1.0, clip / (norm + 1e-6))
        return ((total / gas, losses, norm),
                jax.tree.map(lambda g: g * scale, acc))

    same = lambda a, b: jax.tree.map(  # noqa: E731
        np.testing.assert_array_equal, a, jax.device_get(b))
    want = reference(params, micros)
    same(scalars, want[0])
    same(grads, want[1])
    other = "loss_fn" if given == "model" else "model"
    same(updated, _one_step(gas, other)[2][2])
