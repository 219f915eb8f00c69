"""Activation checkpointing subsystem (reference analog:
tests exercising runtime/activation_checkpointing/checkpointing.py semantics:
checkpointed forward == plain forward, grads identical, RNG streams named)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.runtime import activation_checkpointing as ac


@pytest.fixture(autouse=True)
def _reset():
    ac.reset()
    yield
    ac.reset()


def _mlp(params, x):
    h = jnp.tanh(x @ params["w1"])
    return h @ params["w2"]


def _params(key, d=16):
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (d, 4 * d)) * 0.1,
            "w2": jax.random.normal(k2, (4 * d, d)) * 0.1}


def test_checkpoint_matches_plain():
    p = _params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))

    def loss_plain(p):
        return jnp.sum(_mlp(p, x) ** 2)

    def loss_ckpt(p):
        return jnp.sum(ac.checkpoint(_mlp, p, x) ** 2)

    l0, g0 = jax.value_and_grad(loss_plain)(p)
    l1, g1 = jax.value_and_grad(loss_ckpt)(p)
    assert np.allclose(l0, l1)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        # rtol 5e-5, not 1e-6: this XLA build reassociates the rematted
        # backward's reductions (measured max rel diff 2.7e-6, fp32 noise,
        # not a remat-semantics bug)
        np.testing.assert_allclose(a, b, rtol=5e-5)


def test_configure_and_policies():
    assert not ac.is_configured()
    ac.configure(partition_activations=True, cpu_checkpointing=False)
    assert ac.is_configured()
    # each named policy resolves
    for name in ["nothing_saveable", "everything_saveable", "dots_saveable",
                 "dots_with_no_batch_dims", "save_named", "offload"]:
        assert ac.remat_policy(name) is not None
    with pytest.raises(ValueError):
        ac.remat_policy("bogus")


def test_wrapper_with_selective_policy():
    p = _params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))
    fn = ac.checkpoint_wrapper(_mlp, policy="dots_saveable")
    g0 = jax.grad(lambda p: jnp.sum(_mlp(p, x)))(p)
    g1 = jax.grad(lambda p: jnp.sum(fn(p, x)))(p)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        # rtol 5e-5, not 1e-6: same XLA reduction-reassociation noise as
        # test_checkpoint_matches_plain (measured max rel diff 1.5e-5)
        np.testing.assert_allclose(a, b, rtol=5e-5)


def test_remat_scan_layer_stack():
    L, d = 4, 8
    keys = jax.random.split(jax.random.PRNGKey(0), L)
    stacked = jax.vmap(lambda k: _params(k, d))(keys)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, d))

    def layer(lp, x):
        return x + _mlp(lp, x)

    def plain(stacked, x):
        def body(x, lp):
            return layer(lp, x), None
        out, _ = jax.lax.scan(body, x, stacked)
        return jnp.sum(out ** 2)

    def rematted(stacked, x):
        return jnp.sum(ac.remat_scan(layer, stacked, x) ** 2)

    l0, g0 = jax.value_and_grad(plain)(stacked, x)
    l1, g1 = jax.value_and_grad(rematted)(stacked, x)
    assert np.allclose(l0, l1, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(a, b, rtol=1e-5)


def test_offload_policy_grads_match():
    """cpu_checkpointing: tagged residuals offload to host; numerics equal."""
    ac.configure(cpu_checkpointing=True)
    p = _params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))

    def fwd(p, x):
        h = ac.checkpoint_name(jnp.tanh(x @ p["w1"]))
        return h @ p["w2"]

    fn = ac.checkpoint_wrapper(fwd)  # resolves to offload policy
    l0, g0 = jax.value_and_grad(lambda p: jnp.sum(_mlp(p, x)))(p)
    # jitted: jax only accepts the offload policy's
    # TransferToMemoryKind device_put inside jit — which is where
    # cpu_checkpointing runs in real training steps anyway
    l1, g1 = jax.jit(jax.value_and_grad(lambda p: jnp.sum(fn(p, x))))(p)
    assert np.allclose(l0, l1)
    # eager vs jitted: XLA reassociates the fp32 sums (measured max
    # relative difference 1.3e-5 on this CPU compiler) — the same bound
    # the other grads-match tests here use
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(a, b, rtol=5e-5)


def test_rng_tracker_fork_streams():
    tr = ac.model_parallel_reseed(1234, tp_rank=0)
    with tr.fork("model-parallel-rng") as k1:
        pass
    with tr.fork("model-parallel-rng") as k2:
        pass
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))
    # different tp_rank -> different model-parallel stream, same default
    tr0 = ac.model_parallel_reseed(99, tp_rank=0).get_states()
    tr1 = ac.model_parallel_reseed(99, tp_rank=1).get_states()
    assert np.array_equal(np.asarray(tr0["default"]), np.asarray(tr1["default"]))
    assert not np.array_equal(np.asarray(tr0["model-parallel-rng"]),
                              np.asarray(tr1["model-parallel-rng"]))
    with pytest.raises(KeyError):
        with ac.get_rng_tracker().fork("nope"):
            pass


def test_partition_activation_tags_and_shards(devices8):
    """partition_activations under a tp mesh: function still correct."""
    from deepspeed_tpu.parallel.mesh import make_mesh
    from deepspeed_tpu.parallel.context import set_current_topology
    topo = make_mesh(tp=4)
    set_current_topology(topo)
    try:
        ac.configure(partition_activations=True)
        p = _params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))

        def fwd(p, x):
            h = ac.partition_activation(jnp.tanh(x @ p["w1"]))
            return h @ p["w2"]

        fn = ac.checkpoint_wrapper(fwd)  # save_named policy
        l0 = jnp.sum(_mlp(p, x))
        l1, g1 = jax.value_and_grad(lambda p: jnp.sum(fn(p, x)))(p)
        assert np.allclose(l0, l1, rtol=1e-6)
        assert all(np.all(np.isfinite(g)) for g in jax.tree.leaves(g1))
    finally:
        set_current_topology(None)


def test_save_attn_policy_trains_and_matches():
    """save_attn: full remat except tagged attention outputs (skips the
    flash-forward recompute in bwd).  Loss must equal the full-remat
    path's exactly — the policy changes what is SAVED, not the math."""
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models import Transformer, TransformerConfig

    def run(policy):
        cfg = TransformerConfig(vocab_size=128, hidden_size=64,
                                num_layers=2, num_heads=4, max_seq_len=64,
                                dtype=jnp.float32, attn_impl="jnp",
                                remat=True)
        eng = dstpu.initialize(model=Transformer(cfg), config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "steps_per_print": 0,
            "activation_checkpointing": {"policy": policy}})
        ids = np.random.RandomState(0).randint(
            0, 128, (eng.config.train_batch_size, 64)).astype(np.int32)
        return [float(eng.train_batch({"input_ids": ids})["loss"])
                for _ in range(3)]
    a = run("save_attn")
    b = run("nothing_saveable")
    np.testing.assert_allclose(a, b, rtol=1e-6)


@pytest.mark.parametrize("policy", ["save_attn_proj", "save_attn_proj_up"])
def test_selective_proj_policies_match_full_remat(policy):
    """The finer-grained save policies (qkv/out projections, mlp-up) must be
    numerically identical to full remat — they change what is saved, not
    the math."""
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models import Transformer, TransformerConfig

    def run(pol):
        cfg = TransformerConfig(vocab_size=128, hidden_size=64,
                                num_layers=2, num_heads=4, max_seq_len=64,
                                dtype=jnp.float32, attn_impl="jnp",
                                remat=True)
        eng = dstpu.initialize(model=Transformer(cfg), config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "steps_per_print": 0,
            "activation_checkpointing": {"policy": pol}})
        ids = np.random.RandomState(0).randint(
            0, 128, (eng.config.train_batch_size, 64)).astype(np.int32)
        return [float(eng.train_batch({"input_ids": ids})["loss"])
                for _ in range(3)]

    np.testing.assert_allclose(run(policy), run("nothing_saveable"),
                               rtol=1e-6)


def test_save_attn_skips_flash_forward_recompute(monkeypatch):
    """With out AND lse tagged inside the flash custom_vjp fwd rule
    (ops/flash_attention.py), the remat backward must not re-run the
    forward kernel: 3 pallas_calls in the grad jaxpr (fwd + dq + dkv), not
    4.  This is the regression that made round-2's save_attn a no-op —
    saving only `out` still forced a forward re-run to regenerate lse."""
    import functools
    import jax.experimental.pallas as pl
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    from deepspeed_tpu.ops.flash_attention import flash_attention
    from deepspeed_tpu.runtime.activation_checkpointing import remat_policy

    B, S, N, D = 1, 256, 2, 128
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(B, S, N, D) * 0.1, jnp.float32)
               for _ in range(3))
    w = jnp.asarray(rng.randn(D * N, 8) * 0.1, jnp.float32)

    def make_loss(policy):
        def loss(q, k, v):
            def block(q, k, v):
                o = flash_attention(q, k, v, causal=True,
                                    block_q=128, block_k=128)
                return jnp.sum((o.reshape(B, S, N * D) @ w) ** 2)
            return jax.checkpoint(block, policy=remat_policy(policy))(q, k, v)
        return loss

    counts = {}
    grads = {}
    for pol in ("nothing_saveable", "save_attn"):
        jxp = str(jax.make_jaxpr(
            jax.grad(make_loss(pol), argnums=(0, 1, 2)))(q, k, v))
        counts[pol] = jxp.count("pallas_call")
        grads[pol] = jax.grad(make_loss(pol), argnums=(0, 1, 2))(q, k, v)
    assert counts["nothing_saveable"] == 4
    assert counts["save_attn"] == 3
    for a, b in zip(grads["nothing_saveable"], grads["save_attn"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


# policy -> the weight matmuls of a layer its backward pass computes again:
# (under `attention`, the up-projection under `mlp`).  What a policy saves
# it does not recompute; the down-projection's output feeds no gradient
# and is recomputed under none of them
@pytest.mark.parametrize("policy,attention,up", [
    ("nothing_saveable", 4, 1),     # q, k, v, output; up-projection
    ("save_attn", 4, 1),            # the attention kernel's output is saved
    ("save_attn_proj", 0, 1),       # + the four projections' outputs
    ("save_attn_proj_up", 0, 0),    # + the up-projection's
])
def test_recomputed_weight_matmuls_are_the_policys_own(policy, attention, up):
    """The optimised HLO of a tiny OPT's whole train step (accumulation
    over two micro-batches, the accumulator riding the backward layer
    scan): the weight matmuls whose metadata lies under
    `rematted_computation`, counted per layer call by scope and width."""
    import re

    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models import Transformer, get_model_config
    from deepspeed_tpu.parallel.mesh import make_mesh
    cfg = get_model_config("opt", "tiny", dtype=jnp.float32, remat=True)
    eng = dstpu.initialize(
        model=Transformer(cfg), topology=make_mesh(devices=jax.devices()[:1]),
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 1}, "steps_per_print": 0,
                "activation_checkpointing": {"policy": policy}})
    batch = eng._shard_batch({"input_ids": np.zeros((4, 32), np.int32)})
    hlo = eng._train_step.lower(eng.state, batch, jax.random.PRNGKey(0),
                                {}).compile().as_text()
    rows = 2 * 32
    found = {"attention": 0, "up": 0, "down": 0}
    for shape, scope in re.findall(
            r"= (\S+?)\{[^ ]* dot\(.*op_name=\"[^\"]*rematted_computation/"
            r"(attention|mlp)/bsh,hd->bsd/dot_general\"", hlo):
        if scope == "attention":
            assert shape == f"f32[{rows},{cfg.hidden_size}]", shape
            found["attention"] += 1
        else:
            found["up" if shape == f"f32[{rows},{cfg.ffn_dim}]"
                  else "down"] += 1
    assert found == {"attention": attention, "up": up, "down": 0}
    assert ".remat" not in hlo      # and XLA cloned nothing on its own
