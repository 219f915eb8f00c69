"""Loss-curve parity vs an independent PyTorch implementation.

What the reference promises is throughput at *identical loss curves*.  This test builds the same tiny GPT-2-style model
in torch (CPU), copies our init weights in, trains both with plain SGD in
fp32 on the same token stream, and demands per-step loss agreement — any
divergence in forward math, autodiff, loss reduction, or the engine's
update/GAS plumbing shows up here (reference analog: tests/model/
Megatron_GPT2 run_sanity_check.py curve comparison).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as dstpu
from deepspeed_tpu.models import Transformer, TransformerConfig

torch = pytest.importorskip("torch")

V, H, L, NH, S = 512, 64, 2, 4, 32
LR = 0.05


pytestmark = pytest.mark.slow


def _jax_engine(gas=1):
    cfg = TransformerConfig(vocab_size=V, hidden_size=H, num_layers=L,
                            num_heads=NH, max_seq_len=S, dtype=jnp.float32,
                            tie_embeddings=True)
    model = Transformer(cfg)
    engine = dstpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "sgd", "params": {"lr": LR}},
        "zero_optimization": {"stage": 0},
        "steps_per_print": 0})
    return engine, cfg


class TorchBlock(torch.nn.Module):
    def __init__(self, p):
        super().__init__()
        t = lambda a: torch.nn.Parameter(torch.tensor(np.array(a)))
        self.ln1_w, self.ln1_b = t(p["attn_norm_scale"]), t(p["attn_norm_bias"])
        self.wq, self.wk, self.wv, self.wo = (t(p[k]) for k in
                                              ("wq", "wk", "wv", "wo"))
        self.bq, self.bk, self.bv, self.bo = (t(p[k]) for k in
                                              ("bq", "bk", "bv", "bo"))
        self.ln2_w, self.ln2_b = t(p["mlp_norm_scale"]), t(p["mlp_norm_bias"])
        self.w_up, self.b_up = t(p["w_up"]), t(p["b_up"])
        self.w_down, self.b_down = t(p["w_down"]), t(p["b_down"])

    def forward(self, x):
        B, T, _ = x.shape
        h = torch.nn.functional.layer_norm(x, (H,), self.ln1_w, self.ln1_b)
        q = (h @ self.wq + self.bq).view(B, T, NH, H // NH)
        k = (h @ self.wk + self.bk).view(B, T, NH, H // NH)
        v = (h @ self.wv + self.bv).view(B, T, NH, H // NH)
        s = torch.einsum("bqnd,bknd->bnqk", q, k) / (H // NH) ** 0.5
        mask = torch.tril(torch.ones(T, T, dtype=torch.bool))
        s = s.masked_fill(~mask, float("-inf"))
        a = torch.softmax(s, dim=-1)
        o = torch.einsum("bnqk,bknd->bqnd", a, v).reshape(B, T, H)
        x = x + o @ self.wo + self.bo
        h = torch.nn.functional.layer_norm(x, (H,), self.ln2_w, self.ln2_b)
        h = torch.nn.functional.gelu(h @ self.w_up + self.b_up,
                                     approximate="tanh")
        return x + h @ self.w_down + self.b_down


class TorchGPT(torch.nn.Module):
    """Mirror of models/transformer.py built from OUR init params."""

    def __init__(self, params):
        super().__init__()
        p = jax.tree.map(np.array, jax.device_get(params))
        self.tok = torch.nn.Parameter(torch.tensor(p["tok_embed"]))
        self.pos = torch.nn.Parameter(torch.tensor(p["pos_embed"]))
        layers = p["layers"]
        self.blocks = torch.nn.ModuleList([
            TorchBlock({k: v[i] for k, v in layers.items()})
            for i in range(L)])
        self.lnf_w = torch.nn.Parameter(torch.tensor(p["final_norm_scale"]))
        self.lnf_b = torch.nn.Parameter(torch.tensor(p["final_norm_bias"]))

    def forward(self, ids):
        B, T = ids.shape
        x = self.tok[ids] + self.pos[torch.arange(T)][None]
        for blk in self.blocks:
            x = blk(x)
        x = torch.nn.functional.layer_norm(x, (H,), self.lnf_w, self.lnf_b)
        return x @ self.tok.T

    def loss(self, ids):
        logits = self(ids[:, :-1])
        return torch.nn.functional.cross_entropy(
            logits.reshape(-1, V), ids[:, 1:].reshape(-1))


def test_loss_curve_matches_torch_sgd():
    engine, cfg = _jax_engine()
    net = TorchGPT(engine.state.params)
    opt = torch.optim.SGD(net.parameters(), lr=LR)

    rng = np.random.RandomState(0)
    fixed = rng.randint(0, V, (engine.config.train_batch_size, S + 1)
                        ).astype(np.int32)
    jl, tl = [], []
    for step in range(12):
        jl.append(float(engine.train_batch({"input_ids": fixed})["loss"]))
        opt.zero_grad()
        loss = net.loss(torch.tensor(fixed, dtype=torch.long))
        loss.backward()
        opt.step()
        tl.append(float(loss.detach()))
    np.testing.assert_allclose(jl, tl, rtol=2e-3)
    assert jl[-1] < jl[0]          # memorizing the fixed batch


def test_adam_curve_matches_torch():
    """Adam parity (bias correction, eps placement): our fused Adam update
    must track torch.optim.Adam step-for-step."""
    cfg = TransformerConfig(vocab_size=V, hidden_size=H, num_layers=L,
                            num_heads=NH, max_seq_len=S, dtype=jnp.float32)
    model = Transformer(cfg)
    engine = dstpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adam",
                      "params": {"lr": 1e-3, "betas": [0.9, 0.999],
                                 "eps": 1e-8}},
        "zero_optimization": {"stage": 0}, "steps_per_print": 0})
    net = TorchGPT(engine.state.params)
    opt = torch.optim.Adam(net.parameters(), lr=1e-3, betas=(0.9, 0.999),
                           eps=1e-8)
    rng = np.random.RandomState(2)
    fixed = rng.randint(0, V, (engine.config.train_batch_size, S + 1)
                        ).astype(np.int32)
    jl, tl = [], []
    for step in range(10):
        jl.append(float(engine.train_batch({"input_ids": fixed})["loss"]))
        opt.zero_grad()
        loss = net.loss(torch.tensor(fixed, dtype=torch.long))
        loss.backward()
        opt.step()
        tl.append(float(loss.detach()))
    np.testing.assert_allclose(jl, tl, rtol=3e-3)
    assert jl[-1] < jl[0]


def test_gas_matches_large_batch():
    """micro 2 x GAS 2 x dp must track torch's full-batch SGD curve
    (gradient averaging across micro-steps and data ranks — reference
    scale_wrt_gas + DP allreduce semantics)."""
    engine, cfg = _jax_engine(gas=2)
    net = TorchGPT(engine.state.params)
    opt = torch.optim.SGD(net.parameters(), lr=LR)

    gbs = engine.config.train_batch_size          # micro*gas*dp
    rng = np.random.RandomState(1)
    fixed = rng.randint(0, V, (gbs, S + 1)).astype(np.int32)
    jl, tl = [], []
    for step in range(6):
        jl.append(float(engine.train_batch({"input_ids": fixed})["loss"]))
        opt.zero_grad()
        loss = net.loss(torch.tensor(fixed, dtype=torch.long))
        loss.backward()
        opt.step()
        tl.append(float(loss.detach()))
    np.testing.assert_allclose(jl, tl, rtol=2e-3)
