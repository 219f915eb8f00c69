"""The plain float32 reference against the program at a tiny size on the
CPU, the lower-precision control, and the timed path broken underneath."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark import weights as W

from bench_testlib import DATA


def tiny(name):
    return harness.load_json(DATA, "configs", name + ".json")


def _same(a, b):
    """Equal, but for the rare value whose float32 form (a unit in the last
    place apart between two compiled forms of one formula) sits on a
    bfloat16 rounding boundary."""
    a = np.asarray(a.astype(jnp.float32))
    b = np.asarray(b.astype(jnp.float32))
    off = a != b
    return off.mean() <= 1e-3 and np.all(
        np.abs(a - b)[off] <= 2.0 ** -7 * np.abs(a[off]))


def _step_with(monkeypatch, edit):
    """The compiled step, with `edit(old_state, new_state) -> state` put
    on what it returns."""
    from deepspeed_tpu.runtime.engine import TrainEngine
    real = TrainEngine._build_train_step

    def build(self):
        step = real(self)

        def edited(state, *a, **k):
            kept = jax.tree.map(jnp.copy, state)
            new, metrics = step(state, *a, **k)
            return edit(kept, new), metrics
        return edited
    monkeypatch.setattr(TrainEngine, "_build_train_step", build)


def test_weights_one_call_equals_layer_by_layer():
    """The program's stacked tree and the reference's per-layer leaves are
    the same numbers; another seed gives others; seeds pass 2**31."""
    s = W.sizes_from_config(tiny("qwen2-tiny"))
    whole = W.make_params(W.seed_arg(2**31 + 9), s=s, dtype=jnp.bfloat16)
    key = W.seed_key(W.seed_arg(2**31 + 9))
    for l in range(s.layers):
        lp = W.layer_params(key, np.uint32(l), s, jnp.bfloat16)
        for name, leaf in lp.items():
            assert _same(whole["layers"][name][l], leaf), name
    assert _same(whole["lm_head"],
                 W.top_param(key, "lm_head", s, jnp.bfloat16))
    other = W.make_params(W.seed_arg(2**31 + 10), s=s, dtype=jnp.bfloat16)
    assert not np.array_equal(np.asarray(whole["tok_embed"]),
                              np.asarray(other["tok_embed"]))
    # every leaf is random: a path that drops a bias or a scale would show
    assert float(jnp.std(whole["layers"]["bq"].astype(jnp.float32))) > 0.01
    assert set(whole["layers"]) == {n for n, _, _ in W.layer_leaves(s)}


@pytest.mark.parametrize("cell", ["qwen2-tiny.closed", "opt-tiny.train"])
def test_program_agrees_with_the_reference(run_tiny, cell):
    """A whole run on the CPU: the served tokens (prefill, then decode
    through the paged cache) are the float32 reference's best; the trained
    losses, first gradient and first updates are the reference's."""
    res = run_tiny(cell)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["compiles_in_window"] == 0
    assert set(res) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert res["metrics"]["setup_s"]["value"] > 0


@pytest.mark.parametrize("cell,number", [
    ("qwen2-tiny.closed", "greedy_gap"), ("opt-tiny.train", "grad_norm")])
def test_the_control_comes_out_incorrect(run_tiny, cell, number):
    """The reference, computed in int8 and put in the program's place, goes
    through the same `run_cell` and the cell's own limits, and `correct`
    comes out false (the chip's readings at the cells' own size come from
    the same path: `python3 -m benchmark.control`)."""
    res = run_tiny(cell, seconds=2.0, control="int8")
    assert res["correct"] is False and res["control"] == "int8"
    c = res["compared"][number]
    assert c["value"] > c["limit"]


def test_an_altered_token_makes_the_run_incorrect(run_tiny, monkeypatch):
    """The timed path broken where a token is produced: every decode step's
    best logit is pushed down, so the second-best token is served."""
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    real = InferenceEngineV2.step

    def step(self, *a, **k):
        out = real(self, *a, **k)
        for uid, logits in out.items():
            logits = np.array(logits)
            logits[np.argmax(logits)] = -1e9
            out[uid] = logits
        return out
    monkeypatch.setattr(InferenceEngineV2, "step", step)
    res = run_tiny("qwen2-tiny.closed")
    assert not res["correct"]
    c = res["compared"]["greedy_gap"]
    assert c["value"] > c["limit"]


def test_a_step_that_returns_its_state_unchanged_is_incorrect(
        run_tiny, monkeypatch):
    """The timed path broken underneath: the compiled step computes its
    loss but hands back the state it was given."""
    _step_with(monkeypatch, lambda old, new: old)
    res = run_tiny("opt-tiny.train")
    assert not res["correct"]
    assert res["compared"]["update_norm"]["value"] > 0.9


def test_part_of_the_batch_left_out_moves_the_loss(run_tiny, monkeypatch):
    """The loss is there to catch a part of the batch left out: the feed's
    second row never reaches the step."""
    from deepspeed_tpu.runtime.engine import TrainEngine
    real = TrainEngine.train_batch

    def half(self, batch):
        ids = np.array(batch["input_ids"])
        ids[1:] = ids[0]
        return real(self, {"input_ids": ids})
    monkeypatch.setattr(TrainEngine, "train_batch", half)
    res = run_tiny("opt-tiny.train")
    assert not res["correct"]
    c = res["compared"]["loss_first_step"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("leaf", ["w_up", "attn_norm_scale", "bv"])
def test_one_leaf_left_unupdated_is_incorrect(run_tiny, monkeypatch, leaf):
    """The weights' change goes by the WORST leaf: a step that updates
    every leaf but one (here one leaf of the layers, master and working
    copy) reads 1 there, whatever the median leaf reads."""
    import dataclasses

    def edit(old, new):
        keep = lambda n, o: {**n, "layers": {  # noqa: E731
            **n["layers"], leaf: o["layers"][leaf]}}
        return dataclasses.replace(new, params=keep(new.params, old.params),
                                   master=keep(new.master, old.master))
    _step_with(monkeypatch, edit)
    res = run_tiny("opt-tiny.train")
    assert not res["correct"]
    assert res["compared"]["update_norm"]["value"] > 0.9
    assert leaf in res["notes"]["update_norm_worst_leaf"]
    assert res["notes"]["update_norm_median_leaf"] \
        < res["compared"]["update_norm"]["limit"]


def test_the_leaf_without_a_gradient_is_named(run_tiny):
    """Only the key bias is left out of the weights' change (softmax
    ignores it, so its reference gradient is zero and Adam makes a
    full-size step of the program's rounding noise there)."""
    res = run_tiny("opt-tiny.train")
    assert res["correct"], res["compared"]
    assert res["notes"]["leaves_without_gradient"] == ["['layers']['bk']"]
    assert res["notes"]["update_norm_without_gradient"] > 0.3


def test_reference_block_against_hand_attention():
    """The reference's own block: one token attends to itself alone, so the
    first position's attention output is its own value row."""
    ref = harness.load_module(harness.BENCH_DIR, "references", "transformer")
    s = W.sizes_from_config(tiny("qwen2-tiny"))
    key = W.seed_key(W.seed_arg(1))
    lp = jax.tree.map(lambda a: a.astype(jnp.float32),
                      W.layer_params(key, np.uint32(0), s, jnp.float32))
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 3, s.hidden))
    pos = jnp.arange(3)[None]
    full = ref.block(x, lp, pos, s)
    alone = ref.block(x[:, :1], lp, pos[:, :1], s)
    np.testing.assert_allclose(np.asarray(full[:, 0]), np.asarray(alone[:, 0]),
                               rtol=2e-5, atol=2e-6)
    # causal: a later token never changes an earlier position
    x2 = x.at[:, 2].set(0.0)
    np.testing.assert_allclose(np.asarray(ref.block(x2, lp, pos, s)[:, :2]),
                               np.asarray(full[:, :2]), rtol=2e-5, atol=2e-6)
