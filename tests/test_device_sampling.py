"""Tests: sampling on the device in the default serve step (ISSUE 28).

The per-step programs return each row's argmax beside its logits
(`ragged_ops.greedy_tokens`); `engine.step` fetches those [N] int32 and
leaves the logits on the device, a row crossing only when somebody
reads it (`engine_v2.LogitsRows`).  The serve loop takes the engine's
token for the rows whose sampler is the plain argmax and samples every
other row on the host from that row's own logits.  Locked here: greedy
serving is token-for-token the host path's and fetches B * 4 bytes a
step; stochastic, seeded, top-k and grammar-masked rows keep their
tokens; the engine's put/step/query contract still hands out host rows;
an engine without the capability is served as before."""
import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.analysis.transfer_guard import no_host_transfers
from deepspeed_tpu.config.config import ServingConfig, StructuredConfig
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2 import engine_v2
from deepspeed_tpu.models import Transformer, TransformerConfig
from deepspeed_tpu.serving import RequestState, ServeLoop
from deepspeed_tpu.serving.structured import ResponseFormat

from test_serving import FakeClock, FakeEngine

pytestmark = pytest.mark.serving

VOCAB = 128
EOS = 0


@pytest.fixture(scope="module")
def tiny():
    cfg = TransformerConfig(vocab_size=VOCAB, hidden_size=64, num_layers=2,
                            num_heads=4, max_seq_len=128,
                            dtype=jnp.float32)
    model = Transformer(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


def _engine(tiny, **kw):
    model, params = tiny
    base = dict(num_blocks=32, block_size=8, max_blocks_per_seq=8,
                max_seqs=4, prefill_chunk_size=16)
    base.update(kw)
    return InferenceEngineV2(model, params=params,
                             config=RaggedInferenceEngineConfig(**base))


class HostRowsOnly:
    """The engine as the serve loop saw it before: `put` and `step`
    return plain {uid: host row}, so the loop samples every row on the
    host — the parent's path, on the same weights."""

    def __init__(self, engine):
        self._engine = engine

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def put(self, *a, **k):
        return dict(self._engine.put(*a, **k).items())

    def step(self, *a, **k):
        return dict(self._engine.step(*a, **k).items())


@contextlib.contextmanager
def recorded_fetches(monkeypatch):
    """(program, bytes) of every `engine.fetch` span the engine opens."""
    seen = []
    real = engine_v2.span

    def spy(name, **attrs):
        if name == "engine.fetch":
            seen.append((attrs["program"], attrs["bytes"]))
        return real(name, **attrs)

    with monkeypatch.context() as m:
        m.setattr(engine_v2, "span", spy)
        yield seen


def _serve(engine, requests, **cfg):
    loop = ServeLoop(engine, ServingConfig(audit_blocks=True, **cfg),
                     clock=FakeClock())
    reqs = [loop.submit(p, **kw) for p, kw in requests]
    loop.run_until_idle(max_steps=300)
    assert all(r.state is RequestState.DONE for r in reqs)
    return loop, [list(map(int, r.output_tokens)) for r in reqs]


def _prompts(seed, lengths):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, VOCAB, n).astype(np.int32) for n in lengths]


# -- (a) greedy: the device's tokens are the host path's -------------------
@pytest.mark.parametrize("engine_kw, lengths", [
    ({}, (9, 21, 5)),                                  # prefill_full
    ({"full_prompt_prefill": False}, (9, 21, 5)),      # prefill_chunks
    ({"max_prefill_tokens_per_step": 16}, (40, 7)),    # a prompt over steps
    ({}, (3, 4, 5, 6, 7, 8)),                          # more than max_seqs
], ids=["full", "chunks", "chunked_long", "queued"])
def test_default_loop_greedy_is_the_host_path_and_fetches_tokens_only(
        tiny, monkeypatch, engine_kw, lengths):
    requests = [(p, dict(max_new_tokens=7)) for p in _prompts(3, lengths)]
    _, want = _serve(HostRowsOnly(_engine(tiny, **engine_kw)), requests)
    eng = _engine(tiny, **engine_kw)
    fetches0 = eng.profile["d2h_fetches"]
    with recorded_fetches(monkeypatch) as fetches:
        loop, got = _serve(eng, requests)        # ServingConfig() defaults
    assert got == want
    n_tok = sum(len(t) for t in got)
    counters = loop.telemetry.counters
    assert counters["sampled_on_device"] == n_tok == 7 * len(lengths)
    assert counters["sampled_on_host"] == 0
    assert loop.telemetry.summary()["sampled_on_device"] == n_tok
    # what crossed: each program's [N] int32 tokens, never a logits row
    assert len(fetches) == eng.profile["d2h_fetches"] - fetches0
    assert {p for p, _ in fetches} <= {"prefill_full", "prefill_chunks",
                                       "decode_step"}
    decode = [b for p, b in fetches if p == "decode_step"]
    assert decode and set(decode) == {eng.config.max_seqs * 4}
    assert max(b for _, b in fetches) < VOCAB * 4


# -- (b) a mixed batch: every other row keeps the host sampler --------------
FMT = ResponseFormat.regex(r"ab(ab)?c")
MIXED = [
    dict(max_new_tokens=8),                                       # greedy
    dict(max_new_tokens=8, temperature=0.9),                      # loop RNG
    dict(max_new_tokens=8, temperature=1.1, seed=31337),          # stream
    dict(max_new_tokens=8, temperature=0.8, top_k=5),
    dict(max_new_tokens=8, temperature=0.7, top_k=9, seed=7),
    dict(max_new_tokens=8, eos_token_id=EOS, response_format=FMT),
    dict(max_new_tokens=8, eos_token_id=EOS, response_format=FMT,
         temperature=0.9, seed=11),
    dict(max_new_tokens=8),                                       # greedy
]


@pytest.mark.parametrize("guard", ["off", "disallow"])
@pytest.mark.parametrize("rows", [(0, 1, 2, 7), (3, 4, 5, 6, 7), range(8)],
                         ids=["seeded", "topk_grammar", "all"])
def test_mixed_batch_samples_each_row_where_its_result_is_defined(
        tiny, monkeypatch, rows, guard):
    kws = [MIXED[i] for i in rows]
    requests = list(zip(_prompts(5, [6 + 3 * i for i in rows]), kws))
    cfg = dict(structured=StructuredConfig(), transfer_guard=guard)
    _, want = _serve(HostRowsOnly(_engine(tiny)), requests, **cfg)
    eng = _engine(tiny)
    with recorded_fetches(monkeypatch) as fetches:
        loop, got = _serve(eng, requests, **cfg)
    assert got == want
    on_device = sum(len(t) for t, kw in zip(got, kws)
                    if kw.get("temperature", 0.0) <= 0.0
                    and "response_format" not in kw)
    counters = loop.telemetry.counters
    assert counters["sampled_on_device"] == on_device > 0
    assert counters["sampled_on_host"] \
        == sum(len(t) for t in got) - on_device > 0
    # a host-sampled row brings its own [V] row and nothing wider
    assert sum(1 for p, _ in fetches if p == "logits_rows") \
        == counters["sampled_on_host"]
    assert {b for p, b in fetches if p == "logits_rows"} == {VOCAB * 4}


# -- (c) the engine's own contract: rows on request ------------------------
@pytest.mark.parametrize("engine_kw, program", [
    ({}, "prefill_full"),
    ({"full_prompt_prefill": False}, "prefill_chunks"),
], ids=["full", "chunks"])
def test_put_step_query_hand_out_the_programs_rows_when_read(
        tiny, monkeypatch, engine_kw, program):
    eng = _engine(tiny, max_prefill_tokens_per_step=32, **engine_kw)
    produced = {}

    def keep(name, fn):
        def call(*a, **k):
            out = fn(*a, **k)
            produced[name] = (np.asarray(out[0]), np.asarray(out[1]))
            return out
        return call

    from deepspeed_tpu.inference.v2 import ragged_ops
    monkeypatch.setattr(ragged_ops, "prefill_full",
                        keep("prefill_full", ragged_ops.prefill_full))
    for name in ("prefill_chunks", "decode_step"):
        setattr(eng._programs, name,
                keep(name, getattr(eng._programs, name)))

    short, long_ = _prompts(9, (11, 40))     # 40 > the step's 32 tokens
    with recorded_fetches(monkeypatch) as fetches:
        out = eng.put([1, 2], [short, long_])
        assert set(out) == {1} and 1 in out and len(out) == 1
        assert eng.query(2) is None          # its prompt is not complete
        # tokens only (the long prompt's first chunks ride the same step)
        assert program in [p for p, _ in fetches]
        assert max(b for _, b in fetches) < VOCAB * 4
        before = eng.profile["d2h_fetches"]
        with no_host_transfers(device_to_host="disallow",
                               host_to_device="disallow"):
            row = eng.query(1)
        assert eng.profile["d2h_fetches"] == before + 1
        assert fetches[-1] == ("logits_rows", VOCAB * 4)
        logits, toks = produced[program]
        np.testing.assert_array_equal(row, logits[0])
        np.testing.assert_array_equal(out[1], logits[0])
        assert out.greedy(1) == int(np.argmax(row)) == int(toks[0])
        assert eng.profile["d2h_fetches"] == before + 1   # read once
        first = {1: out.greedy(1)}
        while eng.query(2) is None:
            out = eng.step()
        first[2] = out.greedy(2)
        assert first[2] == int(np.argmax(out[2]))

        # decode: both rows of one [max_seqs, V] output; items() brings
        # the program's whole output once, not a row at a time
        out = eng.put([1, 2], [np.array([first[u]], np.int32)
                               for u in (1, 2)])
        logits, toks = produced["decode_step"]
        assert fetches[-1] == ("decode_step", eng.config.max_seqs * 4)
        before = eng.profile["d2h_fetches"]
        rows = dict(out.items())
        assert eng.profile["d2h_fetches"] == before + 1
        assert fetches[-1] == ("logits_rows",
                               eng.config.max_seqs * VOCAB * 4)
        for i, u in enumerate((1, 2)):
            np.testing.assert_array_equal(rows[u], logits[i])
            np.testing.assert_array_equal(eng.query(u), logits[i])
            assert out.greedy(u) == int(toks[i])
        assert eng.profile["d2h_fetches"] == before + 1
        # a host row put in a row's place has no token of the program's
        out[1] = np.zeros(VOCAB, np.float32)
        assert out.greedy(1) is None and not out[1].any()
    eng.flush(1)
    assert eng.query(1) is None and out.greedy(3) is None


# -- (d) an engine without the capability ----------------------------------
@pytest.mark.parametrize("kw", [dict(), dict(temperature=0.7, top_k=3)],
                         ids=["greedy", "stochastic"])
def test_engine_that_returns_host_rows_is_served_by_the_host_sampler(kw):
    eng = FakeEngine(vocab=32)
    loop = ServeLoop(eng, ServingConfig(), clock=FakeClock())
    req = loop.submit(np.array([3, 4, 5]), max_new_tokens=4, **kw)
    loop.run_until_idle(max_steps=50)
    assert req.state is RequestState.DONE
    if not kw:       # one-hot logits: next token = input + 1
        assert list(req.output_tokens) == [6, 7, 8, 9]
    assert loop.telemetry.counters["sampled_on_host"] == 4
    assert loop.telemetry.counters["sampled_on_device"] == 0
