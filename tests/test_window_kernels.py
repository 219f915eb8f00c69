"""The two kernels a window layer runs, in Pallas interpret mode on the CPU,
against the dense mask: the paged decode kernel with a static `window`
(`ops/paged_attention.py`: the walk starts at the window's first block) and
the chunk attention kernel (`ops/chunk_attention.py`: prompt chunks over
their rows' keys by position); then the static-kind stack's decode and
chunk programs through both, against the CPU's dense path.

What interpret mode cannot vouch for (tiling, VMEM) the v5e compiler is
held to in `tests/test_tpu_compile.py`, at the cell's shapes."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import chunk_attention as ca
from deepspeed_tpu.ops import paged_attention as pa

pytestmark = pytest.mark.kernels


@pytest.fixture
def interpret(monkeypatch):
    import jax.experimental.pallas as pl
    monkeypatch.setattr(pl, "pallas_call", functools.partial(
        pl.pallas_call, interpret=True))


B, NH, NKV, D, BS, MB, NB, L = 5, 4, 2, 64, 8, 12, 40, 3
W = 20


def _decode_operands(seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, NH, D), jnp.float32)
    k = jnp.asarray(rng.randn(L, NB, BS, NKV, D), jnp.float32)
    v = jnp.asarray(rng.randn(L, NB, BS, NKV, D), jnp.float32)
    tables = rng.randint(0, NB, (B, MB)).astype(np.int32)
    return q, k, v, tables


# the window's edges (pos = W - 2 .. W + 1: the first masked key appears at
# pos = W), block boundaries (7 | 8, 63 | 64, 47 | 48), a row at position 0,
# inactive rows, a row at the table's end
@pytest.mark.parametrize("lens", [
    [W - 2, W - 1, W, W + 1, 95], [-1, 7, 8, 63, 64], [0, 19, 20, 21, -1],
    [40, 41, 47, 48, 49]], ids=str)
@pytest.mark.parametrize("window", [None, W, 16, 8, 1])
def test_the_windowed_walk_matches_the_dense_mask(interpret, lens, window):
    q, k, v, tables = _decode_operands()
    lens = jnp.asarray(lens, jnp.int32)
    got = pa.paged_decode_attention(q, k, v, jnp.asarray(tables), lens,
                                    layer_idx=jnp.asarray(1), window=window)
    want = pa.paged_decode_reference(q, k[1], v[1], jnp.asarray(tables),
                                     lens, window=window)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    assert not np.asarray(got)[np.asarray(lens) < 0].any()
    if window is None:
        return
    # the dense mask, by hand: key p of row b counts iff lens - W < p <= lens
    keys = np.asarray(k[1])[tables].reshape(B, MB * BS, NKV, D)
    vals = np.asarray(v[1])[tables].reshape(B, MB * BS, NKV, D)
    for b, n in enumerate(np.asarray(lens)):
        if n < 0:
            continue
        lo = max(0, n - window + 1)
        kk = np.repeat(keys[b, lo:n + 1], NH // NKV, axis=1)
        vv = np.repeat(vals[b, lo:n + 1], NH // NKV, axis=1)
        s = np.einsum("nd,mnd->nm", np.asarray(q[b]), kk) / np.sqrt(D)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(np.asarray(got[b]),
                                   np.einsum("nm,mnd->nd", p, vv),
                                   atol=2e-5, rtol=2e-5)
    # the entries before the window's first block are never read: garbage
    # there (a cache that has handed those blocks back) changes no bit
    first = np.maximum(np.asarray(lens) - window + 1, 0) // BS
    dead = tables.copy()
    for b in range(B):
        dead[b, :first[b]] = 10 ** 6
    again = pa.paged_decode_attention(q, k, v, jnp.asarray(dead), lens,
                                      layer_idx=jnp.asarray(1), window=window)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(again))


def test_a_window_costs_the_walk_its_own_blocks_only():
    """The list the grid walks: with a window a long row has the window's
    tiles, without one all of its own."""
    tables = jnp.zeros((2, 64), jnp.int32)
    lens = jnp.asarray([500, 30], jnp.int32)
    full = pa._walk(tables, lens, 8, 4)[0]
    first = (jnp.maximum(lens - 40 + 1, 0) // 8).astype(jnp.int32)
    windowed = pa._walk(tables, lens, 8, 4, first)[0]
    # row 0: blocks 0..62 (16 tiles of 4) against 57..62 (2 tiles); row 1:
    # blocks 0..3 (1 tile) both ways
    assert int(full) == 16 + 1 and int(windowed) == 2 + 1


@pytest.mark.parametrize("R,C,NH,NKV,D,T,window", [
    (3, 32, 4, 2, 32, 96, None), (3, 32, 4, 2, 32, 96, 16),
    (2, 64, 8, 2, 64, 1024 + 64, 40), (2, 64, 8, 2, 64, 1536, None),
    (1, 16, 4, 4, 32, 48, 5)])
def test_chunk_attention_matches_the_dense_mask(R, C, NH, NKV, D, T, window):
    """Fresh prompts (position 0), chunks deep in their rows, a chunk at
    the buffer's end, padded and empty chunks; with and without a window;
    buffers of one key tile and of three."""
    rng = np.random.RandomState(0)
    T = -(-T // ca.key_tile(T)) * ca.key_tile(T)
    q = jnp.asarray(rng.randn(R, C, NH, D), jnp.float32)
    k = jnp.asarray(rng.randn(R, T, NKV, D), jnp.float32)
    v = jnp.asarray(rng.randn(R, T, NKV, D), jnp.float32)
    for pos0, n_valid in (([0] * R, [C] * R),
                          ([0, 7, T - C][:R], [C, 3, C][:R]),
                          ([T - C - 5] * R, [0, C - 1, 1][:R])):
        pos0 = jnp.asarray(pos0, jnp.int32)
        n_valid = jnp.asarray(n_valid, jnp.int32)
        got = ca.chunk_attention(q, k, v, pos0, n_valid, window=window,
                                 interpret=True)
        want = ca.chunk_attention_reference(q, k, v, pos0, n_valid,
                                            window=window)
        real = np.arange(C)[None] < np.asarray(n_valid)[:, None]
        assert np.abs(np.asarray(got) - np.asarray(want))[real].max(
            initial=0.0) < 2e-5
        assert got.shape == q.shape and got.dtype == q.dtype


def _steps_by_hand(pos0, n_valid, C, T, bq, bk, window):
    """(live, interior) `[tiles, steps]` of one row from the dense mask and
    nothing else: step `j` of a tile takes the key tile `j` past the one
    that holds the first key its first query sees; it is live where some
    REAL query of the tile sees some key of it, interior where EVERY query
    of the tile, padded ones too, sees every key of it."""
    q_pos = pos0 + np.arange(C)[:, None]
    key = np.arange(T + 2 * bk)[None]          # (past the buffer: unseen)
    seen = key <= q_pos
    if window is not None:
        seen &= key > q_pos - window
    tiles, steps = C // bq, T // bk
    live, interior = (np.zeros((tiles, steps), bool) for _ in range(2))
    for t in range(tiles):
        if t * bq >= n_valid:
            continue
        first = int(np.argmax(seen[t * bq])) // bk
        for j in range(steps):
            tile = seen[t * bq:(t + 1) * bq, (first + j) * bk:][:, :bk]
            live[t, j] = tile[:max(min(n_valid - t * bq, bq), 0)].any()
            interior[t, j] = live[t, j] and tile.all() \
                and tile.shape[1] == bk
    return live, interior


# query tiles of 8 against key tiles of 16 in a buffer of six: a fresh
# prompt, chunks whose first query stands ON a key tile's edge (16, 64)
# and off it (37, 61), padding from mid-tile (13, 21), one real query, an
# empty chunk, a chunk at the buffer's end; windows below the query tile,
# equal to it, ON the key tile (16, 32), across tiles, past the buffer
_POSITIONS = [(0, 32), (0, 13), (16, 32), (37, 21), (64, 32), (61, 1),
              (64, 24), (5, 0)]
_WINDOWS = [None, 1, 4, 8, 16, 24, 32, 40, 10 ** 6]


@pytest.mark.parametrize("pos0,n_valid", _POSITIONS)
@pytest.mark.parametrize("window", _WINDOWS)
def test_a_step_is_interior_exactly_where_no_edge_crosses_it(
        pos0, n_valid, window):
    C, T, bq, bk = 32, 96, 8, 16
    t, j = np.arange(C // bq)[:, None], np.arange(T // bk)[None]
    live, interior = ca.step_kind(pos0, n_valid, t, j, bq=bq, bk=bk,
                                  window=window, xp=np)
    want_live, want_interior = _steps_by_hand(pos0, n_valid, C, T, bq, bk,
                                              window)
    np.testing.assert_array_equal(live, want_live)
    np.testing.assert_array_equal(interior, want_interior)
    # jax (the kernel's side) gives what numpy (the host's) gives
    got = ca.step_kind(pos0, n_valid, jnp.asarray(t), jnp.asarray(j), bq=bq,
                       bk=bk, window=window)
    np.testing.assert_array_equal(np.asarray(got[0]), live)
    np.testing.assert_array_equal(np.asarray(got[1]), interior)


@pytest.fixture
def small_tiles(monkeypatch):
    """Query tiles of 8 rows a head and key tiles of 16: many tiles at
    sizes interpret mode runs in no time."""
    monkeypatch.setattr(ca, "ROWS_PER_STEP", 16)
    monkeypatch.setattr(ca, "KEY_TILE", 16)


def _masked_everywhere(monkeypatch):
    """Every live step through the edge body: the kernel before it had
    two."""
    rule = ca.step_kind

    def no_interior(*a, **kw):
        live, interior = rule(*a, **kw)
        return live, interior & False
    monkeypatch.setattr(ca, "step_kind", no_interior)


@pytest.mark.parametrize("window", _WINDOWS)
def test_interior_steps_give_the_bits_of_the_masked_body(
        small_tiles, monkeypatch, window):
    """The same inputs through both bodies and through the masked one
    alone: equal bit for bit, padded queries' rows too, and at the real
    queries the dense mask's numbers; the host's count is the count of
    each body's steps by the dense mask."""
    R, C, NH, NKV, D, T = len(_POSITIONS), 32, 4, 2, 32, 96
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(R, C, NH, D), jnp.float32)
    k = jnp.asarray(rng.randn(R, T, NKV, D), jnp.float32)
    v = jnp.asarray(rng.randn(R, T, NKV, D), jnp.float32)
    pos0, n_valid = (jnp.asarray(a, jnp.int32) for a in zip(*_POSITIONS))
    assert (ca._query_tile(C, NH // NKV), ca.key_tile(T)) == (8, 16)
    got = ca.chunk_attention(q, k, v, pos0, n_valid, window=window,
                             interpret=True)
    want = ca.chunk_attention_reference(q, k, v, pos0, n_valid,
                                        window=window)
    real = np.arange(C)[None] < np.asarray(n_valid)[:, None]
    assert np.abs(np.asarray(got) - np.asarray(want))[real].max() < 2e-5
    by_hand = [_steps_by_hand(p, n, C, T, 8, 16, window)
               for p, n in _POSITIONS]
    live = sum(int(l.sum()) for l, _ in by_hand)
    interior = sum(int(i.sum()) for _, i in by_hand)
    assert ca.count_steps(*zip(*_POSITIONS), C, NH // NKV, 16, window) \
        == (live, live - interior)
    assert live > interior and (interior > 0) == (window is None
                                                  or window >= 24)
    _masked_everywhere(monkeypatch)
    masked = ca.chunk_attention(q, k, v, pos0, n_valid, window=window,
                                interpret=True)
    # (XLA:CPU compiles the two bodies' 16-wide dots differently, an ulp
    # apart: the bits are compared at the kernel's own tiles, below)
    assert np.abs(np.asarray(got) - np.asarray(masked)).max() < 1e-6


@pytest.mark.parametrize("window", [None, 1100, 100, 4096])
def test_interior_steps_at_the_kernels_own_tiles(monkeypatch, window):
    """Key tiles of 512 and query tiles of 128 x 8 heads, a chunk deep in
    a buffer of five key tiles beside a fresh one cut mid-tile: bit-equal
    to the masked body alone."""
    R, C, NH, NKV, D, T = 2, 256, 16, 2, 64, 2560
    rng = np.random.RandomState(6)
    q = jnp.asarray(rng.randn(R, C, NH, D), jnp.float32)
    k = jnp.asarray(rng.randn(R, T, NKV, D), jnp.float32)
    v = jnp.asarray(rng.randn(R, T, NKV, D), jnp.float32)
    rows = [(2048 + 37, 256), (0, 141)]
    pos0, n_valid = (jnp.asarray(a, jnp.int32) for a in zip(*rows))
    assert (ca._query_tile(C, NH // NKV), ca.key_tile(T)) == (128, 512)
    got = ca.chunk_attention(q, k, v, pos0, n_valid, window=window,
                             interpret=True)
    want = ca.chunk_attention_reference(q, k, v, pos0, n_valid,
                                        window=window)
    real = np.arange(C)[None] < np.asarray(n_valid)[:, None]
    assert np.abs(np.asarray(got) - np.asarray(want))[real].max() < 2e-5
    by_hand = [_steps_by_hand(p, n, C, T, 128, 512, window) for p, n in rows]
    live = sum(int(l.sum()) for l, _ in by_hand)
    interior = sum(int(i.sum()) for _, i in by_hand)
    assert ca.count_steps(pos0, n_valid, C, NH // NKV, 512, window) \
        == (live, live - interior)
    # the deep chunk's two tiles see four whole key tiles and cross the
    # diagonal in the fifth; a window of 1100 leaves each of them one whole
    # tile between its edges, a window of 100 none
    assert (live, interior) == {None: (12, 8), 4096: (12, 8), 1100: (9, 2),
                                100: (5, 0)}[window]
    _masked_everywhere(monkeypatch)
    masked = ca.chunk_attention(q, k, v, pos0, n_valid, window=window,
                                interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(masked))


def test_chunk_attention_reference_is_the_plain_softmax():
    """The yardstick itself, against numpy at one query."""
    rng = np.random.RandomState(1)
    q = rng.randn(1, 8, 2, 16).astype(np.float32)
    k = rng.randn(1, 40, 2, 16).astype(np.float32)
    v = rng.randn(1, 40, 2, 16).astype(np.float32)
    got = np.asarray(ca.chunk_attention_reference(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray([20]),
        jnp.asarray([8]), window=6))
    i, h = 5, 1                       # the query at position 25: keys 20..25
    s = k[0, 20:26, h] @ q[0, i, h] / 4.0
    p = np.exp(s - s.max())
    np.testing.assert_allclose(got[0, i, h], (p / p.sum()) @ v[0, 20:26, h],
                               rtol=1e-5, atol=1e-6)


def test_chunk_attention_refuses_ragged_tiles():
    q = jnp.zeros((1, 12, 2, 16))
    k = jnp.zeros((1, 48, 2, 16))
    with pytest.raises(ValueError, match="whole tiles"):
        ca.chunk_attention(q, k, k, jnp.asarray([0]), jnp.asarray([12]),
                           interpret=True)
    assert ca.key_tile(40) == 40 and ca.key_tile(13000) == ca.KEY_TILE == 512
    assert ca._query_tile(12288, 7) == 128 and ca._query_tile(24, 2) == 8


def test_the_stack_through_both_kernels_matches_the_dense_path(
        interpret, monkeypatch):
    """Decode and chunk programs of the static-kind stack at head width
    64 (the kernels' smallest) on a cache a prefill has filled: once on
    the CPU's dense path, once through the kernels (the platform gate
    flipped); the window kind's dead table entries stay -1."""
    import deepspeed_tpu.utils.device as device_mod
    from deepspeed_tpu.inference.v2 import (RaggedInferenceEngineConfig,
                                            build_engine, hybrid_ops)
    from test_grouped_matmul import arena_copy
    eng = build_engine(
        "smallthinker", "tiny", dtype=jnp.float32, attn_head_dim=64,
        sliding_window=20, engine_config=RaggedInferenceEngineConfig(
            num_blocks=48, block_size=8, max_blocks_per_seq=24, max_seqs=4,
            prefill_chunk_size=64, max_prefill_tokens_per_step=64))
    rng = np.random.RandomState(3)
    p = rng.randint(0, 512, 90).astype(np.int32)
    out = eng.put([1], [p])
    while 1 not in out:
        out.update(eng.step())
    first = int(np.asarray(out[1]).argmax())
    eng.state.ensure_capacity(eng.state.seqs[1], 91 + 32, first_query=90)
    table = eng.state.block_table(eng.state.seqs[1])
    assert (table[1, :8] == -1).all() and (table[1, 9:11] >= 0).all()
    dead = np.full_like(table, -1)
    tables = jnp.asarray(np.stack([table, dead, dead, dead]))
    on = jnp.asarray([True, False, False, False])
    # (the counters' number follows the platform's gate, flipped below)
    arena = functools.partial(arena_copy, eng)
    decode = (jnp.asarray([first, 0, 0, 0]), jnp.asarray([90, 0, 0, 0]),
              tables, on)
    chunk = (jnp.asarray(rng.randint(0, 512, (4, 32)).astype(np.int32)),
             jnp.asarray([90, 0, 0, 0]), jnp.asarray([27, 0, 0, 0]), tables,
             on)
    fused_cfg = dataclasses.replace(eng.cfg, attn_impl="pallas")
    dense, _ = hybrid_ops.decode_core(eng.cfg, eng.params, arena(), *decode)
    dense_c = hybrid_ops.prefill_chunks(eng.cfg, eng.params, arena(), *chunk)
    with pytest.raises(ValueError, match="chunk attention kernels"):
        hybrid_ops.decode_core(fused_cfg, eng.params, arena(), *decode)
    monkeypatch.setattr(device_mod, "platform", lambda: "tpu")
    # (chunk_attention's own `interpret` argument beats the patched call's)
    monkeypatch.setattr(ca, "chunk_attention", functools.partial(
        ca.chunk_attention, interpret=True))
    fused, _ = hybrid_ops.decode_core(fused_cfg, eng.params, arena(),
                                      *decode)
    fused_c = hybrid_ops.prefill_chunks(fused_cfg, eng.params, arena(),
                                        *chunk)
    # (the program's own initialiser: logits that spread by 0.16)
    assert np.abs(np.asarray(fused - dense))[0].max() < 2e-5
    assert np.abs(np.asarray(fused_c[0] - dense_c[0]))[0].max() < 2e-5
    assert np.asarray(dense)[0].std() > 0.1


def test_a_chunk_dispatch_says_how_often_the_mask_runs():
    """The `engine.dispatch` span of every chunk program of a two-kind
    stack carries the kernel's live key steps and those an edge crosses,
    summed over the slots and the layers of both kinds: the dense mask's
    count, on the CPU as on the chip."""
    from deepspeed_tpu.inference.v2 import (RaggedInferenceEngineConfig,
                                            build_engine, hybrid_ops)
    from deepspeed_tpu.utils import spans
    eng = build_engine(
        "smallthinker", "tiny", dtype=jnp.float32, attn_head_dim=64,
        sliding_window=20, engine_config=RaggedInferenceEngineConfig(
            num_blocks=48, block_size=8, max_blocks_per_seq=24, max_seqs=4,
            prefill_chunk_size=64, max_prefill_tokens_per_step=64))
    seen, orig = [], spans._Span.set_metadata

    def recording(self, **attrs):
        if self.name == "engine.dispatch" and "attn_steps_live" in attrs:
            seen.append((self.attrs["program"], attrs))
        return orig(self, **attrs)
    spans._Span.set_metadata = recording
    try:
        out = eng.put([1], [np.arange(90, dtype=np.int32)])
        while 1 not in out:
            out.update(eng.step())
    finally:
        spans._Span.set_metadata = orig
    # two programs: 64 tokens at position 0, then 26 at 64; keys by
    # position in a buffer of 24 blocks and a chunk, one key tile
    G, T = eng.cfg.num_heads // eng.cfg.kv_heads, 24 * 8 + 64
    bq, layers = ca._query_tile(64, G), hybrid_ops.kind_layers(eng.cfg)
    assert ca.key_tile(T) == T and min(layers) > 0
    want = []
    for pos0, n in ((0, 64), (64, 26)):
        live = masked = 0
        for count, window in zip(layers, (None, 20)):
            l, i = _steps_by_hand(pos0, n, 64, T, bq, T, window)
            live, masked = live + count * l.sum(), masked + count * (l ^ i).sum()
        want.append(("prefill_chunks", dict(attn_steps_live=live,
                                            attn_steps_masked=masked)))
    assert seen == want and want[0][1]["attn_steps_live"] > 0


def test_a_uniform_window_through_the_kernels_matches_the_dense_path(
        interpret, monkeypatch):
    """One window for every layer (mistral's): chunked prefill through the
    paged prefill kernel and greedy bursts through the decode kernel's
    windowed walk give the token ids of the `attn_impl="jnp"` masked
    gather, rows two and three windows long (f32).  Before the decode
    kernel took a window, such a model fell back to the gather."""
    import deepspeed_tpu.utils.device as device_mod
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import Transformer, TransformerConfig
    monkeypatch.setattr(device_mod, "platform", lambda: "tpu")
    calls = []
    real = pa.paged_decode_attention
    monkeypatch.setattr(pa, "paged_decode_attention", lambda *a, **kw: (
        calls.append(kw.get("window")), real(*a, **kw))[1])
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, 131, n).astype(np.int32) for n in (29, 13)]
    outs = {}
    for impl in ("auto", "jnp"):
        cfg = TransformerConfig(
            vocab_size=131, hidden_size=256, num_layers=2, num_heads=4,
            max_seq_len=192, dtype=jnp.float32, attn_impl=impl,
            pos_emb="rope", sliding_window=12)
        model = Transformer(cfg)
        eng = InferenceEngineV2(
            model, params=model.init_params(jax.random.PRNGKey(0)),
            config=RaggedInferenceEngineConfig(
                num_blocks=16, block_size=8, max_blocks_per_seq=8,
                max_seqs=2, prefill_chunk_size=16, decode_burst=4,
                full_prompt_prefill=False))
        outs[impl] = [list(o) for o in
                      eng.generate_batch(prompts, max_new_tokens=8)]
        eng.audit_blocks()
    assert outs["auto"] == outs["jnp"]
    assert calls and set(calls) == {12}        # the kernel arm, windowed
