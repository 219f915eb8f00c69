"""Paged-attention decode kernel numerics vs the dense-gather reference
(reference analog: tests/unit/inference/v2 kernels — blocked_flash over the
paged KV cache).

Runs the Pallas kernel in interpreter mode on CPU (same code path the TPU
compiles).  The kernel walks the batch's live tiles (`per_step` table
entries of one row a grid step: 8 at these sizes, so 64 keys at the default
block of 8), so the cases stand on the walk's edges: a row that ends on a
block's or a tile's last key, a table narrower than a tile or no whole
number of tiles, rows of one block beside a row of all, inactive rows at
either end, and a table whose dead entries are garbage."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import paged_attention as pa


pytestmark = pytest.mark.kernels


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    import jax.experimental.pallas as pl
    orig = pl.pallas_call
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(orig, interpret=True))
    yield


def _case(B=3, NH=8, NKV=2, D=64, nb=16, bs=8, MB=6, dtype=jnp.float32,
          seed=0, L=None, lens=None):
    """`L`: a layered arena [L, nb, ...], of which layer 1 is attended."""
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, NH, D), dtype)
    shape = (nb, bs, NKV, D) if L is None else (L, nb, bs, NKV, D)
    ak = jnp.asarray(rng.randn(*shape), dtype)
    av = jnp.asarray(rng.randn(*shape), dtype)
    tables = jnp.asarray(rng.randint(0, nb, (B, MB)), jnp.int32)
    lens = jnp.asarray(rng.randint(0, MB * bs, B) if lens is None else lens,
                       jnp.int32)
    return q, ak, av, tables, lens


def _both(q, ak, av, tables, lens, ref_tables=None):
    """(kernel, reference) outputs; a 5-d arena is attended at layer 1."""
    layer = 1 if ak.ndim == 5 else None
    got = pa.paged_decode_attention(q, ak, av, tables, lens,
                                    layer_idx=layer)
    if layer is not None:
        ak, av = ak[layer], av[layer]
    ref = pa.paged_decode_reference(
        q, ak, av, tables if ref_tables is None else ref_tables, lens)
    return np.asarray(got, np.float32), np.asarray(ref, np.float32)


# the walk's tile at these sizes (8 entries of 8 keys)
TILE = 64


@pytest.mark.parametrize("kw", [
    pytest.param({}, id="default"),
    pytest.param({"L": 3}, id="layered"),
    pytest.param({"MB": 1, "lens": [3, 0, 7]}, id="table-of-1"),
    pytest.param({"MB": 11, "B": 4, "lens": [87, 0, 63, 64]},
                 id="table-no-whole-tiles"),
    pytest.param({"NH": 6, "NKV": 3, "MB": 11, "B": 2, "lens": [80, 9]},
                 id="3-kv-heads"),
    pytest.param({"NH": 8, "NKV": 4, "D": 128, "MB": 10, "L": 2},
                 id="rows-view-d128"),
    pytest.param({"NH": 4, "NKV": 1, "D": 128, "bs": 16, "MB": 5},
                 id="1-kv-head"),
])
def test_matches_reference_gqa(kw):
    got, ref = _both(*_case(**kw))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kw", [
    pytest.param({}, id="flat-arena"),
    pytest.param({"L": 2, "MB": 9}, id="layered"),
])
def test_matches_reference_mha(kw):
    got, ref = _both(*_case(NH=4, NKV=4, **kw))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("lens", [
    # len=0 attends to exactly one key; a full table is fully attended
    pytest.param([0, -1, 47, 5], id="one-key-inactive-full"),
    # the row's last key is a block's last, and the next block's first
    pytest.param([7, 8, 15, 16], id="ends-on-a-block"),
    # ... a tile's last, and the next tile's first
    pytest.param([TILE - 1, TILE, 2 * TILE - 1, 2 * TILE],
                 id="ends-on-a-tile"),
    pytest.param([3, 22 * 8 - 1, 5, 0], id="all-blocks-beside-one-block"),
    pytest.param([-1, -1, 30, 100], id="inactive-first"),
    pytest.param([30, 100, -1, -1], id="inactive-last"),
    pytest.param([-1, 70, -1, 9], id="inactive-between"),
    pytest.param([-1, -1, -1, -1], id="inactive-all"),
])
def test_len_boundaries_and_inactive_rows(lens):
    """len<0 (padded row) yields zeros, wherever it stands in the batch;
    every other row agrees with the reference, wherever it ends."""
    q, ak, av, tables, lens = _case(B=4, MB=22, nb=32, lens=lens)
    got, ref = _both(q, ak, av, tables, lens)
    idle = np.asarray(lens) < 0
    assert np.all(got[idle] == 0.0)
    np.testing.assert_allclose(got[~idle], ref[~idle], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("junk", [10 ** 6, -7])
@pytest.mark.parametrize("kw", [
    pytest.param({}, id="flat-arena"),
    pytest.param({"L": 2, "MB": 20}, id="layered"),
])
def test_garbage_table_entries_are_harmless(kw, junk):
    """Entries past the live blocks may be arbitrary (even out of range):
    masking by len must make them irrelevant."""
    q, ak, av, tables, _ = _case(**kw)
    lens = jnp.asarray([7, 7, 7], jnp.int32)          # only block 0 is live
    dirty = tables.at[:, 1:].set(junk)
    got, ref = _both(q, ak, av, dirty, lens,
                     ref_tables=jnp.clip(dirty, 0, ak.shape[-4] - 1))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kw", [
    pytest.param({}, id="default"),
    pytest.param({"NH": 8, "NKV": 4, "D": 128, "MB": 10, "L": 2},
                 id="rows-view-d128"),
    pytest.param({"NH": 6, "NKV": 3, "MB": 11, "L": 2}, id="3-kv-heads"),
])
def test_bf16(kw):
    got, ref = _both(*_case(dtype=jnp.bfloat16, **kw))
    np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_dead_table_entry_changes_nothing(dtype):
    """The same rows under a table of width 10 and of width 32 give the
    same bits: entries past a row's last live block are no part of the
    walk, whatever they hold and however many there are."""
    q, ak, av, tables, lens = _case(B=5, MB=10, nb=32, dtype=dtype, L=2,
                                    lens=[79, 0, -1, 64, 17])
    rng = np.random.RandomState(1)
    wide = jnp.concatenate(
        [tables, jnp.asarray(rng.randint(-9, 99, (5, 22)), jnp.int32)], 1)
    # within the narrow table too, what lies past a row's blocks is dead
    live = np.arange(10)[None] <= (np.asarray(lens) // 8)[:, None]
    wide = wide.at[:, :10].set(jnp.where(live, tables, 31 - tables))
    narrow = pa.paged_decode_attention(q, ak, av, tables, lens, layer_idx=1)
    got = pa.paged_decode_attention(q, ak, av, wide, lens, layer_idx=1)
    assert got.dtype == narrow.dtype == dtype
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(narrow, np.float32))


@pytest.mark.parametrize("shape, want", [
    # (bs, NKV, D, itemsize, MB) -> blocks a grid step
    pytest.param((64, 4, 128, 2, 32), 8, id="qwen2-7b-cell"),
    pytest.param((64, 4, 128, 2, 512), 8, id="32k-table"),
    pytest.param((64, 4, 128, 2, 5), 5, id="narrow-table"),
    pytest.param((8, 2, 64, 4, 6), 6, id="small-blocks"),
    pytest.param((8, 2, 64, 4, 1), 1, id="table-of-1"),
    pytest.param((64, 32, 128, 2, 32), 2, id="32-kv-heads"),
    pytest.param((128, 8, 128, 2, 64), 4, id="128-key-blocks"),
])
def test_the_tile_follows_from_the_static_shapes(shape, want):
    assert pa._blocks_per_step(*shape) == want


def test_the_walk_lists_live_tiles_and_copies_live_blocks_only():
    """`_walk`: one item a live tile, in row order; a slot past a row's
    last live block holds what it held an item earlier, so the pipeline
    copies a block exactly where a live table entry stands."""
    bs, per_step, B, MB = 8, 8, 5, 10
    lens = jnp.asarray([79, 0, -1, 64, 17], jnp.int32)
    tables = jnp.asarray(
        np.random.RandomState(0).permutation(B * MB).reshape(B, MB) + 100,
        jnp.int32)                                   # every entry distinct
    count, rows, tiles, blocks = (np.asarray(x) for x in
                                  pa._walk(tables, lens, bs, per_step))
    n_blocks = [10, 1, 0, 9, 3]
    assert count == 6
    assert rows[:6].tolist() == [0, 0, 1, 3, 3, 4]
    assert tiles[:6].tolist() == [0, 1, 0, 0, 1, 0]
    N = B * 2
    slots = blocks.reshape(per_step, N)              # [slot, item]
    copies = 0
    for i in range(6):
        for s in range(per_step):
            entry = tiles[i] * per_step + s
            before = slots[s, i - 1] if i else 0
            if entry < n_blocks[rows[i]]:
                assert slots[s, i] == tables[rows[i], entry]
                copies += slots[s, i] != before
            else:
                assert slots[s, i] == before
    assert copies == sum(n_blocks)
