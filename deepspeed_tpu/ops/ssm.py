"""The selective state-space recurrence of a Mamba-2 mixer, in its two
serving forms (`inference/v2/ssm_ops.py`):

    h_t = exp(dt_t A) h_{t-1} + dt_t * B_t (outer) x_t        h [N, P]
    y_t = C_t h_t                                             y [P]

per head (P channels, a state of N values a channel; `A` < 0 a head, `dt`
> 0 a head and token; B and C shared by the heads of a group).  `D * x`, the
gate and the norm are the caller's.  The state is kept TRANSPOSED, `[N,
P]` with the channels on the lanes, so that `y` (a sum over N) is a sum
over sublanes and comes out as a lane-dense row.  Heads narrower than the
128 lanes lie SIDE BY SIDE in a row (`lane_heads`: two of 64 channels), so
that the stored state `[heads / pack, N, pack * P]` pads no lane; heads of
a row are of one group.  Callers hand x, dt and y a head at a time and the
state packed (`state_shape`, `pack_state`, `unpack_state`).

- `ssd_scan`: a prompt (or a chunk of one) in chunks of `chunk` positions,
  as matmuls (the state-space-dual form).  With `cs` the running sum of
  `dt A` inside a chunk: the chunk's own part `((C B^T) * exp(cs_t - cs_s)
  * dt_s, s <= t) x`, the carried state's part `exp(cs_t) * (C h)`, and the
  state handed on `exp(cs_Q) h + (B * dt_s exp(cs_Q - cs_s))^T x`.  An
  initial state goes in and the final one comes out (the kernel reads and
  writes both in place on the arena, at the rows' slots); a position with
  `dt` 0 (padding) decays nothing and adds nothing, so it leaves the state
  as it was.  Operands in the stored type, float32 accumulation and state.
- `ssm_update`: one token a row, IN PLACE on the arena's state
  `[L, slots, heads / pack, N, pack * P]` (`input_output_aliases`): a grid
  step reads the `[hb, N, pack * P]` tile of the row's slot, updates it and
  writes it back, so the step moves the state once each way and nothing
  else of that size (gather + update + scatter through XLA is three times
  the traffic).  Rows name their slots by a vector (a decode batch is not
  in slot order).  The update is elementwise over a row's lanes, so packed
  heads are one wider head to it.

Each has a dense `jax.numpy` form: what the CPU runs and what the tests
hold the kernels to.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["ssd_scan", "ssd_scan_reference", "ssm_update",
           "ssm_update_reference", "heads_per_step", "lane_heads",
           "state_shape", "pack_state", "unpack_state"]

LANES = 128
# rows of heads a grid step takes (all of one group: they share B and C)
HEADS_PER_STEP = 8


def heads_per_step(heads: int, groups: int) -> int:
    hb = min(HEADS_PER_STEP, heads // groups)
    while (heads // groups) % hb:
        hb -= 1
    return hb


def lane_heads(heads: int, groups: int, head_dim: int) -> int:
    """Heads that share a 128-lane row of the stored state: as many of
    `head_dim` channels as fill it, all of one group."""
    pack = LANES // head_dim if head_dim < LANES and LANES % head_dim == 0 \
        else 1
    while (heads // groups) % pack:
        pack //= 2
    return pack


def state_shape(heads: int, groups: int, n: int, head_dim: int):
    """What a slot stores of one layer: [heads / pack, N, pack * P]."""
    pack = lane_heads(heads, groups, head_dim)
    return (heads // pack, n, pack * head_dim)


def pack_state(h, pack: int):
    """[..., NH, N, P] a head -> [..., NH / pack, N, pack * P] as stored."""
    *lead, NH, N, P = h.shape
    if pack == 1:
        return h
    return jnp.moveaxis(h.reshape(*lead, NH // pack, pack, N, P), -3, -2) \
        .reshape(*lead, NH // pack, N, pack * P)


def unpack_state(h, pack: int):
    """`pack_state`'s inverse."""
    *lead, rows, N, W = h.shape
    if pack == 1:
        return h
    return jnp.moveaxis(h.reshape(*lead, rows, N, pack, W // pack), -2, -3) \
        .reshape(*lead, rows * pack, N, W // pack)


def _chunked(x, dt, b, c, chunk: int):
    """Pad S to whole chunks (dt 0) and split: x [R, nC, Q, NH, P], dt
    [R, nC, Q, NH], b, c [R, nC, Q, G, N]."""
    R, S = x.shape[:2]
    pad = -S % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) *
                               (t.ndim - 2)) for t in (x, dt, b, c))
    split = lambda t: t.reshape((R, (S + pad) // chunk, chunk)  # noqa: E731
                                + t.shape[2:])
    return split(x), split(dt), split(b), split(c)


def ssd_scan_reference(x, dt, a, b, c, h0, chunk: int):
    """The chunked scan, dense.  x [R, S, NH, P]; dt [R, S, NH] float32
    (0 at padding); a [NH] float32 (< 0); b, c [R, S, G, N]; h0 [R, NH, N,
    P] float32.  Returns (y [R, S, NH, P] float32, final state like h0)."""
    R, S, NH, P = x.shape
    G = b.shape[2]
    xs, dts, bs, cs_ = _chunked(x, dt, b, c, chunk)
    f32 = lambda t: t.astype(jnp.float32)                   # noqa: E731
    heads = lambda t: jnp.repeat(t, NH // G, axis=-2)       # noqa: E731
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one(h, inp):
        xq, dq, bq, cq = inp                   # [R, Q, ...]
        xq, bq, cq = f32(xq), heads(f32(bq)), heads(f32(cq))
        cs = jnp.cumsum(dq * a, axis=1)                     # [R, Q, NH]
        diff = cs[:, :, None] - cs[:, None]                 # [R, t, s, NH]
        decay = jnp.exp(jnp.where(tri[None, :, :, None], diff, -jnp.inf))
        gram = jnp.einsum("rthn,rshn->rtsh", cq, bq)
        y = jnp.einsum("rtsh,rshp->rthp", gram * decay * dq[:, None], xq)
        y += jnp.exp(cs)[..., None] * jnp.einsum("rthn,rhnp->rthp", cq, h)
        last = cs[:, -1]                                    # [R, NH]
        w = dq * jnp.exp(last[:, None] - cs)                # [R, Q, NH]
        h = jnp.exp(last)[..., None, None] * h + jnp.einsum(
            "rshn,rshp->rhnp", bq * w[..., None], xq)
        return h, y

    h, ys = jax.lax.scan(one, f32(h0), tuple(
        jnp.moveaxis(t, 1, 0) for t in (xs, dts, bs, cs_)))
    return jnp.moveaxis(ys, 0, 1).reshape(R, -1, NH, P)[:, :S], h


def _scan_kernel(slot_ref, meta_ref, x_ref, b_ref, c_ref, s_ref, y_ref,
                 h_ref, *, hb: int, rows: int, pack: int):
    """One (row, tile of `hb` lane rows, chunk): meta [hb * pack, 2, Q] =
    (running sum of dt A, dt) as rows, a head each; x [hb, Q, pack * P], a
    lane row's heads side by side; b, c [Q, N]; the state rides `h_ref`,
    the slot's tile (its block stays put along the chunk axis): taken from
    `s_ref` at the first chunk where the row continues a prompt, zeros
    where it starts one.  Heads of one lane row share x's and h's tiles
    and differ in their decay: each runs the row's matmuls whole (a
    64-wide operand fills the MXU's columns no better) and keeps its own
    lanes."""
    from jax.experimental import pallas as pl
    r, j = pl.program_id(0), pl.program_id(2)

    @pl.when(j == 0)
    def _start():
        h_ref[0, 0] = jnp.where(slot_ref[1 + rows + r] > 0, s_ref[0, 0], 0.0)

    b, c = b_ref[0, 0], c_ref[0, 0]
    Q = b.shape[0]
    gram = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    b_t = b.astype(jnp.float32).T                           # [N, Q]
    t_i = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    s_i = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    P = x_ref.shape[-1] // pack
    for i in range(hb):
        x = x_ref[0, i]                                     # [Q, pack * P]
        h = h_ref[0, 0, i]                                  # [N, pack * P]
        carried = jnp.dot(c, h.astype(c.dtype),
                          preferred_element_type=jnp.float32)
        for k in range(pack):
            cs = meta_ref[0, i * pack + k, 0:1, :]          # [1, Q]
            dt = meta_ref[0, i * pack + k, 1:2, :]
            cs_s = jnp.broadcast_to(cs, (Q, Q))             # [t, s] = cs[s]
            cs_t = cs_s.T                                   # [t, s] = cs[t]
            decay = jnp.exp(jnp.where(t_i >= s_i, cs_t - cs_s, -jnp.inf))
            y_k = jnp.dot((gram * decay * dt).astype(x.dtype), x,
                          preferred_element_type=jnp.float32)
            y_k += jnp.exp(cs_t[:, :1]) * carried
            # the sum at the chunk's end: `dt A` <= 0, so it is the least
            # (a reduction, where a [1, 1] slice at lane Q - 1 would not
            # lower)
            last = jnp.min(cs, axis=1, keepdims=True)       # [1, 1]
            w = dt * jnp.exp(last - cs)                     # [1, Q]
            h_k = jnp.exp(last) * h + jnp.dot(
                (b_t * w).astype(x.dtype), x,
                preferred_element_type=jnp.float32)
            if k == 0:
                y, h_new = y_k, h_k
            else:                       # head k's lanes, and the later ones'
                mine = lambda n: jax.lax.broadcasted_iota(  # noqa: E731
                    jnp.int32, (n, pack * P), 1) >= k * P
                y = jnp.where(mine(Q), y_k, y)
                h_new = jnp.where(mine(h.shape[0]), h_k, h_new)
        y_ref[0, i] = y
        h_ref[0, 0, i] = h_new


def ssd_scan(x, dt, a, b, c, state, layer, slots, carried, chunk: int,
             interpret: bool = False):
    """The chunked scan as a Pallas kernel over (row, head tile, chunk),
    reading and writing the rows' state IN PLACE on the arena (donate it):
    `state` [L, slots, NH / pack, N, pack * P] float32 (`state_shape`); row
    r starts from `state[layer, slots[r]]` where `carried[r]` and from
    zeros where not, and leaves its final state there.  Every row's slot
    must exist: a row whose state is to be dropped names a scratch slot.
    x, dt, a, b, c as `ssd_scan_reference`.  Returns (y [R, S, NH, P]
    float32, state).
    (Through XLA the rows' gather and scatter copy the whole arena once a
    layer as soon as a program holds two rows: 2.4 GB of temporaries.)"""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    R, S, NH, P = x.shape
    G, N = b.shape[2:]
    pack = state.shape[-1] // P
    NR, W = NH // pack, pack * P                # lane rows, their width
    hb = heads_per_step(NR, G)
    xs, dts, bs, cs_ = _chunked(x, dt, b, c, chunk)
    nC = xs.shape[1]
    Sp = nC * chunk
    cs = jnp.cumsum(dts * a, axis=2)                        # [R, nC, Q, NH]
    meta = jnp.stack([cs, dts], axis=-1).reshape(R, Sp, NH, 2) \
        .transpose(0, 2, 3, 1)                              # [R, NH, 2, Sp]
    xh = xs.reshape(R, Sp, NR, W).transpose(0, 2, 1, 3)     # [R, NR, Sp, W]
    bh = bs.reshape(R, Sp, G, N).transpose(0, 2, 1, 3)      # [R, G, Sp, N]
    ch = cs_.reshape(R, Sp, G, N).transpose(0, 2, 1, 3)
    where = jnp.concatenate([jnp.asarray(layer, jnp.int32).reshape(1),
                             jnp.asarray(slots, jnp.int32),
                             jnp.asarray(carried, jnp.int32)])
    tiles_per_group = NR // G // hb
    head_map = lambda r, t, j, m: (r, t, j, 0)              # noqa: E731
    group_map = lambda r, t, j, m: (r, t // tiles_per_group, j, 0)  # noqa
    state_map = lambda r, t, j, m: (m[0], m[1 + r], t, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R, NR // hb, nC),
        in_specs=[pl.BlockSpec((1, hb * pack, 2, chunk),
                               lambda r, t, j, m: (r, t, 0, j)),
                  pl.BlockSpec((1, hb, chunk, W), head_map),
                  pl.BlockSpec((1, 1, chunk, N), group_map),
                  pl.BlockSpec((1, 1, chunk, N), group_map),
                  pl.BlockSpec((1, 1, hb, N, W), state_map)],
        out_specs=[pl.BlockSpec((1, hb, chunk, W), head_map),
                   pl.BlockSpec((1, 1, hb, N, W), state_map)])
    y, state = pl.pallas_call(
        functools.partial(_scan_kernel, hb=hb, rows=R, pack=pack),
        name="ssd_scan",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, NR, Sp, W), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # (the scalar-prefetch operand counts: `state` is input 5)
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        # (an explicit False would override a test's interpret default)
        **({"interpret": True} if interpret else {}),
    )(where, meta, xh, bh, ch, state)
    return y.transpose(0, 2, 1, 3).reshape(R, Sp, NH, P)[:, :S], state


def _as_rows(state, *per_head):
    """[B, NH, P] operands a head -> [B, NH / pack, pack * P], a lane row
    of the stored `state` each (the update is elementwise over the lanes,
    and a row's heads share B and C)."""
    rows, width = state.shape[2], state.shape[4]
    return tuple(t.reshape(t.shape[0], rows, width) for t in per_head)


def ssm_update_reference(state, layer, slots, x_dt, decay, b, c):
    """One token a row, dense.  state [L, slots, NH / pack, N, pack * P]
    float32 (`state_shape`); slots [B] (one past the last slot: the row is
    dropped); x_dt = dt * x and decay = exp(dt A) broadcast over the
    channels, [B, NH, P] float32; b, c [B, G, N] float32.  Returns (y [B,
    NH, P] float32, state)."""
    shape, G = x_dt.shape, b.shape[1]
    x_dt, decay = _as_rows(state, x_dt, decay)
    rep = lambda t: jnp.repeat(t, state.shape[2] // G, axis=1)  # noqa: E731
    h = state[layer, jnp.minimum(slots, state.shape[1] - 1)]
    h = h * decay[:, :, None, :] + rep(b)[..., None] * x_dt[:, :, None, :]
    y = jnp.sum(h * rep(c)[..., None], axis=2)
    return y.reshape(shape), state.at[layer, slots].set(h, mode="drop")


def _update_kernel(meta_ref, s_ref, x_ref, d_ref, b_ref, c_ref, y_ref,
                   o_ref, *, hb: int):
    """One (row, head tile): the slot's [hb, N, P] tile in, updated, out."""
    del meta_ref
    P = x_ref.shape[-1]
    N = b_ref.shape[-1]
    # a group's B and C as [N, P] tiles, constant along the channels
    b = jnp.broadcast_to(b_ref[0, 0], (P, N)).T
    c = jnp.broadcast_to(c_ref[0, 0], (P, N)).T
    for i in range(hb):
        h = s_ref[0, 0, i] * d_ref[0, i:i + 1, :] + b * x_ref[0, i:i + 1, :]
        o_ref[0, 0, i] = h
        y_ref[0, i:i + 1, :] = jnp.sum(h * c, axis=0, keepdims=True)


def ssm_update(state, layer, slots, x_dt, decay, b, c,
               interpret: bool = False):
    """The one-token update as a Pallas kernel, in place on `state`
    (donate it); operands and results as `ssm_update_reference`, except
    that every row's slot must exist: a row to be dropped names a scratch
    slot, whose content is then garbage."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    shape = x_dt.shape
    x_dt, decay = _as_rows(state, x_dt, decay)
    L, n_slots, NH, N, P = state.shape          # NH lane rows of P lanes
    B, G = x_dt.shape[0], b.shape[1]
    hb = heads_per_step(NH, G)
    tiles_per_group = NH // G // hb
    meta = jnp.concatenate([jnp.asarray(layer, jnp.int32).reshape(1),
                            jnp.asarray(slots, jnp.int32)])
    state_map = lambda r, t, m: (m[0], m[r + 1], t, 0, 0)   # noqa: E731
    row_map = lambda r, t, m: (r, t, 0)                     # noqa: E731
    group_map = lambda r, t, m: (r, t // tiles_per_group, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, NH // hb),
        in_specs=[pl.BlockSpec((1, 1, hb, N, P), state_map),
                  pl.BlockSpec((1, hb, P), row_map),
                  pl.BlockSpec((1, hb, P), row_map),
                  pl.BlockSpec((1, 1, 1, N), group_map),
                  pl.BlockSpec((1, 1, 1, N), group_map)],
        out_specs=[pl.BlockSpec((1, hb, P), row_map),
                   pl.BlockSpec((1, 1, hb, N, P), state_map)])
    y, state = pl.pallas_call(
        functools.partial(_update_kernel, hb=hb),
        name="ssm_update",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, NH, P), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # (the scalar-prefetch operand counts: `state` is input 1)
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        # (an explicit False would override a test's interpret default)
        **({"interpret": True} if interpret else {}),
    )(meta, state, x_dt, decay, b[:, :, None], c[:, :, None])
    return y.reshape(shape), state
