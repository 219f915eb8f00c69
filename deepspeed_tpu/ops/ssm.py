"""The selective state-space recurrence of a Mamba-2 mixer, in its two
serving forms (`inference/v2/ssm_ops.py`):

    h_t = exp(dt_t A) h_{t-1} + dt_t * B_t (outer) x_t        h [N, P]
    y_t = C_t h_t                                             y [P]

per head (P channels, a state of N values a channel; `A` < 0 a head, `dt`
> 0 a head and token; B and C shared by the heads of a group).  `D * x`, the
gate and the norm are the caller's.  The state is kept TRANSPOSED, `[N,
P]` with the channels on the lanes, so that `y` (a sum over N) is a sum
over sublanes and comes out as a lane-dense row.

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
  `[L, slots, heads, N, P]` (`input_output_aliases`): a grid step reads the
  `[hb, N, P]` tile of the row's slot, updates it and writes it back, so
  the step moves the state once each way and nothing else of that size
  (gather + update + scatter through XLA is three times the traffic).
  Rows name their slots by a vector (a decode batch is not in slot order).

Each has a dense `jax.numpy` form: what the CPU runs and what the tests
hold the kernels to.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["ssd_scan", "ssd_scan_reference", "ssm_update",
           "ssm_update_reference", "heads_per_step"]

# heads a grid step takes (all of one group: they share B and C)
HEADS_PER_STEP = 8


def heads_per_step(heads: int, groups: int) -> int:
    hb = min(HEADS_PER_STEP, heads // groups)
    while (heads // groups) % hb:
        hb -= 1
    return hb


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
                 h_ref, *, hb: int, rows: int):
    """One (row, head tile, chunk): meta [hb, 2, Q] = (running sum of dt A,
    dt) as rows; x [hb, Q, P]; b, c [Q, N]; the state rides `h_ref`, the
    slot's tile (its block stays put along the chunk axis): taken from
    `s_ref` at the first chunk where the row continues a prompt, zeros
    where it starts one."""
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
    for i in range(hb):
        cs = meta_ref[0, i, 0:1, :]                         # [1, Q]
        dt = meta_ref[0, i, 1:2, :]
        x = x_ref[0, i]                                     # [Q, P]
        cs_s = jnp.broadcast_to(cs, (Q, Q))                 # [t, s] = cs[s]
        cs_t = cs_s.T                                       # [t, s] = cs[t]
        decay = jnp.exp(jnp.where(t_i >= s_i, cs_t - cs_s, -jnp.inf))
        h = h_ref[0, 0, i]                                  # [N, P]
        y = jnp.dot((gram * decay * dt).astype(x.dtype), x,
                    preferred_element_type=jnp.float32)
        y += jnp.exp(cs_t[:, :1]) * jnp.dot(
            c, h.astype(c.dtype), preferred_element_type=jnp.float32)
        y_ref[0, i] = y
        # the sum at the chunk's end: `dt A` <= 0, so it is the least (a
        # reduction, where a [1, 1] slice at lane Q - 1 would not lower)
        last = jnp.min(cs, axis=1, keepdims=True)           # [1, 1]
        w = dt * jnp.exp(last - cs)                         # [1, Q]
        h_ref[0, 0, i] = jnp.exp(last) * h + jnp.dot(
            (b_t * w).astype(x.dtype), x, preferred_element_type=jnp.float32)


def ssd_scan(x, dt, a, b, c, state, layer, slots, carried, chunk: int,
             interpret: bool = False):
    """The chunked scan as a Pallas kernel over (row, head tile, chunk),
    reading and writing the rows' state IN PLACE on the arena (donate it):
    `state` [L, slots, NH, N, P] float32; row r starts from `state[layer,
    slots[r]]` where `carried[r]` and from zeros where not, and leaves its
    final state there.  Every row's slot must exist: a row whose state is
    to be dropped names a scratch slot.  x, dt, a, b, c as
    `ssd_scan_reference`.  Returns (y [R, S, NH, P] float32, state).
    (Through XLA the rows' gather and scatter copy the whole arena once a
    layer as soon as a program holds two rows: 2.4 GB of temporaries.)"""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    R, S, NH, P = x.shape
    G, N = b.shape[2:]
    hb = heads_per_step(NH, G)
    xs, dts, bs, cs_ = _chunked(x, dt, b, c, chunk)
    nC = xs.shape[1]
    Sp = nC * chunk
    cs = jnp.cumsum(dts * a, axis=2)                        # [R, nC, Q, NH]
    meta = jnp.stack([cs, dts], axis=-1).reshape(R, Sp, NH, 2) \
        .transpose(0, 2, 3, 1)                              # [R, NH, 2, Sp]
    xh = xs.reshape(R, Sp, NH, P).transpose(0, 2, 1, 3)     # [R, NH, Sp, P]
    bh = bs.reshape(R, Sp, G, N).transpose(0, 2, 1, 3)      # [R, G, Sp, N]
    ch = cs_.reshape(R, Sp, G, N).transpose(0, 2, 1, 3)
    where = jnp.concatenate([jnp.asarray(layer, jnp.int32).reshape(1),
                             jnp.asarray(slots, jnp.int32),
                             jnp.asarray(carried, jnp.int32)])
    tiles_per_group = NH // G // hb
    head_map = lambda r, t, j, m: (r, t, j, 0)              # noqa: E731
    group_map = lambda r, t, j, m: (r, t // tiles_per_group, j, 0)  # noqa
    state_map = lambda r, t, j, m: (m[0], m[1 + r], t, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R, NH // hb, nC),
        in_specs=[pl.BlockSpec((1, hb, 2, chunk),
                               lambda r, t, j, m: (r, t, 0, j)),
                  pl.BlockSpec((1, hb, chunk, P), head_map),
                  pl.BlockSpec((1, 1, chunk, N), group_map),
                  pl.BlockSpec((1, 1, chunk, N), group_map),
                  pl.BlockSpec((1, 1, hb, N, P), state_map)],
        out_specs=[pl.BlockSpec((1, hb, chunk, P), head_map),
                   pl.BlockSpec((1, 1, hb, N, P), state_map)])
    y, state = pl.pallas_call(
        functools.partial(_scan_kernel, hb=hb, rows=R),
        name="ssd_scan",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, NH, Sp, P), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # (the scalar-prefetch operand counts: `state` is input 5)
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        # (an explicit False would override a test's interpret default)
        **({"interpret": True} if interpret else {}),
    )(where, meta, xh, bh, ch, state)
    return y.transpose(0, 2, 1, 3)[:, :S], state


def ssm_update_reference(state, layer, slots, x_dt, decay, b, c):
    """One token a row, dense.  state [L, slots, NH, N, P] float32; slots
    [B] (one past the last slot: the row is dropped); x_dt = dt * x and
    decay = exp(dt A) broadcast over the channels, [B, NH, P] float32; b, c
    [B, G, N] float32.  Returns (y [B, NH, P] float32, state)."""
    NH, G = x_dt.shape[1], b.shape[1]
    rep = lambda t: jnp.repeat(t, NH // G, axis=1)          # noqa: E731
    h = state[layer, jnp.minimum(slots, state.shape[1] - 1)]
    h = h * decay[:, :, None, :] + rep(b)[..., None] * x_dt[:, :, None, :]
    y = jnp.sum(h * rep(c)[..., None], axis=2)
    return y, state.at[layer, slots].set(h, mode="drop")


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
    L, n_slots, NH, N, P = state.shape
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
    return y, state
