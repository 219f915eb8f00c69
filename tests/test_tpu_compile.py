"""The main path's kernels, compiled at real widths by the TPU compiler for
a DESCRIBED v5e chip (nothing attached; on-chip-measurement guide §2.3).
Interpret-mode parity tests cannot see what Mosaic refuses — an unaligned
slice, too much VMEM, an op it does not lower — and these cost about two
seconds each, so they guard every PR at no chip time.

One file on purpose: the worker that runs it loads the TPU library and
keeps it until it exits.  The topology is described inside a module-scoped
fixture (never at import) and each test compiles in its own process."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

pytestmark = pytest.mark.kernels

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """`arg(shape, dtype)` -> ShapeDtypeStruct on the first described chip.
    The persistent cache is off around these compiles: an executable for a
    described chip is written but cannot be read back without one, and the
    next run would only warn about it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    one = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield lambda shape, dtype=BF16: jax.ShapeDtypeStruct(shape, dtype,
                                                         sharding=one)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def kernels(fn, *args) -> int:
    """Compile `fn` for the described chip; count its Mosaic kernels.
    At the precision programs run with: conftest's "highest" (for CPU
    parity checks) asks Mosaic for an fp32 contraction of bf16 operands,
    which it refuses."""
    with jax.default_matmul_precision("default"):
        return jax.jit(fn).lower(*args).compile().as_text().count(
            "tpu_custom_call")


# (B, S, heads, kv heads, head_dim): the smoke's model and a D=128 one
@pytest.mark.parametrize("B,S,NH,NKV,D", [
    pytest.param(4, 2048, 32, 4, 64, id="tinyllama-1.1b"),
    pytest.param(4, 2048, 16, 16, 128, id="gpt2-1.3b"),
])
def test_flash_attention_fwd_bwd(chip, B, S, NH, NKV, D):
    from deepspeed_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    q, kv = chip((B, S, NH, D)), chip((B, S, NKV, D))
    assert kernels(lambda q, k, v: flash_attention(q, k, v, causal=True),
                   q, kv, kv) == 1
    # forward + the dq and dk/dv backward kernels
    assert kernels(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv) == 3


# (B, NH, NKV, D, nb, bs, MB, L; L: a layered arena).  First the serving
# geometry (32/4 heads, D=64, block 64); then the small budgets interpret
# mode cannot vouch for: MB=1 is the degenerate single-block walk, odd head
# counts, 1024-key GQA; then the qwen2-7b cell's own decode (64 rows, 28/4
# heads of 128, the 704-block arena of 16 layers), the same under a 32k
# table (512 entries: 4096 tiles listed in SMEM), and a tensor-parallel
# shard's one local kv head
@pytest.mark.parametrize("B,NH,NKV,D,nb,bs,MB,L", [
    (32, 32, 4, 64, 256, 64, 32, None),
    (3, 8, 2, 64, 4, 8, 1, None),
    (2, 6, 3, 64, 8, 16, 2, None),
    (8, 16, 4, 64, 128, 64, 16, None),
    pytest.param(64, 28, 4, 128, 704, 64, 32, 16, id="qwen2-7b-cell"),
    pytest.param(64, 28, 4, 128, 704, 64, 512, 16, id="qwen2-7b-32k-table"),
    pytest.param(16, 7, 1, 128, 128, 64, 32, 4, id="one-local-kv-head"),
])
def test_paged_decode(chip, B, NH, NKV, D, nb, bs, MB, L):
    """One Mosaic kernel; its tile inside the VMEM a kernel gets (a tile
    over it is refused by the compile itself); and no copy of the arena:
    the kernel takes a block's rows as `[bs * NKV, D]` where that is a
    bitcast, and a reshape that was not would copy the arena whole."""
    from deepspeed_tpu.ops import paged_attention as pa
    arena = chip((nb, bs, NKV, D) if L is None else (L, nb, bs, NKV, D))
    args = (chip((B, NH, D)), arena, arena, chip((B, MB), jnp.int32),
            chip((B,), jnp.int32))
    if L is None:
        attend = pa.paged_decode_attention
    else:
        args += (chip((), jnp.int32),)

        def attend(q, ak, av, tables, lens, layer):
            return pa.paged_decode_attention(q, ak, av, tables, lens,
                                             layer_idx=layer)

    assert kernels(attend, *args) == 1
    tile = (4 * pa._blocks_per_step(bs, NKV, D, 2, MB)
            * pa._block_vmem_bytes(bs, NKV, D, 2))
    assert tile <= pa._TILE_VMEM_BYTES < 16 << 20
    with jax.default_matmul_precision("default"):
        mem = jax.jit(attend).lower(*args).compile().memory_analysis()
    assert mem.temp_size_in_bytes < 16 << 20


# the smallthinker cell's decode: 32 rows, 28/4 heads of 128, a 209-entry
# table; the window kind (2145 blocks of 6 layers, the walk starting at the
# window's first block) and the global kind (6689 blocks of 2 layers, no
# window); then a uniform window at the small serving geometry
@pytest.mark.parametrize("B,NH,NKV,D,nb,MB,L,window", [
    pytest.param(32, 28, 4, 128, 2145, 209, 6, 4096, id="cell-window-kind"),
    pytest.param(32, 28, 4, 128, 6689, 209, 2, None, id="cell-global-kind"),
    pytest.param(8, 32, 4, 64, 256, 32, None, 1024, id="uniform-window"),
])
def test_paged_decode_with_a_window(chip, B, NH, NKV, D, nb, MB, L, window):
    """The window is static: one kernel, the walk's first entries one more
    scalar-prefetch operand, no copy of the arena."""
    from deepspeed_tpu.ops import paged_attention as pa
    arena = chip((nb, 64, NKV, D) if L is None else (L, nb, 64, NKV, D))
    args = (chip((B, NH, D)), arena, arena, chip((B, MB), jnp.int32),
            chip((B,), jnp.int32)) + ((chip((), jnp.int32),) if L else ())

    def attend(q, ak, av, tables, lens, *layer):
        return pa.paged_decode_attention(
            q, ak, av, tables, lens, layer_idx=layer[0] if layer else None,
            window=window)

    assert kernels(attend, *args) == 1
    with jax.default_matmul_precision("default"):
        mem = jax.jit(attend).lower(*args).compile().memory_analysis()
    assert mem.temp_size_in_bytes < 16 << 20


# (rows, window): the smallthinker cell's prompt chunks, 12,288 queries of
# 28/4 heads of 128 over a row's keys by position (a 209-block table and
# the chunk's own: 25,664 positions in 512-key tiles); one chunk slot and
# the four a step can hold
@pytest.mark.parametrize("R,window", [(1, 4096), (1, None), (4, 4096)])
def test_chunk_attention(chip, R, window, monkeypatch):
    """One Mosaic kernel named `chunk_attention` (the benchmark's readers
    match the name), its two bodies over one set of scratch: it compiles
    inside the 5 MiB of scoped VMEM it needed with one body (4.4-4.9 MiB:
    the blocks, the three scratch arrays, the compiler's spills), and the
    program's temporaries are the wrapper's transposed keys and values
    and nothing of the kernel's."""
    import functools
    from jax.experimental.pallas import tpu as pltpu
    from deepspeed_tpu.ops import chunk_attention as ca
    C, T = 12288, 209 * 64 + 12288
    T = -(-T // ca.key_tile(T)) * ca.key_tile(T)
    monkeypatch.setattr(ca.pltpu, "CompilerParams", functools.partial(
        pltpu.CompilerParams, vmem_limit_bytes=5 << 20))

    def attend(q, k, v, pos0, n_valid):
        return ca.chunk_attention(q, k, v, pos0, n_valid, window=window)

    args = (chip((R, C, 28, 128)), chip((R, T, 4, 128)),
            chip((R, T, 4, 128)), chip((R,), jnp.int32),
            chip((R,), jnp.int32))
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(attend).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert text.count('op_name="jit(attend)/chunk_attention/') >= 1
    # (one row's keys and values fit VMEM whole and XLA keeps them there)
    kv = 2 * R * T * 4 * 128 * 2 if R > 1 else 0
    assert compiled.memory_analysis().temp_size_in_bytes < kv + 2 ** 20
    # a query tile is 128 queries x 7 heads; with the window its key steps
    # are a constant few however long the row
    assert ca._query_tile(C, 7) == 128 and T // 512 == 51
    assert (4096 + 128 - 2) // 512 + 2 == 10


# (R, C, NH, NKV, D, T, window): off the cell's shapes, where the running
# max and sum (128 lanes a row) meet scores and outputs that are not whole
# vregs wide: heads of 64, a buffer of one 200-key tile, a key tile of 328
# under a window, heads of 256
@pytest.mark.parametrize("R,C,NH,NKV,D,T,window", [
    (4, 64, 4, 2, 64, 256, 20), (2, 256, 8, 2, 64, 1536, 600),
    (1, 128, 8, 2, 128, 200, None), (1, 128, 8, 2, 128, 328, 100),
    (1, 512, 16, 16, 256, 1024, None)])
def test_chunk_attention_off_the_cells_shapes(chip, R, C, NH, NKV, D, T,
                                              window):
    from deepspeed_tpu.ops import chunk_attention as ca

    def attend(q, k, v, pos0, n_valid):
        return ca.chunk_attention(q, k, v, pos0, n_valid, window=window)

    assert kernels(attend, chip((R, C, NH, D)), chip((R, T, NKV, D)),
                   chip((R, T, NKV, D)), chip((R,), jnp.int32),
                   chip((R,), jnp.int32)) == 1


# (C, NH, NKV, nb, bs, MB).  The serving chunk, then the padded tiles: C=4
# is a sub-8 verify span (pads to the 8-row query tile), C=20 an odd chunk
@pytest.mark.parametrize("C,NH,NKV,nb,bs,MB", [
    (256, 32, 4, 256, 64, 32),
    (4, 8, 2, 16, 8, 8),
    (20, 8, 2, 16, 8, 8),
])
def test_paged_prefill(chip, C, NH, NKV, nb, bs, MB):
    from deepspeed_tpu.ops.paged_prefill import paged_prefill_attention
    D = 64
    arena = chip((nb, bs, NKV, D))

    def prefill(q, ak, av, table, meta):
        return paged_prefill_attention(q, ak, av, table, meta[0], meta[1])

    assert kernels(prefill, chip((C, NH, D)), arena, arena,
                   chip((MB,), jnp.int32), chip((2,), jnp.int32)) == 1


def test_merged_arena_decode(chip):
    """The layered merged arena ([L, nb, bs, NKV*D]) big-context serving
    uses, at the 32/4·D64 geometry."""
    from deepspeed_tpu.ops.paged_merged import merged_decode_attention
    B, NH, NKV, D, L, nb, bs, MB = 32, 32, 4, 64, 22, 256, 64, 32
    arena = chip((L, nb, bs, NKV * D))

    def decode(q, ak, av, tables, lens, layer):
        return merged_decode_attention(q, ak, av, tables, lens,
                                       layer_idx=layer)

    assert kernels(decode, chip((B, NH, D)), arena, arena,
                   chip((B, MB), jnp.int32), chip((B,), jnp.int32),
                   chip((), jnp.int32)) == 1


# (B, Q, NH, rank, rope, attentions, nb, bs, MB): the LongCat-Flash cell's
# decode (one query's 64 heads against one 576-wide row; a row's live
# blocks of its table's 33 in key tiles of 8, each block its own in_spec;
# arena minor padded to 640 lanes) and its chunk slots (tiles of 8
# queries: 512 query tiles, up to 2,560 items on the list); then one block
# a tile and 2 heads
@pytest.mark.parametrize("B,Q,NH,R,Dr,A,nb,bs,MB", [
    pytest.param(96, 1, 64, 512, 64, 8, 3488, 64, 33, id="longcat-decode"),
    pytest.param(4, 1024, 64, 512, 64, 8, 3488, 64, 33, id="longcat-chunks"),
    pytest.param(4, 1, 2, 128, 64, 2, 16, 16, 1, id="one-block"),
    # the DeepSeek-V3 cell's: 128 heads, tables of 41 blocks, one attention
    # a layer; chunk slots in tiles of 4 queries x 128 heads (3,072 items)
    pytest.param(64, 1, 128, 512, 64, 5, 2890, 64, 41, id="deepseek-decode"),
    pytest.param(2, 1024, 128, 512, 64, 5, 2890, 64, 41,
                 id="deepseek-chunks"),
    # a 32k table over the same rows: 6,144 items at most, 49,152 slots
    pytest.param(96, 1, 64, 512, 64, 8, 3488, 64, 512, id="longcat-32k"),
])
def test_mla_paged_attention(chip, B, Q, NH, R, Dr, A, nb, bs, MB):
    """The latent attention kernel at the cell's widths: one Mosaic
    kernel whose grid is the list of live key tiles (the list fits the
    scalar memory), no relayout of the arena (an arena whose minor
    dimension is not whole 128-lane tiles is copied whole before every
    call) and next to no temporaries for the list."""
    from deepspeed_tpu.ops.mla_paged import mla_paged_attention
    W = -(-(R + Dr) // 128) * 128

    def attend(qa, qr, arena, tables, pos0, n_valid, index):
        return mla_paged_attention(qa, qr, arena, tables, pos0, n_valid,
                                   index, sm_scale=0.07)

    args = (chip((B, Q, NH, R)), chip((B, Q, NH, Dr)), chip((A, nb, bs, W)),
            chip((B, MB), jnp.int32), chip((B,), jnp.int32),
            chip((B,), jnp.int32), chip((), jnp.int32))
    assert kernels(attend, *args) == 1
    with jax.default_matmul_precision("default"):
        mem = jax.jit(attend).lower(*args).compile().memory_analysis()
    assert mem.temp_size_in_bytes < 2 ** 20


# (rows of the compact buffer, whether they are every assignment, hidden,
# expert width, local experts, expert layers, ReLU gate): the experts' share
# of the four expert cells, decode (`local_rows_cap` of 64 x 8, 96 x 12,
# 32 x 6, 96 x 10 picks) and prefill (1024 slots; a 4096-row pass of
# smallthinker's chunk program; granite's 512 slots), and a buffer whose last
# row tile is partial.  The two prompt passes that hold every assignment lay
# each expert's rows on a tile edge (`aligns`): the buffer the kernels see is
# `aligned_rows` long
@pytest.mark.parametrize("rows,whole,H,F,El,L,relu", [
    pytest.param(128, False, 7168, 2048, 16, 4, False, id="deepseek-decode"),
    pytest.param(2048, False, 7168, 2048, 16, 4, False,
                 id="deepseek-prefill"),
    pytest.param(96, False, 6144, 2048, 16, 3, False, id="longcat-decode"),
    pytest.param(1024, False, 6144, 2048, 16, 3, False,
                 id="longcat-prefill"),
    pytest.param(192, True, 2560, 768, 64, 8, True,
                 id="smallthinker-decode"),
    pytest.param(24576, True, 2560, 768, 64, 8, True,
                 id="smallthinker-chunk"),
    pytest.param(960, True, 4096, 768, 36, 10, False, id="granite-decode"),
    pytest.param(5120, True, 4096, 768, 36, 10, False,
                 id="granite-prefill"),
    pytest.param(176, True, 2560, 768, 64, 8, True, id="partial-last-tile"),
])
def test_grouped_matmul(chip, rows, whole, H, F, El, L, relu):
    """The experts' two passes of `expert_ffn.moe` (gate and up fused,
    then down) over the whole weight stack at the cells' widths, in the
    layout the shape asks for: two Mosaic kernels within the VMEM they ask
    for, and no copy of the stack (a per-layer slice handed to a custom
    call would be one)."""
    from deepspeed_tpu.ops import grouped_matmul as gm
    tile = gm.row_tile(rows)
    aligned = gm.aligns(rows, tile, El, whole)
    assert aligned == (rows in (24576, 5120))
    buffer = gm.aligned_rows(rows, tile, El) if aligned else rows
    gate = jax.nn.relu if relu else jax.nn.silu

    def experts(x, wg, wu, wd, sizes, li):
        items = gm.list_items(sizes, rows, tile, li * El, aligned=aligned)
        act = gm.grouped_matmul(x, (wg, wu), items, tile=tile,
                                gate_act=gate, out_dtype=x.dtype)
        return gm.grouped_matmul(act, (wd,), items, tile=tile)

    args = (chip((buffer, H)), chip((L * El, H, F)), chip((L * El, H, F)),
            chip((L * El, F, H)), chip((El,), jnp.int32),
            chip((), jnp.int32))
    assert kernels(experts, *args) == 2
    with jax.default_matmul_precision("default"):
        mem = jax.jit(experts).lower(*args).compile().memory_analysis()
    # the activations between the two passes at most
    assert mem.temp_size_in_bytes <= buffer * F * 2 + 2 ** 20


@pytest.mark.parametrize("M,K,N", [(256, 2048, 5632), (8, 2048, 2048)])
def test_tile_matmul(chip, M, K, N):
    """The fused-TP ring's per-hop GEMM (ops.tp_matmul; `tile_matmul`
    dispatches to it on a TPU) at a prefill-chunk and a decode shape."""
    from deepspeed_tpu.ops.tp_matmul import (_pallas_matmul,
                                             tile_matmul_supported)
    assert tile_matmul_supported(M, K, N)
    assert kernels(_pallas_matmul, chip((M, K)), chip((K, N))) == 1


@pytest.mark.parametrize("S", [8, 256])
def test_lora_epilogue(chip, S):
    from deepspeed_tpu.ops.lora_matmul import (_pallas_lora_delta,
                                               lora_delta_supported)
    K, N, rank, slots = 2048, 2048, 16, 4
    assert lora_delta_supported(S, K, N, slots)

    def delta(x, a, b, ids):
        return _pallas_lora_delta(x, a, b, ids, interpret=False)

    assert kernels(delta, chip((S, K)), chip((slots, K, rank)),
                   chip((slots, rank, N)), chip((S,), jnp.int32)) == 1


def test_fused_adam8_compiles(chip):
    """Opt-in (`fused_update`), off the smoke's path: compiled, never run."""
    from deepspeed_tpu.ops.fused_adam8 import fused_adam8_leaf, leaf_supported
    shape = (2048, 5632)
    assert leaf_supported(shape, jnp.float32)
    f32 = jnp.float32

    def step(g, mq, ms, vq, vs, p, lr, gscale, c1, c2):
        return fused_adam8_leaf(g, mq, ms, vq, vs, p, lr, gscale, c1, c2,
                                b1=0.9, b2=0.999, eps=1e-8, wd=0.1,
                                adam_w=True, bias_correction=True)

    scalar = chip((), f32)
    assert kernels(step, chip(shape), chip(shape, jnp.int8),
                   chip((shape[0], 1), f32), chip(shape, jnp.int8),
                   chip((shape[0], 1), f32), chip(shape, f32),
                   scalar, scalar, scalar, scalar) == 1


# what the v5e compiler reported for the cell's step at PR 34, the
# accumulator riding the backward layer scan (PERF.md section 5); before,
# 16.407 GB: at the compiler's limit, where XLA's own rematerialisation
# clones instructions (`*.remat`) to fit
TRAIN_STEP_PEAK_BYTES = 14.201e9


def test_train_step_of_the_opt_cell_fits_without_clones(topo, chip,
                                                        monkeypatch):
    """`opt-1.3b.train_1chip`'s own `jit_train_step` (OPT-1.3B whole,
    `save_attn`, micro-batch 1 x 8, int8f moments, bf16 accumulation),
    compiled for the described chip from abstract state: no instruction
    named `*.remat`, XLA's sign that the program did not fit its memory
    limit otherwise (at PR 33 the MLP up-projection ran a third time in
    every layer of every micro-batch for it), and a peak within 3% of the
    figure above.  The guard that keeps the step from drifting back to the
    limit; the compile takes ~15 s."""
    import json
    import os
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import Transformer, get_model_config
    from deepspeed_tpu.parallel.mesh import make_mesh
    from deepspeed_tpu.runtime import activation_checkpointing, engine
    from deepspeed_tpu.runtime.zero.sharding import (opt_state_specs,
                                                     param_specs)
    from deepspeed_tpu.utils import device

    def abstract_state(self, params):
        """`TrainEngine._init_state` in shapes: a described chip holds no
        array."""
        mesh = self.topology.mesh
        o_specs = opt_state_specs(self.rules, params)

        def like(tree, shardings, dtype=None):
            return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
                x.shape, dtype or x.dtype, sharding=s), tree, shardings)
        master = like(params, self._named(o_specs), jnp.float32)
        scalar = lambda dt: jax.ShapeDtypeStruct(  # noqa: E731
            (), dt, sharding=NamedSharding(mesh, P()))
        return engine.TrainState(
            step=scalar(jnp.int32),
            params=like(params, self._named(param_specs(self.rules, params)),
                        self.compute_dtype),
            master=master,
            opt_state=like(jax.eval_shape(self.optimizer.init, master),
                           self._opt_tree_shardings(params, o_specs)),
            loss_scale=scalar(jnp.float32), good_steps=scalar(jnp.int32),
            skipped_steps=scalar(jnp.int32))

    monkeypatch.setattr(device, "platform", lambda: "tpu")
    monkeypatch.setattr(engine.TrainEngine, "_init_state", abstract_state)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "opt-1.3b.json")) as f:
        prog = json.load(f)["program"]
    with open(os.path.join(root, "benchmark", "traffic",
                           "train_1chip.json")) as f:
        traffic = json.load(f)
    model = Transformer(get_model_config(
        prog["arch"], prog["size"], dtype=BF16, **prog["overrides"]))
    try:
        eng = ds.initialize(
            model=model, config=dict(prog["training"]),
            params=jax.eval_shape(model.init_params, jax.random.PRNGKey(0)),
            topology=make_mesh(devices=[topo.devices[0]]))
        gas = eng.config.gradient_accumulation_steps
        batch = {"input_ids": chip(
            (gas, traffic["rows"] // gas, traffic["seq_len"] + 1), jnp.int32)}
        with jax.default_matmul_precision("default"):
            compiled = eng._train_step.lower(
                eng.state, batch, chip((2,), jnp.uint32), {}).compile()
    finally:
        activation_checkpointing.reset()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= 3      # the flash kernels
    assert ".remat" not in hlo
    # the q/k/v projections, forward and recomputed, are whole [S, H] x
    # [H, H] matmuls: none writes 32 heads of 64 features (`_layer`), which
    # runs at half the MXU's width.  The one left is the output
    # projection's input gradient
    split = re.findall(
        r"= f32\[2048,32,64\]\S* convolution\(.*op_name=\"(\S+)\"", hlo)
    assert len(split) <= 1 and all(
        "transpose(jvp())" in at and "rematted" not in at
        for at in split), split
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert peak < 1.03 * TRAIN_STEP_PEAK_BYTES, peak


def abstract_cell(chip, monkeypatch, config: str, reference: str):
    """A serving cell's programs' operands for the described chip, from
    `benchmark/configs/<config>.json` and its reference module's abstract
    weights: (cfg, params, arena, the file's engine options, the reference,
    its sizes), with the platform's gates flipped to the chip's."""
    from benchmark import harness
    from deepspeed_tpu.inference.v2 import ragged_ops
    from deepspeed_tpu.inference.v2.model_registry import arch_config
    from deepspeed_tpu.utils import device

    monkeypatch.setattr(device, "platform", lambda: "tpu")
    file = harness.load_json(harness.BENCH_DIR, "configs", config + ".json")
    ref = harness.load_module(harness.BENCH_DIR, "references", reference)
    prog, sizes = file["program"], ref.sizes(file)
    cfg = arch_config(prog["arch"], prog["size"], dtype=BF16,
                      **prog["overrides"])
    eng = prog["engine"]
    on = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: chip(a.shape, a.dtype), tree)
    params = on(jax.eval_shape(lambda: ref._make_params(
        jnp.uint32(0), s=sizes, dtype=BF16)))
    arena = on(jax.eval_shape(lambda: ragged_ops.init_arena(
        cfg, eng["num_blocks"], 64, max_seqs=eng["max_seqs"])))
    return cfg, params, arena, eng, ref, sizes


# `deepseek-v3.decode_closed_chat`'s programs: what the v5e compiler reports
# for the weights (12.37 GB), the arena (1.18 GB) and each program's
# temporaries; the chip has 16.9 GB to give
SERVE_PEAK_BYTES = {"decode_step": 13.70e9, "prefill_full": 13.85e9,
                    "prefill_chunks": 13.91e9}


def test_serving_programs_of_the_deepseek_cell_fit_and_discard_nothing(
        topo, chip, monkeypatch):
    """The cell's own `decode_step`, `prefill_full[1, 1024]` and
    `prefill_chunks[1]` (`benchmark/configs/deepseek-v3.json`: 1 dense + 4
    expert layers at the published widths, 16 of 256 experts, 64 rows of
    41 blocks), compiled for the described chip from abstract weights:
    each fits with the arena donated, runs the latent kernel, and the
    decode program holds ONE dense FFN of 18432 (the leading layer's,
    outside the expert layers' loop) and one shared expert in the loop's
    body: no layer computes a branch it throws away.  ~10 s a program."""
    import re

    from deepspeed_tpu.inference.v2 import ragged_ops

    cfg, params, arena, eng, ref, sizes = abstract_cell(
        chip, monkeypatch, "deepseek-v3", "deepseek_v3")
    B, MB = eng["max_seqs"], eng["max_blocks_per_seq"]
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert held == ref.weight_bytes(sizes, "bfloat16")
    assert arena["c"].shape == (5, 2890, 64, 640)
    i32 = lambda *shape: chip(shape, jnp.int32)  # noqa: E731
    flags = lambda n: chip((n,), jnp.bool_)  # noqa: E731
    S = eng["prefill_chunk_size"]
    calls = {
        "decode_step": (ragged_ops.decode_step,
                        (i32(B), i32(B), i32(B, MB), flags(B))),
        "prefill_full": (ragged_ops.prefill_full,
                         (i32(1, S), i32(1), i32(1, MB), flags(1))),
        "prefill_chunks": (ragged_ops.prefill_chunks,
                           (i32(1, S), i32(1), i32(1), i32(1, MB), flags(1))),
    }
    for name, (fn, args) in calls.items():
        with jax.default_matmul_precision("default"):
            compiled = fn.lower(cfg, params, arena, *args).compile()
        mem, hlo = compiled.memory_analysis(), compiled.as_text()
        assert mem.peak_memory_in_bytes < 1.02 * SERVE_PEAK_BYTES[name], name
        assert mem.alias_size_in_bytes >= arena["c"].size * 2    # donated
        assert mem.temp_size_in_bytes < 0.5e9, name
        assert "tpu_custom_call" in hlo, name
        if name != "decode_step":
            continue
        matmuls = [l for l in hlo.splitlines()
                   if re.search(r" (dot|convolution)\(", l)]
        under = lambda scope: [l for l in matmuls  # noqa: E731
                               if f"/{scope}/" in l]
        # gate and up of ONE 18432-wide FFN, then its down-projection
        assert len([l for l in under("dense_ffn") if "18432]" in l]) == 2
        assert len(under("dense_ffn")) == 3
        # the shared expert once in the expert layers' loop body
        assert len(under("shared_expert")) == 3
        assert all("while/body" in l for l in under("shared_expert"))
        assert not any("/while/body/while" in l for l in under("dense_ffn"))


def test_the_chunk_program_of_the_smallthinker_cell_gathers_its_experts(
        topo, chip, monkeypatch):
    """`smallthinker-21b-a3b.decode_closed_long`'s `prefill_chunks[1,
    12288]` (`benchmark/configs/smallthinker-21b-a3b.json`: 8 layers at the
    published widths, all 64 experts held, so a 4096-row pass's compact
    buffer holds its 24,576 assignments), compiled for the described chip
    from abstract weights: the experts' outputs come back to their tokens
    through `experts/combine` (a gather a pick, no scatter-add of rows, no
    loop over pieces around the kernels), and the program fits as it did.
    ~25 s."""
    import re

    from deepspeed_tpu.inference.v2 import ragged_ops

    cfg, params, arena, eng, _, _ = abstract_cell(
        chip, monkeypatch, "smallthinker-21b-a3b", "smallthinker")
    MB, S = eng["max_blocks_per_seq"], eng["prefill_chunk_size"]
    i32 = lambda *shape: chip(shape, jnp.int32)  # noqa: E731
    with jax.default_matmul_precision("default"):
        compiled = ragged_ops.prefill_chunks.lower(
            cfg, params, arena, i32(1, S), i32(1), i32(1), i32(1, 2, MB),
            chip((1,), jnp.bool_)).compile()
    mem, hlo = compiled.memory_analysis(), compiled.as_text()
    assert mem.peak_memory_in_bytes < 1.02 * 12.45e9
    rows = r"f32\[4096,2560\]"
    gathers = re.findall(rows + r"\{1,0[^\n]* fusion\([^\n]*"
                         r"experts/combine/gather", hlo)
    assert len(gathers) >= cfg.moe_top_k
    assert not re.search(rows + r"[^\n]*experts/[^\n]*scatter-add", hlo)
    assert "experts/while/body" not in hlo
    # a pass's 24,576 assignments lie with each expert's rows from a tile
    # edge: 128 x (192 + 64) rows under the kernels, never the compact 24,576
    assert re.search(r"grouped_matmul[.0-9]* = f32\[32768,2560\]", hlo)
    assert not re.search(r"grouped_matmul[.0-9]* = f32\[24576,", hlo)


# `falcon-h1-34b.decode_closed_short`'s programs: what the v5e compiler
# reports for the weights (10.51 GB), the arena (K/V 1.31 GB, state 2.44 GB,
# tails 0.02 GB) and each program's temporaries
FALCON_PEAK_BYTES = {"decode_step": 14.42e9, "prefill_full": 14.33e9,
                     "prefill_full_2": 14.33e9, "prefill_chunks": 14.33e9,
                     "prefill_chunks_4": 14.42e9}


def test_serving_programs_of_the_falcon_h1_cell_fit_and_update_in_place(
        topo, chip, monkeypatch):
    """The cell's own `decode_step`, `prefill_full[1, 512]`,
    `prefill_full[2, 256]`, `prefill_chunks[1]` and `prefill_chunks[4]`
    (`benchmark/configs/falcon-h1-34b.json`: 6 layers at the published
    widths, 96 rows and state slots), compiled for the described chip from
    abstract weights: each fits with the arena donated and next to no
    temporaries (the recurrent state among it: no second copy of 2.4 GB,
    which XLA's own gather and scatter of two rows' states make, nor the
    convolution tails' 18 MB padded to 763), the decode program runs the
    in-place `ssm_update` and the paged kernel, the prefill programs the
    chunked `ssd_scan`.  ~6 s a program."""
    from deepspeed_tpu.inference.v2 import ragged_ops

    cfg, params, arena, eng, ref, sizes = abstract_cell(
        chip, monkeypatch, "falcon-h1-34b", "falcon_h1")
    B, MB = eng["max_seqs"], eng["max_blocks_per_seq"]
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert held == ref.weight_bytes(sizes, "bfloat16")
    assert arena["ssm"].shape == (6, 97, 32, 256, 128)
    assert arena["conv"].shape == (6, 97, 3 * 5120)
    donated = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(arena))
    i32 = lambda *shape: chip(shape, jnp.int32)  # noqa: E731
    flags = lambda n: chip((n,), jnp.bool_)  # noqa: E731
    S = eng["prefill_chunk_size"]
    calls = {
        "decode_step": (ragged_ops.decode_step, "ssm_update",
                        (i32(B), i32(B), i32(B, MB), flags(B)), i32(B)),
        "prefill_full": (ragged_ops.prefill_full, "ssd_scan",
                         (i32(1, S), i32(1), i32(1, MB), flags(1)), i32(1)),
        "prefill_full_2": (ragged_ops.prefill_full, "ssd_scan",
                           (i32(2, S // 2), i32(2), i32(2, MB), flags(2)),
                           i32(2)),
        "prefill_chunks": (ragged_ops.prefill_chunks, "ssd_scan",
                           (i32(1, S), i32(1), i32(1), i32(1, MB), flags(1)),
                           i32(1)),
        "prefill_chunks_4": (ragged_ops.prefill_chunks, "ssd_scan",
                             (i32(4, S), i32(4), i32(4), i32(4, MB),
                              flags(4)), i32(4)),
    }
    for name, (fn, kernel, args, slots) in calls.items():
        with jax.default_matmul_precision("default"):
            compiled = fn.lower(cfg, params, arena, *args,
                                slots=slots).compile()
        mem, hlo = compiled.memory_analysis(), compiled.as_text()
        assert mem.peak_memory_in_bytes < 1.02 * FALCON_PEAK_BYTES[name], name
        assert mem.alias_size_in_bytes >= donated, name          # in place
        assert mem.temp_size_in_bytes < 0.2e9, name
        assert hlo.count("tpu_custom_call") >= 2, name
        assert kernel in hlo, name


# (slots, heads, head_dim, groups, N, chunk, layers): the granite cell's
# mixer (two 64-wide heads a 128-lane row of the stored state, one group)
# and falcon-h1's (whole rows, two groups)
SSM_SHAPES = [pytest.param(96, 128, 64, 1, 128, 256, 9, id="granite-4.0-h"),
              pytest.param(96, 32, 128, 2, 256, 128, 6, id="falcon-h1")]


@pytest.mark.parametrize("slots,NH,P,G,N,chunk,L", SSM_SHAPES)
def test_ssm_update_in_place_at_the_cells_shapes(chip, slots, NH, P, G, N,
                                                 chunk, L):
    """The one-token update at 96 rows on the arena's stored layout: one
    Mosaic kernel, the arena aliased (no copy of 3.7 or 2.4 GB)."""
    from deepspeed_tpu.ops import ssm
    F32 = jnp.float32
    state = chip((L, slots + 1) + ssm.state_shape(NH, G, N, P), F32)
    assert state.shape[-1] == 128
    row = chip((slots, NH, P), F32)
    bc = chip((slots, G, N), F32)
    fn = jax.jit(lambda s, i, x, d, b, c: ssm.ssm_update(s, 1, i, x, d, b, c),
                 donate_argnums=0)
    with jax.default_matmul_precision("default"):
        compiled = fn.lower(state, chip((slots,), jnp.int32), row, row, bc,
                            bc).compile()
    mem = compiled.memory_analysis()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert mem.alias_size_in_bytes >= state.size * 4
    assert mem.temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("slots,NH,P,G,N,chunk,L", SSM_SHAPES)
@pytest.mark.parametrize("R,S", [(1, 512), (4, 512)])
def test_ssd_scan_in_place_at_the_cells_shapes(chip, slots, NH, P, G, N,
                                               chunk, L, R, S):
    """The chunked scan of one and of four 512-token rows, the state in
    place on the arena: one Mosaic kernel, no copy of the arena; what XLA
    runs around it (the cumulative sum, the relayouts) stays under the
    operands' own size."""
    from deepspeed_tpu.ops import ssm
    F32 = jnp.float32
    state = chip((L, slots + 1) + ssm.state_shape(NH, G, N, P), F32)
    x, dt = chip((R, S, NH, P)), chip((R, S, NH), F32)
    bc = chip((R, S, G, N))
    fn = jax.jit(lambda x, dt, a, b, c, s, i, carried: ssm.ssd_scan(
        x, dt, a, b, c, s, 1, i, carried, chunk), donate_argnums=5)
    with jax.default_matmul_precision("default"):
        compiled = fn.lower(x, dt, chip((NH,), F32), bc, bc, state,
                            chip((R,), jnp.int32),
                            chip((R,), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert mem.alias_size_in_bytes >= state.size * 4
    assert mem.temp_size_in_bytes < 12 * R * S * NH * P


# `granite-4.0-h-small.decode_closed_support`'s programs: the weights are
# 9.93 GB, the arenas 4.15 GB (3.66 of state)
GRANITE_PEAK_BYTES = {"decode_step": 14.25e9, "prefill_full": 14.19e9,
                      "prefill_full_2": 14.19e9, "prefill_chunks": 14.19e9,
                      "prefill_chunks_4": 14.36e9}


def test_serving_programs_of_the_granite_cell_fit_and_size_arenas_by_kind(
        topo, chip, monkeypatch):
    """The cell's own `decode_step`, `prefill_full[1, 512]`,
    `prefill_full[2, 256]`, `prefill_chunks[1]` and `prefill_chunks[4]`
    (`benchmark/configs/granite-4.0-h-small.json`: one period of 9
    state-space layers and 1 attention layer at the published widths, 36
    of 72 experts, 96 rows and state slots), compiled for the described
    chip from abstract weights: the state arena has NINE rows of 3.66 GB
    together (two heads a lane row: not 7.3) and the K/V arena ONE; each
    program fits with the arenas donated and next to no temporaries (no
    run's slice of the stacked weights copied: 1.2 GB of them once), runs
    the state kernel of its kind, the paged or flash attention and two
    grouped matmuls a layer.  ~7 s a program."""
    import re

    from deepspeed_tpu.inference.v2 import ragged_ops

    cfg, params, arena, eng, ref, sizes = abstract_cell(
        chip, monkeypatch, "granite-4.0-h-small", "granite_moe_hybrid")
    B, MB = eng["max_seqs"], eng["max_blocks_per_seq"]
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert held == ref.weight_bytes(sizes, "bfloat16")
    assert arena["ssm"].shape == (9, 97, 64, 128, 128)
    assert arena["ssm"].size * 4 == pytest.approx(3.66e9, rel=2e-3)
    assert arena["conv"].shape == (9, 97, 3 * 8448)
    assert arena["k"].shape == arena["v"].shape == (1, 1664, 64, 8, 128)
    donated = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(arena))
    i32 = lambda *shape: chip(shape, jnp.int32)  # noqa: E731
    flags = lambda n: chip((n,), jnp.bool_)  # noqa: E731
    S = eng["prefill_chunk_size"]
    calls = {
        "decode_step": (ragged_ops.decode_step, "ssm_update",
                        (i32(B), i32(B), i32(B, MB), flags(B)), i32(B)),
        "prefill_full": (ragged_ops.prefill_full, "ssd_scan",
                         (i32(1, S), i32(1), i32(1, MB), flags(1)), i32(1)),
        "prefill_full_2": (ragged_ops.prefill_full, "ssd_scan",
                           (i32(2, S // 2), i32(2), i32(2, MB), flags(2)),
                           i32(2)),
        "prefill_chunks": (ragged_ops.prefill_chunks, "ssd_scan",
                           (i32(1, S), i32(1), i32(1), i32(1, MB), flags(1)),
                           i32(1)),
        "prefill_chunks_4": (ragged_ops.prefill_chunks, "ssd_scan",
                             (i32(4, S), i32(4), i32(4), i32(4, MB),
                              flags(4)), i32(4)),
    }
    for name, (fn, kernel, args, slots) in calls.items():
        with jax.default_matmul_precision("default"):
            compiled = fn.lower(cfg, params, arena, *args,
                                slots=slots).compile()
        mem, hlo = compiled.memory_analysis(), compiled.as_text()
        assert mem.peak_memory_in_bytes < 1.02 * GRANITE_PEAK_BYTES[name], \
            name
        assert mem.alias_size_in_bytes >= donated, name          # in place
        assert mem.temp_size_in_bytes < 0.35e9, name
        # two runs of state-space layers, the attention layer between: a
        # state kernel and two grouped matmuls a run, attention's kernel
        assert hlo.count("tpu_custom_call") >= 7, name
        assert kernel in hlo and "grouped_matmul" in hlo, name
        # a 512-row pass of a prompt holds each expert's rows from a tile
        # edge, 128 x (5120 / 128 + 36) rows; a decode step's 960 lie end to
        # end
        rows = "960" if name == "decode_step" else "9728"
        assert set(re.findall(r"grouped_matmul[.0-9]* = f32\[(\d+),4096\]",
                              hlo)) == {rows}, name
