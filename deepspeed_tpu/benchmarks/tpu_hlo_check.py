"""HLO structure checks of compiled programs: facts a result cannot show.

Each check AOT-compiles one program and reads its optimized HLO text:

- `check_multistep_single_scan` / `check_constrained_multistep`: the k
  decode steps of `ragged_ops.decode_multi_step` (with and without the
  grammar automaton) are iterations of one compiled scan, and the group's
  only host-bound output is one packed s32[B, k+1] buffer;
- `check_moe_a2a`: the expert-parallel dispatch/combine hop lowers to an
  all-to-all pair, with s8 payloads exactly on the int8 arms.

The assertions read trace metadata, the alias map and opcodes, so
`platform="cpu"` runs them on the virtual-device mesh (tier-1 calls them
from tests/test_multistep.py, tests/test_structured.py and
tests/test_moe_serving.py); `platform="tpu"` compiles for the DESCRIBED
topology (`TOPOLOGY`, the one v5e 2x2 host that exists; no chip need be
attached).  The collective structure of the ZeRO train step and of the
quantized double-buffered reductions is held on the CPU mesh by
tests/test_hlo_collectives.py and tests/test_quantized_collectives.py.
"""
from __future__ import annotations

import re
from typing import Dict

from jax import shard_map

from .hlo_census import collective_census


# the layer scan running INSIDE the step scan, as the op_name metadata
# spells it (a scan body is a closed_call under the while body)
_NESTED_SCAN = re.compile(r"/while/body(/closed_call)?/while/body")

# the machine that exists: one v5e host, four chips in a 2x2
TOPOLOGY = "v5e:2x2"


def _tpu_devices(n_partitions: int):
    """The first `n_partitions` devices of the DESCRIBED topology (nothing
    need be attached: the TPU compiler compiles for a description)."""
    from jax.experimental import topologies
    devs = list(topologies.get_topology_desc(
        platform="tpu", topology_name=TOPOLOGY).devices)
    if len(devs) < n_partitions:
        raise RuntimeError(
            f"{TOPOLOGY} has {len(devs)} devices, need {n_partitions}")
    return devs[:n_partitions]


def _mesh(n_partitions: int):
    """The described devices as a mesh over dp (AXIS_ORDER's first axis)."""
    import numpy as np
    from jax.sharding import Mesh

    from ..parallel.mesh import AXIS_ORDER

    shape = [n_partitions] + [1] * (len(AXIS_ORDER) - 1)
    return Mesh(np.array(_tpu_devices(n_partitions)).reshape(shape),
                AXIS_ORDER)


def check_multistep_single_scan(platform: str = "tpu") -> Dict:
    """AOT-compile the multi-step decode group program (ISSUE 17:
    `ragged_ops.decode_multi_step`, k decode steps in ONE dispatch with
    on-device sampling + termination) and assert the two structural
    facts the serve loop's host-free steady state rests on:

    - the k steps run as ITERATIONS of one compiled while/scan region
      (the step scan wrapping the layer scan), not as k unrolled or
      re-dispatched step bodies.  Locked two ways: the nested-scan
      trace metadata `.../while/body/.../while/body` is present, and
      the while-op census is IDENTICAL at k=8 and k=16 — only the trip
      count may change with k, never the loop structure;
    - the emission fetch is a single d2h transfer per group: the entry
      root carries exactly one packed s32[B, k+1] buffer, and every
      other root element is a donated arena leaf (input_output_alias),
      so the packed array is the only payload that can cross to host.

    The assertions read trace metadata, the alias map, and the root
    tuple — all backend-portable — so `platform="cpu"` exercises the
    same check on the CPU compiler (used by the standalone smoke);
    the default lowers against the real TPU topology like the other
    checks here.  Returns {whiles_k8, whiles_k16, aliased_outputs,
    root_elems}."""
    import jax
    import jax.numpy as jnp

    from ..inference.v2 import ragged_ops as ro
    from ..models.transformer import Transformer, TransformerConfig

    if platform == "tpu":
        mesh = _mesh(1)
        from jax.sharding import NamedSharding, PartitionSpec
        repl = NamedSharding(mesh, PartitionSpec())
    else:
        repl = None

    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2,
                            num_heads=4, max_seq_len=128,
                            dtype=jnp.float32)
    B, MB, nb, bs = 4, 8, 32, 8
    params_s = jax.eval_shape(Transformer(cfg).init_params,
                              jax.random.PRNGKey(0))
    arena_s = jax.eval_shape(lambda: ro.init_arena(cfg, nb, bs))
    n_arena = len(jax.tree.leaves(arena_s))

    def _s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    def _tree(t):
        return jax.tree.map(lambda l: _s(l.shape, l.dtype), t)

    def _lower(k):
        return ro.decode_multi_step.lower(  # dstpu: noqa[DST004] AOT check compiles each k exactly once; no hot path
            cfg, _tree(params_s), _tree(arena_s),
            _s((B,), jnp.int32),      # tokens
            _s((B,), jnp.int32),      # seq_lens
            _s((B, MB), jnp.int32),   # block_tables
            _s((B,), jnp.bool_),      # active
            _s((2,), jnp.uint32),     # rng key
            _s((B,), jnp.float32),    # temperature
            _s((B,), jnp.int32),      # max_len
            _s((B,), jnp.int32),      # top_k_vec
            _s((B,), jnp.int32),      # eos_ids
            _s((B,), jnp.int32),      # budget
            _s((B,), jnp.uint32),     # seed_hi
            _s((B,), jnp.uint32),     # seed_lo
            _s((B,), jnp.int32),      # seed_pos
            _s((B,), jnp.bool_),      # has_seed
            k=k).compile().as_text()

    def _whiles(txt):
        return len(re.findall(r" while\(", txt))

    txt = _lower(8)
    w8 = _whiles(txt)
    assert w8 >= 2, (
        f"k=8 group program has {w8} while regions — expected at least "
        f"the step scan + the layer scan; the group loop did not "
        f"compile as a loop")
    assert _NESTED_SCAN.search(txt), (
        "nested-scan metadata missing: the layer scan is not running "
        "INSIDE the step scan — the k steps are not one compiled "
        "while/scan decode region")
    # one packed s32[B, k+1] emission buffer in the entry root, every
    # other root element a donated arena alias -> single d2h per group
    entry = txt.split("ENTRY ")[-1]
    root = next(l for l in entry.splitlines()
                if l.strip().startswith("ROOT"))
    packed = f"s32[{B},{8 + 1}]"
    # read the root TUPLE TYPE (the part before the operand list, which
    # carries no types in this XLA's text); shapes hold commas, so count
    # dtype atoms instead
    root_type = root.split(" tuple(")[0]
    assert root_type.count(packed) == 1, (
        f"entry root does not carry exactly one packed {packed} "
        f"emission buffer: {root[:300]}")
    root_elems = len(re.findall(r"(?:pred|bf16|[fsu]\d+)\[", root_type))
    aliased = txt.count("may-alias")
    assert aliased >= n_arena and root_elems == 1 + n_arena, (
        f"root has {root_elems} elements with {aliased} aliased for "
        f"{n_arena} arena leaves — a non-arena, non-packed output "
        f"would be a second d2h payload per group")
    w16 = _whiles(_lower(16))
    assert w16 == w8, (
        f"while census changed with k ({w8} at k=8, {w16} at k=16) — "
        f"the step count is leaking into loop STRUCTURE instead of "
        f"riding the trip count of one compiled region")
    return {"whiles_k8": w8, "whiles_k16": w16,
            "aliased_outputs": aliased, "root_elems": root_elems}


def check_constrained_multistep(platform: str = "tpu") -> Dict:
    """AOT-compile the CONSTRAINED multi-step group program (ISSUE 18:
    `decode_multi_step` with the grammar-automaton operands) and assert
    that adding the FSM changes nothing the host-free steady state
    rests on:

    - the k constrained steps still run as ONE compiled while/scan
      region (nested-scan metadata present; while census identical at
      k=8 and k=16, and identical to the UNCONSTRAINED program's — the
      mask gather and in-scan state advance must ride the existing
      scan body, not add loop structure);
    - the emission fetch is still the single packed s32[B, k+1] d2h
      buffer with every other root element a donated arena alias —
      the per-row FSM states are consumed inside the scan and
      discarded, so constrained decode adds ZERO d2h payloads;
    - no host callback crept in: the automaton tables are device
      operands, so the executable must contain no host-python
      custom-call (a callback would be a hidden per-step round trip).

    Backend-portable like the unconstrained check; `platform="cpu"`
    rides tier-1.  Returns {whiles_k8, whiles_k16, whiles_plain,
    aliased_outputs, root_elems}."""
    import jax
    import jax.numpy as jnp

    from ..inference.v2 import ragged_ops as ro
    from ..models.transformer import Transformer, TransformerConfig

    if platform == "tpu":
        mesh = _mesh(1)
        from jax.sharding import NamedSharding, PartitionSpec
        repl = NamedSharding(mesh, PartitionSpec())
    else:
        repl = None

    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2,
                            num_heads=4, max_seq_len=128,
                            dtype=jnp.float32)
    B, MB, nb, bs = 4, 8, 32, 8
    S, V = 16, cfg.vocab_size           # automaton states x vocab
    params_s = jax.eval_shape(Transformer(cfg).init_params,
                              jax.random.PRNGKey(0))
    arena_s = jax.eval_shape(lambda: ro.init_arena(cfg, nb, bs))
    n_arena = len(jax.tree.leaves(arena_s))

    def _s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    def _tree(t):
        return jax.tree.map(lambda l: _s(l.shape, l.dtype), t)

    def _lower(k, constrained=True):
        fkw = {}
        if constrained:
            fkw = dict(
                fsm_trans=_s((S, V), jnp.int32),
                fsm_mask=_s((S, (V + 31) // 32), jnp.uint32),
                fsm_accept=_s((S,), jnp.bool_),
                fsm_state=_s((B,), jnp.int32),
                has_fsm=_s((B,), jnp.bool_))
        return ro.decode_multi_step.lower(  # dstpu: noqa[DST004] AOT check compiles each variant exactly once; no hot path
            cfg, _tree(params_s), _tree(arena_s),
            _s((B,), jnp.int32),      # tokens
            _s((B,), jnp.int32),      # seq_lens
            _s((B, MB), jnp.int32),   # block_tables
            _s((B,), jnp.bool_),      # active
            _s((2,), jnp.uint32),     # rng key
            _s((B,), jnp.float32),    # temperature
            _s((B,), jnp.int32),      # max_len
            _s((B,), jnp.int32),      # top_k_vec
            _s((B,), jnp.int32),      # eos_ids
            _s((B,), jnp.int32),      # budget
            _s((B,), jnp.uint32),     # seed_hi
            _s((B,), jnp.uint32),     # seed_lo
            _s((B,), jnp.int32),      # seed_pos
            _s((B,), jnp.bool_),      # has_seed
            **fkw, k=k).compile().as_text()

    def _whiles(txt):
        return len(re.findall(r" while\(", txt))

    txt = _lower(8)
    w8 = _whiles(txt)
    assert w8 >= 2, (
        f"constrained k=8 group program has {w8} while regions — "
        f"expected at least the step scan + the layer scan")
    assert _NESTED_SCAN.search(txt), (
        "nested-scan metadata missing from the constrained program: "
        "the FSM mask/advance broke the single compiled decode region")
    w_plain = _whiles(_lower(8, constrained=False))
    assert w8 == w_plain, (
        f"FSM operands changed the while census ({w_plain} "
        f"unconstrained -> {w8} constrained) — the grammar mask must "
        f"ride the existing scan body, not add loop structure")
    # host-callback census: the automaton is device tables; any python
    # callback custom-call would be a hidden per-step host round trip
    assert "xla_python_cpu_callback" not in txt \
        and "xla_ffi_python" not in txt, (
        "constrained program contains a host python callback")
    entry = txt.split("ENTRY ")[-1]
    root = next(l for l in entry.splitlines()
                if l.strip().startswith("ROOT"))
    packed = f"s32[{B},{8 + 1}]"
    root_type = root.split(" tuple(")[0]
    assert root_type.count(packed) == 1, (
        f"constrained entry root does not carry exactly one packed "
        f"{packed} emission buffer: {root[:300]}")
    root_elems = len(re.findall(r"(?:pred|bf16|[fsu]\d+)\[", root_type))
    aliased = txt.count("may-alias")
    assert aliased >= n_arena and root_elems == 1 + n_arena, (
        f"constrained root has {root_elems} elements with {aliased} "
        f"aliased for {n_arena} arena leaves — the FSM added a d2h "
        f"payload (final states must be consumed on device, not "
        f"returned)")
    w16 = _whiles(_lower(16))
    assert w16 == w8, (
        f"constrained while census changed with k ({w8} at k=8, {w16} "
        f"at k=16)")
    return {"whiles_k8": w8, "whiles_k16": w16, "whiles_plain": w_plain,
            "aliased_outputs": aliased, "root_elems": root_elems}


def check_moe_a2a(platform: str = "tpu", n_partitions: int = 4) -> Dict:
    """AOT-compile the expert-parallel MoE wire hop (ISSUE 20:
    `moe/sharded.py moe_dispatch_a2a` + `moe_combine_a2a`, the explicit
    dispatch/combine path of `_moe_layer_a2a`) per [E, C, H] shape and
    assert the structure the comm claim rests on:

    - the raw program carries an all-to-all PAIR (one dispatch hop, one
      combine hop) — a regression to gather-everything would show
      all-gathers instead and ep would stop scaling the wire;
    - under int8 quantized dispatch (dispatch_bits=8), the a2a payloads
      on the wire are s8/u8 — a silent dequantize-before-ship would
      compile, route bit-identically, and quietly give the bytes back.

    Backend-portable (the census reads HLO text): `platform="cpu"`
    rides tier-1 on the virtual-device mesh; the default lowers against
    the described TPU topology like the checks above.  Returns
    {shapes: {label: {census, s8_a2a}}}."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as Pspec

    from ..moe.sharded import moe_combine_a2a, moe_dispatch_a2a

    if platform == "tpu":
        devs = _tpu_devices(n_partitions)
    else:
        devs = jax.devices()[:n_partitions]
        if len(devs) < n_partitions:
            raise RuntimeError(
                f"{len(devs)} devices, need {n_partitions} (run under "
                f"the virtual-device mesh)")
    mesh = Mesh(np.array(devs), ("ep",))

    out: Dict[str, Dict] = {}
    shapes = {
        # (E, C, H): a tiny buffer and a serving-sized one
        "e8_c64_h256": (8, 64, 256),
        "e16_c256_h1024": (16, 256, 1024),
    }
    for label, (E, C, H) in shapes.items():
        for bits in (None, 8):
            def hop(v, b=bits):
                d = moe_dispatch_a2a(v, "ep", bits=b)
                return moe_combine_a2a(d, "ep", bits=b)

            arg = jax.ShapeDtypeStruct(
                (E, C, H), jnp.float32,
                sharding=NamedSharding(mesh, Pspec()))
            sm = shard_map(hop, mesh=mesh, in_specs=(Pspec(),),
                           out_specs=Pspec(), check_vma=False)
            txt = jax.jit(sm).lower(arg).compile().as_text()  # dstpu: noqa[DST004] AOT check compiles each (shape, bits) arm exactly once; no hot path
            census = collective_census(txt)
            a2a = census.get("all-to-all", 0)
            # by opcode: the result type sits between "=" and the op
            s8 = len(re.findall(
                r"= \(?[su]8\[[^\n]*? all-to-all(?:-start)?\(", txt))
            assert a2a >= 2, (
                f"{label} bits={bits}: expected an all-to-all pair "
                f"(dispatch + combine), got {census} — the explicit EP "
                f"wire path is not lowering to a2a")
            if bits == 8:
                assert s8 >= 2, (
                    f"{label} int8: only {s8} of the a2a ops carry "
                    f"s8/u8 payloads — the quantized dispatch is "
                    f"shipping dequantized bytes")
            else:
                assert s8 == 0, (
                    f"{label} raw: unexpected s8 a2a payloads ({s8})")
            key = f"{label}_{'int8' if bits else 'raw'}"
            out[key] = {"census": census, "s8_a2a": s8}
    return {"shapes": out}
