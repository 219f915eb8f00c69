"""The tracing-hygiene rules (DST001-DST005).

Each rule is a pure function over the ProjectIndex returning Finding
objects.  Rules are deliberately over-approximate — static analysis
cannot see dtypes or devices — and the engine's suppression
(`# dstpu: noqa[RULE] reason`) + baseline machinery exists precisely so
a justified site is silenced WITH its justification recorded, while an
accidental new site fails the gate.

Rule catalog (docs/ANALYSIS.md has the long form):

- **DST001 host-sync-in-hot-path**: a host-transfer-shaped call
  (`jax.device_get`, `.item()`, `.tolist()`, `block_until_ready`,
  `np.asarray`/`np.array`, `float()`/`int()`/`bool()` on a
  possibly-device value) inside a function reachable from the serving
  hot roots (`ServeLoop.step`, the engine's prefill/decode surface) or
  inside any `@jax.jit`-decorated function.  This is the bug class PR 2
  removed from the decode loop: one accidental materialization
  in the decode loop ships [max_seqs, vocab] logits to the host every
  token.
- **DST002 traced-control-flow**: Python `if`/`while`/`assert` on a
  value derived from a traced argument inside a jitted function —
  either a trace error waiting for the first non-constant input, or a
  silent specialization-by-value (one recompile per distinct value).
- **DST003 use-after-donation**: an argument passed at a
  `donate_argnums` position of a jitted call is read again before being
  rebound — donated buffers are invalidated by XLA aliasing, so the
  read returns garbage (or raises) on hardware even when CPU happens to
  keep the data alive.
- **DST004 recompile-hazard**: `jax.jit` constructed inside a loop body
  (a fresh compile cache per iteration), or a shape-derived Python
  scalar (`x.shape[...]`, `len(x)`) fed as a static argument of a
  jitted call (one compile per distinct shape, the classic silent
  recompile treadmill; power-of-two bucket it first).
- **DST005 unlocked-shared-mutation**: inside a class that owns a
  `threading.Lock`/`Condition`, a method mutates `self` state outside a
  `with self.<lock>:` block (the `ThreadedServer` contract: the loop
  thread and the client surface share request/telemetry state).
"""
from __future__ import annotations

import ast
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .callgraph import (FunctionInfo, ModuleInfo, ProjectIndex,
                        enclosing_function, iter_parents, reachable)
from .core import Finding

__all__ = ["RULES", "DEFAULT_HOT_ROOTS", "run_rules"]

# The serving hot paths this repo promises to keep sync-free: the serve
# loop step and the engine's prefill/decode/generate surface.  Matching
# is by suffix, so fixture trees with ad-hoc module names participate.
DEFAULT_HOT_ROOTS: Tuple[str, ...] = (
    "serving.server:ServeLoop.step",
    "serving.server:ServeLoop.run_until_idle",
    "serving.server:ThreadedServer._run",
    "inference.v2.engine_v2:InferenceEngineV2.put",
    "inference.v2.engine_v2:InferenceEngineV2.step",
    "inference.v2.engine_v2:InferenceEngineV2.decode_burst_step",
    "inference.v2.engine_v2:InferenceEngineV2.decode_multi_step",
    "inference.v2.engine_v2:InferenceEngineV2.sample_tokens_batch",
    "inference.v2.engine_v2:InferenceEngineV2.generate",
    "inference.v2.engine_v2:InferenceEngineV2.generate_batch",
    "inference.v2.engine_v2:InferenceEngineV2.flush",
)

# builtins whose results are host values — a name assigned from one of
# these can be int()ed / np.asarray()ed freely
_HOST_BUILTINS = {"len", "int", "float", "bool", "str", "list", "dict",
                  "set", "tuple", "sorted", "range", "min", "max", "sum",
                  "enumerate", "zip", "abs", "round", "divmod", "repr"}

_SHAPE_ATTRS = {"shape", "ndim", "dtype", "size"}

_MUTATING_METHODS = {"append", "extend", "insert", "add", "remove",
                     "discard", "pop", "popitem", "popleft", "clear",
                     "update", "setdefault", "appendleft", "sort",
                     "reverse", "push"}


# -- shared AST helpers ----------------------------------------------------

def _root_name(node: ast.AST) -> Optional[str]:
    """Leftmost Name of an attribute/subscript/call chain."""
    while True:
        if isinstance(node, ast.Name):
            return node.id
        if isinstance(node, (ast.Attribute, ast.Subscript, ast.Starred)):
            node = node.value
        elif isinstance(node, ast.Call):
            node = node.func
        else:
            return None


def _attr_chain(node: ast.AST) -> Optional[str]:
    """Dotted string for a pure Name/Attribute chain ("self.arena")."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _ordered_statements(fn_node: ast.AST) -> List[ast.stmt]:
    """All statements in the function, source order, nested included."""
    out = [n for n in ast.walk(fn_node) if isinstance(n, ast.stmt)
           and n is not fn_node]
    out.sort(key=lambda n: (n.lineno, n.col_offset))
    return out


def _is_np_call(call: ast.Call, mod: ModuleInfo,
                names: Iterable[str] = ("asarray", "array",
                                        "ascontiguousarray")) -> bool:
    f = call.func
    return (isinstance(f, ast.Attribute) and f.attr in names
            and isinstance(f.value, ast.Name)
            and f.value.id in mod.numpy_aliases())


def _is_device_get(call: ast.Call, mod: ModuleInfo) -> bool:
    f = call.func
    if isinstance(f, ast.Attribute) and f.attr == "device_get":
        return (isinstance(f.value, ast.Name)
                and f.value.id in mod.jax_aliases())
    if isinstance(f, ast.Name):
        return mod.from_imports.get(f.id) == ("jax", "device_get")
    return False


def _classify_expr(node: ast.AST, mod: ModuleInfo, host: Set[str],
                   device: Set[str], index: ProjectIndex,
                   caller: FunctionInfo) -> Optional[str]:
    """'host' / 'device' / None (unknown) for an assignment RHS."""
    if isinstance(node, ast.Constant):
        return "host"
    if isinstance(node, (ast.List, ast.Dict, ast.Set, ast.Tuple,
                         ast.ListComp, ast.DictComp, ast.SetComp,
                         ast.GeneratorExp, ast.JoinedStr, ast.Compare,
                         ast.BoolOp)):
        return "host"
    if isinstance(node, ast.Name):
        if node.id in host:
            return "host"
        if node.id in device:
            return "device"
        return None
    if isinstance(node, ast.Call):
        f = node.func
        if _is_device_get(node, mod) or _is_np_call(node, mod):
            return "host"
        if isinstance(f, ast.Name) and f.id in _HOST_BUILTINS:
            return "host"
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
            base = f.value.id
            if base in mod.numpy_aliases():
                return "host"                     # any np.* producer
            if (base in mod.jax_numpy_aliases()
                    or base in mod.jax_aliases()):
                return "device"                   # jnp.* / jax.* producer
        # call to a known-jitted project function -> device result
        for fid in _resolved_targets(node, caller, mod, index):
            info = index.functions.get(fid)
            if info is not None and info.jit is not None:
                return "device"
        return None
    if isinstance(node, (ast.Subscript, ast.Attribute)):
        root = _root_name(node)
        if root in host:
            return "host"
        if root in device:
            return "device"
        return None
    if isinstance(node, ast.BinOp):
        left = _classify_expr(node.left, mod, host, device, index, caller)
        right = _classify_expr(node.right, mod, host, device, index, caller)
        if "device" in (left, right):
            return "device"
        if left == "host" and right == "host":
            return "host"
        return None
    return None


def _resolved_targets(call: ast.Call, caller: FunctionInfo,
                      mod: ModuleInfo, index: ProjectIndex) -> Set[str]:
    from .callgraph import _resolve_call
    return _resolve_call(call, caller, mod, index)


class _TaintScan:
    """Flow-sensitive host/device classification of local names.  Drive
    it statement-by-statement in source order: query `host`/`device`
    BEFORE calling `apply(stmt)` so a statement's own rebind (e.g.
    `logits = np.asarray(logits)`) doesn't retroactively launder the
    device value it just fetched."""

    def __init__(self, fn: FunctionInfo, mod: ModuleInfo,
                 index: ProjectIndex) -> None:
        self.fn, self.mod, self.index = fn, mod, index
        self.host: Set[str] = set()
        self.device: Set[str] = set()

    def _set(self, names: Iterable[str], cls: Optional[str]) -> None:
        for n in names:
            self.host.discard(n)
            self.device.discard(n)
            if cls == "host":
                self.host.add(n)
            elif cls == "device":
                self.device.add(n)

    def apply(self, stmt: ast.stmt) -> None:
        targets: List[ast.AST] = []
        value: Optional[ast.AST] = None
        if isinstance(stmt, ast.Assign):
            targets, value = stmt.targets, stmt.value
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            targets, value = [stmt.target], stmt.value
        elif isinstance(stmt, ast.AugAssign):
            targets, value = [stmt.target], stmt.value
        elif isinstance(stmt, ast.For):
            cls = _classify_expr(stmt.iter, self.mod, self.host,
                                 self.device, self.index, self.fn)
            if isinstance(stmt.target, ast.Name):
                self._set([stmt.target.id], cls)
            elif isinstance(stmt.target, (ast.Tuple, ast.List)):
                # element class is unknowable; clear stale state
                self._set([e.id for e in stmt.target.elts
                           if isinstance(e, ast.Name)], None)
            return
        else:
            return
        cls = _classify_expr(value, self.mod, self.host, self.device,
                             self.index, self.fn)
        names: List[str] = []
        for t in targets:
            if isinstance(t, ast.Name):
                names.append(t.id)
            elif isinstance(t, (ast.Tuple, ast.List)):
                names.extend(e.id for e in t.elts
                             if isinstance(e, ast.Name))
        self._set(names, cls)


def _stmt_exprs(stmt: ast.stmt) -> List[ast.AST]:
    """The expressions evaluated directly by `stmt` (nested statements of
    compound bodies are separate entries of the ordered walk)."""
    if isinstance(stmt, ast.Assign):
        return [stmt.value] + list(stmt.targets)
    if isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
        return [e for e in (stmt.value, stmt.target) if e is not None]
    if isinstance(stmt, (ast.Expr, ast.Return)):
        return [stmt.value] if stmt.value is not None else []
    if isinstance(stmt, (ast.If, ast.While)):
        return [stmt.test]
    if isinstance(stmt, (ast.For, ast.AsyncFor)):
        return [stmt.iter]
    if isinstance(stmt, (ast.With, ast.AsyncWith)):
        return [i.context_expr for i in stmt.items]
    if isinstance(stmt, ast.Assert):
        return [e for e in (stmt.test, stmt.msg) if e is not None]
    if isinstance(stmt, ast.Raise):
        return [e for e in (stmt.exc, stmt.cause) if e is not None]
    if isinstance(stmt, ast.Delete):
        return list(stmt.targets)
    return []


def _relpath(index: ProjectIndex, fn: FunctionInfo) -> str:
    return fn.path


# -- DST001: host sync in hot path ----------------------------------------

def rule_dst001(index: ProjectIndex, config) -> List[Finding]:
    hot = reachable(index, config.hot_roots,
                    include_jit=config.include_jit_roots)
    findings: List[Finding] = []
    for fid, provenance in hot.items():
        fn = index.functions[fid]
        mod = index.modules[fn.module]
        scan = _TaintScan(fn, mod, index)

        def emit(node, message):
            findings.append(Finding(
                rule="DST001", path=fn.path, line=node.lineno,
                col=node.col_offset, message=message, symbol=fn.qualname,
                detail=f"hot path via {provenance}"))

        def check_call(node: ast.Call) -> None:
            f = node.func
            host, device = scan.host, scan.device
            if _is_device_get(node, mod):
                emit(node, "host sync: jax.device_get (explicit device->"
                           "host fetch on a hot path)")
            elif isinstance(f, ast.Attribute):
                recv_root = _root_name(f.value)
                recv_host = recv_root in host or (
                    recv_root in mod.numpy_aliases())
                if f.attr == "block_until_ready":
                    emit(node, "host sync: block_until_ready blocks the "
                               "dispatch pipeline")
                elif f.attr in ("item", "tolist") and not recv_host:
                    emit(node, f"host sync: .{f.attr}() materializes a "
                               f"device value")
                elif _is_np_call(node, mod) and node.args:
                    arg = node.args[0]
                    root = _root_name(arg)
                    if not (isinstance(arg, (ast.Constant, ast.List,
                                             ast.Tuple, ast.ListComp,
                                             ast.GeneratorExp))
                            or root in host):
                        emit(node, f"host sync: np.{f.attr} on a "
                                   f"possibly-device value")
            elif isinstance(f, ast.Name) and f.id in ("float", "int",
                                                      "bool"):
                if not node.args:
                    return
                arg = node.args[0]
                flag = False
                if isinstance(arg, ast.Name):
                    flag = arg.id in device
                elif isinstance(arg, (ast.Subscript, ast.Attribute,
                                      ast.Call)):
                    root = _root_name(arg)
                    flag = root not in host and root not in (
                        mod.numpy_aliases())
                    if isinstance(arg, ast.Call):
                        cf = arg.func
                        if (isinstance(cf, ast.Name)
                                and cf.id in _HOST_BUILTINS):
                            flag = False
                if flag:
                    emit(node, f"host sync: {f.id}() on a possibly-device "
                               f"value")

        for stmt in _ordered_statements(fn.node):
            for expr in _stmt_exprs(stmt):
                for node in ast.walk(expr):
                    if isinstance(node, ast.Call):
                        check_call(node)
            scan.apply(stmt)
    return findings


# -- DST002: python control flow on traced values inside jit ---------------

def _names_by_value(expr: ast.AST) -> Set[str]:
    """Names used BY VALUE in `expr`: excludes names only touched under
    .shape/.ndim/.dtype/.size, len(...)/isinstance(...), or `is`/`is not`
    comparisons — those read static trace-time facts, not traced data."""
    out: Set[str] = set()

    def visit(node, skip):
        if isinstance(node, ast.Name):
            if not skip:
                out.add(node.id)
            return
        if isinstance(node, ast.Attribute):
            visit(node.value, skip or node.attr in _SHAPE_ATTRS)
            return
        if isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Name) and f.id in ("len", "isinstance",
                                                    "getattr", "hasattr",
                                                    "type"):
                for a in node.args:
                    visit(a, True)
                return
            for child in ast.iter_child_nodes(node):
                visit(child, skip)
            return
        if isinstance(node, ast.Compare):
            ops_static = all(isinstance(o, (ast.Is, ast.IsNot))
                             for o in node.ops)
            visit(node.left, skip or ops_static)
            for c in node.comparators:
                visit(c, skip or ops_static)
            return
        for child in ast.iter_child_nodes(node):
            visit(child, skip)

    visit(expr, False)
    return out


def rule_dst002(index: ProjectIndex, config) -> List[Finding]:
    findings: List[Finding] = []
    for fn in index.jitted():
        mod = index.modules[fn.module]
        params = fn.params
        jit = fn.jit
        static = set()
        for i in jit.static_argnums:
            if 0 <= i < len(params):
                static.add(params[i])
        static.update(jit.static_argnames)
        tainted = {p for p in params if p not in static and p != "self"}

        # propagate taint through assignments (two passes reach the
        # chains a single forward pass misses in loop bodies)
        stmts = _ordered_statements(fn.node)
        for _ in range(2):
            for stmt in stmts:
                if not isinstance(stmt, (ast.Assign, ast.AugAssign,
                                         ast.AnnAssign)):
                    continue
                value = stmt.value
                if value is None:
                    continue
                if _names_by_value(value) & tainted:
                    targets = (stmt.targets if isinstance(stmt, ast.Assign)
                               else [stmt.target])
                    for t in targets:
                        if isinstance(t, ast.Name):
                            tainted.add(t.id)
                        elif isinstance(t, (ast.Tuple, ast.List)):
                            tainted.update(e.id for e in t.elts
                                           if isinstance(e, ast.Name))

        def emit(node, kind, names):
            findings.append(Finding(
                rule="DST002", path=fn.path, line=node.lineno,
                col=node.col_offset,
                message=f"python {kind} on traced value inside @jax.jit "
                        f"(trace error or silent per-value recompile)",
                symbol=fn.qualname,
                detail=f"traced name(s): {', '.join(sorted(names))}"))

        for node in ast.walk(fn.node):
            # nested defs inside a jitted fn are traced too; keep them
            if isinstance(node, ast.If) or isinstance(node, ast.While):
                used = _names_by_value(node.test) & tainted
                if used:
                    emit(node, "if" if isinstance(node, ast.If) else
                         "while", used)
            elif isinstance(node, ast.Assert):
                used = _names_by_value(node.test) & tainted
                if used:
                    emit(node, "assert", used)
            elif isinstance(node, ast.IfExp):
                used = _names_by_value(node.test) & tainted
                if used:
                    emit(node, "conditional expression", used)
    return findings


# -- DST003: donated-buffer use-after-donation -----------------------------

def rule_dst003(index: ProjectIndex, config) -> List[Finding]:
    findings: List[Finding] = []
    for fn in index.functions.values():
        mod = index.modules[fn.module]
        for node in ast.walk(fn.node):
            if not isinstance(node, ast.Call):
                continue
            for fid in _resolved_targets(node, fn, mod, index):
                callee = index.functions.get(fid)
                if callee is None or callee.jit is None:
                    continue
                for di in callee.jit.donate_argnums:
                    if di >= len(node.args):
                        continue
                    chain = _attr_chain(node.args[di])
                    if chain is None:
                        continue
                    bad = _used_after_donation(fn, node, chain)
                    if bad is not None:
                        findings.append(Finding(
                            rule="DST003", path=fn.path, line=bad.lineno,
                            col=bad.col_offset,
                            message=f"donated buffer `{chain}` read after "
                                    f"donation (donate_argnums aliases it "
                                    f"to the output; the read returns "
                                    f"garbage on hardware)",
                            symbol=fn.qualname,
                            detail=f"donated at call to "
                                   f"{callee.qualname}:{node.lineno}"))
    return findings


def _used_after_donation(fn: FunctionInfo, call: ast.Call,
                         chain: str) -> Optional[ast.AST]:
    """First Load of `chain` after the donating call without an
    intervening rebind.  The donating statement's own assignment targets
    count as the rebind (`x, buf = jitted(buf, ...)`)."""
    call_stmt = None
    for p in iter_parents(call):
        if isinstance(p, ast.stmt):
            call_stmt = p
            break
    if call_stmt is None:
        return None
    # rebind in the donating statement itself?
    if isinstance(call_stmt, ast.Assign):
        for t in call_stmt.targets:
            for el in ([t.elts] if isinstance(t, (ast.Tuple, ast.List))
                       else [[t]]):
                for e in el:
                    if _attr_chain(e) == chain:
                        return None
    # the donating statement's own subtree is not a use-after (the
    # donated argument itself lives there; tuple-target rebinds were
    # checked above)
    own = {id(n) for n in ast.walk(call_stmt)}
    events: List[Tuple[int, int, str, ast.AST]] = []
    for node in ast.walk(fn.node):
        if id(node) in own or _attr_chain(node) != chain:
            continue
        if (node.lineno, node.col_offset) < (call_stmt.lineno,
                                             call_stmt.col_offset):
            continue
        # a store rebinds; a load after donation is the bug
        ctx = getattr(node, "ctx", None)
        kind = "store" if isinstance(ctx, (ast.Store, ast.Del)) else "load"
        events.append((node.lineno, node.col_offset, kind, node))
    events.sort(key=lambda e: (e[0], e[1]))
    for _, _, kind, node in events:
        if kind == "store":
            return None
        return node
    return None


# -- DST004: recompile hazards ---------------------------------------------

def rule_dst004(index: ProjectIndex, config) -> List[Finding]:
    findings: List[Finding] = []
    for fn in index.functions.values():
        mod = index.modules[fn.module]
        from .callgraph import _call_is_jax_jit
        for node in ast.walk(fn.node):
            if not isinstance(node, ast.Call):
                continue
            # (a) jax.jit(...) constructed inside a loop body
            if _call_is_jax_jit(node, mod):
                in_loop = any(isinstance(p, (ast.For, ast.While))
                              for p in iter_parents(node))
                if in_loop:
                    findings.append(Finding(
                        rule="DST004", path=fn.path, line=node.lineno,
                        col=node.col_offset,
                        message="jax.jit constructed inside a loop body "
                                "(fresh compile cache every iteration)",
                        symbol=fn.qualname,
                        detail="auto-fix: hoist the jax.jit(...) above "
                               "the loop (module level or a cached "
                               "attribute) so every iteration reuses ONE "
                               "compiled program and its cache"))
                continue
            # (b) shape-derived python scalar at a static position
            for fid in _resolved_targets(node, fn, mod, index):
                callee = index.functions.get(fid)
                if callee is None or callee.jit is None:
                    continue
                jit = callee.jit
                cparams = callee.params
                static_exprs: List[ast.AST] = []
                for i in jit.static_argnums:
                    if i < len(node.args):
                        static_exprs.append(node.args[i])
                static_names = set(jit.static_argnames)
                static_names.update(cparams[i] for i in jit.static_argnums
                                    if i < len(cparams))
                for kw in node.keywords:
                    if kw.arg in static_names:
                        static_exprs.append(kw.value)
                for expr in static_exprs:
                    if _is_shape_derived(expr):
                        findings.append(Finding(
                            rule="DST004", path=fn.path, line=expr.lineno,
                            col=expr.col_offset,
                            message=f"shape-derived python scalar fed as "
                                    f"a static arg of {callee.qualname} "
                                    f"(one compile per distinct shape — "
                                    f"bucket it)",
                            symbol=fn.qualname,
                            detail=_bucket_suggestion(expr)))
    return findings


def _bucket_suggestion(expr: ast.AST) -> str:
    """Concrete auto-fix for a shape-derived static arg: the power-of-2
    bucket expression (the idiom engine_v2's prefill/NS bucketing uses),
    spelled with the offending expression inlined so the fix is
    copy-pasteable."""
    try:
        src = ast.unparse(expr)
    except Exception:            # very old ast nodes without unparse info
        src = "<value>"
    return (f"auto-fix: bucket the static value to a power of two so "
            f"each bucket compiles once — e.g. "
            f"`n = max(1, 1 << (int({src}) - 1).bit_length())` "
            f"(pad the data to n) — instead of one compile per "
            f"distinct shape")


def _is_shape_derived(expr: ast.AST) -> bool:
    for node in ast.walk(expr):
        if isinstance(node, ast.Attribute) and node.attr in ("shape",):
            return True
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "len"):
            return True
    return False


# -- DST005: shared-state mutation without the lock ------------------------

def _with_lock_attrs(node: ast.AST) -> Set[str]:
    """Lock attrs held at `node`'s position: `with self.X:` ancestors."""
    held: Set[str] = set()
    for p in iter_parents(node):
        if isinstance(p, ast.With):
            for item in p.items:
                ce = item.context_expr
                # `with self.X:` or `with self.X as y:` or
                # self.X.acquire-style helpers are NOT counted — only the
                # context-manager form proves scoped release
                if (isinstance(ce, ast.Attribute)
                        and isinstance(ce.value, ast.Name)
                        and ce.value.id == "self"):
                    held.add(ce.attr)
    return held


def rule_dst005(index: ProjectIndex, config) -> List[Finding]:
    findings: List[Finding] = []
    for mod in index.modules.values():
        for cname, ci in mod.classes.items():
            if not ci.lock_attrs:
                continue
            for meth in ci.methods:
                if meth == "__init__":
                    continue          # construction precedes sharing
                fn = mod.functions.get(f"{cname}.{meth}")
                if fn is None:
                    continue

                def emit(node, what):
                    findings.append(Finding(
                        rule="DST005", path=fn.path, line=node.lineno,
                        col=node.col_offset,
                        message=f"shared-state mutation ({what}) outside "
                                f"`with self.<lock>:` in a lock-owning "
                                f"class",
                        symbol=fn.qualname,
                        detail=f"locks: "
                               f"{', '.join(sorted(ci.lock_attrs))}"))

                for node in ast.walk(fn.node):
                    if isinstance(node, (ast.Assign, ast.AugAssign)):
                        targets = (node.targets
                                   if isinstance(node, ast.Assign)
                                   else [node.target])
                        for t in targets:
                            root = t
                            while isinstance(root, ast.Subscript):
                                root = root.value
                            if (isinstance(root, ast.Attribute)
                                    and isinstance(root.value, ast.Name)
                                    and root.value.id == "self"
                                    and root.attr not in ci.lock_attrs
                                    and not (_with_lock_attrs(node)
                                             & ci.lock_attrs)):
                                emit(node, f"self.{root.attr} = ...")
                    elif isinstance(node, ast.Call):
                        f = node.func
                        if (isinstance(f, ast.Attribute)
                                and f.attr in _MUTATING_METHODS
                                and _attr_chain(f.value) is not None
                                and _attr_chain(f.value).startswith("self.")
                                and not (_with_lock_attrs(node)
                                         & ci.lock_attrs)):
                            emit(node, f"{_attr_chain(f.value)}.{f.attr}()")
    return findings


@dataclass(frozen=True)
class Rule:
    id: str
    title: str
    run: object


from .protocol_rules import (rule_dst006, rule_dst007,  # noqa: E402
                             rule_dst008)

RULES: Dict[str, Rule] = {
    "DST001": Rule("DST001", "host sync in hot path", rule_dst001),
    "DST002": Rule("DST002", "python control flow on traced values",
                   rule_dst002),
    "DST003": Rule("DST003", "donated-buffer use-after-donation",
                   rule_dst003),
    "DST004": Rule("DST004", "recompile hazard", rule_dst004),
    "DST005": Rule("DST005", "shared-state mutation without the lock",
                   rule_dst005),
    "DST006": Rule("DST006", "resource leak on exception path",
                   rule_dst006),
    "DST007": Rule("DST007", "resource-protocol ordering violation",
                   rule_dst007),
    "DST008": Rule("DST008", "inconsistent lock acquisition order",
                   rule_dst008),
}


def run_rules(index: ProjectIndex, config) -> List[Finding]:
    findings: List[Finding] = []
    for rid in config.rules:
        rule = RULES.get(rid)
        if rule is None:
            raise ValueError(
                f"unknown rule {rid!r}; known: {sorted(RULES)}")
        findings.extend(rule.run(index, config))
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings
