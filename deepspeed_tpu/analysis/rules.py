"""graftlint rules — each one distills a past incident into a check.

=====================  ========================================================
rule id                origin
=====================  ========================================================
recompile-hazard       PR 5: the recompile watchdog exists because shape- or
                       value-dependent Python inside a jitted function retraces
                       per value; this rule catches ``.item()`` / ``int(x)`` /
                       ``if x:`` / ``range(len(x))`` on traced values before a
                       trace ever runs.
uncommitted-buffer     PR 5: an uncommitted ``jnp.zeros`` KV cache held as
                       ``self.*`` state double-compiled every program the first
                       post-placement step (committed vs uncommitted layouts).
donation-after-use     the ``donate_argnums=(0,)`` admit/decode paths: a read
                       of a buffer after it was donated to a jit call observes
                       freed memory.
unsafe-scatter         PR 7: dynamic-index ``.at[...].set`` defaults to *clamp*
                       on OOB, silently aliasing row 0 / row N-1; every dynamic
                       scatter must pick its ``mode=`` explicitly.
hot-loop-host-sync     PR 8's cost model exists because stray host syncs
                       (``np.asarray`` / ``.item()`` / ``block_until_ready``)
                       in ``ServingEngine.step``-reachable code serialise the
                       device pipeline; each one must be a deliberate,
                       pragma-documented choice.
=====================  ========================================================

Rules yield :class:`~.findings.Finding` objects; the runner applies
pragmas and the baseline afterwards.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from .dataflow import (FunctionNode, ModuleIndex, flatten_statements,
                       node_path, reads_tainted, target_paths, walk_exprs)
from .findings import ERROR, Finding


class ModuleContext:
    def __init__(self, path: str, source: str, tree: ast.Module,
                 index: ModuleIndex):
        self.path = path
        self.source = source
        self.tree = tree
        self.index = index


class Rule:
    id: str = ""
    severity: str = ERROR
    short: str = ""

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        raise NotImplementedError

    def finding(self, ctx: ModuleContext, node: ast.AST, message: str,
                func: str = "") -> Finding:
        return Finding(rule=self.id, severity=self.severity, path=ctx.path,
                       line=getattr(node, "lineno", 1),
                       col=getattr(node, "col_offset", 0) + 1,
                       message=message, func=func)


# --------------------------------------------------------------------------
class RecompileHazardRule(Rule):
    id = "recompile-hazard"
    severity = ERROR
    short = ("Python-value-dependent control flow or host conversion "
             "inside a jitted function")

    _CASTS = {"int", "float", "bool"}
    _NP_SINKS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for fi in ctx.index.traced_functions():
            tainted: Set[str] = set(fi.traced_param_names())
            if not tainted:
                continue
            for stmt in flatten_statements(fi.node):
                yield from self._scan_stmt(ctx, fi, stmt, tainted)
                self._propagate(stmt, tainted)

    def _propagate(self, stmt: ast.stmt, tainted: Set[str]) -> None:
        if isinstance(stmt, ast.Assign):
            val, targets = stmt.value, stmt.targets
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            val, targets = stmt.value, [stmt.target]
        elif isinstance(stmt, ast.AugAssign):
            val, targets = stmt.value, [stmt.target]
        else:
            return
        is_tainted = reads_tainted(val, tainted)
        for t in targets:
            for p in target_paths(t):
                if is_tainted:
                    tainted.add(p)
                elif not isinstance(stmt, ast.AugAssign):
                    tainted.discard(p)

    def _scan_stmt(self, ctx, fi, stmt, tainted) -> Iterator[Finding]:
        if isinstance(stmt, (ast.If, ast.While)):
            t = stmt.test
            if self._is_bare_truth(t, tainted):
                kind = "while" if isinstance(stmt, ast.While) else "if"
                yield self.finding(
                    ctx, t,
                    f"`{kind}` on a traced value retraces per boolean "
                    "(use jnp.where / lax.cond)", fi.qualname)
        if isinstance(stmt, ast.For) and isinstance(stmt.iter, ast.Call):
            it = stmt.iter
            if isinstance(it.func, ast.Name) and it.func.id == "range" \
                    and it.args and isinstance(it.args[0], ast.Call):
                inner = it.args[0]
                if isinstance(inner.func, ast.Name) \
                        and inner.func.id == "len" and inner.args \
                        and self._names_tainted(inner.args[0], tainted):
                    yield self.finding(
                        ctx, it,
                        "`range(len(...))` over a traced value unrolls "
                        "and retraces per length (use lax.fori_loop or a "
                        "static bucket)", fi.qualname)
        for n in walk_exprs(stmt):
            if not isinstance(n, ast.Call):
                continue
            f = n.func
            if isinstance(f, ast.Attribute) and f.attr == "item" \
                    and not n.args and reads_tainted(f.value, tainted):
                yield self.finding(
                    ctx, n, "`.item()` on a traced value forces a "
                    "concrete value at trace time", fi.qualname)
            elif isinstance(f, ast.Name) and f.id in self._CASTS \
                    and n.args and reads_tainted(n.args[0], tainted):
                yield self.finding(
                    ctx, n, f"`{f.id}()` on a traced value forces a "
                    "concrete value at trace time", fi.qualname)
            else:
                p = node_path(f)
                if p in self._NP_SINKS and n.args \
                        and reads_tainted(n.args[0], tainted):
                    yield self.finding(
                        ctx, n, f"`{p}()` on a traced value materialises "
                        "it at trace time", fi.qualname)

    @staticmethod
    def _names_tainted(expr: ast.expr, tainted: Set[str]) -> bool:
        p = node_path(expr)
        return p is not None and p in tainted

    def _is_bare_truth(self, test: ast.expr, tainted: Set[str]) -> bool:
        """Only bare truthiness of a traced value: ``if x:``,
        ``if not x:``, boolean combinations of those.  Comparisons and
        membership tests are deliberately excluded (``if key not in
        cs:`` over a dict of arrays is static)."""
        if isinstance(test, ast.BoolOp):
            return any(self._is_bare_truth(v, tainted) for v in test.values)
        if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
            return self._is_bare_truth(test.operand, tainted)
        p = node_path(test)
        return p is not None and p in tainted


# --------------------------------------------------------------------------
class UncommittedBufferRule(Rule):
    id = "uncommitted-buffer"
    severity = ERROR
    short = ("jnp allocation stored as long-lived self.* state without a "
             "device_put/sharding commit")

    _SOURCES = {"zeros", "ones", "full", "empty",
                "zeros_like", "ones_like", "full_like", "empty_like"}

    def _is_source_call(self, n: ast.AST) -> bool:
        if not isinstance(n, ast.Call):
            return False
        p = node_path(n.func)
        if p is None or "." not in p:
            return False
        root, _, fn = p.rpartition(".")
        return fn in self._SOURCES and root in ("jnp", "jax.numpy")

    def _is_commit_call(self, n: ast.AST) -> bool:
        return isinstance(n, ast.Call) and \
            node_path(n.func) in ("jax.device_put", "device_put")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for fi in ctx.index.host_functions():
            uncommitted: Set[str] = set()
            for stmt in flatten_statements(fi.node):
                # commit: any device_put over an uncommitted var cleanses
                # it (the committed result replaces or shadows the raw
                # allocation; conditional commits count — we only chase
                # the obviously-never-committed case)
                for n in walk_exprs(stmt):
                    if self._is_commit_call(n):
                        for arg in n.args[:1]:
                            for p in self._paths_in(arg):
                                uncommitted.discard(p)
                if isinstance(stmt, (ast.Assign, ast.AnnAssign)):
                    targets = stmt.targets if isinstance(stmt, ast.Assign) \
                        else [stmt.target]
                    value = stmt.value
                    if value is None:
                        continue
                    val_uncommitted = self._value_uncommitted(
                        value, uncommitted)
                    for t in targets:
                        yield from self._apply_target(
                            ctx, fi, t, value, val_uncommitted, uncommitted)

    def _paths_in(self, expr: ast.AST) -> List[str]:
        out = []
        for n in ast.walk(expr):
            p = node_path(n) if isinstance(n, (ast.Name, ast.Attribute)) \
                else None
            if p:
                out.append(p)
        return out

    def _value_uncommitted(self, value: ast.expr,
                           uncommitted: Set[str]) -> bool:
        if self._is_commit_call(value):
            return False
        for n in ast.walk(value):
            if self._is_commit_call(n):
                # a commit somewhere inside (e.g. dict of device_put
                # results) — treat the whole value as committed unless a
                # raw source also appears outside it; keep it simple and
                # call it committed
                return False
        if any(self._is_source_call(n) for n in ast.walk(value)):
            return True
        return reads_tainted(value, uncommitted)

    def _apply_target(self, ctx, fi, target, value, val_uncommitted,
                      uncommitted) -> Iterator[Finding]:
        if isinstance(target, (ast.Tuple, ast.List)):
            for el in target.elts:
                yield from self._apply_target(ctx, fi, el, value,
                                              val_uncommitted, uncommitted)
            return
        p = node_path(target) or (
            node_path(target.value) if isinstance(target, ast.Subscript)
            else None)
        if p is None:
            return
        if p.startswith("self.") or p.startswith("cls."):
            if val_uncommitted:
                yield self.finding(
                    ctx, target,
                    f"`{p}` holds a jnp allocation that was never "
                    "committed with jax.device_put — long-lived state "
                    "compiles against an uncommitted layout and "
                    "recompiles once placed (PR 5 bug class)",
                    fi.qualname)
            return
        if val_uncommitted:
            uncommitted.add(p)
        elif not isinstance(target, ast.Subscript):
            uncommitted.discard(p)


# --------------------------------------------------------------------------
#: wrapper-attribute name -> donated *call-site* argument positions, for
#: call sites whose wrapper is defined in another module (the engine
#: calling pool/engine jits).  Module-local ``jax.jit(...,
#: donate_argnums=...)`` bindings are discovered from the AST and take
#: precedence.
DONATION_FALLBACK: Dict[str, Tuple[int, ...]] = {
    "_jit_decode": (1,),
    "_jit_prefill_chunk": (1,),
    "_jit_decode_scan": (1,),
    "_jit_copy_page": (0,),
    "_jit_scatter_pages": (0,),
    "_admit_jit": (0,),
    "_admit_rows_jit": (0,),
    "_paged_decode_jit": (1,),
    "_paged_verify_jit": (1,),
    "_paged_chunk_jit": (1,),
    "_paged_chunk_decode_jit": (1,),
    "verify_k": (0,),
    "prefill_chunk": (0,),
}


class DonationAfterUseRule(Rule):
    id = "donation-after-use"
    severity = ERROR
    short = "read of a buffer after it was donated to a jit call"

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        donating = dict(ctx.index.donating_attrs)
        for fi in ctx.index.functions.values():
            if not isinstance(fi.node, FunctionNode) or fi.is_traced:
                continue
            stmts = flatten_statements(fi.node)
            # donated path -> (donation node, wrapper name)
            live: Dict[str, Tuple[ast.AST, str]] = {}
            for stmt in stmts:
                # reads of already-donated paths (donations from
                # *earlier* statements only)
                if live:
                    yield from self._scan_reads(ctx, fi, stmt, live)
                for n in walk_exprs(stmt):
                    if isinstance(n, ast.Call):
                        for path, wrapper in self._donations(
                                n, fi, donating):
                            live[path] = (n, wrapper)
                # kills: assignment to the donated path (or a prefix of
                # it) re-binds the name to the fresh result
                for t in self._stmt_targets(stmt):
                    for tp in target_paths(t):
                        for path in list(live):
                            if path == tp or path.startswith(tp + "."):
                                del live[path]

    def _stmt_targets(self, stmt: ast.stmt) -> List[ast.expr]:
        if isinstance(stmt, ast.Assign):
            return stmt.targets
        if isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
            return [stmt.target]
        return []

    def _donations(self, call: ast.Call, fi, donating):
        name = None
        if isinstance(call.func, ast.Attribute):
            name = call.func.attr
        elif isinstance(call.func, ast.Name):
            name = call.func.id
        if name is None:
            return
        argnums: Optional[Tuple[int, ...]] = None
        if isinstance(call.func, ast.Attribute) and \
                isinstance(call.func.value, ast.Name) and \
                call.func.value.id in ("self", "cls"):
            argnums = donating.get((fi.class_name, name))
        if argnums is None:
            argnums = DONATION_FALLBACK.get(name)
        if not argnums:
            return
        for i in argnums:
            if i < len(call.args):
                p = node_path(call.args[i])
                if p is None and isinstance(call.args[i], ast.Subscript):
                    p = node_path(call.args[i].value)
                if p is not None:
                    yield p, name

    def _scan_reads(self, ctx, fi, stmt, live) -> Iterator[Finding]:
        for n in walk_exprs(stmt):
            if isinstance(n, (ast.Name, ast.Attribute)) and \
                    isinstance(getattr(n, "ctx", None), ast.Load):
                p = node_path(n)
                if p is None:
                    continue
                for path, (don, wrapper) in live.items():
                    if p == path or p.startswith(path + "."):
                        yield self.finding(
                            ctx, n,
                            f"`{p}` is read after being donated to "
                            f"`{wrapper}` (donate_argnums) at line "
                            f"{don.lineno} — the donated buffer is "
                            "freed by XLA and must be rebound from the "
                            "call's result first", fi.qualname)
                        break


# --------------------------------------------------------------------------
class UnsafeScatterRule(Rule):
    id = "unsafe-scatter"
    severity = ERROR
    short = "dynamic-index .at[].set/add without an explicit mode="

    _METHODS = {"set", "add", "subtract", "multiply", "mul", "divide",
                "div", "power", "min", "max", "apply"}

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        funcs = {id(fi.node): fi for fi in ctx.index.functions.values()}
        for n in ast.walk(ctx.tree):
            if not isinstance(n, ast.Call):
                continue
            f = n.func
            if not (isinstance(f, ast.Attribute)
                    and f.attr in self._METHODS
                    and isinstance(f.value, ast.Subscript)
                    and isinstance(f.value.value, ast.Attribute)
                    and f.value.value.attr == "at"):
                continue
            if any(kw.arg == "mode" for kw in n.keywords):
                continue
            idx = f.value.slice
            if self._is_static(idx):
                continue
            qual = self._enclosing(ctx, n)
            yield self.finding(
                ctx, n,
                f"dynamic-index `.at[...].{f.attr}` without an explicit "
                "`mode=` — the default clamps out-of-bounds indices onto "
                "live rows (PR 7 aliasing class); state intent with "
                'mode="drop" (or "promise_in_bounds")', qual)

    def _is_static(self, idx: ast.expr) -> bool:
        if isinstance(idx, ast.Tuple):
            return all(self._is_static(el) for el in idx.elts)
        if isinstance(idx, ast.Slice):
            return all(x is None or self._is_static(x)
                       for x in (idx.lower, idx.upper, idx.step))
        if isinstance(idx, ast.Constant):
            return True
        if isinstance(idx, ast.UnaryOp) and \
                isinstance(idx.op, (ast.USub, ast.UAdd)):
            return self._is_static(idx.operand)
        return False

    def _enclosing(self, ctx: ModuleContext, node: ast.AST) -> str:
        best = ""
        best_span = None
        for fi in ctx.index.functions.values():
            lo = getattr(fi.node, "lineno", None)
            hi = getattr(fi.node, "end_lineno", None)
            if lo is None or hi is None:
                continue
            if lo <= node.lineno <= hi:
                span = hi - lo
                if best_span is None or span < best_span:
                    best, best_span = fi.qualname, span
        return best


# --------------------------------------------------------------------------
class HotLoopHostSyncRule(Rule):
    id = "hot-loop-host-sync"
    severity = ERROR
    short = ("host sync on a device value inside ServingEngine.step-"
             "reachable code")

    #: engine/pool entry points that return device arrays
    _DEVICE_FNS = {"run_decode", "run_verify", "run_prefill_chunk",
                   "verify_k", "prefill_chunk", "prefill_last"}
    _NP_SINKS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}
    _CASTS = {"int", "float", "bool"}

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for cls in ctx.index.classes_with_method("step"):
            methods = ctx.index.methods_of(cls)
            reachable = self._reachable(methods, "step")
            for name in sorted(reachable):
                fi = methods[name]
                if fi.is_traced:
                    continue
                yield from self._scan_method(ctx, fi)

    def _reachable(self, methods, root) -> Set[str]:
        seen = {root} if root in methods else set()
        frontier = list(seen)
        while frontier:
            cur = methods[frontier.pop()]
            for n in ast.walk(cur.node):
                if isinstance(n, ast.Call) and \
                        isinstance(n.func, ast.Attribute) and \
                        isinstance(n.func.value, ast.Name) and \
                        n.func.value.id == "self" and \
                        n.func.attr in methods and \
                        n.func.attr not in seen:
                    seen.add(n.func.attr)
                    frontier.append(n.func.attr)
        return seen

    def _is_device_source(self, call: ast.Call) -> bool:
        f = call.func
        if isinstance(f, ast.Attribute):
            if f.attr.startswith("_jit") or f.attr in self._DEVICE_FNS:
                return True
        p = node_path(f)
        if p is None:
            return False
        return p.startswith("jnp.") or p.startswith("jax.numpy.") \
            or p.startswith("jax.random.")

    def _expr_device(self, expr: ast.AST, tainted: Set[str]) -> bool:
        """Does ``expr`` evaluate to a device value?  Calls are opaque
        barriers unless they are known device sources — a helper like
        ``self._sample(logits)`` syncs internally and hands back a host
        array, and charging its *caller* too would double-count every
        sync."""
        if isinstance(expr, ast.Call):
            return self._is_device_source(expr)
        if isinstance(expr, ast.Attribute):
            if expr.attr in {"shape", "ndim", "dtype", "size"}:
                return False
            p = node_path(expr)
            if p is not None and p in tainted:
                return True
        if isinstance(expr, ast.Name):
            return expr.id in tainted
        return any(self._expr_device(c, tainted)
                   for c in ast.iter_child_nodes(expr))

    def _sink(self, call: ast.Call, tainted: Set[str]):
        """Return a message when ``call`` host-syncs a device value."""
        f = call.func
        p = node_path(f)
        if p in self._NP_SINKS and call.args \
                and self._expr_device(call.args[0], tainted):
            return f"`{p}` copies a device value to host"
        if p == "jax.block_until_ready" and call.args \
                and self._expr_device(call.args[0], tainted):
            return "`jax.block_until_ready` stalls on a device value"
        if isinstance(f, ast.Name) and f.id in self._CASTS and call.args \
                and self._expr_device(call.args[0], tainted):
            return f"`{f.id}()` blocks on a device value"
        if isinstance(f, ast.Attribute) and \
                f.attr in ("item", "tolist", "block_until_ready") and \
                self._expr_device(f.value, tainted):
            return f"`.{f.attr}()` blocks on a device value"
        if isinstance(f, ast.Attribute) and f.attr == "stop":
            # Timer.stop(block_on=...) exists to block_until_ready the
            # values it is handed — it IS a host sync, whatever the
            # taint tracker knows about the bundle's provenance
            for kw in call.keywords:
                if kw.arg == "block_on" and not (
                        isinstance(kw.value, ast.Constant)
                        and kw.value.value is None):
                    return ("`stop(block_on=...)` blocks until the "
                            "device values it is handed exist")
        return None

    def _scan_method(self, ctx, fi) -> Iterator[Finding]:
        tainted: Set[str] = set()
        for stmt in flatten_statements(fi.node):
            emitted_lines = set()
            for n in walk_exprs(stmt):
                if isinstance(n, ast.Call):
                    msg = self._sink(n, tainted)
                    if msg and n.lineno not in emitted_lines:
                        emitted_lines.add(n.lineno)
                        yield self.finding(
                            ctx, n,
                            f"{msg} inside step-reachable "
                            "`{}` — every post-warmup host sync "
                            "serialises the decode pipeline; if "
                            "deliberate, allow it with a pragma and a "
                            "reason".format(fi.qualname), fi.qualname)
            self._propagate(stmt, tainted)

    def _propagate(self, stmt: ast.stmt, tainted: Set[str]) -> None:
        if isinstance(stmt, ast.Assign):
            val, targets = stmt.value, stmt.targets
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            val, targets = stmt.value, [stmt.target]
        else:
            return
        is_sink_result = isinstance(val, ast.Call) and \
            self._sink(val, tainted) is not None
        # a sink call's *result* lives on host: the assignment both
        # emits the finding (above) and cleanses the target
        device = (not is_sink_result) and self._expr_device(val, tainted)
        for t in targets:
            for p in target_paths(t):
                if device:
                    tainted.add(p)
                else:
                    tainted.discard(p)


ALL_RULES: List[Rule] = [
    RecompileHazardRule(),
    UncommittedBufferRule(),
    DonationAfterUseRule(),
    UnsafeScatterRule(),
    HotLoopHostSyncRule(),
]

RULES_BY_ID: Dict[str, Rule] = {r.id: r for r in ALL_RULES}

#: meta-diagnostics emitted by the runner, documented alongside rules
META_RULES: Dict[str, str] = {
    "pragma-missing-reason": "a graftlint pragma must carry `-- reason`",
    "unused-pragma": "a graftlint pragma matched no finding",
    "parse-error": "file does not parse",
}
