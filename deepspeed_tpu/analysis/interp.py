"""graftcheck's abstract interpreter.

Statically enumerates the reachable abstract signature set of every
watched serving jit (the programs ``ServingEngine._ensure_watch``
wraps) by interpreting the REAL method bodies in
``serving/engine.py``, ``serving/slot_pool.py``,
``serving/paged_pool.py`` and ``inference/engine.py`` over the
:mod:`absdomain` lattice — pure stdlib ``ast``, no jax import.

Three curated tables bound the interpretation (each is a *documented
modelling decision*, kept tiny so drift against the real code is
reviewable):

``DRIVERS``
    The calling contexts.  The serving step loop itself is host
    orchestration full of I/O and bookkeeping, so instead of
    interpreting ``step()`` top-down, each driver seeds one
    step-reachable entry point (`_admit`, `_admit_batch`,
    `_prefill_chunk_step`, `_decode_step`, paged
    ``ensure_writable``) with abstract arguments derived from the
    config env — e.g. a singleton admission's seed length is
    ``IntRange(1, min(prefill_chunk, max_prompt_len))``.  The batched
    driver iterates bucket widths eagerly because the reachable batch
    set depends on the width (token-budget grant semantics).

``SKIP_MODELS``
    Host helpers interpreted as opaque models (``pool.alloc`` returns
    an opaque int, ``metrics.record_*`` return nothing).  Everything
    else on a known class is interpreted from its AST.

``WATCHED_MODELS``
    The abstract return value of each watched jit (a jit body is
    traced code — its *callers* are what decide signatures, so the
    body itself is modelled, not interpreted).

Interpretation is path-sensitive (unresolvable branches fork, capped
at :data:`MAX_PATHS`), loops run body-once except the admission
code's power-of-two doubling loop (``b = K; while b < n: b *= 2``)
which is recognised and collapsed to a :class:`~.absdomain.FiniteSet`
— that recognition is what turns "a prompt length in [1, 256]" into
"a padded width in {16, 32, 64, 128, 256}".

The output contract: :func:`enumerate_signatures` returns exactly the
strings :func:`deepspeed_tpu.telemetry.watchdog.manifest_signature`
renders at runtime, so a manifest diff is meaningful in both
directions.
"""

from __future__ import annotations

import ast
import os
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from .absdomain import (COMMITTED, HOST, UNCOMMITTED, AbsValue, Arr, Dim,
                        FiniteSet, IntRange, Known, Obj, Scalar,
                        SignatureError, Tree, Tup, Unbounded, Unknown,
                        dim_of, expand_signatures)
from .findings import ERROR, Finding
from . import shape_rules
from .shape_rules import DTYPE_NAMES, DTypeVal, as_dim, binop

#: fork ceiling per interpreted block — serving methods have a handful
#: of data-dependent guards each; hitting this means the interpreter is
#: lost, and the affected values degrade to Unknown via dead paths
MAX_PATHS = 64
MAX_CALL_DEPTH = 16

#: files the project index loads (relative to the repo root) — the
#: modules that define watched jits or methods reachable from step()
PROJECT_FILES = (
    "deepspeed_tpu/serving/engine.py",
    "deepspeed_tpu/serving/slot_pool.py",
    "deepspeed_tpu/serving/paged_pool.py",
    "deepspeed_tpu/inference/engine.py",
)

_SERVING_ENGINE = "deepspeed_tpu/serving/engine.py"


# ----------------------------------------------------------------------
# extra interp-local values
# ----------------------------------------------------------------------
class DictVal(Tree):
    """A dict literal whose entries we track (still renders ``*``)."""

    __slots__ = ("entries",)

    def __init__(self, entries: Dict[str, AbsValue],
                 placement: str = COMMITTED):
        super().__init__(placement, "dict")
        self.entries = dict(entries)


class ListOf(AbsValue):
    """A homogeneous list/iterator: one element model + abstract length."""

    __slots__ = ("elem", "length", "maybe_empty")

    def __init__(self, elem: AbsValue, length: Optional[Dim] = None,
                 maybe_empty: bool = True):
        self.elem = elem
        self.length = length if length is not None \
            else Unbounded("list length")
        self.maybe_empty = maybe_empty

    def __repr__(self):
        return f"ListOf({self.elem!r})"


class ModuleFn(AbsValue):
    """A dotted module path (``np.zeros``) flowing as a callable."""

    __slots__ = ("path",)

    def __init__(self, path: str):
        self.path = path

    def __repr__(self):
        return f"ModuleFn({self.path})"


# ----------------------------------------------------------------------
# project index
# ----------------------------------------------------------------------
class ModuleInfo:
    def __init__(self, path: str, rel: str, tree: ast.Module):
        self.path = path
        self.rel = rel
        self.tree = tree
        self.aliases: Dict[str, str] = {}       # local name -> canonical
        self.constants: Dict[str, AbsValue] = {}
        self.classes: Dict[str, ast.ClassDef] = {}
        self.methods: Dict[Tuple[str, str], ast.FunctionDef] = {}
        self.bases: Dict[str, List[str]] = {}
        self._scan()

    def _scan(self) -> None:
        for node in self.tree.body:
            if isinstance(node, ast.Import):
                for a in node.names:
                    name = a.asname or a.name.split(".")[0]
                    self.aliases[name] = _canon_module(a.name)
            elif isinstance(node, ast.ImportFrom):
                mod = _canon_module(node.module or "")
                for a in node.names:
                    name = a.asname or a.name
                    self.aliases[name] = f"{mod}.{a.name}" if mod \
                        else a.name
            elif isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                val = _literal_value(node.value)
                if val is not None:
                    self.constants[node.targets[0].id] = val
            elif isinstance(node, ast.ClassDef):
                self.classes[node.name] = node
                self.bases[node.name] = [
                    b.id for b in node.bases if isinstance(b, ast.Name)]
                for item in node.body:
                    if isinstance(item, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                        self.methods[(node.name, item.name)] = item


def _canon_module(name: str) -> str:
    if name in ("numpy",):
        return "np"
    if name in ("jax.numpy",):
        return "jnp"
    return name


def _literal_value(node: ast.expr) -> Optional[AbsValue]:
    if isinstance(node, ast.Constant) and \
            isinstance(node.value, (int, float, str, bool, type(None))):
        return Scalar(node.value)
    if isinstance(node, (ast.Tuple, ast.List)):
        items = [_literal_value(e) for e in node.elts]
        if all(i is not None for i in items):
            return Tup(items)  # type: ignore[arg-type]
    return None


class ProjectIndex:
    """Parsed serving modules + the watched-jit name lists, read from
    the real ``serving/engine.py`` AST so the checker can never drift
    from what the watchdog actually wraps."""

    def __init__(self, root: str):
        self.root = root
        self.modules: List[ModuleInfo] = []
        for rel in PROJECT_FILES:
            path = os.path.join(root, rel)
            if not os.path.isfile(path):
                continue
            with open(path, encoding="utf-8") as fh:
                src = fh.read()
            self.modules.append(
                ModuleInfo(path, rel, ast.parse(src, filename=path)))
        self.watched: Dict[str, Tuple[str, ...]] = {}
        for mod in self.modules:
            if mod.rel.endswith("serving/engine.py"):
                for key in ("_WATCHED_ENGINE_JITS", "_WATCHED_POOL_JITS",
                            "_WATCHED_SERVING_JITS",
                            "_WATCHED_DRAFTER_JITS"):
                    v = mod.constants.get(key)
                    if isinstance(v, Tup):
                        self.watched[key] = tuple(
                            s.value for s in v.items
                            if isinstance(s, Scalar))

    def resolve_method(self, kind: str, name: str
                       ) -> Optional[Tuple[ast.FunctionDef, ModuleInfo]]:
        for cls in self._mro(kind):
            for mod in self.modules:
                fn = mod.methods.get((cls, name))
                if fn is not None:
                    return fn, mod
        return None

    def _mro(self, kind: str) -> List[str]:
        out, frontier = [], [kind]
        while frontier:
            cls = frontier.pop(0)
            if cls in out:
                continue
            out.append(cls)
            for mod in self.modules:
                frontier.extend(mod.bases.get(cls, []))
        return out


# ----------------------------------------------------------------------
# watched-call return models
# ----------------------------------------------------------------------
def _logits(batch: Any, env: dict) -> Arr:
    return Arr((batch, Known(1), Known(int(env["vocab_size"]))),
               env.get("logits_dtype", "float32"), COMMITTED)


def _batch_of(a: AbsValue) -> Any:
    return a.shape[0] if isinstance(a, Arr) and a.ndim else Known(1)


def _verify_outs(args: List[AbsValue], env: dict) -> List[AbsValue]:
    """What a verify program gives beside its cache, from ``(params,
    cache, cur (B,), draft (B, K), ...)``: ``out`` (B, K + 1) with the
    config's K, ``n_emit`` (B,), and the key it was handed, split."""
    batch = _batch_of(args[2])
    return [Arr((batch, Known(int(env.get("spec_k") or 0) + 1)), "int32",
                COMMITTED),
            Arr((batch,), "int32", COMMITTED),
            Arr((Known(2),), "uint32", COMMITTED)]


def _pack_chunk_args(args: List[AbsValue]) -> AbsValue:
    """``inference.engine.pack_chunk_args(ids, slot, start, length,
    last_idx, *rows)``: ONE host int32 vector of the four scalars, the
    chunk's ids and every further row."""
    n = 4
    for a in [args[0]] + list(args[5:]):
        if not isinstance(a, Arr) \
                or not all(isinstance(d, Known) for d in a.shape):
            return Unknown("pack_chunk_args of an unresolved operand")
        size = 1
        for d in a.shape:
            size *= d.v
        n += size
    return Arr((Known(n),), "int32", HOST)


WATCHED_MODELS = {
    "_jit_prefill_at": lambda args, kw, env: Tup(
        [_logits(_batch_of(args[1]), env), Tree(COMMITTED, "pre_cache")]),
    "_jit_decode": lambda args, kw, env: Tup(
        [_logits(_batch_of(args[2]), env), Tree(COMMITTED, "cache")]),
    "_jit_prefill_chunk": lambda args, kw, env: Tup(
        [_logits(Known(1), env), Tree(COMMITTED, "cache")]),
    # (the key it was handed, split; the tokens)
    "_jit_sample": lambda args, kw, env: Tup(
        [Arr((Known(2),), "uint32", COMMITTED),
         Arr((_batch_of(args[0]),), "int32", COMMITTED)]),
    "_jit_verify_k": lambda args, kw, env: Tup(
        [Tree(COMMITTED, "cache")] + _verify_outs(args, env)),
    "_jit_decode_scan": lambda args, kw, env: Unknown("decode_scan"),
    "_admit_jit": lambda args, kw, env: Tree(COMMITTED, "pool"),
    "_admit_rows_jit": lambda args, kw, env: Tree(COMMITTED, "pool"),
    "_jit_copy_page": lambda args, kw, env: Tree(COMMITTED, "pool"),
    "_jit_gather_pages": lambda args, kw, env: Tree(COMMITTED, "pool"),
    "_jit_scatter_pages": lambda args, kw, env: Tree(COMMITTED, "pool"),
    # (logits, pool, what a routed FFN counted: None for a dense model)
    "_paged_decode_jit": lambda args, kw, env: Tup(
        [_logits(_batch_of(args[2]), env), Tree(COMMITTED, "pool"),
         Scalar(None)]),
    "_paged_chunk_jit": lambda args, kw, env: Tup(
        [_logits(Known(1), env), Tree(COMMITTED, "pool"), Scalar(None)]),
    # (the chunk's logits, the decode rows', pool, the routed FFN's counts)
    "_paged_chunk_decode_jit": lambda args, kw, env: Tup(
        [_logits(Known(1), env), _logits(_batch_of(args[3]), env),
         Tree(COMMITTED, "pool"), Scalar(None)]),
    "_paged_verify_jit": lambda args, kw, env: Tup(
        [Tree(COMMITTED, "pool")] + _verify_outs(args, env)),
    "_jit_finite": lambda args, kw, env: Arr(
        (_batch_of(args[0]),), "bool", COMMITTED),
    # fused paged-attention kernel arms: same caller-visible contract as
    # the dense compositions they replace
    "_paged_decode_kernel_jit": lambda args, kw, env: Tup(
        [_logits(_batch_of(args[2]), env), Tree(COMMITTED, "pool"),
         Scalar(None)]),
    "_paged_verify_kernel_jit": lambda args, kw, env: Tup(
        [Tree(COMMITTED, "pool")] + _verify_outs(args, env)),
    # device current-token twin plumbing: scatter returns the (S,) twin
    # it was handed; spec-cur collapses a (S, K+1) verify output to (S,)
    "_jit_cur_scatter": lambda args, kw, env: args[0]
    if isinstance(args[0], Arr)
    else Arr((Known(int(env["num_slots"])),), "int32", COMMITTED),
    "_jit_spec_cur": lambda args, kw, env: Arr(
        (_batch_of(args[0]),), "int32", COMMITTED),
    "_argmax": lambda args, kw, env: Arr((), "int32", COMMITTED),
}

#: host helpers modelled instead of interpreted: pure bookkeeping, or
#: allocators whose result is an opaque host int (never a dimension)
SKIP_MODELS = {
    ("ServingEngine", "_now"): lambda s, a, kw: Scalar(
        Unbounded("wallclock")),
    ("ServingEngine", "_running_count"): lambda s, a, kw: Scalar(
        Unbounded("live count")),
    ("ServingEngine", "_maybe_retire"): lambda s, a, kw: Scalar(None),
    ("ServingEngine", "_ensure_pages"): lambda s, a, kw: Scalar(None),
    ("ServingEngine", "_ensure_decode_pages"): lambda s, a, kw: Scalar(None),
    ("ServingEngine", "_prefix_plan"): lambda s, a, kw: Scalar(
        Unbounded("prefix plan")),
    ("SlotPool", "alloc"): lambda s, a, kw: Scalar(Unbounded("slot id")),
    ("SlotPool", "release"): lambda s, a, kw: Scalar(None),
    ("SlotPool", "reset_row"): lambda s, a, kw: Scalar(None),
    ("PagedKVPool", "alloc_page"): lambda s, a, kw: Scalar(
        Unbounded("page id")),
    ("PagedKVPool", "ref_page"): lambda s, a, kw: Scalar(None),
    ("PagedKVPool", "unref_page"): lambda s, a, kw: Scalar(None),
    ("PagedKVPool", "_sync_table"): lambda s, a, kw: Scalar(None),
    # the wire hop of a cross-pool transfer: device_put of every block
    # leaf onto the pool's committed placement — the scatter's block
    # operand is COMMITTED by construction, which is the whole point
    ("PagedKVPool", "_land_block"): lambda s, a, kw: Tree(COMMITTED,
                                                          "pool"),
    ("PagedKVPool", "bind_engine"): lambda s, a, kw: Scalar(None),
    ("PagedKVPool", "cache_prefix"): lambda s, a, kw: Scalar(
        Unbounded("cached pages")),
    ("Drafter", "propose"): lambda s, a, kw: Unknown("drafter"),
}


# ----------------------------------------------------------------------
# outcomes / frames
# ----------------------------------------------------------------------
class Outcome:
    __slots__ = ("kind", "value", "frame")

    def __init__(self, kind: str, value: Optional[AbsValue],
                 frame: "Frame"):
        self.kind = kind          # fall | return | raise | break | continue
        self.value = value
        self.frame = frame


class Frame:
    __slots__ = ("locals", "module")

    def __init__(self, locs: Dict[str, AbsValue], module: ModuleInfo):
        self.locals = locs
        self.module = module

    def copy(self) -> "Frame":
        return Frame(dict(self.locals), self.module)


class Record:
    """One watched call site observed during interpretation."""

    __slots__ = ("program", "args", "kwargs", "rel", "line")

    def __init__(self, program: str, args: List[AbsValue],
                 kwargs: Dict[str, AbsValue], rel: str, line: int):
        self.program = program
        self.args = args
        self.kwargs = kwargs
        self.rel = rel
        self.line = line


# ----------------------------------------------------------------------
# the interpreter
# ----------------------------------------------------------------------
class Interp:
    def __init__(self, project: ProjectIndex, env: dict):
        self.project = project
        self.env = env
        self.records: List[Record] = []
        self.findings: List[Finding] = []
        self._depth = 0
        eng = project.watched.get("_WATCHED_ENGINE_JITS", ())
        pool = project.watched.get("_WATCHED_POOL_JITS", ())
        srv = project.watched.get("_WATCHED_SERVING_JITS", ())
        drf = project.watched.get("_WATCHED_DRAFTER_JITS", ())
        self._watched_by_kind = {
            "InferenceEngine": (eng, "InferenceEngine"),
            "SlotPool": (pool, "SlotPool"),
            "PagedKVPool": (pool, "SlotPool"),   # watchdog names both
            #                                      families "SlotPool.*"
            "ServingEngine": (srv, "ServingEngine"),
            "Drafter": (drf, "Drafter"),
        }

    # ------------------------------------------------------------ calls
    def call_method(self, recv: Obj, name: str, args: List[AbsValue],
                    kwargs: Dict[str, AbsValue],
                    call_node: Optional[ast.Call] = None,
                    module: Optional[ModuleInfo] = None) -> AbsValue:
        watched, prefix = self._watched_by_kind.get(recv.kind, ((), ""))
        if name in watched:
            if call_node is not None and module is not None:
                self.records.append(Record(
                    f"{prefix}.{name}", args, kwargs, module.rel,
                    call_node.lineno))
            model = WATCHED_MODELS.get(name)
            return model(args, kwargs, self.env) if model \
                else Unknown(f"watched {name}")
        for cls in self.project._mro(recv.kind):
            skip = SKIP_MODELS.get((cls, name))
            if skip is not None:
                return skip(recv, args, kwargs)
        resolved = self.project.resolve_method(recv.kind, name)
        if resolved is None:
            return Unknown(f"unmodelled method {recv.kind}.{name}")
        fn, mod = resolved
        return self.call_function(fn, mod, recv, args, kwargs)

    def call_function(self, fn: ast.FunctionDef, module: ModuleInfo,
                      recv: Optional[Obj], args: List[AbsValue],
                      kwargs: Dict[str, AbsValue]) -> AbsValue:
        if self._depth >= MAX_CALL_DEPTH:
            return Unknown(f"call depth limit at {fn.name}")
        params = [a.arg for a in fn.args.args]
        bound: Dict[str, AbsValue] = {}
        pos = list(args)
        if params and params[0] in ("self", "cls") and recv is not None:
            bound[params[0]] = recv
            params = params[1:]
        defaults = fn.args.defaults
        # align defaults to the trailing params
        dmap: Dict[str, AbsValue] = {}
        for p, d in zip(params[len(params) - len(defaults):], defaults):
            lit = _literal_value(d)
            dmap[p] = lit if lit is not None else Unknown("default")
        for i, p in enumerate(params):
            if i < len(pos):
                bound[p] = pos[i]
            elif p in kwargs:
                bound[p] = kwargs[p]
            elif p in dmap:
                bound[p] = dmap[p]
            else:
                bound[p] = Unknown(f"unbound param {p}")
        self._depth += 1
        try:
            outs = self.exec_block(fn.body, Frame(bound, module))
        finally:
            self._depth -= 1
        returns = [o.value for o in outs if o.kind == "return"
                   and o.value is not None]
        return _join(returns)

    # ---------------------------------------------------------- stmts
    def exec_block(self, stmts: Sequence[ast.stmt],
                   frame: Frame) -> List[Outcome]:
        states = [frame]
        done: List[Outcome] = []
        for stmt in stmts:
            nxt: List[Frame] = []
            for fr in states:
                for out in self.exec_stmt(stmt, fr):
                    if out.kind == "fall":
                        nxt.append(out.frame)
                    elif out.kind == "raise":
                        pass                       # path dies
                    else:
                        done.append(out)
            states = nxt[:MAX_PATHS]
            if not states:
                break
        done.extend(Outcome("fall", None, fr) for fr in states)
        return done

    def exec_stmt(self, stmt: ast.stmt, frame: Frame) -> List[Outcome]:
        if isinstance(stmt, ast.Return):
            v = self.eval(stmt.value, frame) if stmt.value is not None \
                else Scalar(None)
            return [Outcome("return", v, frame)]
        if isinstance(stmt, ast.Raise):
            return [Outcome("raise", None, frame)]
        if isinstance(stmt, (ast.Break,)):
            return [Outcome("break", None, frame)]
        if isinstance(stmt, (ast.Continue,)):
            return [Outcome("continue", None, frame)]
        if isinstance(stmt, ast.Expr):
            self.eval(stmt.value, frame)
            return [Outcome("fall", None, frame)]
        if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            self._assign(stmt, frame)
            return [Outcome("fall", None, frame)]
        if isinstance(stmt, ast.If):
            return self._exec_if(stmt, frame)
        if isinstance(stmt, ast.While):
            return self._exec_while(stmt, frame)
        if isinstance(stmt, ast.For):
            return self._exec_for(stmt, frame)
        if isinstance(stmt, ast.With):
            for item in stmt.items:
                v = self.eval(item.context_expr, frame)
                if item.optional_vars is not None:
                    self._store(item.optional_vars, v, frame)
            return self.exec_block(stmt.body, frame)
        if isinstance(stmt, ast.Try):
            # try-body only: handlers model error recovery (rollbacks /
            # re-raises), which never reaches a watched call with a NEW
            # shape — the shapes were built before the dispatch failed
            return self.exec_block(stmt.body, frame)
        if isinstance(stmt, (ast.Pass, ast.Assert, ast.Delete,
                             ast.Import, ast.ImportFrom, ast.Global,
                             ast.Nonlocal, ast.FunctionDef,
                             ast.AsyncFunctionDef, ast.ClassDef)):
            return [Outcome("fall", None, frame)]
        return [Outcome("fall", None, frame)]

    def _exec_if(self, stmt: ast.If, frame: Frame) -> List[Outcome]:
        t = self.truth(self.eval(stmt.test, frame))
        if t is True:
            return self.exec_block(stmt.body, frame)
        if t is False:
            return self.exec_block(stmt.orelse, frame)
        outs = self.exec_block(stmt.body, frame.copy())
        outs += self.exec_block(stmt.orelse, frame.copy())
        return outs

    def _exec_while(self, stmt: ast.While, frame: Frame) -> List[Outcome]:
        collapsed = self._pow2_while(stmt, frame)
        if collapsed:
            return [Outcome("fall", None, frame)]
        t = self.truth(self.eval(stmt.test, frame))
        if t is False:
            return self.exec_block(stmt.orelse, frame)
        # body-once over-approximation (documented): serving loops do
        # host bookkeeping; shape-relevant values are loop-invariant
        outs = self.exec_block(stmt.body, frame)
        return [Outcome("fall", None, o.frame) if o.kind in
                ("break", "continue") else o for o in outs]

    def _pow2_while(self, stmt: ast.While, frame: Frame) -> bool:
        """Recognise ``while b < n: b *= 2`` over a Known start and a
        bounded n, collapsing to the power-of-two FiniteSet the loop
        can produce.  This is THE abstraction that makes admission
        bucket sets finite."""
        test = stmt.test
        if not (isinstance(test, ast.Compare) and len(test.ops) == 1
                and isinstance(test.ops[0], ast.Lt)
                and isinstance(test.left, ast.Name)
                and len(stmt.body) == 1
                and isinstance(stmt.body[0], ast.AugAssign)
                and isinstance(stmt.body[0].op, ast.Mult)
                and isinstance(stmt.body[0].target, ast.Name)
                and stmt.body[0].target.id == test.left.id
                and isinstance(stmt.body[0].value, ast.Constant)
                and stmt.body[0].value.value == 2):
            return False
        var = test.left.id
        b = frame.locals.get(var)
        n = self.eval(test.comparators[0], frame)
        b_dim = as_dim(b) if b is not None else None
        n_dim = as_dim(n) if n is not None else None
        if not isinstance(b_dim, Known):
            return False
        k0 = b_dim.v
        if isinstance(n_dim, Known):
            v = k0
            while v < n_dim.v:
                v *= 2
            frame.locals[var] = Scalar(v)
            return True
        if isinstance(n_dim, IntRange):
            lo, hi = n_dim.lo, n_dim.hi
        elif n_dim is not None and n_dim.values() is not None:
            lo, hi = min(n_dim.values()), max(n_dim.values())
        else:
            frame.locals[var] = Scalar(Unbounded(
                f"pow2 doubling of {var} over an unbounded count"))
            return True
        out = set()
        v = k0
        while True:
            # v is reachable if some n in [lo, hi] power-of-two-ceils
            # to it: n <= k0 lands on k0; otherwise n in (v/2, v]
            if (v == k0 and lo <= k0) or (v > k0 and lo <= v
                                          and hi > v // 2):
                out.add(v)
            if v >= hi:
                break
            v *= 2
        frame.locals[var] = Scalar(FiniteSet(sorted(out), name=var))
        return True

    def _exec_for(self, stmt: ast.For, frame: Frame) -> List[Outcome]:
        it = self.eval(stmt.iter, frame)
        items: Optional[List[AbsValue]] = None
        if isinstance(it, Tup) and len(it.items) <= MAX_PATHS:
            items = list(it.items)
        if items is not None:
            states = [frame]
            done: List[Outcome] = []
            for item in items:
                nxt = []
                for fr in states:
                    self._store(stmt.target, item, fr)
                    for o in self.exec_block(stmt.body, fr):
                        if o.kind in ("fall", "continue"):
                            nxt.append(o.frame)
                        elif o.kind == "break":
                            done.append(Outcome("fall", None, o.frame))
                        elif o.kind == "return":
                            done.append(o)
                states = nxt[:MAX_PATHS]
            done.extend(Outcome("fall", None, fr) for fr in states)
            return done
        elem = _elem_of(it)
        self._store(stmt.target, elem, frame)
        outs = self.exec_block(stmt.body, frame)
        outs = [Outcome("fall", None, o.frame) if o.kind in
                ("break", "continue") else o for o in outs]
        if not any(o.kind == "fall" for o in outs):
            # every body path broke/returned/raised; the zero-iteration
            # case still falls through
            outs.append(Outcome("fall", None, frame))
        return outs

    # -------------------------------------------------------- assigns
    def _assign(self, stmt: ast.stmt, frame: Frame) -> None:
        if isinstance(stmt, ast.Assign):
            v = self.eval(stmt.value, frame)
            for t in stmt.targets:
                self._store(t, v, frame)
        elif isinstance(stmt, ast.AnnAssign):
            if stmt.value is not None:
                self._store(stmt.target, self.eval(stmt.value, frame),
                            frame)
        elif isinstance(stmt, ast.AugAssign):
            cur = self.eval(_load_of(stmt.target), frame)
            v = self._binop(cur, stmt.op, self.eval(stmt.value, frame))
            self._store(stmt.target, v, frame)

    def _store(self, target: ast.expr, value: AbsValue,
               frame: Frame) -> None:
        if isinstance(target, ast.Name):
            frame.locals[target.id] = value
        elif isinstance(target, ast.Attribute):
            recv = self.eval(target.value, frame)
            if isinstance(recv, Obj):
                recv.attrs[target.attr] = value
        elif isinstance(target, (ast.Tuple, ast.List)):
            vals = _unpack(value, len(target.elts))
            for t, v in zip(target.elts, vals):
                self._store(t, v, frame)
        elif isinstance(target, ast.Subscript):
            recv = self.eval(target.value, frame)
            if isinstance(recv, DictVal):
                idx = self.eval(target.slice, frame)
                if isinstance(idx, Scalar) and isinstance(idx.value, str):
                    recv.entries[idx.value] = value
            # element stores on arrays never change a shape: ignore

    # ----------------------------------------------------------- exprs
    def eval(self, node: ast.expr, frame: Frame) -> AbsValue:
        if isinstance(node, ast.Constant):
            return Scalar(node.value)
        if isinstance(node, ast.Name):
            return self._load_name(node.id, frame)
        if isinstance(node, ast.Attribute):
            return self._load_attr(node, frame)
        if isinstance(node, ast.Call):
            return self._call(node, frame)
        if isinstance(node, ast.Subscript):
            return self._subscript(node, frame)
        if isinstance(node, ast.BinOp):
            return self._binop(self.eval(node.left, frame), node.op,
                               self.eval(node.right, frame))
        if isinstance(node, ast.UnaryOp):
            return self._unary(node, frame)
        if isinstance(node, ast.Compare):
            return self._compare(node, frame)
        if isinstance(node, ast.BoolOp):
            return self._boolop(node, frame)
        if isinstance(node, (ast.Tuple, ast.List)):
            return Tup(self._eval_items(node.elts, frame))
        if isinstance(node, ast.Dict):
            entries = {}
            placement = COMMITTED
            for k, v in zip(node.keys, node.values):
                val = self.eval(v, frame)
                if k is not None and isinstance(k, ast.Constant) \
                        and isinstance(k.value, str):
                    entries[k.value] = val
            return DictVal(entries, placement)
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp)):
            return self._comp(node, frame)
        if isinstance(node, ast.IfExp):
            t = self.truth(self.eval(node.test, frame))
            if t is True:
                return self.eval(node.body, frame)
            if t is False:
                return self.eval(node.orelse, frame)
            return _join([self.eval(node.body, frame),
                          self.eval(node.orelse, frame)])
        if isinstance(node, ast.JoinedStr):
            return Scalar(Unbounded("f-string"))
        if isinstance(node, ast.Slice):
            return Unknown("bare slice")
        if isinstance(node, ast.Starred):
            return self.eval(node.value, frame)
        return Unknown(type(node).__name__)

    def _eval_items(self, nodes: Sequence[ast.expr],
                    frame: Frame) -> List[AbsValue]:
        """The items of a display or of a call's positional arguments;
        ``*seq`` puts the items of a tuple it can see in its place (a
        dispatch's host-built arguments go to the device in one put and
        reach the program as ``*args``) and is dropped otherwise."""
        out: List[AbsValue] = []
        for node in nodes:
            if not isinstance(node, ast.Starred):
                out.append(self.eval(node, frame))
                continue
            seq = self.eval(node.value, frame)
            if isinstance(seq, Tup):
                out.extend(seq.items)
        return out

    def _load_name(self, name: str, frame: Frame) -> AbsValue:
        if name in frame.locals:
            return frame.locals[name]
        mod = frame.module
        if name in mod.constants:
            return mod.constants[name]
        if name in mod.aliases:
            return ModuleFn(mod.aliases[name])
        if name in mod.classes:
            return ModuleFn(name)
        return Unknown(f"unbound name {name}")

    def _load_attr(self, node: ast.Attribute, frame: Frame) -> AbsValue:
        recv = self.eval(node.value, frame)
        attr = node.attr
        if isinstance(recv, ModuleFn):
            path = f"{recv.path}.{attr}"
            root = recv.path.split(".", 1)[0]
            if root in ("np", "jnp") and attr in DTYPE_NAMES:
                return DTypeVal(attr)
            if path == "np.newaxis":
                return Scalar(None)
            return ModuleFn(path)
        if isinstance(recv, Obj):
            if attr in recv.attrs:
                return recv.attrs[attr]
            return Unknown(f"unmodelled attr {recv.kind}.{attr}")
        if isinstance(recv, Arr):
            if attr == "shape":
                return Tup([Scalar(d) for d in recv.shape])
            if attr == "ndim":
                return Scalar(recv.ndim)
            if attr == "dtype":
                return DTypeVal(recv.dtype)
            if attr == "size":
                return Scalar(Unbounded("size"))
            if attr == "T" and recv.ndim == 2:
                return Arr((recv.shape[1], recv.shape[0]), recv.dtype,
                           recv.placement)
        return Unknown(f"attr .{attr} on {type(recv).__name__}")

    # ------------------------------------------------------------ calls
    def _call(self, node: ast.Call, frame: Frame) -> AbsValue:
        kwargs = {kw.arg: self.eval(kw.value, frame)
                  for kw in node.keywords if kw.arg is not None}
        args = self._eval_items(node.args, frame)
        f = node.func
        if isinstance(f, ast.Attribute):
            recv = self.eval(f.value, frame)
            if isinstance(recv, ModuleFn):
                path = f"{recv.path}.{f.attr}"
                rule = shape_rules.RULES.get(path) or _EXTRA_RULES.get(path)
                if rule is not None:
                    return rule(args, kwargs)
                return Unknown(f"no shape rule for {path}")
            if isinstance(recv, Obj):
                return self.call_method(recv, f.attr, args, kwargs,
                                        call_node=node,
                                        module=frame.module)
            if isinstance(recv, ListOf):
                if f.attr == "append":
                    return Scalar(None)
                if f.attr == "pop":
                    return recv.elem
                return Unknown(f"list .{f.attr}")
            if isinstance(recv, (Arr, Tree)):
                return shape_rules.method_call(recv, f.attr, args, kwargs)
            if isinstance(recv, Scalar) and isinstance(recv.value, str):
                return Scalar(Unbounded("str method"))
            return Unknown(f"method on {type(recv).__name__}")
        if isinstance(f, ast.Name):
            fv = self._load_name(f.id, frame)
            if isinstance(fv, ModuleFn):
                rule = shape_rules.RULES.get(fv.path) \
                    or _EXTRA_RULES.get(fv.path)
                if rule is not None:
                    return rule(args, kwargs)
            return self._builtin(f.id, args, kwargs, node, frame)
        return Unknown("indirect call")

    def _builtin(self, name: str, args: List[AbsValue],
                 kwargs: Dict[str, AbsValue], node: ast.Call,
                 frame: Frame) -> AbsValue:
        if name == "len" and args:
            return _length(args[0])
        if name in ("int", "float", "bool") and args:
            return _cast(name, args[0])
        if name in ("min", "max") and args:
            return _minmax(name, args)
        if name == "range":
            return _range(args)
        if name == "enumerate" and args:
            src = args[0]
            if isinstance(src, Tup):
                return Tup([Tup([Scalar(i), v])
                            for i, v in enumerate(src.items)])
            return ListOf(Tup([Scalar(Unbounded("index")),
                               _elem_of(src)]))
        if name == "zip":
            return ListOf(Tup([_elem_of(a) for a in args]))
        if name == "sorted" and args:
            return args[0] if isinstance(args[0], (Tup, ListOf)) \
                else ListOf(_elem_of(args[0]))
        if name == "list" and args:
            return args[0] if isinstance(args[0], (Tup, ListOf)) \
                else ListOf(_elem_of(args[0]))
        if name == "list":
            return ListOf(Unknown("empty"), Known(0))
        if name == "dict" and args:
            src = args[0]
            if isinstance(src, DictVal):
                return DictVal(dict(src.entries), src.placement)
            if isinstance(src, Tree):
                return Tree(src.placement, src.label)
            return Unknown("dict()")
        if name == "dict":
            return DictVal({})
        if name in ("str", "repr"):
            return Scalar(Unbounded("string"))
        if name == "sum" and args:
            return Scalar(Unbounded("sum"))
        if name == "isinstance":
            return Unknown("isinstance")
        if name == "getattr" and len(args) >= 2:
            if isinstance(args[0], Obj) and isinstance(args[1], Scalar) \
                    and isinstance(args[1].value, str):
                if args[1].value in args[0].attrs:
                    return args[0].attrs[args[1].value]
                if len(args) == 3:
                    return args[2]
            return Unknown("getattr")
        if name == "print":
            return Scalar(None)
        if name == "pack_chunk_args" and len(args) >= 5:
            return _pack_chunk_args(args)
        return Unknown(f"builtin {name}")

    # ------------------------------------------------------- subscripts
    def _subscript(self, node: ast.Subscript, frame: Frame) -> AbsValue:
        recv = self.eval(node.value, frame)
        if isinstance(recv, DictVal):
            idx = self.eval(node.slice, frame)
            if isinstance(idx, Scalar) and isinstance(idx.value, str):
                return recv.entries.get(
                    idx.value, Tree(recv.placement, idx.value))
            return Tree(recv.placement)
        if isinstance(recv, Tree):
            return Tree(recv.placement, recv.label)
        if isinstance(recv, Tup):
            idx = self.eval(node.slice, frame)
            if isinstance(idx, Scalar) and isinstance(idx.value, int) \
                    and not isinstance(idx.value, bool):
                i = idx.value
                if -len(recv.items) <= i < len(recv.items):
                    return recv.items[i]
            return Unknown("tuple index")
        if isinstance(recv, ListOf):
            return recv.elem
        if isinstance(recv, Arr):
            return self._index_arr(recv, node.slice, frame)
        return Unknown(f"subscript on {type(recv).__name__}")

    def _index_arr(self, arr: Arr, sl: ast.expr, frame: Frame) -> AbsValue:
        parts = sl.elts if isinstance(sl, ast.Tuple) else [sl]
        shape: List[Dim] = []
        axis = 0
        for part in parts:
            if isinstance(part, ast.Slice):
                if axis >= arr.ndim:
                    return Unknown("over-indexed")
                shape.append(self._slice_dim(arr.shape[axis], part, frame))
                axis += 1
                continue
            v = self.eval(part, frame)
            if isinstance(v, Scalar) and v.value is None:
                shape.append(Known(1))               # newaxis
                continue
            if axis >= arr.ndim:
                return Unknown("over-indexed")
            if isinstance(v, Arr):
                if v.dtype.startswith("bool"):
                    shape.append(Unbounded("boolean mask"))
                else:
                    shape.extend(v.shape)            # advanced indexing
                axis += 1
                continue
            # any scalar-ish index drops the axis
            axis += 1
        shape.extend(arr.shape[axis:])
        return Arr(shape, arr.dtype, arr.placement)

    def _slice_dim(self, dim: Dim, sl: ast.Slice, frame: Frame) -> Dim:
        lo = self.eval(sl.lower, frame) if sl.lower is not None else None
        hi = self.eval(sl.upper, frame) if sl.upper is not None else None
        if sl.step is not None:
            return Unbounded("strided slice")
        lo_d = as_dim(lo) if lo is not None else Known(0)
        if hi is None:
            hi_d: Optional[Dim] = dim
        else:
            hi_d = as_dim(hi)
        if isinstance(lo_d, Known) and isinstance(hi_d, Known):
            return Known(max(0, hi_d.v - lo_d.v))
        if isinstance(lo_d, Known) and lo_d.v == 0 and hi_d is dim:
            return dim
        return Unbounded("abstract slice bounds")

    # ------------------------------------------------------- operators
    def _binop(self, left: AbsValue, op: ast.operator,
               right: AbsValue) -> AbsValue:
        if isinstance(left, (Arr,)) or isinstance(right, (Arr,)):
            return binop(left, right)
        if isinstance(left, Tup) and isinstance(right, Tup) \
                and isinstance(op, ast.Add):
            return Tup(list(left.items) + list(right.items))
        ld = as_dim(left) if isinstance(left, Scalar) else None
        rd = as_dim(right) if isinstance(right, Scalar) else None
        if ld is not None and rd is not None:
            d = _dim_arith(ld, op, rd)
            if d is not None:
                return Scalar(d)
        if isinstance(left, Scalar) and isinstance(right, Scalar) and \
                isinstance(left.value, (int, float)) and \
                isinstance(right.value, (int, float)):
            try:
                return Scalar(_py_arith(left.value, op, right.value))
            except (ZeroDivisionError, TypeError):
                return Unknown("arith error")
        return Unknown("binop")

    def _unary(self, node: ast.UnaryOp, frame: Frame) -> AbsValue:
        v = self.eval(node.operand, frame)
        if isinstance(node.op, ast.Not):
            t = self.truth(v)
            return Scalar(not t) if t is not None else Unknown("not")
        if isinstance(node.op, ast.USub):
            if isinstance(v, Scalar) and isinstance(v.value, (int, float)) \
                    and not isinstance(v.value, bool):
                return Scalar(-v.value)
            return Unknown("negation")
        return Unknown("unary")

    def _compare(self, node: ast.Compare, frame: Frame) -> AbsValue:
        left = self.eval(node.left, frame)
        result: Optional[bool] = None
        for op, comp in zip(node.ops, node.comparators):
            right = self.eval(comp, frame)
            r = _compare_one(left, op, right)
            if r is None:
                return Unknown("compare")
            result = r if result is None else (result and r)
            if result is False:
                return Scalar(False)
            left = right
        return Scalar(bool(result))

    def _boolop(self, node: ast.BoolOp, frame: Frame) -> AbsValue:
        is_and = isinstance(node.op, ast.And)
        last: AbsValue = Scalar(is_and)
        for v in node.values:
            val = self.eval(v, frame)
            t = self.truth(val)
            if t is None:
                return Unknown("boolop")
            if is_and and not t:
                return val
            if not is_and and t:
                return val
            last = val
        return last

    def _comp(self, node, frame: Frame) -> AbsValue:
        gen = node.generators[0]
        it = self.eval(gen.iter, frame)
        inner = frame.copy()
        self._store(gen.target, _elem_of(it), inner)
        elem = self.eval(node.elt, inner)
        return ListOf(elem, maybe_empty=True)

    # ------------------------------------------------------------ truth
    def truth(self, v: AbsValue) -> Optional[bool]:
        if isinstance(v, Scalar):
            if isinstance(v.value, Dim):
                vals = v.value.values()
                if vals is not None:
                    if all(x for x in vals):
                        return True
                    if not any(x for x in vals):
                        return False
                return None
            if isinstance(v.value, (bool, int, float, str, type(None))):
                return bool(v.value)
            return None
        if isinstance(v, Obj):
            return True
        if isinstance(v, (DictVal,)):
            return bool(v.entries) or None
        if isinstance(v, Tree):
            return True
        if isinstance(v, Tup):
            return len(v.items) > 0
        if isinstance(v, ListOf):
            return None if v.maybe_empty else True
        return None


# ----------------------------------------------------------------------
# small value helpers
# ----------------------------------------------------------------------
def _join(vals: List[AbsValue]) -> AbsValue:
    """Pick the most informative of several path results (documented
    over-approximation: serving methods return one shape family; raise
    paths and early outs contribute None/Unknown which we drop)."""
    if not vals:
        return Scalar(None)
    def score(v: AbsValue) -> int:
        if isinstance(v, (Arr, Tup, Tree)):
            return 3
        if isinstance(v, (Obj, ListOf, Scalar)):
            return 2
        return 0
    return max(vals, key=score)


def _elem_of(v: AbsValue) -> AbsValue:
    if isinstance(v, ListOf):
        return v.elem
    if isinstance(v, Tup):
        return _join(list(v.items))
    if isinstance(v, Arr) and v.ndim >= 1:
        return Arr(v.shape[1:], v.dtype, v.placement)
    return Unknown("iteration element")


def _unpack(v: AbsValue, n: int) -> List[AbsValue]:
    if isinstance(v, Tup) and len(v.items) == n:
        return list(v.items)
    if isinstance(v, Arr) and v.ndim >= 1 and \
            isinstance(v.shape[0], Known) and v.shape[0].v == n:
        return [Arr(v.shape[1:], v.dtype, v.placement) for _ in range(n)]
    return [Unknown("unpack") for _ in range(n)]


def _length(v: AbsValue) -> AbsValue:
    if isinstance(v, ListOf):
        return Scalar(v.length)
    if isinstance(v, Tup):
        return Scalar(len(v.items))
    if isinstance(v, Arr) and v.ndim >= 1:
        return Scalar(v.shape[0])
    return Unknown("len")


def _cast(name: str, v: AbsValue) -> AbsValue:
    if isinstance(v, Scalar):
        val = v.value
        if isinstance(val, Dim):
            return v if name == "int" else Scalar(Unbounded(name))
        if isinstance(val, (int, float, bool)):
            return Scalar({"int": int, "float": float,
                           "bool": bool}[name](val))
        return Scalar(Unbounded(f"{name}()"))
    if isinstance(v, Arr) and v.ndim == 0:
        return Scalar(Unbounded(f"{name}() host readback"))
    return Scalar(Unbounded(f"{name}()"))


def _minmax(name: str, args: List[AbsValue]) -> AbsValue:
    if len(args) == 1:
        return Scalar(Unbounded(name))
    dims = [as_dim(a) if isinstance(a, Scalar) else None for a in args]
    if any(d is None for d in dims):
        return Unknown(name)
    vals_list = [d.values() for d in dims]
    if any(v is None for v in vals_list):
        # bound an unbounded operand by a known one for min()
        return Scalar(Unbounded(name))
    if all(len(v) == 1 for v in vals_list):
        pick = (min if name == "min" else max)(v[0] for v in vals_list)
        return Scalar(pick)
    # elementwise over the distinguished non-Known dim (single-set case)
    sets = [d for d in dims if not isinstance(d, Known)]
    if len(sets) == 1:
        other = [d.values()[0] for d in dims if isinstance(d, Known)]
        f = min if name == "min" else max
        merged = {f([v] + other) for v in sets[0].values()}
        if len(merged) == 1:
            return Scalar(next(iter(merged)))
        return Scalar(FiniteSet(sorted(merged)))
    return Unknown(name)


def _range(args: List[AbsValue]) -> AbsValue:
    dims = [as_dim(a) if isinstance(a, Scalar) else None for a in args]
    if all(isinstance(d, Known) for d in dims) and dims:
        vals = [d.v for d in dims]  # type: ignore[union-attr]
        r = range(*vals)
        if len(r) <= MAX_PATHS:
            return Tup([Scalar(i) for i in r])
    return ListOf(Scalar(Unbounded("range")), maybe_empty=True)


def _py_arith(a, op: ast.operator, b):
    if isinstance(op, ast.Add):
        return a + b
    if isinstance(op, ast.Sub):
        return a - b
    if isinstance(op, ast.Mult):
        return a * b
    if isinstance(op, ast.FloorDiv):
        return a // b
    if isinstance(op, ast.Div):
        return a / b
    if isinstance(op, ast.Mod):
        return a % b
    if isinstance(op, ast.Pow):
        return a ** b
    raise TypeError("op")


def _dim_arith(a: Dim, op: ast.operator, b: Dim) -> Optional[Dim]:
    if isinstance(a, Known) and isinstance(b, Known):
        try:
            return dim_of(int(_py_arith(a.v, op, b.v)))
        except (TypeError, ZeroDivisionError):
            return None
    av, bv = a.values(), b.values()
    if av is None or bv is None:
        return Unbounded("arith over unbounded dim")
    if len(av) > 1 and len(bv) > 1:
        return Unbounded("arith over two abstract dims")
    try:
        vals = sorted({int(_py_arith(x, op, y)) for x in av for y in bv})
    except (TypeError, ZeroDivisionError):
        return None
    if len(vals) == 1:
        return Known(vals[0])
    if isinstance(a, IntRange) or isinstance(b, IntRange):
        return IntRange(vals[0], vals[-1])
    return FiniteSet(vals)


def _compare_one(left: AbsValue, op: ast.cmpop,
                 right: AbsValue) -> Optional[bool]:
    # identity / None tests over modelled host objects
    if isinstance(op, (ast.Is, ast.IsNot)):
        def nullness(v: AbsValue) -> Optional[bool]:
            if isinstance(v, Scalar) and v.value is None:
                return True
            if isinstance(v, (Obj, Arr, Tree, Tup, ListOf, DTypeVal)):
                return False
            if isinstance(v, Scalar) and isinstance(
                    v.value, (bool, int, float, str)):
                return False
            return None
        ln, rn = nullness(left), nullness(right)
        if ln is None or rn is None:
            return None
        same = ln and rn
        if not ln and not rn:
            return None if not isinstance(op, ast.Is) else None
        return same if isinstance(op, ast.Is) else not same
    if isinstance(left, Tup) and isinstance(right, Tup) and \
            isinstance(op, (ast.Eq, ast.NotEq)):
        lv = [as_dim(i) if isinstance(i, Scalar) else None
              for i in left.items]
        rv = [as_dim(i) if isinstance(i, Scalar) else None
              for i in right.items]
        if all(isinstance(d, Known) for d in lv) and \
                all(isinstance(d, Known) for d in rv):
            eq = [d.v for d in lv] == [d.v for d in rv]  # type: ignore
            return eq if isinstance(op, ast.Eq) else not eq
        return None
    ld = as_dim(left) if isinstance(left, Scalar) else None
    rd = as_dim(right) if isinstance(right, Scalar) else None
    if ld is None or rd is None:
        if isinstance(left, Scalar) and isinstance(right, Scalar) and \
                isinstance(left.value, (str, bool, int, float)) and \
                isinstance(right.value, (str, bool, int, float)):
            return _py_compare(left.value, op, right.value)
        return None
    lv, rv = ld.values(), rd.values()
    if lv is None or rv is None:
        return None
    lo_l, hi_l, lo_r, hi_r = min(lv), max(lv), min(rv), max(rv)
    if isinstance(op, ast.Lt):
        if hi_l < lo_r:
            return True
        if lo_l >= hi_r:
            return False
    elif isinstance(op, ast.LtE):
        if hi_l <= lo_r:
            return True
        if lo_l > hi_r:
            return False
    elif isinstance(op, ast.Gt):
        if lo_l > hi_r:
            return True
        if hi_l <= lo_r:
            return False
    elif isinstance(op, ast.GtE):
        if lo_l >= hi_r:
            return True
        if hi_l < lo_r:
            return False
    elif isinstance(op, (ast.Eq, ast.NotEq)):
        if len(lv) == 1 and len(rv) == 1:
            eq = lv[0] == rv[0]
            return eq if isinstance(op, ast.Eq) else not eq
        if hi_l < lo_r or lo_l > hi_r:
            return isinstance(op, ast.NotEq)
    return None


def _py_compare(a, op: ast.cmpop, b) -> Optional[bool]:
    try:
        if isinstance(op, ast.Eq):
            return a == b
        if isinstance(op, ast.NotEq):
            return a != b
        if isinstance(op, ast.Lt):
            return a < b
        if isinstance(op, ast.LtE):
            return a <= b
        if isinstance(op, ast.Gt):
            return a > b
        if isinstance(op, ast.GtE):
            return a >= b
    except TypeError:
        return None
    return None


def _load_of(target: ast.expr) -> ast.expr:
    import copy
    node = copy.deepcopy(target)
    for n in ast.walk(node):
        if hasattr(n, "ctx"):
            n.ctx = ast.Load()
    return node


_EXTRA_RULES = {
    "np.ndim": lambda args, kw: Scalar(args[0].ndim)
    if args and isinstance(args[0], Arr)
    else (Scalar(0) if args and isinstance(args[0], Scalar)
          else Unknown("ndim")),
}


# ----------------------------------------------------------------------
# config seeding + drivers
# ----------------------------------------------------------------------
def _engine_obj(env: dict) -> Obj:
    return Obj("InferenceEngine", {
        "params": Tree(COMMITTED, "params"),
        "_jit_prefill_chunk": Obj("jit"),
        "_jit_verify_k": Obj("jit"),
        "_decode_fn": Obj("fn"),
    })


def _pool_obj(env: dict, engine: Obj) -> Obj:
    S = int(env["num_slots"])
    attrs: Dict[str, AbsValue] = {
        "num_slots": Scalar(S),
        "capacity": Scalar(int(env["capacity"])),
        "starts": Arr((S,), "int32", HOST),
        "cache": DictVal({"cache_store": Tree(COMMITTED, "pool")}),
        "_sharding": Obj("sharding"),
        "spec": Obj("spec"),
        "_engine": engine,
        "_admit_jit": Obj("jit"),
        "_admit_rows_jit": Obj("jit"),
    }
    if env.get("paged"):
        P = int(env["num_pages"])
        pps = int(env["pages_per_slot"])
        attrs.update({
            "page_size": Scalar(int(env["page_size"])),
            "num_pages": Scalar(P),
            "pages_per_slot": Scalar(pps),
            "table": Arr((S, pps), "int32", HOST),
            "page_refs": Arr((P,), "int64", HOST),
            "prefix": Obj("PrefixCache") if env.get("use_prefix")
            else Scalar(None),
            "ring": Scalar(None),
            "_table_keys": Tup([Scalar("table")]),
            "cow_copies": Scalar(0),
            "page_evictions": Scalar(0),
            "_jit_copy_page": Obj("jit"),
            "_jit_gather_pages": Obj("jit"),
            "_jit_scatter_pages": Obj("jit"),
            "_paged_decode_jit": Obj("jit"),
            "_paged_verify_jit": Obj("jit"),
            "_paged_chunk_jit": Obj("jit"),
            # the fused-kernel arms exist iff the env arms them
            # (``paged_kernel_active`` in ``_signature_env``); the
            # precise is-not-None nullness test then picks the dispatch
            # branch instead of forking both
            "_paged_decode_kernel_jit": Obj("jit")
            if env.get("paged_kernel_active") else Scalar(None),
            "_paged_verify_kernel_jit": Obj("jit")
            if env.get("paged_kernel_active") else Scalar(None),
            "_paged_chunk_decode_jit": Obj("jit")
            if env.get("paged_kernel_active") else Scalar(None),
        })
        return Obj("PagedKVPool", attrs)
    return Obj("SlotPool", attrs)


def _serving_obj(env: dict) -> Obj:
    engine = _engine_obj(env)
    pool = _pool_obj(env, engine)
    S = int(env["num_slots"])
    spec_k = int(env.get("spec_k") or 0)
    return Obj("ServingEngine", {
        "engine": engine,
        "pool": pool,
        "_paged": Scalar(bool(env.get("paged"))),
        "_use_prefix": Scalar(bool(env.get("use_prefix"))),
        "_stall_free": Scalar(bool(env.get("stall_free"))),
        "prefill_chunk": Scalar(int(env.get("prefill_chunk") or 0)),
        "prefill_token_budget": Scalar(
            int(env.get("prefill_token_budget") or 0)),
        "faults": Scalar(None),
        "_load": Scalar(None),
        "_spec": Obj("SpecConfig", {"k": Scalar(spec_k)})
        if spec_k else Scalar(None),
        "_drafter": Obj("Drafter") if spec_k else Scalar(None),
        "_jit_finite": Obj("jit") if env.get("guard_numerics")
        else Scalar(None),
        "temperature": Scalar(float(env.get("temperature", 1.0))),
        "top_k": Scalar(int(env.get("top_k") or 0)),
        "top_p": Scalar(float(env.get("top_p", 1.0))),
        "_greedy": Arr((), "bool", UNCOMMITTED),
        "_rng": Arr((2,), "uint32", HOST),
        "_current": Arr((S,), "int32", HOST),
        "_cur_dev": Arr((S,), "int32", COMMITTED),
        "_deferred": ListOf(Unknown("deferred fetch"), maybe_empty=True),
        "_in_flight": Scalar(None),
        "_unread": Obj("opaque"),
        "_closing": Obj("opaque"),
        "_ahead": Scalar(None),
        "timers": Obj("opaque"),
        "_slot_req": Obj("opaque"),
        "tracer": Obj("opaque"),
        "metrics": Obj("opaque"),
        "timelines": Obj("opaque"),
        "scheduler": Obj("opaque"),
        "slo": Scalar(None),
        "step_id": Scalar(0),
        "_tokens_emitted": Scalar(0),
        "_prefill_queue": ListOf(Unknown("queue"), maybe_empty=True),
        # a chunk left to the decode dispatch (``_fuses_env``): none but
        # in the driver that hands ``_decode_step`` one
        "_chunk_beside": Scalar(None),
        "_fuses_chunks": Scalar(_fuses_env(env)),
    })


def _fuses_env(env: dict) -> bool:
    """``ServingEngine._fuses_chunks`` of a config: a step's chunk goes
    with its decode rows as one program."""
    return bool(env.get("paged") and env.get("paged_kernel_active")
                and env.get("stall_free") and not env.get("spec_k")
                and str(env.get("role", "both")) != "prefill")


def _request_obj(T: Dim) -> Obj:
    return Obj("Request", {
        "seed_len": Scalar(T),
        "seed_tokens": Arr((T,), "int32", HOST),
        "output_tokens": ListOf(Scalar(Unbounded("token")),
                                maybe_empty=True),
        "prompt_len": Scalar(T),
        "request_id": Scalar(Unbounded("rid")),
        "admit_time": Scalar(None),
        "first_token_time": Scalar(None),
        "slot": Scalar(None),
        "prefill_pos": Scalar(0),
        "chunks": Scalar(0),
        "eos_token_id": Scalar(None),
    })


def _max_group(env: dict, width: int) -> int:
    """Largest same-bucket admission group the token-budget grant can
    produce at ``width`` (mirrors ``FIFOScheduler.grant``: each
    admission is charged its padded bucket; the head may overshoot but
    a GROUP never exceeds budget // width; free slots bound it too)."""
    budget = int(env.get("prefill_token_budget") or 0)
    slots = int(env["num_slots"])
    if budget <= 0:
        return 1
    return max(1, min(slots, budget // width))


def _singleton_T(env: dict) -> IntRange:
    max_len = int(env.get("max_prompt_len")
                  or env.get("max_seed_len") or env["capacity"])
    hi = max_len
    if env.get("stall_free"):
        hi = min(hi, int(env["prefill_chunk"]))
    return IntRange(1, max(1, hi), "seed_len")


def _widths(env: dict) -> List[int]:
    """The reachable padded bucket widths for grouped (>=2) admissions."""
    hi = _singleton_T(env).hi
    cap = int(env["capacity"])
    out = []
    b = 16                       # _MIN_PREFILL_BUCKET
    while True:
        out.append(min(b, cap))
        if b >= hi:
            break
        b *= 2
    return sorted(set(out))


def run_drivers(interp: Interp) -> None:
    """Interpret every config-reachable serving entry point (the
    calling-context table — see module docstring)."""
    env = interp.env
    project = interp.project
    srv = _serving_obj(env)

    def call(obj: Obj, method: str, frame_args: Dict[str, AbsValue]):
        resolved = project.resolve_method(obj.kind, method)
        if resolved is None:
            return
        fn, mod = resolved
        params = [a.arg for a in fn.args.args]
        args = []
        for p in params[1:]:
            args.append(frame_args.get(p, Unknown(f"driver arg {p}")))
        interp.call_function(fn, mod, obj, args, {})

    # 1. singleton bucketed admission (whole-seed prefill at a padded
    #    power-of-two width)
    call(srv, "_admit", {"req": _request_obj(_singleton_T(env))})

    if env.get("stall_free"):
        # 2. batched bucketed admission, per width (the reachable batch
        #    bucket set depends on the width through the token budget)
        for width in _widths(env):
            gmax = _max_group(env, width)
            if gmax < 2:
                continue
            group_n = IntRange(2, gmax, f"group@{width}")
            call(srv, "_admit_batch", {
                "group": ListOf(_request_obj(
                    IntRange(1, min(width, _singleton_T(env).hi))),
                    group_n, maybe_empty=False),
                "width": Scalar(width)})

        # 3. chunked prefill steps for long prompts
        max_len = int(env.get("max_prompt_len")
                      or env.get("max_seed_len") or env["capacity"])
        if max_len > int(env["prefill_chunk"] or 0):
            srv2 = _serving_obj(env)
            req = _request_obj(IntRange(1, max_len, "seed_len"))
            req.attrs["slot"] = Scalar(
                IntRange(0, int(env["num_slots"]) - 1, "slot"))
            req.attrs["prefill_pos"] = Scalar(
                IntRange(0, max_len - 1, "pos"))
            srv2.attrs["_prefill_queue"] = ListOf(req, maybe_empty=False)
            call(srv2, "_prefill_chunk_step", {})

    # 4. the decode step (and the numerics guard, when armed)
    call(srv, "_decode_step", {"t0": Scalar(0.0)})

    # 4b. the same dispatch with the step's chunk left to it: ONE program
    #     for the chunk's rows and the decode rows
    if _fuses_env(env):
        srv3 = _serving_obj(env)
        C = int(env["prefill_chunk"])
        req = _request_obj(IntRange(1, int(env["capacity"]), "seed_len"))
        req.attrs["slot"] = Scalar(
            IntRange(0, int(env["num_slots"]) - 1, "slot"))
        srv3.attrs["_chunk_beside"] = Tup([
            req, Arr((Known(1), Known(C)), "int32", HOST),
            Scalar(IntRange(0, int(env["capacity"]) - 1, "pos")),
            Scalar(C), Scalar(0.0)])
        call(srv3, "_decode_step", {"t0": Scalar(0.0)})

    # 5. speculative verify step
    if env.get("spec_k"):
        call(srv, "_spec_decode_step", {"t0": Scalar(0.0)})

    # 6. paged page management: CoW page copies (also pre-warmed by
    #    bind_engine with a self-copy at runtime)
    if env.get("paged"):
        pool = srv.attrs["pool"]
        cap = int(env["capacity"])
        call(pool, "ensure_writable", {
            "slot": Scalar(IntRange(0, int(env["num_slots"]) - 1)),
            "start": Scalar(IntRange(0, cap, "start")),
            "end": Scalar(IntRange(0, cap, "end")),
            "sync": Scalar(True)})

        # 7. cross-pool page transfer (the disaggregated prefill->decode
        #    handoff): id vectors are always sentinel-padded to
        #    pages_per_slot, so ONE signature covers every transfer
        #    (also pre-warmed by bind_engine with an all-sentinel copy)
        pps = int(env["pages_per_slot"])
        call(pool, "_dispatch_transfer", {
            "src_pool": _pool_obj(env, srv.attrs["engine"]),
            "src_vec": Arr((Known(pps),), "int32", HOST),
            "dst_vec": Arr((Known(pps),), "int32", HOST)})


def default_check_envs() -> List[dict]:
    """The representative configs a bare ``--check`` proves finite, at
    sizes small enough to enumerate. One stands for the server the
    benchmark's cells run (``perf/configs/pythia-1.4b-paged.json``); the
    others are options of ``ServingEngine`` no ``perf/configs/`` file
    turns on, kept finite for the tests and ``chip_smoke.py`` that do.
    Hand-kept: deriving them from ``perf/configs/*.json`` is ROADMAP D7.
    ``--manifest`` runs replace these with the configs recorded in the
    manifest itself."""
    common = dict(top_k=0, top_p=1.0, greedy=False, temperature=1.0,
                  spec_k=0, guard_numerics=False)
    stall = dict(num_slots=8, capacity=1024, prefill_chunk=256,
                 prefill_token_budget=1024, paged=False, page_size=0,
                 num_pages=0, pages_per_slot=0, use_prefix=False,
                 vocab_size=512, max_prompt_len=760, **common)
    paging = dict(num_slots=8, capacity=256, prefill_chunk=32,
                  prefill_token_budget=64, paged=True, page_size=32,
                  num_pages=32, pages_per_slot=8, use_prefix=True,
                  vocab_size=512, max_seed_len=160, **common)
    return [
        # no perf/configs/ server: the contiguous SlotPool (the
        # constructor's default), chunked stall-free admission
        dict(stall, stall_free=True),
        # no perf/configs/ server: the same with prefill_chunk=0,
        # serial admission
        dict(stall, stall_free=False, prefill_chunk=0,
             prefill_token_budget=0),
        # no perf/configs/ server: the paged pool's dense composition
        # (paged_kv.kernel "off"): the oracle arm of chip_smoke.py's
        # serve phase and what the paged serving tests run
        dict(paging, stall_free=True),
        # no perf/configs/ server: the contiguous pool again at the
        # paged envs' capacity, four slots
        dict(paging, paged=False, page_size=0, num_pages=0,
             pages_per_slot=0, num_slots=4, use_prefix=False,
             stall_free=True),
        # perf/configs/pythia-1.4b-paged.json (both serving cells; 64
        # slots and 256 pages of 64 there): paged pool, prefix cache,
        # stall-free admission, decode/verify through the Pallas kernel
        # jits (kernel "auto" resolves to on, on the TPU)
        dict(paging, stall_free=True, paged_kernel="on",
             paged_kernel_active=True),
        # no perf/configs/ server: chip_smoke.py's four-chip serve phase
        # and test_tp_serving.py. A (data, model) mesh changes ONLY
        # array placements, never a traced shape, so its enumerated
        # signature set must be identical to the dense env's
        # (mesh_data/mesh_model ride along in _signature_env for config
        # identity; the drivers ignore unknown keys)
        dict(stall, stall_free=True, mesh_data=4, mesh_model=2),
    ]


# ----------------------------------------------------------------------
# public API
# ----------------------------------------------------------------------
class EnumResult:
    def __init__(self, programs: Dict[str, List[str]],
                 findings: List[Finding]):
        self.programs = programs
        self.findings = findings


def enumerate_signatures(env: dict, root: str,
                         project: Optional[ProjectIndex] = None
                         ) -> EnumResult:
    """Statically enumerate the reachable manifest-signature set per
    watched program for one config ``env`` (the dict
    ``ServingEngine._signature_env`` exports).  Returns the programs
    map plus ``signature-escape`` / ``unbounded-signature`` findings
    anchored at the offending watched call sites."""
    project = project or ProjectIndex(root)
    interp = Interp(project, env)
    run_drivers(interp)
    programs: Dict[str, set] = {}
    findings: List[Finding] = []
    seen_failures = set()
    for rec in interp.records:
        try:
            sigs = expand_signatures(rec.args, rec.kwargs)
        except SignatureError as e:
            key = (rec.program, rec.rel, rec.line, e.kind)
            if key in seen_failures:
                continue
            seen_failures.add(key)
            findings.append(Finding(
                rule=e.kind, severity=ERROR, path=rec.rel,
                line=rec.line, col=1,
                message=f"watched program `{rec.program}`: {e} — the "
                        "zero-recompile invariant cannot be proven for "
                        "this call",
                func=rec.program))
            continue
        programs.setdefault(rec.program, set()).update(sigs)
    out = {name: sorted(vals) for name, vals in sorted(programs.items())}
    return EnumResult(out, findings + interp.findings)


def enumerate_union(envs: Iterable[dict], root: str,
                    project: Optional[ProjectIndex] = None) -> EnumResult:
    """Union of :func:`enumerate_signatures` across configs — the shape
    a merged manifest has (several servers sharing one engine)."""
    project = project or ProjectIndex(root)
    programs: Dict[str, set] = {}
    findings: List[Finding] = []
    seen = set()
    for env in envs:
        res = enumerate_signatures(env, root, project)
        for name, sigs in res.programs.items():
            programs.setdefault(name, set()).update(sigs)
        for f in res.findings:
            key = (f.rule, f.path, f.line)
            if key not in seen:
                seen.add(key)
                findings.append(f)
    return EnumResult(
        {n: sorted(v) for n, v in sorted(programs.items())}, findings)


def diff_manifest(static: Dict[str, List[str]],
                  manifest: Dict[str, List[str]]) -> List[str]:
    """Human-readable divergences between the statically enumerated
    programs and a runtime warmup manifest.  Empty list == exact match.

    Both directions are failures: a runtime signature the static set
    lacks means the interpreter (or a driver) missed a reachable
    shape; a static signature the runtime never hit means the warmup
    sweep under-covers and that bucket will compile post-warmup."""
    out: List[str] = []
    for name in sorted(set(static) | set(manifest)):
        s = set(static.get(name, ()))
        m = set(manifest.get(name, ()))
        if name not in manifest:
            out.append(f"{name}: statically reachable but absent from "
                       f"the runtime manifest ({len(s)} signature(s))")
            continue
        if name not in static:
            out.append(f"{name}: in the runtime manifest but not "
                       f"statically reachable ({len(m)} signature(s))")
            continue
        for sig in sorted(s - m):
            out.append(f"{name}: static-only {sig} (warmup sweep never "
                       "hit this bucket — it will compile post-warmup)")
        for sig in sorted(m - s):
            out.append(f"{name}: runtime-only {sig} (the static "
                       "enumeration missed a reachable shape)")
    return out
