"""Per-op abstract shape/dtype transfer functions for graftcheck.

Each rule maps abstract operands (:mod:`absdomain` values) to the
abstract result of one numpy/jnp/lax operation.  The registry is keyed
by *canonical* dotted name — the interpreter normalises whatever the
module imported (``import numpy as np``, ``from jax import numpy as
jnp``) to the ``np.`` / ``jnp.`` / ``jax.lax.`` / ``jax.random.``
prefixes before lookup.

Rules are deliberately forgiving: an operand combination a rule cannot
handle returns :class:`~.absdomain.Unknown` rather than raising, so
imprecision surfaces as a ``signature-escape`` finding only if the
value actually reaches a watched jit operand.

Placement discipline (the placement-mix rule's input):

* ``np.*`` constructors produce HOST arrays (numpy-backed operands are
  layout-neutral at a jit boundary — they adopt the executable's
  layout);
* ``jnp.*`` constructors produce UNCOMMITTED device arrays (default
  layout, the PR-5 double-compile hazard);
* ``jnp.asarray``/conversions *preserve* the operand's placement —
  converting a host buffer does not commit it;
* only ``jax.device_put`` (modelled in the interpreter, where the
  sharding operand is visible) yields COMMITTED.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence

from .absdomain import (HOST, UNCOMMITTED, AbsValue, Arr, Dim, FiniteSet,
                        IntRange, Known, Obj, Scalar, Tree, Tup, Unknown,
                        dim_of)


class DTypeVal(AbsValue):
    """A dtype object (``jnp.int32``) flowing as a value."""

    __slots__ = ("name",)

    def __init__(self, name: str):
        self.name = name

    def __repr__(self):
        return f"DTypeVal({self.name})"


DTYPE_NAMES = {
    "int8", "int16", "int32", "int64", "uint8", "uint16", "uint32",
    "uint64", "float16", "bfloat16", "float32", "float64", "bool", "bool_",
}


def dtype_name(v: Any, default: str) -> str:
    if isinstance(v, DTypeVal):
        return "bool" if v.name == "bool_" else v.name
    if isinstance(v, Scalar) and isinstance(v.value, str) \
            and v.value in DTYPE_NAMES:
        return "bool" if v.value == "bool_" else v.value
    return default


def as_dim(v: Any) -> Dim:
    """Coerce an abstract value (or int) to a Dim; Unknown on failure."""
    if isinstance(v, Scalar):
        try:
            return v.as_dim()
        except TypeError:
            return None  # type: ignore[return-value]
    if isinstance(v, Arr) and v.ndim == 0:
        # a 0-d int array used as a size — not statically enumerable
        return None  # type: ignore[return-value]
    try:
        return dim_of(v)
    except TypeError:
        return None  # type: ignore[return-value]


def shape_from(v: AbsValue) -> Optional[List[Dim]]:
    """Parse a shape operand: an int scalar, or a Tup of int scalars."""
    if isinstance(v, Tup):
        dims = [as_dim(x) for x in v.items]
        if any(d is None for d in dims):
            return None
        return dims  # type: ignore[return-value]
    d = as_dim(v)
    return None if d is None else [d]


def _broadcast_dim(a: Dim, b: Dim) -> Dim:
    av, bv = a.values(), b.values()
    if av == (1,):
        return b
    if bv == (1,):
        return a
    if isinstance(a, Known):
        return b if not isinstance(b, Known) else a
    return a


def broadcast_shapes(a: Sequence[Dim], b: Sequence[Dim]) -> List[Dim]:
    out: List[Dim] = []
    ra, rb = list(a)[::-1], list(b)[::-1]
    for i in range(max(len(ra), len(rb))):
        if i >= len(ra):
            out.append(rb[i])
        elif i >= len(rb):
            out.append(ra[i])
        else:
            out.append(_broadcast_dim(ra[i], rb[i]))
    return out[::-1]


def merge_placement(vals: Sequence[AbsValue]) -> str:
    for v in vals:
        if isinstance(v, (Arr, Tree)) and v.placement == UNCOMMITTED:
            return UNCOMMITTED
    return HOST


def binop(a: AbsValue, b: AbsValue) -> AbsValue:
    """Elementwise arithmetic/comparison between abstract operands."""
    if isinstance(a, Arr) and isinstance(b, Arr):
        return Arr(broadcast_shapes(a.shape, b.shape), a.dtype,
                   merge_placement((a, b)))
    if isinstance(a, Arr):
        return a
    if isinstance(b, Arr):
        return b
    return Unknown("scalar binop")


# ----------------------------------------------------------------------
# rule implementations
# ----------------------------------------------------------------------
def _constructor(placement: str, default_dtype: str):
    def rule(args, kwargs):
        if not args:
            return Unknown("constructor without shape")
        shape = shape_from(args[0])
        if shape is None:
            return Unknown("unresolvable shape operand")
        dt = default_dtype
        if len(args) > 1:
            dt = dtype_name(args[1], dt)
        dt = dtype_name(kwargs.get("dtype"), dt) if "dtype" in kwargs else dt
        return Arr(shape, dt, placement)
    return rule


def _full(placement: str):
    def rule(args, kwargs):
        if len(args) < 2:
            return Unknown("full without fill value")
        shape = shape_from(args[0])
        if shape is None:
            return Unknown("unresolvable shape operand")
        fill = args[1]
        dt = "float64" if placement == HOST else "float32"
        if isinstance(fill, Scalar):
            if isinstance(fill.value, bool):
                dt = "bool"
            elif isinstance(fill.value, (int, Dim)) \
                    and not isinstance(fill.value, bool):
                dt = "int64" if placement == HOST else "int32"
            elif isinstance(fill.value, float):
                dt = "float64" if placement == HOST else "float32"
        if len(args) > 2:
            dt = dtype_name(args[2], dt)
        dt = dtype_name(kwargs.get("dtype"), dt) if "dtype" in kwargs else dt
        return Arr(shape, dt, placement)
    return rule


def _asarray(placement_default: str):
    def rule(args, kwargs):
        if not args:
            return Unknown("asarray()")
        x = args[0]
        dt = args[1] if len(args) > 1 else kwargs.get("dtype")
        if isinstance(x, Arr):
            out = Arr(x.shape, dtype_name(dt, x.dtype), x.placement)
            return out
        if isinstance(x, Scalar):
            v = x.value
            if isinstance(v, bool):
                base = "bool"
            elif isinstance(v, (int, Dim)):
                base = "int32"
            elif isinstance(v, float):
                base = "float32" if placement_default == UNCOMMITTED \
                    else "float64"
            else:
                return Unknown(f"asarray of {v!r}")
            # scalar conversions inherit HOST: the value came from host
            # python, the array adopts the consumer's layout
            return Arr((), dtype_name(dt, base), HOST)
        if isinstance(x, Tup):
            dims = [as_dim(i) for i in x.items]
            if all(d is not None for d in dims):
                # jnp default-int is int32 (x64 disabled); np is int64
                base = "int32" if placement_default == UNCOMMITTED \
                    else "int64"
                return Arr((Known(len(dims)),),
                           dtype_name(dt, base), HOST)
        if isinstance(x, Tree):
            return x
        return Unknown("asarray of unknown operand")
    return rule


def _concatenate(args, kwargs):
    if not args or not isinstance(args[0], Tup):
        return Unknown("concatenate needs a literal sequence")
    arrs = [a for a in args[0].items]
    if not arrs or not all(isinstance(a, Arr) for a in arrs):
        return Unknown("concatenate of non-arrays")
    axis = 0
    ax = kwargs.get("axis", args[1] if len(args) > 1 else None)
    if ax is not None:
        d = as_dim(ax)
        if d is None or not isinstance(d, Known):
            return Unknown("concatenate with non-literal axis")
        axis = d.v
    first: Arr = arrs[0]
    nd = first.ndim
    if axis < 0:
        axis += nd
    if not 0 <= axis < nd:
        return Unknown("concatenate axis out of range")
    total = 0
    parts = []
    for a in arrs:
        if a.ndim != nd:
            return Unknown("concatenate rank mismatch")
        d = a.shape[axis]
        if not isinstance(d, Known):
            parts = None
            break
        total += d.v
        parts = parts if parts is None else parts + [d]
    shape = list(first.shape)
    if parts is None:
        from .absdomain import Unbounded
        shape[axis] = Unbounded("concatenate of symbolic lengths")
    else:
        shape[axis] = Known(total)
    return Arr(shape, first.dtype, merge_placement(arrs))


def _broadcast_to(args, kwargs):
    if len(args) < 2 or not isinstance(args[0], Arr):
        return Unknown("broadcast_to operands")
    shape = shape_from(args[1])
    if shape is None:
        return Unknown("broadcast_to shape")
    return Arr(shape, args[0].dtype, args[0].placement)


def _reshape(args, kwargs):
    if len(args) < 2 or not isinstance(args[0], Arr):
        return Unknown("reshape operands")
    shape = shape_from(args[1])
    if shape is None:
        return Unknown("reshape shape")
    if any(isinstance(d, Known) and d.v == -1 for d in shape):
        # -1 wildcard: only resolvable when every other dim and the
        # operand's total size are Known
        src = 1
        for d in args[0].shape:
            if not isinstance(d, Known):
                return Unknown("reshape -1 over symbolic operand")
            src *= d.v
        rest = 1
        for d in shape:
            if isinstance(d, Known) and d.v != -1:
                rest *= d.v
            elif not isinstance(d, Known):
                return Unknown("reshape -1 with symbolic dims")
        shape = [Known(src // max(rest, 1)) if
                 (isinstance(d, Known) and d.v == -1) else d for d in shape]
    return Arr(shape, args[0].dtype, args[0].placement)


def _arange(args, kwargs):
    if not args:
        return Unknown("arange()")
    n = as_dim(args[0])
    if n is None:
        return Unknown("arange of non-int")
    dt = dtype_name(kwargs.get("dtype", args[1] if len(args) > 1 else None),
                    "int32")
    return Arr((n,), dt, UNCOMMITTED)


def _take(args, kwargs):
    # jnp.take(x, idx, axis=k): x.shape with axis k replaced by idx.shape
    if len(args) < 2 or not isinstance(args[0], Arr):
        return Unknown("take operands")
    x, idx = args[0], args[1]
    if not isinstance(idx, Arr):
        return Unknown("take with non-array indices")
    ax = kwargs.get("axis", args[2] if len(args) > 2 else None)
    if ax is None:
        return Arr(idx.shape, x.dtype, merge_placement((x, idx)))
    d = as_dim(ax)
    if d is None or not isinstance(d, Known):
        return Unknown("take with non-literal axis")
    axis = d.v if d.v >= 0 else d.v + x.ndim
    if not 0 <= axis < x.ndim:
        return Unknown("take axis out of range")
    shape = list(x.shape[:axis]) + list(idx.shape) + list(x.shape[axis + 1:])
    return Arr(shape, x.dtype, merge_placement((x, idx)))


def _take_along_axis(args, kwargs):
    if len(args) < 2 or not all(isinstance(a, Arr) for a in args[:2]):
        return Unknown("take_along_axis operands")
    x, idx = args[0], args[1]
    return Arr(idx.shape, x.dtype, merge_placement((x, idx)))


def _where(args, kwargs):
    if len(args) == 3:
        arrs = [a for a in args if isinstance(a, Arr)]
        if not arrs:
            return Unknown("where of scalars")
        shape = arrs[0].shape
        for a in arrs[1:]:
            shape = broadcast_shapes(shape, a.shape)
        out = args[1] if isinstance(args[1], Arr) else arrs[0]
        return Arr(shape, out.dtype, merge_placement(arrs))
    return Unknown("where without branches")


def _elementwise(args, kwargs):
    arrs = [a for a in args if isinstance(a, Arr)]
    if not arrs:
        return Unknown("elementwise of scalars")
    shape = arrs[0].shape
    for a in arrs[1:]:
        shape = broadcast_shapes(shape, a.shape)
    return Arr(shape, arrs[0].dtype, merge_placement(arrs))


def _comparison(args, kwargs):
    out = _elementwise(args, kwargs)
    return out.with_dtype("bool") if isinstance(out, Arr) else out


def _reduction(args, kwargs):
    if not args or not isinstance(args[0], Arr):
        return Unknown("reduction operand")
    x: Arr = args[0]
    ax = kwargs.get("axis", args[1] if len(args) > 1 else None)
    if ax is None:
        return Arr((), x.dtype, x.placement)
    axes = []
    if isinstance(ax, Tup):
        for a in ax.items:
            d = as_dim(a)
            if d is None or not isinstance(d, Known):
                return Unknown("reduction with symbolic axes")
            axes.append(d.v % max(x.ndim, 1))
    else:
        d = as_dim(ax)
        if d is None or not isinstance(d, Known):
            return Unknown("reduction with symbolic axis")
        axes.append(d.v % max(x.ndim, 1))
    shape = [s for i, s in enumerate(x.shape) if i not in axes]
    return Arr(shape, x.dtype, x.placement)


def _bool_reduction(args, kwargs):
    out = _reduction(args, kwargs)
    return out.with_dtype("bool") if isinstance(out, Arr) else out


def _argmax(args, kwargs):
    out = _reduction(args, kwargs)
    return out.with_dtype("int32") if isinstance(out, Arr) else out


def _dynamic_slice_in_dim(args, kwargs):
    # lax.dynamic_slice_in_dim(x, start, size, axis)
    if len(args) < 3 or not isinstance(args[0], Arr):
        return Unknown("dynamic_slice_in_dim operands")
    x: Arr = args[0]
    size = as_dim(args[2])
    if size is None:
        return Unknown("dynamic_slice_in_dim with symbolic size")
    ax = kwargs.get("axis", args[3] if len(args) > 3 else Scalar(0))
    d = as_dim(ax)
    if d is None or not isinstance(d, Known):
        return Unknown("dynamic_slice_in_dim axis")
    axis = d.v % max(x.ndim, 1)
    shape = list(x.shape)
    if axis >= len(shape):
        return Unknown("dynamic_slice_in_dim axis out of range")
    shape[axis] = size
    return Arr(shape, x.dtype, x.placement)


def _dynamic_update_slice(args, kwargs):
    # result has the DESTINATION's shape (both _in_dim and plain forms)
    if not args or not isinstance(args[0], Arr):
        return Unknown("dynamic_update_slice operands")
    return args[0]


def _random_split(args, kwargs):
    # legacy PRNG keys: split(key[, n]) -> uint32 (n, 2)
    n: Dim = Known(2)
    if len(args) > 1:
        d = as_dim(args[1])
        if d is None:
            return Unknown("random.split count")
        n = d
    return Arr((n, Known(2)), "uint32", HOST)


def _prng_key(args, kwargs):
    return Arr((Known(2),), "uint32", HOST)


def _random_categorical(args, kwargs):
    if len(args) < 2 or not isinstance(args[1], Arr):
        return Unknown("categorical operands")
    logits: Arr = args[1]
    return Arr(logits.shape[:-1], "int32", logits.placement)


def _device_put(args, kwargs):
    """``jax.device_put(x, where)`` commits ``x``; with no ``where`` it is
    a plain transfer, as ``jnp.asarray`` is: every leaf keeps its
    placement (a host buffer stays layout-neutral). A tuple of operands
    is put leaf by leaf."""
    from .absdomain import COMMITTED
    if not args:
        return Unknown("device_put()")
    x = args[0]
    commits = len(args) > 1 or bool(kwargs)
    if isinstance(x, Tup):
        return Tup([_device_put([item] + list(args[1:]), kwargs)
                    for item in x.items])
    if isinstance(x, Arr):
        return x.with_placement(COMMITTED) if commits else x
    if isinstance(x, Tree):
        return Tree(COMMITTED, x.label) if commits else x
    return Unknown("device_put of unknown operand")


def _np_scalar(name: str):
    """``np.int32(x)`` / ``np.float32(x)``: a host scalar of that type."""
    convert = _asarray(HOST)

    def rule(args, kwargs):
        return convert(args[:1], {"dtype": DTypeVal(name)})
    return rule


RULES: Dict[str, Callable[[List[AbsValue], Dict[str, AbsValue]], AbsValue]] = {
    # constructors
    "np.zeros": _constructor(HOST, "float64"),
    "np.ones": _constructor(HOST, "float64"),
    "np.empty": _constructor(HOST, "float64"),
    "np.full": _full(HOST),
    "jnp.zeros": _constructor(UNCOMMITTED, "float32"),
    "jnp.ones": _constructor(UNCOMMITTED, "float32"),
    "jnp.full": _full(UNCOMMITTED),
    "np.asarray": _asarray(HOST),
    "np.int32": _np_scalar("int32"),
    "np.float32": _np_scalar("float32"),
    "np.array": _asarray(HOST),
    "jnp.asarray": _asarray(UNCOMMITTED),
    "jnp.array": _asarray(UNCOMMITTED),
    "np.arange": _arange,
    "jnp.arange": _arange,
    # structure
    "np.concatenate": _concatenate,
    "jnp.concatenate": _concatenate,
    "np.reshape": _reshape,
    "jnp.reshape": _reshape,
    "np.broadcast_to": _broadcast_to,
    "jnp.broadcast_to": _broadcast_to,
    "np.take": _take,
    "jnp.take": _take,
    "np.take_along_axis": _take_along_axis,
    "jnp.take_along_axis": _take_along_axis,
    "jnp.where": _where,
    "np.where": _where,
    # elementwise / reductions
    "np.minimum": _elementwise,
    "np.maximum": _elementwise,
    "jnp.minimum": _elementwise,
    "jnp.maximum": _elementwise,
    "np.clip": _elementwise,
    "jnp.clip": _elementwise,
    "np.isfinite": _comparison,
    "jnp.isfinite": _comparison,
    "np.sum": _reduction,
    "jnp.sum": _reduction,
    "np.all": _bool_reduction,
    "jnp.all": _bool_reduction,
    "np.any": _bool_reduction,
    "jnp.any": _bool_reduction,
    "np.argmax": _argmax,
    "jnp.argmax": _argmax,
    # lax
    "jax.lax.dynamic_slice_in_dim": _dynamic_slice_in_dim,
    "jax.lax.dynamic_update_slice": _dynamic_update_slice,
    "jax.lax.dynamic_update_slice_in_dim": _dynamic_update_slice,
    # random / placement
    "jax.random.split": _random_split,
    "jax.random.PRNGKey": _prng_key,
    "jax.random.categorical": _random_categorical,
    "jax.device_put": _device_put,
}


# methods on abstract arrays: x.astype(dt), x.reshape(...), x.copy(), ...
def method_call(recv: AbsValue, name: str, args: List[AbsValue],
                kwargs: Dict[str, AbsValue]) -> AbsValue:
    if isinstance(recv, Arr):
        if name == "astype":
            if args:
                return recv.with_dtype(dtype_name(args[0], recv.dtype))
            return recv
        if name == "reshape":
            shape_arg = args[0] if len(args) == 1 else Tup(args)
            return _reshape([recv, shape_arg], {})
        if name == "copy":
            return recv
        if name == "sum":
            return _reduction([recv] + args, kwargs)
        if name in ("tolist", "item"):
            return Unknown(f".{name}() materialises host values")
        if name == "transpose":
            if all(isinstance(as_dim(a), Known) for a in args) \
                    and len(args) == recv.ndim:
                perm = [as_dim(a).v for a in args]
                return Arr([recv.shape[p] for p in perm], recv.dtype,
                           recv.placement)
            return Unknown("transpose with symbolic permutation")
    if isinstance(recv, Tree):
        # dict-style access on an opaque pytree stays opaque
        if name in ("get", "copy", "items", "keys", "values"):
            return Tree(recv.placement, recv.label)
    return Unknown(f"method .{name}() on {type(recv).__name__}")
