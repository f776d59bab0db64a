"""t5x-style logical axis rules: named model dims -> mesh axes.

Modules annotate parameters and cache containers with LOGICAL axis
names ("heads", "ffn", "slots", ...) instead of hard-coding mesh axes;
an ordered rules table (first match wins, ≅ t5x
``LogicalAxisRules`` / flax ``logical_to_mesh``) maps each logical
name to a physical mesh axis from :data:`~.mesh.MESH_AXES`. One table
swap re-partitions the whole serving stack — the modules never change.

Resolution is SHAPE-AWARE, which is what keeps re-partitioning
recompile-free and bitwise-safe in practice:

* a mesh axis of size 1 is dropped from the resolved spec (partitioning
  over one device is replication; keeping the name would give the
  committed arrays a *different but equivalent* sharding from what
  GSPMD stamps on jit outputs, forking every donated-pool executable —
  the PR-5 double-executable class). A TP=1 mesh therefore resolves
  every rule to the fully-replicated spec the engine uses today, which
  is how TP=1 stays bitwise-identical by construction;
* a dimension the mapped axis size does not divide falls back to
  replicated for THAT dimension only (t5x's divisibility fallback), so
  a 4-slot pool on a data=8 CPU test mesh keeps working instead of
  failing in ``device_put``;
* a mesh axis already consumed by an earlier dimension is not repeated
  (PartitionSpec forbids duplicate axes) — later dimensions replicate.

The table's mesh-axis names are pinned against the statically-declared
universe in ``parallel/mesh.py`` both at runtime
(:func:`validate_axis_rules` at import) and statically (graftcheck's
``mesh-axis-unknown`` rule reads the same constants).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

from jax.sharding import NamedSharding, PartitionSpec

from . import mesh as mesh_mod
from .mesh import DATA_AXIS, DATA_OUTER_AXIS, MESH_AXES, MODEL_AXIS

#: logical name -> mesh axis (None = always replicated). Ordered,
#: first match wins. ``heads``/``kv_heads``/``ffn``/``vocab`` carry the
#: Megatron TP sharding (column/row-parallel projections, vocab-parallel
#: embedding — the reference's ``module_inject``/AutoTP placement);
#: ``slots`` is the serving batch dimension (slot-pooled KV rows) and
#: shards over the data axis; ``pages`` stays replicated — the paged
#: pool's free list is host-global, so pages must be reachable from
#: every data shard.
DEFAULT_AXIS_RULES: Tuple[Tuple[str, Optional[str]], ...] = (
    ("layers", None),
    ("embed", None),
    ("vocab", MODEL_AXIS),
    ("heads", MODEL_AXIS),
    ("kv_heads", MODEL_AXIS),
    ("head_dim", None),
    ("ffn", MODEL_AXIS),
    ("slots", DATA_AXIS),
    ("pages", None),
    ("positions", None),
)

#: logical layouts of the serving cache containers (KVCacheSpec
#: layouts; models/kv_cache_spec.py is the shape source of truth)
STACKED_CACHE_AXES: Dict[str, Tuple[Optional[str], ...]] = {
    "k": ("layers", "slots", "kv_heads", "head_dim", "positions"),
    "v": ("layers", "slots", "kv_heads", "head_dim", "positions"),
    "k_scale": ("layers", "slots", "kv_heads", "positions"),
    "v_scale": ("layers", "slots", "kv_heads", "positions"),
    "index": ("slots",),
}
PAGED_CACHE_AXES: Dict[str, Tuple[Optional[str], ...]] = {
    "k": ("layers", "pages", "kv_heads", "head_dim", "positions"),
    "v": ("layers", "pages", "kv_heads", "head_dim", "positions"),
    "k_scale": ("layers", "pages", "kv_heads", "positions"),
    "v_scale": ("layers", "pages", "kv_heads", "positions"),
    "index": ("slots",),
    "table": ("slots", None),
}


def validate_axis_rules(
        rules: Sequence[Tuple[str, Optional[str]]]) -> None:
    """Pin every mesh-axis name in ``rules`` against the mesh universe
    declared in :mod:`.mesh` (``MESH_AXES`` + the MiCS outer axis).
    A typo'd axis name would otherwise surface as a silent
    fully-replicated placement — NamedSharding accepts any string the
    mesh happens to contain, and a name the mesh does NOT contain only
    fails at ``device_put`` time deep inside an engine."""
    universe = set(MESH_AXES) | {DATA_OUTER_AXIS}
    for logical, axis in rules:
        if not isinstance(logical, str) or not logical:
            raise ValueError(f"logical axis name must be a non-empty "
                             f"string, got {logical!r}")
        if axis is not None and axis not in universe:
            raise ValueError(
                f"axis rule ({logical!r} -> {axis!r}) names a mesh axis "
                f"outside the declared universe {sorted(universe)}")


class LogicalAxisRules:
    """Ordered logical->mesh axis table with shape-aware resolution."""

    def __init__(self, rules: Sequence[Tuple[str, Optional[str]]]
                 = DEFAULT_AXIS_RULES):
        validate_axis_rules(rules)
        self.rules: Tuple[Tuple[str, Optional[str]], ...] = tuple(
            (str(l), a) for l, a in rules)

    def mesh_axis(self, logical: Optional[str]) -> Optional[str]:
        """First matching mesh axis for ``logical`` (None if the name is
        None, unmatched, or mapped to replicated)."""
        if logical is None:
            return None
        for name, axis in self.rules:
            if name == logical:
                return axis
        return None

    def spec_entries(self, logical_axes: Sequence[Optional[str]]
                     ) -> Tuple[Optional[str], ...]:
        """Mesh-axis tuple for a logical layout, UNRESOLVED (no shape or
        mesh applied) — the ``(axis_or_None, ...)`` form the module
        sharding-rule tables and ``ShardingRules.spec_for`` trade in."""
        return tuple(self.mesh_axis(l) for l in logical_axes)

    def spec_for(self, logical_axes: Sequence[Optional[str]],
                 shape: Optional[Sequence[int]] = None,
                 mesh: Any = None) -> PartitionSpec:
        """Resolve a logical layout to a PartitionSpec against ``mesh``
        (default: the global mesh), applying the size-1 normalization,
        divisibility fallback, and duplicate-axis suppression documented
        in the module docstring."""
        if mesh is None and mesh_mod.has_mesh():
            mesh = mesh_mod.get_mesh()
        entries = self.spec_entries(logical_axes)
        if shape is not None and len(shape) != len(entries):
            raise ValueError(
                f"logical layout {tuple(logical_axes)} has "
                f"{len(entries)} axes but shape {tuple(shape)} has "
                f"{len(shape)} dims")
        return physical_spec(entries, shape=shape, mesh=mesh)

    def sharding_for(self, logical_axes: Sequence[Optional[str]],
                     shape: Optional[Sequence[int]] = None,
                     mesh: Any = None) -> NamedSharding:
        if mesh is None:
            mesh = mesh_mod.get_mesh()
        return NamedSharding(
            mesh, self.spec_for(logical_axes, shape=shape, mesh=mesh))


def physical_spec(entries: Sequence[Optional[str]],
                  shape: Optional[Sequence[int]] = None,
                  mesh: Any = None) -> PartitionSpec:
    """Guard a raw ``(axis_or_None, ...)`` placement into a spec that is
    always safe to commit: drop size-1 axes, drop axes that do not
    divide their dimension (when ``shape`` is known), never repeat a
    mesh axis. Shared by the rules table and the inference engine's
    parameter placement (AutoTP specs get the same divisibility guard)."""
    sizes = dict(getattr(mesh, "shape", None) or {}) if mesh is not None \
        else {}
    out = []
    used = set()
    for i, axis in enumerate(entries):
        if axis is None or axis in used:
            out.append(None)
            continue
        size = sizes.get(axis) if sizes else None
        if mesh is not None and size is None:
            out.append(None)          # axis absent from this mesh
            continue
        if size is not None and size <= 1:
            out.append(None)          # partitioning over 1 device =
            continue                  # replication; keep specs canonical
        if shape is not None and size is not None \
                and int(shape[i]) % int(size) != 0:
            out.append(None)          # t5x divisibility fallback
            continue
        out.append(axis)
        used.add(axis)
    while out and out[-1] is None:    # canonical: no trailing Nones, so
        out.pop()                     # P() == fully replicated compares
    return PartitionSpec(*out)        # equal across call sites


_DEFAULT_RULES: Optional[LogicalAxisRules] = None


def default_axis_rules() -> LogicalAxisRules:
    """The process-wide default table (validated once, cached)."""
    global _DEFAULT_RULES
    if _DEFAULT_RULES is None:
        _DEFAULT_RULES = LogicalAxisRules(DEFAULT_AXIS_RULES)
    return _DEFAULT_RULES


def cache_leaf_sharding(kind: str, mesh: Any = None,
                        rules: Optional[LogicalAxisRules] = None):
    """Per-leaf sharding resolver for a serving cache container —
    the callable form :class:`~..serving.slot_pool.SlotPool` /
    :class:`~..serving.paged_pool.PagedKVPool` accept through their
    ``sharding`` seam. ``kind`` is ``"stacked"`` or ``"paged"``; the
    returned ``fn(key, leaf) -> NamedSharding`` resolves that
    container's logical layout against ``leaf``'s actual shape, so
    indivisible dims (a 4-slot pool on a data=8 mesh) replicate instead
    of failing, and a TP=1 mesh resolves every leaf to the replicated
    placement the pools committed before this seam existed."""
    layouts = {"stacked": STACKED_CACHE_AXES,
               "paged": PAGED_CACHE_AXES}[kind]
    rules = rules if rules is not None else default_axis_rules()

    def leaf_sharding(key: str, leaf: Any) -> NamedSharding:
        m = mesh if mesh is not None else mesh_mod.get_mesh()
        axes = layouts.get(key)
        shape = getattr(leaf, "shape", None)
        if axes is None or shape is None or len(axes) != len(shape):
            return NamedSharding(m, PartitionSpec())
        return rules.sharding_for(axes, shape=shape, mesh=m)

    return leaf_sharding
