"""XLA program cost model: flops/bytes per executable, live MFU and
bandwidth-utilization gauges, and KV-HBM reconciliation.

DeepSpeed ships a flops profiler that walks modules and counts MACs;
on JAX the compiler already knows — ``lowered.compile()`` exposes
``cost_analysis()`` (flops, bytes accessed) and ``memory_analysis()``
(argument/output/temp bytes) for every executable. This module
harvests those numbers once per ``(program, signature)`` through the
PR-5 ``_WatchedJit`` seam and charges them to the serving step loop on
every call, which turns wall-clock spans into hardware-relative
efficiency:

* ``MFU``            = flops executed / wall / device peak flops
* ``bandwidth_util`` = bytes accessed / wall / device peak HBM BW
* ``tokens_per_gflop`` = emitted tokens / (flops / 1e9)

Harvesting is best-effort: ``cost_analysis`` coverage varies by
backend (PJRT plugins may return nothing), so failures record a
``telemetry/cost_model_unavailable`` gauge and the affected program
simply contributes zero — the serving loop itself is never perturbed
(a CPU test pins bit-identical outputs with the model on vs off).

The AOT harvest compiles the (already warm) program out-of-band, so it
runs under :func:`~.watchdog.suppress_compile_events` to stay invisible
to the recompile watchdog, and lowers against ``ShapeDtypeStruct``
avals so donated buffers are never touched.

KV-HBM reconciliation: :func:`kv_hbm_report` computes the
model-predicted KV footprint from ``KVCacheSpec`` math (paged:
``num_pages x page_bytes``; contiguous: ``num_slots x max_seq_len``
rows) and diffs it against the pool's actual device array bytes plus
``get_accelerator().memory_stats()``. Drift beyond tolerance emits a
``telemetry/hbm_drift`` monitor event — the canary for a pool layout
change silently inflating the cache.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional, Tuple

import numpy as np

from .watchdog import fast_key, suppress_compile_events

try:  # pragma: no cover - jax is always present in this repo
    import jax
except Exception:  # pragma: no cover
    jax = None

# (peak_flops, peak_bytes_per_s) keyed by the exact ``device_kind`` JAX
# reports (the strings of ``jax.experimental.mesh_utils``). Dense bf16
# peaks and HBM bandwidth from the public Cloud TPU system specs; a
# v5e reports itself as "TPU v5 lite" (chip_smoke.py prints the string).
_DEVICE_PEAKS: Dict[str, Tuple[float, float]] = {
    "TPU v2": (46e12, 700e9),
    "TPU v3": (123e12, 900e9),
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
    "TPU v5": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),
}
# the CPU has no published peak: a nominal figure keeps MFU a nonzero,
# host-comparable ratio in the tests; it is never a device metric
_NOMINAL_CPU_PEAKS = (1e12, 1e11)

_MISSING = object()


def _mesh_device_count() -> int:
    """Devices participating in the active global mesh (1 when no mesh
    is set — the single-chip default)."""
    try:
        from ..parallel import mesh as mesh_mod
        if mesh_mod.has_mesh():
            return int(mesh_mod.get_mesh().devices.size)
    except Exception:
        pass
    return 1


def resolve_peaks(device=None) -> Tuple[float, float]:
    """(peak_flops, peak_bytes_per_s) for ``device`` (default: the first
    local device). The CPU resolves to a nominal figure; an accelerator
    whose ``device_kind`` is not in the table raises — an invented peak
    would make every utilization gauge on it wrong by its ratio."""
    dev = device if device is not None else jax.devices()[0]
    if dev.platform == "cpu":
        return _NOMINAL_CPU_PEAKS
    kind = str(dev.device_kind)
    if kind not in _DEVICE_PEAKS:
        raise ValueError(
            f"no published peak for device_kind {kind!r} (platform "
            f"{dev.platform!r}); add it to telemetry/costs.py "
            f"_DEVICE_PEAKS with its source — known: "
            f"{sorted(_DEVICE_PEAKS)}")
    return _DEVICE_PEAKS[kind]


def _abstract(x: Any) -> Any:
    """Array → ShapeDtypeStruct (sharding-preserving when possible) so
    lowering for harvest never reads — or resurrects — real buffers."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None or jax is None:
        return x
    sharding = getattr(x, "sharding", None)
    if sharding is not None:
        try:
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        except Exception:
            pass
    return jax.ShapeDtypeStruct(shape, dtype)


def _abstract_plain(x: Any) -> Any:
    """Placement-free twin of :func:`_abstract`: shape/dtype only. The
    live dispatch lets jit place uncommitted (host-staged) inputs next
    to committed params, but sharding-preserving avals freeze that mix
    into an inconsistent placement AOT lowering rejects — stripping
    placement entirely lowers the same program for costing purposes."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None or jax is None:
        return x
    return jax.ShapeDtypeStruct(shape, dtype)


class ProgramCostModel:
    """Per-``(program, fast signature)`` flops/bytes registry with
    running totals and per-step window gauges.

    Subscribed to ``_WatchedJit`` proxies (via
    ``RecompileWatchdog.attach``); every proxied call lands in
    :meth:`account`, which lazily harvests unknown signatures — so a
    model attached to already-warm programs still gets costed on first
    use, paying one suppressed AOT compile per signature.
    """

    def __init__(self, registry=None, peak_flops: Optional[float] = None,
                 peak_bytes_per_s: Optional[float] = None,
                 hbm_tolerance: float = 0.01, kv_every: int = 16,
                 num_devices: Optional[int] = None):
        pf, pb = resolve_peaks()
        # normalize utilization by the mesh, not one chip: cost_analysis
        # reports WHOLE-program flops/bytes, so on a sharded mesh the
        # denominator is nominal-peak × participating devices — a TP=4
        # run reporting single-chip MFU > 1.0 was the bug this fixes.
        # Explicit peak_flops/peak_bytes_per_s overrides are taken as
        # ALREADY aggregate (callers passing a measured system peak).
        if num_devices is None:
            num_devices = _mesh_device_count()
        self.num_devices = max(1, int(num_devices))
        self.peak_flops = (float(peak_flops) if peak_flops
                           else pf * self.num_devices)
        self.peak_bytes_per_s = (float(peak_bytes_per_s)
                                 if peak_bytes_per_s
                                 else pb * self.num_devices)
        self.hbm_tolerance = float(hbm_tolerance)
        # KV reconciliation cadence in steps (drift is a slow leak, not
        # a per-step event; pull paths always reconcile fresh)
        self.kv_every = max(1, int(kv_every))
        self.registry = registry
        self._handles: Optional[Tuple[Any, ...]] = None  # cached metrics
        # (program, fast key) -> cost dict, or None when harvest failed
        self.programs: Dict[Tuple[str, Any], Optional[Dict[str, float]]] = {}
        self.flops_total = 0.0
        self.bytes_total = 0.0
        self.calls_total = 0
        self.uncosted_calls = 0
        self.harvests = 0
        self.unavailable = 0
        self.wall_total_s = 0.0
        self.tokens_total = 0
        # instrumentation self-accounting (the <=3% overhead budget);
        # one-time harvest compiles are tracked separately from the
        # steady-state per-call cost
        self.overhead_ns = 0
        self.harvest_ns = 0
        # window (since last step_update) accumulators and live gauges
        self._win_flops = 0.0
        self._win_bytes = 0.0
        self.mfu = 0.0
        self.bandwidth_util = 0.0
        self.tokens_per_gflop = 0.0
        self.hbm: Dict[str, float] = {}
        self._hbm_drifted = False

    # -- per-call accounting (hot path) --------------------------------
    def account(self, program: str, fn, args, kwargs) -> None:
        t0 = time.perf_counter_ns()
        key = (program, fast_key(args, kwargs))
        cost = self.programs.get(key, _MISSING)
        if cost is _MISSING:
            self.overhead_ns += time.perf_counter_ns() - t0
            cost = self._harvest(key, fn, args, kwargs)
            t0 = time.perf_counter_ns()
        self.calls_total += 1
        if cost is not None:
            self._win_flops += cost["flops"]
            self._win_bytes += cost["bytes"]
        else:
            self.uncosted_calls += 1
        self.overhead_ns += time.perf_counter_ns() - t0

    # -- harvest (cold path, once per signature) -----------------------
    def _harvest(self, key, fn, args, kwargs) -> Optional[Dict[str, float]]:
        t0 = time.perf_counter_ns()
        cost: Optional[Dict[str, float]] = None
        try:
            aargs, akwargs = jax.tree_util.tree_map(_abstract,
                                                    (args, kwargs))
            with suppress_compile_events():
                try:
                    compiled = fn.lower(*aargs, **akwargs).compile()
                except Exception:
                    # mixed committed/uncommitted inputs (replicated
                    # params + a host-staged token pinned to one device)
                    # lower fine live but not as frozen avals; retry
                    # with placement stripped
                    aargs, akwargs = jax.tree_util.tree_map(
                        _abstract_plain, (args, kwargs))
                    compiled = fn.lower(*aargs, **akwargs).compile()
                ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            ca = dict(ca or {})
            cost = {"flops": max(0.0, float(ca.get("flops", 0.0))),
                    "bytes": max(0.0, float(ca.get("bytes accessed", 0.0)))}
            try:
                ma = compiled.memory_analysis()
                arg_b = float(getattr(ma, "argument_size_in_bytes", 0) or 0)
                out_b = float(getattr(ma, "output_size_in_bytes", 0) or 0)
                tmp_b = float(getattr(ma, "temp_size_in_bytes", 0) or 0)
                peak = getattr(ma, "peak_memory_in_bytes", None)
                if peak is None:
                    # CPU backend reports no peak; arg+out+temp is the
                    # standard upper-bound proxy
                    peak = arg_b + out_b + tmp_b
                cost.update(arg_bytes=arg_b, output_bytes=out_b,
                            temp_bytes=tmp_b, peak_bytes=float(peak))
            except Exception:
                pass
            self.harvests += 1
        except Exception:
            # best-effort across backends: some PJRT plugins implement
            # neither AOT lowering nor cost_analysis for every program
            cost = None
            self.unavailable += 1
            if self.registry is not None:
                self.registry.gauge(
                    "telemetry/cost_model_unavailable").set(self.unavailable)
        self.programs[key] = cost
        self.harvest_ns += time.perf_counter_ns() - t0
        return cost

    # -- per-step gauges -----------------------------------------------
    def step_update(self, wall_s: float, tokens: int = 0) -> None:
        """Fold the window's flops/bytes into gauges against ``wall_s``
        (the step's span duration). Called once per serving step."""
        f, b = self._win_flops, self._win_bytes
        self._win_flops = 0.0
        self._win_bytes = 0.0
        self.wall_total_s += wall_s
        self.tokens_total += int(tokens)
        self.flops_total += f
        self.bytes_total += b
        if wall_s > 0:
            self.mfu = f / wall_s / self.peak_flops
            self.bandwidth_util = b / wall_s / self.peak_bytes_per_s
        self.tokens_per_gflop = tokens / (f / 1e9) if f > 0 else 0.0
        if self.registry is not None:
            if self._handles is None:
                # resolve the metric objects once: registry lookups take
                # a lock each, too dear for 5 of them per serving step
                g, c = self.registry.gauge, self.registry.counter
                self._handles = (g("telemetry/mfu"),
                                 g("telemetry/bandwidth_util"),
                                 g("telemetry/tokens_per_gflop"),
                                 c("telemetry/flops_total"),
                                 c("telemetry/bytes_accessed_total"))
            h = self._handles
            h[0].set(self.mfu)
            h[1].set(self.bandwidth_util)
            h[2].set(self.tokens_per_gflop)
            h[3].inc(f)
            h[4].inc(b)

    # -- KV HBM reconciliation -----------------------------------------
    def reconcile_kv(self, pool, monitor=None, step: int = 0,
                     tracer=None) -> Dict[str, float]:
        """Diff model-predicted KV bytes against the pool's device
        arrays (+ accelerator memory stats); emit ``telemetry/hbm_drift``
        on a tolerance-crossing transition. The serving loop calls this
        every ``kv_every`` steps; pull paths (``efficiency_snapshot``)
        call it directly for a fresh reading."""
        rep = kv_hbm_report(pool)
        rep.update(device_memory_report())
        if not rep.get("hbm_peak_bytes"):
            # CPU runtimes report no allocator stats; the KV pool is the
            # allocation this layer tracks, so fall back to its size
            rep["hbm_peak_bytes"] = rep["kv_bytes_actual"]
        drifted = rep["hbm_drift"] > self.hbm_tolerance
        if self.registry is not None:
            g = self.registry.gauge
            g("telemetry/kv_bytes_predicted").set(rep["kv_bytes_predicted"])
            g("telemetry/kv_bytes_actual").set(rep["kv_bytes_actual"])
            g("telemetry/hbm_drift").set(rep["hbm_drift"])
            g("telemetry/hbm_peak_bytes").set(rep["hbm_peak_bytes"])
        if drifted and not self._hbm_drifted:
            if tracer is not None:
                tracer.instant("telemetry/hbm_drift", **rep)
            if monitor is not None and getattr(monitor, "enabled", False):
                monitor.write_events([
                    ("telemetry/hbm_drift", rep["hbm_drift"], int(step))])
        self._hbm_drifted = drifted
        self.hbm = rep
        return rep

    # -- lifecycle -----------------------------------------------------
    def reset_totals(self) -> None:
        """Zero the running totals (keep harvested program costs) so a
        bench can measure a clean window after warmup."""
        self.flops_total = 0.0
        self.bytes_total = 0.0
        self.calls_total = 0
        self.uncosted_calls = 0
        self.wall_total_s = 0.0
        self.tokens_total = 0
        self.overhead_ns = 0
        self._win_flops = 0.0
        self._win_bytes = 0.0

    @property
    def overhead_s(self) -> float:
        return self.overhead_ns / 1e9

    def summary(self) -> Dict[str, Any]:
        wall = self.wall_total_s
        flops, byts = self.flops_total, self.bytes_total
        return {
            "programs": len(self.programs),
            "harvests": self.harvests,
            "unavailable": self.unavailable,
            "calls_total": self.calls_total,
            "uncosted_calls": self.uncosted_calls,
            "flops_total": flops,
            "bytes_accessed_total": byts,
            "tokens_total": self.tokens_total,
            "wall_s": wall,
            "mfu": flops / wall / self.peak_flops if wall > 0 else 0.0,
            "bandwidth_util": (byts / wall / self.peak_bytes_per_s
                               if wall > 0 else 0.0),
            "tokens_per_gflop": (self.tokens_total / (flops / 1e9)
                                 if flops > 0 else 0.0),
            "peak_flops": self.peak_flops,
            "peak_bytes_per_s": self.peak_bytes_per_s,
            "num_devices": self.num_devices,
            "overhead_s": self.overhead_s,
            "harvest_s": self.harvest_ns / 1e9,
            "hbm": dict(self.hbm),
        }


# ----------------------------------------------------------------------
# KV HBM math
# ----------------------------------------------------------------------
_KV_LEAVES = ("k", "v", "k_scale", "v_scale")


def kv_hbm_report(pool) -> Dict[str, float]:
    """Predicted vs actual KV-cache bytes for a Slot/PagedKV pool.

    Predicted comes from ``KVCacheSpec`` math alone (never from array
    shapes): per-token bytes x capacity tokens, where capacity is
    ``num_pages x page_size`` for the paged pool (stored in whole
    128-lane tiles, ``page_lanes``: a 64-wide page takes the bytes of
    128 columns) and ``num_slots x max_seq_len`` for contiguous rows.
    Actual sums
    ``.nbytes`` over the pool's k/v (+ scale) device leaves — the
    ``index``/``table`` bookkeeping arrays are not KV storage and are
    excluded from both sides, so a healthy pool reports drift 0.0.
    """
    spec = pool.spec
    item = np.dtype(spec.dtype).itemsize
    per_token = spec.n_layer * spec.kv_heads * spec.cache_d * 2 * item
    if spec.quantized:
        per_token += spec.n_layer * spec.kv_heads * 2 * 4  # f32 scales
    paged = hasattr(pool, "num_pages")
    if paged:
        from ..models.kv_cache_spec import page_lanes

        tokens = pool.num_pages * pool.page_size
        page_bytes = per_token * page_lanes(pool.page_size)
        predicted = float(page_bytes * pool.num_pages)
    else:
        tokens = pool.num_slots * spec.max_seq_len
        page_bytes = 0.0
        predicted = float(per_token * tokens)
    cs = pool.cache.get("cache_store", {})
    actual = 0.0
    for leaf_name in _KV_LEAVES:
        leaf = cs.get(leaf_name)
        if leaf is not None:
            actual += float(leaf.nbytes)
    drift = abs(actual - predicted) / predicted if predicted > 0 else 0.0
    rep = {
        "kv_bytes_predicted": predicted,
        "kv_bytes_actual": actual,
        "kv_bytes_per_token": float(per_token),
        "kv_capacity_tokens": float(tokens),
        "hbm_drift": drift,
        "layout": "paged" if paged else "contiguous",
    }
    if paged:
        rep["pages_total"] = float(pool.num_pages)
        rep["page_bytes"] = float(page_bytes)
    return rep


def device_memory_report() -> Dict[str, float]:
    """Accelerator allocator stats (empty dict values → 0 on CPU)."""
    stats: Dict[str, Any] = {}
    try:
        from ..accelerator import get_accelerator
        stats = get_accelerator().memory_stats() or {}
    except Exception:
        pass
    return {
        "hbm_bytes_in_use": float(stats.get("bytes_in_use", 0) or 0),
        "hbm_peak_bytes": float(stats.get("peak_bytes_in_use", 0) or 0),
        "hbm_bytes_limit": float(stats.get("bytes_limit", 0) or 0),
    }
