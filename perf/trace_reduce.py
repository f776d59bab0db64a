"""From a jax.profiler trace (xplane) to the facts the metrics read: device
busy and idle time, time by operation and by module, Pallas custom calls,
collectives and their exposed part, and idle gaps charged to what the host
was doing. Part of the yardstick: later PRs do not edit this file.

What the profiler calls things on a TPU v5e (looked at by hand in PR 22's
first traces):

* one plane per chip, ``/device:TPU:<n>``, with the lines ``XLA Modules``
  (one event per executed program, named ``jit_<fn>(<hash>)``), ``XLA Ops``
  (one event per executed HLO instruction, named by the instruction's whole
  text, ``%name = shape opcode(...)``; a ``while`` or ``call`` spans the
  instructions of its body, which lie on the same line inside it) and
  ``Async XLA Ops`` (one event from each ``-start`` to its ``-done``);
* one plane ``/host:CPU`` with a line per thread; a
  ``jax.profiler.TraceAnnotation`` is an event on its thread's line;
* device and host events share the trace's clock up to about a millisecond
  (the probe saw device events 1.1 ms before the host call that launched
  them), so a gap is charged to host spans only from 2 ms up.

The reducer works on a neutral form, ``{"planes": [{"name", "lines":
[{"name", "events": [[name, start_ns, duration_ns], ...]}]}]}``, which
``load_xplane`` makes from the file and which a test can write by hand.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
LINE_MODULES = "XLA Modules"
LINE_OPS = "XLA Ops"
LINE_ASYNC = "Async XLA Ops"

COLLECTIVE_OPCODES = ("all-gather", "all-reduce", "reduce-scatter",
                      "collective-permute", "all-to-all",
                      "collective-broadcast")
CONTAINER_OPCODES = ("while", "conditional", "call")
MIN_ATTRIBUTED_GAP_NS = 2_000_000
Interval = Tuple[float, float]


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------
def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return files[-1] if files else None


def load_xplane(path: str, host_prefix: str = "bench/") -> dict:
    """The neutral form of an .xplane.pb: every device line, and of the
    host lines only the events whose name starts with ``host_prefix`` (the
    benchmark's own annotations; the rest of the host plane is the
    runtime's internals and large)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_device = DEVICE_PLANE.match(plane.name) is not None
        if not is_device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if is_device and line.name not in (LINE_MODULES, LINE_OPS,
                                               LINE_ASYNC):
                continue
            events = [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                      for ev in line.events
                      if is_device or ev.name.startswith(host_prefix)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# ---------------------------------------------------------------------------
# names
# ---------------------------------------------------------------------------
def parse_op(text: str) -> Tuple[str, str, str]:
    """(name, opcode, shape) of an ``XLA Ops`` event, whose name is the
    instruction's text: ``%fusion.3 = bf16[8,128]{1,0:T(8,128)} fusion(...)``.
    A name that is not instruction text comes back whole with opcode ''."""
    if " = " not in text:
        return text.lstrip("%"), "", ""
    name, rest = text.split(" = ", 1)
    name = name.strip().lstrip("%")
    rest = rest.lstrip()
    if rest.startswith("("):                       # tuple shape
        depth, end = 0, 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                end = i + 1
                break
        shape, tail = rest[:end], rest[end:].lstrip()
    else:
        shape, _, tail = rest.partition(" ")
    opcode = tail.split("(", 1)[0].strip()
    return name, opcode, shape


def short_shape(shape: str) -> str:
    """``bf16[8,128]{1,0:T(8,128)}`` -> ``bf16[8,128]`` (layouts dropped)."""
    return re.sub(r"\{[^{}]*\}", "", shape)[:48]


def module_name(text: str) -> str:
    """``jit_fused_train_batch(123456)`` -> ``jit_fused_train_batch``."""
    return text.split("(", 1)[0]


def collective_kind(name: str, opcode: str) -> Optional[str]:
    """Which collective an instruction is, by its opcode (also the
    ``-start`` / ``-done`` halves, and fusions XLA names after the
    collective they wrap, e.g. ``all-reduce-scatter`` fusions)."""
    for kind in COLLECTIVE_OPCODES:
        if opcode.startswith(kind) or name.startswith(kind):
            return kind
    return None


def is_kernel_call(text: str, name: str, opcode: str,
                   duration_ns: float) -> bool:
    """A Pallas (Mosaic) kernel: a custom call whose target is
    ``tpu_custom_call``. XLA also leaves custom calls of its own in a TPU
    program's stream (one per buffer it sets aside: the first traced train
    step held 29 beside its four kernels, most of zero duration). Where the
    event's text does not carry the target, XLA's own are told by their
    default name, ``custom-call.N``; a Pallas call is named after the
    function that made it (``attn.31``, ``shard_map.411``)."""
    if opcode != "custom-call" or duration_ns <= 0:
        return False
    if "custom_call_target=" in text:
        return "tpu_custom_call" in text
    return not name.startswith("custom-call")


# ---------------------------------------------------------------------------
# intervals
# ---------------------------------------------------------------------------
def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(intervals: Sequence[Interval], holes: Sequence[Interval]
             ) -> List[Interval]:
    """The parts of ``intervals`` that no hole covers. Both are unions
    (sorted, disjoint), so one pass over each is enough."""
    out = []
    first = 0
    for a, b in intervals:
        while first < len(holes) and holes[first][1] <= a:
            first += 1
        cur, j = a, first
        while cur < b and j < len(holes) and holes[j][0] < b:
            h0, h1 = holes[j]
            if h0 > cur:
                out.append((cur, h0))
            cur = max(cur, h1)
            j += 1
        if cur < b:
            out.append((cur, b))
    return out


def self_times(events: Sequence[Sequence]) -> List[float]:
    """Duration of each event minus what the events nested inside it on
    the same line cover (a ``while`` spans its body's instructions)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    selfs = [float(e[2]) for e in events]
    stack: List[int] = []
    for i in order:
        start, end = events[i][1], events[i][1] + events[i][2]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            parent = stack[-1]
            p_end = events[parent][1] + events[parent][2]
            selfs[parent] -= max(0.0, min(end, p_end) - start)
        stack.append(i)
    return [max(0.0, s) for s in selfs]


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------
def _lines(plane: dict) -> Dict[str, list]:
    return {ln["name"]: ln["events"] for ln in plane["lines"]}


def _device_planes(trace: dict) -> List[Tuple[int, dict]]:
    found = []
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if m:
            found.append((int(m.group(1)), plane))
    return sorted(found, key=lambda p: p[0])


def _host_spans(trace: dict) -> List[Tuple[str, float, float]]:
    spans = []
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            spans.extend((e[0], e[1], e[1] + e[2]) for e in line["events"])
    return spans


def charge_gaps(gaps: Sequence[Interval],
                spans: Sequence[Tuple[str, float, float]],
                min_gap_ns: float = MIN_ATTRIBUTED_GAP_NS) -> Dict[str, float]:
    """Seconds of device idleness by what the host was doing. Every gap of
    at least ``min_gap_ns`` is cut at the edges of the host spans; each
    piece goes to the shortest span that covers it (the innermost), or to
    ``(no host span)``. Shorter gaps go to ``(gaps under 2 ms)``: below
    the agreement of the two clocks."""
    charged: Dict[str, float] = {}

    def add(name: str, ns: float) -> None:
        if ns > 0:
            charged[name] = charged.get(name, 0.0) + ns / 1e9

    for g0, g1 in gaps:
        if g1 - g0 < min_gap_ns:
            add("(gaps under 2 ms)", g1 - g0)
            continue
        near = [s for s in spans if s[2] > g0 and s[1] < g1]
        cuts = sorted({g0, g1, *(min(max(t, g0), g1)
                                 for s in near for t in (s[1], s[2]))})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            cover = [s for s in near if s[1] <= mid < s[2]]
            name = min(cover, key=lambda s: s[2] - s[1])[0] if cover \
                else "(no host span)"
            add(name, b - a)
    return charged


def reduce_trace(trace: dict, top: int = 10) -> Optional[dict]:
    """Reduce the neutral form to the record the per-layer readers use, or
    None when no device ran anything in the trace."""
    devices = _device_planes(trace)
    per_device = []
    for index, plane in devices:
        lines = _lines(plane)
        ops = lines.get(LINE_OPS, [])
        modules = lines.get(LINE_MODULES, [])
        if not ops and not modules:
            continue
        per_device.append((index, ops, modules, lines.get(LINE_ASYNC, [])))
    if not per_device:
        return None

    # the window: first program start to last program end on any device
    # (device clock), so ragged edges of the trace count as neither
    starts, ends = [], []
    for _, ops, modules, _ in per_device:
        for e in (modules or ops):
            starts.append(e[1])
            ends.append(e[1] + e[2])
    w0, w1 = min(starts), max(ends)
    window_ns = max(w1 - w0, 1.0)

    busy = []
    for _, ops, modules, _ in per_device:
        spans = union(clip(((e[1], e[1] + e[2]) for e in (ops or modules)),
                           w0, w1))
        busy.append(total(spans))

    # everything below: the lowest-numbered device that ran something
    _, ops, modules, async_ops = per_device[0]
    parsed = [parse_op(e[0]) for e in ops]
    selfs = self_times(ops)
    busy0 = union(clip(((e[1], e[1] + e[2]) for e in (ops or modules)),
                       w0, w1))

    by_op: Dict[str, float] = {}
    custom: Dict[str, dict] = {}
    coll_sync: List[Interval] = []
    compute: List[Interval] = []
    by_kind: Dict[str, float] = {}
    for e, (name, opcode, shape), self_ns in zip(ops, parsed, selfs):
        label = f"{name} {short_shape(shape)}".strip()
        by_op[label] = by_op.get(label, 0.0) + self_ns / 1e9
        kind = collective_kind(name, opcode)
        interval = (e[1], e[1] + e[2])
        if opcode in CONTAINER_OPCODES:
            continue
        if kind is not None:
            coll_sync.append(interval)
            if not opcode.endswith(("-start", "-done")):
                by_kind[kind] = by_kind.get(kind, 0.0) + e[2] / 1e9
            continue
        compute.append(interval)
        if is_kernel_call(e[0], name, opcode, e[2]):
            entry = custom.setdefault(name, {"count": 0, "total_s": 0.0,
                                             "shape": short_shape(shape)})
            entry["count"] += 1
            entry["total_s"] += e[2] / 1e9
    coll_async: List[Interval] = []
    for e in async_ops:
        name, opcode, _ = parse_op(e[0])
        kind = collective_kind(name, opcode)
        if kind is not None:
            coll_async.append((e[1], e[1] + e[2]))
            by_kind[kind] = by_kind.get(kind, 0.0) + e[2] / 1e9
    coll = union(clip(coll_sync + coll_async, w0, w1))
    exposed = subtract(coll, union(clip(compute, w0, w1)))

    by_module: Dict[str, dict] = {}
    for e in modules:
        entry = by_module.setdefault(module_name(e[0]),
                                     {"count": 0, "total_s": 0.0,
                                      "durations_ms": []})
        entry["count"] += 1
        entry["total_s"] += e[2] / 1e9
        entry["durations_ms"].append(e[2] / 1e6)

    gaps = subtract([(w0, w1)], busy0)
    charged = charge_gaps(gaps, _host_spans(trace))

    def top_list(table: Dict[str, float]) -> List[list]:
        return [[k, v] for k, v in sorted(table.items(),
                                          key=lambda kv: -kv[1])[:top]]

    return {
        "devices": len(per_device),
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "busy_s_per_device": [b / 1e9 for b in busy],
        "device0": {
            "busy_s": total(busy0) / 1e9,
            "ops": by_op,
            "modules": by_module,
            "custom_calls": custom,
            "custom_call_s": sum(c["total_s"] for c in custom.values()),
            "collective_s": total(coll) / 1e9,
            "collective_exposed_s": total(exposed) / 1e9,
            "collective_by_kind": by_kind,
            "idle_by_host_span": charged,
        },
        "breakdown": {"device_ops": top_list(by_op),
                      "idle_gaps": top_list(charged)},
    }
