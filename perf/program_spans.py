"""The program's own spans, as the per-layer readers see them. The program
keeps a ring of its host spans (``deepspeed_tpu.telemetry.default_tracer``,
on in every run) on ``time.perf_counter``, which is also the harness's
clock; this file finds the benchmark's window in that ring.

* serve cell: the harness's ``bench/step`` spans (``record["spans"]``, in
  seconds from the opening of the window) are laid over the starts of the
  program's ``serving/step`` spans. Each ``srv.step()`` opens exactly one, a
  few microseconds after the harness's own mark, so one constant offset (the
  harness's ``t_open``) fits every step of the window, and no other run of
  steps as closely.
* train cell: the window's steps are the last ``facts["steps"]``
  ``train/step`` spans of the process.

A program without such a tracer or without these spans (a parent commit from
before PR 23) places no window: every reader then returns ``None``."""

from __future__ import annotations

from bisect import bisect_left
from typing import Dict, List, Optional, Sequence

import numpy as np

# a program step opens this close to the harness's own mark around it
MATCH_TOLERANCE_S = 2e-3
MATCH_SHARE = 0.9
# two runs of steps that fit this nearly alike cannot be told apart
MATCH_TIE_S = 1e-6
# two steps fit some pair of a long run by chance; a real window has ~135
MIN_STEPS = 3
DISPATCH_SPANS = ("serving/decode", "serving/verify_k",
                  "serving/prefill_chunk", "serving/prefill_batch",
                  "serving/admit", "train/dispatch")


def program_events() -> List[dict]:
    """The events of the program's process-wide tracer, or none."""
    try:
        from deepspeed_tpu.telemetry import default_tracer
    except ImportError:
        return []
    return default_tracer().events()


def spans(events: Sequence[dict], name: str) -> List[dict]:
    """The complete spans called ``name``: ``{"t0", "t1"`` (seconds on
    ``perf_counter``), ``"args"}``, by start."""
    out = [{"t0": e["ts"] / 1e9, "t1": (e["ts"] + e["dur"]) / 1e9,
            "args": e.get("args") or {}}
           for e in events if e.get("ph") == "X" and e["name"] == name]
    return sorted(out, key=lambda s: s["t0"])


def _match(bench_starts: Sequence[float], program_starts: Sequence[float]
           ) -> Optional[int]:
    """Index of the program step that is the window's first: the run of
    steps whose starts lie closest to a constant offset from the harness's.
    Steps of one length (a document cell's) fit a neighbouring run within
    the tolerance too, but only as well as the steps are alike: the true
    run fits to the microseconds between the harness's mark and the
    program's. Only a tie that cannot be told apart places no window."""
    b = np.asarray(bench_starts, np.float64)
    p = np.asarray(program_starts, np.float64)
    if len(b) < MIN_STEPS or len(p) < len(b):
        return None
    offset = np.lib.stride_tricks.sliding_window_view(p, len(b)) - b
    off = np.abs(offset - np.median(offset, axis=1, keepdims=True))
    misfit = off.mean(axis=1)
    k = int(np.argmin(misfit))
    if (off[k] < MATCH_TOLERANCE_S).mean() < MATCH_SHARE:
        return None
    others = np.delete(misfit, k)
    if len(others) and others.min() <= misfit[k] + MATCH_TIE_S:
        return None
    return k


def place_window(record: dict, events: Sequence[dict]) -> Optional[dict]:
    """``{"steps": the window's step spans, "open_s": when the window
    opened on the program's clock, "close_s"}``, or None."""
    bench = record.get("spans", {}).get("bench/step")
    if bench:
        steps = spans(events, "serving/step")
        k = _match([a for a, _ in bench], [s["t0"] for s in steps])
        if k is None:
            return None
        steps = steps[k:k + len(bench)]
        open_s = float(np.median([s["t0"] - a
                                  for s, (a, _) in zip(steps, bench)]))
        seconds = record.get("facts", {}).get("seconds")
        return {"steps": steps, "open_s": open_s,
                "close_s": open_s + seconds if seconds else steps[-1]["t1"]}
    n = record.get("facts", {}).get("steps")
    steps = spans(events, "train/step")
    if not n or len(steps) < n:
        return None
    steps = steps[-n:]
    return {"steps": steps, "open_s": steps[0]["t0"],
            "close_s": steps[-1]["t1"]}


def children(events: Sequence[dict], steps: Sequence[dict],
             names: Sequence[str]) -> List[Dict[str, List[dict]]]:
    """For each step, its spans of each of ``names``, by start."""
    by_name = {name: spans(events, name) for name in names}
    starts = {name: [s["t0"] for s in found]
              for name, found in by_name.items()}
    out = []
    for step in steps:
        inside = {}
        for name, found in by_name.items():
            i = bisect_left(starts[name], step["t0"])
            j = bisect_left(starts[name], step["t1"])
            inside[name] = found[i:j]
        out.append(inside)
    return out


def host_serial_ms(events: Sequence[dict], steps: Sequence[dict],
                   sync: str) -> List[float]:
    """For each pair of successive steps, the time nothing is queued on the
    device: end of the last ``sync`` span of step n to the start of the
    first dispatch span of step n + 1 (the caller's work between the two
    steps included)."""
    inside = children(events, steps, (sync,) + DISPATCH_SPANS)
    out = []
    for before, after in zip(inside, inside[1:]):
        dispatches = [s["t0"] for name in DISPATCH_SPANS
                      for s in after[name]]
        if before[sync] and dispatches:
            out.append((min(dispatches) - before[sync][-1]["t1"]) * 1e3)
    return out
