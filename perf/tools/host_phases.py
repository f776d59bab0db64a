#!/usr/bin/env python3
"""Where the host's time goes in one cell: runs the cell once, traced, in a
session of its own, and lays the program's spans over the device.

    chiprun --chips 1 -- python3 perf/tools/host_phases.py \
        --workload serve-pythia-1b4-chat [--seed 9] [--seconds 30]

For the builder only. The benchmark's reducer keeps only the harness's
``bench/*`` annotations of the host plane; this tool loads the same xplane
with the program's prefixes too (``serving/``, ``train/``, ``setup/``), so a
gap of the device is charged to the innermost program span that covers it.
It prints:

1. the device's idle time by innermost host span, and the share of the idle
   time in gaps over 2 ms that a program span (not the harness's) accounts
   for;
2. the phases of the window's steps from the program's ring: count, median,
   99th percentile and self time of every span name;
3. the three slowest steps of the window with their phase split;
4. device time by ``jax.named_scope``, if the trace carries the operations'
   metadata, else a line that says it does not (on the v5e it does not, with
   the HLO proto on or off: PERF.md section 7).

``--rehearsal 1`` walks the same code on the CPU at toy sizes (no device
plane: parts 1 and 4 are empty). Everything printed is also written to
``chiprun_out/host_phases.<workload>.json``."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

PROGRAM_PREFIXES = ("serving/", "train/", "setup/")
HOST_PREFIXES = ("bench/",) + PROGRAM_PREFIXES
SCOPE_STATS = ("tf_op", "op_name", "hlo_op", "long_name", "name_scope")
NOT_A_SPAN = ("(no host span)", "(gaps under 2 ms)")


def keeping_trace(device, trace_reduce, kept: dict):
    """A DeviceTrace that, before the benchmark's reduction removes the
    files, loads the xplane with the program's prefixes and looks for
    operation metadata."""

    class KeepingTrace(device.DeviceTrace):
        def reduce(self):
            path = trace_reduce.find_xplane(self.trace_dir)
            if path is not None:
                kept["trace"] = trace_reduce.load_xplane(
                    path, host_prefix=HOST_PREFIXES)
                kept["scopes"] = scope_times(path, trace_reduce)
            return super().reduce()

    return KeepingTrace


def scope_times(path: str, trace_reduce):
    """Device seconds by named scope on the lowest-numbered chip, from
    whatever statistic of an ``XLA Ops`` event carries the operation's
    ``op_name``; None when no event carries one."""
    from jax.profiler import ProfileData

    planes = sorted((p for p in ProfileData.from_file(path).planes
                     if trace_reduce.DEVICE_PLANE.match(p.name)),
                    key=lambda p: int(
                        trace_reduce.DEVICE_PLANE.match(p.name).group(1)))
    for plane in planes:
        for line in plane.lines:
            if line.name != trace_reduce.LINE_OPS:
                continue
            by_scope, seen = {}, False
            for ev in line.events:
                stats = {k: v for k, v in ev.stats}
                op_name = next((str(stats[k]) for k in SCOPE_STATS
                                if k in stats), None)
                if op_name is None:
                    continue
                seen = True
                _, opcode, _ = trace_reduce.parse_op(ev.name)
                if opcode in trace_reduce.CONTAINER_OPCODES:
                    continue
                scope = "/".join(op_name.split("/")[1:-1]) or "(top)"
                by_scope[scope] = by_scope.get(scope, 0.0) \
                    + ev.duration_ns / 1e9
            return by_scope if seen else None
    return None


def phase_table(events, steps):
    """Per span name over the window's steps: count, median, p99 and total
    self time (its duration less the spans nested in it), milliseconds."""
    from perf import stats, trace_reduce

    lo, hi = steps[0]["t0"], steps[-1]["t1"]
    inside = [[e["name"], e["ts"] / 1e9, e["dur"] / 1e9] for e in events
              if e.get("ph") == "X" and e["name"].startswith(PROGRAM_PREFIXES)
              and lo <= e["ts"] / 1e9 and (e["ts"] + e["dur"]) / 1e9 <= hi]
    selfs = trace_reduce.self_times(inside)
    table = {}
    for (name, _, dur), self_s in zip(inside, selfs):
        row = table.setdefault(name, {"durs": [], "self_ms": 0.0})
        row["durs"].append(dur * 1e3)
        row["self_ms"] += self_s * 1e3
    return {name: {"count": len(row["durs"]),
                   "p50_ms": stats.median(row["durs"]),
                   "p99_ms": stats.percentile(row["durs"], 99),
                   "self_ms_total": row["self_ms"]}
            for name, row in sorted(table.items())}


def slowest_steps(events, steps, top: int = 3):
    from perf import program_spans

    names = sorted({e["name"] for e in events if e.get("ph") == "X"
                    and e["name"].startswith(PROGRAM_PREFIXES[:2])
                    and not e["name"].endswith("/step")})
    order = sorted(range(len(steps)),
                   key=lambda i: steps[i]["t0"] - steps[i]["t1"])[:top]
    kids = program_spans.children(events, steps, names)
    return [{"index_in_window": i,
             "ms": (steps[i]["t1"] - steps[i]["t0"]) * 1e3,
             "args": steps[i]["args"],
             "phases_ms": {n: sum(s["t1"] - s["t0"] for s in found) * 1e3
                           for n, found in kids[i].items() if found}}
            for i in order]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=9)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    if args.rehearsal:
        with open(os.path.join(ROOT, "perf", "cells",
                               f"{args.workload}.json")) as f:
            chips = json.load(f).get("rehearsal_chips", 1)
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}").strip()
    seconds = args.seconds
    if seconds is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            seconds = 3.0 if args.rehearsal else json.load(f)["run_seconds"]

    from perf import device, program_spans, trace_reduce
    from perf.run import run_cell

    kept = {}
    device.DeviceTrace = keeping_trace(device, trace_reduce, kept)
    result = run_cell(args.workload, args.seed, seconds, True,
                      rehearsal=bool(args.rehearsal))
    record = result.pop("_record")
    out = {"workload": args.workload, "seed": args.seed,
           "rehearsal": bool(args.rehearsal), "correct": result["correct"],
           "failures": record["failures"],
           "metrics": result.get("metrics", {})}

    reduced = trace_reduce.reduce_trace(kept["trace"]) \
        if kept.get("trace") else None
    if reduced is not None:
        idle = reduced["device0"]["idle_by_host_span"]
        over = {k: v for k, v in idle.items() if k != "(gaps under 2 ms)"}
        program = sum(v for k, v in over.items()
                      if k.startswith(PROGRAM_PREFIXES))
        out["idle"] = {
            "window_s": reduced["window_s"], "busy_s": reduced["busy_s"],
            "idle_share": 1.0 - reduced["busy_s"] / reduced["window_s"],
            "by_innermost_span_s": dict(sorted(idle.items(),
                                               key=lambda kv: -kv[1])),
            "over_2ms_s": sum(over.values()),
            "over_2ms_share_on_program_spans":
                program / sum(over.values()) if over else None}
        out["custom_calls"] = reduced["device0"]["custom_calls"]
    out["device_time_by_named_scope_s"] = kept.get("scopes") or \
        "the xplane carries no operation metadata: no scope split"

    events = program_spans.program_events()
    window = program_spans.place_window(record, events)
    if window is not None:
        out["steps_in_window"] = len(window["steps"])
        out["phases"] = phase_table(events, window["steps"])
        out["slowest_steps"] = slowest_steps(events, window["steps"])
    out["setup"] = [
        {"name": e["name"], "s": e["dur"] / 1e9,
         **{k: v for k, v in (e.get("args") or {}).items()
            if k in ("program", "cache", "entry", "parameters",
                     "bytes_placed")}}
        for e in events if e["name"].startswith("setup/")
        and e["dur"] >= 0.05e9]
    tracer_totals = None
    try:
        from deepspeed_tpu.telemetry import default_tracer

        tracer_totals = {"events_total": default_tracer().events_total,
                         "dropped": default_tracer().dropped}
    except ImportError:
        pass
    out["ring"] = tracer_totals

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"host_phases.{args.workload}.json"), "w") as f:
        json.dump(out, f, indent=1, default=str)
    print(json.dumps(out, indent=1, default=str))
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
