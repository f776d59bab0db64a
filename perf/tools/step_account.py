#!/usr/bin/env python3
"""The serving step's account of one cell, printed: runs the cell once and
splits the host time the chip waits through into its named parts.

    chiprun --chips 1 -- python3 perf/tools/step_account.py \
        --workload serve-pythia-1b4-docs [--seed 9] [--trace 1]

For the builder only (``perf/STEP_ACCOUNT.md``). From the program's ring,
over the steps of the window (``perf/step_account.py``):

1. ``exposed`` (end of the last sync of step n to the end of the first
   ``program`` enqueue of step n + 1) and its parts, each a median and a
   mean over the steps that have one: the replay and the after-step of step
   n, the caller's time between the two steps, then boundary, grant, and of
   step n + 1 up to that enqueue's end: pages, prepare (inside a dispatch
   span, outside its enqueue children), the enqueue spans queued before it
   (none on the shipped tree: the puts and eager operations are counted
   and leave no span), the program's own call, and whatever lies under no
   span at all (a decode's inputs, put before its span opens);
2. the self time of ``serving/step``: the step less its top-level spans;
3. the enqueue spans by ``program`` and ``kind``: calls a step, median and
   mean duration, and the attributes the dispatch spans carry
   (``pool_writes``, ``pool_reads``, ``read_slots``, ``state_rows``: mean);
4. traced (``--trace 1``): the device's idle time a step of the traced
   stretch beside the stretch's mean ``exposed``, i.e. what
   ``step_idle_unnamed_ms`` subtracts from what, and the stretch's steps
   (how long, how many with a chunk);
5. the ring events the window held; the full collections (``host/gc``)
   that fell in it.

The run's result line (what ``perf/run.py`` prints) is printed last.
``--rehearsal 1`` walks the same code on the CPU at toy sizes. Everything
printed is also written to ``chiprun_out/step_account.<workload>.json``."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

HOST_PHASES = ("serving/boundary", "serving/grant", "serving/pages",
               "serving/replay", "serving/after_step")
ATTRIBUTES = ("pool_writes", "pool_reads", "read_slots", "state_rows",
              "allocated", "forked", "preempted")


def _dur(spans) -> float:
    return sum(s["t1"] - s["t0"] for s in spans)


def _clip(spans, t_end: float) -> float:
    """Seconds of ``spans`` that lie before ``t_end``."""
    return sum(max(0.0, min(s["t1"], t_end) - s["t0"]) for s in spans)


def summary(values) -> dict:
    from perf import stats

    values = [v for v in values if v is not None]
    if not values:
        return {"n": 0}
    return {"n": len(values), "median": stats.median(values),
            "mean": sum(values) / len(values),
            "p99": stats.percentile(values, 99)}


def exposed_parts(events, steps, step_account, program_spans) -> dict:
    """The parts of ``exposed`` for each pair of successive steps, in ms."""
    names = (step_account.ENQUEUE, step_account.SYNC) + HOST_PHASES \
        + step_account.PREPARE_SPANS
    inside = program_spans.children(events, steps, names)
    parts = {k: [] for k in (
        "exposed", "replay", "after_step", "caller", "boundary", "grant",
        "pages", "prepare", "queued_before", "first_program", "unspanned")}
    for (before, b), (step, c) in zip(zip(steps, inside),
                                      zip(steps[1:], inside[1:])):
        programs = [e for e in c[step_account.ENQUEUE]
                    if e["args"].get("kind") == "program"]
        if not b[step_account.SYNC] or not programs:
            continue
        t0, first = b[step_account.SYNC][-1]["t1"], programs[0]
        t1 = first["t1"]
        row = {
            "exposed": t1 - t0,
            "replay": _dur(s for s in b["serving/replay"] if s["t0"] >= t0),
            "after_step": _dur(b["serving/after_step"]),
            "caller": step["t0"] - before["t1"],
            "boundary": _clip(c["serving/boundary"], t1),
            "grant": _clip(c["serving/grant"], t1),
            "first_program": first["t1"] - first["t0"],
        }
        queued = [e for e in c[step_account.ENQUEUE]
                  if e["t1"] <= first["t0"]]
        row["queued_before"] = _dur(queued)
        in_pages = sum(_dur(e for e in queued if step_account.inside(p, e))
                       for p in c["serving/pages"])
        row["pages"] = _clip(c["serving/pages"], t1) - in_pages
        top = step_account.top_level(
            [s for n in step_account.PREPARE_SPANS for s in c[n]])
        in_prepare = sum(_dur(e for e in queued + [first]
                              if step_account.inside(s, e)) for s in top)
        row["prepare"] = _clip(top, t1) - in_prepare
        row["unspanned"] = row["exposed"] - sum(
            v for k, v in row.items() if k != "exposed")
        for k, v in row.items():
            parts[k].append(v * 1e3)
    return {k: summary(v) for k, v in parts.items()}


def self_time_us(events, steps, step_account, program_spans) -> dict:
    """``serving/step`` less the spans directly under it, microseconds:
    in all, and by the two spans each stretch of it lies between."""
    names = sorted({e["name"] for e in events if e.get("ph") == "X"
                    and e["name"].startswith(("serving/", "host/"))
                    and e["name"] != "serving/step"})
    inside = program_spans.children(events, steps, names)
    out, between = [], {}
    for step, kids in zip(steps, inside):
        top = step_account.top_level(
            [dict(s, name=n) for n in names for s in kids[n]])
        out.append((step["t1"] - step["t0"] - _dur(top)) * 1e6)
        edges = [{"name": "(open)", "t1": step["t0"]}] + top \
            + [{"name": "(close)", "t0": step["t1"]}]
        for a, b in zip(edges, edges[1:]):
            key = f"{a['name']} > {b['name']}"
            between.setdefault(key, []).append((b["t0"] - a["t1"]) * 1e6)
    total = dict(summary(out))
    total["between_us"] = {
        k: {"n": len(v), "median": summary(v)["median"],
            "a_step": sum(v) / max(len(steps), 1)}
        for k, v in sorted(between.items(),
                           key=lambda kv: -sum(kv[1]))[:12]}
    return total


def enqueue_table(events, steps, step_account, program_spans) -> dict:
    inside = program_spans.children(
        events, steps, (step_account.ENQUEUE,) + step_account.PREPARE_SPANS
        + ("serving/pages",))
    table, attrs = {}, {}
    for kids in inside:
        for e in kids[step_account.ENQUEUE]:
            key = f"{e['args'].get('program')}:{e['args'].get('kind')}"
            table.setdefault(key, []).append((e["t1"] - e["t0"]) * 1e6)
        for name in step_account.PREPARE_SPANS + ("serving/pages",):
            for s in kids[name]:
                for a in ATTRIBUTES:
                    if a in s["args"]:
                        attrs.setdefault(f"{name}.{a}", []).append(
                            s["args"][a])
    n = max(len(steps), 1)
    return {
        "by_program_us": {k: dict(summary(v), calls_a_step=len(v) / n)
                          for k, v in sorted(table.items())},
        "span_attributes_mean": {k: sum(v) / len(v)
                                 for k, v in sorted(attrs.items())},
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=9)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
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

    from perf import program_spans, step_account
    from perf.run import run_cell

    result = run_cell(args.workload, args.seed, seconds, bool(args.trace),
                      rehearsal=bool(args.rehearsal))
    record = result.pop("_record")
    events = program_spans.program_events()
    out = {"workload": args.workload, "seed": args.seed,
           "rehearsal": bool(args.rehearsal), "correct": result["correct"],
           "failures": record["failures"]}
    window = program_spans.place_window(record, events)
    rows = step_account.window_rows(record, events)
    if window is None or rows is None:
        out["account"] = None       # no window, or a ring with no enqueue
    else:
        steps = window["steps"]
        out["steps"] = len(steps)
        out["exposed_parts_ms"] = exposed_parts(events, steps, step_account,
                                                program_spans)
        out["step_self_us"] = self_time_us(events, steps, step_account,
                                           program_spans)
        out["per_step"] = {
            k: summary([r[k] for r in rows])
            for k in ("exposed_ms", "enqueue_ms", "prepare_ms",
                      "device_calls")}
        out["step_ms"] = summary([(s["t1"] - s["t0"]) * 1e3 for s in steps])
        out.update(enqueue_table(events, steps, step_account,
                                 program_spans))
        interior = step_account.traced_interior(rows)
        trace = record.get("trace")
        if interior is not None and trace:
            gaps = len(interior) + 1
            out["traced_stretch"] = {
                "steps_flagged": gaps + 1, "interior": len(interior),
                "window_s": trace["window_s"], "busy_s": trace["busy_s"],
                "idle_ms_a_step": (trace["window_s"] - trace["busy_s"])
                * 1e3 / gaps,
                "exposed_ms_mean": sum(r["exposed_ms"] or 0.0
                                       for r in interior) / len(interior),
                "step_ms": summary([
                    (r["step"]["t1"] - r["step"]["t0"]) * 1e3
                    for r in interior]),
                # what an unnamed part over a quarter is held against
                "chunk_steps": sum(1 for r in interior
                                   if r["step"]["args"].get("chunk")),
                "without_exposed": sum(1 for r in interior
                                       if r["exposed_ms"] is None),
                "exposed_ms": summary([r["exposed_ms"] for r in interior]),
            }
        lo, hi = window["open_s"], window["close_s"]
        # what the window asks of the ring (telemetry/tracer.py sizes the
        # process-wide one by it): events between its first and last step
        held = sum(1 for e in events
                   if steps[0]["t0"] <= e["ts"] / 1e9 <= steps[-1]["t1"])
        from deepspeed_tpu.telemetry import default_tracer
        out["ring"] = {"events_in_window": held,
                       "events_a_step": held / len(steps),
                       "capacity": default_tracer().capacity,
                       "dropped": default_tracer().dropped}
        out["full_collections_ms"] = [
            round((s["t1"] - s["t0"]) * 1e3, 2)
            for s in program_spans.spans(events, "host/gc")
            if lo <= s["t0"] < hi]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out",
                        f"step_account.{args.workload}.json")
    with open(path, "w") as f:
        json.dump(dict(out, result=result), f, indent=1, default=str)
    print(json.dumps(out, indent=1, default=str))
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
