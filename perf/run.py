#!/usr/bin/env python3
"""perf/run.py: runs ONE cell of BENCHMARK.json ONCE, on the machine it is
started on, and prints one JSON object as the last line of its output.

    python3 perf/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic, splits and metrics are data files under
perf/ found by the names in BENCHMARK.json (perf/README.md). With --trace 0
the line carries the cell's end-to-end metrics, taken on the host clock with
the profiler off; with --trace 1 its per-layer metrics, from the program's
counters, the harness's own spans and a profiler trace of a steady part of
the window. With no TPU, or fewer chips than the cell asks for, it exits
non-zero within seconds and prints no result."""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

PERF_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PERF_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def assemble_result(manifest, workload: str, record: dict, found: dict,
                    traced: bool, rehearsal: bool = False) -> dict:
    """The object of the last line: ``correct``, ``attempted``, ``failed``,
    ``metrics``, ``device`` and, traced, ``breakdown``; no other key. With
    ``traced`` the metrics are the cell's per-layer metrics, else its
    end-to-end metrics."""
    dev = {"platform": found["platform"], "kind": found["kind"],
           "count": found["count"],
           "memory_peak_bytes": record["memory_peak_bytes"]}
    result = {"correct": not record["failures"],
              "attempted": int(record["attempted"]),
              "failed": int(record["failed"])}
    if traced:
        reduced = record.get("trace")
        if reduced is None and not rehearsal:
            # (the CPU of a rehearsal has no device plane to reduce)
            record["failures"].append("the traced window holds no device "
                                      "operation")
            result["correct"] = False
        elif reduced is not None:
            dev["busy_s"] = reduced["busy_s"]
            dev["window_s"] = reduced["window_s"]
            result["breakdown"] = reduced["breakdown"]
        result["metrics"] = manifest.read_layer_metrics(workload, record)
    else:
        values = dict(record["end_to_end"], setup_s=record["setup_s"])
        result["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in manifest.metrics_for(workload, "end_to_end")}
    result["device"] = dev
    return result


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearsal: bool = False, t_start: float = T_PROCESS_START,
             override: dict = None) -> dict:
    """Run the cell and return the result object (and, under the key
    ``_record``, everything the run recorded, for the tools)."""
    from perf import device
    from perf.manifest import Manifest

    manifest = Manifest(ROOT)
    entry = manifest.workload(workload)
    config = manifest.config(entry["config"])
    traffic = manifest.traffic(entry["traffic"])
    cell = manifest.cell(workload)
    if override:            # perf/tools/find_knee.py only: no flag of the
        #                     command reaches this
        cell = _merge(cell, override.get("cell", {}))
        traffic = _merge(traffic, override.get("traffic", {}))
        config = _merge(config, override.get("config", {}))
    chips = cell.get("rehearsal_chips", 1) if rehearsal \
        else int(entry["chips"])

    found = device.open_device(chips, rehearsal)
    peaks = None if rehearsal else device.peaks_for(found["kind"],
                                                    manifest.peaks())
    import jax

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    if not rehearsal:       # a CPU rehearsal leaves nothing in the cache
        enable_compile_cache()
    setup, marks = {}, {}
    ctx = {
        "mark": lambda name: marks.setdefault(
            name, round(time.perf_counter() - t_start, 2)),
        "manifest": manifest, "workload": workload, "config": config,
        "traffic": traffic, "cell": cell, "chips": chips, "seed": seed,
        "seconds": float(seconds), "rehearsal": rehearsal, "peaks": peaks,
        "compile_requests": device.CompileRequests(),
        "trace": device.DeviceTrace(os.path.join(ROOT, ".perf_trace",
                                                 workload))
        if trace else None,
        "mark_setup_done": lambda: setup.setdefault(
            "s", time.perf_counter() - t_start),
    }
    record = manifest.entry(config["entry"]).run(ctx)
    record["setup_s"] = setup["s"]
    record.setdefault("facts", {})["setup_marks_s"] = marks
    record.setdefault("samples", {})
    record.setdefault("spans", {})
    record["cell"], record["config"] = cell, config
    record["traffic"], record["peaks"] = traffic, peaks
    devices = jax.devices()[:chips]
    record["memory_peak_bytes"] = device.memory_peak_bytes(devices)
    record["counters"]["num_devices"] = chips

    if trace:
        record["trace"] = ctx["trace"].reduce()
    result = assemble_result(manifest, workload, record, found, trace,
                             rehearsal)
    result["_record"] = record
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from perf import device

    if not os.path.isdir(os.path.join(ROOT, "deepspeed_tpu")):
        print(f"perf/run.py: no deepspeed_tpu package in {ROOT}: there is "
              f"no program here to measure.", file=sys.stderr)
        return device.EXIT_NO_PROGRAM
    seconds = args.seconds
    if seconds is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            seconds = json.load(f)["run_seconds"]
    try:
        result = run_cell(args.workload, args.seed, seconds,
                          bool(args.trace))
    except Exception:
        traceback.print_exc()
        return device.EXIT_FAILED
    record = result.pop("_record")
    # the run's facts, for whoever reads the log; the LAST line is the result
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "failures": record["failures"],
                      "facts": record.get("facts", {})},
                     default=str), file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
