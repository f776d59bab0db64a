#!/usr/bin/env python3
"""A rehearsal: one cell's control flow on the CPU at toy widths, in seconds.

    python3 perf/tools/rehearse.py --workload <name> [--trace 1] [--seconds 3]

For the builder only. It walks the same code as perf/run.py (same entry,
generators, readers, reducer) with the toy sizes each data file carries under
its ``rehearsal_*`` keys, with JAX held to the CPU (four forced host devices
for a four-chip cell). It finds wrong paths, arguments and control flow
before chip time is spent. It is NOT a measurement: what it prints is marked
``"rehearsal": true``, carries no ``metrics`` key, and puts whatever numbers
the run produced under ``rehearsal_values_not_metrics``."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    with open(os.path.join(ROOT, "perf", "cells",
                           f"{args.workload}.json")) as f:
        chips = json.load(f).get("rehearsal_chips", 1)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={chips}").strip()

    from perf.run import run_cell

    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), rehearsal=True)
    record = result.pop("_record")
    values = result.pop("metrics")
    print(json.dumps({
        "rehearsal": True, "workload": args.workload,
        "passed": result["correct"], "failures": record["failures"],
        "attempted": result["attempted"], "failed": result["failed"],
        "device": result["device"],
        "rehearsal_values_not_metrics": values,
        "facts": record.get("facts", {})}, default=str))
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
