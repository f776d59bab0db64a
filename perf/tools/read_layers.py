"""Run a cell once, traced, and print what the named per-layer readers read
off its record, beside the result line: for readers that ``BENCHMARK.json``
has no entry for (yet), such as the six of ``serve-minicpm-sala-9b-longdoc``
(PERF.md section 7: the manifest holds its 128 per-layer entries, the most
it may). Builder only; ``--rehearsal 1`` walks it on the CPU, where every
device reader reads nothing.

    chiprun --chips 1 -- python3 perf/tools/read_layers.py \\
        --workload serve-minicpm-sala-9b-longdoc --seed 7 \\
        --readers sparse_dev_share,sparse_roofline,lightning_roofline"""

from __future__ import annotations

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
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--readers", required=True)
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    seconds = args.seconds
    if seconds is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            seconds = json.load(f)["run_seconds"]
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        seconds = min(seconds, 3.0)
    from perf import run
    from perf.manifest import Manifest

    result = run.run_cell(args.workload, args.seed, seconds, True,
                          rehearsal=bool(args.rehearsal))
    record = result.pop("_record")
    manifest = Manifest(ROOT)
    read = {name: manifest.layer_reader(name)(record)
            for name in args.readers.split(",")}
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "failures": record["failures"],
                      "facts": record.get("facts", {})}, default=str),
          file=sys.stderr)
    print(json.dumps({"readers": read}))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
