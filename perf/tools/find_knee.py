#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: the highest arrival rate the
server sustains. Not part of a run; the builder's instrument for the rate in
the cell's traffic file, kept so a later benchmark issue can find it again.

    chiprun --chips 1 --timeout 3000 -- python3 perf/tools/find_knee.py \
        --workload serve-pythia-1b4-chat --rates 0.8,2,4,6,8,12,16,24

(go up geometrically until two rates in a row are not sustained, then a
second call fills in around the edge: ``--light <row of the first call's
first rate>`` keeps the same reference).

Each rate is one run of the cell in a new process (this file started again
with ``--one-rate``: ``perf/run.py`` itself takes only the contract's four
flags), the window as long as ``run_seconds``, the traffic file's rate
replaced and nothing else. The FIRST rate is the lightly loaded reference.
A rate is sustained when

- every request due in the window showed a first token, none failed, and
  none was preempted out of its slot;
- the backlog (requests handed to the server that have shown no token yet,
  in the queue or in a slot still being prefilled) is no deeper over the
  last fifth of the window than over its middle fifth, give or take one
  request;
- the median wait for the first token is at most ``--ttft-factor`` (2) times
  the reference rate's, and the mean gap between tokens at most
  ``--gap-factor`` (1.25) times the reference rate's: the knee of a latency
  curve is where the wait leaves its lightly loaded level. Limits relative
  to the light load stay meaningful when the step gets faster; absolute ones
  (ISSUE 22 asked for 500 ms and 100 ms) are reported beside them as the
  share of requests inside both.

The knee is the highest sustained rate; the cell runs at 0.8 x it, rounded
to two significant digits. Every rate that was run is printed and appended to
chiprun_out/knee-<workload>.jsonl, sustained or not."""

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

ABS_TTFT_MS, ABS_GAP_MS = 500.0, 100.0      # ISSUE 22's limits, reported


def one_rate_here(workload: str, rate: float, seed: int, seconds) -> dict:
    """This process runs the cell once at ``rate`` and returns the row."""
    from perf.manifest import Manifest
    from perf.run import run_cell

    if seconds is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            seconds = json.load(f)["run_seconds"]
    result = run_cell(workload, seed, seconds, False, override={
        "traffic": {"params": {"rate_per_s": rate}}})
    record = result["_record"]
    facts = record["facts"]
    chunk_share = Manifest(ROOT).layer_reader("chunk_steps_share")(record)
    whole = facts["whole_window"]
    inside = sum(1 for x in whole.get("ttft_ms", []) if x <= ABS_TTFT_MS) \
        if whole.get("gap_mean_ms", ABS_GAP_MS + 1) <= ABS_GAP_MS else 0
    return {"rate_per_s": rate, "seed": seed,
            "lead_in_s": record["traffic"]["params"]["lead_in_s"],
            "correct": result["correct"],
            "attempted": result["attempted"], "failed": result["failed"],
            "requests_due": whole["requests_due"],
            "no_first_token": facts["no_first_token"],
            "finished_in_window": facts["finished_in_window"],
            "preempted": facts["window_counters"]["preempted"],
            "ttft_p50_ms": whole.get("ttft_p50_ms"),
            "ttft_p90_ms": whole.get("ttft_p90_ms"),
            "gap_mean_ms": whole.get("gap_mean_ms"),
            **{f"gap_p{q}_ms": whole.get(f"gap_p{q}_ms")
               for q in (50, 90, 95, 99)},
            "steps_in_window": facts["steps_in_window"],
            "chunk_steps_share": chunk_share,
            "inside_abs_limits_share": inside / max(whole["requests_due"], 1),
            "step_ms_mean": facts["step_ms_mean"],
            "backlog_mid_end": facts["backlog_mid_end"],
            "pages_mapped_mid_end_peak": facts["pages_mapped_mid_end_peak"],
            "live_slots_mean": facts["window_counters"]["slot_steps"]
            / max(facts["window_counters"]["decode_steps"], 1),
            "setup_s": result["_record"]["setup_s"]}


def sustained(row: dict, light: dict, ttft_factor: float, gap_factor: float
              ) -> bool:
    mid, end = row["backlog_mid_end"] or (0.0, 0.0)
    return (row["failed"] == 0 and row["no_first_token"] == 0
            and row["preempted"] == 0
            and end <= mid + 1.0
            and row["ttft_p50_ms"] is not None
            and row["ttft_p50_ms"] <= ttft_factor * light["ttft_p50_ms"]
            and row["gap_mean_ms"] is not None
            and row["gap_mean_ms"] <= gap_factor * light["gap_mean_ms"])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", help="comma-separated request/s, ascending; "
                                    "the first is the light-load reference")
    ap.add_argument("--one-rate", type=float, default=None,
                    help="(the tool's own child) run this rate here")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--light", default=None,
                    help="ttft_p50_ms,gap_mean_ms of an earlier call's "
                         "light rate: every rate here is held against it")
    ap.add_argument("--ttft-factor", type=float, default=2.0)
    ap.add_argument("--gap-factor", type=float, default=1.25)
    ap.add_argument("--stop-after-unsustained", type=int, default=2)
    args = ap.parse_args()

    if args.one_rate is not None:
        print(json.dumps(one_rate_here(args.workload, args.one_rate,
                                       args.seed, args.seconds)), flush=True)
        return 0

    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    table, misses, light = [], 0, None
    if args.light:
        ttft, gap = (float(x) for x in args.light.split(","))
        light = {"ttft_p50_ms": ttft, "gap_mean_ms": gap}
    for rate in [float(r) for r in args.rates.split(",")]:
        cmd = [sys.executable, os.path.abspath(__file__), "--workload",
               args.workload, "--one-rate", str(rate), "--seed",
               str(args.seed)]
        if args.seconds is not None:
            cmd += ["--seconds", str(args.seconds)]
        proc = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True, timeout=1200)
        if proc.returncode != 0:
            sys.stderr.write(proc.stderr[-4000:])
            raise RuntimeError(f"rate {rate}: exit {proc.returncode}")
        row = json.loads([ln for ln in proc.stdout.splitlines()
                          if ln.strip()][-1])
        light = light or row
        row["sustained"] = sustained(row, light, args.ttft_factor,
                                     args.gap_factor)
        table.append(row)
        print(json.dumps(row), flush=True)
        with open(os.path.join(out_dir, f"knee-{args.workload}.jsonl"),
                  "a") as f:
            f.write(json.dumps(row) + "\n")
        misses = 0 if row["sustained"] else misses + 1
        if misses >= args.stop_after_unsustained:
            break
    good = [r["rate_per_s"] for r in table if r["sustained"]]
    knee = max(good) if good else None
    print(json.dumps({"workload": args.workload, "knee_per_s": knee,
                      "cell_rate_per_s": None if knee is None
                      else float(f"{0.8 * knee:.2g}")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
