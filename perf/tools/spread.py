#!/usr/bin/env python3
"""Run one cell n times, each run a new process with another seed, and print
each metric's median and spread (distance between the quartiles over the
median), as the driver measures them. The builder's instrument for the
bounds in BENCHMARK.json; run it through the chip tool:

    chiprun --chips 1 --timeout 1800 -- python3 perf/tools/spread.py \
        --workload serve-pythia-1b4-chat --runs 6 --seed0 100 [--trace 0] \
        [--sets 2 [--same-seeds 1]]

With ``--sets 2`` it makes the driver's check of a bound from the builder's
seat: two sets of ``--runs`` runs (other seeds in the second set, or with
``--same-seeds 1`` the first set's again, as the driver's two sets have
them), and for each metric the mean of the two sets' spreads with each
set's farthest run left out (the driver refuses a bound as too tight where
that is over half of it) beside the wider spread of all runs of a set (too
loose where the bound is over eight times it).

This parent never touches JAX, so it never holds the chip a run needs. Every
run's last line is also appended to chiprun_out/spread-<workload>.jsonl."""

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf import stats  # noqa: E402  (NumPy only: no JAX, no chip)


def run_once(workload: str, seed: int, seconds, trace: int, timeout: float):
    cmd = [sys.executable, os.path.join(ROOT, "perf", "run.py"),
           "--workload", workload, "--seed", str(seed), "--trace",
           str(trace)]
    if seconds is not None:
        cmd += ["--seconds", str(seconds)]
    proc = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                          stderr=subprocess.PIPE, text=True, timeout=timeout)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    if proc.returncode != 0 or not lines:
        sys.stderr.write(proc.stderr[-4000:])
        raise RuntimeError(f"run of {workload} seed {seed} exited "
                           f"{proc.returncode}")
    facts = [ln for ln in proc.stderr.splitlines()
             if ln.startswith('{"workload"')]
    return json.loads(lines[-1]), (json.loads(facts[-1]) if facts else None)


METRICS_BY_WINDOW = ("serve_tok_s", "ttft_p50_ms", "gap_p50_ms", "gap_p75_ms",
                     "gap_p90_ms", "gap_p95_ms", "gap_p99_ms", "gap_mean_ms")


def windows_of(facts) -> dict:
    """{window seconds: {metric: value}} from a serve run's facts."""
    f = (facts or {}).get("facts", {})
    if "whole_window" not in f:
        return {}
    spans = dict(f.get("shorter_windows", {}), whole=f["whole_window"])
    return {("%g" % f["seconds"] if w == "whole" else w):
            {k: m[k] for k in METRICS_BY_WINDOW if k in m}
            for w, m in spans.items()}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seed0", type=int, default=100)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--timeout", type=float, default=1200.0)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--same-seeds", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    summaries = []
    for k in range(args.sets):
        seed0 = args.seed0 + (0 if args.same_seeds else k * args.runs)
        summaries.append(one_set(args, seed0))
    if args.sets > 1:
        print(json.dumps({"workload": args.workload, "sets": args.sets,
                          "as_the_driver_reads_it": {
            name: {"medians": [s[name]["median"] for s in summaries],
                   "mean_spread_without_farthest": sum(
                       s[name]["spread_without_farthest"]
                       for s in summaries) / len(summaries),
                   "widest_spread": max(s[name]["spread"]
                                        for s in summaries)}
            for name in summaries[0] if name != "setup_s_without_first"}}))
    return 0


def one_set(args, seed0: int) -> dict:
    """One set of runs: prints each run and the set's summary; returns it."""
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log = os.path.join(out_dir, f"spread-{args.workload}.jsonl")
    rows, by_window = [], []
    for i in range(args.runs):
        result, facts = run_once(args.workload, seed0 + i, args.seconds,
                                 args.trace, args.timeout)
        rows.append({k: v["value"] for k, v in result["metrics"].items()})
        by_window.append(windows_of(facts))
        with open(log, "a") as f:
            f.write(json.dumps({"seed": seed0 + i, "trace": args.trace,
                                "result": result, "facts": facts}) + "\n")
        print(json.dumps({"seed": seed0 + i,
                          "correct": result["correct"],
                          "failed": result["failed"],
                          "attempted": result["attempted"],
                          **rows[-1]}), flush=True)
    # the first run of a checkout compiles: its set-up is recorded apart
    summary = stats.summarize_runs(rows)
    if "setup_s" in summary and len(rows) > 1:
        summary["setup_s_without_first"] = stats.summarize_runs(
            [{"setup_s": r["setup_s"]} for r in rows[1:]])["setup_s"]
    print(json.dumps({"workload": args.workload, "runs": len(rows),
                      "summary": summary}))
    if any(by_window):
        # a serve cell: what every window length up to this one read, from
        # the same runs (the ground for run_seconds), whichever of the
        # metrics BENCHMARK.json holds a bound for
        print(json.dumps({"workload": args.workload, "by_window_seconds": {
            w: {k: {"median": v["median"], "spread": v["spread"]}
                for k, v in stats.summarize_runs(
                    [r[w] for r in by_window if w in r]).items()}
            for w in sorted(by_window[0], key=float)}}))
    return summary


if __name__ == "__main__":
    sys.exit(main())
