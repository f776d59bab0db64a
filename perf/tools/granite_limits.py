#!/usr/bin/env python3
"""The readings behind the limits of ``perf/reference/granite_hybrid.py``'s
``check_greedy``, taken on the chip THROUGH THE SERVER at the cell's load.

    chiprun --chips 1 -- python3 perf/tools/granite_limits.py \\
        --seeds 5001 [--seconds 10] \\
        [--arms configured state_bfloat16 weights_float8]

For the builder (PERF.md section 6, PR 47), not a cell. For each seed and
each arm the cell ``serve-granite4h-3b-agents`` runs once over a shorter
window, as ``perf/run.py`` runs it, and the four requests ``serve.py``
judges are written down; a second child makes the seed's weights as the cell
makes them and holds those requests to the float32 reference.

* ``configured``: the cell as it is (bfloat16 weights, float32 state).
* ``state_bfloat16``: the same server with every state block rounded to
  bfloat16's eight bits of mantissa each time a kernel has written it: what
  a server that HELD its state in bfloat16 would read back. The rounding is
  put around ``ssm_decode`` and ``ssm_chunk`` from here (the program has no
  option for it): after a call has updated the rows of its work list in one
  layer, those rows' blocks are rounded in place, one row at a time.
* ``weights_float8``: the same server over the weights rounded to e4m3's
  three bits of mantissa (the reference judges against the weights as
  seeded).

The two below are the nearest precisions under the configuration's and have
to come out as not correct: ``weights_float8`` by the reference's limits on
the served tokens, ``state_bfloat16`` by the pool's audit of the state it
holds (``PagedKVPool.consistency_errors``, which the cell runs after its
window: ``failures`` below is what the cell would report), since no number
of the served tokens tells that arm from the configured server (the
readings this prints for both: the mean shortfall, and the share of
positions beyond ``rel_tol`` on the requests of 1,000 positions and more).
``state_words_narrow`` is the audit's own reading, least and largest row.
One process owns the chip: this parent never touches JAX and runs two
children a reading. Prints one JSON object; ``summary`` gathers an arm's
readings over the seeds."""

import argparse
import json
import os
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf.tools.brumby_limits import round_to_bfloat16  # noqa: E402
from perf.tools.mellum_limits import fp8  # noqa: E402

WORKLOAD = "serve-granite4h-3b-agents"
ARMS = ("configured", "state_bfloat16", "weights_float8")


def held_in_bfloat16(kernel):
    """``kernel`` (``ssm_decode`` / ``ssm_chunk``) followed by the rounding
    of the blocks it wrote."""
    import jax
    import jax.numpy as jnp

    def wrapped(x, dt, a, b, c, s, layer, rows, fresh):
        y, s = kernel(x, dt, a, b, c, s, layer, rows, fresh)
        rows = jnp.asarray(rows, jnp.int32)
        layer = jnp.asarray(layer, jnp.int32)
        zero = jnp.zeros((), jnp.int32)

        def one(i, s):
            row = rows[i]
            runs = (row >= 0) & (row < s.shape[1])
            at = (layer, jnp.clip(row, 0, s.shape[1] - 1)) + (zero,) * 3
            block = jax.lax.dynamic_slice(s, at, (1, 1) + s.shape[2:])
            block = jnp.where(runs, round_to_bfloat16(block), block)
            return jax.lax.dynamic_update_slice(s, block, at)

        return y, jax.lax.fori_loop(0, rows.shape[0], one, s)

    return wrapped


def serve(seed: int, seconds: float, arm: str, path: str,
          rehearsal: bool) -> None:
    """Child 1: the cell under ``arm``; ``serve.py``'s judge is replaced by
    one that writes its requests to ``path``."""
    import jax
    import numpy as np

    from deepspeed_tpu.ops import state_space as ss
    from deepspeed_tpu.serving import paged_pool
    from perf import build
    from perf.manifest import Manifest
    from perf.run import run_cell

    if arm == "state_bfloat16":
        # (ssm_prefill finds ssm_chunk by its module name)
        ss.ssm_decode = held_in_bfloat16(ss.ssm_decode)
        ss.ssm_chunk = held_in_bfloat16(ss.ssm_chunk)
    if arm == "weights_float8":
        make = build.init_params
        build.init_params = lambda *a, **kw: jax.jit(
            fp8, donate_argnums=0)(make(*a, **kw))
    judged, narrow = [], []
    count_words = paged_pool._narrow_words

    def noted(leaf):
        words, empty = count_words(leaf)
        share = (np.asarray(empty) / np.maximum(np.asarray(words), 1))[
            np.asarray(words) > 0]
        narrow.extend([float(share.min()), float(share.max())])
        return words, empty

    paged_pool._narrow_words = noted

    class Recorder:
        @staticmethod
        def make_forward(**_):
            return None

        @staticmethod
        def check_greedy(_, __, prompt, output, context_len, score_len,
                         rel_tol):
            judged.append({"prompt": [int(t) for t in prompt],
                           "output": [int(t) for t in output],
                           "context_len": context_len,
                           "score_len": score_len, "rel_tol": rel_tol})
            return {"ok": True, "positions": len(output)}

    Manifest.reference = lambda self, file: Recorder
    result = run_cell(WORKLOAD, seed, seconds, False, rehearsal)
    record = result.pop("_record")
    with open(path, "w") as f:
        json.dump({"judged": judged, "failures": record["failures"],
                   "state_words_narrow": narrow,
                   "gap_p90_ms": record["end_to_end"].get("gap_p90_ms"),
                   "device": result["device"]}, f)


def judge(seed: int, path: str, rehearsal: bool) -> dict:
    """Child 2: the seed's weights as the cell makes them, and the
    reference's readings of each request child 1 wrote down."""
    import jax.numpy as jnp
    import numpy as np

    from perf import build, device
    from perf.manifest import Manifest

    with open(path) as f:
        served = json.load(f)
    device.open_device(1, rehearsal)
    manifest = Manifest(ROOT)
    config = manifest.config(manifest.workload(WORKLOAD)["config"])
    model, cfg = build.build_model(config["model"], None, rehearsal)
    params = build.init_params(
        model, (jnp.zeros((1, 8), jnp.int32),),
        {"method": getattr(model, config["model"]["init_method"])}, seed,
        cast_to=build._dtype(config["model"]["dtype"]))
    reference = manifest.reference(config["reference"]["file"])
    logits_fn = reference.make_forward(**{
        k: getattr(cfg, v)
        for k, v in config["reference"]["args_from_config"].items()})
    requests = []
    for r in served["judged"]:
        short, scale = reference.shortfalls(
            logits_fn, params, np.asarray(r["prompt"], np.int32),
            r["output"], r["context_len"], r["score_len"])
        out = reference.verdict(short, scale, r["rel_tol"])
        requests.append({
            "prompt_len": len(r["prompt"]), "positions": out["positions"],
            "ok": out["ok"],
            "share_over_rel_tol": out["positions_over_rel_tol"]
            / out["positions"],
            "share_not_the_best": float(np.mean(short > 0)),
            "worst_shortfall_over_scale": float(np.max(short / scale)),
            "mean_shortfall_over_scale": float(np.mean(short / scale))})
    return {"gap_p90_ms": served["gap_p90_ms"],
            "failures": served["failures"],
            "state_words_narrow": served["state_words_narrow"],
            "device": served["device"], "requests": requests}


LONG = 1000     # positions: where a state's rounding has had time to gather


def summary(runs: list) -> dict:
    """An arm's readings over its seeds: least and largest of each."""
    out = {}
    for arm in sorted({r["arm"] for r in runs}):
        mine = [r for r in runs if r["arm"] == arm]
        reqs = [q for r in mine for q in r["requests"]]
        long = [q for q in reqs if q["positions"] >= LONG]

        def span(values):
            values = list(values)
            return [min(values), max(values)] if values else None

        out[arm] = {
            "seeds": [r["seed"] for r in mine],
            "cell_correct": [not r["failures"]
                             and all(q["ok"] for q in r["requests"])
                             for r in mine],
            "failed_the_tokens": [not all(q["ok"] for q in r["requests"])
                                  for r in mine],
            "failed_the_audit": [any("narrower than the spec" in f
                                     for f in r["failures"]) for r in mine],
            "state_words_narrow": span(
                x for r in mine for x in r["state_words_narrow"]),
            "worst_shortfall_over_scale": span(
                q["worst_shortfall_over_scale"] for q in reqs),
            "mean_shortfall_over_scale": span(
                q["mean_shortfall_over_scale"] for q in reqs),
            "share_not_the_best": span(q["share_not_the_best"]
                                       for q in reqs),
            "long_requests": len(long),
            "long_share_over_rel_tol": span(q["share_over_rel_tol"]
                                            for q in long),
            "long_mean_shortfall_over_scale": span(
                q["mean_shortfall_over_scale"] for q in long),
            "run_mean_shortfall_over_scale": span(
                sum(q["mean_shortfall_over_scale"] * q["positions"]
                    for q in r["requests"])
                / sum(q["positions"] for q in r["requests"]) for r in mine),
        }
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[5001])
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--arms", nargs="+", default=list(ARMS), choices=ARMS)
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0,
                    help="1: walk it on the CPU at the rehearsal sizes")
    ap.add_argument("--child", choices=["serve", "judge"])
    ap.add_argument("--path")
    args = ap.parse_args()
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if args.child == "serve":
        serve(args.seeds[0], args.seconds, args.arms[0], args.path,
              bool(args.rehearsal))
        return 0
    if args.child == "judge":
        print(json.dumps(judge(args.seeds[0], args.path,
                               bool(args.rehearsal))))
        return 0

    out = {"workload": WORKLOAD, "seconds": args.seconds, "runs": []}
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "judged.json")
        for seed in args.seeds:
            for arm in args.arms:
                common = [sys.executable, os.path.abspath(__file__),
                          "--seeds", str(seed), "--path", path,
                          "--rehearsal", str(args.rehearsal)]
                subprocess.run(
                    common + ["--child", "serve", "--arms", arm,
                              "--seconds", str(args.seconds)],
                    check=True, stdout=sys.stderr)
                judged = subprocess.run(
                    common + ["--child", "judge"], check=True,
                    stdout=subprocess.PIPE, text=True).stdout
                out["runs"].append(dict(
                    json.loads(judged.strip().splitlines()[-1]),
                    seed=seed, arm=arm))
                print(json.dumps(out["runs"][-1]), file=sys.stderr,
                      flush=True)
    out["summary"] = summary(out["runs"])
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
