#!/usr/bin/env python3
"""The readings behind the limits of ``perf/reference/lfm2_moe.py``'s
``check_greedy``, taken on the chip THROUGH THE SERVER at the cell's load,
and the least a decode step of the cell could take, part by part.

    chiprun --chips 1 -- python3 perf/tools/lfm2_limits.py \\
        --seeds 5001 [--seconds 10] \\
        [--arms configured weights_float8 tail_float8]
    python3 perf/tools/lfm2_limits.py --least 1      (no chip: arithmetic)

For the builder (PERF.md section 6, PR 54), not a cell. The readings are
``perf/tools/granite_limits.py``'s, child by child (one process owns the
chip: this parent never touches JAX), over the cell
``serve-lfm2-24b-assist``:

* ``configured``: the cell as it is (bfloat16 weights, a bfloat16 tail).
* ``weights_float8``: the same server over the weights rounded to e4m3's
  three bits of mantissa, the nearest precision below the configuration's
  (the reference judges against the weights as seeded). Has to come out as
  not correct by the reference's limits on the served tokens.
* ``tail_float8``: the same server with every carried tail (the last two
  rows of ``v = B (.) z`` a slot a conv layer) rounded from bfloat16 to
  e4m3's three bits of mantissa each time it is written: what a server
  that HELD its tail in float8 would read back. The rounding is put around
  ``ops/state_space.causal_conv`` from here (the program has no option for
  it). Two of a token's three taps read the tail, in six of eight layers.

``--least 1`` prints, from the configuration's file and ``perf/peaks.json``
alone, the bytes a plain decode step of ``num_slots`` rows must move and the
time the HBM's peak leaves for each part: the experts a step touches, the
K/V read at ``--positions`` a slot, the tails (read and written), and every
other weight once."""

import argparse
import json
import os
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf.tools import granite_limits as base  # noqa: E402
from perf.tools.mellum_limits import fp8  # noqa: E402

WORKLOAD = "serve-lfm2-24b-assist"
ARMS = ("configured", "weights_float8", "tail_float8")
base.WORKLOAD = WORKLOAD        # (its children read the cell by this name)


def tail_in_float8(conv):
    """``causal_conv`` with the tail it hands back rounded to float8."""
    def wrapped(*args, **kw):
        out, tail = conv(*args, **kw)
        return out, fp8(tail)
    return wrapped


def serve(seed: int, seconds: float, arm: str, path: str,
          rehearsal: bool) -> None:
    """Child 1: the cell under ``arm`` (``granite_limits.serve``, the
    convolution wrapped for the tail's arm)."""
    if arm == "tail_float8":
        from deepspeed_tpu.ops import state_space as ss

        ss.causal_conv = tail_in_float8(ss.causal_conv)
        arm = "configured"
    base.serve(seed, seconds, arm, path, rehearsal)


def least(positions: int) -> dict:
    """The bytes of a plain decode step of every slot and the HBM's time
    for them, by part."""
    from perf.manifest import Manifest

    manifest = Manifest(ROOT)
    config = manifest.config(manifest.workload(WORKLOAD)["config"])
    hbm = manifest.peaks()["TPU v5 lite"]["hbm_bytes_per_s"]
    slots = config["server"]["num_slots"]
    a_layer, kinds = config["parameters_a_layer"], config["layer_types"]
    routed = len(kinds) - config["num_dense_layers"]
    experts, k = config["num_experts"], config["num_experts_per_tok"]
    # experts of a layer that slots x k assignments touch, if the router
    # is even
    touched = experts * (1 - (1 - 1 / experts) ** (slots * k))
    parts = {
        "experts_touched": 2 * routed * touched * a_layer["one_expert"],
        "kv_read": slots * positions * kinds.count("full_attention")
        * config["kv_bytes_per_token_a_layer"],
        "tails_read_and_written": 2 * slots * config["state"]["bytes_a_slot"],
        "other_weights": config["weight_bytes"]
        - 2 * routed * a_layer["routed_experts"],
    }
    return {"workload": WORKLOAD, "slots": slots, "positions_a_slot":
            positions, "experts_touched_a_layer": touched,
            "rows_an_expert": slots * k / experts, "bytes": parts,
            "ms_at_hbm_peak": {key: 1e3 * val / hbm
                               for key, val in parts.items()},
            "step_ms_at_hbm_peak": 1e3 * sum(parts.values()) / hbm}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[5001])
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--arms", nargs="+", default=list(ARMS), choices=ARMS)
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0,
                    help="1: walk it on the CPU at the rehearsal sizes")
    ap.add_argument("--least", type=int, choices=(0, 1), default=0)
    ap.add_argument("--positions", type=int, default=1400)
    ap.add_argument("--child", choices=["serve", "judge"])
    ap.add_argument("--path")
    args = ap.parse_args()
    if args.least:
        print(json.dumps(least(args.positions)))
        return 0
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if args.child == "serve":
        serve(args.seeds[0], args.seconds, args.arms[0], args.path,
              bool(args.rehearsal))
        return 0
    if args.child == "judge":
        print(json.dumps(base.judge(args.seeds[0], args.path,
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
    out["summary"] = base.summary(out["runs"])
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
