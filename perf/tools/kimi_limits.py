#!/usr/bin/env python3
"""The readings behind the limits of ``perf/reference/kimi_linear.py``'s
``check_greedy``, taken on the chip THROUGH THE SERVER at the cell's load,
and the least a decode step of the cell could take, part by part.

    chiprun --chips 1 -- python3 perf/tools/kimi_limits.py \\
        --seeds 5001 [--seconds 10] \\
        [--arms configured state_bfloat16 weights_float8]
    python3 perf/tools/kimi_limits.py --least 1      (no chip: arithmetic)

For the builder (PERF.md section 6, PR 50), not a cell. The readings are
``perf/tools/granite_limits.py``'s, child by child (one process owns the
chip: this parent never touches JAX), over the cell
``serve-kimi-linear-48b-longform``:

* ``configured``: the cell as it is (bfloat16 weights, float32 state).
* ``state_bfloat16``: the same server with every state block rounded to
  bfloat16's eight bits of mantissa each time ``kda_decode`` or
  ``kda_chunk`` has written it. Has to come out as not correct by the
  pool's audit of the state it holds (``PagedKVPool.consistency_errors``).
* ``weights_float8``: the same server over the weights rounded to e4m3's
  three bits of mantissa (the reference judges against the weights as
  seeded). Has to come out as not correct by the reference's limits on the
  served tokens.

``--least 1`` prints, from the configuration's file and ``perf/peaks.json``
alone, the bytes a plain decode step of ``num_slots`` rows must move and the
time the HBM's peak leaves for each part: the KDA state (read and written),
the held experts a step touches, the latent rows read at ``--positions`` a
slot, and every other weight once."""

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
from perf.tools.brumby_limits import round_to_bfloat16  # noqa: E402

WORKLOAD = "serve-kimi-linear-48b-longform"
ARMS = base.ARMS
base.WORKLOAD = WORKLOAD        # (its children read the cell by this name)


def held_in_bfloat16(kernel):
    """``kernel`` (``kda_decode`` / ``kda_chunk``) followed by the rounding
    of the blocks it wrote."""
    import jax
    import jax.numpy as jnp

    def wrapped(q, k, v, g, beta, s, layer, rows, fresh):
        o, s = kernel(q, k, v, g, beta, s, layer, rows, fresh)
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

        return o, jax.lax.fori_loop(0, rows.shape[0], one, s)

    return wrapped


def serve(seed: int, seconds: float, arm: str, path: str,
          rehearsal: bool) -> None:
    """Child 1: the cell under ``arm`` (``granite_limits.serve`` with this
    model's kernels wrapped for the state's arm)."""
    if arm == "state_bfloat16":
        from deepspeed_tpu.ops import kda

        # (kda_prefill finds kda_chunk by its module name)
        kda.kda_decode = held_in_bfloat16(kda.kda_decode)
        kda.kda_chunk = held_in_bfloat16(kda.kda_chunk)
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
    a_layer, state = config["parameters_a_layer"], config["state"]
    kinds = config["layer_types"]
    routed = len(kinds) - config["first_k_dense_replace"]
    held, k = config["num_experts"], config["num_experts_per_token"]
    published = config["published"]["num_experts"]
    # experts of a layer that 128 x 8 assignments over 256 touch among the
    # 32 held, if the router is even
    touched = held * (1 - (1 - 1 / published) ** (slots * k))
    parts = {
        "kda_state_read_and_written": 2 * slots * state["bytes_a_slot"],
        "held_experts_touched": 2 * routed * touched * a_layer["one_expert"],
        "latent_rows_read": slots * positions * kinds.count("attention")
        * config["kv_bytes_per_token_a_layer"],
        "other_weights": config["weight_bytes"]
        - 2 * routed * a_layer["routed_experts_held"]
        - config["embedding_and_head_parameters"],      # (the head: half)
    }
    return {"workload": WORKLOAD, "slots": slots, "positions_a_slot":
            positions, "experts_touched_a_layer": touched,
            "bytes": parts, "ms_at_hbm_peak": {
                key: 1e3 * val / hbm for key, val in parts.items()},
            "step_ms_at_hbm_peak": 1e3 * sum(parts.values()) / hbm}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[5001])
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--arms", nargs="+", default=list(ARMS), choices=ARMS)
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0,
                    help="1: walk it on the CPU at the rehearsal sizes")
    ap.add_argument("--least", type=int, choices=(0, 1), default=0)
    ap.add_argument("--positions", type=int, default=3600)
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
