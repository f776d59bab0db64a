#!/usr/bin/env python3
"""The two readings behind the limits of ``perf/reference/brumby.py``'s
``check_greedy``, taken on the chip THROUGH THE SERVER at the cell's load.

    chiprun --chips 1 -- python3 perf/tools/brumby_limits.py \\
        --seeds 5001 5002 [--seconds 10] [--state bfloat16 float32]

For the builder (PERF.md section 6), not a cell. For each seed and each kind
of state the cell ``serve-brumby-14b-continue`` runs once over a shorter
window, as ``perf/run.py`` runs it, and the four requests ``serve.py``
judges are held to the float32 reference: ``float32`` is the cell as it is;
``bfloat16`` is the same server with every state block rounded to
bfloat16's eight bits of mantissa each time a kernel has written it (the
nearest precision below the configuration's float32 state: what a server
that HELD its state in bfloat16 would read back), and has to come out as
not correct.

The rounding is put around the program's two kernel wrappers from here
(the program has no option for it): after ``retention_decode`` or
``retention_chunk`` has updated the rows of its work list in one layer,
those rows' blocks are rounded in place, one row at a time. One process
owns the chip: this parent never touches JAX and runs a child a reading.
Prints one JSON object."""

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

WORKLOAD = "serve-brumby-14b-continue"


def round_to_bfloat16(x):
    """float32 -> the nearest bfloat16 (ties to even) -> float32, on the
    bits: a convert there and back the compiler may drop."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))) \
        & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def held_in_bfloat16(kernel):
    """``kernel`` (``retention_decode`` / ``retention_chunk``) followed by
    the rounding of the blocks it wrote."""
    import jax
    import jax.numpy as jnp

    def wrapped(q, k, v, log_g, s, layer, rows, fresh):
        o, s = kernel(q, k, v, log_g, s, layer, rows, fresh)
        rows = jnp.asarray(rows, jnp.int32)
        layer = jnp.asarray(layer, jnp.int32)
        zero = jnp.zeros((), jnp.int32)

        def one(b, s):
            row = rows[b]
            runs = (row >= 0) & (row < s.shape[1])
            at = (layer, jnp.clip(row, 0, s.shape[1] - 1)) + (zero,) * 4
            block = jax.lax.dynamic_slice(s, at, (1, 1) + s.shape[2:])
            block = jnp.where(runs, round_to_bfloat16(block), block)
            return jax.lax.dynamic_update_slice(s, block, at)

        return o, jax.lax.fori_loop(0, rows.shape[0], one, s)

    return wrapped


def reading(seed: int, seconds: float, state: str, rehearsal: bool) -> dict:
    """The child: the cell once, the reference's judge listening in."""
    from deepspeed_tpu.ops.attention import power_retention as pr
    from perf.manifest import Manifest
    from perf.run import run_cell

    if state == "bfloat16":
        # (retention_prefill finds retention_chunk by its module name)
        pr.retention_decode = held_in_bfloat16(pr.retention_decode)
        pr.retention_chunk = held_in_bfloat16(pr.retention_chunk)
    requests = []
    real = Manifest.reference

    def listening(self, file):
        import numpy as np

        reference = real(self, file)

        class Judge:
            make_forward = staticmethod(reference.make_forward)

            @staticmethod
            def check_greedy(logits_fn, params, prompt, output, context_len,
                             score_len, rel_tol):
                short, scale = reference.shortfalls(
                    logits_fn, params, prompt, output, context_len,
                    score_len)
                out = reference.verdict(short, scale, rel_tol)
                requests.append({
                    "prompt_len": len(prompt), "positions": len(short),
                    "ok": out["ok"],
                    "share_over_rel_tol": float(np.mean(
                        short > rel_tol * scale)),
                    "worst_shortfall_over_scale": float(np.max(
                        short / scale)),
                    "mean_shortfall_over_scale": float(np.mean(
                        short / scale))})
                return out

        return Judge

    Manifest.reference = listening
    result = run_cell(WORKLOAD, seed, seconds, False, rehearsal)
    record = result.pop("_record")
    return {"seed": seed, "state": state, "correct": result["correct"],
            "failures": record["failures"],
            "serve_tok_s": record["end_to_end"]["serve_tok_s"],
            "device": result["device"], "requests": requests}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[5001, 5002])
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--state", nargs="+", default=["bfloat16", "float32"],
                    choices=["bfloat16", "float32"])
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0,
                    help="1: walk it on the CPU at the rehearsal sizes")
    ap.add_argument("--child", action="store_true")
    args = ap.parse_args()
    if args.child:
        if args.rehearsal:
            os.environ["JAX_PLATFORMS"] = "cpu"
        print(json.dumps(reading(args.seeds[0], args.seconds, args.state[0],
                                 bool(args.rehearsal))))
        return 0
    out = {"workload": WORKLOAD, "seconds": args.seconds, "runs": []}
    for seed in args.seeds:
        for state in args.state:
            child = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--child",
                 "--seeds", str(seed), "--state", state,
                 "--seconds", str(args.seconds),
                 "--rehearsal", str(args.rehearsal)],
                check=True, stdout=subprocess.PIPE, text=True).stdout
            out["runs"].append(json.loads(child.strip().splitlines()[-1]))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
