#!/usr/bin/env python3
"""The readings behind the limits of ``perf/reference/minicpm_sala.py``'s
``check_greedy``, taken on the chip THROUGH THE SERVER at the cell's load.

    chiprun --chips 1 -- python3 perf/tools/minicpm_limits.py \\
        --seeds 5001 [--seconds 10] \\
        [--arms configured state_bfloat16 index_bfloat16 weights_float8]

For the builder (PERF.md section 6, PR 56), not a cell. The readings are
``perf/tools/granite_limits.py``'s, child by child (one process owns the
chip: this parent never touches JAX), over the cell
``serve-minicpm-sala-9b-longdoc``:

* ``configured``: the cell as it is (bfloat16 weights, a float32 Lightning
  state, float32 group means under the index).
* ``state_bfloat16``: the same server with every state block rounded to
  bfloat16's eight bits of mantissa each time a kernel has written it: what
  a server that HELD its Lightning state in bfloat16 would read back. The
  rounding is put around ``ops/state_space``'s two kernels from here (the
  program has no option for it). Has to come out as not correct: by the
  pool's audit of the state it holds, whatever the tokens read.
* ``index_bfloat16``: the same server choosing its blocks from bfloat16
  products: the queries and the group means rounded to bfloat16 and the
  scores at the default precision inside ``sparse_index.choose_blocks``,
  where the configuration states float32 at ``Precision.HIGHEST``. The
  program has no option for it; the rounding is put around the function
  from here. With seeded weights the choice is near uniform and the 64th
  and 65th blocks weigh alike: whether any of the cell's numbers tells
  this arm from the configured one is what the arm is run to find out
  (PERF.md section 6 and 7, PR 56).
* ``weights_float8``: the same server over the weights rounded to e4m3's
  three bits of mantissa, the nearest precision below the configuration's
  (the reference judges against the weights as seeded). Has to come out as
  not correct by the reference's limits on the served tokens."""

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

WORKLOAD = "serve-minicpm-sala-9b-longdoc"
ARMS = ("configured", "state_bfloat16", "index_bfloat16", "weights_float8")
# (its children read the cell by this name: the way ``kimi_limits.py`` and
# ``lfm2_limits.py`` reuse it; ``granite_limits.py`` is a benchmark file)
base.WORKLOAD = WORKLOAD


def held_in_bfloat16(kernel):
    """``granite_limits.held_in_bfloat16`` for kernels called under a name
    of the caller's (``lightning_decode`` / ``lightning_chunk``)."""
    def wrapped(*args, **named):
        return base.held_in_bfloat16(
            lambda *a: kernel(*a, **named))(*args)
    return wrapped


def serve(seed: int, seconds: float, arm: str, path: str,
          rehearsal: bool) -> None:
    """Child 1: the cell under ``arm`` (``granite_limits.serve``; the
    state's arm wraps the kernels here, which take the caller's name)."""
    if arm == "state_bfloat16":
        from deepspeed_tpu.ops import state_space as ss

        ss.ssm_decode = held_in_bfloat16(ss.ssm_decode)
        ss.ssm_chunk = held_in_bfloat16(ss.ssm_chunk)
        arm = "configured"
    if arm == "index_bfloat16":
        import jax.numpy as jnp

        from deepspeed_tpu.ops.attention import sparse_index as si

        exact = si.choose_blocks
        si.choose_blocks = lambda q, means, qpos, sizes, scale, *_: exact(
            q.astype(jnp.bfloat16), means.astype(jnp.bfloat16), qpos, sizes,
            scale, None)
        arm = "configured"
    base.serve(seed, seconds, arm, path, rehearsal)


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
