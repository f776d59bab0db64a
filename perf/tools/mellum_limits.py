#!/usr/bin/env python3
"""The two readings behind the limits of ``perf/reference/mellum.py``'s
``check_greedy``, taken on the chip THROUGH THE SERVER at the cell's load.

    chiprun --chips 1 -- python3 perf/tools/mellum_limits.py \\
        --seeds 5001 5002 [--seconds 10] [--weights float8 bfloat16]

For the builder (PERF.md section 6), not a cell. For each seed and each kind
of weights the cell ``serve-mellum2-12b-ide`` runs once over a shorter
window, as ``perf/run.py`` runs it, and the four requests ``serve.py``
judges are held to the float32 reference of the configuration's OWN
bfloat16 weights: ``bfloat16`` is the cell as it is; ``float8`` serves the
same weights rounded to e4m3's three bits of mantissa, the nearest precision
below the configuration's, and has to come out as not correct.

The server and the reference's weights do not fit the chip together when
they differ, and one process owns the chip: this parent never touches JAX;
a first child serves and writes down the judged requests, a second makes the
seed's weights again and judges them. Prints one JSON object."""

import argparse
import json
import os
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

WORKLOAD = "serve-mellum2-12b-ide"


def fp8(tree):
    """Every bfloat16 weight rounded to e4m3's three bits of mantissa (on
    the bits: a convert there and back the compiler may drop)."""
    import jax
    import jax.numpy as jnp

    def one(a):
        if a.dtype != jnp.bfloat16:
            return a
        bits = jax.lax.bitcast_convert_type(a, jnp.uint16)
        bits = (bits + jnp.uint16(8)) & jnp.uint16(0xFFF0)
        return jax.lax.bitcast_convert_type(bits, jnp.bfloat16)
    return jax.tree_util.tree_map(one, tree)


def serve(seed: int, seconds: float, weights: str, path: str,
          rehearsal: bool) -> None:
    """Child 1: the cell, its weights rounded if asked; ``serve.py``'s
    judge is replaced by one that writes its requests to ``path``."""
    import jax

    from perf import build
    from perf.manifest import Manifest
    from perf.run import run_cell

    if weights == "float8":
        make = build.init_params
        build.init_params = lambda *a, **kw: jax.jit(
            fp8, donate_argnums=0)(make(*a, **kw))
    judged = []

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
                   "serve_tok_s": record["end_to_end"]["serve_tok_s"],
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
            "worst_shortfall_over_scale": float(np.max(short / scale))})
    return {"serve_tok_s": served["serve_tok_s"],
            "failures": served["failures"], "device": served["device"],
            "requests": requests}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[5001, 5002])
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--weights", nargs="+", default=["float8", "bfloat16"],
                    choices=["float8", "bfloat16"])
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0,
                    help="1: walk it on the CPU at the rehearsal sizes")
    ap.add_argument("--child", choices=["serve", "judge"])
    ap.add_argument("--path")
    args = ap.parse_args()
    if args.child == "serve":
        serve(args.seeds[0], args.seconds, args.weights[0], args.path,
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
            for weights in args.weights:
                common = [sys.executable, os.path.abspath(__file__),
                          "--seeds", str(seed), "--path", path,
                          "--rehearsal", str(args.rehearsal)]
                subprocess.run(
                    common + ["--child", "serve", "--weights", weights,
                              "--seconds", str(args.seconds)],
                    check=True, stdout=sys.stderr)
                judged = subprocess.run(
                    common + ["--child", "judge"], check=True,
                    stdout=subprocess.PIPE, text=True).stdout
                out["runs"].append(dict(
                    json.loads(judged.strip().splitlines()[-1]),
                    seed=seed, weights=weights))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
