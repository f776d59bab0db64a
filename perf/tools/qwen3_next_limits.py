#!/usr/bin/env python3
"""The readings behind the limits of ``perf/reference/qwen3_next.py``'s
``check_greedy``, taken on the chip THROUGH THE SERVER at the cell's load,
and the configuration's arithmetic from its file.

    chiprun --chips 1 -- python3 perf/tools/qwen3_next_limits.py \\
        --seeds 5001 [--seconds 10] \\
        [--arms configured state_bfloat16 router_bfloat16 weights_float8]
    python3 perf/tools/qwen3_next_limits.py --least 1    (no chip: arithmetic)

For the builder (PERF.md section 6, PR 60), not a cell. The readings are
``perf/tools/granite_limits.py``'s, child by child (one process owns the
chip: this parent never touches JAX), over the cell
``serve-qwen3-next-80b-rag``:

* ``configured``: the cell as it is (bfloat16 weights, float32 state, a
  float32 router).
* ``state_bfloat16``: the same server with every state block rounded to
  bfloat16's eight bits of mantissa each time ``gdn_decode`` or
  ``gdn_chunk`` has written it. Has to come out as not correct by the
  pool's audit of the state it holds (``PagedKVPool.consistency_errors``).
* ``router_bfloat16``: the same server with the router's LOGITS rounded to
  bfloat16 before the softmax (what a router whose product came out in
  bfloat16 would score with; the program's are float32 sums at
  ``Precision.HIGHEST``): near ties among the top 10 of 512 then fall the
  other way. (Rounding the product's OPERANDS instead changes nothing: the
  served row and the router matrix are bfloat16 already, their products
  are exact in float32, and that arm read the configured server's numbers
  digit for digit: my chip run, PR 60, call 3.)
* ``weights_float8``: the same server over the weights rounded to e4m3's
  three bits of mantissa, the nearest precision below the configuration's
  (the reference judges against the weights as seeded). Has to come out as
  not correct by the reference's limits on the served tokens.

``--least 1`` prints, from the configuration's file and ``perf/peaks.json``
alone: the parameters and bytes by part (a layer of each kind, the eight
layers, the vocabulary, the whole published model by the same count), the
state and the K/V pool, what is resident, and the bytes a step of the cell
(a 512-token chunk beside ``--rows`` decode rows at ``--positions`` a row)
must move with the time the HBM's peak leaves for each part."""

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

WORKLOAD = "serve-qwen3-next-80b-rag"
ARMS = ("configured", "state_bfloat16", "router_bfloat16", "weights_float8")
base.WORKLOAD = WORKLOAD        # (its children read the cell by this name)


def held_in_bfloat16(kernel):
    """``kernel`` (``kda_decode`` / ``kda_chunk``, which a Gated DeltaNet
    layer calls under its own names) followed by the rounding of the blocks
    it wrote."""
    import jax
    import jax.numpy as jnp

    def wrapped(q, k, v, g, beta, s, layer, rows, fresh, *name, **named):
        o, s = kernel(q, k, v, g, beta, s, layer, rows, fresh, *name,
                      **named)
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


def routed_in_bfloat16(module):
    """``module.route`` (``moe.routed_ffn``) with the product that makes its
    logits rounded to bfloat16 (the softmax and the top-k stay float32):
    while it is traced the module sees a ``jnp`` whose ``dot`` rounds."""
    route, real = module.route, module.jnp

    class Rounding:
        def __getattr__(self, name):
            return getattr(real, name)

        @staticmethod
        def dot(*args, **kw):
            return round_to_bfloat16(real.dot(*args, **kw))

    def wrapped(*args, **kw):
        module.jnp = Rounding()
        try:
            return route(*args, **kw)
        finally:
            module.jnp = real

    return wrapped


def serve(seed: int, seconds: float, arm: str, path: str,
          rehearsal: bool) -> None:
    """Child 1: the cell under ``arm`` (``granite_limits.serve`` with this
    model's kernels wrapped for the state's and the router's arms)."""
    if arm == "state_bfloat16":
        from deepspeed_tpu.ops import kda

        # (kda_prefill finds kda_chunk by its module name)
        kda.kda_decode = held_in_bfloat16(kda.kda_decode)
        kda.kda_chunk = held_in_bfloat16(kda.kda_chunk)
        arm = "configured"
    if arm == "router_bfloat16":
        from deepspeed_tpu.moe import routed_ffn

        routed_ffn.route = routed_in_bfloat16(routed_ffn)
        arm = "configured"
    base.serve(seed, seconds, arm, path, rehearsal)


def least(rows: int, positions: int) -> dict:
    """The configuration's arithmetic, and the bytes of a step of the cell
    with the HBM's time for them, by part."""
    from perf.manifest import Manifest

    manifest = Manifest(ROOT)
    config = manifest.config(manifest.workload(WORKLOAD)["config"])
    hbm = manifest.peaks()["TPU v5 lite"]["hbm_bytes_per_s"]
    a_layer, state = config["parameters_a_layer"], config["state"]
    kinds = config["layer_types"]
    n_gdn, n_att = kinds.count("linear_attention"), \
        kinds.count("full_attention")
    held, k = config["num_experts"], config["num_experts_per_tok"]
    published = config["published"]["num_experts"]
    chunk = config["server"]["prefill_chunk"]
    slots = config["server"]["num_slots"]
    pages = config["server"]["paged_kv"]
    step_rows = chunk + rows
    # experts of a layer that the step's rows' choices touch among the
    # held, if the router is even
    touched = held * (1 - (1 - 1 / published) ** (step_rows * k))
    layers = n_gdn + n_att
    outside = a_layer["router"] + a_layer["shared_expert_and_gate"] \
        + a_layer["norms"]
    whole_layer = {
        kind: a_layer[mixer] + outside + published * a_layer["one_expert"]
        for kind, mixer in (("gdn", "gdn_mixer"),
                            ("attention", "gated_attention"))}
    period = config["full_attention_interval"]
    total_layers = config["published"]["num_hidden_layers"]
    parts = {
        "gdn_state_read_and_written":
            2 * (rows + 1) * n_gdn * state["s_shape_a_slot_a_layer"][0]
            * state["s_shape_a_slot_a_layer"][1]
            * state["s_shape_a_slot_a_layer"][2] * 4,
        "held_experts_touched": 2 * layers * touched * a_layer["one_expert"],
        "kv_read": rows * positions * n_att
        * config["kv_bytes_per_token_a_layer"],
        "other_weights": config["weight_bytes"]
        - 2 * layers * a_layer["routed_experts_held"]
        - config["embedding_and_head_parameters"],      # (the head: half)
    }
    return {
        "workload": WORKLOAD,
        "parameters": {
            "gdn_layer_outside_its_experts": a_layer["gdn_mixer"] + outside,
            "attention_layer_outside_its_experts":
                a_layer["gated_attention"] + outside,
            "held_experts_a_layer": a_layer["routed_experts_held"],
            "published_experts_a_layer": a_layer["routed_experts_published"],
            "gdn_layer": a_layer["gdn_layer_with_held_experts"],
            "attention_layer": a_layer["attention_layer_with_held_experts"],
            "layers": n_gdn * a_layer["gdn_layer_with_held_experts"]
            + n_att * a_layer["attention_layer_with_held_experts"],
            "embedding_and_head": config["embedding_and_head_parameters"],
            "served": config["parameters"],
            "published_by_this_count":
                (total_layers - total_layers // period) * whole_layer["gdn"]
                + total_layers // period * whole_layer["attention"]
                + config["embedding_and_head_parameters"]
                + config["hidden_size"]},
        "bytes": {
            "weights": config["weight_bytes"],
            "state_a_slot": state["bytes_a_slot"],
            "state": slots * state["bytes_a_slot"],
            "kv_pages": pages["num_pages"] * pages["page_size"] * n_att
            * config["kv_bytes_per_token_a_layer"],
            "resident": config["resident_bytes"]},
        "step": {"chunk": chunk, "decode_rows": rows,
                 "positions_a_row": positions,
                 "assignments": step_rows * k,
                 "rows_a_held_expert": step_rows * k / published,
                 "experts_touched_a_layer": touched,
                 "bytes": parts,
                 "ms_at_hbm_peak": {key: 1e3 * val / hbm
                                    for key, val in parts.items()},
                 "step_ms_at_hbm_peak": 1e3 * sum(parts.values()) / hbm}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[5001])
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--arms", nargs="+", default=list(ARMS), choices=ARMS)
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0,
                    help="1: walk it on the CPU at the rehearsal sizes")
    ap.add_argument("--least", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rows", type=int, default=64)
    ap.add_argument("--positions", type=int, default=3700)
    ap.add_argument("--child", choices=["serve", "judge"])
    ap.add_argument("--path")
    args = ap.parse_args()
    if args.least:
        print(json.dumps(least(args.rows, args.positions)))
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
