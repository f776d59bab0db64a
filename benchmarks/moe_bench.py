"""Single-chip MoE vs FLOP-matched dense — measured (VERDICT r3 #5).

Anchors the reference's MoE claims with on-chip numbers
(docs/_posts/2021-12-09-deepspeed-moe-nlg.md:40 — "same quality at 5x
lower training cost" rests on MoE adding parameters, not step time):

* ``dense``      — GPT with 4n MLPs everywhere (moe_every=0).
* ``moe_top1``   — every 2nd block is 8-expert Switch-style top-1,
  capacity 1.25. Active FLOPs are IDENTICAL to ``dense`` (each token
  visits one 4n expert), so (t_moe1 - t_dense)/t_dense IS the
  gating+dispatch overhead — the cost of the router, the capacity
  sort/scatter, and the einsum dispatch, isolated.
* ``moe_top2``   — GShard top-2, capacity 1.25: the reference's NLG
  recipe shape; 2x active expert FLOPs on MoE blocks, 8x the MLP
  parameters of its active compute.

Also records the aux-loss (load-balance) trajectory and per-expert token
shares for top-2 over 30 training steps — the router must spread load,
not collapse onto one expert.

Run ON the real chip: python benchmarks/moe_bench.py
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

B, T = 16, 1024
STEPS_TIMED = 8
STEPS_WARM = 3


def build(kind, dispatch_mode="index"):
    import jax.numpy as jnp

    from deepspeed_tpu.models.gpt_moe import GPTMoEConfig, GPTMoEModel

    kw = dict(vocab_size=32768, n_positions=T, n_embd=1024, n_layer=8,
              n_head=16, capacity_factor=1.25, drop_tokens=True,
              moe_dispatch_mode=dispatch_mode, dtype=jnp.bfloat16)
    if kind == "dense":
        cfg = GPTMoEConfig(moe_every=0, **kw)
    elif kind == "moe_top1":
        cfg = GPTMoEConfig(moe_every=2, num_experts=8, k=1, **kw)
    elif kind == "moe_top2":
        cfg = GPTMoEConfig(moe_every=2, num_experts=8, k=2, **kw)
    return GPTMoEModel(cfg)


def run(kind, steps=STEPS_WARM + STEPS_TIMED, record_aux=False,
        dispatch_mode="index"):
    import jax

    import deepspeed_tpu as ds

    model = build(kind, dispatch_mode)
    engine, _, _, _ = ds.initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": B,
                "gradient_accumulation_steps": 1,
                "zero_optimization": {"stage": 0},
                "optimizer": {"type": "AdamW", "params": {"lr": 3e-4}},
                "bf16": {"enabled": True}, "steps_per_print": 10 ** 9})
    rng = np.random.default_rng(0)
    batches = [{"input_ids": rng.integers(0, 32768, (B, T)).astype(np.int32)}
               for _ in range(steps)]

    aux_fn = None
    if record_aux:
        import jax.numpy as jnp

        def aux_eval(params, batch):
            loss, aux = model.apply({"params": params}, batch,
                                    deterministic=True)
            return aux

        aux_fn = jax.jit(aux_eval)

    walls, aux_traj = [], []
    n_params = None
    for i, b in enumerate(batches):
        t0 = time.perf_counter()
        loss = engine.train_batch(batch=b)
        jax.block_until_ready(loss)
        walls.append(time.perf_counter() - t0)
        if record_aux:
            aux_traj.append(float(aux_fn(engine.state["params"], b)))
        if n_params is None:
            n_params = engine.num_parameters
    timed = walls[STEPS_WARM:]
    med = float(np.median(timed))
    return {
        "kind": kind,
        "dispatch_mode": dispatch_mode if kind != "dense" else None,
        "params_m": round(n_params / 1e6, 1),
        "median_step_s": round(med, 4),
        "tokens_per_s": round(B * T / med, 1),
        "loss_final": float(np.round(float(loss), 4)),
        "aux_trajectory": [round(a, 5) for a in aux_traj] or None,
    }


def expert_balance():
    """Per-expert token shares after 30 top-2 training steps on one fixed
    batch distributionally: the router must spread load."""
    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.moe.layer import MoE

    model = build("moe_top2")
    engine, _, _, _ = ds.initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": B,
                "gradient_accumulation_steps": 1,
                "zero_optimization": {"stage": 0},
                "optimizer": {"type": "AdamW", "params": {"lr": 3e-4}},
                "bf16": {"enabled": True}, "steps_per_print": 10 ** 9})
    import jax.numpy as jnp  # noqa: F401

    def aux_eval(params, batch):
        return model.apply({"params": params}, batch,
                           deterministic=True)[1]

    aux_fn = jax.jit(aux_eval)
    rng = np.random.default_rng(1)
    aux_traj = []
    for _ in range(30):
        b = {"input_ids": rng.integers(0, 32768, (B, T)).astype(np.int32)}
        engine.train_batch(batch=b)
        aux_traj.append(float(aux_fn(engine.state["params"], b)))

    # fish the expert counts out of every MoE block with a probe apply
    import flax

    probe = {"input_ids": rng.integers(0, 32768, (B, T)).astype(np.int32)}

    counts = {}

    # params as an ARGUMENT — a closure would bake 370M weights into the
    # HLO as constants (a program the remote-compile service rejects)
    def capture(p, batch):
        return model.apply({"params": p}, batch, deterministic=True,
                           capture_intermediates=lambda m, _: isinstance(m, MoE))

    out, inter = jax.jit(capture)(engine.state["params"], probe)
    flat = flax.traverse_util.flatten_dict(inter["intermediates"])
    for path, vals in flat.items():
        if path[-1] == "__call__":
            _, _, exp_counts = vals[0]
            counts["/".join(path[:-1])] = np.asarray(exp_counts, np.float64)
    shares = {k: (v / v.sum()).round(4).tolist() for k, v in counts.items()}
    return aux_traj, shares


from deepspeed_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402


def main():
    enable_compile_cache()
    out_path = os.path.join(os.path.dirname(__file__),
                            "moe_bench_results.json")
    result = {
        "config": {"batch": B, "seq": T, "n_embd": 1024, "n_layer": 8,
                   "experts": 8, "capacity_factor": 1.25},
        "rows": [],
    }

    def flush():
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)

    # A/B the two dispatch materializations at the same routing decisions:
    # "einsum" = the reference's dense one-hot form, "index" = the
    # TPU-native scatter/gather default (moe/sharded_moe.py module doc)
    for kind, mode in (("dense", "index"),
                       ("moe_top1", "einsum"), ("moe_top1", "index"),
                       ("moe_top2", "einsum"), ("moe_top2", "index")):
        result["rows"].append(run(kind, dispatch_mode=mode))
        print(f"[moe_bench] row done: {result['rows'][-1]}", flush=True)
        flush()  # partial results survive a late failure
    rows = result["rows"]
    by = {(r["kind"], r["dispatch_mode"]): r["median_step_s"] for r in rows}
    dense_t = by[("dense", None)]
    moe1_t = by[("moe_top1", "index")]
    overhead_pct = 100.0 * (moe1_t - dense_t) / dense_t
    result["gating_dispatch_overhead_pct"] = round(overhead_pct, 1)
    result["index_vs_einsum_speedup"] = {
        k: round(by[(k, "einsum")] / by[(k, "index")], 3)
        for k in ("moe_top1", "moe_top2")}
    flush()
    try:
        aux_traj, shares = expert_balance()
        result["top2_aux_loss_trajectory"] = [round(a, 4) for a in aux_traj]
        result["top2_expert_token_shares"] = shares
    except Exception as e:  # the balance probe is additive — keep the rows
        result["balance_error"] = str(e)[:200]
    flush()
    for r in rows:
        mode = f" [{r['dispatch_mode']}]" if r["dispatch_mode"] else ""
        print(f"[moe_bench] {r['kind']}{mode}: {r['params_m']}M params, "
              f"{r['tokens_per_s']} tok/s (step {r['median_step_s']}s)",
              flush=True)
    print(f"[moe_bench] gating+dispatch overhead (top1 vs FLOP-matched "
          f"dense): {overhead_pct:.1f}%", flush=True)
    print(f"[moe_bench] -> {out_path}", flush=True)


if __name__ == "__main__":
    main()
