"""Train a model whose bf16 parameters EXCEED device HBM on one chip.

The measured analog of the reference's ZeRO-Infinity headline ("13B
trainable on one 32 GB V100", docs/_pages/training.md:302): an 8.5B-param
llama-style model — 17.1 GB of bf16 parameters vs 16 GB of HBM (1.07x),
57 GB counting grads+optimizer vs HBM (3.6x) — trains on the single
v5e chip via `zero_optimization.offload_param` streaming
(runtime/zero/param_offload.py).

Placement on this host (125 GB DRAM, ~80 GB free SSD):
  params bf16        17 GB  host DRAM (offload_param.device=cpu)
  fp32 master        34 GB  host DRAM (offload_optimizer.swap_master=false)
  Adam moments       68 GB  NVMe      (offload_optimizer.device=nvme)
  grads fp32         34 GB  host DRAM, freed progressively by the update

Protocol: ONE fixed batch, >=4 steps — the loss must decrease
monotonically (memorization), proving the full fwd/bwd/update loop is
real. Per-phase wall times from the runner's instrumentation; host RSS
sampled per step. Single process, sync points only at step boundaries.

Run ON the real chip (no platform override):
    python benchmarks/param_offload_bench.py [--layers N] [--steps K]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def rss_gb() -> float:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS"):
                return int(line.split()[1]) / 1e6
    return -1.0


def make_params(model, batch, seed=0):
    """Host param tree WITHOUT running flax init (8.5B fp32 init on a
    single core would dominate the run): eval_shape gives the structure,
    numpy fills it — randn*0.02 for kernels/embeddings, ones for norm
    scales, zeros for biases. Statistically equivalent to the module's
    init for this purpose."""
    import jax
    import ml_dtypes

    rngs = {"params": jax.random.PRNGKey(seed)}
    shapes = jax.eval_shape(lambda: model.init(rngs, batch))["params"]
    rng = np.random.default_rng(seed)

    def fill(path, sds):
        name = str(getattr(path[-1], "key", ""))
        shape, dtype = sds.shape, sds.dtype
        if name == "scale":          # rmsnorm gain
            return np.ones(shape, np.dtype(dtype))
        if name == "bias":
            return np.zeros(shape, np.dtype(dtype))
        n = int(np.prod(shape))
        out = np.empty(n, ml_dtypes.bfloat16)
        CH = 1 << 24
        for lo in range(0, n, CH):      # chunked: no fp32 full-size copy
            hi = min(lo + CH, n)
            out[lo:hi] = (rng.standard_normal(hi - lo, np.float32) *
                          0.02).astype(ml_dtypes.bfloat16)
        return out.reshape(shape)

    return jax.tree_util.tree_map_with_path(fill, shapes)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=34)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--nvme", default="/tmp/ds_param_bench_nvme")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "param_offload_results.json"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import deepspeed_tpu as ds
    from deepspeed_tpu.models.transformer_lm import (
        TransformerLM,
        transformer_config,
    )

    cfg = transformer_config(
        "llama", vocab_size=32000, max_seq_len=args.seq, n_embd=4096,
        n_layer=args.layers, n_head=32, mlp_ratio=3.5, dtype=jnp.bfloat16)
    model = TransformerLM(cfg)

    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, cfg.vocab_size, (args.batch, args.seq)).astype(np.int32)}

    t0 = time.perf_counter()
    params = make_params(model, batch)
    n_params = sum(int(np.prod(np.shape(l)))
                   for l in jax.tree_util.tree_leaves(params))
    param_gb = sum(np.asarray(l).nbytes
                   for l in jax.tree_util.tree_leaves(params)) / 1e9
    dev = jax.devices()[0]
    hbm_gb = 16.0
    try:
        stats = dev.memory_stats()
        if stats and stats.get("bytes_limit"):
            hbm_gb = stats["bytes_limit"] / 1e9
    except Exception:
        pass
    print(f"[bench] {n_params / 1e9:.2f}B params, {param_gb:.1f} GB bf16 "
          f"vs {hbm_gb:.1f} GB HBM ({param_gb / hbm_gb:.2f}x); init "
          f"{time.perf_counter() - t0:.0f}s rss={rss_gb():.1f} GB",
          flush=True)

    os.makedirs(args.nvme, exist_ok=True)
    t1 = time.perf_counter()
    engine, _, _, _ = ds.initialize(
        model=model, model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": args.batch,
            "gradient_accumulation_steps": 1,
            "zero_optimization": {
                "offload_param": {"device": "cpu"},
                "offload_optimizer": {"device": "nvme",
                                      "nvme_path": args.nvme,
                                      "swap_master": False},
            },
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 1e-4, "weight_decay": 0.0}},
            "bf16": {"enabled": True},
            "gradient_clipping": 1.0,
            "steps_per_print": 1,
        })
    del params
    print(f"[bench] engine built in {time.perf_counter() - t1:.0f}s "
          f"rss={rss_gb():.1f} GB", flush=True)

    steps = []
    for i in range(args.steps):
        ts = time.perf_counter()
        loss = float(engine.train_batch(batch=batch))
        wall = time.perf_counter() - ts
        row = {"step": i + 1, "loss": loss, "wall_s": round(wall, 2),
               "rss_gb": round(rss_gb(), 1),
               "grad_norm": float(engine.get_global_grad_norm()),
               "timings": {k: round(v, 2) for k, v in
                           engine._param_offload.last_timings.items()}}
        steps.append(row)
        print(f"[bench] {json.dumps(row)}", flush=True)
        # flush partial rows every step: an hours-long run that dies
        # late must still leave an artifact
        with open(args.out + ".partial", "w") as f:
            json.dump({"steps": steps}, f, indent=1)

    losses = [s["loss"] for s in steps]
    decreasing = all(b < a for a, b in zip(losses, losses[1:]))
    tokens = args.batch * args.seq
    best_wall = min(s["wall_s"] for s in steps[1:]) if len(steps) > 1 \
        else steps[0]["wall_s"]
    result = {
        "model": {"params_b": round(n_params / 1e9, 2),
                  "bf16_gb": round(param_gb, 1),
                  "hbm_gb": round(hbm_gb, 1),
                  "params_vs_hbm": round(param_gb / hbm_gb, 2),
                  "n_layer": cfg.n_layer, "n_embd": cfg.n_embd,
                  "seq": args.seq, "batch": args.batch},
        "placement": {"params": "cpu", "master": "cpu(dram)",
                      "moments": "nvme", "grads": "cpu(progressive)"},
        "steps": steps,
        "loss_decreasing": decreasing,
        "tokens_per_step": tokens,
        "tokens_per_s_best": round(tokens / best_wall, 1),
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"[bench] loss_decreasing={decreasing} -> {args.out}", flush=True)
    if not decreasing:
        sys.exit(1)


if __name__ == "__main__":
    main()
