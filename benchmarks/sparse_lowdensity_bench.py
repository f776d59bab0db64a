"""Block-sparse attention at BigBird-realistic density + model-level row
(VERDICT r3 #3).

Two measurements the round-3 microbench did not make:

1. **Kernel rows at density <= 0.16** — the regime block-sparsity exists
   for. Round 3 benchmarked 0.28-0.375, where a causal dense flash kernel
   (effective density 0.5) does a comparable amount of work and the sparse
   kernel's scheduling overhead erased the FLOP savings (0.92-1.31x).
   BigBird-style layouts (sliding window + random + global) at 5-8%
   density carry a 4-6x FLOP advantage over causal flash — the honest
   comparator, this repo's own best dense path.

2. **Model-level training row** — GPT-2 at seq 8k/16k, tokens/s with the
   model's attention routed through the sparse kernel
   (``GPT2Config.sparse_attention``) vs the flash-dense model: the
   repo-native analog of the reference's "up to 6.1x faster GPT-2
   pretraining" claim (docs/_posts/2020-09-09-sparse-attention.md:31).

Writes ``benchmarks/sparse_lowdensity_results.json``. Run ON the chip.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from deepspeed_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402
from attn_bench import timed  # noqa: E402


def kernel_rows():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.attention.flash_attention import flash_attention
    from deepspeed_tpu.ops.sparse_attention.pallas_kernel import (
        block_sparse_flash_attention,
        layout_to_schedule,
    )
    from deepspeed_tpu.ops.sparse_attention.sparsity_config import (
        BigBirdSparsityConfig,
    )

    H, D = 12, 64
    rng = np.random.default_rng(0)
    rows = []

    def loss_of(attn):
        def f(q, k, v):
            return attn(q, k, v).astype(jnp.float32).sum()

        grad_f = jax.grad(f, argnums=(0, 1, 2))

        def scalar(q, k, v):
            gq, gk, gv = grad_f(q, k, v)
            return (gq.astype(jnp.float32).sum() +
                    gk.astype(jnp.float32).sum() +
                    gv.astype(jnp.float32).sum())

        return scalar

    CASES = [
        # (seq, block, window, random, global)
        (8192, 256, 3, 1, 1),     # d ~ 0.15
        (8192, 512, 3, 1, 1),     # d ~ 0.29 (granule-bound floor at 8k)
        (16384, 512, 3, 1, 1),    # d ~ 0.15
        (16384, 256, 3, 1, 1),    # d ~ 0.08
        (32768, 512, 3, 1, 1),    # d ~ 0.08
    ]
    for seq, block, w, r, g in CASES:
        B = max(1, 8192 // seq)
        cfg = BigBirdSparsityConfig(
            num_heads=H, block=block, num_random_blocks=r,
            num_sliding_window_blocks=w, num_global_blocks=g,
            attention="unidirectional")
        layout = cfg.make_layout(seq)
        _, cnt = layout_to_schedule(layout)
        density = float(layout.sum()) / layout[0].size / H
        shape = (B, seq, H, D)
        q, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
                   for _ in range(3))
        row = {"kind": "bigbird_lowdensity_fwd_bwd", "seq": seq,
               "batch": B, "block": block,
               "pattern": f"w{w}r{r}g{g}",
               "layout_density": round(density, 4),
               "max_live_blocks": int(cnt.max())}
        for name, attn in [
            ("flash_dense", lambda q, k, v: flash_attention(
                q, k, v, causal=True)),
            ("pallas_sparse", lambda q, k, v: block_sparse_flash_attention(
                q, k, v, layout, block, causal=True)),
        ]:
            try:
                dt = timed(loss_of(attn), q, k, v, iters=10)
                row[f"{name}_ms"] = round(dt * 1e3, 3)
            except Exception as e:
                row[f"{name}_ms"] = None
                row[f"{name}_error"] = str(e)[:160]
        if row.get("flash_dense_ms") and row.get("pallas_sparse_ms"):
            row["vs_flash_dense"] = round(
                row["flash_dense_ms"] / row["pallas_sparse_ms"], 2)
            # FLOP advantage the layout carries over causal dense
            row["flop_advantage"] = round(0.5 / density, 2)
        rows.append(row)
        print("[sparse_ld]", row, flush=True)
    return rows


def model_rows(seq=8192, block=512):
    """GPT-2 training tokens/s: sparse-attention model vs flash-dense.
    block 512 is the measured-efficient granule (the 256 granule wastes
    the MXU — kernel rows)."""
    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.ops.sparse_attention.sparsity_config import (
        BigBirdSparsityConfig,
    )

    B = 1
    rows = []
    variants = {
        "flash_dense": dict(use_flash_attention=True),
        "bigbird_sparse": dict(sparse_attention=BigBirdSparsityConfig(
            num_heads=12, block=block, num_random_blocks=1,
            num_sliding_window_blocks=3, num_global_blocks=1,
            attention="unidirectional")),
    }
    for name, extra in variants.items():
        cfg = GPT2Config(n_positions=seq, n_embd=768, n_layer=12, n_head=12,
                         remat=True, **extra)
        engine, _, _, _ = ds.initialize(
            model=GPT2LMHeadModel(cfg),
            config={"train_micro_batch_size_per_gpu": B,
                    "gradient_accumulation_steps": 1,
                    "zero_optimization": {"stage": 0},
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                    "bf16": {"enabled": True}, "steps_per_print": 10 ** 9})
        rng = np.random.default_rng(0)
        walls = []
        for i in range(8):
            b = {"input_ids": rng.integers(
                0, 50257, (B, seq)).astype(np.int32)}
            t0 = time.perf_counter()
            loss = engine.train_batch(batch=b)
            jax.block_until_ready(loss)
            walls.append(time.perf_counter() - t0)
        med = float(np.median(walls[3:]))
        row = {"kind": "gpt2_train_row", "variant": name, "seq": seq,
               "batch": B, "median_step_s": round(med, 3),
               "tokens_per_s": round(B * seq / med, 1),
               "loss": round(float(loss), 3)}
        rows.append(row)
        print("[sparse_ld]", row, flush=True)
    if len(rows) == 2 and rows[0]["median_step_s"]:
        rows.append({"kind": "gpt2_train_speedup", "seq": seq,
                     "sparse_vs_flash": round(
                         rows[0]["median_step_s"] / rows[1]["median_step_s"],
                         2)})
        print("[sparse_ld]", rows[-1], flush=True)
    return rows


def main():
    enable_compile_cache()
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "sparse_lowdensity_results.json")
    out = {"kernel": [], "model": []}

    def flush():
        with open(path, "w") as f:
            json.dump(out, f, indent=1)

    out["kernel"] = kernel_rows()
    flush()
    for seq in (8192, 16384):
        out["model"] += model_rows(seq=seq)
        flush()
    print("[sparse_ld] wrote", path, flush=True)


if __name__ == "__main__":
    main()
