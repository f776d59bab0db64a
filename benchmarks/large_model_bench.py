"""GPT-2 Large-class (774M) single-chip training row — measured.

The flagship row (bench.py) is 350M; this is the same protocol one size
up, answering "does the MFU hold when the model 2.2x's?". Earlier
round-5 attempts at this size died in remote-compile with HTTP 500 —
root-caused this session to a compile-time HBM OOM (dots-remat at
mbs4 wants 18.4 GB; ZeRO-2 single-chip optimizer state for 774M is
~10.9 GB), not infra: full remat at mbs2 x gas32 fits with room.

Run ON the real chip: python benchmarks/large_model_bench.py
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from deepspeed_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

V5E_PEAK_TFLOPS = 197.0
SEQ = 1024


def run_config(mbs, gas, remat_policy):
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu as ds
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    cfg = GPT2Config(vocab_size=50257, n_positions=SEQ, n_embd=1280,
                     n_layer=36, n_head=20, dtype=jnp.bfloat16,
                     remat=True, remat_policy=remat_policy)
    engine, _, _, _ = ds.initialize(model=GPT2LMHeadModel(cfg), config={
        "train_micro_batch_size_per_gpu": mbs,
        "gradient_accumulation_steps": gas,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2},
        "optimizer": {"type": "Adam",
                      "params": {"lr": 3e-4, "weight_decay": 0.1}},
        "steps_per_print": 1000000,
    })
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, cfg.vocab_size,
        (engine.train_batch_size(), SEQ)).astype(np.int32)}
    for _ in range(2):  # compile + settle
        loss = engine.train_batch(batch=batch)
    jax.block_until_ready(loss)
    steps = 5
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(batch=batch)
    jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / steps
    tok_s = engine.train_batch_size() * SEQ / dt
    n = engine.num_parameters
    tf6 = tok_s * 6 * n / 1e12
    return {
        "config": f"mbs{mbs}xgas{gas} remat={remat_policy}",
        "params_m": round(n / 1e6, 1),
        "tokens_per_s_chip": round(tok_s, 1),
        "tflops_6n": round(tf6, 2),
        "mfu_pct_6n": round(100 * tf6 / V5E_PEAK_TFLOPS, 1),
        "loss": round(float(loss), 4),
    }


def main():
    enable_compile_cache()
    out_path = os.path.join(os.path.dirname(__file__),
                            "large_model_results.json")
    result = {"model": "GPT-2 Large-class 774M (36L x 1280 x 20h, seq 1024)",
              "note": "dots remat OOMs at this size on one chip "
                      "(compile-time 18.4G at mbs4 / 16.3G at mbs2 vs "
                      "15.75G HBM); full remat trades recompute for fit. "
                      "Sweep (fresh process each): mbs2xgas32 40.0-40.4%, "
                      "mbs4xgas16 38.0%, mbs6 OOM — this script measures "
                      "the winner; one engine per process (a second "
                      "engine OOMs against the first's live buffers)",
              "rows": []}
    row = run_config(2, 32, "full")
    result["rows"].append(row)
    print(f"[large_model] {row}", flush=True)
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(f"[large_model] -> {out_path}", flush=True)


if __name__ == "__main__":
    main()
