"""Real-text training row on the chip (VERDICT r3 #4's BASELINE row).

Trains a GPT-2-class byte-level LM on the committed REAL-prose corpus
(tests/model/fixtures/realtext_*.txt.xz — human-written documentation
English) and reports the held-out perplexity trajectory: the loss curve
on real data, not synthetic tokens. Byte-level vocab because the
environment has no egress for a pretrained BPE; the text statistics are
genuinely Zipfian either way.

Run ON the chip: python benchmarks/realtext_bench.py [--steps N]
"""

from __future__ import annotations

import argparse
import json
import lzma
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

FIXTURES = os.path.join(os.path.dirname(__file__), "..", "tests", "model",
                        "fixtures")


def load(split):
    with lzma.open(os.path.join(FIXTURES, f"realtext_{split}.txt.xz"),
                   "rt") as f:
        return np.frombuffer(f.read().encode("utf-8"), np.uint8)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--eval-every", type=int, default=50)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()  # before the first compile

    import deepspeed_tpu as ds
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    train, val = load("train"), load("val")
    cfg = GPT2Config(vocab_size=256, n_positions=args.seq, n_embd=768,
                     n_layer=12, n_head=12, dtype=jnp.bfloat16)
    engine, _, _, _ = ds.initialize(
        model=GPT2LMHeadModel(cfg),
        config={"train_micro_batch_size_per_gpu": args.batch,
                "gradient_accumulation_steps": 1,
                "zero_optimization": {"stage": 0},
                "optimizer": {"type": "AdamW",
                              "params": {"lr": 3e-4, "weight_decay": 0.01}},
                "scheduler": {"type": "WarmupLR",
                              "params": {"warmup_num_steps": 50}},
                "bf16": {"enabled": True},
                "gradient_clipping": 1.0, "steps_per_print": 10 ** 9})

    rng = np.random.default_rng(0)

    def batch_from(data, n, seed_rng):
        starts = seed_rng.integers(0, len(data) - args.seq - 1, n)
        return {"input_ids": np.stack(
            [data[s:s + args.seq] for s in starts]).astype(np.int32)}

    val_rng = np.random.default_rng(7)
    val_batches = [batch_from(val, args.batch, val_rng) for _ in range(4)]
    eval_fn = None

    def val_ppl():
        nonlocal eval_fn
        if eval_fn is None:
            eval_fn = engine.eval_batch_fn()
        losses = [float(eval_fn(engine.state["params"], b))
                  for b in val_batches]
        return float(np.exp(np.mean(losses)))

    traj = []
    step_walls = []
    for step in range(1, args.steps + 1):
        ts = time.perf_counter()
        loss = float(engine.train_batch(
            batch=batch_from(train, args.batch, rng)))
        step_walls.append(time.perf_counter() - ts)
        if step == 1 or step % args.eval_every == 0:
            ppl = val_ppl()
            traj.append({"step": step, "train_loss": round(loss, 4),
                         "val_ppl": round(ppl, 2)})
            print(f"[realtext] {traj[-1]}", flush=True)
    # steady-state rate: median step wall, warmup/compile excluded (and
    # eval time never counted — it is outside the per-step windows)
    med = float(np.median(step_walls[3:] or step_walls))
    tok_s = args.batch * args.seq / med

    result = {
        "model": "gpt2-125m-class byte-level (vocab 256)",
        "corpus": "real prose fixture (2.8 MB train / 0.2 MB val)",
        "batch": args.batch, "seq": args.seq, "steps": args.steps,
        "trajectory": traj,
        "final_val_ppl": traj[-1]["val_ppl"],
        "tokens_per_s_steady": round(tok_s, 1),
        "ppl_uniform_ceiling": 256.0,
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "realtext_results.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    print(f"[realtext] final val ppl {result['final_val_ppl']} "
          f"({tok_s:.0f} tok/s steady) -> {path}", flush=True)


if __name__ == "__main__":
    main()
