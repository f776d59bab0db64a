"""Servable capacity at long context: bf16 vs int8 KV cache — measured.

The KV cache dominates serving memory at long context (GPT-2 350M-class:
~100 KB per position per sequence in bf16 — ~1.6 GB/sequence at 16k,
~3.2 GB at 32k — vs ~0.7 GB of weights). ``kv_cache_quant=True`` halves
it. This bench walks a batch-size ladder on the real chip and records
the largest batch each cache dtype can actually serve (allocate full
cache, prefill, decode tokens) at ``max_seq_len = KV_CAPACITY_SEQ``
(default 16384; 32768 writes the suffixed artifact).

Each trial runs in its OWN subprocess: earlier trials' device buffers
must not change later trials' headroom. The engine AOT-compiles the
decode program before prefill buffers go live (inference/engine.py
``_compile_decode_scan``), so the compile-time HBM check is not
inflated by transient double-residency at the prefill→decode boundary.

Run ON the real chip: [KV_CAPACITY_SEQ=32768] python benchmarks/kv_capacity_bench.py
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

SEQ = int(os.environ.get("KV_CAPACITY_SEQ", 16384))  # 32768 for the
# long-context row (writes kv_capacity_results_32k.json)
PROMPT = 64
NEW_TOKENS = 8

TRIAL = """
import sys
sys.path.insert(0, {repo!r})
from deepspeed_tpu.utils.compile_cache import enable_compile_cache
enable_compile_cache()
import numpy as np
import deepspeed_tpu as ds
from deepspeed_tpu.models.transformer_lm import (TransformerConfig,
                                                 TransformerLM)
cfg = TransformerConfig(vocab_size=50257, max_seq_len={seq}, n_embd=1024,
                        n_layer=24, n_head=16, kv_cache_quant={quant},
                        kv_cache_packed={packed})
eng = ds.init_inference(TransformerLM(cfg), config={{"dtype": "bf16"}})
prompts = np.random.default_rng(0).integers(
    0, 50257, ({batch}, {prompt})).astype(np.int32)
toks = eng.generate(prompts, max_new_tokens={new})
import jax; jax.block_until_ready(toks)
print("TRIAL_OK", toks.shape)
"""


OOM_MARKS = ("RESOURCE_EXHAUSTED", "Out of memory", "Ran out of memory",
             "Exceeded hbm capacity")


def try_batch(B: int, quant: bool, packed: bool = True) -> bool:
    """True = serves; False = HBM-infeasible. Infra failures (timeouts,
    persistent non-OOM errors) RAISE — they must never be recorded as a
    measured capacity boundary."""
    here = os.path.dirname(os.path.abspath(__file__))
    code = TRIAL.format(repo=os.path.dirname(here), seq=SEQ,
                        quant=quant, packed=packed, batch=B, prompt=PROMPT,
                        new=NEW_TOKENS)
    try:
        proc = subprocess.run([sys.executable, "-c", code], timeout=900,
                              capture_output=True, text=True)
    except subprocess.TimeoutExpired:
        raise RuntimeError(
            f"trial B={B} quant={quant} timed out (900s) — infra, "
            f"not a capacity result")
    if "TRIAL_OK" in proc.stdout:
        return True
    err = proc.stderr or ""
    if any(m in err for m in OOM_MARKS):
        return False
    tail = " | ".join(err.strip().splitlines()[-3:])[-300:]
    raise RuntimeError(
        f"trial B={B} quant={quant} packed={packed} failed for a "
        f"non-OOM reason: {tail}")


def main():
    suffix = "" if SEQ == 16384 else f"_{SEQ // 1024}k"
    out_path = os.path.join(os.path.dirname(__file__),
                            f"kv_capacity_results{suffix}.json")
    result = {"seq": SEQ, "model": "gpt2-350m-class (24L, 1024d, 16h)",
              "ladder": {}, "max_batch": {}}
    # ~100 KB/position/sequence bf16 KV, ~55 KB int8 (cache + scales);
    # ladders run past the expected boundary so a rung is never reported
    # as the maximum merely because the ladder ended there (gap-walk +
    # climb logic below closes any remainder). Arms:
    #   bf16     — full-precision cache
    #   int8_s8  — plain-int8 layout (the round-5 double-buffering
    #              negative; fixed by the carry-DUS scan, kept for A/B)
    #   int8     — the kv_cache_packed int32 container (default)
    scale = 16384 / SEQ  # halve the rungs when the cache doubles
    rung = lambda b: max(1, int(b * scale))  # noqa: E731
    for quant, packed, label, ladder in (
            (False, True, "bf16", tuple(dict.fromkeys(
                rung(b) for b in (3, 4, 5, 6, 7, 8, 9)))),
            (True, False, "int8_s8", tuple(dict.fromkeys(
                rung(b) for b in (4, 6, 8, 10, 12, 14, 16)))),
            (True, True, "int8", tuple(dict.fromkeys(
                rung(b) for b in (4, 6, 8, 10, 12, 13, 14, 15, 16, 18))))):
        rows = {}
        best, first_fail = 0, None
        for B in ladder:
            ok = try_batch(B, quant, packed)
            rows[B] = ok
            print(f"[kv_capacity] {label} B={B}: {'ok' if ok else 'OOM'}",
                  flush=True)
            if ok:
                best = B
            else:
                first_fail = B
                break
        if best == 0 and first_fail is not None:
            # the ladder's first rung already failed; walk down so the
            # reported max is measured, not assumed
            for B in range(first_fail - 1, 0, -1):
                ok = try_batch(B, quant, packed)
                rows[B] = ok
                print(f"[kv_capacity] {label} B={B}: "
                      f"{'ok' if ok else 'OOM'}", flush=True)
                if ok:
                    best = B
                    break
        elif first_fail is not None and first_fail - best > 1:
            # the failure landed past a ladder gap: walk the gap upward so
            # max_batch is the true boundary, never a rung artifact
            for B in range(best + 1, first_fail):
                ok = try_batch(B, quant, packed)
                rows[B] = ok
                print(f"[kv_capacity] {label} B={B}: "
                      f"{'ok' if ok else 'OOM'}", flush=True)
                if ok:
                    best = B
                else:
                    break
        elif first_fail is None:
            # every rung passed — keep climbing until a measured failure,
            # capped at 2x the ladder's last rung (each trial costs
            # minutes; past the cap the arm is reported as bounded)
            B, cap = best + 1, 2 * ladder[-1]
            while B <= cap:
                ok = try_batch(B, quant, packed)
                rows[B] = ok
                print(f"[kv_capacity] {label} B={B}: "
                      f"{'ok' if ok else 'OOM'}", flush=True)
                if not ok:
                    break
                best = B
                B += 1
            else:
                result.setdefault("bounded", []).append(label)
                print(f"[kv_capacity] {label}: still serving at the "
                      f"B={cap} climb cap — max_batch is a lower bound",
                      flush=True)
        result["ladder"][label] = rows
        result["max_batch"][label] = best
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
    bf, i8 = result["max_batch"]["bf16"], result["max_batch"]["int8"]
    result["capacity_ratio"] = round(i8 / bf, 2) if bf else None
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(f"[kv_capacity] max batch at seq {SEQ}: bf16={bf} int8={i8} "
          f"-> {result['capacity_ratio']}x", flush=True)


if __name__ == "__main__":
    main()
