"""int8 KV-cache decode attention vs bf16 — measured on the real chip.

Decode is HBM-bandwidth bound: every generated token re-reads the whole
live cache. Quantizing the cache to int8 (per-row scales,
``quantize_kv_rows``) halves those bytes; the kernel folds the scales
into the score/probability rows so no dequantized block is ever
materialized (ops/attention/decode_attention.py). This bench times the
kernel at generation-realistic shapes (the 350M flagship head layout and
a GQA serving layout) with the cache fully live.

Run ON the real chip: python benchmarks/kv_int8_bench.py
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from deepspeed_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

ITERS = 64   # kernel calls per on-device loop (amortizes host dispatch)
REPS = 7     # loop dispatches; median taken


def run_case(B, H, KV, D, S, block=None):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.attention.decode_attention import (
        decode_attention, pack_int8_sublanes, pick_block_s,
        quantize_kv_rows)

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.bfloat16)
    lengths = jnp.full((B,), S, jnp.int32)  # fully live cache
    k8, ks = quantize_kv_rows(k)
    v8, vs = quantize_kv_rows(v)
    ds = lambda c: c.transpose(0, 1, 3, 2)  # noqa: E731 (B,KV,D,S) layout
    k, v, k8, v8 = ds(k), ds(v), ds(k8), ds(v8)
    if block is None:
        block = pick_block_s(S)

    # time an ON-DEVICE chain of ITERS kernel calls — a single host
    # dispatch per measurement, so per-call host dispatch latency
    # divides out. Each iteration's q depends on the previous output via
    # a tiny non-foldable term (q + out*1e-30), so the calls serialize
    # and cannot be DCE'd; cache operands are ARGUMENTS (a closure would
    # bake them into the HLO as constants).
    def chain(kernel_call):
        def fn(qq, *ops):
            def body(i, q_carry):
                out = kernel_call(q_carry, *ops)
                return q_carry + out * jnp.asarray(1e-30, out.dtype)
            return jax.lax.fori_loop(0, ITERS, body, qq)
        return jax.jit(fn)

    f_bf16 = chain(lambda qq, kk, vv: decode_attention(
        qq, kk, vv, lengths, block_s=block))
    f_int8 = chain(lambda qq, kk, vv, kss, vss: decode_attention(
        qq, kk, vv, lengths, k_scale=kss, v_scale=vss, block_s=block))

    def med(fn, *ops):
        fn(q, *ops).block_until_ready()  # compile
        walls = []
        for _ in range(REPS):
            t0 = time.perf_counter()
            fn(q, *ops).block_until_ready()
            walls.append(time.perf_counter() - t0)
        return float(np.median(walls)) / ITERS

    t_bf16 = med(f_bf16, k, v)
    t_int8 = med(f_int8, k8, v8, ks, vs)
    # int32-packed container (the kv_cache_packed default): same bytes,
    # free in-kernel bitcast unpack — times any container overhead
    t_i32 = med(f_int8, pack_int8_sublanes(k8), pack_int8_sublanes(v8),
                ks, vs)
    single_bf16 = jax.jit(lambda qq, kk, vv: decode_attention(
        qq, kk, vv, lengths, block_s=block))
    single_int8 = jax.jit(lambda qq, kk, vv, kss, vss: decode_attention(
        qq, kk, vv, lengths, k_scale=kss, v_scale=vss, block_s=block))
    # numerics: int8 output tracks bf16 closely
    err = float(jnp.max(jnp.abs(
        single_int8(q, k8, v8, ks, vs).astype(jnp.float32)
        - single_bf16(q, k, v).astype(jnp.float32))))
    kv_bytes_bf16 = 2 * B * KV * S * D * 2
    kv_bytes_int8 = 2 * B * KV * S * D * 1 + 2 * B * KV * S * 4
    return {
        "B": B, "H": H, "KV": KV, "D": D, "cache_len": S, "block_s": block,
        "bf16_ms": round(t_bf16 * 1e3, 3),
        "int8_ms": round(t_int8 * 1e3, 3),
        "int8_i32packed_ms": round(t_i32 * 1e3, 3),
        "speedup": round(t_bf16 / t_int8, 3),
        "speedup_i32packed": round(t_bf16 / t_i32, 3),
        "kv_mb_bf16": round(kv_bytes_bf16 / 2 ** 20, 1),
        "kv_mb_int8": round(kv_bytes_int8 / 2 ** 20, 1),
        "max_abs_err": round(err, 4),
    }


def run_e2e(key, prompt_len, gen_len, arms=("bf16", "int8"), note="",
            batch=2, smax=8192, batch_by_arm=None):
    """End-to-end generation throughput through the public generate():
    the measurement behind the ``e2e_generate*`` keys. Arms: bf16 cache,
    int8 (the kv_cache_packed int32-container default), int8_s8 (the
    plain-int8 layout, for the container A/B). ``batch_by_arm`` lets the
    capacity-throughput row serve each cache dtype at ITS measured max
    batch (the serving-aggregate comparison)."""
    import jax
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models.transformer_lm import (TransformerConfig,
                                                     TransformerLM)

    rows = []
    for arm in arms:
        B = (batch_by_arm or {}).get(arm, batch)
        prompts = np.random.default_rng(0).integers(
            0, 50257, (B, prompt_len)).astype(np.int32)
        cfg = TransformerConfig(
            vocab_size=50257, max_seq_len=smax, n_embd=1024, n_layer=24,
            n_head=16, kv_cache_quant=arm != "bf16",
            kv_cache_packed=arm != "int8_s8")
        eng = ds.init_inference(TransformerLM(cfg), config={"dtype": "bf16"})
        jax.block_until_ready(  # compile prefill+decode
            eng.generate(prompts, max_new_tokens=gen_len))
        walls = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(
                eng.generate(prompts, max_new_tokens=gen_len))
            walls.append(time.perf_counter() - t0)
        sec = float(np.median(walls))
        rows.append({"kv": arm, "B": B, "gen_s": round(sec, 3),
                     "tok_s": round(B * gen_len / sec, 1),
                     "_raw_tok_s": B * gen_len / sec})
        print(f"[kv_int8] e2e {key} {rows[-1]}", flush=True)
        del eng
    out = {"config": {"max_seq_len": smax, "prompt": prompt_len,
                      "gen": gen_len, "model": "350m-class", "note": note},
           "rows": rows}
    by = {r["kv"]: r.pop("_raw_tok_s") for r in rows}  # ratio from raw,
    # not the display-rounded tok_s
    if "bf16" in by and "int8" in by:
        out["e2e_speedup"] = round(by["int8"] / by["bf16"], 3)
    out_path = os.path.join(os.path.dirname(__file__),
                            "kv_int8_results.json")
    result = json.load(open(out_path)) if os.path.exists(out_path) else {}
    result[key] = out
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(f"[kv_int8] {key} -> {out_path}", flush=True)


def main():
    if "--e2e-32k" not in sys.argv:
        # the --e2e-32k parent only spawns one process per arm; each arm
        # needs the chip, so the parent stays off JAX altogether
        enable_compile_cache()
    if "--e2e" in sys.argv:
        run_e2e("e2e_generate", 512, 1024,
                arms=("bf16", "int8", "int8_s8"),
                note="decode-dominated; live 512->1536")
        run_e2e("e2e_generate_long_prompt", 4096, 256,
                note="pre-fix this config OOM-crashed the worker (prefill "
                     "attended over the allocated cache)")
        return
    here = os.path.dirname(os.path.abspath(__file__))

    def capacity_32k_batches():
        """Each arm's measured max batch, read from the capacity
        artifact so a re-measured ladder automatically reflows here."""
        with open(os.path.join(here, "kv_capacity_results_32k.json")) as f:
            caps = json.load(f)["max_batch"]
        return {"bf16": caps["bf16"], "int8": caps["int8"]}

    if "--e2e-32k-arm" in sys.argv:
        # internal: one arm in this process (the 13 GB bf16 cache does
        # not reliably free before the next arm's allocation — same
        # isolation rationale as kv_capacity_bench)
        arm = sys.argv[sys.argv.index("--e2e-32k-arm") + 1]
        run_e2e(f"e2e_serving_32k_{arm}", 512, 128, arms=(arm,),
                smax=32768, batch_by_arm=capacity_32k_batches())
        return
    if "--e2e-32k" in sys.argv:
        # aggregate SERVING throughput at 32k context: each cache dtype
        # runs at its own measured max batch (kv_capacity_results_32k) —
        # the capacity win expressed as tokens/s/chip. One subprocess
        # per arm; merge into a single artifact key and always clean the
        # per-arm temp keys, even when an arm fails.
        import subprocess

        out_path = os.path.join(here, "kv_int8_results.json")
        merged = None
        try:
            for arm in ("bf16", "int8"):
                subprocess.run([sys.executable, os.path.abspath(__file__),
                                "--e2e-32k-arm", arm], check=True, cwd=here)
            result = json.load(open(out_path))
            rows = [result[f"e2e_serving_32k_{arm}"]["rows"][0]
                    for arm in ("bf16", "int8")]
            # ratio from gen_s (3-decimal), not the 1-decimal tok_s
            rate = {r["kv"]: r["B"] * 128 / r["gen_s"] for r in rows}
            merged = {
                "config": {"max_seq_len": 32768, "prompt": 512, "gen": 128,
                           "model": "350m-class",
                           "note": "each arm at its measured max batch at "
                                   "S=32768 (kv_capacity_results_32k.json);"
                                   " aggregate tok/s"},
                "rows": rows,
                "serving_throughput_ratio": round(
                    rate["int8"] / rate["bf16"], 3),
            }
        finally:
            res = json.load(open(out_path))
            for arm in ("bf16", "int8"):
                res.pop(f"e2e_serving_32k_{arm}", None)
            if merged is not None:
                res["e2e_serving_32k"] = merged
            with open(out_path, "w") as f:
                json.dump(res, f, indent=1)
        print(f"[kv_int8] e2e_serving_32k -> {out_path}: "
              f"{res['e2e_serving_32k']}", flush=True)
        return
    out_path = os.path.join(os.path.dirname(__file__),
                            "kv_int8_results.json")
    result = json.load(open(out_path)) if os.path.exists(out_path) else {}
    result.update({"iters": ITERS, "rows": []})
    cases = [
        # 350M-flagship head layout (H=16, D=64), growing cache
        (8, 16, 16, 64, 2048, None),
        (8, 16, 16, 64, 8192, None),
        (8, 16, 16, 64, 16384, None),
        # GQA 4x serving layout (llama-style), long cache
        (4, 32, 8, 128, 8192, None),
        (4, 32, 8, 128, 16384, None),
        # long-context block sweep: grid overhead, not bandwidth, bounds
        # the default 1024 block at 16k — bigger blocks amortize it
        (8, 16, 16, 64, 16384, 2048),
        (8, 16, 16, 64, 16384, 4096),
    ]
    for case in cases:
        row = run_case(*case)
        result["rows"].append(row)
        print(f"[kv_int8] {row}", flush=True)
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
    print(f"[kv_int8] -> {out_path}", flush=True)


if __name__ == "__main__":
    main()
