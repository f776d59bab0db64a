"""Benchmark: flagship north-star row — GPT-2 350M causal-LM training on
one chip (the best measured MFU config from the benchmarks/model_bench.py
sweeps; VERDICT r2 next-#1).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "tokens/s/chip", "vs_baseline": N}

``python bench.py serving`` instead runs the Poisson-arrival serving row:
continuous batching (deepspeed_tpu/serving/) vs the batch-synchronous
"gang" discipline ``generate()`` imposes, SAME engine/kernels/slot count,
only the admission policy differs. Reports req/s and p50/p99 TTFT for
both arms; ``vs_baseline`` = continuous req/s over gang req/s.

``python bench.py spec`` runs the speculative-decoding row: n-gram
(prompt-lookup) draft + one fixed-shape ``verify_k`` forward vs plain
one-token decode, same engine/slots/workload, on a repetitive-text
workload. Reports tokens per slot-decode-step (plain pins this at
exactly 1.0), draft acceptance rate and draft overhead, and checks the
greedy outputs are bitwise identical between arms; ``vs_baseline`` =
spec tokens/s over plain tokens/s (wall-clock).

``python bench.py serving-stall`` runs the stall-free admission row:
chunked prefill interleaved with decode plus batched bucketed admission
(``prefill_chunk > 0``) vs the PR-2 serial whole-prompt admission
(``prefill_chunk=0``), SAME engine/kernels/slots/policy, only the
admission path differs. The workload mixes short prompts with long ones
whose serial prefill stalls every live decode slot (and, landing between
power-of-two width buckets, pads to the next bucket in serial but only
to the next chunk when chunked); reports TTFT p50/p99, per-token p99,
p50/p99 inter-token step gap and req/s for both arms (median of 3
interleaved replays), checks greedy outputs are bitwise identical across
arms and replays and that the decode program did not recompile after
warmup; ``vs_baseline`` = serial inter-token-gap p99 over stall-free
inter-token-gap p99 (>1 means the streaming tail shrank).

``python bench.py paging`` runs the paged-KV row: a PagedKVPool server
(refcounted pages + radix-trie prefix cache + copy-on-write) vs the
contiguous SlotPool at the SAME KV HBM budget, on a >=50%-shared-prefix
workload. The paged arm runs 2x the slots in the same bytes (shared
pages are mapped, not copied); reports peak resident requests at equal
HBM (headline, gate >= 1.5), served requests per KV-GB, TTFT cold vs
prefix-hit, prefix hit rate, CoW forks, peak pages in use, and the
zero-recompile gate after a warm all-hits replay; greedy outputs must
be bitwise identical across arms.

``python bench.py serving-decode`` runs the raw-decode-speed row: the
fused Pallas paged-attention decode kernel plus overlapped host
scheduling (``paged_kv={"kernel": "on"}, overlap=True``) vs the dense
gather/scatter oracle with serial stepping, SAME engine/pool geometry
on a decode-heavy workload; greedy outputs must be bitwise identical
across arms and replications. Reports p50/p99 inter-token step gap
(headline: the kernel arm's p99; ``vs_baseline`` = dense p99 over it),
per-token latency, tokens/s ratio, and MFU from the runtime cost model
(``check_regression.py --warn-metric detail.efficiency.mfu``); carries
the zero-recompile gate (``--max-recompiles 0``) and the
``--signatures`` manifest for ``--require-signature-match``.

``python bench.py serving-tp`` runs the multi-chip serving row on the
forced 8-device CPU host (``--xla_force_host_platform_device_count=8``,
exported before the row's own jax import): TP=1 (mesh ``data=8``) vs
TP=2 (``data=4, model=2``) with bitwise-identical greedy outputs across
mesh shapes (only shardings move; jit signatures do not), plus a DP=2
``ReplicaRouter`` over two paged replicas on disjoint 4-device meshes
vs one identically-configured replica, on a 4-session-group workload
whose prefixes overflow a single page pool. Headline ``vs_baseline`` =
router req/s over single-replica req/s (session affinity keeps each
group's prefix resident where the single pool thrashes), gated by
``check_regression.py --threshold 1.5`` together with
``--max-recompiles 0 --require-zero-leaks --require-signature-match``.

``python bench.py serving-async`` runs the async front-end row: the
stdlib asyncio HTTP/SSE server (deepspeed_tpu/serving/frontend/) on a
localhost socket with Poisson arrivals at three priority tiers
(interactive / standard / batch) from a hand-rolled asyncio client.
The standard tier's TTFT contract is unmeetable by construction, so
its SLO burn pages and the priority scheduler sheds the batch tier
(HTTP 429 + Retry-After) while interactive traffic keeps flowing.
Headline ``value`` (and ``detail.efficiency.goodput_slo``, gated by
``check_regression.py --min-goodput``) is the TOP-class (interactive)
goodput measured while the bottom class is actively shed; the row also
gates on zero slot leaks, clean ``check_invariants``, complete request
timelines and zero post-warmup recompiles across the whole
HTTP -> bridge -> step-thread path (``--require-zero-leaks`` +
``--max-recompiles 0``).

``--json <path>`` additionally writes the full result object to
``<path>`` (e.g. ``BENCH_serving.json``) for dashboards/drivers.
``check_regression.py`` diffs two such files and gates on named
metrics (and on ``detail.recompiles_after_warmup`` via
``--max-recompiles`` — every serving row reports it from the runtime
recompile watchdog after a post-run warm replay).  The static side of
the same gate is ``--lint-json`` (repeatable): an all-tiers
``bin/graftlint --json`` report plus a ``bin/graftlint --tier own
deepspeed_tpu/serving --json`` ownership report, both held at
``--max-lint-errors 0`` — the lifecycle invariants the chaos row
audits at runtime are proven on every exception path before the row
runs.

``--trace <path>`` additionally writes a Chrome trace-event / Perfetto
JSON timeline (open at ui.perfetto.dev) for the row: serving rows run
one extra traced replay on the warmed server (step-phase spans +
per-request lifecycle lanes + flow events) and report the tracer's
throughput overhead vs an untraced replay; the training row traces one
extra ``train_batch`` step.

``--dump-dir <path>`` (serving-chaos): the row ends with a
flight-recorder drill — a planted ``state_corruption`` fault followed
by the ``check_invariants`` audit must drop EXACTLY ONE post-mortem
JSON under ``<path>`` (a tmpdir when the flag is absent). The
serving-stall and paging rows also report an ``efficiency`` detail
block (MFU, goodput vs generous SLO targets, KV-HBM drift against the
page math, telemetry ``overhead_pct``) from the runtime cost model +
SLO tracker; ``check_regression.py --min-goodput/--max-overhead-pct``
gate on it.

``--signatures <path>`` (serving-stall, paging, serving-decode): each
arm exports (and
merge-unions into) a ``signatures.json`` warmup manifest — the exact
abstract signature each watched jitted program was traced with during
warmup — for ``bin/graftlint --check --manifest`` and the
``check_regression.py --require-signature-match`` gate: the statically
enumerated reachable-signature set must equal the runtime warmup set
in both directions.

``vs_baseline`` compares achieved model TFLOPS against the reference's
headline single-device number: 64 TFLOPS/GPU for BERT-Large pretraining
with DeepSpeed's fused kernels on V100-32GB (BASELINE.md row 1, reference
docs/_tutorials/bert-pretraining.md:392). The reference's accounting
counts the FULL attention matmuls (the Megatron 96·B·S·L·h²(1+S/6h+...)
convention behind that 64-TFLOPS claim), so ``vs_baseline`` uses the same;
``detail`` also reports the stricter 6N-only and causal-halved-attention
numbers, and MFU against the v5e bf16 peak (197 TFLOPS) under each.
"""

from __future__ import annotations

import json
import time

import numpy as np

V5E_PEAK_TFLOPS = 197.0

_JSON_PATH = None   # set by __main__ from --json <path>
_TRACE_PATH = None  # set by __main__ from --trace <path>
_DUMP_DIR = None    # set by __main__ from --dump-dir <path>; chaos-row
#                     post-mortem JSONs land here (tmpdir if unset)
_SIGNATURES_PATH = None  # set by __main__ from --signatures <path>;
#                     serving rows export the runtime warmup manifest
#                     (signatures.json) for graftlint --check / the
#                     check_regression.py --require-signature-match gate


def _emit(result: dict) -> None:
    """Print the one-line JSON row; mirror it to --json <path> if given."""
    print(json.dumps(result))
    if _JSON_PATH:
        with open(_JSON_PATH, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")


def _enable_compile_cache():
    # imported here, not at the top: two rows set JAX_PLATFORMS before
    # their first jax import, and the package imports jax
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()


def main():
    import jax
    import jax.numpy as jnp

    _enable_compile_cache()

    import deepspeed_tpu as ds
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    SEQ = 1024
    # rounds 1-5 frontier (that runtime, not today's; BASELINE.md): 350M
    # at mbs 10 x gas 16 with selective ("dots") remat was the best MFU
    # row the chip fit; mbs 16 OOMed at 350M, mbs 8/12 measured slower
    MICRO_BS = 10
    GAS = 16
    N_EMBD, N_LAYER, N_HEAD = 1024, 24, 16

    cfg = GPT2Config(vocab_size=50257, n_positions=SEQ, n_embd=N_EMBD,
                     n_layer=N_LAYER, n_head=N_HEAD, dtype=jnp.bfloat16,
                     remat=True, remat_policy="dots")
    model = GPT2LMHeadModel(cfg)
    config = {
        "train_micro_batch_size_per_gpu": MICRO_BS,
        "gradient_accumulation_steps": GAS,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2},
        "optimizer": {"type": "Adam",
                      "params": {"lr": 3e-4, "weight_decay": 0.1}},
        "gradient_clipping": 1.0,
        "steps_per_print": 1000000,
    }
    engine, _, _, _ = ds.initialize(model=model, config=config)

    rng = np.random.default_rng(0)

    def make_batch():
        return {"input_ids": rng.integers(
            0, cfg.vocab_size,
            (engine.train_batch_size(), SEQ)).astype(np.int32)}

    # warmup (compile)
    for _ in range(2):
        loss = engine.train_batch(batch=make_batch())
    jax.block_until_ready(loss)

    steps = 5
    batches = [make_batch() for _ in range(steps)]
    t0 = time.perf_counter()
    for b in batches:
        loss = engine.train_batch(batch=b)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0

    n_chips = jax.device_count()
    tokens_per_step = engine.train_batch_size() * SEQ
    tok_s_chip = tokens_per_step * steps / dt / n_chips

    trace_events = None
    if _TRACE_PATH:
        # one extra traced step AFTER timing (train_batch phase spans)
        from deepspeed_tpu.telemetry import Tracer

        engine.tracer = Tracer()
        jax.block_until_ready(engine.train_batch(batch=make_batch()))
        trace_events = engine.tracer.export(_TRACE_PATH)

    n_params = engine.num_parameters
    # three accountings, strictest to reference-convention (see module doc)
    attn_full = 12 * N_LAYER * SEQ * N_EMBD       # QK^T + AV, fwd+bwd
    f_6n = 6 * n_params
    f_causal = f_6n + attn_full // 2              # only the causal half is
    f_full = f_6n + attn_full                     # real work; full = ref conv.
    tf = {k: tok_s_chip * f / 1e12
          for k, f in (("6n", f_6n), ("causal_attn", f_causal),
                       ("full_attn", f_full))}

    _emit({
        "metric": "GPT-2 350M seq1024 bf16 ZeRO-2 training throughput "
                  "(mbs10 x gas16, dots remat)",
        "value": round(tok_s_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(tf["full_attn"] / 64.0, 3),
        "detail": {
            "baseline": "DeepSpeed BERT-Large 64 TFLOPS on 1xV100-32GB "
                        "(full-attention accounting, as the reference uses)",
            "n_chips": n_chips,
            "params_m": round(n_params / 1e6, 1),
            "tflops_6n": round(tf["6n"], 2),
            "tflops_causal_attn": round(tf["causal_attn"], 2),
            "tflops_full_attn": round(tf["full_attn"], 2),
            "mfu_pct_6n": round(100 * tf["6n"] / V5E_PEAK_TFLOPS, 1),
            "mfu_pct_causal_attn": round(
                100 * tf["causal_attn"] / V5E_PEAK_TFLOPS, 1),
            "mfu_pct_full_attn": round(
                100 * tf["full_attn"] / V5E_PEAK_TFLOPS, 1),
            "loss": float(loss),
            "tracer": ({"path": _TRACE_PATH, "events": trace_events}
                       if _TRACE_PATH else None),
        },
    })


def serving_main():
    """Poisson-arrival serving row: continuous vs gang scheduling."""
    import jax
    import jax.numpy as jnp

    _enable_compile_cache()

    import deepspeed_tpu as ds
    from deepspeed_tpu.models.transformer_lm import (TransformerConfig,
                                                     TransformerLM)
    from deepspeed_tpu.serving import ServingEngine

    on_cpu = jax.default_backend() == "cpu"
    if on_cpu:  # keep the row runnable for local validation
        cfg = TransformerConfig(vocab_size=512, max_seq_len=256, n_embd=64,
                                n_layer=2, n_head=4, dtype=jnp.float32)
        n_req, slots, rate = 32, 4, 200.0
        len_lo, len_hi, gen_lo, gen_hi = 8, 48, 4, 48
    else:
        # GPT-2 124M-ish decode under a bursty open-loop arrival process
        cfg = TransformerConfig(vocab_size=50257, max_seq_len=1024,
                                n_embd=768, n_layer=12, n_head=12,
                                dtype=jnp.bfloat16)
        n_req, slots, rate = 64, 8, 48.0
        len_lo, len_hi, gen_lo, gen_hi = 32, 256, 16, 128

    model = TransformerLM(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init({"params": rng}, jnp.zeros((1, 8), jnp.int32),
                        method=model.logits)["params"]
    engine = ds.init_inference(model, model_parameters=params,
                               dtype="fp32" if on_cpu else "bf16", mp_size=1)

    gen = np.random.default_rng(0)
    # one workload, replayed identically into both arms: bursty Poisson
    # arrivals, mixed prompt lengths, mixed generation budgets (length
    # spread is exactly what gang scheduling wastes slots on)
    arrivals = np.cumsum(gen.exponential(1.0 / rate, size=n_req))
    prompts = [gen.integers(0, cfg.vocab_size,
                            size=int(gen.integers(len_lo, len_hi + 1))
                            ).astype(np.int32) for _ in range(n_req)]
    budgets = gen.integers(gen_lo, gen_hi + 1, size=n_req)

    def run_arm(policy: str, tracer=None):
        srv = ServingEngine(engine, num_slots=slots, max_queue_depth=n_req,
                            policy=policy, tracer=tracer)
        t0 = time.perf_counter()
        i = 0
        while i < n_req or srv.pending or srv.live_count:
            now = time.perf_counter() - t0
            while i < n_req and arrivals[i] <= now:
                srv.submit(prompts[i], max_new_tokens=int(budgets[i]))
                i += 1
            if not (srv.pending or srv.live_count):
                time.sleep(min(max(arrivals[i] - now, 0.0), 0.05))
                continue
            srv.step()
        return srv.stats(), srv

    # warmup: compile every prefill bucket + admit + decode + sample once;
    # must include len_hi so the TOP bucket is compiled before timing starts
    warm = ServingEngine(engine, num_slots=slots, max_queue_depth=n_req)
    w = len_lo
    while True:
        warm.submit(np.zeros((w,), np.int32), max_new_tokens=2)
        if w >= len_hi:
            break
        w = min(w * 2, len_hi)
    warm.run_until_drained()
    # ...and every BATCHED admission combo: stall-free admission compiles
    # one program per (rows, bucket) pair, so same-bucket pairs and
    # slot-full groups must run here or the timed Poisson run (and the
    # post-run recompile probe) pays first-touch compiles mid-flight
    w = len_lo
    while True:
        for group in (2, slots):
            for _ in range(group):
                warm.submit(np.zeros((w,), np.int32), max_new_tokens=2)
            warm.run_until_drained()
        if w >= len_hi:
            break
        w = min(w * 2, len_hi)
    # ...and every BATCHED admission combo: stall-free admission compiles
    # one program per (rows, bucket) pair, so same-bucket pairs and
    # slot-full groups must run here or the timed Poisson run (and the
    # post-run recompile probe) pays first-touch compiles mid-flight
    w = len_lo
    while True:
        for group in (2, slots):
            for _ in range(group):
                warm.submit(np.zeros((w,), np.int32), max_new_tokens=2)
            warm.run_until_drained()
        if w >= len_hi:
            break
        w = min(w * 2, len_hi)

    cont, srv_cont = run_arm("continuous")
    gang, _ = run_arm("gang")

    # recompile probe — AFTER timing: declare warmup over on the fully
    # exercised server and replay a slice of the workload; any cache
    # growth now is real compilation churn (the gate --max-recompiles
    # reads this as detail.recompiles_after_warmup)
    srv_cont.end_warmup()
    for p, b in zip(prompts[:8], budgets[:8]):
        srv_cont.submit(p, max_new_tokens=int(b))
    srv_cont.run_until_drained()
    recompiles = srv_cont.watchdog.recompiles

    tracer_detail = None
    if _TRACE_PATH:
        from deepspeed_tpu.telemetry import Tracer

        # overhead = traced vs untraced replay of the SAME warmed arm
        base, _ = run_arm("continuous")
        traced, srv_tr = run_arm("continuous", tracer=Tracer())
        n_events = srv_tr.tracer.export(_TRACE_PATH)
        overhead = 100.0 * (base["requests_per_s"] -
                            traced["requests_per_s"]) / base["requests_per_s"]
        tracer_detail = {
            "path": _TRACE_PATH, "events": n_events,
            "traced_requests_per_s": round(traced["requests_per_s"], 3),
            "untraced_requests_per_s": round(base["requests_per_s"], 3),
            "overhead_pct": round(overhead, 2),
        }

    def arm_detail(s):
        return {"requests_per_s": round(s["requests_per_s"], 3),
                "tokens_per_s": round(s["tokens_per_s"], 1),
                "ttft_p50_ms": round(s["ttft_p50_ms"], 1),
                "ttft_p99_ms": round(s["ttft_p99_ms"], 1),
                "per_token_p50_ms": round(s["per_token_p50_ms"], 2),
                "tokens_per_decode_step": round(s["tokens_per_decode_step"],
                                                3),
                "completed": s["completed"]}

    _emit({
        "metric": f"continuous-batching serving, Poisson arrivals "
                  f"({n_req} req @ {rate}/s, {slots} slots, prompts "
                  f"{len_lo}-{len_hi}, budgets {gen_lo}-{gen_hi})",
        "value": round(cont["requests_per_s"], 3),
        "unit": "req/s",
        "vs_baseline": round(cont["requests_per_s"] / gang["requests_per_s"],
                             3),
        "detail": {
            "baseline": "gang (batch-synchronous) admission at equal slot "
                        "count — the generate() discipline on the same "
                        "engine and kernels",
            "recompiles_after_warmup": int(recompiles),
            "tracer": tracer_detail,
            "continuous": arm_detail(cont),
            "gang": arm_detail(gang),
        },
    })


def serving_stall_main():
    """Stall-free admission row: chunked+batched vs serial admission."""
    import jax
    import jax.numpy as jnp

    _enable_compile_cache()

    import deepspeed_tpu as ds
    from deepspeed_tpu.models.transformer_lm import (TransformerConfig,
                                                     TransformerLM)
    from deepspeed_tpu.serving import ServingEngine
    from deepspeed_tpu.serving.metrics import ServingMetrics

    on_cpu = jax.default_backend() == "cpu"
    if on_cpu:  # runnable locally, but heavy enough that a monolithic
        # long-prompt prefill genuinely stalls concurrent decodes (the
        # phenomenon this row measures needs prefill >> decode cost)
        cfg = TransformerConfig(vocab_size=512, max_seq_len=1024, n_embd=128,
                                n_layer=4, n_head=4, dtype=jnp.float32)
        n_req, slots, rate, chunk = 64, 8, 120.0, 256
        len_lo, len_hi, long_lo, long_hi = 17, 32, 520, 760
        long_every, gen_lo, gen_hi = 8, 24, 32
    else:
        cfg = TransformerConfig(vocab_size=50257, max_seq_len=1024,
                                n_embd=768, n_layer=12, n_head=12,
                                dtype=jnp.bfloat16)
        n_req, slots, rate, chunk = 64, 8, 48.0, 256
        len_lo, len_hi, long_lo, long_hi = 32, 128, 520, 760
        long_every, gen_lo, gen_hi = 8, 16, 96

    model = TransformerLM(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init({"params": rng}, jnp.zeros((1, 8), jnp.int32),
                        method=model.logits)["params"]
    engine = ds.init_inference(model, model_parameters=params,
                               dtype="fp32" if on_cpu else "bf16", mp_size=1)

    gen = np.random.default_rng(0)
    # one workload replayed identically into both arms: saturating
    # Poisson arrivals, mostly short prompts (which batched admission
    # coalesces into one dispatch where serial admission pays one
    # full-width dispatch per request), plus a long prompt every
    # ``long_every``-th request — the arrival whose serial prefill
    # stalls every live slot for a whole monolithic dispatch. Under
    # saturation TTFT is queue-drain-bound, so the arm that admits
    # faster finishes faster and wins TTFT across the board.
    arrivals = np.cumsum(gen.exponential(1.0 / rate, size=n_req))
    prompts, budgets = [], []
    for i in range(n_req):
        if i % long_every == long_every - 1:
            T = int(gen.integers(long_lo, long_hi + 1))
        else:
            T = int(gen.integers(len_lo, len_hi + 1))
        prompts.append(gen.integers(0, cfg.vocab_size, size=T)
                       .astype(np.int32))
        budgets.append(int(gen.integers(gen_lo, gen_hi + 1)))

    def warm_arm(srv: ServingEngine) -> None:
        """Compile every program admission can EVER reach BEFORE timing —
        the full statically-enumerable set (graftlint --check proves it
        finite and equal to this sweep), not just the shapes this
        workload's length distribution happens to hit: each singleton
        width bucket up to the arm's clamp (one chunk when stall-free;
        the capacity bucket when serial admission pads whole prompts),
        each (batch-bucket x width-bucket) grouping the token budget
        allows (driven through real closed-loop admissions, so the
        pool's jitted multi-row admit warms too), the chunk program,
        decode and sampling. Warm-by-replay is NOT enough — admission
        grouping depends on wall-clock arrival interleaving, so a
        grouping first seen mid-timed-run would compile inside a timed
        step and masquerade as a stall."""
        sf = srv._stall_free
        w, top = 16, (chunk if sf else 1024)
        while w <= top:
            srv.submit(np.ones((min(w, long_hi),), np.int32),
                       max_new_tokens=2)
            srv.run_until_drained()
            w *= 2
        if sf:
            budget = 2 * chunk + 64 * slots  # == arm_sf construction
            w = 16
            while w <= chunk:
                for count in range(2, min(slots, max(1, budget // w)) + 1):
                    for _ in range(count):
                        srv.submit(np.ones((w,), np.int32),
                                   max_new_tokens=2)
                    srv.run_until_drained()
                w *= 2
        srv.submit(np.ones((long_hi,), np.int32), max_new_tokens=2)
        srv.run_until_drained()

    def run_arm(srv: ServingEngine, timed: bool) -> dict:
        if timed:  # fresh aggregates; warmup polluted them
            srv.metrics = ServingMetrics(None, registry=srv.registry,
                                         step_fn=lambda s=srv: s.step_id)
            srv.reset_efficiency_window()
        reqs = []
        t0 = time.perf_counter()
        i = 0
        while i < n_req or srv.pending or srv.live_count:
            now = time.perf_counter() - t0
            while i < n_req and arrivals[i] <= now:
                reqs.append(srv.submit(prompts[i],
                                       max_new_tokens=budgets[i]))
                i += 1
            if not (srv.pending or srv.live_count):
                time.sleep(min(max(arrivals[i] - now, 0.0), 0.05))
                continue
            srv.step()
        s = srv.stats()
        s["outputs"] = [list(r.output_tokens) for r in reqs]
        return s

    # one engine per arm, reused warm->timed, so the timed pass replays
    # fully-compiled programs (incl. this pool's jitted multi-row admit)
    # budget = chunk + a full batch of shorts: bounds the per-step
    # prefill stall without starving free slots while a long is chunking
    # the measured arm carries the full efficiency stack: XLA cost-model
    # harvest (compiles land in warm_arm, where account() first sees each
    # program), SLO digests with deliberately generous targets — this row
    # gates that goodput is MEASURED sanely, not that a CPU box meets a
    # production SLO — and the default flight recorder
    arm_sf = ServingEngine(engine, num_slots=slots, max_queue_depth=n_req,
                           prefill_chunk=chunk,
                           prefill_token_budget=2 * chunk + 64 * slots,
                           cost_model=True,
                           slo={"ttft_ms": 120_000.0, "gap_ms": 2_000.0,
                                "window_steps": 64})
    arm_serial = ServingEngine(engine, num_slots=slots,
                               max_queue_depth=n_req, prefill_chunk=0)
    assert arm_sf._stall_free and not arm_serial._stall_free
    warm_arm(arm_sf)
    warm_arm(arm_serial)
    # both arms fully warmed: the runtime watchdogs now count any cache
    # growth as a real recompile (both watch the SHARED engine jits, so
    # max() rather than sum() avoids double-counting those)
    arm_sf.end_warmup()
    arm_serial.end_warmup()
    if _SIGNATURES_PATH:
        extra = {"vocab_size": cfg.vocab_size, "max_prompt_len": long_hi}
        arm_sf.export_signatures(_SIGNATURES_PATH, merge=True, extra=extra)
        arm_serial.export_signatures(_SIGNATURES_PATH, merge=True,
                                     extra=extra)
    n_decode_programs = engine._jit_decode._cache_size()

    # interleaved replications with per-metric medians: single CPU
    # replays jitter ~10% run-to-run, enough to flip a close verdict
    reps = 3
    sf_runs, serial_runs = [], []
    for _ in range(reps):
        sf_runs.append(run_arm(arm_sf, timed=True))
        serial_runs.append(run_arm(arm_serial, timed=True))
    # efficiency rollup for the LAST stall-free replication (the window
    # resets per rep); must precede the traced replay, which resets again
    eff = arm_sf.efficiency_snapshot()

    decode_recompiles = engine._jit_decode._cache_size() - n_decode_programs
    recompiles = max(arm_sf.watchdog.recompiles,
                     arm_serial.watchdog.recompiles)
    # greedy: outputs must be bitwise identical across arms AND reps
    # (admission grouping varies with timing; results must not)
    parity = all(r["outputs"] == serial_runs[0]["outputs"]
                 for r in sf_runs + serial_runs)

    tracer_detail = None
    if _TRACE_PATH:
        from deepspeed_tpu.telemetry import Tracer

        arm_sf.set_tracer(Tracer())
        run_arm(arm_sf, timed=True)     # traced replay on the warmed arm
        n_events = arm_sf.tracer.export(_TRACE_PATH)
        tracer_detail = {"path": _TRACE_PATH, "events": n_events}

    _MED_KEYS = ("requests_per_s", "tokens_per_s", "ttft_p50_ms",
                 "ttft_p99_ms", "per_token_p50_ms", "per_token_p99_ms",
                 "step_gap_p50_ms", "step_gap_p99_ms", "stall_time_s")

    def _median(runs):
        out = dict(runs[-1])
        for k in _MED_KEYS:
            out[k] = float(np.median([r[k] for r in runs]))
        return out

    sf, serial = _median(sf_runs), _median(serial_runs)

    def arm_detail(s):
        return {"requests_per_s": round(s["requests_per_s"], 3),
                "tokens_per_s": round(s["tokens_per_s"], 1),
                "ttft_p50_ms": round(s["ttft_p50_ms"], 1),
                "ttft_p99_ms": round(s["ttft_p99_ms"], 1),
                "per_token_p50_ms": round(s["per_token_p50_ms"], 2),
                "per_token_p99_ms": round(s["per_token_p99_ms"], 2),
                "step_gap_p50_ms": round(s["step_gap_p50_ms"], 2),
                "step_gap_p99_ms": round(s["step_gap_p99_ms"], 2),
                "prefill_dispatches": s["prefill_dispatches"],
                "stall_time_s": round(s["stall_time_s"], 4),
                "completed": s["completed"]}

    _emit({
        "metric": f"stall-free serving admission (chunk {chunk}, "
                  f"{n_req} req @ {rate}/s, {slots} slots, short "
                  f"{len_lo}-{len_hi} / long {long_lo}-{long_hi} prompts): "
                  f"p99 inter-token gap",
        "value": round(sf["step_gap_p99_ms"], 2),
        "unit": "ms (lower is better)",
        "vs_baseline": round(serial["step_gap_p99_ms"] /
                             max(sf["step_gap_p99_ms"], 1e-9), 3),
        "detail": {
            "baseline": "serial whole-prompt admission (prefill_chunk=0) "
                        "at equal slots/policy — the PR-2 discipline on "
                        "the same engine and kernels. vs_baseline is the "
                        "serial arm's p99 inter-token gap over the "
                        "stall-free arm's (>1: the tail shrank)",
            "greedy_parity": bool(parity),
            "decode_recompiles_after_warmup": int(decode_recompiles),
            "recompiles_after_warmup": int(recompiles),
            "tracer": tracer_detail,
            "replications": reps,
            "efficiency": {
                "mfu": round(eff.get("mfu") or 0.0, 6),
                "bandwidth_util": round(
                    eff.get("bandwidth_util") or 0.0, 6),
                "hbm_peak_bytes": eff.get("hbm_peak_bytes"),
                "hbm_drift": eff.get("hbm_drift"),
                "goodput_slo": round(eff.get("goodput_slo") or 0.0, 4),
                "slo_ttft_p99_ms": round(eff.get("ttft_p99_ms") or 0.0, 1),
                "slo_gap_p99_ms": round(eff.get("gap_p99_ms") or 0.0, 2),
                "alert_state": eff.get("alert_state"),
                "overhead_pct": round(eff.get("overhead_pct") or 0.0, 3),
                "cost_model_unavailable":
                    eff["costs"]["unavailable"] if "costs" in eff else None,
            },
            "ttft_p99_ratio": round(serial["ttft_p99_ms"] /
                                    max(sf["ttft_p99_ms"], 1e-9), 3),
            "stall_free": arm_detail(sf),
            "serial": arm_detail(serial),
        },
    })


def spec_main():
    """Speculative-decoding serving row: n-gram draft + verify_k vs plain
    one-token decode — same engine, slots and workload; the only change
    is the ``spec_decode`` block."""
    import jax
    import jax.numpy as jnp

    _enable_compile_cache()

    import deepspeed_tpu as ds
    from deepspeed_tpu.models.transformer_lm import (TransformerConfig,
                                                     TransformerLM)
    from deepspeed_tpu.serving import ServingEngine

    on_cpu = jax.default_backend() == "cpu"
    if on_cpu:  # keep the row runnable for local validation
        cfg = TransformerConfig(vocab_size=512, max_seq_len=256, n_embd=64,
                                n_layer=2, n_head=4, dtype=jnp.float32)
        n_req, slots, k = 16, 4, 6
        len_lo, len_hi, gen_lo, gen_hi = 16, 48, 32, 96
    else:
        cfg = TransformerConfig(vocab_size=50257, max_seq_len=1024,
                                n_embd=768, n_layer=12, n_head=12,
                                dtype=jnp.bfloat16)
        n_req, slots, k = 32, 8, 8
        len_lo, len_hi, gen_lo, gen_hi = 32, 128, 64, 224

    model = TransformerLM(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init({"params": rng}, jnp.zeros((1, 8), jnp.int32),
                        method=model.logits)["params"]
    engine = ds.init_inference(model, model_parameters=params,
                               dtype="fp32" if on_cpu else "bf16", mp_size=1)

    gen = np.random.default_rng(0)
    # repetitive-text workload — prompt-lookup drafting's home turf
    # (summarization/code-edit/retrieval-style traffic that quotes its
    # own context): each prompt tiles a short random motif
    prompts, budgets = [], []
    for _ in range(n_req):
        T = int(gen.integers(len_lo, len_hi + 1))
        motif = gen.integers(0, cfg.vocab_size,
                             size=int(gen.integers(4, 9)))
        prompts.append(np.tile(motif, T // len(motif) + 1)[:T]
                       .astype(np.int32))
        budgets.append(int(gen.integers(gen_lo, gen_hi + 1)))

    spec_cfg = {"drafter": "ngram", "k": k, "max_ngram": 3}

    def run_arm(spec):
        srv = ServingEngine(engine, num_slots=slots, max_queue_depth=n_req,
                            spec_decode=spec)
        for p, b in zip(prompts, budgets):
            srv.submit(p, max_new_tokens=b)
        t0 = time.perf_counter()
        done = srv.run_until_drained()
        wall = time.perf_counter() - t0
        s = srv.stats()
        s["wall_s"] = wall
        s["outputs"] = {r.request_id % n_req: list(r.output_tokens)
                        for r in done}
        return s, srv

    run_arm(None), run_arm(spec_cfg)       # warmup: compile both arms
    plain, _ = run_arm(None)
    spec, srv_spec = run_arm(spec_cfg)

    # post-run recompile probe (+ traced replay when --trace is given):
    # the spec arm's server is fully exercised, so a warm replay of the
    # workload must not grow any executable cache
    srv_spec.end_warmup()
    if _TRACE_PATH:
        from deepspeed_tpu.telemetry import Tracer

        srv_spec.set_tracer(Tracer())
    for p, b in zip(prompts, budgets):
        srv_spec.submit(p, max_new_tokens=b)
    srv_spec.run_until_drained()
    tracer_detail = None
    if _TRACE_PATH:
        tracer_detail = {"path": _TRACE_PATH,
                         "events": srv_spec.tracer.export(_TRACE_PATH)}
    recompiles = srv_spec.watchdog.recompiles

    parity = plain["outputs"] == spec["outputs"]  # greedy: must be bitwise
    tps_plain = plain["new_tokens"] / plain["wall_s"]
    tps_spec = spec["new_tokens"] / spec["wall_s"]

    _emit({
        "metric": f"speculative decoding (ngram k={k}) on repetitive-text "
                  f"serving ({n_req} req, {slots} slots, prompts "
                  f"{len_lo}-{len_hi}, budgets {gen_lo}-{gen_hi})",
        "value": round(spec["tokens_per_decode_step"], 3),
        "unit": "tokens/slot-decode-step",
        "vs_baseline": round(tps_spec / tps_plain, 3),
        "detail": {
            "baseline": "plain one-token decode, same engine/slots/"
                        "workload (tokens_per_decode_step == 1.0 by "
                        "construction)",
            "greedy_parity": bool(parity),
            "recompiles_after_warmup": int(recompiles),
            "tracer": tracer_detail,
            "acceptance_rate": round(spec["spec_acceptance_rate"], 3)
            if spec["spec_acceptance_rate"] is not None else None,
            "draft_overhead_pct": round(spec["draft_overhead_pct"], 2)
            if spec["draft_overhead_pct"] is not None else None,
            "spec": {
                "tokens_per_s": round(tps_spec, 1),
                "tokens_per_decode_step": round(
                    spec["tokens_per_decode_step"], 3),
                "decode_steps": spec["decode_steps"],
                "drafted": spec["spec_drafted"],
                "accepted": spec["spec_accepted"],
                "ttft_p50_ms": round(spec["ttft_p50_ms"], 1),
                "ttft_p99_ms": round(spec["ttft_p99_ms"], 1),
            },
            "plain": {
                "tokens_per_s": round(tps_plain, 1),
                "tokens_per_decode_step": round(
                    plain["tokens_per_decode_step"], 3),
                "decode_steps": plain["decode_steps"],
                "ttft_p50_ms": round(plain["ttft_p50_ms"], 1),
                "ttft_p99_ms": round(plain["ttft_p99_ms"], 1),
            },
        },
    })


def paging_main():
    """Paged-KV row: the SAME ≥50%-shared-prefix workload driven through
    a contiguous-SlotPool server and a PagedKVPool server given the SAME
    KV HBM budget (``slots_c * capacity == num_pages * page_size``), but
    the paged arm runs 2x the slots — prefix sharing dedupes the common
    pages, so more requests fit in the same memory. Reports peak resident
    requests at equal HBM (the headline), served requests per KV-GB,
    TTFT cold vs prefix-hit, prefix hit rate, CoW forks, peak pages in
    use, and the zero-recompile gate after a warm replay; greedy outputs
    must be bitwise identical across both arms."""
    import jax
    import jax.numpy as jnp

    _enable_compile_cache()

    import deepspeed_tpu as ds
    from deepspeed_tpu.models.transformer_lm import (TransformerConfig,
                                                     TransformerLM)
    from deepspeed_tpu.serving import ServingEngine

    on_cpu = jax.default_backend() == "cpu"
    if on_cpu:  # keep the row runnable for local validation
        cfg = TransformerConfig(vocab_size=512, max_seq_len=256, n_embd=64,
                                n_layer=2, n_head=4, dtype=jnp.float32)
        n_req, slots_c, ps = 16, 4, 32
        pre_len, suf_lo, suf_hi = 96, 8, 32       # shared prefix: 3 pages
        dup_len, gen_lo, gen_hi = 128, 16, 32     # dup: 4 FULL pages (CoW)
        cold_lo, cold_hi = 32, 64
    else:
        cfg = TransformerConfig(vocab_size=50257, max_seq_len=1024,
                                n_embd=768, n_layer=12, n_head=12,
                                dtype=jnp.bfloat16)
        n_req, slots_c, ps = 32, 8, 64
        pre_len, suf_lo, suf_hi = 256, 32, 128
        dup_len, gen_lo, gen_hi = 512, 64, 128
        cold_lo, cold_hi = 64, 256
    slots_p = 2 * slots_c
    num_pages = slots_c * cfg.max_seq_len // ps   # EQUAL KV bytes by
    #                                               construction

    model = TransformerLM(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init({"params": rng}, jnp.zeros((1, 8), jnp.int32),
                        method=model.logits)["params"]
    engine = ds.init_inference(model, model_parameters=params,
                               dtype="fp32" if on_cpu else "bf16", mp_size=1)

    gen = np.random.default_rng(0)
    shared = gen.integers(0, cfg.vocab_size, size=pre_len).astype(np.int32)
    dup = gen.integers(0, cfg.vocab_size, size=dup_len).astype(np.int32)
    prompts, budgets = [], []
    for i in range(n_req):
        if i < 2:         # page-aligned exact duplicates: full hit -> CoW
            prompts.append(dup.copy())
        elif i < n_req - n_req // 4:   # shared prefix + unique suffix
            suf = gen.integers(0, cfg.vocab_size,
                               size=int(gen.integers(suf_lo, suf_hi + 1)))
            prompts.append(np.concatenate([shared, suf]).astype(np.int32))
        else:             # cold random tail (~25%)
            prompts.append(gen.integers(
                0, cfg.vocab_size,
                size=int(gen.integers(cold_lo, cold_hi + 1)))
                .astype(np.int32))
        budgets.append(int(gen.integers(gen_lo, gen_hi + 1)))
    # leaders = [dup, first shared]; the second duplicate rides in the
    # burst so its full hit (and the CoW fork it forces) lands under load
    prompts[1], prompts[2] = prompts[2], prompts[1]
    budgets[1], budgets[2] = budgets[2], budgets[1]

    def make_srv(paged: bool) -> ServingEngine:
        # the measured (paged) arm also carries the cost model so the row
        # can gate page-math-predicted KV HBM == actual device bytes
        return ServingEngine(
            engine, num_slots=slots_p if paged else slots_c,
            max_queue_depth=2 * n_req, prefill_chunk=ps,
            preempt_queue_threshold=n_req // 2,
            cost_model=paged,
            slo={"ttft_ms": 120_000.0, "gap_ms": 2_000.0,
                 "window_steps": 64} if paged else None,
            paged_kv={"page_size": ps, "num_pages": num_pages}
            if paged else False)

    def kv_bytes(pool) -> int:
        cs = pool.cache["cache_store"]
        return sum(int(np.prod(cs[k].shape)) * cs[k].dtype.itemsize
                   for k in ("k", "v"))

    def run_arm(srv: ServingEngine, paged: bool) -> dict:
        # compile this server's programs on prompts DISJOINT from the
        # workload (the trie must stay cold for the measured run) by
        # sweeping every admission grouping the static checker
        # enumerates — each singleton width bucket up to the chunk,
        # each (rows x width) group the prefill token budget allows,
        # and one chunk-looped long prefill — not just the shapes this
        # workload's length mix happens to hit. A distinct leading
        # token per warm prompt keeps the sweep from prefix-hitting
        # itself, so every entry drives the cold admission path it is
        # meant to compile.
        tok = 0

        def warm(w: int, count: int) -> None:
            nonlocal tok
            for _ in range(count):
                tok += 1
                srv.submit(np.full((w,), tok, np.int32), max_new_tokens=2)
            srv.run_until_drained()

        slots = slots_p if paged else slots_c
        budget = 2 * ps   # the ServingEngine default this row runs with
        w = 16
        while w <= ps:
            for count in range(1, min(slots, max(1, budget // w)) + 1):
                warm(w, count)
            w *= 2
        warm(4 * ps, 1)   # long prefill: drives the chunk loop
        srv.reset_efficiency_window()   # efficiency covers the timed drain
        peak_live = peak_pages = guard = 0
        t0 = time.perf_counter()

        def drain():
            nonlocal peak_live, peak_pages, guard
            while srv.pending or srv.live_count:
                srv.step()
                peak_live = max(peak_live, srv.live_count)
                if paged:
                    peak_pages = max(peak_pages, srv.pool.num_pages
                                     - srv.pool.free_page_count)
                guard += 1
                assert guard < 20_000, "paging drain did not terminate"

        # leaders first (one duplicate, one shared-prefix request) so the
        # trie is warm when the burst lands — the realistic steady state,
        # where earlier traffic has already published the hot prefixes
        reqs = [srv.submit(p, max_new_tokens=b)
                for p, b in zip(prompts[:2], budgets[:2])]
        drain()
        reqs += [srv.submit(p, max_new_tokens=b)
                 for p, b in zip(prompts[2:], budgets[2:])]
        drain()
        wall = time.perf_counter() - t0
        srv.check_invariants()
        s = srv.stats()
        s["wall_s"] = wall
        s["peak_live"] = peak_live
        s["peak_pages"] = peak_pages
        s["kv_gb"] = kv_bytes(srv.pool) / 2**30
        s["outputs"] = [list(r.output_tokens) for r in reqs]
        # prefill latency (admit -> first token), NOT submit-based TTFT:
        # under an all-at-once burst queueing dominates submit-based
        # numbers, hiding the prefill work the prefix cache skips
        lat = [(r.prefix_hit_tokens, r.first_token_time - r.admit_time)
               for r in reqs]
        s["prefill_cold_ms"] = 1e3 * float(np.median(
            [t for h, t in lat if h == 0]))
        hits = [t for h, t in lat if h > 0]
        s["prefill_hit_ms"] = 1e3 * float(np.median(hits)) if hits else None
        s["n_prefix_hit_reqs"] = len(hits)
        return s

    srv_paged = make_srv(paged=True)
    srv_dense = make_srv(paged=False)
    dense = run_arm(srv_dense, paged=False)
    paged = run_arm(srv_paged, paged=True)
    # page-math-predicted KV bytes vs actual device bytes must agree
    # EXACTLY (drift 0.0) — taken before the warm replay below
    eff = srv_paged.efficiency_snapshot()

    # zero-recompile gate: warm replay of the whole workload (now ALL
    # prefix hits, including the CoW forks the duplicates force) on the
    # measured paged server must not grow any executable cache
    srv_paged.end_warmup()
    if _SIGNATURES_PATH:
        # the manifest freezes at end_warmup: everything up to and
        # including the measured run is warmup-eligible traffic the
        # static enumeration must cover; the warm replay below is the
        # post-warmup phase the invariant protects
        extra = {"vocab_size": cfg.vocab_size,
                 "max_seed_len": dup_len + gen_hi}
        srv_paged.export_signatures(_SIGNATURES_PATH, merge=True,
                                    extra=extra)
        srv_dense.export_signatures(_SIGNATURES_PATH, merge=True,
                                    extra=extra)
    if _TRACE_PATH:
        from deepspeed_tpu.telemetry import Tracer

        srv_paged.set_tracer(Tracer())
    for p, b in zip(prompts, budgets):
        srv_paged.submit(p, max_new_tokens=b)
    srv_paged.run_until_drained(max_steps=20_000)
    tracer_detail = None
    if _TRACE_PATH:
        tracer_detail = {"path": _TRACE_PATH,
                         "events": srv_paged.tracer.export(_TRACE_PATH)}
    recompiles = srv_paged.watchdog.recompiles
    pstats = srv_paged.pool.page_stats()

    parity = dense["outputs"] == paged["outputs"]  # greedy: must be bitwise
    resident_ratio = paged["peak_live"] / max(dense["peak_live"], 1)

    _emit({
        "metric": f"paged KV + prefix cache vs contiguous slots at EQUAL "
                  f"KV HBM ({n_req} req, >=50% shared prefix, "
                  f"{slots_c}->{slots_p} slots, {num_pages} pages x {ps}): "
                  f"peak resident requests ratio",
        "value": round(resident_ratio, 3),
        "unit": "resident-requests ratio at equal KV HBM (higher is "
                "better)",
        "vs_baseline": round(resident_ratio, 3),
        "detail": {
            "baseline": "contiguous SlotPool, same engine/workload/"
                        "chunked admission; the paged arm holds the same "
                        "KV bytes (num_pages*page_size == slots*capacity) "
                        "but seats 2x the slots — shared-prefix pages are "
                        "mapped, not copied, so the extra slots are real "
                        "concurrency, not extra memory",
            "greedy_parity": bool(parity),
            "recompiles_after_warmup": int(recompiles),
            "tracer": tracer_detail,
            "prefix_hit_rate": round(paged["prefix_hit_rate"], 3),
            "n_prefix_hit_reqs": paged["n_prefix_hit_reqs"],
            "prefill_cold_ms": round(paged["prefill_cold_ms"], 1),
            "prefill_hit_ms": round(paged["prefill_hit_ms"], 1)
            if paged["prefill_hit_ms"] is not None else None,
            "cow_copies": pstats["cow_copies"],
            "page_evictions": pstats["page_evictions"],
            "preempted": paged["preempted"],
            "efficiency": {
                "mfu": round(eff.get("mfu") or 0.0, 6),
                "hbm_peak_bytes": eff.get("hbm_peak_bytes"),
                "hbm_drift": eff.get("hbm_drift"),
                "kv_bytes_predicted":
                    eff["costs"]["hbm"].get("kv_bytes_predicted")
                    if "costs" in eff else None,
                "kv_bytes_actual":
                    eff["costs"]["hbm"].get("kv_bytes_actual")
                    if "costs" in eff else None,
                "goodput_slo": round(eff.get("goodput_slo") or 0.0, 4),
                "overhead_pct": round(eff.get("overhead_pct") or 0.0, 3),
            },
            "paged": {
                "peak_resident_requests": paged["peak_live"],
                "served_per_kv_gb": round(
                    paged["completed"] / paged["kv_gb"], 1),
                "peak_pages_in_use": paged["peak_pages"],
                "pages_total": num_pages,
                "requests_per_s": round(
                    paged["completed"] / paged["wall_s"], 2),
                "ttft_p50_ms": round(paged["ttft_p50_ms"], 1),
                "ttft_p99_ms": round(paged["ttft_p99_ms"], 1),
            },
            "contiguous": {
                "peak_resident_requests": dense["peak_live"],
                "served_per_kv_gb": round(
                    dense["completed"] / dense["kv_gb"], 1),
                "requests_per_s": round(
                    dense["completed"] / dense["wall_s"], 2),
                "ttft_p50_ms": round(dense["ttft_p50_ms"], 1),
                "ttft_p99_ms": round(dense["ttft_p99_ms"], 1),
            },
        },
    })


def serving_tp_main():
    """Multi-chip serving row: (data, model)-mesh sharded engines plus
    the data-parallel replica router, on the forced 8-device CPU host.

    Three arm families on one model/workload family:

    * **TP=1** (mesh ``data=8, model=1``) and **TP=2** (``data=4,
      model=2``): the same stall-free dense-slot serving config on two
      mesh shapes. Greedy outputs must be BITWISE identical across the
      two meshes and across replications (the tentpole parity
      invariant), and neither arm may recompile after warmup (the jit
      signatures are mesh-shape-independent; only shardings move).
    * **DP=2 router**: a :class:`ReplicaRouter` over two paged replicas
      on DISJOINT 4-device meshes vs ONE identically-configured paged
      replica, on a 4-session-group workload whose prefixes cannot all
      fit in one replica's page pool. Session affinity keeps each
      group's prefix resident on its home replica while the single
      replica thrashes (evicts and re-prefills) — the skipped prefill
      chunks are the aggregate-throughput win the headline gates
      (``vs_baseline`` = router req/s over single-replica req/s,
      ``check_regression.py --threshold 1.5``).

    Example::

        python bench.py serving-tp --json BENCH_serving_tp.json \\
            --signatures signatures.json
        python check_regression.py BENCH_serving_tp.json \\
            BENCH_serving_tp.json --threshold 1.5 --max-recompiles 0 \\
            --require-zero-leaks --signatures-json signatures.json \\
            --require-signature-match

    The row also carries the zero-leak / invariant / timeline gates
    (``--require-zero-leaks``) summed over ALL five servers, and every
    arm merge-unions its warmup manifest into ``--signatures`` for the
    ``--require-signature-match`` gate.
    """
    import os

    # This row runs on forced host devices and never reaches a chip (it
    # prints "platform": "cpu"). Both env vars must land BEFORE the
    # first jax import in this process: XLA_FLAGS is read once at
    # backend initialization (exporting it later is a silent no-op and
    # every mesh axis comes up size 1), and JAX_PLATFORMS=cpu selects
    # the platform the forced devices exist on.
    _flag = "--xla_force_host_platform_device_count=8"
    if _flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = \
            (os.environ.get("XLA_FLAGS", "") + " " + _flag).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp

    _enable_compile_cache()

    import deepspeed_tpu as ds
    from deepspeed_tpu.models.transformer_lm import (TransformerConfig,
                                                     TransformerLM)
    from deepspeed_tpu.parallel import mesh as mesh_mod
    from deepspeed_tpu.serving import ReplicaRouter, ServingEngine
    from deepspeed_tpu.serving.metrics import ServingMetrics

    cfg = TransformerConfig(vocab_size=512, max_seq_len=1024, n_embd=128,
                            n_layer=4, n_head=4, dtype=jnp.float32)
    model = TransformerLM(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init({"params": rng}, jnp.zeros((1, 8), jnp.int32),
                        method=model.logits)["params"]
    devs = jax.devices()
    if len(devs) < 8:
        raise RuntimeError(
            f"serving-tp needs the forced 8-device host ({len(devs)} "
            f"visible) — was jax imported before this row set XLA_FLAGS?")

    def make_engine(devices, data, model_ax):
        # serving reads the global mesh at CONSTRUCTION time only, so
        # installing each engine's mesh just before building it (and its
        # server) is sufficient — replicas on disjoint meshes then step
        # concurrently without touching the global registry
        mesh = mesh_mod.build_mesh(devices=devices, data=data,
                                   model=model_ax)
        mesh_mod.set_mesh(mesh)
        return ds.init_inference(model, model_parameters=params,
                                 dtype="fp32", mesh=mesh)

    gen = np.random.default_rng(0)

    # -- tensor-parallel arms (dense slots, stall-free admission) ------
    slots_tp, chunk = 8, 256
    budget_tp = 2 * chunk + 64 * slots_tp
    n_tp, long_hi = 24, 512
    tp_prompts, tp_budgets = [], []
    for i in range(n_tp):
        T = int(gen.integers(300, 500)) if i % 6 == 5 \
            else int(gen.integers(17, 33))
        tp_prompts.append(gen.integers(0, cfg.vocab_size, size=T)
                          .astype(np.int32))
        tp_budgets.append(int(gen.integers(8, 17)))

    def make_tp(data, model_ax):
        eng = make_engine(devs, data, model_ax)
        return ServingEngine(eng, num_slots=slots_tp,
                             max_queue_depth=2 * n_tp,
                             prefill_chunk=chunk,
                             prefill_token_budget=budget_tp,
                             strict_recompile=True)

    def warm_tp(srv):
        # stall-row discipline: every admission grouping the static
        # checker enumerates — singleton width buckets up to the chunk,
        # each (rows x width) group the token budget allows, one
        # chunk-looped long prefill — then arm the watchdog
        w = 16
        while w <= chunk:
            for count in range(1, min(slots_tp,
                                      max(1, budget_tp // w)) + 1):
                for _ in range(count):
                    srv.submit(np.ones((w,), np.int32), max_new_tokens=2)
                srv.run_until_drained()
            w *= 2
        srv.submit(np.ones((long_hi,), np.int32), max_new_tokens=2)
        srv.run_until_drained()
        srv.end_warmup()

    def run_tp(srv):
        # fresh aggregates per replication; warmup and earlier reps
        # polluted the percentile digests
        srv.metrics = ServingMetrics(None, registry=srv.registry,
                                     step_fn=lambda s=srv: s.step_id)
        t0 = time.perf_counter()
        reqs = [srv.submit(p, max_new_tokens=b)
                for p, b in zip(tp_prompts, tp_budgets)]
        srv.run_until_drained(max_steps=50_000)
        wall = time.perf_counter() - t0
        s = srv.stats()
        s["wall_s"] = wall
        s["outputs"] = [list(r.output_tokens) for r in reqs]
        return s

    tp1 = make_tp(data=8, model_ax=1)
    warm_tp(tp1)
    tp2 = make_tp(data=4, model_ax=2)
    warm_tp(tp2)

    # -- data-parallel router arms (paged KV, session affinity) --------
    # geometry chosen so ONE replica's page pool cannot hold all four
    # session groups' prefixes (4 x 8 pages + working set > 24 pages)
    # while each router replica CAN hold its own two (2 x 8 + working
    # set < 24): the single replica thrashes, the router does not
    ps, prefix_pages, n_groups = 32, 8, 4
    prefix_len = prefix_pages * ps
    slots_dp, num_pages, n_dp, gen_dp = 2, 24, 32, 8
    budget_dp = 2 * ps + 16 * slots_dp
    prefixes = {g: gen.integers(1, cfg.vocab_size, size=prefix_len)
                .astype(np.int32) for g in range(n_groups)}
    dp_reqs = []
    for i in range(n_dp):
        g = i % n_groups   # strict group cycling: the LRU-worst order
        suf = gen.integers(1, cfg.vocab_size,
                           size=int(gen.integers(4, 12))).astype(np.int32)
        dp_reqs.append((str(g), np.concatenate([prefixes[g], suf])))

    def make_dp(devices):
        eng = make_engine(devices, data=len(devices), model_ax=1)
        return ServingEngine(eng, num_slots=slots_dp,
                             max_queue_depth=2 * n_dp,
                             prefill_chunk=ps,
                             prefill_token_budget=budget_dp,
                             strict_recompile=True,
                             paged_kv={"page_size": ps,
                                       "num_pages": num_pages})

    def warm_dp(srv):
        # same sweep as the paging row: distinct leading tokens keep
        # the warm prompts from prefix-hitting themselves
        tok = 0

        def warm(w, count):
            nonlocal tok
            for _ in range(count):
                tok += 1
                srv.submit(np.full((w,), tok, np.int32), max_new_tokens=2)
            srv.run_until_drained()

        w = 16
        while w <= ps:
            for count in range(1, min(slots_dp,
                                      max(1, budget_dp // w)) + 1):
                warm(w, count)
            w *= 2
        warm(prefix_len + 16, 1)   # chunk-loop long prefill
        # page-aligned exact duplicate: the full-page hit + decode
        # forces the copy-on-write page copy, the one paged program the
        # distinct-token sweep above can never reach
        dup = np.full((2 * ps,), cfg.vocab_size - 3, np.int32)
        for _ in range(2):
            srv.submit(dup, max_new_tokens=2)
            srv.run_until_drained()
        srv.end_warmup()

    single = make_dp(devs[:4])
    warm_dp(single)
    rep_a = make_dp(devs[:4])
    warm_dp(rep_a)
    rep_b = make_dp(devs[4:])
    warm_dp(rep_b)
    router = ReplicaRouter([rep_a, rep_b])

    if _SIGNATURES_PATH:
        extra_tp = {"vocab_size": cfg.vocab_size, "max_prompt_len": long_hi}
        extra_dp = {"vocab_size": cfg.vocab_size,
                    "max_seed_len": prefix_len + 16 + gen_dp}
        tp1.export_signatures(_SIGNATURES_PATH, merge=True, extra=extra_tp)
        tp2.export_signatures(_SIGNATURES_PATH, merge=True, extra=extra_tp)
        for srv in (single, rep_a, rep_b):
            srv.export_signatures(_SIGNATURES_PATH, merge=True,
                                  extra=extra_dp)

    def run_dp(target, use_session):
        t0 = time.perf_counter()
        reqs = []
        for sess, prompt in dp_reqs:
            kw = {"session": sess} if use_session else {}
            reqs.append(target.submit(prompt, max_new_tokens=gen_dp, **kw))
        target.run_until_drained(max_steps=100_000)
        wall = time.perf_counter() - t0
        return {"requests_per_s": n_dp / wall,
                "outputs": [list(r.output_tokens) for r in reqs]}

    # interleaved replications with per-metric medians (single-CPU
    # replays jitter enough to flip a close verdict); every arm is
    # fully warmed, so the strict watchdogs police the whole timed
    # phase — any recompile here raises at the step boundary
    reps = 3
    tp1_runs, tp2_runs, single_runs, router_runs = [], [], [], []
    for _ in range(reps):
        tp1_runs.append(run_tp(tp1))
        tp2_runs.append(run_tp(tp2))
        single_runs.append(run_dp(single, use_session=False))
        router_runs.append(run_dp(router, use_session=True))

    def _med(runs, key):
        return float(np.median([r[key] for r in runs]))

    tp_parity = all(r["outputs"] == tp1_runs[0]["outputs"]
                    for r in tp1_runs + tp2_runs)
    dp_parity = all(r["outputs"] == single_runs[0]["outputs"]
                    for r in single_runs + router_runs)
    single_rps = _med(single_runs, "requests_per_s")
    router_rps = _med(router_runs, "requests_per_s")
    dp_ratio = router_rps / max(single_rps, 1e-9)

    servers = [tp1, tp2, single, rep_a, rep_b]
    recompiles = (tp1.watchdog.recompiles + tp2.watchdog.recompiles
                  + single.watchdog.recompiles + router.recompiles)
    leaks = sum(s.pool.num_slots - s.pool.free_count - s.live_count
                for s in servers)
    invariants_ok = True
    try:
        for s in servers[:3]:
            s.check_invariants()
        router.check_invariants()
    except Exception:
        invariants_ok = False
    open_tl = [rid for s in servers for rid in s.timelines.open_ids()]
    timelines_complete = not open_tl

    sstats = single.stats()["paging"]
    astats = rep_a.stats()["paging"]
    bstats = rep_b.stats()["paging"]
    rstats = router.stats()

    def tp_detail(runs, srv):
        s = runs[-1]
        return {"requests_per_s": round(_med(runs, "requests_per_s"), 3),
                "per_token_p50_ms": round(_med(runs, "per_token_p50_ms"),
                                          2),
                "per_token_p99_ms": round(_med(runs, "per_token_p99_ms"),
                                          2),
                "step_gap_p99_ms": round(_med(runs, "step_gap_p99_ms"), 2),
                "completed": s["completed"],
                "mesh": {"data": srv._mesh_axis_size("data"),
                         "model": srv._mesh_axis_size("model")}}

    _emit({
        "metric": f"multi-chip serving ((data,model) mesh + DP router, "
                  f"forced 8-device host; DP: {n_groups} session groups "
                  f"x {prefix_pages}-page prefixes over {num_pages}-page "
                  f"pools): router req/s over single replica",
        "value": round(dp_ratio, 3),
        "platform": jax.devices()[0].platform,
        "unit": "aggregate req/s ratio (higher is better)",
        "vs_baseline": round(dp_ratio, 3),
        "detail": {
            "baseline": "ONE paged replica with the identical serving "
                        "config and page pool, same workload without "
                        "session routing — its pool cannot hold every "
                        "group's prefix, so admissions thrash the trie "
                        "(evict + re-prefill) where the router's "
                        "session affinity keeps each group's prefix "
                        "resident on its home replica",
            "greedy_parity_tp": bool(tp_parity),
            "greedy_parity_dp": bool(dp_parity),
            "recompiles_after_warmup": int(recompiles),
            "slot_leaks": int(leaks),
            "invariants_ok": bool(invariants_ok),
            "timelines_complete": bool(timelines_complete),
            "replications": reps,
            "tp1": tp_detail(tp1_runs, tp1),
            "tp2": tp_detail(tp2_runs, tp2),
            "dp": {
                "single_requests_per_s": round(single_rps, 3),
                "router_requests_per_s": round(router_rps, 3),
                "single_page_evictions": sstats["page_evictions"],
                "single_prefix_hits": sstats["prefix_hits"],
                "single_prefix_misses": sstats["prefix_misses"],
                "replica_page_evictions": [astats["page_evictions"],
                                           bstats["page_evictions"]],
                "replica_prefix_hits": [astats["prefix_hits"],
                                        bstats["prefix_hits"]],
                "replica_prefix_misses": [astats["prefix_misses"],
                                          bstats["prefix_misses"]],
                "router": {"dispatched": rstats["dispatched"],
                           "affinity_hits": rstats["affinity_hits"],
                           "spills": rstats["spills"],
                           "failovers": rstats["failovers"]},
            },
        },
    })


def serving_disagg_main():
    """Disaggregated prefill/decode row: a 1-prefill + 1-decode fleet
    (cross-pool page transfer handoffs) vs a colocated DP=2 router at
    EQUAL device count (two disjoint 4-device meshes each), on the
    forced 8-device CPU host.

    The workload is prefill-HEAVY Poisson traffic (long multi-page
    prompts, short decode budgets, seeded arrivals): on a colocated
    replica every admission chunk runs inside a step that decoding
    requests are waiting through, so prefill interference lands
    directly in the inter-token gap tail. The disaggregated decode
    replica never prefills — its steps are pure decode — which is the
    DistServe/Splitwise claim this row pins. Headline ``value`` is the
    disaggregated arm's decode step-gap p99 (gaps recorded on
    decode-capable replicas only); ``vs_baseline`` is the colocated
    arm's over it (>1: disaggregation shrank the decode tail).

    Both arms run strict recompile watchdogs the whole timed phase, the
    warmup drives real transfers through the fleet BEFORE end_warmup so
    the transfer program's signature lands in the manifest, and greedy
    outputs must be bitwise identical across arms and replications (a
    transferred page is the exact bits the prefill replica wrote).
    ``detail.prefix`` pins the global-prefix-awareness lift: handoffs
    routed via the shared first-page index and the transfer pages a
    destination trie hit kept off the wire.

    ``detail.journeys`` / ``detail.transfer_latency_p99_ms`` /
    ``detail.efficiency`` report the fleet observability plane over the
    disaggregated arm: cross-replica journey completeness (every
    terminal journey stitches with all homes closed), the merged
    per-transfer latency tail, fleet goodput and the instrumentation
    overhead as a fraction of accumulated step wall.

    Example::

        python bench.py serving-disagg --json BENCH_serving_disagg.json \\
            --signatures signatures.json
        python check_regression.py BENCH_serving_disagg.json \\
            BENCH_serving_disagg.json --metric value:lower \\
            --max-overhead-pct 3 --require-complete-journeys \\
            --max-recompiles 0 --require-zero-leaks \\
            --signatures-json signatures.json --require-signature-match
    """
    import os

    # forced host devices, never a chip (prints "platform": "cpu");
    # must land before the first jax import (see serving_tp_main)
    _flag = "--xla_force_host_platform_device_count=8"
    if _flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = \
            (os.environ.get("XLA_FLAGS", "") + " " + _flag).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp

    _enable_compile_cache()

    import deepspeed_tpu as ds
    from deepspeed_tpu.models.transformer_lm import (TransformerConfig,
                                                     TransformerLM)
    from deepspeed_tpu.parallel import mesh as mesh_mod
    from deepspeed_tpu.serving import ReplicaRouter, ServingEngine
    from deepspeed_tpu.serving.metrics import ServingMetrics

    cfg = TransformerConfig(vocab_size=512, max_seq_len=512, n_embd=128,
                            n_layer=4, n_head=4, dtype=jnp.float32)
    model = TransformerLM(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init({"params": rng}, jnp.zeros((1, 8), jnp.int32),
                        method=model.logits)["params"]
    devs = jax.devices()
    if len(devs) < 8:
        raise RuntimeError(
            f"serving-disagg needs the forced 8-device host ({len(devs)} "
            f"visible) — was jax imported before this row set XLA_FLAGS?")

    def make_engine(devices):
        mesh = mesh_mod.build_mesh(devices=devices, data=len(devices),
                                   model=1)
        mesh_mod.set_mesh(mesh)
        return ds.init_inference(model, model_parameters=params,
                                 dtype="fp32", mesh=mesh)

    # -- workload: prefill-heavy, seeded Poisson arrivals --------------
    # long multi-page prompts (2-3 pages, chunk-looped prefill), short
    # decode budgets; a quarter of the traffic shares per-group
    # first-page prefixes so the shared first-page index has something
    # to route on (and the colocated arm's tries get the same benefit)
    gen = np.random.default_rng(0)
    ps, slots, num_pages = 32, 4, 96
    n_req, n_groups = 24, 4
    budget = 2 * ps + 16 * slots
    group_prefix = {g: gen.integers(1, cfg.vocab_size, size=ps)
                    .astype(np.int32) for g in range(n_groups)}

    def make_workload(seed):
        wrng = np.random.default_rng(seed)
        prompts, budgets, sessions = [], [], []
        for i in range(n_req):
            n = int(wrng.integers(ps + 1, 3 * ps))
            body = wrng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
            if i % 4 == 0:          # grouped: shared first page
                g = (i // 4) % n_groups
                body[:ps] = group_prefix[g]
                sessions.append(str(g))
            else:
                sessions.append(None)
            prompts.append(body)
            budgets.append(int(wrng.integers(4, 9)))
        return prompts, budgets, sessions

    prompts, budgets, sessions = make_workload(7)
    # Poisson arrivals in router-step units — identical schedule for
    # both arms, sustained enough that admissions overlap live decode
    arrivals = []
    t = 0
    arr_rng = np.random.default_rng(11)
    for _ in range(n_req):
        arrivals.append(t)
        t += int(arr_rng.poisson(1.0))

    def make_srv(devices, role):
        eng = make_engine(devices)
        return ServingEngine(eng, num_slots=slots,
                             max_queue_depth=2 * n_req, prefill_chunk=ps,
                             prefill_token_budget=budget,
                             strict_recompile=True, role=role, slo=True,
                             paged_kv={"page_size": ps,
                                       "num_pages": num_pages})

    def warm_admitting(srv):
        """The paging-row width sweep on a replica that can finish work
        (role 'both' or 'decode'): every admission grouping, the
        chunk-looped long prefill, and the page-aligned duplicate that
        forces the copy-on-write fork."""
        tok = 0

        def warm(w, count):
            nonlocal tok
            for _ in range(count):
                tok += 1
                srv.submit(np.full((w,), tok, np.int32), max_new_tokens=2)
            srv.run_until_drained()

        w = 16
        while w <= ps:
            for count in range(1, min(slots, max(1, budget // w)) + 1):
                warm(w, count)
            w *= 2
        warm(3 * ps + 16, 1)          # longer than any timed prompt
        dup = np.full((2 * ps,), cfg.vocab_size - 3, np.int32)
        for _ in range(2):
            srv.submit(dup, max_new_tokens=2)
            srv.run_until_drained()

    def warm_prefill(srv):
        """Same width sweep on a prefill-role replica: it can never
        finish a request (no decode), so each group prefills to the
        parked-handoff state and is then cancelled."""
        tok = 0
        w = 16
        while w <= ps:
            for count in range(1, min(slots, max(1, budget // w)) + 1):
                reqs = []
                for _ in range(count):
                    tok += 1
                    reqs.append(srv.submit(np.full((w,), tok, np.int32),
                                           max_new_tokens=2))
                for _ in range(40):
                    srv.step()
                    if all(r in srv.pending_handoffs() for r in reqs):
                        break
                for r in reqs:
                    srv.cancel(r.request_id)
            w *= 2
        r = srv.submit(np.full((3 * ps + 16,), 1, np.int32),
                       max_new_tokens=2)
        for _ in range(40):
            srv.step()
            if r in srv.pending_handoffs():
                break
        srv.cancel(r.request_id)

    def warm_fleet(router):
        """Transfers must run BEFORE end_warmup: the cross-pool
        transfer program only records its signature when a real adopt
        traces it through the attached watchdog. A repeated grouped
        prompt exercises the trie-hit adopt path too."""
        wprompts, wbudgets, wsessions = make_workload(3)
        reqs = []
        for p, b, s in zip(wprompts, wbudgets, wsessions):
            kw = {"session": s} if s is not None else {}
            reqs.append(router.submit(p, max_new_tokens=b, **kw))
        router.run_until_drained(max_steps=20_000)
        assert all(r.state.value == "finished" for r in reqs), \
            "disagg warmup did not drain"
        router.end_warmup()

    # -- arms (equal device count: two disjoint 4-device meshes) -------
    co_a = make_srv(devs[:4], "both")
    warm_admitting(co_a)
    co_b = make_srv(devs[4:], "both")
    warm_admitting(co_b)
    colocated = ReplicaRouter([co_a, co_b])
    warm_fleet(colocated)

    pre = make_srv(devs[:4], "prefill")
    warm_prefill(pre)
    dec = make_srv(devs[4:], "decode")
    warm_admitting(dec)
    disagg = ReplicaRouter([pre, dec])
    warm_fleet(disagg)

    servers = [co_a, co_b, pre, dec]
    if _SIGNATURES_PATH:
        extra = {"vocab_size": cfg.vocab_size,
                 "max_seed_len": 3 * ps + 16}
        for srv in servers:
            srv.export_signatures(_SIGNATURES_PATH, merge=True, extra=extra)

    def run_arm(router):
        for i in router.alive_replicas:
            rep = router.replicas[i]
            rep.metrics = ServingMetrics(None, registry=rep.registry,
                                         step_fn=lambda s=rep: s.step_id)
            # overhead_pct measures the TIMED phase only: drop the
            # warmup's instrumentation time and step wall
            rep.reset_efficiency_window()
        reqs, i, step = [], 0, 0
        t0 = time.perf_counter()
        while i < n_req or router.has_work():
            while i < n_req and arrivals[i] <= step:
                kw = {"session": sessions[i]} if sessions[i] else {}
                reqs.append(router.submit(prompts[i],
                                          max_new_tokens=budgets[i], **kw))
                i += 1
            router.step()
            step += 1
            if step > 50_000:
                break
        wall = time.perf_counter() - t0
        gaps = []
        for j in router.decode_capable:
            gaps += [g * 1e3
                     for g in router.replicas[j].metrics.step_gaps]
        arr = np.asarray(gaps) if gaps else np.zeros((1,))
        return {"wall_s": wall,
                "decode_gap_p50_ms": float(np.percentile(arr, 50)),
                "decode_gap_p99_ms": float(np.percentile(arr, 99)),
                "tokens": int(sum(len(r.output_tokens) for r in reqs)),
                "outputs": [list(r.output_tokens) for r in reqs]}

    # interleaved replications, per-metric medians (same discipline as
    # every serving row: single-CPU replays jitter enough to flip a
    # close verdict)
    reps = 3
    co_runs, dis_runs = [], []
    for _ in range(reps):
        co_runs.append(run_arm(colocated))
        dis_runs.append(run_arm(disagg))

    def _med(runs, key):
        return float(np.median([r[key] for r in runs]))

    parity = all(r["outputs"] == co_runs[0]["outputs"]
                 for r in co_runs + dis_runs)
    co_p99 = _med(co_runs, "decode_gap_p99_ms")
    dis_p99 = _med(dis_runs, "decode_gap_p99_ms")

    recompiles = colocated.recompiles + disagg.recompiles
    leaks = sum(s.pool.num_slots - s.pool.free_count - s.live_count
                for s in servers)
    invariants_ok = True
    try:
        colocated.check_invariants()
        disagg.check_invariants()
    except Exception:
        invariants_ok = False
    open_tl = [rid for s in servers for rid in s.timelines.open_ids()]
    timelines_complete = not open_tl

    dstats = disagg.stats()
    transferred_pages = max(
        1, dstats["transfer_bytes"] // dec.pool.page_nbytes)
    saved = dstats["transfer_pages_saved"]

    # fleet observability detail (the --require-complete-journeys /
    # --max-overhead-pct gates read these): journey completeness over
    # the whole disaggregated run, merged transfer-latency tail, and
    # fleet goodput + instrumentation overhead from FleetTelemetry
    journeys = disagg.journey_summary()
    fleet_eff = disagg.fleet.efficiency_snapshot()

    def arm_detail(runs):
        return {"decode_gap_p50_ms": round(_med(runs,
                                                "decode_gap_p50_ms"), 2),
                "decode_gap_p99_ms": round(_med(runs,
                                                "decode_gap_p99_ms"), 2),
                "wall_s": round(_med(runs, "wall_s"), 3),
                "tokens": runs[-1]["tokens"]}

    _emit({
        "metric": f"disaggregated prefill/decode (1P+1D page-transfer "
                  f"fleet vs colocated DP=2 at equal device count; "
                  f"{n_req} req Poisson, prompts {ps + 1}-{3 * ps - 1}, "
                  f"budgets 4-8, {num_pages} pages x {ps}): decode "
                  f"step-gap p99",
        "value": round(dis_p99, 2),
        "platform": jax.devices()[0].platform,
        "unit": "ms (lower is better)",
        "vs_baseline": round(co_p99 / max(dis_p99, 1e-9), 3),
        "detail": {
            "baseline": "colocated DP=2 router (two role-'both' paged "
                        "replicas on the same two disjoint 4-device "
                        "meshes, same workload/arrivals/sessions): every "
                        "admission chunk runs inside a step that live "
                        "decodes wait through. vs_baseline is its decode "
                        "step-gap p99 over the disaggregated arm's (>1: "
                        "the decode tail shrank)",
            "greedy_parity": bool(parity),
            "recompiles_after_warmup": int(recompiles),
            "slot_leaks": int(leaks),
            "invariants_ok": bool(invariants_ok),
            "timelines_complete": bool(timelines_complete),
            "replications": reps,
            "transfers": dstats["transfers"],
            "transfer_bytes": dstats["transfer_bytes"],
            "transfer_latency_p99_ms": round(
                disagg.transfer_latency.quantile(0.99), 3),
            "journeys": journeys,
            "efficiency": {
                "goodput_slo": round(fleet_eff["goodput_slo"], 4),
                "overhead_pct": round(
                    fleet_eff.get("overhead_pct", 0.0), 3),
            },
            "prefix": {
                "prefix_routed_handoffs": dstats["prefix_routed"],
                "transfer_pages_saved": int(saved),
                "transfer_page_hit_rate": round(
                    saved / (saved + transferred_pages), 4),
            },
            "colocated": arm_detail(co_runs),
            "disaggregated": arm_detail(dis_runs),
        },
    })


def serving_decode_main():
    """Raw-decode-speed row: the fused paged-attention decode kernel plus
    overlapped host scheduling (``paged_kv={"kernel": "on"}, overlap=True``)
    vs the dense gather/scatter oracle with serial stepping
    (``kernel="off", overlap=False``) — SAME engine, pool geometry and
    decode-heavy workload; greedy outputs must be bitwise identical
    across arms and replications (the kernel is a bitwise-parity
    reimplementation, not an approximation). Headline ``value`` is the
    kernel+overlap arm's p99 inter-token step gap; ``vs_baseline`` is
    the dense-serial p99 over it (>1: the streaming tail shrank).
    ``detail.efficiency.mfu`` rides the cost model for the
    ``check_regression.py --warn-metric`` floor, and the row carries the
    full zero-recompile stack: post-warmup watchdog count for
    ``--max-recompiles 0`` plus the ``--signatures`` warmup manifest for
    ``--require-signature-match``."""
    import jax
    import jax.numpy as jnp

    _enable_compile_cache()

    import deepspeed_tpu as ds
    from deepspeed_tpu.models.transformer_lm import (TransformerConfig,
                                                     TransformerLM)
    from deepspeed_tpu.serving import ServingEngine
    from deepspeed_tpu.serving.metrics import ServingMetrics

    on_cpu = jax.default_backend() == "cpu"
    if on_cpu:  # keep the row runnable for local validation (the kernel
        # runs in Pallas interpret mode off-TPU, so parity and all the
        # static/recompile gates are exercised; only the speedup isn't)
        cfg = TransformerConfig(vocab_size=512, max_seq_len=256, n_embd=64,
                                n_layer=2, n_head=4, dtype=jnp.float32)
        n_req, slots, ps = 24, 4, 32
        len_lo, len_hi, gen_lo, gen_hi = 8, 24, 32, 64
    else:
        cfg = TransformerConfig(vocab_size=50257, max_seq_len=1024,
                                n_embd=768, n_layer=12, n_head=12,
                                dtype=jnp.bfloat16)
        n_req, slots, ps = 48, 8, 64
        len_lo, len_hi, gen_lo, gen_hi = 32, 128, 64, 192
    num_pages = slots * cfg.max_seq_len // ps

    model = TransformerLM(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init({"params": rng}, jnp.zeros((1, 8), jnp.int32),
                        method=model.logits)["params"]
    engine = ds.init_inference(model, model_parameters=params,
                               dtype="fp32" if on_cpu else "bf16", mp_size=1)

    gen = np.random.default_rng(0)
    # decode-heavy closed loop: short prompts (single-chunk prefill),
    # long budgets — the steady state is all slots decoding, which is
    # exactly where the fused kernel and the deferred-fetch/overlap
    # pipeline pay off; prompt tokens start at 1 so the page-aligned
    # CoW warm prompt below (token 0) can never prefix-hit the workload
    prompts = [gen.integers(1, cfg.vocab_size,
                            size=int(gen.integers(len_lo, len_hi + 1)))
               .astype(np.int32) for _ in range(n_req)]
    budgets = [int(gen.integers(gen_lo, gen_hi + 1)) for _ in range(n_req)]

    def make_srv(kernel: bool) -> ServingEngine:
        # the measured arm carries the cost model (MFU) + generous SLO
        # targets (this row gates that goodput is MEASURED, not that a
        # CPU box meets a production SLO)
        return ServingEngine(
            engine, num_slots=slots, max_queue_depth=2 * n_req,
            prefill_chunk=ps, overlap=kernel, cost_model=kernel,
            slo={"ttft_ms": 120_000.0, "gap_ms": 2_000.0,
                 "window_steps": 64} if kernel else None,
            paged_kv={"page_size": ps, "num_pages": num_pages,
                      "kernel": "on" if kernel else "off"})

    def warm_arm(srv: ServingEngine) -> None:
        """Compile (and — as important — RECORD into the watchdog's
        warmup manifest) every program the timed run and the signature
        gate can reach. The ``__init__`` pre-warm runs before the
        watchdog attaches, so this sweep is what actually records each
        admission grouping: every singleton width bucket up to the
        chunk (``_jit_cur_scatter`` at ``int32[1]``), every
        (rows x width) group the prefill token budget allows (each
        power-of-two group width), the chunk-looped long prefill,
        decode and sampling. A page-aligned prompt submitted twice
        forces one full prefix hit + copy-on-write fork so the CoW
        program lands in the manifest too — graftcheck enumerates it
        for every paged config, hit or no hit."""
        tok = 0

        def warm(w: int, count: int) -> None:
            nonlocal tok
            for _ in range(count):
                tok += 1
                srv.submit(np.full((w,), tok % (cfg.vocab_size - 1) + 1,
                                   np.int32), max_new_tokens=2)
            srv.run_until_drained()

        budget = 2 * ps   # the ServingEngine default this row runs with
        w = 16
        while w <= ps:
            for count in range(1, min(slots, max(1, budget // w)) + 1):
                warm(w, count)
            w *= 2
        warm(4 * ps, 1)   # long prefill: drives the chunk loop
        for _ in range(2):  # 2nd pass full-hits page-aligned prefix -> CoW
            srv.submit(np.zeros((2 * ps,), np.int32), max_new_tokens=2)
            srv.run_until_drained()

    def run_arm(srv: ServingEngine, timed: bool) -> dict:
        if timed:  # fresh aggregates; warmup polluted them
            srv.metrics = ServingMetrics(None, registry=srv.registry,
                                         step_fn=lambda s=srv: s.step_id)
            srv.reset_efficiency_window()
        reqs = [srv.submit(p, max_new_tokens=b)
                for p, b in zip(prompts, budgets)]
        t0 = time.perf_counter()
        srv.run_until_drained(max_steps=50_000)
        wall = time.perf_counter() - t0
        s = srv.stats()
        s["wall_s"] = wall
        s["outputs"] = [list(r.output_tokens) for r in reqs]
        return s

    arm_kernel = make_srv(kernel=True)
    arm_dense = make_srv(kernel=False)
    assert arm_kernel.pool.kernel_active and not arm_dense.pool.kernel_active
    warm_arm(arm_kernel)
    warm_arm(arm_dense)
    # both arms fully warmed: the runtime watchdogs now count any cache
    # growth as a real recompile (both watch the SHARED engine jits, so
    # max() rather than sum() avoids double-counting those)
    arm_kernel.end_warmup()
    arm_dense.end_warmup()
    if _SIGNATURES_PATH:
        extra = {"vocab_size": cfg.vocab_size, "max_prompt_len": 4 * ps}
        arm_kernel.export_signatures(_SIGNATURES_PATH, merge=True,
                                     extra=extra)
        arm_dense.export_signatures(_SIGNATURES_PATH, merge=True,
                                    extra=extra)

    # interleaved replications with per-metric medians: single CPU
    # replays jitter ~10% run-to-run, enough to flip a close verdict
    reps = 3
    kernel_runs, dense_runs = [], []
    for _ in range(reps):
        kernel_runs.append(run_arm(arm_kernel, timed=True))
        dense_runs.append(run_arm(arm_dense, timed=True))
    # efficiency rollup for the LAST kernel replication (the window
    # resets per rep); must precede the traced replay, which resets again
    eff = arm_kernel.efficiency_snapshot()

    recompiles = max(arm_kernel.watchdog.recompiles,
                     arm_dense.watchdog.recompiles)
    # greedy: outputs must be bitwise identical across arms AND reps —
    # the kernel arm is a different executable and a different step
    # pipeline, but NOT a different function
    parity = all(r["outputs"] == dense_runs[0]["outputs"]
                 for r in kernel_runs + dense_runs)

    tracer_detail = None
    if _TRACE_PATH:
        from deepspeed_tpu.telemetry import Tracer

        arm_kernel.set_tracer(Tracer())
        run_arm(arm_kernel, timed=True)  # traced replay on the warmed arm
        n_events = arm_kernel.tracer.export(_TRACE_PATH)
        tracer_detail = {"path": _TRACE_PATH, "events": n_events}

    _MED_KEYS = ("tokens_per_s", "per_token_p50_ms", "per_token_p99_ms",
                 "step_gap_p50_ms", "step_gap_p99_ms", "ttft_p50_ms",
                 "ttft_p99_ms", "wall_s")

    def _median(runs):
        out = dict(runs[-1])
        for k in _MED_KEYS:
            out[k] = float(np.median([r[k] for r in runs]))
        return out

    kern, dense = _median(kernel_runs), _median(dense_runs)

    def arm_detail(s):
        return {"tokens_per_s": round(s["tokens_per_s"], 1),
                "step_gap_p50_ms": round(s["step_gap_p50_ms"], 2),
                "step_gap_p99_ms": round(s["step_gap_p99_ms"], 2),
                "per_token_p50_ms": round(s["per_token_p50_ms"], 2),
                "per_token_p99_ms": round(s["per_token_p99_ms"], 2),
                "ttft_p50_ms": round(s["ttft_p50_ms"], 1),
                "decode_steps": s["decode_steps"],
                "completed": s["completed"],
                "wall_s": round(s["wall_s"], 3)}

    _emit({
        "metric": f"fused paged-attention decode kernel + overlapped "
                  f"host scheduling ({n_req} req, {slots} slots, "
                  f"{num_pages} pages x {ps}, prompts {len_lo}-{len_hi}, "
                  f"budgets {gen_lo}-{gen_hi}): p99 inter-token gap",
        "value": round(kern["step_gap_p99_ms"], 2),
        "unit": "ms (lower is better)",
        "vs_baseline": round(dense["step_gap_p99_ms"] /
                             max(kern["step_gap_p99_ms"], 1e-9), 3),
        "detail": {
            "baseline": "dense gather/scatter decode (kernel='off') with "
                        "serial stepping (overlap=False) on the same "
                        "engine, pool geometry and workload — the bitwise "
                        "oracle the kernel must match. vs_baseline is the "
                        "dense arm's p99 inter-token gap over the kernel "
                        "arm's (>1: the tail shrank)",
            "greedy_parity": bool(parity),
            "recompiles_after_warmup": int(recompiles),
            "kernel_backend": "pallas" if not on_cpu else
                              "pallas-interpret (CPU validation)",
            "tracer": tracer_detail,
            "replications": reps,
            "tokens_per_s_ratio": round(kern["tokens_per_s"] /
                                        max(dense["tokens_per_s"], 1e-9),
                                        3),
            "efficiency": {
                "mfu": round(eff.get("mfu") or 0.0, 6),
                "bandwidth_util": round(
                    eff.get("bandwidth_util") or 0.0, 6),
                "hbm_peak_bytes": eff.get("hbm_peak_bytes"),
                "hbm_drift": eff.get("hbm_drift"),
                "goodput_slo": round(eff.get("goodput_slo") or 0.0, 4),
                "slo_gap_p99_ms": round(eff.get("gap_p99_ms") or 0.0, 2),
                "overhead_pct": round(eff.get("overhead_pct") or 0.0, 3),
                "cost_model_unavailable":
                    eff["costs"]["unavailable"] if "costs" in eff else None,
            },
            "paged_kernel": arm_detail(kern),
            "dense_oracle": arm_detail(dense),
        },
    })


def serving_chaos_main():
    """Fault-tolerant serving row: the SAME workload driven through a
    fault-free arm and a chaos arm with a deterministic fault schedule
    (admit-OOM, NaN logits, mid-step host exception, slow dispatch) on
    a server running every resilience feature — numerics guard,
    degradation ladder, automatic pressure preemption. The row reports
    goodput retained under faults and gates on the invariants a fault
    may never break: zero slot leaks, clean engine bookkeeping
    (``check_invariants``), complete request timelines (every request
    terminal with a reason), zero post-warmup recompiles."""
    import jax
    import jax.numpy as jnp

    _enable_compile_cache()

    import deepspeed_tpu as ds
    from deepspeed_tpu.models.transformer_lm import (TransformerConfig,
                                                     TransformerLM)
    from deepspeed_tpu.serving import ServingEngine
    from deepspeed_tpu.serving.metrics import ServingMetrics
    from deepspeed_tpu.serving.resilience import FaultInjector, InjectedFault

    on_cpu = jax.default_backend() == "cpu"
    if on_cpu:  # keep the row runnable for local validation
        cfg = TransformerConfig(vocab_size=512, max_seq_len=256, n_embd=64,
                                n_layer=2, n_head=4, dtype=jnp.float32)
        n_req, slots = 24, 4
        len_lo, len_hi, gen_lo, gen_hi = 16, 48, 8, 24
    else:
        cfg = TransformerConfig(vocab_size=50257, max_seq_len=1024,
                                n_embd=768, n_layer=12, n_head=12,
                                dtype=jnp.bfloat16)
        n_req, slots = 32, 8
        len_lo, len_hi, gen_lo, gen_hi = 32, 128, 16, 64

    model = TransformerLM(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init({"params": rng}, jnp.zeros((1, 8), jnp.int32),
                        method=model.logits)["params"]
    engine = ds.init_inference(model, model_parameters=params,
                               dtype="fp32" if on_cpu else "bf16", mp_size=1)

    gen = np.random.default_rng(0)
    prompts = [gen.integers(0, cfg.vocab_size,
                            size=int(gen.integers(len_lo, len_hi + 1)))
               .astype(np.int32) for _ in range(n_req)]
    budgets = [int(gen.integers(gen_lo, gen_hi + 1)) for _ in range(n_req)]

    # the measured fault plan, pinned to call ordinals so every rerun
    # injects the identical failures at the identical points. Spec decode
    # stays OFF in this row (the NaN point lives in the plain decode
    # path); drafter faults are covered by the chaos unit suite.
    fault_plan = {"admit_oom": [3], "nan_logits": [5],
                  "step_host_error": [9], "slow_dispatch": [2, 12]}
    # degradation thresholds low enough that the all-at-once submission
    # walks HEALTHY -> OVERLOADED and back while the queue drains
    degr = {"queue_pressured": max(slots, 4),
            "queue_overloaded": max(2 * slots, 10), "cooldown_steps": 4}

    def make_srv(faulty: bool) -> ServingEngine:
        return ServingEngine(
            engine, num_slots=slots, max_queue_depth=2 * n_req,
            guard_numerics=True, degradation=dict(degr),
            preempt_queue_threshold=n_req // 2, step_wall_budget_ms=250.0,
            fault_injector=FaultInjector(seed=0) if faulty else None)

    def warm(srv: ServingEngine) -> None:
        """Compile every (batch-bucket x width-bucket) admission program
        a preemption-resume can reach (resumed seeds land on LARGER
        width buckets than their prompts), plus chunked prefill, decode,
        the numerics guard and sampling — all before the measured run,
        so the zero-recompile gate is meaningful."""
        w = 16
        while w <= srv.pool.capacity:
            for count in range(1, slots + 1):
                for _ in range(count):
                    srv.submit(np.ones((min(w, srv.pool.capacity - 2),),
                                       np.int32), max_new_tokens=2)
                srv.run_until_drained()
            w *= 2
        srv.submit(np.ones((srv.pool.capacity - 2,), np.int32),
                   max_new_tokens=2)
        srv.run_until_drained()

    def run_arm(srv: ServingEngine, plan=None) -> dict:
        srv.metrics = ServingMetrics(None, registry=srv.registry,
                                     step_fn=lambda s=srv: s.step_id)
        if srv.faults is not None:
            srv.faults.load_schedule(plan or {})
        reqs = [srv.submit(p, max_new_tokens=b)
                for p, b in zip(prompts, budgets)]
        injected_aborts = 0
        t0 = time.perf_counter()
        guard = 0
        while srv.pending or srv.live_count:
            try:
                srv.step()
            except InjectedFault:
                # the harness absorbs INJECTED failures (a real serving
                # front-end would log and carry on); anything else is a
                # genuine bug and propagates
                injected_aborts += 1
            guard += 1
            assert guard < 10_000, "chaos drain did not terminate"
        wall = time.perf_counter() - t0
        s = srv.stats()
        s["wall_s"] = wall
        s["injected_aborts"] = injected_aborts
        s["reqs"] = reqs
        return s

    srv_chaos = make_srv(faulty=True)
    srv_clean = make_srv(faulty=False)
    warm(srv_chaos)   # empty schedule: warmup consumes no fault ordinals
    warm(srv_clean)
    srv_chaos.end_warmup()
    srv_clean.end_warmup()

    clean = run_arm(srv_clean)
    chaos = run_arm(srv_chaos, plan=fault_plan)

    # -- the gates ------------------------------------------------------
    leaks = slots - srv_chaos.pool.free_count - srv_chaos.live_count
    invariants_ok = True
    try:
        srv_chaos.check_invariants()
        srv_clean.check_invariants()
    except Exception:
        invariants_ok = False
    open_tl = srv_chaos.timelines.open_ids()
    terminal_ok = all(
        r.state.value in ("finished", "rejected", "failed")
        and (r.finish_reason is not None or r.reject_reason is not None)
        for r in chaos["reqs"])
    recompiles = max(srv_chaos.watchdog.recompiles,
                     srv_clean.watchdog.recompiles)
    goodput = chaos["completed"] / max(clean["completed"], 1)
    # snapshot before the traced replay below re-fires the schedule
    faults_fired = dict(srv_chaos.faults.summary()["fired"])

    # -- flight-recorder post-mortem drill ------------------------------
    # a FRESH server (same warmed engine) with an armed state_corruption
    # point and a dump_dir: the planted corruption breaks slot
    # bookkeeping at the first step's tail, the check_invariants audit
    # raises, and EXACTLY ONE self-contained post-mortem JSON must land
    # under --dump-dir (a tmpdir when the flag is absent)
    import glob
    import os
    import tempfile

    dump_dir = _DUMP_DIR or tempfile.mkdtemp(prefix="dstpu-postmortem-")
    srv_pm = make_srv(faulty=True)
    srv_pm.dump_dir = dump_dir
    srv_pm.recorder.dump_dir = dump_dir
    srv_pm.faults.load_schedule({"state_corruption": [1]})
    for p, b in zip(prompts[:slots], budgets[:slots]):
        srv_pm.submit(p, max_new_tokens=b)
    srv_pm.step()           # corruption fires at this step's tail
    violation = None
    try:
        srv_pm.check_invariants()
    except Exception as e:  # InvariantViolation; dumping rides the raise
        violation = type(e).__name__
    pm_files = sorted(os.path.basename(f) for f in glob.glob(
        os.path.join(dump_dir, "postmortem-*.json")))
    post_mortem = {"dir": dump_dir, "files": pm_files,
                   "raised": violation,
                   "exactly_one": len(pm_files) == 1}

    tracer_detail = None
    if _TRACE_PATH:
        from deepspeed_tpu.telemetry import Tracer

        srv_chaos.set_tracer(Tracer())
        run_arm(srv_chaos, plan=fault_plan)  # traced replay, same faults
        tracer_detail = {"path": _TRACE_PATH,
                         "events": srv_chaos.tracer.export(_TRACE_PATH)}

    _emit({
        "metric": f"fault-tolerant serving under deterministic chaos "
                  f"({n_req} req, {slots} slots, faults: "
                  f"{sorted(k for k, v in fault_plan.items() if v)}): "
                  f"goodput retained vs fault-free arm",
        "value": round(goodput, 3),
        "unit": "fraction of fault-free completions (higher is better)",
        "vs_baseline": round(goodput, 3),
        "detail": {
            "baseline": "identical engine/config/workload with no fault "
                        "injector; goodput = chaos completions over "
                        "fault-free completions (lost requests are the "
                        "ones a fault FAILED — never a leaked slot or a "
                        "stranded queue entry)",
            "slot_leaks": int(leaks),
            "invariants_ok": bool(invariants_ok),
            "timelines_complete": bool(not open_tl and terminal_ok),
            "recompiles_after_warmup": int(recompiles),
            "tracer": tracer_detail,
            "fault_plan": {k: list(v) for k, v in fault_plan.items()},
            "faults_fired": faults_fired,
            "injected_aborts": chaos["injected_aborts"],
            "post_mortem": post_mortem,
            "chaos": {
                "completed": chaos["completed"],
                "failed": chaos["failed"],
                "failed_reasons": chaos["failed_reasons"],
                "preempted": chaos["preempted"],
                "step_overruns": chaos["step_overruns"],
                "load_transitions": chaos["load_transitions"],
                "tokens_per_s": round(chaos["new_tokens"] /
                                      chaos["wall_s"], 1),
            },
            "fault_free": {
                "completed": clean["completed"],
                "failed": clean["failed"],
                "preempted": clean["preempted"],
                "load_transitions": clean["load_transitions"],
                "tokens_per_s": round(clean["new_tokens"] /
                                      clean["wall_s"], 1),
            },
        },
    })


def serving_async_main():
    """Async front-end row: Poisson load at three priority tiers driven
    through the REAL HTTP/SSE server over a localhost socket. The
    standard tier's TTFT target is unmeetable by construction, so its
    burn-rate alert pages and the scheduler sheds the batch tier while
    the interactive tier keeps its goodput — that top-class goodput is
    the headline, measured only while the bottom class is actively
    shed. Gates: zero slot leaks, clean invariants, complete timelines
    (every SSE stream terminal), zero post-warmup recompiles."""
    import asyncio

    import jax
    import jax.numpy as jnp

    _enable_compile_cache()

    import deepspeed_tpu as ds
    from deepspeed_tpu.models.transformer_lm import (TransformerConfig,
                                                     TransformerLM)
    from deepspeed_tpu.serving import ServingEngine, ServingFrontend
    from deepspeed_tpu.serving.metrics import ServingMetrics

    on_cpu = jax.default_backend() == "cpu"
    if on_cpu:  # keep the row runnable for local validation
        cfg = TransformerConfig(vocab_size=512, max_seq_len=256, n_embd=64,
                                n_layer=2, n_head=4, dtype=jnp.float32)
        slots = 4
        n_int, n_std, n_batch = 12, 10, 10
    else:
        cfg = TransformerConfig(vocab_size=50257, max_seq_len=1024,
                                n_embd=768, n_layer=12, n_head=12,
                                dtype=jnp.bfloat16)
        slots = 8
        n_int, n_std, n_batch = 16, 12, 12

    model = TransformerLM(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init({"params": rng}, jnp.zeros((1, 8), jnp.int32),
                        method=model.logits)["params"]
    engine = ds.init_inference(model, model_parameters=params,
                               dtype="fp32" if on_cpu else "bf16", mp_size=1)

    # the standard tier's contract is unmeetable ON PURPOSE: every
    # finish blows TTFT, burn = (1-0)/(1-0.95) = 20 >= page_burn on
    # both horizons, and the shed floor drops to rank(standard) — so
    # batch (ranked below) is shed while interactive/standard admit.
    lenient = {"ttft_ms": 6e5, "gap_ms": 6e5}
    slo_cfg = {
        **lenient,                      # default class: lenient
        "window_steps": 8, "windows": 4,
        "goodput_target": 0.95, "warn_burn": 2.0, "page_burn": 10.0,
        "classes": {
            "interactive": dict(lenient),
            "standard": {"ttft_ms": 1e-3, "gap_ms": None},
            "batch": dict(lenient),
        },
    }
    srv = ServingEngine(engine, num_slots=slots, max_queue_depth=64,
                        priority=True, slo=slo_cfg)

    def warm() -> None:
        """Compile every admission/decode program the measured run (and
        a burn-preemption resume) can reach before end_warmup(), so the
        zero-recompile gate is meaningful."""
        w = 16
        while w <= min(srv.pool.capacity, 64):
            for count in range(1, slots + 1):
                for _ in range(count):
                    srv.submit(np.ones((min(w, srv.pool.capacity - 2),),
                                       np.int32), max_new_tokens=2)
                srv.run_until_drained()
            w *= 2

    warm()
    srv.end_warmup()
    # measured run starts from clean counters: fresh request metrics,
    # zeroed SLO windows/alerts and cost-model totals
    srv.metrics = ServingMetrics(None, registry=srv.registry,
                                 step_fn=lambda s=srv: s.step_id)
    srv.reset_efficiency_window()

    # deterministic workload: prompts, budgets and Poisson gaps are all
    # drawn up front (async interleaving must not reorder rng draws)
    gen = np.random.default_rng(0)

    def _tier(n, mean_gap_s):
        return [{"prompt": gen.integers(1, cfg.vocab_size,
                                        size=int(gen.integers(8, 25)))
                 .astype(int).tolist(),
                 "max_new_tokens": int(gen.integers(8, 17)),
                 "gap_s": float(gen.exponential(mean_gap_s))}
                for _ in range(n)]

    tiers = {"interactive": _tier(n_int, 0.02),
             "standard": _tier(n_std, 0.02),
             "batch": _tier(n_batch, 0.015)}
    burn_seed = _tier(4, 0.0)           # phase 1: ignite the standard burn

    # -- minimal stdlib HTTP/SSE client (mirrors the server's framing) --
    def _http_bytes(method, path, body=None):
        payload = b"" if body is None else json.dumps(body).encode()
        return (f"{method} {path} HTTP/1.1\r\nHost: b\r\n"
                f"Content-Length: {len(payload)}\r\n\r\n"
                .encode("latin-1") + payload)

    async def _next_frame(reader):
        try:
            block = await reader.readuntil(b"\n\n")
        except asyncio.IncompleteReadError:
            return None
        event, data = None, None
        for line in block.decode().strip().split("\n"):
            if line.startswith("event: "):
                event = line[len("event: "):]
            elif line.startswith("data: "):
                data = json.loads(line[len("data: "):])
        return event, data

    async def _generate(port, cls, spec):
        """One POST /v1/generate exchange; returns a result record."""
        rec = {"cls": cls, "status": None, "reject_reason": None,
               "ttft_ms": None, "tokens": 0, "terminal": None}
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        t0 = time.perf_counter()
        writer.write(_http_bytes("POST", "/v1/generate", {
            "prompt": spec["prompt"],
            "max_new_tokens": spec["max_new_tokens"],
            "priority": cls, "tenant": cls}))
        await writer.drain()
        head = await reader.readuntil(b"\r\n\r\n")
        rec["status"] = int(head.decode("latin-1").split(" ")[1])
        if rec["status"] != 200:
            body = await reader.read()
            info = json.loads(body) if body else {}
            rec["reject_reason"] = info.get("reject_reason")
        else:
            while True:
                fr = await _next_frame(reader)
                if fr is None:
                    break
                ev, _ = fr
                if ev == "token":
                    if rec["tokens"] == 0:
                        rec["ttft_ms"] = (time.perf_counter() - t0) * 1e3
                    rec["tokens"] += 1
                elif ev in ("done", "error"):
                    rec["terminal"] = ev
                    break
        writer.close()
        try:
            await writer.wait_closed()
        except (ConnectionResetError, BrokenPipeError):
            pass
        return rec

    async def _healthz(port):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(_http_bytes("GET", "/healthz"))
        await writer.drain()
        raw = await reader.read()
        writer.close()
        await writer.wait_closed()
        return json.loads(raw.partition(b"\r\n\r\n")[2])

    async def drive():
        fe = ServingFrontend(srv, port=0, idle_poll_s=0.002)
        await fe.start()
        port = fe.port
        results, alerts_at_batch = [], {}
        try:
            # phase 1: burn the standard tier, wait for the page alert
            results += await asyncio.gather(*[
                _generate(port, "standard", s) for s in burn_seed])
            for _ in range(300):
                alerts_at_batch = (await _healthz(port))["class_alerts"]
                if alerts_at_batch.get("standard") == "page":
                    break
                await asyncio.sleep(0.01)

            # phase 2: Poisson arrivals at all three tiers while the
            # burn is hot — batch lands on the shed floor
            async def tier(cls):
                tasks = []
                for spec in tiers[cls]:
                    await asyncio.sleep(spec["gap_s"])
                    tasks.append(asyncio.create_task(
                        _generate(port, cls, spec)))
                return await asyncio.gather(*tasks)

            for part in await asyncio.gather(*(tier(c) for c in tiers)):
                results += part
        finally:
            await fe.stop()
        return results, alerts_at_batch

    t0 = time.perf_counter()
    results, alerts = asyncio.run(asyncio.wait_for(drive(), timeout=600))
    wall = time.perf_counter() - t0

    # -- per-class client-side rollup -----------------------------------
    def _client(cls):
        rs = [r for r in results if r["cls"] == cls]
        ttfts = [r["ttft_ms"] for r in rs if r["ttft_ms"] is not None]
        return {
            "sent": len(rs),
            "streamed": sum(1 for r in rs if r["status"] == 200),
            "shed": sum(1 for r in rs if r["status"] == 429
                        and r["reject_reason"] == "retry_after"),
            "rejected_other": sum(1 for r in rs if r["status"] not in
                                  (200, None) and r["status"] != 429),
            "ttft_p50_ms": (round(float(np.percentile(ttfts, 50)), 1)
                            if ttfts else None),
            "ttft_p99_ms": (round(float(np.percentile(ttfts, 99)), 1)
                            if ttfts else None),
        }

    client = {cls: _client(cls) for cls in
              ("interactive", "standard", "batch")}

    # -- the gates ------------------------------------------------------
    leaks = slots - srv.pool.free_count - srv.live_count
    invariants_ok = True
    try:
        srv.check_invariants()
    except Exception:
        invariants_ok = False
    # timelines complete on BOTH sides of the socket: no open engine
    # timelines, and every accepted SSE stream reached a terminal frame
    open_tl = srv.timelines.open_ids()
    terminal_ok = all(r["terminal"] == "done"
                      for r in results if r["status"] == 200)
    recompiles = srv.watchdog.recompiles

    snap = srv.slo.snapshot()
    pc = snap["per_class"]
    top = pc.get("interactive", {"admitted": 0, "good": 0})
    top_goodput = (top["good"] / top["admitted"]
                   if top["admitted"] else 1.0)
    eff = srv.efficiency_snapshot()
    # --min-goodput gates the TOP class: the row's claim is that the
    # paying tier keeps its SLO while a lower tier is being shed
    eff["goodput_slo_overall"] = eff.get("goodput_slo")
    eff["goodput_slo"] = top_goodput
    stats = srv.stats()

    _emit({
        "metric": f"async HTTP/SSE serving, 3 priority tiers under "
                  f"burn-driven shedding ({slots} slots, "
                  f"{len(results)} requests): interactive goodput "
                  f"while batch is shed",
        "value": round(top_goodput, 3),
        "unit": "fraction of interactive admissions finishing within "
                "SLO (higher is better)",
        "vs_baseline": round(top_goodput, 3),
        "detail": {
            "baseline": "the standard tier's TTFT contract is "
                        "unmeetable by construction, paging its burn "
                        "alert; goodput_slo is the INTERACTIVE class "
                        "(good/admitted from the SLO tracker) measured "
                        "while batch submissions are shed with 429 + "
                        "Retry-After over the real localhost socket",
            "slot_leaks": int(leaks),
            "invariants_ok": bool(invariants_ok),
            "timelines_complete": bool(not open_tl and terminal_ok),
            "recompiles_after_warmup": int(recompiles),
            "efficiency": eff,
            "class_alerts": snap and {
                k: v["alert"] for k, v in pc.items()},
            "alerts_when_batch_arrived": alerts,
            "batch_actively_shed": client["batch"]["shed"] > 0,
            "per_class_slo": pc,
            "per_class_http": client,
            "engine": {
                "completed": stats["completed"],
                "rejected": stats["rejected"],
                "preempted": stats["preempted"],
                "cancelled": stats["cancelled"],
                "new_tokens": stats["new_tokens"],
            },
            "wall_s": round(wall, 2),
            "requests_per_s": round(len(results) / wall, 2),
        },
    })


if __name__ == "__main__":
    import sys

    argv = sys.argv[1:]
    if "--json" in argv:
        _JSON_PATH = argv[argv.index("--json") + 1]
    if "--trace" in argv:
        _TRACE_PATH = argv[argv.index("--trace") + 1]
    if "--dump-dir" in argv:
        _DUMP_DIR = argv[argv.index("--dump-dir") + 1]
    if "--signatures" in argv:
        _SIGNATURES_PATH = argv[argv.index("--signatures") + 1]
    if "serving-chaos" in argv:
        entry = serving_chaos_main
    elif "serving-async" in argv:
        entry = serving_async_main
    elif "serving-tp" in argv:
        entry = serving_tp_main
    elif "serving-disagg" in argv:
        entry = serving_disagg_main
    elif "paging" in argv:
        entry = paging_main
    elif "serving-decode" in argv:
        entry = serving_decode_main
    elif "serving-stall" in argv:
        entry = serving_stall_main
    elif "spec" in argv:
        entry = spec_main
    elif "serving" in argv:
        entry = serving_main
    else:
        entry = main
    entry()
