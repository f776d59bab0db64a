"""One serving cell, once: ``ds.init_serving`` driven through ``submit`` and
``step`` from ONE thread. The load (open or closed loop) is a generator's
object; this file only submits what is due, steps the server, and notes on
its own clock when each token became visible to the caller (the return of
the step that produced it). Latencies run from the time a request was DUE.
After the window a plain reference judges the tokens of four requests."""

from __future__ import annotations

import time

import numpy as np

from perf import build, device, stats

# A served token against the reference's logits at the same position: it
# must lie within this share of the position's largest |logit| below the
# reference's maximum. The server computes in bfloat16 (8 bits of mantissa:
# one rounding moves a value by 2**-8 of its size; every layer rounds the
# attention output once, and 24 layers can move a logit a few such steps at
# the scale of the largest). 2**-5, eight steps, allows that and is far
# below what a wrong page, mask or position does, which moves logits by
# their whole scale (the smoke's tolerance and reason, chip_smoke.py).
LOGIT_REL_TOL = 2.0 ** -5
REFERENCE_REQUESTS = 4
MIN_PREFILL_BUCKET = 16     # serving/engine.py buckets short prompts from 16


def _bucket(n: int) -> int:
    b = MIN_PREFILL_BUCKET
    while b < n:
        b *= 2
    return b


def warm_up(srv, prompt_len: dict, vocab_size: int, seed: int) -> int:
    """Run once every program the cell's traffic can reach, and no other.
    Prompts up to one prefill chunk are admitted whole, padded to a bucket,
    and same-bucket prompts granted in one step are batched to a power of
    two bounded by the step's token budget: every (batch, bucket) pair the
    clip of the prompt lengths allows. Longer prompts go chunk by chunk
    through one program. Decode and sampling run in every pass."""
    rng = np.random.default_rng([seed, 3])
    chunk, budget = srv.prefill_chunk, srv.prefill_token_budget
    lo, hi = prompt_len["min"], prompt_len["max"]
    passes = []
    if chunk == 0 or lo <= chunk:
        top = hi if chunk == 0 else min(hi, chunk)
        width = _bucket(lo)
        while True:
            length = min(width, top)
            n = 1
            while n * width <= max(budget or width, width):
                passes.append((length, n))
                n *= 2
            if width >= top:
                break
            width *= 2
    if chunk and hi > chunk:
        passes.append((min(hi, 2 * chunk + 1), 1))
    for length, n in passes:
        for _ in range(n):
            srv.submit(rng.integers(1, vocab_size, length, dtype=np.int32),
                       max_new_tokens=2)
        srv.run_until_drained(max_steps=10_000)
    srv.check_invariants()
    srv.end_warmup()
    return len(passes)


def trace_placement(cell: dict, seconds: float) -> tuple:
    """(start, length, stopped inside the window?) of the profiler's trace,
    in seconds of the window. Stopping the profiler stalls this thread for
    seconds (the longer the more steps the trace holds), and an open loop's
    arrivals pile up behind it. A cell whose file says ``"trace_at": "end"``
    is traced over the window's last ``trace_seconds``, whatever the
    window's length, and the profiler is stopped after the loop, where no
    request due in the window waits for it. Any other cell is traced from
    30 % of the window and stopped there."""
    length = float(cell.get("trace_seconds", 3.0))
    if cell.get("trace_at") == "end":
        return max(seconds - length, 0.0), length, False
    return 0.3 * seconds, length, True


def run(ctx: dict) -> dict:
    import jax.numpy as jnp

    import deepspeed_tpu as ds
    from deepspeed_tpu.serving import RequestState

    config, cell, traffic = ctx["config"], ctx["cell"], ctx["traffic"]
    rehearsal = ctx["rehearsal"]
    seconds = ctx["seconds"]
    failures = []
    compiles = ctx["compile_requests"]

    # -- build ------------------------------------------------------------
    model, model_cfg = build.build_model(config["model"], None, rehearsal)
    dtype = build._dtype(config["model"]["dtype"])
    params = build.init_params(
        model, (jnp.zeros((1, 8), jnp.int32),),
        {"method": getattr(model, config["model"]["init_method"])},
        ctx["seed"], cast_to=dtype)
    ctx["mark"]("imports_and_weights")
    server = config["rehearsal_server"] if rehearsal else config["server"]
    srv = ds.init_serving(model, model_parameters=params, **server)
    ctx["mark"]("server_built")
    gen_params = dict(traffic["params"])
    if rehearsal:
        gen_params.update(traffic["rehearsal_params"])
    context_len = int(model_cfg.max_seq_len)
    load = ctx["manifest"].generator(traffic["generator"]).generate(
        gen_params, ctx["seed"], seconds,
        {"vocab_size": model_cfg.vocab_size, "context_len": context_len})
    warm_passes = warm_up(srv, gen_params["prompt_len"],
                          model_cfg.vocab_size, ctx["seed"])
    ctx["mark"]("warmed_up")

    # -- the run ------------------------------------------------------------
    trace = ctx["trace"]
    trace_at, trace_len, stop_in_window = trace_placement(cell, seconds)
    grace = float(gen_params.get("tail_s", 5.0))
    clock = time.perf_counter
    pool = srv.pool
    num_pages = getattr(pool, "num_pages", None)
    records, by_id, live = [], {}, []
    step_spans, pages_mapped, traced_steps, progress = [], [], [], []
    at_open = at_close = None
    t_open = clock() + float(gen_params["lead_in_s"])

    def snapshot() -> dict:
        m = srv.metrics
        return {"prefill_tokens": m.prefill_tokens,
                "decode_steps": m.decode_steps, "slot_steps": m.slot_steps,
                "preempted": m.preempted, "failed": m.failed,
                "finished": len(m.finished), "steps": srv.step_id}

    def window_requests():
        return [r for r in records if 0.0 <= r["due_s"] < seconds]

    while True:
        now = clock() - t_open
        if at_open is None and now >= 0.0:
            at_open = snapshot()
            compiles.count, compiles.active = 0, True
            ctx["mark_setup_done"]()
        if at_open is not None and at_close is None and now >= seconds:
            at_close = snapshot()
            compiles.active = False
        if trace is not None and not trace.done:
            if not trace.running and now >= trace_at:
                trace.start()
            elif trace.running and stop_in_window \
                    and now >= trace_at + trace_len:
                trace.stop()
        if at_close is not None:
            waiting = [r for r in window_requests()
                       if not r["token_s"] and not r["rejected"]]
            unsent = load.next_due_s()      # due in the window, not yet
            #                                 handed over (the loop stalled)
            if load.closed or now >= seconds + grace or not (
                    waiting or (unsent is not None and unsent < seconds)):
                break

        for spec in load.due(now):
            with device.annotate("bench/submit"):
                req = srv.submit(spec["prompt"],
                                 max_new_tokens=spec["max_new_tokens"])
            rec = {"spec": spec, "req": req, "due_s": spec["due_s"],
                   "submit_s": clock() - t_open, "token_s": [],
                   "rejected": req.state is RequestState.REJECTED,
                   "finished_s": None}
            records.append(rec)
            by_id[req.request_id] = rec
            if not rec["rejected"]:
                live.append(rec)

        if srv.live_count or srv.pending:
            t0 = clock()
            with device.annotate("bench/step"):
                finished = srv.step()
            t1 = clock() - t_open
            for rec in live:
                n = len(rec["req"].output_tokens)
                if n > len(rec["token_s"]):
                    rec["token_s"].extend([t1] * (n - len(rec["token_s"])))
            for req in finished:
                rec = by_id.get(req.request_id)
                if rec is not None and rec["finished_s"] is None:
                    rec["finished_s"] = t1
                    live.remove(rec)
                    load.on_finished(rec["spec"], t1)
            if 0.0 <= t1 < seconds:
                step_spans.append((t0 - t_open, t1))
                # the backlog: requests handed to the server that have shown
                # no token yet, wherever they wait (the queue, or a slot
                # whose prompt is still being prefilled chunk by chunk)
                progress.append((t1, srv.metrics.prefill_tokens,
                                 sum(1 for r in live if not r["token_s"])))
                if num_pages is not None:
                    pages_mapped.append(
                        int(np.count_nonzero(pool.table < num_pages)))
                if trace is not None and trace.running:
                    running = [r for r in live if r["token_s"]]
                    traced_steps.append(
                        (sum(len(r["spec"]["prompt"]) + len(r["token_s"])
                             for r in running), len(running)))
        else:
            nxt = load.next_due_s()
            if nxt is None:
                break
            with device.annotate("bench/idle_wait"):
                time.sleep(min(max(nxt - (clock() - t_open), 0.0), 0.05))
    compiles.active = False
    if trace is not None:
        trace.stop()
    if at_close is None:
        at_close = snapshot()

    # -- end to end -----------------------------------------------------------
    def over(w: float) -> dict:
        """The end-to-end metrics over the first ``w`` seconds of the window
        (the whole of it for the result; shorter stretches go to the facts,
        to show what a shorter ``run_seconds`` would have read)."""
        due = [r for r in records if 0.0 <= r["due_s"] < w]
        ttft = stats.ttft_ms([r["due_s"] for r in due],
                             [r["token_s"][0] if r["token_s"] else None
                              for r in due])
        gaps = stats.token_gaps_ms([r["token_s"] for r in records], 0.0, w)
        # prompt tokens as the server prefilled them (its own counter, read
        # at both ends: 64 a chunk, so the count is smooth where whole
        # documents complete only a few times in a window) + tokens the
        # caller saw appear
        tokens_out = sum(1 for r in records for t in r["token_s"]
                         if 0.0 <= t < w)
        prefilled = [p for t, p, _ in progress if t < w]
        tokens_in = (prefilled[-1] if prefilled
                     else at_open["prefill_tokens"]) \
            - at_open["prefill_tokens"]
        out = {"serve_tok_s": (tokens_in + tokens_out) / w,
               "tokens_in": tokens_in, "tokens_out": tokens_out,
               "requests_due": len(due), "gaps": len(gaps)}
        if ttft and np.isfinite(ttft).all():    # (not so where no request
            #                                     due here showed a token)
            out["ttft_ms"] = [round(x) for x in sorted(ttft)]
            out["ttft_p50_ms"] = stats.percentile(ttft, 50)
            out["ttft_p90_ms"] = stats.percentile(ttft, 90)
        if gaps:
            out.update({f"gap_p{q}_ms": stats.percentile(gaps, q)
                        for q in (50, 75, 90, 95, 99)})
            out["gap_mean_ms"] = float(np.mean(gaps))
        return out

    due = window_requests()
    whole = over(seconds)
    end_to_end = {k: whole[k] for k in ("serve_tok_s", "ttft_p50_ms",
                                        "gap_p50_ms", "gap_p90_ms",
                                        "gap_p99_ms")
                  if k in whole}
    shorter = {f"{w:g}": over(w) for w in (20.0, 30.0, 40.0) if w < seconds}

    # -- correct ----------------------------------------------------------------
    done = [r for r in records if r["finished_s"] is not None]
    bad_state = [r["req"].request_id for r in records
                 if r["req"].state is RequestState.FAILED]
    if bad_state:
        failures.append(f"requests FAILED: {bad_state[:8]}")
    short = [(r["req"].request_id, len(r["req"].output_tokens),
              r["spec"]["max_new_tokens"]) for r in done
             if r["req"].state is not RequestState.FINISHED
             or len(r["req"].output_tokens) != r["spec"]["max_new_tokens"]]
    if short:
        failures.append(f"finished requests (id, tokens, asked) that did "
                        f"not get what they asked for: {short[:8]}")
    try:
        srv.check_invariants()
    except Exception as e:                       # reported, not raised
        failures.append(f"check_invariants: {e!r}")
    no_first = [r for r in due if not r["token_s"]]
    if compiles.count:
        failures.append(f"{compiles.count} compile request(s) inside the "
                        f"window")
    reference_check = []
    judged = [r for r in records if len(r["req"].output_tokens) >= 2]
    if not judged:
        failures.append("no request produced two tokens: nothing to hold "
                        "to the reference")
    else:
        reference = ctx["manifest"].reference(config["reference"]["file"])
        logits_fn = reference.make_forward(**{
            k: getattr(model_cfg, v)
            for k, v in config["reference"]["args_from_config"].items()})
        # the shortest, the longest and two between, of the requests that
        # have tokens (finished or still running: a prefix of a greedy
        # answer is judged like a whole one)
        by_len = sorted(judged, key=lambda r: len(r["spec"]["prompt"])
                        + len(r["req"].output_tokens))
        picks = [by_len[i] for i in sorted({
            round(k * (len(by_len) - 1) / (REFERENCE_REQUESTS - 1))
            for k in range(REFERENCE_REQUESTS)})]
        score_len = int(gen_params["output_len"]["max"])
        for r in picks:
            out = reference.check_greedy(
                logits_fn, srv.engine.params, r["spec"]["prompt"],
                list(r["req"].output_tokens), context_len, score_len,
                LOGIT_REL_TOL)
            out["prompt_len"] = len(r["spec"]["prompt"])
            reference_check.append(out)
            if not out["ok"]:
                failures.append(
                    f"request {r['req'].request_id} (prompt "
                    f"{out['prompt_len']}, {out['positions']} tokens): a "
                    f"served token lies {out['worst_shortfall']:.4f} below "
                    f"the reference's best logit, tolerance "
                    f"{out['tolerance_there']:.4f}")

    queue_wait = [r["req"].queue_wait * 1e3 for r in records
                  if r["token_s"] and 0.0 <= r["token_s"][0] < seconds
                  and r["req"].queue_wait is not None]
    n_failed = sum(1 for r in due if r["rejected"]
                   or r["req"].state is RequestState.FAILED
                   or (not load.closed and not r["token_s"]))
    counters = {k: at_close[k] - at_open[k] for k in at_open}
    counters.update(compiles_in_window=compiles.count, num_pages=num_pages,
                    num_slots=int(srv.pool.num_slots))
    steps = [(b - a) * 1e3 for a, b in step_spans]
    return {
        "attempted": len(due), "failed": n_failed, "failures": failures,
        "end_to_end": end_to_end,
        "facts": {
            "requests_due_in_window": len(due),
            "requests_finished": len(done),
            "rejected": sum(1 for r in records if r["rejected"]),
            "no_first_token": len(no_first),
            "finished_in_window": sum(
                1 for r in done if 0.0 <= r["finished_s"] < seconds),
            "seconds": seconds, "whole_window": whole,
            "shorter_windows": shorter,
            "steps_in_window": len(step_spans),
            "step_ms_mean": float(np.mean(steps)) if steps else None,
            "step_ms_p50_p90_p99_max": [
                float(x) for x in np.percentile(steps, [50, 90, 99, 100])]
            if steps else None,
            # mean backlog over the middle fifth of the window and over
            # its last fifth: a server that keeps up holds them level
            "backlog_mid_end": [
                float(np.mean([b for t, _, b in progress
                               if lo * seconds <= t < (lo + 0.2) * seconds]
                              or [0])) for lo in (0.4, 0.8)],
            "pages_mapped_mid_end_peak": [
                pages_mapped[len(pages_mapped) // 2], pages_mapped[-1],
                max(pages_mapped)] if pages_mapped else None,
            "window_counters": counters, "warm_passes": warm_passes,
            "reference_check": reference_check,
            "kernel_active": bool(getattr(pool, "kernel_active", False)),
            "prefill_chunk": int(srv.prefill_chunk),
        },
        "counters": counters,
        "spans": {"bench/step": step_spans},
        "samples": {
            "pages_mapped": pages_mapped,
            "queue_wait_ms": queue_wait,
            "submit_late_ms": [(r["submit_s"] - r["due_s"]) * 1e3
                               for r in due],
            "traced_decode_steps": traced_steps,
        },
        "kernel_dims": {"H": model_cfg.n_head, "KV": model_cfg.kv_heads,
                        "D": model_cfg.head_dim, "L": model_cfg.n_layer},
    }
