"""Stall-free admission tests: chunked prefill interleaved with decode
and batched bucketed admission must be pure SCHEDULING changes — greedy
tokens bitwise-match ``generate()`` through every admission path, the
chunk/batch programs never recompile on churn, long prompts stop
stalling live decode slots, and capacity exhaustion retires with
``"length_cap"`` instead of silently clamping cache writes."""

import numpy as np
import pytest

from deepspeed_tpu.serving import RequestState

from .conftest import make_server


def _prompts(rng, lengths):
    return [rng.integers(1, 64, size=n).astype(np.int32) for n in lengths]


def test_chunked_prefill_parity_with_generate(stack, servers, pool):
    """Prompts longer than the chunk width stream in chunk by chunk; the
    resulting greedy tokens must bitwise-match whole-prompt generate()."""
    _, _, engine = stack
    rng = np.random.default_rng(23)
    lengths = [40, 33, 17]          # 3 chunks, 3 chunks (odd tail), 2 chunks
    budgets = [6, 5, 4]
    prompts = _prompts(rng, lengths)
    srv = servers(pool, num_slots=2, max_queue_depth=8, prefill_chunk=16)
    assert srv._stall_free and srv.prefill_chunk == 16
    reqs = [srv.submit(p, max_new_tokens=b) for p, b in zip(prompts, budgets)]
    srv.run_until_drained(max_steps=300)
    for req, prompt, budget in zip(reqs, prompts, budgets):
        assert req.state == RequestState.FINISHED, req.request_id
        expected = engine.generate(prompt[None], max_new_tokens=budget)[0]
        np.testing.assert_array_equal(req.tokens(), expected,
                                      err_msg=f"req {req.request_id}")


def test_bucket_boundary_prompt_lengths(stack, servers, pool):
    """Power-of-two bucket edges (15/16/17, 31/32/33) and a prompt that
    exactly fills its slot with its budget (60 + 4 = capacity 64) must
    all admit, finish, and match generate() bitwise."""
    _, _, engine = stack
    rng = np.random.default_rng(29)
    lengths = [15, 16, 17, 31, 32, 33, 60]
    budgets = [3, 3, 3, 3, 3, 3, 4]
    prompts = _prompts(rng, lengths)
    srv = servers(pool, num_slots=2, max_queue_depth=8, prefill_chunk=16)
    reqs = [srv.submit(p, max_new_tokens=b) for p, b in zip(prompts, budgets)]
    srv.run_until_drained(max_steps=400)
    for req, prompt, budget in zip(reqs, prompts, budgets):
        assert req.state == RequestState.FINISHED
        assert req.finish_reason == "length"
        expected = engine.generate(prompt[None], max_new_tokens=budget)[0]
        np.testing.assert_array_equal(req.tokens(), expected,
                                      err_msg=f"len {req.prompt_len}")


def test_long_prompt_does_not_stall_running_slot(stack, pool):
    """THE stall-free property: while a long prompt is PREFILLING chunk
    by chunk, an already-running request keeps emitting one token per
    step — admission no longer monopolizes whole steps."""
    _, _, engine = stack
    rng = np.random.default_rng(31)
    srv = make_server(engine, pool, num_slots=2, max_queue_depth=8,
                      prefill_chunk=16)
    short = srv.submit(rng.integers(1, 64, size=6).astype(np.int32),
                       max_new_tokens=20)
    srv.step()
    srv.settle()
    assert short.state == RequestState.RUNNING

    long = srv.submit(rng.integers(1, 64, size=48).astype(np.int32),
                      max_new_tokens=4)
    while long.state in (RequestState.QUEUED, RequestState.PREFILLING):
        before = len(short.output_tokens)
        srv.step()
        srv.settle()        # (a token is visible when its step is settled)
        if long.state == RequestState.PREFILLING:
            # a mid-prefill step still ran the decode for the live slot
            assert len(short.output_tokens) == before + 1
    assert long.state == RequestState.RUNNING
    assert long.prefill_pos == long.prompt_len
    srv.run_until_drained(max_steps=100)
    for req in (short, long):
        expected = engine.generate(np.asarray(req.prompt)[None],
                                   max_new_tokens=req.max_new_tokens)[0]
        np.testing.assert_array_equal(req.tokens(), expected)
    # the long admission took multiple steps => multiple prefill
    # dispatches, and decode time kept accumulating alongside
    s = srv.stats()
    assert s["prefill_dispatches"] >= 3
    assert s["stall_time_s"] > 0 and s["decode_time_s"] > 0


class _FakeMonitor:
    enabled = True

    def __init__(self):
        self.events = []

    def write_events(self, events):
        self.events.extend(events)


def test_length_cap_retires_full_slot(stack, pool):
    """A slot whose cache row fills to max_seq_len retires with
    ``"length_cap"`` (plus its monitor event) instead of silently
    clamp-overwriting the last column forever."""
    _, _, engine = stack
    rng = np.random.default_rng(37)
    mon = _FakeMonitor()
    srv = make_server(engine, pool, num_slots=1, max_queue_depth=4,
                      prefill_chunk=16, monitor=mon)
    # normal admission control forbids prompt+budget > capacity, which is
    # exactly what makes the cap unreachable; disable it to exercise the
    # engine-side safety net behind it (rows, and on the paged pool pages)
    srv.scheduler.capacity = srv.scheduler.num_pages = None
    req = srv.submit(rng.integers(1, 64, size=60).astype(np.int32),
                     max_new_tokens=10)
    srv.run_until_drained(max_steps=100)
    assert req.state == RequestState.FINISHED
    assert req.finish_reason == "length_cap"
    # 60 prompt positions + first token at 60 + 4 decode writes = 64
    assert len(req.output_tokens) == 5
    assert int(srv.pool.free_count) == 1  # slot returned
    assert "serving/finished/length_cap" in [t for t, _, _ in mon.events]


def test_spec_decode_skips_prefilling_slots(stack, pool):
    """Speculative decoding + chunked admission: verify steps must not
    advance (or corrupt) half-prefilled rows — outputs stay bitwise
    equal to generate() for both the running and the chunked request."""
    _, _, engine = stack
    rng = np.random.default_rng(41)
    srv = make_server(engine, pool, num_slots=2, max_queue_depth=8,
                      prefill_chunk=16, spec_decode={"drafter": "ngram",
                                                     "k": 4})
    short = srv.submit(rng.integers(1, 64, size=9).astype(np.int32),
                       max_new_tokens=12)
    long = srv.submit(rng.integers(1, 64, size=44).astype(np.int32),
                      max_new_tokens=6)
    srv.run_until_drained(max_steps=200)
    for req in (short, long):
        assert req.state == RequestState.FINISHED
        expected = engine.generate(np.asarray(req.prompt)[None],
                                   max_new_tokens=req.max_new_tokens)[0]
        np.testing.assert_array_equal(req.tokens(), expected,
                                      err_msg=f"req {req.request_id}")


def test_batched_admission_is_one_dispatch(stack, pool):
    """Same-bucket waiting prompts admit through ONE prefill dispatch and
    ONE multi-row scatter, not one dispatch per prompt."""
    _, _, engine = stack
    rng = np.random.default_rng(43)
    srv = make_server(engine, pool, num_slots=4, max_queue_depth=8,
                      prefill_chunk=16, prefill_token_budget=64)
    reqs = [srv.submit(p, max_new_tokens=3)
            for p in _prompts(rng, [5, 9, 12])]

    calls = []
    orig = engine._jit_prefill_at

    def counting(*a, **k):
        calls.append(np.shape(a[1]))
        return orig(*a, **k)

    engine._jit_prefill_at = counting
    try:
        srv.step()
    finally:
        engine._jit_prefill_at = orig
    assert len(calls) == 1          # one batched dispatch for all three
    assert calls[0][0] == 4         # power-of-two batch bucket (3 -> 4)
    assert all(r.state == RequestState.RUNNING for r in reqs)
    assert srv.stats()["prefill_dispatches"] == 1
    srv.run_until_drained(max_steps=50)
    for req in reqs:
        expected = engine.generate(np.asarray(req.prompt)[None],
                                   max_new_tokens=3)[0]
        np.testing.assert_array_equal(req.tokens(), expected)


def test_token_budget_bounds_admission(stack, pool):
    """The per-step token budget defers admissions past the budget and an
    in-flight chunk blocks new grants entirely — but the FIFO head is
    never starved (liveness overshoot when nothing else was spent)."""
    _, _, engine = stack
    rng = np.random.default_rng(47)
    srv = make_server(engine, pool, num_slots=4, max_queue_depth=8,
                      prefill_chunk=16, prefill_token_budget=16)
    a = srv.submit(rng.integers(1, 64, size=6).astype(np.int32),
                   max_new_tokens=8)
    b = srv.submit(rng.integers(1, 64, size=6).astype(np.int32),
                   max_new_tokens=8)
    srv.step()                       # budget 16 = one bucket-16 admission
    assert a.state == RequestState.RUNNING
    assert b.state == RequestState.QUEUED
    srv.step()
    assert b.state == RequestState.RUNNING

    long = srv.submit(rng.integers(1, 64, size=40).astype(np.int32),
                      max_new_tokens=4)
    srv.step()                       # head granted despite cost==budget
    assert long.state == RequestState.PREFILLING
    c = srv.submit(rng.integers(1, 64, size=6).astype(np.int32),
                   max_new_tokens=4)
    srv.step()                       # in-flight chunk consumes the budget
    assert long.state == RequestState.PREFILLING
    assert c.state == RequestState.QUEUED
    srv.run_until_drained(max_steps=100)
    for req in (a, b, long, c):
        assert req.state == RequestState.FINISHED
        expected = engine.generate(np.asarray(req.prompt)[None],
                                   max_new_tokens=req.max_new_tokens)[0]
        np.testing.assert_array_equal(req.tokens(), expected)


def test_no_recompile_across_chunked_and_batched_churn(stack, pool):
    """Extended churn coverage: after one warmup wave that touches every
    program (batched admission at nB=1/2, the chunk program, decode),
    further waves of NEW lengths/offsets/slots must not add a single
    compiled program."""
    _, _, engine = stack
    rng = np.random.default_rng(53)
    srv = make_server(engine, pool, own_programs=True, num_slots=2,
                      max_queue_depth=16, prefill_chunk=16)
    # warmup: two shorts together (nB=2), a straggler short (nB=1 refill),
    # and a long prompt (chunk program at several offsets)
    for n, b in [(6, 3), (9, 3), (7, 3), (40, 3)]:
        srv.submit(rng.integers(1, 64, size=n).astype(np.int32),
                   max_new_tokens=b)
    srv.run_until_drained(max_steps=200)
    n_decode = engine._jit_decode._cache_size()
    n_prefill = engine._jit_prefill_at._cache_size()
    n_chunk = engine._jit_prefill_chunk._cache_size()
    srv.end_warmup()  # arm the watchdog's post-warmup counter

    # churn: different prompt lengths in the same buckets, different
    # chunk counts/final-tail widths, reused slots
    for n, b in [(5, 4), (11, 2), (33, 3), (48, 2), (8, 3), (17, 2)]:
        srv.submit(rng.integers(1, 64, size=n).astype(np.int32),
                   max_new_tokens=b)
    srv.run_until_drained(max_steps=400)
    assert engine._jit_decode._cache_size() == n_decode
    assert engine._jit_prefill_at._cache_size() == n_prefill
    assert engine._jit_prefill_chunk._cache_size() == n_chunk
    assert srv.watchdog.recompiles == 0


def test_config_validation_and_fallbacks(stack, pool):
    """Knob validation: chunk auto-halves until it divides capacity,
    budget below the chunk raises, chunk=0 falls back to serial
    admission."""
    _, _, engine = stack
    srv = make_server(engine, pool, num_slots=1, prefill_chunk=48)
    assert srv._stall_free
    assert srv.pool.capacity % srv.prefill_chunk == 0
    with pytest.raises(ValueError, match="prefill_token_budget"):
        make_server(engine, pool, num_slots=1, prefill_chunk=32,
                    prefill_token_budget=16)
    with pytest.raises(ValueError, match="prefill_chunk"):
        make_server(engine, pool, num_slots=1, prefill_chunk=-1)
    off = make_server(engine, pool, num_slots=1, prefill_chunk=0)
    assert not off._stall_free and off.prefill_token_budget is None


def test_metrics_prefill_decode_split(stack, pool):
    _, _, engine = stack
    rng = np.random.default_rng(59)
    srv = make_server(engine, pool, num_slots=2, max_queue_depth=8,
                      prefill_chunk=16)
    for n in (6, 10, 40):
        srv.submit(rng.integers(1, 64, size=n).astype(np.int32),
                   max_new_tokens=4)
    srv.run_until_drained(max_steps=200)
    s = srv.stats()
    assert s["completed"] == 3
    assert s["prefill_tokens"] == 6 + 10 + 40  # true tokens, not padding
    assert s["prefill_dispatches"] >= 3
    assert s["prefill_time_s"] > 0 and s["decode_time_s"] > 0
    assert 0 <= s["stall_time_s"] <= s["prefill_time_s"]
    # inter-token gap tail: every step where a RUNNING request waited
    # contributes one whole-step wall time
    assert s["step_gap_p50_ms"] is not None and s["step_gap_p50_ms"] > 0
    assert s["step_gap_p99_ms"] >= s["step_gap_p50_ms"]
