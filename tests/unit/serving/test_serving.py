"""Serving subsystem tests: continuous batching over the slot-pooled KV
cache must be a pure SCHEDULING change — per-request tokens bitwise-match
whole-batch ``generate()``, slot reuse never recompiles the decode step,
staggered arrivals admit/retire correctly, and admission control sheds
load with a reason instead of raising."""

import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.serving import FIFOScheduler, RequestState, ServingEngine

from .conftest import POOLS, make_server


def test_tokens_bitwise_match_generate(stack, pool):
    """Continuous batching through 2 slots (forcing multi-wave slot reuse)
    must produce EXACTLY the tokens static-batch generate() produces per
    prompt — scheduling policy can never change model output (greedy)."""
    _, _, engine = stack
    rng = np.random.default_rng(7)
    lengths = [5, 9, 12, 5, 9, 12]
    budgets = [6, 4, 8, 3, 7, 5]
    prompts = [rng.integers(0, 64, size=n).astype(np.int32) for n in lengths]

    srv = make_server(engine, pool, num_slots=2, max_queue_depth=8)
    reqs = [srv.submit(p, max_new_tokens=b) for p, b in zip(prompts, budgets)]
    finished = srv.run_until_drained(max_steps=200)

    assert len(finished) == len(reqs)
    for req, prompt, budget in zip(reqs, prompts, budgets):
        assert req.state == RequestState.FINISHED
        assert req.finish_reason == "length"
        expected = engine.generate(prompt[None], max_new_tokens=budget)[0]
        np.testing.assert_array_equal(req.tokens(), expected,
                                      err_msg=f"req {req.request_id}")


@pytest.mark.parametrize("paged", [False, {"page_size": 8, "kernel": "on"}])
def test_a_slot_left_idle_past_the_capacity_breaks_no_invariant(stack, paged):
    """Every decode step advances every slot's index, free ones too: a
    slot nobody was seated in for more steps than the cache has columns
    counts past the capacity (``positions()`` clamps it). The audit is
    about seated slots; the answers stay what ``generate()`` gives. On
    the chip a chat server reached this after 25 s once a step took 12
    ms (chip run of PR 27)."""
    _, _, engine = stack
    rng = np.random.default_rng(11)
    srv = ServingEngine(engine, num_slots=2, max_queue_depth=8,
                        paged_kv=paged)
    for _ in range(3):                    # one at a time: slot 1 never used
        prompt = rng.integers(0, 64, size=6).astype(np.int32)
        req = srv.submit(prompt, max_new_tokens=30)
        srv.run_until_drained(max_steps=100)
        srv.check_invariants()
        np.testing.assert_array_equal(
            req.tokens(), engine.generate(prompt[None], max_new_tokens=30)[0])
    assert srv.pool.starts.max() > srv.pool.capacity
    # and a seated slot out of range is still caught
    srv.submit(rng.integers(0, 64, size=6).astype(np.int32),
               max_new_tokens=4)
    srv.step()
    (slot,) = srv._slot_req
    srv.pool.starts[slot] = srv.pool.capacity + 1
    with pytest.raises(Exception, match="seated slots"):
        srv.check_invariants()


def test_staggered_admission_and_slot_reuse(stack, pool):
    """A request submitted while all slots are busy waits QUEUED, then is
    admitted into the retired request's slot; timing stamps are ordered."""
    _, _, engine = stack
    rng = np.random.default_rng(3)
    srv = make_server(engine, pool, num_slots=2, max_queue_depth=8)
    r1 = srv.submit(rng.integers(0, 64, size=6).astype(np.int32),
                    max_new_tokens=2)
    r2 = srv.submit(rng.integers(0, 64, size=10).astype(np.int32),
                    max_new_tokens=12)
    # admit both; r1 (budget 2) finishes with this step's tokens
    done = srv.step() + srv.settle()
    assert r1 in done and r1.state == RequestState.FINISHED
    assert r2.state == RequestState.RUNNING

    r3 = srv.submit(rng.integers(0, 64, size=7).astype(np.int32),
                    max_new_tokens=4)
    assert r3.state == RequestState.QUEUED and srv.pending == 1
    srv.step()  # admits r3 into r1's freed slot
    assert r3.state == RequestState.RUNNING
    assert r3.slot == r1.slot

    srv.run_until_drained(max_steps=50)
    for r in (r1, r2, r3):
        assert r.state == RequestState.FINISHED
        assert r.submit_time <= r.admit_time <= r.first_token_time \
            <= r.finish_time
        assert r.queue_wait >= 0 and r.ttft >= 0
        assert len(r.output_tokens) == r.max_new_tokens


def test_slot_reuse_does_not_recompile(stack, pool):
    """Retire/admit churn across waves must keep the jitted decode and
    prefill caches at a FIXED number of compiled programs — dead slots are
    masked padding, not shape changes."""
    _, _, engine = stack
    rng = np.random.default_rng(5)
    srv = make_server(engine, pool, own_programs=True, num_slots=2,
                      max_queue_depth=16)
    # wave A: compile everything once — 3 requests over 2 slots so both
    # admission batch buckets (nB=2 full step, nB=1 single refill) warm up
    for _ in range(3):
        srv.submit(rng.integers(0, 64, size=6).astype(np.int32),
                   max_new_tokens=3)
    srv.run_until_drained(max_steps=50)
    n_decode = engine._jit_decode._cache_size()
    n_prefill = engine._jit_prefill_at._cache_size()
    srv.end_warmup()  # arm the watchdog's post-warmup counter

    for _ in range(5):  # wave B: same buckets through reused slots
        srv.submit(rng.integers(0, 64, size=6).astype(np.int32),
                   max_new_tokens=4)
    srv.run_until_drained(max_steps=100)
    assert engine._jit_decode._cache_size() == n_decode
    assert engine._jit_prefill_at._cache_size() == n_prefill
    assert srv.watchdog.recompiles == 0


def test_admission_control_rejects_with_reason(stack, pool):
    _, _, engine = stack
    rng = np.random.default_rng(11)
    srv = make_server(engine, pool, num_slots=1, max_queue_depth=2)

    ok = [srv.submit(rng.integers(0, 64, size=5).astype(np.int32),
                     max_new_tokens=2) for _ in range(2)]
    full = srv.submit(rng.integers(0, 64, size=5).astype(np.int32),
                      max_new_tokens=2)
    assert full.state == RequestState.REJECTED
    assert full.reject_reason == "queue_full"

    # prompt + budget exceeding KV capacity is rejected up front, not
    # admitted into a slot it can never finish in
    long = srv.submit(rng.integers(0, 64, size=60).astype(np.int32),
                      max_new_tokens=10)
    assert long.state == RequestState.REJECTED
    assert long.reject_reason == "prompt_too_long"

    with pytest.raises(ValueError, match="max_new_tokens"):
        srv.submit(np.zeros((4,), np.int32), max_new_tokens=0)

    srv.run_until_drained(max_steps=50)
    assert all(r.state == RequestState.FINISHED for r in ok)
    stats = srv.stats()
    assert stats["completed"] == 2
    assert stats["rejected"] == {"queue_full": 1, "prompt_too_long": 1}


def test_eos_retires_early(stack, pool):
    """With eos_token_id set, a slot retires the moment greedy emits it —
    and the emitted prefix still matches generate()'s."""
    _, _, engine = stack
    rng = np.random.default_rng(13)
    prompt = rng.integers(0, 64, size=8).astype(np.int32)
    full = engine.generate(prompt[None], max_new_tokens=8)[0]
    gen = np.asarray(full[len(prompt):])
    eos = int(gen[2])  # greedy will deterministically reach this token
    first = int(np.argmax(gen == eos))

    srv = make_server(engine, pool, num_slots=1, max_queue_depth=2)
    req = srv.submit(prompt, max_new_tokens=8, eos_token_id=eos)
    srv.run_until_drained(max_steps=50)
    assert req.finish_reason == "eos"
    assert req.output_tokens[-1] == eos
    np.testing.assert_array_equal(req.output_tokens, gen[:first + 1])


def test_scheduler_unit():
    sched = FIFOScheduler(num_slots=2, max_queue_depth=2, capacity=32)

    class R:  # minimal stand-in (the admission surface of Request:
        # capacity charges seed + REMAINING budget, see Scheduler.submit)
        def __init__(self, n, m):
            self.prompt_len, self.max_new_tokens = n, m
            self.output_tokens = []
            self.seed_len = n

    ok, _ = sched.submit(R(4, 4))
    assert ok
    ok, reason = sched.submit(R(30, 8))
    assert not ok and reason == "prompt_too_long"
    sched.submit(R(4, 4))
    ok, reason = sched.submit(R(4, 4))
    assert not ok and reason == "queue_full"
    assert len(sched.grant(free_slots=2)) == 2
    assert sched.pending == 0


def test_init_serving_wrapper(stack, pool):
    """ds.init_serving splits serving knobs from inference knobs: every
    option of ServingEngine's constructor goes to the server."""
    model, params, _ = stack
    srv = ds.init_serving(model, config={"dtype": "float32"},
                          model_parameters=params, num_slots=2,
                          max_queue_depth=4, seed=3, role="both",
                          paged_kv=POOLS[pool])
    assert isinstance(srv, ServingEngine)
    assert srv.pool.num_slots == 2 and srv.role == "both"
    assert srv._paged == (pool == "paged")
    req = srv.submit(np.arange(5, dtype=np.int32), max_new_tokens=2)
    srv.run_until_drained(max_steps=20)
    assert req.state == RequestState.FINISHED


def test_release_double_free_guard(stack):
    """Releasing a freed (or out-of-range) slot raises instead of
    silently corrupting the free heap into double-granting a slot."""
    _, _, engine = stack
    from deepspeed_tpu.serving import SlotPool
    pool = SlotPool(engine.kv_cache_spec(), 2)
    s = pool.alloc()
    pool.release(s)
    with pytest.raises(RuntimeError, match="double release"):
        pool.release(s)
    assert pool.free_count == 2  # the guard fired before corrupting
    with pytest.raises(ValueError, match="range"):
        pool.release(7)


def test_midstep_decode_exception_never_leaks_slots(stack, pool):
    """An engine exception mid-decode must FAIL the running requests
    (their donated KV state is unrecoverable), keep queued requests
    queued, return every slot, and leave the server usable."""
    _, _, engine = stack
    rng = np.random.default_rng(41)
    srv = make_server(engine, pool, num_slots=2, max_queue_depth=8)
    r1 = srv.submit(rng.integers(0, 64, size=6).astype(np.int32),
                    max_new_tokens=6)
    r2 = srv.submit(rng.integers(0, 64, size=9).astype(np.int32),
                    max_new_tokens=6)
    r3 = srv.submit(rng.integers(0, 64, size=7).astype(np.int32),
                    max_new_tokens=4)  # no free slot: stays QUEUED
    srv.step()
    assert r1.state == r2.state == RequestState.RUNNING

    # the decode program the server dispatches: the engine's on the
    # contiguous pool, the pool's own dense composition on the paged one
    owner, name = (srv.pool, "_paged_decode_jit") if srv._paged \
        else (engine, "_jit_decode")
    orig = getattr(owner, name)
    setattr(owner, name, lambda *a, **k: (_ for _ in ()).throw(
        RuntimeError("injected decode failure")))
    try:
        with pytest.raises(RuntimeError, match="injected"):
            srv.step()
    finally:
        setattr(owner, name, orig)

    assert srv.live_count == 0 and srv.pool.free_count == 2
    for r in (r1, r2):
        assert r.state == RequestState.FAILED
        assert r.finish_reason == "error" and r.finish_time is not None
    assert r3.state == RequestState.QUEUED  # survives the abort

    srv.run_until_drained(max_steps=50)    # server still works
    assert r3.state == RequestState.FINISHED
    expected = engine.generate(np.asarray(r3.prompt)[None],
                               max_new_tokens=4)[0]
    np.testing.assert_array_equal(r3.tokens(), expected)
    assert srv.stats()["failed"] == 2


def test_admit_exception_requeues_request(stack, pool):
    """A prefill exception during admission rolls the request back to
    QUEUED (front of queue, state scrubbed) instead of leaking its slot
    or failing it — it lost nothing but time."""
    _, _, engine = stack
    rng = np.random.default_rng(43)
    srv = make_server(engine, pool, num_slots=2, max_queue_depth=8)
    prompt = rng.integers(0, 64, size=6).astype(np.int32)
    r1 = srv.submit(prompt, max_new_tokens=3)

    orig = engine._jit_prefill_at
    engine._jit_prefill_at = lambda *a, **k: (_ for _ in ()).throw(
        RuntimeError("injected prefill failure"))
    try:
        with pytest.raises(RuntimeError, match="injected"):
            srv.step()
    finally:
        engine._jit_prefill_at = orig

    assert r1.state == RequestState.QUEUED and srv.pending == 1
    assert srv.pool.free_count == 2 and srv.live_count == 0
    assert r1.slot is None and r1.output_tokens == []
    assert r1.admit_time is None and r1.first_token_time is None

    srv.run_until_drained(max_steps=50)
    assert r1.state == RequestState.FINISHED
    expected = engine.generate(prompt[None], max_new_tokens=3)[0]
    np.testing.assert_array_equal(r1.tokens(), expected)
    assert srv.stats()["failed"] == 0


class _FakeMonitor:
    enabled = True

    def __init__(self):
        self.events = []

    def write_events(self, events):
        self.events.extend(events)


def test_rejection_paths_end_to_end_with_metrics(stack, pool):
    """queue_full / prompt_too_long shedding: the request never consumes
    a slot, the reason lands in stats() AND as a monitor event, and the
    accepted workload is unaffected."""
    _, _, engine = stack
    rng = np.random.default_rng(47)
    mon = _FakeMonitor()
    srv = make_server(engine, pool, num_slots=1, max_queue_depth=1,
                      monitor=mon)

    ok = srv.submit(rng.integers(0, 64, size=5).astype(np.int32),
                    max_new_tokens=2)
    full = srv.submit(rng.integers(0, 64, size=5).astype(np.int32),
                      max_new_tokens=2)
    long = srv.submit(rng.integers(0, 64, size=60).astype(np.int32),
                      max_new_tokens=10)
    assert full.state == RequestState.REJECTED
    assert full.reject_reason == "queue_full"
    assert long.state == RequestState.REJECTED
    assert long.reject_reason == "prompt_too_long"
    # shedding happened at submit: no slot was ever consumed
    assert srv.pool.free_count == 1 and srv.live_count == 0
    tags = [t for t, _, _ in mon.events]
    assert tags.count("serving/rejected/queue_full") == 1
    assert tags.count("serving/rejected/prompt_too_long") == 1

    srv.run_until_drained(max_steps=20)
    assert ok.state == RequestState.FINISHED
    s = srv.stats()
    assert s["completed"] == 1
    assert s["rejected"] == {"queue_full": 1, "prompt_too_long": 1}
    assert "serving/ttft_ms" in [t for t, _, _ in mon.events]


def test_metrics_snapshot_fields(stack, pool):
    _, _, engine = stack
    rng = np.random.default_rng(19)
    srv = make_server(engine, pool, num_slots=2, max_queue_depth=8)
    for _ in range(3):
        srv.submit(rng.integers(0, 64, size=6).astype(np.int32),
                   max_new_tokens=3)
    srv.run_until_drained(max_steps=50)
    s = srv.stats()
    assert s["completed"] == 3
    assert s["new_tokens"] == 9
    assert s["requests_per_s"] > 0 and s["tokens_per_s"] > 0
    for k in ("ttft_p50_ms", "ttft_p99_ms", "queue_wait_p50_ms",
              "per_token_p50_ms", "per_token_p99_ms"):
        assert np.isfinite(s[k]) and s[k] >= 0, k
    # plain decode: exactly one token per live slot per step, no spec
    assert s["tokens_per_decode_step"] == 1.0
    assert s["failed"] == 0 and s["spec_drafted"] == 0
    assert s["spec_acceptance_rate"] is None
