"""Async front-end unit tests: the priority scheduler (rank-ordered
grant, fair-share token slices, head-liveness, tenant rate limits and
quotas), the shared injected clock (ONE monotonic source drives
deadlines, queue expiry and rate buckets — pinned), burn-rate shedding
and preemption at the engine level, client-cancellation rollback
(mid-PREFILLING, mid-decode, paged), and the asyncio<->step-thread
bridge (streaming, cancellation, backpressure, drain-on-shutdown)."""

import asyncio
import threading

import numpy as np
import pytest

from deepspeed_tpu.serving import (FIFOScheduler, FinishReason,
                                   PriorityConfig, PriorityScheduler,
                                   RejectReason, Request, RequestState,
                                   ServingEngine, TenantPolicy)
from deepspeed_tpu.serving.frontend import AsyncEngineBridge


# relaxed SLO for engine tests: the first step's jit compile lands in
# TTFT, which would trip the default 500 ms target and turn burn-rate
# shedding ON mid-test (that behavior gets its own deterministic tests)
LENIENT_SLO = {"ttft_ms": 6e5, "gap_ms": 6e5}


class FakeClock:
    """Injected monotonic clock; tests advance ``t`` explicitly."""

    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


def _req(rid, plen=8, mnt=8, cls=None, tenant="default"):
    r = Request(rid, np.zeros(plen, np.int32), mnt)
    if cls is not None:
        r.priority_class = cls
    r.tenant = tenant
    return r


def _prompt(rng, lo=5, hi=10):
    return rng.integers(0, 64, size=int(rng.integers(lo, hi + 1))) \
              .astype(np.int32)


def _assert_clean(srv):
    srv.check_invariants()
    assert srv.pool.free_count == srv.pool.num_slots
    assert srv.live_count == 0
    assert srv.timelines.open_ids() == []


# ---------------------------------------------------------------------------
# FIFO head-liveness: the base-class guarantee the priority scheduler
# builds on (regression pin — see FIFOScheduler.grant docstring)
# ---------------------------------------------------------------------------
class TestFIFOHeadLiveness:
    def test_head_granted_over_budget_when_nothing_committed(self):
        s = FIFOScheduler(num_slots=2)
        ok, _ = s.submit(_req(0, plen=32))
        assert ok
        got = s.grant(2, token_budget=4, cost=lambda r: 100)
        assert [r.request_id for r in got] == [0]

    def test_head_blocked_when_prefill_already_committed(self):
        s = FIFOScheduler(num_slots=2)
        s.submit(_req(0, plen=32))
        assert s.grant(2, token_budget=4, cost=lambda r: 100,
                       spent=1) == []
        assert s.pending == 1  # still queued, granted next idle step

    def test_head_accessor_matches_pop_order(self):
        s = FIFOScheduler(num_slots=2)
        assert s.head() is None
        a, b = _req(0), _req(1)
        s.submit(a)
        s.submit(b)
        assert s.head() is a
        assert s.grant(1)[0] is a
        assert s.head() is b


# ---------------------------------------------------------------------------
# priority scheduler: rank order, fair shares, liveness, page strictness
# ---------------------------------------------------------------------------
class TestPriorityGrant:
    def test_strict_rank_order_for_slots(self):
        s = PriorityScheduler(num_slots=4)
        s.submit(_req(0, cls="batch"))
        s.submit(_req(1, cls="standard"))
        s.submit(_req(2, cls="interactive"))
        got = [r.request_id for r in s.grant(2)]
        assert got == [2, 1]      # rank order beats arrival order
        assert s.head().request_id == 0

    def test_head_is_oldest_of_highest_class(self):
        s = PriorityScheduler(num_slots=4)
        s.submit(_req(0, cls="batch"))
        s.submit(_req(1, cls="interactive"))
        s.submit(_req(2, cls="interactive"))
        assert s.head().request_id == 1
        assert s.head_within(0).request_id == 1
        # nothing at-or-above rank 0 once interactive drains
        s.grant(2)
        assert s.head_within(0) is None
        assert s.head_within(2).request_id == 0

    def test_fair_share_slices_split_token_budget(self):
        s = PriorityScheduler(num_slots=4)
        s.submit(_req(0, cls="interactive"))
        s.submit(_req(1, cls="interactive"))
        s.submit(_req(10, cls="batch"))
        s.submit(_req(11, cls="batch"))
        # budget 20, cost 10 each, equal shares -> ONE grant per class:
        # a high-class flood cannot eat the whole step's prefill budget
        got = [r.request_id for r in
               s.grant(4, token_budget=20, cost=lambda r: 10)]
        assert got == [0, 10]

    def test_shares_weight_the_split(self):
        s = PriorityScheduler(
            num_slots=4,
            priority={"classes": ("interactive", "batch"),
                      "shares": {"interactive": 3.0, "batch": 1.0}})
        for i in range(3):
            s.submit(_req(i, cls="interactive"))
        s.submit(_req(10, cls="batch"))
        s.submit(_req(11, cls="batch"))
        # budget 40 -> slices 30/10 at cost 10: three interactive, one batch
        got = [r.request_id for r in
               s.grant(5, token_budget=40, cost=lambda r: 10)]
        assert got == [0, 1, 2, 10]

    def test_leftover_budget_is_work_conserving(self):
        s = PriorityScheduler(num_slots=8)
        s.submit(_req(0, cls="interactive"))
        for i in range(3):
            s.submit(_req(10 + i, cls="batch"))
        # slices 6/6; interactive spends 2, batch spends 6 in-slice and
        # the third batch request rides the global leftover (pass 2)
        cost = {0: 2, 10: 3, 11: 3, 12: 3}
        got = [r.request_id for r in
               s.grant(8, token_budget=12,
                       cost=lambda r: cost[r.request_id])]
        assert got == [0, 10, 11, 12]
        assert s.pending == 0

    def test_highest_ranked_waiter_keeps_liveness_overshoot(self):
        s = PriorityScheduler(num_slots=2)
        s.submit(_req(0, cls="interactive", plen=32))
        s.submit(_req(1, cls="batch"))
        got = [r.request_id for r in
               s.grant(2, token_budget=4, cost=lambda r: 100)]
        # the overshoot grants exactly the head — it must NOT also be
        # re-spent on lower classes (budget already blown)
        assert got == [0]
        assert s.pending == 1

    def test_lowest_class_progresses_when_higher_classes_idle(self):
        # satellite pin: no starvation livelock — with interactive and
        # standard idle, batch IS the highest-ranked waiter and inherits
        # the head-liveness overshoot
        s = PriorityScheduler(num_slots=2)
        s.submit(_req(0, cls="batch", plen=32))
        got = s.grant(2, token_budget=1, cost=lambda r: 100)
        assert [r.request_id for r in got] == [0]

    def test_overshoot_suppressed_after_committed_work(self):
        s = PriorityScheduler(num_slots=2)
        s.submit(_req(0, cls="batch", plen=32))
        assert s.grant(2, token_budget=1, cost=lambda r: 100,
                       spent=1) == []

    def test_page_budget_strict_and_global(self):
        s = PriorityScheduler(num_slots=4)
        s.submit(_req(0, cls="interactive"))
        s.submit(_req(1, cls="batch"))
        pages = {0: 5, 1: 1}
        # the interactive head does not fit 2 pages -> the WHOLE grant
        # stops; letting batch take pages the blocked head needs would
        # invert priority under memory pressure
        assert s.grant(4, page_budget=2,
                       page_cost=lambda r: pages[r.request_id]) == []
        assert s.pending == 2

    def test_base_requeue_and_expire_paths_still_work(self):
        clock = FakeClock()
        s = PriorityScheduler(num_slots=2, clock=clock)
        a = _req(0, cls="batch")
        b = _req(1, cls="interactive")
        s.submit(a)
        s.submit(b)
        s.requeue_front([_req(2, cls="standard")])
        assert [r.request_id for r in s.queue] == [2, 0, 1]
        a.deadline_time = clock.t - 1.0
        expired = s.expire(clock.t)
        assert [r.request_id for r in expired] == [0]
        assert s.pending == 2


class TestPriorityAdmission:
    def test_unknown_class_fails_loudly(self):
        s = PriorityScheduler(num_slots=2)
        with pytest.raises(ValueError, match="unknown priority class"):
            s.submit(_req(0, cls="platinum"))

    def test_default_class_is_lowest_and_stamped(self):
        s = PriorityScheduler(num_slots=2)
        r = _req(0)                       # dataclass default "default"
        ok, _ = s.submit(r)
        assert ok and r.priority_class == "batch"
        assert PriorityConfig().default_class == "batch"

    def test_class_depths(self):
        s = PriorityScheduler(num_slots=2)
        s.submit(_req(0, cls="interactive"))
        s.submit(_req(1, cls="batch"))
        s.submit(_req(2, cls="batch"))
        assert s.class_depths() == {"interactive": 1, "standard": 0,
                                    "batch": 2}

    def test_tenant_rate_limit_rejects_then_refills_on_clock(self):
        clock = FakeClock()
        s = PriorityScheduler(
            num_slots=2, clock=clock,
            priority={"tenants": {"t1": {"tokens_per_s": 10.0,
                                         "burst_tokens": 20.0}}})
        # cost = prompt + max_new_tokens = 20 = exactly the burst
        ok, _ = s.submit(_req(0, plen=10, mnt=10, tenant="t1"))
        assert ok
        r = _req(1, plen=10, mnt=10, tenant="t1")
        ok, reason = s.submit(r)
        assert (ok, reason) == (False, RejectReason.RATE_LIMITED)
        assert r.retry_after_s == pytest.approx(2.0)  # 20 tokens @ 10/s
        clock.t += 2.0                    # refill WITHOUT wall time passing
        ok, _ = s.submit(_req(2, plen=10, mnt=10, tenant="t1"))
        assert ok

    def test_rate_bucket_refunded_on_downstream_rejection(self):
        clock = FakeClock()
        s = PriorityScheduler(
            num_slots=2, max_queue_depth=1, clock=clock,
            priority={"tenants": {"*": {"tokens_per_s": 10.0,
                                        "burst_tokens": 40.0}}})
        assert s.submit(_req(0, plen=10, mnt=10))[0]      # bucket 40 -> 20
        ok, reason = s.submit(_req(1, plen=10, mnt=10))   # queue full
        assert (ok, reason) == (False, RejectReason.QUEUE_FULL)
        # the rejection refunded its 20 tokens: draining the queue
        # re-admits immediately — only requests that actually joined the
        # queue consume rate (without the refund the bucket would be
        # empty here and this would be RATE_LIMITED)
        s.grant(2)
        assert s.submit(_req(2, plen=10, mnt=10))[0]

    def test_tenant_queue_quota(self):
        s = PriorityScheduler(
            num_slots=2,
            priority={"tenants": {"noisy": {"max_queued": 1}}})
        assert s.submit(_req(0, tenant="noisy"))[0]
        ok, reason = s.submit(_req(1, tenant="noisy"))
        assert (ok, reason) == (False, RejectReason.TENANT_QUOTA)
        assert s.submit(_req(2, tenant="quiet"))[0]   # others unaffected

    def test_wildcard_policy_applies_to_unlisted_tenants(self):
        s = PriorityScheduler(
            num_slots=2,
            priority={"tenants": {"*": {"max_queued": 1},
                                  "vip": {"max_queued": 8}}})
        assert s.submit(_req(0, tenant="anon"))[0]
        assert s.submit(_req(1, tenant="anon"))[1] is \
            RejectReason.TENANT_QUOTA
        assert s.submit(_req(2, tenant="vip"))[0]
        assert s.submit(_req(3, tenant="vip"))[0]

    def test_config_validation(self):
        with pytest.raises(ValueError, match="duplicate"):
            PriorityConfig(classes=("a", "a"))
        with pytest.raises(ValueError, match="unknown class"):
            PriorityConfig(classes=("a",), shares={"b": 1.0})
        with pytest.raises(ValueError, match="default_class"):
            PriorityConfig(classes=("a",), default_class="z")
        with pytest.raises(ValueError, match="positive"):
            TenantPolicy(tokens_per_s=-1.0)
        assert TenantPolicy(tokens_per_s=5.0).burst_tokens == 20.0


# ---------------------------------------------------------------------------
# shared clock (satellite): ONE injected monotonic source drives
# deadlines, expiry and rate buckets together
# ---------------------------------------------------------------------------
class TestSharedClock:
    def test_clock_is_plumbed_to_scheduler_and_deadlines(self, stack):
        _, _, engine = stack
        clock = FakeClock()
        srv = ServingEngine(engine, num_slots=2, priority=True, clock=clock)
        assert srv._now is clock
        assert srv.scheduler.clock is srv._now   # same object, by identity

    def test_fake_clock_drives_deadline_expiry_without_wall_time(self, stack):
        _, _, engine = stack
        clock = FakeClock()
        srv = ServingEngine(engine, num_slots=1, priority=True, clock=clock)
        rng = np.random.default_rng(0)
        blocker = srv.submit(_prompt(rng), max_new_tokens=4)
        waiter = srv.submit(_prompt(rng), max_new_tokens=4,
                            deadline_ms=100.0)
        assert waiter.deadline_time == pytest.approx(clock.t + 0.1)
        clock.t += 1.0        # no wall time passed; only the fake clock
        srv.step()
        assert waiter.finish_reason is FinishReason.DEADLINE
        srv.run_until_drained()
        assert blocker.finish_reason is not None
        _assert_clean(srv)

    def test_fake_clock_drives_rate_bucket_through_engine(self, stack):
        _, _, engine = stack
        clock = FakeClock()
        srv = ServingEngine(
            engine, num_slots=2, clock=clock,
            priority={"tenants": {"t": {"tokens_per_s": 8.0,
                                        "burst_tokens": 16.0}}})
        rng = np.random.default_rng(1)
        p = rng.integers(0, 64, size=8).astype(np.int32)
        assert srv.submit(p, max_new_tokens=8, tenant="t").reject_reason \
            is None
        r = srv.submit(p, max_new_tokens=8, tenant="t")
        assert r.reject_reason is RejectReason.RATE_LIMITED
        assert r.retry_after_s == pytest.approx(2.0)
        clock.t += 2.0
        assert srv.submit(p, max_new_tokens=8, tenant="t").reject_reason \
            is None
        srv.run_until_drained()
        _assert_clean(srv)


# ---------------------------------------------------------------------------
# burn-rate shedding / preemption at the engine level
# ---------------------------------------------------------------------------
class TestBurnRateControl:
    def _burn(self, srv, cls="interactive"):
        """Blow one admitted request's TTFT target so the class's burn
        hits page on both horizons (goodput 0 in every window)."""
        srv.slo.observe_admitted(cls=cls)
        srv.slo.observe_finish(ttft_s=999.0, cls=cls)
        srv.slo._recompute_alert()
        assert srv.slo.class_alerts[cls] == "page"

    def test_lower_classes_shed_while_higher_class_burns(self, stack):
        _, _, engine = stack
        srv = ServingEngine(engine, num_slots=2, priority=True, slo=True)
        self._burn(srv, "interactive")
        rng = np.random.default_rng(2)
        shed = srv.submit(_prompt(rng), max_new_tokens=4, priority="batch")
        assert shed.reject_reason is RejectReason.RETRY_AFTER
        assert shed.retry_after_s is not None
        # the burning class itself (and anything above the floor) is NOT
        # shed — shedding defends it, it must keep being admitted
        kept = srv.submit(_prompt(rng), max_new_tokens=4,
                          priority="interactive")
        assert kept.reject_reason is None
        srv.run_until_drained()
        _assert_clean(srv)

    def test_burn_preempts_shed_class_resident_for_protected_head(
            self, stack):
        _, _, engine = stack
        srv = ServingEngine(engine, num_slots=2, priority=True, slo=True,
                            preempt_min_run_steps=0)
        rng = np.random.default_rng(3)
        b1 = srv.submit(_prompt(rng), max_new_tokens=24, priority="batch")
        b2 = srv.submit(_prompt(rng), max_new_tokens=24, priority="batch")
        srv.step()                      # both batch requests seated
        assert srv.pool.free_count == 0
        self._burn(srv, "interactive")
        vip = srv.submit(_prompt(rng), max_new_tokens=4,
                         priority="interactive")
        srv.step()
        # one shed-class resident evicted (paced: exactly one) and the
        # protected head seated in its place
        assert (b1.preemptions + b2.preemptions) == 1
        assert vip.slot is not None or vip.finish_reason is not None
        srv.run_until_drained()
        assert vip.finish_reason in (FinishReason.EOS, FinishReason.LENGTH)
        _assert_clean(srv)

    def test_no_burn_no_shed(self, stack):
        _, _, engine = stack
        srv = ServingEngine(engine, num_slots=2, priority=True,
                            slo=LENIENT_SLO)
        rng = np.random.default_rng(4)
        r = srv.submit(_prompt(rng), max_new_tokens=4, priority="batch")
        assert r.reject_reason is None
        assert srv._shed_floor() is None
        srv.run_until_drained()
        _assert_clean(srv)

    def test_priority_kw_requires_priority_engine(self, stack):
        _, _, engine = stack
        srv = ServingEngine(engine, num_slots=2)
        with pytest.raises(ValueError, match="priority-enabled"):
            srv.submit(np.zeros(4, np.int32), priority="interactive")


# ---------------------------------------------------------------------------
# cancellation rollback (client disconnect / DELETE): queued,
# mid-PREFILLING, mid-decode, paged — no slot or page leaks
# ---------------------------------------------------------------------------
class TestCancellation:
    def test_cancel_queued_request_never_costs_a_prefill(self, stack):
        _, _, engine = stack
        srv = ServingEngine(engine, num_slots=1)
        rng = np.random.default_rng(5)
        blocker = srv.submit(_prompt(rng), max_new_tokens=4)
        waiter = srv.submit(_prompt(rng), max_new_tokens=4)
        got = srv.cancel(waiter.request_id)
        assert got is waiter
        assert waiter.finish_reason is FinishReason.CANCELLED
        assert waiter.admit_time is None      # never seated
        srv.run_until_drained()
        assert blocker.finish_reason is not None
        _assert_clean(srv)
        tl = [e["event"] for e in srv.timeline(waiter.request_id)]
        assert tl[0] == "submitted" and tl[-1] == "finished"

    def test_cancel_mid_prefilling_releases_slot(self, stack):
        _, _, engine = stack
        srv = ServingEngine(engine, num_slots=2, prefill_chunk=4,
                            prefill_token_budget=4)
        rng = np.random.default_rng(6)
        prompt = rng.integers(0, 64, size=14).astype(np.int32)
        r = srv.submit(prompt, max_new_tokens=4)
        srv.step()
        assert r.state is RequestState.PREFILLING   # chunks remain
        got = srv.cancel(r.request_id)
        assert got is r and r.finish_reason is FinishReason.CANCELLED
        assert not srv._prefill_queue               # chunk queue filtered
        srv.step()                                  # engine keeps running
        _assert_clean(srv)

    def test_cancel_mid_decode_releases_slot(self, stack):
        _, _, engine = stack
        srv = ServingEngine(engine, num_slots=2)
        rng = np.random.default_rng(7)
        r = srv.submit(_prompt(rng), max_new_tokens=32)
        survivor = srv.submit(_prompt(rng), max_new_tokens=8)
        srv.step()
        srv.step()
        srv.settle()
        assert r.state is RequestState.RUNNING and r.output_tokens
        n = len(r.output_tokens)
        assert srv.cancel(r.request_id) is r
        assert r.finish_reason is FinishReason.CANCELLED
        assert len(r.output_tokens) == n        # nothing generated after
        srv.run_until_drained()
        assert survivor.finish_reason in (FinishReason.EOS,
                                          FinishReason.LENGTH)
        _assert_clean(srv)

    def test_cancel_mid_decode_paged_frees_pages(self, stack):
        _, _, engine = stack
        srv = ServingEngine(engine, num_slots=2, prefill_chunk=8,
                            paged_kv={"page_size": 8,
                                      "prefix_cache": False})
        rng = np.random.default_rng(8)
        r = srv.submit(_prompt(rng), max_new_tokens=24)
        srv.step()
        srv.step()
        assert srv.pool.free_page_count < srv.pool.num_pages
        assert srv.cancel(r.request_id) is r
        assert srv.pool.free_page_count == srv.pool.num_pages
        _assert_clean(srv)

    def test_cancel_unknown_or_terminal_returns_none(self, stack):
        _, _, engine = stack
        srv = ServingEngine(engine, num_slots=1)
        rng = np.random.default_rng(9)
        r = srv.submit(_prompt(rng), max_new_tokens=2)
        srv.run_until_drained()
        assert srv.cancel(r.request_id) is None     # races the final token
        assert srv.cancel(10_000) is None
        _assert_clean(srv)

    def test_cancel_withdraws_slo_admission(self, stack):
        _, _, engine = stack
        srv = ServingEngine(engine, num_slots=2, priority=True,
                            slo=LENIENT_SLO)
        rng = np.random.default_rng(10)
        srv.submit(_prompt(rng), max_new_tokens=16)
        r2 = srv.submit(_prompt(rng), max_new_tokens=16)
        srv.step()
        srv.cancel(r2.request_id)
        assert srv.slo.cancelled_total == 1
        srv.run_until_drained()
        # the cancelled request neither helps nor hurts goodput
        assert srv.slo.goodput() == pytest.approx(1.0)
        _assert_clean(srv)


# ---------------------------------------------------------------------------
# asyncio <-> step-thread bridge
# ---------------------------------------------------------------------------
async def _collect(stream):
    return [ev async for ev in stream]


class TestBridge:
    def _srv(self, stack, **kw):
        _, _, engine = stack
        kw.setdefault("num_slots", 2)
        return ServingEngine(engine, **kw)

    def test_submit_streams_tokens_then_done(self, stack):
        srv = self._srv(stack)

        async def run():
            bridge = AsyncEngineBridge(srv, idle_poll_s=0.005)
            await bridge.start()
            try:
                req, stream = await bridge.submit(
                    [1, 2, 3, 4], max_new_tokens=5)
                events = await _collect(stream)
            finally:
                await bridge.stop()
            return req, events

        req, events = asyncio.run(run())
        tokens = [e for e in events if e["event"] == "token"]
        assert [e["index"] for e in tokens] == list(range(len(tokens)))
        assert [e["token"] for e in tokens] == req.output_tokens
        assert events[-1]["event"] == "done"
        assert events[-1]["reason"] in ("eos", "length")
        assert events[-1]["tokens"] == len(req.output_tokens)
        _assert_clean(srv)

    def test_concurrent_streams_all_complete(self, stack):
        srv = self._srv(stack)

        async def run():
            bridge = AsyncEngineBridge(srv, idle_poll_s=0.005)
            await bridge.start()
            try:
                pairs = [await bridge.submit([1 + i, 2, 3],
                                             max_new_tokens=4 + i)
                         for i in range(5)]
                results = await asyncio.gather(
                    *[_collect(s) for _, s in pairs])
            finally:
                await bridge.stop()
            return pairs, results

        pairs, results = asyncio.run(run())
        for (req, _), events in zip(pairs, results):
            assert events[-1]["event"] == "done"
            assert events[-1]["request_id"] == req.request_id
        _assert_clean(srv)

    def test_cancel_mid_stream_emits_terminal_cancelled(self, stack):
        srv = self._srv(stack)

        async def run():
            bridge = AsyncEngineBridge(srv, idle_poll_s=0.005)
            await bridge.start()
            try:
                req, stream = await bridge.submit([1, 2, 3],
                                                  max_new_tokens=48)
                first = await stream.__anext__()     # at least one token
                assert await bridge.cancel(req.request_id) is True
                rest = await _collect(stream)
            finally:
                await bridge.stop()
            return first, rest

        first, rest = asyncio.run(run())
        assert first["event"] == "token"
        assert rest[-1]["event"] == "done"
        assert rest[-1]["reason"] == "cancelled"
        _assert_clean(srv)

    def test_cancel_unknown_id_returns_false(self, stack):
        srv = self._srv(stack)

        async def run():
            bridge = AsyncEngineBridge(srv, idle_poll_s=0.005)
            await bridge.start()
            try:
                return await bridge.cancel(31337)
            finally:
                await bridge.stop()

        assert asyncio.run(run()) is False

    def test_rejected_submit_yields_single_terminal_event(self, stack):
        srv = self._srv(stack, num_slots=1, max_queue_depth=1)
        # the engine thread stands still after its first step, the first
        # request in the one slot, until the two later submits are both in
        # its inbox: it then takes them one after the other, no step (and
        # no finished request) between them
        in_slot, inbox_full = threading.Event(), threading.Event()
        real_step = srv.step

        def step_then_stand():
            real_step()
            in_slot.set()
            assert inbox_full.wait(timeout=60)

        srv.step = step_then_stand

        async def release():
            inbox_full.set()

        async def run():
            bridge = AsyncEngineBridge(srv, idle_poll_s=0.005)
            await bridge.start()
            try:
                # fill slot + queue, then overflow
                first, _ = await bridge.submit([1, 2], max_new_tokens=16)
                assert await asyncio.get_running_loop().run_in_executor(
                    None, in_slot.wait, 60)
                held = first.state
                _, (req, stream), _ = await asyncio.gather(
                    bridge.submit([1, 2], max_new_tokens=16),
                    bridge.submit([1, 2], max_new_tokens=4), release())
                events = await _collect(stream)
            finally:
                inbox_full.set()
                await bridge.stop()
            return held, req, events

        held, req, events = asyncio.run(run())
        assert held in (RequestState.PREFILLING, RequestState.RUNNING)
        assert req.state is RequestState.REJECTED
        assert len(events) == 1
        assert events[0]["reason"] == "rejected"
        assert events[0]["reject_reason"] == "queue_full"
        _assert_clean(srv)

    def test_slow_consumer_is_closed_and_cancelled(self, stack):
        srv = self._srv(stack)

        async def run():
            bridge = AsyncEngineBridge(srv, stream_buffer=2,
                                       idle_poll_s=0.005)
            await bridge.start()
            try:
                req, stream = await bridge.submit([1, 2, 3],
                                                  max_new_tokens=48)
                for _ in range(400):        # deaf consumer: never reads
                    await asyncio.sleep(0.005)
                    if stream.closed and not bridge._streams:
                        break
                ev = await stream.__anext__()
                with pytest.raises(StopAsyncIteration):
                    await stream.__anext__()
            finally:
                await bridge.stop()
            return req, ev

        req, ev = asyncio.run(run())
        assert ev == {"event": "error", "reason": "slow_consumer",
                      "request_id": req.request_id}
        assert req.finish_reason is FinishReason.CANCELLED
        _assert_clean(srv)

    def test_call_serializes_reads_onto_step_thread(self, stack):
        srv = self._srv(stack)

        async def run():
            bridge = AsyncEngineBridge(srv, idle_poll_s=0.005)
            await bridge.start()
            try:
                req, stream = await bridge.submit([1, 2], max_new_tokens=4)
                stats = await bridge.call(lambda s: s.stats())
                await _collect(stream)
            finally:
                await bridge.stop()
            return stats

        stats = asyncio.run(run())
        assert isinstance(stats, dict) and "completed" in stats

    def test_stop_drains_in_flight_requests(self, stack):
        srv = self._srv(stack)

        async def run():
            bridge = AsyncEngineBridge(srv, idle_poll_s=0.005)
            await bridge.start()
            req, stream = await bridge.submit([1, 2, 3], max_new_tokens=8)
            await bridge.stop(drain=True)      # no reads before stop
            return req, await _collect(stream)

        req, events = asyncio.run(run())
        assert req.finish_reason in (FinishReason.EOS, FinishReason.LENGTH)
        assert events[-1]["event"] == "done"
        _assert_clean(srv)

    def test_stop_without_drain_closes_streams(self, stack):
        srv = self._srv(stack)

        async def run():
            bridge = AsyncEngineBridge(srv, idle_poll_s=0.005)
            await bridge.start()
            req, stream = await bridge.submit([1, 2, 3],
                                              max_new_tokens=48)
            await stream.__anext__()
            await bridge.stop(drain=False)
            return req, await _collect(stream)

        req, events = asyncio.run(run())
        assert events[-1]["event"] == "done"
        assert events[-1]["reason"] == "shutdown"
        # not drained: the engine-side request may be unfinished, but the
        # bridge must not be left running
        srv.check_invariants()

    def test_submit_kwargs_validation_error_propagates(self, stack):
        srv = self._srv(stack)

        async def run():
            bridge = AsyncEngineBridge(srv, idle_poll_s=0.005)
            await bridge.start()
            try:
                with pytest.raises(ValueError, match="max_new_tokens"):
                    await bridge.submit([1, 2], max_new_tokens=0)
            finally:
                await bridge.stop()

        asyncio.run(run())
        _assert_clean(srv)

    def test_stream_buffer_floor(self, stack):
        srv = self._srv(stack)
        with pytest.raises(ValueError, match="stream_buffer"):
            AsyncEngineBridge(srv, stream_buffer=1)

    def test_concurrent_calls_racing_stop_never_hang(self, stack):
        """Stress the shutdown race: call() coroutines hammer the op
        queue while stop(drain=True) runs. Before the bridge rejected
        leftover ops, a call enqueued between the step thread's final
        queue drain and its exit awaited its future forever; now every
        racing call must either return a value or raise RuntimeError —
        a hang fails the gather timeout below."""
        srv = self._srv(stack)

        async def one_round(bridge):
            await bridge.start()
            outcomes = {"ok": 0, "rejected": 0}

            async def hammer():
                while True:
                    try:
                        n = await bridge.call(lambda s: s.live_count)
                    except RuntimeError:
                        outcomes["rejected"] += 1
                        return
                    assert n == 0
                    outcomes["ok"] += 1

            tasks = [asyncio.ensure_future(hammer()) for _ in range(6)]
            await asyncio.sleep(0.01)         # let the hammering overlap
            await bridge.stop(drain=True)
            await asyncio.wait_for(asyncio.gather(*tasks), timeout=30)
            assert outcomes["rejected"] == 6  # every task exited cleanly
            assert bridge._ops.empty()        # nothing left un-serviced
            with pytest.raises(RuntimeError, match="not running"):
                await bridge.call(lambda s: 0)
            return outcomes["ok"]

        async def run():
            bridge = AsyncEngineBridge(srv, idle_poll_s=0.002)
            total_ok = 0
            for _ in range(10):               # re-roll the race window
                total_ok += await one_round(bridge)
            return total_ok

        total_ok = asyncio.run(run())
        assert total_ok > 0                   # the calls really ran
        _assert_clean(srv)

    def test_ops_left_after_thread_exit_are_rejected(self, stack):
        """Deterministic pin for the leftover-op path: an op sitting in
        the queue once the step thread is gone must have its future
        rejected fast (never resolved, never hung)."""
        srv = self._srv(stack)

        async def run():
            bridge = AsyncEngineBridge(srv, idle_poll_s=0.002)
            await bridge.start()
            await bridge.stop(drain=False)
            # simulate the racing op that slipped past the final drain
            fut = asyncio.get_running_loop().create_future()
            bridge._ops.put(("call", (lambda s: 0), None, fut))
            bridge._reject_pending_ops("stopped")
            with pytest.raises(RuntimeError, match="not serviced"):
                await fut
            assert bridge._ops.empty()

        asyncio.run(run())
        _assert_clean(srv)
