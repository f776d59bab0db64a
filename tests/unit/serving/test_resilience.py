"""Fault-tolerance & chaos tests for the serving engine: deadlines,
preemption (bitwise-identical resume), graceful degradation, the
deterministic fault injector, and the cross-bookkeeping invariant
audit. The contract under test: NO fault, wherever injected, may leak
a slot, strand a request without a terminal reason, or change the
compiled program set — and a preempted greedy request's output is
bitwise what it would have been without the preemption."""

import time

import numpy as np
import pytest

from deepspeed_tpu.serving import (FIFOScheduler, FinishReason, RejectReason,
                                   Request, RequestState)
from deepspeed_tpu.serving.metrics import ServingMetrics
from deepspeed_tpu.serving.resilience import (DegradationConfig,
                                              FaultInjector, InjectedFault,
                                              LoadState, ServingStalledError)
from deepspeed_tpu.serving.resilience.degradation import LoadStateMachine
from deepspeed_tpu.serving.resilience.preemption import select_victims

from .conftest import make_server


def _prompts(rng, n, lo=5, hi=12):
    return [rng.integers(0, 64, size=int(rng.integers(lo, hi + 1)))
            .astype(np.int32) for _ in range(n)]


def _assert_clean(srv):
    """The post-fault contract: bookkeeping consistent, no leaked slot,
    every timeline terminal."""
    srv.check_invariants()
    assert srv.pool.free_count == srv.pool.num_slots
    assert srv.live_count == 0
    assert srv.timelines.open_ids() == []


# ---------------------------------------------------------------------------
# fault injector (no model needed)
# ---------------------------------------------------------------------------
class TestFaultInjector:
    def test_schedule_fires_exact_ordinals(self):
        fi = FaultInjector(seed=0, schedule={"admit_oom": [2, 4]})
        fired = []
        for _ in range(5):
            try:
                fi.check("admit_oom")
                fired.append(False)
            except InjectedFault as e:
                assert e.point == "admit_oom"
                fired.append(True)
        assert fired == [False, True, False, True, False]
        assert fi.counts["admit_oom"] == 5 and fi.fired["admit_oom"] == 2

    def test_schedule_ordinals_are_one_based(self):
        with pytest.raises(ValueError, match="1-based"):
            FaultInjector(schedule={"admit_oom": [0]})

    def test_unknown_point_rejected(self):
        with pytest.raises(ValueError, match="unknown injection point"):
            FaultInjector(schedule={"disk_full": [1]})
        with pytest.raises(ValueError, match="unknown injection point"):
            FaultInjector().check("disk_full")

    def test_rate_streams_deterministic_and_per_point(self):
        def pattern(seed, point, n=64):
            fi = FaultInjector(seed=seed, rates={point: 0.5})
            return [fi._roll(point) for _ in range(n)]

        a = pattern(7, "nan_logits")
        assert a == pattern(7, "nan_logits")          # replayable
        assert a != pattern(8, "nan_logits")          # seed matters
        # independent stream per point: same seed, different point,
        # different draws
        assert a != pattern(7, "drafter_error")

    def test_load_schedule_resets_counts(self):
        fi = FaultInjector(schedule={"admit_oom": [1]})
        with pytest.raises(InjectedFault):
            fi.check("admit_oom")
        fi.load_schedule({"admit_oom": [1]})
        assert fi.counts["admit_oom"] == 0
        with pytest.raises(InjectedFault):    # ordinal 1 re-armed
            fi.check("admit_oom")

    def test_maybe_sleep_only_fires_on_schedule(self):
        fi = FaultInjector(schedule={"slow_dispatch": [2]}, slow_ms=0.0)
        assert fi.maybe_sleep() is False
        assert fi.maybe_sleep() is True


# ---------------------------------------------------------------------------
# reason enums (satellite: every monitor event uses them)
# ---------------------------------------------------------------------------
class TestReasonEnums:
    def test_finish_reason_str_mixin(self):
        assert FinishReason.DEADLINE == "deadline"
        assert str(FinishReason.NUMERICAL_ERROR) == "numerical_error"
        assert f"{FinishReason.EOS}" == "eos"
        assert FinishReason.of("length") is FinishReason.LENGTH
        assert FinishReason.of(FinishReason.ERROR) is FinishReason.ERROR
        with pytest.raises(ValueError):
            FinishReason.of("melted")

    def test_reject_reason_roundtrip(self):
        assert RejectReason.of("retry_after") is RejectReason.RETRY_AFTER
        with pytest.raises(ValueError):
            RejectReason.of("because")

    def test_metrics_reject_unknown_reasons(self):
        m = ServingMetrics(None)
        req = Request(0, np.arange(4, dtype=np.int32), 4, None)
        req.reject_reason = "bogus"
        with pytest.raises(ValueError):
            m.record_rejection(req)
        req.reject_reason = RejectReason.QUEUE_FULL
        m.record_rejection(req)     # enum member: accepted
        bad = Request(1, np.arange(4, dtype=np.int32), 4, None)
        bad.finish_reason = "imploded"
        with pytest.raises(ValueError):
            m.record_failure(bad)


# ---------------------------------------------------------------------------
# scheduler hardening (satellite: requeue_front FIFO regression)
# ---------------------------------------------------------------------------
class TestSchedulerResilience:
    @staticmethod
    def _req(i, out=0):
        r = Request(i, np.arange(4, dtype=np.int32), 8, None)
        r.output_tokens = list(range(out))
        return r

    def test_requeue_front_preserves_relative_order(self):
        # the FIFO-inversion regression: requeue_front([a, b]) with [c]
        # already queued must pop a, b, c — never b, a, c
        s = FIFOScheduler(2, max_queue_depth=8)
        a, b, c = (self._req(i) for i in range(3))
        s.submit(c)
        s.requeue_front([a, b])
        assert [r.request_id for r in s.queue] == [0, 1, 2]
        assert all(r.state is RequestState.QUEUED for r in (a, b))

    def test_requeue_back_appends_tail(self):
        s = FIFOScheduler(2, max_queue_depth=8)
        a, b = self._req(0), self._req(1)
        s.submit(a)
        s.requeue_back([b])
        assert [r.request_id for r in s.queue] == [0, 1]

    def test_expire_removes_only_expired(self):
        s = FIFOScheduler(2, max_queue_depth=8)
        a, b = self._req(0), self._req(1)
        a.deadline_time = 10.0
        b.deadline_time = 30.0
        s.submit(a)
        s.submit(b)
        gone = s.expire(now=20.0)
        assert gone == [a]
        assert list(s.queue) == [b]

    def test_capacity_accounts_resumed_seed(self):
        # a preempted request's footprint is seed + REMAINING budget;
        # one that can no longer fit is refused, not admitted to die
        s = FIFOScheduler(2, max_queue_depth=8, capacity=16)
        r = self._req(0, out=10)    # seed = 4 prompt + 10 generated = 14
        r.max_new_tokens = 12       # 2 remaining -> 16 total: fits
        assert s.submit(r) == (True, None)
        r2 = self._req(1, out=10)
        r2.max_new_tokens = 13      # 3 remaining -> 17 total: too long
        ok, why = s.submit(r2)
        assert not ok and why is RejectReason.PROMPT_TOO_LONG


class TestVictimSelection:
    @staticmethod
    def _seated(i, tokens, admit_step):
        r = Request(i, np.arange(4, dtype=np.int32), 32, None)
        r.state = RequestState.RUNNING
        r.output_tokens = list(range(tokens))
        r.last_admit_step = admit_step
        return r

    def test_youngest_lowest_progress_first(self):
        old = self._seated(0, tokens=9, admit_step=0)
        young = self._seated(1, tokens=2, admit_step=3)
        younger = self._seated(2, tokens=2, admit_step=5)
        got = select_victims([old, young, younger], n=2, current_step=20)
        assert [r.request_id for r in got] == [2, 1]

    def test_min_run_steps_protects_fresh_seats(self):
        fresh = self._seated(0, tokens=0, admit_step=9)
        settled = self._seated(1, tokens=5, admit_step=0)
        assert select_victims([fresh, settled], n=2, current_step=10,
                              min_run_steps=2) == [settled]
        # queued / terminal states are never victims
        q = self._seated(2, tokens=0, admit_step=0)
        q.state = RequestState.QUEUED
        assert select_victims([q], current_step=10) == []


class TestLoadStateMachine:
    def test_escalates_immediately_deescalates_after_cooldown(self):
        cfg = DegradationConfig.from_value(
            {"queue_pressured": 2, "queue_overloaded": 4,
             "cooldown_steps": 3})
        m = LoadStateMachine(cfg)
        assert m.update(4, None, step=0) == (LoadState.HEALTHY,
                                             LoadState.OVERLOADED)
        # calm observations: no transition until cooldown_steps of them
        assert m.update(0, None, step=1) is None
        assert m.update(0, None, step=2) is None
        # ...and de-escalation goes straight to the observed level
        assert m.update(0, None, step=3) == (LoadState.OVERLOADED,
                                             LoadState.HEALTHY)
        assert [t[1:] for t in m.transitions] == [
            (LoadState.HEALTHY, LoadState.OVERLOADED),
            (LoadState.OVERLOADED, LoadState.HEALTHY)]

    def test_worst_signal_wins_and_config_validates(self):
        cfg = DegradationConfig.from_value(
            {"queue_pressured": 8, "queue_overloaded": 16,
             "gap_p99_pressured_ms": 5.0, "gap_p99_overloaded_ms": 50.0})
        m = LoadStateMachine(cfg)
        assert m.classify(0, 7.0) is LoadState.PRESSURED
        assert m.classify(20, 0.0) is LoadState.OVERLOADED
        with pytest.raises(ValueError):
            DegradationConfig.from_value({"queue_pressured": 9,
                                          "queue_overloaded": 4})
        with pytest.raises(ValueError):
            DegradationConfig.from_value({"nope": 1})
        assert DegradationConfig.from_value(None) is None
        assert DegradationConfig.from_value(True).queue_pressured == 8


# ---------------------------------------------------------------------------
# deadlines
# ---------------------------------------------------------------------------
class TestDeadlines:
    def test_queued_request_expires_before_costing_prefill(self, stack, pool):
        _, _, engine = stack
        rng = np.random.default_rng(0)
        srv = make_server(engine, pool, num_slots=2, max_queue_depth=8)
        req = srv.submit(rng.integers(0, 64, size=6).astype(np.int32),
                         max_new_tokens=8, deadline_ms=1.0)
        time.sleep(0.01)
        srv.step()
        assert req.state is RequestState.FINISHED
        assert req.finish_reason is FinishReason.DEADLINE
        assert req.output_tokens == [] and req.slot is None
        assert srv.stats()["deadline_expired"] == 1
        _assert_clean(srv)

    def test_seated_request_retires_via_rollback_path(self, stack, pool):
        _, _, engine = stack
        rng = np.random.default_rng(1)
        srv = make_server(engine, pool, num_slots=2, max_queue_depth=8)
        req = srv.submit(rng.integers(0, 64, size=6).astype(np.int32),
                         max_new_tokens=32, deadline_ms=60_000.0)
        srv.step()
        srv.step()
        srv.settle()
        assert req.state is RequestState.RUNNING
        got = len(req.output_tokens)
        assert got >= 1
        req.deadline_time = srv._now() - 1.0   # force expiry
        srv.step()
        assert req.state is RequestState.FINISHED
        assert req.finish_reason is FinishReason.DEADLINE
        assert len(req.output_tokens) == got   # partial output preserved
        _assert_clean(srv)

    def test_engine_default_ttl_applies(self, stack, pool):
        _, _, engine = stack
        srv = make_server(engine, pool, num_slots=2, deadline_default_ms=500.0)
        req = srv.submit(np.arange(5, dtype=np.int32), max_new_tokens=2)
        assert req.deadline_ms == 500.0 and req.deadline_time is not None
        srv.run_until_drained(max_steps=30)
        assert req.finish_reason is FinishReason.LENGTH


# ---------------------------------------------------------------------------
# preemption
# ---------------------------------------------------------------------------
class TestPreemption:
    def test_preempted_output_bitwise_identical(self, stack, pool):
        """The headline resume guarantee: preempt mid-generation, resume
        through re-prefill, and the greedy token stream is EXACTLY what
        an unpreempted run produces."""
        _, _, engine = stack
        rng = np.random.default_rng(2)
        prompt = rng.integers(0, 64, size=9).astype(np.int32)
        budget = 12
        expected = engine.generate(prompt[None], max_new_tokens=budget)[0]

        srv = make_server(engine, pool, num_slots=2, max_queue_depth=8)
        req = srv.submit(prompt, max_new_tokens=budget)
        for _ in range(4):
            srv.step()
        srv.settle()
        assert req.state is RequestState.RUNNING
        mid = len(req.output_tokens)
        assert 0 < mid < budget

        srv.preempt(req.request_id)
        assert req.state is RequestState.QUEUED and req.slot is None
        assert req.preemptions == 1
        assert len(req.output_tokens) == mid   # generated work carried
        srv.check_invariants()

        srv.run_until_drained(max_steps=100)
        assert req.state is RequestState.FINISHED
        np.testing.assert_array_equal(req.tokens(), expected)
        assert srv.stats()["preempted"] == 1
        _assert_clean(srv)

    def test_preempt_requeues_front_of_line(self, stack, pool):
        _, _, engine = stack
        rng = np.random.default_rng(3)
        srv = make_server(engine, pool, num_slots=1, max_queue_depth=8)
        victim = srv.submit(rng.integers(0, 64, size=5).astype(np.int32),
                            max_new_tokens=16)
        waiter = srv.submit(rng.integers(0, 64, size=5).astype(np.int32),
                            max_new_tokens=4)
        srv.step()
        assert victim.state is RequestState.RUNNING
        srv.preempt(victim.request_id)
        # manual preemption goes to the HEAD: the operator's victim
        # resumes before requests that were already waiting behind it
        assert [r.request_id for r in srv.scheduler.queue] == \
            [victim.request_id, waiter.request_id]

    def test_preempt_unknown_id_raises(self, stack, pool):
        _, _, engine = stack
        srv = make_server(engine, pool, num_slots=2)
        with pytest.raises(ValueError, match="not seated"):
            srv.preempt(12345)

    def test_auto_preemption_under_pressure_still_exact(self, stack, pool):
        """Queue pressure past the threshold triggers automatic victim
        eviction (requeued at the TAIL — time-slicing, not a swap
        livelock) and every request still finishes with bitwise-exact
        greedy output."""
        _, _, engine = stack
        rng = np.random.default_rng(4)
        prompts = _prompts(rng, 6)
        srv = make_server(engine, pool, num_slots=2, max_queue_depth=16,
                          preempt_queue_threshold=2,
                          preempt_min_run_steps=2)
        reqs = [srv.submit(p, max_new_tokens=6) for p in prompts]
        srv.run_until_drained(max_steps=400)
        assert srv.stats()["preempted"] >= 1
        for req, prompt in zip(reqs, prompts):
            assert req.state is RequestState.FINISHED
            expected = engine.generate(prompt[None], max_new_tokens=6)[0]
            np.testing.assert_array_equal(req.tokens(), expected)
        _assert_clean(srv)

    def test_preempt_mid_chunked_prefill(self, stack, pool):
        """A PREFILLING victim restarts its chunk walk from zero on
        resume; output parity still holds."""
        _, _, engine = stack
        rng = np.random.default_rng(5)
        prompt = rng.integers(0, 64, size=40).astype(np.int32)
        srv = make_server(engine, pool, num_slots=2, prefill_chunk=16,
                          prefill_token_budget=16)
        req = srv.submit(prompt, max_new_tokens=6)
        srv.step()
        assert req.state is RequestState.PREFILLING
        srv.preempt(req.request_id)
        assert req.state is RequestState.QUEUED and req.prefill_pos == 0
        srv.check_invariants()
        srv.run_until_drained(max_steps=100)
        expected = engine.generate(prompt[None], max_new_tokens=6)[0]
        np.testing.assert_array_equal(req.tokens(), expected)
        _assert_clean(srv)


# ---------------------------------------------------------------------------
# graceful degradation
# ---------------------------------------------------------------------------
class TestDegradation:
    def test_ladder_walks_and_sheds_with_retry_after(self, stack, pool):
        _, _, engine = stack
        rng = np.random.default_rng(6)
        srv = make_server(engine, pool, num_slots=2, max_queue_depth=32,
                          degradation={"queue_pressured": 2,
                                       "queue_overloaded": 4,
                                       "cooldown_steps": 2,
                                       "retry_after_s": 0.25})
        reqs = [srv.submit(p, max_new_tokens=4) for p in _prompts(rng, 6)]
        srv.step()   # boundary sees queue depth >= 4 -> OVERLOADED
        assert srv._load.state is LoadState.OVERLOADED
        shed = srv.submit(rng.integers(0, 64, size=5).astype(np.int32),
                          max_new_tokens=4)
        assert shed.state is RequestState.REJECTED
        assert shed.reject_reason is RejectReason.RETRY_AFTER
        assert shed.retry_after_s == 0.25
        srv.run_until_drained(max_steps=200)
        stats = srv.stats()
        assert stats["load_transitions"] >= 2    # up AND back down
        assert stats["rejected"].get("retry_after") == 1
        for r in reqs:
            assert r.state is RequestState.FINISHED
        _assert_clean(srv)

    def test_pressure_shrinks_prefill_budget(self, stack, pool):
        _, _, engine = stack
        srv = make_server(engine, pool, num_slots=2, prefill_chunk=16,
                          prefill_token_budget=64,
                          degradation={"queue_pressured": 1,
                                       "queue_overloaded": 8})
        assert srv._effective_prefill_budget() == 64
        srv._load.state = LoadState.PRESSURED
        assert srv._effective_prefill_budget() == 32
        srv._load.state = LoadState.OVERLOADED
        assert srv._effective_prefill_budget() == 16   # one chunk

    def test_overload_suspends_spec_drafting(self, stack, pool):
        """OVERLOADED pushes zero-length drafts through the SAME verify
        program — throughput degrades, shapes (and greedy output) do
        not."""
        _, _, engine = stack
        rng = np.random.default_rng(7)
        prompts = _prompts(rng, 4)
        srv = make_server(engine, pool, num_slots=2, max_queue_depth=16,
                          spec_decode={"drafter": "ngram", "k": 4},
                          degradation={"queue_pressured": 1,
                                       "queue_overloaded": 2,
                                       "cooldown_steps": 64})
        reqs = [srv.submit(p, max_new_tokens=5) for p in prompts]
        srv.run_until_drained(max_steps=200)
        assert srv._load.state is not LoadState.HEALTHY  # ladder engaged
        for req, prompt in zip(reqs, prompts):
            assert req.state is RequestState.FINISHED
            expected = engine.generate(prompt[None], max_new_tokens=5)[0]
            np.testing.assert_array_equal(req.tokens(), expected)
        _assert_clean(srv)


# ---------------------------------------------------------------------------
# chaos: every injection point, invariants after each
# ---------------------------------------------------------------------------
class TestChaos:
    def test_admit_oom_rolls_back_and_recovers(self, stack, pool):
        _, _, engine = stack
        rng = np.random.default_rng(8)
        prompts = _prompts(rng, 3)
        srv = make_server(engine, pool, num_slots=2, max_queue_depth=8,
                          fault_injector=FaultInjector(
                              seed=0, schedule={"admit_oom": [1]}))
        reqs = [srv.submit(p, max_new_tokens=4) for p in prompts]
        with pytest.raises(InjectedFault):
            srv.step()
        srv.check_invariants()
        assert srv.pool.free_count == 2          # rolled back, no leak
        assert all(r.state is RequestState.QUEUED for r in reqs)
        srv.run_until_drained(max_steps=100)     # ordinal consumed: clean
        for req, prompt in zip(reqs, prompts):
            assert req.state is RequestState.FINISHED
            expected = engine.generate(prompt[None], max_new_tokens=4)[0]
            np.testing.assert_array_equal(req.tokens(), expected)
        _assert_clean(srv)

    def test_admit_oom_with_spec_decode_enabled(self, stack, pool):
        # satellite: the admission failure path must also be exception-
        # safe when speculative decoding is configured
        _, _, engine = stack
        rng = np.random.default_rng(9)
        prompts = _prompts(rng, 3)
        srv = make_server(engine, pool, num_slots=2, max_queue_depth=8,
                          spec_decode={"drafter": "ngram", "k": 4},
                          fault_injector=FaultInjector(
                              seed=0, schedule={"admit_oom": [1]}))
        reqs = [srv.submit(p, max_new_tokens=4) for p in prompts]
        with pytest.raises(InjectedFault):
            srv.step()
        srv.check_invariants()
        assert all(r.state is RequestState.QUEUED for r in reqs)
        srv.run_until_drained(max_steps=200)
        for r in reqs:
            assert r.state is RequestState.FINISHED
        _assert_clean(srv)

    def test_drafter_failure_aborts_cleanly(self, stack, pool):
        # satellite: drafter raises mid-step with spec decode enabled —
        # running requests FAIL with a reason, nothing leaks, and the
        # server keeps serving afterwards
        _, _, engine = stack
        rng = np.random.default_rng(10)
        srv = make_server(engine, pool, num_slots=2, max_queue_depth=8,
                          spec_decode={"drafter": "ngram", "k": 4},
                          fault_injector=FaultInjector(
                              seed=0, schedule={"drafter_error": [1]}))
        reqs = [srv.submit(p, max_new_tokens=8) for p in _prompts(rng, 2)]
        with pytest.raises(InjectedFault):
            srv.run_until_drained(max_steps=50)
        srv.check_invariants()
        assert srv.pool.free_count == 2
        for r in reqs:
            assert r.state is RequestState.FAILED
            assert r.finish_reason is FinishReason.ERROR
        assert srv.stats()["failed_reasons"] == {"error": 2}
        # the server is still healthy: fresh traffic completes
        again = srv.submit(rng.integers(0, 64, size=6).astype(np.int32),
                           max_new_tokens=4)
        srv.run_until_drained(max_steps=50)
        assert again.state is RequestState.FINISHED
        _assert_clean(srv)

    def test_nan_logits_fails_only_poisoned_slot(self, stack, pool):
        _, _, engine = stack
        rng = np.random.default_rng(11)
        prompts = _prompts(rng, 3)
        srv = make_server(engine, pool, num_slots=3, max_queue_depth=8,
                          guard_numerics=True,
                          fault_injector=FaultInjector(
                              seed=0, schedule={"nan_logits": [2]}))
        reqs = [srv.submit(p, max_new_tokens=8) for p in prompts]
        srv.run_until_drained(max_steps=100)
        failed = [r for r in reqs if r.state is RequestState.FAILED]
        ok = [r for r in reqs if r.state is RequestState.FINISHED]
        assert len(failed) == 1 and len(ok) == 2
        assert failed[0].finish_reason is FinishReason.NUMERICAL_ERROR
        assert srv.stats()["failed_reasons"] == {"numerical_error": 1}
        # survivors are untouched by their neighbour's poisoning
        for r in ok:
            i = reqs.index(r)
            expected = engine.generate(prompts[i][None], max_new_tokens=8)[0]
            np.testing.assert_array_equal(r.tokens(), expected)
        _assert_clean(srv)

    def test_step_host_error_aborts_without_leaks(self, stack, pool):
        _, _, engine = stack
        rng = np.random.default_rng(12)
        srv = make_server(engine, pool, num_slots=2, max_queue_depth=8,
                          fault_injector=FaultInjector(
                              seed=0, schedule={"step_host_error": [2]}))
        reqs = [srv.submit(p, max_new_tokens=8) for p in _prompts(rng, 2)]
        with pytest.raises(InjectedFault):
            srv.run_until_drained(max_steps=50)
        srv.check_invariants()
        assert srv.pool.free_count == 2
        for r in reqs:
            assert r.state is RequestState.FAILED
            assert r.finish_reason is FinishReason.ERROR
        _assert_clean(srv)

    def test_chaos_zero_postwarmup_recompiles(self, stack, pool):
        """End-to-end invariant: injected faults (including the NaN
        poisoning, which round-trips logits through the host) must not
        change the compiled program set, and every request still ends
        terminal with a reason."""
        _, _, engine = stack
        rng = np.random.default_rng(14)
        fi = FaultInjector(seed=0)   # empty schedule through warmup
        srv = make_server(engine, pool, own_programs=True, num_slots=2,
                          max_queue_depth=16, guard_numerics=True,
                          fault_injector=fi)
        for count in (1, 2):         # cover single + batched admission
            for _ in range(count):
                srv.submit(rng.integers(0, 64, size=6).astype(np.int32),
                           max_new_tokens=3)
            srv.run_until_drained(max_steps=60)
        srv.end_warmup()
        fi.load_schedule({"nan_logits": [2], "slow_dispatch": [1]})
        reqs = [srv.submit(p, max_new_tokens=5)
                for p in _prompts(rng, 4, lo=5, hi=8)]
        guard = 0
        while srv.pending or srv.live_count:
            try:
                srv.step()
            except InjectedFault:
                pass
            guard += 1
            assert guard < 500
        assert srv.watchdog.recompiles == 0
        for r in reqs:
            assert r.state in (RequestState.FINISHED, RequestState.FAILED)
            assert r.finish_reason is not None
        _assert_clean(srv)

    def test_slow_dispatch_trips_step_wall_watchdog(self, stack, pool):
        _, _, engine = stack
        rng = np.random.default_rng(13)
        srv = make_server(engine, pool, num_slots=2, step_wall_budget_ms=0.001,
                          fault_injector=FaultInjector(
                              seed=0, schedule={"slow_dispatch": [1]},
                              slow_ms=5.0))
        req = srv.submit(rng.integers(0, 64, size=5).astype(np.int32),
                         max_new_tokens=2)
        srv.run_until_drained(max_steps=20)
        assert req.state is RequestState.FINISHED   # flagged, never killed
        assert srv.stats()["step_overruns"] >= 1
        _assert_clean(srv)


# ---------------------------------------------------------------------------
# stall guard
# ---------------------------------------------------------------------------
class TestStallGuard:
    def test_livelock_raises_with_dump(self, stack, pool):
        _, _, engine = stack
        srv = make_server(engine, pool, num_slots=2, max_queue_depth=8)
        req = srv.submit(np.arange(6, dtype=np.int32), max_new_tokens=4)
        # sever the scheduler: queued work that can never be granted is
        # exactly the livelock signature the guard exists to catch
        srv.scheduler.grant = lambda *a, **k: []
        with pytest.raises(ServingStalledError) as ei:
            srv.run_until_drained(stall_patience=5)
        dump = ei.value.dump
        assert [d["request_id"] for d in dump] == [req.request_id]
        assert dump[0]["state"] == "queued"
        assert "no progress" in str(ei.value)

    def test_max_steps_break_still_returns(self, stack, pool):
        # the pre-existing contract: max_steps caps work WITHOUT raising
        _, _, engine = stack
        srv = make_server(engine, pool, num_slots=2)
        srv.submit(np.arange(6, dtype=np.int32), max_new_tokens=50)
        out = srv.run_until_drained(max_steps=3)
        assert isinstance(out, list)
        assert srv.live_count == 1      # genuinely mid-flight, no error
