"""Disaggregated prefill/decode serving: cross-pool page transfer,
role-aware routing, elastic scale events.

The oracle is the same one every paging test leans on: static-batch
``generate()`` greedy tokens. A transferred page is EXACTLY the bits
the prefill replica wrote, so a disaggregated fleet must be bitwise
identical to a single colocated engine — any drift means the transfer
primitive corrupted a page or seated it at the wrong table entry.
"""

import numpy as np
import pytest

from deepspeed_tpu.serving import RequestState, ServingEngine
from deepspeed_tpu.serving.router import ReplicaRouter

PS = 8  # page size == prefill chunk for every server in this file

LENGTHS = [5, 9, 12, 5, 17, 12]
BUDGETS = [6, 4, 8, 3, 7, 5]


def paged_server(engine, role="both", **kw):
    kw.setdefault("prefill_chunk", PS)
    return ServingEngine(engine, num_slots=2, max_queue_depth=32,
                         paged_kv={"page_size": PS, "num_pages": None},
                         role=role, **kw)


def _prompts(seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 64, size=n).astype(np.int32) for n in LENGTHS]


def _warm(router, *, max_steps=600):
    """Drive one full shape population through the fleet, then arm the
    watchdogs: admit widths, decode, sampling AND the transfer program
    all record their signatures before end_warmup."""
    reqs = [router.submit(p, max_new_tokens=b)
            for p, b in zip(_prompts(3), BUDGETS)]
    router.run_until_drained(max_steps=max_steps)
    assert all(r.state is RequestState.FINISHED for r in reqs)
    router.end_warmup()


def _spawn_factory(engine, **kw):
    """Elastic provisioner: a newcomer must arrive TRAFFIC-WARMED (the
    constructor pre-warm does not cover admit/decode/sample widths), so
    the factory drives the warm population standalone before handing
    the replica to ``add_replica``."""
    wprompts = _prompts(3)

    def spawn(role):
        rep = paged_server(engine, role=role, **kw)
        if role != "prefill":
            w = [rep.submit(p, max_new_tokens=b)
                 for p, b in zip(wprompts, BUDGETS)]
            rep.run_until_drained(max_steps=600)
            assert all(r.state is RequestState.FINISHED for r in w)
        else:
            # prefill-role replicas never decode: warm by prefilling to
            # the parked-handoff state, then cancel
            for p, b in zip(wprompts, BUDGETS):
                r = rep.submit(p, max_new_tokens=b)
                for _ in range(40):
                    rep.step()
                    if r in rep.pending_handoffs():
                        break
                rep.cancel(r.request_id)
        return rep

    return spawn


def _assert_bitwise(engine, reqs, prompts, budgets):
    for req, prompt, budget in zip(reqs, prompts, budgets):
        assert req.state is RequestState.FINISHED, (
            req.request_id, req.state, req.finish_reason)
        expected = engine.generate(np.asarray(prompt)[None],
                                   max_new_tokens=budget)[0]
        np.testing.assert_array_equal(req.tokens(), expected,
                                      err_msg=f"req {req.request_id}")


def _assert_no_page_leaks(srv):
    srv.check_invariants()
    assert srv.live_count == 0
    pool = srv.pool
    # after a full drain, every non-free page is trie-held — anything
    # else is a leaked transfer
    trie_pages = set(pool.prefix.page_counts())
    assert len(pool._free_page_set) + len(trie_pages) == pool.num_pages
    assert not (trie_pages & pool._free_page_set)


# ---------------------------------------------------------------------------
class TestDisaggParity:
    def test_disaggregated_greedy_bitwise_matches_single_engine(self, stack):
        """1-prefill + 1-decode fleet produces EXACTLY the single-engine
        generate() tokens, every request travelling through a page
        transfer; zero post-warmup recompiles with strict watchdogs on
        BOTH replicas."""
        _, _, engine = stack
        router = ReplicaRouter(
            [paged_server(engine, role="prefill", strict_recompile=True),
             paged_server(engine, role="decode", strict_recompile=True)])
        _warm(router)
        prompts = _prompts(7)
        reqs = [router.submit(p, max_new_tokens=b)
                for p, b in zip(prompts, BUDGETS)]
        router.run_until_drained(max_steps=600)
        _assert_bitwise(engine, reqs, prompts, BUDGETS)
        router.check_invariants()
        assert router.recompiles == 0
        assert router.transfers >= len(reqs)
        topo = router.fleet_topology()
        assert topo["counts"] == {"prefill": 1, "decode": 1, "both": 0}
        assert topo["transfers_in_flight"] == 0

    def test_fleet_metrics_and_prometheus_surface(self, stack):
        _, _, engine = stack
        router = ReplicaRouter([paged_server(engine, role="prefill"),
                                paged_server(engine, role="decode")])
        _warm(router)
        prom = router.registry.to_prometheus()
        assert "router_fleet_size 2" in prom
        assert "router_transfers_total" in prom
        assert "router_transfers_in_flight 0" in prom
        st = router.stats()
        assert st["transfers"] == router.transfers > 0
        assert st["transfer_bytes"] == router.transfer_bytes > 0
        assert st["fleet"]["fleet_size"] == 2


# ---------------------------------------------------------------------------
class TestMidTransferDeath:
    def test_decode_replica_dies_mid_transfer(self, stack):
        """A destination replica that dies while seating an imported
        batch: its pages are unwound (no leak in EITHER pool), the
        replica is retired, and the parked request re-homes to the
        surviving decode replica with bitwise-correct output."""
        _, _, engine = stack
        pre = paged_server(engine, role="prefill")
        d0 = paged_server(engine, role="decode")
        d1 = paged_server(engine, role="decode")
        router = ReplicaRouter([pre, d0, d1])
        _warm(router)

        # make d0's next seat_pages blow up mid-transfer (AFTER
        # import_pages has allocated destination pages)
        victim = router.replicas[1]
        real_seat = victim.pool.seat_pages
        state = {"armed": True}

        def dying_seat(*a, **kw):
            if state["armed"]:
                state["armed"] = False
                raise RuntimeError("replica died mid-transfer")
            return real_seat(*a, **kw)

        victim.pool.seat_pages = dying_seat
        prompts = _prompts(11)
        reqs = [router.submit(p, max_new_tokens=b)
                for p, b in zip(prompts, BUDGETS)]
        router.run_until_drained(max_steps=800)
        # the victim was retired by the failed transfer (the parked
        # request never left the source, so it re-homes by retry on the
        # surviving sibling, not through the failover re-admit path)
        assert not router._alive[1]
        # ... every request still finished, bitwise identical
        _assert_bitwise(engine, reqs, prompts, BUDGETS)
        router.check_invariants()
        # no page leaked in either pool: the dead replica's imported
        # pages were unwound, the source's copies released on handoff
        victim.pool.seat_pages = real_seat
        for srv in (pre, d0, d1):
            _assert_no_page_leaks(srv)

    def test_adopt_unwind_leaves_destination_pool_clean(self, stack):
        """Engine-level unwind contract: a seat failure inside adopt()
        hands back the WHOLE imported batch and the slot, leaving the
        destination pool exactly as it was."""
        _, _, engine = stack
        pre = paged_server(engine, role="prefill")
        dec = paged_server(engine, role="decode")
        prompt = _prompts(13)[2]
        req = pre.submit(prompt, max_new_tokens=4)
        for _ in range(40):
            pre.step()
            if req in pre.pending_handoffs():
                break
        assert req in pre.pending_handoffs()
        free_slots = dec.pool.free_count
        free_pages = len(dec.pool._free_page_set)
        real_seat = dec.pool.seat_pages
        dec.pool.seat_pages = lambda *a, **kw: (_ for _ in ()).throw(
            RuntimeError("boom"))
        with pytest.raises(RuntimeError):
            dec.adopt(req, pre)
        dec.pool.seat_pages = real_seat
        assert dec.pool.free_count == free_slots
        assert len(dec.pool._free_page_set) == free_pages
        dec.check_invariants()
        # source still owns the request; a later adopt succeeds
        src_slot = next(s for s, r in pre._slot_req.items() if r is req)
        stats = dec.adopt(req, pre)
        assert stats["pages"] >= 1
        pre.finish_handoff(req, src_slot)
        dec.run_until_drained(max_steps=200)
        assert req.state is RequestState.FINISHED


# ---------------------------------------------------------------------------
class TestElasticFleet:
    def test_add_and_retire_under_load_drops_nothing(self, stack):
        """Scale events racing live traffic: no request is dropped or
        rejected to death, no page leaks, and the watchdogs stay at
        zero recompiles (strict on every replica, including the
        newcomer)."""
        _, _, engine = stack
        router = ReplicaRouter(
            [paged_server(engine, role="prefill", strict_recompile=True),
             paged_server(engine, role="decode", strict_recompile=True)])
        _warm(router)
        spawn = _spawn_factory(engine, strict_recompile=True)
        prompts = _prompts(17)
        # wave 1 in flight ...
        reqs = [router.submit(p, max_new_tokens=b)
                for p, b in zip(prompts[:3], BUDGETS[:3])]
        for _ in range(4):
            router.step()
        # ... scale OUT mid-flight, then submit wave 2
        i = router.add_replica(spawn("decode"), "decode")
        assert router.last_scale_event["action"] == "add"
        reqs += [router.submit(p, max_new_tokens=b)
                 for p, b in zip(prompts[3:], BUDGETS[3:])]
        for _ in range(4):
            router.step()
        # scale IN (drain-then-retire via failover re-homing)
        router.retire_replica(i)
        assert router.last_scale_event["action"] == "retire"
        router.run_until_drained(max_steps=800)
        _assert_bitwise(engine, reqs, prompts, BUDGETS)
        router.check_invariants()
        assert router.recompiles == 0
        assert len(router.scale_events) == 2

    def test_autoscale_spawns_on_sustained_pressure_and_retires_idle(
            self, stack):
        """The burn-rate-driven loop: sustained saturation on a role
        spawns a replica of that role; sustained idleness drains and
        retires it back to the floor."""
        _, _, engine = stack
        router = ReplicaRouter(
            [paged_server(engine, role="prefill"),
             paged_server(engine, role="decode")],
            spawner=_spawn_factory(engine), scale_patience=2)
        _warm(router)
        # saturate the decode role: more live work than its 2 slots
        prompts = _prompts(19) + _prompts(23)
        budgets = BUDGETS + BUDGETS
        reqs = [router.submit(p, max_new_tokens=b)
                for p, b in zip(prompts, budgets)]
        router.run_until_drained(max_steps=1200)
        assert all(r.state is RequestState.FINISHED for r in reqs)
        grew = [e for e in router.scale_events if e["action"] == "add"]
        assert grew, "sustained pressure never triggered a spawn"
        # idle ticks retire the surplus back down
        for _ in range(40):
            router.step()
            if router.num_replicas - len(
                    [e for e in router.scale_events
                     if e["action"] == "retire"]) <= 2:
                break
        shrank = [e for e in router.scale_events if e["action"] == "retire"]
        assert shrank, "sustained idleness never retired the surplus"
        router.check_invariants()

    def test_retire_refuses_to_strand_a_role(self, stack):
        _, _, engine = stack
        router = ReplicaRouter([paged_server(engine, role="prefill"),
                                paged_server(engine, role="decode")])
        with pytest.raises(ValueError):
            router.retire_replica(0)   # last prefill-capable
        with pytest.raises(ValueError):
            router.retire_replica(1)   # last decode-capable
