"""Fleet-scope observability: cross-replica request journeys, the
merged telemetry plane, and fleet post-mortems (ISSUE 20).

The load-bearing scenario is the nasty one: a request prefilled on
replica 0, handed off to decode replica 1, which is then killed
MID-STREAM. The journey must still read as ONE story — dispatch,
transfer, failover re-home, finish — stitched across every home it
touched, and the merged Perfetto export must carry one process lane
per replica with flow arrows across the boundaries. Everything here
is host-side bookkeeping: the strict recompile watchdogs stay armed
throughout, pinning the zero-new-jitted-programs acceptance bar.
"""

import json
import os

import numpy as np
import pytest

from deepspeed_tpu.serving import RequestState, ServingEngine
from deepspeed_tpu.serving.router import ReplicaRouter
from deepspeed_tpu.telemetry import (FLEET_POST_MORTEM_KEYS,
                                     QuantileDigest, Tracer)

PS = 8

LENGTHS = [5, 9, 12, 5, 17, 12]
BUDGETS = [6, 4, 8, 3, 7, 5]


def paged_server(engine, role="both", **kw):
    kw.setdefault("prefill_chunk", PS)
    kw.setdefault("tracer", Tracer())
    kw.setdefault("slo", True)
    kw.setdefault("flight_recorder", True)
    return ServingEngine(engine, num_slots=2, max_queue_depth=32,
                         paged_kv={"page_size": PS, "num_pages": None},
                         role=role, **kw)


def _fleet(engine, roles, **kw):
    kw.setdefault("tracer", Tracer())
    return ReplicaRouter([paged_server(engine, role=r) for r in roles], **kw)


def _prompts(seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 64, size=n).astype(np.int32) for n in LENGTHS]


def _warm(router, *, max_steps=600):
    reqs = [router.submit(p, max_new_tokens=b)
            for p, b in zip(_prompts(3), BUDGETS)]
    router.run_until_drained(max_steps=max_steps)
    assert all(r.state is RequestState.FINISHED for r in reqs)
    router.end_warmup()


def _assert_bitwise(engine, reqs, prompts, budgets):
    for req, prompt, budget in zip(reqs, prompts, budgets):
        assert req.state is RequestState.FINISHED, (
            req.request_id, req.state, req.finish_reason)
        expected = engine.generate(np.asarray(prompt)[None],
                                   max_new_tokens=budget)[0]
        np.testing.assert_array_equal(req.tokens(), expected,
                                      err_msg=f"req {req.request_id}")


def _assert_perfetto_schema(doc, *, lanes):
    """Minimal Chrome-trace/Perfetto schema check for a merged fleet
    export: per-replica process lanes, named via metadata, every flow
    terminator carrying ``bp: "e"`` (enclosing-slice binding — without
    it Perfetto drops the arrow)."""
    assert set(doc) >= {"traceEvents", "displayTimeUnit", "otherData"}
    events = doc["traceEvents"]
    assert {e["pid"] for e in events} == set(range(lanes))
    names = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert set(names) == set(range(lanes))
    assert names[0] == "router"
    for ev in events:
        assert ev["ph"] in ("X", "i", "C", "b", "n", "e", "s", "f", "M"), ev
        if ev["ph"] in ("s", "f"):
            assert "id" in ev and "cat" in ev
        if ev["ph"] == "f":
            assert ev.get("bp") == "e", ev
    # flow arrows must actually pair ACROSS lanes (same cat+id, start
    # and finish on different pids), else the hop renders as nothing
    starts = {(e["cat"], e["id"]): e["pid"] for e in events
              if e["ph"] == "s"}
    cross = [e for e in events if e["ph"] == "f"
             and starts.get((e["cat"], e["id"])) not in (None, e["pid"])]
    assert cross, "no cross-lane flow arrow in merged trace"
    return names


# ---------------------------------------------------------------------------
class TestJourneyStitching:
    def test_handoff_then_decode_death_is_one_complete_journey(self, stack):
        """Prefill -> handoff -> decode replica KILLED mid-stream ->
        failover re-home: the stitched journey is ONE complete story
        spanning every home, the output stays bitwise-identical, and
        the merged Perfetto export passes the schema check."""
        _, _, engine = stack
        router = _fleet(engine, ["prefill", "decode", "decode"])
        _warm(router)
        prompts = _prompts(7)
        reqs = [router.submit(p, max_new_tokens=b)
                for p, b in zip(prompts, BUDGETS)]
        # step until a decode replica owns live work, then kill it
        victim = None
        for _ in range(200):
            router.step()
            victim = next((i for i in (1, 2) if router._alive[i]
                           and router.replicas[i].live_count), None)
            if victim is not None:
                break
        assert victim is not None, "no request ever reached a decode home"
        vic = router.replicas[victim]
        real_step = vic.step
        vic.step = lambda: (_ for _ in ()).throw(
            RuntimeError("decode replica killed mid-stream"))
        router.run_until_drained(max_steps=800)
        vic.step = real_step
        assert not router._alive[victim]
        assert router.failovers >= 1
        _assert_bitwise(engine, reqs, prompts, BUDGETS)

        # every journey closed: finished == complete, nothing parked
        js = router.journey_summary()
        assert js["finished"] == js["total"]
        assert js["complete"] == js["finished"], js["incomplete"]

        # at least one journey was re-homed by the failover and its
        # stitched view covers BOTH decode homes plus the prefill home
        rehomed = [router.journey(router.journey_of(r.request_id))
                   for r in reqs]
        multi = [j for j in rehomed
                 if any(h["kind"] == "failover" for h in j["hops"])]
        assert multi, "failover left no journey hop"
        j = multi[0]
        assert j["complete"] and j["terminal"] == "finish"
        assert victim in j["homes"] and len(set(j["homes"])) >= 2
        kinds = [h["kind"] for h in j["hops"]]
        assert kinds[0] == "dispatch" and kinds[-1] == "finish"
        assert "transfer" in kinds and "failover" in kinds
        # hop timestamps interleave with timeline events on ONE clock:
        # the stitched event list is globally sorted
        ts = [e["t_ns"] for e in j["events"]]
        assert ts == sorted(ts)
        # the corpse's lifecycle was closed terminally (failed_over) and
        # the inheritor opened a resumed line — no home left dangling
        evs = [(e["replica"], e["event"]) for e in j["events"]]
        assert any(ev == "failed_over" for _, ev in evs)
        assert any(ev == "resumed" for _, ev in evs)
        router.check_invariants()

    def test_export_trace_merged_perfetto_document(self, stack, tmp_path):
        _, _, engine = stack
        router = _fleet(engine, ["prefill", "decode"])
        _warm(router)
        path = str(tmp_path / "fleet-trace.json")
        n = router.export_trace(path)
        assert n > 0
        doc = json.load(open(path))
        names = _assert_perfetto_schema(doc, lanes=3)
        assert names[1].startswith("replica0") and "prefill" in names[1]
        assert names[2].startswith("replica1") and "decode" in names[2]
        assert doc["otherData"]["processes"]["0"] == "router"
        router.check_invariants()

    def test_parked_mid_handoff_journey_is_not_falsely_complete(self, stack):
        """A request parked in ``pending_handoffs()`` is BETWEEN homes:
        its source timeline is still open AND flagged parked, so the
        stitched journey must read incomplete until a decode replica
        adopts and finishes it."""
        _, _, engine = stack
        router = _fleet(engine, ["prefill", "decode"])
        _warm(router)
        pre = router.replicas[0]
        req = router.submit(_prompts(13)[2], max_new_tokens=4)
        parked = False
        for _ in range(40):
            pre.step()          # step ONLY the prefill replica: the
            #                     router never drains the handoff
            if req in pre.pending_handoffs():
                parked = True
                break
        assert parked
        assert req.request_id in pre.timelines.parked_ids()
        j = router.journey(req.journey_id)
        assert not j["complete"]
        assert j["parked_homes"] == [0]
        assert j["terminal"] is None  # in flight: not finished, so the
        #                               completeness gate ignores it
        # drain through the router: adoption clears the parked flag and
        # the journey closes
        router.run_until_drained(max_steps=400)
        assert req.state is RequestState.FINISHED
        j = router.journey(req.journey_id)
        assert j["complete"] and not j["parked_homes"]
        assert req.request_id not in pre.timelines.parked_ids()
        router.check_invariants()

    def test_journeys_survive_zero_recompile_budget(self, stack):
        """The whole observability plane is host-side: strict watchdogs
        on every replica see ZERO post-warmup compiles with journeys,
        fleet metrics and trace export all active."""
        _, _, engine = stack
        router = ReplicaRouter(
            [paged_server(engine, role="prefill", strict_recompile=True),
             paged_server(engine, role="decode", strict_recompile=True)],
            tracer=Tracer())
        _warm(router)
        prompts = _prompts(29)
        reqs = [router.submit(p, max_new_tokens=b)
                for p, b in zip(prompts, BUDGETS)]
        router.run_until_drained(max_steps=600)
        _assert_bitwise(engine, reqs, prompts, BUDGETS)
        router.fleet.to_prometheus()
        router.fleet.health_summary()
        router.fleet.efficiency_snapshot()
        assert router.recompiles == 0


# ---------------------------------------------------------------------------
class TestFleetTelemetryPlane:
    def test_merged_prometheus_exposition(self, stack):
        _, _, engine = stack
        router = _fleet(engine, ["prefill", "decode"])
        _warm(router)
        prom = router.fleet.to_prometheus()
        # router-scope series stay unlabeled (backward compatible)
        assert "router_fleet_size 2" in prom
        assert "router_transfers_total" in prom
        assert "router_transfer_wire_bytes_total" in prom
        # per-replica series labeled by replica + role
        assert 'replica="0",role="prefill"' in prom
        assert 'replica="1",role="decode"' in prom
        # fleet rollups
        for series in ("fleet_goodput", "fleet_burn_short",
                       "fleet_journeys_total", "fleet_journeys_complete",
                       "fleet_transfer_latency_p99_ms"):
            assert series in prom, series
        # exactly one TYPE line per metric family, even with one series
        # per replica (Prometheus text format rejects duplicates)
        type_lines = [ln for ln in prom.splitlines()
                      if ln.startswith("# TYPE ")]
        assert len(type_lines) == len({ln.split()[2] for ln in type_lines})

    def test_transfer_wire_bytes_and_latency_metrics(self, stack):
        """Satellite (a): every page transfer feeds the wire-bytes
        counter + histogram and the transfer-latency digest; trie-hit
        pages never cross the wire so the counter equals the router's
        ``transfer_bytes`` (which already excludes them)."""
        _, _, engine = stack
        router = _fleet(engine, ["prefill", "decode"])
        _warm(router)
        prompts = _prompts(7)
        reqs = [router.submit(p, max_new_tokens=b)
                for p, b in zip(prompts, BUDGETS)]
        router.run_until_drained(max_steps=600)
        assert all(r.state is RequestState.FINISHED for r in reqs)
        assert router.transfers >= len(reqs)
        assert router.transfer_latency.count == router.transfers
        p99 = router.transfer_latency.quantile(0.99)
        assert p99 > 0
        snap = router.registry.snapshot()
        assert snap["router/transfer_wire_bytes_total"] == \
            router.transfer_bytes > 0
        assert snap["router/transfer_wire_bytes/count"] == router.transfers
        assert snap["router/transfer_wire_bytes/sum"] == \
            router.transfer_bytes
        eff = router.fleet.efficiency_snapshot()
        assert eff["transfer_latency_p99_ms"] == pytest.approx(p99)

    def test_fleet_goodput_sums_windows_not_burns(self, stack):
        """Fleet goodput must equal what ONE tracker that saw every
        request would report — sum the raw [admitted, good] window
        pairs across replicas, never average per-replica ratios
        (2/10 + 8/8 averaged is 0.6; pooled it is 10/18)."""
        _, _, engine = stack
        router = _fleet(engine, ["prefill", "decode"])
        a, b = router.replicas[0].slo, router.replicas[1].slo
        for _ in range(10):
            a.observe_admitted()
        for _ in range(2):
            a.observe_finish(ttft_s=0.01, e2e_s=0.01)
        for _ in range(8):
            b.observe_admitted()
            b.observe_finish(ttft_s=0.01, e2e_s=0.01)
        g = router.fleet.goodput()
        assert g["admitted"] == 18 and g["good"] == 10
        assert g["goodput_slo"] == pytest.approx(10 / 18)
        assert g["alert_state"] in ("ok", "warn", "page")

    def test_quantile_merge_accuracy_pinned(self):
        """Satellite/acceptance: merging N per-replica digests is as
        accurate as one digest that saw every sample, and both land
        within the digest's relative-error bound of the exact numpy
        percentile."""
        rng = np.random.default_rng(42)
        samples = rng.lognormal(mean=3.0, sigma=1.2, size=8000)
        shards = np.array_split(samples, 4)
        digests = []
        for shard in shards:
            d = QuantileDigest()
            for v in shard:
                d.add(float(v))
            digests.append(d)
        merged = QuantileDigest()
        for d in digests:
            merged = merged.merge(d)
        one = QuantileDigest()
        for v in samples:
            one.add(float(v))
        assert merged.count == one.count == len(samples)
        for q in (0.5, 0.9, 0.99):
            exact = float(np.percentile(samples, q * 100))
            got = merged.quantile(q)
            # merged == single-digest (bucketwise merge is lossless)
            assert got == pytest.approx(one.quantile(q))
            assert abs(got - exact) <= 2 * merged.rel_error * exact, (
                q, got, exact)

    def test_digest_param_mismatch_raises(self):
        a, b = QuantileDigest(), QuantileDigest(rel_error=0.05)
        with pytest.raises(ValueError):
            a.merge(b)

    def test_health_summary_per_replica_and_per_role(self, stack):
        _, _, engine = stack
        router = _fleet(engine, ["prefill", "decode"])
        _warm(router)
        hs = router.fleet.health_summary()
        assert set(hs["replicas"]) == {"0", "1"}
        assert hs["replicas"]["0"]["role"] == "prefill"
        assert hs["replicas"]["1"]["alert"] in ("ok", "warn", "page")
        assert set(hs["roles"]) == {"prefill", "decode"}
        for role in hs["roles"].values():
            assert {"replicas", "queue_depth", "backlog"} <= set(role)
        assert hs["journeys"]["complete"] == hs["journeys"]["finished"]
        assert hs["alert_state"] in ("ok", "warn", "page")


# ---------------------------------------------------------------------------
class TestFleetPostMortem:
    def test_replica_death_dumps_one_fleet_scoped_file(self, stack,
                                                       tmp_path):
        """ANY replica failing mid-step produces ONE fleet post-mortem:
        every replica's flight-recorder ring, the router's dispatch and
        scale-event log, journeys, and the trigger replica marked."""
        _, _, engine = stack
        router = _fleet(engine, ["prefill", "decode", "decode"],
                        dump_dir=str(tmp_path))
        _warm(router)
        prompts = _prompts(7)
        reqs = [router.submit(p, max_new_tokens=b)
                for p, b in zip(prompts, BUDGETS)]
        victim = None
        for _ in range(200):
            router.step()
            victim = next((i for i in (1, 2) if router._alive[i]
                           and router.replicas[i].live_count), None)
            if victim is not None:
                break
        assert victim is not None
        vic = router.replicas[victim]
        real_step = vic.step
        vic.step = lambda: (_ for _ in ()).throw(RuntimeError("boom"))
        router.run_until_drained(max_steps=800)
        vic.step = real_step
        assert all(r.state is RequestState.FINISHED for r in reqs)

        files = [f for f in os.listdir(tmp_path)
                 if f.startswith("fleet-postmortem")]
        assert len(files) == 1, files
        assert "replica_error" in files[0]
        pm = json.load(open(tmp_path / files[0]))
        # the key set is the fleet debugging contract — pinned
        assert set(pm) == set(FLEET_POST_MORTEM_KEYS)
        assert pm["trigger_replica"] == victim
        assert pm["fleet_size"] == 3
        assert set(pm["replicas"]) == {"0", "1", "2"}
        assert pm["replicas"][str(victim)]["trigger"] is True
        assert sum(r["trigger"] for r in pm["replicas"].values()) == 1
        # per-replica rings share the injected clock: step records carry
        # router-clock "t" stamps so the dump aligns without guesswork
        for rep in pm["replicas"].values():
            assert {"schema_version", "steps", "registry",
                    "role", "alive"} <= set(rep)
        steps = [s for rep in pm["replicas"].values()
                 for s in rep["steps"]]
        assert steps and all("t" in s and "replica" in s for s in steps)
        assert pm["router"]["failovers"] >= 0
        assert pm["journeys"]
        assert len(router.fleet.dumps) == 1

    def test_invariant_violation_dumps_with_trigger(self, stack, tmp_path):
        _, _, engine = stack
        router = _fleet(engine, ["prefill", "decode"],
                        dump_dir=str(tmp_path))
        _warm(router)
        # corrupt replica 1's slot bookkeeping so its own invariant
        # audit trips inside router.check_invariants()
        from deepspeed_tpu.serving import Request
        from deepspeed_tpu.serving.resilience import InvariantViolation
        ghost = Request(999, np.zeros(4, np.int32), 4)
        router.replicas[1]._slot_req[99] = ghost
        with pytest.raises(InvariantViolation):
            router.check_invariants()
        del router.replicas[1]._slot_req[99]
        files = [f for f in os.listdir(tmp_path)
                 if f.startswith("fleet-postmortem")]
        assert len(files) == 1
        pm = json.load(open(tmp_path / files[0]))
        assert pm["trigger_replica"] == 1
        assert pm["replicas"]["1"]["trigger"] is True

    def test_dump_never_raises_without_dump_dir(self, stack):
        _, _, engine = stack
        router = _fleet(engine, ["prefill", "decode"])
        assert router.fleet.dump("replica_error") is None
        assert router.fleet.dumps == []
