"""Efficiency/goodput observability through the serving engine: the
flight recorder must produce exactly one schema-pinned post-mortem per
planted invariant violation, ``debug_dump`` must serve the same payload
live, the cost model must never perturb serving outputs, the SLO
tracker must count failures against goodput, and the telemetry-health
collector must surface tracer/sink/recorder counters in Prometheus."""

import json

import numpy as np
import pytest

from deepspeed_tpu.serving import InvariantViolation
from deepspeed_tpu.serving.resilience import FaultInjector
from deepspeed_tpu.telemetry.flight_recorder import (POST_MORTEM_KEYS,
                                                     SCHEMA_VERSION)

from .conftest import make_server


def _prompts(rng, n, lo=5, hi=12):
    return [rng.integers(0, 64, size=int(rng.integers(lo, hi)))
            .astype(np.int32) for _ in range(n)]


def test_postmortem_on_planted_invariant_violation(stack, pool, tmp_path):
    _, _, engine = stack
    rng = np.random.default_rng(71)
    srv = make_server(engine, pool, num_slots=2, max_queue_depth=8,
                      fault_injector=FaultInjector(seed=0),
                      dump_dir=str(tmp_path))
    srv.faults.load_schedule({"state_corruption": [1]})
    for p in _prompts(rng, 2):
        srv.submit(p, max_new_tokens=4)
    srv.step()              # corruption fires at this step's tail
    with pytest.raises(InvariantViolation):
        srv.check_invariants()

    files = sorted(tmp_path.glob("postmortem-*.json"))
    assert len(files) == 1          # exactly one per planted violation
    with open(files[0]) as f:
        pm = json.load(f)
    # the file shape external tooling relies on, pinned
    assert sorted(pm) == sorted(POST_MORTEM_KEYS)
    assert pm["schema_version"] == SCHEMA_VERSION
    assert pm["reason"] == "invariant_violation"
    assert "free" in pm["error"]            # the corrupted free set
    assert pm["extra"]["violations"]
    # the last ring record is the step the corruption landed in
    last = pm["steps"][-1]
    assert last["step_id"] == srv.step_id
    assert last["live"] == 2
    for key in ("t_unix", "wall_ms", "pending", "prefilling", "free_slots",
                "granted", "finished", "tokens_total", "load_state",
                "alert_state"):
        assert key in last
    assert srv.recorder.dump_count == 1


def test_debug_dump_serves_postmortem_payload_live(stack, pool):
    _, _, engine = stack
    rng = np.random.default_rng(73)
    srv = make_server(engine, pool, num_slots=2, max_queue_depth=8, slo=True)
    for p in _prompts(rng, 3):
        srv.submit(p, max_new_tokens=8)
    for _ in range(2):
        srv.step()
    d = srv.debug_dump()            # healthy process, no files written
    assert d["schema_version"] == SCHEMA_VERSION
    assert d["step_id"] == 2 and d["live"] >= 1
    assert len(d["steps"]) == 2
    assert d["watchdog"]["recompiles"] == 0
    assert d["telemetry_overhead_s"] >= 0.0
    # 3 admitted, none finished yet: goodput is legitimately burning
    assert d["slo"]["alert_state"] in ("ok", "warn", "page")
    assert d["slo"]["admitted"] == 3
    assert isinstance(d["requests"], (list, dict))
    srv.run_until_drained(max_steps=100)
    assert srv.recorder.dump_count == 0


def test_cost_model_never_perturbs_outputs(stack, pool):
    _, _, engine = stack
    rng = np.random.default_rng(79)
    prompts = _prompts(rng, 6)

    def run(cost_model):
        srv = make_server(engine, pool, num_slots=2, max_queue_depth=8,
                          cost_model=cost_model)
        reqs = [srv.submit(p, max_new_tokens=5) for p in prompts]
        srv.run_until_drained(max_steps=200)
        return [list(r.output_tokens) for r in reqs]

    assert run(False) == run(True)  # greedy serving is bit-identical


def test_cost_model_harvests_and_reconciles(stack, pool):
    _, _, engine = stack
    rng = np.random.default_rng(83)
    srv = make_server(engine, pool, num_slots=2, max_queue_depth=8,
                      cost_model=True)
    for p in _prompts(rng, 4):
        srv.submit(p, max_new_tokens=4)
    srv.run_until_drained(max_steps=200)
    cs = srv.costs.summary()
    assert cs["programs"] >= 1 and cs["flops_total"] > 0
    assert cs["unavailable"] == 0           # XLA:CPU serves cost_analysis
    eff = srv.efficiency_snapshot()
    assert eff["mfu"] > 0.0
    assert eff["hbm_drift"] == 0.0          # page math == device bytes
    assert eff["hbm_peak_bytes"] > 0
    assert eff["telemetry_overhead_s"] > 0.0
    assert 0.0 <= eff["overhead_pct"]


def test_slo_counts_deadline_expiry_against_goodput(stack, pool):
    _, _, engine = stack
    rng = np.random.default_rng(89)
    srv = make_server(engine, pool, num_slots=2, max_queue_depth=8,
                      slo={"ttft_ms": 60_000.0, "gap_ms": 60_000.0,
                           "window_steps": 8})
    good = [srv.submit(p, max_new_tokens=3) for p in _prompts(rng, 3)]
    srv.run_until_drained(max_steps=100)
    assert srv.slo.goodput() == 1.0
    # an expired deadline finishes with reason=deadline -> not good
    # service no matter how fast it failed
    srv.submit(_prompts(rng, 1)[0], max_new_tokens=3, deadline_ms=1e-3)
    srv.step()
    snap = srv.slo.snapshot()
    assert snap["admitted"] == len(good) + 1
    assert snap["good"] == len(good)
    assert srv.slo.goodput() == pytest.approx(len(good) / (len(good) + 1))
    eff = srv.efficiency_snapshot()
    assert eff["goodput_slo"] == pytest.approx(snap["good"]
                                               / snap["admitted"])
    assert eff["alert_state"] in ("ok", "warn", "page")


def test_prometheus_exposes_telemetry_health(stack, pool):
    _, _, engine = stack

    class _Sink:
        enabled = True
        write_errors = 3                    # a bare JSONL-style sink

        def write_events(self, events):
            pass

    rng = np.random.default_rng(97)
    srv = make_server(engine, pool, num_slots=2, max_queue_depth=8,
                      tracer=True, monitor=_Sink())
    for p in _prompts(rng, 2):
        srv.submit(p, max_new_tokens=3)
    srv.run_until_drained(max_steps=100)
    text = srv.registry.to_prometheus()
    assert "telemetry_tracer_events_total" in text
    assert "telemetry_tracer_dropped" in text
    assert "telemetry_flight_recorder_records" in text
    assert "telemetry_postmortem_dumps" in text
    assert "monitor_jsonl_write_errors 3" in text
    snap = srv.registry.snapshot()
    assert snap["telemetry/tracer_events_total"] > 0
    assert snap["telemetry/flight_recorder_records"] == srv.step_id
