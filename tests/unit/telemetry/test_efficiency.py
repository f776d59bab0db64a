"""Efficiency/goodput telemetry unit tests (host-only, no engine):
quantile-digest accuracy against numpy on adversarial distributions,
window rotation and merge semantics, SLO goodput + burn-rate alerting,
and the flight-recorder ring + post-mortem file schema."""

import json
import math
import os

import numpy as np
import pytest

from deepspeed_tpu.telemetry.flight_recorder import (POST_MORTEM_KEYS,
                                                     SCHEMA_VERSION,
                                                     FlightRecorder)
from deepspeed_tpu.telemetry.slo import (QuantileDigest, SLOConfig,
                                         SLOTargets, SLOTracker,
                                         WindowedQuantiles)


# -- QuantileDigest ----------------------------------------------------
# the digest's guarantee is RELATIVE error (geometric bucket midpoint),
# so every accuracy assertion is on |est/true - 1|. rel_error=0.01
# bounds the bucket half-width at 1%; rank rounding vs numpy's
# interpolation adds at most one bucket, hence the 3% tolerance.
_DISTS = {
    "lognormal": lambda g: g.lognormal(mean=3.0, sigma=1.5, size=20_000),
    "pareto": lambda g: (1.0 + g.pareto(a=1.5, size=20_000)) * 10.0,
    # unequal modes so p50/p90/p99 land INSIDE a mode — a quantile at
    # the exact mode boundary is degenerate (numpy interpolates across
    # the gap, a rank-based digest picks a side; both are defensible)
    "bimodal": lambda g: np.concatenate([
        g.normal(5.0, 0.5, size=9_000),
        g.normal(5_000.0, 250.0, size=11_000)]),
    "uniform_wide": lambda g: g.uniform(0.05, 9e6, size=20_000),
}


@pytest.mark.parametrize("name", sorted(_DISTS))
@pytest.mark.parametrize("q", [0.5, 0.9, 0.99])
def test_digest_accuracy_vs_numpy(name, q):
    vals = np.clip(_DISTS[name](np.random.default_rng(7)), 1e-2, 1e7)
    d = QuantileDigest(min_value=1e-2, max_value=1e7, rel_error=0.01)
    for v in vals:
        d.add(float(v))
    true = float(np.quantile(vals, q))
    assert abs(d.quantile(q) / true - 1.0) < 0.03, \
        f"{name} p{int(q * 100)}: digest={d.quantile(q)} numpy={true}"


def test_digest_constant_stream_is_exact():
    d = QuantileDigest()
    for _ in range(1000):
        d.add(42.0)
    # min/max clamping collapses the bucket midpoint to the only value
    for q in (0.01, 0.5, 0.99):
        assert d.quantile(q) == 42.0


def test_digest_edge_inputs():
    d = QuantileDigest(min_value=1e-2, max_value=1e3)
    d.add(float("nan"))          # dropped
    assert d.count == 0
    d.add(-5.0)                  # clamped to 0 -> bottom bucket
    d.add(0.0)
    d.add(1e9)                   # above max -> top bucket, clamped answer
    assert d.count == 3
    assert d.quantile(0.99) <= 1e9


def test_digest_merge_equals_union_stream():
    g = np.random.default_rng(11)
    a_vals = g.lognormal(2.0, 1.0, size=5_000)
    b_vals = g.lognormal(4.0, 0.5, size=5_000)
    a = QuantileDigest()
    b = QuantileDigest()
    u = QuantileDigest()
    for v in a_vals:
        a.add(float(v))
        u.add(float(v))
    for v in b_vals:
        b.add(float(v))
        u.add(float(v))
    a.merge(b)
    assert a.count == u.count == 10_000
    for q in (0.5, 0.9, 0.99):
        assert a.quantile(q) == u.quantile(q)


def test_digest_merge_rejects_mismatched_params():
    with pytest.raises(ValueError):
        QuantileDigest(rel_error=0.01).merge(QuantileDigest(rel_error=0.05))


def test_digest_memory_is_fixed():
    d = QuantileDigest(min_value=1e-2, max_value=1e7, rel_error=0.01)
    n0 = len(d.counts)
    assert n0 == int(math.ceil(
        math.log(1e9) / math.log(1.02))) + 1
    for v in np.random.default_rng(3).lognormal(3, 2, size=50_000):
        d.add(float(v))
    assert len(d.counts) == n0        # no growth, ever


# -- WindowedQuantiles -------------------------------------------------
def test_window_rotation_expires_old_values():
    wq = WindowedQuantiles(windows=4)
    for _ in range(100):
        wq.add(1000.0)                # a spike in the oldest window
    assert wq.quantile(0.5) == pytest.approx(1000.0, rel=0.03)
    for _ in range(3):
        wq.rotate()
        for _ in range(100):
            wq.add(1.0)
    # spike window still in the ring: p99 sees it
    assert wq.quantile(0.99) == pytest.approx(1000.0, rel=0.03)
    wq.rotate()                       # ...now recycled
    for _ in range(100):
        wq.add(1.0)
    assert wq.quantile(0.99) == pytest.approx(1.0, rel=0.03)
    assert wq.count == 400


# -- SLOConfig / SLOTracker --------------------------------------------
def test_slo_config_resolve_forms():
    assert SLOConfig.resolve(None) is None
    assert SLOConfig.resolve(False) is None
    assert SLOConfig.resolve(True).classes["default"].ttft_ms == 500.0
    cfg = SLOConfig.resolve({"ttft_ms": 50.0, "window_steps": 16,
                             "classes": {"batch": {"ttft_ms": None,
                                                   "gap_ms": 1000.0}}})
    assert cfg.classes["default"].ttft_ms == 50.0
    assert cfg.classes["default"].gap_ms == 200.0     # default kept
    assert cfg.classes["batch"].ttft_ms is None
    assert cfg.window_steps == 16
    assert SLOConfig.resolve(cfg) is cfg
    with pytest.raises(TypeError):
        SLOConfig.resolve(123)


def test_slo_goodput_counts_failures_against():
    t = SLOTracker({"ttft_ms": 100.0, "gap_ms": None})
    for _ in range(8):
        t.observe_admitted()
    for _ in range(6):
        t.observe_finish(ttft_s=0.010)              # within
    t.observe_finish(ttft_s=5.0)                    # TTFT blown
    t.observe_finish(ttft_s=0.010, ok=False)        # fast but failed
    assert t.goodput() == pytest.approx(6 / 8)
    snap = t.snapshot()
    assert snap["admitted"] == 8 and snap["good"] == 6
    assert snap["ttft_p50_ms"] == pytest.approx(10.0, rel=0.03)


def test_slo_burn_rate_alerting_and_reset():
    t = SLOTracker({"ttft_ms": 100.0, "gap_ms": None, "window_steps": 4,
                    "windows": 4, "goodput_target": 0.9,
                    "warn_burn": 2.0, "page_burn": 5.0})
    # every admitted request blows its SLO -> goodput 0, burn 1/0.1 = 10
    for step in range(16):
        t.observe_admitted()
        t.observe_finish(ttft_s=9.0)
        t.on_step(step)
    assert t.alert_state == "page"
    assert t.burn_short >= 5.0 and t.burn_long >= 5.0
    assert t.rotations == 4
    t.reset()
    assert t.alert_state == "ok" and t.goodput() == 1.0
    assert t.overhead_ns == 0
    # healthy traffic keeps it ok
    for step in range(8):
        t.observe_admitted()
        t.observe_finish(ttft_s=0.010)
        t.on_step(step)
    assert t.alert_state == "ok"


def test_slo_per_class_targets():
    t = SLOTracker({"ttft_ms": 100.0, "gap_ms": None,
                    "classes": {"batch": SLOTargets(ttft_ms=None,
                                                    gap_ms=None)}})
    t.observe_admitted("batch")
    assert t.observe_finish(ttft_s=99.0, cls="batch")   # no targets: good
    t.observe_admitted()
    assert not t.observe_finish(ttft_s=99.0)            # default: blown
    assert t.snapshot()["per_class"]["batch"]["good"] == 1


def test_slo_per_class_burn_alerts_are_independent():
    """One class burning must not page the others — the per-class
    two-horizon burn drives the priority scheduler's shedding floor, so
    a batch-tier meltdown paging the interactive tier would shed the
    wrong traffic."""
    t = SLOTracker({"ttft_ms": 100.0, "gap_ms": None, "window_steps": 4,
                    "windows": 4, "goodput_target": 0.9,
                    "warn_burn": 2.0, "page_burn": 5.0})
    for step in range(16):
        t.observe_admitted(cls="interactive")
        t.observe_finish(ttft_s=9.0, cls="interactive")   # always blown
        t.observe_admitted(cls="batch")
        t.observe_finish(ttft_s=0.010, cls="batch")       # always within
        t.on_step(step)
    assert t.class_alert("interactive") == "page"
    assert t.class_alert("batch") == "ok"
    assert t.class_alert("never_seen") == "ok"
    short, long = t.class_burns["interactive"]
    assert short >= 5.0 and long >= 5.0
    snap = t.snapshot()
    assert snap["per_class"]["interactive"]["alert"] == "page"
    assert snap["per_class"]["batch"]["alert"] == "ok"
    assert snap["per_class"]["batch"]["goodput_window"] == 1.0
    t.reset()
    assert t.class_alerts == {} and t.class_burns == {}


def test_slo_observe_cancel_is_goodput_neutral():
    """A cancelled request withdraws its admission: goodput must move
    neither up (it never finished well) nor down (the client hanging up
    is not the server's failure)."""
    t = SLOTracker({"ttft_ms": 100.0, "gap_ms": None})
    for _ in range(4):
        t.observe_admitted(cls="interactive")
    for _ in range(3):
        t.observe_finish(ttft_s=0.010, cls="interactive")
    t.observe_cancel(cls="interactive")
    assert t.goodput() == pytest.approx(1.0)
    assert t.cancelled_total == 1
    snap = t.snapshot()
    assert snap["cancelled"] == 1
    assert snap["per_class"]["interactive"]["admitted"] == 3
    # floors at zero even if the admitting window already rotated out
    t2 = SLOTracker(True)
    t2.observe_cancel(cls="ghost")
    assert t2.goodput() == 1.0 and t2.admitted_total == 0


# -- FlightRecorder ----------------------------------------------------
def test_recorder_ring_is_bounded():
    r = FlightRecorder(capacity=8)
    for i in range(100):
        r.record({"step_id": i})
    assert r.records_total == 100
    steps = r.last()
    assert len(steps) == 8
    assert [s["step_id"] for s in steps] == list(range(92, 100))
    assert [s["step_id"] for s in r.last(3)] == [97, 98, 99]


def test_post_mortem_schema_and_dump(tmp_path):
    r = FlightRecorder(capacity=4, dump_dir=str(tmp_path))
    for i in range(6):
        r.record({"step_id": i, "live": i % 2})
    path = r.dump("invariant_violation",
                  error=RuntimeError("free set corrupt"),
                  extra={"violations": ["x"]})
    assert path is not None and os.path.exists(path)
    assert os.path.basename(path) == \
        "postmortem-000-step5-invariant_violation.json"
    with open(path) as f:
        pm = json.load(f)
    assert sorted(pm) == sorted(POST_MORTEM_KEYS)
    assert pm["schema_version"] == SCHEMA_VERSION
    assert pm["reason"] == "invariant_violation"
    assert "free set corrupt" in pm["error"]
    assert pm["records_total"] == 6
    assert [s["step_id"] for s in pm["steps"]] == [2, 3, 4, 5]
    assert pm["extra"] == {"violations": ["x"]}
    assert r.dump_count == 1 and r.dumps == [path]


def test_dump_without_dir_returns_none_and_never_raises(tmp_path):
    r = FlightRecorder(capacity=2)
    r.record({"step_id": 0})
    assert r.dump("stalled") is None
    assert r.dump_count == 0
    # unwritable dir: swallowed, counted, no raise
    blocked = tmp_path / "file-not-dir"
    blocked.write_text("x")
    r.dump_dir = str(blocked)
    assert r.dump("stalled") is None
    assert r.dump_failures == 1


# --------------------------------------------- mesh-normalized peaks
def test_cost_model_peaks_scale_with_mesh_device_count():
    """cost_analysis reports WHOLE-program flops/bytes, so on a sharded
    mesh the MFU/bandwidth denominators must be nominal-peak x
    participating devices — a TP=4 run reporting single-chip MFU > 1.0
    was the bug this normalization fixes."""
    from deepspeed_tpu.telemetry.costs import (ProgramCostModel,
                                               resolve_peaks)

    pf, pb = resolve_peaks()
    one = ProgramCostModel(num_devices=1)
    four = ProgramCostModel(num_devices=4)
    assert one.peak_flops == pytest.approx(pf)
    assert four.peak_flops == pytest.approx(4 * pf)
    assert four.peak_bytes_per_s == pytest.approx(4 * pb)
    assert four.summary()["num_devices"] == 4


def test_resolve_peaks_knows_the_chip_and_invents_nothing():
    """The v5e reports itself as "TPU v5 lite" (chip_smoke.py, PR 21): it
    must resolve to the published 197 TFLOP/s / 819 GB/s, and an
    accelerator that is not in the table is an error, never a made-up
    peak. Only the CPU gets a nominal figure (for these tests)."""
    from types import SimpleNamespace

    from deepspeed_tpu.telemetry.costs import resolve_peaks

    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert resolve_peaks(v5e) == (197e12, 819e9)
    unknown = SimpleNamespace(platform="tpu", device_kind="TPU v9 mega")
    with pytest.raises(ValueError, match="TPU v9 mega"):
        resolve_peaks(unknown)
    cpu = SimpleNamespace(platform="cpu", device_kind="cpu")
    assert resolve_peaks(cpu) == resolve_peaks()   # tests run on the CPU


def test_cost_model_autodetects_global_mesh():
    """num_devices=None resolves against the installed global mesh at
    construction (1 with no mesh — the single-chip default)."""
    from deepspeed_tpu.parallel import mesh as mesh_mod
    from deepspeed_tpu.telemetry.costs import ProgramCostModel

    assert ProgramCostModel().num_devices == 1  # no mesh installed
    mesh_mod.set_mesh(mesh_mod.initialize_mesh(data=4, model=2))
    try:
        assert ProgramCostModel().num_devices == 8
    finally:
        mesh_mod.reset_mesh()


def test_cost_model_explicit_peaks_stay_aggregate():
    """Caller-supplied peaks are a MEASURED system aggregate: the mesh
    multiplier must not double-scale them."""
    from deepspeed_tpu.parallel import mesh as mesh_mod
    from deepspeed_tpu.telemetry.costs import ProgramCostModel

    mesh_mod.set_mesh(mesh_mod.initialize_mesh(data=8))
    try:
        m = ProgramCostModel(peak_flops=123.0, peak_bytes_per_s=45.0)
        assert m.peak_flops == 123.0
        assert m.peak_bytes_per_s == 45.0
        assert m.num_devices == 8  # recorded for attribution regardless
    finally:
        mesh_mod.reset_mesh()
