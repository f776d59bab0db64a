"""The one span primitive (PR 23): a span is an event in the ring on
``perf_counter_ns`` AND a ``TraceAnnotation`` a profiler session records; it
notes whether a session ran; an explicit ``Tracer(enabled=False)`` silences
the ring; ``setup/*`` events outlive the ring's wrap-around; the compile
listener leaves one ``setup/compile`` span per compile, named at the
``_WatchedJit`` seam."""

import glob
import time

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.telemetry import (RecompileWatchdog, Tracer,
                                     default_tracer)
from deepspeed_tpu.telemetry.tracer import profiler_active
from deepspeed_tpu.utils import nvtx


def test_span_is_a_ring_event_on_perf_counter_and_nests():
    tr = Tracer()
    before = time.perf_counter_ns()
    with tr.span("outer", k=1) as outer:
        with tr.span("inner") as inner:
            pass
    after = time.perf_counter_ns()
    evs = {e["name"]: e for e in tr.events()}
    assert [e["name"] for e in tr.events()] == ["inner", "outer"]
    o, i = evs["outer"], evs["inner"]
    assert before <= o["ts"] <= i["ts"] and \
        i["ts"] + i["dur"] <= o["ts"] + o["dur"] <= after
    assert o["args"] == {"k": 1} and o["ph"] == "X"
    # the span object keeps its own timing for the caller
    assert outer.t0_ns == o["ts"] and outer.dur_ns == o["dur"]
    assert inner.dur_ns <= outer.dur_ns


def test_profiled_flag_off_outside_a_session():
    tr = Tracer()
    assert not profiler_active()
    with tr.span("work"):
        pass
    tr.instant("tick")
    tr.counter("level", v=1)
    assert [e["profiled"] for e in tr.events()] == [False] * 3


def test_span_lies_in_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData

    tr = Tracer()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with tr.span("probe/outer"):
            with tr.span("probe/inner"):
                jax.block_until_ready(jnp.ones(8) + 1)
    finally:
        jax.profiler.stop_trace()
    with tr.span("probe/after"):
        pass
    flags = {e["name"]: e["profiled"] for e in tr.events()}
    assert flags == {"probe/inner": True, "probe/outer": True,
                     "probe/after": False}
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = [ev for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events
            if ev.name.startswith("probe/")]
    by_name = {ev.name: ev for ev in host}
    assert set(by_name) == {"probe/outer", "probe/inner"}
    o, i = by_name["probe/outer"], by_name["probe/inner"]
    assert o.start_ns <= i.start_ns and \
        i.start_ns + i.duration_ns <= o.start_ns + o.duration_ns


def test_explicit_disabled_tracer_records_nothing_but_still_times():
    tr = Tracer(enabled=False)
    with tr.span("quiet") as sp:
        time.sleep(0.001)
    tr.complete("setup/quiet", 0, 5)
    assert tr.events() == [] and tr.events_total == 0
    assert sp.dur_ns >= 1_000_000


def test_setup_events_outlive_the_ring():
    tr = Tracer(capacity=4)
    tr.complete("setup/import", 10, 20)
    with tr.span("setup/build", entry="x"):
        pass
    for i in range(50):
        tr.instant(f"ev-{i}")
    names = [e["name"] for e in tr.events()]
    assert names[:2] == ["setup/import", "setup/build"]
    assert names[2:] == [f"ev-{i}" for i in range(46, 50)]
    assert tr.dropped == 46         # the ring's own count, setup aside
    first = tr.events()[0]
    assert (first["ts"], first["dur"], first["ph"]) == (10, 20, "X")
    # the Chrome export carries them too
    assert "setup/import" in {e["name"] for e in
                              tr.to_chrome()["traceEvents"]}


def test_default_tracer_is_one_enabled_tracer_with_the_import_span():
    import deepspeed_tpu  # noqa: F401

    tr = default_tracer()
    assert tr is default_tracer() and tr.enabled
    imports = [e for e in tr.events() if e["name"] == "setup/import"]
    assert len(imports) == 1 and imports[0]["dur"] > 0


def test_nvtx_ranges_are_spans_of_the_default_tracer():
    @nvtx.instrument_w_nvtx
    def work(x):
        return x + 1

    n0 = default_tracer().events_total
    assert work(1) == 2
    with nvtx.trace_range("phase/x"):
        pass
    nvtx.range_push("phase/pushed")
    nvtx.range_pop()
    names = [e["name"] for e in default_tracer().events()][-3:]
    assert default_tracer().events_total == n0 + 3
    assert names[0].endswith("work") and names[1:] == ["phase/x",
                                                        "phase/pushed"]


class _Holder:
    pass


def test_compile_leaves_a_setup_span_named_at_the_watched_seam():
    def compiles(program):
        return [e for e in default_tracer().events()
                if e["name"] == "setup/compile"
                and e["args"]["program"] == program]

    # a bare jit: JAX's own name, no signature
    @jax.jit
    def pr23_bare(x):
        return x * 3 + 1

    pr23_bare(jnp.ones((5,)))
    (ev,) = compiles("jit(pr23_bare)")
    assert ev["args"]["signature"] is None
    assert ev["args"]["cache"] in ("hit", "miss")
    assert ev["args"]["backend_s"] > 0 and ev["args"]["trace_s"] > 0
    assert ev["dur"] >= int(ev["args"]["backend_s"] * 1e9)
    assert ev["ts"] + ev["dur"] <= time.perf_counter_ns()

    # through the watchdog's proxy: the program's name and signature
    holder = _Holder()
    holder.step = jax.jit(lambda x: x * 5 - 2)
    wd = RecompileWatchdog()
    wd.attach(holder, "step", name="Pr23.step")
    holder.step(jnp.ones((7,)))
    (ev,) = compiles("Pr23.step")
    assert ev["args"]["signature"] == "(float32[7])"
    assert ev["args"]["fun"].startswith("jit(")
    holder.step(jnp.ones((7,)))             # warm: no second span
    assert len(compiles("Pr23.step")) == 1
