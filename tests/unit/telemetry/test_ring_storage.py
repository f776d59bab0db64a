"""The ring's storage (PR 52): one flat record an event, no lock on the
record path, and the dicts built when they are READ. What every reader
under ``perf/`` takes from ``events()``, and Perfetto from ``to_chrome()``,
is key for key what the ring handed out when it stored dicts; an event's
``args`` is the recorder's own object; the flight recorder's tail is read
without the rest of the ring; the count and the records survive threads."""

import sys
import threading
import time

import pytest

from deepspeed_tpu.telemetry import FlightRecorder, Tracer, merge_chrome
from deepspeed_tpu.telemetry import tracer as tracer_mod


def _span(name, **args):
    def record(tr):
        with tr.span(name, **args):
            pass
    return record


def _boom(tr):
    with pytest.raises(KeyError):
        with tr.span("work/boom", k=1):
            raise KeyError("x")


# kind -> (what records ONE event, the dict the parent's ring held for it
# less ts / dur / tid, its keys in the parent's order)
KINDS = {
    "X": (_span("work/x", a=1),
          {"name": "work/x", "ph": "X", "args": {"a": 1}, "profiled": False},
          ["name", "ph", "ts", "dur", "tid", "args", "profiled"]),
    "X-bare": (_span("work/bare"),
               {"name": "work/bare", "ph": "X", "args": None,
                "profiled": False},
               ["name", "ph", "ts", "dur", "tid", "args", "profiled"]),
    "X-error": (_boom,
                {"name": "work/boom", "ph": "X",
                 "args": {"k": 1, "error": "KeyError"}, "profiled": False},
                ["name", "ph", "ts", "dur", "tid", "args", "profiled"]),
    "X-complete": (lambda tr: tr.complete("work/late", 7, 5, why="z"),
                   {"name": "work/late", "ph": "X", "ts": 7, "dur": 5,
                    "args": {"why": "z"}, "profiled": False},
                   ["name", "ph", "ts", "dur", "tid", "args", "profiled"]),
    "setup": (lambda tr: tr.complete("setup/import", 10, 20),
              {"name": "setup/import", "ph": "X", "ts": 10, "dur": 20,
               "args": None, "profiled": False},
              ["name", "ph", "ts", "dur", "tid", "args", "profiled"]),
    "i": (lambda tr: tr.instant("mark", why="y"),
          {"name": "mark", "ph": "i", "s": "t", "args": {"why": "y"},
           "profiled": False},
          ["name", "ph", "ts", "tid", "s", "args", "profiled"]),
    "i-bare": (lambda tr: tr.instant("mark"),
               {"name": "mark", "ph": "i", "s": "t", "args": None,
                "profiled": False},
               ["name", "ph", "ts", "tid", "s", "args", "profiled"]),
    "C": (lambda tr: tr.counter("level", live=3, pending=0),
          {"name": "level", "ph": "C", "args": {"live": 3, "pending": 0},
           "profiled": False},
          ["name", "ph", "ts", "tid", "args", "profiled"]),
    "b": (lambda tr: tr.async_begin("request", "req-7", 7, event="submitted"),
          {"name": "req-7", "ph": "b", "cat": "request", "id": 7,
           "args": {"event": "submitted"}, "profiled": False},
          ["name", "ph", "cat", "id", "ts", "tid", "args", "profiled"]),
    "n": (lambda tr: tr.async_instant("request", "first_token", 7),
          {"name": "first_token", "ph": "n", "cat": "request", "id": 7,
           "args": None, "profiled": False},
          ["name", "ph", "cat", "id", "ts", "tid", "args", "profiled"]),
    "e": (lambda tr: tr.async_end("request", "req-7", 7, tokens=4),
          {"name": "req-7", "ph": "e", "cat": "request", "id": 7,
           "args": {"tokens": 4}, "profiled": False},
          ["name", "ph", "cat", "id", "ts", "tid", "args", "profiled"]),
    "s": (lambda tr: tr.flow("s", "req", 7),
          {"name": "req", "ph": "s", "cat": "flow", "id": 7,
           "profiled": False},
          ["name", "ph", "cat", "id", "ts", "tid", "profiled"]),
    "f": (lambda tr: tr.flow("f", "journey", "j-1", cat="journey"),
          {"name": "journey", "ph": "f", "cat": "journey", "id": "j-1",
           "bp": "e", "profiled": False},
          ["name", "ph", "cat", "id", "ts", "tid", "bp", "profiled"]),
}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_an_event_is_read_as_the_dict_the_ring_used_to_hold(kind):
    record, want, keys = KINDS[kind]
    tr = Tracer()
    before = time.perf_counter_ns()
    record(tr)
    after = time.perf_counter_ns()
    (ev,) = tr.events()
    assert list(ev) == keys
    assert ev["tid"] == threading.get_ident()
    if "ts" not in want:
        assert before <= ev["ts"] <= after
        if "dur" in ev:
            assert 0 <= ev["dur"] <= after - ev["ts"]
    assert ev == dict({k: ev[k] for k in ("ts", "dur", "tid") if k in ev},
                      **want)
    # every read builds the same dict; the flight recorder's tail too
    assert tr.events() == [ev] and tr.tail(1) == [ev]


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_an_event_is_exported_as_the_object_perfetto_was_given(kind):
    record, want, _ = KINDS[kind]
    tr = Tracer()
    tr.instant("first")             # the export's time base
    record(tr)
    evs = tr.events()
    ev = next(e for e in evs if e["name"] == want["name"])
    base = min(e["ts"] for e in evs)
    out = {"name": ev["name"], "ph": ev["ph"], "pid": 0, "tid": 0,
           "ts": (ev["ts"] - base) / 1e3}
    if "dur" in ev:
        out["dur"] = ev["dur"] / 1e3
    out.update({k: ev[k] for k in ("cat", "id", "s", "bp") if k in ev})
    if ev.get("args"):
        out["args"] = ev["args"]
    for doc in (tr.to_chrome(), merge_chrome([("only", tr)])):
        found = [e for e in doc["traceEvents"]
                 if e["name"] == want["name"] and e["ph"] != "M"]
        assert found == [out] and list(found[0]) == list(out)
        assert doc["otherData"]["dropped"] == 0


def test_an_attribute_written_after_the_close_is_in_the_export():
    """``args`` is held BY REFERENCE: the routed FFN's counters are written
    onto a ``serving/step``'s attributes one step after the span closed."""
    tr = Tracer()
    with tr.span("serving/step", step=3) as sp:
        pass
    attrs = sp.args
    attrs.update(moe_assignments=12)
    (ev,) = tr.events()
    assert ev["args"] is attrs
    assert ev["args"] == {"step": 3, "moe_assignments": 12}
    chrome = [e for e in tr.to_chrome()["traceEvents"] if e["ph"] == "X"]
    assert chrome[0]["args"]["moe_assignments"] == 12


@pytest.mark.parametrize("capacity,recorded,n", [
    (8, 3, 2), (8, 3, 3), (8, 3, 5), (8, 3, 64), (8, 20, 4), (8, 20, 8),
    (8, 20, 64), (8, 0, 2), (8, 20, 0)])
def test_the_tail_is_the_end_of_events_without_reading_the_rest(
        capacity, recorded, n):
    tr = Tracer(capacity=capacity)
    tr.complete("setup/import", 1, 2)
    tr.complete("setup/build", 3, 4)
    for i in range(recorded):
        tr.instant(f"ev-{i}")
    assert tr.tail(n) == (tr.events()[-n:] if n else [])
    assert tr.dropped == max(0, recorded - capacity)
    assert tr.events_total == recorded


def test_the_flight_recorder_takes_the_tail_alone(monkeypatch):
    tr = Tracer()
    for i in range(200):
        tr.instant(f"ev-{i}")
    monkeypatch.setattr(Tracer, "events", lambda self: pytest.fail(
        "the flight recorder materialised the whole ring"))
    spans = FlightRecorder(last_spans=64).snapshot(tracer=tr)["last_spans"]
    assert [e["name"] for e in spans] == [f"ev-{i}" for i in range(136, 200)]
    assert FlightRecorder().snapshot(
        tracer=Tracer(enabled=False))["last_spans"] == []


def test_wrap_around_keeps_the_newest_and_counts_the_rest():
    tr = Tracer(capacity=16)
    for i in range(100):
        with tr.span("work", i=i):
            pass
    evs = tr.events()
    assert [e["args"]["i"] for e in evs] == list(range(84, 100))
    assert (tr.events_total, tr.dropped) == (100, 84)
    assert tr.to_chrome()["otherData"] == {
        "epoch_unix": tr.epoch_unix, "events_total": 100, "dropped": 84}
    tr.clear()
    assert (tr.events(), tr.events_total, tr.dropped) == ([], 0, 0)


def test_the_default_ring_holds_a_window_of_fast_plain_steps():
    """11 events a plain decode step (``test_engine_spans.py`` pins that):
    a 30 s window of 2.7 ms steps is 11,000 of them."""
    assert Tracer().capacity // 11 >= 11_000


def test_threads_record_without_a_lock_and_lose_nothing():
    """More threads than cores, a short switch interval, a reader that
    snapshots meanwhile: every event is counted once, every record is
    whole, and each thread's events are in its own order."""
    tr = Tracer(capacity=1 << 16)
    n_threads, n_events = 16, 400
    errors, stop = [], threading.Event()

    def worker(k):
        try:
            for i in range(n_events):
                with tr.span("w", k=k, i=i):
                    pass
                tr.async_instant("request", "tick", k, i=i)
        except Exception as e:          # pragma: no cover
            errors.append(e)

    def reader():
        try:
            while not stop.is_set():
                for ev in tr.events():
                    assert ev["ph"] in ("X", "n") and ev["tid"]
                tr.tail(64)
        except Exception as e:          # pragma: no cover
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(n_threads)]
        watch = threading.Thread(target=reader)
        watch.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        stop.set()
        watch.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not watch.is_alive()
    assert not any(t.is_alive() for t in threads)
    total = n_threads * n_events * 2
    assert tr.events_total == total and tr.dropped == 0
    evs = tr.events()
    assert len(evs) == total
    for k in range(n_threads):
        mine = [e["args"]["i"] for e in evs
                if e["ph"] == "X" and e["args"]["k"] == k]
        assert mine == list(range(n_events))
    # one tid object a thread, the thread's ident
    assert len({e["tid"] for e in evs}) <= n_threads


def test_a_step_span_asks_the_profiler_once_for_all_its_events(monkeypatch):
    """``serving/step`` is a ``_StepSpan``: the tracer marks the step's
    events with the answer taken at its opening; outside one it asks an
    event, as before."""
    asked = []
    monkeypatch.setattr(tracer_mod, "profiler_active",
                        lambda: asked.append(1) or True)
    tr = Tracer()
    with tracer_mod._StepSpan(tr, "serving/step", {"step": 1}):
        with tr.span("serving/grant"):
            pass
        tr.counter("serving/occupancy", live=1)
        tr.instant("serving/preempt")
        tr.flow("s", "req", 1)
    assert len(asked) == 1
    assert [e["profiled"] for e in tr.events()] == [True] * 5
    tr.instant("outside")
    tr.instant("outside")
    assert len(asked) == 3
    # a step that raises leaves the tracer asking again
    with pytest.raises(ValueError):
        with tracer_mod._StepSpan(tr, "serving/step", {"step": 2}):
            raise ValueError("x")
    assert tr._profiled is None
    assert tr.events()[-1]["args"] == {"step": 2, "error": "ValueError"}
    # with the ring off nothing is asked
    quiet = Tracer(enabled=False)
    with tracer_mod._StepSpan(quiet, "serving/step", None):
        pass
    assert len(asked) == 4 and quiet._profiled is None
