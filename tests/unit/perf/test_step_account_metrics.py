"""The five readers of the serving step's account (PR 34), each on a
hand-written event list: ``serving/enqueue`` spans laid inside the dispatch
spans of a window of steps. A ring without them (a parent commit) reads
nothing. No JAX work: the readers see whatever ``program_events`` hands
them."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import program_spans, step_account  # noqa: E402
from perf.manifest import Manifest  # noqa: E402

BASES = ["step_exposed_host_ms_p50", "step_enqueue_ms_p50",
         "step_prepare_ms_p50", "step_device_calls_mean",
         "step_idle_unnamed_ms"]
VARIANTS = {".gap": (["serve-brumby-14b-continue"], "gap_p90_ms"),
            ".tok": (["serve-pythia-1b4-docs", "serve-mellum2-12b-ide"],
                     "serve_tok_s"),
            # PR 45: chat is judged on its median gap and left the ``.gap``
            # lists for entries of its own, appended after every other
            ".chat": (["serve-pythia-1b4-chat"], "gap_p50_ms")}
NEW = [base + suffix for base in BASES for suffix in (".gap", ".tok")]
T_OPEN = 2000.0           # the harness's t_open on perf_counter, seconds
US = 1e-6


def X(name, t0_s, dur_s, profiled=False, **args):
    return {"name": name, "ph": "X", "ts": int(round(t0_s * 1e9)),
            "dur": int(round(dur_s * 1e9)), "tid": 1, "args": args or None,
            "profiled": profiled}


def enq(t, dur_us, program, kind="program"):
    return X("serving/enqueue", t, dur_us * US, program=program, kind=kind)


def step_events(t0, step, length, *, chunk=False, decode=True, admit=False,
                profiled=False, account=True, sync=True):
    """One program step, 20 us after the harness's mark: boundary and grant
    (100 us each), 50 us of pages, then the dispatches. Every duration is a
    round number of microseconds, so the expected sums are exact:

    * a chunk: 300 us span = 40 before, a 60 us put, an 80 us call, 120
      after;
    * an admission: 500 us span = 30 before, a 50 us put, a 120 us call, a
      sampling span of 200 (60 + 90 inside it) that lies INSIDE it, 100
      after;
    * a decode: 250 us span = 20 before, a 30 us eager op, a 40 us put, a
      100 us call, 60 after; then a sampling span of 150 = 10, a 50 us
      call, a 70 us put, 20."""
    t = t0 + 20 * US
    evs, calls = [], 0
    evs += [X("serving/boundary", t, 100 * US),
            X("serving/grant", t + 100 * US, 100 * US),
            X("serving/pages", t + 200 * US, 50 * US, allocated=1, forked=0,
              preempted=0)]
    t += 250 * US
    if chunk:
        evs.append(X("serving/prefill_chunk", t, 300 * US, len=64))
        if account:
            evs += [enq(t + 40 * US, 60, "chunk", "transfer"),
                    enq(t + 100 * US, 80, "chunk")]
            calls += 2
        t += 300 * US
    if admit:
        evs.append(X("serving/admit", t, 500 * US, rid=step))
        evs.append(X("serving/sample", t + 200 * US, 200 * US))
        if account:
            evs += [enq(t + 30 * US, 50, "prefill_at", "transfer"),
                    enq(t + 80 * US, 120, "prefill_at"),
                    enq(t + 210 * US, 60, "sample"),
                    enq(t + 280 * US, 90, "cur_commit", "transfer")]
            calls += 4
        t += 500 * US
    if decode:
        evs.append(X("serving/decode", t, 250 * US, live=3))
        if account:
            evs += [enq(t + 20 * US, 30, "cur_tokens", "op"),
                    enq(t + 50 * US, 40, "decode", "transfer"),
                    enq(t + 90 * US, 100, "decode")]
            calls += 3
        t += 250 * US
        evs.append(X("serving/sample", t, 150 * US))
        if account:
            evs += [enq(t + 10 * US, 50, "sample"),
                    enq(t + 60 * US, 70, "cur_commit", "transfer")]
            calls += 2
        t += 150 * US
    end = t0 + length - 20 * US
    if sync:
        evs.append(X("serving/sync", t, end - t - 200 * US, arrays=2))
    evs += [X("serving/replay", end - 200 * US, 100 * US),
            X("serving/after_step", end - 100 * US, 100 * US)]
    args = {"step": step, "tokens": 3}
    if account:
        args["device_calls"] = calls
    evs.append(X("serving/step", t0 + 20 * US, length - 40 * US,
                 profiled=profiled, **args))
    return evs


# (length s, chunk, decode, admit, sync) of the window's steps; the caller
# takes 300 us between two steps
PLAN = [(0.0100, False, True, False, True),
        (0.0140, True, True, False, True),
        (0.0080, True, False, False, False),     # only queues a chunk
        (0.0120, False, True, False, True),      # after a step with no sync
        (0.0160, False, True, True, True),       # an admission first
        (0.0110, False, True, False, True),
        (0.0105, False, True, False, True),
        (0.0095, False, True, False, True)]
CALLER = 300 * US


def case(account=True, profiled=()):
    """Five warm-up steps, the window of PLAN, three steps of tail."""
    events, bench, step = [], [], 1
    t = T_OPEN - 5 * 0.011
    for _ in range(5):
        events += step_events(t, step, 0.0107, account=account)
        t, step = t + 0.011, step + 1
    t = T_OPEN + 0.002
    for i, (length, chunk, decode, admit, sync) in enumerate(PLAN):
        events += step_events(t, step, length, chunk=chunk, decode=decode,
                              admit=admit, sync=sync, account=account,
                              profiled=i in profiled)
        bench.append((t - T_OPEN, t + length - T_OPEN))
        t, step = t + length + CALLER, step + 1
    for _ in range(3):
        events += step_events(t, step, 0.0107, account=account)
        t, step = t + 0.011, step + 1
    record = {"spans": {"bench/step": bench}, "facts": {"seconds": 0.2},
              "samples": {}, "counters": {}}
    return events, record


# what each step of PLAN holds, in ms, from the durations above
DECODE_ENQ = (30 + 40 + 100 + 50 + 70) / 1e3          # 0.29
DECODE_PREP = (250 - 170 + 150 - 120) / 1e3           # 0.11
CHUNK_ENQ, CHUNK_PREP = (60 + 80) / 1e3, (300 - 140) / 1e3
ADMIT_ENQ = (50 + 120 + 60 + 90) / 1e3
ADMIT_PREP = (500 - 320) / 1e3            # the sampling inside it once
ENQUEUE = [DECODE_ENQ, CHUNK_ENQ + DECODE_ENQ, CHUNK_ENQ, DECODE_ENQ,
           ADMIT_ENQ + DECODE_ENQ, DECODE_ENQ, DECODE_ENQ, DECODE_ENQ]
PREPARE = [DECODE_PREP, CHUNK_PREP + DECODE_PREP, CHUNK_PREP, DECODE_PREP,
           ADMIT_PREP + DECODE_PREP, DECODE_PREP, DECODE_PREP, DECODE_PREP]
CALLS = [5, 7, 2, 5, 9, 5, 5, 5]
# end of the sync (200 us before the step's last 20) to the end of the
# first PROGRAM enqueue of the next step: replay + after-step (200), 20 +
# the caller + 20, boundary + grant + pages (250), then into the dispatch
AFTER = (200 + 20 + 300 + 20 + 250) / 1e3
EXPOSED = [None,                   # the window's first step
           AFTER + 0.180,          # a chunk's call ends 180 us in
           AFTER + 0.180,
           None,                   # the step before ended in no sync
           AFTER + 0.200,          # the admission's prefill
           AFTER + 0.190,          # a decode's: the eager op ends nothing
           AFTER + 0.190, AFTER + 0.190]


@pytest.fixture
def manifest():
    return Manifest(ROOT)


def read(manifest, monkeypatch, metric, events, record):
    monkeypatch.setattr(program_spans, "program_events", lambda: events)
    return manifest.layer_reader(metric)(record)


def test_rows_of_the_window():
    events, record = case(profiled=(1, 2, 3, 4, 5))
    rows = step_account.window_rows(record, events)
    assert [r["step"]["args"]["step"] for r in rows] == list(range(6, 14))
    assert [r["enqueue_ms"] for r in rows] == pytest.approx(ENQUEUE)
    assert [r["prepare_ms"] for r in rows] == pytest.approx(PREPARE)
    assert [r["device_calls"] for r in rows] == CALLS
    for got, want in zip((r["exposed_ms"] for r in rows), EXPOSED):
        assert got is None if want is None \
            else got == pytest.approx(want, abs=1e-6)
    assert [r["profiled"] for r in rows] == \
        [False, True, True, True, True, True, False, False]
    # the order the ring hands the events over does not matter
    again = step_account.window_rows(record, events[::-1])
    assert [r["exposed_ms"] for r in again] == \
        [r["exposed_ms"] for r in rows]


@pytest.mark.parametrize("suffix", sorted(VARIANTS))
def test_exposed_host_is_the_median_of_the_steps_that_have_one(
        manifest, monkeypatch, suffix):
    events, record = case()
    got = read(manifest, monkeypatch, "step_exposed_host_ms_p50" + suffix,
               events, record)
    assert got == pytest.approx(AFTER + 0.190, abs=1e-6)
    # the old metric stops at the START of the first dispatch span
    old = read(manifest, monkeypatch, "step_host_serial_ms_p50.chat",
               events, record)
    assert old == pytest.approx(AFTER, abs=1e-6) and old < got


@pytest.mark.parametrize("suffix", sorted(VARIANTS))
def test_enqueue_is_the_median_of_the_steps_sums(manifest, monkeypatch,
                                                  suffix):
    events, record = case()
    assert read(manifest, monkeypatch, "step_enqueue_ms_p50" + suffix,
                events, record) == pytest.approx(DECODE_ENQ)


@pytest.mark.parametrize("suffix", sorted(VARIANTS))
def test_prepare_leaves_out_the_enqueues_and_counts_a_nested_sample_once(
        manifest, monkeypatch, suffix):
    events, record = case()
    assert read(manifest, monkeypatch, "step_prepare_ms_p50" + suffix,
                events, record) == pytest.approx(DECODE_PREP)
    rows = step_account.window_rows(record, events)
    assert rows[4]["prepare_ms"] == pytest.approx(ADMIT_PREP + DECODE_PREP)


@pytest.mark.parametrize("suffix", sorted(VARIANTS))
def test_device_calls_is_the_mean_of_the_steps_own_count(
        manifest, monkeypatch, suffix):
    events, record = case()
    assert read(manifest, monkeypatch, "step_device_calls_mean" + suffix,
                events, record) == pytest.approx(sum(CALLS) / len(CALLS))


@pytest.mark.parametrize("suffix", sorted(VARIANTS))
def test_idle_unnamed_drops_the_edges_of_the_traced_stretch(
        manifest, monkeypatch, suffix):
    """Steps 1-5 of the window are traced: the interior is 2, 3, 4, of
    which step 3 has no exposed time (counts 0); the device window holds
    four gaps."""
    events, record = case(profiled=(1, 2, 3, 4, 5))
    record["trace"] = {"window_s": 0.060, "busy_s": 0.050}
    interior = (AFTER + 0.180 + 0.0 + AFTER + 0.200) / 3
    got = read(manifest, monkeypatch, "step_idle_unnamed_ms" + suffix,
               events, record)
    assert got == pytest.approx(10.0 / 4 - interior, abs=1e-6)
    # not clipped: a device that idled less than the host explains
    record["trace"] = {"window_s": 0.052, "busy_s": 0.050}
    got = read(manifest, monkeypatch, "step_idle_unnamed_ms" + suffix,
               events, record)
    assert got == pytest.approx(2.0 / 4 - interior, abs=1e-6) and got < 0


@pytest.mark.parametrize("trace,profiled", [
    (None, (1, 2, 3, 4, 5)),                               # untraced
    ({"window_s": 0.060, "busy_s": 0.050}, ()),            # no step flagged
    ({"window_s": 0.060, "busy_s": 0.050}, (2, 3, 4)),     # no interior
])
def test_idle_unnamed_reads_nothing_without_a_traced_stretch(
        manifest, monkeypatch, trace, profiled):
    events, record = case(profiled=profiled)
    if trace is not None:
        record["trace"] = trace
    assert read(manifest, monkeypatch, "step_idle_unnamed_ms.gap", events,
                record) is None
    # the other four do not need the trace
    assert read(manifest, monkeypatch, "step_enqueue_ms_p50.gap", events,
                record) is not None


@pytest.mark.parametrize("metric", NEW)
def test_a_ring_without_enqueue_spans_reads_nothing(manifest, monkeypatch,
                                                    metric):
    """A parent commit: the same steps, no ``serving/enqueue``, no
    ``device_calls``. The window is placed (the old readers read it) and
    each new reader returns None."""
    events, record = case(account=False, profiled=(1, 2, 3, 4, 5))
    record["trace"] = {"window_s": 0.060, "busy_s": 0.050}
    assert program_spans.place_window(record, events) is not None
    assert read(manifest, monkeypatch, "step_sync_wait_ms_p50.chat", events,
                record) is not None
    assert read(manifest, monkeypatch, metric, events, record) is None


@pytest.mark.parametrize("metric", NEW)
def test_no_window_no_reading(manifest, monkeypatch, metric):
    events, record = case()
    record["trace"] = {"window_s": 0.060, "busy_s": 0.050}
    assert read(manifest, monkeypatch, metric, [], record) is None
    del record["spans"]["bench/step"][3:]     # two steps place no window
    del record["spans"]["bench/step"][:1]
    assert read(manifest, monkeypatch, metric, events, record) is None


@pytest.mark.parametrize("metric", NEW)
def test_the_ten_entries_fit_the_contract(manifest, metric):
    entry, = [m for m in manifest.data["per_layer"] if m["name"] == metric]
    base, suffix = metric.rsplit(".", 1)
    first, moves = VARIANTS["." + suffix]
    cells = entry["workloads"]
    # the cells ISSUE 34 entered it for, and whichever later PRs appended
    assert cells[:len(first)] == first and entry["moves"] == moves
    assert len(set(cells)) == len(cells)
    assert entry["layer"] == "server step" and entry["better"] == "lower"
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["source"] == {
        "step_device_calls_mean": "program_counter",
        "step_idle_unnamed_ms": "device_trace"}.get(base, "program_span")
    assert entry["unit"] == ("count" if base == "step_device_calls_mean"
                             else "ms")
    # every cell it lists reports the end-to-end metric it moves
    moved, = [m for m in manifest.data["end_to_end"] if m["name"] == moves]
    assert set(cells) <= set(moved["workloads"])
    assert callable(manifest.layer_reader(metric))
    # found by name: one contiguous run in the issue's order, wherever
    # later PRs have appended to
    names = [m["name"] for m in manifest.data["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + 10] == NEW
    for cell in cells:
        assert metric in [m["name"] for m in
                          manifest.metrics_for(cell, "per_layer")]


@pytest.mark.parametrize("base", BASES)
def test_chats_twin_is_the_same_reader_entered_for_another_judge(manifest,
                                                                 base):
    """PR 45: chat is judged on its median gap, so it left each ``.gap``
    list for a ``.chat`` entry that moves ``gap_p50_ms``."""
    by_name = {m["name"]: m for m in manifest.data["per_layer"]}
    twin, old = by_name[base + ".chat"], by_name[base + ".gap"]
    assert (twin["workloads"], twin["moves"]) == VARIANTS[".chat"]
    assert {k: twin[k] for k in ("unit", "better", "source", "layer")} == \
        {k: old[k] for k in ("unit", "better", "source", "layer")}
    mine = [m["name"] for m in manifest.metrics_for(*twin["workloads"],
                                                    "per_layer")]
    assert base + ".chat" in mine and base + ".gap" not in mine
