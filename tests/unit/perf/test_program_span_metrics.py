"""The per-layer metrics that read the program's own spans (PR 23), each on
a hand-written event list: the window is placed by laying the program's
step starts over the harness's, and a reader that cannot place it returns
None. No JAX work: the readers see whatever ``program_events`` hands them."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import program_spans  # noqa: E402
from perf.manifest import Manifest  # noqa: E402

NEW = ["step_sync_wait_ms_p50.chat", "step_sync_wait_ms_p50.docs",
       "step_host_serial_ms_p50.chat", "step_host_serial_ms_p50.docs",
       "chunk_steps_share.chat", "chunk_steps_share.docs",
       "prefill_wait_p50_ms.chat", "prefill_wait_p50_ms.docs",
       "train_host_serial_ms_p50", "setup_import_s", "setup_build_s",
       "setup_compile_s"]
T_OPEN = 1000.0           # the harness's t_open on perf_counter, seconds


def X(name, t0_s, dur_s, **args):
    return {"name": name, "ph": "X", "ts": int(round(t0_s * 1e9)),
            "dur": int(round(dur_s * 1e9)), "tid": 1, "args": args or None,
            "profiled": False}


def N(name, rid, t_s):
    return {"name": name, "ph": "n", "cat": "request", "id": rid,
            "ts": int(round(t_s * 1e9)), "tid": 1, "args": None,
            "profiled": False}


def serving_step(t0, length, step, chunk=None, admit=None, sync=0.150,
                 host_before=0.002):
    """One program step: 20 us after the harness's mark, boundary + grant
    (``host_before`` in all), decode, an optional chunk, the sync, 1 ms of
    replay and 1 ms of after-step."""
    t = t0 + 20e-6
    args = {"step": step, "tokens": 3, "decode": 3}
    evs = [X("serving/boundary", t, host_before / 2),
           X("serving/grant", t + host_before / 2, host_before / 2)]
    t += host_before
    evs.append(X("serving/decode", t, 0.004, live=3))
    t += 0.004
    if chunk:
        args["chunk"] = chunk
        evs.append(X("serving/prefill_chunk", t, 0.003, len=chunk))
        t += 0.003
    if admit:
        args["admit"], args["admit_tokens"] = admit, 16 * admit
        evs.append(X("serving/prefill_batch", t, 0.003, n=admit))
        t += 0.003
    evs.append(X("serving/sync", t, sync, arrays=2))
    t += sync
    evs += [X("serving/replay", t, 0.001), X("serving/after_step",
                                             t + 0.001, 0.001)]
    evs.append(X("serving/step", t0 + 20e-6, length - 40e-6, **args))
    return evs


def serving_case():
    """Warm-up steps, then a window of six steps, then a tail: steps of
    uneven length so only one run of starts fits the harness's."""
    events, bench = [], []
    events.append(X("setup/import", 900.0, 1.5))
    events.append(X("setup/build", 902.0, 10.0, entry="init_serving"))
    events.append(X("setup/init_serving", 906.0, 5.0))
    events.append(X("setup/compile", 903.0, 2.0, program="a", cache="miss"))
    events.append(X("setup/compile", 911.5, 1.0, program="b", cache="hit"))
    events.append(X("setup/compile", 920.0, 3.0, program="c", cache="miss"))
    t, step = 990.0, 1
    plan = [(0.20, None, None)] * 5 + [       # before the window
        (0.21, None, None), (0.24, 64, None), (0.20, None, None),
        (0.30, None, 2), (0.22, 64, None), (0.205, None, None),
    ] + [(0.20, None, None)] * 3              # the tail
    for i, (length, chunk, admit) in enumerate(plan):
        if i == 5:
            t = T_OPEN + 0.010
        events += serving_step(t, length, step, chunk, admit,
                               sync=length - 0.020)
        if 5 <= i < 11:
            bench.append((t - T_OPEN, t + length - T_OPEN))
        t += length + 0.003          # the caller's 3 ms between steps
        step += 1
    # requests: admitted -> first token; 1 and 2 in the window, 3 before
    events += [N("submitted", 1, T_OPEN - 1.0), N("admitted", 1, T_OPEN - 0.5),
               N("first_token", 1, T_OPEN + 0.3),
               N("admitted", 2, T_OPEN + 0.2), N("first_token", 2,
                                                 T_OPEN + 0.6),
               N("admitted", 3, T_OPEN - 3.0), N("first_token", 3,
                                                 T_OPEN - 2.0),
               N("admitted", 4, T_OPEN + 0.9)]
    record = {"spans": {"bench/step": bench},
              "facts": {"seconds": 1.5}, "samples": {}, "counters": {}}
    return events, record


@pytest.fixture
def manifest():
    return Manifest(ROOT)


def read(manifest, monkeypatch, metric, events, record):
    monkeypatch.setattr(program_spans, "program_events", lambda: events)
    return manifest.layer_reader(metric)(record)


def test_window_is_placed_by_the_offset_of_the_step_starts():
    events, record = serving_case()
    window = program_spans.place_window(record, events)
    assert [s["args"]["step"] for s in window["steps"]] == [6, 7, 8, 9, 10, 11]
    assert window["open_s"] == pytest.approx(T_OPEN + 20e-6, abs=1e-6)
    assert window["close_s"] == pytest.approx(T_OPEN + 1.5, abs=1e-4)
    # the order the ring hands the events over does not matter
    assert program_spans.place_window(record, events[::-1])["steps"] == \
        window["steps"]


@pytest.mark.parametrize("jitter_ms", [0.05, 0.5, 1.5])
def test_window_of_nearly_even_steps_is_placed_where_it_fits_best(jitter_ms):
    """A document cell's steps are all ~221 ms long, alike to within the
    tolerance, so the neighbouring runs fit too; the true run fits to the
    microseconds between the harness's mark and the program's."""
    rng = np.random.default_rng(0)
    events, bench, t = [], [], T_OPEN - 70 * 0.224
    for i in range(70 + 124 + 20):
        length = 0.221 + rng.uniform(-jitter_ms, jitter_ms) * 1e-3
        mark = rng.uniform(15e-6, 40e-6)   # harness's mark -> program's
        events += serving_step(t + mark - 20e-6, length, i + 1, chunk=64,
                               sync=length - 0.020)
        if 70 <= i < 194:
            bench.append((t - T_OPEN, t + length - T_OPEN))
        t += length + 0.003
    record = {"spans": {"bench/step": bench}, "facts": {"seconds": 30.0}}
    window = program_spans.place_window(record, events)
    assert [s["args"]["step"] for s in window["steps"]] == \
        list(range(71, 195))


def test_window_cannot_be_placed(monkeypatch, manifest):
    events, record = serving_case()
    # no program spans at all (a parent from before PR 23)
    assert program_spans.place_window(record, []) is None
    # the harness's steps fit no run of the program's
    skewed = dict(record, spans={"bench/step": [
        (a * 1.5, b * 1.5) for a, b in record["spans"]["bench/step"]]})
    assert program_spans.place_window(skewed, events) is None
    # steps all of one length to the microsecond fit more than one run
    # alike: a tie, so None
    same = []
    for i in range(12):
        same += serving_step(990.0 + 0.2 * i, 0.2, i + 1)
    even = {"spans": {"bench/step": [(0.2 * i, 0.2 * i + 0.2)
                                     for i in range(4)]}, "facts": {}}
    assert program_spans.place_window(even, same) is None
    for metric in NEW[:8] + NEW[9:]:
        assert read(manifest, monkeypatch, metric, events, skewed) is None
        assert read(manifest, monkeypatch, metric, [], record) is None


def test_serving_readers_on_the_hand_written_window(monkeypatch, manifest):
    events, record = serving_case()
    lengths = [0.21, 0.24, 0.20, 0.30, 0.22, 0.205]
    # the sync of each step is its length less 20 ms
    assert read(manifest, monkeypatch, "step_sync_wait_ms_p50.chat", events,
                record) == pytest.approx(
        1e3 * (sorted(lengths)[2] + sorted(lengths)[3]) / 2 - 20.0, abs=1e-3)
    # end of sync -> next decode: replay 1 + after 1 + what is left of the
    # step + the caller's 3 ms + 20 us + boundary and grant 2 ms
    serial = read(manifest, monkeypatch, "step_host_serial_ms_p50.docs",
                  events, record)
    assert 7.0 < serial < 20.0
    by_hand = []
    for length, chunk, admit in [(0.21, 0, 0), (0.24, 1, 0), (0.20, 0, 0),
                                 (0.30, 0, 1), (0.22, 1, 0)]:
        used = 20e-6 + 0.002 + 0.004 + 0.003 * (chunk + admit) \
            + (length - 0.020)
        by_hand.append((length - used) + 0.003 + 20e-6 + 0.002)
    assert serial == pytest.approx(1e3 * sorted(by_hand)[2], abs=1e-3)
    # three of the six steps carried a chunk or an admission
    assert read(manifest, monkeypatch, "chunk_steps_share.chat", events,
                record) == pytest.approx(50.0)
    # requests 1 (800 ms) and 2 (400 ms); 3 fell before the window and 4
    # has no first token
    assert read(manifest, monkeypatch, "prefill_wait_p50_ms.chat", events,
                record) == pytest.approx(600.0, abs=1e-3)


def test_setup_readers(monkeypatch, manifest):
    events, record = serving_case()
    assert read(manifest, monkeypatch, "setup_import_s", events,
                record) == pytest.approx(1.5)
    # 10 s of build less the compiles inside it: 2 s whole, 0.5 of the 1 s
    assert read(manifest, monkeypatch, "setup_build_s", events,
                record) == pytest.approx(7.5)
    # every compile ended before the window opened
    assert read(manifest, monkeypatch, "setup_compile_s", events,
                record) == pytest.approx(6.0)
    late = events + [X("setup/compile", T_OPEN + 0.5, 0.25, program="late")]
    assert read(manifest, monkeypatch, "setup_compile_s", late,
                record) == pytest.approx(6.0)


def test_train_window_is_the_last_steps_of_the_process(monkeypatch,
                                                       manifest):
    events, t = [X("setup/compile", 10.0, 4.0, program="step")], 20.0
    for i in range(7):               # 3 warm-up steps, then 4 in the window
        events += [X("train/stack_batch", t, 0.001),
                   X("train/dispatch", t + 0.001, 0.002),
                   X("train/sync", t + 0.003, 0.95),
                   X("train/after_step", t + 0.953, 0.0005),
                   X("train/step", t, 0.954, step=i, micro_batches=8)]
        t += 0.954 + 0.002 + 0.001 * i       # the caller's batch grows
    record = {"spans": {"bench/train_batch": []}, "facts": {"steps": 4}}
    window = program_spans.place_window(record, events)
    assert [s["args"]["step"] for s in window["steps"]] == [3, 4, 5, 6]
    # after-step 0.5 + rest of the step 0.5 + caller (2 + i) + stack 1 ms
    assert read(manifest, monkeypatch, "train_host_serial_ms_p50", events,
                record) == pytest.approx(0.5 + 0.5 + 6.0 + 1.0, abs=1e-3)
    assert read(manifest, monkeypatch, "setup_compile_s", events,
                record) == pytest.approx(4.0)
    short = {"spans": {}, "facts": {"steps": 9}}
    assert program_spans.place_window(short, events) is None
    assert read(manifest, monkeypatch, "train_host_serial_ms_p50", events,
                short) is None


def test_the_twelve_entries_have_readers_and_fit_the_contract(manifest):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # found by name: a contiguous run, wherever later PRs have appended to
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW[0])
    tail = bench["per_layer"][first:first + 12]
    assert [m["name"] for m in tail] == NEW
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    for m in tail:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("program_span", "program_counter")
        assert callable(manifest.layer_reader(m["name"]))
        moved = end_to_end[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells)
    assert [m["name"] for m in tail if m["moves"] == "setup_s"] == NEW[-3:]
    assert all("workloads" not in m for m in tail[-3:])
    for cell in cells:
        mine = {m["name"] for m in manifest.metrics_for(cell, "per_layer")}
        assert set(NEW[-3:]) <= mine
        assert ("train_host_serial_ms_p50" in mine) == cell.startswith("train")
    # a layer name is one already in use, letter for letter
    old_layers = {m["layer"] for m in bench["per_layer"][:first]}
    assert {m["layer"] for m in tail} <= old_layers


def test_readers_find_no_tracer_on_a_program_without_one(monkeypatch):
    import deepspeed_tpu.telemetry as telemetry

    monkeypatch.delattr(telemetry, "default_tracer")
    assert program_spans.program_events() == []
