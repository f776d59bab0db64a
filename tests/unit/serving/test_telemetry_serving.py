"""Serving telemetry integration: per-request timelines must be complete
for every lifecycle outcome (finished / rejected / length_cap / failed /
requeued), traced runs must export step-phase spans plus request flow
lanes, all monitor events must share the engine's step axis, and the
recompile watchdog must read zero across warmed churn."""

import json

import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.serving import RequestState, ServingEngine
from deepspeed_tpu.telemetry import default_tracer, RecompileAfterWarmupError, Tracer


class _FakeMonitor:
    enabled = True

    def __init__(self):
        self.events = []

    def write_events(self, events):
        self.events.extend(events)


def test_timeline_complete_for_finished_request(stack):
    _, _, engine = stack
    rng = np.random.default_rng(53)
    srv = ServingEngine(engine, num_slots=2, max_queue_depth=8)
    req = srv.submit(rng.integers(0, 64, size=6).astype(np.int32),
                     max_new_tokens=3)
    srv.run_until_drained(max_steps=50)
    assert req.state == RequestState.FINISHED
    names = [e["event"] for e in srv.timeline(req.request_id)]
    assert names == ["submitted", "admitted", "first_token", "finished"]
    last = srv.timeline(req.request_id)[-1]
    assert last["attrs"]["reason"] == "length"
    assert last["attrs"]["new_tokens"] == 3
    assert last["attrs"]["chunks"] == 0
    assert srv.timeline(999_999) is None  # unknown id


def test_timeline_rejected_request(stack):
    _, _, engine = stack
    rng = np.random.default_rng(59)
    srv = ServingEngine(engine, num_slots=1, max_queue_depth=1)
    srv.submit(rng.integers(0, 64, size=5).astype(np.int32),
               max_new_tokens=2)
    full = srv.submit(rng.integers(0, 64, size=5).astype(np.int32),
                      max_new_tokens=2)
    assert full.state == RequestState.REJECTED
    tl = srv.timeline(full.request_id)
    assert [e["event"] for e in tl] == ["submitted", "rejected"]
    assert tl[-1]["attrs"]["reason"] == "queue_full"
    srv.run_until_drained(max_steps=20)


def test_timeline_length_cap(stack):
    _, _, engine = stack
    rng = np.random.default_rng(61)
    srv = ServingEngine(engine, num_slots=1, max_queue_depth=4,
                        prefill_chunk=16)
    srv.scheduler.capacity = None  # reach the engine-side safety net
    req = srv.submit(rng.integers(1, 64, size=60).astype(np.int32),
                     max_new_tokens=10)
    srv.run_until_drained(max_steps=100)
    assert req.finish_reason == "length_cap"
    names = srv.timelines.events_of(req.request_id)
    assert names[0] == "submitted" and names[-1] == "finished"
    assert "prefill_chunk" in names
    last = srv.timeline(req.request_id)[-1]
    assert last["attrs"]["reason"] == "length_cap"
    assert last["attrs"]["chunks"] == req.chunks > 0


def test_timeline_failed_request(stack):
    _, _, engine = stack
    rng = np.random.default_rng(67)
    srv = ServingEngine(engine, num_slots=2, max_queue_depth=8)
    r1 = srv.submit(rng.integers(0, 64, size=6).astype(np.int32),
                    max_new_tokens=6)
    srv.step()
    assert r1.state == RequestState.RUNNING

    orig = engine._jit_decode
    engine._jit_decode = lambda *a, **k: (_ for _ in ()).throw(
        RuntimeError("injected decode failure"))
    try:
        with pytest.raises(RuntimeError, match="injected"):
            srv.step()
    finally:
        engine._jit_decode = orig

    tl1 = srv.timeline(r1.request_id)
    assert tl1[-1]["event"] == "failed"
    assert tl1[-1]["attrs"]["reason"] == "error"


def test_timeline_requeued_after_admit_error(stack):
    _, _, engine = stack
    rng = np.random.default_rng(101)
    srv = ServingEngine(engine, num_slots=2, max_queue_depth=8)
    req = srv.submit(rng.integers(0, 64, size=6).astype(np.int32),
                     max_new_tokens=3)

    orig = engine._jit_prefill_at
    engine._jit_prefill_at = lambda *a, **k: (_ for _ in ()).throw(
        RuntimeError("injected prefill failure"))
    try:
        with pytest.raises(RuntimeError, match="injected"):
            srv.step()
    finally:
        engine._jit_prefill_at = orig

    tl = srv.timeline(req.request_id)
    assert tl[-1]["event"] == "requeued"
    assert tl[-1]["attrs"]["reason"] == "admit_error"
    srv.run_until_drained(max_steps=50)
    assert srv.timelines.events_of(req.request_id)[-1] == "finished"


def test_traced_run_exports_step_spans_and_request_lanes(stack, tmp_path):
    _, _, engine = stack
    rng = np.random.default_rng(71)
    srv = ServingEngine(engine, num_slots=2, max_queue_depth=8,
                        tracer=Tracer())
    for n, b in ((5, 3), (9, 4), (6, 2)):
        srv.submit(rng.integers(0, 64, size=n).astype(np.int32),
                   max_new_tokens=b)
    srv.run_until_drained(max_steps=50)

    path = tmp_path / "serving.json"
    srv.tracer.export(str(path))
    evs = json.loads(path.read_text())["traceEvents"]

    spans = {e["name"] for e in evs if e["ph"] == "X"}
    assert {"serving/step", "serving/grant", "serving/decode",
            "serving/sample"} <= spans
    assert "serving/admit" in spans or "serving/prefill_batch" in spans
    # per-request async lanes with begin/end pairs
    reqs = [e for e in evs if e.get("cat") == "request"]
    begins = {e["id"] for e in reqs if e["ph"] == "b"}
    ends = {e["id"] for e in reqs if e["ph"] == "e"}
    assert len(begins) == 3 and begins == ends
    # flow arrows from admission into retirement
    assert {e["ph"] for e in evs if e.get("cat") == "flow"} == {"s", "f"}
    # occupancy counter track samples
    assert any(e["ph"] == "C" and e["name"] == "serving/occupancy"
               for e in evs)
    # step spans carry the engine step id
    steps = [e["args"]["step"] for e in evs
             if e["ph"] == "X" and e["name"] == "serving/step"]
    assert steps == sorted(steps) and steps[0] >= 1


@pytest.mark.parametrize("kernel", ["on", "off"])
def test_dispatch_spans_count_the_pages_they_write(stack, kernel):
    """``pool_writes`` on the decode, chunk and admission dispatch spans:
    the pages of the call's write list for one leaf of one layer, as the
    host's table mirror knows them. A decode step writes one page for
    each slot that maps one at its index; a 16-token chunk over pages of
    8 writes two or, from an unaligned start, three; an admission writes
    the pages its prompt fills."""
    _, _, engine = stack
    rng = np.random.default_rng(72)
    srv = ServingEngine(engine, num_slots=2, max_queue_depth=8,
                        tracer=Tracer(), prefill_chunk=16,
                        paged_kv={"page_size": 8, "kernel": kernel,
                                  "prefix_cache": False})
    srv.submit(rng.integers(0, 64, size=5).astype(np.int32),
               max_new_tokens=4)
    srv.submit(rng.integers(0, 64, size=40).astype(np.int32),
               max_new_tokens=3)
    srv.run_until_drained(max_steps=50)
    srv.check_invariants()
    events = [e for e in srv.tracer.events() if e["ph"] == "X"]

    def writes(name):
        return [(e["args"], e["args"]["pool_writes"]) for e in events
                if e["name"] == name]

    decodes = writes("serving/decode")
    assert decodes and all(0 <= n <= 2 for _, n in decodes)
    assert any(n == args["live"] for args, n in decodes)
    chunks = writes("serving/prefill_chunk")
    assert [args["pos"] for args, _ in chunks] == [0, 16, 32]
    assert [n for _, n in chunks] == [2, 2, 1]     # 40 tokens: five pages
    admits = writes("serving/admit") + writes("serving/prefill_batch")
    assert [n for _, n in admits] == [1]           # 5 tokens: one page


def test_set_tracer_enables_post_hoc_tracing(stack):
    _, _, engine = stack
    rng = np.random.default_rng(73)
    srv = ServingEngine(engine, num_slots=1, max_queue_depth=4)
    srv.submit(rng.integers(0, 64, size=5).astype(np.int32),
               max_new_tokens=2)
    srv.run_until_drained(max_steps=20)
    # given no tracer the server records into the process-wide one, on
    assert srv.tracer is default_tracer() and srv.tracer.enabled
    assert any(e["name"] == "serving/step" for e in srv.tracer.events())

    tr = Tracer()
    srv.set_tracer(tr)
    assert srv.timelines.tracer is tr and srv.watchdog.tracer is tr
    srv.submit(rng.integers(0, 64, size=5).astype(np.int32),
               max_new_tokens=2)
    srv.run_until_drained(max_steps=20)
    assert any(e["name"] == "serving/step" for e in tr.events())


def test_monitor_events_share_engine_step_axis(stack):
    _, _, engine = stack
    rng = np.random.default_rng(79)
    mon = _FakeMonitor()
    srv = ServingEngine(engine, num_slots=2, max_queue_depth=8, monitor=mon)
    for _ in range(3):
        srv.submit(rng.integers(0, 64, size=6).astype(np.int32),
                   max_new_tokens=4)
    srv.run_until_drained(max_steps=50)
    assert mon.events
    for tag, _, step in mon.events:
        assert isinstance(step, int)
        assert 0 <= step <= srv.step_id, tag
    # finish events land on the step that retired them, not a token count
    finish_steps = [s for t, _, s in mon.events
                    if t == "serving/new_tokens"]
    assert len(finish_steps) == 3
    assert max(finish_steps) <= srv.step_id


def test_publish_telemetry_routes_registry_snapshot(stack):
    _, _, engine = stack
    rng = np.random.default_rng(83)
    mon = _FakeMonitor()
    srv = ServingEngine(engine, num_slots=1, max_queue_depth=4, monitor=mon)
    srv.submit(rng.integers(0, 64, size=5).astype(np.int32),
               max_new_tokens=2)
    srv.run_until_drained(max_steps=20)
    before = len(mon.events)
    n = srv.publish_telemetry()
    assert n > 0 and len(mon.events) == before + n
    tele = [t for t, _, s in mon.events[before:]]
    assert all(t.startswith("telemetry/") for t in tele)
    assert "telemetry/serving/finished" in tele
    assert all(s == srv.step_id for _, _, s in mon.events[before:])
    # registry mirrored the counters the monitor saw
    assert srv.registry.counter("serving/finished").value == 1


def test_watchdog_zero_after_warmup_and_strict_raise(stack):
    _, _, engine = stack
    rng = np.random.default_rng(89)
    srv = ServingEngine(engine, num_slots=2, max_queue_depth=16,
                        strict_recompile=True)
    for _ in range(3):  # warm both admission buckets
        srv.submit(rng.integers(0, 64, size=6).astype(np.int32),
                   max_new_tokens=3)
    srv.run_until_drained(max_steps=50)
    srv.end_warmup()
    assert srv.watchdog.warmed

    for _ in range(5):  # churn through reused slots: no recompiles
        srv.submit(rng.integers(0, 64, size=6).astype(np.int32),
                   max_new_tokens=4)
    srv.run_until_drained(max_steps=100)
    assert srv.watchdog.recompiles == 0

    # force a fresh program: strict mode aborts at the step boundary
    srv.submit(rng.integers(0, 64, size=33).astype(np.int32),
               max_new_tokens=2)  # new prefill bucket (width 64)
    with pytest.raises(RecompileAfterWarmupError):
        srv.run_until_drained(max_steps=20)
    assert srv.watchdog.recompiles > 0
    assert srv.watchdog.summary()["recompiles"] == srv.watchdog.recompiles


def test_tracer_overhead_is_bounded(stack):
    """Tracing 50 steps of a drained server must not blow up step cost —
    a loose 2x smoke bound (the ring's real cost is a chip number: PERF.md
    §6, PR 23)."""
    import time

    _, _, engine = stack
    rng = np.random.default_rng(97)

    def run(tracer):
        srv = ServingEngine(engine, num_slots=2, max_queue_depth=64,
                            tracer=tracer)
        for _ in range(8):
            srv.submit(rng.integers(0, 64, size=6).astype(np.int32),
                       max_new_tokens=8)
        t0 = time.perf_counter()
        srv.run_until_drained(max_steps=200)
        return time.perf_counter() - t0

    run(None)                      # warm compile caches
    base = min(run(None), run(None))
    traced = min(run(Tracer()), run(Tracer()))
    assert traced < base * 2 + 0.05


def test_warmup_manifest_records_then_freezes(stack):
    """The watchdog's signature manifest collects every watched call's
    manifest signature during warmup, freezes at end_warmup, and
    renders in the exact grammar graftcheck's static enumeration
    emits (pinned byte-for-byte in tests/unit/analysis)."""
    _, _, engine = stack
    rng = np.random.default_rng(23)
    srv = ServingEngine(engine, num_slots=2, max_queue_depth=16)
    srv.submit(rng.integers(0, 64, size=6).astype(np.int32),
               max_new_tokens=3)
    srv.run_until_drained(max_steps=50)
    man = srv.watchdog.signature_manifest()
    assert any(k.startswith("InferenceEngine.") for k in man)
    flat = [s for sigs in man.values() for s in sigs]
    assert flat and all(s.startswith("(") and s.endswith(")")
                        for s in flat)
    # the 6-token prompt pads to the minimum 16-wide prefill bucket
    assert any("int32[1,16]" in s
               for s in man.get("InferenceEngine._jit_prefill_at", []))

    srv.end_warmup()
    srv.submit(rng.integers(0, 64, size=6).astype(np.int32),
               max_new_tokens=3)  # same bucket: no recompile, no record
    srv.run_until_drained(max_steps=50)
    assert srv.watchdog.signature_manifest() == man  # frozen


def test_export_signatures_merges_by_union(stack, tmp_path):
    # watchdog proxies are shared per ENGINE (attach is idempotent), so
    # a merged union of distinct warmup sets needs two engines — exactly
    # the shape of several servers exporting into one signatures.json
    model, params, engine = stack
    rng = np.random.default_rng(29)
    path = str(tmp_path / "signatures.json")

    def serve(eng, n_tok):
        srv = ServingEngine(eng, num_slots=2, max_queue_depth=16)
        srv.submit(rng.integers(0, 64, size=n_tok).astype(np.int32),
                   max_new_tokens=2)
        srv.run_until_drained(max_steps=50)
        srv.end_warmup()
        return srv

    doc1 = serve(engine, 6).export_signatures(path)
    assert doc1["version"] == 1 and len(doc1["configs"]) == 1
    engine2 = ds.init_inference(model=model, model_parameters=params,
                                config={"dtype": "float32"})
    doc2 = serve(engine2, 20).export_signatures(
        path, merge=True, extra={"max_prompt_len": 20})
    # identical env dicts dedupe; the extra key makes this one distinct
    assert len(doc2["configs"]) == 2
    pre = doc2["programs"]["InferenceEngine._jit_prefill_at"]
    assert any("int32[1,16]" in s for s in pre)   # first engine's bucket
    assert any("int32[1,32]" in s for s in pre)   # second engine's bucket
    on_disk = json.loads(open(path).read())
    assert on_disk == doc2
