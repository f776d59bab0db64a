"""The spans the two engines leave in the process-wide tracer by default
(PR 23): the phases of a serving step and of a train step, in order and
inside their step span; the request events mirrored into the ring; the
flight recorder's phase split; the set-up spans of both entry points.
Since PR 34 the step's account: a ``serving/enqueue`` around every device
call, ``serving/pages``, ``host/gc``, and what ``serving/step`` says of
them at its close, on both K/V pools."""

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models.lm_config import TransformerConfig
from deepspeed_tpu.models.transformer_lm import TransformerLM
from deepspeed_tpu.telemetry import Tracer, default_tracer
from tests.unit.kinds import TINY, kind_stack
from tests.unit.simple_model import SimpleModel, base_config, random_batch

SERVING_PHASES = ["serving/boundary", "serving/grant", "serving/sync",
                  "serving/replay", "serving/after_step"]
TRAIN_PHASES = ["train/stack_batch", "train/dispatch", "train/sync",
                "train/after_step"]


def _new_events(n0):
    """Events the default tracer took since its ``events_total`` was n0
    (the ring is process-wide: other tests wrote before us)."""
    tr = default_tracer()
    # (the count and the events are read with the collector paused: a full
    # collection between the two would leave its host/gc span in one only)
    paused = gc.isenabled()
    gc.disable()
    try:
        total, evs = tr.events_total, tr.events()
    finally:
        if paused:
            gc.enable()
    evs = [e for e in evs if not e["name"].startswith("setup/")]
    return evs[len(evs) - (total - n0):]


def _inside(step, ev):
    return step["ts"] <= ev["ts"] and \
        ev["ts"] + ev["dur"] <= step["ts"] + step["dur"]


@pytest.fixture(scope="module")
def server_parts():
    cfg = TransformerConfig(**TINY)
    model = TransformerLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 0, 64)
    params = model.init({"params": jax.random.PRNGKey(1)}, ids,
                        method=model.logits)["params"]
    return model, params


def _serve(server_parts, **kw):
    model, params = server_parts
    return ds.init_serving(model, model_parameters=params,
                           config={"dtype": "float32"}, num_slots=2,
                           max_queue_depth=8, **kw)


def test_serving_step_leaves_its_phases_in_order(server_parts):
    n_setup = len([e for e in default_tracer().events()
                   if e["name"] == "setup/init_serving"])
    srv = _serve(server_parts)
    assert srv.tracer is default_tracer()
    setup = [e for e in default_tracer().events()
             if e["name"].startswith("setup/")]
    assert len([e for e in setup if e["name"] == "setup/init_serving"]) \
        == n_setup + 1
    build = [e for e in setup if e["name"] == "setup/build"
             and e["args"]["entry"] == "init_serving"][-1]
    init = [e for e in setup if e["name"] == "setup/init_serving"][-1]
    assert _inside(build, init) and init["args"]["slots"] == 2

    rng = np.random.default_rng(5)
    n0 = default_tracer().events_total
    reqs = [srv.submit(rng.integers(0, 64, size=n).astype(np.int32),
                       max_new_tokens=5) for n in (6, 9)]
    srv.run_until_drained(max_steps=50)
    evs = _new_events(n0)
    steps = [e for e in evs if e["name"] == "serving/step"]
    assert [s["args"]["step"] for s in steps] == \
        list(range(1, srv.step_id + 1))
    assert len(steps) == 5 and \
        [s["args"]["in_flight"] for s in steps] == [0, 1, 1, 1, 1]
    settling = ["serving/sync", "serving/replay"]
    for step in steps:
        kids = sorted((e for e in evs if e["ph"] == "X"
                       and e is not step and _inside(step, e)),
                      key=lambda e: e["ts"])
        names = [k["name"] for k in kids]
        # every phase of the table, once, in order; but the first step has
        # no step before it to settle (the sync waits for THAT step's
        # bundle, and says so)
        first = step is steps[0]
        assert [n for n in names if n in SERVING_PHASES] == [
            n for n in SERVING_PHASES if not (first and n in settling)]
        # the dispatches lie between the grant and the sync; the last step
        # has nothing to queue, and settles
        dispatch = [n for n in names if n in (
            "serving/decode", "serving/admit", "serving/prefill_batch",
            "serving/prefill_chunk")]
        assert bool(dispatch) == (step is not steps[-1])
        if not first:
            sync = kids[names.index("serving/sync")]
            assert sync["args"]["step"] == step["args"]["step"] - 1
            lo, hi = names.index("serving/grant"), names.index(
                "serving/sync")
            assert all(lo < names.index(n) < hi for n in dispatch)
        # sync + the other phases fit inside the step, end to end
        top = [k for k in kids if k["name"] in SERVING_PHASES + dispatch]
        assert sum(k["dur"] for k in top) <= step["dur"]
        for a, b in zip(top, top[1:]):
            assert a["ts"] + a["dur"] <= b["ts"]
    # at its close the step says what it dispatched and emitted
    assert sum(s["args"]["tokens"] for s in steps) == 10
    assert sum(s["args"].get("admit", 0) for s in steps) == 2
    assert all(s["args"]["decode"] >= 1 for s in steps
               if "decode" in s["args"])
    assert any("decode" in s["args"] for s in steps)
    # request events are mirrored into the ring, one id per request
    for req in reqs:
        mine = [e["name"] for e in evs if e.get("cat") == "request"
                and e.get("id") == req.request_id and e["ph"] == "n"]
        assert mine == ["submitted", "admitted", "first_token", "finished"]


def test_flight_recorder_shows_where_the_step_went(server_parts):
    srv = _serve(server_parts)
    rng = np.random.default_rng(7)
    srv.submit(rng.integers(0, 64, size=6).astype(np.int32),
               max_new_tokens=4)
    srv.step()
    srv.step()
    srv.step()
    dump = srv.debug_dump()
    last = dump["steps"][-1]
    phases = last["phases_ms"]
    # measured, none subtracted: the third step of a server has them all
    # (the first settles nothing, so the second starts from no sync's end;
    # `pages` only where a pool has pages: the default one has none)
    assert set(phases) == {"boundary", "grant", "prepare", "enqueue",
                           "exposed", "sync", "replay"}
    assert "dispatch" not in srv._phase_ns
    assert all(v >= 0 for v in phases.values())
    # `exposed` lies across the others; they add up to less than the step
    assert sum(v for k, v in phases.items() if k != "exposed") \
        <= last["wall_ms"]
    assert last["dispatched"]["decode"] == 1
    assert last["dispatched"]["device_calls"] >= DECODE_CALLS
    # no longer empty by default: the tail of the process-wide ring
    assert any(e["name"] == "serving/step" for e in dump["last_spans"])
    assert dump["telemetry_overhead_s"] > 0.0


def test_cur_commit_is_a_dispatch_not_a_fetch(server_parts):
    """The resolver reads a shape: it gets the device array itself, so
    committing the sampled tokens never waits for them (the fetch that
    np.asarray made here was a hidden second sync of every step)."""
    srv = _serve(server_parts)
    if not callable(srv._pool_sharding):
        pytest.skip("no mesh: the commit is replicated, nothing resolves")
    seen, real = [], srv._pool_sharding
    srv._pool_sharding = lambda key, leaf: (seen.append(leaf),
                                            real(key, leaf))[1]
    tokens = jnp.zeros((2,), jnp.int32)
    out = srv._cur_commit(tokens)
    assert seen[0] is tokens and out.shape == (2,)
    srv._cur_commit(np.zeros((1,), np.int32))
    assert isinstance(seen[1], np.ndarray)


def test_explicit_disabled_tracer_silences_a_server(server_parts):
    quiet = Tracer(enabled=False)
    n0 = default_tracer().events_total
    srv = _serve(server_parts, tracer=quiet)
    rng = np.random.default_rng(9)
    srv.submit(rng.integers(0, 64, size=6).astype(np.int32),
               max_new_tokens=3)
    srv.run_until_drained(max_steps=50)
    assert quiet.events() == []
    # (a full collection is the process's, not the server's: host/gc)
    assert [e["name"] for e in _new_events(n0)
            if e["name"] != "host/gc"] == []
    # the after-step clock still runs: spans time themselves regardless
    assert srv.telemetry_overhead_s > 0.0
    assert srv.debug_dump()["steps"][-1]["wall_ms"] > 0


# -- the step's account (PR 34) ---------------------------------------------
POOLS = {"contiguous": False, "paged": {"kernel": "off"}}
DISPATCH = ("serving/admit", "serving/prefill_batch",
            "serving/prefill_chunk", "serving/decode")
# a plain decode step: the program, the sampler, the commit of its tokens
# (PR 35: the token twin goes in as it is, the positions are the cache's own
# index, the key is split in the sampler, the temperature is put once)
DECODE_CALLS = 3
# a step that only carries a chunk: its arguments as one vector, the one
# transfer, + the program (PR 35: which patches the table row it is handed,
# so the table is not republished for the fresh page the chunk mapped)
CHUNK_CALLS = {"contiguous": 2, "paged": 2}


def _kids(evs, parent, name=None):
    return sorted((e for e in evs if e["ph"] == "X" and e is not parent
                   and _inside(parent, e)
                   and (name is None or e["name"] == name)),
                  key=lambda e: e["ts"])


@pytest.fixture(scope="module", params=sorted(POOLS))
def account(request, server_parts):
    """One server a pool, driven through a chunked prompt, a batched and a
    single bucketed admission, plain decode steps, and one step with a
    forced full collection; every step overruns its (tiny) wall budget."""
    model, params = server_parts
    srv = ds.init_serving(model, model_parameters=params,
                          config={"dtype": "float32"}, num_slots=4,
                          max_queue_depth=8, prefill_chunk=8,
                          prefill_token_budget=64,
                          paged_kv=POOLS[request.param],
                          step_wall_budget_ms=1e-6)
    rng = np.random.default_rng(11)
    n0 = default_tracer().events_total
    levels, track = [], srv._track
    srv._track = lambda name, **values: (
        levels.append((srv.step_id, name, values)), track(name, **values))

    def prompt(n):
        return rng.integers(0, 64, size=n).astype(np.int32)

    srv.submit(prompt(20), max_new_tokens=12)      # three chunks of 8
    for _ in range(4):
        srv.step()
    for n in (5, 6):                               # one bucket: batched
        srv.submit(prompt(n), max_new_tokens=4)
    srv.step()
    srv.submit(prompt(7), max_new_tokens=4)        # alone: serving/admit
    srv.step()
    grant = srv.scheduler.grant

    def collecting_grant(*a, **kw):
        gc.collect()
        return grant(*a, **kw)

    srv.scheduler.grant = collecting_grant
    srv.step()
    srv.scheduler.grant = grant
    srv.run_until_drained(max_steps=60)
    evs = _new_events(n0)
    steps = [e for e in evs if e["name"] == "serving/step"]
    assert len(steps) == srv.step_id
    return {"pool": request.param, "srv": srv, "evs": evs, "steps": steps,
            "levels": levels}


def _steps_with(account, *names, without=()):
    out = []
    for step in account["steps"]:
        have = {k["name"] for k in _kids(account["evs"], step)}
        if all(n in have for n in names) \
                and not any(n in have for n in without):
            out.append(step)
    assert out, (names, without)
    return out


@pytest.mark.parametrize("dispatch", DISPATCH)
def test_every_dispatch_span_holds_its_enqueue(account, dispatch):
    evs = account["evs"]
    found = [e for e in evs if e["name"] == dispatch]
    assert found
    for sp in found:
        # the program itself is queued inside the span that names it; the
        # puts that feed it are counted and leave no span
        kids = _kids(evs, sp, "serving/enqueue")
        assert kids and all(k["args"]["program"] for k in kids)
        assert {k["args"]["kind"] for k in kids} == {"program"}


def test_device_calls_counts_every_call_and_the_programs_have_spans(account):
    evs = account["evs"]
    for step in account["steps"]:
        kids = _kids(evs, step, "serving/enqueue")
        # the enqueue children are the step's programs; its puts and eager
        # operations are in the count alone
        assert step["args"]["device_calls"] >= len(kids)
        # (a step with nothing to queue still settles the one before: a
        # paged pool republishes its table when that retires a request)
        assert bool(kids) == any(k in step["args"] for k in (
            "decode", "admit", "chunk"))
        assert step["args"].get("enqueue_ns", 0) == \
            sum(k["dur"] for k in kids)
        # a program is queued under a span that says what the host was
        # doing (pages, a dispatch, the sampling, the replay), or it is one
        # of the step's own: the finite check and the twin's update
        named = [p for name in DISPATCH + (
            "serving/pages", "serving/sample", "serving/replay",
            "serving/boundary") for p in _kids(evs, step, name)]
        own = {k["args"]["program"] for k in kids
               if not any(_inside(p, k) for p in named)}
        assert own <= {"finite", "cur_scatter"}


def test_device_calls_of_a_plain_decode_step_are_pinned(account):
    plain = _steps_with(account, "serving/decode", without=(
        "serving/admit", "serving/prefill_batch", "serving/prefill_chunk"))
    # (a replay that retires a request republishes a paged pool's table:
    # puts that leave no span, so the quiet steps are the cheapest ones)
    counts = [s["args"]["device_calls"] for s in plain]
    assert min(counts) == DECODE_CALLS
    assert counts.count(DECODE_CALLS) > len(counts) // 2
    quiet = [s for s in plain if s["args"]["device_calls"] == DECODE_CALLS]
    programs = [k["args"]["program"] for k in _kids(
        account["evs"], quiet[0], "serving/enqueue")]
    assert programs == ["decode", "sample"]


def test_device_calls_of_a_chunk_step_are_pinned(account):
    only = _steps_with(account, "serving/prefill_chunk",
                       without=("serving/decode", "serving/admit",
                                "serving/prefill_batch"))
    # the step that seats the request also resets its row: not that one
    later = [s for s in only if s["args"]["step"] > 1]
    assert later
    assert {s["args"]["device_calls"] for s in later} == \
        {CHUNK_CALLS[account["pool"]]}


def test_exposed_runs_from_the_last_sync_to_the_first_program(account):
    evs, steps = account["evs"], account["steps"]
    assert "exposed_ns" not in steps[0]["args"]
    checked = 0
    for before, step in zip(steps, steps[1:]):
        syncs = _kids(evs, before, "serving/sync")
        first = [k for k in _kids(evs, step, "serving/enqueue")
                 if k["args"]["kind"] == "program"]
        if not syncs or not first:
            # the device was never known idle: the step before ended in
            # no sync (it only queued a chunk), or this one queued nothing
            assert "exposed_ns" not in step["args"]
            continue
        assert step["args"]["exposed_ns"] == first[0]["ts"] \
            + first[0]["dur"] - (syncs[-1]["ts"] + syncs[-1]["dur"])
        assert step["args"]["exposed_ns"] > 0
        checked += 1
    assert checked >= 5


def test_prepare_is_the_dispatch_spans_outside_their_enqueues(account):
    evs = account["evs"]
    for step in account["steps"]:
        spans = [e for name in DISPATCH + ("serving/sample",)
                 for e in _kids(evs, step, name)]
        # (an admission's sampling lies inside its dispatch span)
        top = [e for e in spans if not any(
            o is not e and _inside(o, e) for o in spans)]
        want = sum(e["dur"] - sum(k["dur"] for k in _kids(
            evs, e, "serving/enqueue")) for e in top)
        assert step["args"].get("prepare_ns", 0) == want


def test_pages_span_says_what_the_pool_did(account):
    evs, paged = account["evs"], account["pool"] == "paged"
    pages = [e for e in evs if e["name"] == "serving/pages"]
    assert pages and all(set(e["args"]) == {"allocated", "forked",
                                            "preempted"} for e in pages)
    allocated = sum(e["args"]["allocated"] for e in pages)
    if paged:
        # every page the chunks and the decode steps wrote into (a
        # bucketed admission maps its own inside its dispatch span)
        assert 0 < allocated <= account["srv"].pool.pages_allocated
    else:
        assert allocated == 0
    for step in account["steps"]:
        mine = _kids(evs, step, "serving/pages")
        want = sum(e["dur"] - sum(k["dur"] for k in _kids(
            evs, e, "serving/enqueue")) for e in mine)
        assert step["args"].get("pages_ns", 0) == want


def test_self_time_of_a_step_is_what_no_span_holds(account):
    """The account's phases are disjoint: with the after-step and the
    step's self time (the step less its top-level spans: the puts that
    leave no span are in it) they add up to the step."""
    evs = account["evs"]
    over = [e for e in evs if e["name"] == "serving/step_overrun"]
    for inst, step in zip(over, account["steps"]):
        kids = _kids(evs, step)
        top = [e for e in kids if not any(
            o is not e and _inside(o, e) for o in kids)]
        self_ns = step["dur"] - sum(e["dur"] for e in top)
        assert self_ns >= 0
        after, = _kids(evs, step, "serving/after_step")
        collected = sum(g["dur"] for g in _kids(evs, step, "host/gc")
                        if not any(_inside(p, g) for p in top if p is not g))
        phases_ns = sum(v * 1e6 for k, v in inst["args"]["phases_ms"].items()
                        if k != "exposed")
        assert phases_ns + after["dur"] + collected + self_ns == \
            pytest.approx(step["dur"], abs=100)


def test_a_full_collection_inside_a_step_is_named(account):
    evs = account["evs"]
    held = [(s, _kids(evs, s, "host/gc")) for s in account["steps"]]
    held = [(s, g) for s, g in held if g]
    assert held
    for step, collections in held:
        assert all(g["args"]["generation"] == 2
                   and g["args"]["collected"] >= 0 for g in collections)
        assert step["args"]["gc_ns"] == sum(g["dur"] for g in collections)
    # the forced one fell inside serving/grant of its step
    assert any(_inside(grant, g) for s, gs in held for g in gs
               for grant in _kids(evs, s, "serving/grant"))
    assert all("gc_ns" not in s["args"] for s in account["steps"]
               if not _kids(evs, s, "host/gc"))


def test_an_overrun_names_the_phases_of_its_step(account):
    evs, srv = account["evs"], account["srv"]
    over = [e for e in evs if e["name"] == "serving/step_overrun"]
    assert len(over) == len(account["steps"])
    for inst, step in zip(over, account["steps"]):
        phases = inst["args"]["phases_ms"]
        assert "dispatch" not in phases
        assert {"boundary", "grant"} <= set(phases)
        assert inst["args"]["device_calls"] == step["args"]["device_calls"]
        if "enqueue_ns" in step["args"]:
            assert phases["enqueue"] * 1e6 == pytest.approx(
                step["args"]["enqueue_ns"])
    # the flight recorder's steps carry the same split
    rec = srv.debug_dump()["steps"][-1]
    assert "dispatch" not in rec["phases_ms"]
    assert rec["dispatched"]["device_calls"] == \
        account["steps"][-1]["args"]["device_calls"]


def test_a_dispatch_span_opens_where_it_did(account, server_parts):
    """The accepted ``step_host_serial_ms_p50`` ends where a step's first
    dispatch span opens: ``serving/decode`` opens after the running set is
    taken, as before the account, and holds the program's call and no
    other (since PR 35 nothing is read or put for it: the positions are
    the cache's own index); ``serving/sample`` holds the sampler."""
    srv = _serve(server_parts)
    read_at = []
    positions = srv.pool.positions
    srv.pool.positions = lambda: read_at.append(
        time.perf_counter_ns()) or positions()
    taken_at = []
    note_rows = srv._state_rows
    srv._state_rows = lambda running: taken_at.append(
        time.perf_counter_ns()) or note_rows(running)
    n0 = default_tracer().events_total
    rng = np.random.default_rng(13)
    srv.submit(rng.integers(0, 64, size=5).astype(np.int32),
               max_new_tokens=4)
    srv.run_until_drained(max_steps=20)
    new = _new_events(n0)
    decodes = [e for e in new if e["name"] == "serving/decode"]
    assert not read_at and len(taken_at) == len(decodes) > 0
    for t, decode in zip(taken_at, decodes):
        assert t < decode["ts"]
        assert [(k["args"]["program"], k["args"]["kind"]) for k in _kids(
            new, decode, "serving/enqueue")] == [("decode", "program")]
    evs = account["evs"]
    for step in _steps_with(account, "serving/decode"):
        sample = _kids(evs, step, "serving/sample")[-1]
        assert [k["args"]["program"] for k in _kids(
            evs, sample, "serving/enqueue")] == ["sample"]


@pytest.fixture(scope="module")
def hybrid_account():
    """A server of mamba and attention layers over the page pool (a state
    group beside paged K/V, PR 47), driven as ``account`` is: a chunked
    prompt, a batched and a single bucketed admission, plain decode steps."""
    # (two periods: two state layers and two of K/V)
    model, params, _ = kind_stack(
        "state_group", n_layer=4, layer_types=["mamba", "attention"] * 2)
    srv = ds.init_serving(model, model_parameters=params,
                          config={"dtype": "float32"}, num_slots=4,
                          max_queue_depth=8, prefill_chunk=8,
                          prefill_token_budget=64,
                          paged_kv={"kernel": "off", "prefix_cache": False})
    rng = np.random.default_rng(11)
    n0 = default_tracer().events_total

    def prompt(n):
        return rng.integers(0, 64, size=n).astype(np.int32)

    srv.submit(prompt(20), max_new_tokens=12)      # chunks of 8, 8 and 4
    for _ in range(4):
        srv.step()
    for n in (5, 6):                               # one bucket: batched
        srv.submit(prompt(n), max_new_tokens=4)
    srv.step()
    srv.submit(prompt(7), max_new_tokens=4)        # alone: serving/admit
    srv.step()
    srv.run_until_drained(max_steps=60)
    srv.check_invariants()
    evs = _new_events(n0)
    steps = [e for e in evs if e["name"] == "serving/step"]
    return {"pool": "paged", "srv": srv, "evs": evs, "steps": steps}


def test_a_state_group_beside_pages_counts_its_rows_and_tokens(
        hybrid_account):
    """``state_rows`` on every dispatch span, ``ssm_chunk_tokens`` (REAL
    tokens, from the host's positions) on the three prefill dispatches and
    both summed on the step with ``state_bytes``; the K/V group's
    ``pool_writes`` beside them; the resident gauge from the spec."""
    srv, evs = hybrid_account["srv"], hybrid_account["evs"]
    by = {name: [e for e in evs if e["name"] == name] for name in DISPATCH}
    assert all(by.values())
    assert [e["args"]["ssm_chunk_tokens"]
            for e in by["serving/prefill_chunk"]] == [8, 8, 4]
    assert [e["args"]["ssm_chunk_tokens"]
            for e in by["serving/prefill_batch"]] == [5 + 6]
    assert [e["args"]["ssm_chunk_tokens"]
            for e in by["serving/admit"]] == [7]
    assert [e["args"]["state_rows"] for e in by["serving/prefill_batch"]] \
        == [2]
    assert {e["args"]["state_rows"] for e in by["serving/prefill_chunk"]
            + by["serving/admit"]} == {1}
    for e in by["serving/decode"]:
        assert e["args"]["state_rows"] == e["args"]["live"]
        assert "ssm_chunk_tokens" not in e["args"]
        assert "pool_writes" in e["args"]       # the 4 attention layers'
    row_bytes = srv.pool.spec.state_bytes_per_row
    assert row_bytes == 2 * (4 * 8 * 8 * 4 + 3 * (4 * 8 + 16) * 4)
    told = 0
    for step in hybrid_account["steps"]:
        kids = [k for name in DISPATCH for k in _kids(evs, step, name)]
        rows = sum(k["args"]["state_rows"] for k in kids)
        tokens = sum(k["args"].get("ssm_chunk_tokens", 0) for k in kids)
        assert step["args"].get("state_rows", 0) == rows
        assert step["args"].get("ssm_chunk_tokens", 0) == tokens
        assert step["args"].get("state_bytes", 0) == 2 * row_bytes * rows
        told += tokens
    assert told == 20 + 5 + 6 + 7
    assert srv.registry.gauge("serving/state_bytes_resident").value \
        == 4 * row_bytes


def test_a_plain_decode_step_beside_a_state_group_makes_three_calls(
        hybrid_account):
    """As the retention state's on the contiguous pool: the decode program,
    the sampler, the commit; one more, the running rows, in a step whose
    running set changed. Seating a slot zeroes nothing (a row at position 0
    reads neither its state nor its tail)."""
    plain = _steps_with(hybrid_account, "serving/decode", without=(
        "serving/admit", "serving/prefill_batch", "serving/prefill_chunk"))
    counts = [s["args"]["device_calls"] for s in plain]
    # (four short requests: half the plain steps follow a retirement or a
    # change of the running set, which put the table or the rows again)
    assert min(counts) == DECODE_CALLS and counts.count(DECODE_CALLS) >= 3
    quiet = [s for s in plain if s["args"]["device_calls"] == DECODE_CALLS]
    assert [k["args"]["program"] for k in _kids(
        hybrid_account["evs"], quiet[0], "serving/enqueue")] \
        == ["decode", "sample"]
    only = _steps_with(hybrid_account, "serving/prefill_chunk",
                       without=("serving/decode", "serving/admit",
                                "serving/prefill_batch"))
    later = [s for s in only if s["args"]["step"] > 1]
    assert later and {s["args"]["device_calls"] for s in later} \
        == {CHUNK_CALLS["paged"]}


# -- a chunk beside running slots as ONE program (PR 48) ---------------------
# the chunk's vector (the one transfer), the program, the sampler, the commit
# of its tokens, the index put again from the mirror (the slot in mid-prefill
# rode the decode rows along): one call under the two programs' six
BESIDE_CALLS = 5
# a prompt's LAST chunk beside running slots keeps the two programs (the slot
# it finishes decodes in the same step from the token its head chose): the
# vector, the chunk, its sampler, its commit, the twin's scatter (a put and a
# program), the decode, its sampler, its commit
LAST_CHUNK_CALLS = 9


@pytest.fixture(scope="module")
def kimi_account():
    """A server of kda and latent attention layers with a routed FFN that
    holds 2 of its 8 experts (PR 50: a state group beside latent pages),
    driven as ``hybrid_account`` is."""
    model, params, _ = kind_stack("kda_latent")
    srv = ds.init_serving(model, model_parameters=params,
                          config={"dtype": "float32"}, num_slots=4,
                          max_queue_depth=8, prefill_chunk=8,
                          prefill_token_budget=64,
                          paged_kv={"kernel": "off", "prefix_cache": False})
    rng = np.random.default_rng(11)
    n0 = default_tracer().events_total
    srv.submit(rng.integers(0, 64, size=20).astype(np.int32),
               max_new_tokens=12)                  # chunks of 8, 8 and 4
    for _ in range(4):
        srv.step()
    srv.submit(rng.integers(0, 64, size=7).astype(np.int32),
               max_new_tokens=4)                   # alone: serving/admit
    srv.run_until_drained(max_steps=60)
    srv.check_invariants()
    evs = _new_events(n0)
    steps = [e for e in evs if e["name"] == "serving/step"]
    return {"pool": "paged", "srv": srv, "evs": evs, "steps": steps}


def test_a_kda_state_group_beside_latent_pages_counts_what_it_ran(
        kimi_account):
    """``state_rows`` on every dispatch span and ``kda_chunk_tokens`` (REAL
    tokens) on the prefill dispatches, as the mamba layers'
    ``ssm_chunk_tokens`` and never both; ``latent_tokens_read`` over the
    ONE latent layer's bytes; on ``serving/step`` the routed FFN's counts of
    the HELD experts (what the kernels ran) beside every assignment the
    router made, ``rows x k`` a routed layer a call. A plain decode step
    makes the three device calls it makes beside any state group."""
    srv, evs = kimi_account["srv"], kimi_account["evs"]
    chunks = [e for e in evs if e["name"] == "serving/prefill_chunk"]
    assert [e["args"]["kda_chunk_tokens"] for e in chunks] == [8, 8, 4]
    admits = [e for e in evs if e["name"] == "serving/admit"]
    assert [e["args"]["kda_chunk_tokens"] for e in admits] == [7]
    assert not any("ssm_chunk_tokens" in (e.get("args") or {}) for e in evs)
    decodes = [e for e in evs if e["name"] == "serving/decode"]
    for e in decodes:
        assert e["args"]["state_rows"] == e["args"]["live"]
        assert e["args"]["latent_tokens_read"] > 0
    spec = srv.pool.spec
    assert spec.state_bytes_per_row == 3 * (2 * 8 * 8 * 4 + 3 * 48 * 4)
    assert srv._latent_token_bytes == 1 * (16 + 8) * 4    # (one layer's)
    counted = [s["args"] for s in kimi_account["steps"]
               if s["args"].get("moe_layer_calls")]
    assert counted
    for args in counted:
        calls = args["moe_layer_calls"]
        assert calls % 3 == 0                   # three routed layers a call
        assert 0 <= args["moe_assignments"] <= args["moe_routed_assignments"]
        assert args["moe_experts_touched"] <= 2 * calls
        assert isinstance(args["moe_routed_assignments"], int)
    # a plain decode step of n rows: 3 layers x n x 2 assignments made
    plain = _steps_with(kimi_account, "serving/decode", without=(
        "serving/admit", "serving/prefill_batch", "serving/prefill_chunk"))
    for step in plain:
        if step["args"].get("moe_layer_calls") == 3:
            assert step["args"]["moe_routed_assignments"] == 3 * 4 * 2
    assert sum(a["moe_assignments"] for a in counted) \
        < sum(a["moe_routed_assignments"] for a in counted)
    assert srv.registry.counter("serving/moe_routed_assignments").value \
        == sum(a["moe_routed_assignments"] for a in counted)
    counts = [s["args"]["device_calls"] for s in plain]
    assert min(counts) == DECODE_CALLS


@pytest.fixture(scope="module")
def lfm2_account():
    """A server of gated short-convolution layers beside QK-normed rotary
    attention with a routed FFN behind two dense layers (PR 54: a state
    group of ONE leaf, the convolution's tail, beside paged K/V), driven as
    ``kimi_account`` is."""
    model, params, _ = kind_stack("conv_tail")
    srv = ds.init_serving(model, model_parameters=params,
                          config={"dtype": "float32"}, num_slots=4,
                          max_queue_depth=8, prefill_chunk=8,
                          prefill_token_budget=64,
                          paged_kv={"kernel": "off", "prefix_cache": False})
    rng = np.random.default_rng(11)
    n0 = default_tracer().events_total
    srv.submit(rng.integers(0, 64, size=20).astype(np.int32),
               max_new_tokens=12)                  # chunks of 8, 8 and 4
    for _ in range(4):
        srv.step()
    srv.submit(rng.integers(0, 64, size=7).astype(np.int32),
               max_new_tokens=4)                   # alone: serving/admit
    srv.run_until_drained(max_steps=60)
    srv.check_invariants()
    evs = _new_events(n0)
    steps = [e for e in evs if e["name"] == "serving/step"]
    return {"pool": "paged", "srv": srv, "evs": evs, "steps": steps}


def test_a_conv_tail_beside_pages_counts_what_it_ran(lfm2_account):
    """``state_rows`` on every dispatch span and ``conv_chunk_tokens`` (REAL
    tokens) on the prefill dispatches, as the mamba layers'
    ``ssm_chunk_tokens`` and the kda layers' ``kda_chunk_tokens`` and never
    two of them; the state's bytes are the tail's alone (two rows of the
    hidden width a conv layer); on ``serving/step`` the routed FFN's five
    counts as for Moonlight (every expert held: no ``routed_assignments``
    beside ``assignments``). A plain decode step makes the three device
    calls it makes beside any state group."""
    srv, evs = lfm2_account["srv"], lfm2_account["evs"]
    chunks = [e for e in evs if e["name"] == "serving/prefill_chunk"]
    assert [e["args"]["conv_chunk_tokens"] for e in chunks] == [8, 8, 4]
    assert all(e["args"]["state_rows"] == 1 for e in chunks)
    admits = [e for e in evs if e["name"] == "serving/admit"]
    assert [e["args"]["conv_chunk_tokens"] for e in admits] == [7]
    assert not any(key in (e.get("args") or {}) for e in evs
                   for key in ("ssm_chunk_tokens", "kda_chunk_tokens",
                               "latent_tokens_read"))
    decodes = [e for e in evs if e["name"] == "serving/decode"]
    assert decodes
    for e in decodes:
        assert e["args"]["state_rows"] == e["args"]["live"]
    spec = srv.pool.spec
    assert spec.state_leaves == ("conv",)
    assert spec.state_bytes_per_row == 3 * (2 * 32 * 4)    # three conv layers
    assert srv._latent_token_bytes == 0
    summed = [s["args"] for s in lfm2_account["steps"]
              if s["args"].get("state_rows")]
    assert summed and all(
        a["state_bytes"] == 2 * spec.state_bytes_per_row * a["state_rows"]
        for a in summed)
    counted = [s["args"] for s in lfm2_account["steps"]
               if s["args"].get("moe_layer_calls")]
    assert counted
    for args in counted:
        calls = args["moe_layer_calls"]
        assert calls % 2 == 0                   # two routed layers a call
        assert {"moe_assignments", "moe_experts_touched", "moe_load_max",
                "moe_load_max_over_mean", "moe_bias_reordered"} <= set(args)
        assert "moe_routed_assignments" not in args
        assert args["moe_experts_touched"] <= 8 * calls
    # a plain decode step of n rows: 2 layers x n x 2 assignments
    plain = _steps_with(lfm2_account, "serving/decode", without=(
        "serving/admit", "serving/prefill_batch", "serving/prefill_chunk"))
    for step in plain:
        if step["args"].get("moe_layer_calls") == 2:
            assert step["args"]["moe_assignments"] \
                == 2 * step["args"]["decode"] * 2
    counts = [s["args"]["device_calls"] for s in plain]
    assert min(counts) == DECODE_CALLS


@pytest.fixture(scope="module")
def qwen_account():
    """A server of Gated DeltaNet layers beside gated QK-normed rotary
    attention with a routed FFN that holds 2 of its 8 experts beside a
    gated shared expert (PR 60: KDA's state leaf under one decay a head),
    driven as ``kimi_account`` is."""
    model, params, _ = kind_stack("gdn_gated")
    srv = ds.init_serving(model, model_parameters=params,
                          config={"dtype": "float32"}, num_slots=4,
                          max_queue_depth=8, prefill_chunk=8,
                          prefill_token_budget=64,
                          paged_kv={"kernel": "off", "prefix_cache": False})
    rng = np.random.default_rng(11)
    n0 = default_tracer().events_total
    srv.submit(rng.integers(0, 64, size=20).astype(np.int32),
               max_new_tokens=12)                  # chunks of 8, 8 and 4
    for _ in range(4):
        srv.step()
    srv.submit(rng.integers(0, 64, size=7).astype(np.int32),
               max_new_tokens=4)                   # alone: serving/admit
    srv.run_until_drained(max_steps=60)
    srv.check_invariants()
    evs = _new_events(n0)
    steps = [e for e in evs if e["name"] == "serving/step"]
    return {"pool": "paged", "srv": srv, "evs": evs, "steps": steps}


def test_a_gdn_state_group_beside_pages_counts_what_it_ran(qwen_account):
    """``state_rows`` on every dispatch span and ``gdn_chunk_tokens`` (REAL
    tokens) on the prefill dispatches, as the kda layers'
    ``kda_chunk_tokens`` and never two of them; the state's bytes are a
    value head's matrices and the one convolution's tail; on
    ``serving/step`` the routed FFN's counts of the HELD experts (what the
    kernels ran) beside every assignment the router made, ``rows x k`` a
    layer a call, every one of the four layers routed."""
    srv, evs = qwen_account["srv"], qwen_account["evs"]
    chunks = [e for e in evs if e["name"] == "serving/prefill_chunk"]
    assert [e["args"]["gdn_chunk_tokens"] for e in chunks] == [8, 8, 4]
    assert all(e["args"]["state_rows"] == 1 for e in chunks)
    admits = [e for e in evs if e["name"] == "serving/admit"]
    assert [e["args"]["gdn_chunk_tokens"] for e in admits] == [7]
    assert not any(key in (e.get("args") or {}) for e in evs
                   for key in ("ssm_chunk_tokens", "kda_chunk_tokens",
                               "conv_chunk_tokens", "latent_tokens_read"))
    decodes = [e for e in evs if e["name"] == "serving/decode"]
    assert decodes
    for e in decodes:
        assert e["args"]["state_rows"] == e["args"]["live"]
    spec = srv.pool.spec
    assert spec.state_leaves == ("s", "conv")
    # three DeltaNet layers: 4 value heads of (8, 8) float32 and a tail of
    # 3 x (2 x 2 + 4) x 8 channels
    assert spec.state_bytes_per_row == 3 * (4 * 8 * 8 * 4 + 3 * 64 * 4)
    summed = [s["args"] for s in qwen_account["steps"]
              if s["args"].get("state_rows")]
    assert summed and all(
        a["state_bytes"] == 2 * spec.state_bytes_per_row * a["state_rows"]
        for a in summed)
    counted = [s["args"] for s in qwen_account["steps"]
               if s["args"].get("moe_layer_calls")]
    assert counted
    for args in counted:
        calls = args["moe_layer_calls"]
        assert calls % 4 == 0                   # four routed layers a call
        assert 0 <= args["moe_assignments"] <= args["moe_routed_assignments"]
        assert args["moe_experts_touched"] <= 2 * calls
        assert args["moe_bias_reordered"] == 0  # (a softmax router)
    plain = _steps_with(qwen_account, "serving/decode", without=(
        "serving/admit", "serving/prefill_batch", "serving/prefill_chunk"))
    for step in plain:
        if step["args"].get("moe_layer_calls") == 4:
            assert step["args"]["moe_routed_assignments"] == 4 * 4 * 2
    assert sum(a["moe_assignments"] for a in counted) \
        < sum(a["moe_routed_assignments"] for a in counted)
    counts = [s["args"]["device_calls"] for s in plain]
    assert min(counts) == DECODE_CALLS


@pytest.fixture(scope="module")
def sala_account():
    """A server of Lightning layers beside learned sparse attention in an
    irregular stack (PR 56), its chunks and decode rows reading the pages
    in place (the sparse read is the kernel path's), driven as
    ``lfm2_account`` is: a prompt of three chunks past the toy
    ``dense_len``, then one admitted whole."""
    model, params, _ = kind_stack("sparse_lightning")
    srv = ds.init_serving(model, model_parameters=params,
                          config={"dtype": "float32"}, num_slots=4,
                          max_queue_depth=8, prefill_chunk=8,
                          prefill_token_budget=64,
                          paged_kv={"kernel": "on", "page_size": 8,
                                    "prefix_cache": False})
    rng = np.random.default_rng(11)
    n0 = default_tracer().events_total
    srv.submit(rng.integers(0, 64, size=20).astype(np.int32),
               max_new_tokens=12)                  # chunks of 8, 8 and 4
    for _ in range(4):
        srv.step()
    srv.submit(rng.integers(0, 64, size=7).astype(np.int32),
               max_new_tokens=4)                   # alone: serving/admit
    srv.run_until_drained(max_steps=60)
    srv.check_invariants()
    evs = _new_events(n0)
    steps = [e for e in evs if e["name"] == "serving/step"]
    return {"pool": "paged", "srv": srv, "evs": evs, "steps": steps}


def test_sparse_attention_beside_a_lightning_state_counts_what_it_ran(
        sala_account):
    """``sparse_rows`` / ``sparse_tokens_read`` / ``sparse_index_rows`` on
    the chunk and decode spans (``sparse_pages_most`` / ``sparse_blocks_most``
    on the decode spans alone), from the host's positions by the EQUATIONS
    (a row under ``dense_len`` 16 reads its whole context, one past it its
    window of 8 and 2 blocks of 4; a compressed key a position once 2 have
    arrived), ``lightning_chunk_tokens`` (REAL tokens) on the prefill
    dispatches and ``state_rows`` everywhere; the step's span sums them."""
    srv, evs = sala_account["srv"], sala_account["evs"]
    sizes = srv.pool.spec.sparse
    assert sizes.dense_len == 16 and srv._sparse == sizes
    chunks = [e["args"] for e in evs if e["name"] == "serving/prefill_chunk"]
    assert [a["lightning_chunk_tokens"] for a in chunks] == [8, 8, 4]
    assert [a["sparse_rows"] for a in chunks] == [8, 8, 4]
    # positions 0-7, 8-15 (the last one past dense_len) and 16-19
    assert [a["sparse_tokens_read"] for a in chunks] == [
        sum(range(1, 9)), sum(range(9, 16)) + 16, 4 * 16]
    assert [a["sparse_index_rows"] for a in chunks] == [
        sum(range(0, 8)), sum(range(8, 16)), sum(range(16, 20))]
    admits = [e["args"] for e in evs if e["name"] == "serving/admit"]
    assert [a["lightning_chunk_tokens"] for a in admits] == [7]
    decodes = [e["args"] for e in evs if e["name"] == "serving/decode"]
    assert decodes
    for a in decodes:
        assert a["state_rows"] == a["sparse_rows"] == a["live"]
        assert a["sparse_tokens_read"] <= 16 * a["live"]
    # the long request's decode rows are past dense_len: 16 tokens a row
    assert any(a["sparse_tokens_read"] == 16 * a["live"] for a in decodes)
    # how the decode rows' read engages (PR 57): the pages their (row, KV
    # head)s can list and the blocks those make, a block each at this size
    for a in decodes:
        assert a["sparse_blocks_most"] == 2 * a["live"] \
            <= a["sparse_pages_most"] <= 2 * 4 * a["live"]
    assert not any("sparse_pages_most" in a for a in chunks + admits)
    assert not any(key in (e.get("args") or {}) for e in evs
                   for key in ("ssm_chunk_tokens", "kda_chunk_tokens",
                               "conv_chunk_tokens", "latent_tokens_read"))
    spec = srv.pool.spec
    assert spec.state_leaves == ("s",) and spec.kv_layers == 3
    assert set(srv.pool.cache["cache_store"]) \
        == {"s", "k", "v", "kc", "index", "table"}
    summed = [s["args"] for s in sala_account["steps"]
              if s["args"].get("sparse_rows")]
    assert summed and all(a["sparse_tokens_read"] >= a["sparse_rows"]
                          for a in summed)


@pytest.fixture(scope="module")
def beside_account(server_parts):
    """A server whose chunks read their pages in place (``kernel: "on"``),
    warmed the way the benchmark's harness warms one (a request a pass,
    drained, then ``end_warmup()``: no warm-up step carries a chunk beside a
    running slot), then given a prompt of three chunks beside a running
    request, twice."""
    model, params = server_parts
    srv = ds.init_serving(model, model_parameters=params,
                          config={"dtype": "float32"}, num_slots=4,
                          max_queue_depth=8, prefill_chunk=8,
                          prefill_token_budget=16, tracer=Tracer(),
                          paged_kv={"kernel": "on", "page_size": 8,
                                    "prefix_cache": False})
    rng = np.random.default_rng(23)

    def prompt(n):
        return rng.integers(0, 64, size=n).astype(np.int32)

    for n in (5, 20):
        srv.submit(prompt(n), max_new_tokens=3)
        srv.run_until_drained(max_steps=50)
    assert srv.registry.counter("serving/fused_steps").value == 0
    srv.end_warmup()
    warm = len(srv.tracer.events())
    for _ in range(2):
        # (two tokens: its write column stays inside its first page while
        # the chunks ride beside it, so no table is put for it)
        srv.submit(prompt(2), max_new_tokens=10)
        srv.step()
        srv.submit(prompt(20), max_new_tokens=3)      # chunks of 8, 8 and 4
        srv.run_until_drained(max_steps=60)
    srv.check_invariants()
    evs = srv.tracer.events()[warm:]
    steps = [e for e in evs if e["name"] == "serving/step"]
    return {"srv": srv, "evs": evs, "steps": steps}


def test_a_chunk_beside_running_slots_is_one_program(beside_account):
    """``fused`` on ``serving/step`` and what it stands for: the chunk's
    span and the decode's both there, the ONE enqueue under the decode's,
    one device call fewer than the two programs made."""
    evs, srv = beside_account["evs"], beside_account["srv"]
    both = _steps_with(beside_account, "serving/prefill_chunk",
                       "serving/decode")
    fused = [s for s in both if s["args"]["fused"]]
    last = [s for s in both if not s["args"]["fused"]]
    assert len(fused) == 4 and len(last) == 2
    assert srv.registry.counter("serving/fused_steps").value == 4
    assert all(s["args"]["fused"] == 0 for s in _steps_with(
        beside_account, "serving/decode", without=("serving/prefill_chunk",)))
    for step in fused:
        assert step["args"]["chunk"] == 8 and step["args"]["decode"] == 1
        chunk, = _kids(evs, step, "serving/prefill_chunk")
        decode, = _kids(evs, step, "serving/decode")
        assert not _kids(evs, chunk, "serving/enqueue")
        assert [k["args"]["program"] for k in _kids(
            evs, decode, "serving/enqueue")] == ["chunk_decode"]
        assert [k["args"]["program"] for k in _kids(
            evs, step, "serving/enqueue")] == ["chunk_decode", "sample"]
        # each span says what it said when the programs were two
        assert {"rid", "pos", "len", "pool_writes", "pool_reads",
                "read_slots"} <= set(chunk["args"])
        assert chunk["args"]["len"] == 8 and chunk["args"]["read_slots"] == 1
        assert {"live", "pool_writes", "pool_reads", "read_slots"} \
            <= set(decode["args"])
        assert decode["args"]["live"] == 1
        # the running slot and the one in mid-prefill both map pages
        assert decode["args"]["read_slots"] == 2
    # the step that seats the prompt also resets its row; the one after it
    # is the quiet one
    assert [s["args"]["device_calls"] for s in fused[1::2]] \
        == [BESIDE_CALLS] * 2
    assert all(s["args"]["device_calls"] > BESIDE_CALLS
               and s["args"]["table_puts"] for s in fused[0::2])
    for step in last:
        assert step["args"]["chunk"] == 4
        assert [k["args"]["program"] for k in _kids(
            evs, step, "serving/enqueue")] == [
                "chunk", "sample", "cur_scatter", "decode", "sample"]
        assert step["args"]["device_calls"] == LAST_CHUNK_CALLS


def test_the_one_program_is_compiled_before_the_warm_up_ends(beside_account):
    """The harness's warm-up never puts a chunk beside a running slot, so
    ``end_warmup`` brings the program in itself; mixed traffic after it
    compiles nothing, and the warm-up call left the pool as it was."""
    srv = beside_account["srv"]
    assert srv.watchdog.recompiles == 0
    manifest = srv.watchdog.signature_manifest()
    assert len(manifest["SlotPool._paged_chunk_decode_jit"]) == 1
    np.testing.assert_array_equal(
        np.asarray(srv.pool.cache["cache_store"]["table"]), srv.pool.table)


def test_counter_tracks_sample_every_step(account, server_parts):
    """Since PR 52 a track takes a sample where a level CHANGED (and at
    every 256th step besides): the staircase is the one a sample a step
    drew, so the level a track shows at any step is the server's."""
    evs, paged = account["evs"], account["pool"] == "paged"
    names = ("serving/occupancy",) + (("paging/pages",) if paged else ())
    assert {name for _, name, _ in account["levels"]} == set(names)
    for name in names:
        samples = [e for e in evs if e["ph"] == "C" and e["name"] == name]
        true = [(step, values) for step, n, values in account["levels"]
                if n == name]
        # the server offered the track its level once a step
        assert [step for step, _ in true] == \
            [s["args"]["step"] for s in account["steps"]]
        # a sample wherever the level differs from the one before it
        moved = [values for k, (_, values) in enumerate(true)
                 if k == 0 or values != true[k - 1][1]]
        assert [e["args"] for e in samples] == moved
        assert 1 < len(samples) < len(account["steps"])
        # and the newest sample at or before a step's own is its level
        at = 0
        for step, (_, values) in zip(account["steps"], true):
            while at + 1 < len(samples) and samples[at + 1]["ts"] \
                    <= step["ts"] + step["dur"]:
                at += 1
            assert samples[at]["args"] == values
    # the level a track shows is the server's: the last sample is the end
    occupancy = [e for e in evs if e["name"] == "serving/occupancy"]
    assert occupancy[-1]["args"]["pending"] == 0
    # an idle server's level never moves: one sample, then the 256th step's
    srv = _serve(server_parts, tracer=Tracer())
    for _ in range(3):
        srv.step()
    srv.step_id = 254
    srv.step()
    assert len(srv.tracer.events()) == 3 * 4 + 4 + 1
    srv.step()
    assert srv.step_id == 256
    samples = [e for e in srv.tracer.events() if e["ph"] == "C"]
    assert [e["args"] for e in samples] == [{"live": 0, "pending": 0}] * 2
    # another tracer's tracks start afresh
    srv.set_tracer(Tracer())
    srv.step()
    assert [e["name"] for e in srv.tracer.events() if e["ph"] == "C"] == \
        ["serving/occupancy"]


PLAIN_STEP_EVENTS = {"contiguous": 10, "paged": 11}     # (+ serving/pages)


def test_a_plain_decode_step_leaves_eleven_events(account):
    """``serving/step``, ``boundary``, ``grant``, ``pages`` on a paged pool,
    ``decode``, ``sample``, two ``enqueue``, ``sync``, ``replay``,
    ``after_step``: what the default ring is sized by (131,072 // 11 plain
    steps). A counter track adds a sample only where its level moved."""
    evs = account["evs"]
    plain = [s for s in _steps_with(account, "serving/decode", without=(
        "serving/admit", "serving/prefill_batch", "serving/prefill_chunk"))
        if s["args"]["device_calls"] == DECODE_CALLS]
    counts, sampled = [], []
    for step in plain:
        inside = [e for e in evs if step["ts"] <= e["ts"]
                  and e["ts"] + e.get("dur", 0) <= step["ts"] + step["dur"]]
        # (every step of this server overruns its tiny wall budget)
        inside = [e for e in inside if e["name"] != "serving/step_overrun"]
        if any(e["ph"] not in ("X", "C") for e in inside):
            continue                # a request came or went: its events
        if any(e["name"] == "host/gc" for e in inside):
            continue
        tracks = [e for e in inside if e["ph"] == "C"]
        counts.append(len(inside) - len(tracks))
        sampled.append(len(tracks))
    assert counts and set(counts) == {PLAIN_STEP_EVENTS[account["pool"]]}
    assert max(counts) <= 11 and sampled.count(0) > len(sampled) // 2
    assert Tracer().capacity // 11 >= 11_000


@pytest.fixture
def ready(monkeypatch):
    """A stand-in for the readiness of the bundle in flight."""
    from deepspeed_tpu.serving import engine as serving_engine

    answer = {"ready": True, "asked": 0}

    def stand_in(bundle):
        answer["asked"] += 1
        return answer["ready"]

    monkeypatch.setattr(serving_engine._Bundle, "ready", stand_in)
    return answer


@pytest.mark.parametrize("is_ready", [True, False])
def test_a_step_that_found_the_device_dry_says_so(server_parts, ready,
                                                  is_ready):
    """``dry``: the bundle in flight was READY before the step's first
    program was called, so the chip idles until that call lands. On
    ``serving/step`` beside ``in_flight``, counted in the registry, in the
    flight recorder's step and on ``serving/step_overrun``. One query a
    step that queues a program with a bundle in flight; no device call."""
    ready["ready"] = is_ready
    srv = _serve(server_parts, tracer=Tracer(), step_wall_budget_ms=1e-6)
    rng = np.random.default_rng(21)
    srv.submit(rng.integers(0, 64, size=6).astype(np.int32),
               max_new_tokens=5)
    srv.run_until_drained(max_steps=50)
    evs = srv.tracer.events()
    steps = [e for e in evs if e["name"] == "serving/step"]
    queued = [int(any(k in s["args"] for k in ("decode", "admit", "chunk")))
              for s in steps]
    ahead = [s["args"]["in_flight"] & q for s, q in zip(steps, queued)]
    assert sum(ahead) >= 3 and ready["asked"] == sum(ahead)
    want = [a if is_ready else 0 for a in ahead]
    assert [s["args"]["dry"] for s in steps] == want
    # the first step has nothing in flight: dry by construction, which
    # `in_flight` 0 says; the last queues nothing
    assert (steps[0]["args"]["in_flight"], want[0], want[-1]) == (0, 0, 0)
    snap = srv.registry.snapshot()
    assert snap.get("serving/steps_device_dry", 0) == sum(want)
    assert snap["serving/steps_run_ahead"] == sum(ahead)
    recorded = srv.debug_dump()["steps"]
    assert [r["dry"] for r in recorded] == want
    over = [e for e in evs if e["name"] == "serving/step_overrun"]
    assert [e["args"]["dry"] for e in over] == want
    # the query is no device call: a plain step's count is the pinned one
    assert min(s["args"]["device_calls"] for s in steps
               if "decode" in s["args"]) == DECODE_CALLS


def test_with_the_ring_off_a_dry_step_is_still_counted(server_parts, ready):
    srv = _serve(server_parts, tracer=Tracer(enabled=False))
    rng = np.random.default_rng(22)
    srv.submit(rng.integers(0, 64, size=6).astype(np.int32),
               max_new_tokens=5)
    srv.run_until_drained(max_steps=50)
    assert srv.tracer.events() == []
    snap = srv.registry.snapshot()
    assert snap["serving/steps_device_dry"] == ready["asked"] >= 3
    assert snap["serving/steps_device_dry"] == snap["serving/steps_run_ahead"]
    assert [r["dry"] for r in srv.debug_dump()["steps"]].count(1) == \
        ready["asked"]


def test_a_bundles_readiness_is_its_last_queued_arrays(account):
    """The real query: ``jax.Array.is_ready`` of the array the step queued
    last (a step's programs run in order), without a wait."""
    from deepspeed_tpu.serving.engine import _Bundle

    class _Arr:
        def __init__(self, done):
            self.done, self.asked = done, 0

        def is_ready(self):
            self.asked += 1
            return self.done

    first, last, stats = _Arr(True), _Arr(False), _Arr(True)
    bundle = _Bundle(1, [([first], None), ([first, last], None)], [stats], {})
    assert bundle.ready() is False
    assert (first.asked, last.asked, stats.asked) == (0, 1, 0)
    assert _Bundle(1, [], [stats], {}).ready() is True
    done = jax.block_until_ready(jnp.ones(4) + 1)
    assert _Bundle(1, [([done], None)], [], {}).ready() is True
    # a real server's steps ask it and stay whole
    steps, srv = account["steps"], account["srv"]
    assert all(s["args"]["dry"] in (0, 1) for s in steps)
    assert srv.registry.snapshot().get("serving/steps_device_dry", 0) == \
        sum(s["args"]["dry"] for s in steps)
    assert all(s["args"]["dry"] <= s["args"]["in_flight"] for s in steps)


def test_every_event_of_a_step_under_a_profiler_session_says_so(
        server_parts, monkeypatch):
    """The step asks once, at its opening, whether a session is recording;
    every event up to its close carries the answer (``perf/step_account.py``
    finds the traced stretch by the ``serving/step`` events' ``profiled``)."""
    from deepspeed_tpu.telemetry import tracer as tracer_mod

    srv = _serve(server_parts, tracer=Tracer())
    rng = np.random.default_rng(24)
    srv.submit(rng.integers(0, 64, size=6).astype(np.int32),
               max_new_tokens=4)
    srv.step()
    srv.step()
    before = len(srv.tracer.events())
    assert not any(e["profiled"] for e in srv.tracer.events())
    asked = []
    monkeypatch.setattr(tracer_mod, "profiler_active",
                        lambda: asked.append(1) or True)
    srv.step()
    srv.step()
    assert len(asked) == 2
    monkeypatch.undo()
    srv.run_until_drained(max_steps=50)
    evs = srv.tracer.events()
    steps = [e for e in evs if e["name"] == "serving/step"]
    traced = [s for s in steps if s["profiled"]]
    assert [s["args"]["step"] for s in traced] == [3, 4]
    for ev in evs[before:]:
        inside = any(_inside(s, dict(ev, dur=ev.get("dur", 0)))
                     for s in traced)
        assert ev["profiled"] == inside, ev


def test_with_the_ring_off_a_device_call_is_counted_and_nothing_else(
        server_parts):
    """``Tracer.enabled`` False: no ``serving/enqueue`` object, annotation
    or clock read a device call, so the cost of the marks can be measured
    on against off; the step still counts its calls."""
    from deepspeed_tpu.serving import engine as serving_engine

    srv = _serve(server_parts, tracer=Tracer(enabled=False))
    assert srv._enqueue("decode") is serving_engine.NO_SPAN
    rng = np.random.default_rng(12)
    srv.submit(rng.integers(0, 64, size=6).astype(np.int32),
               max_new_tokens=4)
    srv.run_until_drained(max_steps=50)
    assert srv.tracer.events() == []
    steps = srv.debug_dump()["steps"]
    assert any(s["dispatched"]["device_calls"] == DECODE_CALLS
               for s in steps)
    assert all("enqueue" not in s["phases_ms"]
               and "exposed" not in s["phases_ms"] for s in steps)


def test_a_pool_without_a_server_still_marks_its_calls(server_parts):
    """The pool's own default: a program's span alone, in the process-wide
    ring; a put leaves none."""
    from deepspeed_tpu.serving.slot_pool import SlotPool

    model, params = server_parts
    engine = ds.init_inference(model=model, model_parameters=params,
                               config={"dtype": "float32"})
    engine._ensure_params(jnp.zeros((1, 2), jnp.int32))
    pool = SlotPool(engine.kv_cache_spec(), 2)
    n0 = default_tracer().events_total
    pool.advance(np.array([1, 0], np.int32))       # the index: a put
    assert not [e for e in _new_events(n0)
                if e["name"] == "serving/enqueue"]
    with pool.enqueue("admit_row"):
        pass
    mine = [e for e in _new_events(n0) if e["name"] == "serving/enqueue"]
    assert [(e["args"]["program"], e["args"]["kind"]) for e in mine] == \
        [("admit_row", "program")]


def test_train_timers_start_without_a_device_round_trip():
    """One clock in the trainer: nothing between ``train/stack_batch`` and
    ``train/dispatch`` waits for the device (each timer's start used to
    drain every local device), and the timers still log a step's wall."""
    from deepspeed_tpu.utils.timer import TRAIN_BATCH_TIMER

    engine, _, _, _ = ds.initialize(
        model=SimpleModel(hidden_dim=16),
        config=dict(base_config(micro=2, gas=2), wall_clock_breakdown=True))
    batch = random_batch(32)
    engine.train_batch(batch=batch)        # builds the state, compiles
    n0 = default_tracer().events_total
    for _ in range(4):
        engine.train_batch(batch=batch)
    evs = _new_events(n0)
    steps = [e for e in evs if e["name"] == "train/step"]
    assert len(steps) == 4
    for step in steps:
        stack, = _kids(evs, step, "train/stack_batch")
        dispatch, = _kids(evs, step, "train/dispatch")
        sync, = _kids(evs, step, "train/sync")
        gap_ns = dispatch["ts"] - (stack["ts"] + stack["dur"])
        assert 0 <= gap_ns < 1_000_000
    timer = engine.timers(TRAIN_BATCH_TIMER)
    assert not timer.started_
    # a step's wall, dispatch to the end of the sync, each step recorded
    recorded = timer.records[-4:]
    assert len(recorded) == 4
    for ms, step in zip(recorded, steps):
        dispatch, = _kids(evs, step, "train/dispatch")
        sync, = _kids(evs, step, "train/sync")
        wall_ms = (sync["ts"] + sync["dur"] - dispatch["ts"]) / 1e6
        assert ms == pytest.approx(wall_ms, abs=2.0)
    assert engine.tput_timer.global_step_count == 5
    assert engine.tput_timer.total_elapsed_time > 0.0


def test_train_step_leaves_its_phases_in_order():
    engine, _, _, _ = ds.initialize(model=SimpleModel(hidden_dim=16),
                                    config=base_config(micro=2, gas=2))
    assert engine.tracer is default_tracer()
    batch = random_batch(32)
    n0 = default_tracer().events_total
    for _ in range(3):
        engine.train_batch(batch=batch)
    evs = _new_events(n0)
    steps = [e for e in evs if e["name"] == "train/step"]
    assert [s["args"]["step"] for s in steps] == [0, 1, 2]
    assert all(s["args"]["micro_batches"] == 2 for s in steps)
    for step in steps:
        kids = sorted((e for e in evs if e["ph"] == "X" and e is not step
                       and e["name"].startswith("train/")
                       and _inside(step, e)), key=lambda e: e["ts"])
        assert [k["name"] for k in kids] == TRAIN_PHASES
        assert sum(k["dur"] for k in kids) <= step["dur"]
        for a, b in zip(kids, kids[1:]):
            assert a["ts"] + a["dur"] <= b["ts"]
    # set-up: the lazy build of the state fell in the first step; the
    # entry point's own span holds no state yet (no parameters passed)
    setup = [e for e in default_tracer().events()
             if e["name"].startswith("setup/")]
    state = [e for e in setup if e["name"] == "setup/build_state"][-1]
    assert _inside(steps[0], state)
    assert state["args"]["parameters"] == engine.num_parameters > 0
    assert state["args"]["bytes_placed"] >= 4 * engine.num_parameters
    assert any(e["name"] == "setup/build"
               and e["args"]["entry"] == "initialize" for e in setup)
    assert any(e["name"] == "setup/compile"
               and "fused_train_batch" in e["args"]["program"]
               for e in setup)
