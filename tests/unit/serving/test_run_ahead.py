"""The run-ahead step (ISSUE 49): ``step()`` number n queues its programs,
then settles the bundle of step n - 1 and leaves its own in flight. Only
WHEN the host learns a value changes, never WHAT is computed: every outcome
(tokens, finish reasons, terminal timeline events) of a loop that runs
ahead must be bitwise what the same server gives when ``settle()`` follows
every ``step()`` (the serial order of the servers before it), across the
contiguous pool, the paged kernel, a chunk fused beside decode rows, a
state group, latent pages, a routed FFN and speculative decoding (which
settles inside the step), including preempt / resume, ``cancel`` and a
deadline with a bundle in flight, and an EOS, the one end that costs a dead
row. A request ended by its budget gets exactly ``max_new_tokens`` and
takes no row after its last.

The two arms of a case run on ONE server, which traces its programs once:
each arm starts from ``emptied(srv)`` (nothing seated, queued or in flight,
the audit clean, the pool reset to a new server's) and reads the counters it
asserts on as differences."""

import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.serving import FinishReason, RequestState, ServingEngine
from deepspeed_tpu.telemetry import Tracer
from tests.unit.kinds import TINY, kind_stack

from .conftest import emptied, traced_once

PS = 8
# the cache kinds the page pool serves (rows of tests/unit/kinds.py), the
# kernels in interpret mode, as tests/unit/serving/test_chunk_beside_decode.py
# serves them: chunks of 4 in pages of 8, so prompts stream in beside
# running slots and every chunk but a prompt's last goes with the decode
# rows as ONE program
KINDS = {"fused-chunk": "plain", "routed-ffn": "window_routed",
         "latent-pages": "latent_routed", "state-group": "state_group"}
KIND_SERVER = dict(num_slots=3, prefill_chunk=4, prefill_token_budget=8,
                   paged_kv={"kernel": "on", "page_size": PS,
                             "prefix_cache": False})


def kind_engine(kind):
    return kind_stack(KINDS[kind])[2]


def make_srv(engine, num_slots=3, **kw):
    kw.setdefault("prefill_chunk", PS)
    kw.setdefault("tracer", Tracer())
    return traced_once(ServingEngine(
        engine, num_slots=num_slots, max_queue_depth=32, **kw))


def _workload(seed=11, n=8):
    rng = np.random.default_rng(seed)
    lengths = rng.integers(3, 22, size=n)
    prompts = [rng.integers(0, 64, size=int(T)).astype(np.int32)
               for T in lengths]
    budgets = [int(b) for b in rng.integers(3, 10, size=n)]
    return prompts, budgets


def drive(srv, serial, max_steps=600):
    """Step until the server is empty: running ahead, or with ``settle()``
    after every step, the serial order."""
    steps = 0
    while srv.pending or srv.live_count:
        srv.step()
        if serial:
            srv.settle()
        steps += 1
        assert steps < max_steps
    return steps


def run_traffic(srv, prompts, budgets, serial, **submit):
    reqs = [srv.submit(p, max_new_tokens=b, **submit)
            for p, b in zip(prompts, budgets)]
    drive(srv, serial)
    srv.settle()
    assert srv._in_flight is None and not srv._deferred
    assert not srv._unread and not srv._closing
    srv.check_invariants()
    return reqs


def counter(srv, name):
    return int(srv.registry.counter(name).value)


def counting(srv, name):
    """``counter(srv, name)`` from now on: the two arms of a case run on
    one server, each from :func:`emptied`, and read their own counts."""
    base = counter(srv, name)
    return lambda: counter(srv, name) - base


def same_outcomes(srv, reqs_a, reqs_b):
    for a, b in zip(reqs_a, reqs_b):
        assert a.state == b.state == RequestState.FINISHED, a.finish_reason
        assert a.finish_reason == b.finish_reason
        np.testing.assert_array_equal(a.tokens(), b.tokens())
        ev_a = srv.timelines.events_of(a.request_id)
        ev_b = srv.timelines.events_of(b.request_id)
        assert ev_a[0] == ev_b[0] and ev_a[-1] == ev_b[-1]


def _server(stack, case):
    if case in KINDS:
        return make_srv(kind_engine(case), **KIND_SERVER)
    extra = {"plain": {},
             "paged-kernel": {"paged_kv": {"page_size": PS, "kernel": "on"}},
             "spec": {"spec_decode": {"k": 3, "drafter": "ngram"}}}[case]
    return make_srv(stack[2], **extra)


def tally(srv):
    """The counters the parity case reads, as they stand: it drives ONE
    server twice (its programs are traced once), so an arm's count is a
    difference of two of these."""
    said = {name: counter(srv, "serving/" + name) for name in (
        "fused_steps", "steps_run_ahead", "settled_early")}
    return dict(said, slot_steps=srv.metrics.slot_steps)


@pytest.mark.parametrize("case", ["plain", "paged-kernel", "spec",
                                  *sorted(KINDS)])
def test_run_ahead_outcome_parity(stack, case):
    """Same staggered workload through a loop that runs ahead and through
    the serial one: every request finishes with identical tokens, finish
    reason and first/terminal timeline events; ended by its budget, with
    exactly ``max_new_tokens``, and no slot takes a row after its last."""
    prompts, budgets = _workload()
    srv = _server(stack, case)
    assert srv._runs_ahead == (case != "spec")
    got = run_traffic(srv, prompts, budgets, serial=False)
    ahead = tally(srv)
    # the serial arm: the same server, its pool as a new server has it
    want = run_traffic(emptied(srv), prompts, budgets, serial=True)
    serial = {name: n - ahead[name] for name, n in tally(srv).items()}
    same_outcomes(srv, got, want)
    for req, budget in zip(got, budgets):
        assert req.finish_reason == FinishReason.LENGTH
        assert len(req.output_tokens) == budget
    if case in KINDS:
        assert ahead["fused_steps"] > 0
    if case != "spec":
        # every row of every decode program gave a token that was kept: a
        # request's first comes from its admission, the others one a row
        for arm in (ahead, serial):
            assert arm["slot_steps"] == sum(budgets) - len(budgets)
        assert ahead["steps_run_ahead"] > len(prompts)
        assert serial["steps_run_ahead"] == 0
    assert ahead["settled_early"] == 0


def test_run_ahead_matches_generate(stack):
    """The run-ahead loop against the whole-batch oracle directly."""
    _, _, engine = stack
    prompts, budgets = _workload(seed=17, n=5)
    reqs = run_traffic(make_srv(engine), prompts, budgets, serial=False)
    for req, p, b in zip(reqs, prompts, budgets):
        expected = engine.generate(np.asarray(p)[None],
                                   max_new_tokens=b)[0]
        np.testing.assert_array_equal(req.tokens(), expected)


def test_run_ahead_preempt_resume_parity(stack):
    """Preempting mid-decode with a bundle in flight: the forced settle
    hands the rollback a host that has read every queued token (none
    applied twice, none lost) — the resumed request's output equals the
    serial arm's, and it carries the same tokens back into the queue."""
    _, _, engine = stack
    rng = np.random.default_rng(23)
    prompt = rng.integers(0, 64, size=14).astype(np.int32)

    srv = make_srv(engine, num_slots=2)

    def run(serial):
        settled = counting(emptied(srv), "serving/settled_early/preempt")
        req = srv.submit(prompt, max_new_tokens=10)
        for _ in range(4):
            srv.step()
            if serial:
                srv.settle()
        assert (srv._in_flight is None) == serial
        srv.preempt(req.request_id)
        assert srv._in_flight is None and not srv._unread
        assert req.preemptions == 1 and req.state == RequestState.QUEUED
        carried = list(req.output_tokens)
        assert settled() == (0 if serial else 1)
        drive(srv, serial)
        srv.check_invariants()
        return req, carried

    (a, carried_a), (b, carried_b) = run(False), run(True)
    assert a.state == RequestState.FINISHED
    assert a.finish_reason == b.finish_reason
    assert carried_a == carried_b and len(carried_a) == 4
    np.testing.assert_array_equal(a.tokens(), b.tokens())


def test_a_starved_server_settles_early_only_for_a_victim(stack):
    """Auto-preemption asks for the forced settle once it has a victim to
    take: while the queue waits on residents that are all too young, the
    server keeps running ahead (no settle, every step counted); from the
    step a resident is old enough, each eviction settles first, the victim
    is chosen from what the host then knows, and every outcome is the
    serial order's."""
    _, _, engine = stack
    rng = np.random.default_rng(29)
    prompts = [rng.integers(0, 64, size=n).astype(np.int32)
               for n in (5, 7, 6, 4)]

    srv = make_srv(engine, num_slots=2, preempt_queue_threshold=1,
                   preempt_min_run_steps=5)

    def run(serial):
        emptied(srv)
        settled, ran_ahead, evicted = (counting(srv, name) for name in (
            "serving/settled_early", "serving/steps_run_ahead",
            "serving/settled_early/preempt"))
        reqs = [srv.submit(p, max_new_tokens=12) for p in prompts]
        for _ in range(5):      # residents seated in step 1: too young
            srv.step()
            if serial:
                srv.settle()
        assert srv.pending == 2 and srv.live_count == 2
        assert settled() == 0
        assert ran_ahead() == (0 if serial else 4)
        srv.step()              # held five steps: one is evicted
        assert evicted() == (0 if serial else 1)
        assert sum(r.preemptions for r in reqs) == 1
        drive(srv, serial)
        srv.settle()
        srv.check_invariants()
        return reqs

    ahead, serial = run(False), run(True)
    for a, b in zip(ahead, serial):
        assert a.preemptions == b.preemptions
        assert a.finish_reason == b.finish_reason == FinishReason.LENGTH
        assert a.output_tokens == b.output_tokens
        assert len(a.output_tokens) == 12


def test_a_step_queues_its_programs_before_it_waits_for_the_last(stack):
    """The pipeline is real, not vacuous: with running slots a step
    returns with its bundle in flight, its programs were queued BEFORE
    the one ``serving/sync`` that waited for the step before, and a step
    with nothing to queue settles and returns."""
    _, _, engine = stack
    tracer = Tracer()
    srv = make_srv(engine, num_slots=2, tracer=tracer)
    req = srv.submit(np.arange(6, dtype=np.int32), max_new_tokens=5)
    seen = []
    while srv.pending or srv.live_count:
        srv.step()
        seen.append((srv.step_id, len(req.output_tokens),
                     srv._in_flight is not None))
    # admission + first decode row in step 1; a token is visible one step
    # after the step that queued it; step 5 has nothing to queue
    assert seen == [(1, 0, True), (2, 2, True), (3, 3, True), (4, 4, True),
                    (5, 5, False)]
    assert req.state == RequestState.FINISHED and not srv._unread
    events = [e for e in tracer.events() if e.get("ph") == "X"]
    steps = {e["args"]["step"]: e for e in events
             if e["name"] == "serving/step"}

    def inside(step, name):
        lo, hi = step["ts"], step["ts"] + step["dur"]
        return [e for e in events if e["name"] == name
                and lo <= e["ts"] < hi]

    for n in (2, 3, 4):
        syncs = inside(steps[n], "serving/sync")
        queued = inside(steps[n], "serving/enqueue")
        assert len(syncs) == 1 and syncs[0]["args"]["step"] == n - 1
        assert queued and max(e["ts"] + e["dur"] for e in queued) \
            <= syncs[0]["ts"]
        assert steps[n]["args"]["in_flight"] == 1
    assert steps[1]["args"]["in_flight"] == 0
    assert not inside(steps[1], "serving/sync")
    assert not inside(steps[5], "serving/enqueue")
    assert counter(srv, "serving/steps_run_ahead") == 3
    assert counter(srv, "serving/settled_early") == 0


def test_init_serving_builds_a_server_that_runs_ahead(stack):
    """``ds.init_serving(paged_kv={"kernel": ...})`` reaches the
    ServingEngine; running ahead is how it steps, not an option of it."""
    model, params, _ = stack
    kw = dict(model=model, model_parameters=params,
              config={"dtype": "float32"}, num_slots=2, prefill_chunk=PS,
              paged_kv={"page_size": PS, "kernel": "on"})
    import inspect
    assert "overlap" not in inspect.signature(
        ServingEngine.__init__).parameters
    srv = ds.init_serving(**kw)
    assert srv._runs_ahead and srv.pool.kernel_active
    req = srv.submit(np.arange(5, dtype=np.int32), max_new_tokens=3)
    srv.step()
    assert srv._in_flight is not None and not req.output_tokens
    assert srv.settle() == [] and len(req.output_tokens) == 2
    done = srv.run_until_drained(max_steps=100)
    srv.check_invariants()
    assert done == [req] and req.state == RequestState.FINISHED


def test_run_ahead_cancel_midflight(stack):
    """Cancel with a bundle in flight: the settle comes first (the
    cancelled request keeps what it was owed), the slot frees, invariants
    hold, and the other request's tokens are untouched."""
    _, _, engine = stack
    rng = np.random.default_rng(29)
    keep_p = rng.integers(0, 64, size=9).astype(np.int32)
    kill_p = rng.integers(0, 64, size=12).astype(np.int32)

    srv = make_srv(engine, num_slots=2)

    def run(serial):
        settled = counting(emptied(srv), "serving/settled_early/cancel")
        keep = srv.submit(keep_p, max_new_tokens=6)
        kill = srv.submit(kill_p, max_new_tokens=20)
        for _ in range(6):
            srv.step()
            if serial:
                srv.settle()
        assert (srv._in_flight is None) == serial
        assert srv.cancel(kill.request_id) is kill
        assert srv._in_flight is None
        assert settled() == (0 if serial else 1)
        drive(srv, serial)
        srv.check_invariants()
        assert keep.state == RequestState.FINISHED
        assert kill.finish_reason == FinishReason.CANCELLED
        return keep, kill

    (keep, kill), (keep_s, kill_s) = run(False), run(True)
    expected = engine.generate(np.asarray(keep_p)[None],
                               max_new_tokens=6)[0]
    np.testing.assert_array_equal(keep.tokens(), expected)
    np.testing.assert_array_equal(keep_s.tokens(), expected)
    assert kill.output_tokens == kill_s.output_tokens
    assert 1 <= len(kill.output_tokens) < 6


def test_run_ahead_deadline_expiry(stack):
    """A seated request's deadline passes with a bundle in flight: the
    boundary settles first, so it retires ``deadline`` with exactly the
    tokens the serial order gives it."""
    _, _, engine = stack
    prompt = np.random.default_rng(31).integers(0, 64, 10).astype(np.int32)

    def run(serial):
        now = [0.0]
        srv = make_srv(engine, num_slots=2, clock=lambda: now[0])
        late = srv.submit(prompt, max_new_tokens=30, deadline_ms=1000.0)
        fine = srv.submit(prompt[:7], max_new_tokens=8)
        for _ in range(4):
            srv.step()
            if serial:
                srv.settle()
        now[0] = 2.0
        drive(srv, serial)
        srv.check_invariants()
        assert counter(srv, "serving/settled_early/deadline") == \
            (0 if serial else 1)
        return late, fine

    (late, fine), (late_s, fine_s) = run(False), run(True)
    assert late.finish_reason == late_s.finish_reason == \
        FinishReason.DEADLINE
    assert late.output_tokens == late_s.output_tokens
    assert len(late.output_tokens) == 4
    assert fine.finish_reason == FinishReason.LENGTH
    np.testing.assert_array_equal(fine.tokens(), fine_s.tokens())


@pytest.mark.parametrize("paged", [False, {"page_size": PS, "kernel": "on"}],
                         ids=["contiguous", "paged-kernel"])
def test_an_eos_costs_one_dead_row(stack, paged):
    """An end only the value tells: the step after the EOS was queued with
    the slot still in its running set. Its token is dropped by the replay,
    the request ends where the serial order ends it, and the slot is
    released one step late."""
    _, _, engine = stack
    rng = np.random.default_rng(37)
    prompt = rng.integers(0, 64, size=11).astype(np.int32)
    other = rng.integers(0, 64, size=6).astype(np.int32)
    srv = make_srv(engine, paged_kv=paged)
    free = run_traffic(srv, [prompt], [12], serial=True)[0].output_tokens
    at = next(i for i in range(3, 12) if free[i] not in free[:i])

    def run(serial):
        metrics = emptied(srv).metrics
        rows, tokens = metrics.slot_steps, metrics.decode_tokens
        a = srv.submit(prompt, max_new_tokens=12, eos_token_id=free[at])
        b = srv.submit(other, max_new_tokens=9)
        drive(srv, serial)
        srv.settle()
        srv.check_invariants()
        assert not srv._unread and not srv._closing
        return (metrics.slot_steps - rows, metrics.decode_tokens - tokens,
                a, b)

    (ahead, tokens, a, b), (serial, tokens_s, a_s, b_s) = \
        run(False), run(True)
    assert a.finish_reason == a_s.finish_reason == FinishReason.EOS
    assert a.output_tokens == a_s.output_tokens == free[:at + 1]
    assert b.output_tokens == b_s.output_tokens and len(b.output_tokens) == 9
    rows = at + 8                 # a's decode tokens + b's
    assert serial == rows
    assert ahead == rows + 1
    assert tokens == tokens_s


def test_an_eos_in_flight_under_page_pressure_maps_nothing(stack):
    """The forced settle of ``_ensure_pages`` may end the very request
    whose column it was paging in: ``a`` crosses a page boundary in the
    step whose bundle in flight holds its EOS, on a pool with no page
    free. The settle retires it, and nothing is mapped into the slot it
    gave back (free slots map nothing, which the audit checks every
    step); the page it held is what ``b``'s next column takes, with no
    preemption."""
    _, _, engine = stack
    rng = np.random.default_rng(41)
    base = rng.integers(0, 64, size=6).astype(np.int32)
    other = rng.integers(0, 64, size=14).astype(np.int32)
    paged = {"page_size": PS, "kernel": "off", "prefix_cache": False}
    # a's token number ``at`` is its first of that value, the EOS, and is
    # queued by the step that writes column 7: the next pages column 8 in
    for at in range(2, 6):
        prompt = base[:PS - at]
        free = np.asarray(engine.generate(prompt[None], max_new_tokens=at + 1)
                          )[0, len(prompt):].tolist()
        if free[at] not in free[:at]:
            break
    else:
        pytest.fail("no prompt here ends on a token it has not given yet")

    srv = make_srv(engine, num_slots=2, prefill_chunk=16,
                   paged_kv=dict(paged, num_pages=3))

    def run(serial):
        # a holds one page, b two: the three there are. a's column 8 is
        # paged in with its EOS in flight; b's column 16 comes later
        emptied(srv)
        evicted, settled = (counting(srv, name) for name in (
            "serving/settled_early/preempt", "serving/settled_early"))
        a = srv.submit(prompt, max_new_tokens=8, eos_token_id=free[at])
        b = srv.submit(other, max_new_tokens=6)
        steps = 0
        while srv.pending or srv.live_count:
            srv.step()
            if serial:
                srv.settle()
            else:
                errors = srv.pool.consistency_errors()
                assert not errors, errors
            steps += 1
            assert steps < 50
        srv.settle()
        srv.check_invariants()
        assert srv.pool.free_page_count == 3
        assert a.preemptions == b.preemptions == 0
        return evicted(), settled(), a, b

    (ahead, _, a, b), (_, serial, a_s, b_s) = run(False), run(True)
    assert a.finish_reason == a_s.finish_reason == FinishReason.EOS
    assert a.output_tokens == a_s.output_tokens == free
    assert b.output_tokens == b_s.output_tokens and len(b.output_tokens) == 6
    assert ahead == 1       # settled_early/preempt where it ran ahead
    assert serial == 0      # settled_early, of any cause, where it did not


@pytest.mark.parametrize("paged", [False, {"page_size": PS, "kernel": "off"}],
                         ids=["contiguous", "paged"])
@pytest.mark.parametrize("room, tokens", [(1, 2), (2, 3)])
def test_the_capacity_edge_ends_on_the_decode_that_fills_the_row(
        stack, paged, room, tokens):
    """LENGTH_CAP is counted when a token is queued: a request ends with
    the token sampled by the decode that writes its row's last column,
    whichever step seated it. (The servers before the run-ahead step
    dropped that token where the request was admitted and decoded in one
    step, ``room`` 1: there they gave 1 token and here 2, both valid; at
    ``room`` 2 and beyond both give the same.) Admission control keeps
    prompt + budget inside the capacity, so only the safety net behind it
    sees this."""
    model, params, engine = stack
    capacity = TINY["max_seq_len"]
    prompt = np.random.default_rng(59).integers(
        0, 64, size=capacity - room).astype(np.int32)
    # (``generate`` refuses prompt + budget past the capacity: the whole
    # sequence through the model, one more token a pass)
    ids = prompt
    for _ in range(tokens):
        logits = model.apply({"params": params}, ids[None],
                             method=model.logits)
        ids = np.append(ids, np.int32(jnp.argmax(logits[0, -1])))
    want = ids[len(prompt):]

    srv = make_srv(engine, num_slots=2, paged_kv=paged)
    srv.scheduler.capacity = srv.scheduler.num_pages = None

    def run(serial):
        req = emptied(srv).submit(prompt, max_new_tokens=8)
        drive(srv, serial)
        srv.settle()
        srv.check_invariants()
        assert not srv._unread and not srv._closing
        return req

    for req in (run(False), run(True)):
        assert req.finish_reason == FinishReason.LENGTH_CAP
        np.testing.assert_array_equal(req.output_tokens, want)


def test_routed_counters_ride_the_bundle_of_the_step_that_ran_them():
    """Point 2 of the issue, on a routed FFN: over a steady stretch step
    n's programs are queued BEFORE the one ``serving/sync``, which waits
    for step n - 1; the pool holds no counters when ``step()`` returns
    (they are in the bundle); and they land on the span of the step that
    ran them: absent while its bundle is in flight, there once the next
    step has settled it."""
    tracer = Tracer()
    srv = make_srv(kind_engine("routed-ffn"), tracer=tracer, **KIND_SERVER)
    rng = np.random.default_rng(5)
    for n in (3, 4):
        srv.submit(rng.integers(0, 64, size=n).astype(np.int32),
                   max_new_tokens=12)
    srv.step()                          # both admitted whole, first rows
    first = srv.step_id + 1
    for _ in range(6):                  # the steady stretch
        srv.step()
        assert srv.pool.moe_stats == []
        assert srv._in_flight is not None and srv._in_flight.moe_stats
    last = srv.step_id
    events = [e for e in tracer.events() if e.get("ph") == "X"]
    steps = {e["args"]["step"]: e for e in events
             if e["name"] == "serving/step"}
    assert "moe_layer_calls" not in steps[last]["args"]     # in flight
    assert counter(srv, "serving/steps_run_ahead") == last - first + 1
    calls = counter(srv, "serving/moe_layer_calls")
    srv.settle()
    for n in range(first, last + 1):
        step = steps[n]
        lo, hi = step["ts"], step["ts"] + step["dur"]
        syncs = [e for e in events if e["name"] == "serving/sync"
                 and lo <= e["ts"] < hi]
        queued = [e for e in events if e["name"] == "serving/enqueue"
                  and lo <= e["ts"] < hi]
        assert [e["args"]["step"] for e in syncs] == [n - 1]
        assert max(e["ts"] + e["dur"] for e in queued) <= syncs[0]["ts"]
        # one decode program: two routed layers, top-2 of its three rows
        assert step["args"]["moe_layer_calls"] == 2
        assert step["args"]["moe_assignments"] == 2 * 2 * 3
        assert step["args"]["decode"] == 2 and step["args"]["in_flight"]
    assert counter(srv, "serving/moe_layer_calls") == calls + 2
    assert counter(srv, "serving/settled_early") == 0
    # a forced settle is counted by what forced it
    srv.step()
    srv.check_invariants()
    assert counter(srv, "serving/settled_early/audit") == 1
    assert counter(srv, "serving/settled_early") == 1


@pytest.mark.parametrize("paged", [False, {"page_size": PS, "kernel": "off"}],
                         ids=["contiguous", "paged"])
def test_a_published_mirror_is_a_host_copy(stack, paged):
    """The index and the page table a pool publishes are copies made on the
    host at the call: the program that takes the leaf may still be queued
    when the next step moves the mirror in place (a copy on the device,
    after the put, read the next step's mirror: 4 of 14 fuzzed runs of the
    Moonlight rehearsal server gave other tokens)."""
    _, _, engine = stack
    pool = make_srv(engine, paged_kv=paged).pool
    pool.starts[:] = [3, 1, 4]
    index, want = pool._index_from_mirror(), pool.starts.copy()
    pool.starts += 1
    pool.starts[:] = 0
    np.testing.assert_array_equal(np.asarray(index), want)
    if paged:
        pool.table[0, :2] = [5, 6]
        table, want = pool._table_from_mirror(), pool.table.copy()
        pool.table[:] = 1
        np.testing.assert_array_equal(np.asarray(table), want)
