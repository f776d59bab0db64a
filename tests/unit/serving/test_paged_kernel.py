"""Paged-kernel serving tests (ISSUE 13): the fused Pallas paged-attention
path (``paged_kv={"kernel": "on"}``) must be a pure EXECUTABLE change —
greedy tokens bitwise-match the dense gather/scatter oracle (``"off"``)
and whole-batch ``generate()`` under slot churn, speculative rollback,
and preempt/resume; the kernel knob is validated and backend-gated; page
churn through the kernel never recompiles after warmup."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.serving import PagedKVPool, RequestState, ServingEngine
from deepspeed_tpu.telemetry import Tracer

from .conftest import (Servers, emptied, spans_since, traced_once,
                       watch_kernel_reads)

PS = 8  # page size == prefill chunk for every server in this file


def kernel_server(engine, kernel="on", num_slots=2, own_programs=False,
                  **kw):
    kw.setdefault("prefill_chunk", PS)
    srv = ServingEngine(engine, num_slots=num_slots, max_queue_depth=32,
                        paged_kv={"page_size": PS, "kernel": kernel}, **kw)
    return srv if own_programs else traced_once(srv)


@pytest.fixture(scope="module")
def servers(stack):
    """``servers(kernel)``: the module's one plain ``kernel_server`` of two
    slots on that arm, emptied between the cases that drive it."""
    return Servers(lambda kernel: kernel_server(stack[2], kernel))


def run_traffic(srv, prompts, budgets, max_steps=400):
    reqs = [srv.submit(p, max_new_tokens=b) for p, b in zip(prompts, budgets)]
    srv.run_until_drained(max_steps=max_steps)
    srv.check_invariants()
    return reqs


def _mixed_workload(seed=7, n=6):
    rng = np.random.default_rng(seed)
    lengths = rng.integers(3, 22, size=n)
    prompts = [rng.integers(0, 64, size=int(T)).astype(np.int32)
               for T in lengths]
    budgets = [int(b) for b in rng.integers(3, 9, size=n)]
    return prompts, budgets


# ---------------------------------------------------------------------------
# knob + gating


def test_kernel_knob_validates_and_gates(stack):
    _, _, engine = stack
    srv_on = kernel_server(engine, "on")
    assert isinstance(srv_on.pool, PagedKVPool)
    assert srv_on.pool.kernel_active
    assert srv_on.pool._paged_decode_kernel_jit is not None
    srv_off = kernel_server(engine, "off")
    assert not srv_off.pool.kernel_active
    assert srv_off.pool._paged_decode_kernel_jit is None
    # "auto" follows the backend: kernel only on real TPU hardware
    srv_auto = kernel_server(engine, "auto")
    expect = jax.default_backend() == "tpu"
    assert srv_auto.pool.kernel_active == expect
    with pytest.raises(ValueError, match="kernel"):
        kernel_server(engine, "sometimes")


# ---------------------------------------------------------------------------
# bitwise parity


def test_kernel_tokens_bitwise_match_dense_and_generate(stack, servers):
    """Multi-wave slot churn through the fused kernel: per-request tokens
    must equal the dense-oracle server's AND static-batch generate()'s,
    bit for bit (greedy)."""
    _, _, engine = stack
    prompts, budgets = _mixed_workload()
    on = run_traffic(servers("on"), prompts, budgets)
    off = run_traffic(servers("off"), prompts, budgets)
    for a, b, p, budget in zip(on, off, prompts, budgets):
        assert a.state == RequestState.FINISHED, a.finish_reason
        np.testing.assert_array_equal(a.tokens(), b.tokens())
        expected = engine.generate(np.asarray(p)[None],
                                   max_new_tokens=budget)[0]
        np.testing.assert_array_equal(a.tokens(), expected)


def test_kernel_spec_verify_parity_with_rollback(stack):
    """Speculative decoding through the fused verify kernel: repetitive
    prompts drive acceptances (multi-row verify widths), random ones
    drive rejections (rollback across page boundaries); tokens must
    bitwise-match the dense verify path either way."""
    _, _, engine = stack
    rng = np.random.default_rng(3)
    motif = rng.integers(0, 64, size=5)
    prompts = [np.tile(motif, 4).astype(np.int32),          # acceptances
               rng.integers(0, 64, size=17).astype(np.int32),  # rejections
               np.tile(motif, 3)[:-2].astype(np.int32)]
    budgets = [8, 6, 9]
    spec = {"k": 3, "drafter": "ngram"}

    def run(kernel):
        srv = kernel_server(engine, kernel, spec_decode=dict(spec))
        return srv, run_traffic(srv, prompts, budgets)

    srv_on, on = run("on")
    assert srv_on.pool._paged_verify_kernel_jit is not None
    _, off = run("off")
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a.tokens(), b.tokens())
    s = srv_on.stats()
    assert s["spec_drafted"] > 0 and s["spec_accepted"] > 0


def test_a_verify_of_nine_rows_takes_the_kernel(stack):
    """spec_k + 1 = 9 rows, one more than a sublane tile: the verify step
    reads and writes the pages in place like any narrower one (until
    PR 33 it fell back to the dense composition), tokens equal to the
    dense arm's."""
    _, _, engine = stack
    rng = np.random.default_rng(5)
    motif = rng.integers(0, 64, size=4)
    prompts = [np.tile(motif, 5).astype(np.int32)]
    budgets = [10]
    spec = {"k": 8, "drafter": "ngram"}
    srv_on = kernel_server(engine, "on", spec_decode=dict(spec),
                           tracer=Tracer())
    assert srv_on.pool.reads_in_place(9)
    on = run_traffic(srv_on, prompts, budgets)
    off = run_traffic(kernel_server(engine, "off",
                                    spec_decode=dict(spec)),
                      prompts, budgets)
    np.testing.assert_array_equal(on[0].tokens(), off[0].tokens())
    spans = [e["args"] for e in srv_on.tracer.events()
             if e["ph"] == "X" and e["name"] == "serving/verify_k"]
    assert spans and all("pool_reads" in args for args in spans)
    manifest = srv_on.watchdog.signature_manifest()
    assert "SlotPool._paged_verify_kernel_jit" in manifest
    assert "SlotPool._paged_verify_jit" not in manifest


def test_kernel_preempt_resume_parity(stack, servers):
    """Preempt mid-decode, resume through the kernel arm: the rebuilt
    page table must feed the kernel exactly the tokens the dense arm
    (and an unpreempted generate()) sees."""
    _, _, engine = stack
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, 64, size=18).astype(np.int32)

    def run(kernel):
        srv = servers(kernel)
        req = srv.submit(prompt, max_new_tokens=12)
        for _ in range(4):                       # partway through decode
            srv.step()
        srv.preempt(req.request_id)
        assert req.preemptions == 1
        srv.run_until_drained(max_steps=200)
        srv.check_invariants()
        return req

    a, b = run("on"), run("off")
    assert a.state == RequestState.FINISHED
    np.testing.assert_array_equal(a.tokens(), b.tokens())
    expected = engine.generate(np.asarray(prompt)[None],
                               max_new_tokens=12)[0]
    np.testing.assert_array_equal(a.tokens(), expected)


# ---------------------------------------------------------------------------
# zero-recompile churn


def test_kernel_churn_never_recompiles_after_warmup(stack):
    """A warm replay of the whole workload (slot churn, prefix hits,
    every admission grouping it uses) through the kernel server must not
    grow any executable cache."""
    _, _, engine = stack
    prompts, budgets = _mixed_workload(seed=13, n=6)
    # and prompts of several chunks, the last one short: the chunk
    # program reads and writes the pages through the same kernels
    rng = np.random.default_rng(14)
    prompts += [rng.integers(0, 64, size=n).astype(np.int32)
                for n in (29, 43)]
    budgets += [4, 5]
    srv = kernel_server(engine, "on", own_programs=True, tracer=Tracer())
    run_traffic(srv, prompts, budgets)
    srv.end_warmup()
    run_traffic(srv, prompts, budgets)
    assert srv.watchdog.recompiles == 0
    manifest = srv.watchdog.signature_manifest()
    assert "SlotPool._paged_decode_kernel_jit" in manifest
    assert "SlotPool._paged_chunk_jit" in manifest
    # (PR 48) and a chunk beside running slots rode their program
    assert "SlotPool._paged_chunk_decode_jit" in manifest
    assert srv.registry.counter("serving/fused_steps").value > 0
    chunks = [e["args"] for e in srv.tracer.events()
              if e["ph"] == "X" and e["name"] == "serving/prefill_chunk"]
    assert len(chunks) >= 2 * (4 + 6)
    assert all(args["read_slots"] == 1 for args in chunks)


# ---------------------------------------------------------------------------
# the prefill chunk reads and writes its pages in place (ISSUE 33)

CHUNK = 12      # not a multiple of the page: chunks cross page boundaries
# float32 logits of size ~1 through the pages (one page folded at a time)
# against the dense row's one softmax over 64 positions
CHUNK_ATOL = 2e-5


def _chunk_server(engine, kernel):
    """A server of 12-token chunks over pages of 8 whose chunk dispatches
    are recorded: ``(start, logits, steps of the device's work list)``."""
    from deepspeed_tpu.ops.attention.paged_attention import live_pages

    srv = kernel_server(engine, kernel, prefill_chunk=CHUNK, tracer=Tracer())
    pool, calls = srv.pool, []
    run, run_beside = pool.run_prefill_chunk, pool.run_chunk_decode

    def steps_of(ids, slot, start):
        return int(live_pages(
            jnp.asarray([start], jnp.int32), jnp.asarray(pool.table[slot])[None],
            ids.shape[1], PS, pool.num_pages)[-1])

    def run_prefill_chunk(eng, ids, slot, start, length, last_idx):
        steps = steps_of(ids, slot, start)
        logits = run(eng, ids, slot, start, length, last_idx)
        calls.append((start, np.asarray(logits), steps))
        return logits

    def run_chunk_decode(eng, ids, slot, start, length, last_idx, *more):
        # (PR 48) a chunk beside running slots rides the decode rows'
        # program on the kernel arm: the same chunk, the same logits
        steps = steps_of(ids, slot, start)
        logits, decoded = run_beside(eng, ids, slot, start, length,
                                     last_idx, *more)
        calls.append((start, np.asarray(logits), steps))
        return logits, decoded

    pool.run_prefill_chunk = run_prefill_chunk
    pool.run_chunk_decode = run_chunk_decode
    return srv, calls


@pytest.fixture(scope="module")
def chunk_servers(stack):
    """``{kernel: (server, calls)}``, one pair a module: a case takes each
    :func:`emptied`, its record cleared, and reads the spans and the
    copies-on-write it added."""
    return {kernel: _chunk_server(stack[2], kernel)
            for kernel in ("on", "off")}


def _drive_chunks(srv, case):
    rng = np.random.default_rng(33)
    reqs = []
    if case == "crosses_pages":
        # whole chunks and a short last one (6 and 5 tokens of 12), two
        # prompts prefilling one after the other beside a decoding row
        for n, budget in ((36, 6), (30, 5), (41, 4)):
            reqs.append(srv.submit(rng.integers(0, 64, n).astype(np.int32),
                                   max_new_tokens=budget))
    elif case == "prefix_hit_mid_page":
        # a hit of two pages resumes at position 12 (the chunk multiple
        # under it), in the middle of the hit's second page: that page is
        # forked before the chunk writes it
        prompt = rng.integers(0, 64, 24).astype(np.int32)
        reqs.append(srv.submit(prompt, max_new_tokens=3))
        srv.run_until_drained(max_steps=100)
        reqs.append(srv.submit(prompt, max_new_tokens=5))
        reqs.append(srv.submit(
            np.concatenate([prompt[:16], rng.integers(0, 64, 13)])
            .astype(np.int32), max_new_tokens=4))
    else:
        assert case == "preempt_mid_prefill"
        reqs.append(srv.submit(rng.integers(0, 64, 45).astype(np.int32),
                               max_new_tokens=6))
        srv.step()
        srv.step()
        assert reqs[0].state == RequestState.PREFILLING \
            and 0 < reqs[0].prefill_pos < 45
        srv.preempt(reqs[0].request_id)
        assert reqs[0].preemptions == 1
    for _ in range(300):
        if not (srv.live_count or srv.pending):
            break
        srv.step()
        srv.check_invariants()
    assert all(r.state == RequestState.FINISHED for r in reqs)
    return reqs


@pytest.mark.parametrize("case", ["crosses_pages", "prefix_hit_mid_page",
                                  "preempt_mid_prefill"])
def test_chunked_prefill_through_the_pages_matches_the_dense_arm(
        chunk_servers, case):
    """Every chunk of the kernel arm writes its 12 columns into the pages
    and reads them back in place; the dense arm gathers the slot's dense
    row. Greedy tokens equal, every chunk's logits within ``CHUNK_ATOL``,
    the invariants clean after every step, and the chunk spans say what
    the read's work list held (and nothing on the dense arm)."""
    (srv_on, on_calls), (srv_off, off_calls) = (
        chunk_servers[kernel] for kernel in ("on", "off"))
    before = {}
    for srv, calls in chunk_servers.values():
        emptied(srv)
        calls.clear()
        before[srv] = (srv.tracer.events_total, srv.pool.cow_copies)
    on, off = _drive_chunks(srv_on, case), _drive_chunks(srv_off, case)
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a.tokens(), b.tokens())
    assert [c[0] for c in on_calls] == [c[0] for c in off_calls]
    assert len(on_calls) >= 4
    for (start, got, _), (_, want, _) in zip(on_calls, off_calls):
        np.testing.assert_allclose(got, want, atol=CHUNK_ATOL,
                                   err_msg=f"chunk at {start}")
    if case == "prefix_hit_mid_page":
        assert srv_on.pool.cow_copies - before[srv_on][1] \
            == srv_off.pool.cow_copies - before[srv_off][1] >= 1
        assert any(start % PS for start, _, _ in on_calls)

    def spans(srv):
        return [e["args"] for e in spans_since(srv, before[srv][0])
                if e["name"] == "serving/prefill_chunk"]

    assert len(spans(srv_on)) == len(on_calls)
    for args, (start, _, steps) in zip(spans(srv_on), on_calls):
        assert args["pos"] == start
        assert (args["pool_reads"], args["read_slots"]) == (steps, 1)
        assert args["pool_read_pages"] == steps     # a step of K/V: a page
        assert args["pool_writes"] >= 1
    assert spans(srv_off) and not any(
        "pool_reads" in args or "read_slots" in args
        for args in spans(srv_off))
    assert srv_off.pool.pages_read(CHUNK, [0], [0]) is None


# ---------------------------------------------------------------------------
# the read's work list holds the slots that map a page (ISSUE 31)


def _churn(engine, kernel, spec=None):
    """Admit -> finish -> re-admit on four slots with the finite guard on:
    seven requests of mixed budgets, so slots stand freed (row all
    sentinel, index counting on) beside decoding ones for many steps."""
    from deepspeed_tpu.ops.attention.paged_attention import live_pages

    srv = kernel_server(engine, kernel, num_slots=4, guard_numerics=True,
                        tracer=Tracer(),
                        **({"spec_decode": dict(spec)} if spec else {}))
    pool = srv.pool
    finite_rows, record = watch_kernel_reads(srv, lambda rows: int(
        live_pages(jnp.asarray(pool.positions()),
                   pool.cache["cache_store"]["table"], rows, PS,
                   pool.num_pages)[-1]))
    rng = np.random.default_rng(31)
    prompts = [rng.integers(0, 64, size=int(n)).astype(np.int32)
               for n in (5, 19, 9, 3, 12, 7, 21)]
    budgets = [3, 14, 6, 10, 4, 12, 5]
    reqs = [srv.submit(p, max_new_tokens=b)
            for p, b in zip(prompts, budgets)]
    for _ in range(400):
        if not (srv.live_count or srv.pending):
            break
        srv.step()
        srv.check_invariants()
    assert all(r.state == RequestState.FINISHED for r in reqs)
    # every row of every guarded step, the freed slots' too (the verify
    # program samples inside and hands the guard no logits)
    assert bool(finite_rows) == (spec is None)
    assert all(rows.all() for rows in finite_rows)
    assert all(r.finish_reason != "numerical_error" for r in reqs)
    return srv, reqs, record


@pytest.mark.parametrize("spec", [None, {"k": 3, "drafter": "ngram"}],
                         ids=["decode", "verify"])
def test_freed_slots_are_no_step_under_churn_with_the_finite_guard(
        stack, spec):
    """The kernel arm leaves freed slots out of its work list: tokens
    bitwise the dense arm's for every request, no row of any step
    non-finite, invariants clean after every step, and the dispatch span
    says what the list held: ``pool_reads`` the device list's length,
    ``read_slots`` the seated rows that map a page."""
    _, _, engine = stack
    srv, on, record = _churn(engine, "on", spec)
    srv_off, off, none = _churn(engine, "off", spec)
    assert not none                 # the dense composition has no list
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a.tokens(), b.tokens())
    name = "serving/verify_k" if spec else "serving/decode"
    spans = [e["args"] for e in srv.tracer.events()
             if e["ph"] == "X" and e["name"] == name]
    assert len(spans) == len(record) > 10
    for args, ((reads, slots), total, seated) in zip(spans, record):
        assert (args["pool_reads"], args["read_slots"]) == (reads, slots)
        assert args["pool_read_pages"] == reads == total and slots == seated
    # slots stood freed beside decoding ones, and cost nothing
    assert any(0 < slots < 4 for (_, slots), _, _ in record)
    assert srv_off.pool.pages_read(1) is None
