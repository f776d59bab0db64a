"""Paged KV pool tests: paging must be a pure MEMORY-LAYOUT change — greedy
tokens bitwise-match both whole-batch ``generate()`` and the contiguous
SlotPool under slot churn, prefix hits, copy-on-write forks, speculative
rollback across page boundaries, and preempt/resume; page churn never
recompiles; refcount bookkeeping survives the invariant audit; admission is
page-denominated."""

import numpy as np
import pytest

from deepspeed_tpu.serving import (PagedKVPool, PagePoolExhausted, PrefixCache,
                                   RejectReason, RequestState, ServingEngine)
from deepspeed_tpu.serving.resilience import InvariantViolation

from .conftest import Servers, traced_once

PS = 8  # page size == prefill chunk for every server in this file


def paged_server(engine, num_slots=2, num_pages=None, own_programs=False,
                 **kw):
    kw.setdefault("prefill_chunk", PS)
    srv = ServingEngine(engine, num_slots=num_slots, max_queue_depth=32,
                        paged_kv={"page_size": PS, "num_pages": num_pages},
                        **kw)
    return srv if own_programs else traced_once(srv)


@pytest.fixture(scope="module")
def shared(stack):
    """``shared()``: the module's one ``paged_server`` of two slots, for the
    cases that assert on nothing a server counts over its life; handed out
    :func:`emptied` (every page free, an empty trie)."""
    return Servers(lambda: paged_server(stack[2]))


def run_traffic(srv, prompts, budgets):
    reqs = [srv.submit(p, max_new_tokens=b) for p, b in zip(prompts, budgets)]
    srv.run_until_drained(max_steps=400)
    return reqs


def assert_matches_generate(engine, reqs, prompts, budgets):
    for req, prompt, budget in zip(reqs, prompts, budgets):
        assert req.state == RequestState.FINISHED, req.finish_reason
        expected = engine.generate(np.asarray(prompt)[None],
                                   max_new_tokens=budget)[0]
        np.testing.assert_array_equal(req.tokens(), expected,
                                      err_msg=f"req {req.request_id}")


# ---------------------------------------------------------------------------
# bitwise parity


def test_paged_tokens_bitwise_match_generate(stack, shared):
    """Multi-wave slot reuse through the paged pool must produce EXACTLY
    the tokens static-batch generate() produces — page tables are an
    addressing change, never a numerics change (greedy)."""
    _, _, engine = stack
    rng = np.random.default_rng(7)
    lengths = [5, 9, 12, 5, 17, 12]
    budgets = [6, 4, 8, 3, 7, 5]
    prompts = [rng.integers(0, 64, size=n).astype(np.int32) for n in lengths]
    srv = shared()
    assert isinstance(srv.pool, PagedKVPool)
    reqs = run_traffic(srv, prompts, budgets)
    assert_matches_generate(engine, reqs, prompts, budgets)
    srv.check_invariants()


def test_paged_matches_contiguous_pool(stack, shared):
    """The same staggered traffic through a paged and a contiguous server
    yields identical per-request tokens — pinning paged-vs-SlotPool parity
    directly, not just both-against-generate."""
    _, _, engine = stack
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, 64, size=n).astype(np.int32)
               for n in (6, 11, 24, 9, 6)]
    budgets = [5, 7, 4, 6, 8]
    paged = run_traffic(shared(), prompts, budgets)
    dense = run_traffic(
        ServingEngine(engine, num_slots=2, max_queue_depth=32,
                      prefill_chunk=PS), prompts, budgets)
    for p, d in zip(paged, dense):
        np.testing.assert_array_equal(p.tokens(), d.tokens())


# ---------------------------------------------------------------------------
# prefix cache


def test_prefix_hit_skips_prefill_and_keeps_parity(stack):
    """Requests sharing a 3-page prefix: followers must hit the trie (pay
    only the uncached suffix) AND still emit bitwise-identical tokens."""
    _, _, engine = stack
    base = list(range(1, 25))                    # 24 tokens = 3 full pages
    prompts = [np.asarray(base + [30 + i], np.int32) for i in range(4)]
    budgets = [5, 5, 5, 5]
    srv = paged_server(engine, num_slots=2)
    reqs = []
    for p, b in zip(prompts, budgets):           # drain between arrivals so
        reqs.append(srv.submit(p, max_new_tokens=b))   # the trie is warm
        srv.run_until_drained(max_steps=100)
    assert_matches_generate(engine, reqs, prompts, budgets)

    stats = srv.pool.page_stats()
    assert stats["prefix_hits"] >= 3             # every follower hit
    assert stats["prefix_hit_tokens"] >= 3 * 24
    assert reqs[0].prefix_hit_tokens == 0
    # pos0 is aligned DOWN to a chunk boundary; a 24-token hit on a
    # 25-token seed re-enters prefill at 24
    assert all(r.prefix_hit_tokens == 24 for r in reqs[1:])
    snap = srv.stats()
    assert snap["prefix_hits"] >= 3
    assert snap["prefix_hit_rate"] > 0
    assert snap["paging"]["pages_total"] == srv.pool.num_pages
    srv.check_invariants()


def test_cow_fork_on_page_aligned_duplicate(stack):
    """A page-aligned duplicate prompt full-hits the trie; re-prefilling
    the final chunk (to recover the next-token logits) lands inside a
    SHARED page and must fork it copy-on-write — with bitwise parity."""
    _, _, engine = stack
    dup = np.asarray([40] * 32, np.int32)        # 4 full pages exactly
    srv = paged_server(engine, num_slots=2)
    r1 = srv.submit(dup, max_new_tokens=4)
    srv.run_until_drained(max_steps=100)
    r2 = srv.submit(dup, max_new_tokens=4)
    srv.run_until_drained(max_steps=100)
    assert srv.pool.cow_copies >= 1
    assert r2.prefix_hit_tokens == 24            # full hit, last chunk redone
    expected = engine.generate(dup[None], max_new_tokens=4)[0]
    np.testing.assert_array_equal(r1.tokens(), expected)
    np.testing.assert_array_equal(r2.tokens(), expected)
    srv.check_invariants()


def test_prefix_cache_unit():
    """Trie semantics in isolation: full-page matching, peek neutrality,
    insert dedup, and leaf-LRU eviction order."""

    class FakePool:
        def __init__(self):
            self.refs = {}

        def ref_page(self, pid):
            self.refs[pid] = self.refs.get(pid, 0) + 1

        def unref_page(self, pid):
            self.refs[pid] -= 1
            return self.refs[pid] == 0

    pool, trie = FakePool(), PrefixCache(4)
    a = list(range(12))                          # 3 full pages
    assert trie.match(a) == [] and trie.misses == 1
    trie.insert(a, [10, 11, 12], pool)
    assert pool.refs == {10: 1, 11: 1, 12: 1}
    assert trie.peek(a) == 3 and trie.hits == 0  # peek leaves counters alone
    assert trie.match(a) == [10, 11, 12] and trie.hits == 1
    assert trie.match(a[:10]) == [10, 11]        # partial page dropped
    assert trie.match([9] * 8) == []             # divergent first page
    trie.insert(a, [20, 21, 22], pool)           # dedup: keeps older pages
    assert trie.num_nodes == 3 and 20 not in pool.refs

    b = a[:8] + [50, 51, 52, 53]                 # shares 2 pages, forks 3rd
    trie.insert(b, [10, 11, 30], pool)
    assert trie.num_nodes == 4
    trie.match(b)                                # stamp b's branch young
    assert trie.evict(pool, need=1) == 1         # LRU leaf = a's page 12
    assert 12 not in [n for n in pool.refs if pool.refs[n] > 0]
    assert trie.match(a) == [10, 11]
    trie.clear(pool)
    assert trie.num_nodes == 0
    assert all(v == 0 for v in pool.refs.values())


# ---------------------------------------------------------------------------
# speculative decoding / preemption composition


def test_spec_decode_paged_parity_across_page_boundary(stack):
    """Draft-verify over the paged pool: the K+1-wide verify window and
    its rollback regularly straddle page boundaries (budget spans several
    pages); greedy output must stay bitwise-identical to generate()."""
    _, _, engine = stack
    rng = np.random.default_rng(23)
    prompts = [rng.integers(0, 8, size=n).astype(np.int32)
               for n in (6, 14, 10)]             # small vocab => ngram hits
    budgets = [20, 18, 16]                       # crosses 2-3 page boundaries
    srv = paged_server(engine, num_slots=2,
                       spec_decode={"drafter": "ngram", "k": 3})
    reqs = run_traffic(srv, prompts, budgets)
    assert_matches_generate(engine, reqs, prompts, budgets)
    srv.check_invariants()


def test_preempt_resume_with_cached_prefix(stack, shared):
    """Preempt mid-decode, resume through the paged pool: the re-prefill
    walks the prefix cache (the preempted prompt's own full pages are
    trie-cached) and the final tokens are bitwise what an unpreempted run
    produces."""
    _, _, engine = stack
    rng = np.random.default_rng(31)
    prompt = rng.integers(0, 64, size=18).astype(np.int32)
    srv = shared()
    req = srv.submit(prompt, max_new_tokens=12)
    for _ in range(4):                           # partway through decode
        srv.step()
    srv.preempt(req.request_id)
    assert req.preemptions == 1
    srv.run_until_drained(max_steps=200)
    assert_matches_generate(engine, [req], [prompt], [12])
    assert req.prefix_hit_tokens > 0             # resume hit its own pages
    srv.check_invariants()


# ---------------------------------------------------------------------------
# zero-recompile + pressure


def test_no_recompile_after_warmup_page_churn(stack):
    """Strict watchdog: once warm traffic has covered prefill, decode,
    prefix hits, and a CoW fork, page churn (new tables, eviction,
    oversubscription pressure) must never recompile a paged program."""
    _, _, engine = stack
    srv = paged_server(engine, num_slots=4, num_pages=12, own_programs=True,
                       preempt_queue_threshold=2, strict_recompile=True)
    base = list(range(1, 25))
    for i in range(3):
        srv.submit(np.asarray(base + [30 + i], np.int32), max_new_tokens=6)
    srv.run_until_drained(max_steps=200)
    dup = np.asarray([40] * 32, np.int32)
    for _ in range(2):                           # 2nd dup full-hits -> CoW
        srv.submit(dup, max_new_tokens=4)
        srv.run_until_drained(max_steps=100)
    assert srv.pool.cow_copies >= 1
    srv.end_warmup()

    srv.submit(dup, max_new_tokens=4)            # post-warmup CoW fork
    for i in range(8):                           # oversubscription churn
        srv.submit(np.asarray(base + [50 + i], np.int32), max_new_tokens=8)
    srv.run_until_drained(max_steps=400)
    assert srv.watchdog.recompiles == 0
    srv.check_invariants()


def test_oversubscribed_pool_drains_under_pressure(stack):
    """num_pages far below worst-case: admission throttles on the page
    budget, trie eviction and pressure preemption reclaim pages, and every
    request still finishes with exact tokens."""
    _, _, engine = stack
    rng = np.random.default_rng(41)
    prompts = [rng.integers(0, 64, size=n).astype(np.int32)
               for n in (20, 24, 20, 24, 20, 24)]
    budgets = [10, 8, 10, 8, 10, 8]
    # worst case is 4 slots * 64 tokens = 32 pages; give it 12
    srv = paged_server(engine, num_slots=4, num_pages=12,
                       preempt_queue_threshold=2,
                       degradation={"queue_pressured": 4,
                                    "queue_overloaded": 12,
                                    "cooldown_steps": 2})
    reqs = run_traffic(srv, prompts, budgets)
    assert_matches_generate(engine, reqs, prompts, budgets)
    assert srv.pool.free_page_count + srv.pool.prefix.num_nodes \
        <= srv.pool.num_pages
    # page starvation must register as load even with a short queue —
    # the degradation ladder is page-denominated under oversubscription
    assert srv.stats()["load_transitions"] >= 1
    srv.check_invariants()


def test_page_denominated_admission_rejects(stack):
    """A prompt whose page footprint exceeds the whole pool is rejected at
    submit with PROMPT_TOO_LONG — page-denominated admission control."""
    _, _, engine = stack
    srv = paged_server(engine, num_slots=2, num_pages=4)   # 32 tokens total
    rng = np.random.default_rng(43)
    req = srv.submit(rng.integers(0, 64, size=40).astype(np.int32),
                     max_new_tokens=8)
    assert req.state == RequestState.REJECTED
    assert req.reject_reason == RejectReason.PROMPT_TOO_LONG
    ok = srv.submit(rng.integers(0, 64, size=10).astype(np.int32),
                    max_new_tokens=4)
    srv.run_until_drained(max_steps=100)
    assert ok.state == RequestState.FINISHED
    srv.check_invariants()


# ---------------------------------------------------------------------------
# bookkeeping integrity


def test_invariant_audit_catches_refcount_corruption(shared):
    """The page audit must detect a refcount that no held reference
    explains — the chaos-suite contract extended to page bookkeeping."""
    srv = shared()
    srv.submit(np.arange(1, 20, dtype=np.int32), max_new_tokens=4)
    srv.run_until_drained(max_steps=100)
    srv.check_invariants()                       # clean before corruption
    pool = srv.pool
    victim = int(pool.table[0, 0]) if int(pool.table[0, 0]) != pool.num_pages \
        else next(iter(pool.prefix.page_counts()))
    pool.page_refs[victim] += 1                  # phantom reference
    with pytest.raises(InvariantViolation, match="page"):
        srv.check_invariants()
    pool.page_refs[victim] -= 1
    srv.check_invariants()


def test_paging_telemetry_gauges_and_stats(shared):
    """stats() carries the paging panel and the registry exports the
    paging/* gauges every step."""
    srv = shared()
    srv.submit(np.arange(1, 15, dtype=np.int32), max_new_tokens=3)
    srv.run_until_drained(max_steps=100)
    snap = srv.stats()
    paging = snap["paging"]
    for key in ("pages_total", "pages_free", "pages_in_use",
                "refcounted_pages", "cow_copies", "page_evictions",
                "page_size", "prefix_hits", "prefix_misses"):
        assert key in paging
    assert paging["pages_total"] == paging["pages_free"] \
        + paging["pages_in_use"]
    sample = srv.registry.snapshot()
    assert "paging/free_pages" in sample
    assert "paging/pages_in_use" in sample
    text = srv.registry.to_prometheus()
    assert "paging" in text
