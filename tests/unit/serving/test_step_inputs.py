"""A serving step sends the device only what it does not already hold
(PR 35): the decode and verify programs take the (B,) token twin as it is
and their positions from the cache's own ``index``; the sampler and verify
programs split the key they are handed and hand the next one back; the
temperature is a device scalar put when it changes; a chunk's arguments are
one vector; the chunk program patches the table row it is handed, and the
host republishes the whole table only for a row the device has not seen
that is not the chunk's own."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine import (pack_chunk_args,
                                            unpack_chunk_args)
from deepspeed_tpu.serving import RequestState, ServingEngine
from deepspeed_tpu.serving.resilience import FaultInjector, InjectedFault
from tests.unit.kinds import MELLUM_PERIOD, kind_stack

from .conftest import make_server

# What the parent of PR 35 (commit c3d4d53) served at a fixed seed from the
# tiny model of conftest.py (learned position embeddings: a wrong position
# is another token), recorded there with the drive of ``_serve`` /
# ``_generate`` below: the eager ``jax.random.split`` before every sampler
# and verify call, ``_cur_dev[:, None]`` and a put of ``pool.positions()``
# before every decode. Greedy and speculative-greedy are one stream.
GREEDY = [[17, 17, 42, 52, 52, 52, 52, 52, 43, 43],
          [43, 43, 23, 23, 23, 23, 59, 23, 23, 23],
          [4, 4, 4, 4, 4, 4, 4, 4, 4, 4],
          [36, 0, 17, 29, 17, 6, 6, 62, 43, 43]]
SAMPLED = [[8, 0, 52, 15, 14, 52, 13, 17, 22, 40],
           [47, 52, 3, 59, 23, 12, 33, 33, 26, 39],
           [22, 55, 11, 51, 24, 48, 55, 39, 33, 42],
           [27, 38, 42, 42, 29, 0, 19, 57, 28, 51]]
SPEC_SAMPLED = [[8, 17, 42, 17, 14, 43, 43, 36, 8, 5],
                [47, 17, 30, 31, 16, 23, 0, 23, 23, 21],
                [22, 21, 26, 47, 17, 47, 63, 11, 47, 41],
                [27, 17, 43, 7, 3, 7, 53, 14, 42, 40]]
GENERATED = {
    ("scan", True): [[8, 5, 52, 15, 16, 14, 52, 22, 13],
                     [13, 53, 42, 42, 29, 29, 51, 5, 13]],
    ("loop", True): [[8, 5, 52, 15, 16, 14, 52, 22, 13],
                     [13, 53, 42, 42, 29, 29, 51, 5, 13]],
    ("scan", False): [[17, 17, 17, 17, 17, 6, 43, 43, 43],
                      [42, 42, 42, 33, 33, 33, 42, 33, 33]],
}
SPEC = {"k": 3, "drafter": "ngram"}


def _prompts():
    rng = np.random.default_rng(35)
    return [rng.integers(1, 64, n).astype(np.int32) for n in (5, 19, 9, 3)]


def _serve(engine, pool, do_sample, **kw):
    srv = make_server(engine, pool, own_programs=True, num_slots=3,
                      prefill_chunk=8, do_sample=do_sample, temperature=0.8,
                      top_k=20, seed=7, **kw)
    prompts = _prompts()
    reqs = [srv.submit(p, max_new_tokens=10) for p in prompts[:2]]
    # every step settled before the next, the parents' order: a slot is
    # granted again in the step after its request's last, so the sampler's
    # calls, and with them the keys, fall on the same tokens (a loop that
    # runs ahead seats the fourth request one step later: the same key
    # sequence under other tokens; greedy streams do not move)
    for _ in range(2):
        srv.step()
        srv.settle()
    reqs += [srv.submit(p, max_new_tokens=10) for p in prompts[2:]]
    while srv.pending or srv.live_count:
        srv.step()
        srv.settle()
    return srv, [list(map(int, r.output_tokens)) for r in reqs]


# ---------------------------------------------------------------- (c)
@pytest.mark.parametrize("spec", [None, SPEC], ids=["plain", "speculative"])
@pytest.mark.parametrize("do_sample", [False, True],
                         ids=["greedy", "sampled"])
def test_served_streams_are_the_parents_at_a_fixed_seed(stack, pool,
                                                        do_sample, spec):
    """The key split moved inside the sampler and verify programs, the
    token axis and the positions inside the decode program: the same
    threefry split, the same integers, so the same tokens, bit for bit."""
    engine = stack[2]
    kw = {} if spec is None else {"spec_decode": spec}
    srv, streams = _serve(engine, pool, do_sample, **kw)
    want = GREEDY if not do_sample else (
        SAMPLED if spec is None else SPEC_SAMPLED)
    assert streams == want
    assert srv.watchdog.recompiles == 0


@pytest.mark.parametrize("path,do_sample", sorted(GENERATED))
def test_generate_streams_are_the_parents_at_a_fixed_seed(stack, path,
                                                          do_sample):
    """``generate()`` calls the same sampler program and keeps the key it
    hands back: the whole-loop scan and the eager loop with an eos check."""
    engine = stack[2]
    batch = np.stack([np.resize(p, 6) for p in _prompts()[:2]])
    kw = dict(do_sample=True, temperature=0.8, top_k=20, seed=7) \
        if do_sample else {}
    if path == "loop":
        kw["eos_token_id"] = 63
    out = np.asarray(engine.generate(batch, max_new_tokens=9, **kw))
    assert out[:, 6:].tolist() == GENERATED[(path, do_sample)]


def test_one_sampler_program_splits_the_key_it_is_handed(stack):
    """``(key', tokens)``: the sub-key the tokens are drawn with and the
    key handed back are the two halves of ``jax.random.split``."""
    engine = stack[2]
    engine._ensure_params(jnp.zeros((1, 2), jnp.int32))
    logits = jax.random.normal(jax.random.PRNGKey(3), (4, 1, 64))
    key = jax.device_put(jax.random.PRNGKey(11), engine.key_sharding)
    temperature = jnp.asarray(0.7, jnp.float32)
    nxt, tokens = engine._jit_sample(logits, key, temperature, 0, 1.0,
                                     jnp.asarray(False))
    want_key, sub = jax.random.split(jax.random.PRNGKey(11))
    np.testing.assert_array_equal(np.asarray(nxt), np.asarray(want_key))
    want = jax.random.categorical(sub, logits[:, -1, :] / 0.7, axis=-1)
    np.testing.assert_array_equal(np.asarray(tokens), np.asarray(want))
    assert nxt.sharding.is_equivalent_to(engine.key_sharding, nxt.ndim)


# ---------------------------------------------------------------- (b)
def test_in_program_positions_are_the_host_mirrors(stack, pool):
    """Whenever a decode is queued the device ``index`` equals ``starts``,
    so ``minimum(index, capacity - 1)`` inside the program is
    ``pool.positions()``: for live rows, free rows (which count on past
    the capacity), a row in mid-prefill (the rollback republished it) and
    rows held at the last column."""
    engine = stack[2]
    srv = make_server(engine, pool, num_slots=4, prefill_chunk=8)
    cap = srv.pool.capacity
    seen = {"live": 0, "free": 0, "prefilling": 0, "clamped": 0}
    owner, name = (srv.pool, "run_decode") if srv._paged \
        else (engine, "_jit_decode")
    queue = getattr(owner, name)

    def spy(*args, **kw):
        index = np.asarray(srv.pool.cache["cache_store"]["index"])
        np.testing.assert_array_equal(index, srv.pool.starts)
        np.testing.assert_array_equal(np.minimum(index, cap - 1),
                                      srv.pool.positions())
        for slot in range(srv.pool.num_slots):
            req = srv._slot_req.get(slot)
            kind = "free" if req is None else (
                "live" if req.state is RequestState.RUNNING
                else "prefilling")
            seen[kind] += 1
            seen["clamped"] += int(index[slot] > cap - 1)
        return queue(*args, **kw)

    setattr(owner, name, spy)
    try:
        rng = np.random.default_rng(5)
        first = srv.submit(rng.integers(1, 64, 40).astype(np.int32),
                           max_new_tokens=24)       # runs into the capacity
        short = srv.submit(rng.integers(1, 64, 4).astype(np.int32),
                           max_new_tokens=60)
        for _ in range(8):
            srv.step()
        late = srv.submit(rng.integers(1, 64, 30).astype(np.int32),
                          max_new_tokens=6)         # chunked beside decodes
        srv.run_until_drained(max_steps=400)
    finally:
        setattr(owner, name, queue)
    assert first.state is short.state is late.state is RequestState.FINISHED
    assert all(seen.values()), seen


def test_the_decode_program_makes_the_positions_the_host_used_to_put(stack):
    """One program, two ways in: the (B,) tokens and no positions give, bit
    for bit, the logits of the (B, 1) tokens with ``pool.positions()``,
    for an index under, at and past the capacity."""
    engine = stack[2]
    engine._ensure_params(jnp.zeros((1, 2), jnp.int32))
    spec = engine.kv_cache_spec()
    cap = int(spec.max_seq_len)
    store = dict(spec.stacked_cache(4))
    store["k"] = jax.random.normal(jax.random.PRNGKey(1), store["k"].shape,
                                   store["k"].dtype)
    store["v"] = jax.random.normal(jax.random.PRNGKey(2), store["v"].shape,
                                   store["v"].dtype)
    index = np.asarray([3, cap - 1, cap, cap + 40], np.int32)
    store["index"] = jnp.asarray(index)
    cache = {"cache_store": store}
    tokens = jnp.asarray([5, 9, 2, 7], jnp.int32)
    decode = jax.jit(engine._decode_fn)
    implicit, new = decode(engine.params, cache, tokens)
    explicit, _ = decode(engine.params, cache, tokens[:, None],
                         jnp.asarray(np.minimum(index, cap - 1)))
    np.testing.assert_array_equal(np.asarray(implicit), np.asarray(explicit))
    np.testing.assert_array_equal(
        np.asarray(new["cache_store"]["index"]), index + 1)


# ---------------------------------------------------------------- (d)
def test_the_temperatures_device_scalar_follows_the_attribute(stack, pool):
    engine = stack[2]
    srv = make_server(engine, pool, num_slots=2, do_sample=True,
                      temperature=0.8, seed=7)
    first = srv._temperature()
    assert srv._temperature() is first and float(first) == np.float32(0.8)
    calls = srv._device_calls
    srv._temperature()
    assert srv._device_calls == calls            # nothing is put again
    srv.temperature = 0.3
    second = srv._temperature()
    assert second is not first and float(second) == np.float32(0.3)
    assert srv._device_calls == calls + 1        # the one put of the change
    assert srv._temperature() is second
    # and the sampler draws under it: as a server built at 0.3
    prompt = np.arange(1, 8, dtype=np.int32)
    a = srv.submit(prompt, max_new_tokens=12)
    srv.run_until_drained(max_steps=100)
    cold = make_server(engine, pool, num_slots=2, do_sample=True,
                       temperature=0.3, seed=7)
    b = cold.submit(prompt, max_new_tokens=12)
    cold.run_until_drained(max_steps=100)
    assert a.output_tokens == b.output_tokens and len(a.output_tokens) == 12


# ------------------------------------------------- a chunk's arguments
@pytest.mark.parametrize("rows", [0, 1, 2])
def test_a_chunks_arguments_pack_into_one_vector_and_back(rows):
    ids = np.arange(100, 108, dtype=np.int32)[None]
    tables = [np.arange(6, dtype=np.int32) + 10 * (i + 1)
              for i in range(rows)]
    packed = pack_chunk_args(ids, 2, 16, 7, 6, *tables)
    assert packed.dtype == np.int32 and packed.shape == (4 + 8 + 6 * rows,)
    got = jax.jit(lambda p: unpack_chunk_args(p, 6, rows))(packed)
    np.testing.assert_array_equal(np.asarray(got[0]), ids)
    assert [int(x) for x in got[1:5]] == [2, 16, 7, 6]
    assert len(got) == 5 + rows
    for want, have in zip(tables, got[5:]):
        np.testing.assert_array_equal(np.asarray(have), want)


# ---------------------------------------------------------------- (a)
WINDOW, PAGE = 16, 8


@pytest.fixture(scope="module")
def grouped_engine():
    """A small ``mellum``: three window layers to one full one, so a paged
    pool keeps two page groups (``table`` and ``table_win``)."""
    engine = kind_stack("window_routed", **MELLUM_PERIOD)[2]
    engine._ensure_params(jnp.zeros((1, 2), jnp.int32))
    return engine


def _watch_tables(srv):
    """Check the device tables against the host mirrors wherever a program
    that reads them is queued: before a decode or verify every row, before
    a chunk every row but its own, after a chunk (whose program patched
    its row) every row again. Returns the counts of each check."""
    pool, checked = srv.pool, {"readers": 0, "chunks": 0}

    def same(but=None):
        cs = pool.cache["cache_store"]
        for key in pool._table_keys:
            mirror = pool.table if key == "table" else pool.ring.table
            keep = np.arange(pool.num_slots) != (-1 if but is None else but)
            np.testing.assert_array_equal(np.asarray(cs[key])[keep],
                                          mirror[keep], err_msg=key)

    publish, chunk = pool._publish_stale, pool.run_prefill_chunk

    def publish_spy(own=None):
        publish(own)
        same(but=own)
        checked["readers"] += own is None

    def chunk_spy(engine, ids, slot, *rest):
        logits = chunk(engine, ids, slot, *rest)
        same()
        assert not pool._stale_rows
        checked["chunks"] += 1
        return logits

    pool._publish_stale, pool.run_prefill_chunk = publish_spy, chunk_spy
    return checked


def _step(srv, log):
    """One step, and what its span says it did to the tables."""
    pages0 = srv.pool.pages_allocated
    released0 = len(srv.pool._free_set)
    preempted0 = srv.metrics.preempted
    finished = srv.step()
    d = srv._dispatched
    log.append({"chunk": "chunk" in d, "decode": "decode" in d,
                "admit": "admit" in d, "table_puts": d["table_puts"],
                "allocated": srv.pool.pages_allocated - pages0,
                "released": len(srv.pool._free_set) != released0
                or bool(finished),
                "preempted": srv.metrics.preempted - preempted0})
    srv.check_invariants()


@pytest.mark.parametrize("groups", [1, 2], ids=["one-group", "two-groups"])
def test_device_tables_are_the_mirrors_wherever_a_program_reads_them(
        stack, grouped_engine, groups):
    """Chunks that map fresh pages, decodes that cross a page boundary,
    releases, a preemption, a copy-on-write fork of a shared prefix (one
    group: a ring shares nothing), an aborted step. ``table_puts`` on the
    step's span: 0 on a step that only mapped its chunk's own row, at
    least 1 on the step of the preemption."""
    if groups == 1:
        engine, vocab = stack[2], 64
        paged = {"page_size": 8, "num_pages": 24, "kernel": "off"}
    else:
        engine, vocab = grouped_engine, 128
        paged = {"page_size": PAGE, "num_pages": 40, "prefix_cache": False,
                 "kernel": "off"}
    faults = FaultInjector(seed=0, schedule={"step_host_error": [14]})
    srv = ServingEngine(engine, num_slots=3, prefill_chunk=8,
                        paged_kv=paged, fault_injector=faults)
    checked, log = _watch_tables(srv), []
    rng = np.random.default_rng(8)
    shared = rng.integers(1, vocab, 24).astype(np.int32)
    a = srv.submit(np.concatenate([shared, [3, 4, 5]]).astype(np.int32),
                   max_new_tokens=12)
    b = srv.submit(rng.integers(1, vocab, 5).astype(np.int32),
                   max_new_tokens=20)
    for _ in range(6):
        _step(srv, log)
    # the same 24 tokens and no more: with a prefix cache its three pages
    # are mapped shared, and the last chunk, prefilled again for its
    # logits, forks the page it writes
    c = srv.submit(shared, max_new_tokens=6)
    for _ in range(4):
        _step(srv, log)
    victim = b if b.state is RequestState.RUNNING else a
    srv.preempt(victim.request_id)
    at_preempt = len(log)
    aborted, d = False, None
    for _ in range(200):
        if not (srv.live_count or srv.pending):
            break
        try:
            _step(srv, log)
        except InjectedFault:
            aborted = True             # the pool restarts from sentinels
            cs = srv.pool.cache["cache_store"]
            for key in srv.pool._table_keys:
                assert (np.asarray(cs[key]) == (
                    srv.pool.num_pages if key == "table"
                    else srv.pool.ring.num_pages)).all()
            assert not srv.pool._stale_rows
            d = srv.submit(rng.integers(1, vocab, 20).astype(np.int32),
                           max_new_tokens=4)
    # (the abort fails what was running and serves on from a fresh pool)
    assert aborted and d.state is RequestState.FINISHED
    assert c.state in (RequestState.FINISHED, RequestState.FAILED)
    assert checked["readers"] > 10 and checked["chunks"] >= 8
    if groups == 1:
        assert srv.pool.cow_copies > 0
    else:
        assert srv.pool.ring.recycled > 0
    # a step that only mapped its chunk's own row publishes no table
    own_row = [s for s in log if s["chunk"] and s["allocated"]
               and not (s["admit"] or s["released"] or s["preempted"])]
    quiet = [s for s in own_row if s["table_puts"] == 0]
    assert quiet and len(quiet) >= len(own_row) - sum(
        s["decode"] for s in own_row)       # (a decode may cross a page)
    assert any(s["chunk"] and not s["decode"] and s["allocated"]
               and s["table_puts"] == 0 for s in log)
    # the preemption released a slot's pages: the whole table again, once
    # for the release (counted on the step that follows it)
    assert log[at_preempt]["table_puts"] >= 1 or \
        srv.pool.table_puts > sum(s["table_puts"] for s in log)
