"""A step that carries a prefill chunk beside running slots as ONE program
(PR 48: ``PagedKVPool.run_chunk_decode`` over
``TransformerLM.chunk_beside_decode``), held against the same server kept
to the two programs it replaces (``run_prefill_chunk`` then ``run_decode``),
for each cache kind the page pool serves: K/V pages; a window ring beside
the full pages under a routed FFN; latent pages behind a leading dense layer
with a shared expert; a state group beside K/V pages. float32 on the CPU,
the kernels in interpret mode (``kernel: "on"``).

What "the same" means where. With the CPU's compiler told to leave the
arithmetic as written (``xla_backend_optimization_level`` 0; op by op under
``jax.disable_jit`` reads the same and takes four times as long) the one
pass gives every row the bits the two passes give it: decode logits, page
leaves, state rows. At the default level LLVM vectorises and contracts the
one program's elementwise operations otherwise than the two programs', so
float32 leaves agree to rounding (2e-6 read, held to 2e-5) for three of
the kinds and to the bit for plain K/V pages; tokens, tables and ``index``
are equal outright everywhere."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.serving import ServingEngine
from deepspeed_tpu.serving.request import RequestState
from deepspeed_tpu.telemetry import Tracer
from tests.unit.kinds import kind_stack

from .conftest import traced_once

# chunks of 4 in pages of 8: a chunk ends inside a page and on a page
# boundary in turn
PAGE, CHUNK, SLOTS, CTX = 8, 4, 3, 64
# the case's name -> its row of tests/unit/kinds.py
KINDS = {"kv_pages": "plain", "window_group_routed_ffn": "window_routed",
         "latent_pages_dense_first": "latent_routed",
         "state_group": "state_group"}
# compiled float32 against compiled float32 of another program text
ATOL = {"kv_pages": 0.0}
PROMPTS = (5, 14, 9)        # bucketed; chunks of 4, 4, 4, 2; of 4, 4, 1
FUSED_STEPS = 3 + 2         # every chunk but a prompt's last


def _servers(engine, **paged):
    """The fused server, and the same server without the one program:
    every chunk step of it runs the two it replaces, which are the fused
    server's own two (a prompt's last chunk and a plain decode step run
    them there too), traced once."""
    fused, two = (traced_once(ServingEngine(
        engine, num_slots=SLOTS, prefill_chunk=CHUNK,
        prefill_token_budget=2 * CHUNK, max_queue_depth=8,
        paged_kv={"kernel": "on", "page_size": PAGE, "prefix_cache": False,
                  **paged}, tracer=Tracer())) for _ in range(2))
    two.pool._paged_chunk_decode_jit = None
    assert fused._fuses_chunks and not two._fuses_chunks
    return {True: fused, False: two}


def _pool_state(srv):
    """Everything the device holds of the pool, with the host's mirrors."""
    state = {key: np.asarray(leaf)
             for key, leaf in srv.pool.cache["cache_store"].items()}
    state["starts"] = srv.pool.starts.copy()
    state["host_table"] = srv.pool.table.copy()
    if srv.pool.ring is not None:
        state["host_table_win"] = srv.pool.ring.table.copy()
    return state


def _columns(srv, slot, lo, hi):
    """Positions ``[lo, hi)`` of ``slot`` in every page leaf, through the
    host's tables: (layers, ..., hi - lo) a leaf."""
    cs, out = srv.pool.cache["cache_store"], {}
    for key in ("k", "v", "c", "k_win", "v_win"):
        if key not in cs:
            continue
        pool = srv.pool.ring if key.endswith("_win") else srv.pool
        pages = pool.table[slot][:-(-hi // PAGE)]
        if (pages[lo // PAGE:] == pool.num_pages).any():
            continue        # (a ring that has let the entry go)
        leaf = np.asarray(cs[key])[:, pages]
        cols = np.concatenate([leaf[:, i, ..., :PAGE]
                               for i in range(len(pages))], axis=-1)
        out[key] = cols[..., lo:hi]
    return out


@pytest.fixture(scope="module", params=sorted(KINDS))
def served(request):
    """One scenario a cache kind on two servers in lockstep: a request
    running, then two long prompts whose chunks ride beside it. Kept: the
    pool after every step, what every ``serving/step`` said, and a chunk's
    columns as the step that wrote them left them."""
    kind = request.param
    engine = kind_stack(KINDS[kind])[2]
    rng = np.random.default_rng(17)
    prompts = [rng.integers(1, 64, size=n).astype(np.int32) for n in PROMPTS]
    servers = _servers(engine)
    reqs, states, written = {}, [], []
    for fused, srv in servers.items():
        reqs[fused] = [srv.submit(prompts[0], max_new_tokens=14)]
        srv.step()
        reqs[fused] += [srv.submit(p, max_new_tokens=3) for p in prompts[1:]]
    while any(r.state is not RequestState.FINISHED
              for rs in reqs.values() for r in rs):
        before = [(r, r.prefill_pos) for r in reqs[True][1:]
                  if r.state is RequestState.PREFILLING]
        for srv in servers.values():
            srv.step()
        assert len(states) < 60
        states.append({f: _pool_state(servers[f]) for f in servers})
        for r, pos in before:
            if r.slot is None or r.prefill_pos == pos:
                continue
            written.append((r, pos, r.prefill_pos, _columns(
                servers[True], r.slot, pos, r.prefill_pos)))
            if r.state is RequestState.PREFILLING:
                continue
            # the prompt is in: what each of its chunks wrote, read again
            for req, lo, hi, cols in written:
                again = _columns(servers[True], r.slot, lo, hi) \
                    if req is r else {}
                for key in again:
                    np.testing.assert_array_equal(again[key], cols[key],
                                                  err_msg=f"{key} {lo}:{hi}")
    for srv in servers.values():
        srv.check_invariants()
    return {"kind": kind, "servers": servers, "reqs": reqs, "states": states,
            "written": written,
            "steps": {f: [e["args"] for e in servers[f].tracer.events()
                          if e["name"] == "serving/step"] for f in servers}}


def test_the_token_streams_are_the_two_programs(served):
    one, two = (served["reqs"][f] for f in (True, False))
    assert [list(r.output_tokens) for r in one] \
        == [list(r.output_tokens) for r in two]
    assert [len(r.output_tokens) for r in one] == [14, 3, 3]


def test_the_pool_after_every_step_is_the_two_programs(served):
    """Page tables, ``index`` and the host's mirrors equal outright; page
    leaves and state rows to the bit for K/V pages and to float32 rounding
    for the kinds whose compiled text the CPU's compiler fuses otherwise
    (the module's note; as written they are equal to the bit, below)."""
    atol = ATOL.get(served["kind"], 2e-5)
    for n, both in enumerate(served["states"]):
        a, b = both[True], both[False]
        assert sorted(a) == sorted(b)
        for key in a:
            if a[key].dtype.kind == "f" and atol:
                np.testing.assert_allclose(a[key], b[key], rtol=0, atol=atol,
                                           err_msg=f"{key} after step {n + 2}")
            else:
                np.testing.assert_array_equal(
                    a[key], b[key], err_msg=f"{key} after step {n + 2}")


def test_every_chunk_but_a_prompts_last_rides_the_decode_program(served):
    one, two = (served["steps"][f] for f in (True, False))
    assert len(one) == len(two) == served["servers"][True].step_id
    assert sum(s.get("fused", 0) for s in one) == FUSED_STEPS
    assert not any(s.get("fused") for s in two)
    counter = served["servers"][True].registry.counter("serving/fused_steps")
    assert counter.value == FUSED_STEPS
    for s1, s2 in zip(one, two):
        for key in ("chunk", "decode", "tokens", "admit"):
            assert s1.get(key) == s2.get(key)
        if s1.get("fused"):
            # a chunk that does not end its prompt, beside a running slot
            assert s1["chunk"] == CHUNK and s1["decode"] >= 1
            assert s1["device_calls"] == s2["device_calls"] - 1
        elif "chunk" in s1 and "decode" in s1:
            # a prompt's last chunk: the slot it finishes decodes in the
            # same step, from the token the chunk's head chose
            assert s1["chunk"] < CHUNK
            assert s1["device_calls"] == s2["device_calls"]


def test_the_routed_ffn_counts_both_groups_rows_in_one_call(served):
    one, two = (served["steps"][f] for f in (True, False))
    if not any("moe_assignments" in s for s in two):
        pytest.skip("no routed FFN in this kind")
    for s1, s2 in zip(one, two):
        assert s1.get("moe_assignments") == s2.get("moe_assignments")
        assert s1.get("moe_bias_reordered") == s2.get("moe_bias_reordered")
        if s1.get("fused"):
            assert 2 * s1["moe_layer_calls"] == s2["moe_layer_calls"]
            assert s1["moe_experts_touched"] <= s2["moe_experts_touched"]
        else:
            assert s1.get("moe_layer_calls") == s2.get("moe_layer_calls")


def test_the_dead_decode_row_never_lands_on_the_chunks_columns(served):
    """The decode row of the slot in mid-prefill writes its dead column at
    the index AS THE CHUNK LEAVES IT, ``start + length``: behind the
    chunk's columns. Chunks that end inside a page and on a page boundary
    were both written beside a running slot, and every one read the same
    again once its prompt was in (the fixture's check)."""
    ends = {hi % PAGE for _, _, hi, _ in served["written"]}
    assert {0, 4} <= ends
    assert all(cols for *_, cols in served["written"])


def _seated_state(srv):
    """What the seated requests can read of the pool: the host's mirrors,
    and each seated slot's device index and written columns. (A free slot's
    index and a freed page's bytes are whatever was last there: a chunk
    written and then preempted leaves other garbage than one dropped.)"""
    state = {"starts": srv.pool.starts.copy(),
             "host_table": srv.pool.table.copy()}
    index = np.asarray(srv.pool.cache["cache_store"]["index"])
    for slot in sorted(srv._slot_req):
        state[f"index[{slot}]"] = index[slot]
        if srv.pool.starts[slot]:
            for key, cols in _columns(srv, slot, 0,
                                      int(srv.pool.starts[slot])).items():
                state[f"{key}[{slot}]"] = cols
    return state


# name: (prompts that run first, who is preempted [first..., long], what
#        the long prompt's first eight steps say: (chunk, decode, fused))
PRESSED = {
    # paging the long prompt's seventh chunk in (not its last) takes the
    # ONLY running slot away: no decode follows, the chunk goes alone
    "the_only_running_slot": ((21,), [1, 0], [(CHUNK, 1, 1)] * 6 + [
        (CHUNK, None, None), (2, 1, 0)]),
    # ... takes one of two away: the chunk rides beside the other
    "one_of_two_running_slots": ((5, 2), [0, 1, 0], [(CHUNK, 2, 1)] * 6 + [
        (CHUNK, 1, 1), (2, 2, 0)]),
    # paging a running slot's decode column in takes the CHUNK's request
    # away, after the chunk was prepared: dropped, a plain decode
    "the_chunks_own_request": ((5, 1), [0, 0, 1], [(CHUNK, 2, 1)] * 6 + [
        (CHUNK, 2, 0), (None, 2, 0)]),
}


@pytest.fixture(scope="module")
def pressed():
    """Two servers on a pool of seven pages, fused and held to two
    programs; every scenario drains them, so the next finds them empty."""
    engine = kind_stack(KINDS["kv_pages"])[2]
    return _servers(engine, num_pages=7)


@pytest.mark.parametrize("name", sorted(PRESSED))
def test_a_chunk_under_page_pressure_is_the_two_programs(pressed, name):
    """A pool too small for what is seated: the long prompt's four pages
    were there when it was granted and the running slots have grown since.
    What is preempted, when, the tokens, and after every step the tables,
    the ``index`` and the written columns of every seated slot are the
    two-program server's, and no prepared chunk is left behind (``step``
    asserts it)."""
    firsts, preempted, shape = PRESSED[name]
    rng = np.random.default_rng(23)
    prompts = [rng.integers(1, 64, size=n).astype(np.int32)
               for n in firsts + (30,)]
    reqs = {}
    for fused, srv in pressed.items():
        assert srv.pool.free_page_count == 7 and not srv._slot_req
        reqs[fused] = [srv.submit(p, max_new_tokens=24) for p in prompts[:-1]]
        while any(r.state is not RequestState.RUNNING for r in reqs[fused]):
            srv.step()
        reqs[fused].append(srv.submit(prompts[-1], max_new_tokens=3))
    step0, steps = pressed[True].step_id, 0
    assert pressed[False].step_id == step0
    while any(r.state is not RequestState.FINISHED
              for rs in reqs.values() for r in rs):
        for srv in pressed.values():
            srv.step()
            srv.check_invariants()
        steps += 1
        assert steps < 100
        a, b = (_seated_state(pressed[f]) for f in (True, False))
        assert sorted(a) == sorted(b)
        for key in a:
            np.testing.assert_array_equal(a[key], b[key],
                                          err_msg=f"{key} after step {steps}")
    one, two = (reqs[f] for f in (True, False))
    assert [list(r.output_tokens) for r in one] \
        == [list(r.output_tokens) for r in two]
    assert [len(r.output_tokens) for r in one] == [24] * len(firsts) + [3]
    assert [r.preemptions for r in one] == [r.preemptions for r in two] \
        == preempted
    said = [(s.get("chunk"), s.get("decode"), s.get("fused"))
            for s in (e["args"] for e in pressed[True].tracer.events()
                      if e["name"] == "serving/step")
            if s["step"] > step0]
    assert said[:8] == shape


# (plain K/V pages are held to the bit at the default level, above)
@pytest.mark.parametrize("kind", sorted(k for k in KINDS if k not in ATOL))
def test_as_written_one_pass_gives_each_row_the_bits_of_two(
        kind, users_compiles):
    """``chunk_beside_decode`` against ``prefill_chunk`` (through a table
    row) then ``decode_paged`` on the same pool, compiled with the
    arithmetic left as written: the decode rows' logits and every leaf of
    the pool equal to the bit. (The chunk's logits to rounding: the head
    over one row is a matrix-vector product, over B + 1 rows a matrix
    product, two routines.) Slot 1 is in mid-prefill (8 of its tokens in, a
    chunk of 4 to go: it ends inside a page), slots 0 and 2 run."""
    model, params, _ = kind_stack(KINDS[kind])
    spec = model.kv_cache_spec()
    pages, per_slot = 12, CTX // PAGE
    ring = 3 * (16 // PAGE + 1) if spec.groups is not None else None
    rng = np.random.default_rng(3)
    cs = {key: jnp.asarray(rng.standard_normal(leaf.shape), leaf.dtype)
          for key, leaf in spec.paged_cache(pages, PAGE, ring,
                                            num_slots=SLOTS).items()}
    table = np.full((SLOTS, per_slot), pages, np.int32)
    table[0, :2], table[1, :2], table[2, :1] = [0, 1], [2, 3], [4]
    tables = {"table": jnp.asarray(table)}
    if ring is not None:
        tables["table_win"] = jnp.asarray(
            np.where(table == pages, ring, table))
    slot, start = 1, 8
    index = jnp.asarray([9, start, 3], jnp.int32)
    ids = jnp.asarray(rng.integers(1, 64, (1, CHUNK)), jnp.int32)
    token = jnp.asarray([5, 6, 7], jnp.int32)
    state = bool(spec.state_leaves)
    mutable = ["cache", "stats"] if model.config.n_experts else ["cache"]

    def given(t):
        return t if ring is not None else t["table"]

    row = {key: t[slot:slot + 1] for key, t in tables.items()}
    after = index.at[slot].set(start + CHUNK)
    at = jnp.asarray([start], jnp.int32)
    running = {"rows": jnp.asarray([0, -1, 2])} if state else {}

    def apply(vals, *args, **kw):
        out, var = model.apply({"params": params,
                                "cache": {"cache_store": vals}}, *args,
                               mutable=mutable, **kw)
        return out, var["cache"]["cache_store"]

    def two(cs):
        chunk, mid = apply(
            dict(cs, index=at), ids, at, CHUNK - 1, table=given(row),
            method=model.prefill_chunk,
            **({"rows": jnp.asarray([slot])} if state else {}))
        logits, new = apply(
            dict(mid, index=after), token[:, None], after, given(tables),
            method=model.decode_paged, **running)
        return chunk, logits, new

    def one(cs):
        (chunk, logits), new = apply(
            dict(cs, index=after), ids, at, CHUNK - 1, given(row), token,
            after, given(tables), method=model.chunk_beside_decode,
            **running,
            **({"chunk_row": jnp.asarray([slot])} if state else {}))
        return chunk, logits, new

    as_written = dict(users_compiles, xla_backend_optimization_level=0)
    (chunk2, logits2, cs2), (chunk1, logits1, cs1) = (
        jax.jit(f).lower(cs).compile(compiler_options=as_written)(cs)
        for f in (two, one))
    np.testing.assert_allclose(np.asarray(chunk1), np.asarray(chunk2),
                               rtol=0, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(logits1), np.asarray(logits2))
    assert sorted(cs1) == sorted(cs2)
    for key in cs1:
        np.testing.assert_array_equal(np.asarray(cs1[key]),
                                      np.asarray(cs2[key]), err_msg=key)
    np.testing.assert_array_equal(np.asarray(cs1["index"]),
                                  np.asarray(after) + 1)
