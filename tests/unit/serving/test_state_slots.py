"""A recurrent state as a slot of the pool (``power_retention`` layers on
the contiguous ``SlotPool``): a reused row starts from nothing, a row the
decode program does not run keeps its state bit for bit, preemption and
re-prefill give the same tokens, and what does not compose refuses at
construction, by mechanism."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models.lm_config import transformer_config
from deepspeed_tpu.models.transformer_lm import TransformerLM
from deepspeed_tpu.serving import RequestState, ServingEngine

from .conftest import traced_once

SMALL = dict(vocab_size=96, max_seq_len=128, n_embd=32, n_layer=2, n_head=4,
             n_kv_head=2, head_size=16, ffn_dim=48, dtype=jnp.float32)
CHUNK = 16


@pytest.fixture(scope="module")
def stack():
    cfg = transformer_config("brumby", **SMALL)
    model = TransformerLM(cfg)
    ids = np.random.default_rng(3).integers(1, 96, (3, 80)).astype(np.int32)
    params = model.init({"params": jax.random.PRNGKey(2)},
                        jnp.asarray(ids[:1, :8]),
                        method=model.logits)["params"]
    engine = ds.init_inference(model=model, model_parameters=params,
                               config={"dtype": "float32"})
    return cfg, model, params, engine, ids


def server(engine, slots, **kw):
    return traced_once(ServingEngine(engine, num_slots=slots,
                                     prefill_chunk=CHUNK, **kw))


def record_logits(srv):
    """Every logits array the server samples from, in dispatch order."""
    seen, sample = [], srv._sample_dev

    def spy(logits):
        seen.append(np.asarray(logits))
        return sample(logits)

    srv._sample_dev = spy
    return seen


@pytest.mark.parametrize("second", [40, 12])     # chunked; one bucket
def test_a_reused_row_gives_the_logits_of_a_fresh_server(stack, second):
    """One slot: the second request sits where the first left its state
    (a state is hidden by no length). Its logits, every dispatch, are a
    fresh server's: a first chunk at position 0 reads no state, and a
    bucketed admission overwrites the row."""
    _, _, _, engine, ids = stack
    used = server(engine, 1)
    first = used.submit(ids[0, :50], max_new_tokens=8)
    used.run_until_drained(max_steps=200)
    assert first.state is RequestState.FINISHED
    assert float(jnp.abs(used.pool.cache["cache_store"]["s"]).max()) > 0
    seen_used = record_logits(used)
    a = used.submit(ids[1, :second], max_new_tokens=8)
    used.run_until_drained(max_steps=200)
    fresh = server(engine, 1)
    seen_fresh = record_logits(fresh)
    b = fresh.submit(ids[1, :second], max_new_tokens=8)
    fresh.run_until_drained(max_steps=200)
    assert a.output_tokens == b.output_tokens and len(a.output_tokens) == 8
    assert len(seen_used) == len(seen_fresh)
    for x, y in zip(seen_used, seen_fresh):
        np.testing.assert_array_equal(x, y)


def test_decode_leaves_a_prefilling_rows_state_bitwise_alone(stack):
    """Two requests decode while a third streams in chunk by chunk: after
    every decode dispatch the prefilling row's state is, bit for bit, what
    its last chunk wrote, and the decode program was told the rows that
    run (``state_rows`` on its span is their count)."""
    _, _, _, engine, ids = stack
    srv = server(engine, 3)
    n0 = srv.tracer.events_total    # (the ring is the process's: other
    #                                 servers with a state wrote before us)
    short = [srv.submit(ids[i, :10], max_new_tokens=30) for i in (0, 1)]
    srv.step()
    long = srv.submit(ids[2, :80], max_new_tokens=4)
    eng = srv.engine
    chunk, decode = eng.prefill_chunk, eng._jit_decode
    after_chunk, checked, told = {}, [], []

    def chunk_spy(cache, input_ids, slot, start, length, last_idx):
        logits, cache = chunk(cache, input_ids, slot, start, length, last_idx)
        after_chunk[int(slot)] = np.asarray(
            cache["cache_store"]["s"][:, int(slot)])
        return logits, cache

    def decode_spy(p, cache, tokens, pos, rows):
        assert pos is None        # (PR 35: the cache's own index)
        told.append(np.asarray(rows))
        logits, cache = decode(p, cache, tokens, pos, rows)
        if long.state is RequestState.PREFILLING and long.slot in after_chunk:
            now = np.asarray(cache["cache_store"]["s"][:, long.slot])
            checked.append(np.array_equal(now, after_chunk[long.slot]))
        return logits, cache

    eng.prefill_chunk, eng._jit_decode = chunk_spy, decode_spy
    try:
        srv.run_until_drained(max_steps=300)
    finally:
        eng.prefill_chunk, eng._jit_decode = chunk, decode
    assert long.chunks == 5 and len(checked) >= 4 and all(checked)
    # the rows handed to the program: a slot's own number where it runs,
    # out of range where it does not; never the prefilling row
    during = [rows for rows in told if (rows < 0).any()]
    assert during and all(set(rows[rows >= 0]) <= {0, 1, 2} for rows in told)
    assert all(r.state is RequestState.FINISHED for r in short + [long])
    mine = srv.tracer.events()
    mine = mine[max(len(mine) - (srv.tracer.events_total - n0), 0):]
    spans = [e for e in mine
             if e.get("name") == "serving/decode" and e.get("ph") == "X"]
    assert spans and all("state_rows" in e["args"] for e in spans[-20:])
    assert {e["args"]["state_rows"] for e in spans[-40:]} <= {1, 2, 3}
    steps = [e["args"] for e in mine
             if e.get("name") == "serving/step" and e.get("ph") == "X"
             and "state_rows" in (e.get("args") or {})]
    row_bytes = srv.pool.spec.state_bytes_per_row
    assert steps and all(a["state_bytes"] == 2 * row_bytes * a["state_rows"]
                         for a in steps)
    assert srv.registry.gauge("serving/state_bytes_resident").value \
        == 3 * row_bytes


def test_a_preempted_request_resumes_to_the_same_tokens(stack):
    """Release, re-queue, re-prefill from prompt + answer so far: the
    greedy answer is the unpreempted one."""
    _, _, _, engine, ids = stack
    plain = server(engine, 2)
    want = plain.submit(ids[0, :40], max_new_tokens=16)
    plain.run_until_drained(max_steps=200)
    srv = server(engine, 2)
    req = srv.submit(ids[0, :40], max_new_tokens=16)
    other = srv.submit(ids[1, :20], max_new_tokens=16)
    while len(req.output_tokens) < 6:
        srv.step()
    srv.preempt(req.request_id)
    assert req.state is RequestState.QUEUED and req.slot is None
    srv.run_until_drained(max_steps=300)
    assert req.state is RequestState.FINISHED and req.preemptions == 1
    assert req.output_tokens == want.output_tokens
    assert other.state is RequestState.FINISHED
    srv.check_invariants()


@pytest.mark.parametrize("kw,names", [
    (dict(spec_decode={"k": 2}), ("spec_decode", "rejected draft")),
    (dict(paged_kv=True), ("paged_kv", "snapshot")),
    (dict(paged_kv={"prefix_cache": False}), ("paged_kv", "page")),
    (dict(role="decode"), ("prefill/decode roles", "handoff")),
])
def test_what_does_not_compose_refuses_at_construction(stack, kw, names):
    _, _, _, engine, _ = stack
    with pytest.raises(ValueError) as refusal:
        server(engine, 2, **kw)
    assert all(name in str(refusal.value) for name in names)
    assert "recurrent state" in str(refusal.value)


def test_model_level_refusals_name_their_mechanism(stack):
    cfg, _, params, _, _ = stack
    from deepspeed_tpu.inference.zero_inference import ZeroInferenceEngine

    with pytest.raises(ValueError, match="kv_cache_quant.*float32 state"):
        transformer_config("brumby", **{**SMALL, "kv_cache_quant": True})
    with pytest.raises(ValueError, match="beside attention layers"):
        transformer_config("brumby", **{**SMALL, "layer_types": [
            "power_retention", "full_attention"]})
    with pytest.raises(ValueError, match="head_dim"):
        transformer_config("brumby", **{**SMALL, "head_size": 12})
    with pytest.raises(ValueError, match="layer kinds|recurrent state"):
        ZeroInferenceEngine(cfg, params)


def test_tensor_parallel_serving_refuses(stack):
    from deepspeed_tpu.parallel import mesh as mesh_mod

    _, model, params, _, _ = stack
    if jax.device_count() < 2:
        pytest.skip("needs two devices")
    mesh = mesh_mod.initialize_mesh(model=2, data=jax.device_count() // 2)
    try:
        engine = ds.init_inference(model=model, model_parameters=params,
                                   config={"dtype": "float32"}, mesh=mesh)
        with pytest.raises(ValueError, match="tensor-parallel.*state"):
            ServingEngine(engine, num_slots=2, prefill_chunk=CHUNK)
    finally:
        mesh_mod.reset_mesh()


def test_the_rows_are_put_again_exactly_when_the_running_set_changes(stack):
    """The decode program's ``rows`` stay on the device beside the slots
    they were built from (PR 35): over a drive in which requests join, stream
    in chunk by chunk and retire, one put of a host array for every change
    of the running set and none on a step that decodes the same rows."""
    _, _, _, engine, ids = stack
    srv = server(engine, 3)
    eng = srv.engine
    decode, commit = eng._jit_decode, srv._cur_commit
    told, puts = [], []

    def decode_spy(p, cache, tokens, pos, rows):
        told.append((rows, tuple(int(r) for r in np.asarray(rows))))
        return decode(p, cache, tokens, pos, rows)

    def commit_spy(arr):
        if isinstance(arr, np.ndarray):       # (sampled tokens are device)
            puts.append(tuple(int(r) for r in arr))
        return commit(arr)

    eng._jit_decode, srv._cur_commit = decode_spy, commit_spy
    try:
        reqs = [srv.submit(ids[0, :10], max_new_tokens=25),
                srv.submit(ids[1, :12], max_new_tokens=6)]
        for _ in range(4):
            srv.step()
        reqs.append(srv.submit(ids[2, :60], max_new_tokens=5))
        srv.run_until_drained(max_steps=300)
    finally:
        eng._jit_decode, srv._cur_commit = decode, commit
    assert all(r.state is RequestState.FINISHED for r in reqs)
    sets = [rows for _, rows in told]
    changes = [rows for before, rows in zip([None] + sets, sets)
               if rows != before]
    assert len(sets) > 2 * len(changes) > 4
    assert puts == changes
    for (a, rows_a), (b, rows_b) in zip(told, told[1:]):
        assert (a is b) == (rows_a == rows_b)
