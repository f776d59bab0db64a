"""Latent pages (``KVCacheSpec.latent``: one cached row a token that every
head reads) in the serving pools: the same tokens from the contiguous
``SlotPool``, the page pool's dense composition and its kernels; a
preempted request re-prefills into latent pages and the audit stays clean;
a prefix hit maps latent pages like any; the counters a dispatch leaves;
and what does not compose refuses at construction, by mechanism."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models.lm_config import (TransformerConfig,
                                            transformer_config)
from deepspeed_tpu.models.transformer_lm import TransformerLM
from deepspeed_tpu.serving import RequestState, ServingEngine
from deepspeed_tpu.telemetry.tracer import Tracer
from tests.unit.kinds import init_params, kind_stack, kind_widths

from .conftest import Servers, spans_since, traced_once

# the latent row of tests/unit/kinds.py with two routed layers behind the
# dense one, and room for a prompt of five chunks of 16 over a vocabulary
# of 96
OVER = dict(vocab_size=96, max_seq_len=128, n_layer=3)
SMALL = kind_widths("latent_routed", **OVER)
CHUNK = 16
POOLS = {"contiguous": False,
         "paged": {"kernel": "off", "page_size": 16, "prefix_cache": False},
         "kernel": {"kernel": "on", "page_size": 16, "prefix_cache": False}}


@pytest.fixture(scope="module")
def stack():
    model, params, engine = kind_stack("latent_routed", **OVER)
    ids = np.random.default_rng(3).integers(1, 96, (3, 80)).astype(np.int32)
    return model.config, model, params, engine, ids


@pytest.fixture(scope="module")
def server(stack):
    """``server(pool)``: the module's one server on that pool (with a
    tracer of its own), emptied between the cases that take it."""
    engine = stack[3]
    return Servers(lambda pool: ServingEngine(
        engine, num_slots=3, prefill_chunk=CHUNK, paged_kv=POOLS[pool],
        tracer=Tracer()))


def drained(srv, prompts, new_tokens=8):
    reqs = [srv.submit(p, max_new_tokens=new_tokens) for p in prompts]
    srv.run_until_drained(max_steps=400)
    assert all(r.state is RequestState.FINISHED for r in reqs)
    srv.check_invariants()
    return [list(r.output_tokens) for r in reqs]


def prompts_of(ids):
    # a bucket's, two of one bucket, chunked ones
    return [ids[0, :9], ids[1, :30], ids[2, 3:31], ids[0, :50], ids[1, :77]]


def test_every_pool_serves_the_same_tokens(stack, server):
    """Bucketed admission, chunked prefill and decode on the contiguous
    pool (it takes the latent row like any leaf), the page pool's dense
    composition and its kernels: one greedy answer, which is the no-cache
    forward's."""
    _, model, params, _, ids = stack
    outs = {pool: drained(server(pool), prompts_of(ids)) for pool in POOLS}
    assert outs["contiguous"] == outs["paged"] == outs["kernel"]
    for prompt, out in zip(prompts_of(ids), outs["kernel"]):
        seq = jnp.asarray([list(prompt) + out])
        lg = model.apply({"params": params}, seq, method=model.logits)[0]
        want = np.asarray(jnp.argmax(lg[len(prompt) - 1:-1], -1))
        assert out == want.tolist()


def test_the_pool_holds_one_leaf_of_whole_pages(server):
    srv = server("kernel")
    cs = srv.pool.cache["cache_store"]
    assert set(cs) == {"c", "index", "table"}            # no k, no v
    assert cs["c"].shape == (3, srv.pool.num_pages, 24, 128)
    assert srv.pool.page_nbytes == 3 * 24 * 128 * 4
    assert set(server("contiguous").pool.cache["cache_store"]) \
        == {"c", "index"}


@pytest.mark.parametrize("pool", ["paged", "kernel"])
def test_a_preempted_request_re_prefills_into_latent_pages(stack, server,
                                                           pool):
    ids = stack[4]
    want = drained(server(pool), [ids[0, :40]], 12)[0]
    srv = server(pool)
    req = srv.submit(ids[0, :40], max_new_tokens=12)
    other = srv.submit(ids[1, :20], max_new_tokens=12)
    while len(req.output_tokens) < 5:
        srv.step()
    pages_before = srv.pool.num_pages - srv.pool.free_page_count
    srv.preempt(req.request_id)
    assert req.preemptions == 1
    assert srv.pool.num_pages - srv.pool.free_page_count < pages_before
    srv.check_invariants()
    srv.run_until_drained(max_steps=300)
    assert req.state is RequestState.FINISHED and list(req.output_tokens) \
        == want
    assert other.state is RequestState.FINISHED
    srv.check_invariants()
    assert srv.pool.free_page_count == srv.pool.num_pages
    assert not srv.pool.consistency_errors()


def test_a_prefix_hit_maps_latent_pages(stack, server):
    """A latent page is position-indexed like a K/V page: the trie hands
    the second request the first's pages, and its answer is what a server
    without a trie gives."""
    _, _, _, engine, ids = stack
    shared = ids[0, :48]
    second = np.concatenate([shared, ids[1, :10]])
    want = drained(server("kernel"), [second])[0]
    srv = traced_once(ServingEngine(
        engine, num_slots=2, prefill_chunk=CHUNK,
        paged_kv={"kernel": "on", "page_size": 16, "prefix_cache": True}))
    drained(srv, [np.concatenate([shared, ids[2, :7]])])
    req = srv.submit(second, max_new_tokens=8)
    srv.run_until_drained(max_steps=200)
    assert req.prefix_hit_tokens == 48
    assert list(req.output_tokens) == want
    srv.check_invariants()


def test_the_dispatch_spans_count_latent_rows(stack, server):
    """``latent_tokens_read`` / ``latent_rows_written`` on the decode,
    chunk and admission spans, the step's totals with the bytes they stand
    for, and the gauge of mapped pages."""
    ids = stack[4]
    srv = server("kernel")
    n0 = srv.tracer.events_total
    a = srv.submit(ids[0, :40], max_new_tokens=4)      # chunked: 16, 16, 8
    b = srv.submit(ids[1, :9], max_new_tokens=4)       # a bucket's
    srv.run_until_drained(max_steps=100)
    spans, events = {}, spans_since(srv, n0)
    for e in events:
        if "latent_tokens_read" in (e.get("args") or {}):
            spans.setdefault(e["name"], []).append(e["args"])
    chunks = spans["serving/prefill_chunk"]
    assert [(c["latent_tokens_read"], c["latent_rows_written"])
            for c in chunks] == [(16, 16), (32, 16), (40, 8)]
    admits = spans.get("serving/prefill_batch", []) \
        + spans.get("serving/admit", [])
    assert [(c["latent_tokens_read"], c["latent_rows_written"])
            for c in admits] == [(0, 9)]
    for d in spans["serving/decode"]:
        assert d["latent_rows_written"] == d["live"] >= 1
        assert d["latent_tokens_read"] >= d["live"]
    # from one decode to the next every running slot sees one row more
    decodes = spans["serving/decode"]
    steady = [(x, y) for x, y in zip(decodes, decodes[1:])
              if x["live"] == y["live"]]
    assert steady and all(
        y["latent_tokens_read"] - x["latent_tokens_read"] == x["live"]
        for x, y in steady)
    # the first decode after the prompt of 9: its rows and its own token
    assert decodes[0]["live"] == 1 and decodes[0]["latent_tokens_read"] == 10
    steps = [e["args"] for e in events if e["name"] == "serving/step"
             and "latent_tokens_read" in (e.get("args") or {})]
    assert steps and all(
        s["latent_bytes_read"] == s["latent_tokens_read"] * 24 * 4 * 3
        for s in steps)
    assert a.state is b.state is RequestState.FINISHED
    srv.registry.snapshot()
    assert srv.registry.gauge("serving/latent_pages_mapped").value == 0.0
    hold = srv.submit(ids[2, :20], max_new_tokens=30)
    for _ in range(4):
        srv.step()
    srv.registry.snapshot()
    assert srv.registry.gauge("serving/latent_pages_mapped").value == 2.0
    srv.cancel(hold.request_id)


def test_the_read_spans_count_blocks_and_the_pages_in_them(stack, server,
                                                           monkeypatch):
    """``pool_reads`` on a latent pool's decode and chunk spans is the
    read's grid steps, which are BLOCKS of ``G`` pages (``G`` from the
    dispatch's rows), and ``pool_read_pages`` the pages in them: both as
    the device's own work list has them (``live_pages`` /
    ``page_blocks``) at the dispatch, pages over steps x ``G`` the fill."""
    from deepspeed_tpu.ops.attention.latent_attention import (
        call_rows, page_blocks, pages_a_step)
    from deepspeed_tpu.ops.attention.paged_attention import live_pages

    cfg, _, _, _, ids = stack
    srv = server("kernel")
    n0 = srv.tracer.events_total
    pool, record = srv.pool, []
    count = pool.pages_read

    def pages_read(rows, slots=None, starts=None):
        work = count(rows, slots, starts)
        G = pages_a_step(call_rows(rows, cfg.n_head), cfg.latent,
                         cfg.kv_lora_rank, 128, jnp.float32)
        assert G == pool.pages_a_read_step(rows)
        if slots is None:
            slots, starts = np.arange(pool.num_slots), pool.positions()
        live = live_pages(jnp.asarray(starts, jnp.int32),
                          jnp.asarray(pool.table[np.asarray(slots)]), rows,
                          16, pool.num_pages)[3]
        blocks = int(page_blocks(live, G, pool.pages_per_slot)[2])
        assert work == (blocks, int(np.count_nonzero(live)), int(live.sum()))
        record.append((work, G))
        return work

    monkeypatch.setattr(pool, "pages_read", pages_read)
    drained(srv, [ids[1, :77], ids[0, :9]])
    spans = [e["args"] for e in spans_since(srv, n0)
             if "pool_reads" in (e.get("args") or {})
             and e["name"] in ("serving/decode", "serving/prefill_chunk")]
    assert len(spans) == len(record) > 8
    assert sorted((a["pool_reads"], a["read_slots"], a["pool_read_pages"])
                  for a in spans) == sorted(work for work, _ in record)
    for (steps, slots, pages), G in record:
        assert slots <= steps <= pages <= steps * G
    # a slot's several pages were one step, and a slot's pages two steps
    assert any(steps < pages for (steps, _, pages), _ in record)
    assert any(steps > slots for (steps, slots, _), _ in record)


# ---------------------------------------------------------------------------
# what does not compose yet refuses at construction, by mechanism
# ---------------------------------------------------------------------------
def _latent_only(**over):
    """Latent attention without a routed FFN: the latent refusals are the
    cache's own, not the router's."""
    return dict(vocab_size=96, max_seq_len=64, n_embd=32, n_layer=2,
                n_head=4, kv_lora_rank=16, qk_nope_head_dim=8,
                qk_rope_head_dim=8, v_head_dim=8, pos_emb="rotary",
                norm="rmsnorm", activation="swiglu", qkv_bias=False,
                mlp_bias=False, tie_word_embeddings=False,
                dtype=jnp.float32, **over)


@pytest.mark.parametrize("over,message", [
    (dict(kv_cache_quant=True), "kv_cache_quant quantizes K/V columns"),
    (dict(kv_cache_quant=True, kv_cache_packed=True),
     "kv_cache_quant quantizes K/V columns"),
    (dict(int8_weights=True), "int8_weights does not reach latent"),
    (dict(layer_types=("full_attention",) * 2), "knows no layer kinds"),
    (dict(pos_emb="learned"), "shared rotary key"),
    (dict(qk_rope_head_dim=0), "needs qk_nope_head_dim"),
])
def test_a_configuration_that_does_not_compose_refuses(over, message):
    with pytest.raises(ValueError, match=message):
        TransformerConfig(**{**_latent_only(), **over})


def test_mlp_layer_types_are_dense_layers_then_sparse_ones():
    sizes = {k: v for k, v in SMALL.items() if k != "first_k_dense"}
    cfg = transformer_config("moonlight", **{
        **sizes, "n_layer": 4,
        "mlp_layer_types": ["dense", "dense", "sparse", "sparse", "sparse"]})
    assert cfg.first_k_dense == 2 and cfg.dense_layers().n_experts == 0
    with pytest.raises(ValueError, match="leading dense layers, then"):
        transformer_config("moonlight", **{
            **sizes, "mlp_layer_types": ["sparse", "dense", "sparse"]})
    with pytest.raises(ValueError, match="first_k_dense=3 names"):
        TransformerConfig(**{**dataclasses.asdict(cfg), "first_k_dense": 3,
                             "n_layer": 3})
    with pytest.raises(ValueError, match="scoring_func"):
        TransformerConfig(scoring_func="tanh")
    with pytest.raises(ValueError, match="routed_scaling_factor multiplies"):
        TransformerConfig(routed_scaling_factor=2.0)


@pytest.fixture(scope="module")
def latent_engine():
    model = TransformerLM(TransformerConfig(**_latent_only()))
    params = init_params(model, seed=0)
    return ds.init_inference(model=model, model_parameters=params,
                             config={"dtype": "float32"})


@pytest.mark.parametrize("kw,message", [
    (dict(spec_decode={"k": 2}), "spec_decode does not compose with latent"),
    (dict(role="prefill", paged_kv=True),
     "prefill/decode roles does not compose with latent"),
    (dict(role="decode", paged_kv=True),
     "prefill/decode roles does not compose with latent"),
])
def test_a_server_option_that_does_not_compose_refuses(latent_engine, kw,
                                                       message):
    with pytest.raises(ValueError, match=message):
        ServingEngine(latent_engine, num_slots=2, **kw)


def test_tensor_parallel_serving_refuses(latent_engine, monkeypatch):
    class Mesh:
        shape = {"model": 2, "data": 1}

    monkeypatch.setattr(latent_engine, "mesh", Mesh(), raising=False)
    with pytest.raises(ValueError, match="tensor-parallel serving does not "
                                         "compose with latent"):
        ServingEngine(latent_engine, num_slots=2)


def test_zero_inference_refuses():
    from deepspeed_tpu.inference.zero_inference import ZeroInferenceEngine

    with pytest.raises(ValueError, match="latent attention's one cached row"):
        ZeroInferenceEngine(TransformerConfig(**_latent_only()), {})


def test_a_latent_model_without_experts_serves(latent_engine):
    """The cache is the attention's, not the router's: a plain FFN behind
    latent attention serves on latent pages too."""
    ids = np.random.default_rng(0).integers(1, 96, (30,)).astype(np.int32)
    outs = [drained(traced_once(ServingEngine(
        latent_engine, num_slots=2, prefill_chunk=CHUNK, paged_kv=paged)),
                    [ids, ids[:7]], 6)
            for paged in (False, {"kernel": "on", "page_size": 16})]
    assert outs[0] == outs[1]


def test_generate_decodes_through_the_dense_latent_cache(stack):
    """``InferenceEngine.generate`` (no server): prefill writes the rows
    of a contiguous cache, every further token reads them absorbed."""
    _, model, params, engine, ids = stack
    out = np.asarray(engine.generate(ids[:2, :20], max_new_tokens=6))
    for b in range(2):
        lg = model.apply({"params": params}, jnp.asarray(out[b:b + 1]),
                         method=model.logits)[0]
        assert out[b, 20:].tolist() \
            == np.asarray(jnp.argmax(lg[19:-1], -1)).tolist()
