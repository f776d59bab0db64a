"""A page pool of layer groups: the full group beside a window ring
(``PagedKVPool`` with ``KVCacheSpec.groups``). The model is the small
``mellum`` preset (two periods of three sliding layers and one full layer,
window 16, pages of 8); float32 on the CPU, kernels in interpret mode."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models.cache_kinds import kv_cache_groups
from deepspeed_tpu.models.lm_config import transformer_config
from deepspeed_tpu.models.transformer_lm import TransformerLM
from deepspeed_tpu.serving import ServingEngine
from deepspeed_tpu.serving.paged_pool import PagedKVPool, PagePoolExhausted

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf.reference import mellum as ref  # noqa: E402

from tests.unit.kinds import (MELLUM_PERIOD, kind_stack,  # noqa: E402
                              kind_widths)

from .conftest import traced_once, watch_kernel_reads  # noqa: E402

WINDOW, PAGE, CTX = 16, 8, 128
# two periods of tests/unit/kinds.py's
OVER = dict(MELLUM_PERIOD, n_layer=8,
            layer_types=MELLUM_PERIOD["layer_types"] * 2)
SMALL = kind_widths("window_routed", **OVER)
# float32 at "highest" against float32 through pages, chunks and kernels:
# logits of size ~3 agree to ~1e-5. A key one position outside the window,
# a stale page or the other rotary table moves them by ~1e-1
ATOL = 2e-4


@pytest.fixture(scope="module")
def stack():
    model, params, engine = kind_stack("window_routed", **OVER)
    cfg = model.config
    assert (cfg.max_seq_len, cfg.sliding_window) == (CTX, WINDOW)
    engine._ensure_params(jnp.zeros((1, 2), jnp.int32))
    logits_fn = ref.make_forward(
        n_head=cfg.n_head, n_kv_head=cfg.n_kv_head, head_dim=cfg.head_dim,
        layer_types=cfg.layer_types, sliding_window=cfg.sliding_window,
        rope_parameters=cfg.rope_parameters,
        experts_per_token=cfg.experts_per_token)
    return cfg, model, params, engine, logits_fn


def _pool(stack, kernel):
    """A new pool of three slots and forty pages, bound; the programs of
    every such pool of the module (the churn servers' among them) are
    traced once (``traced_once``)."""
    pool = PagedKVPool(stack[1].kv_cache_spec(), num_slots=3, num_pages=40,
                       page_size=PAGE, prefix_cache=False, kernel=kernel)
    pool.bind_engine(stack[3])
    return traced_once(pool)


def test_groups_of_the_spec(stack):
    cfg, model = stack[0], stack[1]
    assert kv_cache_groups(cfg) == (("", (3, 7), 0),
                                    ("_win", (0, 1, 2, 4, 5, 6), WINDOW))
    spec = model.kv_cache_spec()
    cache = jax.eval_shape(lambda: spec.paged_cache(10, PAGE, 6))
    assert cache["k"].shape == (2, 10, 2, 32, 128)
    assert cache["k_win"].shape == (6, 6, 2, 32, 128)
    dense = transformer_config("gpt-neox", n_layer=2)
    assert kv_cache_groups(dense) is None
    assert TransformerLM(dense).kv_cache_spec().groups is None


@pytest.mark.parametrize("kernel", ["on", "off"])
def test_chunked_prefill_then_decode_past_the_window_matches_the_reference(
        stack, kernel):
    """Two slots at different lengths, prompts prefilled chunk by chunk,
    then decoded token by token (teacher-forced) to 4 x the window and
    beyond: every logit row the pool's programs return against the
    reference's full forward of the same tokens. The ring never maps more
    than window / page + 1 pages of a slot."""
    cfg, model, params, engine, logits_fn = stack
    chunk = 8
    pool = _pool(stack, kernel)
    rng = np.random.default_rng(0)
    seqs = {0: rng.integers(1, 128, 4 * WINDOW + 9),
            1: rng.integers(1, 128, 4 * WINDOW + 30)}
    prompts = {0: 11, 1: 37}
    want = {s: np.asarray(logits_fn(
        params, np.pad(seq, (0, CTX - len(seq))).astype(np.int32),
        np.arange(len(seq)))) for s, seq in seqs.items()}
    most = 0

    def mapped():
        return int((pool.ring.table != pool.ring.num_pages).sum(1).max())

    for s in seqs:
        assert pool.alloc() == s
        pool.reset_row(s)
        pos = 0
        while pos < prompts[s]:
            n = min(chunk, prompts[s] - pos)
            ids = np.zeros((1, chunk), np.int32)
            ids[0, :n] = seqs[s][pos:pos + n]
            pool.ensure_writable(s, pos, pos + n)
            most = max(most, mapped())
            lg = pool.run_prefill_chunk(engine, ids, s, pos, n, n - 1)
            pool.starts[s] = pos + n
            np.testing.assert_allclose(np.asarray(lg[0, 0]),
                                       want[s][pos + n - 1], atol=ATOL)
            pos += n
    length = dict(prompts)
    while any(length[s] < len(seqs[s]) for s in seqs):
        live = [s for s in seqs if length[s] < len(seqs[s])]
        tokens = np.zeros((3, 1), np.int32)
        for s in live:
            tokens[s, 0] = seqs[s][length[s]]
            pool.ensure_writable(s, length[s], length[s] + 1)
        most = max(most, mapped())
        lg = pool.run_decode(engine, jnp.asarray(tokens[:, 0]))
        deltas = np.zeros((3,), np.int32)
        deltas[live] = 1
        pool.advance(deltas)
        for s in live:
            np.testing.assert_allclose(np.asarray(lg[s, 0]),
                                       want[s][length[s]], atol=ATOL)
            length[s] += 1
        assert not pool.consistency_errors()
    assert most == WINDOW // PAGE + 1
    assert pool.ring.recycled > 0
    full_pages = int((pool.table != pool.num_pages).sum())
    assert full_pages == sum(-(-n // PAGE) for n in length.values())
    for s in seqs:
        pool.release(s)
    assert pool.ring.free_count == 9 and pool.free_page_count == 40
    assert not pool.consistency_errors()


def test_a_swapped_window_page_fails_the_reference_by_its_worst_limit(stack):
    """A fault the pool's audit cannot see (both pages are mapped, once
    each): two entries of a slot's ring in each other's place. The tokens
    decoded after it lie beyond the reference's worst limit at serve.py's
    tolerance; the same decode without the swap passes with nothing over."""
    cfg, model, params, engine, logits_fn = stack
    prompt = np.random.default_rng(3).integers(1, 128, 40).astype(np.int32)

    # one pool for both decodes, reset between them to what a new pool is
    pool = _pool(stack, "on")

    def decode(swap):
        pool.reset()
        slot = pool.alloc()
        pool.reset_row(slot)
        for pos in range(0, 40, 8):
            pool.ensure_writable(slot, pos, pos + 8)
            lg = pool.run_prefill_chunk(engine, prompt[None, pos:pos + 8],
                                        slot, pos, 8, 7)
            pool.starts[slot] = pos + 8
        if swap:
            row = pool.ring.table[slot]
            row[3], row[4] = row[4], row[3]     # positions 24..39
            pool._sync_table()
            assert not pool.consistency_errors()
        out = [int(np.argmax(np.asarray(lg[0, 0])))]
        for _ in range(23):
            pool.ensure_writable(slot, int(pool.starts[slot]),
                                 int(pool.starts[slot]) + 1)
            tokens = np.zeros((3, 1), np.int32)
            tokens[slot, 0] = out[-1]
            lg = pool.run_decode(engine, jnp.asarray(tokens[:, 0]))
            pool.advance(np.asarray([1, 0, 0], np.int32))
            out.append(int(np.argmax(np.asarray(lg[slot, 0]))))
        return ref.check_greedy(logits_fn, params, prompt, out, CTX, 24,
                                2.0 ** -5)

    sound, swapped = decode(False), decode(True)
    assert sound["ok"] and sound["positions_over_rel_tol"] == 0
    assert not swapped["ok"]
    assert swapped["worst_shortfall"] > swapped["tolerance_there"]


def test_recycled_window_pages_are_reused_by_another_slot(stack):
    cfg, model, params, engine, _ = stack
    pool = PagedKVPool(model.kv_cache_spec(), num_slots=2, num_pages=40,
                       page_size=PAGE, prefix_cache=False, kernel="off")
    ring = pool.ring
    assert ring.num_pages == 2 * (WINDOW // PAGE + 1) == 6
    a, b = pool.alloc(), pool.alloc()
    for slot in (a, b):
        pool.ensure_writable(slot, 0, 20)   # entries 0, 1, 2
        pool.starts[slot] = 20
    held = set(ring.table[a][ring.table[a] < 6].tolist())
    assert len(held) == 3 and ring.free_count == 0
    with pytest.raises(PagePoolExhausted):
        # a write that is not page-aligned needs one page more than a
        # ring while position 5 is still in slot b's window
        pool.ensure_writable(b, 20, 28)
    # slot a decodes on inside its third page: at position 23 its first
    # page has left the window and goes back, with nothing new mapped
    for pos in range(20, 24):
        pool.ensure_writable(a, pos, pos + 1)
        pool.starts[a] = pos + 1
    assert ring.recycled == 1 and ring.free_count == 1
    pool.ensure_writable(b, 20, 28)
    pool.starts[b] = 28
    got = set(ring.table[b][ring.table[b] < 6].tolist())
    assert got & held, "slot b holds the page slot a recycled"
    assert not pool.consistency_errors()
    # the audit sees a page that stayed mapped behind the window
    ring.table[a, 0] = ring.table[a, 2]
    assert any("window group" in e for e in pool.consistency_errors())


def test_a_write_longer_than_the_window_maps_one_ring(stack):
    """A whole prompt admitted at once (``prefill_chunk=0``): only the
    entries a later step can see are mapped; the write's work list leaves
    the others out. (A chunk wider than the window would also need the
    entries its own rows read: the server refuses it.)"""
    cfg, model, params, engine, _ = stack
    pool = PagedKVPool(model.kv_cache_spec(), num_slots=2, num_pages=40,
                       page_size=PAGE, prefix_cache=False, kernel="off")
    ring, slot = pool.ring, pool.alloc()
    pool.ensure_writable(slot, 0, 70)
    pool.starts[slot] = 70
    mapped = np.nonzero(ring.table[slot] != ring.num_pages)[0]
    # position 70 sees keys 55..69: entries 6, 7, 8
    assert mapped.tolist() == [6, 7, 8]
    assert int((pool.table[slot] != pool.num_pages).sum()) == 9
    assert not pool.consistency_errors()


PAGED = {"num_pages": 40, "page_size": PAGE, "prefix_cache": False,
         "kernel": "off"}


@pytest.mark.parametrize("paged,chunk", [(PAGED, 8), (PAGED, 0), (False, 8)],
                         ids=["paged-chunked", "paged-serial", "contiguous"])
def test_server_answers_match_the_reference(stack, paged, chunk):
    """Through ``ServingEngine`` (admission, chunked prefill or whole
    prompts longer than a ring admitted at once, grants counted in whole
    rings) on the paged pool, and on the contiguous pool, which keeps a
    window layer's rows at full length under the mask: each served token
    within 1e-4 of the reference's best logit at its position."""
    cfg, model, params, engine, logits_fn = stack
    srv = ServingEngine(engine, num_slots=4, prefill_chunk=chunk,
                        paged_kv=paged)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 128, n).astype(np.int32)
               for n in (5, 23, 41, 70)]
    reqs = [srv.submit(p, max_new_tokens=24) for p in prompts]
    for _ in range(600):
        if not (srv.live_count or srv.pending):
            break
        srv.step()
        srv.check_invariants()
    for p, r in zip(prompts, reqs):
        assert len(r.output_tokens) == 24
        out = ref.check_greedy(logits_fn, params, p, list(r.output_tokens),
                               CTX, 24, 1e-4)
        assert out["ok"], out
    if paged:
        assert srv.pool.ring.recycled > 0
    if paged and chunk:
        # (with the kernel off only the chunk program reports counts: the
        # dense composition decodes through the engine's own program)
        moe = {name: srv.registry.counter(f"serving/moe_{name}").value
               for name in ("assignments", "experts_touched", "layer_calls")}
        # every layer call routes its rows to 2 of 8 experts
        assert moe["layer_calls"] and moe["layer_calls"] % cfg.n_layer == 0
        assert moe["assignments"] % 2 == 0
        assert moe["layer_calls"] <= moe["experts_touched"] \
            <= min(moe["assignments"], 8 * moe["layer_calls"])
        text = srv.registry.to_prometheus()
        for name in ("moe_assignments", "moe_experts_touched",
                     "moe_load_max", "pages_mapped_full",
                     "pages_mapped_window", "window_pages_recycled"):
            assert name in text, name


def test_freed_slots_of_both_groups_are_no_step_under_churn(stack):
    """Admit -> finish -> re-admit on three slots, the finite guard on,
    through the kernels of both page groups: greedy tokens equal the
    dense composition's for every request, no row of any decode step is
    non-finite (a freed slot's rows are routed and counted like any),
    invariants clean after every step, and the decode span carries the
    work lists of both groups: ``pool_reads`` the steps of one full and
    one window layer, ``read_slots`` the seated rows that map a page."""
    from deepspeed_tpu.ops.attention.paged_attention import live_pages
    from deepspeed_tpu.telemetry import Tracer

    cfg, model, params, engine, _ = stack
    rng = np.random.default_rng(31)
    prompts = [rng.integers(1, 128, n).astype(np.int32)
               for n in (5, 30, 11, 19, 7)]
    budgets = [3, 12, 5, 9, 4]

    def churn(kernel):
        srv = traced_once(ServingEngine(
            engine, num_slots=3, prefill_chunk=8, guard_numerics=True,
            tracer=Tracer(), paged_kv=dict(PAGED, kernel=kernel)))
        pool = srv.pool

        def device_steps(rows):     # one layer of each group
            cs, pos = pool.cache["cache_store"], pool.positions()
            return sum(int(live_pages(
                jnp.asarray(pos), cs["table" + suffix], rows, PAGE, pages,
                window or None)[4])
                for (suffix, _, window), pages in zip(
                    kv_cache_groups(cfg),
                    (pool.num_pages, pool.ring.num_pages)))

        finite_rows, record = watch_kernel_reads(srv, device_steps)
        reqs = [srv.submit(p, max_new_tokens=b)
                for p, b in zip(prompts, budgets)]
        for _ in range(300):
            if not (srv.live_count or srv.pending):
                break
            srv.step()
            srv.check_invariants()
        assert finite_rows and all(rows.all() for rows in finite_rows)
        assert [len(r.output_tokens) for r in reqs] == budgets
        return srv, reqs, record

    srv, on, record = churn("on")
    _, off, none = churn("off")
    assert not none                 # the dense composition has no list
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a.tokens(), b.tokens())
    spans = [e["args"] for e in srv.tracer.events()
             if e["ph"] == "X" and e["name"] == "serving/decode"]
    assert len(spans) == len(record) > 5
    for args, ((reads, slots), total, seated) in zip(spans, record):
        assert (args["pool_reads"], args["read_slots"]) == (reads, slots)
        assert reads == total and slots == seated
    assert any(0 < slots < 3 for (_, slots), _, _ in record)


def test_a_chunk_reads_and_writes_both_groups_in_place(stack):
    """A prompt three windows long, 8 tokens a chunk, kernel on: every
    chunk goes through the pages of both groups while the ring turns
    (PR 33; no dense row), its logits the reference's, and the host's
    count of the read's work list is the device's for one full and one
    window layer. A chunk as wide as the window stays on the dense
    composition (a ring maps only what a LATER step can see, and such a
    chunk's own later rows need more), with the reference's logits too."""
    from deepspeed_tpu.ops.attention.paged_attention import live_pages

    cfg, model, params, engine, logits_fn = stack
    seq = np.random.default_rng(33).integers(1, 128, 3 * WINDOW + 5)
    want = np.asarray(logits_fn(
        params, np.pad(seq, (0, CTX - len(seq))).astype(np.int32),
        np.arange(len(seq))))
    for chunk, in_place in ((8, True), (WINDOW, False)):
        pool = _pool(stack, "on")
        assert pool.reads_in_place(chunk) is in_place
        slot = pool.alloc()
        pool.reset_row(slot)
        for pos in range(0, len(seq), chunk):
            n = min(chunk, len(seq) - pos)
            ids = np.zeros((1, chunk), np.int32)
            ids[0, :n] = seq[pos:pos + n]
            pool.ensure_writable(slot, pos, pos + n)
            work = pool.pages_read(chunk, [slot], [pos])
            if in_place:
                tables = ((pool.table, pool.num_pages, None),
                          (pool.ring.table, pool.ring.num_pages, WINDOW))
                steps = sum(int(live_pages(
                    jnp.asarray([pos], jnp.int32),
                    jnp.asarray(table[slot])[None], chunk, PAGE, pages,
                    window)[4]) for table, pages, window in tables)
                assert work == (steps, 1, steps)
            else:
                assert work is None
            lg = pool.run_prefill_chunk(engine, ids, slot, pos, n, n - 1)
            pool.starts[slot] = pos + n
            np.testing.assert_allclose(np.asarray(lg[0, 0]),
                                       want[pos + n - 1], atol=ATOL)
            assert not pool.consistency_errors()
        assert pool.ring.recycled > 0


def test_what_does_not_compose_refuses_at_construction(stack):
    cfg, model, params, engine, _ = stack
    spec = model.kv_cache_spec()
    with pytest.raises(ValueError, match="prefix_cache"):
        PagedKVPool(spec, 2, num_pages=8, page_size=PAGE)
    with pytest.raises(ValueError, match="prefix_cache"):
        ServingEngine(engine, num_slots=2, prefill_chunk=8,
                      paged_kv={"page_size": PAGE})
    off = {"page_size": PAGE, "prefix_cache": False, "kernel": "off"}
    with pytest.raises(ValueError, match="spec_decode"):
        ServingEngine(engine, num_slots=2, prefill_chunk=8, paged_kv=off,
                      spec_decode={"k": 2})
    with pytest.raises(ValueError, match="roles"):
        ServingEngine(engine, num_slots=2, prefill_chunk=8, paged_kv=off,
                      role="prefill")
    with pytest.raises(ValueError, match="kv_cache_quant"):
        transformer_config("mellum", **{**SMALL, "kv_cache_quant": True})
    with pytest.raises(ValueError, match="int8_weights"):
        transformer_config("mellum", **{**SMALL, "int8_weights": True})
    with pytest.raises(ValueError, match="sliding_window"):
        transformer_config("mellum", **{**SMALL, "sliding_window": None})
    with pytest.raises(ValueError, match="layer_types"):
        transformer_config("mellum", **{**SMALL, "n_layer": 4})
    with pytest.raises(ValueError, match="experts_per_token"):
        transformer_config("mellum", **{**SMALL, "experts_per_token": 9})
    with pytest.raises(ValueError, match="sliding_window"):
        ServingEngine(engine, num_slots=2, prefill_chunk=32, paged_kv=off)
    with pytest.raises(ValueError, match="unknown paged_kv keys"):
        ServingEngine(engine, num_slots=2, prefill_chunk=8,
                      paged_kv=dict(off, window_pages=4))
    pool = PagedKVPool(spec, 2, num_pages=8, page_size=PAGE,
                       prefix_cache=False)
    with pytest.raises(ValueError, match="window page group"):
        pool.import_pages(pool, [0])
    from deepspeed_tpu.inference.zero_inference import ZeroInferenceEngine

    with pytest.raises(ValueError, match="ZeRO-Inference"):
        ZeroInferenceEngine(cfg, {})


def test_tensor_parallel_serving_refuses(stack):
    from deepspeed_tpu.parallel import mesh as mesh_mod

    cfg, model, params, _, _ = stack
    if jax.device_count() < 2:
        pytest.skip("needs two devices")
    mesh = mesh_mod.initialize_mesh(model=2, data=jax.device_count() // 2)
    try:
        engine = ds.init_inference(model=model, model_parameters=params,
                                   config={"dtype": "float32"}, mesh=mesh)
        with pytest.raises(ValueError, match="tensor-parallel"):
            ServingEngine(engine, num_slots=2, prefill_chunk=8,
                          paged_kv={"page_size": PAGE,
                                    "prefix_cache": False})
    finally:
        mesh_mod.reset_mesh()
