"""Data-parallel replica router tests: the router must be a pure
DISPATCH layer — routing, spill, and failover can never change model
output (greedy tokens bitwise-match a single engine), ids stay globally
unique, session/prefix affinity beats least-loaded deterministically,
and a replica lost mid-request re-homes its work to a sibling with zero
slot or page leaks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models.lm_config import TransformerConfig
from deepspeed_tpu.models.transformer_lm import TransformerLM
from deepspeed_tpu.serving import (ID_STRIDE, FinishReason,
                                   NoLiveReplicaError, ReplicaRouter,
                                   RequestState, ServingEngine)

TINY = dict(vocab_size=64, max_seq_len=128, n_embd=32, n_layer=2, n_head=4,
            dtype=jnp.float32)


@pytest.fixture(scope="module")
def stack():
    cfg = TransformerConfig(**TINY)
    model = TransformerLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 0, 64)
    params = model.init({"params": jax.random.PRNGKey(1)}, ids,
                        method=model.logits)["params"]
    engine = ds.init_inference(model=model, model_parameters=params,
                               config={"dtype": "float32"})
    return model, params, engine


def _mk(engine, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_queue_depth", 16)
    return ServingEngine(engine, **kw)


def _prompts(n, rng, lo=5, hi=12):
    return [rng.integers(0, 64, size=int(rng.integers(lo, hi + 1)))
            .astype(np.int32) for _ in range(n)]


def test_router_matches_single_engine_bitwise(stack):
    """Routing over two replicas is invisible to the client: greedy
    outputs bitwise-match the same workload through one engine."""
    _, _, engine = stack
    rng = np.random.default_rng(11)
    prompts = _prompts(8, rng)
    budgets = [int(rng.integers(3, 8)) for _ in prompts]

    solo = _mk(engine)
    ref = [solo.submit(p, max_new_tokens=b)
           for p, b in zip(prompts, budgets)]
    solo.run_until_drained(max_steps=400)

    router = ReplicaRouter([_mk(engine), _mk(engine)])
    got = [router.submit(p, max_new_tokens=b)
           for p, b in zip(prompts, budgets)]
    router.run_until_drained(max_steps=400)

    for r, g in zip(ref, got):
        assert g.state == RequestState.FINISHED
        np.testing.assert_array_equal(g.output_tokens, r.output_tokens)


def test_router_ids_globally_unique(stack):
    """Replica i issues ids in [i*ID_STRIDE, (i+1)*ID_STRIDE): a
    router-issued id names one request regardless of seat."""
    _, _, engine = stack
    rng = np.random.default_rng(5)
    router = ReplicaRouter([_mk(engine), _mk(engine), _mk(engine)])
    reqs = [router.submit(p, max_new_tokens=2) for p in _prompts(9, rng)]
    ids = [r.request_id for r in reqs]
    assert len(set(ids)) == len(ids)
    for r in reqs:
        owner = router._owner[r.request_id]
        assert r.request_id // ID_STRIDE == owner
    router.run_until_drained(max_steps=400)


def test_owner_map_retired_with_tracking(stack):
    """Router bookkeeping may not outlive a request: finishing,
    cancelling and unplaceable-failover all retire the ``_owner`` entry
    alongside ``_tracked`` (regression: ``_owner`` kept every id ever
    routed, an unbounded host-side leak graftown's
    leak-on-exception-path family is built to catch)."""
    _, _, engine = stack
    rng = np.random.default_rng(7)
    router = ReplicaRouter([_mk(engine), _mk(engine)])
    reqs = [router.submit(p, max_new_tokens=3) for p in _prompts(6, rng)]
    assert len(router._owner) == len(reqs)

    victim = reqs[-1]
    assert router.cancel(victim.request_id) is not None
    assert victim.request_id not in router._owner
    assert victim.request_id not in router._tracked

    router.run_until_drained(max_steps=400)
    assert router._tracked == {}
    assert router._owner == {}
    router.check_invariants()


def test_failover_requeues_to_sibling_bitwise(stack):
    """A replica that dies MID-REQUEST (some tokens already generated)
    re-homes every owed request to the sibling; greedy resume via
    ``seed_tokens`` is bitwise identical to never having failed."""
    _, _, engine = stack
    rng = np.random.default_rng(23)
    prompts = _prompts(6, rng)
    budgets = [6] * len(prompts)

    solo = _mk(engine, num_slots=2, max_queue_depth=16)
    ref = [solo.submit(p, max_new_tokens=b)
           for p, b in zip(prompts, budgets)]
    solo.run_until_drained(max_steps=400)

    rep_a, rep_b = _mk(engine), _mk(engine)
    router = ReplicaRouter([rep_a, rep_b])
    got = [router.submit(p, max_new_tokens=b)
           for p, b in zip(prompts, budgets)]
    # let both replicas make partial progress, then kill replica 0
    # mid-decode: its seated requests have output_tokens already
    router.step()
    router.step()
    assert any(r.output_tokens for r in got)
    boom = RuntimeError("injected replica loss")
    original_step = rep_a.step

    def dying_step():
        raise boom

    rep_a.step = dying_step
    fins = router.run_until_drained(max_steps=800)
    rep_a.step = original_step

    assert router.alive_replicas == [1]
    assert router.failovers > 0
    assert len(fins) >= 1
    for r, g in zip(ref, got):
        assert g.state == RequestState.FINISHED
        assert g.finish_reason == FinishReason.LENGTH
        np.testing.assert_array_equal(g.output_tokens, r.output_tokens)
    # the survivor's books must balance; the corpse is a tombstone
    router.check_invariants()
    assert rep_b.pool.free_count == rep_b.pool.num_slots
    assert rep_b.live_count == 0 and rep_b.scheduler.pending == 0


def test_all_replicas_dead_raises(stack):
    _, _, engine = stack
    rng = np.random.default_rng(2)
    rep = _mk(engine)
    router = ReplicaRouter([rep])
    router.submit(_prompts(1, rng)[0], max_new_tokens=4)
    rep.step = lambda: (_ for _ in ()).throw(RuntimeError("boom"))
    with pytest.raises(NoLiveReplicaError):
        router.run_until_drained(max_steps=10)


def test_affinity_vs_least_loaded_tiebreak_deterministic(stack):
    """Dispatch priority is sticky-session -> prefix-peek -> least
    loaded -> lowest index, and two routers fed the same sequence
    dispatch identically (the determinism pin)."""
    _, _, engine = stack
    rng = np.random.default_rng(9)
    page = 8
    shared = rng.integers(0, 64, size=3 * page).astype(np.int32)

    def build():
        reps = [
            _mk(engine, prefill_chunk=page,
                paged_kv={"page_size": page, "num_pages": 16}),
            _mk(engine, prefill_chunk=page,
                paged_kv={"page_size": page, "num_pages": 16}),
        ]
        return ReplicaRouter(reps), reps

    def drive(router):
        trace = []
        # 1) empty tries, equal load: lowest index wins
        r = router.submit(shared, max_new_tokens=2)
        trace.append(router._owner[r.request_id])
        router.run_until_drained(max_steps=200)
        # 2) replica 0 now caches the shared prefix; load replica 1
        #    being idle must NOT steal a prefix-affine prompt
        busy = router.replicas[0].submit(
            rng.integers(0, 64, size=5).astype(np.int32), max_new_tokens=6)
        r = router.submit(
            np.concatenate([shared,
                            rng.integers(0, 64, size=3).astype(np.int32)]),
            max_new_tokens=2)
        trace.append(router._owner[r.request_id])
        # 3) a cold prompt goes least-loaded (replica 1), not index 0
        r = router.submit(rng.integers(0, 64, size=2 * page)
                          .astype(np.int32), max_new_tokens=2)
        trace.append(router._owner[r.request_id])
        # 4) session pin beats both: with replica 0 strictly busier, a
        #    cold session request homes on 1; the follow-up turn carries
        #    a prompt whose prefix lives on 0 — stickiness wins anyway
        busy2 = router.replicas[0].submit(
            rng.integers(0, 64, size=5).astype(np.int32), max_new_tokens=6)
        r = router.submit(rng.integers(0, 64, size=6).astype(np.int32),
                          session="s1", max_new_tokens=2)
        home = router._owner[r.request_id]
        trace.append(home)
        del busy2
        r = router.submit(
            np.concatenate([shared,
                            rng.integers(0, 64, size=2).astype(np.int32)]),
            session="s1", max_new_tokens=2)
        trace.append(router._owner[r.request_id])
        router.run_until_drained(max_steps=400)
        del busy
        return trace

    router1, _ = build()
    t1 = drive(router1)
    assert t1[0] == 0          # lowest-index tie-break
    assert t1[1] == 0          # prefix affinity beats idle sibling
    assert t1[2] == 1          # least-loaded for cold prompts
    assert t1[3] == 1          # cold session homes least-loaded
    assert t1[4] == 1          # session stickiness beats prefix score
    assert router1.affinity_hits > 0

    router2, _ = build()
    t2 = drive(router2)
    assert t1 == t2            # identical sequence -> identical dispatch


def test_router_zero_leaks_after_failover_and_drain(stack):
    """After spills, failover and a full drain, no replica leaks a slot
    or a page: free counts match pool sizes and check_invariants holds
    on every ALIVE replica (paged pools included)."""
    _, _, engine = stack
    rng = np.random.default_rng(31)
    page = 8

    def mk_paged():
        return _mk(engine, prefill_chunk=page, max_queue_depth=8,
                   paged_kv={"page_size": page, "num_pages": 12})

    rep_a, rep_b, rep_c = mk_paged(), mk_paged(), mk_paged()
    router = ReplicaRouter([rep_a, rep_b, rep_c])
    reqs = [router.submit(p, max_new_tokens=4)
            for p in _prompts(10, rng, lo=6, hi=20)]
    router.step()
    rep_b.step = lambda: (_ for _ in ()).throw(RuntimeError("gone"))
    router.run_until_drained(max_steps=800)

    assert router.alive_replicas == [0, 2]
    router.check_invariants()
    for rep in (rep_a, rep_c):
        assert rep.live_count == 0
        assert rep.scheduler.pending == 0
        assert rep.pool.free_count == rep.pool.num_slots
        # every page is either free or held only by the prefix cache
        stats = rep.pool.page_stats()
        assert stats["pages_in_use"] == stats["prefix_evictable_pages"]
    placed = [r for r in reqs if r.state == RequestState.FINISHED]
    assert len(placed) == len(reqs)  # nobody stranded by the failover
