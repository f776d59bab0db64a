"""The spans the two engines leave in the process-wide tracer by default
(PR 23): the phases of a serving step and of a train step, in order and
inside their step span; the request events mirrored into the ring; the
flight recorder's phase split; the set-up spans of both entry points."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models.transformer_lm import TransformerConfig, TransformerLM
from deepspeed_tpu.telemetry import Tracer, default_tracer
from tests.unit.simple_model import SimpleModel, base_config, random_batch

TINY = dict(vocab_size=64, max_seq_len=64, n_embd=32, n_layer=2, n_head=4,
            dtype=jnp.float32)
SERVING_PHASES = ["serving/boundary", "serving/grant", "serving/sync",
                  "serving/replay", "serving/after_step"]
TRAIN_PHASES = ["train/stack_batch", "train/dispatch", "train/sync",
                "train/after_step"]


def _new_events(n0):
    """Events the default tracer took since its ``events_total`` was n0
    (the ring is process-wide: other tests wrote before us)."""
    tr = default_tracer()
    evs = [e for e in tr.events() if not e["name"].startswith("setup/")]
    return evs[len(evs) - (tr.events_total - n0):]


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
                       max_new_tokens=3) for n in (6, 9)]
    srv.run_until_drained(max_steps=50)
    evs = _new_events(n0)
    steps = [e for e in evs if e["name"] == "serving/step"]
    assert [s["args"]["step"] for s in steps] == \
        list(range(1, srv.step_id + 1))
    for step in steps:
        kids = sorted((e for e in evs if e["ph"] == "X"
                       and e is not step and _inside(step, e)),
                      key=lambda e: e["ts"])
        names = [k["name"] for k in kids]
        # every phase of the table, once, in order
        assert [n for n in names if n in SERVING_PHASES] == SERVING_PHASES
        # the dispatches lie between the grant and the sync
        dispatch = [n for n in names if n in (
            "serving/decode", "serving/admit", "serving/prefill_batch",
            "serving/prefill_chunk")]
        assert dispatch
        lo, hi = names.index("serving/grant"), names.index("serving/sync")
        assert all(lo < names.index(n) < hi for n in dispatch)
        # sync + the other phases fit inside the step, end to end
        top = [k for k in kids if k["name"] in SERVING_PHASES + dispatch]
        assert sum(k["dur"] for k in top) <= step["dur"]
        for a, b in zip(top, top[1:]):
            assert a["ts"] + a["dur"] <= b["ts"]
    # at its close the step says what it dispatched and emitted
    assert sum(s["args"]["tokens"] for s in steps) == 6
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
    dump = srv.debug_dump()
    last = dump["steps"][-1]
    phases = last["phases_ms"]
    assert set(phases) == {"boundary", "grant", "dispatch", "sync", "replay"}
    assert all(v >= 0 for v in phases.values())
    assert sum(phases.values()) <= last["wall_ms"]
    assert last["dispatched"]["decode"] == 1
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
    assert default_tracer().events_total == n0
    # the after-step clock still runs: spans time themselves regardless
    assert srv.telemetry_overhead_s > 0.0
    assert srv.debug_dump()["steps"][-1]["wall_ms"] > 0


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
