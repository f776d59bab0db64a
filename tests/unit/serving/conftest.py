"""What the serving tests share: one tiny engine, and one way to build a
server on either K/V pool.

Every serving cell of ``BENCHMARK.json`` runs ``PagedKVPool``; the
constructor's default is the contiguous ``SlotPool``. A test that builds
a server takes the ``pool`` fixture and calls :func:`make_server`, so
each behaviour is checked on both. ``paged`` is the dense
gather/scatter composition (``kernel: "off"``): the kernel's parity is
``test_paged_kernel.py``'s and ``ops/test_paged_attention.py``'s job,
and interpret mode would cost minutes here.

A ``ServingEngine`` traces and lowers every program it runs (seconds for
the tiny model, tens of them for a hybrid kind), so cases that want a server
of the same constructor arguments share one, and each takes it through
:func:`emptied`."""

import jax
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models.lm_config import TransformerConfig
from deepspeed_tpu.models.transformer_lm import TransformerLM
from deepspeed_tpu.serving import ServingEngine
from tests.unit.kinds import TINY, hashable

POOLS = {"contiguous": False, "paged": {"kernel": "off"}}


@pytest.fixture(scope="module")
def stack():
    """(model, params, engine) of the tiny LM, built once a module."""
    cfg = TransformerConfig(**TINY)
    model = TransformerLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 0, 64)
    params = model.init({"params": jax.random.PRNGKey(1)}, ids,
                        method=model.logits)["params"]
    engine = ds.init_inference(model=model, model_parameters=params,
                               config={"dtype": "float32"})
    return model, params, engine


@pytest.fixture(params=sorted(POOLS))
def pool(request):
    return request.param


# the programs a page pool builds over an engine (``bind_engine``)
_POOL_PROGRAMS = ("_paged_decode_jit", "_paged_verify_jit",
                  "_paged_chunk_jit", "_paged_decode_kernel_jit",
                  "_paged_verify_kernel_jit", "_paged_chunk_decode_jit")


def _program_constants(pool):
    """What a pool's programs close over, and the shapes they are given."""
    return (pool.num_slots, pool.capacity, pool.num_pages, pool.page_size,
            pool.kernel_active, pool.ring and pool.ring.num_pages,
            str(jax.tree.map(lambda x: (x.shape, str(x.dtype)), pool.cache)))


def traced_once(owner):
    """``owner`` (a server on a page pool, or a bound page pool) runs the
    step programs of the first pool of its construction that came here
    over the same engine: two such pools close over equal constants and
    would trace, lower and compile the same text, each for itself (seconds
    the tiny model, tens of them a hybrid kind on the CPU). So a case can
    have a NEW server or pool, for what it holds and counts over its life,
    without new programs. A case that asserts on what a server compiles
    (no program after the warm-up) keeps its own: a shape another server
    had compiled would pass for one this server's warm-up covered. A
    program ``owner`` was built without stays out."""
    pool = getattr(owner, "pool", owner)
    if not hasattr(pool, "page_size"):      # the contiguous pool runs the
        return owner                        # engine's own programs
    first = pool._engine.__dict__.setdefault("_pools_traced", {}) \
        .setdefault(_program_constants(pool), pool)
    for name in _POOL_PROGRAMS:
        if getattr(pool, name) is not None \
                and getattr(first, name) is not None:
            setattr(pool, name, getattr(first, name))
        if owner is not pool:
            owner.watchdog.attach(pool, name)
    return owner


def make_server(engine, pool, own_programs=False, **kw):
    """A ``ServingEngine`` over ``engine`` on the named pool; on the paged
    one its step programs are traced once an engine (:func:`traced_once`)
    unless the case counts what the server compiles (``own_programs``)."""
    srv = ServingEngine(engine, paged_kv=POOLS[pool], **kw)
    return srv if own_programs else traced_once(srv)


def emptied(srv):
    """``srv`` handed from one case (or one arm of a comparison) to the
    next: settled, nothing queued, seated or in flight, the audit clean,
    every slot and every page free once the trie has let its own go. Then
    its pool as a new server has it (``reset()``: zeroed leaves, the free
    lists in order, a new trie). A case that leaks a slot or a page fails
    HERE, and none reads what another left."""
    srv.settle()
    assert not srv.pending and not srv.live_count and not srv._slot_req
    assert srv._in_flight is None and not srv._deferred
    assert not srv._unread and not srv._closing
    srv.check_invariants()
    pool = srv.pool
    assert pool.free_count == pool.num_slots
    if hasattr(pool, "free_page_count"):
        if pool.prefix is not None:
            pool.prefix.clear(pool)
        assert pool.free_page_count == pool.num_pages
    pool.reset()
    return srv


def spans_since(srv, n0):
    """The complete spans ``srv`` has left since its tracer stood at
    ``events_total == n0``: what a case on a shared server reads as its
    own."""
    tracer = srv.tracer
    events = tracer.events()
    fresh = tracer.events_total - n0
    assert fresh <= len(events), "the ring wrapped: the window is lost"
    return [e for e in events[len(events) - fresh:] if e.get("ph") == "X"]


class Servers:
    """The servers of one module: ``build(*args, **kw)`` runs once for each
    set of arguments, and every later call for the same gets that server
    :func:`emptied`. For the cases that only DRIVE a server; one that
    asserts on what a new server does first (compiles, first-call spans,
    set-up events) builds its own."""

    def __init__(self, build):
        self._build, self._built = build, {}

    def __call__(self, *args, **kw):
        key = hashable((args, kw))
        if key in self._built:
            return emptied(self._built[key])
        self._built[key] = self._build(*args, **kw)
        return self._built[key]


@pytest.fixture(scope="module")
def servers(stack):
    """``servers(pool, **kw)``: :func:`make_server` over the module's engine
    through :class:`Servers`."""
    return Servers(lambda pool, **kw: make_server(stack[2], pool, **kw))


def watch_kernel_reads(srv, device_steps):
    """Spy on a server's finite guard and on its pool's ``pages_read``.
    Returns ``(finite_rows, record)``: the guard's (num_slots,) verdict of
    every guarded step, and for every decode or verify dispatch of the
    kernel ``((steps, slots),
    device_steps(rows), seated rows that map a page)``, taken AT the
    dispatch (a step frees slots after it). ``device_steps(rows)`` is the
    test's own count of the device work list's steps."""
    pool, finite_rows, record = srv.pool, [], []
    guard, count = srv._jit_finite, pool.pages_read

    def finite(logits):
        rows = guard(logits)
        finite_rows.append(np.asarray(rows))
        return rows

    def pages_read(rows, slots=None, starts=None):
        work = count(rows, slots, starts)
        if work is not None and slots is None:     # (not a chunk's)
            assert work[2] == work[0]      # a step of K/V is a page
            record.append((work[:2], device_steps(rows), int(np.count_nonzero(
                (pool.table != pool.num_pages).any(axis=1)))))
        return work

    srv._jit_finite, pool.pages_read = finite, pages_read
    return finite_rows, record
