"""What the serving tests share: one tiny engine, and one way to build a
server on either K/V pool.

Every serving cell of ``BENCHMARK.json`` runs ``PagedKVPool``; the
constructor's default is the contiguous ``SlotPool``. A test that builds
a server takes the ``pool`` fixture and calls :func:`make_server`, so
each behaviour is checked on both. ``paged`` is the dense
gather/scatter composition (``kernel: "off"``): the kernel's parity is
``test_paged_kernel.py``'s and ``ops/test_paged_attention.py``'s job,
and interpret mode would cost minutes here."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models.transformer_lm import TransformerConfig, TransformerLM
from deepspeed_tpu.serving import ServingEngine

TINY = dict(vocab_size=64, max_seq_len=64, n_embd=32, n_layer=2, n_head=4,
            dtype=jnp.float32)

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


def make_server(engine, pool, **kw):
    """A ``ServingEngine`` over ``engine`` on the named pool."""
    return ServingEngine(engine, paged_kv=POOLS[pool], **kw)


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
