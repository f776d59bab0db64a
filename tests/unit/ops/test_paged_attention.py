"""Fused paged-attention decode kernel tests (ISSUE 13). The parity
contract under test: a single-token call is BITWISE identical to
``decode_attention`` over the gathered dense view with ``block_s`` pinned
to the page size — paging is an addressing change, never a numerics
change — and garbage pages (unmapped sentinels, stale contents past the
live length) can never reach the output. Pallas runs in interpreter mode
on CPU."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import paged_attention
from deepspeed_tpu.ops.attention.decode_attention import (
    decode_attention,
    pack_int8_sublanes,
)
from deepspeed_tpu.ops.attention.paged_attention import (
    live_pages,
    plan_grid,
)

# Called eagerly, every call of a kernel's entry point lowers and compiles
# its kernel anew (a ``pallas_call`` bound outside ``jit`` carries a fresh
# jaxpr each time: nothing to find in a cache). The tests below call the
# entry points through ``_once``: ONE ``jax.jit`` for each (entry point,
# static arguments, and the module's attributes a test may have patched),
# so the same shapes compile once a file (PR 56; 191 cases took 327-334 s
# in one process before).
_JITS = {}
_STATIC = (int, float, bool, str, type(None))


def _once(name):
    def call(*args, **kw):
        fn = getattr(paged_attention, name)
        fixed = tuple((i, a) for i, a in enumerate(args)
                      if isinstance(a, _STATIC))
        named = tuple(sorted((k, v) for k, v in kw.items()
                             if isinstance(v, _STATIC)))
        key = (fn, fixed, named, paged_attention.VMEM_BUDGET_BYTES,
               paged_attention.plan_grid, paged_attention.pl.pallas_call)
        if key not in _JITS:
            at = dict(fixed)

            def traced(*arrays, **more):
                given = iter(arrays)
                return fn(*(at[i] if i in at else next(given)
                            for i in range(len(args))),
                          **dict(named), **more)
            _JITS[key] = jax.jit(traced)
        return _JITS[key](
            *(a for i, a in enumerate(args) if not isinstance(a, _STATIC)),
            **{k: v for k, v in kw.items() if not isinstance(v, _STATIC)})
    return functools.wraps(getattr(paged_attention, name))(call)


paged_decode_attention = _once("paged_decode_attention")


# the dense oracle, compiled once for the rows of a test that asks it row
# after row (called eagerly, every row lowers and compiles the kernel anew)
_dense_rows = jax.jit(decode_attention, static_argnames=("block_s",))


def _make_paged(rng, B, KV, D, S, ps, n_free=2, dtype=np.float32):
    """Random dense positions-minor cache (B, KV, D, S) cut into pages at
    a random physical placement. Returns (dense_k, dense_v, k_pages,
    v_pages, table); ``n_free`` extra physical pages stay unmapped so the
    permutation is non-trivial."""
    pages_per_slot = S // ps
    P = B * pages_per_slot + n_free
    dense_k = rng.standard_normal((B, KV, D, S)).astype(dtype)
    dense_v = rng.standard_normal((B, KV, D, S)).astype(dtype)
    perm = rng.permutation(P)[:B * pages_per_slot]
    table = perm.reshape(B, pages_per_slot).astype(np.int32)
    k_pages = np.zeros((P, KV, D, ps), dtype)
    v_pages = np.zeros((P, KV, D, ps), dtype)
    for b in range(B):
        for j in range(pages_per_slot):
            k_pages[table[b, j]] = dense_k[b, :, :, j * ps:(j + 1) * ps]
            v_pages[table[b, j]] = dense_v[b, :, :, j * ps:(j + 1) * ps]
    return dense_k, dense_v, k_pages, v_pages, table


@pytest.mark.parametrize("B,H,KV,D,S,ps", [
    (2, 4, 4, 64, 128, 32),     # MHA
    (2, 8, 2, 64, 128, 16),     # GQA 4x
    (1, 4, 1, 128, 256, 64),    # MQA
])
def test_decode_bitwise_matches_dense_oracle(B, H, KV, D, S, ps):
    """T=1 decode: bitwise-equal to the dense kernel at block_s=ps on
    the gathered view (the serving pool's dense-composition oracle),
    including non-power-of-two live lengths."""
    rng = np.random.default_rng(0)
    dense_k, dense_v, k_pages, v_pages, table = _make_paged(
        rng, B, KV, D, S, ps)
    # non-pow2, page-straddling starts; one slot with a single live token
    starts = np.asarray([0, S - ps - 3][:B], np.int32) \
        if B == 2 else np.asarray([S // 2 - 5], np.int32)
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)

    out = paged_decode_attention(q, jnp.asarray(k_pages),
                                 jnp.asarray(v_pages), jnp.asarray(table),
                                 jnp.asarray(starts))
    oracle = decode_attention(q[:, 0], jnp.asarray(dense_k),
                              jnp.asarray(dense_v),
                              jnp.asarray(starts + 1), block_s=ps)
    assert out.shape == (B, 1, H, D)
    np.testing.assert_array_equal(np.asarray(out[:, 0]), np.asarray(oracle))


def test_garbage_pages_and_sentinels_never_reach_output():
    """Dead table entries (sentinel = P) and garbage in unmapped / past-
    length pages must not change a single output bit — masking is by
    length, and dead grid steps clamp to the last live page."""
    rng = np.random.default_rng(1)
    B, H, KV, D, S, ps = 2, 4, 2, 64, 128, 32
    _, _, k_pages, v_pages, table = _make_paged(rng, B, KV, D, S, ps)
    starts = np.asarray([ps + 5, 2 * ps - 1], np.int32)  # 2 live pages each
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    P = k_pages.shape[0]

    clean = paged_decode_attention(q, jnp.asarray(k_pages),
                                   jnp.asarray(v_pages), jnp.asarray(table),
                                   jnp.asarray(starts))

    # poison every page past each slot's live range and point the dead
    # table entries at the unmapped sentinel (the pool's discipline for
    # freed pages); large-but-finite garbage — exp(NEG_INF - m) == 0
    # exactly, so masked columns contribute exactly nothing
    dirty_k, dirty_v, dirty_t = (k_pages.copy(), v_pages.copy(),
                                 table.copy())
    live_pages = (starts + 1 + ps - 1) // ps
    mapped_live = {int(table[b, j])
                   for b in range(B) for j in range(live_pages[b])}
    for p in range(P):
        if p not in mapped_live:
            dirty_k[p] = 1e4
            dirty_v[p] = -1e4
    for b in range(B):
        dirty_t[b, live_pages[b]:] = P          # unmapped sentinel
    # stale columns past the live length INSIDE the last live page too
    for b in range(B):
        last = int(table[b, live_pages[b] - 1])
        col = (starts[b] + 1) % ps
        if col:
            dirty_k[last, :, :, col:] = 1e4
            dirty_v[last, :, :, col:] = -1e4

    dirty = paged_decode_attention(q, jnp.asarray(dirty_k),
                                   jnp.asarray(dirty_v),
                                   jnp.asarray(dirty_t),
                                   jnp.asarray(starts))
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))


def _reference_rows(q, dense_k, dense_v, starts, window=None, slopes=None):
    """Plain fp32 softmax reference with per-row causal limits: row t of
    slot b attends cache positions [0, starts[b] + t], with a ``window``
    the last ``window`` of them, with ``slopes`` under head h's ALiBi
    bias ``slopes[h] * (pos - (starts[b] + t))``."""
    B, T, H, D = q.shape
    _, KV, _, S = dense_k.shape
    rep = H // KV
    k = np.repeat(dense_k, rep, axis=1)          # (B, H, D, S)
    v = np.repeat(dense_v, rep, axis=1)
    s = np.einsum("bthd,bhds->bths", q.astype(np.float64),
                  k.astype(np.float64)) / np.sqrt(D)
    pos = np.arange(S)[None, None, None, :]
    limit = (starts[:, None, None, None]
             + np.arange(T)[None, :, None, None])
    seen = pos <= limit
    if slopes is not None:
        s = s + np.asarray(slopes, np.float64)[None, None, :, None] \
            * (pos - limit)
    if window is not None:
        seen &= pos > limit - window
    s = np.where(seen, s, -np.inf)
    p = np.exp(s - s.max(axis=-1, keepdims=True))
    p = p / p.sum(axis=-1, keepdims=True)
    return np.einsum("bths,bhds->bthd", p, v.astype(np.float64))


# a verify step's rows (2, 3, 8; 9 and 16 for a wide one) and a prefill
# chunk's (64 in the Pythia cells, 128 in ide; 61: a width that is no
# multiple of the sublane tile)
_ROWS = [2, 3, 8, 9, 16, 61, 64, 128]


@pytest.mark.parametrize("T", _ROWS)
def test_multi_row_verify_matches_reference(T):
    """T>1 (speculative verify, a prefill chunk): each query row carries
    its own causal limit; numerics match a plain-softmax reference. The
    rows of a chunk cross several pages."""
    rng = np.random.default_rng(2)
    B, H, KV, D, S, ps = 2, 4, 2, 64, 256, 16
    dense_k, dense_v, k_pages, v_pages, table = _make_paged(
        rng, B, KV, D, S, ps)
    starts = np.asarray([ps - 1, 3 * ps + 2], np.int32)  # straddle pages
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    out = paged_decode_attention(q, jnp.asarray(k_pages),
                                 jnp.asarray(v_pages), jnp.asarray(table),
                                 jnp.asarray(starts))
    ref = _reference_rows(np.asarray(q), dense_k, dense_v, starts)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4, rtol=1e-4)


def _quantized_pool(rng, B, KV, D, S, ps, packed):
    """An int8 (or int32-packed) page pool with per-column scales, and
    the float dense view it stands for."""
    per_slot = S // ps
    P = B * per_slot + 2
    k8 = rng.integers(-127, 128, (P, KV, D, ps)).astype(np.int8)
    v8 = rng.integers(-127, 128, (P, KV, D, ps)).astype(np.int8)
    ks = rng.uniform(0.01, 0.1, (P, KV, ps)).astype(np.float32)
    vs = rng.uniform(0.01, 0.1, (P, KV, ps)).astype(np.float32)
    table = rng.permutation(P)[:B * per_slot].reshape(B, per_slot) \
        .astype(np.int32)
    dense_k = _gather(k8 * ks[:, :, None, :], table)
    dense_v = _gather(v8 * vs[:, :, None, :], table)
    kp, vp = jnp.asarray(k8), jnp.asarray(v8)
    if packed:
        kp, vp = pack_int8_sublanes(kp), pack_int8_sublanes(vp)
    scales = dict(k_scale_pages=jnp.asarray(ks), v_scale_pages=jnp.asarray(vs))
    return dense_k, dense_v, kp, vp, table, scales


@pytest.mark.parametrize("T", [9, 64, 128])
@pytest.mark.parametrize("variant", ["gqa8", "window", "inactive", "int8",
                                     "int32-packed", "dead_slot"])
def test_chunk_rows_match_reference(variant, T):
    """A wide verify's and a chunk's rows in every form the decode read
    has: a KV head's eight query heads in one operand (1,024 rows at
    T = 128), a window shorter than the cached length (the slot's steps
    begin past entry 0), a call that is not ``active`` (no step: the rows
    come back as they went in), the quantized tiers, and a slot that maps
    nothing beside one that does."""
    rng = np.random.default_rng(330 + T)
    B, KV, D, S, ps = 2, 2, 64, 256, 16
    H = KV * (8 if variant == "gqa8" else 2)
    starts = np.asarray([ps - 1, 5 * ps + 2], np.int32)
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    scales, window, kwargs = {}, None, {}
    if variant in ("int8", "int32-packed"):
        dense_k, dense_v, k_pages, v_pages, table, scales = _quantized_pool(
            rng, B, KV, D, S, ps, variant == "int32-packed")
    else:
        dense_k, dense_v, k_pages, v_pages, table = _make_paged(
            rng, B, KV, D, S, ps)
    P = k_pages.shape[0]
    if variant == "window":
        window = 2 * ps + 5
        # what lies a window behind the first row has been recycled
        first = np.maximum(starts - window + 1, 0) // ps
        assert first.tolist() == [0, 2]
        for b in range(B):
            k_pages[table[b, :first[b]]] = np.nan
            v_pages[table[b, :first[b]]] = np.nan
            table[b, :first[b]] = P
        kwargs = dict(window=window)
    if variant == "dead_slot":
        k_pages[table[0]] = v_pages[table[0]] = np.nan
        table[0] = P                      # freed: its start counts on
    if variant == "inactive":
        kwargs = dict(active=jnp.asarray(False))
    out = np.asarray(paged_decode_attention(
        q, jnp.asarray(k_pages), jnp.asarray(v_pages), jnp.asarray(table),
        jnp.asarray(starts), **scales, **kwargs))
    assert out.shape == (B, T, H, D) and np.isfinite(out).all()
    if variant == "inactive":
        np.testing.assert_array_equal(out, np.asarray(q))
        return
    ref = _reference_rows(np.asarray(q), dense_k, dense_v, starts, window)
    live = [1] if variant == "dead_slot" else [0, 1]
    # (the quantized tiers round the probabilities to the compute dtype
    # before the value product, as the dense quantized kernel does)
    tol = 2e-2 if scales else 1e-4
    np.testing.assert_allclose(out[live], ref[live], atol=tol, rtol=tol)
    if variant == "dead_slot":
        np.testing.assert_array_equal(out[0], np.asarray(q)[0])


def test_rows_that_do_not_fit_vmem_go_in_two_calls(monkeypatch):
    """A budget that one KV head's rows do not fit: the call is cut in two
    by query rows, the second half further on, and answers the same."""
    rng = np.random.default_rng(33)
    B, H, KV, D, S, ps, T = 2, 4, 2, 64, 256, 16, 40
    dense_k, dense_v, k_pages, v_pages, table = _make_paged(
        rng, B, KV, D, S, ps)
    starts = np.asarray([ps - 1, 3 * ps + 2], np.int32)
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    args = (q, jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(table), jnp.asarray(starts))
    whole = paged_decode_attention(*args)
    # a step of one KV head (two query heads): 408 KB at 40 rows, 296 KB
    # at 24
    monkeypatch.setattr(paged_attention, "VMEM_BUDGET_BYTES", 300 * 1024)
    calls = []
    call = paged_attention.pl.pallas_call
    monkeypatch.setattr(
        paged_attention.pl, "pallas_call",
        lambda *a, **kw: calls.append(kw["out_shape"].shape) or call(*a, **kw))
    split = paged_decode_attention(*args)
    assert calls == [(B, KV, 2 * 24, D), (B, KV, 2 * 16, D)]
    np.testing.assert_allclose(np.asarray(split), np.asarray(whole),
                               atol=1e-6)
    ref = _reference_rows(np.asarray(q), dense_k, dense_v, starts)
    np.testing.assert_allclose(np.asarray(split), ref, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("T", [1, 24])
def test_heads_of_256_read_and_write_through_the_pages(T):
    """(PR 60) ``head_dim`` 256, eight query heads a K/V head (Qwen3-Next's
    gated attention): a decode row and a chunk's rows through the write
    (the stacked leaf bitwise the XLA scatter's) and through the read of
    what was written, against the plain softmax."""
    rng = np.random.default_rng(256 + T)
    B, KV, D, S, ps, rep = 2, 2, 256, 128, 16, 8
    dense_k, dense_v, k_pages, v_pages, table = _make_paged(
        rng, B, KV, D, S, ps)
    starts = np.asarray([ps - 1, 5 * ps + 2], np.int32)
    leaves = [jnp.asarray(x)[None] for x in (k_pages, v_pages)]
    new = [jnp.asarray(rng.standard_normal((B, KV, D, T)), jnp.float32)
           for _ in leaves]
    wrote = [jax.jit(paged_write_columns)(
        leaf, jnp.asarray(0, jnp.int32), cols, jnp.asarray(table),
        jnp.asarray(starts)) for leaf, cols in zip(leaves, new)]
    for leaf, cols, out in zip(leaves, new, wrote):
        _same(out, _scatter_columns(leaf, 0, cols, table, starts))
    for dense, cols in zip((dense_k, dense_v), new):
        for b in range(B):
            dense[b, :, :, starts[b]:starts[b] + T] = np.asarray(cols[b])
    q = jnp.asarray(rng.standard_normal((B, T, KV * rep, D)), jnp.float32)
    out = np.asarray(paged_decode_attention(
        q, wrote[0][0], wrote[1][0], jnp.asarray(table),
        jnp.asarray(starts)))
    np.testing.assert_allclose(
        out, _reference_rows(np.asarray(q), dense_k, dense_v, starts),
        atol=1e-4, rtol=1e-4)


def test_a_chunk_of_512_at_head_dim_256_is_read_in_four_calls():
    """(PR 60) At the served shape (16 query heads over 2 K/V heads of 256,
    pages of 128, bfloat16) a chunk's 512 x 8 rows a K/V head do not fit a
    step, nor do 256 x 8 (two (2,048, 256) blocks and their float32
    accumulator: 8.25 MiB of the 8 a step may hold): the call halves twice,
    four calls of 128 positions each 128 further on. Traced, not run."""
    calls = []
    call = paged_attention.pl.pallas_call

    def noted(*a, **kw):
        calls.append(kw["out_shape"].shape)
        return call(*a, **kw)

    bf16 = jnp.bfloat16
    shape = jax.ShapeDtypeStruct
    try:
        paged_attention.pl.pallas_call = noted
        out = jax.eval_shape(
            functools.partial(paged_attention.paged_decode_attention,
                              page_size=128),
            shape((1, 512, 16, 256), bf16),
            shape((2, 64, 2, 256, 128), bf16),
            shape((2, 64, 2, 256, 128), bf16), shape((1, 64), jnp.int32),
            shape((1,), jnp.int32), layer=shape((), jnp.int32))
    finally:
        paged_attention.pl.pallas_call = call
    assert out.shape == (1, 512, 16, 256)
    assert calls == [(1, 2, 8 * 128, 256)] * 4


@pytest.mark.parametrize("KV", [4, 2])
def test_alibi_matches_dense_oracle(KV):
    """(With KV 2 the rows of two query heads share one product and each
    row takes its head's slope.) Bitwise for the T=1 row: the paged kernel builds the bias as the
    dense kernel does for the query at ``start`` (a scalar query
    position) and subtracts each row's own offset, an exact zero on
    row 0. Writing it as ``slope * (pos - (start + row))`` made the CPU
    contract the two kernels' multiply-adds differently (351 of 512
    elements, 3.0e-7)."""
    rng = np.random.default_rng(4)
    B, H, D, S, ps = 2, 4, 64, 128, 32
    dense_k, dense_v, k_pages, v_pages, table = _make_paged(
        rng, B, KV, D, S, ps)
    starts = np.asarray([40, 97], np.int32)
    slopes = jnp.asarray(rng.standard_normal(H) * 0.1, jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    out = paged_decode_attention(q, jnp.asarray(k_pages),
                                 jnp.asarray(v_pages), jnp.asarray(table),
                                 jnp.asarray(starts), alibi_slopes=slopes)
    oracle = decode_attention(q[:, 0], jnp.asarray(dense_k),
                              jnp.asarray(dense_v),
                              jnp.asarray(starts + 1),
                              alibi_slopes=slopes, block_s=ps)
    np.testing.assert_array_equal(np.asarray(out[:, 0]), np.asarray(oracle))


@pytest.mark.parametrize("packed", [False, True])
def test_quantized_pages_match_dense_oracle(packed):
    """int8 (and int32-packed) page pools with per-column scales: bitwise
    against the dense quantized kernel on the gathered view."""
    rng = np.random.default_rng(5)
    B, H, KV, D, S, ps = 2, 4, 2, 64, 128, 32
    pages_per_slot = S // ps
    P = B * pages_per_slot + 2
    k8 = rng.integers(-127, 128, (P, KV, D, ps)).astype(np.int8)
    v8 = rng.integers(-127, 128, (P, KV, D, ps)).astype(np.int8)
    ks = rng.uniform(0.01, 0.1, (P, KV, ps)).astype(np.float32)
    vs = rng.uniform(0.01, 0.1, (P, KV, ps)).astype(np.float32)
    perm = rng.permutation(P)[:B * pages_per_slot]
    table = perm.reshape(B, pages_per_slot).astype(np.int32)

    def gather(pages):
        return _gather(pages, table)

    starts = np.asarray([S - 3, ps + 7], np.int32)
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    kp, vp = (jnp.asarray(k8), jnp.asarray(v8))
    dk, dv = (jnp.asarray(gather(k8)), jnp.asarray(gather(v8)))
    if packed:
        kp, vp = pack_int8_sublanes(kp), pack_int8_sublanes(vp)
        dk, dv = pack_int8_sublanes(dk), pack_int8_sublanes(dv)
    out = paged_decode_attention(
        q, kp, vp, jnp.asarray(table), jnp.asarray(starts),
        k_scale_pages=jnp.asarray(ks), v_scale_pages=jnp.asarray(vs))
    oracle = decode_attention(
        q[:, 0], dk, dv, jnp.asarray(starts + 1),
        k_scale=jnp.asarray(gather(ks)), v_scale=jnp.asarray(gather(vs)),
        block_s=ps)
    np.testing.assert_array_equal(np.asarray(out[:, 0]), np.asarray(oracle))


def test_jit_and_eager_agree():
    """The kernel under jit (how the pool always calls it) is the same
    function it is eagerly — no trace-time shape surprises."""
    rng = np.random.default_rng(6)
    B, H, KV, D, S, ps = 2, 2, 2, 64, 64, 16
    _, _, k_pages, v_pages, table = _make_paged(rng, B, KV, D, S, ps)
    starts = np.asarray([9, 31], np.int32)
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    args = (q, jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(table), jnp.asarray(starts))
    np.testing.assert_array_equal(
        np.asarray(paged_decode_attention(*args)),      # (under jit)
        np.asarray(paged_attention.paged_decode_attention(*args)))


# ---------------------------------------------------------------------------
# the grid follows the live pages (ISSUE 24)
# ---------------------------------------------------------------------------
def _gather(pages, table):
    """(B, KV, ..., S) dense view through the table; sentinel entries
    clip to the last physical page like the pool's dense gather."""
    table = np.minimum(table, pages.shape[0] - 1)
    return np.concatenate([pages[table[:, j]]
                           for j in range(table.shape[1])], axis=-1)


# the served shape (Pythia-1.4B: 16 heads of 128, pages of 64) cut down
# only in slots and pages; 5 table entries a slot
_SERVED = dict(H=16, KV=16, D=128, ps=64, per_slot=5, P=12)
_SLOT_CASES = {
    # starts, table rows (12 = the unmapped sentinel)
    "start_0": ([0, 200], [[3, 12, 12, 12, 12], [7, 1, 9, 4, 12]]),
    "all_sentinel": ([0, 0, 70], [[12] * 5, [12] * 5, [2, 5, 12, 12, 12]]),
    "ends_on_page_edge": ([127, 63], [[0, 8, 12, 12, 12], [6, 12, 12, 12, 12]]),
    "one_past_page_edge": ([128, 64], [[0, 8, 4, 12, 12], [6, 10, 12, 12, 12]]),
    "full_row": ([319, 5], [[11, 10, 9, 8, 7], [0, 12, 12, 12, 12]]),
    "shared_prefix": ([150, 170, 131], [[1, 2, 3, 12, 12], [1, 2, 6, 12, 12],
                                        [1, 2, 9, 12, 12]]),
}


@pytest.mark.parametrize("case", sorted(_SLOT_CASES))
def test_served_shape_slots_match_dense_oracle(case):
    """Every head of a page in one grid step, one step a live page: dead
    slots, sentinel rows, page edges, a full table row and shared pages,
    each bitwise against the dense kernel on the gathered view. A slot
    whose row maps nothing is no step: its rows are finite and are not
    attention output."""
    starts, table = _SLOT_CASES[case]
    starts, table = np.asarray(starts, np.int32), np.asarray(table, np.int32)
    H, KV, D, ps, P = (_SERVED[k] for k in ("H", "KV", "D", "ps", "P"))
    rng = np.random.default_rng(24)
    k_pages = rng.standard_normal((P, KV, D, ps)).astype(np.float32)
    v_pages = rng.standard_normal((P, KV, D, ps)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((len(starts), 1, H, D)), jnp.float32)
    out = paged_decode_attention(q, jnp.asarray(k_pages),
                                 jnp.asarray(v_pages), jnp.asarray(table),
                                 jnp.asarray(starts))
    oracle = decode_attention(q[:, 0], jnp.asarray(_gather(k_pages, table)),
                              jnp.asarray(_gather(v_pages, table)),
                              jnp.asarray(starts + 1), block_s=ps)
    maps = (table < P).any(axis=1)
    np.testing.assert_array_equal(np.asarray(out[:, 0])[maps],
                                  np.asarray(oracle)[maps])
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("tier", ["bf16", "int8", "int32-packed"])
@pytest.mark.parametrize("T", [1, 3, 8])
def test_rows_and_cache_tiers_match_dense_oracle(T, tier):
    """Row t of a T-row call is the dense kernel's answer for a query at
    ``start + t`` (the call's columns are already in the pages), bitwise,
    for each K/V tier."""
    rng = np.random.default_rng(7)
    B, H, KV, D, ps, per_slot = 2, 4, 2, 128, 64, 3
    P = B * per_slot + 1
    table = rng.permutation(P)[:B * per_slot].reshape(B, per_slot) \
        .astype(np.int32)
    starts = np.asarray([ps - 2, 2 * ps + 5], np.int32)   # rows cross a page
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.bfloat16)
    scales = {}
    if tier == "bf16":
        k_pages = jnp.asarray(rng.standard_normal((P, KV, D, ps)),
                              jnp.bfloat16)
        v_pages = jnp.asarray(rng.standard_normal((P, KV, D, ps)),
                              jnp.bfloat16)
        dense_k, dense_v = (jnp.asarray(_gather(np.asarray(x), table))
                            for x in (k_pages, v_pages))
    else:
        k8 = rng.integers(-127, 128, (P, KV, D, ps)).astype(np.int8)
        v8 = rng.integers(-127, 128, (P, KV, D, ps)).astype(np.int8)
        ks = rng.uniform(0.01, 0.1, (P, KV, ps)).astype(np.float32)
        vs = rng.uniform(0.01, 0.1, (P, KV, ps)).astype(np.float32)
        k_pages, v_pages = jnp.asarray(k8), jnp.asarray(v8)
        dense_k = jnp.asarray(_gather(k8, table))
        dense_v = jnp.asarray(_gather(v8, table))
        if tier == "int32-packed":
            k_pages, v_pages, dense_k, dense_v = (
                pack_int8_sublanes(x)
                for x in (k_pages, v_pages, dense_k, dense_v))
        scales = dict(k_scale_pages=jnp.asarray(ks),
                      v_scale_pages=jnp.asarray(vs))
        dense_scales = dict(k_scale=jnp.asarray(_gather(ks, table)),
                            v_scale=jnp.asarray(_gather(vs, table)))
    out = paged_decode_attention(q, k_pages, v_pages, jnp.asarray(table),
                                 jnp.asarray(starts), **scales)
    assert out.shape == (B, T, H, D) and out.dtype == q.dtype
    for t in range(T):
        oracle = _dense_rows(
            q[:, t], dense_k, dense_v, jnp.asarray(starts + t + 1),
            block_s=ps, **(dense_scales if scales else {}))
        np.testing.assert_array_equal(
            np.asarray(out[:, t].astype(jnp.float32)),
            np.asarray(oracle.astype(jnp.float32)), err_msg=f"row {t}")


# PR 55: a KV head's rep query heads, T rows each, stand one head after
# another and are padded to whole sublane tiles ONCE. (rep, T, what else
# the call carries): every packed layout (rep * T short of a tile's
# multiple) under every mask and tier, the layouts that are what they
# were (rep 1; T in whole tiles) beside them, and the packed tier at
# three of them
_PACKED_CASES = [
    (rep, T, extra) for rep in (1, 4, 8) for T in (1, 3, 5, 8, 16)
    for extra in ("bf16", "window", "alibi", "int8")
] + [(1, 5, "int32-packed"), (4, 5, "int32-packed"),
     (8, 3, "int32-packed")]


@pytest.mark.parametrize("rep,T,extra", _PACKED_CASES)
def test_packed_rows_and_cache_tiers_match_dense_oracle(rep, T, extra):
    """The parity contract, row by row and head by head: row t of a
    T-row call is the dense kernel's answer for a query at ``start + t``
    (the call's columns are already in the pages), bitwise, for each K/V
    tier and any number of query heads a KV head: a row that stood at
    the wrong ``r // T`` or ``r % T`` is another head's or another
    position's answer. Where the dense kernel has no twin (a window; an
    ALiBi row past the first, whose bias it writes another way) the
    oracle is the same read with EVERY query head given a KV head of its
    own (its KV head's pages repeated): ``rep`` 1, the layout that never
    changed, which the plain reference holds in its turn. A block of 64
    rows and more (``T`` in whole tiles: the program of before PR 55) is
    held to one unit in bf16's last place in at most one value of a
    thousand: the CPU's compiler orders such a block's sums another way
    than the oracle's few rows, and the parent's kernel differs there
    from the same oracle in the same value."""
    rng = np.random.default_rng(55 + 16 * rep + T)
    B, KV, D, ps, per_slot = 2, 2, 64, 16, 4
    H, P = KV * rep, B * per_slot + 1
    table = rng.permutation(P)[:B * per_slot].reshape(B, per_slot) \
        .astype(np.int32)
    starts = np.asarray([ps - 2, 3 * ps - T], np.int32)   # rows cross a page
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.bfloat16)
    kwargs, dense_kwargs = {}, {}
    if extra in ("int8", "int32-packed"):
        k8 = rng.integers(-127, 128, (P, KV, D, ps)).astype(np.int8)
        v8 = rng.integers(-127, 128, (P, KV, D, ps)).astype(np.int8)
        ks = rng.uniform(0.01, 0.1, (P, KV, ps)).astype(np.float32)
        vs = rng.uniform(0.01, 0.1, (P, KV, ps)).astype(np.float32)
        k_pages, v_pages = jnp.asarray(k8), jnp.asarray(v8)
        dense_k, dense_v = _gather(k8, table), _gather(v8, table)
        if extra == "int32-packed":
            k_pages, v_pages, dense_k, dense_v = (
                pack_int8_sublanes(jnp.asarray(x))
                for x in (k_pages, v_pages, dense_k, dense_v))
        kwargs = dict(k_scale_pages=jnp.asarray(ks),
                      v_scale_pages=jnp.asarray(vs))
        dense_kwargs = dict(k_scale=_gather(ks, table),
                            v_scale=_gather(vs, table))
    else:
        k_pages, v_pages = (
            jnp.asarray(rng.standard_normal((P, KV, D, ps)), jnp.bfloat16)
            for _ in range(2))
        dense_k, dense_v = (_gather(np.asarray(x), table)
                            for x in (k_pages, v_pages))
    window = slopes = None
    if extra == "window":
        window = ps + ps // 2
        kwargs = dict(window=window)
        if starts[1] - window + 1 >= ps:    # behind every row's window:
            table[1, 0] = P                 # recycled
    if extra == "alibi":
        slopes = 2.0 ** -np.linspace(1, 8, H)
        kwargs = dict(alibi_slopes=jnp.asarray(slopes, jnp.float32))
    out = paged_decode_attention(q, k_pages, v_pages, jnp.asarray(table),
                                 jnp.asarray(starts), **kwargs)
    assert out.shape == (B, T, H, D) and out.dtype == q.dtype
    out = np.asarray(out.astype(jnp.float32))
    assert np.isfinite(out).all()

    def same(oracle):
        oracle = np.asarray(oracle.astype(jnp.float32)).reshape(out.shape)
        if rep * T < 64:
            np.testing.assert_array_equal(out, oracle)
            return
        assert rep == 1 or T % 8 == 0
        np.testing.assert_allclose(out, oracle, rtol=2.0 ** -7, atol=0)
        assert (out != oracle).mean() <= 1e-3

    if extra != "window" and (extra != "alibi" or T == 1):
        # every row at once: row t of slot b as a sequence of its own
        def rows(x):
            return jnp.asarray(np.repeat(np.asarray(x), T, axis=0))
        oracle = decode_attention(
            q.reshape(B * T, H, D), rows(dense_k), rows(dense_v),
            jnp.asarray((starts[:, None] + np.arange(T) + 1).reshape(-1)),
            block_s=ps, **{k: rows(v) for k, v in dense_kwargs.items()},
            **(kwargs if extra == "alibi" else {}))
        same(oracle)
        return
    ref = _reference_rows(
        np.asarray(q.astype(jnp.float32)), dense_k.astype(np.float32),
        dense_v.astype(np.float32), starts, window=window, slopes=slopes)
    np.testing.assert_allclose(out, ref, atol=3e-2, rtol=3e-2)
    if rep > 1:
        own = paged_decode_attention(
            q, jnp.repeat(k_pages, rep, axis=1),
            jnp.repeat(v_pages, rep, axis=1), jnp.asarray(table),
            jnp.asarray(starts), **kwargs)
        same(own)


def _tiles_then_heads(q, KV):
    """The operand as it was until PR 55: each query head's T rows padded
    to whole sublane tiles, THEN a KV head's rep heads stacked."""
    B, T, H, D = q.shape
    t_pad = -(-T // 8) * 8
    q4 = q.transpose(0, 2, 1, 3)
    if T < t_pad:
        q4 = jnp.pad(q4, ((0, 0), (0, 0), (0, t_pad - T), (0, 0)))
    return q4.reshape(B, KV, H // KV * t_pad, D)


def _heads_then_tiles_back(out, T, H):
    B, KV, rows, D = out.shape
    out = out.reshape(B, H, rows * KV // H, D)[:, :, :T]
    return out.transpose(0, 2, 1, 3)


@pytest.mark.parametrize("T", [1, 3, 5, 8, 16])
@pytest.mark.parametrize("rep", [1, 4, 8])
def test_a_kv_heads_rows_are_packed_once_into_whole_tiles(rep, T):
    """The wrapper's half of the layout, without a kernel: row ``r`` of a
    KV head's block is query row ``r % T`` of its head ``r // T``, the
    block is ``rep * T`` rows in whole tiles (not ``rep`` heads of one
    tile each: a decode step of Granite's, LFM2's or Mellum's folds the
    8 rows it reads), and the way back is its inverse. With ``rep`` 1,
    or ``T`` in whole tiles, the program is the one it was: the same
    jaxpr as the former layout's."""
    B, KV, D = 2, 2, 8
    H = KV * rep
    q = np.arange(B * T * H * D, dtype=np.float32).reshape(B, T, H, D)
    packed = np.asarray(paged_attention._pack_rows(jnp.asarray(q), KV))
    rows = -(-rep * T // 8) * 8
    assert packed.shape == (B, KV, rows, D)
    assert paged_attention.block_rows(rep, T) == rows
    for r in range(rep * T):
        np.testing.assert_array_equal(
            packed[:, :, r], q[:, r % T].reshape(B, KV, rep, D)[:, :, r // T])
    assert not packed[:, :, rep * T:].any()
    np.testing.assert_array_equal(
        np.asarray(paged_attention._unpack_rows(jnp.asarray(packed), T, H)),
        q)
    was = _tiles_then_heads(jnp.asarray(q), KV)
    same = rep == 1 or T % 8 == 0
    assert (was.shape == packed.shape) == same
    if same:
        assert str(jax.make_jaxpr(
            lambda x: paged_attention._pack_rows(x, KV))(q)) \
            == str(jax.make_jaxpr(lambda x: _tiles_then_heads(x, KV))(q))
        assert str(jax.make_jaxpr(
            lambda x: paged_attention._unpack_rows(x, T, H))(packed)) \
            == str(jax.make_jaxpr(
                lambda x: _heads_then_tiles_back(x, T, H))(packed))


def test_heads_that_do_not_fit_vmem_split_into_groups(monkeypatch):
    """A budget one page of every head does not fit: kv_group falls to a
    divisor of KV, the head-group axis comes back into the grid, and the
    answer is the same bit for bit (GQA, so a group carries rep heads)."""
    rng = np.random.default_rng(8)
    B, H, KV, D, S, ps = 2, 8, 4, 64, 128, 32
    dense_k, dense_v, k_pages, v_pages, table = _make_paged(
        rng, B, KV, D, S, ps)
    starts = np.asarray([S - 3, ps + 1], np.int32)
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    args = (q, jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(table), jnp.asarray(starts))
    shape = (B, H, KV, D, D, ps, S // ps, jnp.float32, jnp.float32, False)
    assert plan_grid(*shape)[0] == KV
    whole = paged_decode_attention(*args)
    monkeypatch.setattr(paged_attention, "VMEM_BUDGET_BYTES", 400 * 1024)
    kv_group, _, (groups, _) = plan_grid(*shape)
    assert (kv_group, groups) == (2, 2)
    split = paged_decode_attention(*args)
    np.testing.assert_array_equal(np.asarray(split), np.asarray(whole))
    oracle = decode_attention(q[:, 0], jnp.asarray(dense_k),
                              jnp.asarray(dense_v), jnp.asarray(starts + 1),
                              block_s=ps)
    np.testing.assert_array_equal(np.asarray(split[:, 0]),
                                  np.asarray(oracle))


# PR 53: a grid step folds its KV heads a RUN at a time (every head's
# scores, then the statistics, then every head's values). name: H, KV, D,
# page size, lanes of the stored page, query rows, what else the call
# carries, and the runs tried. The served shapes: Pythia (MHA 16, a
# 64-wide page in 128 lanes), Mellum (4 x 8, pages of 128, a full and a
# window group), Granite (8 x 4, heads of 64)
_RUN_CASES = {
    "pythia-decode": (16, 16, 128, 64, 128, 1, None, (2, 4, 8, 16)),
    "pythia-chunk64": (16, 16, 128, 64, 128, 64, None, (2, 4)),
    "pythia-int8": (16, 16, 128, 64, 64, 1, "int8", (16,)),
    "pythia-int32-packed": (16, 16, 128, 64, 64, 5, "int32-packed", (2, 16)),
    "mellum-decode": (32, 4, 128, 128, 128, 1, None, (2, 4)),
    "mellum-window": (32, 4, 128, 128, 128, 1, "window", (4,)),
    "mellum-verify-window": (32, 4, 128, 128, 128, 5, "window", (2, 4)),
    "granite-decode": (32, 8, 64, 128, 128, 1, None, (2, 4, 8)),
    "granite-alibi": (32, 8, 64, 128, 128, 5, "alibi", (4,)),
}


@pytest.mark.parametrize("case", sorted(_RUN_CASES))
def test_fold_in_runs_is_the_head_after_head_fold_bit_for_bit(
        case, monkeypatch):
    """Every value's arithmetic is in the order it was: a call whose
    steps fold their KV heads in runs of 2, 4, ... (between the cases
    every run the rule can return, the shape's own among them) answers bit
    for bit what the fold of one head after another answers (a run of 1:
    the kernel of PR 52)."""
    H, KV, D, ps, lanes, T, extra, runs = _RUN_CASES[case]
    rng = np.random.default_rng(53)
    B, per_slot = 2, 3
    S = per_slot * ps
    starts = np.asarray([2 * ps + ps // 2, 0], np.int32)
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.bfloat16)
    kwargs = {}
    if extra in ("int8", "int32-packed"):
        _, _, k_pages, v_pages, table, kwargs = _quantized_pool(
            rng, B, KV, D, S, ps, extra == "int32-packed")
    else:
        _, _, k_pages, v_pages, table = _make_paged(rng, B, KV, D, S, ps)
        k_pages, v_pages = (
            jnp.pad(jnp.asarray(x, jnp.bfloat16),
                    ((0, 0),) * 3 + ((0, lanes - ps),), constant_values=3.0)
            for x in (k_pages, v_pages))
    if extra == "window":
        kwargs = dict(window=ps + ps // 4)
        table[0, 0] = k_pages.shape[0]      # behind the window: recycled
    if extra == "alibi":
        kwargs = dict(alibi_slopes=jnp.asarray(
            2.0 ** -np.linspace(1, 8, H), jnp.float32))
    real = paged_attention.plan_grid
    shape = (B, H, KV, D, k_pages.shape[2], lanes, per_slot, k_pages.dtype,
             jnp.bfloat16, extra in ("int8", "int32-packed"))
    kv_group, rule, _ = real(*shape, query_rows=T)
    assert kv_group == KV

    def call(run):
        def planned(*args, **kw):
            kv_group, _, grid = real(*args, **kw)
            return kv_group, run, grid
        monkeypatch.setattr(paged_attention, "plan_grid", planned)
        return np.asarray(paged_decode_attention(
            q, k_pages, v_pages, jnp.asarray(table), jnp.asarray(starts),
            page_size=ps, **kwargs).astype(jnp.float32))

    one = call(1)
    assert np.isfinite(one).all()
    assert rule in runs
    for run in runs:
        np.testing.assert_array_equal(call(run), one, err_msg=f"run {run}")


@pytest.mark.parametrize("shape,T,rows,kv_group,run", [
    # (B, H, KV, D, lanes, entries a slot), query rows of the call -> the
    # rows of one KV head (its rep heads' rows packed, PR 55: in brackets
    # what rep heads of one tile each took), the heads of a step and of a
    # run
    ((64, 16, 16, 128, 128, 32), 1, 8, 16, 16),       # Pythia decode
    ((64, 16, 16, 128, 128, 32), 5, 8, 16, 16),       # ... verify
    ((1, 16, 16, 128, 128, 32), 64, 64, 16, 4),       # docs' chunk
    ((64, 16, 16, 128, 128, 32), 16, 16, 16, 8),
    ((1, 16, 16, 128, 128, 32), 128, 128, 16, 1),
    ((64, 32, 4, 128, 128, 64), 1, 8, 4, 4),          # Mellum decode [64]
    ((64, 32, 8, 64, 128, 128), 1, 8, 8, 8),          # Granite decode [32]
    ((256, 32, 8, 64, 128, 32), 1, 8, 8, 8),          # LFM2 decode [32]
    ((64, 32, 8, 64, 128, 128), 5, 24, 8, 4),         # ... verify [32]
    ((64, 32, 8, 64, 128, 128), 8, 32, 8, 4),         # ... 8 rows a head
    ((64, 8, 2, 64, 128, 16), 1, 8, 2, 2),            # fewer heads than 4
    ((1, 32, 8, 64, 128, 128), 128, 512, 4, 1),       # Granite's chunk
    ((1, 32, 4, 128, 128, 64), 128, 1024, 2, 1),      # Mellum's chunk
    ((96, 16, 2, 256, 128, 64), 1, 8, 2, 2),          # Qwen3-Next decode
    ((1, 16, 2, 256, 128, 64), 128, 1024, 1, 1),      # ... a chunk's quarter
    ((1, 16, 2, 256, 128, 64), 64, 512, 2, 1),
])
def test_the_run_follows_the_rows_of_a_kv_head(shape, T, rows, kv_group,
                                               run):
    """``plan_grid``'s second return, from static shapes alone: a power of
    two, at most the step's heads, ``RUN_ROWS // rows`` but ``RUN_HEADS``
    up to 64 rows; a chunk of 512 or 1,024 rows a KV head folds head
    after head as it always did. The rows are a KV head's ``rep * T`` in
    whole tiles (``block_rows``), so a GQA decode step runs as Pythia's
    does."""
    B, H, KV, D, lanes, per_slot = shape
    assert paged_attention.block_rows(H // KV, T) == rows
    got = plan_grid(B, H, KV, D, D, lanes, per_slot, jnp.bfloat16,
                    jnp.bfloat16, False, query_rows=T)
    assert got[:2] == (kv_group, run)


def test_grid_follows_live_pages_not_table_entries():
    """The mechanism itself, so that a return to a per-head or per-entry
    grid fails here: at the served shape a grid step holds every head of
    a page, and the call runs one step for each live (slot, page)."""
    B, H, KV, D, ps, per_slot, P = 64, 16, 16, 128, 64, 32, 256
    for dtype, quantized, Dc in ((jnp.bfloat16, False, D),
                                 (jnp.int8, True, D),
                                 (jnp.int32, True, D // 4)):
        kv_group, _, grid = plan_grid(
            B, H, KV, D, Dc, ps, per_slot, dtype, jnp.bfloat16, quantized)
        assert kv_group == KV
        assert int(np.prod(grid)) <= B * per_slot        # one page a step
    # a much wider model: the heads of one page no longer fit
    kv_group, _, grid = plan_grid(4, 64, 64, 256, 256, 128, 8, jnp.bfloat16,
                                  jnp.bfloat16, False)
    assert kv_group < 64 and 64 % kv_group == 0 and grid[0] == 64 // kv_group

    # 30 slots decoding at 100-600 tokens, the chat cell. The rest are
    # freed: rows all sentinel, and a ``start`` that kept counting (the
    # pool advances every slot's index each decode step)
    rng = np.random.default_rng(0)
    starts = rng.integers(0, per_slot * ps, (B,)).astype(np.int32)
    table = np.full((B, per_slot), P, np.int32)
    free = iter(rng.permutation(P))
    live = np.zeros((B,), np.int64)         # a freed slot is no step
    for b in rng.choice(B, 30, replace=False):
        starts[b] = rng.integers(100, 601)
        live[b] = (starts[b] + 1 + ps - 1) // ps
        for j in range(live[b]):
            table[b, j] = next(free)
    slot_of, entry_of, page_of, live_of, total = (
        np.asarray(x) for x in live_pages(jnp.asarray(starts),
                                          jnp.asarray(table), 1, ps, P))
    np.testing.assert_array_equal(live_of, live)
    assert total == live.sum() < B * per_slot // 8
    want = [(b, j) for b in range(B) for j in range(live[b])]
    assert list(zip(slot_of[:total], entry_of[:total])) == want
    np.testing.assert_array_equal(
        page_of[:total], [min(table[b, j], P - 1) for b, j in want])
    # past the end the lists stay in range (never run, but prefetchable)
    assert slot_of.max() < B and entry_of.max() < per_slot \
        and page_of.max() < P
    # a slot whose rows overflow its table row stops at the row's end
    _, entry_of, _, _, total = live_pages(
        jnp.asarray([per_slot * ps + 5], jnp.int32),
        jnp.zeros((1, per_slot), jnp.int32), 8, ps, P)
    assert int(total) == per_slot and int(entry_of[per_slot - 1]) \
        == per_slot - 1


def test_freed_slots_with_stale_starts_cost_no_step_and_change_nothing():
    """A freed slot's ``start`` is whatever the pool's index counted up
    to. Its row is unmapped, so it is no step at all, its rows come back
    finite, and the live slots' answers do not depend on it."""
    rng = np.random.default_rng(9)
    B, H, KV, D, S, ps = 3, 4, 2, 64, 128, 32
    dense_k, dense_v, k_pages, v_pages, table = _make_paged(
        rng, B, KV, D, S, ps)
    P = k_pages.shape[0]
    table[1] = P                                   # slot 1 was freed
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    outs = []
    for stale in (0, S - 1):
        starts = np.asarray([ps + 3, stale, S - 2], np.int32)
        *_, live, total = live_pages(jnp.asarray(starts), jnp.asarray(table),
                                     1, ps, P)
        assert live.tolist() == [2, 0, 4] and int(total) == 6
        outs.append(np.asarray(paged_decode_attention(
            q, jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(table), jnp.asarray(starts))))
        assert np.isfinite(outs[-1]).all()
    np.testing.assert_array_equal(outs[0][[0, 2]], outs[1][[0, 2]])
    oracle = decode_attention(q[:, 0], jnp.asarray(dense_k),
                              jnp.asarray(dense_v), jnp.asarray(starts + 1),
                              block_s=ps)
    np.testing.assert_array_equal(outs[1][[0, 2], 0],
                                  np.asarray(oracle)[[0, 2]])


# ---------------------------------------------------------------------------
# the work list holds the slots that map a page and no others (ISSUE 31)
# ---------------------------------------------------------------------------
def test_a_table_that_maps_nothing_is_a_grid_of_no_step():
    """Every row all sentinel (a pool nobody is seated in): the list is
    empty, the call returns, and what it returns is finite."""
    rng = np.random.default_rng(31)
    B, H, KV, D, ps, per_slot, P = 3, 4, 2, 64, 32, 4, 6
    table = np.full((B, per_slot), P, np.int32)
    starts = np.asarray([0, 70, per_slot * ps + 9], np.int32)  # stale
    *_, live, total = live_pages(jnp.asarray(starts), jnp.asarray(table), 1,
                                 ps, P)
    assert live.tolist() == [0, 0, 0] and int(total) == 0
    pages = jnp.full((P, KV, D, ps), jnp.nan, jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    out = paged_decode_attention(q, pages, pages, jnp.asarray(table),
                                 jnp.asarray(starts))
    assert out.shape == q.shape and np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("tier", ["bf16", "int8"])
@pytest.mark.parametrize("rep", [1, 8])
@pytest.mark.parametrize("T", [1, 8])
def test_dead_slots_between_live_ones_are_no_step_and_change_nothing(
        T, rep, tier):
    """Freed slots (rows all sentinel, stale starts) before, between and
    after two live ones, every unmapped page poisoned: the live slots'
    rows are bitwise the dense kernel's and bitwise those of the same
    call without the dead slots, and the dead slots' rows are finite."""
    rng = np.random.default_rng(310 + T + rep)
    KV, D, ps, per_slot = 2, 64, 32, 4
    B, H, P = 5, KV * rep, 2 * per_slot + 3
    alive = np.asarray([1, 3])
    starts = np.asarray([per_slot * ps - 1, ps + 3, 0, 2 * ps - 3, 77],
                        np.int32)
    table = np.full((B, per_slot), P, np.int32)
    perm = rng.permutation(P - 1)       # page P - 1, the clip's, is unmapped
    table[1, :2], table[3, :3] = perm[:2], perm[2:5]
    unmapped = np.setdiff1d(np.arange(P), table[table < P])
    # bf16, the served dtype (a 64-row fp32 product is blocked otherwise
    # than the oracle's 8-row one on the CPU: 5e-7 at rep 8 with or
    # without dead slots)
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.bfloat16)
    scales, dense_scales = {}, {}
    if tier == "bf16":
        clean_k = jnp.asarray(rng.standard_normal((P, KV, D, ps)),
                              jnp.bfloat16)
        clean_v = jnp.asarray(rng.standard_normal((P, KV, D, ps)),
                              jnp.bfloat16)
        k_pages = clean_k.at[unmapped].set(jnp.nan)
        v_pages = clean_v.at[unmapped].set(jnp.nan)
    else:
        clean_k = rng.integers(-127, 128, (P, KV, D, ps)).astype(np.int8)
        clean_v = rng.integers(-127, 128, (P, KV, D, ps)).astype(np.int8)
        k_pages, v_pages = clean_k, clean_v
        ks = rng.uniform(0.01, 0.1, (P, KV, ps)).astype(np.float32)
        vs = rng.uniform(0.01, 0.1, (P, KV, ps)).astype(np.float32)
        dense_scales = dict(k_scale=jnp.asarray(_gather(ks, table)),
                            v_scale=jnp.asarray(_gather(vs, table)))
        ks[unmapped] = vs[unmapped] = np.nan     # the poison of this tier
        scales = dict(k_scale_pages=jnp.asarray(ks),
                      v_scale_pages=jnp.asarray(vs))
    *_, live, total = live_pages(jnp.asarray(starts), jnp.asarray(table), T,
                                 ps, P)
    assert live.tolist() == [0, 2, 0, 2 if T == 1 else 3, 0]
    assert int(total) == int(live.sum())

    def call(rows):
        return np.asarray(paged_decode_attention(
            q[rows], jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(table[rows]), jnp.asarray(starts[rows]), **scales))

    out = call(np.arange(B))
    assert np.isfinite(out.astype(np.float32)).all()
    np.testing.assert_array_equal(out[alive], call(alive))
    dense_k = jnp.asarray(_gather(np.asarray(clean_k), table))
    dense_v = jnp.asarray(_gather(np.asarray(clean_v), table))
    for t in range(T):
        oracle = _dense_rows(q[:, t], dense_k, dense_v,
                             jnp.asarray(starts + t + 1), block_s=ps,
                             **dense_scales)
        np.testing.assert_array_equal(out[alive, t].astype(np.float32),
                                      np.asarray(oracle, np.float32)[alive],
                                      err_msg=f"row {t}")


# ---------------------------------------------------------------------------
# the write: columns into the stacked leaf, in place (ISSUE 27)
# ---------------------------------------------------------------------------
from deepspeed_tpu.ops.attention.paged_attention import (  # noqa: E402
    plan_write,
)

paged_write_columns = _once("paged_write_columns")
paged_write_runs = _once("paged_write_runs")

# dtype and stored head dim of a 128-wide head in each K/V tier
_WRITE_TIERS = {"bf16": (jnp.bfloat16, 128), "int8": (jnp.int8, 128),
                "int32-packed": (jnp.int32, 32)}


def _values(rng, shape, dtype):
    if dtype == jnp.int32:                   # four packed int8: any word
        return jnp.asarray(rng.integers(-2 ** 31, 2 ** 31, shape), dtype)
    if dtype == jnp.int8:
        return jnp.asarray(rng.integers(-127, 128, shape), dtype)
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _scatter_columns(leaf, layer, cols, table, starts):
    """What the model's XLA scatter did: ``buf.at[pages, :, :, offs]
    .set(mode="drop")`` on one layer, sentinels and positions out of
    range dropped."""
    P, ps = leaf.shape[1], leaf.shape[-1]
    maxP, T = table.shape[1], cols.shape[-1]
    pos = starts[:, None] + np.arange(T)[None]
    valid = (pos >= 0) & (pos < maxP * ps)
    pages = np.where(valid, np.take_along_axis(
        table, np.clip(pos // ps, 0, maxP - 1), axis=1), P)
    if leaf.ndim == 4:                                    # a scale leaf
        return leaf.at[layer, pages, :, pos % ps].set(
            cols.transpose(0, 2, 1), mode="drop")
    return leaf.at[layer, pages, :, :, pos % ps].set(
        cols.transpose(0, 3, 1, 2), mode="drop")


def _scatter_runs(leaf, dense, tables, positions):
    """What ``_scatter_cols_body`` did on the stacked leaf."""
    P, ps = leaf.shape[1], leaf.shape[-1]
    maxP = tables.shape[1]
    valid = (positions >= 0) & (positions < maxP * ps)
    pages = np.where(valid, np.take_along_axis(
        tables, np.clip(positions // ps, 0, maxP - 1), axis=1), P)
    pos = jnp.asarray(positions)
    if leaf.ndim == 4:
        vals = jnp.take_along_axis(dense, pos[None, :, None, :], axis=3,
                                   mode="clip").transpose(1, 3, 0, 2)
        return leaf.at[:, pages, :, positions % ps].set(vals, mode="drop")
    vals = jnp.take_along_axis(dense, pos[None, :, None, None, :], axis=4,
                               mode="clip").transpose(1, 4, 0, 2, 3)
    return leaf.at[:, pages, :, :, positions % ps].set(vals, mode="drop")


def _same(a, b):
    np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                  np.asarray(b.astype(jnp.float32)))


@pytest.mark.parametrize("tier", sorted(_WRITE_TIERS))
@pytest.mark.parametrize("T", [1, 4, 8])
def test_write_columns_matches_the_scatter_it_replaced(T, tier):
    """A decode (T=1) or verify (T=4, 8) step's columns: a run inside a
    page, one that crosses into the next page, one whose next page is
    unmapped (that part is dropped), a freed slot with a stale start
    and a start past the table's end; the whole stacked leaf bitwise
    equal to the XLA scatter's, so every other layer is untouched."""
    dtype, Dc = _WRITE_TIERS[tier]
    rng = np.random.default_rng(27)
    L, P, KV, ps, maxP = 3, 9, 2, 64, 3
    leaf = _values(rng, (L, P, KV, Dc, ps), dtype)
    table = np.asarray([[4, 2, P], [7, 0, 8], [1, P, P], [P, P, P],
                        [3, 5, 6]], np.int32)
    starts = np.asarray([10, ps - 2, ps - 3, 77, maxP * ps - 1], np.int32)
    cols = _values(rng, (len(starts), KV, Dc, T), dtype)
    out = jax.jit(paged_write_columns)(leaf, jnp.asarray(1, jnp.int32),
                                       cols, jnp.asarray(table),
                                       jnp.asarray(starts))
    _same(out, _scatter_columns(leaf, 1, cols, table, starts))
    _same(out[0], leaf[0])
    _same(out[2], leaf[2])
    assert not np.array_equal(np.asarray(out[1].astype(jnp.float32)),
                              np.asarray(leaf[1].astype(jnp.float32)))


@pytest.mark.parametrize("tier", sorted(_WRITE_TIERS))
@pytest.mark.parametrize("first", [64, 64 + 37])
def test_write_chunk_matches_the_scatter_it_replaced(first, tier):
    """``paged_chunk``'s window, 64 columns of one slot's dense row in
    every layer: a whole page at an aligned start, parts of two pages
    at an unaligned one."""
    dtype, Dc = _WRITE_TIERS[tier]
    rng = np.random.default_rng(28)
    L, P, KV, ps, maxP = 2, 6, 2, 64, 4
    leaf = _values(rng, (L, P, KV, Dc, ps), dtype)
    dense = _values(rng, (L, 1, KV, Dc, maxP * ps), dtype)
    table = np.asarray([[5, 1, 3, P]], np.int32)
    out = jax.jit(paged_write_runs, static_argnums=4)(
        leaf, dense, jnp.asarray(table), jnp.asarray([first], jnp.int32), 64)
    positions = first + np.arange(64)[None]
    _same(out, _scatter_runs(leaf, dense, table, positions))
    touched = {int(table[0, p // ps]) for p in positions[0]}
    for page in set(range(P)) - touched:
        _same(out[:, page], leaf[:, page])


@pytest.mark.parametrize("tier", sorted(_WRITE_TIERS))
def test_write_admitted_rows_matches_the_scatter_it_replaced(tier):
    """``_paged_admit_rows``: every column of a prefill cache through
    per-row tables; what a row does not map (and a padding row maps
    nothing) is dropped."""
    dtype, Dc = _WRITE_TIERS[tier]
    rng = np.random.default_rng(29)
    L, P, KV, ps, maxP = 2, 7, 2, 64, 3
    leaf = _values(rng, (L, P, KV, Dc, ps), dtype)
    dense = _values(rng, (L, 3, KV, Dc, maxP * ps), dtype)
    tables = np.asarray([[6, 2, P], [P, P, P], [0, P, P]], np.int32)
    out = jax.jit(paged_write_runs, static_argnums=4)(
        leaf, dense, jnp.asarray(tables), jnp.zeros((3,), jnp.int32),
        maxP * ps)
    positions = np.broadcast_to(np.arange(maxP * ps)[None], (3, maxP * ps))
    _same(out, _scatter_runs(leaf, dense, tables, positions))
    for page in (1, 3, 4, 5):
        _same(out[:, page], leaf[:, page])


@pytest.mark.parametrize("tier", sorted(_WRITE_TIERS))
def test_write_that_maps_nothing_touches_nothing(tier):
    """Sentinel pages and positions out of range are not in the work
    list: a call whose every write is dropped runs no step and hands
    the leaf back as it was, bit for bit."""
    dtype, Dc = _WRITE_TIERS[tier]
    rng = np.random.default_rng(30)
    L, P, KV, ps, maxP = 2, 4, 2, 64, 2
    leaf = _values(rng, (L, P, KV, Dc, ps), dtype)
    cols = _values(rng, (3, KV, Dc, 4), dtype)
    # a freed slot, a start past the row's end, a negative start whose
    # four columns all lie under position 0
    table = jnp.asarray([[P, P], [0, 1], [2, 3]], jnp.int32)
    starts = jnp.asarray([5, maxP * ps, -4], jnp.int32)
    _same(paged_write_columns(leaf, 0, cols, table, starts), leaf)
    dense = _values(rng, (L, 2, KV, Dc, maxP * ps), dtype)
    _same(paged_write_runs(leaf, dense, jnp.full((2, maxP), P, jnp.int32),
                           jnp.zeros((2,), jnp.int32), maxP * ps), leaf)
    _same(paged_write_runs(leaf, dense, table[1:],
                           jnp.asarray([maxP * ps, maxP * ps + 9]), 64),
          leaf)


def test_write_leaves_a_shared_prefix_page_alone():
    """Two slots map the same read-only prefix page and write their own
    next page: the shared page keeps every bit, each slot's column is
    its own."""
    rng = np.random.default_rng(31)
    L, P, KV, Dc, ps = 2, 5, 2, 128, 64
    leaf = _values(rng, (L, P, KV, Dc, ps), jnp.bfloat16)
    table = np.asarray([[1, 3, P], [1, 4, P]], np.int32)
    starts = np.asarray([ps + 9, ps + 20], np.int32)
    cols = _values(rng, (2, KV, Dc, 1), jnp.bfloat16)
    out = paged_write_columns(leaf, 0, cols, jnp.asarray(table),
                              jnp.asarray(starts))
    _same(out, _scatter_columns(leaf, 0, cols, table, starts))
    _same(out[:, 1], leaf[:, 1])
    _same(out[0, 3, :, :, 9], cols[0, :, :, 0])
    _same(out[0, 4, :, :, 20], cols[1, :, :, 0])


@pytest.mark.parametrize("T", [1, 8])
def test_write_scale_leaves_through_the_same_kernel(T):
    """The (L, P, KV, page_size) scale leaves of the quantized tiers go
    through ``paged_write`` with the heads in the stored-dim's place."""
    rng = np.random.default_rng(32)
    L, P, KV, ps, maxP = 2, 6, 4, 64, 2
    leaf = jnp.asarray(rng.uniform(0.01, 0.1, (L, P, KV, ps)), jnp.float32)
    table = np.asarray([[2, 5], [P, P], [0, 3]], np.int32)
    starts = np.asarray([ps - 3, 7, 12], np.int32)
    cols = jnp.asarray(rng.uniform(0.01, 0.1, (3, KV, T)), jnp.float32)
    out = paged_write_columns(leaf, 1, cols, jnp.asarray(table),
                              jnp.asarray(starts))
    _same(out, _scatter_columns(leaf, 1, cols, table, starts))
    dense = jnp.asarray(rng.uniform(0.01, 0.1, (L, 3, KV, maxP * ps)),
                        jnp.float32)
    out = paged_write_runs(leaf, dense, jnp.asarray(table),
                           jnp.asarray(starts), T)
    _same(out, _scatter_runs(leaf, dense, table,
                             starts[:, None] + np.arange(T)[None]))


def test_write_group_follows_the_vmem_budget(monkeypatch):
    """``plan_write`` keeps every KV head in one step at the served
    shape and splits the heads where a page of each does not fit; the
    split call writes the same bits."""
    assert plan_write(16, 128, 64, 128, jnp.bfloat16) == (16, 128)
    assert plan_write(16, 32, 128, 128, jnp.int32) == (16, 128)
    # (PR 60) two K/V heads of 256: 384 KB a step, both heads in one
    assert plan_write(2, 256, 128, 128, jnp.bfloat16) == (2, 128)
    rng = np.random.default_rng(33)
    L, P, KV, Dc, ps = 2, 4, 4, 128, 64
    leaf = _values(rng, (L, P, KV, Dc, ps), jnp.bfloat16)
    cols = _values(rng, (2, KV, Dc, 2), jnp.bfloat16)
    args = (leaf, 1, cols, jnp.asarray([[3, 0], [2, 4]], jnp.int32),
            jnp.asarray([ps - 1, 5], jnp.int32))
    whole = paged_write_columns(*args)
    monkeypatch.setattr(paged_attention, "VMEM_BUDGET_BYTES", 400 * 1024)
    assert plan_write(KV, Dc, ps, ps, jnp.bfloat16)[0] == 2
    _same(paged_write_columns(*args), whole)


@pytest.mark.parametrize("tier", sorted(_WRITE_TIERS))
def test_pages_stored_in_whole_lane_tiles_read_and_write_the_same(tier):
    """The pool stores a 64-wide page in the first 64 of 128 lanes
    (``page_lanes``) and tells the kernels the page size: the write
    touches those lanes alone and the read answers bit for bit what it
    answers on the unpadded leaf."""
    from deepspeed_tpu.models.kv_cache_spec import page_lanes

    dtype, Dc = _WRITE_TIERS[tier]
    rng = np.random.default_rng(34)
    L, P, KV, ps, maxP, H, D = 2, 7, 2, 64, 3, 4, 128
    lanes = page_lanes(ps)
    assert (lanes, page_lanes(128), page_lanes(8)) == (128, 128, 128)
    leaf = _values(rng, (L, P, KV, Dc, ps), dtype)
    junk = _values(rng, (L, P, KV, Dc, lanes - ps), dtype)
    wide = jnp.concatenate([leaf, junk], axis=-1)
    table = np.asarray([[4, 2, P], [6, 0, 5]], np.int32)
    starts = np.asarray([ps - 2, ps + 9], np.int32)
    cols = _values(rng, (2, KV, Dc, 4), dtype)
    args = (jnp.asarray(1, jnp.int32), cols, jnp.asarray(table),
            jnp.asarray(starts))
    out = paged_write_columns(wide, *args, page_size=ps)
    _same(out[..., :ps], paged_write_columns(leaf, *args))
    _same(out[..., ps:], junk)
    dense = _values(rng, (L, 2, KV, Dc, maxP * ps), dtype)
    out = paged_write_runs(wide, dense, jnp.asarray(table),
                           jnp.asarray(starts), 64, page_size=ps)
    _same(out[..., :ps], paged_write_runs(leaf, dense, jnp.asarray(table),
                                          jnp.asarray(starts), 64))
    _same(out[..., ps:], junk)

    q = jnp.asarray(rng.standard_normal((2, 1, H, D)), jnp.bfloat16)
    scales, wide_scales = {}, {}
    if tier != "bf16":
        sc = jnp.asarray(rng.uniform(0.01, 0.1, (L, P, KV, ps)), jnp.float32)
        scales = dict(k_scale_pages=sc, v_scale_pages=sc)
        wide_sc = jnp.pad(sc, ((0, 0),) * 3 + ((0, lanes - ps),),
                          constant_values=7.0)
        wide_scales = dict(k_scale_pages=wide_sc, v_scale_pages=wide_sc)
    narrow = paged_decode_attention(q, leaf, leaf, jnp.asarray(table),
                                    jnp.asarray(starts), layer=1, **scales)
    padded = paged_decode_attention(q, wide, wide, jnp.asarray(table),
                                    jnp.asarray(starts), layer=1,
                                    page_size=ps, **wide_scales)
    _same(padded, narrow)


# ---------------------------------------------------------------------------
# a page list that is a choice (PR 56): learned sparse attention reads the
# blocks each (query, KV head) chose and its window (ops/attention/
# sparse_read.py: one kernel over (query tile, KV head, page) steps)
# ---------------------------------------------------------------------------
def _sparse_case(rng, B, KV, D, S, ps, sizes, qpos):
    """Pages, a table and a random choice as ``choose_blocks`` hands it:
    (N, KV, nb) blocks each query reads whole (never one that meets its
    window; every block under ``dense_len``), and the float64 softmax over
    what the equations let each query see."""
    from deepspeed_tpu.ops.attention import sparse_index as si

    dense_k, dense_v, k_pages, v_pages, table = _make_paged(
        rng, B, KV, D, S, ps)
    nb = S // sizes.block_size
    b = np.arange(nb)
    meets = (b + 1) * sizes.block_size - 1 >= qpos[:, None] \
        - sizes.window_size + 1
    # the topk best of random scores, the first blocks before all others
    score = rng.random((len(qpos), KV, nb))
    score[..., :sizes.init_blocks] = 2.0
    score = np.where(meets[:, None, :], -1.0, score)
    kth = np.sort(score, axis=-1)[..., -sizes.topk][..., None]
    blocks = (score >= kth) & (score >= 0)
    blocks |= (qpos + 1 < sizes.dense_len)[:, None, None]
    may = np.asarray(si.token_mask(jnp.asarray(blocks)[None],
                                   jnp.asarray(qpos)[None], sizes, S))[0]
    seen = may & (np.arange(S) <= qpos[:, None])[None]     # (KV, N, S)
    return (dense_k, dense_v, jnp.asarray(k_pages)[None],
            jnp.asarray(v_pages)[None], table, blocks, seen)


def _seen_softmax(q, k, v, seen):
    """``q`` (N, H, D) over one slot's ``k``, ``v`` (KV, D, S) where
    ``seen`` (KV, N, S)."""
    N, H, D = q.shape
    KV = k.shape[0]
    qg = q.astype(np.float64).reshape(N, KV, H // KV, D)
    s = np.einsum("nkrd,kds->knrs", qg, k.astype(np.float64)) / np.sqrt(D)
    s = np.where(seen[:, :, None, :], s, -np.inf)
    p = np.exp(s - s.max(axis=-1, keepdims=True))
    return np.einsum("knrs,kds->nkrd", p / p.sum(-1, keepdims=True),
                     v.astype(np.float64)).reshape(N, H, D)


@pytest.mark.parametrize("rep,ps,bk", [(8, 16, 8), (2, 128, 64)])
def test_decode_rows_read_their_own_choice_and_their_window(rep, ps, bk):
    """A (row, KV head)'s steps are the pages that hold a block IT chose
    or meet ITS window, nobody else's, and a page's unchosen block stays
    unseen (a bit a block, no mask): a row under ``dense_len`` reads all
    before it, a row that does not run reads nothing and comes back zero.
    The numerics are the plain softmax's over the equations' tokens."""
    from deepspeed_tpu.ops.attention import sparse_index as si
    from deepspeed_tpu.ops.attention import sparse_read as sr

    rng = np.random.default_rng(56 + rep)
    B, KV, D, S = 4, 2, 64, 16 * ps
    sizes = si.SparseSizes(2 * bk, bk, bk, 1, 3 * bk, 3, 5 * ps)
    qpos = np.asarray([S - 1, 9 * ps + 3, 2 * ps + 1, 7 * ps], np.int32)
    dense_k, dense_v, k_pages, v_pages, table, blocks, seen = _sparse_case(
        rng, B, KV, D, S, ps, sizes, qpos)
    q = rng.standard_normal((B, KV * rep, D)).astype(np.float32)
    running = np.asarray([True, True, True, False])
    read = jax.jit(functools.partial(
        sr.read_rows, sizes=sizes, page_size=ps, scale=1 / np.sqrt(D)))
    y, pages = read(jnp.asarray(q), k_pages, v_pages, None,
                    jnp.asarray(table), jnp.asarray(qpos),
                    jnp.asarray(blocks), running=jnp.asarray(running))
    for r in range(3):
        np.testing.assert_allclose(
            np.asarray(y)[r], _seen_softmax(
                q[r:r + 1], dense_k[r], dense_v[r], seen[:, r:r + 1])[0],
            atol=1e-4, rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(y)[3], 0.0)
    # the list: a page a (row, KV head) that holds something it sees
    own = seen.reshape(KV, B, S // ps, ps).any(-1)[:, :3]
    assert int(pages) == own.sum()
    (page, entry, _), count = sr.rows_plan(
        jnp.asarray(blocks), jnp.asarray(qpos), jnp.asarray(table), sizes,
        ps, k_pages.shape[1], jnp.asarray(running))
    page, entry, count = (np.asarray(x) for x in (page, entry, count))
    assert count.sum() == own.sum()
    assert count.max() <= len(page) // (B * KV)
    first = np.cumsum(count) - count
    for r in range(B * KV):
        mine = entry[first[r]:first[r] + count[r]]
        assert (np.diff(mine) > 0).all()
        assert mine.tolist() == (np.flatnonzero(
            own[r % KV, r // KV]).tolist() if r // KV < 3 else [])
        assert (page[first[r]:first[r] + count[r]]
                == table[r // KV, mine]).all()


@pytest.mark.parametrize("p0", [8, 40])
def test_a_chunks_queries_each_read_their_own_choice(p0):
    """A chunk's queries stand as the rows of the blocks they chose, a
    block's tiles one step each, and every query's partial results join
    its window's: the plain softmax over the equations' tokens, for a
    chunk that crosses ``dense_len`` (48) and one past it, with blocks
    nobody chose, blocks everybody chose, more queries than a tile and a
    last tile that is not full."""
    from deepspeed_tpu.ops.attention import sparse_index as si
    from deepspeed_tpu.ops.attention import sparse_read as sr

    rng = np.random.default_rng(57 + p0)
    KV, rep, D, ps, bk, T = 2, 4, 64, 16, 8, 40
    S = 8 * ps
    sizes = si.SparseSizes(2 * bk, bk, bk, 1, 2 * bk, 3, 48)
    qpos = (p0 + np.arange(T)).astype(np.int32)
    dense_k, dense_v, k_pages, v_pages, table, blocks, seen = _sparse_case(
        rng, 1, KV, D, S, ps, sizes, qpos)
    q = rng.standard_normal((T, KV * rep, D)).astype(np.float32)
    y, tiles = jax.jit(functools.partial(
        sr.read_chunk, sizes=sizes, page_size=ps, scale=1 / np.sqrt(D)))(
        jnp.asarray(q), k_pages, v_pages, None, jnp.asarray(table[0]),
        jnp.asarray(qpos), jnp.asarray(blocks))
    np.testing.assert_allclose(
        np.asarray(y), _seen_softmax(q, dense_k[0], dense_v[0], seen),
        atol=1e-4, rtol=1e-4)
    # a block's steps: the queries that read it whole, a tile at a time
    far = blocks & (np.arange(S // bk)[None, :]
                    < ((qpos - sizes.window_size + 1) // bk)[:, None])[
                        :, None, :]
    assert int(tiles) == (-(-far.sum(0) // sr.CHUNK_TILE)).sum()
