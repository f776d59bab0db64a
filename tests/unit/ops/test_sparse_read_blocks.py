"""The decode rows' chosen read in blocks of pages (PR 57;
``ops/attention/sparse_read.py``: ``rows_plan``'s lists cut into blocks of
``pages_a_step`` pages by ``latent_attention.page_blocks``, the kernel
fetching a block's pages itself and folding them as one run): the block
list against a walk of the lists, the kernel in interpret mode against a
float32 dense softmax under the equations' mask, and the host's bounds
(``sparse_index.pages_most``) against the plan's counts."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import sparse_index as si
from deepspeed_tpu.ops.attention import sparse_read as sr
from deepspeed_tpu.ops.attention.latent_attention import page_blocks
from tests.unit.ops.test_paged_attention import _sparse_case

KV, REP, D, PS, BK = 2, 8, 64, 16, 8
G = sr.pages_a_step(REP, D, PS, jnp.float32)
# dense_len at 2 G + 3 pages: a row under it lists up to 2 G + 2 pages;
# one past it its window's 3-4 pages and at most 3 chosen blocks' pages
SIZES = si.SparseSizes(2 * BK, BK, BK, 1, 3 * BK, 3, (2 * G + 3) * PS)
E = 3 * G + 8                   # table entries a row
S = E * PS


@functools.lru_cache(maxsize=None)
def _case(seed, qpos, running=None):
    """``test_paged_attention._sparse_case`` at this file's sizes: pages at
    a random placement, a random choice as ``choose_blocks`` hands it, and
    what each (KV head, row) sees of the ``S`` positions (nothing, for a
    row that does not run)."""
    qpos = np.asarray(qpos, np.int32)
    k, v, k_pages, v_pages, table, blocks, seen = _sparse_case(
        np.random.default_rng(seed), len(qpos), KV, D, S, PS, SIZES, qpos)
    if running is not None:
        seen = seen & np.asarray(running)[None, :, None]
    return dict(k=k, v=v, k_pages=k_pages, v_pages=v_pages, table=table,
                qpos=qpos, blocks=blocks, seen=seen, P=k_pages.shape[1],
                running=None if running is None else np.asarray(running))


@functools.lru_cache(maxsize=None)
def _plan(seed, qpos, running=None):
    """``rows_plan`` over :func:`_case` (one plan a case, whatever the
    block sizes it is cut into)."""
    case = _case(seed, qpos, running)
    lists, count = sr.rows_plan(
        jnp.asarray(case["blocks"]), jnp.asarray(case["qpos"]),
        jnp.asarray(case["table"]), SIZES, PS, case["P"],
        None if case["running"] is None else jnp.asarray(case["running"]))
    return [np.asarray(x) for x in lists], np.asarray(count)


def _dense_softmax(q, k, v, seen):
    """``q`` (B, H, D) float32, a row a slot, over ``k``, ``v`` (B, KV, D,
    S) where ``seen`` (KV, B, S): the plain float32 softmax."""
    B, H, _ = q.shape
    qg = q.reshape(B, KV, H // KV, D)
    s = np.einsum("bkrd,bkds->bkrs", qg, k).astype(np.float32) / np.sqrt(
        np.float32(D))
    s = np.where(seen.transpose(1, 0, 2)[:, :, None, :], s, -np.inf)
    p = np.exp(s - s.max(axis=-1, keepdims=True))
    return np.einsum("bkrs,bkds->bkrd", p / p.sum(-1, keepdims=True),
                     v).reshape(B, H, D).astype(np.float32)


# positions by the pages a row under dense_len lists: one page, exactly G,
# exactly 2 G, a partial last block; and rows past dense_len
ONE, WHOLE, TWO, PART = 3, G * PS - 1, 2 * G * PS - 1, (G + 2) * PS + 5
PAST = (SIZES.dense_len + 7, S - 1, SIZES.dense_len - 1)


@pytest.mark.parametrize("g", [1, 2, 4, 8, 16])
@pytest.mark.parametrize("name,qpos,running", [
    ("under", (ONE, WHOLE, TWO, PART), None),
    ("past", PAST, None),
    ("mixed", (PART, PAST[0], ONE, PAST[1]), (True, True, False, True)),
])
def test_every_listed_page_is_in_exactly_one_block(name, qpos, running, g):
    """``page_blocks`` over ``rows_plan``'s counts against a walk of the
    lists: every listed page in exactly one block, an entry's blocks one
    after another and its pages ascending through them, ``ceil(count /
    g)`` blocks an entry with only the last partial, none for a row that is
    not ``running``, a list of one page one block, a list of exactly ``k
    g`` pages ``k`` full blocks."""
    case = _case(57, qpos, running)
    (page, entry, _), count = _plan(57, qpos, running)
    width = len(page) // len(count)
    ent, first, total = (np.asarray(x) for x in page_blocks(
        jnp.asarray(count), g, width))
    offs = np.cumsum(count) - count
    own = case["seen"].reshape(KV, len(qpos), E, PS).any(-1)
    taken = np.zeros(count.sum(), int)
    walked = []
    for w in range(int(total)):
        r = ent[w]
        n = min(count[r] - first[w], g)
        assert n >= 1 and first[w] % g == 0
        assert n == g or first[w] + n == count[r]       # only the last
        taken[offs[r] + first[w]:offs[r] + first[w] + n] += 1
        walked.append((r, first[w]))
    assert (taken == 1).all()
    assert walked == sorted(walked)
    assert int(total) == (-(-count // g)).sum()
    for r in range(len(count)):
        b, kv = r // KV, r % KV
        mine = entry[offs[r]:offs[r] + count[r]]
        assert mine.tolist() == np.flatnonzero(own[kv, b]).tolist()
        assert (page[offs[r]:offs[r] + count[r]]
                == case["table"][b, mine]).all()
    if name == "under":
        assert count.reshape(-1, KV)[:, 0].tolist() == [1, G, 2 * G, G + 3]
    if name == "mixed":
        assert count.reshape(-1, KV)[2].tolist() == [0, 0]


@pytest.mark.parametrize("name,qpos,running", [
    ("under", (ONE, WHOLE, TWO, PART), None),
    ("past", PAST, None),
    ("mixed", (PART, PAST[0], ONE, PAST[1]), (True, True, False, True)),
])
def test_the_block_kernel_is_the_dense_softmax_under_the_mask(
        name, qpos, running):
    """``read_rows`` in interpret mode at toy sizes against the float32
    dense softmax over what the equations let each (row, KV head) see:
    rows under ``dense_len`` (lists of one page, of whole blocks, of a
    partial last block), rows past it (their own chosen blocks and their
    window), and the two mixed in one call beside a row that does not run,
    which comes back zero."""
    case = _case(58, qpos, running)
    rng = np.random.default_rng(59)
    q = rng.standard_normal((len(qpos), KV * REP, D)).astype(np.float32)
    y, pages = jax.jit(functools.partial(
        sr.read_rows, sizes=SIZES, page_size=PS, scale=1 / np.sqrt(D)))(
        jnp.asarray(q), case["k_pages"], case["v_pages"], None,
        jnp.asarray(case["table"]), jnp.asarray(case["qpos"]),
        jnp.asarray(case["blocks"]), running=None if running is None
        else jnp.asarray(case["running"]))
    live = np.ones(len(qpos), bool) if running is None \
        else case["running"]
    want = _dense_softmax(q[live], case["k"][live], case["v"][live],
                          case["seen"][:, live])
    np.testing.assert_allclose(np.asarray(y)[live], want, atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(y)[~live], 0.0)
    assert int(pages) == case["seen"].reshape(
        KV, len(qpos), E, PS).any(-1).sum()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_plan_lists_no_more_than_the_hosts_bounds(seed):
    """``sparse_index.pages_most`` (the engine's ``sparse_pages_most`` /
    ``sparse_blocks_most``, from positions alone) against the plan's
    counts under a random choice: never under them, equal for every row
    under ``dense_len``, and past it over them by no more than the chosen
    blocks that can share a page."""
    rng = np.random.default_rng(60 + seed)
    qpos = np.concatenate([rng.integers(0, SIZES.dense_len - 1, 5),
                           rng.integers(SIZES.dense_len - 1, S, 5)])
    _, count = _plan(61 + seed, tuple(qpos.tolist()))
    count = count.reshape(len(qpos), KV)
    most = si.pages_most(qpos, SIZES, PS)
    assert (count <= most[:, None]).all()
    dense = qpos + 1 < SIZES.dense_len
    assert (count[dense] == most[dense, None]).all()
    assert (count[~dense] >= most[~dense, None] - SIZES.topk).all()
    assert (-(-count // G) <= -(-most // G)[:, None]).all()
    # the same numbers from the device's positions
    np.testing.assert_array_equal(
        np.asarray(si.pages_most(jnp.asarray(qpos), SIZES, PS)), most)
