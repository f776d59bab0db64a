"""The routed FFN (``deepspeed_tpu/moe/routed_ffn.py``): the Pallas expert
products over rows grouped by expert against a per-token loop in numpy,
float32 on the CPU (kernels in interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe.routed_ffn import (CALL_STATS, ROW_TILE, call_stats,
                                           group_rows, route, routed_ffn,
                                           routed_ffn_reference)
from deepspeed_tpu.moe.sharded_moe import gate_decisions

# float32 sums in another order: a product of 64 terms of size ~0.1 differs
# by a few float32 roundings (1e-7 each) of values ~1; a wrong expert,
# weight or row moves an output by its whole size
ATOL = 2e-5


def _weights(L, E, C, F, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (C, E)),
            jax.random.normal(ks[1], (L, E, C, F)) / np.sqrt(C),
            jax.random.normal(ks[2], (L, E, C, F)) / np.sqrt(C),
            jax.random.normal(ks[3], (L, E, F, C)) / np.sqrt(F))


def _token_loop(h, router, gate, up, down, k, norm):
    """One token, one chosen expert at a time."""
    h, router, gate, up, down = (np.asarray(a, np.float64)
                                 for a in (h, router, gate, up, down))
    out = np.zeros_like(h)
    for n, x in enumerate(h):
        z = x @ router
        p = np.exp(z - z.max())
        p /= p.sum()
        chosen = np.argsort(-p, kind="stable")[:k]
        w = p[chosen] / (p[chosen].sum() if norm else 1.0)
        for e, we in zip(chosen, w):
            g, u = x @ gate[e], x @ up[e]
            out[n] += we * ((g / (1 + np.exp(-g)) * u) @ down[e])
    return out


@pytest.mark.parametrize("E,k", [(8, 2), (64, 8)])
@pytest.mark.parametrize("norm", [True, False])
def test_routed_ffn_matches_a_per_token_loop(E, k, norm):
    L, C, F, N = 3, 64, 32, 37
    router, gate, up, down = _weights(L, E, C, F)
    h = jax.random.normal(jax.random.PRNGKey(9), (N, C))
    layer = 1
    y, stats = jax.jit(lambda h, l: routed_ffn(
        h, router, gate, up, down, l, k=k, norm_topk_prob=norm))(
        h, jnp.asarray(layer))
    want = _token_loop(h, router, gate[layer], up[layer], down[layer], k,
                       norm)
    np.testing.assert_allclose(np.asarray(y), want, atol=ATOL)
    # the dense-sum form the other tests lean on is the same layer
    np.testing.assert_allclose(
        np.asarray(routed_ffn_reference(h, router, gate[layer], up[layer],
                                        down[layer], k=k,
                                        norm_topk_prob=norm)),
        want, atol=ATOL)
    assert int(stats[0]) == N * k
    assert 1 <= int(stats[1]) <= E and int(stats[2]) >= -(-N * k // E)


@pytest.mark.parametrize("case", ["one_takes_all", "one_takes_none"])
def test_imbalanced_router(case):
    """An expert that every token chooses fills ``N`` rows of tiles; an
    expert that no token chooses has no tile and is never read (its
    weights are NaN here: had a tile read them, the output would be)."""
    L, E, C, F, N, k = 2, 8, 64, 32, 50, 2
    router, gate, up, down = _weights(L, E, C, F, seed=3)
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (N, C)))
    if case == "one_takes_all":
        router = router.at[:, 5].set(10.0)       # h > 0: logit 5 is huge
        poison = None
    else:
        router = router.at[:, 2].set(-10.0)
        poison = 2
        gate, up, down = (w.at[:, poison].set(jnp.nan)
                          for w in (gate, up, down))
    w, experts = route(h, router, k, True)
    groups = group_rows(experts, E)
    counts = np.asarray(groups.counts)
    if poison is None:
        assert counts[5] == N
    else:
        assert counts[poison] == 0
        assert poison not in np.asarray(groups.tile_expert)[
            :int(groups.num_tiles)]
    y, stats = routed_ffn(h, router, gate, up, down, jnp.asarray(0), k=k,
                          norm_topk_prob=True)
    assert np.isfinite(np.asarray(y)).all()
    clean = [np.nan_to_num(np.asarray(a[0])) for a in (gate, up, down)]
    np.testing.assert_allclose(
        np.asarray(y), _token_loop(h, router, *clean, k, True), atol=ATOL)
    assert int(stats[2]) == counts.max()
    assert int(stats[1]) == int((counts > 0).sum())


def test_call_stats_reduce_the_layers_counts():
    """Two layers of 4 experts: 12 assignments each, 3 and 4 experts
    touched, the fullest with 6 and 9 rows (mean 3 rows an expert)."""
    got = dict(zip(CALL_STATS, np.asarray(call_stats(
        jnp.asarray([[12, 3, 6], [12, 4, 9]], jnp.int32), 4)).tolist()))
    assert got == {"assignments": 24.0, "experts_touched": 7.0,
                   "layer_calls": 2.0, "load_max": 9.0,
                   "load_max_over_mean": 3.0}


def test_group_rows_layout():
    """Every assignment has a row of its own in a tile of its expert; the
    tiles that run are exactly the padded groups; the bound holds for the
    two extremes."""
    rng = np.random.default_rng(0)
    N, k, E = 41, 4, 16
    experts = np.stack([rng.choice(E, k, replace=False) for _ in range(N)])
    g = group_rows(jnp.asarray(experts, jnp.int32), E)
    row_of, token_of = np.asarray(g.row_of), np.asarray(g.token_of)
    assert len(set(row_of.reshape(-1).tolist())) == N * k
    tile_expert = np.asarray(g.tile_expert)
    for n in range(N):
        for j in range(k):
            r = row_of[n, j]
            assert token_of[r] == n
            assert tile_expert[r // ROW_TILE] == experts[n, j]
            assert r // ROW_TILE < int(g.num_tiles)
    counts = np.bincount(experts.reshape(-1), minlength=E)
    assert int(g.num_tiles) == int(np.sum(-(-counts // ROW_TILE)))
    assert len(tile_expert) == -(-N * k // ROW_TILE) + E
    for extreme in (np.zeros((N, 1), np.int32),
                    (np.arange(N)[:, None] % E).astype(np.int32)):
        g = group_rows(jnp.asarray(extreme), E)
        assert int(g.num_tiles) <= len(np.asarray(g.tile_expert))


def test_capacity_gate_points_at_the_routed_ffn():
    with pytest.raises(ValueError, match="routed_ffn"):
        gate_decisions(jnp.zeros((4, 8)), k=8)
