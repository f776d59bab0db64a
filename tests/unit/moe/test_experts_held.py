"""A routed FFN that holds a share of its experts
(``moe/routed_ffn.py``, ``TransformerConfig.experts_held``): the router and
the top-k run over all the experts, a chip computes the part of the sum its
own experts give, and the shares' parts add up to the uncut layer; an
assignment to an expert that is not held makes no tile and no read; with
every expert held nothing differs from the layer as it was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import routed_ffn as routed
from deepspeed_tpu.moe.routed_ffn import (CALL_STATS, ROW_TILE, RoutedFFN,
                                           call_stats, group_rows,
                                           routed_ffn, routed_ffn_reference)

# float32 sums in another order (eight parts added up where the uncut
# layer sums a token's k rows at once): a few roundings of 1e-7 at values
# ~1; a dropped or doubled expert moves an output by its whole size
ATOL = 2e-5
L, E, C, F, N, K = 2, 32, 64, 32, 41, 8
SCORING = {"softmax": {}, "sigmoid": {"scoring": "sigmoid", "scaling": 2.446}}


def weights(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (C, E)),
            jax.random.normal(ks[1], (L, E, C, F)) / np.sqrt(C),
            jax.random.normal(ks[2], (L, E, C, F)) / np.sqrt(C),
            jax.random.normal(ks[3], (L, E, F, C)) / np.sqrt(F),
            0.05 * jax.random.normal(ks[4], (E,)),
            jax.random.normal(ks[5], (N, C)))


def scoring_of(name, bias):
    return dict(SCORING[name], **({"bias": bias} if name == "sigmoid"
                                  else {}))


@pytest.mark.parametrize("scoring", sorted(SCORING))
def test_the_eight_shares_parts_add_up_to_the_uncut_layer(scoring):
    """Chip ``c`` of 8 holds experts ``[4 c, 4 c + 4)``: the program's
    share is always ``[0, held)`` of the leaves it is given, so chip c's
    leaves are the uncut ones rolled by ``4 c`` experts, with the router's
    columns (and bias) rolled alike. Their parts sum to the plain dense
    sum over all 32 experts; the held experts' counts sum to ``N k``."""
    router, gate, up, down, bias, h = weights()
    more = scoring_of(scoring, bias)
    want = routed_ffn_reference(h, router, gate[1], up[1], down[1], k=K,
                                norm_topk_prob=True, **more)
    total, ran, routed = 0.0, 0, []
    for chip in range(8):
        roll = lambda x, axis: jnp.roll(x, -4 * chip, axis)    # noqa: E731
        mine = dict(more, **({"bias": roll(bias, 0)} if "bias" in more
                             else {}))
        y, stats = jax.jit(lambda h: routed_ffn(
            h, roll(router, 1), roll(gate, 1)[:, :4], roll(up, 1)[:, :4],
            roll(down, 1)[:, :4], jnp.asarray(1), k=K, norm_topk_prob=True,
            **mine))(h)
        total = total + np.asarray(y, np.float64)
        assert stats.shape == (5,)
        ran += int(stats[0])
        routed.append(int(stats[4]))
        assert 0 <= int(stats[1]) <= 4 and int(stats[2]) <= N
    np.testing.assert_allclose(total, np.asarray(want), atol=ATOL)
    assert ran == N * K and routed == [N * K] * 8


def test_an_unheld_assignment_makes_no_tile_and_no_read():
    """The layout is over the held experts alone: as many tiles as their
    rows fill, every unheld assignment past the layout's end; and with NO
    assignment held nothing runs and the part is exactly 0 (the rows no
    tile wrote are whatever the buffer held: selected away, not
    multiplied)."""
    held = 4
    experts = jnp.asarray([[0, 9, 3, 31], [3, 30, 17, 5], [8, 9, 10, 11]],
                          jnp.int32)
    groups = group_rows(experts, E, ROW_TILE, held)
    assert groups.counts.tolist() == [1, 0, 0, 2]
    assert int(groups.num_tiles) == 2
    assert groups.tile_expert[:2].tolist() == [0, 3]
    end = groups.token_of.shape[0]
    assert end == (-(-12 // ROW_TILE) + held) * ROW_TILE
    rows = np.asarray(groups.row_of)
    there = np.asarray(experts) < held
    assert (rows[~there] == end).all() and (rows[there] < 2 * ROW_TILE).all()
    assert sorted(rows[there]) == [0, ROW_TILE, ROW_TILE + 1]
    assert groups.token_of[ROW_TILE:ROW_TILE + 2].tolist() == [0, 1]
    # nobody routed here: a router that scores the held experts last
    router, gate, up, down, bias, h = weights(seed=2)
    h = jnp.abs(h)
    router = router.at[:, :held].set(-10.0)         # (h > 0)
    poison = [jnp.full_like(x[:, :held], jnp.nan) for x in (gate, up, down)]
    y, stats = jax.jit(lambda h: routed_ffn(
        h, router, *poison, jnp.asarray(0), k=K, norm_topk_prob=True))(h)
    assert (np.asarray(y) == 0).all()
    assert stats.tolist() == [0, 0, 0, 0, N * K]


@pytest.mark.parametrize("scoring", sorted(SCORING))
def test_all_held_is_the_layer_as_it_was_bit_for_bit(scoring, monkeypatch):
    """Leaves as wide as the router: the same output to the bit, the same
    counts, and the same program (the text the compiler is given does not
    know of ``held``)."""
    router, gate, up, down, bias, h = weights(seed=1)
    more = scoring_of(scoring, bias)

    def layer(h):
        return routed_ffn(h, router, gate, up, down, jnp.asarray(1), k=K,
                          norm_topk_prob=True, **more)

    y, stats = jax.jit(layer)(h)
    want = routed_ffn_reference(h, router, gate[1], up[1], down[1], k=K,
                                norm_topk_prob=True, **more)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=ATOL)
    assert stats.shape == ((4,) if scoring == "sigmoid" else (3,))
    assert int(stats[0]) == N * K
    # (the layout takes the path it always took: no ``held``; that the
    # compiled step programs of the served all-held configurations are the
    # parent's, text for text, is tests/unit/accelerator/test_chip_path.py's)
    seen = []
    monkeypatch.setattr(
        routed, "group_rows",
        lambda *a: seen.append(a[3]) or group_rows(*a))
    again, _ = jax.jit(lambda h: layer(h))(h)
    assert seen == [None]
    np.testing.assert_array_equal(np.asarray(again), np.asarray(y))


def test_the_model_holds_its_share_and_counts_what_ran():
    """``experts_held`` through ``TransformerLM``: the expert leaves are
    (routed layers, held, ...), the router keeps its width, and a call's
    counts are of the held experts beside every assignment made."""
    from deepspeed_tpu.models.lm_config import transformer_config
    from deepspeed_tpu.models.transformer_lm import TransformerLM

    sizes = dict(vocab_size=64, max_seq_len=32, n_embd=32, n_layer=3,
                 n_head=4, ffn_dim=16, n_experts=16, experts_per_token=4,
                 dtype=jnp.float32)
    ids = jnp.asarray(np.random.default_rng(0).integers(1, 64, (2, 12)),
                      jnp.int32)
    cut = TransformerLM(transformer_config("llama", experts_held=4, **sizes))
    params = cut.init(jax.random.PRNGKey(0), ids, method=cut.logits)["params"]
    assert params["experts"]["gate_proj"].shape == (3, 4, 32, 16)
    assert params["blocks"]["block"]["mlp"]["router"].shape == (3, 32, 16)
    _, vars_ = cut.apply({"params": params}, ids, method=cut.prefill,
                         mutable=["cache", "stats"])
    stats = dict(zip(CALL_STATS, np.asarray(vars_["stats"]["moe"])))
    assert stats["routed_assignments"] == 3 * 24 * 4
    assert 0 < stats["assignments"] < stats["routed_assignments"]
    assert stats["experts_touched"] <= 3 * 4 and stats["layer_calls"] == 3
    # mean rows an expert: over the HELD ones
    assert stats["load_max_over_mean"] == pytest.approx(
        stats["load_max"] / (stats["assignments"] / (3 * 4)))
    # every expert held says the same of itself as no key at all
    whole = transformer_config("llama", **sizes)
    assert transformer_config("llama", experts_held=16, **sizes) \
        .experts_held == 16 and whole.experts_held is None
    with pytest.raises(ValueError, match="experts_held=17 of n_experts=16"):
        transformer_config("llama", experts_held=17, **sizes)
    assert len(CALL_STATS) == 7 and call_stats(
        jnp.ones((2, 3), jnp.int32), 4).shape == (5,)
    assert RoutedFFN.__dataclass_fields__["n_experts"] is not None
