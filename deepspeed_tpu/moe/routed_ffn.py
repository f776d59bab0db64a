"""Routed feed-forward that drops no token: the sparse FFN of a *served*
``TransformerLM`` (``TransformerConfig.n_experts > 0``).

Beside the capacity layer (``sharded_moe.py``: the reference's training
gate, ``k`` in {1, 2}, a capacity that drops what overflows) this is the
form today's sparse decoders publish: a float32 softmax router, the ``k``
largest of ``E`` experts a token (``k`` = 8 of 64 for the configuration the
benchmark serves), optionally renormalised over the chosen
(``norm_topk_prob``), every chosen expert computed, nothing dropped::

    p = softmax(h @ Wr)            float32
    S = top_k(p, k);  w_e = p_e / sum_{e' in S} p_e'      (or p_e)
    y = sum_{e in S} w_e * down_e( silu(gate_e h) * up_e h )

and the sigmoid form (``scoring="sigmoid"``) whose choice is ordered by a
learned bias that the weights do not see, with a shared expert beside the
routed sum::

    s = sigmoid(h @ Wr)            float32
    S = top_k(s + b, k);  w_e = f * s_e / (sum_{e' in S} s_e' + 1e-20)
    y = sum_{e in S} w_e * down_e( silu(gate_e h) * up_e h ) + shared(h)

(under ``RoutedFFN.shared_gate`` the shared expert, beside either router,
is scaled a token: ``sigmoid(w_s . h) * shared(h)``).

**Rows grouped by expert.** The ``N * k`` (token, expert) assignments are
sorted by expert and laid out in tiles of :data:`ROW_TILE` rows, each
group padded to whole tiles, so a tile belongs to ONE expert
(:func:`group_rows`). The bound on tiles is static (``ceil(N k / tile) +
E``); how many run is known on the device only and sizes the grid.

**Expert products are Pallas calls that read the stacked leaf in place.**
``moe_gate_up`` and ``moe_down`` (``pallas_call(name=...)``; the v5e trace
carries no operation metadata, so the name is what a trace can attribute)
take the expert leaves WHOLE, ``(layers, E, C, F)`` and ``(layers, E, F,
C)``, and find a tile's block by ``(layer, expert)`` on scalar prefetch:
no slice of a leaf is made (one layer's experts are 0.79 GB at the served
size; XLA cannot fuse a dynamic-slice into a custom call, so a sliced
layer is a copy of it), and an expert no row chose is never read. Tiles of
one expert follow each other, so its block is fetched once. The blocks
hold a whole ``(C, F)`` matrix (4.1 MB at 2304 x 896 bf16, two matrices,
double-buffered: 16.5 MB), which is why the calls raise
``vmem_limit_bytes`` over the 16 MiB default.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops import backend

__all__ = ["route", "group_rows", "routed_ffn", "routed_ffn_reference",
           "RowGroups", "ROW_TILE", "RoutedFFN", "ExpertLeaves"]

# rows of one grid step: one bf16 sublane tile. A tile is bound by its
# expert's weights (an MXU pass costs the same for 16 rows as for 128), so
# a larger tile would only pad more
ROW_TILE = 16
VMEM_LIMIT_BYTES = 64 * 2 ** 20


def route(h: jax.Array, router: jax.Array, k: int, norm_topk_prob: bool,
          scoring: str = "softmax", bias: Optional[jax.Array] = None,
          scaling: float = 1.0, eps: float = 1e-20):
    """``(weights (N, k) float32, experts (N, k) int32)`` of ``h`` (N, C)
    under the router matrix (C, E): softmax in float32, the ``k`` largest,
    renormalised over the chosen when ``norm_topk_prob``.

    ``scoring="sigmoid"``: the scores are sigmoids, the chosen are the
    ``k`` largest of ``score + bias`` (E,), the weights are the UNBIASED
    scores of the chosen (renormalised likewise, over ``sum + eps``: 1e-20
    as Moonlight and Kimi Linear publish it, 1e-6 for LFM2) times
    ``scaling``; a third value comes back, how many assignments the
    bias moved: those whose expert is not among the ``k`` largest unbiased
    scores."""
    logits = jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if scoring == "sigmoid":
        s = jax.nn.sigmoid(logits)
        _, e = jax.lax.top_k(s + bias.astype(jnp.float32), k)
        w = jnp.take_along_axis(s, e, axis=-1)
        if norm_topk_prob:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
        _, unbiased = jax.lax.top_k(s, k)
        moved = jnp.sum(~jnp.any(e[:, :, None] == unbiased[:, None, :], -1),
                        dtype=jnp.int32)
        return w * scaling, e.astype(jnp.int32), moved
    p = jax.nn.softmax(logits, axis=-1)
    w, e = jax.lax.top_k(p, k)
    if norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w, e.astype(jnp.int32)


class RowGroups(NamedTuple):
    """Where each assignment stands once rows are grouped by expert."""

    row_of: jax.Array       # (N, k) row of the tiled layout of each assignment
    token_of: jax.Array     # (rows,) token a row holds (padding rows: 0)
    tile_expert: jax.Array  # (tiles,) expert of each tile
    num_tiles: jax.Array    # () tiles that hold a row
    counts: jax.Array       # (E,) assignments an expert


def group_rows(experts: jax.Array, num_experts: int, tile: int = ROW_TILE,
               held: Optional[int] = None) -> RowGroups:
    """Lay the ``N * k`` assignments out expert by expert, each expert's
    rows padded to whole tiles of ``tile``. ``ceil(N k / tile) + E`` tiles
    bound the layout whatever the router does (one expert taking every
    token fills ``N k / tile`` of them; every expert taking one row, ``E``).

    ``held``: experts ``[0, held)`` of ``num_experts`` are here
    (:func:`routed_ffn`). An assignment to any other makes no row: the
    layout is over ``held`` experts, an unheld assignment is counted
    nowhere, sorts behind the last row and its ``row_of`` is the layout's
    end, which no tile holds."""
    N, k = experts.shape
    A = N * k
    flat = experts.reshape(A)
    drop = {}       # (how an update out of range is said to be dropped)
    if held is not None:
        num_experts, drop = held, {"mode": "drop"}
        flat = jnp.where(flat < held, flat, held)   # (behind every held one)
    tiles = -(-A // tile) + num_experts
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    counts = jnp.zeros((num_experts,), jnp.int32).at[flat].add(1, **drop)
    padded = -(-counts // tile) * tile
    ends_p = jnp.cumsum(padded)
    begin, begin_p = jnp.cumsum(counts) - counts, ends_p - padded
    sorted_e = flat[order]
    rank = jnp.arange(A, dtype=jnp.int32)
    row_sorted = begin_p[sorted_e] + rank - begin[sorted_e]
    if held is not None:
        # (the gathers above read an unheld assignment's place off the
        # last held expert: name it here)
        row_sorted = jnp.where(sorted_e < held, row_sorted, tiles * tile)
    row_of = jnp.zeros((A,), jnp.int32).at[order].set(row_sorted)
    token_of = jnp.zeros((tiles * tile,), jnp.int32).at[row_sorted].set(
        order // k, **drop)
    first_row = jnp.arange(tiles, dtype=jnp.int32) * tile
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends_p, first_row, side="right"),
        num_experts - 1).astype(jnp.int32)
    return RowGroups(row_of.reshape(N, k), token_of, tile_expert,
                     (ends_p[-1] // tile).astype(jnp.int32), counts)


def _gate_up_kernel(layer_ref, expert_ref, x_ref, gate_ref, up_ref, h_ref):
    x = x_ref[...]
    g = jnp.dot(x, gate_ref[0, 0], preferred_element_type=jnp.float32)
    u = jnp.dot(x, up_ref[0, 0], preferred_element_type=jnp.float32)
    h_ref[...] = (jax.nn.silu(g) * u).astype(h_ref.dtype)


def _down_kernel(layer_ref, expert_ref, h_ref, down_ref, y_ref):
    y_ref[...] = jnp.dot(h_ref[...], down_ref[0, 0],
                         preferred_element_type=jnp.float32
                         ).astype(y_ref.dtype)


def _expert_call(kernel, name: str, rows: jax.Array, leaves, layer,
                 groups: RowGroups, out_width: int, out_dtype, tile: int):
    """One product over the tiled rows: tile ``w`` of ``rows`` against
    block ``(layer, tile_expert[w])`` of each stacked leaf."""

    def row_index(w, layer_ref, expert_ref):
        return (w, 0)

    def leaf_index(w, layer_ref, expert_ref):
        return (layer_ref[0], expert_ref[w], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(groups.num_tiles,),
        in_specs=[pl.BlockSpec((tile, rows.shape[1]), row_index)]
        + [pl.BlockSpec((1, 1) + leaf.shape[2:], leaf_index)
           for leaf in leaves],
        out_specs=pl.BlockSpec((tile, out_width), row_index),
    )
    return pl.pallas_call(
        kernel, name=name, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows.shape[0], out_width),
                                       out_dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=backend.pallas_interpret(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), groups.tile_expert, rows,
      *leaves)


def routed_ffn(h: jax.Array, router: jax.Array, gate: jax.Array,
               up: jax.Array, down: jax.Array, layer, *, k: int,
               norm_topk_prob: bool, tile: int = ROW_TILE,
               **scoring):
    """``(y (N, C), stats (3,) int32)``: the routed FFN of ``h`` (N, C)
    with layer ``layer`` (traced) of the stacked expert leaves ``gate`` /
    ``up`` (L, E, C, F) and ``down`` (L, E, F, C). ``stats`` =
    (assignments, experts that a row chose, rows of the fullest expert)
    and, under sigmoid scoring (``scoring``: :func:`route`'s ``scoring``,
    ``bias``, ``scaling``), a fourth: assignments the bias moved.

    **Experts held.** The leaves may hold fewer experts than the router
    is wide (``E < router.shape[1]``): experts ``[0, E)``, one chip's share
    of a layer that several chips divide. The router and the top-k run
    over all of them; only assignments to held experts are laid out (the
    others make no row, no tile and no read), and ``y`` is the held
    experts' part of the sum, which is what goes on. ``stats`` then count
    what the kernels ran (assignments, experts and the fullest expert
    among the HELD), always carry the fourth (0 under softmax) and a
    fifth: all ``N k`` assignments the router made."""
    E = gate.shape[1]
    held = E if E < router.shape[1] else None
    w, experts, *moved = route(h, router, k, norm_topk_prob, **scoring)
    groups = group_rows(experts, router.shape[1], tile, held)
    dtype = gate.dtype
    x = h.astype(dtype)[groups.token_of]                    # (rows, C)
    act = _expert_call(_gate_up_kernel, "moe_gate_up", x, (gate, up), layer,
                       groups, gate.shape[3], dtype, tile)
    y = _expert_call(_down_kernel, "moe_down", act, (down,), layer, groups,
                     down.shape[3], jnp.float32, tile)
    # each token gathers the rows of its k choices: no scatter, one order
    if held is not None:
        # an unheld assignment's row is past the layout: it adds nothing
        # (a select: rows no tile wrote are whatever the buffer held)
        there = (experts < held)[..., None]
        y = jnp.sum(jnp.where(
            there, y.at[groups.row_of].get(mode="clip") * w[..., None], 0.0),
            axis=1)
        stats = jnp.stack([jnp.sum(groups.counts),
                           jnp.sum(groups.counts > 0, dtype=jnp.int32),
                           jnp.max(groups.counts),
                           *(moved or [jnp.zeros((), jnp.int32)]),
                           jnp.asarray(experts.size, jnp.int32)])
        return y.astype(h.dtype), stats
    y = jnp.sum(y[groups.row_of] * w[..., None], axis=1)
    stats = jnp.stack([jnp.asarray(experts.size, jnp.int32),
                       jnp.sum(groups.counts > 0, dtype=jnp.int32),
                       jnp.max(groups.counts), *moved])
    return y.astype(h.dtype), stats


#: what :func:`call_stats` holds, in order
CALL_STATS = ("assignments", "experts_touched", "layer_calls", "load_max",
              "load_max_over_mean", "bias_reordered", "routed_assignments")


def call_stats(layer_stats: jax.Array, n_experts: int) -> jax.Array:
    """One model call's counts from its layers' ``stats`` (L, 3), as
    float32 in the order of :data:`CALL_STATS`: assignments and experts
    touched summed over the layers, the layers, the rows of the fullest
    expert of any layer, those rows over a layer's mean rows an
    expert (1 is perfectly even) and, where the layers counted them
    (sigmoid scoring), the assignments a bias moved; where a layer holds
    a share of its experts (``n_experts``: those held), all of these are of
    the held experts and a last one counts every assignment the router
    made."""
    assignments, touched = jnp.sum(layer_stats[:, :2], axis=0)
    load_max = jnp.max(layer_stats[:, 2])
    calls = layer_stats.shape[0]
    mean = jnp.maximum(assignments, 1) / (calls * n_experts)
    return jnp.stack([assignments, touched, calls, load_max,
                      load_max / mean, *jnp.sum(layer_stats[:, 3:], axis=0)]
                     ).astype(jnp.float32)


def routed_ffn_reference(h, router, gate, up, down, *, k: int,
                         norm_topk_prob: bool, **scoring):
    """The same layer as a plain sum over experts (every expert computed
    for every token, the unchosen weighted 0): what the tests hold the
    kernels to. ``gate`` / ``up`` (E, C, F), ``down`` (E, F, C)."""
    w, experts, *_ = route(h, router, k, norm_topk_prob, **scoring)
    E = gate.shape[0]
    dense_w = jnp.zeros((h.shape[0], E), jnp.float32).at[
        jnp.arange(h.shape[0])[:, None], experts].add(w)
    hp = functools.partial(jnp.einsum,
                           precision=jax.lax.Precision.HIGHEST)
    h32 = h.astype(jnp.float32)
    act = jax.nn.silu(hp("nc,ecf->enf", h32, gate.astype(jnp.float32))) \
        * hp("nc,ecf->enf", h32, up.astype(jnp.float32))
    y = hp("enf,efc->enc", act, down.astype(jnp.float32))
    return jnp.einsum("enc,ne->nc", y, dense_w).astype(h.dtype)


def _expert_init(key, shape, dtype=jnp.float32):
    """LeCun normal over the contracted dimension (the last but one), as
    ``nn.Dense`` draws a kernel; layers and experts are batch dimensions."""
    return jax.nn.initializers.lecun_normal(
        in_axis=-2, out_axis=-1, batch_axis=(0, 1))(key, shape, dtype)


#: spread of the seeded ``router_bias``. A trained bias balances the
#: experts' load and is small beside the scores' spread; a zero one would
#: leave "ordered by score + bias" equal to "ordered by score", and nothing
#: that compares outputs could see the rule broken. 0.05 beside sigmoid
#: scores of a unit-normal logit (spread ~0.2) moves the choice for a few
#: assignments in a hundred (``moe_bias_reordered``)
ROUTER_BIAS_STD = 0.05


def _bias_init(key, shape, dtype=jnp.float32):
    return ROUTER_BIAS_STD * jax.random.normal(key, shape, dtype)


class ExpertLeaves(nn.Module):
    """The stacked expert weights of a model, ``gate_proj`` / ``up_proj``
    (layers, E, C, F) and ``down_proj`` (layers, E, F, C): parameters of
    the MODEL, beside the scanned blocks, because a parameter of the
    scanned block reaches a layer as a slice of its stack, and a slice
    handed to a custom call is a copy."""

    n_layer: int
    n_experts: int      # the experts held here (all, or this chip's share)
    n_embd: int
    width: int

    @nn.compact
    def __call__(self):
        L, E, C, F = self.n_layer, self.n_experts, self.n_embd, self.width
        return {"gate_proj": self.param("gate_proj", _expert_init,
                                        (L, E, C, F)),
                "up_proj": self.param("up_proj", _expert_init, (L, E, C, F)),
                "down_proj": self.param("down_proj", _expert_init,
                                        (L, E, F, C))}


class RoutedFFN(nn.Module):
    """One layer's routed FFN inside the block: owns the router matrix
    (C, E), takes the model's expert leaves and the layer's index. The
    leaves may hold experts ``[0, held)`` of the ``E`` the router knows
    (:func:`routed_ffn`): the shared FFN, which every chip computes alike,
    is added to the held experts' part."""

    n_experts: int
    experts_per_token: int
    norm_topk_prob: bool
    scoring_func: str = "softmax"
    routed_scaling_factor: float = 1.0
    shared_width: int = 0       # > 0: a gated FFN of this width for every
    # token, added to the routed sum
    dtype: Any = jnp.bfloat16   # the shared FFN's
    norm_eps: float = 1e-20     # the sigmoid router's: what joins the chosen
    # scores' sum before they are divided by it
    shared_gate: bool = False   # the shared FFN's output is scaled by
    # sigmoid(w_s . x) a token (``shared_gate_w`` (C,); Qwen3-Next)

    @nn.compact
    def __call__(self, x, experts, layer):
        B, T, C = x.shape
        router = self.param("router", nn.initializers.lecun_normal(),
                            (C, self.n_experts))
        scoring = {}
        if self.scoring_func == "sigmoid":
            scoring = dict(
                scoring=self.scoring_func,
                scaling=self.routed_scaling_factor, eps=self.norm_eps,
                bias=self.param("router_bias", _bias_init,
                                (self.n_experts,)))
        y, stats = routed_ffn(
            x.reshape(B * T, C), router, experts["gate_proj"],
            experts["up_proj"], experts["down_proj"], layer,
            k=self.experts_per_token, norm_topk_prob=self.norm_topk_prob,
            **scoring)
        y = y.reshape(B, T, C)
        if self.shared_width:
            def dense(width, name):
                return nn.Dense(width, use_bias=False, dtype=self.dtype,
                                name=name)

            shared = dense(C, "shared_down_proj")(
                jax.nn.silu(dense(self.shared_width, "shared_gate_proj")(x))
                * dense(self.shared_width, "shared_up_proj")(x))
            if self.shared_gate:
                w_s = self.param("shared_gate_w",
                                 nn.initializers.normal(C ** -0.5), (C,))
                gate = jax.nn.sigmoid(jnp.einsum(
                    "btc,c->bt", x.astype(jnp.float32),
                    w_s.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST))
                shared = (shared.astype(jnp.float32)
                          * gate[..., None]).astype(shared.dtype)
            y = y + shared
        return y, stats
