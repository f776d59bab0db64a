"""Lightning linear attention and learned block-sparse attention in one
irregular layer stack (``models/lightning_sparse.py``, PR 56), at a toy size
on the CPU WITH a context past the toy ``dense_len`` (so a choice of blocks
happens): the full forward, chunked prefill and decode through the paged
cache against the plain reference's logits; the choice itself against the
reference's; the published pattern as a list of runs; rows that do not run
come back bit for bit; a bfloat16 state and a bfloat16 index are each told
apart. One model, one set of weights and one jit a shape for the module."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from deepspeed_tpu.models.lm_config import transformer_config
from deepspeed_tpu.models.transformer_lm import TransformerLM
from deepspeed_tpu.ops import lightning  # noqa: E402
from deepspeed_tpu.ops import state_space as ss  # noqa: E402
from deepspeed_tpu.ops.attention import sparse_index as si  # noqa: E402

SPARSE = dict(kernel_size=2, kernel_stride=1, block_size=4, init_blocks=1,
              window_size=8, topk=2, dense_len=16)
MIXERS = ["minicpm4", "lightning-attn", "lightning-attn", "minicpm4",
          "minicpm4", "lightning-attn"]
T, PAGE, CHUNK = 64, 8, 16


def _config(**over):
    return transformer_config("minicpm_sala", **{**dict(
        vocab_size=97, max_seq_len=T, n_embd=32, n_layer=6, n_head=4,
        n_kv_head=2, head_size=8, ffn_dim=48, dtype=jnp.float32,
        sparse_attention=SPARSE, attn_output_gate=True, mixer_types=MIXERS,
        embedding_multiplier=12.0, residual_multiplier=1.4 / 32 ** 0.5,
        logits_scaling=2.0), **over})


@pytest.fixture(scope="module")
def toy():
    """``(cfg, model, params, ids)``: the norms' weights drawn (ones would
    hide a missing one), 64 positions of tokens."""
    cfg = _config()
    model = TransformerLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, T), 0, 97)
    params = jax.jit(lambda: model.init(
        jax.random.PRNGKey(0), {"input_ids": ids[:, :8]}))()["params"]
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(flat))
    params = jax.tree_util.tree_unflatten(tree, [
        leaf * (1 + 0.3 * jax.random.normal(key, leaf.shape))
        if "scale" in str(path) else leaf
        for (path, leaf), key in zip(flat, keys)])
    return cfg, model, params, ids


def _reference(cfg, **kw):
    from perf.reference import minicpm_sala as ref

    return ref.make_forward(
        cfg.layer_types, cfg.n_head, cfg.kv_heads, cfg.head_dim,
        cfg.rope_theta, cfg.sparse_attention, cfg.embedding_multiplier,
        cfg.residual_multiplier, cfg.logits_scaling,
        cfg.layer_norm_epsilon, **kw)


@pytest.fixture(scope="module")
def reference_logits(toy):
    cfg, _, params, ids = toy
    return np.asarray(_reference(cfg)(params, np.asarray(ids[0]),
                                      np.arange(T)))


def test_the_published_pattern_is_a_list_of_runs(toy):
    cfg = toy[0]
    assert cfg.layer_types == ("attention", "lightning", "lightning",
                               "attention", "attention", "lightning")
    assert cfg.hybrid == "lightning" and not cfg.hybrid_repeats
    # adjacent attention layers, runs of unequal length, no period
    assert cfg.hybrid_runs == (("attention", 0, 1), ("lightning", 0, 2),
                               ("attention", 1, 2), ("lightning", 2, 1))
    assert cfg.pos_emb == "none" and cfg.qk_norm and cfg.attn_output_gate
    spec = toy[1].kv_cache_spec()
    assert spec.kinds == ("sparse", "lightning")
    assert spec.kv_layers == 3 and spec.state_leaves == ("s",)
    assert spec.index_stride == 1
    pool = jax.eval_shape(lambda: spec.paged_cache(8, PAGE, num_slots=2))
    assert {k: v.shape for k, v in pool.items()} == {
        "k": (3, 8, 2, 8, 128), "v": (3, 8, 2, 8, 128),
        "kc": (3, 8, 2, PAGE, 8), "s": (3, 2) + ss.state_shape(4, 8, 8)}
    assert pool["kc"].dtype == pool["s"].dtype == jnp.float32
    # the period's list is the pattern's too where it repeats
    periodic = _config(mixer_types=["lightning-attn", "minicpm4"] * 3)
    assert periodic.hybrid_repeats and periodic.hybrid_period == (1, 0, 3)


@pytest.mark.parametrize("over,why", [
    (dict(n_experts=4, experts_per_token=2), "period by period"),
    (dict(pos_emb="rotary"), "without positions"),
    (dict(sparse_attention=dict(SPARSE, kernel_size=3, kernel_stride=2)),
     "multiples"),
])
def test_what_the_stack_does_not_run_with_says_so(over, why):
    with pytest.raises(ValueError, match=why):
        _config(**over)


def test_the_full_forward_is_the_references(toy, reference_logits):
    _, model, params, ids = toy
    full = jax.jit(lambda p, i: model.apply({"params": p}, i,
                                            method=model.logits))(params, ids)
    assert np.abs(reference_logits).max() > 0.5
    np.testing.assert_allclose(np.asarray(full[0]), reference_logits,
                               atol=2e-5)


@pytest.fixture(scope="module")
def through_the_pages(toy):
    """Row 1 of a pool of two slots takes the sequence: three chunks of 16
    (the last one of 9 real tokens), then decode steps to the end. Returns
    ``{position: logits}`` and the pool as it ended."""
    cfg, model, params, ids = toy
    spec = model.kv_cache_spec()
    pages = T // PAGE
    cs = spec.paged_cache(2 * pages + 1, PAGE, num_slots=2)
    cs = {k: v + 0.5 for k, v in cs.items()}    # (what another row left)
    # (row 0 maps nothing: the sentinel is the number of pages)
    table = jnp.asarray([[2 * pages + 1] * pages,
                         list(range(pages, 2 * pages))], jnp.int32)

    @jax.jit
    def chunk(cs, tokens, start, last):
        out, new = model.apply(
            {"params": params, "cache": {"cache_store": dict(
                cs, index=start[None])}}, tokens, start[None], last,
            rows=jnp.asarray([1]), table=table[1:], mutable=["cache"],
            method=model.prefill_chunk)
        return out, {k: v for k, v in new["cache"]["cache_store"].items()
                     if k != "index"}

    @jax.jit
    def decode(cs, tokens, start):
        out, new = model.apply(
            {"params": params, "cache": {"cache_store": dict(
                cs, index=start)}}, tokens[:, None], start, table,
            rows=jnp.asarray([2, 1]), mutable=["cache"],
            method=model.decode_paged)
        return out, {k: v for k, v in new["cache"]["cache_store"].items()
                     if k != "index"}

    got, prompt = {}, 2 * CHUNK + 9
    for first in range(0, prompt, CHUNK):
        real = min(CHUNK, prompt - first)
        tokens = jnp.where(jnp.arange(CHUNK) < real,
                           ids[0, first:first + CHUNK], 0)[None]
        out, cs = chunk(cs, tokens, jnp.int32(first), jnp.int32(real - 1))
        got[first + real - 1] = np.asarray(out[0, 0])
    before = cs
    for pos in range(prompt, T):
        out, cs = decode(cs, jnp.stack([ids[0, 0], ids[0, pos]]),
                         jnp.asarray([7, pos], jnp.int32))
        got[pos] = np.asarray(out[1, 0])
    return got, before, cs, table


def test_chunked_prefill_and_decode_through_the_pages_are_the_references(
        through_the_pages, reference_logits):
    """Chunks whose queries each choose their own blocks, a last chunk with
    padding, then decode rows beside a row that does not run: every
    position's logits are the full pass's. A bfloat16 state or a coarser
    index would not pass this tolerance (the two tests below)."""
    got = through_the_pages[0]
    assert sorted(got) == [15, 31] + list(range(40, T))
    for pos, logits in got.items():
        np.testing.assert_allclose(logits, reference_logits[pos], atol=5e-5,
                                   err_msg=f"position {pos}")


def test_a_row_that_does_not_run_comes_back_bit_for_bit(through_the_pages):
    _, before, after, table = through_the_pages
    # the state of row 0 (entry ``rows`` 2: out of range), and the pages
    # nobody's table maps but the sentinel's clip
    np.testing.assert_array_equal(np.asarray(after["s"][:, 0]),
                                  np.asarray(before["s"][:, 0]))
    assert not np.array_equal(np.asarray(after["s"][:, 1]),
                              np.asarray(before["s"][:, 1]))
    free = [p for p in range(before["kc"].shape[1])
            if p not in np.asarray(table[1])]
    for key in ("k", "v", "kc"):
        np.testing.assert_array_equal(np.asarray(after[key][:, free]),
                                      np.asarray(before[key][:, free]))
    # the index's groups of the row that ran are the means of its keys:
    # stride 1, so a group IS a key (the page's columns, transposed)
    k = np.asarray(after["k"][0, table[1]])[..., :PAGE]     # (P,KV,D,ps)
    np.testing.assert_allclose(np.asarray(after["kc"][0, table[1]]),
                               k.transpose(0, 1, 3, 2), atol=1e-6)


def _first_layer_qk(cfg, params, ids):
    """q and k of layer 0 (a sparse layer) from the parameters, in the
    test's own words."""
    p = jax.tree_util.tree_map(lambda w: w[0], params["attn_blocks"])["block"]

    def norm(x, w):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * w

    u = norm(12.0 * params["embed_tokens"]["embedding"][ids[0]],
             p["ln_1"]["scale"])
    a = p["attn"]
    q = norm((u @ a["q_proj"]["kernel"]).reshape(T, 4, 8),
             a["q_norm"]["scale"])
    k = norm((u @ a["k_proj"]["kernel"]).reshape(T, 2, 8),
             a["k_norm"]["scale"])
    return q, k


def test_the_choice_is_the_references(toy):
    cfg, _, params, ids = toy
    want = np.asarray(_reference(cfg).chosen(params, np.asarray(ids[0]))[0])
    q, k = _first_layer_qk(cfg, params, ids)
    means = si.group_means(k.transpose(1, 0, 2)[None], 1)
    got = np.asarray(si.choose_blocks(
        q[None], means, jnp.arange(T)[None], cfg.sparse, 8 ** -0.5)[0])
    assert got.shape == want.shape == (T, 2, T // 4)
    np.testing.assert_array_equal(got, want)
    # under dense_len every block; past it block 0 and topk - 1 others,
    # none of them in the window (whose tokens token_mask joins)
    assert got[:15].all() and (got[15:].sum(-1) == 2).all()
    assert got[15:, :, 0].all()
    for i in (15, 40, 63):
        assert not got[i, :, (i - 7) // 4:].any()
    may = np.asarray(si.token_mask(jnp.asarray(got)[None],
                                   jnp.arange(T)[None], cfg.sparse, T))[0]
    assert may[:, 40, 33:41].all() and not may[:, 40, 4:32].all()
    # what the host's counters say the equations read
    assert si.tokens_read(np.asarray([3, 15, 40]), cfg.sparse).tolist() \
        == [4, 8 + 2 * 4, 8 + 2 * 4]
    assert si.index_rows(np.asarray([0, 1, 40]), cfg.sparse).tolist() \
        == [0, 1, 40]


def test_a_bfloat16_index_chooses_other_blocks():
    """The control of the test above: scores against the group means made
    in bfloat16, where the configuration states float32 at the highest
    precision, swap a block somewhere in 256 queries of 8 blocks each."""
    sizes = si.SparseSizes(**dict(SPARSE, topk=8))
    keys = jax.random.split(jax.random.PRNGKey(5), 2)
    q = jax.random.normal(keys[0], (1, 256, 4, 8))
    means = si.group_means(jax.random.normal(keys[1], (1, 2, 256, 8)), 1)
    qpos = jnp.arange(256)[None]
    choose = jax.jit(si.choose_blocks, static_argnums=(3, 4, 5))
    exact = np.asarray(choose(q, means, qpos, sizes, 8 ** -0.5))
    again = np.asarray(choose(q, means, qpos, sizes, 8 ** -0.5))
    coarse = np.asarray(choose(
        q.astype(jnp.bfloat16).astype(jnp.float32),
        means.astype(jnp.bfloat16).astype(jnp.float32), qpos, sizes,
        8 ** -0.5, jax.lax.Precision.DEFAULT))
    np.testing.assert_array_equal(exact, again)
    assert (exact[0, 64:].sum(-1) == 8).all()
    assert 0 < (exact != coarse).any(-1).sum() < exact[..., 0].size // 2


def test_a_bfloat16_state_is_told_apart(toy, reference_logits):
    """The control of the logits' tolerance: the Lightning state carried in
    bfloat16, where the configuration states float32, moves the logits by
    twenty times the tolerance the program is held to and more."""
    cfg, _, params, ids = toy
    coarse = np.asarray(_reference(cfg, state_dtype=jnp.bfloat16)(
        params, np.asarray(ids[0]), np.arange(T)))
    assert np.abs(coarse - reference_logits).max() > 20 * 5e-5


def _by_definition(q, k, v, s0):
    """One sequence (T, H, d) after the state ``s0`` (H, d, d), position
    by position."""
    decay = np.exp(np.asarray(lightning.decay_log(q.shape[1])))
    out, s = [], np.asarray(s0, np.float64)
    for t in range(q.shape[0]):
        s = decay[:, None, None] * s + np.einsum("hk,hv->hkv", k[t], v[t])
        out.append(np.einsum("hkv,hk->hv", s, q[t]))
    return np.stack(out), s


@pytest.mark.parametrize("form, H, P", [
    ("chunk", 4, 16), ("decode", 4, 16), ("sequence", 4, 16),
    ("decode", 2, 128),     # ONE head a tile: the width it is served at
    ("decode", 4, 64),      # two heads a tile, two tiles
    ("decode", 2, 64),      # two heads, one tile
    ("decode", 3, 32),      # heads that do not fill a tile's 128 lanes
])
def test_the_lightning_kernels_are_the_recurrence(form, H, P):
    """A head's own q and k through the state-space leaf: the chunk form
    (``ssm_chunk``'s one-group rule lifted) and a decode step (a head's k
    and q as columns beside its tile, on that head's lanes where a tile
    holds several) against the definition; an entry out of range does not
    run (its state bit for bit, its output 0), a fresh entry reads none (a
    NaN planted in its old state does not come through), no other layer's
    state moves."""
    B, T = 3, 16
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v = (np.asarray(jax.random.normal(key, (B, T, H, P)))
               for key in keys[:3])
    if form == "sequence":
        got = np.asarray(jax.jit(lightning.lightning_sequence)(q, k, v))
        for b in range(3):
            want, _ = _by_definition(q[b], k[b], v[b], np.zeros((H, P, P)))
            np.testing.assert_allclose(got[b], want, atol=2e-5)
        return
    leaf = np.array(jax.random.normal(
        keys[3], (2, 4) + lightning.state_shape(H, P)))
    leaf[1, 0] = np.nan     # the fresh entry's row: what it held is unread
    rows, fresh = jnp.asarray([2, -1, 0]), jnp.asarray([False, False, True])
    # the leaf holds S transposed: the key's channel on the sublanes
    carried = np.asarray(ss.from_tiles(leaf[1], P)).transpose(0, 1, 3, 2)
    if form == "chunk":
        out, new = jax.jit(lightning.lightning_prefill)(
            q, k, v, leaf, 1, rows, fresh, jnp.asarray([16, 16, 11]))
        spans = (16, 16, 11)
    else:
        out, new = jax.jit(lightning.lightning_decode)(
            q[:, 0], k[:, 0], v[:, 0], leaf, 1, rows, fresh)
        out, spans = out[:, None], (1, 1, 1)
    out, new = np.asarray(out), np.asarray(new)
    after = np.asarray(ss.from_tiles(new[1], P)).transpose(0, 1, 3, 2)
    for b, row in ((0, 2), (2, 0)):
        n = spans[b]
        want, s = _by_definition(
            q[b, :n], k[b, :n], v[b, :n],
            np.zeros((H, P, P)) if row == 0 else carried[row])
        np.testing.assert_allclose(out[b, :n], want, atol=2e-5)
        np.testing.assert_allclose(after[row], s, atol=2e-5)
    assert (out[1] == 0).all()
    np.testing.assert_array_equal(new[0], leaf[0])
    np.testing.assert_array_equal(new[1, [1, 3]], leaf[1, [1, 3]])


def test_keys_join_their_groups_as_they_arrive():
    """``group_sums_write``: a chunk, a padded chunk and decode steps leave
    the means of the REAL keys; a group that began in an earlier step goes
    on from it, one that begins is started anew; a row that does not run
    writes nothing."""
    ps, st, KV, D = 8, 4, 2, 8
    k = jax.random.normal(jax.random.PRNGKey(0), (1, 24, KV, D))
    leaf = jnp.full((2, 5, KV, ps // st, D), 7.0)
    table = jnp.asarray([[3, 1, 4]], jnp.int32)
    write = jax.jit(functools.partial(si.group_sums_write, page_size=ps,
                                      stride=st))
    one = jnp.ones((1,), bool)
    # 8 tokens of which 6 are real, a decode step at 6, 7, then 8 .. 10
    leaf = write(leaf, 1, k[:, :8], table, jnp.asarray([0]),
                 jnp.asarray([6]), one)
    for pos in (6, 7, 8, 9, 10):
        leaf = write(leaf, 1, k[:, pos:pos + 1], table, jnp.asarray([pos]),
                     jnp.asarray([1]), one)
    untouched = write(leaf, 1, k[:, 11:12], table, jnp.asarray([11]),
                      jnp.asarray([1]), ~one)
    np.testing.assert_array_equal(np.asarray(untouched), np.asarray(leaf))
    want = np.asarray(si.group_means(
        jnp.where(jnp.arange(24)[None, :, None, None] < 11, k, 0.0)
        .transpose(0, 2, 1, 3), st))[0]                    # (KV, 6, D)
    got = np.asarray(leaf[1, table[0]]).transpose(1, 0, 2, 3).reshape(
        KV, 6, D)
    np.testing.assert_allclose(got[:, :3], want[:, :3], atol=1e-6)
    assert (np.asarray(leaf[0]) == 7.0).all()
    assert (np.asarray(leaf[1, [0, 2]]) == 7.0).all()
    # the page the row has not reached holds what it held
    assert (np.asarray(leaf[1, 4]) == 7.0).all()
