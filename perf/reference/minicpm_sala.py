"""Plain reference forward of MiniCPM-SALA (OpenBMB; config.json
``model_type`` ``minicpm_sala``): a pre-norm decoder, RMSNorm (eps 1e-6), no
bias anywhere, every layer either Lightning linear attention or learned
block-sparse grouped-query attention by ``layer_types`` (the published
``mixer_types``, in their irregular order), a gated-silu FFN in every layer,
MiniCPM's three scalars, an untied head::

    x = scale_emb E[ids]
    x <- x + r Mixer(RMSNorm x)     x <- x + r FFN(RMSNorm x)       a layer
    logits = W_head RMSNorm(x_L) / logits_scaling

with ``r = scale_depth / sqrt(num_hidden_layers)`` of the PUBLISHED depth
(the caller's ``residual_multiplier``) and ``FFN = W_d (silu(W_g h) (.) W_u
h)``.

* Lightning layer ("lightning"), ``u`` its normed input, ``H`` heads of
  ``d``: ``q, k, v = W u``; ``q, k <- RMSNorm_d`` a head (one learned weight
  of ``d`` each); rotary over the whole head, half-rotation (``x cos + [-x_2
  ; x_1] sin``, channel ``i`` and ``i + d/2`` at ``pos theta^(-2i/d)``), on q
  and k; ONE position at a time from the definition, a ``lax.scan``::

      S_t = l_h S_{t-1} + k_t v_t^T       (d x d a head, float32)
      o_t = S_t^T q_t / sqrt(d)

  ``o <- RMSNorm_d(o)`` a head (one learned weight of ``d``); ``o <- o (.)
  sigmoid(W_z u)``; ``W_o``. ``l_h = exp(-2^(-8 (h + 1) / H))``.
* Sparse layer ("attention"), ``H`` query heads and ``KV`` K/V heads of
  ``d``, ``H / KV`` query heads a KV head: ``q, k, v = W u``, ``q, k <-
  RMSNorm_d``, NO rotary, scale ``1 / sqrt(d)``. With ``kernel_size`` k,
  ``kernel_stride`` s, ``block_size`` Bk, ``init_blocks``, ``window_size``
  W, ``topk``, ``dense_len``:
  (1) compressed key ``j`` of a KV head is ``mean(key[s j : s j + k])``,
  visible to query ``i`` iff ``s j + k - 1 <= i``;
  (2) a query with ``i + 1 < dense_len`` attends to every ``p <= i``. Else,
  a head ``h``: ``p^h_ij = softmax_j(q^h_i . Kc_j / sqrt(d))`` over the
  visible ``j``; the KV head's ``P_ij = sum_h p^h_ij``; block ``b`` = ``[Bk
  b, Bk b + Bk)`` scores ``max P_ij`` over the compressed keys that overlap
  it (``s j < Bk b + Bk`` and ``s j + k > Bk b``); the first ``init_blocks``
  score ``+inf``; then a block that meets the window ``[i - W + 1, i]``
  scores ``-inf`` (the window reads it); the ``topk`` best are chosen (all
  that are finite, if fewer);
  (3) every head of the group: softmax over the positions ``p <= i`` of the
  chosen blocks joined with the window, a MASK over the full scores, then
  ``v``;
  (4) ``o <- o (.) sigmoid(W_z u)``, ``W_o``.

float32 ``jax.numpy`` at matmul precision "highest"; no kernel, no cache, no
batching, no gather of keys. One sequence; a layer is ONE loop over blocks
of rows that rewrites the stream in place (the Lightning state rides the
loop; a sparse layer's keys, values and compressed keys are made first, from
the stream as it came), the head over blocks of the vocabulary
(:func:`shortfalls`): the stream of 40,960 positions (0.67 GB), one layer's
weights as float32 (1.1 GB) and a block's scores (64 rows x 32 heads x
40,960 keys: 0.34 GB) fit beside a 12.8 GB server. Shares no code with
``deepspeed_tpu/`` or the other references; reads only the parameter tree of
``TransformerLM``.

Departures from the published description, each the configuration file's
``assumed``: (a) "dense below ``dense_len``" is decided a QUERY, by its own
position, so that chunked prefill and decoding through a cache equal ONE
full pass (the family's code decides it a call, by the sequence's length
then); (b) the softmax of (2) is exact (the family's kernels approximate its
normaliser from a coarser pooling); (c) the sizes of the index, the decay
``l_h`` and the norms' shapes, which the published configuration has no key
for."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

ROW_BLOCK = 1024        # rows of one block of a Lightning layer or an FFN
QUERY_BLOCK = 64        # query rows of one block of a sparse layer's scores
VOCAB_BLOCK = 9181      # rows of the head scored at a time (73,448 / 8)
PAD_TO = 1024           # a sequence is padded to a multiple of this


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(scale)


def _rotary(x, first, theta):
    """Half-rotation rotary over the whole last dimension of ``x`` (T,
    heads, d), positions ``first .. first + T - 1``."""
    T, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (first + jnp.arange(T)).astype(jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + turned * sin


def make_forward(layer_types, n_head: int, n_kv_head: int, head_dim: int,
                 rope_theta: float, sparse_attention, embedding_multiplier:
                 float, residual_multiplier: float, logits_scaling: float,
                 eps: float = 1e-6, state_dtype=jnp.float32):
    """``logits(params, ids, positions)``: one sequence ``ids`` (T,), the
    logits (len(positions), V) at the given positions. ``logits.hidden``
    stops before the head, for :func:`shortfalls`; ``logits.chosen(params,
    ids)`` gives, for each sparse layer in order, the (T, KV, blocks) mask
    of the blocks each query chose (step (2); every block for a query under
    ``dense_len``). ``state_dtype``: the precision the Lightning state is
    carried in, float32 as the configuration states; the controls ask for
    the nearest below to show that it tells."""
    H, KV, D = n_head, n_kv_head, head_dim
    layer_types = tuple(layer_types)
    sz = dict(sparse_attention)
    ks, st, bk = sz["kernel_size"], sz["kernel_stride"], sz["block_size"]
    r = residual_multiplier
    decay = jnp.exp(-jnp.exp2(-8.0 * jnp.arange(1, H + 1) / H))[:, None, None]

    def gated_ffn(p, x):
        m = p["mlp"]
        h = _rms_norm(x, p["ln_2"]["scale"], eps)
        return x + r * ((jax.nn.silu(h @ _f32(m["gate_proj"]["kernel"]))
                         * (h @ _f32(m["up_proj"]["kernel"])))
                        @ _f32(m["down_proj"]["kernel"]))

    def qk(a, u, heads):
        T = u.shape[0]
        q = _rms_norm((u @ _f32(a["q_proj"]["kernel"])).reshape(T, H, D),
                      a["q_norm"]["scale"], eps)
        k = _rms_norm((u @ _f32(a["k_proj"]["kernel"])).reshape(T, heads, D),
                      a["k_norm"]["scale"], eps)
        return q, k, (u @ _f32(a["v_proj"]["kernel"])).reshape(T, heads, D)

    # -- Lightning ---------------------------------------------------------
    def lightning_rows(p, first, xb, S):
        """A block of rows after the state ``S`` (H, d, d) of the rows
        before it: the rows after the layer, and the state after them."""
        a = p["lightning"]
        u = _rms_norm(xb, p["ln_1"]["scale"], eps)
        q, k, v = qk(a, u, H)
        q, k = _rotary(q, first, rope_theta), _rotary(k, first, rope_theta)

        def position(S, qkv):
            q_t, k_t, v_t = qkv
            S = (decay * _f32(S) + k_t[:, :, None] * v_t[:, None, :]
                 ).astype(state_dtype)
            return S, jnp.einsum("hkv,hk->hv", _f32(S), q_t) / math.sqrt(D)

        S, o = jax.lax.scan(position, S, (q, k, v))
        o = _rms_norm(o, a["o_norm"]["scale"], eps).reshape(-1, H * D)
        o = o * jax.nn.sigmoid(u @ _f32(a["z_proj"]["kernel"]))
        return gated_ffn(p, xb + r * (o @ _f32(a["o_proj"]["kernel"]))), S

    @functools.partial(jax.jit, donate_argnums=(2,))
    def lightning_layer(blocks, i, x):
        p = jax.tree_util.tree_map(lambda w: w[i], blocks["block"])
        T = x.shape[0]
        block = min(ROW_BLOCK, T)
        assert T % block == 0, (T, block)

        def body(n, carry):
            x, S = carry
            rows, S = lightning_rows(
                p, n * block, jax.lax.dynamic_slice_in_dim(x, n * block,
                                                           block), S)
            return jax.lax.dynamic_update_slice_in_dim(x, rows, n * block,
                                                       0), S

        return jax.lax.fori_loop(
            0, T // block, body, (x, jnp.zeros((H, D, D), state_dtype)))[0]

    # -- sparse ------------------------------------------------------------
    def keys_of(p, x):
        """The layer's keys, values (T, KV, d) and compressed keys (n, KV,
        d), block of rows by block of rows."""
        a = p["attn"]
        T = x.shape[0]
        block = min(ROW_BLOCK, T)

        def rows(xb):
            u = _rms_norm(xb, p["ln_1"]["scale"], eps)
            k = _rms_norm((u @ _f32(a["k_proj"]["kernel"])).reshape(-1, KV,
                                                                    D),
                          a["k_norm"]["scale"], eps)
            return k, (u @ _f32(a["v_proj"]["kernel"])).reshape(-1, KV, D)

        k, v = jax.lax.map(rows, x.reshape(T // block, block, -1))
        k, v = k.reshape(T, KV, D), v.reshape(T, KV, D)
        n = (T - ks) // st + 1
        window = st * jnp.arange(n)[:, None] + jnp.arange(ks)[None, :]
        return k, v, k[window].mean(1)

    def choose(q, kc, pos):
        """Step (2) for a block of queries ``q`` (b, H, d) at ``pos`` (b,):
        the mask (b, KV, blocks) of the blocks each chose."""
        T = kc.shape[0] * st + ks - st      # (positions the keys cover)
        nb = -(-T // bk)
        n = kc.shape[0]
        qg = q.reshape(-1, KV, H // KV, D)
        s = jnp.einsum("bgrd,ngd->bgrn", qg, kc) / math.sqrt(D)
        last = st * jnp.arange(n) + ks - 1
        vis = (last[None, :] <= pos[:, None])[:, None, None, :]
        s = jnp.where(vis, s, -jnp.inf)
        e = jnp.where(vis, jnp.exp(s - jnp.max(
            jnp.where(vis, s, -1e30), -1, keepdims=True)), 0.0)
        P = (e / jnp.maximum(e.sum(-1, keepdims=True), 1e-30)).sum(2)
        # the compressed keys that overlap block b, from the definition
        reach = (bk + ks) // st + 1
        cand = (bk * jnp.arange(nb)[:, None] - ks) // st \
            + jnp.arange(reach)[None, :]                        # (nb, w)
        meets = (st * cand < bk * (jnp.arange(nb)[:, None] + 1)) \
            & (st * cand + ks > bk * jnp.arange(nb)[:, None]) \
            & (cand >= 0) & (cand < n)
        score = jnp.where(meets, P[:, :, jnp.clip(cand, 0, n - 1)],
                          -jnp.inf).max(-1)                     # (b,KV,nb)
        b = jnp.arange(nb)
        score = jnp.where(b < sz["init_blocks"], jnp.inf, score)
        in_window = bk * (b + 1) - 1 >= pos[:, None] - sz["window_size"] + 1
        score = jnp.where(in_window[:, None, :], -jnp.inf, score)
        val, idx = jax.lax.top_k(score, min(sz["topk"], nb))
        chosen = ((idx[..., None] == b) & (val[..., None] > -jnp.inf)
                  ).any(-2)
        return chosen | (pos + 1 < sz["dense_len"])[:, None, None]

    def sparse_rows(p, first, xb, k, v, kc):
        a = p["attn"]
        T = k.shape[0]
        u = _rms_norm(xb, p["ln_1"]["scale"], eps)
        q = _rms_norm((u @ _f32(a["q_proj"]["kernel"])).reshape(-1, H, D),
                      a["q_norm"]["scale"], eps)
        pos = first + jnp.arange(xb.shape[0])
        chosen = choose(q, kc, pos)                             # (b,KV,nb)
        key = jnp.arange(T)
        may = jnp.repeat(chosen, bk, axis=-1)[..., :T] \
            | (key[None, :] > pos[:, None] - sz["window_size"])[:, None, :]
        may = may & (key[None, :] <= pos[:, None])[:, None, :]
        qg = q.reshape(-1, KV, H // KV, D)
        s = jnp.einsum("bgrd,tgd->bgrt", qg, k) / math.sqrt(D)
        w = jax.nn.softmax(jnp.where(may[:, :, None, :], s, -jnp.inf), -1)
        o = jnp.einsum("bgrt,tgd->bgrd", w, v).reshape(-1, H * D)
        o = o * jax.nn.sigmoid(u @ _f32(a["z_proj"]["kernel"]))
        return gated_ffn(p, xb + r * (o @ _f32(a["o_proj"]["kernel"]))), \
            chosen

    def sparse_layer_fn(collect: bool):
        def run(blocks, i, x):
            p = jax.tree_util.tree_map(lambda w: w[i], blocks["block"])
            T = x.shape[0]
            block = min(QUERY_BLOCK, T)
            assert T % block == 0, (T, block)
            k, v, kc = keys_of(p, x)
            if collect:
                rows, chosen = jax.lax.map(
                    lambda n: sparse_rows(
                        p, n * block, jax.lax.dynamic_slice_in_dim(
                            x, n * block, block), k, v, kc),
                    jnp.arange(T // block))
                return rows.reshape(T, -1), chosen.reshape(
                    (T,) + chosen.shape[2:])

            def body(n, x):
                rows, _ = sparse_rows(
                    p, n * block, jax.lax.dynamic_slice_in_dim(
                        x, n * block, block), k, v, kc)
                return jax.lax.dynamic_update_slice_in_dim(x, rows,
                                                           n * block, 0)

            return jax.lax.fori_loop(0, T // block, body, x)
        return jax.jit(run, donate_argnums=() if collect else (2,))

    sparse_layer = sparse_layer_fn(False)
    sparse_layer_collect = sparse_layer_fn(True)

    @jax.jit
    def embed(params, ids):
        return embedding_multiplier \
            * _f32(params["embed_tokens"]["embedding"][ids])

    @jax.jit
    def final_norm(params, x, positions):
        return _rms_norm(x[positions], params["ln_f"]["scale"], eps)

    def stream(params, ids, collect=False):
        i32 = jnp.int32
        seen = {"lightning": 0, "attention": 0}
        x, chosen = embed(params, jnp.asarray(ids)), []
        for kind in layer_types:
            i = jnp.asarray(seen[kind], i32)
            seen[kind] += 1
            if kind == "lightning":
                x = lightning_layer(params["lightning_blocks"], i, x)
            elif collect:
                x, c = sparse_layer_collect(params["attn_blocks"], i, x)
                chosen.append(c)
            else:
                x = sparse_layer(params["attn_blocks"], i, x)
        return x, chosen

    def hidden(params, ids, positions):
        with jax.default_matmul_precision("highest"):
            return final_norm(params, stream(params, ids)[0],
                              jnp.asarray(positions))

    def logits(params, ids, positions):
        with jax.default_matmul_precision("highest"):
            return hidden(params, ids, positions) \
                @ _f32(params["lm_head"]["kernel"]) / logits_scaling

    def chosen(params, ids):
        with jax.default_matmul_precision("highest"):
            return stream(params, ids, collect=True)[1]

    logits.hidden = hidden
    logits.chosen = chosen
    logits.logits_scaling = logits_scaling
    return logits


@jax.jit
def _head_stats(kernel, x, tokens):
    """Over blocks of the vocabulary (columns of the head): each position's
    best logit, largest |logit| and its logit of ``tokens``'s entry."""
    V = kernel.shape[1]
    block = VOCAB_BLOCK if V % VOCAB_BLOCK == 0 else V

    def one(carry, first):
        best, size, chosen = carry
        lg = x @ _f32(jax.lax.dynamic_slice_in_dim(kernel, first, block, 1))
        inside = (tokens >= first) & (tokens < first + block)
        mine = jnp.take_along_axis(
            lg, jnp.clip(tokens - first, 0, block - 1)[:, None], 1)[:, 0]
        return (jnp.maximum(best, lg.max(-1)),
                jnp.maximum(size, jnp.abs(lg).max(-1)),
                jnp.where(inside, mine, chosen)), None

    n = x.shape[0]
    start = (jnp.full((n,), -jnp.inf), jnp.zeros((n,)), jnp.zeros((n,)))
    with jax.default_matmul_precision("highest"):
        (best, size, chosen), _ = jax.lax.scan(one, start,
                                               jnp.arange(0, V, block))
    return best, size, chosen


# check_greedy's limits beside the caller's ``rel_tol`` (2**-5 of the
# position's largest |logit|, serve.py's). A choice of 64 blocks is not
# continuous: where a query's 64th and 65th block scores nearly tie, one
# bfloat16 rounding of a key swaps a block of 64 of the ~6,144 tokens it
# reads. It hardly shows: the readings (PERF.md section 6, PR 56; through
# the server on the chip, the four requests serve.py judges, 1,400-3,800
# positions each at prompts of 16k-33k; perf/tools/minicpm_limits.py):
#
# * ``SHARE_OVER``: the share of a request's positions beyond ``rel_tol``
#   that is allowed. The configured server (bfloat16 weights, float32
#   state and index): 0 of every request but one, 1 of 3,471 (0.03 %),
#   over three seeds' twelve requests. With the WEIGHTS rounded to float8,
#   the nearest precision below theirs: 34.0-36.0 % of every request. 5 %
#   lies 170 x over the one and 7 x under the other. ``MIN_OVER``: never
#   fewer than this many positions (a request seated a moment before the
#   window closed is not judged on one tie).
# * ``WORST_FACTOR`` x ``rel_tol`` (0.125 of the scale), which no position
#   may pass: the configured server's worst position lies 0.023-0.035 of
#   the scale below the reference's best, float8 weights' 0.22-0.29.
# * A Lightning state HELD in bfloat16 does not show in the tokens (0
#   positions over ``rel_tol``, worst 0.028 of the scale: the control run):
#   it comes out as not correct by the pool's audit of the state it holds
#   (``NARROW_STATE_WORDS``, part of the cell's ``check_invariants``), as
#   the state-space layers'.
SHARE_OVER = 0.05
MIN_OVER = 2
WORST_FACTOR = 4.0


def shortfalls(logits_fn, params, prompt, output, context_len: int,
               score_len: int):
    """``(shortfall (n,), scale (n,))`` of the ``n`` generated tokens: the
    reference's best logit at the token's position less its logit of the
    token, and the position's largest |logit|. The sequence is padded to a
    multiple of ``PAD_TO`` positions (a causal model keeps the padding from
    reaching earlier positions) and the scored positions to ``score_len``:
    a few compiled shapes."""
    import numpy as np

    P, n = len(prompt), len(output)
    length = min(-(-(P + n) // PAD_TO) * PAD_TO, max(context_len, P + n))
    seq = np.zeros((length,), np.int32)
    seq[:P] = prompt
    seq[P:P + n - 1] = output[:-1]
    positions = np.full((max(score_len, n),), P - 1, np.int32)
    positions[:n] = np.arange(P - 1, P - 1 + n)
    tokens = np.zeros((len(positions),), np.int32)
    tokens[:n] = output
    best, size, chosen = _head_stats(
        params["lm_head"]["kernel"],
        logits_fn.hidden(params, seq, positions), jnp.asarray(tokens))
    scale = logits_fn.logits_scaling
    return np.asarray(best - chosen)[:n] / scale, \
        np.asarray(size)[:n] / scale


def verdict(short, scale, rel_tol: float) -> dict:
    """The limits above over one request's positions."""
    import numpy as np

    n = len(short)
    over = int(np.sum(short > rel_tol * scale))
    worst = int(np.argmax(short / scale))
    allowed = int(max(MIN_OVER, SHARE_OVER * n))
    return {"positions": n, "worst_shortfall": float(short[worst]),
            "scale_there": float(scale[worst]),
            "tolerance_there": float(WORST_FACTOR * rel_tol * scale[worst]),
            "positions_over_rel_tol": over,
            "positions_over_allowed": allowed,
            "ok": bool(over <= allowed and np.all(
                short <= WORST_FACTOR * rel_tol * scale))}


def check_greedy(logits_fn, params, prompt, output, context_len: int,
                 score_len: int, rel_tol: float) -> dict:
    """Run prompt + generated tokens through the reference and hold the
    generated tokens to it (logits, not tokens: with random weights the top
    logits are nearly tied and a rounding flips the argmax): see the limits
    above. ``tolerance_there`` is the limit no position may pass."""
    return verdict(*shortfalls(logits_fn, params, prompt, output,
                               context_len, score_len), rel_tol)
