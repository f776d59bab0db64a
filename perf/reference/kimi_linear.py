"""Plain reference forward of Kimi-Linear-48B-A3B-Instruct (Moonshot AI;
config.json ``model_type`` ``kimi_linear``; Kimi Linear, arXiv:2510.26692):
pre-norm decoder, RMSNorm (eps 1e-5), no bias on a projection, untied head,
final norm, no position signal anywhere. With ``h`` a layer's normed input:

* KDA layer (``layer_types`` "kda"), as the token-by-token recurrence and
  no chunk form. ``[q ; k ; v] = silu(conv(W_qkv h))``, the convolution
  causal and depthwise over 4 taps without bias (``conv_w[3]`` meets the
  token itself); a head of ``d`` channels: ``q = l2norm(q) / sqrt(d)``,
  ``k = l2norm(k)`` (``x / sqrt(sum x^2 + 1e-6)``);
  ``g = -exp(A_log) softplus(W_f^up W_f^down h + dt_bias)`` a channel,
  ``beta = sigmoid(W_beta h)`` a head. From ``S = 0`` (d x d a head)::

      S' = Diag(exp g_t) S      u = beta_t (v_t - S'^T k_t)
      S = S' + k_t u^T          o_t = S^T q_t

  ``out = W_o [w (.) o / rms_d(o) (.) sigmoid(W_g^up W_g^down h)]``, the
  norm over a head's ``d`` with one weight ``w`` (d) for all heads.
* latent attention layer (``layer_types`` "attention"), EXPANDED form only,
  ``mla_use_nope``: ``q = W_q h``, a head ``[q_n (128) ; q_r (64)]``;
  ``[c' (512) ; k_r (64)] = W_kva h``; ``c = RMSNorm(c')``; NOTHING is
  rotated; ``[k_n,h ; v_h] = W_kvb,h c``; ``score_h(i, j) = (q_n,h(i) .
  k_n,h(j) + q_r,h(i) . k_r(j)) / sqrt(192)``, causal softmax, ``o_h =
  sum_j p_h(i, j) v_h(j)``, ``out = W_o [o_h]``. No absorbed matrix, no
  cache.
* FFN of the first ``first_k_dense`` layers: ``down(silu(gate h) * up
  h)``. Of the others: ``s = sigmoid(h W_r)`` over ALL experts the router
  knows (256); the chosen are the ``k`` largest of ``s + b`` (one group: no
  group limit); ``w_e = f * s_e / (sum_chosen s + 1e-20)`` from the
  unbiased scores; ``y = sum_{chosen e HELD} w_e down_e(silu(gate_e h) *
  up_e h) + shared(h)``: the expert leaves hold experts ``[0, held)``, one
  chip's share, every held expert is computed for every token and weighted
  0 where it was not chosen, and what the absent experts would have added
  is left out (the partial result is what goes on).

The vocabulary is the slice the parameter tree holds (``lm_head`` and the
embedding of ``vocab_size`` rows). float32 ``jax.numpy`` at matmul precision
"highest"; no kernel, no cache, no batching. One sequence, layers in a
Python loop in the published order, one layer's weights cast at a time;
attention and the FFNs over blocks of query rows, the routed FFN one held
expert at a time, the head over blocks of the vocabulary
(:func:`shortfalls`), so that 12 layers at 8,192 positions fit beside a
12.5 GB server. Shares no code with ``deepspeed_tpu/`` or the other
references; reads only the parameter tree of ``TransformerLM``.

Assumed, the published config having no key for them: the low ranks of the
decay and the gate (``head_dim``, 128), the l2norm and ``1 / sqrt(d)`` on q,
the softplus / ``A_log`` / ``dt_bias`` form of the decay, no convolution
bias, and the pre-norm residual order ``x + mixer(norm x)``, then ``x +
ffn(norm x)``. The server keeps ``W_q``, ``W_k``, ``W_v`` as the one leaf
``qkv_proj`` (same parameters, same product)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ROW_BLOCK = 256         # query rows of one block of scores or of an FFN
VOCAB_BLOCK = 4096      # columns of the head at a time


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(scale)


def _by_rows(fn, x, block=ROW_BLOCK):
    """``fn`` over blocks of ``x``'s rows, put together again."""
    T = x.shape[0]
    block = min(block, T)
    assert T % block == 0, (T, block)
    out = jax.lax.map(fn, x.reshape((T // block, block) + x.shape[1:]))
    return out.reshape((T,) + out.shape[2:])


def make_forward(layer_types, n_head: int, kv_lora_rank: int,
                 qk_nope_head_dim: int, qk_rope_head_dim: int,
                 v_head_dim: int, kda_n_heads: int, kda_d_head: int,
                 experts_per_token: int, routed_scaling_factor: float,
                 first_k_dense: int, norm_topk_prob: bool = True,
                 eps: float = 1e-5):
    """``logits(params, ids, positions)``: one sequence ``ids`` (T,), the
    logits (len(positions), V) at the given positions. ``logits.hidden``
    stops before the head (the final norm's output at the positions), for
    :func:`shortfalls`, which never holds a position's whole logits."""
    H, R = n_head, kv_lora_rank
    dn, dr, dv = qk_nope_head_dim, qk_rope_head_dim, v_head_dim
    KH, KD = kda_n_heads, kda_d_head
    layer_types = tuple(layer_types)

    @jax.jit
    def embed(params, ids):
        return _f32(params["embed_tokens"]["embedding"][ids])

    def kda(p, x):
        T = x.shape[0]
        h = _rms_norm(x, p["ln_1"]["scale"], eps)
        a = p["kda"]
        inner = KH * KD
        qkv = h @ _f32(a["qkv_proj"]["kernel"])             # (T, 3 inner)
        w = _f32(a["conv_w"])                               # (taps, 3 inner)
        taps = w.shape[0]
        padded = jnp.concatenate(
            [jnp.zeros((taps - 1, 3 * inner), jnp.float32), qkv])
        qkv = jax.nn.silu(sum(w[j] * padded[j:j + T] for j in range(taps)))

        def unit(v):
            return v / jnp.sqrt((v * v).sum(-1, keepdims=True) + 1e-6)

        q, k, v = (qkv[:, i * inner:(i + 1) * inner].reshape(T, KH, KD)
                   for i in range(3))
        q, k = unit(q) / math.sqrt(KD), unit(k)
        f = (h @ _f32(a["f_a_proj"]["kernel"])) @ _f32(a["f_b_proj"]["kernel"])
        g = -jnp.exp(_f32(a["A_log"]))[:, None] * jax.nn.softplus(
            f + _f32(a["dt_bias"])).reshape(T, KH, KD)
        beta = jax.nn.sigmoid(h @ _f32(a["b_proj"]["kernel"]))     # (T, KH)

        def step(s, xs):
            q_t, k_t, v_t, g_t, b_t = xs
            s = jnp.exp(g_t)[:, :, None] * s                # (KH, KD, KD)
            u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
            s = s + k_t[:, :, None] * u[:, None, :]
            return s, jnp.einsum("hkv,hk->hv", s, q_t)

        _, o = jax.lax.scan(step, jnp.zeros((KH, KD, KD), jnp.float32),
                            (q, k, v, g, beta))
        o = _rms_norm(o, a["o_norm"], eps)                  # a head's d
        gate = (h @ _f32(a["g_a_proj"]["kernel"])) \
            @ _f32(a["g_b_proj"]["kernel"])
        y = o.reshape(T, inner) * jax.nn.sigmoid(gate)
        return x + y @ _f32(a["o_proj"]["kernel"])

    def attention(p, x):
        T = x.shape[0]
        h = _rms_norm(x, p["ln_1"]["scale"], eps)
        a = p["attn"]
        q = (h @ _f32(a["q_proj"]["kernel"])).reshape(T, H, dn + dr)
        ckr = h @ _f32(a["kv_a_proj"]["kernel"])
        c = _rms_norm(ckr[:, :R], a["kv_a_norm"]["scale"], eps)
        k_r = ckr[:, R:]                                    # (T, dr)
        q_n, q_r = q[..., :dn], q[..., dn:]
        kv = (c @ _f32(a["kv_b_proj"])).reshape(T, H, dn + dv)
        k_n, v = kv[..., :dn], kv[..., dn:]
        key_pos = jnp.arange(T)

        def rows(first):
            qn = jax.lax.dynamic_slice_in_dim(q_n, first, block, 0)
            qr = jax.lax.dynamic_slice_in_dim(q_r, first, block, 0)
            scores = (jnp.einsum("thd,shd->hts", qn, k_n)
                      + jnp.einsum("thd,sd->hts", qr, k_r)) \
                / math.sqrt(dn + dr)
            seen = (first + jnp.arange(block))[:, None] >= key_pos[None]
            scores = jnp.where(seen[None], scores, -jnp.inf)
            return jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, -1), v)

        block = min(ROW_BLOCK, T)
        assert T % block == 0, (T, block)
        att = jax.lax.map(rows, jnp.arange(0, T, block))    # (nb, b, H, dv)
        return x + att.reshape(T, H * dv) @ _f32(a["o_proj"]["kernel"])

    def gated(h, gate, up, down):
        return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)

    def dense_ffn(p, x):
        m = p["mlp"]
        return x + _by_rows(
            lambda h: gated(_rms_norm(h, p["ln_2"]["scale"], eps),
                            m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
                            m["down_proj"]["kernel"]), x)

    def sparse_ffn(p, experts, x):
        m = p["mlp"]
        held = experts["gate_proj"].shape[0]    # experts [0, held) are here

        def block(xb):
            h = _rms_norm(xb, p["ln_2"]["scale"], eps)
            score = jax.nn.sigmoid(h @ _f32(m["router"]))       # (b, E)
            _, chosen = jax.lax.top_k(score + _f32(m["router_bias"]),
                                      experts_per_token)
            top = jnp.take_along_axis(score, chosen, axis=-1)   # unbiased
            if norm_topk_prob:
                top = top / (top.sum(-1, keepdims=True) + 1e-20)
            weight = jnp.zeros_like(score).at[
                jnp.arange(h.shape[0])[:, None], chosen].add(
                    top * routed_scaling_factor)

            def one(acc, e):
                y = gated(h, experts["gate_proj"][e], experts["up_proj"][e],
                          experts["down_proj"][e])
                return acc + y * weight[:, e][:, None], None

            out, _ = jax.lax.scan(one, jnp.zeros_like(xb), jnp.arange(held))
            return out + gated(h, m["shared_gate_proj"]["kernel"],
                               m["shared_up_proj"]["kernel"],
                               m["shared_down_proj"]["kernel"])

        return x + _by_rows(block, x, 8 * ROW_BLOCK)

    mixers = {"kda": kda, "attention": attention}

    @jax.jit
    def dense_layer(blocks, i, x):
        p = jax.tree_util.tree_map(lambda a: a[i], blocks["block"])
        return dense_ffn(p, kda(p, x))

    def sparse_layer(kind):
        @jax.jit
        def run(blocks, experts, i, j, x):
            p = jax.tree_util.tree_map(lambda a: a[i], blocks["block"])
            e = jax.tree_util.tree_map(lambda a: a[j], experts)
            return sparse_ffn(p, e, mixers[kind](p, x))
        return run

    sparse = {kind: sparse_layer(kind) for kind in mixers}

    @jax.jit
    def final_norm(params, x, positions):
        return _rms_norm(x[positions], params["ln_f"]["scale"], eps)

    def hidden(params, ids, positions):
        i32 = jnp.int32
        with jax.default_matmul_precision("highest"):
            x = embed(params, jnp.asarray(ids))
            seen = {"kda": 0, "attention": 0}
            for n, kind in enumerate(layer_types):
                if n < first_k_dense:
                    assert kind == "kda", layer_types
                    x = dense_layer(params["dense_blocks"],
                                    jnp.asarray(n, i32), x)
                    continue
                leaf = {"kda": "kda_blocks", "attention": "attn_blocks"}[kind]
                x = sparse[kind](params[leaf], params["experts"],
                                 jnp.asarray(seen[kind], i32),
                                 jnp.asarray(n - first_k_dense, i32), x)
                seen[kind] += 1
            return final_norm(params, x, jnp.asarray(positions))

    def logits(params, ids, positions):
        with jax.default_matmul_precision("highest"):
            return hidden(params, ids, positions) \
                @ _f32(params["lm_head"]["kernel"])

    logits.hidden = hidden
    return logits


@jax.jit
def _head_stats(kernel, x, tokens):
    """Over blocks of the vocabulary: each position's best logit, largest
    |logit| and its logit of ``tokens``'s entry."""
    V = kernel.shape[1]
    block = min(VOCAB_BLOCK, V)
    assert V % block == 0, (V, block)

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


# check_greedy's two limits beside the caller's ``rel_tol`` (2**-5 of the
# position's largest |logit|, serve.py's). As Moonlight's: a top-8 choice
# is not continuous, and where a token's 8th and 9th biased scores nearly
# tie, one bfloat16 rounding upstream swaps an expert; here only a swap
# that touches one of the 32 held experts (an eighth of them) moves the
# sum. The readings (PERF.md section 6, PR 50) are taken through the server
# on the chip, on the four requests serve.py judges, against this reference
# of the bfloat16 weights (perf/tools/kimi_limits.py and the cell's runs):
#
# * ``SHARE_OVER`` (never fewer than ``MIN_OVER`` positions, so that a
#   request of a few tokens is not judged on a few ties): the share of a
#   request's positions beyond ``rel_tol``. The configured server (bfloat16
#   weights, float32 state): 11.0-16.5 % a request over three seeds (twelve
#   requests of 21-3,262 positions). The same server with weights rounded
#   to float8's three bits of mantissa, the nearest precision below: 78.8-
#   80.9 % on its requests of 1,330-2,085 positions: not correct, by this
#   limit, every long request. 40 % lies 2.4 x over the one reading and
#   2.0 x under the other. (The same server with its state HELD in
#   bfloat16 reads 16.7-20.6 %: over the configured server's on every
#   request of its seed, too near it for a limit on tokens; that precision
#   is held by the pool's audit of the state it holds, which the arm fails
#   on every row.)
# * ``WORST_FACTOR`` x ``rel_tol``, which no position may pass, tells no
#   precision apart (worst position of a bfloat16 run: up to 0.41 of the
#   scale; of a float8 run: 0.68-0.76) and a limit between the two would
#   leave a fresh seed's tail a third of room. At 1.5 x the scale it
#   guards against garbage (logits of another magnitude), as Moonlight's.
SHARE_OVER = 0.40
MIN_OVER = 4
WORST_FACTOR = 48.0


def shortfalls(logits_fn, params, prompt, output, context_len: int,
               score_len: int):
    """``(shortfall (n,), scale (n,))`` of the ``n`` generated tokens: the
    reference's best logit at the token's position less its logit of the
    token, and the position's largest |logit|. The sequence is padded to
    ``context_len`` and the scored positions to ``score_len`` (one compiled
    shape; a causal model keeps the padding from reaching earlier
    positions)."""
    import numpy as np

    P, n = len(prompt), len(output)
    seq = np.zeros((context_len,), np.int32)
    seq[:P] = prompt
    seq[P:P + n - 1] = output[:-1]
    positions = np.full((max(score_len, n),), P - 1, np.int32)
    positions[:n] = np.arange(P - 1, P - 1 + n)
    tokens = np.zeros((len(positions),), np.int32)
    tokens[:n] = output
    best, size, chosen = _head_stats(
        params["lm_head"]["kernel"],
        logits_fn.hidden(params, seq, positions), jnp.asarray(tokens))
    return np.asarray(best - chosen)[:n], np.asarray(size)[:n]


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
