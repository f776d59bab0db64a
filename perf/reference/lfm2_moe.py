"""Plain reference forward of LFM2-24B-A2B (Liquid AI; config.json
``model_type`` ``lfm2_moe``): a pre-norm decoder, RMSNorm (eps 1e-5), no
bias on any projection, the layers gated short convolutions or grouped-query
attention by ``layer_types``, a gated-silu FFN in the first
``first_k_dense`` layers and a routed one in the others, a final RMSNorm
(the family's ``embedding_norm``: it is the OUTPUT norm) and the head tied
to the embedding::

    x = E[ids]
    x <- x + Mixer(RMSNorm(x))      x <- x + FFN(RMSNorm(x))     a layer
    logits = E RMSNorm(x_L)

* conv layer (``layer_types`` "conv"), ``u`` its normed input (C wide), ONE
  position at a time from its definition::

      [B ; C ; z] = W_in u            (C channels each, in this order)
      v_t = B_t (.) z_t
      c_t = w_0 (.) v_{t-2} + w_1 (.) v_{t-1} + w_2 (.) v_t      v_{<0} = 0
      out = W_out (C_t (.) c_t)

  depthwise, causal, ``conv_L_cache`` = 3 taps (``conv_w[2]`` meets the
  token itself), no bias, NO activation. What a sequence carries is
  ``v_{t-2}, v_{t-1}``; here a ``lax.scan`` over the positions shifts them.
* attention layer ("attention"; published as "full_attention"): ``q, k, v
  = W_q u, W_k u, W_v u``, ``n_head`` query heads and ``n_kv_head`` K/V
  heads of ``head_dim``; ``q <- RMSNorm_d(q)``, ``k <- RMSNorm_d(k)`` a
  head (one learned weight of ``head_dim`` each, the block's eps); rotary
  over the whole head, half-rotation (``x cos + [-x_2 ; x_1] sin``, the
  angle of channel ``i`` and ``i + d/2`` is ``pos theta^(-2i/d)``); causal
  softmax at ``1 / sqrt(head_dim)``; ``W_o``.
* FFN of the first ``first_k_dense`` layers: ``W_2 (silu(W_1 h) (.) W_3
  h)``. Of the others: ``s = sigmoid(h W_r)`` (no bias on the projection);
  the chosen are the ``k`` largest of ``s + expert_bias``; ``w_e = f s_e /
  (sum_chosen s + 1e-6)`` from the UNBIASED scores; ``y = sum_e w_e
  down_e(silu(gate_e h) (.) up_e h)``: every expert is computed for every
  token and weighted 0 where it was not chosen. No shared expert.

float32 ``jax.numpy`` at matmul precision "highest"; no kernel, no cache,
no batching. One sequence, layers in a Python loop in the published order,
one layer's weights cast at a time; attention and the FFNs over blocks of
query rows, the routed FFN one expert at a time, the head over blocks of
the vocabulary (:func:`shortfalls`), so that 8 layers at 4,096 positions
fit beside a 12.4 GB server. Shares no code with ``deepspeed_tpu/`` or the
other references; reads only the parameter tree of ``TransformerLM``.

Departures from the published description, each the configuration file's
``assumed``: the head is tied to the embedding (the family's convention;
the config has no key); ``head_dim`` is ``hidden_size / num_attention_heads``
= 64. The router's epsilon is the published ``1e-6``, here and in the
program (``TransformerConfig.topk_norm_eps``; Moonlight's and Kimi Linear's
routers divide by ``sum + 1e-20``, which ``route`` keeps as its default).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ROW_BLOCK = 256         # query rows of one block of scores or of an FFN
VOCAB_BLOCK = 8192      # rows of the tied embedding scored at a time
ROUTER_EPS = 1e-6       # joins the chosen scores' sum (published)
PAD_TO = 1024           # a sequence is padded to a multiple of this (and
# the routed FFN runs this many rows at a time)


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


def _short_conv(v, w):
    """``c_t = sum_j w_j (.) v_{t - (K - 1) + j}`` position by position: the
    carry is the last K - 1 rows of ``v`` (zeros before the sequence)."""
    taps = w.shape[0]

    def position(before, v_t):
        seen = jnp.concatenate([before, v_t[None]])         # (K, C)
        return seen[1:], (w * seen).sum(0)

    _, c = jax.lax.scan(
        position, jnp.zeros((taps - 1, v.shape[1]), jnp.float32), v)
    return c


def _rotary(x, theta):
    """Half-rotation rotary over the whole last dimension of ``x`` (T,
    heads, d), positions 0 .. T - 1."""
    T, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv   # (T, d/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + turned * sin


def make_forward(layer_types, n_head: int, n_kv_head: int, head_dim: int,
                 rope_theta: float, experts_per_token: int,
                 routed_scaling_factor: float, first_k_dense: int,
                 norm_topk_prob: bool = True, eps: float = 1e-5):
    """``logits(params, ids, positions)``: one sequence ``ids`` (T,), the
    logits (len(positions), V) at the given positions. ``logits.hidden``
    stops before the head (the final norm's output at the positions), for
    :func:`shortfalls`, which never holds a position's whole logits."""
    H, KV, D = n_head, n_kv_head, head_dim
    layer_types = tuple(layer_types)

    @jax.jit
    def embed(params, ids):
        return _f32(params["embed_tokens"]["embedding"][ids])

    def conv(p, x):
        u = _rms_norm(x, p["ln_1"]["scale"], eps)
        m = p["conv"]
        C = x.shape[1]
        bcz = u @ _f32(m["in_proj"]["kernel"])              # (T, 3 C)
        b, c, z = bcz[:, :C], bcz[:, C:2 * C], bcz[:, 2 * C:]
        y = c * _short_conv(b * z, _f32(m["conv_w"]))
        return x + y @ _f32(m["out_proj"]["kernel"])

    def attention(p, x):
        T = x.shape[0]
        u = _rms_norm(x, p["ln_1"]["scale"], eps)
        a = p["attn"]
        q = (u @ _f32(a["q_proj"]["kernel"])).reshape(T, H, D)
        k = (u @ _f32(a["k_proj"]["kernel"])).reshape(T, KV, D)
        v = (u @ _f32(a["v_proj"]["kernel"])).reshape(T, KV, D)
        q = _rotary(_rms_norm(q, a["q_norm"]["scale"], eps), rope_theta)
        k = _rotary(_rms_norm(k, a["k_norm"]["scale"], eps), rope_theta)
        q = q.transpose(1, 0, 2)                            # (H, T, D)
        k = jnp.repeat(k.transpose(1, 0, 2), H // KV, axis=0)
        v = jnp.repeat(v.transpose(1, 0, 2), H // KV, axis=0)
        block = min(ROW_BLOCK, T)
        assert T % block == 0, (T, block)
        key_pos = jnp.arange(T)

        def rows(first):
            qb = jax.lax.dynamic_slice_in_dim(q, first, block, 1)
            scores = (qb @ k.transpose(0, 2, 1)) / math.sqrt(D)
            seen = (first + jnp.arange(block))[:, None] >= key_pos[None]
            return jax.nn.softmax(
                jnp.where(seen[None], scores, -jnp.inf), -1) @ v

        out = jax.lax.map(rows, jnp.arange(0, T, block))    # (nb, H, b, D)
        out = out.transpose(0, 2, 1, 3).reshape(T, H * D)
        return x + out @ _f32(a["o_proj"]["kernel"])

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
        n_experts = experts["gate_proj"].shape[0]

        def block(xb):
            h = _rms_norm(xb, p["ln_2"]["scale"], eps)
            score = jax.nn.sigmoid(h @ _f32(m["router"]))       # (b, E)
            _, chosen = jax.lax.top_k(score + _f32(m["router_bias"]),
                                      experts_per_token)
            top = jnp.take_along_axis(score, chosen, axis=-1)   # unbiased
            if norm_topk_prob:
                top = top / (top.sum(-1, keepdims=True) + ROUTER_EPS)
            weight = jnp.zeros_like(score).at[
                jnp.arange(h.shape[0])[:, None], chosen].add(
                    top * routed_scaling_factor)

            def one(acc, e):
                y = gated(h, experts["gate_proj"][e], experts["up_proj"][e],
                          experts["down_proj"][e])
                return acc + y * weight[:, e][:, None], None

            out, _ = jax.lax.scan(one, jnp.zeros_like(xb),
                                  jnp.arange(n_experts))
            return out

        return x + _by_rows(block, x, PAD_TO)

    mixers = {"conv": conv, "attention": attention}

    @jax.jit
    def dense_layer(blocks, i, x):
        p = jax.tree_util.tree_map(lambda a: a[i], blocks["block"])
        return dense_ffn(p, conv(p, x))

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
            seen = {"conv": 0, "attention": 0}
            for n, kind in enumerate(layer_types):
                if n < first_k_dense:
                    assert kind == "conv", layer_types
                    x = dense_layer(params["dense_blocks"],
                                    jnp.asarray(n, i32), x)
                    continue
                leaf = {"conv": "conv_blocks", "attention": "attn_blocks"}[
                    kind]
                x = sparse[kind](params[leaf], params["experts"],
                                 jnp.asarray(seen[kind], i32),
                                 jnp.asarray(n - first_k_dense, i32), x)
                seen[kind] += 1
            return final_norm(params, x, jnp.asarray(positions))

    def logits(params, ids, positions):
        with jax.default_matmul_precision("highest"):
            return hidden(params, ids, positions) \
                @ _f32(params["embed_tokens"]["embedding"]).T

    logits.hidden = hidden
    return logits


@jax.jit
def _head_stats(table, x, tokens):
    """Over blocks of the vocabulary (rows of the tied embedding): each
    position's best logit, largest |logit| and its logit of ``tokens``'s
    entry."""
    V = table.shape[0]
    block = min(VOCAB_BLOCK, V)
    assert V % block == 0, (V, block)

    def one(carry, first):
        best, size, chosen = carry
        lg = x @ _f32(jax.lax.dynamic_slice_in_dim(table, first, block, 0)).T
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
# position's largest |logit|, serve.py's). As Moonlight's and Kimi Linear's:
# a top-4 choice is not continuous, and where a token's 4th and 5th biased
# scores nearly tie, one bfloat16 rounding upstream swaps an expert and the
# token's FFN output with it; six routed layers deep and under a tied head
# of 65,536 seeded rows whose best two lie close, such a swap moves a served
# token past ``rel_tol`` where nothing is wrong. The readings (PERF.md
# section 6, PR 54) are taken through the server on the chip, on the four
# requests serve.py judges, against this reference of the bfloat16 weights
# (perf/tools/lfm2_limits.py and the cell's runs):
#
# * ``SHARE_OVER``: the share of a request's positions beyond ``rel_tol``.
#   The configured server (bfloat16 weights, a bfloat16 tail): 13.1-16.8 %
#   a request of 500 positions and more, fourteen seeds (some forty such requests
#   of 522-3,072 positions). The same server with the carried tail rounded
#   from bfloat16 to float8's three bits of mantissa each time it is
#   written, the nearest precision below the tail's: 34.2-39.0 % on its
#   long requests at three seeds. With the WEIGHTS rounded to float8, the
#   nearest precision below theirs: 69.2-74.3 % at two seeds. 25 % lies
#   1.5 x over the configured server's largest reading and 1.4 x under the
#   nearer control's smallest: both controls come out as not correct by
#   this limit, on every long request of every run (the tail's arm once
#   more through the harness's own verdict under these limits).
# * ``MIN_OVER``: never fewer than this many positions are allowed, so that
#   a request seated a moment before the window closed is not judged on a
#   few ties (requests of 2-71 positions read 0-43 % on the configured
#   server: 3 of 7, 7 of 39, 11 of 60). At a share of 16 % a request of 128
#   positions passes 32 by chance once in ~400; a control still fails the
#   run on its long requests, of which every run judges three.
# * ``WORST_FACTOR`` x ``rel_tol``, which no position may pass, tells no
#   precision apart (worst position of a configured run: up to 0.40 of the
#   scale; of a float8 tail 0.52, of float8 weights 0.66) and a limit
#   between them would leave a fresh seed's tail no room. At 1.5 x the
#   scale it guards against garbage (logits of another magnitude), as
#   Moonlight's.
SHARE_OVER = 0.25
MIN_OVER = 32
WORST_FACTOR = 48.0


def shortfalls(logits_fn, params, prompt, output, context_len: int,
               score_len: int):
    """``(shortfall (n,), scale (n,))`` of the ``n`` generated tokens: the
    reference's best logit at the token's position less its logit of the
    token, and the position's largest |logit|. The sequence is padded to a
    block of query rows or to a multiple of ``PAD_TO`` positions (a causal
    model keeps the padding from reaching earlier positions) and the scored
    positions to ``score_len``: a few compiled shapes."""
    import numpy as np

    P, n = len(prompt), len(output)
    length = ROW_BLOCK if P + n <= ROW_BLOCK \
        else min(-(-(P + n) // PAD_TO) * PAD_TO, max(context_len, P + n))
    seq = np.zeros((length,), np.int32)
    seq[:P] = prompt
    seq[P:P + n - 1] = output[:-1]
    positions = np.full((max(score_len, n),), P - 1, np.int32)
    positions[:n] = np.arange(P - 1, P - 1 + n)
    tokens = np.zeros((len(positions),), np.int32)
    tokens[:n] = output
    best, size, chosen = _head_stats(
        params["embed_tokens"]["embedding"],
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
