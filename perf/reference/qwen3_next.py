"""Plain reference forward of Qwen3-Next-80B-A3B-Instruct (Qwen; config.json
``model_type`` ``qwen3_next``): pre-norm decoder, no bias on a projection,
untied head, final norm. ``norm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)``
(the zero-centred RMSNorm, eps 1e-6) everywhere but the DeltaNet output
norm. Layer ``i`` is ``layer_types[i]``; with ``h = norm_1(x)``:

* Gated DeltaNet layer ("gdn"), as the token-by-token recurrence and no
  chunk form. ``Hk`` key heads, ``H`` value heads of ``d`` channels each,
  key head ``j`` serving value heads ``rep j .. rep j + rep - 1``:
  ``[q ; k ; v ; z] = W_qkvz h`` (``Hk d + Hk d + H d + H d`` columns, in
  this order), ``[b ; a] = W_ba h`` (``H + H``). ``[q ; k ; v] =
  silu(conv([q ; k ; v]))``, causal and depthwise over 4 taps without bias
  (``conv_w[3]`` meets the token itself). A head: ``q = l2(q) / sqrt(d)``,
  ``k = l2(k)``, ``l2(x) = x / sqrt(sum x^2 + 1e-6)``. ``beta =
  sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``, one number a
  value head. From ``S = 0`` (``d x d`` a value head)::

      S' = exp(g_t) S           u = beta_t (v_t - S'^T k_t)
      S = S' + k_t u^T          o_t = S^T q_t

  ``out = W_o [o / sqrt(mean_d(o^2) + eps) * w_o * silu(z)]``, the norm
  over a head's ``d`` with one PLAIN weight ``w_o`` (d) for all heads.
* gated attention layer ("attention"): ``q = W_q h``, ``gate = W_z h``
  (``n_head x head_dim`` each), ``k = W_k h``, ``v = W_v h`` (``kv_heads x
  head_dim``); ``q = norm_q(q)``, ``k = norm_k(k)`` a head (the ``1 + w``
  norm over ``head_dim``); the rotary over a head's first ``rotary_pct x
  head_dim`` channels (pairs ``(i, i + r / 2)``, angle ``p theta^(-2 i /
  r)``); causal softmax attention at scale ``1 / sqrt(head_dim)``, KV head
  ``j`` serving query heads ``rep j .. rep j + rep - 1``; ``out = W_o (o *
  sigmoid(gate))``. No cache, no pages.
* FFN of every layer, with ``h = norm_2(x)``: ``p = softmax(h W_r)`` over
  ALL experts the router knows (512), float32; the ``k`` largest; ``w_e =
  p_e / sum of the chosen`` (``norm_topk_prob``); ``y = sum_{chosen e
  HELD} w_e down_e(silu(gate_e h) * up_e h) + sigmoid(w_s . h)
  shared(h)``: the expert leaves hold experts ``[0, held)``, one chip's
  share, every held expert is computed for every token and weighted 0
  where it was not chosen, and what the absent experts would have added is
  left out (the partial result is what goes on).

float32 ``jax.numpy`` at matmul precision "highest"; no kernel, no cache, no
batching. One sequence, layers in a Python loop in the published order, one
layer's weights cast at a time; attention and the FFNs over blocks of query
rows, the routed FFN one held expert at a time, the head over blocks of the
vocabulary (:func:`shortfalls`), so that 8 layers at 8,192 positions fit
beside a 12.5 GB server. Shares no code with ``deepspeed_tpu/`` or the other
references; reads only the parameter tree of ``TransformerLM``.

Departures from the checkpoint, as the configuration file lists them: the
multi-token-prediction module is left out; ``W_qkvz``'s columns lie ``[q ;
k ; v ; z]`` plainly where the checkpoint groups them a key head; the
attention's ``[q ; gate]`` projection is two leaves (``q_proj``,
``z_proj``): the same parameters, the same products."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ROW_BLOCK = 256         # query rows of one block of scores
FFN_BLOCK = 2048        # rows of one block of an FFN
VOCAB_BLOCK = 4096      # columns of the head at a time


def _f32(x):
    return x.astype(jnp.float32)


def _norm1p(x, w, eps):
    """The zero-centred RMSNorm: ``x / rms(x) * (1 + w)``."""
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) \
        * (1.0 + _f32(w))


def _by_rows(fn, x, block):
    """``fn`` over blocks of ``x``'s rows, put together again."""
    T = x.shape[0]
    block = min(block, T)
    assert T % block == 0, (T, block)
    out = jax.lax.map(fn, x.reshape((T // block, block) + x.shape[1:]))
    return out.reshape((T,) + out.shape[2:])


def make_forward(layer_types, n_head: int, kv_heads: int, head_dim: int,
                 rotary_pct: float, rope_theta: float, gdn_n_key_heads: int,
                 gdn_n_value_heads: int, gdn_d_head: int,
                 experts_per_token: int, norm_topk_prob: bool = True,
                 eps: float = 1e-6):
    """``logits(params, ids, positions)``: one sequence ``ids`` (T,), the
    logits (len(positions), V) at the given positions. ``logits.hidden``
    stops before the head (the final norm's output at the positions), for
    :func:`shortfalls`, which never holds a position's whole logits."""
    H, KV, D = n_head, kv_heads, head_dim
    Hk, Hv, d = gdn_n_key_heads, gdn_n_value_heads, gdn_d_head
    rd = int(rotary_pct * D) // 2 * 2
    layer_types = tuple(layer_types)

    @jax.jit
    def embed(params, ids):
        return _f32(params["embed_tokens"]["embedding"][ids])

    def gdn(p, x):
        T = x.shape[0]
        h = _norm1p(x, p["ln_1"]["scale"], eps)
        a = p["gdn"]
        keys, values = Hk * d, Hv * d
        qkvz = h @ _f32(a["qkvz_proj"]["kernel"])
        qkv, z = qkvz[:, :2 * keys + values], qkvz[:, 2 * keys + values:]
        w = _f32(a["conv_w"])                           # (taps, channels)
        taps = w.shape[0]
        padded = jnp.concatenate(
            [jnp.zeros((taps - 1, qkv.shape[1]), jnp.float32), qkv])
        qkv = jax.nn.silu(sum(w[j] * padded[j:j + T] for j in range(taps)))

        def l2(v):
            return v / jnp.sqrt((v * v).sum(-1, keepdims=True) + 1e-6)

        q = l2(qkv[:, :keys].reshape(T, Hk, d)) / math.sqrt(d)
        k = l2(qkv[:, keys:2 * keys].reshape(T, Hk, d))
        v = qkv[:, 2 * keys:].reshape(T, Hv, d)
        # key head j serves value heads rep j .. rep j + rep - 1
        q, k = (jnp.repeat(t, Hv // Hk, axis=1) for t in (q, k))
        ba = h @ _f32(a["ba_proj"]["kernel"])
        beta = jax.nn.sigmoid(ba[:, :Hv])                       # (T, Hv)
        g = -jnp.exp(_f32(a["A_log"])) * jax.nn.softplus(
            ba[:, Hv:] + _f32(a["dt_bias"]))                    # (T, Hv)

        def step(s, xs):
            q_t, k_t, v_t, g_t, b_t = xs
            s = jnp.exp(g_t)[:, None, None] * s                 # (Hv, d, d)
            u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
            s = s + k_t[:, :, None] * u[:, None, :]
            return s, jnp.einsum("hkv,hk->hv", s, q_t)

        _, o = jax.lax.scan(step, jnp.zeros((Hv, d, d), jnp.float32),
                            (q, k, v, g, beta))
        o = o / jnp.sqrt((o * o).mean(-1, keepdims=True) + eps) \
            * _f32(a["o_norm"])                 # a plain weight, a head's d
        y = o.reshape(T, values) * jax.nn.silu(z)
        return x + y @ _f32(a["o_proj"]["kernel"])

    def rotary(t, positions):
        """The first ``rd`` channels of every head of ``t`` (T, heads, D)
        rotated by position: channel ``i < rd / 2`` pairs with ``i + rd /
        2``."""
        half = rd // 2
        freq = rope_theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / rd)
        ang = positions[:, None].astype(jnp.float32) * freq     # (T, half)
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        lo, hi, rest = t[..., :half], t[..., half:rd], t[..., rd:]
        return jnp.concatenate(
            [lo * cos - hi * sin, hi * cos + lo * sin, rest], -1)

    def attention(p, x):
        T = x.shape[0]
        h = _norm1p(x, p["ln_1"]["scale"], eps)
        a = p["attn"]
        q = (h @ _f32(a["q_proj"]["kernel"])).reshape(T, H, D)
        gate = h @ _f32(a["z_proj"]["kernel"])                  # (T, H D)
        k = (h @ _f32(a["k_proj"]["kernel"])).reshape(T, KV, D)
        v = (h @ _f32(a["v_proj"]["kernel"])).reshape(T, KV, D)
        q = _norm1p(q, a["q_norm"]["scale"], eps)
        k = _norm1p(k, a["k_norm"]["scale"], eps)
        pos = jnp.arange(T)
        q, k = rotary(q, pos), rotary(k, pos)
        q = q.reshape(T, KV, H // KV, D)    # query head j r: KV head j

        def rows(first):
            qb = jax.lax.dynamic_slice_in_dim(q, first, block, 0)
            scores = jnp.einsum("tjrd,sjd->jrts", qb, k) / math.sqrt(D)
            seen = (first + jnp.arange(block))[:, None] >= pos[None]
            scores = jnp.where(seen[None, None], scores, -jnp.inf)
            return jnp.einsum("jrts,sjd->tjrd", jax.nn.softmax(scores, -1),
                              v)

        block = min(ROW_BLOCK, T)
        assert T % block == 0, (T, block)
        att = jax.lax.map(rows, jnp.arange(0, T, block))
        y = att.reshape(T, H * D) * jax.nn.sigmoid(gate)
        return x + y @ _f32(a["o_proj"]["kernel"])

    def gated(h, gate, up, down):
        return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)

    def ffn(p, experts, x):
        m = p["mlp"]
        held = experts["gate_proj"].shape[0]    # experts [0, held) are here

        def block(xb):
            h = _norm1p(xb, p["ln_2"]["scale"], eps)
            prob = jax.nn.softmax(h @ _f32(m["router"]), axis=-1)   # (b, E)
            top, chosen = jax.lax.top_k(prob, experts_per_token)
            if norm_topk_prob:
                top = top / top.sum(-1, keepdims=True)
            weight = jnp.zeros_like(prob).at[
                jnp.arange(h.shape[0])[:, None], chosen].add(top)

            def one(acc, e):
                y = gated(h, experts["gate_proj"][e], experts["up_proj"][e],
                          experts["down_proj"][e])
                return acc + y * weight[:, e][:, None], None

            out, _ = jax.lax.scan(one, jnp.zeros_like(xb), jnp.arange(held))
            share = jax.nn.sigmoid(h @ _f32(m["shared_gate_w"]))    # (b,)
            return out + share[:, None] * gated(
                h, m["shared_gate_proj"]["kernel"],
                m["shared_up_proj"]["kernel"],
                m["shared_down_proj"]["kernel"])

        return x + _by_rows(block, x, FFN_BLOCK)

    mixers = {"gdn": gdn, "attention": attention}

    def layer_of(kind):
        @jax.jit
        def run(blocks, experts, i, j, x):
            p = jax.tree_util.tree_map(lambda a: a[i], blocks["block"])
            e = jax.tree_util.tree_map(lambda a: a[j], experts)
            return ffn(p, e, mixers[kind](p, x))
        return run

    layers = {kind: layer_of(kind) for kind in mixers}

    @jax.jit
    def final_norm(params, x, positions):
        return _norm1p(x[positions], params["ln_f"]["scale"], eps)

    def hidden(params, ids, positions):
        i32 = jnp.int32
        with jax.default_matmul_precision("highest"):
            x = embed(params, jnp.asarray(ids))
            seen = {"gdn": 0, "attention": 0}
            for n, kind in enumerate(layer_types):
                leaf = {"gdn": "gdn_blocks", "attention": "attn_blocks"}[kind]
                x = layers[kind](params[leaf], params["experts"],
                                 jnp.asarray(seen[kind], i32),
                                 jnp.asarray(n, i32), x)
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
    edge = -(-V // block) * block       # (151,936 is no multiple of 4,096)

    def one(carry, first):
        best, size, chosen = carry
        # (a block that would pass the edge is read from further back, and
        # the columns a block before it has seen are masked out)
        at = jnp.minimum(first, V - block)
        lg = x @ _f32(jax.lax.dynamic_slice_in_dim(kernel, at, block, 1))
        col = at + jnp.arange(block)
        new = col >= first
        inside = (tokens >= at) & (tokens < at + block)
        mine = jnp.take_along_axis(
            lg, jnp.clip(tokens - at, 0, block - 1)[:, None], 1)[:, 0]
        return (jnp.maximum(best, jnp.where(new, lg, -jnp.inf).max(-1)),
                jnp.maximum(size, jnp.where(new, jnp.abs(lg), 0.0).max(-1)),
                jnp.where(inside, mine, chosen)), None

    n = x.shape[0]
    start = (jnp.full((n,), -jnp.inf), jnp.zeros((n,)), jnp.zeros((n,)))
    with jax.default_matmul_precision("highest"):
        (best, size, chosen), _ = jax.lax.scan(
            one, start, jnp.arange(0, edge, block))
    return best, size, chosen


# check_greedy's two limits beside the caller's ``rel_tol`` (2**-5 of the
# position's largest |logit|, serve.py's). As Kimi's and Moonlight's: a
# top-10 choice is not continuous, and where a token's 10th and 11th
# probabilities nearly tie, one bfloat16 rounding upstream swaps an expert;
# here only a swap that touches one of the 128 held experts (a quarter of
# them) moves the sum. The readings are taken through the server on the
# chip, on the four requests serve.py judges, against this reference of the
# bfloat16 weights (perf/tools/qwen3_next_limits.py; PERF.md section 6,
# PR 60, has the numbers of each arm):
#
# * ``SHARE_OVER`` (never fewer than ``MIN_OVER`` positions, so that a
#   request of a few tokens is not judged on a few ties): the share of a
#   request's positions beyond ``rel_tol``. The configured server (bfloat16
#   weights, float32 state): 2.7-9.1 % a request over four seeds (sixteen
#   requests of 112-1,024 positions; my chip runs, PR 60, calls 1, 3, 4).
#   The same server with weights rounded to float8's three bits of
#   mantissa, the nearest precision below: 76.7-80.5 % on its four
#   requests of 197-910 positions: not correct, by this limit, every
#   request. 30 % lies 3.3 x over the one reading and 2.6 x under the
#   other. (The same server with its state HELD in bfloat16 reads
#   4.8-7.3 %, and with its router's logits rounded to bfloat16 5.6-8.6 %
#   beside 4.0-8.2 % at its seed: too near it for a limit on tokens. The
#   state's precision is held by the pool's audit of the state it holds,
#   which that arm fails on every row; the router's is told by nothing the
#   cell measures: PERF.md section 7.)
# * ``WORST_FACTOR`` x ``rel_tol``, which no position may pass, tells no
#   precision apart (worst position of a bfloat16 run: 0.08-0.13 of the
#   scale; of a float8 run: 0.53-0.60) and at 1.5 x the scale guards
#   against garbage (logits of another magnitude), as Kimi's.
SHARE_OVER = 0.30
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
