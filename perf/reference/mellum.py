"""Plain reference forward of Mellum 2 (JetBrains,
``Mellum2-12B-A2.5B-Instruct`` config.json): pre-norm decoder, RMSNorm
(eps 1e-6), no bias anywhere; grouped-query attention with ``head_dim``
given (not hidden / heads), rotary over the whole head by layer type
(``sliding_attention``: plain ``theta ** (-2i/d)``; ``full_attention``:
static YaRN as the ``transformers`` library computes it, cos and sin
scaled by ``attention_factor``), a sliding layer's query ``i`` seeing key
``j`` iff ``0 <= i - j < sliding_window``; every FFN routed: float32 softmax
over the experts, the ``k`` largest, renormalised (``norm_topk_prob``),
``sum_e w_e * down_e(silu(gate_e h) * up_e h)``, no token dropped, no
shared expert; final RMSNorm, untied head.

float32 ``jax.numpy`` at matmul precision "highest"; no kernel, no cache,
no batching. One sequence, layers in a Python loop, one layer's weights
cast at a time; attention over blocks of query rows and the FFN one expert
at a time (every expert computed for every token, the unchosen weighted
0), so that 8 layers at 8k positions fit beside a server. Shares no code
with ``deepspeed_tpu/`` or the other references; reads only the parameter
tree of ``TransformerLM``.

Assumed, the published config having no key for them: the residual order
of the families the program already has (``x + attn(norm x)``, then
``x + ffn(norm x)``) and no norm on ``q`` or ``k``. The "MTP head" the
model card mentions has no key in the config and is not part of serving:
left out."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256       # rows of one block of attention scores


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(scale)


def inv_freq_and_factor(head_dim: int, rope: dict):
    """One ``rope_parameters`` section -> ``(inv_freq (d/2,), factor)``."""
    d, theta = head_dim, float(rope["rope_theta"])
    i = jnp.arange(d // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / d)
    if rope.get("rope_type", "default") == "default":
        return f, 1.0
    s = float(rope["factor"])
    L0 = float(rope["original_max_position_embeddings"])

    def corr(beta):
        return d * math.log(L0 / (2 * math.pi * beta)) / (2 * math.log(theta))

    low = max(math.floor(corr(float(rope.get("beta_fast", 32.0)))), 0)
    high = min(math.ceil(corr(float(rope.get("beta_slow", 1.0)))), d - 1)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    factor = rope.get("attention_factor") or 0.1 * math.log(s) + 1.0
    return f / s * ramp + f * (1.0 - ramp), float(factor)


def _rotary(x, inv_freq, factor):
    """x: (H, T, D), rotate-half form over the whole head."""
    T, D = x.shape[1], x.shape[2]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None] * factor
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None] * factor
    turned = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * cos + turned * sin


def make_forward(n_head: int, n_kv_head: int, head_dim: int, layer_types,
                 sliding_window: int, rope_parameters, experts_per_token: int,
                 norm_topk_prob: bool = True, eps: float = 1e-6):
    """``logits(params, ids, positions)``: one sequence ``ids`` (T,), the
    logits (len(positions), V) at the given positions. ``rope_parameters``
    maps a layer type to its section (a dict, or the pairs of one)."""
    sections = {kind: dict(section)
                for kind, section in dict(rope_parameters).items()}
    rep = n_head // n_kv_head

    @jax.jit
    def embed(params, ids):
        return _f32(params["embed_tokens"]["embedding"][ids])

    def attention(p, x, inv_freq, factor, window):
        T = x.shape[0]
        h = _rms_norm(x, p["ln_1"]["scale"], eps)
        q = (h @ _f32(p["attn"]["q_proj"]["kernel"])).reshape(
            T, n_head, head_dim).transpose(1, 0, 2)
        k, v = ((h @ _f32(p["attn"][n]["kernel"])).reshape(
                    T, n_kv_head, head_dim).transpose(1, 0, 2)
                for n in ("k_proj", "v_proj"))
        q, k = _rotary(q, inv_freq, factor), _rotary(k, inv_freq, factor)
        k, v = jnp.repeat(k, rep, axis=0), jnp.repeat(v, rep, axis=0)
        block = min(QUERY_BLOCK, T)
        assert T % block == 0, (T, block)
        key_pos = jnp.arange(T)

        def rows(first):
            qb = jax.lax.dynamic_slice_in_dim(q, first, block, 1)
            scores = qb @ k.transpose(0, 2, 1) / math.sqrt(head_dim)
            dist = (first + jnp.arange(block))[:, None] - key_pos[None]
            seen = dist >= 0
            if window:
                seen = seen & (dist < window)
            scores = jnp.where(seen[None], scores, -jnp.inf)
            return jax.nn.softmax(scores, axis=-1) @ v      # (H, block, D)

        att = jax.lax.map(rows, jnp.arange(0, T, block))    # (nb, H, b, D)
        att = att.transpose(0, 2, 1, 3).reshape(T, n_head * head_dim)
        return x + att @ _f32(p["attn"]["o_proj"]["kernel"])

    def ffn(p, experts, x):
        h = _rms_norm(x, p["ln_2"]["scale"], eps)
        prob = jax.nn.softmax(h @ _f32(p["mlp"]["router"]), axis=-1)
        top, chosen = jax.lax.top_k(prob, experts_per_token)
        if norm_topk_prob:
            top = top / top.sum(-1, keepdims=True)
        n_experts = prob.shape[-1]
        weight = jnp.zeros_like(prob).at[
            jnp.arange(h.shape[0])[:, None], chosen].add(top)   # (T, E)

        def one(acc, e):
            g, u, d = (_f32(experts[n][e])
                       for n in ("gate_proj", "up_proj", "down_proj"))
            y = (jax.nn.silu(h @ g) * (h @ u)) @ d
            return acc + y * weight[:, e][:, None], None

        out, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(n_experts))
        return x + out

    @jax.jit
    def sliding_layer(blocks, experts, i, x, inv_freq, factor):
        p = jax.tree_util.tree_map(lambda a: a[i], blocks["block"])
        e = jax.tree_util.tree_map(lambda a: a[i], experts)
        return ffn(p, e, attention(p, x, inv_freq, factor, sliding_window))

    @jax.jit
    def full_layer(blocks, experts, i, x, inv_freq, factor):
        p = jax.tree_util.tree_map(lambda a: a[i], blocks["block"])
        e = jax.tree_util.tree_map(lambda a: a[i], experts)
        return ffn(p, e, attention(p, x, inv_freq, factor, 0))

    @jax.jit
    def head(params, x, positions):
        x = _rms_norm(x[positions], params["ln_f"]["scale"], eps)
        return x @ _f32(params["lm_head"]["kernel"])

    def logits(params, ids, positions):
        with jax.default_matmul_precision("highest"):
            x = embed(params, jnp.asarray(ids))
            for i, kind in enumerate(layer_types):
                inv_freq, factor = inv_freq_and_factor(head_dim,
                                                       sections[kind])
                layer = sliding_layer if kind == "sliding_attention" \
                    else full_layer
                x = layer(params["blocks"], params["experts"],
                          jnp.asarray(i, jnp.int32), x, inv_freq,
                          jnp.asarray(factor, jnp.float32))
            return head(params, x, jnp.asarray(positions))

    return logits


# check_greedy's two limits beside the caller's ``rel_tol`` (2**-5 of the
# position's largest |logit|, serve.py's). A routed FFN is not continuous:
# where a token's 8th and 9th router probabilities nearly tie, one bfloat16
# rounding upstream swaps an expert (an eighth of the layer's output), and
# every logit of that position moves by a few per cent of the scale. Both
# readings are taken through the server on the chip, on the four requests
# serve.py judges, against this reference of the bfloat16 weights (PERF.md
# section 6; the second by perf/tools/mellum_limits.py):
#
# * ``SHARE_OVER`` (never fewer than ``MIN_OVER`` positions, so that a
#   request of a few tokens is not judged on one tie): the share of a
#   request's positions beyond ``rel_tol``. The bfloat16 server: at most
#   5.5 % of any request's positions over 24 runs (96 requests; the long
#   prompts read highest). The same server with weights rounded to float8's
#   three bits of mantissa, the nearest precision below: 2.4-47 % a
#   request, and in every run at least one request at 17 % or more (five
#   runs): not correct, by this limit. 10 % lies 1.8 x over the one
#   reading and 1.7 x under the other.
# * ``WORST_FACTOR`` x ``rel_tol``, which no position may pass, does NOT
#   tell precisions apart (worst position of a bfloat16 run: up to 16.3 %
#   of the scale; of a float8 run: 6.5-20.6 %): it is there for a fault
#   that moves few positions and so stays under the share limit, but moves
#   them grossly: a wrong page, mask, table or position makes the served
#   token one the reference puts about the whole scale below its best (the
#   planted faults of tests/unit/perf/test_reference_mellum.py and
#   tests/unit/serving/test_window_groups.py). With only the one reading
#   it stands at three times it: 16 x 2**-5, half the scale.
SHARE_OVER = 0.10
MIN_OVER = 2
WORST_FACTOR = 16.0


def shortfalls(logits_fn, params, prompt, output, context_len: int,
               score_len: int):
    """``(shortfall (n,), scale (n,))`` of the ``n`` generated tokens: the
    reference's best logit at the token's position less its logit of the
    token, and the position's largest |logit|. The sequence is padded to
    ``context_len`` and the scored positions to ``score_len`` (one compiled
    shape; causal attention keeps the padding from reaching earlier
    positions)."""
    import numpy as np

    P, n = len(prompt), len(output)
    seq = np.zeros((context_len,), np.int32)
    seq[:P] = prompt
    seq[P:P + n - 1] = output[:-1]
    positions = np.full((max(score_len, n),), P - 1, np.int32)
    positions[:n] = np.arange(P - 1, P - 1 + n)
    lg = logits_fn(params, seq, positions)[:n]
    chosen = lg[jnp.arange(n), jnp.asarray(np.asarray(output, np.int32))]
    return (np.asarray(lg.max(-1) - chosen),
            np.asarray(jnp.abs(lg).max(-1)))


def verdict(short, scale, rel_tol: float) -> dict:
    """The limits above over one request's positions."""
    import numpy as np

    n = len(short)
    over = int(np.sum(short > rel_tol * scale))
    worst = int(np.argmax(short / scale))
    return {"positions": n, "worst_shortfall": float(short[worst]),
            "scale_there": float(scale[worst]),
            "tolerance_there": float(WORST_FACTOR * rel_tol * scale[worst]),
            "positions_over_rel_tol": over,
            "positions_over_allowed": int(max(MIN_OVER, SHARE_OVER * n)),
            "ok": bool(over <= max(MIN_OVER, SHARE_OVER * n)
                       and np.all(short <= WORST_FACTOR * rel_tol * scale))}


def check_greedy(logits_fn, params, prompt, output, context_len: int,
                 score_len: int, rel_tol: float) -> dict:
    """Run prompt + generated tokens through the reference and hold the
    generated tokens to it (logits, not tokens: with random weights the top
    logits are nearly tied and a rounding flips the argmax): see the limits
    above. ``tolerance_there`` is the limit no position may pass."""
    return verdict(*shortfalls(logits_fn, params, prompt, output,
                               context_len, score_len), rel_tol)
