"""Plain reference forward of GPT-NeoX / Pythia (Black et al. 2022; Biderman
et al. 2023; the public EleutherAI ``pythia-*`` config.json files): no
learned positions; rotary embedding on the first ``rotary_pct`` of each
head's channels (rotate-half form, base 10000); parallel residual,
``x + attn(ln_1 x) + mlp(ln_2 x)``; separate q, k, v projections with bias;
GELU; final LayerNorm; an untied ``lm_head`` without bias. float32
``jax.numpy`` at matmul precision "highest", layers in a Python loop, one
layer's weights cast at a time. Shares no code with
``deepspeed_tpu/models/transformer_lm.py``; reads only its parameter tree.

Departure from the published config, noted: ``hidden_act`` is ``"gelu"``
(the exact erf form) and this reference follows it, while the program's
``gpt-neox`` preset computes the tanh approximation. The two differ by at
most 5e-4 per activation, an order of magnitude below one bf16 rounding of
the same value, so the tolerance of the check does not see it."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _f32(x):
    return x.astype(jnp.float32)


def _layer_norm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * _f32(p["scale"]) + _f32(p["bias"])


def _dense(x, p):
    return x @ _f32(p["kernel"]) + _f32(p["bias"])


def _rotary(x, rotary_dim: int, theta: float):
    """x: (H, T, D). Rotate the first ``rotary_dim`` channels by position."""
    T = x.shape[1]
    inv_freq = theta ** (-jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                         / rotary_dim)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None]
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    half = rotary_dim // 2
    turned = jnp.concatenate([-rot[..., half:], rot[..., :half]], -1)
    return jnp.concatenate([rot * cos + turned * sin, rest], -1)


def make_forward(n_head: int, rotary_pct: float = 0.25,
                 theta: float = 10000.0, eps: float = 1e-5):
    """``logits(params, ids, positions)``: one sequence ``ids`` (T,), the
    logits (len(positions), V) at the given positions."""

    @jax.jit
    def embed(params, ids):
        return _f32(params["embed_tokens"]["embedding"][ids])

    @jax.jit
    def block(blocks, i, x):
        p = jax.tree_util.tree_map(lambda a: a[i], blocks["block"])
        T, C = x.shape
        D = C // n_head
        rd = int(rotary_pct * D) // 2 * 2
        h = _layer_norm(x, p["ln_1"], eps)
        q, k, v = (_dense(h, p["attn"][n]).reshape(T, n_head, D)
                   .transpose(1, 0, 2) for n in ("q_proj", "k_proj",
                                                 "v_proj"))
        q, k = _rotary(q, rd, theta), _rotary(k, rd, theta)
        scores = q @ k.transpose(0, 2, 1) / math.sqrt(D)
        causal = jnp.tril(jnp.ones((T, T), bool))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        att = jax.nn.softmax(scores, axis=-1) @ v
        a = _dense(att.transpose(1, 0, 2).reshape(T, C), p["attn"]["o_proj"])
        h2 = _layer_norm(x, p["ln_2"], eps)
        m = _dense(jax.nn.gelu(_dense(h2, p["mlp"]["up_proj"]),
                               approximate=False), p["mlp"]["down_proj"])
        return x + a + m

    @jax.jit
    def head(params, x, positions):
        x = _layer_norm(x[positions], params["ln_f"], eps)
        return x @ _f32(params["lm_head"]["kernel"])

    def logits(params, ids, positions):
        with jax.default_matmul_precision("highest"):
            x = embed(params, jnp.asarray(ids))
            n_layer = params["blocks"]["block"]["ln_1"]["scale"].shape[0]
            for i in range(n_layer):
                x = block(params["blocks"], jnp.asarray(i, jnp.int32), x)
            return head(params, x, jnp.asarray(positions))

    return logits


def check_greedy(logits_fn, params, prompt, output, context_len: int,
                 score_len: int, rel_tol: float) -> dict:
    """Run prompt + generated tokens through the reference and hold every
    generated token to it: at each generated position the token the server
    chose must have a reference logit within ``rel_tol`` x (largest |logit|
    at that position) of the reference's maximum. Logits, not tokens: with
    random weights the top logits are nearly tied and a rounding flips the
    argmax. The sequence is padded to ``context_len`` and the scored
    positions to ``score_len`` (one compiled shape; causal attention keeps
    the padding from reaching earlier positions)."""
    import numpy as np

    P, n = len(prompt), len(output)
    seq = np.zeros((context_len,), np.int32)
    seq[:P] = prompt
    seq[P:P + n - 1] = output[:-1]
    positions = np.full((max(score_len, n),), P - 1, np.int32)
    positions[:n] = np.arange(P - 1, P - 1 + n)
    lg = logits_fn(params, seq, positions)[:n]
    chosen = lg[jnp.arange(n), jnp.asarray(np.asarray(output, np.int32))]
    short = np.asarray(lg.max(-1) - chosen)
    scale = np.asarray(jnp.abs(lg).max(-1))
    worst = int(np.argmax(short / scale))
    return {"positions": n, "worst_shortfall": float(short[worst]),
            "scale_there": float(scale[worst]),
            "tolerance_there": float(rel_tol * scale[worst]),
            "ok": bool(np.all(short <= rel_tol * scale))}
