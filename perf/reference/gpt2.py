"""Plain reference forward of GPT-2 (Radford et al. 2019; the public
``gpt2*`` config.json files): learned positions, pre-LayerNorm blocks,
fused qkv projection split in thirds, causal softmax attention scaled by
1/sqrt(head), tanh-approximated GELU (``gelu_new``), final LayerNorm, head
tied to the token embedding. float32 ``jax.numpy`` at matmul precision
"highest"; no kernel, no cache, no scan: the layers are walked in a Python
loop and one layer's weights are cast to float32 at a time, so the check
costs one layer of float32 and not a model. Shares no code with
``deepspeed_tpu/models/gpt2.py``; reads only its parameter tree
(``wte``, ``wpe``, ``blocks/block/{ln_1,attn/{qkv,proj},ln_2,mlp/{fc,proj}}``
stacked on a leading layer axis, ``ln_f``)."""

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


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def make_forward(n_head: int, eps: float = 1e-5):
    """``logits(params, ids)`` for one sequence ``ids`` (T,) -> (T, V)."""

    @jax.jit
    def embed(params, ids):
        T = ids.shape[0]
        return _f32(params["wte"]["embedding"][ids]) + \
            _f32(params["wpe"]["embedding"][:T])

    @jax.jit
    def block(blocks, i, x):
        p = jax.tree_util.tree_map(lambda a: a[i], blocks["block"])
        T, C = x.shape
        D = C // n_head
        h = _layer_norm(x, p["ln_1"], eps)
        q, k, v = jnp.split(_dense(h, p["attn"]["qkv"]), 3, axis=-1)
        q, k, v = (a.reshape(T, n_head, D).transpose(1, 0, 2)
                   for a in (q, k, v))
        scores = q @ k.transpose(0, 2, 1) / math.sqrt(D)
        causal = jnp.tril(jnp.ones((T, T), bool))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        att = jax.nn.softmax(scores, axis=-1) @ v
        x = x + _dense(att.transpose(1, 0, 2).reshape(T, C),
                       p["attn"]["proj"])
        h = _layer_norm(x, p["ln_2"], eps)
        return x + _dense(_gelu_tanh(_dense(h, p["mlp"]["fc"])),
                          p["mlp"]["proj"])

    @jax.jit
    def head(params, x):
        x = _layer_norm(x, params["ln_f"], eps)
        return x @ _f32(params["wte"]["embedding"]).T

    def logits(params, ids):
        with jax.default_matmul_precision("highest"):
            x = embed(params, ids)
            n_layer = params["blocks"]["block"]["ln_1"]["scale"].shape[0]
            for i in range(n_layer):
                x = block(params["blocks"], jnp.asarray(i, jnp.int32), x)
            return head(params, x)

    return logits


def greedy_labels_and_loss(logits_fn, params, ids):
    """For a batch ``ids`` (B, T): labels whose position t+1 is the
    reference's own most likely next token after position t, and the
    reference's mean loss on those labels, mean over (lse - max logit).

    Why these labels: with random weights the loss on ANY fixed labels
    sits at ln V + 0.5 whatever the model computes, so a wrong mask or a
    dropped layer would pass a loss comparison. On the reference's own
    argmax the loss is low only for a model whose logits agree with the
    reference's at every position."""
    import numpy as np

    labels = np.zeros(ids.shape, np.int32)
    losses = []
    for b in range(ids.shape[0]):
        lg = logits_fn(params, jnp.asarray(ids[b]))[:-1]
        top = jnp.argmax(lg, axis=-1)
        nll = jax.nn.logsumexp(lg, axis=-1) - jnp.max(lg, axis=-1)
        labels[b, 1:] = np.asarray(top)
        losses.append(float(nll.mean()))
    return labels, float(np.mean(losses))
