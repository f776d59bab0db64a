"""The plain reference of MiniCPM-SALA (``perf/reference/minicpm_sala.py``,
PR 56) held to its own equations at a toy size, on a parameter tree written
here by hand (the reference shares no code with the program; the program is
held to the reference in ``tests/unit/models/test_lightning_sparse.py``):
causality in both layer kinds, the Lightning scan against its closed form,
the choice's rules, "every block chosen" equal to dense attention, padding,
and the limits of ``check_greedy``."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf.reference import minicpm_sala as ref  # noqa: E402

V, C, H, KV, D, F, T = 61, 24, 4, 2, 6, 40, 48
SPARSE = dict(kernel_size=2, kernel_stride=1, block_size=4, init_blocks=1,
              window_size=8, topk=2, dense_len=16)
TYPES = ("attention", "lightning", "attention")


def _tree(rng, layers, mixer, kv):
    def w(*shape, scale=None):
        return jnp.asarray(rng.standard_normal((layers,) + shape)
                           * (scale or shape[0] ** -0.5), jnp.float32)

    def ones(n):
        return jnp.asarray(1 + 0.2 * rng.standard_normal((layers, n)),
                           jnp.float32)

    mix = {"q_proj": {"kernel": w(C, H * D)}, "k_proj": {"kernel": w(C, kv * D)},
           "v_proj": {"kernel": w(C, kv * D)}, "o_proj": {"kernel": w(H * D, C)},
           "z_proj": {"kernel": w(C, H * D)}, "q_norm": {"scale": ones(D)},
           "k_norm": {"scale": ones(D)}}
    if mixer == "lightning":
        mix["o_norm"] = {"scale": ones(D)}
    return {"block": {
        "ln_1": {"scale": ones(C)}, "ln_2": {"scale": ones(C)}, mixer: mix,
        "mlp": {"gate_proj": {"kernel": w(C, F)}, "up_proj": {"kernel": w(C, F)},
                "down_proj": {"kernel": w(F, C)}}}}


@pytest.fixture(scope="module")
def params():
    rng = np.random.default_rng(0)
    return {"embed_tokens": {"embedding": jnp.asarray(
                rng.standard_normal((V, C)) * 0.3, jnp.float32)},
            "attn_blocks": _tree(rng, 2, "attn", KV),
            "lightning_blocks": _tree(rng, 1, "lightning", H),
            "ln_f": {"scale": jnp.ones((C,), jnp.float32)},
            "lm_head": {"kernel": jnp.asarray(
                rng.standard_normal((C, V)) * C ** -0.5, jnp.float32)}}


def _forward(types=TYPES, **sparse):
    return ref.make_forward(types, H, KV, D, 10000.0,
                            tuple(sorted({**SPARSE, **sparse}.items())),
                            12.0, 1.4 / 32 ** 0.5, 4.0)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(1).integers(0, V, T).astype(np.int32)


@pytest.fixture(scope="module")
def logits(params, ids):
    return np.asarray(_forward()(params, ids, np.arange(T)))


def test_a_later_token_reaches_no_earlier_position(params, ids, logits):
    other = ids.copy()
    other[30] = (other[30] + 1) % V
    moved = np.asarray(_forward()(params, other, np.arange(T)))
    np.testing.assert_array_equal(moved[:30], logits[:30])
    assert np.abs(moved[30:] - logits[30:]).max() > 1e-3
    # and the sequence's length (the harness pads it) reaches none either
    short = np.asarray(_forward()(params, ids[:32], np.arange(32)))
    np.testing.assert_allclose(short, logits[:32], atol=1e-5)


def test_the_lightning_scan_is_its_closed_form(params, ids):
    """``o_t = sum_{s <= t} l^(t - s) (q_t . k_s) v_s / sqrt(d)`` for a
    model of the one Lightning layer, against the scan, through the
    logits: the closed form here in float64 with the same projections."""
    only = _forward(types=("lightning",))
    got = np.asarray(only.hidden(params, ids, np.arange(T)))
    p = {k: np.asarray(v[0], np.float64) for k, v in {
        **{f"{n}": params["lightning_blocks"]["block"]["lightning"][n][
            "kernel" if "proj" in n else "scale"]
           for n in ("q_proj", "k_proj", "v_proj", "o_proj", "z_proj",
                     "q_norm", "k_norm", "o_norm")},
        "ln_1": params["lightning_blocks"]["block"]["ln_1"]["scale"],
        "ln_2": params["lightning_blocks"]["block"]["ln_2"]["scale"],
        **{n: params["lightning_blocks"]["block"]["mlp"][n]["kernel"]
           for n in ("gate_proj", "up_proj", "down_proj")}}.items()}

    def norm(x, w):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * w

    def rotary(x):
        inv = 10000.0 ** (-np.arange(0, D, 2) / D)
        ang = np.arange(T)[:, None] * inv
        cos, sin = (np.concatenate([f(ang)] * 2, -1)[:, None]
                    for f in (np.cos, np.sin))
        turned = np.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
        return x * cos + turned * sin

    r = 1.4 / 32 ** 0.5
    x = 12.0 * np.asarray(params["embed_tokens"]["embedding"],
                          np.float64)[ids]
    u = norm(x, p["ln_1"])
    q = rotary(norm((u @ p["q_proj"]).reshape(T, H, D), p["q_norm"]))
    k = rotary(norm((u @ p["k_proj"]).reshape(T, H, D), p["k_norm"]))
    v = (u @ p["v_proj"]).reshape(T, H, D)
    decay = np.exp(-2.0 ** (-8.0 * np.arange(1, H + 1) / H))
    t = np.arange(T)
    weight = np.where(t[:, None] >= t[None, :],
                      decay[:, None, None] ** (t[:, None] - t[None, :]), 0.0)
    o = np.einsum("hts,thd,shd,she->the", weight, q, k, v) / np.sqrt(D)
    o = norm(o, p["o_norm"]).reshape(T, H * D)
    o = o / (1 + np.exp(-(u @ p["z_proj"])))
    x = x + r * (o @ p["o_proj"])
    h = norm(x, p["ln_2"])
    g = h @ p["gate_proj"]
    x = x + r * ((g / (1 + np.exp(-g)) * (h @ p["up_proj"]))
                 @ p["down_proj"])
    np.testing.assert_allclose(got, norm(x, 1.0), atol=2e-5)


def test_the_choice_follows_its_rules(params, ids):
    first, second = (np.asarray(c) for c in _forward().chosen(params, ids))
    assert first.shape == second.shape == (T, KV, T // 4)
    for chosen in (first, second):
        # under dense_len every block; from there on block 0, and topk in
        # all, none of which meets the window [i - 7, i]
        assert chosen[:15].all()
        assert (chosen[15:].sum(-1) == 2).all() and chosen[15:, :, 0].all()
        for i in range(15, T):
            assert not chosen[i, :, (i - 7) // 4:].any(), i
    # the two KV heads choose for themselves
    assert (first[15:, 0] != first[15:, 1]).any()
    # with fewer finite blocks than topk, all of them: block 0 and the
    # one other block that lies before the window at position 16
    many = np.asarray(_forward(topk=5).chosen(params, ids)[0])
    assert many[16].sum(-1).tolist() == [2, 2]
    assert (many[40].sum(-1) == 5).all()


def test_every_block_chosen_is_dense_attention_where_the_window_is_whole_blocks(
        params, ids, logits):
    """``topk`` as many as there are blocks, in a model of ONE sparse
    layer: a query whose window starts on a block's edge (``i % 4 == 3``:
    the window ``[i - 7, i]`` is two whole blocks) reads every earlier
    token, which is what ``dense_len`` past the sequence reads. Any other
    query loses the head of the block its window starts in (a block that
    meets the window is never chosen: the equations', not an accident),
    and another ``topk`` reads fewer still."""
    one = ("attention",)
    at = np.arange(T)
    dense = np.asarray(_forward(one, dense_len=10 ** 6)(params, ids, at))
    every = np.asarray(_forward(one, topk=T // 4)(params, ids, at))
    whole = (at % 4 == 3) | (at < 15)
    np.testing.assert_allclose(every[whole], dense[whole], atol=1e-5)
    assert (np.abs(every[~whole] - dense[~whole]).max(-1) > 1e-6).all()
    few = np.asarray(_forward(one)(params, ids, at))
    np.testing.assert_array_equal(few[:15], dense[:15])
    assert np.abs(few[15:] - dense[15:]).max() > 1e-3


def test_shortfalls_and_the_verdicts_limits(params, ids):
    fn = _forward()
    prompt, out = ids[:20].tolist(), []
    for _ in range(6):
        seq = np.asarray(prompt + out, np.int32)
        pad = np.concatenate([seq, np.zeros(-len(seq) % 4, np.int32)])
        out.append(int(np.argmax(np.asarray(
            fn(params, pad, np.asarray([len(seq) - 1]))[0]))))
    got = ref.check_greedy(fn, params, prompt, out, 64, 8, 2.0 ** -5)
    assert got["ok"] and got["positions"] == 6
    assert got["worst_shortfall"] == pytest.approx(0.0, abs=1e-6)
    short, scale = ref.shortfalls(fn, params, prompt, out, 64, 8)
    assert short.shape == scale.shape == (6,) and (scale > 0).all()
    wrong = list(out)
    wrong[2] = (wrong[2] + 7) % V
    assert ref.shortfalls(fn, params, prompt, wrong, 64, 8)[0][2] > 0
    # the limits: a share of the positions may pass rel_tol, none may pass
    # WORST_FACTOR times it
    scale = np.ones(100)
    over = np.where(np.arange(100) < 5, 0.04, 0.0)
    assert ref.verdict(over, scale, 2.0 ** -5)["ok"]
    assert not ref.verdict(np.where(np.arange(100) < 6, 0.04, 0.0), scale,
                           2.0 ** -5)["ok"]
    assert not ref.verdict(np.where(np.arange(100) < 1, 0.2, 0.0), scale,
                           2.0 ** -5)["ok"]
    assert (ref.SHARE_OVER, ref.MIN_OVER, ref.WORST_FACTOR) == (0.05, 2, 4.0)
