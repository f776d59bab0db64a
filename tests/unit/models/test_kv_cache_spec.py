"""KV-cache container spec: the int32 sublane packing (4 head-dim rows
per word) only exists for head_dim % 4 == 0 — explicit opt-in must fail
loudly, auto mode must fall back to the plain int8 container with a
one-time warning."""

import dataclasses

import jax.numpy as jnp
import pytest

from deepspeed_tpu.models.kv_cache_spec import (_PACK_DISABLED_WARNED,
                                                kv_cache_spec)
from deepspeed_tpu.models.lm_config import TransformerConfig

# n_embd=30 / n_head=2 -> head_dim=15, not a multiple of 4
ODD = dict(vocab_size=64, max_seq_len=16, n_embd=30, n_layer=1, n_head=2,
           dtype=jnp.float32, kv_cache_quant=True)


def test_packed_explicit_raises_on_odd_head_dim():
    cfg = TransformerConfig(**ODD, kv_cache_packed=True)
    with pytest.raises(ValueError, match="head_dim % 4"):
        kv_cache_spec(cfg)


def test_packed_auto_falls_back_with_one_warning():
    cfg = TransformerConfig(**ODD, kv_cache_packed=None)
    _PACK_DISABLED_WARNED.discard(cfg.head_dim)
    dtype, cache_d, packed = kv_cache_spec(cfg)
    assert (dtype, cache_d, packed) == (jnp.int8, 15, False)
    assert cfg.head_dim in _PACK_DISABLED_WARNED  # warned this call...
    dtype2, _, _ = kv_cache_spec(cfg)  # ...and only once (set-gated)
    assert dtype2 == jnp.int8


def test_packed_auto_engages_on_aligned_head_dim():
    cfg = TransformerConfig(**{**ODD, "n_embd": 32},  # head_dim 16
                            kv_cache_packed=None)
    dtype, cache_d, packed = kv_cache_spec(cfg)
    assert packed and dtype == jnp.int32 and cache_d == 4

    off = dataclasses.replace(cfg, kv_cache_packed=False)
    dtype, cache_d, packed = kv_cache_spec(off)
    assert (dtype, cache_d, packed) == (jnp.int8, 16, False)


@pytest.mark.parametrize("routed", [False, True])
def test_remat_hands_a_block_its_layer_and_its_experts(routed):
    """``_ScanBlock`` names ``TransformerBlock``'s static arguments to
    ``nn.remat`` by POSITION (decode, deterministic) and passes the scan's
    counter and the expert leaves after the cache: with ``remat`` on, a
    model of layer kinds (and a routed FFN) gives the logits and the decode
    step of the same model without it, to the bit."""
    import jax
    import numpy as np

    from deepspeed_tpu.models.lm_config import transformer_config
    from deepspeed_tpu.models.transformer_lm import TransformerLM

    sizes = dict(vocab_size=128, max_seq_len=64, n_embd=32, n_layer=4,
                 n_head=4, n_kv_head=2, head_size=8, ffn_dim=16,
                 layer_types=["sliding_attention", "full_attention"] * 2,
                 sliding_window=16, dtype=jnp.float32,
                 **(dict(n_experts=4, experts_per_token=2) if routed else {}))
    ids = jnp.asarray(np.random.default_rng(0).integers(1, 128, (2, 12)),
                      jnp.int32)
    outs = []
    for remat in (False, True):
        model = TransformerLM(transformer_config("mellum", remat=remat,
                                                 **sizes))
        def run(model=model):
            params = model.init(jax.random.PRNGKey(0), ids,
                                method=model.logits)["params"]
            logits = model.apply({"params": params}, ids,
                                 method=model.logits)
            _, cache = model.apply({"params": params}, ids,
                                   method=model.prefill, mutable=["cache"])
            step, _ = model.apply(
                {"params": params, "cache": cache["cache"]}, ids[:, :1],
                jnp.asarray(12), method=model.decode, mutable=["cache"])
            return logits, step

        logits, step = jax.jit(run)()    # (one program, not op by op)
        outs.append((np.asarray(logits), np.asarray(step)))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_array_equal(outs[0][1], outs[1][1])


GRANITE_PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


def test_a_state_group_beside_kv_at_the_published_widths():
    """Granite-4.0-H-Micro's cache (PR 47), as shapes alone
    (``jax.eval_shape``: nothing is allocated): a state group over the 36
    Mamba layers, ``s`` float32 in whole (128, 128) tiles of two heads and
    ``conv`` bfloat16, the last 3 inputs of 4,352 channels time-major, a
    row a slot; K/V over the 4 attention layers, paged or contiguous; and
    a slot's state over the layers to the byte."""
    import jax

    from deepspeed_tpu.models.lm_config import transformer_config
    from deepspeed_tpu.models.transformer_lm import TransformerLM

    cfg = transformer_config(
        "granite-hybrid", vocab_size=100352, max_seq_len=16384, n_embd=2048,
        n_layer=40, n_head=32, n_kv_head=8, ffn_dim=8192,
        layer_types=GRANITE_PERIOD * 4, mamba_n_heads=64, mamba_d_head=64,
        mamba_d_state=128)
    spec = TransformerLM(cfg).kv_cache_spec()
    assert spec.n_layer == 40 and spec.kv_layers == 4
    assert spec.kinds == ("ssm",)
    assert spec.state_group == (36, (
        ("s", (32, 128, 128), jnp.float32),
        ("conv", (3 * 4352,), jnp.bfloat16)))
    assert spec.state_leaves == ("s", "conv") and spec.state is None
    assert spec.state_bytes_per_row == 76_437_504 \
        == 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    paged = jax.eval_shape(lambda: spec.paged_cache(1536, 128, num_slots=64))
    shapes = {key: (leaf.shape, leaf.dtype.name)
              for key, leaf in paged.items()}
    assert shapes == {
        "s": ((36, 64, 32, 128, 128), "float32"),
        "conv": ((36, 64, 13056), "bfloat16"),
        "k": ((4, 1536, 8, 64, 128), "bfloat16"),
        "v": ((4, 1536, 8, 64, 128), "bfloat16")}
    rows = jax.eval_shape(lambda: spec.stacked_cache(2))
    assert rows["s"].shape == (36, 2, 32, 128, 128)
    assert rows["conv"].shape == (36, 2, 13056)
    assert rows["k"].shape == rows["v"].shape == (4, 2, 8, 64, 16384)
    # a K/V model's spec names no state leaf, a retention model's one
    plain = TransformerLM(TransformerConfig(
        vocab_size=64, max_seq_len=16, n_embd=32, n_layer=1, n_head=2))
    assert plain.kv_cache_spec().state_leaves == ()
    assert plain.kv_cache_spec().state_bytes_per_row == 0
    assert plain.kv_cache_spec().kv_layers == 1
    # the one description of a state group: every layer of a retention
    # model, one leaf, no layer left to keep K/V
    kept = TransformerLM(transformer_config(
        "brumby", vocab_size=128, max_seq_len=128, n_embd=64, n_layer=2,
        n_head=4, n_kv_head=2, head_size=16, ffn_dim=96)).kv_cache_spec()
    assert kept.state_group == (2, (("s", (2, 10, 16, 16), jnp.float32),))
    assert kept.state == (10, 16, 16) and kept.state_leaves == ("s",)
    assert (kept.n_layer, kept.kv_layers) == (2, 0)
    assert kept.state_bytes_per_row == 2 * 2 * 10 * 16 * 16 * 4


@pytest.mark.parametrize("layer_types,why", [
    (["mamba"] * 4, "ONE attention layer"),
    # (PR 56) a pattern without a period is a list of runs
    (["mamba", "attention", "attention", "mamba"],
     (("mamba", 0, 1), ("attention", 0, 2), ("mamba", 1, 1))),
    (["attention", "mamba", "mamba", "attention"],
     (("attention", 0, 1), ("mamba", 0, 2), ("attention", 1, 1))),
    (["mamba", "full_attention"] * 2, "ONE attention layer"),
    (["attention"] * 4, "ONE attention layer"),
])
def test_mamba_and_attention_layers_come_as_a_pattern_or_as_runs(layer_types,
                                                                 why):
    from deepspeed_tpu.models.lm_config import transformer_config

    def build(**more):
        return transformer_config(
            "granite-hybrid", vocab_size=64, max_seq_len=16, n_embd=32,
            n_layer=4, n_head=2, layer_types=layer_types, mamba_n_heads=4,
            mamba_d_head=8, mamba_d_state=8, **more)

    if isinstance(why, str):
        with pytest.raises(ValueError, match=why):
            build()
        return
    cfg = build()
    assert not cfg.hybrid_repeats and cfg.hybrid_runs == why
    # a routed FFN counts its layers period by period
    with pytest.raises(ValueError, match="period by period"):
        build(ffn_dim=8, n_experts=4, experts_per_token=2)


def test_mamba_layers_need_their_widths():
    from deepspeed_tpu.models.lm_config import transformer_config

    with pytest.raises(ValueError, match="mamba_n_heads"):
        transformer_config(
            "granite-hybrid", vocab_size=64, max_seq_len=16, n_embd=32,
            n_layer=2, n_head=2, layer_types=["mamba", "attention"])
    cfg = transformer_config(
        "granite-hybrid", vocab_size=64, max_seq_len=16, n_embd=32,
        n_layer=10, n_head=2, layer_types=GRANITE_PERIOD, mamba_n_heads=4,
        mamba_d_head=8, mamba_d_state=8)
    assert cfg.hybrid_period == (5, 4, 1) and cfg.mamba_channels == 48


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("rows", [
    [3],                        # one entry: the scatter itself
    [5, 0],                     # a bucket of two
    [2, -1, 6, 7],              # one that does not run (-1)
    [8, 1, 9, 4],               # out of [0, R) above: 8 is R, 9 past it
    [7, 6, 5, 4, 3, 2, 1, 0],   # every row, as a decode step names them
    [2, -1, 6, 8, 7, 9, -1, 0, 4, 1],   # the slab's form, with rows that
                                        # do not run (-1, R, past R)
    [-1, 8, -1],                # none runs: the leaf comes back as it was
    [-1, 8, -1, 9, -1, 8, -1, 9],       # and in the slab's form
])
def test_a_state_layers_tail_write_is_the_scatter_it_replaced(rows, dtype):
    """``_write_rows`` with ``_SLAB_FROM`` entries or more goes through the
    layer's whole slab (a select and one update); until PR 50
    ``Mamba2Mixer`` wrote its tail with ``leaf.at[layer, rows].set(values,
    mode="drop")``. Same leaf, bit for bit, rows that do not run
    included."""
    import jax
    import numpy as np

    from deepspeed_tpu.models.state_layers import _SLAB_FROM, _write_rows

    assert _SLAB_FROM == 8      # (the cases above stand on both sides of it)
    L, R, W = 3, 8, 24
    k1, k2 = jax.random.split(jax.random.PRNGKey(len(rows)))
    leaf = jax.random.normal(k1, (L, R, W), jnp.float32).astype(dtype)
    values = jax.random.normal(k2, (len(rows), W), jnp.float32)
    rows = jnp.asarray(rows, jnp.int32)
    for layer in (0, L - 1):
        at = (layer, jnp.where((rows >= 0) & (rows < R), rows, R))
        scatter = leaf.at[at].set(values.astype(dtype), mode="drop")
        got = jax.jit(_write_rows)(leaf, jnp.int32(layer), rows, values)
        assert got.dtype == leaf.dtype
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.asarray(scatter, np.float32))
        ran = [int(r) for r in rows if 0 <= r < R]
        untouched = np.ones((L, R), bool)
        untouched[layer, ran] = False
        np.testing.assert_array_equal(
            np.asarray(got, np.float32)[untouched],
            np.asarray(leaf, np.float32)[untouched])


# the modules a layer kind is written in stand BELOW transformer_lm.py: none
# imports it, at its top or on the way (ROADMAP.md, D14)
BELOW = ["lm_config", "cache_kinds", "kv_cache_spec", "lm_parts",
         "attention_layers", "state_layers", "lightning_sparse"]


@pytest.fixture(scope="module")
def imported_with():
    """``{module: the deepspeed_tpu.models modules its import loaded}``,
    taken in ONE fresh interpreter (whose cost is importing jax and flax):
    the package's models are forgotten between one import and the next."""
    import json
    import os
    import subprocess
    import sys

    code = (
        "import importlib, json, sys\n"
        "out = {}\n"
        f"for name in {BELOW!r}:\n"
        "    importlib.import_module('deepspeed_tpu.models.' + name)\n"
        "    mine = [m for m in sys.modules\n"
        "            if m.startswith('deepspeed_tpu.models.')]\n"
        "    out[name] = [m.rpartition('.')[2] for m in mine]\n"
        "    for m in mine:\n"
        "        del sys.modules[m]\n"
        "print(json.dumps(out))\n")
    root = os.path.join(os.path.dirname(__file__), "..", "..", "..")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300, cwd=root)
    assert proc.returncode == 0, proc.stderr
    return json.loads(proc.stdout.splitlines()[-1])


@pytest.mark.parametrize("module", BELOW)
def test_a_layer_kinds_module_does_not_import_transformer_lm(imported_with,
                                                             module):
    loaded = imported_with[module]
    assert module in loaded and "transformer_lm" not in loaded, loaded
