"""What can be known on the CPU about the path to the chip.

(a) every Pallas kernel, with interpret mode forced off, lowers for the TPU
    platform to at least one ``tpu_custom_call`` (Mosaic); (b) importing the
    package initialises no JAX backend, so a parent process that imports it
    holds no chip; (c) the compile cache is placed by
    ``JAX_COMPILATION_CACHE_DIR`` or at ``<checkout>/.jax_cache``, nowhere
    else; (d) ``chip_smoke.py`` fails without a TPU and fails when a phase
    raises. Whether Mosaic compiles the kernels and the programs fit the
    device is what ``chip_smoke.py`` itself establishes on the chip.
"""

import contextlib
import functools
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import backend

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
SMOKE = os.path.join(REPO, "chip_smoke.py")


# ---------------------------------------------------------------------------
# (a) kernels lower to Mosaic custom calls
# ---------------------------------------------------------------------------
@pytest.fixture
def compiled_kernels(monkeypatch):
    """Interpret mode off, as on the chip (one switch: ops/backend.py)."""
    monkeypatch.setattr(backend, "pallas_interpret", lambda: False)


def _tpu_custom_calls(fn, *args) -> int:
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    return lowered.as_text().count("tpu_custom_call")


def _rand(shape, dtype=jnp.bfloat16, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(shape), dtype)


@pytest.mark.parametrize("seq,head_dim,causal", [
    (1024, 64, True), (1024, 128, True), (2048, 128, True), (1024, 64, False)])
def test_flash_forward_and_backward_lower_for_tpu(compiled_kernels, seq,
                                                  head_dim, causal):
    from deepspeed_tpu.ops.attention.flash_attention import flash_attention

    q = _rand((1, seq, 2, head_dim))
    fwd = _tpu_custom_calls(
        lambda q, k, v: flash_attention(q, k, v, causal=causal), q, q, q)
    assert fwd == 1

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=causal).astype(
            jnp.float32).sum()

    both = _tpu_custom_calls(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert both == 3       # flash_fwd, flash_bwd_dq, flash_bwd_dkv


def _paged_operands(page_size, rows=1, quant=None):
    from deepspeed_tpu.ops.attention.decode_attention import (
        pack_int8_sublanes, quantize_kv_rows)

    B, H, KV, D, P, per_slot = 2, 4, 2, 128, 8, 4
    q = _rand((B, rows, H, D))
    pages = _rand((P, KV, D, page_size), seed=1)
    table = jnp.arange(B * per_slot, dtype=jnp.int32).reshape(B, per_slot)
    starts = jnp.asarray([page_size + 3, 5], jnp.int32)
    kwargs = {}
    if quant:
        # quantize rows (positions-major view), store positions-minor
        vals, scales = quantize_kv_rows(pages.transpose(0, 1, 3, 2))
        pages = vals.transpose(0, 1, 3, 2)
        if quant == "packed":
            pages = pack_int8_sublanes(pages)
        kwargs = dict(k_scale_pages=scales, v_scale_pages=scales)
    return (q, pages, pages, table, starts), kwargs


@pytest.mark.parametrize("page_size", [64, 128])
@pytest.mark.parametrize("rows,quant", [(1, None), (1, "int8"),
                                        (1, "packed"), (5, None)])
def test_paged_attention_lowers_for_tpu(compiled_kernels, page_size, rows,
                                        quant):
    from deepspeed_tpu.ops.attention.paged_attention import (
        paged_decode_attention)

    args, kwargs = _paged_operands(page_size, rows, quant)
    assert _tpu_custom_calls(
        lambda *a: paged_decode_attention(*a, **kwargs), *args) >= 1


@pytest.mark.parametrize("page_size", [64, 128])
@pytest.mark.parametrize("tier", ["bf16", "int8", "packed", "scale"])
def test_paged_write_lowers_for_tpu(compiled_kernels, page_size, tier):
    """The pool's write kernel, both of its callers: a step's columns into
    one layer, runs of a dense cache into every layer."""
    from deepspeed_tpu.ops.attention.paged_attention import (
        paged_write_columns, paged_write_runs)

    L, P, KV, B, per_slot = 2, 8, 2, 2, 4
    dtype, Dc = {"bf16": (jnp.bfloat16, 128), "int8": (jnp.int8, 128),
                 "packed": (jnp.int32, 32), "scale": (jnp.float32, None)}[tier]
    mid = (KV,) if Dc is None else (KV, Dc)
    leaf = jnp.zeros((L, P) + mid + (page_size,), dtype)
    table = jnp.arange(B * per_slot, dtype=jnp.int32).reshape(B, per_slot)
    starts = jnp.asarray([page_size - 2, 5], jnp.int32)
    cols = jnp.zeros((B,) + mid + (5,), dtype)
    assert _tpu_custom_calls(paged_write_columns, leaf,
                             jnp.asarray(1, jnp.int32), cols, table,
                             starts) == 1
    dense = jnp.zeros((L, B) + mid + (per_slot * page_size,), dtype)
    assert _tpu_custom_calls(
        lambda *a: paged_write_runs(*a, 64), leaf, dense, table,
        starts) == 1


@pytest.fixture(scope="module")
def described_v5e():
    """One chip of a v5e that is described and not attached: the TPU's own
    compiler, no device. Made inside a fixture, never at import (only one
    process may hold libtpu; see the on-chip-measurement guide)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                               # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _compile_cache_off():
    """A compile for a described chip cannot be read back from the
    persistent cache and warns when it tries: off around such compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


def test_pool_kernels_compile_for_a_described_v5e_at_the_served_shape(
        compiled_kernels, described_v5e):
    """Mosaic itself, which the lowering above does not reach: it refused
    the write's first form ("Rotate with non-32-bit data": bf16 and int8
    columns are rotated as 32-bit words since), and a block that breaks the
    tiling or the VMEM limit fails only here. Pythia-1.4B's pool (24 layers,
    256 pages of 64 in 128 lanes, 16 heads of 128, 64 slots), each K/V
    tier; and XLA around it: stored in whole lane tiles the leaf goes in
    and comes out in one buffer, stored 64 wide it is copied whole to
    row-major and back."""
    from deepspeed_tpu.models.kv_cache_spec import page_lanes
    from deepspeed_tpu.ops.attention.paged_attention import (
        paged_decode_attention, paged_write_columns, paged_write_runs)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=described_v5e)

    L, P, KV, ps, B, per_slot = 24, 256, 16, 64, 64, 32
    lanes = page_lanes(ps)
    table, starts = shape((B, per_slot), jnp.int32), shape((B,), jnp.int32)
    layer = shape((), jnp.int32)

    def write(leaf, cols):
        return jax.jit(lambda *a: paged_write_columns(*a, page_size=ps),
                       donate_argnums=0).lower(leaf, layer, cols, table,
                                               starts).compile()

    with _compile_cache_off():
        for dtype, Dc in ((jnp.bfloat16, 128), (jnp.int8, 128),
                          (jnp.int32, 32)):
            leaf = shape((L, P, KV, Dc, lanes), dtype)
            compiled = write(leaf, shape((B, KV, Dc, 8), dtype))
            text = compiled.as_text()
            assert "input_output_alias={ {}: (0, {}, may-alias) }" in text
            assert text.count("tpu_custom_call") >= 1
            assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 24
            jax.jit(lambda *a: paged_write_runs(*a, 64, page_size=ps),
                    donate_argnums=0).lower(
                leaf, shape((L, 1, KV, Dc, per_slot * ps), dtype),
                shape((1, per_slot), jnp.int32),
                shape((1,), jnp.int32)).compile()
            scales = {} if dtype == jnp.bfloat16 else dict(
                k_scale_pages=shape((L, P, KV, lanes), jnp.float32),
                v_scale_pages=shape((L, P, KV, lanes), jnp.float32))
            jax.jit(lambda q, k, v, t, s, li, **kw: paged_decode_attention(
                q, k, v, t, s, layer=li, page_size=ps, **kw)).lower(
                shape((B, 1, 16, 128), jnp.bfloat16), leaf, leaf, table,
                starts, layer, **scales).compile()
        # why the lanes: the same call on a 64-wide bf16 leaf
        narrow = write(shape((L, P, KV, 128, ps), jnp.bfloat16),
                       shape((B, KV, 128, 8), jnp.bfloat16))
        assert narrow.memory_analysis().temp_size_in_bytes > 2 ** 30


def test_mellum_kernels_compile_for_a_described_v5e_at_the_served_shape(
        compiled_kernels, described_v5e):
    """The kernels PR 30 brought, through Mosaic at the widths of
    ``perf/configs/mellum2-12b-a2b5-paged.json``: the expert products over
    the stacked leaves (8 x 64 experts of 2304 x 896: whole (C, F) blocks
    need the raised VMEM limit, which only this compile checks) for a
    chunk's 128 rows and a decode step's 64, with no copy of a leaf around
    them; and the window group's write and read (6 layers, 576 pages of
    128, a grid that may have no step)."""
    from deepspeed_tpu.moe.routed_ffn import routed_ffn
    from deepspeed_tpu.ops.attention.paged_attention import (
        paged_decode_attention, paged_write_columns)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=described_v5e)

    bf16 = jnp.bfloat16
    L, E, C, F = 8, 64, 2304, 896
    with _compile_cache_off():
        for rows in (128, 64):
            compiled = jax.jit(lambda h, r, g, u, d, li: routed_ffn(
                h, r, g, u, d, li, k=8, norm_topk_prob=True)).lower(
                shape((rows, C), bf16), shape((C, E), jnp.float32),
                shape((L, E, C, F), bf16), shape((L, E, C, F), bf16),
                shape((L, E, F, C), bf16), shape((), jnp.int32)).compile()
            text = compiled.as_text()
            assert "moe_gate_up" in text and "moe_down" in text
            # one layer's experts are 0.79 GB: a slice of a leaf would show
            assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 26
        B, H, KV, D, ps, per_slot, P = 64, 32, 4, 128, 128, 64, 576
        leaf = shape((6, P, KV, D, ps), bf16)

        def step(q, k, v, table, starts, layer, active, cols):
            k = paged_write_columns(k, layer, cols, table, starts,
                                    page_size=ps, active=active)
            return paged_decode_attention(
                q, k, v, table, starts, layer=layer, page_size=ps,
                window=1024, active=active), k

        compiled = jax.jit(step, donate_argnums=1).lower(
            shape((B, 1, H, D), bf16), leaf, leaf,
            shape((B, per_slot), jnp.int32), shape((B,), jnp.int32),
            shape((), jnp.int32), shape((), jnp.bool_),
            shape((B, KV, D, 1), bf16)).compile()
        assert compiled.as_text().count("tpu_custom_call") >= 2
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 24


def test_retention_kernels_compile_for_a_described_v5e_at_the_served_shape(
        compiled_kernels, described_v5e):
    """The kernels PR 32 brought, through Mosaic at the widths of
    ``perf/configs/brumby-14b-retention.json``: 16 rows' tokens through
    ``retention_decode`` and one row's 128-token chunk through
    ``retention_chunk`` on the pool's stacked leaf (8 layers x 16 slots x 8
    KV heads of (66, 128, 128) float32, 4.43 GB). A block in and one out,
    double-buffered, pass the default scoped VMEM limit, and a lane rotation
    wants whole 128-lane rows: both fail only here. The leaf goes in and
    comes out in one buffer: no operation of the program copies it. And a
    leaf small enough for the chip's fast memory stays in HBM."""
    from deepspeed_tpu.ops.attention import power_retention as pr

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=described_v5e)

    bf16, i32 = jnp.bfloat16, jnp.int32
    L, R, KV, H, d = 8, 16, 8, 40, 128
    leaf = shape((L, R, KV) + pr.state_shape(d))
    with _compile_cache_off():
        for name, fn, B, tokens in (("retention_decode", pr.retention_decode,
                                     R, ()),
                                    ("retention_chunk", pr.retention_chunk,
                                     1, (128,))):
            compiled = jax.jit(fn, donate_argnums=4).lower(
                shape((B,) + tokens + (H, d), bf16),
                shape((B,) + tokens + (KV, d), bf16),
                shape((B,) + tokens + (KV, d), bf16),
                shape((B,) + tokens + (KV,)), leaf, shape((), i32),
                shape((B,), i32), shape((B,), jnp.bool_)).compile()
            text = compiled.as_text()
            assert name in text and text.count("tpu_custom_call") == 1
            assert "may-alias" in text
            # the leaf is 4.43 GB: a copy or a slice of it would show
            assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 26
        # the small leaf that read wrong on the chip (PR 32): one row of one
        # layer, 34.6 MB, made inside the program and carried through a
        # layer scan. Left to XLA it gets the fast memory (``S(1)`` on the
        # custom call's aliased result), where the Mosaic operand read
        # nothing; the kernels pin it to HBM, whatever its size
        def scan_layers(q, k, v, log_g, s0, rows, fresh):
            def layer(carry, li):
                s, acc = carry
                o, s = pr.retention_decode(q, k, v, log_g, s, li, rows, fresh)
                return (s, acc + o), None
            return jax.lax.scan(layer, (s0 * 0.5, jnp.zeros(q.shape)),
                                jnp.arange(s0.shape[0]))[0]

        small = shape((1, 1, KV) + pr.state_shape(d))
        text = jax.jit(scan_layers).lower(
            shape((1, H, d), bf16), shape((1, KV, d), bf16),
            shape((1, KV, d), bf16), shape((1, KV)), small,
            shape((1,), i32), shape((1,), jnp.bool_)).compile().as_text()
        call, = [line for line in text.splitlines()
                 if "tpu_custom_call" in line and " custom-call(" in line]
        state_out = call.split("= (")[1].split(", f32[")[0]
        assert state_out.startswith("f32[1,1,8,66,128,128]"), call[:300]
        assert "S(1)" not in state_out, state_out
        assert '"input_memory_space_colors":[{"operand_index":"8",' \
               '"color":"0"' in call and '"output_memory_colors":["0"' in call


def test_ssm_kernels_compile_for_a_described_v5e_at_the_served_shape(
        compiled_kernels, described_v5e):
    """The kernels PR 47 brought, through Mosaic at the widths of
    ``perf/configs/granite-4.0-h-micro-hybrid.json``: 64 rows' tokens
    through ``ssm_decode`` and one row's 128-token chunk through
    ``ssm_chunk`` on the pool's stacked leaf (36 layers x 64 slots x 32
    tiles of (128, 128) float32, 4.83 GB). Lane-dense rows, columns one
    lane wide and blocks of 16 tiles pass Mosaic's tiling only here. The
    leaf goes in and comes out in one buffer: no operation of the program
    copies it."""
    from deepspeed_tpu.ops import state_space as ss

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=described_v5e)

    i32 = jnp.int32
    L, R, H, P, N = 36, 64, 64, 64, 128
    leaf = shape((L, R) + ss.state_shape(H, P, N))
    assert leaf.shape[2:] == (32, 128, 128)
    with _compile_cache_off():
        for name, fn, B, tokens in (("ssm_decode", ss.ssm_decode, R, ()),
                                    ("ssm_chunk", ss.ssm_chunk, 1, (128,))):
            compiled = jax.jit(fn, donate_argnums=5).lower(
                shape((B,) + tokens + (H, P)), shape((B,) + tokens + (H,)),
                shape((H,)), shape((B,) + tokens + (N,)),
                shape((B,) + tokens + (N,)), leaf, shape((), i32),
                shape((B,), i32), shape((B,), jnp.bool_)).compile()
            text = compiled.as_text()
            assert name in text and text.count("tpu_custom_call") == 1
            assert "may-alias" in text
            # the leaf is 4.83 GB: a copy or a slice of it would show
            assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 27


def test_lightning_and_sparse_kernels_compile_for_a_described_v5e_as_served(
        compiled_kernels, described_v5e):
    """The kernels PR 56 brought, through Mosaic at the widths of
    ``perf/configs/minicpm-sala-9b-sparse.json``: 32 rows' tokens through
    ``lightning_decode`` (PR 59: on the vector unit, a row's 32 tiles a
    grid step, a head's k and q as columns ONE lane wide beside its tile,
    as ``kda_decode``'s, turned from their lane-dense rows by a transpose
    of (64, 128) inside the kernel, which passes Mosaic only here) and one
    row's 128-token block through ``lightning_chunk`` (the state-space
    chunk kernel with a head's own B and C, its products on the MXU) on
    the pool's stacked leaf (9 layers x 32 slots x 32 tiles of (128, 128)
    float32); and the sparse layers' page read (``ops/attention/
    sparse_read.py``) with its plan: 32 decode rows, a (row, KV head) one
    entry of 16 query heads, a grid step a block of 16 of its own pages
    that the kernel fetches from the leaves in HBM by its own copies (PR
    57; ``sparse_read``), and a chunk's 512 queries, they and their
    accumulators whole in VMEM, 16 of them a step, under the blocks they
    chose and over their window (``sparse_read_chunk``), pages of 128. The
    leaves go in and come out in one buffer: no operation copies them."""
    from deepspeed_tpu.ops import lightning
    from deepspeed_tpu.ops.attention import sparse_read as sr
    from deepspeed_tpu.ops.attention.sparse_index import SparseSizes

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=described_v5e)

    i32, bf16 = jnp.int32, jnp.bfloat16
    L, R, H, D = 9, 32, 32, 128
    leaf = shape((L, R) + lightning.state_shape(H, D))
    assert leaf.shape[2:] == (32, 128, 128)
    with _compile_cache_off():
        for name, fn, B, tokens, more in (
                ("lightning_decode", lightning.lightning_decode, R, (), ()),
                ("lightning_chunk", lightning.lightning_prefill, 1, (128,),
                 (shape((1,), i32),))):
            qkv = shape((B,) + tokens + (H, D))
            compiled = jax.jit(fn, donate_argnums=3).lower(
                qkv, qkv, qkv, leaf, shape((), i32), shape((B,), i32),
                shape((B,), jnp.bool_), *more).compile()
            text = compiled.as_text()
            assert name in text and text.count("tpu_custom_call") == 1
            assert "may-alias" in text
            assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 27
        pages, per_slot, KV = 10240, 320, 2
        k = shape((3, pages, KV, D, 128), bf16)
        sizes = SparseSizes(32, 16, 64, 1, 2048, 64, 8192)
        for name, fn, rows, table, limit in (
                ("sparse_read", sr.read_rows, 32, (32, per_slot), 2 ** 24),
                ("sparse_read_chunk", sr.read_chunk, 512, (per_slot,),
                 2 ** 24)):
            compiled = jax.jit(functools.partial(
                fn, sizes=sizes, page_size=128, scale=D ** -0.5,
                name=name)).lower(
                shape((rows, H, D), bf16), k, k, shape((), i32),
                shape(table, i32), shape((rows,), i32),
                shape((rows, KV, per_slot * 2), jnp.bool_)).compile()
            text = compiled.as_text()
            # (the target, not the bare word: the text's table of source
            # frames names this file's ``_tpu_custom_calls`` too)
            assert name in text and text.count(
                'custom_call_target="tpu_custom_call"') == 1
            # the K/V leaves are 4 GB: a copy of one would show, and so
            # would a gather of the queries or of partial results
            assert compiled.memory_analysis().temp_size_in_bytes < limit


def test_kda_kernels_compile_for_a_described_v5e_at_the_served_shape(
        compiled_kernels, described_v5e):
    """The kernels PR 50 brought, through Mosaic at the widths of
    ``perf/configs/kimi-linear-48b-a3b-ep8.json``: 128 rows' tokens through
    ``kda_decode`` (one grid step a row with its 32 heads; the channels'
    vectors as columns ONE lane wide of a (128, 128) block, which pass
    Mosaic's tiling only here) and one row's 128-token chunk through
    ``kda_chunk`` on the pool's stacked leaf (9 layers x 128 slots x 32
    heads of (128, 128) float32, 2.4 GB). The leaf goes in and comes out in
    one buffer: no operation of the program copies it."""
    from deepspeed_tpu.ops import kda

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=described_v5e)

    i32 = jnp.int32
    L, R, H, K = 9, 128, 32, 128
    leaf = shape((L, R, H, K, K))
    with _compile_cache_off():
        for name, fn, B, tokens in (("kda_decode", kda.kda_decode, R, ()),
                                    ("kda_chunk", kda.kda_chunk, 1, (128,))):
            vectors = shape((B,) + tokens + (H, K))
            compiled = jax.jit(fn, donate_argnums=5).lower(
                vectors, vectors, vectors, vectors,
                shape((B,) + tokens + (H,)), leaf, shape((), i32),
                shape((B,), i32), shape((B,), jnp.bool_)).compile()
            text = compiled.as_text()
            assert name in text and text.count("tpu_custom_call") == 1
            assert "may-alias" in text
            # the leaf is 2.4 GB: a copy or a slice of it would show
            assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 27


def test_a_kda_state_group_beside_latent_pages_compiles_with_no_copy_of_a_leaf(
        compiled_kernels, described_v5e):
    """PR 50. The one program of a step that carries a chunk beside running
    slots, for a server of kda and latent attention layers at the published
    widths of one period (``[kda (dense FFN), kda, kda, attention]``), 32
    of 256 experts held, 128 slots, 8,192 pages of 128, compiled for a
    described v5e. It holds each group's cache kernels a layer body (the
    dense layer's, the state layers' scan's, the attention layer's) and
    ONE routed FFN a routed body over both groups' rows; no leaf of the
    pool and no stacked weight is copied (temporaries under 128 MB beside
    1.7 GB of leaves and 0.9 GB of weights), the 32-wide ``b_proj`` and the
    conv tail's rows written through the layer's slab among them."""
    model, engine = _zero_engine(
        "kimi_linear", max_seq_len=8192, n_embd=2304, n_layer=4, n_head=32,
        n_kv_head=32, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128,
        layer_types=["kda", "kda", "kda", "attention"], kda_n_heads=32,
        kda_d_head=128, ffn_dim=1024, n_experts=256, experts_held=32,
        experts_per_token=8, routed_scaling_factor=2.446,
        n_shared_experts=1, dense_ffn_dim=9216,
        mlp_layer_types=["dense", "sparse", "sparse", "sparse"])
    compiled, served = _chunk_beside_decode_of(
        model, engine, described_v5e, slots=128, chunk=128, num_pages=8192)
    assert served["s"].shape == (3, 128, 32, 128, 128)
    assert served["c"].shape == (1, 8192, 576, 128)
    text = compiled.as_text()
    calls = re.findall(
        r"%(\w+?)(?:\.\d+)? = .* custom-call\(.*tpu_custom_call", text)
    assert sorted(calls) == sorted(
        ["kda_chunk", "kda_decode"] * 2
        # (a chunk's 128 x 32 query-head rows are two calls of MAX_ROWS)
        + ["paged_write"] * 2 + ["mla_chunk"] * 2 + ["mla_decode"]
        + ["moe_gate_up", "moe_down"] * 2), calls
    assert "may-alias" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 27, \
        compiled.memory_analysis()


def test_gdn_kernels_compile_for_a_described_v5e_at_the_served_shape(
        compiled_kernels, described_v5e):
    """PR 60. ``ops/kda.py``'s two kernels in their scalar-decay form, under
    the names a Gated DeltaNet layer gives them, through Mosaic at the
    widths of ``perf/configs/qwen3-next-80b-a3b-ep4.json``: 96 rows' tokens
    through ``gdn_decode`` (32 value heads over 16 key heads, one log decay
    a head) and one row's 128-token block through ``gdn_chunk`` on the
    pool's stacked leaf (6 layers x 96 slots x 32 heads of (128, 128)
    float32, 1.2 GB), which goes in and comes out in one buffer."""
    from deepspeed_tpu.ops import kda

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=described_v5e)

    i32 = jnp.int32
    L, R, Hk, H, K = 6, 96, 16, 32, 128
    leaf = shape((L, R, H, K, K))
    with _compile_cache_off():
        for name, fn, B, tokens in (("gdn_decode", kda.kda_decode, R, ()),
                                    ("gdn_chunk", kda.kda_chunk, 1, (128,))):
            keys = shape((B,) + tokens + (Hk, K))
            heads = shape((B,) + tokens + (H,))
            compiled = jax.jit(functools.partial(fn, name=name),
                               donate_argnums=5).lower(
                keys, keys, shape((B,) + tokens + (H, K)), heads, heads,
                leaf, shape((), i32), shape((B,), i32),
                shape((B,), jnp.bool_)).compile()
            text = compiled.as_text()
            assert re.findall(r"%(\w+?)(?:\.\d+)? = .* custom-call\(.*"
                              r"tpu_custom_call", text) == [name]
            assert "may-alias" in text
            assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 27


def _qwen3_next_period(held: int):
    """One period of Qwen3-Next at its published widths (``[gdn, gdn, gdn,
    attention]``), ``held`` of the router's 512 experts here."""
    return _zero_engine(
        "qwen3_next", max_seq_len=8192, n_embd=2048, n_layer=4, n_head=16,
        n_kv_head=2, head_size=256, rope_theta=10000000,
        layer_types=["linear_attention"] * 3 + ["full_attention"],
        gdn_n_key_heads=16, gdn_n_value_heads=32, gdn_d_head=128,
        ffn_dim=512, n_experts=512, experts_held=held, experts_per_token=10)


def _chunk_beside_decode_of(model, engine, described_v5e, slots, chunk,
                            num_pages):
    """The one program of a step that carries a ``chunk`` beside ``slots``
    running rows over a pool of ``num_pages`` pages of 128, compiled for a
    described v5e: ``(compiled, the pool's leaves as shapes)``."""
    from deepspeed_tpu.inference.engine import pack_chunk_args
    from deepspeed_tpu.parallel import mesh
    from deepspeed_tpu.serving.paged_pool import PagedKVPool

    spec = model.kv_cache_spec()
    pool = PagedKVPool(spec, 2, num_pages=2, kernel="on", page_size=128,
                       prefix_cache=False)
    pool.bind_engine(engine)
    assert pool.fuses(chunk)
    served = jax.eval_shape(
        lambda: spec.paged_cache(num_pages, 128, num_slots=slots))
    cs = dict(served, index=jax.ShapeDtypeStruct((slots,), jnp.int32),
              table=jax.ShapeDtypeStruct((slots, pool.pages_per_slot),
                                         jnp.int32))
    token = jnp.zeros((slots,), jnp.int32)
    packed = jnp.asarray(pack_chunk_args(
        np.zeros((1, chunk), np.int32), 0, chunk, chunk, chunk - 1,
        np.zeros((pool.pages_per_slot,), np.int32)))
    mesh.reset_mesh()       # (the engine's mesh is of this process's CPUs)

    def described(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=described_v5e)

    with _compile_cache_off():
        return pool._paged_chunk_decode_jit.lower(
            *jax.tree_util.tree_map(
                described, (engine.params, cs, packed, token, token))
        ).compile(), served


def test_a_gdn_state_group_beside_wide_pages_compiles_with_no_copy_of_a_leaf(
        compiled_kernels, described_v5e):
    """PR 60. The one program of a step that carries a chunk of 512 beside
    96 running slots, for a server of Gated DeltaNet and gated GQA layers
    at Qwen3-Next's published widths of one period, 16 of 512 experts held
    (128 as served: the tile bound and the leaf grow, the text does not),
    5,632 pages of 128 at head_dim 256, compiled for a described v5e. A
    state layer's body holds the conv tail's slab write and the two forms
    of the delta rule under their own names; the attention layer's the
    256-wide page write (a chunk's 512 columns a window of 128 at a time,
    K and V: eight calls, and the decode rows' two), and the read of a
    chunk's 512 x 8 query rows a KV head as FOUR calls of 128 positions
    (``_step_bytes``: two (1,024, 256) blocks and their float32 accumulator
    are 4.5 MB of the 8 MB a step may hold; 256 positions would be 8.7)
    beside the decode rows' one; ONE routed FFN a body over both groups'
    rows. No leaf of the pool and no stacked weight is copied."""
    model, engine = _qwen3_next_period(16)
    compiled, served = _chunk_beside_decode_of(
        model, engine, described_v5e, slots=96, chunk=512, num_pages=5632)
    assert served["s"].shape == (3, 96, 32, 128, 128)
    assert served["conv"].shape == (3, 96, 3 * 8192)
    assert served["k"].shape == (1, 5632, 2, 256, 128)
    text = compiled.as_text()
    calls = re.findall(
        r"%(\w+?)(?:\.\d+)? = .* custom-call\(.*tpu_custom_call", text)
    assert sorted(calls) == sorted(
        ["gdn_chunk", "gdn_decode"]
        + ["paged_write"] * 10 + ["paged_decode"] * 5
        + ["moe_gate_up", "moe_down"] * 2), calls
    assert "may-alias" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 27, \
        compiled.memory_analysis()


def test_a_state_group_beside_pages_compiles_with_no_copy_of_a_leaf(
        compiled_kernels, described_v5e):
    """The decode, the chunk and (PR 48) the chunk-beside-decode program of
    a server of mamba and attention
    layers at the published widths of one period (``[m, m, attention, m]``
    in place of ``[5, attention, 4]``: the body of the period's scan does
    not depend on the count), 64 slots, 1,536 pages of 128, compiled for a
    described v5e. Both hold the five kernels (``ssm_decode`` or
    ``ssm_chunk`` twice, ``paged_write`` twice, ``paged_decode``); no leaf
    of the pool and no stacked weight is copied (a program's temporaries
    stay under 64 MB beside 2 GB of leaves); in particular the input
    projection, kept as [z ; xBC] and dt, comes in in the layout its
    product takes (as ONE leaf 8,512 wide, no multiple of 128 lanes, the
    chip's client stores it transposed and every program copied it whole:
    627 MB of temporaries at 18 layers)."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.inference.engine import pack_chunk_args
    from deepspeed_tpu.models.lm_config import transformer_config
    from deepspeed_tpu.models.transformer_lm import TransformerLM
    from deepspeed_tpu.parallel import mesh
    from deepspeed_tpu.serving.paged_pool import PagedKVPool

    pattern = ["mamba", "mamba", "attention", "mamba"]
    model = TransformerLM(transformer_config(
        "granite-hybrid", vocab_size=128, max_seq_len=16384, n_embd=2048,
        n_layer=4, n_head=32, n_kv_head=8, ffn_dim=8192,
        layer_types=pattern, mamba_n_heads=64, mamba_d_head=64,
        mamba_d_state=128, embedding_multiplier=12.0,
        attention_multiplier=0.015625, residual_multiplier=0.22,
        logits_scaling=8.0, dtype=jnp.bfloat16))
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32),
                           method=model.logits)["params"])
    engine = ds.init_inference(
        model=model, config={"dtype": "bf16"},
        model_parameters=jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, jnp.bfloat16), params))
    engine._ensure_params(jnp.zeros((1, 2), jnp.int32))
    spec, slots, chunk = model.kv_cache_spec(), 64, 128
    pool = PagedKVPool(spec, 2, num_pages=2, kernel="on", page_size=128,
                       prefix_cache=False)
    pool.bind_engine(engine)
    cs = dict(jax.eval_shape(
        lambda: spec.paged_cache(1536, 128, num_slots=slots)))
    assert cs["s"].shape == (3, 64, 32, 128, 128)
    cs["index"] = jax.ShapeDtypeStruct((slots,), jnp.int32)
    cs["table"] = jax.ShapeDtypeStruct((slots, pool.pages_per_slot),
                                       jnp.int32)
    token = jnp.zeros((slots,), jnp.int32)
    packed = jnp.asarray(pack_chunk_args(
        np.zeros((1, chunk), np.int32), 0, chunk, chunk, chunk - 1,
        np.zeros((pool.pages_per_slot,), np.int32)))
    step = ["paged_write"] * 2 + ["paged_decode"]
    programs = {
        "kernel_decode": (pool._paged_decode_kernel_jit,
                          (engine.params, cs, token, token),
                          ["ssm_decode"] * 2 + step),
        "paged_chunk": (pool._paged_chunk_jit, (engine.params, cs, packed),
                        ["ssm_chunk"] * 2 + step),
        # (PR 48) the chunk's rows and the decode rows in one pass: each
        # group through its own kernels, the projections over both
        "paged_chunk_beside_decode": (
            pool._paged_chunk_decode_jit,
            (engine.params, cs, packed, token, token),
            ["ssm_chunk"] * 2 + ["ssm_decode"] * 2 + step * 2)}
    mesh.reset_mesh()       # (the engine's mesh is of this process's CPUs)

    def described(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=described_v5e)

    with _compile_cache_off():
        for name, (jitted, args, kernels) in programs.items():
            compiled = jitted.lower(*jax.tree_util.tree_map(
                described, args)).compile()
            text = compiled.as_text()
            calls = re.findall(
                r"%(\w+?)(?:\.\d+)? = .* custom-call\(.*tpu_custom_call",
                text)
            assert sorted(calls) == sorted(kernels), name
            assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 26, \
                (name, compiled.memory_analysis())


def test_a_conv_tail_beside_pages_compiles_with_no_copy_of_a_leaf(
        compiled_kernels, described_v5e):
    """PR 54. The decode, the chunk and the chunk-beside-decode program of
    a server of gated short-convolution and QK-normed rotary attention
    layers at every published width of ``perf/configs/lfm2-24b-a2b-conv
    .json``, cut as PR 42's recipe cuts (one period ``[conv, conv,
    attention, conv]`` whose two leading conv layers carry the dense FFN of
    11,776 and whose other two a routed one, 8 of 64 experts, a vocabulary
    of 128), 256 slots, 8,192 pages of 128, compiled for a described v5e.
    The mixer brings no kernel: a program holds the attention layer's
    ``paged_write`` twice and ``paged_decode`` for each group of rows, and
    ONE routed FFN a routed layer body over both groups' rows. No leaf of
    the pool (2.1 GB of pages; the tail's (3, 256, 4096) written through a
    layer's slab) and no stacked weight is copied."""
    from deepspeed_tpu.inference.engine import pack_chunk_args
    from deepspeed_tpu.parallel import mesh
    from deepspeed_tpu.serving.paged_pool import PagedKVPool

    model, engine = _zero_engine(
        "lfm2_moe", max_seq_len=4096, n_embd=2048, n_layer=4, n_head=32,
        n_kv_head=8, rope_theta=1000000.0,
        layer_types=["conv", "conv", "full_attention", "conv"],
        ffn_dim=1536, n_experts=8, experts_per_token=4, first_k_dense=2,
        dense_ffn_dim=11776)
    assert model.config.head_dim == 64 and model.config.qk_norm
    spec, slots, chunk = model.kv_cache_spec(), 256, 128
    pool = PagedKVPool(spec, 2, num_pages=2, kernel="on", page_size=128,
                       prefix_cache=False)
    pool.bind_engine(engine)
    assert pool.fuses(chunk)
    cs = dict(jax.eval_shape(
        lambda: spec.paged_cache(8192, 128, num_slots=slots)))
    assert set(cs) == {"k", "v", "conv"}
    assert cs["conv"].shape == (3, 256, 4096)
    assert cs["k"].shape == (1, 8192, 8, 64, 128)
    cs["index"] = jax.ShapeDtypeStruct((slots,), jnp.int32)
    cs["table"] = jax.ShapeDtypeStruct((slots, pool.pages_per_slot),
                                       jnp.int32)
    token = jnp.zeros((slots,), jnp.int32)
    packed = jnp.asarray(pack_chunk_args(
        np.zeros((1, chunk), np.int32), 0, chunk, chunk, chunk - 1,
        np.zeros((pool.pages_per_slot,), np.int32)))
    step = ["paged_write"] * 2 + ["paged_decode"]
    routed = ["moe_gate_up", "moe_down"] * 2    # the attention layer's and
    #                                             the conv layer's behind it
    programs = {
        "kernel_decode": (pool._paged_decode_kernel_jit,
                          (engine.params, cs, token, token), step + routed),
        "paged_chunk": (pool._paged_chunk_jit, (engine.params, cs, packed),
                        step + routed),
        "paged_chunk_beside_decode": (
            pool._paged_chunk_decode_jit,
            (engine.params, cs, packed, token, token), step * 2 + routed)}
    mesh.reset_mesh()       # (the engine's mesh is of this process's CPUs)

    def described(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=described_v5e)

    with _compile_cache_off():
        for name, (jitted, args, kernels) in programs.items():
            compiled = jitted.lower(*jax.tree_util.tree_map(
                described, args)).compile()
            text = compiled.as_text()
            calls = re.findall(
                r"%(\w+?)(?:\.\d+)? = .* custom-call\(.*tpu_custom_call",
                text)
            assert sorted(calls) == sorted(kernels), (name, calls)
            assert "may-alias" in text
            assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 27, \
                (name, compiled.memory_analysis())


def test_latent_kernels_compile_for_a_described_v5e_at_the_served_shape(
        compiled_kernels, described_v5e):
    """The kernels PR 38 brought, through Mosaic at the widths of
    ``perf/configs/moonlight-16b-a3b-mla.json``: the latent pool's leaf (7
    layers x 3,072 pages of 576 stored rows x 128 positions, 3.17 GB)
    written through ``paged_write``'s scale-leaf form and read by
    ``mla_decode`` (64 slots, 16 heads' rows of one token, 16 pages a grid
    step by the kernel's own copies: PR 51) and ``mla_chunk`` (one slot, a
    chunk's 2,048 query-head rows in one call: ~22 MB of VMEM, which passes
    only under the raised limit), and at the decode shape of
    ``perf/configs/kimi-linear-48b-a3b-ep8.json`` (128 slots, 32 heads, 3
    layers x 8,192 pages, 3.62 GB: 8 pages a step). The leaf goes in and
    comes out in one buffer: no operation of the program copies or slices
    it."""
    from deepspeed_tpu.ops.attention.latent_attention import latent_attention
    from deepspeed_tpu.ops.attention.paged_attention import \
        paged_write_columns

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=described_v5e)

    W, ps, per_slot = 576, 128, 64

    def step(q, leaf, table, starts, layer, cols):
        leaf = paged_write_columns(leaf, layer, cols, table, starts,
                                   page_size=ps)
        return latent_attention(q, leaf, table, starts, layer=layer,
                                rank=512, scale=192 ** -0.5,
                                page_size=ps), leaf

    with _compile_cache_off():
        for name, L, P, B, T, H in (("mla_decode", 7, 3072, 64, 1, 16),
                                    ("mla_chunk", 7, 3072, 1, 128, 16),
                                    ("mla_decode", 3, 8192, 128, 1, 32)):
            compiled = jax.jit(step, donate_argnums=1).lower(
                shape((B, T, H, W)), shape((L, P, W, ps)),
                shape((B, per_slot), jnp.int32), shape((B,), jnp.int32),
                shape((), jnp.int32), shape((B, W, T))).compile()
            text = compiled.as_text()
            assert name in text and "paged_write" in text
            assert text.count("tpu_custom_call") >= 2
            assert "may-alias" in text
            # the leaf is 3.17 GB: a copy or a slice of it would show
            assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 24


# the lowered programs of a small paged server: what touches a pool leaf
_LEAF_OPS = ("dynamic_slice", "dynamic_update_slice", "scatter", "transpose",
             "gather", "pad", "concatenate", "convert", "select")


def _ops_on(lowered, shapes):
    """Names of the StableHLO operations, at any depth, that take or give
    a value of one of ``shapes`` (MLIR tensor types)."""
    found = []

    def walk(op):
        types = [str(v.type) for v in list(op.operands) + list(op.results)]
        if any(t in shapes for t in types):
            found.append(op.name)
        for region in op.regions:
            for block in region:
                for child in block:
                    walk(child.operation)

    walk(lowered.compiler_ir(dialect="stablehlo").operation)
    return found


def _zero_engine(family, **widths):
    """A bf16 ``TransformerLM`` of ``family`` over a vocabulary of 128 and
    an inference engine on its parameters, all zero."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.lm_config import transformer_config
    from deepspeed_tpu.models.transformer_lm import TransformerLM

    model = TransformerLM(transformer_config(
        family, vocab_size=128, dtype=jnp.bfloat16, **widths))
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32),
                           method=model.logits)["params"])
    engine = ds.init_inference(
        model=model, config={"dtype": "bf16"},
        model_parameters=jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, jnp.bfloat16), params))
    engine._ensure_params(jnp.zeros((1, 2), jnp.int32))
    return model, engine


def _small_paged_server(pages=6):
    """A two-layer GPT-NeoX server over a page pool with the kernel on, and
    the operands of its three step programs. ``max_seq_len`` is 384, a
    width nothing else in the model has."""
    from deepspeed_tpu.inference.engine import pack_chunk_args
    from deepspeed_tpu.serving.paged_pool import PagedKVPool

    model, engine = _zero_engine("gpt-neox", max_seq_len=384, n_embd=256,
                                 n_layer=2, n_head=2)
    slots, ps = 2, 64
    pool = PagedKVPool(model.kv_cache_spec(), slots, num_pages=pages,
                       page_size=ps, kernel="on")
    pool.bind_engine(engine)
    cs = pool.cache["cache_store"]
    assert cs["k"].shape == (2, pages, 2, 128, 128)
    assert pool.pages_per_slot == 6
    i32 = jnp.int32
    pre = dict(model.kv_cache_spec().stacked_cache(2))
    programs = {
        # (the (B,) token twin; a chunk's arguments as one vector)
        "kernel_decode": (pool._paged_decode_kernel_jit, (
            engine.params, cs, jnp.zeros((slots,), i32))),
        "paged_chunk": (pool._paged_chunk_jit, (
            engine.params, cs, jnp.asarray(pack_chunk_args(
                np.zeros((1, 64), np.int32), 0, 64, 64, 63,
                np.zeros((pool.pages_per_slot,), np.int32))))),
        "_paged_admit_rows": (pool._admit_rows_jit, (
            cs, pre, jnp.zeros((2, pool.pages_per_slot), i32),
            jnp.zeros((2,), i32), jnp.zeros((2,), i32))),
    }
    # (PR 48) a chunk beside the decode rows, ONE program: the chunk's
    # vector and the token twin
    programs["paged_chunk_beside_decode"] = (
        pool._paged_chunk_decode_jit,
        programs["paged_chunk"][1] + (jnp.zeros((slots,), i32),))
    return pool, programs


def test_no_program_of_a_serving_step_passes_over_a_pool_leaf(
        compiled_kernels):
    """``kernel_decode``, ``paged_chunk``, (PR 48) the two as one program and
    the admission program take the
    stacked K and V leaves and hand them on through custom calls alone: no
    slice, update-slice, scatter, gather or transpose of a leaf (one
    layer's or the stacked one). Since PR 33 the chunk program reads
    through ``paged_decode`` like the decode program: its gather of one
    slot's dense row is gone, and with it every value of ``max_seq_len``
    positions."""
    pool, programs = _small_paged_server()
    pages, lanes = pool.num_pages, 128
    leaves = {f"tensor<2x{pages}x2x128x{lanes}xbf16>",
              f"tensor<{pages}x2x128x{lanes}xbf16>",
              f"tensor<1x{pages}x2x128x{lanes}xbf16>"}
    for name, (jitted, args) in programs.items():
        lowered = jitted.trace(*args).lower(lowering_platforms=("tpu",))
        text = lowered.as_text()
        assert "paged_write" in text, name
        ops = [op for op in _ops_on(lowered, leaves)
               if op.split(".")[-1] in _LEAF_OPS]
        assert not ops, (name, ops)
        if name != "_paged_admit_rows":
            # both read through the kernel, on the same leaf, and neither
            # holds a dense row (the admission program is handed one)
            assert "paged_decode" in text, name
            dims = {d for t in re.findall(r"tensor<((?:\d+x)+)", text)
                    for d in t.split("x")}
            assert "128" in dims and "384" not in dims, name


# the served attention kinds at the least widths at which the PARENT of PR 42
# (ac6b3dd) showed a layer's attention weights sliced out of the stacked leaf
# and transposed: family, widths, slots, the pool's pages of 128 lanes
# (None: the contiguous SlotPool), a chunk's tokens
_ATTENTION_KINDS = {
    "kv_pages": ("gpt-neox", dict(n_embd=512, n_layer=2, n_head=4), 64,
                 dict(page_size=64), 64),
    "state": ("brumby", dict(n_embd=512, n_layer=2, n_head=4, n_kv_head=2,
                             head_size=128, ffn_dim=1024), 16, None, 128),
    "latent_pages": ("moonlight", dict(
        n_embd=512, n_layer=3, n_head=4, n_kv_head=4, kv_lora_rank=128,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        ffn_dim=128, n_experts=8, experts_per_token=2, dense_ffn_dim=1024,
        mlp_layer_types=["dense", "sparse", "sparse"]), 64,
        dict(page_size=128, prefix_cache=False), 128),
}
_POOL_PAGES = 4096      # described, as in the test of the chunk program


def _attention_step_programs(kind):
    """The decode and the chunk program of a small server of one attention
    kind with their operands as shapes, and the per-layer shapes of its
    stacked attention weights (``bf16[1,in,out]`` of every ``Dense`` under
    ``attn``)."""
    from deepspeed_tpu.inference.engine import pack_chunk_args
    from deepspeed_tpu.serving.paged_pool import PagedKVPool

    family, widths, slots, paged, chunk = _ATTENTION_KINDS[kind]
    model, engine = _zero_engine(family, max_seq_len=2048, **widths)
    token = jnp.zeros((slots,), jnp.int32)

    def packed(*rows):
        return jnp.asarray(pack_chunk_args(
            np.zeros((1, chunk), np.int32), 0, chunk, chunk, chunk - 1,
            *rows))

    if paged is None:
        cache = {"cache_store": jax.eval_shape(
            lambda: model.kv_cache_spec().stacked_cache(slots))}
        programs = {
            "decode": (engine._jit_decode, (
                engine.params, cache, token, None, token)),
            "prefill_chunk": (engine._jit_prefill_chunk, (
                engine.params, cache, packed()))}
    else:
        pool = PagedKVPool(model.kv_cache_spec(), slots, num_pages=slots,
                           kernel="on", **paged)
        pool.bind_engine(engine)
        # the pool as a deployment's: nothing the compiler could keep in
        # the chip's fast memory
        cs = {key: jax.ShapeDtypeStruct(
            (leaf.shape[0], _POOL_PAGES) + leaf.shape[2:], leaf.dtype)
            if leaf.ndim > 2 else leaf
            for key, leaf in pool.cache["cache_store"].items()}
        rows = [np.zeros((pool.pages_per_slot,), np.int32)
                for _ in pool._table_keys]
        programs = {
            "kernel_decode": (pool._paged_decode_kernel_jit, (
                engine.params, cs, token)),
            "paged_chunk": (pool._paged_chunk_jit, (
                engine.params, cs, packed(*rows)))}
    weights = {}

    def note(path, leaf):
        keys = [getattr(k, "key", None) for k in path]
        if "attn" in keys and keys[-1] == "kernel" and leaf.ndim == 3:
            weights.setdefault("bf16[1,%d,%d]" % leaf.shape[1:],
                               []).append(keys[-2])

    jax.tree_util.tree_map_with_path(note, engine.params)
    return programs, weights


@pytest.mark.parametrize("kind", sorted(_ATTENTION_KINDS))
def test_attention_projections_read_the_stacked_leaf(
        compiled_kernels, described_v5e, kind):
    """PR 42. In the decode and the chunk program of a server, compiled for
    a described v5e, no instruction of a layer's body gives a value with the
    per-layer shape of a stacked attention weight: every projection's dot
    takes its matrix from the stacked leaf, the slice fused into it. The
    fault this guards (seen at these widths on PR 42's parent, n_embd 512
    and 4 heads of 128 in every kind, and at the served ones: ``bf16[1,
    2048,2048]`` x 3 in Pythia's programs, ``[1,2304,4096]`` and ``[1,2304,
    512]`` x 2 in Mellum's, ``[1,5120,5120]`` and ``[1,5120,1024]`` x 2 in
    Brumby's, ``[1,2048,3072]`` in Moonlight's): the cache write and the
    read kernels want head_dim ahead of the rows, layout assignment carried
    that through the projection onto its weight, and the program made
    ``%constant_dynamic-slice_fusion = bf16[1,C,C']{2,1,0}`` (the layer's
    matrix out of the leaf) and ``%copy = bf16[1,C,C']{1,2,0}`` (transposed)
    a layer a projection a step; ``lm_parts._settled`` is what stops
    it. Not looked at: a latent layer's ``kv_b_proj``, a parameter and no
    ``Dense``, whose slice and copy are the batched product's own (the
    heads lie in the middle of the stored matrix; the einsum compiled alone
    makes both); and the asynchronous ``slice-start`` / ``copy-start`` with
    which the compiler moves a WHOLE parameter of a model this small into
    the chip's fast memory ahead of the loop."""
    from deepspeed_tpu.parallel import mesh

    programs, weights = _attention_step_programs(kind)
    assert {"q_proj", "o_proj"} <= set(sum(weights.values(), [])), weights
    mesh.reset_mesh()       # (the engine's mesh is of this process's CPUs)

    def described(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=described_v5e)

    with _compile_cache_off():
        texts = {name: jitted.lower(*jax.tree_util.tree_map(
            described, args)).compile().as_text()
            for name, (jitted, args) in programs.items()}
    for name, text in texts.items():
        assert "tpu_custom_call" in text, name
        found, inside = [], ""
        for line in text.split("\n"):
            head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\) -> .* \{$", line)
            if head:
                inside = head.group(1)
                continue
            op = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\w+\[[\d,]*\])\S* "
                          r"([\w-]+)\(", line)
            if op and "fused_computation" not in inside \
                    and op.group(2) in weights and op.group(3) in (
                        "fusion", "copy", "dynamic-slice", "slice",
                        "transpose"):
                found.append((op.group(1), op.group(2),
                              weights[op.group(2)]))
        assert not found, (kind, name, found)


# the paged serve cells' models at the widths of their ``perf/configs/``
# files, cut where a compile's cost lies and its text does not: depth (the
# layer scan's body is one), experts 64 -> 8, the vocabulary. Family, widths,
# the pool (pages as served), a chunk's tokens, the kernels one layer body
# holds for the chunk's rows and for the decode rows, the stacked weights
# that must not be copied (bytes of one)
_SERVED = {
    "pythia-1.4b-paged": ("gpt-neox", dict(
        max_seq_len=2048, n_embd=2048, n_layer=2, n_head=16),
        dict(num_pages=256, page_size=64), 64,
        ["paged_write"] * 4 + ["paged_decode"] * 2),
    "mellum2-12b-a2b5-paged": ("mellum", dict(
        max_seq_len=8192, n_embd=2304, n_layer=4, n_head=32, n_kv_head=4,
        head_size=128, ffn_dim=896, n_experts=8, experts_per_token=8,
        norm_topk_prob=True,
        layer_types=["sliding_attention"] * 3 + ["full_attention"],
        sliding_window=1024, rope_theta=500000),
        dict(num_pages=1024, page_size=128, prefix_cache=False), 128,
        ["paged_write"] * 8 + ["paged_decode"] * 4
        + ["moe_gate_up", "moe_down"]),
    "moonlight-16b-a3b-mla": ("moonlight", dict(
        max_seq_len=8192, n_embd=2048, n_layer=3, n_head=16, n_kv_head=16,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, ffn_dim=1408, n_experts=8, experts_per_token=6,
        norm_topk_prob=True, scoring_func="sigmoid",
        routed_scaling_factor=2.446, n_shared_experts=2,
        mlp_layer_types=["dense", "sparse", "sparse"], dense_ffn_dim=11264,
        rope_theta=50000),
        dict(num_pages=3072, page_size=128, prefix_cache=False), 128,
        # (the dense scan's body and the sparse scan's)
        (["paged_write"] * 2 + ["mla_chunk", "mla_decode"]) * 2
        + ["moe_gate_up", "moe_down"]),
}


@pytest.mark.parametrize("config", sorted(_SERVED))
def test_a_chunk_beside_decode_compiles_for_a_described_v5e_as_served(
        compiled_kernels, described_v5e, config):
    """PR 48. The one program of a step that carries a chunk beside running
    slots, for the three paged serve configurations whose layers are one
    scan (Granite's, at the published widths of one period, is in
    ``test_a_state_group_beside_pages_compiles_with_no_copy_of_a_leaf``):
    64 slots, the pool's pages and the chunk as served. Mosaic takes the
    chunk's kernels and the decode's in one program; a layer body holds
    each group's cache kernels and ONE routed FFN (``moe_gate_up`` /
    ``moe_down`` once, over both groups' rows: the experts are read once);
    the pool's leaves are aliased through (a copy of one, or of a stacked
    weight, would be over the temporaries' bound) and the tables come back
    as leaves of the result."""
    from deepspeed_tpu.inference.engine import pack_chunk_args
    from deepspeed_tpu.parallel import mesh
    from deepspeed_tpu.serving.paged_pool import PagedKVPool

    family, widths, paged, chunk, kernels = _SERVED[config]
    model, engine = _zero_engine(family, **widths)
    slots = 64
    pool = PagedKVPool(model.kv_cache_spec(), 2, kernel="on",
                       **dict(paged, num_pages=2))
    pool.bind_engine(engine)
    assert pool.fuses(chunk)
    served = jax.eval_shape(lambda: model.kv_cache_spec().paged_cache(
        paged["num_pages"], paged["page_size"],
        slots * (-(-1024 // 128) + 1) if pool.ring is not None else None,
        num_slots=slots))
    cs = dict(served, index=jax.ShapeDtypeStruct((slots,), jnp.int32))
    rows = []
    for key in pool._table_keys:
        cs[key] = jax.ShapeDtypeStruct((slots, pool.pages_per_slot),
                                       jnp.int32)
        rows.append(np.zeros((pool.pages_per_slot,), np.int32))
    packed = jnp.asarray(pack_chunk_args(
        np.zeros((1, chunk), np.int32), 0, chunk, chunk, chunk - 1, *rows))
    args = (engine.params, cs, packed, jnp.zeros((slots,), jnp.int32))
    mesh.reset_mesh()       # (the engine's mesh is of this process's CPUs)

    def described(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=described_v5e)

    with _compile_cache_off():
        compiled = pool._paged_chunk_decode_jit.lower(
            *jax.tree_util.tree_map(described, args)).compile()
    text = compiled.as_text()
    calls = re.findall(
        r"%(\w+?)(?:\.\d+)? = .* custom-call\(.*tpu_custom_call", text)
    assert sorted(calls) == sorted(kernels), calls
    leaf_bytes = min(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                     for key, leaf in served.items())
    assert compiled.memory_analysis().temp_size_in_bytes \
        < min(leaf_bytes, 2 ** 27), compiled.memory_analysis()
    assert "may-alias" in text
    # (PR 50) the routed FFN learned to hold a share of its experts; with
    # every expert held, as these configurations have them, the program is
    # the one PR 49 compiled, text for text (sha256 of _program_text, the
    # first 16 digits; Mellum's taken of commit 4378523 and of PR 50's tree
    # alike, and re-taken at PR 53, whose K/V read folds a step's KV heads
    # a run at a time: 4a3978ddd2f56143 before, the two paged_decode
    # bodies alone differ, ide measured on both, PERF.md section 6; and at
    # PR 55, whose K/V read packs a KV head's query rows into whole tiles:
    # f43b131370936a4f before; what differs is the DECODE rows' read, the
    # two paged_decode bodies of bf16[64,4,64,128], now [64,4,8,128], and
    # XLA's small fusions that lay those rows out; the chunk's two
    # paged_decode bodies bf16[1,4,1024,128], the eight paged_write and
    # the routed FFN's two are the text they were; ide measured on both,
    # PERF.md section 6, PR 55;
    # Moonlight's re-taken at PR 51, whose latent read takes a block of
    # pages a grid step: d12581f2ad0b359a before, reason measured on both,
    # and unchanged by PR 53 and PR 55)
    import hashlib

    pinned = {"mellum2-12b-a2b5-paged": "9d4509a5d64249a7",
              "moonlight-16b-a3b-mla": "1830eba84bdfb003"}
    if config in pinned:
        digest = hashlib.sha256(_program_text(compiled).encode()).hexdigest()
        assert digest[:16] == pinned[config], (
            "the compiled chunk-beside-decode program of an all-held "
            "configuration is not the one this digest was taken of: "
            "compare _program_text() of both trees, and pin the new digest "
            "with the cell measured on both")


def _program_text(compiled) -> str:
    """A compiled program's HLO without what names this checkout: the
    stack-frame tables and ``metadata`` (files and line numbers), and each
    Mosaic kernel as its module's text without locations in place of the
    serialized one."""
    import base64

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    def kernel(match):
        with jax_mlir.make_ir_context() as ctx:
            ctx.allow_unregistered_dialects = True
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            return '"body":"' + " ".join(module.operation.get_asm(
                enable_debug_info=False).split()) + '"'

    lines, tables = [], False
    for line in compiled.as_text().split("\n"):
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            tables = True
        elif tables and (not line.strip() or re.match(r"\d+ ", line)):
            continue
        else:
            tables = False
            lines.append(line)
    text = re.sub(r", metadata=\{[^}]*\}", "", "\n".join(lines))
    return re.sub(r'"body":"([A-Za-z0-9+/=]+)"', kernel, text)


# sha256 of _program_text(kernel_decode) of _small_paged_server(4096) since
# PR 42. Until then "4b94b625...109c1", pinned by PR 35 (whose text differed
# from PR 31's "eccfe7ce...aaff9" in the token operand alone: ``s32[2]`` for
# ``s32[2,1]``; chat `gap_p90_ms` 9.196 / 8.402 then). PR 42's differs from
# it, once the numbers XLA gives its instructions and their parameters are
# taken out, in 461 lines (241 of the old text, 220 new), all of them the
# layer body's q, k and v projections: gone are the three fusions that
# sliced ``bf16[1,256,256]`` out of the stacked ``bf16[2,256,256]`` leaves
# into buffers of their own (`constant_dynamic-slice_fusion.15/.16/.17`),
# the three ``copy`` of them into ``{1,2,0}`` (`copy.100/.106/.107`) and the
# three products over the transposed ``bf16[2,128,256]`` with the batch as
# the minor dimension; in their place three products ``bf16[2,1,256]`` that
# take the stacked leaf as an operand and slice it inside the fusion, as
# `o_proj`'s did and does (one ``dynamic-slice`` of a ``bf16[1,256,256]``
# inside a fusion then, four now), and the small copies that give the
# kernels their layout on the results. Kernels and every other instruction
# as they were. Measured with it, `serve-pythia-1b4-chat`, parent ac6b3dd /
# PR 42 at one seed a pair, one v5e chip (PERF.md §6, PR 42, calls A and B):
# `gap_p90_ms` 8.525 / 7.536, 8.399 / 7.219, 8.313 / 7.053 and 9.446 /
# 8.226 ms; `decode_dev_ms_p50` 5.953 / 4.940 (the traced pair).
# Re-taken at PR 53 ("766626d4...b6fd7" until then): the body of the
# `paged_decode` kernel alone differs (the step's KV heads folded a run at
# a time: every head's scores, the statistics, every head's values), every
# instruction of XLA's as it was. Measured with it, `serve-pythia-1b4-chat`,
# parent b162d6f / PR 53 at one seed a pair, one v5e chip (PERF.md §6,
# PR 53, call 2): `gap_p50_ms` 5.403 / 5.135 and 5.426 / 5.147 ms;
# `decode_dev_ms_p50.chat` 5.036 / 4.814, `gap_p90_ms.chat` 6.232 / 5.789
# (the traced pair).
_KERNEL_DECODE_TEXT = (
    "a21fb47b36e01cd8f5ee202825e9a420f2779fa3360208f5be9240a345344eec")


def test_the_chunk_program_goes_through_the_pages_and_decode_is_unchanged(
        compiled_kernels, described_v5e):
    """The two step programs of a paged server, compiled for a described
    v5e with a pool of 4,096 pages (0.5 GB a leaf: nothing the compiler
    could move to the chip's fast memory). ``paged_chunk`` (PR 33): the
    leaves go from parameter to custom call to result and no other
    operation takes or gives one, no value has ``max_seq_len`` positions,
    and the temporaries are a chunk's activations. ``kernel_decode``: its
    text is the pinned one, kernels included (what changed it last, and
    what was measured then, is beside the digest)."""
    import hashlib

    from deepspeed_tpu.parallel import mesh

    pages = 4096
    pool, programs = _small_paged_server()
    # the engine made a mesh of this process's CPU devices; the programs
    # are compiled for one described chip, as a one-chip server's are
    mesh.reset_mesh()
    leaf = f"bf16[2,{pages},2,128,128]"

    def described(x):
        shape = tuple(pages if d == pool.num_pages else d for d in x.shape) \
            if x.ndim == 5 else x.shape
        return jax.ShapeDtypeStruct(shape, x.dtype, sharding=described_v5e)

    with _compile_cache_off():
        compiled = {
            name: jitted.lower(
                *jax.tree_util.tree_map(described, args)).compile()
            for name, (jitted, args) in programs.items()
            if name != "_paged_admit_rows"}
    chunk = compiled["paged_chunk"]
    text = chunk.as_text()
    calls = re.findall(r"%(\w+)\.\d+ = [^\n]*tpu_custom_call", text)
    assert sorted(calls) == ["paged_decode", "paged_write", "paged_write"]
    passes = {"parameter", "custom-call", "get-tuple-element", "tuple",
              "while", "bitcast"}
    for line in text.split("\n"):
        op = re.search(r" = \S+ ([\w-]+)\(", line)
        if op and leaf in line:
            assert op.group(1) in passes, line[:300]
    assert not re.search(r"[\[,]384[\],]", text)
    assert chunk.memory_analysis().temp_size_in_bytes < 2 ** 23
    # (PR 48) both as ONE program: the two programs' kernels, the leaves
    # likewise from parameter to custom call to result
    beside = compiled["paged_chunk_beside_decode"]
    text = beside.as_text()
    calls = re.findall(r"%(\w+)\.\d+ = [^\n]*tpu_custom_call", text)
    assert sorted(calls) == ["paged_decode"] * 2 + ["paged_write"] * 4
    for line in text.split("\n"):
        op = re.search(r" = \S+ ([\w-]+)\(", line)
        if op and leaf in line:
            assert op.group(1) in passes, line[:300]
    assert beside.memory_analysis().temp_size_in_bytes < 2 ** 23
    digest = hashlib.sha256(
        _program_text(compiled["kernel_decode"]).encode()).hexdigest()
    assert digest == _KERNEL_DECODE_TEXT, (
        "the compiled decode program is not the one this digest was taken "
        "of: compare _program_text() of both trees, and pin the new digest "
        "with the chat cell's gap_p90_ms measured on both")


def test_dense_decode_lowers_for_tpu(compiled_kernels):
    from deepspeed_tpu.ops.attention.decode_attention import decode_attention

    q = _rand((2, 4, 128))
    cache = _rand((2, 2, 128, 1024), seed=1)
    lengths = jnp.asarray([700, 9], jnp.int32)
    assert _tpu_custom_calls(decode_attention, q, cache, cache, lengths) >= 1


def test_dense_decode_block_below_lane_width_does_not_lower(compiled_kernels):
    """The K/V block puts ``block_s`` on the 128-wide lane axis: a block of
    one default page (64) is not a legal Mosaic block. This is why the
    dense oracle pinned to ``decode_block=page_size`` cannot run on the chip
    at the default page size (chip_smoke.py, PR 21)."""
    from deepspeed_tpu.ops.attention.decode_attention import decode_attention

    q = _rand((2, 4, 128))
    cache = _rand((2, 2, 128, 1024), seed=1)
    lengths = jnp.asarray([700, 9], jnp.int32)
    with pytest.raises(ValueError, match="divisible by 8 and 128"):
        _tpu_custom_calls(
            lambda *a: decode_attention(*a, block_s=64),
            q, cache, cache, lengths)


def test_int8_gemm_lowers_for_tpu():
    from deepspeed_tpu.ops.quantization.int8_matmul import int8_matmul

    x = _rand((8, 1024))
    w = jnp.ones((1024, 512), jnp.int8)
    scales = jnp.ones((512,), jnp.float32)
    assert _tpu_custom_calls(
        lambda *a: int8_matmul(*a, interpret=False), x, w, scales) >= 1


# ---------------------------------------------------------------------------
# (b) (c) (d): fresh processes, started together
# ---------------------------------------------------------------------------
_IMPORT_AND_CACHE_PROBE = r"""
import importlib, json, os, pkgutil
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
import jax
from jax._src import xla_bridge
import deepspeed_tpu
for pkg in ("launcher", "serving", "telemetry", "models", "ops", "runtime",
            "inference", "parallel", "elasticity", "utils"):
    mod = importlib.import_module("deepspeed_tpu." + pkg)
    for info in pkgutil.iter_modules(mod.__path__):
        if not info.ispkg and not info.name.startswith("_"):
            importlib.import_module(mod.__name__ + "." + info.name)
import deepspeed_tpu.ops.attention.paged_attention
import deepspeed_tpu.ops.attention.flash_attention
import deepspeed_tpu.ops.quantization.int8_matmul
out = {"backend_after_imports": xla_bridge.backends_are_initialized()}

from deepspeed_tpu.utils.compile_cache import enable_compile_cache
# JAX read its environment at import; a directory that shows up in the
# config now can only have been set in code
os.environ["JAX_COMPILATION_CACHE_DIR"] = "/placed/from/outside"
out["env_set_returns"] = enable_compile_cache()
out["env_set_config"] = jax.config.jax_compilation_cache_dir
del os.environ["JAX_COMPILATION_CACHE_DIR"]
out["env_unset_returns"] = enable_compile_cache()
out["env_unset_config"] = jax.config.jax_compilation_cache_dir
out["min_compile_secs"] = jax.config.jax_persistent_cache_min_compile_time_secs
out["backend_after_helper"] = xla_bridge.backends_are_initialized()
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def fresh_processes():
    """All the subprocess checks at once: their cost is import time."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    commands = {
        "probe": [sys.executable, "-c", _IMPORT_AND_CACHE_PROBE],
        "smoke_default": [sys.executable, SMOKE],
        # one phase each, as the parent starts them: a whole rehearsal
        # around the planted failure would cost this tier half a minute
        "plant_train": [sys.executable, SMOKE, "--rehearsal", "--phase",
                        "train", "--plant-failure", "train"],
        "plant_serve": [sys.executable, SMOKE, "--rehearsal", "--phase",
                        "serve", "--plant-failure", "serve"],
    }
    procs = {name: subprocess.Popen(cmd, env=env, cwd=REPO, text=True,
                                    stdout=subprocess.PIPE,
                                    stderr=subprocess.PIPE)
             for name, cmd in commands.items()}
    done = {}
    try:
        for name, proc in procs.items():
            out, err = proc.communicate(timeout=300)
            done[name] = (proc.returncode, out, err)
    finally:
        for proc in procs.values():
            if proc.poll() is None:
                proc.kill()
    return done


def test_importing_the_package_initialises_no_backend(fresh_processes):
    code, out, err = fresh_processes["probe"]
    assert code == 0, err[-2000:]
    probe = json.loads(out.strip().splitlines()[-1])
    assert probe["backend_after_imports"] is False
    assert probe["backend_after_helper"] is False


def test_compile_cache_is_placed_by_env_or_at_the_checkout(fresh_processes):
    code, out, err = fresh_processes["probe"]
    assert code == 0, err[-2000:]
    probe = json.loads(out.strip().splitlines()[-1])
    # env set: the helper reports it and sets no directory in code
    assert probe["env_set_returns"] == "/placed/from/outside"
    assert probe["env_set_config"] is None
    # env unset: the fixed directory of this checkout
    assert probe["env_unset_returns"] == os.path.join(REPO, ".jax_cache")
    assert probe["env_unset_config"] == os.path.join(REPO, ".jax_cache")
    assert probe["min_compile_secs"] == 0


def test_chip_smoke_fails_without_a_tpu_and_prints_no_result(
        fresh_processes):
    code, out, err = fresh_processes["smoke_default"]
    assert code != 0
    assert out.strip() == ""
    assert "not a TPU" in err and "'cpu'" in err


@pytest.mark.parametrize("phase", ["train", "serve"])
def test_chip_smoke_phase_fails_when_it_raises(fresh_processes, phase):
    code, out, err = fresh_processes[f"plant_{phase}"]
    assert code != 0
    assert f"planted failure in the {phase} phase" in err
    assert "CHIP_SMOKE_PHASE_RESULT" not in out


# ---------------------------------------------------------------------------
# (d) the parent's verdict, with the phases replaced by canned results
# ---------------------------------------------------------------------------
def _verdict(monkeypatch, capsys, argv, phases):
    import importlib.util

    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    monkeypatch.setattr(
        smoke, "_run_phase",
        lambda phase, args, env: {"exit_code": 0, **phases[phase]})
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py", *argv])
    code = smoke.main()
    report, last = capsys.readouterr().out.strip().splitlines()[-2:]
    return code, json.loads(report), json.loads(last)


def _is_verdict(line, device):
    """The last line holds the driver's keys and no other."""
    return (set(line) == {"ok", "device"} and line["device"] == device
            and set(device) == {"platform", "kind", "count"})


_TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
_CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def test_pass_line_needs_every_phase_ok_on_a_tpu(monkeypatch, capsys):
    good = {"ok": True, "device": _TPU}
    code, report, last = _verdict(monkeypatch, capsys, [],
                                  {"train": good, "serve": dict(good)})
    assert code == 0 and last["ok"] is True and _is_verdict(last, _TPU)
    assert report["ok"] is True and report["train"]["ok"] is True

    bad = {"ok": False, "device": _TPU, "exit_code": 1}
    for phases in ({"train": bad, "serve": dict(good)},
                   {"train": dict(good), "serve": bad}):
        code, report, last = _verdict(monkeypatch, capsys, [], phases)
        assert code != 0 and last["ok"] is False and _is_verdict(last, _TPU)
        assert report["ok"] is False

    # a phase that died before its result line: still the driver's shape
    dead = {"ok": False, "exit_code": -9}
    code, _, last = _verdict(monkeypatch, capsys, [],
                             {"train": dead, "serve": dict(dead)})
    assert code != 0 and last["ok"] is False
    assert _is_verdict(last, last["device"])


def test_rehearsal_never_prints_the_pass_line(monkeypatch, capsys):
    good = {"ok": True, "device": _CPU}
    code, report, last = _verdict(monkeypatch, capsys, ["--rehearsal"],
                                  {"train": good, "serve": dict(good)})
    assert code == 0
    assert last["ok"] is False and _is_verdict(last, _CPU)
    assert report["ok"] is False and report["rehearsal"] is True
    assert report["rehearsal_passed"] is True

    bad = {"ok": False, "device": _CPU, "exit_code": 1}
    code, report, last = _verdict(monkeypatch, capsys, ["--rehearsal"],
                                  {"train": dict(good), "serve": bad})
    assert code != 0 and report["rehearsal_passed"] is False
    assert last["ok"] is False
