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
    from jax.experimental.compilation_cache import compilation_cache
    from deepspeed_tpu.models.transformer_lm import page_lanes
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

    # a compile for a described chip cannot be read back from the
    # persistent cache and warns when it tries
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
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
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


def test_mellum_kernels_compile_for_a_described_v5e_at_the_served_shape(
        compiled_kernels, described_v5e):
    """The kernels PR 30 brought, through Mosaic at the widths of
    ``perf/configs/mellum2-12b-a2b5-paged.json``: the expert products over
    the stacked leaves (8 x 64 experts of 2304 x 896: whole (C, F) blocks
    need the raised VMEM limit, which only this compile checks) for a
    chunk's 128 rows and a decode step's 64, with no copy of a leaf around
    them; and the window group's write and read (6 layers, 576 pages of
    128, a grid that may have no step)."""
    from jax.experimental.compilation_cache import compilation_cache
    from deepspeed_tpu.moe.routed_ffn import routed_ffn
    from deepspeed_tpu.ops.attention.paged_attention import (
        paged_decode_attention, paged_write_columns)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=described_v5e)

    bf16 = jnp.bfloat16
    L, E, C, F = 8, 64, 2304, 896
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
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
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


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
    from jax.experimental.compilation_cache import compilation_cache
    from deepspeed_tpu.ops.attention import power_retention as pr

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=described_v5e)

    bf16, i32 = jnp.bfloat16, jnp.int32
    L, R, KV, H, d = 8, 16, 8, 40, 128
    leaf = shape((L, R, KV) + pr.state_shape(d))
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
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
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


def test_latent_kernels_compile_for_a_described_v5e_at_the_served_shape(
        compiled_kernels, described_v5e):
    """The kernels PR 38 brought, through Mosaic at the widths of
    ``perf/configs/moonlight-16b-a3b-mla.json``: the latent pool's leaf (7
    layers x 3,072 pages of 576 stored rows x 128 positions, 3.17 GB)
    written through ``paged_write``'s scale-leaf form and read by
    ``mla_decode`` (64 slots, 16 heads' rows of one token) and ``mla_chunk``
    (one slot, a chunk's 2,048 query-head rows in one call: ~22 MB of VMEM,
    which passes only under the raised limit). The leaf goes in and comes
    out in one buffer: no operation of the program copies or slices it."""
    from jax.experimental.compilation_cache import compilation_cache
    from deepspeed_tpu.ops.attention.latent_attention import latent_attention
    from deepspeed_tpu.ops.attention.paged_attention import \
        paged_write_columns

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=described_v5e)

    L, P, W, ps, H, per_slot = 7, 3072, 576, 128, 16, 64
    leaf = shape((L, P, W, ps))

    def step(q, leaf, table, starts, layer, cols):
        leaf = paged_write_columns(leaf, layer, cols, table, starts,
                                   page_size=ps)
        return latent_attention(q, leaf, table, starts, layer=layer,
                                rank=512, scale=192 ** -0.5,
                                page_size=ps), leaf

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        for name, B, T in (("mla_decode", 64, 1), ("mla_chunk", 1, 128)):
            compiled = jax.jit(step, donate_argnums=1).lower(
                shape((B, T, H, W)), leaf, shape((B, per_slot), jnp.int32),
                shape((B,), jnp.int32), shape((), jnp.int32),
                shape((B, W, T))).compile()
            text = compiled.as_text()
            assert name in text and "paged_write" in text
            assert text.count("tpu_custom_call") >= 2
            assert "may-alias" in text
            # the leaf is 3.17 GB: a copy or a slice of it would show
            assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 24
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


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


def _small_paged_server(pages=6):
    """A two-layer GPT-NeoX server over a page pool with the kernel on, and
    the operands of its three step programs. ``max_seq_len`` is 384, a
    width nothing else in the model has."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.transformer_lm import (TransformerLM,
                                                     transformer_config)
    from deepspeed_tpu.inference.engine import pack_chunk_args
    from deepspeed_tpu.serving.paged_pool import PagedKVPool

    cfg = transformer_config("gpt-neox", vocab_size=128, max_seq_len=384,
                             n_embd=256, n_layer=2, n_head=2,
                             dtype=jnp.bfloat16)
    model = TransformerLM(cfg)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32),
                           method=model.logits)["params"])
    params = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, jnp.bfloat16), params)
    engine = ds.init_inference(model=model, model_parameters=params,
                               config={"dtype": "bf16"})
    engine._ensure_params(jnp.zeros((1, 2), jnp.int32))
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
    return pool, programs


def test_no_program_of_a_serving_step_passes_over_a_pool_leaf(
        compiled_kernels):
    """``kernel_decode``, ``paged_chunk`` and the admission program take the
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
# PR 35. Until then "eccfe7ce...aaff9", the program PR 31 measured (taken on
# commit 2a1c43f, still the text of PR 35's parent c3d4d53). PR 35's differs
# from it, once the numbers XLA gives its instructions are taken out, in 62
# lines, all of them the token operand: ``s32[2]`` where it was ``s32[2,1]``
# (the program takes the server's (B,) token twin and adds the axis itself),
# through the two fusions of the embedding lookup that read it. The positions
# operand is in neither text: this model's are rotary, made from the cache's
# index, so XLA had dropped the argument the host still put every step.
# Measured with it, `serve-pythia-1b4-chat` `gap_p90_ms`, parent / PR 35 at
# one seed a pair, one v5e chip (PERF.md §6, PR 35, call C1): 9.196 / 8.402
# and 9.132 / 8.206 ms; `decode_dev_ms_p50` 5.99 / 6.04 (a traced pair).
_KERNEL_DECODE_TEXT = (
    "4b94b6257df37087f22d38d8c575a6d3aa0297868123f58b280f53b5c13109c1")


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

    from jax.experimental.compilation_cache import compilation_cache
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

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = {
            name: jitted.lower(
                *jax.tree_util.tree_map(described, args)).compile()
            for name, (jitted, args) in programs.items()
            if name != "_paged_admit_rows"}
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
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
