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


@pytest.mark.parametrize("head_dim", [64, 128])
def test_flash_forward_and_backward_lower_for_tpu(compiled_kernels, head_dim):
    from deepspeed_tpu.ops.attention.flash_attention import flash_attention

    q = _rand((1, 1024, 2, head_dim))
    fwd = _tpu_custom_calls(flash_attention, q, q, q)
    assert fwd >= 1

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    both = _tpu_custom_calls(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert both > fwd      # the backward kernels are custom calls too


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
