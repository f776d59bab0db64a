#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives both normal entry points once, on the TPU, through the public API
(``import deepspeed_tpu as ds``), at the full width of the models the
benchmark uses, with random weights from a seed:

* **train** — ``ds.initialize`` + ``engine.train_batch`` on the flagship of
  rounds 1-5: GPT-2 350M (1024 x 24 x 16 heads, vocab 50257, seq 1024),
  bf16, ZeRO-2, Adam, ``remat_policy="dots"``, micro-batch 10 (gas cut to 2).
  A few steps on one repeated batch: first loss near ln(vocab), every loss
  finite, last below first; the compiled step must contain the Pallas flash
  kernels (``tpu_custom_call`` > 0).
* **serve** — ``ds.init_serving`` on ``TransformerLM`` with the ``gpt-neox``
  preset at Pythia-1.4B's published sizes (2048 x 24 x 16 heads of 128,
  vocab 50304, ctx 2048), bf16, 8 slots, ``paged_kv=True`` (kernel "auto"),
  default prefill chunk. A dozen requests through ``submit`` +
  ``run_until_drained``: every request gets the tokens it asked for,
  ``check_invariants()`` is clean, nothing compiles after warm-up, and the
  compiled decode step contains the paged-attention kernel. The same
  requests then run with ``paged_kv={"kernel": "off"}`` (the dense gather
  oracle) and the first decoded position's logits are compared.

On four chips (``jax.device_count() == 4``) the same script trains under
ZeRO-3 over ``data=4`` and checks that every device holds a quarter of the
state, and serves on the ``data=2, model=2`` mesh, comparing with a
one-device server in the same process.

Each phase is a child process; this parent never touches JAX, so it never
holds the chip a child needs. Stdout ends with two lines of JSON: the report
(versions, compile cache, and everything each phase found), and last the
verdict, ``{"ok": ..., "device": {"platform", "kind", "count"}}`` with
exactly those keys and the device as JAX reports it. The exit code is 0 only
if every phase passed on a TPU. With no TPU it exits non-zero within seconds
and prints no result. ``--rehearsal`` runs a tiny-shape copy on the CPU (to
debug the script before spending chip time); its report says
``"rehearsal": true`` and it can never print ``"ok": true``.

Seconds printed here are set-up facts for the log, not metrics.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
PHASES = ("train", "serve")
RESULT_TAG = "CHIP_SMOKE_PHASE_RESULT "
SEED = 0

EXIT_PHASE_FAILED = 1
EXIT_NO_ACCELERATOR = 4
EXIT_NO_PROGRAM = 5
# the whole script has 1200 s, compilation included
PHASE_TIMEOUT_S = {"train": 420, "serve": 720}

# -- sizes: published width and depth on the chip, a toy for the rehearsal --
# zero: every leaf of the toy is below ZeRO-3's default persistence
# threshold (1e5 elements stay replicated), which would leave the
# four-device rehearsal nothing to shard.
# flash: "auto" picks the Pallas kernel on a TPU from seq 1024 up; the toy
# forces it on (interpret mode) so the same kernel path runs on the CPU.
TRAIN_CHIP = dict(vocab_size=50257, seq=1024, n_embd=1024, n_layer=24,
                  n_head=16, micro_batch=10, gas=2, steps=4, zero={},
                  flash="auto")
TRAIN_REHEARSAL = dict(vocab_size=512, seq=128, n_embd=64, n_layer=2,
                       n_head=4, micro_batch=2, gas=2, steps=4,
                       zero={"stage3_param_persistence_threshold": 0},
                       flash=True)
SERVE_CHIP = dict(vocab_size=50304, max_seq_len=2048, n_embd=2048,
                  n_layer=24, n_head=16, num_slots=8, n_requests=12,
                  prompt_len=(32, 512), new_tokens=(32, 64))
SERVE_REHEARSAL = dict(vocab_size=512, max_seq_len=256, n_embd=64,
                       n_layer=2, n_head=4, num_slots=4, n_requests=6,
                       prompt_len=(8, 100), new_tokens=(4, 8))

# First loss of a randomly initialised LM: ln(vocab) + var(logit)/2. The
# tied head sums n_embd products of a unit-variance LayerNorm output and an
# embedding of variance 1/n_embd (flax ``nn.Embed``'s default), so the
# logits have variance 1 at any width and the first loss sits at
# ln(vocab) + 0.5 (measured on the chip: 11.325 = ln 50257 + 0.500).
FIRST_LOSS_OFFSET = 0.5
FIRST_LOSS_WINDOW = 0.25
# Logit tolerance between two arms that compute the same function in bf16
# with a different order of operations (page-blocked vs. dense softmax
# accumulation, all-reduce order under TP). bf16 keeps 8 bits of mantissa,
# so one rounding moves a value by 2**-8 of its size, and every layer
# rounds the attention output once; 24 layers can move a logit by a few
# such steps at the scale of the largest logit. 2**-5 (eight steps) allows
# that and is far below what a wrong page, mask or shard does, which moves
# logits by their whole scale.
LOGIT_REL_TOL = 2.0 ** -5
# ZeRO-3: every device holds 1/n of the state; leaves too small or too odd
# to split stay replicated, so allow a little over the exact share.
SHARD_SHARE_SLACK = 1.15
DEVICE_BYTES_SPREAD = 0.20


# ---------------------------------------------------------------------------
# child side: shared helpers (everything below imports JAX lazily)
# ---------------------------------------------------------------------------
def _open_device(rehearsal: bool) -> dict:
    """First touch of JAX in a child. Names what it found, and stops the
    run unless that is a TPU (or this is a rehearsal)."""
    import jax

    devices = jax.devices()
    first = devices[0]
    device = {"platform": first.platform, "kind": first.device_kind,
              "count": len(devices)}
    if first.platform != "tpu" and not rehearsal:
        print(f"chip_smoke: JAX found {len(devices)} device(s) of platform "
              f"{first.platform!r} (device_kind {first.device_kind!r}), not "
              f"a TPU. This check only passes on the chip; "
              f"`--rehearsal` runs a tiny copy on the CPU.",
              file=sys.stderr)
        sys.exit(EXIT_NO_ACCELERATOR)
    return device


def _versions() -> dict:
    from importlib import metadata

    out = {}
    for pkg in ("jax", "jaxlib", "libtpu", "flax", "optax"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def _cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def _memory(device=None) -> dict:
    """bytes_in_use / peak_bytes_in_use of one device (None on the CPU,
    which reports no memory stats)."""
    import jax

    stats = (device or jax.devices()[0]).memory_stats() or {}
    return {"bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


def _compiled_program_facts(jitted, *args) -> dict:
    """What the executable ``jitted`` compiles to for ``args`` contains:
    Mosaic (Pallas) kernels, and the collectives XLA put between devices.
    Lowering executes nothing and donates nothing; the compile is the one
    the call already made, found again in the compile cache."""
    text = jitted.lower(*args).compile().as_text()
    facts = {"tpu_custom_calls":
             text.count('custom_call_target="tpu_custom_call"')}
    collectives = {op: text.count(f" {op}(") for op in (
        "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
        "collective-permute")}
    facts["collectives"] = {k: v for k, v in collectives.items() if v}
    return facts


def _shard_bytes(tree) -> dict:
    """Total bytes of a pytree of arrays and the bytes each local device
    holds of it, from ``addressable_shards``."""
    import jax

    per_device = {d.id: 0 for d in jax.local_devices()}
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if not hasattr(leaf, "addressable_shards"):
            continue
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] += shard.data.nbytes
    return {"total_bytes": total,
            "per_device_bytes": [per_device[d.id]
                                 for d in jax.local_devices()]}


class _CompileRequests:
    """Counts compile requests while ``active``. The recompile watchdog
    listens for backend compiles, and a program found in a warm persistent
    cache is loaded without one — this event fires either way, so a warm
    cache cannot hide a program that first appears after warm-up."""

    EVENT = "/jax/compilation_cache/compile_requests_use_cache"

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if self.active and event == self.EVENT:
            self.count += 1


def _begin_phase(phase: str, rehearsal: bool) -> dict:
    """Open the device, place the compile cache, start the phase's record."""
    device = _open_device(rehearsal)

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    return {"phase": phase, "device": device, "versions": _versions(),
            "compile_cache": {"dir": cache_dir,
                              "entries_before": _cache_entries(cache_dir)}}


def _end_phase(out: dict, failures: list) -> dict:
    out["memory"] = _memory()
    out["compile_cache"]["entries_after"] = _cache_entries(
        out["compile_cache"]["dir"])
    out["failures"] = failures
    out["ok"] = not failures
    return out


# ---------------------------------------------------------------------------
# phase: train
# ---------------------------------------------------------------------------
def phase_train(rehearsal: bool, plant: bool) -> dict:
    out = _begin_phase("train", rehearsal)
    device = out["device"]
    failures = []

    import jax.numpy as jnp
    import numpy as np

    compile_requests = _CompileRequests()

    import deepspeed_tpu as ds
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    if plant:
        raise RuntimeError("planted failure in the train phase")
    size = TRAIN_REHEARSAL if rehearsal else TRAIN_CHIP
    n_dev = device["count"]
    # one chip: ZeRO-2, the flagship's own stage; several chips:
    # ZeRO-3 over the default all-data mesh, where sharding is the point
    stage = 3 if n_dev > 1 else 2
    cfg = GPT2Config(vocab_size=size["vocab_size"], n_positions=size["seq"],
                     n_embd=size["n_embd"], n_layer=size["n_layer"],
                     n_head=size["n_head"], dtype=jnp.bfloat16,
                     use_flash_attention=size["flash"],
                     remat=True, remat_policy="dots")
    config = {
        "train_micro_batch_size_per_gpu": size["micro_batch"],
        "gradient_accumulation_steps": size["gas"],
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": stage, **size["zero"]},
        "optimizer": {"type": "Adam",
                      "params": {"lr": 3e-4, "weight_decay": 0.1}},
        "gradient_clipping": 1.0,
        "steps_per_print": 1000000,
    }
    out["config"] = {**size, "zero_stage": stage, "dtype": "bfloat16",
                     "remat_policy": "dots"}

    t0 = time.perf_counter()
    engine, _, _, _ = ds.initialize(model=GPT2LMHeadModel(cfg), config=config)
    batch = {"input_ids": np.random.default_rng(SEED).integers(
        0, cfg.vocab_size,
        (engine.train_batch_size(), size["seq"])).astype(np.int32)}
    losses = [float(engine.train_batch(batch=batch))]   # compiles
    out["setup_s"] = round(time.perf_counter() - t0, 1)

    compile_requests.active = True
    t0 = time.perf_counter()
    for _ in range(size["steps"] - 1):
        losses.append(float(engine.train_batch(batch=batch)))
    out["run_s"] = round(time.perf_counter() - t0, 2)
    compile_requests.active = False
    # reported, not failed: a step that compiles again after the first
    # (new input placement) shows here and explains a long run_s
    out["compile_requests_after_first_step"] = compile_requests.count
    out["losses"] = [round(x, 4) for x in losses]
    out["params_m"] = round(engine.num_parameters / 1e6, 1)

    expect = math.log(cfg.vocab_size) + FIRST_LOSS_OFFSET
    out["first_loss_expected"] = round(expect, 4)
    if not all(math.isfinite(x) for x in losses):
        failures.append(f"non-finite loss in {losses}")
    if abs(losses[0] - expect) > FIRST_LOSS_WINDOW:
        failures.append(f"first loss {losses[0]:.3f} not within "
                        f"{FIRST_LOSS_WINDOW} of ln(vocab)+"
                        f"{FIRST_LOSS_OFFSET}={expect:.3f}")
    if not losses[-1] < losses[0]:
        failures.append(f"loss did not fall: {losses}")

    # the compiled step the calls above ran (engine internals: there is no
    # public handle on the executable)
    out.update(_compiled_program_facts(
        engine._jit_train_batch, engine.state,
        engine._stack_micro_batches(batch)))
    # one forward kernel and at least one backward kernel (3 on the v5e:
    # forward, dq, dk/dv)
    if device["platform"] == "tpu" and out["tpu_custom_calls"] < 2:
        failures.append(f"compiled train step holds "
                        f"{out['tpu_custom_calls']} tpu_custom_call(s): "
                        f"flash forward and backward were not both "
                        f"compiled for the chip")

    if n_dev > 1:
        out["sharding"] = _check_zero3_shards(engine, n_dev, failures)

    return _end_phase(out, failures)


def _check_zero3_shards(engine, n_dev: int, failures: list) -> dict:
    """ZeRO-3 over ``data=n``: every device holds about 1/n of the bf16
    parameters, the fp32 master copy and the optimizer moments (gradients
    live only inside the compiled step, so the per-device memory spread
    below is what covers them), and no device carries the others' share."""
    import jax

    report = {}
    for name in ("params", "master", "opt_state"):
        part = _shard_bytes(engine.state[name])
        report[name] = part
        share = part["total_bytes"] / n_dev * SHARD_SHARE_SLACK
        worst = max(part["per_device_bytes"])
        if part["total_bytes"] and worst > share:
            failures.append(
                f"ZeRO-3 {name}: a device holds {worst} of "
                f"{part['total_bytes']} bytes, more than 1/{n_dev} "
                f"(+{SHARD_SHARE_SLACK - 1:.0%})")
    in_use = [_memory(d)["bytes_in_use"] for d in jax.local_devices()]
    report["bytes_in_use_per_device"] = in_use
    if all(b is not None for b in in_use) and \
            max(in_use) - min(in_use) > DEVICE_BYTES_SPREAD * max(in_use):
        failures.append(f"per-device bytes_in_use differ by more than "
                        f"{DEVICE_BYTES_SPREAD:.0%}: {in_use}")
    return report


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------
def _workload(size: dict, token_seed: int):
    """Seeded prompts and token budgets. Lengths and budgets are the same
    for every ``token_seed`` (the shapes a warm-up must cover); only the
    tokens differ. Both ends of the length range are always present: a
    one-bucket prompt and a chunk-looped one."""
    import numpy as np

    shape_rng = np.random.default_rng(SEED)
    lo, hi = size["prompt_len"]
    lengths = shape_rng.integers(lo, hi + 1, size["n_requests"])
    lengths[0], lengths[1] = lo, hi
    budgets = [int(b) for b in shape_rng.integers(
        size["new_tokens"][0], size["new_tokens"][1] + 1,
        size["n_requests"])]
    token_rng = np.random.default_rng(token_seed)
    prompts = [token_rng.integers(1, size["vocab_size"], int(n))
               .astype(np.int32) for n in lengths]
    return prompts, budgets


def _make_logit_tap():
    """The engine hands every decode step's logits and live slots to its
    fault injector; an injector that injects nothing and keeps the first
    decode step of each request is a logit tap on the public API."""
    from deepspeed_tpu.serving import RequestState
    from deepspeed_tpu.serving.resilience import FaultInjector

    class LogitTap(FaultInjector):
        def __init__(self):
            super().__init__(seed=SEED)
            self.requests = []
            self.first = {}      # request_id -> (step logits, slot)

        def watch(self, requests) -> None:
            self.requests = list(requests)
            self.first = {}

        def corrupt_logits(self, logits, rows):
            live = set(rows)
            for req in self.requests:
                if req.request_id not in self.first and req.slot in live \
                        and req.state is RequestState.RUNNING:
                    # a reference to the step's array: no device work here
                    self.first[req.request_id] = (logits, req.slot)
            return logits, None

        def first_logits(self):
            import numpy as np

            return {rid: np.asarray(step, np.float32)[slot]
                    for rid, (step, slot) in self.first.items()}

    return LogitTap()


def _run_arm(model, params, size: dict, paged_kv, mesh,
             compile_requests: _CompileRequests, on_tpu: bool) -> dict:
    """Build one server, warm it with traffic of the measured shape (other
    tokens, so the prefix cache cannot turn the measured pass into hits),
    then run the measured requests."""
    import jax.numpy as jnp

    import deepspeed_tpu as ds
    from deepspeed_tpu.serving import RequestState

    arm = {"paged_kv": paged_kv}
    failures = []
    tap = _make_logit_tap()
    mem0 = _memory()["bytes_in_use"]

    t0 = time.perf_counter()
    kwargs = {} if mesh is None else {"mesh": mesh}
    srv = ds.init_serving(model, model_parameters=params, dtype="bf16",
                          num_slots=size["num_slots"], paged_kv=paged_kv,
                          fault_injector=tap, **kwargs)
    pool = srv.pool
    arm["kernel_active"] = bool(pool.kernel_active)
    arm["page_size"] = int(pool.page_size)
    arm["num_pages"] = int(pool.num_pages)
    arm["prefill_chunk"] = int(srv.prefill_chunk)

    def drive(token_seed: int):
        prompts, budgets = _workload(size, token_seed)
        reqs = [srv.submit(p, max_new_tokens=b)
                for p, b in zip(prompts, budgets)]
        tap.watch(reqs)
        srv.run_until_drained(max_steps=20_000)
        srv.check_invariants()
        return reqs, budgets

    drive(SEED + 1)                   # warm-up: same lengths, other tokens
    srv.end_warmup()
    arm["setup_s"] = round(time.perf_counter() - t0, 1)

    compile_requests.count = 0
    compile_requests.active = True
    t0 = time.perf_counter()
    reqs, budgets = drive(SEED)
    arm["run_s"] = round(time.perf_counter() - t0, 2)
    compile_requests.active = False

    short = [(r.request_id, r.state.value, len(r.output_tokens), b)
             for r, b in zip(reqs, budgets)
             if r.state is not RequestState.FINISHED
             or len(r.output_tokens) != b]
    if short:
        failures.append(f"requests (id, state, tokens, asked) incomplete: "
                        f"{short}")
    arm["requests"] = len(reqs)
    arm["tokens_out"] = sum(len(r.output_tokens) for r in reqs)
    arm["steps"] = int(srv.step_id)
    arm["recompiles_after_warmup"] = int(srv.watchdog.recompiles)
    arm["compile_requests_after_warmup"] = compile_requests.count
    if arm["recompiles_after_warmup"] or compile_requests.count:
        late = [(e["program"], e["signature"]) for e in srv.watchdog.events
                if not e["warmup"]]
        failures.append(
            f"compiled after warm-up: watchdog {srv.watchdog.recompiles}, "
            f"compile requests {compile_requests.count}: {late}")

    arm["outputs"] = [list(r.output_tokens) for r in reqs]
    first = tap.first_logits()
    if sorted(first) != sorted(r.request_id for r in reqs):
        failures.append("the logit tap missed a request's first decode step")
    arm["first_logits"] = [first.get(r.request_id) for r in reqs]

    # the compiled decode step (pool internals: no public handle exists)
    decode_jit = pool._paged_decode_kernel_jit or pool._paged_decode_jit
    arm.update(_compiled_program_facts(
        decode_jit, srv.engine.params, pool.cache["cache_store"],
        jnp.zeros((size["num_slots"],), jnp.int32)))
    if on_tpu and arm["kernel_active"] and arm["tpu_custom_calls"] == 0:
        failures.append("compiled paged decode step holds no "
                        "tpu_custom_call: the paged kernel was not compiled")

    arm["pool"] = _pool_report(srv, mesh, mem0, failures)
    arm["memory"] = _memory()
    arm["failures"] = failures
    # the server is a web of callbacks that refer back to it; collect it
    # now so its pool is gone before the next arm allocates one
    del srv, pool, tap, decode_jit
    gc.collect()
    return arm


def _pool_report(srv, mesh, mem_before, failures: list) -> dict:
    """K/V pool bytes as the shapes say and as the device holds them (is the
    64-wide page padded to 128 lanes in HBM?), and, on a mesh, whether each
    leaf still carries the placement the axis-rules table gives it."""
    import jax
    import jax.numpy as jnp

    store = srv.pool.cache["cache_store"]
    report = {"leaves": {k: [list(v.shape), str(v.dtype)]
                         for k, v in store.items()},
              "logical_bytes": sum(v.nbytes for v in store.values()),
              "params_bytes": sum(
                  x.nbytes for x in jax.tree_util.tree_leaves(
                      srv.engine.params)),
              "bytes_in_use_growth_since_before_server": None}
    now = _memory()["bytes_in_use"]
    if now is not None and mem_before is not None:
        report["bytes_in_use_growth_since_before_server"] = now - mem_before
        # a small array with the K pool's minor dims and dtype: what the
        # device charges for it over what its shape says
        k = store["k"]
        lead = max(1, (64 << 20) // (k.nbytes // k.shape[0]))
        probe = jnp.zeros((min(lead, k.shape[0]),) + k.shape[1:], k.dtype)
        probe.block_until_ready()
        charged = _memory()["bytes_in_use"] - now
        report["layout_probe"] = {
            "shape": list(probe.shape), "dtype": str(probe.dtype),
            "logical_bytes": probe.nbytes, "device_bytes": charged,
            "padding_factor": round(charged / probe.nbytes, 3)}
        del probe
    if mesh is not None and mesh.devices.size > 1:
        from deepspeed_tpu.parallel.axis_rules import cache_leaf_sharding

        expect = cache_leaf_sharding("paged", mesh=mesh)
        placed = {}
        for key, leaf in store.items():
            want = expect(key, leaf)
            placed[key] = str(leaf.sharding.spec)
            if not leaf.sharding.is_equivalent_to(want, leaf.ndim):
                failures.append(f"pool leaf {key!r} is placed "
                                f"{leaf.sharding.spec}, the axis rules say "
                                f"{want.spec}")
        report["placement"] = placed
        report["shards"] = _shard_bytes(store)
    return report


def _compare_arms(ref: dict, other: dict, label: str, failures: list) -> dict:
    """max |delta logit| at each request's first decoded position and
    whether the greedy tokens are equal. A difference above the bf16
    tolerance fails; unequal tokens alone are reported, not failed (with
    random weights the top logits are nearly tied, and one flipped argmax
    changes everything after it)."""
    import numpy as np

    deltas, scale = [], 0.0
    for a, b in zip(ref["first_logits"], other["first_logits"]):
        if a is None or b is None:
            continue
        deltas.append(float(np.max(np.abs(a - b))))
        scale = max(scale, float(np.max(np.abs(a))))
    tol = LOGIT_REL_TOL * max(1.0, scale)
    out = {"max_abs_dlogit_per_request": [round(d, 6) for d in deltas],
           "max_abs_dlogit": max(deltas) if deltas else None,
           "max_abs_logit": round(scale, 4), "tolerance": round(tol, 6),
           "first_logits_bitwise_equal": bool(deltas) and max(deltas) == 0.0,
           "greedy_tokens_equal": ref["outputs"] == other["outputs"]}
    if not deltas or max(deltas) > tol:
        failures.append(f"{label}: first-decode logits differ by "
                        f"{out['max_abs_dlogit']} (tolerance {tol:.4g})")
    return out


def phase_serve(rehearsal: bool, plant: bool) -> dict:
    out = _begin_phase("serve", rehearsal)
    device = out["device"]
    failures = []

    import jax
    import jax.numpy as jnp

    compile_requests = _CompileRequests()

    from deepspeed_tpu.models.lm_config import transformer_config
    from deepspeed_tpu.models.transformer_lm import TransformerLM
    from deepspeed_tpu.parallel import initialize_mesh

    if plant:
        raise RuntimeError("planted failure in the serve phase")
    size = SERVE_REHEARSAL if rehearsal else SERVE_CHIP
    on_tpu = device["platform"] == "tpu"
    n_dev = device["count"]
    dims = {k: size[k] for k in ("vocab_size", "max_seq_len", "n_embd",
                                 "n_layer", "n_head")}
    model = TransformerLM(transformer_config("gpt-neox", dtype=jnp.bfloat16,
                                             **dims))
    out["config"] = {**size, "family": "gpt-neox", "dtype": "bfloat16"}

    # random weights, cast to bf16 inside the initialising program so the
    # float32 tree (5.6 GB at 1.4B) never sits beside its bf16 copy
    def init_params():
        tree = model.init({"params": jax.random.PRNGKey(SEED)},
                          jnp.zeros((1, 8), jnp.int32),
                          method=model.logits)["params"]
        return jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)

    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(init_params)())
    out["params_m"] = round(sum(
        x.size for x in jax.tree_util.tree_leaves(params)) / 1e6, 1)
    out["init_params_s"] = round(time.perf_counter() - t0, 1)
    out["memory_after_init"] = _memory()

    # the page size defaults to the prefill chunk (64). On the chip the
    # kernel is left at "auto"; the rehearsal forces it on (interpret
    # mode) so the same plumbing runs on the CPU
    kernel_arm = True if on_tpu else {"kernel": "on"}
    # the oracle arm ({"kernel": "off"}) gathers the pages to a dense view
    # and runs the dense decode path at its default block. Pinning that
    # block to one page (decode_block=64), which paged_attention.py names
    # as the bitwise twin, does not lower on the chip: the dense kernel's
    # K/V block puts the block on the lane axis and Mosaic wants a
    # multiple of 128 there (first chip run, PR 21). So the two arms block
    # the softmax differently and are compared within the bf16 tolerance.
    mesh = initialize_mesh(data=2, model=2) if n_dev == 4 else None
    out["mesh"] = None if mesh is None else {"data": 2, "model": 2}

    arms = {}
    arms["kernel"] = _run_arm(model, params, size, kernel_arm, mesh,
                              compile_requests, on_tpu)
    arms["dense_oracle"] = _run_arm(model, params, size, {"kernel": "off"},
                                    mesh, compile_requests, on_tpu)
    out["kernel_vs_dense_oracle"] = _compare_arms(
        arms["dense_oracle"], arms["kernel"], "kernel vs dense oracle",
        failures)
    if mesh is not None:
        one = initialize_mesh(devices=jax.devices()[:1])
        arms["kernel_one_device"] = _run_arm(
            model, params, size, kernel_arm, one, compile_requests, on_tpu)
        out["mesh_vs_one_device"] = _compare_arms(
            arms["kernel_one_device"], arms["kernel"],
            "data=2,model=2 vs one device", failures)

    for name, arm in arms.items():
        failures.extend(f"{name}: {f}" for f in arm.pop("failures"))
        arm.pop("first_logits")
        arm.pop("outputs")
    out["arms"] = arms
    out["setup_s"] = round(sum(a["setup_s"] for a in arms.values())
                           + out["init_params_s"], 1)
    out["run_s"] = round(sum(a["run_s"] for a in arms.values()), 2)
    out["tpu_custom_calls"] = arms["kernel"]["tpu_custom_calls"]
    return _end_phase(out, failures)


# ---------------------------------------------------------------------------
# parent side: no JAX here
# ---------------------------------------------------------------------------
def _kill_group(proc: subprocess.Popen) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass


def _run_phase(phase: str, args, env: dict) -> dict:
    """Run one phase as a child in its own process group, pass its output
    through to stderr, pick up its result line, and leave nothing of it
    running."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase]
    if args.rehearsal:
        cmd.append("--rehearsal")
    if args.plant_failure == phase:
        cmd += ["--plant-failure", phase]
    result = None
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True, env=env,
                            cwd=HERE, start_new_session=True)
    timer = threading.Timer(PHASE_TIMEOUT_S[phase], _kill_group, [proc])
    timer.start()
    try:
        for line in proc.stdout:
            if line.startswith(RESULT_TAG):
                result = json.loads(line[len(RESULT_TAG):])
            else:
                sys.stderr.write(line)
        code = proc.wait()
    finally:
        timer.cancel()
        _kill_group(proc)
    if result is None:
        result = {"phase": phase, "ok": False,
                  "failures": [f"no result; exit code {code} (killed at "
                               f"{PHASE_TIMEOUT_S[phase]} s if -9)"]}
    result["exit_code"] = code
    result["ok"] = bool(result.get("ok")) and code == 0
    return result


def parent(args) -> int:
    if not os.path.isdir(os.path.join(HERE, "deepspeed_tpu")):
        print(f"chip_smoke: no deepspeed_tpu package beside {__file__}; "
              f"there is no program here to check.", file=sys.stderr)
        return EXIT_NO_PROGRAM
    env = dict(os.environ)
    if args.rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
        if args.rehearsal_devices > 1:
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "") +
                f" --xla_force_host_platform_device_count="
                f"{args.rehearsal_devices}").strip()

    phases = {}
    for phase in PHASES:
        phases[phase] = _run_phase(phase, args, env)
        if phases[phase]["exit_code"] == EXIT_NO_ACCELERATOR:
            return EXIT_NO_ACCELERATOR      # the child said what it found

    passed = all(p["ok"] for p in phases.values())
    # a phase that died before its result line names no device
    device = next((p["device"] for p in phases.values() if "device" in p),
                  {"platform": "unknown", "kind": "unknown", "count": 0})
    on_tpu = device["platform"] == "tpu"
    verdict = {"ok": passed and on_tpu and not args.rehearsal,
               "device": device}
    report = {
        **verdict,
        "versions": next((p.pop("versions") for p in phases.values()
                          if "versions" in p), None),
        "compile_cache": {
            "dir": phases["train"].get("compile_cache", {}).get("dir"),
            "entries_before": phases["train"].get(
                "compile_cache", {}).get("entries_before"),
            "entries_after": phases["serve"].get(
                "compile_cache", {}).get("entries_after")},
    }
    if args.rehearsal:
        report["rehearsal"] = True
        report["rehearsal_passed"] = passed
    for phase, result in phases.items():
        result.pop("device", None)
        result.pop("versions", None)
        report[phase] = result
    print(json.dumps(report))
    # the last line is the verdict and nothing else: the driver reads it
    print(json.dumps(verdict), flush=True)
    if args.rehearsal:
        return 0 if passed else EXIT_PHASE_FAILED
    return 0 if verdict["ok"] else EXIT_PHASE_FAILED


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny shapes on the CPU; never prints the pass line")
    ap.add_argument("--rehearsal-devices", type=int, default=1,
                    help="forced host devices for the rehearsal (4 rehearses "
                         "the four-chip path)")
    ap.add_argument("--plant-failure", choices=PHASES,
                    help="raise inside that phase (rehearsal only): proves "
                         "a failing phase fails the run")
    ap.add_argument("--phase", choices=PHASES, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if (args.plant_failure or args.rehearsal_devices != 1) \
            and not args.rehearsal:
        ap.error("--plant-failure and --rehearsal-devices need --rehearsal")
    if args.phase is None:
        return parent(args)
    run = {"train": phase_train, "serve": phase_serve}[args.phase]
    result = run(args.rehearsal, args.plant_failure == args.phase)
    print(RESULT_TAG + json.dumps(result), flush=True)
    return 0 if result["ok"] else EXIT_PHASE_FAILED


if __name__ == "__main__":
    sys.exit(main())
