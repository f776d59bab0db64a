#!/usr/bin/env python3
"""Compile a training cell's step at its real size for a described TPU v5e,
without the chip, and print the compiler's memory report per device.

    python3 perf/tools/aot_compile.py --workload train-gpt2-xl-zero3-x4 \
        [--micro 2 --gas 4 --remat-policy full]

A builder's instrument, run by hand before chip time is spent: what the
chip's compiler refuses here (a program that does not fit 16 GB, a kernel
that cannot be partitioned) costs no chip time. It is NOT a measurement:
nothing runs, and what it prints is the compiler's estimate for ONE program,
not what else the process keeps on the device.

It describes the topology at its top level (``get_topology_desc`` loads the
TPU's library into this process), so it must never be imported by a test.
It reaches into the engine (policy, optimizer definition, ``_build_jits``)
to hand it shapes where ``_build_state`` would place arrays: a scratch
script in the sense of the on-chip-measurement guide, section 2.3.
Serving cells are not covered: the server places real arrays as it is
built (PERF.md, Open questions)."""

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402

TOPOLOGY = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--micro", type=int)
    ap.add_argument("--gas", type=int)
    ap.add_argument("--remat-policy")
    args = ap.parse_args()

    import deepspeed_tpu as ds
    from deepspeed_tpu.ops import backend
    from deepspeed_tpu.parallel import initialize_mesh
    from jax.sharding import NamedSharding, PartitionSpec
    from perf import build
    from perf.manifest import Manifest

    # the program asks the backend whether it is on a TPU; here it is
    # compiled FOR one, so answer for the target (in this script only)
    backend.on_tpu = lambda: True
    backend.pallas_interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)

    manifest = Manifest(ROOT)
    entry = manifest.workload(args.workload)
    config, cell = manifest.config(entry["config"]), manifest.cell(
        args.workload)
    traffic = manifest.traffic(entry["traffic"])
    if config["entry"] != "train":
        print("aot_compile.py covers training cells only", file=sys.stderr)
        return 2
    chips = int(entry["chips"])
    micro = args.micro or cell["split"]["micro_batch_per_chip"]
    gas = args.gas or cell["split"]["gradient_accumulation_steps"]
    overrides = dict(cell.get("model_overrides", {}))
    if args.remat_policy:
        overrides["remat_policy"] = args.remat_policy
    seq = traffic["params"]["seq_len"]

    mesh = initialize_mesh(devices=TOPOLOGY.devices[:chips],
                           **config["mesh"])
    model, _ = build.build_model(config["model"], overrides)
    engine_config = dict(config["engine"],
                         train_micro_batch_size_per_gpu=micro,
                         gradient_accumulation_steps=gas)
    engine, _, _, _ = ds.initialize(model=model, config=engine_config,
                                    mesh=mesh)

    def init():
        key = jax.random.PRNGKey(0)
        return model.init({"params": key, "dropout": key},
                          {"input_ids": jnp.zeros((1, seq), jnp.int32)}
                          )["params"]

    shapes = jax.eval_shape(init)

    def like(tree, dtype=None):        # arrays of no memory, for np.shape
        return jax.tree_util.tree_map(
            lambda s: np.broadcast_to(np.zeros((), dtype or s.dtype),
                                      s.shape), tree)

    policy = engine.policy
    params_like = like(shapes, jnp.bfloat16)
    master_like = like(shapes)
    opt_shapes = jax.eval_shape(engine.optimizer_def.init, shapes)
    param_sh = policy.param_shardings(params_like)
    master_sh = policy.master_shardings(master_like)
    opt_sh = policy.opt_state_shardings(like(opt_shapes), master_like)
    rep = NamedSharding(mesh, PartitionSpec())
    engine._shardings = {"params": param_sh, "master": master_sh,
                         "opt_state": opt_sh, "step": rep, "opt_step": rep,
                         "scale": None, "rng": rep}
    engine._build_jits()

    def abstract(tree, shardings, dtype=None):
        return jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, dtype or s.dtype,
                                               sharding=sh), tree, shardings)

    state = {"params": abstract(shapes, param_sh, jnp.bfloat16),
             "master": abstract(shapes, master_sh),
             "opt_state": abstract(opt_shapes, opt_sh),
             "step": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
             "opt_step": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
             "scale": None,
             "rng": jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)}
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (gas, micro * chips, seq), jnp.int32,
        sharding=engine._batch_leaf_sharding(3, scan_dim=True))}
    compiled = engine._jit_train_batch.lower(state, batch).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    report = {
        "aot_compile_not_a_measurement": True,
        "workload": args.workload, "chips": chips, "micro": micro,
        "gas": gas, "model_overrides": overrides,
        "per_device_bytes": {
            "arguments": mem.argument_size_in_bytes,
            "outputs": mem.output_size_in_bytes,
            "aliased": mem.alias_size_in_bytes,
            "temporaries": mem.temp_size_in_bytes,
            "program": mem.generated_code_size_in_bytes,
            "total_live_estimate": mem.argument_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes
            + mem.temp_size_in_bytes},
        "tpu_custom_calls": text.count(
            'custom_call_target="tpu_custom_call"'),
        "collectives": {op: text.count(f" {op}(") for op in (
            "all-gather", "all-gather-start", "all-reduce",
            "all-reduce-start", "reduce-scatter", "collective-permute",
            "all-to-all") if text.count(f" {op}(")},
    }
    print(json.dumps(report, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
