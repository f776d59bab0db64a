"""ZeRO-3 gathers a layer's weights where they are used.

The four-chip training cell's rehearsal model (``perf/configs/
gpt2-xl-zero3.json``, ``rehearsal_kwargs``) under stage 3 on ``data=4`` of
the suite's host devices: what the compiled step holds (no activation
crosses the ``data`` axis, every ZeRO-sharded kernel of a block is gathered
inside the layer loop, no gathered weight is kept for the backward), what
the counter says, that the numbers are stage 2's, and that below stage 3 or
on one chip nothing is traced at all.
"""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu as ds
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.parallel import initialize_mesh, zero_policy_scope
from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig
from deepspeed_tpu.runtime.zero.policy import ShardingRules, ZeroShardingPolicy

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))
SEQ = 128
MICRO = 2
# the rehearsal's kernels are [2, 64, 64..256] stacked: over this, and its
# biases and norms ([2, 64..256]) under it, as at the real widths
THRESHOLD = 1000
KERNELS = ("attn/qkv", "attn/proj", "mlp/fc", "mlp/proj")


def _config():
    with open(os.path.join(ROOT, "perf/configs/gpt2-xl-zero3.json")) as f:
        return json.load(f)


def _engine(stage, data=4, remat="full", dtype="bfloat16", n_layer=None,
            use_site=True, monkeypatch=None):
    cell = _config()
    kwargs = dict(cell["model"]["rehearsal_kwargs"], dtype=getattr(jnp, dtype),
                  remat=remat != "off")
    if remat != "off":
        kwargs["remat_policy"] = remat
    if n_layer:
        kwargs["n_layer"] = n_layer
    if not use_site:    # the program of before: the scan body finds no policy
        monkeypatch.setattr(gpt2, "get_zero_policy", lambda: None)
    model = gpt2.GPT2LMHeadModel(gpt2.GPT2Config(**kwargs))
    mesh = initialize_mesh(devices=jax.devices()[:data], data=data)
    key = jax.random.PRNGKey(0)
    params = model.init({"params": key, "dropout": key},
                        {"input_ids": jnp.zeros((1, SEQ), jnp.int32)})["params"]
    config = dict(cell["engine"], train_micro_batch_size_per_gpu=MICRO,
                  gradient_accumulation_steps=1,
                  zero_optimization={
                      "stage": stage,
                      "stage3_param_persistence_threshold": THRESHOLD})
    config["bf16"] = {"enabled": dtype == "bfloat16"}
    engine, _, _, _ = ds.initialize(model=model, model_parameters=params,
                                    config=config, mesh=mesh)
    return engine


def _batch(engine, step=0):
    rng = np.random.default_rng(step)
    n = MICRO * engine.dp_world_size
    return {"input_ids": rng.integers(0, 512, (n, SEQ)).astype(np.int32)}


def _compiled_step(engine):
    stacked = jax.tree_util.tree_map(lambda x: x[None], _batch(engine))
    return engine._jit_train_batch.lower(engine.state, stacked).compile()


def _computations(text):
    """HLO text → {computation name: its lines}."""
    out, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            name = head.group(1)
            out[name] = []
        elif name is not None:
            out[name].append(line)
    return out


def _loop_lines(text):
    """Every line of the while loops' bodies and of what they call."""
    comps = _computations(text)
    todo = re.findall(r"body=%?([\w.\-]+)", text)
    seen, lines = set(), []
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        lines += comps[name]
        for line in comps[name]:
            todo += re.findall(
                r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", line)
    return lines


def _result_dims(line):
    m = re.search(r"= \(?\w+\[([\d,]*)\]", line)
    return tuple(int(d) for d in m.group(1).split(",") if d)


def _collectives(lines, op):
    return [_result_dims(ln) for ln in lines
            if re.search(rf"\s{op}(?:-start)?\(", ln)]


def _moved_activations(text):
    """All-to-alls over an activation: [micro.., seq, features..]."""
    return [d for d in _collectives(text.splitlines(), "all-to-all")
            if len(d) >= 3 and SEQ in d]


def _block_kernels(engine):
    block = engine.state["params"]["blocks"]["block"]
    return {k: _get(block, k)["kernel"] for k in KERNELS}


def _get(tree, path):
    for key in path.split("/"):
        tree = tree[key]
    return tree


# "full" keeps the attention kernel's named (out, lse) since PR 61: two
# activations of a chip's own sequences and no weight, so what is counted
# here (gathers inside the loop, no gathered weight kept, no activation
# moved) is unchanged; the rehearsal's SEQ runs XLA's attention on the CPU,
# which names nothing, so this program is the one of before.
@pytest.mark.parametrize("remat", ["off", "full"])
def test_the_step_gathers_weights_and_moves_no_activation(remat):
    engine = _engine(3, remat=remat)
    text = _compiled_step(engine).as_text()
    kernels = _block_kernels(engine)

    # (a) no activation crosses the data axis
    assert not _moved_activations(text)

    # (b) each ZeRO-sharded kernel is gathered whole inside the layer loop
    gathered = {d[-2:] for d in _collectives(_loop_lines(text), "all-gather")}
    for name, leaf in kernels.items():
        assert not leaf.sharding.is_fully_replicated, name
        assert leaf.shape[1:] in gathered, (name, leaf.shape, gathered)

    # (c) the counter: those leaves, and their bytes over the layers
    policy = engine.policy
    assert policy.use_site_gathers == len(kernels)
    assert policy.use_site_gather_bytes == sum(
        int(x.nbytes) for x in kernels.values())
    assert f"use_site_gathers={len(kernels)}" in policy.describe()
    state = [e for e in engine.tracer.events()
             if e["name"] == "setup/build_state"][-1]
    assert state["args"]["use_site_gathers"] == len(kernels)
    assert state["args"]["use_site_gather_bytes"] == \
        policy.use_site_gather_bytes


def test_without_the_call_the_partitioner_moves_the_activation(monkeypatch):
    """The control of (a): the same step with the scan body's call taken
    out reshards an activation, so (a) can see what it says is gone."""
    engine = _engine(3, use_site=False, monkeypatch=monkeypatch)
    assert _moved_activations(_compiled_step(engine).as_text())


@pytest.mark.parametrize("dtype,rtol,atol", [("float32", 1e-4, 1e-5),
                                             ("bfloat16", 2e-2, 1e-3)])
def test_three_steps_are_stage_twos(dtype, rtol, atol, users_compiles):
    """Stage 2 on the same mesh gathers nothing: same losses, same updated
    master parameters, to the tolerance test_zero.py holds the stages to.
    (Each stage's step compiled as a user's process compiles it: a key
    bias's gradient is zero but for rounding, Adam makes a step of the
    learning rate of it, and the two programs agree there only where the
    compiler rounds them alike.)"""
    runs = {}
    for stage in (2, 3):
        engine = _engine(stage, dtype=dtype)
        engine._jit_train_batch = engine._jit_train_batch.lower(
            engine.state, engine._stack_micro_batches(_batch(engine))
        ).compile(compiler_options=users_compiles)
        losses = [float(engine.train_batch(batch=_batch(engine, i)))
                  for i in range(3)]
        master = engine.state["master"] or engine.state["params"]
        runs[stage] = losses, jax.device_get(master)
        assert engine.policy.use_site_gathers == (4 if stage == 3 else 0)
    assert np.allclose(runs[2][0], runs[3][0], rtol=rtol, atol=atol), runs
    for a, b in zip(jax.tree_util.tree_leaves(runs[2][1]),
                    jax.tree_util.tree_leaves(runs[3][1])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=rtol, atol=atol)


@pytest.mark.parametrize("stage,data", [(3, 1), (0, 4), (1, 4), (2, 4)])
def test_nothing_is_traced_where_nothing_is_sharded(stage, data):
    """One chip, or a stage that keeps the parameters whole: the function
    hands back the very object, and the model's program is the one it
    traces with no engine."""
    engine = _engine(stage, data=data)
    policy = engine.policy
    layer = jax.tree_util.tree_map(
        lambda x: x[0], engine.state["params"]["blocks"]["block"])
    assert policy.gather_at_use_site(layer, "blocks/block", 2) is layer
    assert not policy.gathers_at_use_site
    assert (policy.use_site_gathers, policy.use_site_gather_bytes) == (0, 0)

    def loss(params, batch):
        return engine.module.apply({"params": params}, batch,
                                   deterministic=True)

    args = engine.state["params"], _batch(engine)
    bare = str(jax.make_jaxpr(loss)(*args))
    with zero_policy_scope(policy):
        scoped = str(jax.make_jaxpr(loss)(*args))
    assert scoped == bare


def test_the_gathered_weight_is_not_a_residual(monkeypatch):
    """Inside the remat the backward gathers again: with eight layers the
    step's temporaries grow by no more than two gathered layers, where a
    gather kept for the backward would cost all eight."""
    sizes = {}
    for use_site in (True, False):
        engine = _engine(3, n_layer=8, use_site=use_site,
                         monkeypatch=monkeypatch)
        sizes[use_site] = _compiled_step(
            engine).memory_analysis().temp_size_in_bytes
        layer_bytes = sum(int(x.nbytes) // x.shape[0]
                          for x in _block_kernels(engine).values())
    assert sizes[True] <= sizes[False] + 2 * layer_bytes, (sizes, layer_bytes)


def test_the_use_site_spec_keeps_tensor_parallel_axes(eight_device_mesh):
    """Beside test_zero.py's ``zero_shard_spec((128, 64), tp_spec=
    P("model", None)) == P("model", ZERO_AXES)``: where that leaf is used
    only the ZeRO axes are gathered."""
    policy = ZeroShardingPolicy(
        DeepSpeedZeroConfig(stage=3, stage3_param_persistence_threshold=0),
        eight_device_mesh, ShardingRules([(r"w/kernel", ("model", None))]))
    assert policy.param_spec("w/kernel", (128, 64)) == \
        P("model", ("data", "expert", "seq"))
    assert policy.use_site_spec("w/kernel", (128, 64)) == P("model", None)
    assert policy.use_site_spec("v/kernel", (128, 64)) == P(None, None)
