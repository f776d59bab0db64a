"""1F1B executor: parity vs GPipe and TrainSchedule-semantics conformance.

The executed tick plan (one_f_one_b.py) must agree with the instruction
streams ``TrainSchedule`` generates (the reference's executable spec,
deepspeed/runtime/pipe/schedule.py:189-257): per-stage forward/backward
micro order, the F-before-B dependency chain, and the last stage's F(m)/B(m)
alternation.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models.gpt2 import GPT2Config
from deepspeed_tpu.models.gpt2_pipe import gpt2_pipe_module
from deepspeed_tpu.parallel import initialize_mesh, reset_mesh
from deepspeed_tpu.runtime.pipe.schedule import (
    BackwardPass,
    ForwardPass,
    TrainSchedule,
)


def _train(schedule, steps=2, gas=4, stages=2, zero_stage=0, fp16=False):
    reset_mesh()
    initialize_mesh(data=8 // stages, pipe=stages)
    cfg = GPT2Config(vocab_size=64, n_positions=32, n_embd=32, n_layer=4,
                     n_head=2, dtype=jnp.float32)
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": gas,
        "zero_optimization": {"stage": zero_stage},
        "pipeline": {"schedule": schedule},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
    }
    if fp16:
        config["fp16"] = {"enabled": True, "loss_scale": 128.0}
    eng, _, _, _ = ds.initialize(model=gpt2_pipe_module(cfg, num_stages=stages),
                                 config=config)
    rng = np.random.default_rng(11)
    losses = []
    for _ in range(steps):
        batch = {"input_ids": rng.integers(
            0, 64, (eng.train_batch_size(), 32), dtype=np.int32)}
        losses.append(float(eng.train_batch(batch=batch)))
    return losses, jax.device_get(eng.state["params"])


def _max_param_diff(a, b):
    return max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda x, y: float(np.max(np.abs(np.asarray(x, np.float64) -
                                         np.asarray(y, np.float64)))), a, b)))


def test_1f1b_matches_gpipe_loss_and_params():
    """Same data, same init: the interleaved executor must reproduce the
    GPipe trajectory (identical math, different schedule)."""
    l_g, p_g = _train("gpipe")
    l_f, p_f = _train("1f1b")
    np.testing.assert_allclose(l_f, l_g, rtol=1e-5, atol=1e-5)
    assert _max_param_diff(p_g, p_f) < 1e-3


def test_1f1b_matches_gpipe_gas_2x_stages():
    """The done-criterion: parity at gas >= 2 x stages."""
    l_g, _ = _train("gpipe", steps=1, gas=8, stages=4)
    l_f, _ = _train("1f1b", steps=1, gas=8, stages=4)
    np.testing.assert_allclose(l_f, l_g, rtol=1e-5, atol=1e-5)


def test_1f1b_with_zero1_and_fp16():
    losses, _ = _train("1f1b", steps=3, zero_stage=1, fp16=True)
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_unknown_schedule_rejected():
    # Literal["1f1b","gpipe"] → pydantic rejects at config-parse time
    with pytest.raises(Exception, match="schedule|1f1b|literal_error"):
        _train("bogus", steps=1)


# ---------------------------------------------------------------------------
# schedule-semantics conformance
# ---------------------------------------------------------------------------

def _executor_ticks(M, S):
    """Per-stage per-tick ops [('F'|'B', micro), ...] built from the SAME
    index functions the executor's scan body consumes."""
    from deepspeed_tpu.runtime.pipe.one_f_one_b import (
        backward_micro_ids,
        forward_micro_ids,
        total_ticks,
    )

    stage_ids = np.arange(S)
    ticks = {s: [] for s in range(S)}
    for t in range(total_ticks(M, S)):
        f_ids = forward_micro_ids(t, stage_ids, S)
        b_ids = backward_micro_ids(t, stage_ids, S)
        for s in range(S):
            ops = []
            if 0 <= f_ids[s] < M:
                ops.append(("F", int(f_ids[s])))
            if 0 <= b_ids[s] < M:
                ops.append(("B", int(b_ids[s])))
            ticks[s].append(ops)
    return ticks


def _executor_plan(M, S):
    ticks = _executor_ticks(M, S)
    return {s: [op for tick in ticks[s] for op in tick] for s in range(S)}


def _schedule_plan(M, S):
    """Per-stage ('F'|'B', micro) order from the TrainSchedule streams."""
    plan = {}
    for s in range(S):
        sched = TrainSchedule(micro_batches=M, stages=S, stage_id=s)
        seq = []
        for step_id, cmds in enumerate(sched.steps()):
            micro, is_fwd = sched._step_to_micro_batch(step_id)
            for cmd in cmds:
                if isinstance(cmd, ForwardPass):
                    seq.append(("F", micro))
                elif isinstance(cmd, BackwardPass):
                    seq.append(("B", micro))
        plan[s] = seq
    return plan


@pytest.mark.parametrize("M,S", [(4, 2), (8, 4), (4, 4), (2, 4), (6, 3)])
def test_executor_order_matches_train_schedule(M, S):
    """Per-stage forward micro order and backward micro order equal the
    TrainSchedule streams. (Exact F/B interleaving differs by at most the
    within-pair order on odd stages — the executor packs one F and one B per
    tick, the reference alternates one op per step; the dependency test
    below pins the semantics that matter.)"""
    ex, ref = _executor_plan(M, S), _schedule_plan(M, S)
    for s in range(S):
        assert [op for op in ex[s] if op[0] == "F"] == \
            [op for op in ref[s] if op[0] == "F"]
        assert [op for op in ex[s] if op[0] == "B"] == \
            [op for op in ref[s] if op[0] == "B"]
    # last stage alternates F(m), B(m) — the 1F1B signature
    last = ex[S - 1]
    for m in range(M):
        assert ("F", m) in last and ("B", m) in last
        assert last.index(("B", m)) == last.index(("F", m)) + 1


@pytest.mark.parametrize("M,S", [(8, 4), (4, 2), (4, 4)])
def test_executor_dependencies(M, S):
    """From the BUILT tick plan: B(m) at stage s happens at/after F(m) at
    stage s, after F(m) at the last stage (the loss), and exactly one tick
    after B(m) at stage s+1 (the cotangent producer)."""
    ticks = _executor_ticks(M, S)

    def tick_of(s, op):
        for t, ops in enumerate(ticks[s]):
            if op in ops:
                return t
        raise AssertionError(f"{op} never executed on stage {s}")

    for s in range(S):
        for m in range(M):
            tb = tick_of(s, ("B", m))
            assert tb >= tick_of(s, ("F", m))
            assert tb >= tick_of(S - 1, ("F", m))
            if s + 1 < S:
                assert tb == tick_of(s + 1, ("B", m)) + 1


def test_tick_count_packs_tighter_than_reference_steps():
    """Executor ticks (1F+1B each) = M + 2(S-1) vs the reference's
    2(M+S-1) single-op steps — the same schedule packed two ops per tick."""
    for M, S in [(4, 2), (8, 4)]:
        assert M + 2 * (S - 1) <= 2 * (M + S - 1)
