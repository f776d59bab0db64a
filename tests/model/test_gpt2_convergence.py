"""E2E convergence harness — analog of reference ``tests/model/
Megatron_GPT2`` (run a real training config matrix and compare loss curves
against the baseline config). Uses a tiny GPT-2 on synthetic data so the
whole matrix runs in CI; the comparison logic mirrors
``tests/model/run_sanity_check.py``: every ZeRO/precision variant must
track the stage-0 fp32 curve within tolerance and reach a clearly lower
final loss than initial.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.parallel import mesh as mesh_mod
from tests.unit.kinds import engine_weights

STEPS = 30
SEQ = 32
VOCAB = 97


def _data(batch_size, steps, seed=0):
    rng = np.random.default_rng(seed)
    # learnable structure: next token = (token * 3 + 1) % VOCAB with noise
    batches = []
    for _ in range(steps):
        start = rng.integers(0, VOCAB, (batch_size, 1))
        seqs = [start]
        for _ in range(SEQ - 1):
            nxt = (seqs[-1] * 3 + 1) % VOCAB
            seqs.append(nxt)
        ids = np.concatenate(seqs, axis=1).astype(np.int32)
        batches.append({"input_ids": ids})
    return batches


def _run(config_overrides, seed=0):
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config

    mesh_mod.reset_mesh()
    cfg = gpt2_config("gpt2-125m", n_layer=2, n_head=2, n_embd=32,
                      vocab_size=VOCAB, n_positions=SEQ)
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 3e-3}},
        "gradient_clipping": 1.0,
        "steps_per_print": 10 ** 9,
        "seed": 1234,
    }
    config.update(config_overrides)
    model = GPT2LMHeadModel(cfg)
    # (every variant and the baseline start from the same weights)
    params = engine_weights(
        model, {"input_ids": jnp.zeros((2, SEQ), jnp.int32)})
    engine, _, _, _ = ds.initialize(model=model, model_parameters=params,
                                    config=config)
    losses = []
    for batch in _data(engine.train_batch_size(), STEPS, seed):
        losses.append(float(engine.train_batch(batch=batch)))
    return np.asarray(losses)


@pytest.fixture(scope="module")
def baseline_curve():
    return _run({})


VARIANTS = {
    "zero1": {"zero_optimization": {"stage": 1}},
    "zero2_bf16": {"zero_optimization": {"stage": 2}, "bf16": {"enabled": True}},
    "zero3_bf16": {"zero_optimization": {"stage": 3}, "bf16": {"enabled": True}},
    "zero2_offload": {"zero_optimization": {"stage": 2,
                                            "offload_optimizer": {"device": "cpu"}},
                      "bf16": {"enabled": True}},
    "gas4": {"train_micro_batch_size_per_gpu": 2,
             "gradient_accumulation_steps": 4},
}


@pytest.mark.parametrize("name", sorted(VARIANTS))
def test_variant_tracks_baseline(name, baseline_curve):
    curve = _run(VARIANTS[name])
    assert curve[-1] < curve[0] * 0.8, \
        f"{name} did not learn: {curve[0]:.3f} -> {curve[-1]:.3f}"
    if name == "gas4":
        # different effective batch → only require learning
        return
    # final-quarter average must track the baseline curve (reference
    # run_sanity_check tolerance-style comparison)
    tail = curve[-STEPS // 4:].mean()
    base_tail = baseline_curve[-STEPS // 4:].mean()
    assert abs(tail - base_tail) / base_tail < 0.15, \
        f"{name}: tail {tail:.3f} vs baseline {base_tail:.3f}"


def test_baseline_learns(baseline_curve):
    assert baseline_curve[-1] < baseline_curve[0] * 0.6, baseline_curve
