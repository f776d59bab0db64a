"""Elastic training closed end-to-end.

One composition test covering the loop the reference's elastic machinery
exists for (``deepspeed/elasticity/elastic_agent.py:28`` +
``checkpoint/universal_checkpoint.py:12``):

  2-proc launch via the CLI launcher → a worker dies mid-training → the
  elastic agent restarts the job → training resumes from the checkpoint →
  the job is then relaunched at a DIFFERENT world size resuming from the
  UNIVERSAL checkpoint → the loss continues where it left off.

The phases run as real subprocess launches of ``deepspeed_tpu.launcher
.runner`` (CPU backend, Gloo rendezvous); continuity is asserted through a
fixed probe batch whose loss must be preserved across kill + restart +
re-mesh, plus the recorded loss trajectory.
"""

import json
import os
import subprocess
import sys
import textwrap

REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))

_TRAIN_SCRIPT = r"""
import json
import os
import sys

import jax
jax.config.update("jax_platforms", "cpu")  # before any backend use

import numpy as np

work = sys.argv[1]
mode = sys.argv[2]                  # "train" | "resume_universal"
total_steps = int(sys.argv[3])
kill_at = int(sys.argv[4])          # rank 1 dies after this step on 1st run
rank = int(os.environ.get("RANK", "0"))
world = int(os.environ.get("WORLD_SIZE", "1"))

import deepspeed_tpu as ds

ds.init_distributed()

from deepspeed_tpu.models.lm_config import TransformerConfig
from deepspeed_tpu.models.transformer_lm import TransformerLM

GLOBAL_BATCH = 4
ckpt = os.path.join(work, "ckpt")


def make_batch(step):
    # ONE fixed batch for every step: the loss then decreases monotonically
    # (memorization), so trajectory continuity across kill/restart/re-mesh
    # is directly assertable
    rng = np.random.default_rng(1000)
    return {"input_ids": rng.integers(0, 64, (GLOBAL_BATCH, 32)).astype(np.int32)}


def probe_loss(engine):
    rng = np.random.default_rng(7)
    batch = {"input_ids": rng.integers(0, 64, (GLOBAL_BATCH, 32)).astype(np.int32)}
    params = jax.device_get(engine.state["params"])
    # (one program: op by op the scan's body is ~100 small compiles)
    return float(jax.jit(lambda p, b: engine.module.apply(
        {"params": p}, b, deterministic=True))(params, batch))


def record(payload):
    if rank == 0:
        with open(os.path.join(work, "losses.jsonl"), "a") as f:
            f.write(json.dumps(payload) + "\n")


model = TransformerLM(TransformerConfig(
    vocab_size=64, n_embd=32, n_layer=2, n_head=4, max_seq_len=32))
# the same weights on every rank, made inside one jit (left to the engine,
# 6 s of the 17 a worker lives; three workers' lives, one after another,
# are the test's seconds)
from tests.unit.kinds import engine_weights

params = engine_weights(model, make_batch(0))
engine, _, _, _ = ds.initialize(
    model=model, model_parameters=params,
    config={"train_micro_batch_size_per_gpu": GLOBAL_BATCH // world,
            "gradient_accumulation_steps": 1,
            "zero_optimization": {"stage": 1},
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "bf16": {"enabled": True},
            "steps_per_print": 10 ** 9})

# per-rank start counter — distinguishes the pre-kill attempt from the
# agent's restart
marker = os.path.join(work, f"starts_rank{rank}")
starts = int(open(marker).read()) if os.path.exists(marker) else 0
open(marker, "w").write(str(starts + 1))

if mode == "resume_universal":
    from deepspeed_tpu.checkpoint import ds_to_universal

    if rank == 0:
        ds_to_universal(ckpt)
    engine.train_batch(batch=make_batch(0))       # build state (overwritten)
    engine.load_universal_checkpoint(ckpt)
    with open(os.path.join(work, "probe_after_remesh.json"), "w") as f:
        json.dump({"probe": probe_loss(engine),
                   "resumed_step": engine.global_steps, "world": world}, f)
elif os.path.exists(os.path.join(ckpt, "latest")):
    engine.train_batch(batch=make_batch(0))       # build state (overwritten)
    engine.load_checkpoint(ckpt)

while engine.global_steps < total_steps:
    step = engine.global_steps
    loss = float(engine.train_batch(batch=make_batch(step)))
    record({"mode": mode, "world": world, "attempt": starts,
            "step": engine.global_steps, "loss": loss})
    engine.save_checkpoint(ckpt)
    if mode == "train" and rank == 1 and starts == 0 and \
            engine.global_steps == kill_at:
        os._exit(1)                               # simulated worker death

if mode == "train" and rank == 0:
    with open(os.path.join(work, "probe_after_train.json"), "w") as f:
        json.dump({"probe": probe_loss(engine),
                   "final_step": engine.global_steps}, f)
"""


def _free_port() -> int:
    """An ephemeral port from the OS — fixed ports collide under parallel
    test execution (xdist / concurrent CI jobs on one host)."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch(script, work, mode, total, kill_at, nprocs, port, flags,
            elastic=False):
    cmd = [sys.executable, "-u", "-m", "deepspeed_tpu.launcher.runner",
           "--num_gpus", str(nprocs), "--master_port", str(port)]
    if elastic:
        cmd += ["--elastic_training", "--max_elastic_restarts", "2"]
    cmd += [script, work, mode, str(total), str(kill_at)]
    env = dict(os.environ)
    env["XLA_FLAGS"] = flags    # (no virtual-mesh leak into real procs)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(cmd, capture_output=True, text=True, timeout=900,
                          cwd=REPO_ROOT, env=env)


def test_elastic_loop_end_to_end(tmp_path, light_compiles):
    script = tmp_path / "elastic_train.py"
    script.write_text(textwrap.dedent(_TRAIN_SCRIPT))
    work = str(tmp_path)

    # Phase A: 2 workers, elastic agent on; rank 1 dies after step 2 on the
    # first attempt; the agent restarts and training resumes to step 4.
    proc = _launch(str(script), work, "train", 4, 2, nprocs=2, port=_free_port(),
                   flags=light_compiles, elastic=True)
    assert proc.returncode == 0, proc.stderr[-4000:]

    starts0 = int((tmp_path / "starts_rank0").read_text())
    starts1 = int((tmp_path / "starts_rank1").read_text())
    assert (starts0, starts1) == (2, 2), \
        f"agent restart did not happen: starts={starts0, starts1}"

    rows = [json.loads(l) for l in
            (tmp_path / "losses.jsonl").read_text().splitlines()]
    attempt0 = [r["step"] for r in rows if r["attempt"] == 0]
    attempt1 = [r["step"] for r in rows if r["attempt"] == 1 and
                r["mode"] == "train"]
    assert attempt0 == [1, 2], attempt0          # trained to the kill point
    assert attempt1 == [3, 4], attempt1          # resumed, not restarted at 0

    probe_a = json.loads((tmp_path / "probe_after_train.json").read_text())
    assert probe_a["final_step"] == 4

    # Phase B: relaunch at world size 1 from the universal checkpoint.
    proc = _launch(str(script), work, "resume_universal", 6, -1, nprocs=1,
                   port=_free_port(), flags=light_compiles)
    assert proc.returncode == 0, proc.stderr[-4000:]

    probe_b = json.loads((tmp_path / "probe_after_remesh.json").read_text())
    assert probe_b["resumed_step"] == 4, probe_b  # step counter survived
    assert probe_b["world"] == 1
    # weights survived kill + restart + re-mesh: same probe batch, same loss
    assert abs(probe_b["probe"] - probe_a["probe"]) < 5e-3, (probe_a, probe_b)

    # loss continuity: the re-meshed run continues the trajectory
    rows = [json.loads(l) for l in
            (tmp_path / "losses.jsonl").read_text().splitlines()]
    resumed = [r for r in rows if r["mode"] == "resume_universal"]
    assert [r["step"] for r in resumed] == [5, 6], resumed
    assert all(np.isfinite(r["loss"]) for r in resumed)
    # single fixed batch -> the whole trajectory (across the kill, the
    # restart, and the re-mesh) must be monotonically decreasing
    train_rows = sorted((r for r in rows if r["mode"] == "train"),
                        key=lambda r: r["step"])
    trajectory = [r["loss"] for r in train_rows + resumed]
    assert all(b < a + 1e-3 for a, b in zip(trajectory, trajectory[1:])), \
        trajectory
    assert trajectory[-1] < trajectory[0], trajectory


import numpy as np  # noqa: E402  (used in assertions)
