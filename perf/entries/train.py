"""One training cell, once: ``ds.initialize`` + ``engine.train_batch`` on a
seeded token stream. Measures whole optimizer steps inside the window on the
host clock (each step ends in ``block_until_ready`` on its loss), checks the
trainer against the plain reference before the window and its losses after
it."""

from __future__ import annotations

import math
import time

import numpy as np

from perf import build, device

# The trainer's loss on the reference's own greedy labels against the
# reference's (perf/reference/gpt2.py says why those labels). The trainer
# computes in bfloat16: each logit is off by about 2**-8 of its size, which
# over a thousand positions moves the mean loss by under 2e-3 (log-softmax
# in bfloat16 rounds each term near 11 to 1/16, unbiased). A dropped layer
# or a mask that lets the future in lowers the agreement of the logits and
# raises this loss by 0.045 and more (one layer of 48: the logits keep a
# correlation of 1 - 1/96 with the reference's, and the reference's best
# logit stands 4.3 deviations out). Measured on the chip over two sequences:
# 0.0014 and 0.0088 apart (PR 22). Four sequences halve the noise of that;
# 0.03 then lies some five deviations out and still under the 0.045.
REFERENCE_LOSS_TOL = 0.03
REFERENCE_SEQUENCES = 4
# First loss of a randomly initialised LM with a tied head: ln V + 0.5
# (unit-variance logits; measured 11.325 = ln 50257 + 0.500, PERF.md PR 21)
FIRST_LOSS_OFFSET = 0.5
FIRST_LOSS_WINDOW = 0.25


def _born_on_the_host(chips: int):
    """On several chips, build the engine with the host as JAX's default
    device. The engine makes Adam's two moments with ``jnp.zeros(shape)``,
    whole and on the default device, before it shards them
    (``ops/optimizers.py`` ``_tree_zeros_like``, ``_build_state``): for
    GPT-2 XL that is 12.5 GB on chip 0 beside its share of the weights, and
    the build dies there (first four-chip run, PR 22: 268 MB free when the
    first moment was to be sliced). Born on the host, each moment goes
    straight to its four shards. The weights passed in are already sharded
    and committed to the chips, so nothing else moves. A workaround on the
    benchmark's side for a flaw of the program (PERF.md, Open questions):
    once ``_build_state`` makes its state sharded this changes nothing. One
    chip holds the whole state anyway and builds as any user would."""
    import contextlib

    import jax

    if chips == 1:
        return contextlib.nullcontext()
    return jax.default_device(jax.devices("cpu")[0])


def run(ctx: dict) -> dict:
    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.parallel import initialize_mesh

    config, cell, traffic = ctx["config"], ctx["cell"], ctx["traffic"]
    rehearsal = ctx["rehearsal"]
    chips = ctx["chips"]
    failures = []
    compiles = ctx["compile_requests"]

    # -- build ------------------------------------------------------------
    devices = jax.devices()[:chips]
    mesh = initialize_mesh(devices=devices, **config["mesh"])
    model, model_cfg = build.build_model(config["model"],
                                         cell.get("model_overrides"),
                                         rehearsal)
    gen_params = dict(traffic["params"])
    if rehearsal:
        gen_params.update(traffic["rehearsal_params"])
    stream = ctx["manifest"].generator(traffic["generator"]).generate(
        gen_params, ctx["seed"], ctx["seconds"],
        {"vocab_size": model_cfg.vocab_size})
    split = cell["rehearsal_split"] if rehearsal else cell["split"]
    gas = split["gradient_accumulation_steps"]
    micro = split["micro_batch_per_chip"]
    if micro * gas * chips != stream.sequences:
        raise ValueError(f"micro-batch {micro} x accumulation {gas} x chips "
                         f"{chips} is not the {stream.sequences} sequences "
                         f"a step of this traffic has")
    engine_config = dict(config["engine"])
    if rehearsal:
        engine_config["zero_optimization"] = {
            **engine_config["zero_optimization"],
            "stage3_param_persistence_threshold": 0}
    engine_config["train_micro_batch_size_per_gpu"] = micro
    engine_config["gradient_accumulation_steps"] = gas
    engine_config["seed"] = ctx["seed"]

    ctx["mark"]("imports_and_model")
    first = stream.batch(-1)
    params = build.init_params(
        model, ({"input_ids": first["input_ids"][:1]},), {}, ctx["seed"],
        mesh=mesh)
    with _born_on_the_host(chips):
        engine, _, _, _ = ds.initialize(model=model, model_parameters=params,
                                        config=engine_config, mesh=mesh)
    del params
    ctx["mark"]("weights_and_engine")

    # -- correct, part 1: the trainer against the plain reference ---------
    reference = ctx["manifest"].reference(config["reference"]["file"])
    logits_fn = reference.make_forward(**{
        k: getattr(model_cfg, v)
        for k, v in config["reference"]["args_from_config"].items()})
    check_ids = stream.batch(-2)["input_ids"][:REFERENCE_SEQUENCES]
    labels, ref_loss = reference.greedy_labels_and_loss(
        logits_fn, engine.state["params"], check_ids)
    got_loss = float(engine.eval_batch_fn()(
        engine.state["params"], {"input_ids": check_ids, "labels": labels}))
    reference_check = {"reference_loss": ref_loss, "trainer_loss": got_loss,
                       "tolerance": REFERENCE_LOSS_TOL,
                       "sequences": int(check_ids.shape[0])}
    if not abs(got_loss - ref_loss) <= REFERENCE_LOSS_TOL:
        failures.append(f"trainer loss {got_loss:.4f} on the reference's "
                        f"greedy labels, reference {ref_loss:.4f}: apart by "
                        f"more than {REFERENCE_LOSS_TOL}")

    ctx["mark"]("reference_check")

    # -- warm-up: the step compiles on its first two calls (PERF.md s7) ----
    warm_losses = [float(engine.train_batch(batch=stream.batch(-3 - i)))
                   for i in range(3)]
    tokens_per_step = stream.tokens_per_step
    ctx["mark_setup_done"]()

    # -- the window ---------------------------------------------------------
    trace = ctx["trace"]
    trace_steps = int(cell.get("trace_steps", 3))
    seconds = ctx["seconds"]
    losses, step_spans = [], []
    compiles.count, compiles.active = 0, True
    t_open = time.perf_counter()
    step, step_s = 0, 0.0
    while True:
        now = time.perf_counter() - t_open
        if step and now + step_s > seconds:
            break
        if trace is not None and not trace.done and not trace.running \
                and now >= 0.3 * seconds:
            trace.start()
            trace_until = step + trace_steps
        t0 = time.perf_counter()
        with device.annotate("bench/make_batch"):
            batch = stream.batch(step)
        with device.annotate("bench/train_batch", step=step):
            loss = float(engine.train_batch(batch=batch))   # waits
        t1 = time.perf_counter()
        losses.append(loss)
        step_spans.append((t0 - t_open, t1 - t_open))
        step_s = max(step_s, t1 - t0)
        step += 1
        if trace is not None and trace.running and step >= trace_until:
            trace.stop()
    compiles.active = False
    if trace is not None:
        trace.stop()

    # -- correct, part 2: the losses of the window --------------------------
    expect = math.log(model_cfg.vocab_size) + FIRST_LOSS_OFFSET
    all_losses = warm_losses + losses
    if not all(math.isfinite(x) for x in all_losses):
        failures.append(f"non-finite loss among {all_losses}")
    if abs(warm_losses[0] - expect) > FIRST_LOSS_WINDOW:
        failures.append(f"first loss {warm_losses[0]:.3f} not within "
                        f"{FIRST_LOSS_WINDOW} of ln V + {FIRST_LOSS_OFFSET} "
                        f"= {expect:.3f}")
    if len(losses) >= 10 and \
            not np.mean(losses[-5:]) < np.mean(losses[:5]):
        failures.append(f"loss did not fall over the window: first five "
                        f"{losses[:5]}, last five {losses[-5:]}")
    if compiles.count:
        failures.append(f"{compiles.count} compile request(s) inside the "
                        f"window")

    n = len(losses)
    elapsed = step_spans[-1][1] - step_spans[0][0]
    tok_s_chip = n * tokens_per_step / elapsed / chips
    flops_per_token = config["flops_per_token"]
    return {
        "attempted": n, "failed": 0 if not failures else n,
        "failures": failures,
        "end_to_end": {"train_tok_s_chip": tok_s_chip},
        "facts": {
            "steps": n, "tokens_per_step": tokens_per_step,
            "elapsed_s": elapsed, "losses": losses,
            # the slowest step, for the day one stalls (PERF.md section 6)
            "step_ms_p50_max_at": [
                float(np.median([(b - a) * 1e3 for a, b in step_spans])),
                max((b - a) * 1e3 for a, b in step_spans),
                int(np.argmax([b - a for a, b in step_spans]))],
            "warm_losses": warm_losses, "reference_check": reference_check,
            "params": int(engine.num_parameters),
            "mfu_strict_6n": tok_s_chip * flops_per_token
            / ctx["peaks"]["bf16_flops_per_s"] if ctx["peaks"] else None,
        },
        "counters": {"compiles_in_window": compiles.count},
        "spans": {"bench/train_batch": step_spans},
        "kernel_dims": {"B": micro, "H": model_cfg.n_head,
                        "T": stream.seq_len,
                        "D": model_cfg.n_embd // model_cfg.n_head},
    }
