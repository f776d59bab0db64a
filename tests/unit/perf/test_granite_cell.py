"""The benchmark entries and readers PR 47 brought for
``serve-granite4h-3b-agents``: the cell's files are found by name, what the
cell reports, that every per-layer metric it lists moves a metric the cell
reports and has a reader, that the new readers find nothing (and do not
raise) on a program without state-space layers, the roofline's counts by
hand, the readers on a hand-written record, the configuration's counts
against the built model (shapes alone), and the cell's rehearsal on the
CPU."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import program_spans  # noqa: E402
from perf.manifest import Manifest, load_module  # noqa: E402

CELL = "serve-granite4h-3b-agents"
CONFIG = "granite-4.0-h-micro-hybrid"
NEW = ["ssm_dev_share", "ssm_roofline", "ssm_chunk_tokens_mean"]
TWINS = ["state_rows_mean", "pages_peak_share", "chunk_steps_share",
         "live_slots_mean", "serve_step_ms_p50", "compiles_in_window",
         "prefill_dev_share", "pallas_share", "peak_hbm_gb",
         "step_sync_wait_ms_p50", "prefill_wait_p50_ms",
         "step_host_serial_ms_p50"]
T_OPEN = 1000.0


@pytest.fixture(scope="module")
def manifest():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def peaks():
    with open(os.path.join(ROOT, "perf", "peaks.json")) as f:
        return json.load(f)["TPU v5 lite"]


@pytest.fixture(scope="module")
def roof():
    return load_module(os.path.join(ROOT, "perf", "layer_metrics",
                                    "ssm_roofline.py"), "ssm_roof")


def test_the_cells_files_are_found_by_name(manifest):
    cell = manifest.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "agents-closed", 1)
    assert manifest.cell(CELL)["config"] == CONFIG
    config = manifest.config(CONFIG)
    assert config["reference"]["file"] == "granite_hybrid"
    assert callable(manifest.reference("granite_hybrid").make_forward)
    assert callable(manifest.reference("granite_hybrid").check_greedy)
    assert config["trace"]["kernel_family"] == "ssm"
    names = [m["name"] for m in manifest.metrics_for(CELL, "end_to_end")]
    assert sorted(names) == ["gap_p90_ms", "setup_s"]
    entry, = [c for c in manifest.data["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == config["reduced"] \
        == ["max_position_embeddings"]
    assert manifest.data["configs"][-1] is entry
    assert manifest.data["workloads"][-1]["name"] == CELL


def test_the_traffic_is_the_issues(manifest):
    traffic = manifest.traffic("agents-closed")
    assert traffic["generator"] == "closed_loop_clients"
    assert traffic["params"] == {
        "clients": 64, "think_s": 0.0, "lead_in_s": 60.0,
        "prompt_len": {"median": 512, "sigma": 0.6, "min": 128,
                       "max": 2048},
        "output_len": {"median": 1024, "sigma": 0.5, "min": 256,
                       "max": 2048}}
    config = manifest.config(CONFIG)
    # prompt + answer inside the served context; a caller a slot
    assert 2048 + 2048 <= config["max_position_embeddings"] == 16384 \
        == config["model"]["config_kwargs"]["max_seq_len"]
    assert traffic["params"]["clients"] == config["server"]["num_slots"]
    assert config["server"] == {
        "dtype": "bf16", "num_slots": 64, "prefill_chunk": 128,
        "paged_kv": {"num_pages": 1536, "page_size": 128,
                     "prefix_cache": False}}


def test_the_configuration_is_the_published_one(manifest):
    """Every published key of the catalog's entry as it is, but the served
    context; the program's arguments say the same."""
    config = manifest.config(CONFIG)
    published = {
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 8192,
        "logits_scaling": 8, "mamba_chunk_size": 256,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 64, "mamba_proj_bias": False,
        "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 0,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True,
        "vocab_size": 100352}
    assert {key: config[key] for key in published} == published
    period = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert config["layer_types"] == period * 4
    kw = config["model"]["config_kwargs"]
    assert kw["layer_types"] == config["layer_types"]
    assert (kw["n_embd"], kw["n_layer"], kw["n_head"], kw["n_kv_head"],
            kw["ffn_dim"], kw["vocab_size"]) == (2048, 40, 32, 8, 8192,
                                                  100352)
    assert (kw["mamba_n_heads"], kw["mamba_d_head"], kw["mamba_d_state"],
            kw["mamba_d_conv"], kw["mamba_n_groups"]) == (64, 64, 128, 4, 1)
    assert (kw["embedding_multiplier"], kw["attention_multiplier"],
            kw["residual_multiplier"], kw["logits_scaling"]) == (
        12.0, 0.015625, 0.22, 8.0)


def test_the_counts_are_the_built_models(manifest):
    """``jax.eval_shape`` of the model the cell builds: nothing is
    allocated. Parameters, the state a slot and the pages to the byte."""
    import jax
    import jax.numpy as jnp

    from perf import build

    config = manifest.config(CONFIG)
    model, cfg = build.build_model(config["model"], None, False)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32),
                           method=model.logits)["params"])
    count = sum(leaf.size for leaf in jax.tree_util.tree_leaves(shapes))
    assert count == config["parameters"] == 3_191_396_096
    assert config["weight_bytes"] == 2 * count
    a_layer = config["parameters_a_layer"]
    mamba = shapes["mamba_blocks"]["block"]
    assert sum(leaf.size for leaf in jax.tree_util.tree_leaves(mamba)) \
        == 36 * a_layer["mamba_layer"] == 36 * 76_182_976
    attn = shapes["attn_blocks"]["block"]
    assert sum(leaf.size for leaf in jax.tree_util.tree_leaves(attn)) \
        == 4 * a_layer["attention_layer"] == 4 * 60_821_504
    # the published input projection, 2048 x 8512, as two leaves
    assert mamba["mamba"]["in_proj"]["kernel"].shape == (36, 2048, 8448)
    assert mamba["mamba"]["dt_proj"]["kernel"].shape == (36, 2048, 64)
    assert a_layer["mamba_in_proj"] == 2048 * (8448 + 64)
    spec = model.kv_cache_spec()
    state = config["state"]
    assert spec.state_bytes_per_row == state["bytes_a_slot"] == 76_437_504
    assert state["bytes_resident"] == 64 * 76_437_504
    pages = jax.eval_shape(lambda: spec.paged_cache(
        1536, 128, num_slots=64))
    assert pages["s"].shape[2:] == tuple(state["s_shape_a_slot_a_layer"])
    assert pages["conv"].shape[2:] == tuple(
        state["conv_shape_a_slot_a_layer"])
    kv = sum(pages[key].size * 2 for key in ("k", "v"))
    assert kv == config["kv_bytes"]["pages"] == 1_610_612_736
    assert config["kv_bytes_per_token"] * 1536 * 128 == kv


@pytest.mark.parametrize("spread, fixed_point", [("the_files", False),
                                                 ("flaxs_default", True)])
def test_the_seeded_embedding_leaves_the_head_something_to_decide(
        manifest, spread, fixed_point):
    """``embedding_init_std`` of the configuration's file, at the rehearsal
    sizes: under the tied head a greedy token is the position's own input
    token once in a hundred (one of 512 by chance) and the best two logits
    lie 8 % of the scale apart, so a served token can fall short of the
    reference's best. At flax's default spread every position scores its
    own input token first, 59 % of the scale clear: greedy decoding is a
    fixed point and the comparison of served tokens holds nothing (the
    first chip run of PR 47: a shortfall of exactly 0.0 at 4,018
    positions)."""
    import copy

    import jax.numpy as jnp
    import numpy as np

    from perf import build

    described = copy.deepcopy(manifest.config(CONFIG)["model"])
    assert described["config_kwargs"]["embedding_init_std"] == 0.004
    if spread == "flaxs_default":
        del described["config_kwargs"]["embedding_init_std"]
    model, cfg = build.build_model(described, None, True)
    params = build.init_params(
        model, (jnp.zeros((1, 8), jnp.int32),),
        {"method": getattr(model, described["init_method"])}, 11,
        cast_to=build._dtype(described["dtype"]))
    ids = np.random.default_rng(3).integers(
        0, cfg.vocab_size, (4, 64)).astype(np.int32)
    logits = np.asarray(model.apply({"params": params}, jnp.asarray(ids),
                                    method=model.logits), np.float32)
    own = float((logits.argmax(-1) == ids).mean())
    best = np.sort(logits, -1)
    gap = float(np.median((best[..., -1] - best[..., -2])
                          / np.abs(logits).max(-1)))
    if fixed_point:
        assert own == 1.0 and gap > 0.4
    else:
        assert own < 0.05 and gap < 0.15


@pytest.mark.parametrize("name", [
    "queue_wait_p50_ms", "decode_dev_ms_p50", "gen_late_p99_ms",
    "ttft_p50_ms", "gap_p99_ms", "gen_tok_s", "served_tok_s",
    "step_exposed_host_ms_p50.gap", "step_enqueue_ms_p50.gap",
    "step_prepare_ms_p50.gap", "step_device_calls_mean.gap",
    "step_idle_unnamed_ms.gap"])
def test_accepted_readers_that_move_the_gap_list_the_cell(manifest, name):
    entry, = [m for m in manifest.data["per_layer"] if m["name"] == name]
    assert entry["moves"] == "gap_p90_ms"
    assert entry["workloads"][-1] == CELL      # appended, nothing moved


def test_every_metric_of_the_cell_moves_something_it_reports(manifest):
    reported = {m["name"] for m in manifest.metrics_for(CELL, "end_to_end")}
    layer = manifest.metrics_for(CELL, "per_layer")
    names = {m["name"] for m in layer}
    assert len(layer) == 12 + 3 + len(NEW) + len(TWINS)
    for m in layer:
        assert m["moves"] in reported, m
        assert callable(manifest.layer_reader(m["name"]))
    assert set(NEW) | {base + ".agents" for base in TWINS} <= names
    by_name = {m["name"]: m for m in layer}
    for name in NEW + ["state_rows_mean.agents"]:
        assert by_name[name]["layer"] == "state-space layers"
        assert by_name[name]["workloads"] == [CELL]
    assert by_name["ssm_roofline"]["better"] == "higher"
    assert by_name["ssm_roofline"]["unit"] == "%"
    # the new entries stand at the end of the list, in the issue's order
    tail = [m["name"] for m in manifest.data["per_layer"][
        -len(NEW) - len(TWINS):]]
    assert tail == NEW + [base + ".agents" for base in TWINS]


@pytest.mark.parametrize("name", NEW + ["state_rows_mean.agents"])
def test_new_readers_find_nothing_where_there_is_nothing(manifest, name,
                                                         monkeypatch):
    """The parent's record, and a K/V model's: no span attribute, no named
    call. The reader returns None and does not raise."""
    monkeypatch.setattr(program_spans, "program_events", lambda: [])
    read = manifest.layer_reader(name)
    assert read({"facts": {}, "end_to_end": {}, "counters": {},
                 "samples": {}, "spans": {}}) is None
    trace = {"device0": {"busy_s": 1.0, "custom_calls": {
        "paged_decode.3": {"count": 10, "total_s": 0.1}}}}
    assert read({"trace": trace, "peaks": {}, "kernel_dims": {},
                 "facts": {}, "spans": {}, "config": {}}) is None
    assert read({"trace": trace, "peaks": {"hbm_bytes_per_s": 1.0},
                 "facts": {}, "spans": {},
                 "config": {"mamba_n_heads": 64, "mamba_d_head": 64,
                            "mamba_d_state": 128}}) is None


def test_the_roofline_counts_by_hand(roof, peaks):
    # a row's state in a layer: 64 heads x 64 x 128 float32
    assert roof.state_bytes_a_row_a_layer(64, 64, 128) == 2_097_152
    # x and y (64 x 64), dt (64), B and C (128), float32
    assert roof.vector_bytes_a_token(64, 64, 128) \
        == 4 * (2 * 4096 + 64 + 256) == 34_048
    # ssm_decode, 64 rows of one layer: read and write of the state + the
    # vectors; five operations a state element
    flops, moved = roof.decode_call(64, 64, 64, 128)
    assert moved == 64 * (2 * 2_097_152 + 34_048) == 270_614_528
    assert flops == 64 * 5 * 524_288
    # bytes lead by far: 0.33 ms a layer, 11.9 ms over the 36
    least = roof.least_seconds(flops, moved, peaks)
    assert least == moved / peaks["hbm_bytes_per_s"]
    assert 11.8e-3 < 36 * least < 12.0e-3
    half, half_moved = roof.decode_call(32, 64, 64, 128)
    assert (half, half_moved) == (flops / 2, moved / 2)     # by rows run
    # ssm_chunk, one row, 128 real tokens at the kernel's block of 128:
    # a token: C B^T 2 x 128 x 128, and a head 2 x 128 x 64 + 4 x 128 x 64
    a_token = 2 * 128 * 128 + 64 * (2 * 128 * 64 + 4 * 128 * 64)
    assert a_token == 3_178_496
    flops, moved = roof.chunk_call(1, 128, 64, 64, 128)
    assert flops == 128 * a_token
    assert moved == 2 * 2_097_152 + 128 * 34_048
    assert roof.least_seconds(flops, moved, peaks) \
        == moved / peaks["hbm_bytes_per_s"]
    # real tokens alone: a chunk of 20 costs the state's bytes all the same
    few, few_moved = roof.chunk_call(1, 20, 64, 64, 128)
    assert few == 20 * a_token and few_moved == 2 * 2_097_152 + 20 * 34_048


def X(name, t0_s, dur_s, **args):
    return {"name": name, "ph": "X", "ts": int(round(t0_s * 1e9)),
            "dur": int(round(dur_s * 1e9)), "tid": 1, "args": args or None,
            "profiled": False}


def _record(peaks, roof, measured_over_least=1.0, steps=40, layers=36):
    """A window of decode steps with 60 running rows, every fourth with a
    chunk of 100 real tokens, and the trace of an implementation that moves
    the rows' state once each way at ``1 / measured_over_least`` of the
    HBM's peak."""
    events, bench = [], []
    chunks = 0
    for i in range(steps):
        t0 = T_OPEN + 0.030 * i
        bench.append((0.030 * i, 0.030 * i + 0.029))
        events.append(X("serving/step", t0 + 20e-6, 0.029, step=i,
                        decode=60))
        events.append(X("serving/decode", t0 + 0.001, 0.002, live=60,
                        state_rows=60))
        if i % 4 == 0:
            chunks += 1
            events.append(X("serving/prefill_chunk", t0 + 0.004, 0.002,
                            pos=128, len=100, state_rows=1,
                            ssm_chunk_tokens=100))
    decode = roof.least_seconds(*roof.decode_call(60, 64, 64, 128), peaks)
    chunk = roof.least_seconds(*roof.chunk_call(1, 100, 64, 64, 128), peaks)
    d_calls, c_calls = 10 * layers, 3 * layers
    record = {
        "spans": {"bench/step": bench},
        "facts": {"seconds": 0.030 * steps, "prefill_chunk": 128},
        "peaks": peaks, "kernel_dims": {"H": 32, "KV": 8, "D": 64, "L": 40},
        "config": {"mamba_n_heads": 64, "mamba_d_head": 64,
                   "mamba_d_state": 128},
        "trace": {"device0": {"busy_s": 0.3, "custom_calls": {
            "ssm_decode.14": {"count": d_calls // 2, "total_s":
                              d_calls // 2 * decode * measured_over_least},
            "ssm_decode.15": {"count": d_calls // 2, "total_s":
                              d_calls // 2 * decode * measured_over_least},
            "ssm_chunk.3": {"count": c_calls, "total_s":
                            c_calls * chunk * measured_over_least},
            "paged_decode.11": {"count": 40, "total_s": 0.02}}}}}
    return record, events


def test_new_readers_on_a_hand_written_record(manifest, peaks, roof,
                                              monkeypatch):
    record, events = _record(peaks, roof)
    monkeypatch.setattr(program_spans, "program_events", lambda: events)
    assert manifest.layer_reader("ssm_chunk_tokens_mean")(record) == 100
    assert manifest.layer_reader("state_rows_mean.agents")(record) == 60
    calls = record["trace"]["device0"]["custom_calls"]
    share = manifest.layer_reader("ssm_dev_share")(record)
    assert share == pytest.approx(100 * sum(
        c["total_s"] for name, c in calls.items()
        if name.startswith("ssm_")) / 0.3)
    # the rows' state moved once each way at the HBM's peak: the whole
    # roofline, and not a hair over it
    full = manifest.layer_reader("ssm_roofline")(record)
    assert full == pytest.approx(100.0) and full <= 100.0 + 1e-9
    slower, _ = _record(peaks, roof, measured_over_least=2.5)
    assert manifest.layer_reader("ssm_roofline")(slower) \
        == pytest.approx(40.0)
    # a program that sets no such attribute: nothing, no raise
    bare = [dict(e, args={k: v for k, v in (e["args"] or {}).items()
                          if k not in ("state_rows", "ssm_chunk_tokens")})
            for e in events]
    monkeypatch.setattr(program_spans, "program_events", lambda: bare)
    for name in ("ssm_roofline", "ssm_chunk_tokens_mean",
                 "state_rows_mean.agents"):
        assert manifest.layer_reader(name)(record) is None


def test_the_cell_rehearses_on_the_cpu():
    """``perf/tools/rehearse.py``: the same entry, generator, reference and
    readers at the toy sizes, the state kernels and the paged read in
    interpret mode; a process of its own, as the builder runs it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perf", "tools", "rehearse.py"),
         "--workload", CELL, "--trace", "1"], cwd=ROOT, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["rehearsal"] and out["passed"] and not out["failures"]
    assert "metrics" not in out
    values, facts = out["rehearsal_values_not_metrics"], out["facts"]
    assert facts["kernel_active"] and facts["window_counters"][
        "compiles_in_window"] == 0
    assert facts["window_counters"]["preempted"] == 0
    assert len(facts["reference_check"]) >= 2
    assert all(c["ok"] for c in facts["reference_check"])
    for name in ("ssm_chunk_tokens_mean", "state_rows_mean.agents",
                 "chunk_steps_share.agents", "pages_peak_share.agents",
                 "live_slots_mean.agents"):
        assert name in values, sorted(values)
    # (the two windows are placed apart: the harness's clock and the
    # ring's; the rows a dispatch is told are the slots that run)
    assert values["state_rows_mean.agents"]["value"] == pytest.approx(
        values["live_slots_mean.agents"]["value"], rel=0.05)
