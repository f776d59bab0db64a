"""The benchmark entries PR 54 brought for ``serve-lfm2-24b-assist``: the
cell's files are found by name (never by position or by a list's length),
the traffic is the issue's letter for letter, the configuration is the
published one but for its two cuts, the file's arithmetic against the built
model (shapes alone), every accepted per-layer list the cell joined moves
``serve_tok_s`` and has a reader, no per-layer entry was added, the joined
readers on a hand-written record of the cell (the routed FFN's share of its
roofline from the cell's own widths, never over 100 %), the builder's
least-time instrument, and the cell's rehearsal on the CPU."""

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

CELL = "serve-lfm2-24b-assist"
CONFIG = "lfm2-24b-a2b-conv"
TRAFFIC = "assist-closed"
# accepted entries (all move serve_tok_s) whose list the cell joined; the
# per_layer list stands at its cap of 128, so the cell brings no entry
JOINED = ["moe_dev_share", "moe_roofline", "moe_load_max_over_mean",
          "moe_experts_touched_mean", "compiles_in_window.ide",
          "serve_step_ms_p50.ide", "live_slots_mean.ide",
          "chunk_steps_share.ide", "prefill_dev_share.ide",
          "pallas_share.ide", "peak_hbm_gb.ide", "pages_peak_share.ide",
          "step_sync_wait_ms_p50.ide", "step_host_serial_ms_p50.ide",
          "prefill_wait_p50_ms.ide", "step_exposed_host_ms_p50.tok",
          "step_enqueue_ms_p50.tok", "step_prepare_ms_p50.tok",
          "step_device_calls_mean.tok", "step_idle_unnamed_ms.tok"]
SETUP = ["setup_import_s", "setup_build_s", "setup_compile_s"]
PUBLISHED_TYPES = ["conv", "conv", "full_attention", "conv"] * 10
T_OPEN = 1000.0


@pytest.fixture(scope="module")
def manifest():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def peaks():
    with open(os.path.join(ROOT, "perf", "peaks.json")) as f:
        return json.load(f)["TPU v5 lite"]


def test_the_cells_files_are_found_by_name(manifest):
    cell = manifest.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200
    assert manifest.cell(CELL)["config"] == CONFIG
    assert manifest.cell(CELL)["traffic"] == TRAFFIC
    config = manifest.config(CONFIG)
    assert config["entry"] == "serve"
    assert config["reference"]["file"] == "lfm2_moe"
    assert callable(manifest.reference("lfm2_moe").make_forward)
    assert callable(manifest.reference("lfm2_moe").check_greedy)
    # what the joined readers ask of a configuration's file, as Mellum's
    assert config["trace"] == manifest.config(
        "mellum2-12b-a2b5-paged")["trace"]
    names = [m["name"] for m in manifest.metrics_for(CELL, "end_to_end")]
    assert sorted(names) == ["serve_tok_s", "setup_s"]
    entry, = [c for c in manifest.data["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "max_position_embeddings"]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    assert entry["file"] == f"perf/configs/{CONFIG}.json"
    assert len(entry["why"]) <= 200
    # appended: behind the configuration and the cell the parent ended with
    configs = [c["name"] for c in manifest.data["configs"]]
    cells = [w["name"] for w in manifest.data["workloads"]]
    assert configs.index(CONFIG) > configs.index("kimi-linear-48b-a3b-ep8")
    assert cells.index(CELL) > cells.index("serve-kimi-linear-48b-longform")
    assert [w["name"] for w in manifest.data["workloads"]
            if w["config"] == CONFIG] == [CELL]     # the one cell


def test_the_traffic_is_the_issues(manifest):
    traffic = manifest.traffic(TRAFFIC)
    assert traffic["generator"] == "closed_loop_clients"
    assert traffic["params"] == {
        "clients": 256, "think_s": 0.0, "lead_in_s": 75.0,
        "prompt_len": {"median": 512, "sigma": 0.4, "min": 256,
                       "max": 1024},
        "output_len": {"median": 1536, "sigma": 0.5, "min": 512,
                       "max": 3072}}
    config = manifest.config(CONFIG)
    # prompt + answer inside the served context; a caller a slot, a queue
    # that takes every caller's first request at once, and pages for every
    # slot's whole context: no request is ever preempted for pages
    assert 1024 + 3072 <= config["max_position_embeddings"] == 4096 \
        == config["model"]["config_kwargs"]["max_seq_len"]
    assert config["server"] == {
        "dtype": "bf16", "num_slots": 256, "max_queue_depth": 256,
        "prefill_chunk": 128,
        "paged_kv": {"num_pages": 8192, "page_size": 128,
                     "prefix_cache": False}}
    assert traffic["params"]["clients"] == config["server"]["num_slots"]
    assert 8192 * 128 == 256 * 4096
    # every prompt is longer than a chunk: none takes the bucketed admission
    assert traffic["params"]["prompt_len"]["min"] \
        > config["server"]["prefill_chunk"]


def test_the_configuration_is_the_published_one_but_for_its_cuts(manifest):
    """Every key of the catalog's entry (the model-configs guide) under the
    same name: as published, but the two under ``reduced``, each with the
    published value beside it; the program's arguments say the same."""
    config = manifest.config(CONFIG)
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
        "num_experts_per_tok": 4, "num_hidden_layers": 40,
        "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536}
    cut = {"num_hidden_layers": 8, "max_position_embeddings": 4096}
    assert sorted(cut) == sorted(config["reduced"]) \
        == sorted(config["reduced_how"])
    assert {key: config[key] for key in published} == {**published, **cut}
    assert {key: config["published"][key] for key in cut} \
        == {key: published[key] for key in cut}
    # the published list's first eight: two whole periods
    assert config["published"]["layer_types"] == PUBLISHED_TYPES
    assert config["layer_types"] == PUBLISHED_TYPES[:8]
    assert [i for i, kind in enumerate(PUBLISHED_TYPES)
            if kind == "full_attention"] == list(range(2, 40, 4))
    assert config["assumed"] and config["deployment"]
    assert config["tie_word_embeddings"] is True and config["head_dim"] == 64
    kw = config["model"]["config_kwargs"]
    assert config["model"]["config_args"] == ["lfm2_moe"]
    assert kw["layer_types"] == PUBLISHED_TYPES[:8]
    assert (kw["n_embd"], kw["n_layer"], kw["n_head"], kw["n_kv_head"],
            kw["vocab_size"], kw["ffn_dim"], kw["dense_ffn_dim"]) == (
        2048, 8, 32, 8, 65536, 1536, 11776)
    assert (kw["n_experts"], kw["experts_per_token"], kw["first_k_dense"],
            kw["routed_scaling_factor"], kw["n_shared_experts"],
            kw["conv_taps"], kw["rope_theta"]) == (64, 4, 2, 1.0, 0, 3, 1e6)
    assert "experts_held" not in kw     # every expert is here


def test_the_counts_are_the_built_models(manifest):
    """``jax.eval_shape`` of the model the cell builds: nothing is
    allocated. Parameters (the issue's count, to the unit), the tail a
    slot and the pages to the byte, and the published total."""
    import jax
    import jax.numpy as jnp

    from perf import build

    config = manifest.config(CONFIG)
    model, cfg = build.build_model(config["model"], None, False)
    assert cfg.pos_emb == "rotary" and cfg.hybrid == "conv" and cfg.qk_norm
    assert cfg.hybrid_period == (2, 1, 2) and cfg.first_k_dense == 2
    assert cfg.head_dim == 64 and cfg.topk_norm_eps == 1e-6
    assert cfg.layer_types == ("conv", "conv", "attention", "conv") * 2
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32),
                           method=model.logits)["params"])

    def count(tree):
        return sum(leaf.size for leaf in jax.tree_util.tree_leaves(tree))

    assert count(shapes) == config["parameters"] == 4_025_293_440
    assert config["weight_bytes"] == 2 * count(shapes)
    a_layer = config["parameters_a_layer"]
    conv = shapes["conv_blocks"]["block"]["conv"]
    assert count(conv) == 4 * a_layer["conv_operator"] == 4 * 16_783_360
    assert count(shapes["dense_blocks"]["block"]["conv"]) == 2 * 16_783_360
    assert conv["in_proj"]["kernel"].shape == (4, 2048, 3 * 2048)
    assert conv["conv_w"].shape == (4, 3, 2048)
    attn = shapes["attn_blocks"]["block"]["attn"]
    assert count(attn) == 2 * a_layer["attention"] == 2 * 10_485_888
    assert attn["q_norm"]["scale"].shape == (2, 64)
    assert attn["k_proj"]["kernel"].shape == (2, 2048, 8 * 64)
    assert count(shapes["dense_blocks"]["block"]["mlp"]) \
        == 2 * a_layer["dense_ffn"] == 2 * 3 * 2048 * 11776
    experts = shapes["experts"]
    assert experts["gate_proj"].shape == (6, 64, 2048, 1536)
    assert count(experts) == 6 * a_layer["routed_experts"] \
        == 6 * 64 * a_layer["one_expert"]
    mlp = shapes["attn_blocks"]["block"]["mlp"]
    assert set(mlp) == {"router", "router_bias"}    # no shared expert
    assert count(mlp) == 2 * a_layer["router_and_bias"]
    assert config["embedding_and_head_parameters"] == 65536 * 2048
    assert "lm_head" not in shapes
    # the published model by the same count, the head tied: 23.84 B
    total = config["published"]["parameters_by_this_count_tied"]
    assert total == 30 * 16_783_360 + 10 * 10_485_888 \
        + 2 * a_layer["dense_ffn"] + 38 * (a_layer["routed_experts"]
                                           + a_layer["router_and_bias"]) \
        + 40 * a_layer["norms"] + 2048 + 65536 * 2048
    assert 23.8e9 < total < 23.9e9
    spec = model.kv_cache_spec()
    assert spec.kinds == ("conv", "routed")
    state = config["state"]
    assert spec.state_bytes_per_row == state["bytes_a_slot"] == 49_152 \
        == 6 * 2 * 2048 * 2
    assert state["bytes_resident"] == 256 * 49_152
    pages = jax.eval_shape(lambda: spec.paged_cache(
        8192, 128, num_slots=256))
    assert set(pages) == {"k", "v", "conv"}
    assert pages["conv"].shape == (6, 256, 4096) \
        and pages["conv"].dtype == jnp.bfloat16
    assert pages["k"].shape == (2, 8192, 8, 64, 128)
    assert 2 * pages["k"].size * 2 == config["kv_bytes"]["pages"] \
        == 2 * config["kv_bytes_per_token_a_layer"] * 8192 * 128
    resident = config["resident_bytes"]
    assert resident == config["weight_bytes"] + state["bytes_resident"] \
        + config["kv_bytes"]["pages"]
    assert 0.78 < resident / 15.75e9 < 0.79


def test_every_metric_of_the_cell_moves_something_it_reports(manifest):
    reported = {m["name"] for m in manifest.metrics_for(CELL, "end_to_end")}
    layer = manifest.metrics_for(CELL, "per_layer")
    names = [m["name"] for m in layer]
    # the three of the set-up that every cell reports, and the joined
    assert sorted(names) == sorted(SETUP + JOINED)
    by_name = {m["name"]: m for m in layer}
    for m in layer:
        assert m["moves"] in reported, m
        assert callable(manifest.layer_reader(m["name"]))
    for name in JOINED:
        assert by_name[name]["moves"] == "serve_tok_s"
        assert CELL in by_name[name]["workloads"]
        assert "serve-mellum2-12b-ide" in by_name[name]["workloads"]
    tok_s, = [m for m in manifest.data["end_to_end"]
              if m["name"] == "serve_tok_s"]
    assert CELL in tok_s["workloads"] and tok_s["bound"] == 0.04
    # no entry was added: the list stands at the contract's cap, and no
    # entry names this cell alone
    assert len(manifest.data["per_layer"]) == 128
    assert not [m["name"] for m in manifest.data["per_layer"]
                if m.get("workloads") == [CELL]]
    # the cell joined no list that moves another metric, and no window's
    assert not [m["name"] for m in manifest.data["per_layer"]
                if CELL in m.get("workloads", ())
                and m["moves"] != "serve_tok_s"]
    assert "window_pages_peak_share" not in names
    roofline = by_name["moe_roofline"]
    assert (roofline["unit"], roofline["better"]) == ("%", "higher")


def X(name, t0_s, dur_s, **args):
    return {"name": name, "ph": "X", "ts": int(round(t0_s * 1e9)),
            "dur": int(round(dur_s * 1e9)), "tid": 1, "args": args or None,
            "profiled": False}


def _record(manifest, peaks, measured_over_least=1.0, steps=40):
    """A window of the cell's steps: 254 rows decode, two steps in three
    carry a chunk of 128 tokens (a fused program), the six routed layers
    count ``rows x 4`` assignments on 63 experts a layer; the trace of
    expert products that read each touched expert's three matrices once at
    ``1 / measured_over_least`` of the HBM's peak."""
    roof = load_module(os.path.join(ROOT, "perf", "layer_metrics",
                                    "moe_roofline.py"), "moe_roof_lfm2")
    config = manifest.config(CONFIG)
    events, bench, layer_calls, least = [], [], 0, 0.0
    for i in range(steps):
        t0 = T_OPEN + 0.020 * i
        bench.append((0.020 * i, 0.020 * i + 0.019))
        rows = 254 + (128 if i % 3 else 0)
        more = {"chunk": 128} if i % 3 else {}
        events.append(X("serving/step", t0 + 20e-6, 0.019, step=i,
                        decode=254, moe_assignments=6 * rows * 4,
                        moe_experts_touched=6 * 63, moe_layer_calls=6,
                        moe_load_max=40.0, moe_load_max_over_mean=2.4,
                        moe_bias_reordered=30, state_rows=254 + bool(more),
                        **more))
        layer_calls += 6
        least += 6 * roof.least_seconds(
            *roof.expert_call(63, rows * 4, 2048, 1536), peaks)
    busy = 0.020 * steps * 0.97
    record = {
        "spans": {"bench/step": bench},
        "facts": {"seconds": 0.020 * steps, "prefill_chunk": 128},
        "peaks": peaks, "config": config,
        "kernel_dims": {"H": 32, "KV": 8, "D": 64, "L": 8},
        "counters": {"compiles_in_window": 0, "num_pages": 8192,
                     "num_slots": 256, "slot_steps": 254 * steps,
                     "decode_steps": steps},
        "trace": {"device0": {"busy_s": busy, "custom_call_s": 0.8 * busy,
                              "modules": {}, "custom_calls": {
            "moe_gate_up.28": {"count": layer_calls, "total_s":
                               0.62 * least * measured_over_least},
            "moe_down.28": {"count": layer_calls, "total_s":
                            0.38 * least * measured_over_least},
            "paged_decode.9": {"count": 2 * steps, "total_s": 0.2 * busy},
        }}}}
    return record, events, least


def test_the_joined_readers_on_a_hand_written_record(manifest, peaks,
                                                     monkeypatch):
    """``moe_roofline`` reads the cell's OWN widths (the file's
    ``hidden_size`` 2,048 and ``moe_intermediate_size`` 1,536, not
    Mellum's): expert products that read each touched expert's weights once
    at the HBM's peak read 100 %, and not a hair over; slower ones their
    share. The shares of device time stay under 100 %."""
    record, events, least = _record(manifest, peaks)
    monkeypatch.setattr(program_spans, "program_events", lambda: events)
    full = manifest.layer_reader("moe_roofline")(record)
    assert full == pytest.approx(100.0) and full <= 100.0 + 1e-9
    slower, _, _ = _record(manifest, peaks, measured_over_least=1.25)
    assert manifest.layer_reader("moe_roofline")(slower) \
        == pytest.approx(80.0)
    # with Mellum's widths (2304 x 896) the same trace would read another
    # number: the reader takes the widths from the record's configuration
    other = dict(record, config=manifest.config("mellum2-12b-a2b5-paged"))
    assert manifest.layer_reader("moe_roofline")(other) \
        != pytest.approx(100.0, abs=1.0)
    busy = record["trace"]["device0"]["busy_s"]
    share = manifest.layer_reader("moe_dev_share")(record)
    assert share == pytest.approx(100 * least / busy) and 0 < share < 100
    assert manifest.layer_reader("pallas_share.ide")(record) \
        == pytest.approx(80.0)
    assert manifest.layer_reader("moe_experts_touched_mean")(record) \
        == pytest.approx(63)
    assert manifest.layer_reader("moe_load_max_over_mean")(record) \
        == pytest.approx(2.4)
    assert manifest.layer_reader("chunk_steps_share.ide")(record) \
        == pytest.approx(100 * sum(1 for i in range(40) if i % 3) / 40)
    # a program that sets no such attribute, and a trace without the
    # calls: nothing, and no raise
    bare = [dict(e, args={"step": e["args"]["step"]}) for e in events]
    monkeypatch.setattr(program_spans, "program_events", lambda: bare)
    for name in ("moe_roofline", "moe_experts_touched_mean",
                 "moe_load_max_over_mean"):
        assert manifest.layer_reader(name)(record) is None
    record["trace"]["device0"]["custom_calls"] = {}
    assert manifest.layer_reader("moe_dev_share")(record) is None


def test_the_least_a_step_can_take_is_the_files_arithmetic(manifest, peaks):
    """``perf/tools/lfm2_limits.py --least 1``: bytes by part from the
    configuration's file alone."""
    tool = load_module(os.path.join(ROOT, "perf", "tools",
                                    "lfm2_limits.py"), "lfm2_limits")
    assert tool.WORKLOAD == CELL
    assert tool.ARMS == ("configured", "weights_float8", "tail_float8")
    out = tool.least(1400)
    assert out["slots"] == 256 and out["rows_an_expert"] == 16.0
    assert 63.99 < out["experts_touched_a_layer"] <= 64
    parts = out["bytes"]
    assert parts["experts_touched"] == pytest.approx(
        6 * 64 * 9_437_184 * 2, rel=1e-6)
    assert parts["kv_read"] == 256 * 1400 * 2 * 2048
    assert parts["tails_read_and_written"] == 2 * 256 * 49_152
    assert parts["other_weights"] == 8_050_586_880 - 6 * 64 * 9_437_184 * 2
    assert out["step_ms_at_hbm_peak"] == pytest.approx(
        1e3 * sum(parts.values()) / peaks["hbm_bytes_per_s"])
    assert 11 < out["step_ms_at_hbm_peak"] < 12.5


def test_the_cell_rehearses_on_the_cpu():
    """``perf/tools/rehearse.py``: the same entry, generator, reference and
    readers at the toy sizes, the K/V kernels and the expert products in
    interpret mode; a process of its own, as the builder runs it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perf", "tools", "rehearse.py"),
         "--workload", CELL, "--trace", "1", "--seconds", "2"], cwd=ROOT,
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
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
    for name in ("moe_experts_touched_mean", "moe_load_max_over_mean",
                 "chunk_steps_share.ide", "pages_peak_share.ide",
                 "live_slots_mean.ide", "compiles_in_window.ide",
                 "step_device_calls_mean.tok", "setup_compile_s"):
        assert name in values, sorted(values)
    # every one of the 8 toy experts is held
    assert values["moe_experts_touched_mean"]["value"] <= 8
    assert values["compiles_in_window.ide"]["value"] == 0
