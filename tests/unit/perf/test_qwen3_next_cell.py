"""The benchmark entries and readers PR 60 brought for
``serve-qwen3-next-80b-rag``: the cell's files are found by name, the
traffic is the issue's, the configuration is the catalog row's but for the
three cuts it lists, the file's arithmetic against the built model (shapes
alone) and against ``perf/tools/qwen3_next_limits.py``, that every
per-layer metric the cell joined moves the metric it is judged on and has a
reader, that the new readers (files without an entry: ``per_layer`` stands
at its cap) find nothing and do not raise on a program without DeltaNet
layers, the roofline's counts by hand, the readers on a hand-written record
(shares never over 100 %), and the cell's rehearsal on the CPU.

Membership only: nothing here asserts a position in, or the length of, a
list that a later cell may join (ROADMAP R0 (a))."""

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

CELL = "serve-qwen3-next-80b-rag"
CONFIG = "qwen3-next-80b-a3b-ep4"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# readers that arrive as files without an entry (perf/tools/read_layers.py
# prints them), and the accepted reader the cell reads the same way
NEW = ["gdn_dev_share", "gdn_roofline"]
READ_TOO = ["moe_local_share"]
# accepted entries (all move serve_tok_s) whose list the cell joined
JOINED = ["moe_dev_share", "moe_roofline", "moe_load_max_over_mean",
          "moe_experts_touched_mean", "compiles_in_window.ide",
          "serve_step_ms_p50.ide", "live_slots_mean.ide",
          "chunk_steps_share.ide", "prefill_dev_share.ide",
          "pallas_share.ide", "peak_hbm_gb.ide", "pages_peak_share.ide",
          "step_sync_wait_ms_p50.ide", "step_host_serial_ms_p50.ide",
          "prefill_wait_p50_ms.ide", "step_exposed_host_ms_p50.tok",
          "step_enqueue_ms_p50.tok", "step_prepare_ms_p50.tok",
          "step_device_calls_mean.tok", "step_idle_unnamed_ms.tok"]
REDUCED = ["num_hidden_layers", "num_experts", "max_position_embeddings"]
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
                                    "gdn_roofline.py"), "gdn_roof")


def test_the_cells_files_are_found_by_name(manifest):
    cell = manifest.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "rag-closed", 1)
    assert len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert manifest.cell(CELL) == {"name": CELL, "config": CONFIG,
                                   "traffic": "rag-closed",
                                   "trace_seconds": 3.0}
    config = manifest.config(CONFIG)
    assert config["reference"]["file"] == "qwen3_next"
    reference = manifest.reference("qwen3_next")
    assert callable(reference.make_forward)
    assert callable(reference.check_greedy)
    assert config["entry"] == "serve" and config["chips"] == 1
    names = {m["name"] for m in manifest.metrics_for(CELL, "end_to_end")}
    assert names == {"serve_tok_s", "setup_s"}
    entry, = [c for c in manifest.data["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == config["reduced"] == REDUCED
    assert entry["source"] == config["source"]
    assert entry["file"] == f"perf/configs/{CONFIG}.json"
    assert len(entry["why"]) <= 200
    # one configuration, one cell of it
    assert [w["name"] for w in manifest.data["workloads"]
            if w["config"] == CONFIG] == [CELL]


def test_the_traffic_is_the_issues(manifest):
    traffic = manifest.traffic("rag-closed")
    assert traffic["generator"] == "closed_loop_clients"
    assert traffic["params"] == {
        "clients": 96, "think_s": 0.0, "lead_in_s": 60.0,
        "prompt_len": {"median": 3072, "sigma": 0.5, "min": 1024,
                       "max": 6144},
        "output_len": {"median": 384, "sigma": 0.5, "min": 128,
                       "max": 1024}}
    config = manifest.config(CONFIG)
    # prompt + answer inside the served context, and inside the pages a
    # full house can ask for; a caller a slot, and a queue that takes every
    # caller's first request at once; every prompt goes chunk by chunk
    assert 6144 + 1024 <= config["max_position_embeddings"] == 8192 \
        == config["model"]["config_kwargs"]["max_seq_len"]
    assert config["server"] == {
        "dtype": "bf16", "num_slots": 96, "max_queue_depth": 96,
        "prefill_chunk": 512,
        "paged_kv": {"num_pages": 5632, "page_size": 128,
                     "prefix_cache": False}}
    assert traffic["params"]["clients"] == config["server"]["num_slots"]
    assert 96 * -(-(6144 + 1024) // 128) == 5376 <= 5632
    assert traffic["params"]["prompt_len"]["min"] > 512


def test_the_configuration_is_the_catalog_rows_but_for_its_cuts(manifest):
    """Every key of the catalog's entry (the model-configs guide) under the
    same name: as published, but the three under ``reduced``, each with the
    published value beside it; the program's arguments say the same."""
    config = manifest.config(CONFIG)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row, = [r for r in map(json.loads, f)
                    if r["name"] == "Qwen3-Next-80B-A3B-Instruct"]
        published = row["config"]
        assert row["source_url"] == config["source"]
    else:                           # (the guide is not on this machine)
        published = {key: config[key] for key in (
            "hidden_size", "head_dim", "vocab_size")}
    assert (published["hidden_size"], published["head_dim"],
            published["vocab_size"]) == (2048, 256, 151936)
    cut = {"num_hidden_layers": 8, "num_experts": 128,
           "max_position_embeddings": 8192}
    assert sorted(cut) == sorted(config["reduced"]) \
        == sorted(config["reduced_how"])
    assert {key: config[key] for key in published} \
        == {**published, **{k: v for k, v in cut.items() if k in published}}
    assert {key: config["published"][key] for key in cut} \
        == {"num_hidden_layers": 48, "num_experts": 512,
            "max_position_embeddings": 262144}
    # layers 0-7: two whole periods of the published pattern
    every = config["full_attention_interval"]
    pattern = ["full_attention" if (i + 1) % every == 0
               else "linear_attention" for i in range(8)]
    kw = config["model"]["config_kwargs"]
    assert config["layer_types"] == kw["layer_types"] == pattern \
        == ["linear_attention"] * 3 + ["full_attention"] \
        + ["linear_attention"] * 3 + ["full_attention"]
    assert (kw["n_embd"], kw["n_layer"], kw["n_head"], kw["n_kv_head"],
            kw["head_size"], kw["vocab_size"], kw["ffn_dim"]) == (
        config["hidden_size"], 8, config["num_attention_heads"],
        config["num_key_value_heads"], config["head_dim"],
        config["vocab_size"], config["moe_intermediate_size"])
    assert (kw["gdn_n_key_heads"], kw["gdn_n_value_heads"],
            kw["gdn_d_head"], kw["gdn_d_conv"]) == (
        config["linear_num_key_heads"], config["linear_num_value_heads"],
        config["linear_key_head_dim"], config["linear_conv_kernel_dim"])
    assert config["linear_key_head_dim"] == config["linear_value_head_dim"]
    assert (kw["rotary_pct"], kw["rope_theta"], kw["layer_norm_epsilon"]) \
        == (config["partial_rotary_factor"], config["rope_theta"],
            config["rms_norm_eps"])
    # the router keeps its width and its ten a token; 128 experts are here
    assert (kw["n_experts"], kw["experts_held"], kw["experts_per_token"],
            kw["norm_topk_prob"], kw["n_shared_experts"]) == (
        512, 128, config["num_experts_per_tok"], True, 1)
    assert config["shared_expert_intermediate_size"] \
        == kw["n_shared_experts"] * kw["ffn_dim"]
    said = " ".join(config["assumed"]) + config["deployment"]
    for word in ("multi-token-prediction", "[q (2,048) ; k (2,048)",
                 "normal with spread 0.1", "4 pipeline stages x 4 chips"):
        assert word in said, word


def test_the_counts_are_the_built_models(manifest):
    """``jax.eval_shape`` of the model the cell builds: nothing is
    allocated. Parameters (the issue's count, to the unit), the state a
    slot and the pages to the byte, and the published total."""
    import jax
    import jax.numpy as jnp

    from perf import build

    config = manifest.config(CONFIG)
    model, cfg = build.build_model(config["model"], None, False)
    assert cfg.hybrid == "gdn" and cfg.hybrid_period == (3, 0, 2)
    assert cfg.first_k_dense == 0 and cfg.norm == "rmsnorm1p"
    assert (cfg.head_dim, cfg.kv_heads, cfg.rotary_pct) == (256, 2, 0.25)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32),
                           method=model.logits)["params"])

    def count(tree):
        return sum(leaf.size for leaf in jax.tree_util.tree_leaves(tree))

    assert count(shapes) == config["parameters"] == 4_133_998_720
    assert config["weight_bytes"] == 2 * count(shapes)
    assert 8.26e9 < config["weight_bytes"] < 8.28e9
    a_layer = config["parameters_a_layer"]
    gdn = shapes["gdn_blocks"]["block"]["gdn"]
    assert count(gdn) == 6 * a_layer["gdn_mixer"] == 6 * 33_718_464
    assert gdn["qkvz_proj"]["kernel"].shape == (6, 2048, 12288)
    assert gdn["ba_proj"]["kernel"].shape == (6, 2048, 64)
    assert gdn["conv_w"].shape == (6, 4, 8192)
    assert gdn["o_proj"]["kernel"].shape == (6, 4096, 2048)
    attn = shapes["attn_blocks"]["block"]["attn"]
    assert count(attn) == 2 * a_layer["gated_attention"] == 2 * 27_263_488
    assert attn["q_proj"]["kernel"].shape == (2, 2048, 4096) \
        == attn["z_proj"]["kernel"].shape
    assert attn["k_proj"]["kernel"].shape == (2, 2048, 512)
    experts = shapes["experts"]
    assert experts["gate_proj"].shape == (8, 128, 2048, 512)
    assert count(experts) == 8 * a_layer["routed_experts_held"] \
        == 8 * 128 * a_layer["one_expert"]
    assert a_layer["one_expert"] == 3 * 2048 * 512 == 3_145_728
    assert a_layer["routed_experts_published"] == 512 * 3_145_728
    mlp = shapes["gdn_blocks"]["block"]["mlp"]
    assert mlp["router"].shape == (6, 2048, 512)
    assert count(mlp) == 6 * (a_layer["router"]
                              + a_layer["shared_expert_and_gate"])
    assert a_layer["gdn_layer_with_held_experts"] == 440_572_096
    assert a_layer["attention_layer_with_held_experts"] == 434_117_120
    assert config["embedding_and_head_parameters"] == 2 * 151936 * 2048
    assert "lm_head" in shapes
    # the whole model by the same count: 79.7 B beside the published 80 B
    total = config["published"]["parameters_by_this_count"]
    outside = a_layer["router"] + a_layer["shared_expert_and_gate"] \
        + a_layer["norms"]
    assert total == 36 * (33_718_464 + outside + 512 * 3_145_728) \
        + 12 * (27_263_488 + outside + 512 * 3_145_728) \
        + 2 * 151936 * 2048 + 2048
    assert 79.6e9 < total < 79.8e9
    spec = model.kv_cache_spec()
    assert spec.kinds == ("gdn", "routed") and spec.rep == 8
    state = config["state"]
    assert spec.state_bytes_per_row == state["bytes_a_slot"] == 12_877_824 \
        == 6 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)
    assert state["bytes_resident"] == 96 * 12_877_824
    pages = jax.eval_shape(lambda: spec.paged_cache(
        5632, 128, num_slots=96))
    assert set(pages) == {"k", "v", "s", "conv"}
    assert pages["s"].shape == (6, 96, 32, 128, 128) \
        and pages["s"].dtype == jnp.float32
    assert pages["conv"].shape == (6, 96, 3 * 8192)
    assert pages["k"].shape == pages["v"].shape == (2, 5632, 2, 256, 128)
    assert 2 * pages["k"].size * 2 == config["kv_bytes"]["pages"] \
        == 2 * config["kv_bytes_per_token_a_layer"] * 5632 * 128
    resident = config["resident_bytes"]
    assert resident == config["weight_bytes"] + state["bytes_resident"] \
        + config["kv_bytes"]["pages"]
    assert 0.78 < resident / 15.75e9 < 0.80
    # the tool prints the same arithmetic from the file
    from perf.tools import qwen3_next_limits as limits

    out = limits.least(64, 3700)
    assert out["parameters"]["served"] == config["parameters"]
    assert out["parameters"]["published_by_this_count"] == total
    assert out["bytes"]["resident"] == resident
    assert out["bytes"]["kv_pages"] == config["kv_bytes"]["pages"]
    assert out["step"]["assignments"] == 5760
    assert 127.9 < out["step"]["experts_touched_a_layer"] <= 128
    assert 12 < out["step"]["step_ms_at_hbm_peak"] < 13


def test_every_metric_the_cell_joined_moves_what_it_is_judged_on(manifest):
    reported = {m["name"] for m in manifest.metrics_for(CELL, "end_to_end")}
    layer = {m["name"]: m for m in manifest.metrics_for(CELL, "per_layer")}
    for name in JOINED:
        assert name in layer, name
        assert CELL in layer[name]["workloads"]
        assert layer[name]["moves"] == "serve_tok_s"
    for name in ("setup_import_s", "setup_build_s", "setup_compile_s"):
        assert name in layer and layer[name]["moves"] == "setup_s"
    for m in layer.values():
        assert m["moves"] in reported, m
        assert callable(manifest.layer_reader(m["name"]))
    # no list that moves another cell's judged metric was joined, and the
    # new readers are files without an entry
    every = {m["name"]: m for m in manifest.data["per_layer"]}
    for name, m in every.items():
        if m["moves"] in ("gap_p90_ms", "gap_p50_ms", "train_tok_s_chip"):
            assert CELL not in m.get("workloads", []), name
    for name in NEW:
        assert name not in every
        assert callable(manifest.layer_reader(name))
    assert CELL not in every["window_pages_peak_share"]["workloads"]
    assert CELL not in every["moe_local_share"]["workloads"]
    assert (every["moe_roofline"]["unit"], every["moe_roofline"]["better"]) \
        == ("%", "higher")


@pytest.mark.parametrize("name", NEW + READ_TOO)
def test_new_readers_find_nothing_where_there_is_nothing(manifest, name,
                                                         monkeypatch):
    """The parent's record, and a K/V model's: no span attribute, no named
    call. The reader returns None and does not raise."""
    monkeypatch.setattr(program_spans, "program_events", lambda: [])
    read = manifest.layer_reader(name)
    assert read({"facts": {}, "end_to_end": {}, "counters": {},
                 "samples": {}, "spans": {}}) is None
    trace = {"device0": {"busy_s": 1.0, "custom_calls": {
        "paged_decode.3": {"count": 10, "total_s": 0.1},
        "kda_decode.4": {"count": 10, "total_s": 0.1}}}}
    assert read({"trace": trace, "peaks": {}, "kernel_dims": {},
                 "facts": {}, "spans": {}, "config": {}}) is None
    assert read({"trace": trace, "peaks": {"hbm_bytes_per_s": 1.0},
                 "facts": {}, "spans": {}, "kernel_dims": {"H": 16},
                 "config": {"linear_num_key_heads": 16,
                            "linear_num_value_heads": 32,
                            "linear_key_head_dim": 128}}) is None


def test_the_roofline_counts_by_hand(roof, peaks):
    # a row's state in a layer: 32 value heads x 128 x 128 float32
    assert roof.state_bytes_a_row_a_layer(32, 128) == 2_097_152
    # q, k (16 x 128 each), v, o (32 x 128 each), the decay and beta (32
    # each), float32
    assert roof.vector_bytes_a_token(16, 32, 128) \
        == 4 * (2 * 2048 + 2 * 4096 + 64) == 49_408
    # gdn_decode, 64 rows of one layer: read and write of the state + the
    # vectors; seven operations a state element
    flops, moved = roof.decode_call(64, 16, 32, 128)
    assert moved == 64 * (2 * 2_097_152 + 49_408) == 271_597_568
    assert flops == 64 * 7 * 524_288
    # bytes lead by far: 0.33 ms a layer, 2.0 ms over the 6
    least = roof.least_seconds(flops, moved, peaks)
    assert least == moved / peaks["hbm_bytes_per_s"]
    assert 1.9e-3 < 6 * least < 2.1e-3
    half, half_moved = roof.decode_call(32, 16, 32, 128)
    assert (half, half_moved) == (flops / 2, moved / 2)     # by rows run
    # a dispatch's chunk, one row, 512 real tokens: the recurrence is the
    # cheaper form at d = 128 (7 d d against 6 d d + 4 x 128 d a head a
    # token), and the state is moved ONCE for the dispatch's four calls
    a_token = 32 * 7 * 128 * 128
    assert a_token == 32 * min(7 * 16384, 6 * 16384 + 4 * 128 * 128)
    flops, moved = roof.chunk_dispatch(1, 512, 16, 32, 128)
    assert flops == 512 * a_token
    assert moved == 2 * 2_097_152 + 512 * 49_408
    # real tokens alone: a chunk of 20 costs the state's bytes all the same
    few, few_moved = roof.chunk_dispatch(1, 20, 16, 32, 128)
    assert few == 20 * a_token and few_moved == 2 * 2_097_152 + 20 * 49_408


def X(name, t0_s, dur_s, **args):
    return {"name": name, "ph": "X", "ts": int(round(t0_s * 1e9)),
            "dur": int(round(dur_s * 1e9)), "tid": 1, "args": args or None,
            "profiled": False}


def _record(peaks, roof, measured_over_least=1.0, steps=40, layers=6):
    """A window of steps with 60 running rows, every one beside a chunk of
    500 real tokens (``prefill_chunk`` 512: four ``gdn_chunk`` calls a
    layer a dispatch); the routed layers count 1,400 of 5,600 assignments
    on 128 held experts a layer; the trace of an implementation that moves
    the rows' state once each way a dispatch at ``1 /
    measured_over_least`` of the HBM's peak."""
    events, bench = [], []
    for i in range(steps):
        t0 = T_OPEN + 0.030 * i
        bench.append((0.030 * i, 0.030 * i + 0.029))
        events.append(X("serving/step", t0 + 20e-6, 0.029, step=i,
                        decode=60, moe_assignments=8 * 1400,
                        moe_experts_touched=8 * 128, moe_layer_calls=8,
                        moe_load_max=30.0, moe_load_max_over_mean=2.7,
                        moe_routed_assignments=8 * 5600))
        events.append(X("serving/decode", t0 + 0.001, 0.002, live=60,
                        state_rows=60))
        events.append(X("serving/prefill_chunk", t0 + 0.004, 0.002,
                        pos=512, len=500, state_rows=1,
                        gdn_chunk_tokens=500))
    decode = roof.least_seconds(*roof.decode_call(60, 16, 32, 128), peaks)
    chunk = roof.least_seconds(*roof.chunk_dispatch(1, 500, 16, 32, 128),
                               peaks)
    dispatches = 10
    d_calls, c_calls = dispatches * layers, 4 * dispatches * layers
    record = {
        "spans": {"bench/step": bench},
        "facts": {"seconds": 0.030 * steps, "prefill_chunk": 512},
        "peaks": peaks, "kernel_dims": {"H": 16, "KV": 2, "D": 256, "L": 8},
        "config": {"linear_num_key_heads": 16, "linear_num_value_heads": 32,
                   "linear_key_head_dim": 128, "hidden_size": 2048,
                   "moe_intermediate_size": 512},
        "trace": {"device0": {"busy_s": 0.3, "custom_calls": {
            "gdn_decode.14": {"count": d_calls, "total_s":
                              d_calls * decode * measured_over_least},
            "gdn_chunk.3": {"count": c_calls, "total_s": dispatches
                            * layers * chunk * measured_over_least},
            "paged_write.11": {"count": 40, "total_s": 0.02}}}}}
    return record, events


def test_new_readers_on_a_hand_written_record(manifest, peaks, roof,
                                              monkeypatch):
    record, events = _record(peaks, roof)
    monkeypatch.setattr(program_spans, "program_events", lambda: events)
    assert manifest.layer_reader("state_rows_mean")(record) == 60
    assert manifest.layer_reader("moe_local_share")(record) \
        == pytest.approx(25.0)
    calls = record["trace"]["device0"]["custom_calls"]
    share = manifest.layer_reader("gdn_dev_share")(record)
    assert share == pytest.approx(100 * sum(
        c["total_s"] for name, c in calls.items()
        if name.startswith("gdn_")) / 0.3)
    # the rows' state moved once each way a dispatch at the HBM's peak: the
    # whole roofline, and not a hair over it
    full = manifest.layer_reader("gdn_roofline")(record)
    assert full == pytest.approx(100.0) and full <= 100.0 + 1e-9
    slower, _ = _record(peaks, roof, measured_over_least=2.5)
    assert manifest.layer_reader("gdn_roofline")(slower) \
        == pytest.approx(40.0)
    # the accepted readers the cell joined read the HELD experts' counts
    assert manifest.layer_reader("moe_experts_touched_mean")(record) \
        == pytest.approx(128)
    # a program that sets no such attribute: nothing, no raise
    drop = ("state_rows", "gdn_chunk_tokens", "moe_routed_assignments")
    bare = [dict(e, args={k: v for k, v in (e["args"] or {}).items()
                          if k not in drop}) for e in events]
    monkeypatch.setattr(program_spans, "program_events", lambda: bare)
    for name in ("gdn_roofline", "moe_local_share"):
        assert manifest.layer_reader(name)(record) is None


def test_the_routed_share_of_the_roofline_cannot_pass_the_whole(manifest,
                                                                peaks, roof,
                                                                monkeypatch):
    """``moe_roofline``'s reader, which the cell joined, on the cell's
    counters: ``moe_assignments`` and ``moe_experts_touched`` are of the
    HELD experts, which the kernels ran; an implementation that reads each
    of the 128 held experts' three matrices once at the HBM's peak reads
    100 %. Had the counters been the router's (5,600 assignments a layer,
    not 1,400), the same trace would read over it."""
    moe = load_module(os.path.join(ROOT, "perf", "layer_metrics",
                                   "moe_roofline.py"), "moe_roof")
    record, events = _record(peaks, roof)
    least = moe.least_seconds(*moe.expert_call(128, 1400, 2048, 512), peaks)
    calls = record["trace"]["device0"]["custom_calls"]
    calls["moe_gate_up.5"] = {"count": 320, "total_s": 320 * least * 0.6}
    calls["moe_down.6"] = {"count": 320, "total_s": 320 * least * 0.4}
    monkeypatch.setattr(program_spans, "program_events", lambda: events)
    assert manifest.layer_reader("moe_roofline")(record) \
        == pytest.approx(100.0)
    routed = [dict(e, args=dict(e["args"], moe_assignments=8 * 5600))
              if e["name"] == "serving/step" else e for e in events]
    monkeypatch.setattr(program_spans, "program_events", lambda: routed)
    assert manifest.layer_reader("moe_roofline")(record) > 100.0


def test_the_cell_rehearses_on_the_cpu():
    """``perf/tools/rehearse.py``: the same entry, generator, reference and
    readers at the toy sizes, the state kernels, the page read and the
    expert products in interpret mode; a process of its own, as the
    builder runs it."""
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
                 "live_slots_mean.ide", "step_device_calls_mean.tok",
                 "setup_compile_s"):
        assert name in values, sorted(values)
    # 8 of 32 experts are held at the toy sizes
    assert values["moe_experts_touched_mean"]["value"] <= 8
