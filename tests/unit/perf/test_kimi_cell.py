"""The benchmark entries and readers PR 50 brought for
``serve-kimi-linear-48b-longform``: the cell's files are found by name, the
traffic is the issue's, the configuration is the published one but for the
four cuts it lists, the file's arithmetic against the built model (shapes
alone), that every per-layer metric the cell lists moves a metric it
reports and has a reader, that the new readers find nothing (and do not
raise) on a program without KDA layers or a held share, the roofline's
counts by hand, the readers on a hand-written record (shares never over
100 %), and the cell's rehearsal on the CPU."""

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

CELL = "serve-kimi-linear-48b-longform"
CONFIG = "kimi-linear-48b-a3b-ep8"
NEW = ["kda_dev_share", "kda_roofline", "moe_local_share",
       "mla_roofline.longform"]
# accepted entries (all move gap_p90_ms) whose list the cell joined: the
# per_layer list stands at its cap of 128 with the four above
JOINED = ["moe_dev_share.reason", "moe_roofline.reason",
          "moe_load_max_over_mean.reason", "moe_experts_touched_mean.reason",
          "compiles_in_window.reason", "serve_step_ms_p50.reason",
          "live_slots_mean.reason", "chunk_steps_share.reason",
          "prefill_dev_share.reason", "pallas_share.reason",
          "peak_hbm_gb.reason", "pages_peak_share.reason",
          "step_sync_wait_ms_p50.reason", "step_host_serial_ms_p50.reason",
          "prefill_wait_p50_ms.reason", "state_rows_mean"]
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
                                    "kda_roofline.py"), "kda_roof")


def test_the_cells_files_are_found_by_name(manifest):
    cell = manifest.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longform-closed", 1)
    assert len(cell["why"]) <= 200
    assert manifest.cell(CELL)["config"] == CONFIG
    config = manifest.config(CONFIG)
    assert config["reference"]["file"] == "kimi_linear"
    assert callable(manifest.reference("kimi_linear").make_forward)
    assert callable(manifest.reference("kimi_linear").check_greedy)
    assert config["trace"]["kernel_family"] == "kda"
    names = [m["name"] for m in manifest.metrics_for(CELL, "end_to_end")]
    assert sorted(names) == ["gap_p90_ms", "setup_s"]
    entry, = [c for c in manifest.data["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "model_max_length"]
    assert entry["source"] == config["source"]
    # appended: behind the configuration and the cell the parent ended with
    configs = [c["name"] for c in manifest.data["configs"]]
    cells = [w["name"] for w in manifest.data["workloads"]]
    assert configs.index(CONFIG) > configs.index("granite-4.0-h-micro-hybrid")
    assert cells.index(CELL) > cells.index("serve-granite4h-3b-agents")


def test_the_traffic_is_the_issues(manifest):
    traffic = manifest.traffic("longform-closed")
    assert traffic["generator"] == "closed_loop_clients"
    assert traffic["params"] == {
        "clients": 128, "think_s": 0.0, "lead_in_s": 75.0,
        "prompt_len": {"median": 1024, "sigma": 0.6, "min": 256,
                       "max": 4096},
        "output_len": {"median": 2048, "sigma": 0.5, "min": 512,
                       "max": 4096}}
    config = manifest.config(CONFIG)
    # prompt + answer inside the served context; a caller a slot, and a
    # queue that takes every caller's first request at once
    assert 4096 + 4096 <= config["model_max_length"] == 8192 \
        == config["model"]["config_kwargs"]["max_seq_len"]
    assert config["server"] == {
        "dtype": "bf16", "num_slots": 128, "max_queue_depth": 128,
        "prefill_chunk": 128,
        "paged_kv": {"num_pages": 8192, "page_size": 128,
                     "prefix_cache": False}}
    assert traffic["params"]["clients"] == config["server"]["num_slots"]


def test_the_configuration_is_the_published_one_but_for_its_cuts(manifest):
    """Every key of the catalog's entry (the model-configs guide) under the
    same name: as published, but the four under ``reduced``, each with the
    published value beside it; the program's arguments say the same."""
    config = manifest.config(CONFIG)
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
        "linear_attn_config": {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                           19, 21, 22, 23, 25, 26],
            "num_heads": 32, "short_conv_kernel_size": 4},
        "mla_use_nope": True, "model_max_length": 1048576,
        "model_type": "kimi_linear", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts": 256,
        "num_experts_per_token": 8, "num_hidden_layers": 27,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
        "num_shared_experts": 1, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
        "vocab_size": 163840}
    cut = {"num_hidden_layers": 12, "num_experts": 32, "vocab_size": 20480,
           "model_max_length": 8192}
    assert sorted(cut) == sorted(config["reduced"]) \
        == sorted(config["reduced_how"])
    assert {key: config[key] for key in published} == {**published, **cut}
    assert {key: config["published"][key] for key in cut} \
        == {key: published[key] for key in cut}
    # the first 12 of the published pattern: three whole periods K K K M
    group = published["linear_attn_config"]
    pattern = ["attention" if n in group["full_attn_layers"] else "kda"
               for n in range(1, 13)]
    assert all((n in group["kda_layers"]) != (n in group["full_attn_layers"])
               for n in range(1, 28))
    assert config["layer_types"] == pattern \
        == ["kda", "kda", "kda", "attention"] * 3
    kw = config["model"]["config_kwargs"]
    assert kw["layer_types"] == pattern
    assert (kw["n_embd"], kw["n_layer"], kw["n_head"], kw["vocab_size"],
            kw["ffn_dim"], kw["dense_ffn_dim"]) == (2304, 12, 32, 20480,
                                                    1024, 9216)
    assert (kw["kv_lora_rank"], kw["qk_nope_head_dim"],
            kw["qk_rope_head_dim"], kw["v_head_dim"]) == (512, 128, 64, 128)
    assert (kw["kda_n_heads"], kw["kda_d_head"], kw["kda_d_conv"]) \
        == (32, 128, 4)
    # the router keeps its width and its eight a token; 32 experts are here
    assert (kw["n_experts"], kw["experts_held"], kw["experts_per_token"],
            kw["routed_scaling_factor"], kw["n_shared_experts"]) == (
        256, 32, 8, 2.446, 1)
    assert kw["mlp_layer_types"][:2] == ["dense", "sparse"]


def test_the_counts_are_the_built_models(manifest):
    """``jax.eval_shape`` of the model the cell builds: nothing is
    allocated. Parameters (the issue's count, to the unit), the state a
    slot and the latent pages to the byte, and the published total."""
    import jax
    import jax.numpy as jnp

    from perf import build

    config = manifest.config(CONFIG)
    model, cfg = build.build_model(config["model"], None, False)
    assert cfg.pos_emb == "none" and cfg.hybrid == "kda"
    assert cfg.hybrid_period == (3, 0, 3) and cfg.first_k_dense == 1
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32),
                           method=model.logits)["params"])

    def count(tree):
        return sum(leaf.size for leaf in jax.tree_util.tree_leaves(tree))

    assert count(shapes) == config["parameters"] == 3_176_867_744
    assert config["weight_bytes"] == 2 * count(shapes)
    a_layer = config["parameters_a_layer"]
    kda = shapes["kda_blocks"]["block"]["kda"]
    assert count(kda) == 8 * a_layer["kda_mixer"] == 8 * 39_514_272
    assert count(shapes["dense_blocks"]["block"]["kda"]) == 39_514_272
    assert kda["qkv_proj"]["kernel"].shape == (8, 2304, 3 * 4096)
    assert kda["conv_w"].shape == (8, 4, 3 * 4096)
    assert kda["f_a_proj"]["kernel"].shape == (8, 2304, 128)
    assert kda["f_b_proj"]["kernel"].shape == (8, 128, 4096)
    assert kda["b_proj"]["kernel"].shape == (8, 2304, 32)
    assert kda["o_norm"].shape == (8, 128)
    attn = shapes["attn_blocks"]["block"]["attn"]
    assert count(attn) == 3 * a_layer["latent_attention"] == 3 * 29_114_880
    assert count(shapes["dense_blocks"]["block"]["mlp"]) \
        == a_layer["dense_ffn"] == 3 * 2304 * 9216
    experts = shapes["experts"]
    assert experts["gate_proj"].shape == (11, 32, 2304, 1024)
    assert count(experts) == 11 * a_layer["routed_experts_held"] \
        == 11 * 32 * a_layer["one_expert"]
    assert a_layer["routed_experts_published"] == 256 * 7_077_888
    router = shapes["attn_blocks"]["block"]["mlp"]["router"]
    assert router.shape == (3, 2304, 256)
    assert config["embedding_and_head_parameters"] == 2 * 20480 * 2304
    assert "lm_head" in shapes
    # the published model by the same count: 49.12 B
    total = config["published"]["parameters_by_this_count"]
    assert total == 20 * 39_514_272 + 7 * 29_114_880 + 3 * 2304 * 9216 \
        + 26 * (256 * 7_077_888 + a_layer["shared_expert"]
                + a_layer["router_and_bias"]) + 27 * a_layer["norms"] \
        + 2304 + 2 * 163840 * 2304
    assert 49.1e9 < total < 49.2e9
    spec = model.kv_cache_spec()
    assert spec.kinds == ("kda", "latent", "routed")
    state = config["state"]
    assert spec.state_bytes_per_row == state["bytes_a_slot"] == 19_537_920 \
        == 9 * (32 * 128 * 128 * 4 + 3 * 12288 * 2)
    assert state["bytes_resident"] == 128 * 19_537_920
    pages = jax.eval_shape(lambda: spec.paged_cache(
        8192, 128, num_slots=128))
    assert set(pages) == {"c", "s", "conv"}
    assert pages["s"].shape == (9, 128, 32, 128, 128) \
        and pages["s"].dtype == jnp.float32
    assert pages["conv"].shape == (9, 128, 3 * 12288)
    assert pages["c"].shape == (3, 8192, 576, 128)
    assert pages["c"].size * 2 == config["kv_bytes"]["latent_pages"] \
        == 3 * config["kv_bytes_per_token_a_layer"] * 8192 * 128
    resident = config["resident_bytes"]
    assert resident == config["weight_bytes"] + state["bytes_resident"] \
        + config["kv_bytes"]["latent_pages"]
    assert 0.78 < resident / 15.75e9 < 0.80


def test_every_metric_of_the_cell_moves_something_it_reports(manifest):
    reported = {m["name"] for m in manifest.metrics_for(CELL, "end_to_end")}
    layer = manifest.metrics_for(CELL, "per_layer")
    names = [m["name"] for m in layer]
    # the three of the set-up that every cell reports, the joined, the new
    assert sorted(names) == sorted(
        ["setup_import_s", "setup_build_s", "setup_compile_s"] + JOINED + NEW)
    by_name = {m["name"]: m for m in layer}
    for m in layer:
        assert m["moves"] in reported, m
        assert callable(manifest.layer_reader(m["name"]))
    for name in JOINED:
        assert by_name[name]["workloads"][-1] == CELL
        assert len(by_name[name]["workloads"]) == 2
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "gap_p90_ms"
    assert [by_name[n]["layer"] for n in NEW] == [
        "KDA state layers", "KDA state layers", "routed FFN",
        "latent attention"]
    for name in ("kda_roofline", "mla_roofline.longform",
                 "moe_roofline.reason"):
        assert (by_name[name]["unit"], by_name[name]["better"]) \
            == ("%", "higher")
    # a contiguous run in this order, wherever later PRs append
    every = [m["name"] for m in manifest.data["per_layer"]]
    at = every.index(NEW[0])
    assert every[at:at + len(NEW)] == NEW
    assert len(every) <= 128        # (the contract's cap: full with these)
    # the satellite that was left out: an accepted test holds the three
    # set-up readers to NO list, so the new cell reports them
    assert all("workloads" not in m for m in manifest.data["per_layer"]
               if m["name"].startswith("setup_"))


@pytest.mark.parametrize("name", NEW)
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
                 "facts": {}, "spans": {}, "kernel_dims": {"H": 32},
                 "config": {"linear_attn_config": {"num_heads": 32,
                                                   "head_dim": 128},
                            "kv_lora_rank": 512, "qk_rope_head_dim": 64,
                            "qk_nope_head_dim": 128,
                            "v_head_dim": 128}}) is None


def test_the_roofline_counts_by_hand(roof, peaks):
    # a row's state in a layer: 32 heads x 128 x 128 float32
    assert roof.state_bytes_a_row_a_layer(32, 128) == 2_097_152
    # q, k, the decay, v, o (32 x 128 each) and beta (32), float32
    assert roof.vector_bytes_a_token(32, 128) == 4 * (5 * 4096 + 32) == 82_048
    # kda_decode, 128 rows of one layer: read and write of the state + the
    # vectors; seven operations a state element
    flops, moved = roof.decode_call(128, 32, 128)
    assert moved == 128 * (2 * 2_097_152 + 82_048) == 547_373_056
    assert flops == 128 * 7 * 524_288
    # bytes lead by far: 0.67 ms a layer, 6.0 ms over the 9
    least = roof.least_seconds(flops, moved, peaks)
    assert least == moved / peaks["hbm_bytes_per_s"]
    assert 5.9e-3 < 9 * least < 6.1e-3
    half, half_moved = roof.decode_call(64, 32, 128)
    assert (half, half_moved) == (flops / 2, moved / 2)     # by rows run
    # kda_chunk, one row, 128 real tokens: the recurrence is the cheaper
    # form at d = 128 (7 d d against 6 d d + 4 x 128 d a head a token)
    a_token = 32 * 7 * 128 * 128
    assert a_token == 32 * min(7 * 16384, 6 * 16384 + 4 * 128 * 128)
    flops, moved = roof.chunk_call(1, 128, 32, 128)
    assert flops == 128 * a_token
    assert moved == 2 * 2_097_152 + 128 * 82_048
    assert roof.least_seconds(flops, moved, peaks) \
        == moved / peaks["hbm_bytes_per_s"]
    # real tokens alone: a chunk of 20 costs the state's bytes all the same
    few, few_moved = roof.chunk_call(1, 20, 32, 128)
    assert few == 20 * a_token and few_moved == 2 * 2_097_152 + 20 * 82_048


def X(name, t0_s, dur_s, **args):
    return {"name": name, "ph": "X", "ts": int(round(t0_s * 1e9)),
            "dur": int(round(dur_s * 1e9)), "tid": 1, "args": args or None,
            "profiled": False}


def _record(peaks, roof, measured_over_least=1.0, steps=40, layers=9):
    """A window of decode steps with 120 running rows, every second with a
    chunk of 100 real tokens; the routed layers count 110 of 960
    assignments on 30 held experts a layer; the trace of an implementation
    that moves the rows' state once each way at ``1 / measured_over_least``
    of the HBM's peak."""
    events, bench = [], []
    chunks = 0
    for i in range(steps):
        t0 = T_OPEN + 0.030 * i
        bench.append((0.030 * i, 0.030 * i + 0.029))
        events.append(X("serving/step", t0 + 20e-6, 0.029, step=i,
                        decode=120, moe_assignments=11 * 110,
                        moe_experts_touched=11 * 30, moe_layer_calls=11,
                        moe_load_max=9.0, moe_load_max_over_mean=2.6,
                        moe_routed_assignments=11 * 960))
        events.append(X("serving/decode", t0 + 0.001, 0.002, live=120,
                        state_rows=120, latent_tokens_read=120 * 3000))
        if i % 2 == 0:
            chunks += 1
            events.append(X("serving/prefill_chunk", t0 + 0.004, 0.002,
                            pos=128, len=100, state_rows=1,
                            kda_chunk_tokens=100, latent_tokens_read=228))
    decode = roof.least_seconds(*roof.decode_call(120, 32, 128), peaks)
    chunk = roof.least_seconds(*roof.chunk_call(1, 100, 32, 128), peaks)
    d_calls, c_calls = 10 * layers, 5 * layers
    record = {
        "spans": {"bench/step": bench},
        "facts": {"seconds": 0.030 * steps, "prefill_chunk": 128},
        "peaks": peaks, "kernel_dims": {"H": 32, "KV": 32, "D": 72, "L": 12},
        "config": {"linear_attn_config": {"num_heads": 32, "head_dim": 128},
                   "hidden_size": 2304, "moe_intermediate_size": 1024,
                   "kv_lora_rank": 512, "qk_rope_head_dim": 64,
                   "qk_nope_head_dim": 128, "v_head_dim": 128},
        "trace": {"device0": {"busy_s": 0.3, "custom_calls": {
            "kda_decode.14": {"count": d_calls // 2, "total_s":
                              d_calls // 2 * decode * measured_over_least},
            "kda_decode.15": {"count": d_calls - d_calls // 2, "total_s":
                              (d_calls - d_calls // 2) * decode
                              * measured_over_least},
            "kda_chunk.3": {"count": c_calls, "total_s":
                            c_calls * chunk * measured_over_least},
            "paged_write.11": {"count": 40, "total_s": 0.02}}}}}
    return record, events


def test_new_readers_on_a_hand_written_record(manifest, peaks, roof,
                                              monkeypatch):
    record, events = _record(peaks, roof)
    monkeypatch.setattr(program_spans, "program_events", lambda: events)
    assert manifest.layer_reader("state_rows_mean")(record) == 120
    assert manifest.layer_reader("moe_local_share")(record) \
        == pytest.approx(100 * 110 / 960)
    calls = record["trace"]["device0"]["custom_calls"]
    share = manifest.layer_reader("kda_dev_share")(record)
    assert share == pytest.approx(100 * sum(
        c["total_s"] for name, c in calls.items()
        if name.startswith("kda_")) / 0.3)
    # the rows' state moved once each way at the HBM's peak: the whole
    # roofline, and not a hair over it
    full = manifest.layer_reader("kda_roofline")(record)
    assert full == pytest.approx(100.0) and full <= 100.0 + 1e-9
    slower, _ = _record(peaks, roof, measured_over_least=2.5)
    assert manifest.layer_reader("kda_roofline")(slower) \
        == pytest.approx(40.0)
    # the accepted readers the cell joined read the HELD experts' counts
    assert manifest.layer_reader("moe_experts_touched_mean.reason")(record) \
        == pytest.approx(30)
    # a program that sets no such attribute: nothing, no raise
    drop = ("state_rows", "kda_chunk_tokens", "moe_routed_assignments")
    bare = [dict(e, args={k: v for k, v in (e["args"] or {}).items()
                          if k not in drop}) for e in events]
    monkeypatch.setattr(program_spans, "program_events", lambda: bare)
    for name in ("kda_roofline", "moe_local_share", "state_rows_mean"):
        assert manifest.layer_reader(name)(record) is None


def test_the_routed_share_of_the_roofline_cannot_pass_the_whole(manifest,
                                                                peaks,
                                                                monkeypatch):
    """``moe_roofline``'s reader, which the cell joined, on the cell's
    counters: ``moe_assignments`` and ``moe_experts_touched`` are of the
    HELD experts, which the kernels ran; an implementation that reads each
    touched expert's three matrices once at the HBM's peak reads 100 %. Had
    the counters been the router's (960 assignments a layer, not 110), the
    same trace would read over it."""
    roof = load_module(os.path.join(ROOT, "perf", "layer_metrics",
                                    "moe_roofline.py"), "moe_roof")
    record, events = _record(peaks, load_module(os.path.join(
        ROOT, "perf", "layer_metrics", "kda_roofline.py"), "kda_roof2"))
    least = roof.least_seconds(*roof.expert_call(30, 110, 2304, 1024), peaks)
    calls = record["trace"]["device0"]["custom_calls"]
    calls["moe_gate_up.5"] = {"count": 440, "total_s": 440 * least * 0.6}
    calls["moe_down.6"] = {"count": 440, "total_s": 440 * least * 0.4}
    monkeypatch.setattr(program_spans, "program_events", lambda: events)
    assert manifest.layer_reader("moe_roofline.reason")(record) \
        == pytest.approx(100.0)
    routed = [dict(e, args=dict(e["args"], moe_assignments=11 * 960))
              if e["name"] == "serving/step" else e for e in events]
    monkeypatch.setattr(program_spans, "program_events", lambda: routed)
    assert manifest.layer_reader("moe_roofline.reason")(record) > 100.0


def test_the_cell_rehearses_on_the_cpu():
    """``perf/tools/rehearse.py``: the same entry, generator, reference and
    readers at the toy sizes, the state kernels, the latent read and the
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
    for name in ("moe_local_share", "state_rows_mean",
                 "moe_experts_touched_mean.reason",
                 "chunk_steps_share.reason", "pages_peak_share.reason",
                 "live_slots_mean.reason", "setup_compile_s"):
        assert name in values, sorted(values)
    # 8 of 32 experts are held at the toy sizes: about a quarter
    assert 10 < values["moe_local_share"]["value"] < 45
    assert values["moe_experts_touched_mean.reason"]["value"] <= 8
    assert values["state_rows_mean"]["value"] == pytest.approx(
        values["live_slots_mean.reason"]["value"], rel=0.05)
