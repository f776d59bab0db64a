"""The benchmark entries PR 56 brought for ``serve-minicpm-sala-9b-longdoc``:
the cell's files are found by name (membership, never position), the traffic
is the issue's, the configuration is the published one but for its two cuts,
the file's arithmetic against the built model (shapes alone), every accepted
per-layer list the cell joined moves ``gap_p90_ms`` and has a reader, the six
readers the PR brought (which wait for room in the manifest: its per-layer
list stands at its cap of 128) on a hand-written record, never over 100 %,
and the cell's rehearsal on the CPU."""

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
from perf.manifest import Manifest  # noqa: E402

CELL = "serve-minicpm-sala-9b-longdoc"
CONFIG = "minicpm-sala-9b-sparse"
TRAFFIC = "longdoc-closed"
# accepted entries (all move gap_p90_ms) whose list the cell joined: one
# variant a base name, those whose lists no other cell's test pins its own
# place in (the ``.reason`` lists and ``state_rows_mean`` end with Kimi
# Linear's cell by ``test_kimi_cell.py``, the un-suffixed and ``.gap`` ones
# with Granite's by ``test_granite_cell.py``: benchmark files, not this
# PR's to edit)
JOINED = ["compiles_in_window.cont", "serve_step_ms_p50.cont",
          "live_slots_mean.cont", "chunk_steps_share.cont",
          "prefill_dev_share.cont", "pallas_share.cont",
          "peak_hbm_gb.cont", "pages_peak_share.agents",
          "step_sync_wait_ms_p50.cont", "step_host_serial_ms_p50.cont",
          "prefill_wait_p50_ms.cont"]
SETUP = ["setup_import_s", "setup_build_s", "setup_compile_s"]
BROUGHT = ["sparse_dev_share", "sparse_roofline", "sparse_tokens_read_mean",
           "sparse_index_rows_mean", "lightning_dev_share",
           "lightning_roofline"]
S, L = "minicpm4", "lightning-attn"
PUBLISHED = [S] + [L] * 8 + [S] + [L] * 6 + [S, S] + [L] * 4 + [S] \
    + [L] * 6 + [S] * 3
SPARSE = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
          "init_blocks": 1, "window_size": 2048, "topk": 64,
          "dense_len": 8192}
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
    assert manifest.cell(CELL) == {"name": CELL, "config": CONFIG,
                                   "traffic": TRAFFIC, "trace_seconds": 3.0}
    config = manifest.config(CONFIG)
    assert config["entry"] == "serve"
    assert config["reference"]["file"] == "minicpm_sala"
    assert callable(manifest.reference("minicpm_sala").make_forward)
    assert callable(manifest.reference("minicpm_sala").check_greedy)
    names = [m["name"] for m in manifest.metrics_for(CELL, "end_to_end")]
    assert sorted(names) == ["gap_p90_ms", "setup_s"]
    entry, = [c for c in manifest.data["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "max_position_embeddings"]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json")
    assert entry["file"] == f"perf/configs/{CONFIG}.json"
    assert len(entry["why"]) <= 200
    # membership, and behind what the parent had (no pin on "the last")
    configs = [c["name"] for c in manifest.data["configs"]]
    cells = [w["name"] for w in manifest.data["workloads"]]
    assert configs.index(CONFIG) > configs.index("lfm2-24b-a2b-conv")
    assert cells.index(CELL) > cells.index("serve-lfm2-24b-assist")
    assert [w["name"] for w in manifest.data["workloads"]
            if w["config"] == CONFIG] == [CELL]
    assert sum(w["chips"] == 4 for w in manifest.data["workloads"]) == 1


def test_the_traffic_is_the_issues(manifest):
    traffic = manifest.traffic(TRAFFIC)
    assert traffic["generator"] == "closed_loop_clients"
    assert traffic["params"] == {
        "clients": 32, "think_s": 0.0, "lead_in_s": 120.0,
        "prompt_len": {"median": 24576, "sigma": 0.3, "min": 16384,
                       "max": 32768},
        "output_len": {"median": 4096, "sigma": 0.4, "min": 2048,
                       "max": 8192}}
    config = manifest.config(CONFIG)
    assert 32768 + 8192 <= config["max_position_embeddings"] == 40960 \
        == config["model"]["config_kwargs"]["max_seq_len"]
    assert config["server"] == {
        "dtype": "bf16", "num_slots": 32, "prefill_chunk": 512,
        "paged_kv": {"num_pages": 10240, "page_size": 128,
                     "prefix_cache": False}}
    assert traffic["params"]["clients"] == config["server"]["num_slots"]
    assert 10240 * 128 == 32 * 40960
    # every prompt is past dense_len (every decode row chooses) and longer
    # than a chunk (none takes the bucketed admission)
    assert traffic["params"]["prompt_len"]["min"] > SPARSE["dense_len"] \
        > config["server"]["prefill_chunk"]


def test_the_configuration_is_the_published_one_but_for_its_cuts(manifest):
    """Every key of the catalog's entry (the model-configs guide) under the
    same name: as published, but the two under ``reduced`` and the
    ``mixer_types`` cut with the layers, each with the published value
    beside it; the program's arguments say the same."""
    config = manifest.config(CONFIG)
    published = {
        "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 4096,
        "intermediate_size": 16384, "lightning_head_dim": 128,
        "lightning_nh": 32, "lightning_nkv": 32,
        "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
        "max_position_embeddings": 524288, "model_type": "minicpm_sala",
        "num_attention_heads": 32, "num_hidden_layers": 32,
        "num_key_value_heads": 2, "qk_norm": True, "rand_init": False,
        "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000,
        "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
        "dim_model_base": 256, "tie_word_embeddings": False,
        "use_output_gate": True, "use_output_norm": True,
        "attn_use_output_gate": True}
    cut = {"num_hidden_layers": 12, "max_position_embeddings": 40960}
    assert sorted(cut) == sorted(config["reduced"]) \
        == sorted(config["reduced_how"])
    assert {key: config[key] for key in published} == {**published, **cut}
    assert {key: config["published"][key] for key in cut} \
        == {key: published[key] for key in cut}
    # the published list, irregular: sparse layers at 0, 9, 16, 17, 22, 29,
    # 30, 31; the cut is its layers 9-20, verbatim
    assert config["published"]["mixer_types"] == PUBLISHED
    assert [i for i, kind in enumerate(PUBLISHED) if kind == S] \
        == [0, 9, 16, 17, 22, 29, 30, 31]
    assert config["mixer_types"] == PUBLISHED[9:21] \
        == [S] + [L] * 6 + [S, S] + [L] * 3
    assert "layers 9-20 verbatim" in config["reduced_how"][
        "num_hidden_layers"]
    assert "524,288 -> 40,960" in config["reduced_how"][
        "max_position_embeddings"]
    assert config["sparse_config"] == SPARSE
    assumed = " ".join(config["assumed"])
    for said in ("kernel_size 32", "kernel_stride 16", "block_size 64",
                 "init_blocks 1", "window_size 2,048", "topk 64",
                 "dense_len 8,192", "decided a QUERY", "softmax",
                 "exp(-2^(-8 (h + 1) / 32))", "pages of 128"):
        assert said in assumed, said
    assert "3 pipeline stages" in config["deployment"] \
        and "ONE chip a layer" in config["deployment"]
    kw = config["model"]["config_kwargs"]
    assert config["model"]["config_args"] == ["minicpm_sala"]
    assert kw["mixer_types"] == PUBLISHED[9:21]
    assert (kw["n_embd"], kw["n_layer"], kw["n_head"], kw["n_kv_head"],
            kw["head_size"], kw["vocab_size"], kw["ffn_dim"]) == (
        4096, 12, 32, 2, 128, 73448, 16384)
    assert kw["sparse_attention"] == SPARSE
    assert (kw["embedding_multiplier"], kw["logits_scaling"]) == (12.0, 16.0)
    assert kw["residual_multiplier"] == pytest.approx(1.4 / 32 ** 0.5)


def test_the_counts_are_the_built_models(manifest):
    """``jax.eval_shape`` of the model the cell builds: nothing is
    allocated. Parameters to the unit, the state a slot, the pages and the
    index's leaf to the byte."""
    import jax
    import jax.numpy as jnp

    from perf import build

    config = manifest.config(CONFIG)
    model, cfg = build.build_model(config["model"], None, False)
    assert cfg.pos_emb == "none" and cfg.hybrid == "lightning" \
        and cfg.qk_norm and cfg.attn_output_gate
    assert not cfg.hybrid_repeats and cfg.hybrid_runs == (
        ("attention", 0, 1), ("lightning", 0, 6), ("attention", 1, 2),
        ("lightning", 6, 3))
    assert cfg.sparse._asdict() == SPARSE and cfg.head_dim == 128
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32),
                           method=model.logits)["params"])

    def count(tree):
        return sum(leaf.size for leaf in jax.tree_util.tree_leaves(tree))

    assert count(shapes) == config["parameters"] == 3_929_972_864
    assert config["weight_bytes"] == 2 * count(shapes)
    a_layer = config["parameters_a_layer"]
    attn = shapes["attn_blocks"]["block"]["attn"]
    assert count(attn) == 3 * a_layer["sparse_mixer"] \
        == 3 * (3 * 4096 * 4096 + 2 * 4096 * 256 + 2 * 128)
    assert attn["k_proj"]["kernel"].shape == (3, 4096, 2 * 128)
    assert attn["z_proj"]["kernel"].shape == (3, 4096, 4096)
    light = shapes["lightning_blocks"]["block"]["lightning"]
    assert count(light) == 9 * a_layer["lightning_mixer"] \
        == 9 * (5 * 4096 * 4096 + 3 * 128)
    assert light["o_norm"]["scale"].shape == (9, 128)
    assert count(shapes["attn_blocks"]["block"]["mlp"]) \
        == 3 * a_layer["ffn"] == 3 * 3 * 4096 * 16384
    assert config["embedding_and_head_parameters"] == 2 * 73448 * 4096
    assert shapes["lm_head"]["kernel"].shape == (4096, 73448)
    total = config["published"]["parameters_by_this_count"]
    assert 9.4e9 < total < 9.6e9
    spec = model.kv_cache_spec()
    assert spec.kinds == ("sparse", "lightning")
    state = config["state"]
    assert spec.state_bytes_per_row == state["bytes_a_slot"] \
        == 9 * 32 * 128 * 128 * 4
    assert state["bytes_resident"] == 32 * state["bytes_a_slot"]
    pages = jax.eval_shape(lambda: spec.paged_cache(
        10240, 128, num_slots=32))
    assert set(pages) == {"k", "v", "kc", "s"}
    assert pages["s"].shape == (9, 32, 32, 128, 128) \
        and pages["s"].dtype == jnp.float32
    assert pages["k"].shape == (3, 10240, 2, 128, 128)
    assert pages["kc"].shape == (3, 10240, 2, 8, 128) \
        and pages["kc"].dtype == jnp.float32
    assert 2 * pages["k"].size * 2 == config["kv_bytes"]["pages"] \
        == 3 * config["kv_bytes_per_token_a_layer"] * 10240 * 128
    assert pages["kc"].size * 4 == config["kv_bytes"]["index"] \
        == 3 * config["index_bytes_per_token_a_layer"] * 10240 * 128
    resident = config["resident_bytes"]
    assert resident == config["weight_bytes"] + state["bytes_resident"] \
        + config["kv_bytes"]["pages"] + config["kv_bytes"]["index"]
    assert 0.80 < resident / 15.75e9 < 0.82


def test_every_metric_of_the_cell_moves_something_it_reports(manifest):
    reported = {m["name"] for m in manifest.metrics_for(CELL, "end_to_end")}
    layer = manifest.metrics_for(CELL, "per_layer")
    names = [m["name"] for m in layer]
    assert sorted(names) == sorted(SETUP + JOINED)
    by_name = {m["name"]: m for m in layer}
    for m in layer:
        assert m["moves"] in reported, m
        assert callable(manifest.layer_reader(m["name"]))
    for name in JOINED:
        assert by_name[name]["moves"] == "gap_p90_ms"
        assert CELL in by_name[name]["workloads"]
        assert len(by_name[name]["workloads"]) == 2
    gap, = [m for m in manifest.data["end_to_end"]
            if m["name"] == "gap_p90_ms"]
    assert CELL in gap["workloads"] and gap["bound"] == 0.04
    # the list stands at the contract's cap: the six readers the PR brought
    # have a file each and no entry yet (PERF.md section 7)
    assert len(manifest.data["per_layer"]) == 128
    entered = {m["name"] for m in manifest.data["per_layer"]}
    for name in BROUGHT:
        assert name not in entered
        assert callable(manifest.layer_reader(name))
    assert not [m["name"] for m in manifest.data["per_layer"]
                if CELL in m.get("workloads", ())
                and m["moves"] != "gap_p90_ms"]


def X(name, t0_s, dur_s, **args):
    return {"name": name, "ph": "X", "ts": int(round(t0_s * 1e9)),
            "dur": int(round(dur_s * 1e9)), "tid": 1, "args": args or None,
            "profiled": False}


def _record(manifest, peaks, measured_over_least=1.0, steps=40):
    """A window of the cell's steps: 32 rows at 30,000 positions decode,
    two steps in five carry a chunk of 512 tokens at 20,480; a trace whose
    ``sparse_*`` and ``lightning_*`` calls take ``measured_over_least``
    times the least the readers reckon."""
    sparse = manifest.layer_reader("sparse_roofline").__globals__
    light = manifest.layer_reader("lightning_roofline").__globals__
    config = manifest.config(CONFIG)
    dims = (32, 2, 128)
    events, bench = [], []
    n_decode = n_chunk = 0
    for i in range(steps):
        t0 = T_OPEN + 0.030 * i
        bench.append((0.030 * i, 0.030 * i + 0.029))
        events.append(X("serving/step", t0 + 20e-6, 0.029, step=i,
                        decode=32))
        if i % 5 < 2:
            events.append(X("serving/prefill_chunk", t0 + 30e-6, 1e-4,
                            pos=20480, len=512, state_rows=1,
                            lightning_chunk_tokens=512, sparse_rows=512,
                            sparse_tokens_read=512 * 6144,
                            sparse_index_rows=512 * 1290))
            n_chunk += 1
        events.append(X("serving/decode", t0 + 200e-6, 1e-3, live=32,
                        state_rows=32, sparse_rows=32,
                        sparse_tokens_read=32 * 6144,
                        sparse_index_rows=32 * 1874))
        n_decode += 1
    least = sparse["least_seconds"]
    decode_least = least(*sparse["read_work"](32 * 6144, *dims), peaks)
    chunk_least = least(sparse["read_work"](512 * 6144, *dims)[0],
                        sparse["read_work"](6144, *dims)[1], peaks)
    l_decode = light["least_seconds"](*light["decode_call"](32, 32, 128),
                                      peaks)
    l_chunk = light["least_seconds"](
        *light["chunk_dispatch"](1, 512, 32, 128), peaks)
    busy = 0.030 * steps * 0.99
    m = measured_over_least
    record = {
        "spans": {"bench/step": bench},
        "facts": {"seconds": 0.030 * steps, "prefill_chunk": 512},
        "peaks": peaks, "config": config,
        "counters": {"compiles_in_window": 0, "num_pages": 10240,
                     "num_slots": 32, "slot_steps": 32 * steps,
                     "decode_steps": steps},
        "trace": {"device0": {"busy_s": busy, "custom_call_s": 0.4 * busy,
                              "modules": {}, "custom_calls": {
            "sparse_read.16": {"count": 3 * n_decode, "shape":
                               "(f32[65,16,128], f32[65,16,128])",
                               "total_s": 3 * n_decode * decode_least * m},
            "sparse_read_chunk.37": {
                "count": 3 * n_chunk,
                "shape": "(f32[1025,16,128], f32[1025,16,128])",
                "total_s": 3 * n_chunk * chunk_least * m},
            "lightning_decode.20": {
                "count": 9 * n_decode, "shape": "(f32[9,32,32,128,128])",
                "total_s": 9 * n_decode * l_decode * m},
            # four blocks of 128 tokens a Lightning layer a chunk
            "lightning_chunk.5": {
                "count": 4 * 9 * n_chunk, "shape": "(f32[9,32,32,128,128])",
                "total_s": 9 * n_chunk * l_chunk * m},
            "paged_write.3": {"count": 6 * steps, "total_s": 0.01 * busy},
        }}}}
    sparse_s = 3 * (n_decode * decode_least + n_chunk * chunk_least) * m
    light_s = 9 * (n_decode * l_decode + n_chunk * l_chunk) * m
    return record, events, sparse_s, light_s


def test_the_readers_the_pr_brought_on_a_hand_written_record(
        manifest, peaks, monkeypatch):
    """Calls that take the least their bytes and operations allow read
    100 % and not a hair over; slower ones their share; the counters are
    a row's; a program without the spans, or a trace without the calls,
    reads nothing and raises nothing (the parent commit)."""
    record, events, sparse_s, light_s = _record(manifest, peaks)
    monkeypatch.setattr(program_spans, "program_events", lambda: events)
    for name in ("sparse_roofline", "lightning_roofline"):
        full = manifest.layer_reader(name)(record)
        assert full == pytest.approx(100.0) and full <= 100.0 + 1e-9, name
    slower, _, _, _ = _record(manifest, peaks, measured_over_least=4.0)
    assert manifest.layer_reader("sparse_roofline")(slower) \
        == pytest.approx(25.0)
    assert manifest.layer_reader("lightning_roofline")(slower) \
        == pytest.approx(25.0)
    busy = record["trace"]["device0"]["busy_s"]
    assert manifest.layer_reader("sparse_dev_share")(record) \
        == pytest.approx(100 * sparse_s / busy)
    assert manifest.layer_reader("lightning_dev_share")(record) \
        == pytest.approx(100 * light_s / busy)
    assert manifest.layer_reader("sparse_tokens_read_mean")(record) == 6144
    assert manifest.layer_reader("sparse_index_rows_mean")(record) == 1874
    assert manifest.layer_reader("state_rows_mean")(record) == 32
    assert manifest.layer_reader("chunk_steps_share.cont")(record) \
        is not None
    # the bytes are the equations', not the implementation's pages: 6,144
    # tokens a KV head x 2 heads x 128 x 2 bytes x K and V a row a layer
    ops, moved = manifest.layer_reader("sparse_roofline").__globals__[
        "read_work"](6144, 32, 2, 128)
    assert moved == 6144 * 2 * 128 * 2 * 2 and ops == 4 * 6144 * 32 * 128
    bare = [dict(e, args={"step": 0}) for e in events]
    monkeypatch.setattr(program_spans, "program_events", lambda: bare)
    for name in BROUGHT:
        if not name.endswith("dev_share"):
            assert manifest.layer_reader(name)(record) is None, name
    record["trace"]["device0"]["custom_calls"] = {}
    monkeypatch.setattr(program_spans, "program_events", lambda: events)
    for name in ("sparse_dev_share", "sparse_roofline",
                 "lightning_dev_share", "lightning_roofline"):
        assert manifest.layer_reader(name)(record) is None, name


def test_the_cell_rehearses_on_the_cpu():
    """``perf/tools/rehearse.py``: the same entry, generator, reference and
    readers at the toy sizes (an irregular stack of four layers, contexts
    past the toy ``dense_len``), the kernels in interpret mode; a process of
    its own, as the builder runs it."""
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
    # every prompt is past the toy dense_len: the judged rows chose blocks
    assert min(c["prompt_len"] for c in facts["reference_check"]) >= 64 > 48
    for name in ("chunk_steps_share.cont", "pages_peak_share.agents",
                 "live_slots_mean.cont", "compiles_in_window.cont",
                 "setup_compile_s"):
        assert name in values, sorted(values)
    assert values["compiles_in_window.cont"]["value"] == 0
