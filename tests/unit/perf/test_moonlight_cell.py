"""The benchmark entries and readers PR 38 brought for
``serve-moonlight-16b-reason``: what the cell reports, that every per-layer
metric it lists has a reader that finds nothing (and does not raise) on a
program without latent attention, the functions the latent read's roofline
counts with, the readers on a hand-written record, and the cell's rehearsal
on the CPU."""

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

CELL = "serve-moonlight-16b-reason"
NEW = ["mla_dev_share", "mla_roofline", "latent_tokens_read_mean"]
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
                                    "mla_roofline.py"), "mla_roof")


def test_the_cell_reports_the_gap_and_the_setup(manifest):
    names = [m["name"] for m in manifest.metrics_for(CELL, "end_to_end")]
    assert sorted(names) == ["gap_p90_ms", "setup_s"]
    cell = manifest.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "moonlight-16b-a3b-mla", "reason-closed", 1)


def test_the_traffic_is_the_issues(manifest):
    traffic = manifest.traffic("reason-closed")
    assert traffic["generator"] == "closed_loop_clients"
    assert traffic["params"] == {
        "clients": 64, "think_s": 0.0, "lead_in_s": 60.0,
        "prompt_len": {"median": 1536, "sigma": 0.6, "min": 256,
                       "max": 4096},
        "output_len": {"median": 2048, "sigma": 0.5, "min": 512,
                       "max": 4096}}
    # prompt + answer inside the published context
    config = manifest.config("moonlight-16b-a3b-mla")
    assert 4096 + 4096 <= config["max_position_embeddings"]
    assert traffic["params"]["clients"] == config["server"]["num_slots"]


@pytest.mark.parametrize("name", [
    "queue_wait_p50_ms", "decode_dev_ms_p50", "gen_late_p99_ms",
    "ttft_p50_ms", "gap_p99_ms", "gen_tok_s", "served_tok_s",
    "step_exposed_host_ms_p50.gap", "step_enqueue_ms_p50.gap",
    "step_prepare_ms_p50.gap", "step_device_calls_mean.gap",
    "step_idle_unnamed_ms.gap"])
def test_accepted_readers_that_move_the_gap_list_the_cell(manifest, name):
    entry, = [m for m in manifest.data["per_layer"] if m["name"] == name]
    assert entry["moves"] == "gap_p90_ms" and CELL in entry["workloads"]


def test_every_metric_of_the_cell_moves_something_it_reports(manifest):
    reported = {m["name"] for m in manifest.metrics_for(CELL, "end_to_end")}
    layer = manifest.metrics_for(CELL, "per_layer")
    names = {m["name"] for m in layer}
    assert len(layer) >= 30
    for m in layer:
        assert m["moves"] in reported, m
        assert callable(manifest.layer_reader(m["name"]))
    assert set(NEW) | {"moe_dev_share.reason", "moe_roofline.reason",
                       "chunk_steps_share.reason",
                       "pages_peak_share.reason"} <= names
    for m in layer:
        if m["name"] in ("mla_roofline", "moe_roofline.reason", "gen_tok_s",
                         "live_slots_mean.reason"):
            assert m["better"] == "higher", m
        if m["name"] in NEW:
            assert m["layer"] == "latent attention"
            assert m["workloads"] == [CELL]


@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_where_there_is_nothing(manifest, name):
    """The parent's record, and a K/V model's: no span attribute, no named
    call. The reader returns None and does not raise."""
    read = manifest.layer_reader(name)
    assert read({"facts": {}, "end_to_end": {}, "counters": {},
                 "samples": {}, "spans": {}}) is None
    trace = {"device0": {"busy_s": 1.0, "custom_calls": {
        "paged_decode.3": {"count": 10, "total_s": 0.1}}}}
    assert read({"trace": trace, "peaks": {}, "kernel_dims": {},
                 "facts": {}, "spans": {}, "config": {}}) is None


def test_the_roofline_counts_the_rows_that_are_read_once(roof, peaks):
    # 64 slots at 3,072 cached rows each, one layer: bytes lead
    flops, moved = roof.decode_call(64 * 3072, 64, 16, 512, 64)
    rows = 64 * 3072 * 1152
    assert rows < moved < 1.02 * rows
    assert 0.27e-3 < roof.least_seconds(flops, moved, peaks) < 0.29e-3
    half, _ = roof.decode_call(32 * 3072, 32, 16, 512, 64)
    assert half == flops / 2                     # by rows read, not by slots
    # a chunk of 128 behind 4,096: operations lead, the absorbed form's
    flops, moved = roof.chunk_call(4096 + 128, 128, 16, 512, 64, 128, 128)
    pairs = 128 * (4224 - 63.5)
    assert flops == 2.0 * 16 * (576 + 512) * pairs
    assert roof.least_seconds(flops, moved, peaks) \
        == flops / peaks["bf16_flops_per_s"]
    # where rebuilding K and V is cheaper (many query rows a cached row),
    # that form's operations are the least
    wide, _ = roof.chunk_call(4096, 4096, 16, 512, 64, 128, 128)
    assert wide < 2.0 * 16 * (576 + 512) * 4096 * (4096 - 4095 / 2.0)


def X(name, t0_s, dur_s, **args):
    return {"name": name, "ph": "X", "ts": int(round(t0_s * 1e9)),
            "dur": int(round(dur_s * 1e9)), "tid": 1, "args": args or None,
            "profiled": False}


def _record(peaks, measured_over_least=1.0, steps=40, layers=7):
    """A window of plain decode steps, 64 running slots at 3,000 cached
    rows each, and the trace of an implementation that reads every cached
    row ONCE at ``1 / measured_over_least`` of the HBM's peak."""
    events, bench = [], []
    tokens, live = 64 * 3000 + 64, 64
    for i in range(steps):
        t0 = T_OPEN + 0.030 * i
        bench.append((0.030 * i, 0.030 * i + 0.029))
        events.append(X("serving/step", t0 + 20e-6, 0.029, step=i,
                        decode=live))
        events.append(X("serving/decode", t0 + 0.001, 0.002, live=live,
                        latent_tokens_read=tokens,
                        latent_rows_written=live))
    moved = 2 * (tokens * 576 + live * 576 + live * 16 * (576 + 512))
    a_call = moved / peaks["hbm_bytes_per_s"] * measured_over_least
    calls = 10 * layers
    record = {
        "spans": {"bench/step": bench}, "facts": {"seconds": 0.030 * steps,
                                                  "prefill_chunk": 128},
        "peaks": peaks, "kernel_dims": {"H": 16, "KV": 16, "D": 128, "L": 7},
        "config": {"kv_lora_rank": 512, "qk_rope_head_dim": 64,
                   "qk_nope_head_dim": 128, "v_head_dim": 128},
        "trace": {"device0": {"busy_s": 0.3, "custom_calls": {
            "mla_decode.7": {"count": calls, "total_s": calls * a_call},
            "moe_down.3": {"count": 60, "total_s": 0.09}}}}}
    return record, events, tokens


def test_new_readers_on_a_hand_written_record(manifest, peaks, monkeypatch):
    record, events, tokens = _record(peaks)
    monkeypatch.setattr(program_spans, "program_events", lambda: events)
    assert manifest.layer_reader("latent_tokens_read_mean")(record) == tokens
    share = manifest.layer_reader("mla_dev_share")(record)
    assert share == pytest.approx(
        100 * record["trace"]["device0"]["custom_calls"]["mla_decode.7"][
            "total_s"] / 0.3)
    # every latent row read once at the HBM's peak: the whole roofline,
    # and not a hair over it
    full = manifest.layer_reader("mla_roofline")(record)
    assert full == pytest.approx(100.0) and full <= 100.0 + 1e-9
    slower, _, _ = _record(peaks, measured_over_least=2.5)
    assert manifest.layer_reader("mla_roofline")(slower) \
        == pytest.approx(40.0)
    # a program that sets no such attribute: nothing, no raise
    bare = [dict(e, args={k: v for k, v in (e["args"] or {}).items()
                          if not k.startswith("latent_")}) for e in events]
    monkeypatch.setattr(program_spans, "program_events", lambda: bare)
    assert manifest.layer_reader("mla_roofline")(record) is None
    assert manifest.layer_reader("latent_tokens_read_mean")(record) is None


def test_the_cell_rehearses_on_the_cpu():
    """``perf/tools/rehearse.py``: the same entry, generator, reference and
    readers at the toy sizes, the absorbed read as the kernel in interpret
    mode; a process of its own, as the builder runs it."""
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
    assert len(facts["reference_check"]) >= 2
    assert all(c["ok"] for c in facts["reference_check"])
    for name in ("latent_tokens_read_mean", "moe_experts_touched_mean.reason",
                 "chunk_steps_share.reason", "pages_peak_share.reason"):
        assert name in values, sorted(values)
