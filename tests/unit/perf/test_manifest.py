"""BENCHMARK.json against the contract's rules, the last line's keys, and
the harness finding a configuration, a traffic mix, a cell and a per-layer
metric that were dropped in as new files, with no edit to a file that was
there."""

import json
import os
import re
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf.manifest import Manifest  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden_size", "hidden_dim", "intermediate", "latent",
               "state", "proj", "head_dim", "expansion", "experts_per_tok")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert 1 <= len(bench["command"]) <= 32
    assert all(_line(w) and not w.startswith("/") and ".." not in w
               for w in bench["command"])
    assert 1 <= len(bench["paths"]) <= 16
    assert all(PATH.match(p) for p in bench["paths"])
    rs = bench["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check with all 24 cells must fit 43200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs(bench):
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank"))
            assert not any(w in key for w in WIDTH_WORDS)
        with open(os.path.join(ROOT, c["file"])) as f:
            data = json.load(f)
        assert data["source"] == c["source"]
        assert data["reduced"] == c["reduced"]


def test_workloads(bench):
    cells = bench["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    configs = {c["name"] for c in bench["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)


def test_metrics(bench):
    e2e, layers = bench["end_to_end"], bench["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layers) <= 128
    names = [m["name"] for m in e2e + layers]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in bench["workloads"]}
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in layers:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
        assert m["moves"] in {e["name"] for e in e2e}
    for m in e2e + layers:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    setup = [m for m in e2e if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]


def test_every_cell_reports_setup_another_end_to_end_and_a_layer(bench):
    m = Manifest(ROOT)
    for w in bench["workloads"]:
        e2e = {x["name"] for x in m.metrics_for(w["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        layers = m.metrics_for(w["name"], "per_layer")
        assert layers
        # a per-layer metric is reported only where the metric it moves is
        assert all(x["moves"] in e2e for x in layers)
        # and every one of them has a reader, a cell file and its data
        for x in layers:
            assert callable(m.layer_reader(x["name"]))
        cell = m.cell(w["name"])
        assert cell["config"] == w["config"]
        assert cell["traffic"] == w["traffic"]
        traffic = m.traffic(w["traffic"])
        assert hasattr(m.generator(traffic["generator"]), "generate")
        config = m.config(w["config"])
        assert hasattr(m.entry(config["entry"]), "run")


def test_files_under_paths_are_named_from_the_characters_of_a_name(bench):
    import subprocess

    listed = subprocess.run(
        ["git", "ls-files", "--cached", "--others", "--exclude-standard",
         "--", *bench["paths"]], cwd=ROOT, capture_output=True, text=True)
    if listed.returncode != 0:
        pytest.skip("not a git checkout")
    files = listed.stdout.split()
    assert files
    assert all(PATH.match(f) for f in files), \
        [f for f in files if not PATH.match(f)]


def test_a_layer_reader_that_finds_nothing_is_left_out():
    m = Manifest(ROOT)
    record = {"counters": {"compiles_in_window": 0}, "spans": {},
              "samples": {}, "trace": None, "memory_peak_bytes": None,
              "config": m.config("gpt2-large-zero2"), "peaks": None}
    got = m.read_layer_metrics("train-gpt2-large-seq1k", record)
    assert got == {"compiles_in_window.train": {"value": 0.0,
                                                "unit": "count"}}


def test_new_files_are_discovered_without_editing_any(tmp_path):
    """A later PR adds a configuration, a traffic mix, a cell and a
    per-layer metric as NEW files plus APPENDED entries."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "perf"), root / "perf",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    before = {p: p.read_bytes() for p in (root / "perf").rglob("*")
              if p.is_file()}

    cfg = json.loads((root / "perf/configs/pythia-1.4b-paged.json")
                     .read_text())
    cfg["name"] = "pythia-1.4b-paged-128slots"
    cfg["server"]["num_slots"] = 128
    (root / "perf/configs/pythia-1.4b-paged-128slots.json").write_text(
        json.dumps(cfg))
    traffic = json.loads((root / "perf/traffic/chat-open.json").read_text())
    traffic["name"] = "chat-burst"
    traffic["params"]["rate_per_s"] = 2.5
    (root / "perf/traffic/chat-burst.json").write_text(json.dumps(traffic))
    (root / "perf/cells/serve-128-burst.json").write_text(json.dumps(
        {"name": "serve-128-burst", "config": cfg["name"],
         "traffic": "chat-burst", "trace_seconds": 1.5}))
    (root / "perf/layer_metrics/steps_in_window.py").write_text(
        "def read(record):\n    return len(record['spans']['bench/step'])\n")

    bench["configs"].append({
        "name": cfg["name"], "source": cfg["source"], "reduced": [],
        "file": "perf/configs/pythia-1.4b-paged-128slots.json",
        "why": "test"})
    bench["workloads"].append({
        "name": "serve-128-burst", "config": cfg["name"],
        "traffic": "chat-burst", "chips": 1, "why": "test"})
    for metric in bench["end_to_end"]:
        if metric["name"] == "gap_p90_ms":
            metric["workloads"].append("serve-128-burst")
    bench["per_layer"].append({
        "name": "steps_in_window", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "server step",
        "moves": "gap_p90_ms", "workloads": ["serve-128-burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    m = Manifest(str(root))
    entry = m.workload("serve-128-burst")
    assert m.config(entry["config"])["server"]["num_slots"] == 128
    assert m.traffic(entry["traffic"])["params"]["rate_per_s"] == 2.5
    assert m.cell("serve-128-burst")["trace_seconds"] == 1.5
    load = m.generator(m.traffic("chat-burst")["generator"]).generate(
        m.traffic("chat-burst")["params"], 1, 10.0,
        {"vocab_size": 50304, "context_len": 2048})
    assert load.requests
    got = m.read_layer_metrics("serve-128-burst", {
        "spans": {"bench/step": [(0.0, 0.1), (0.1, 0.2)]}})
    assert got == {"steps_in_window": {"value": 2.0, "unit": "count"}}
    assert {x["name"] for x in m.metrics_for("serve-128-burst",
                                             "end_to_end")} == \
        {"gap_p90_ms", "setup_s"}
    # nothing that was there has changed
    assert all(p.read_bytes() == data for p, data in before.items())


FOUND = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def _record(**extra):
    m = Manifest(ROOT)
    base = {"failures": [], "attempted": 40, "failed": 0,
            "setup_s": 33.25, "memory_peak_bytes": 13958643712,
            "end_to_end": {"ttft_p50_ms": 81.5, "gap_p50_ms": 100.125,
                           "gap_p90_ms": 120.25, "gap_p99_ms": 160.5,
                           "serve_tok_s": 1.0},
            "counters": {"compiles_in_window": 0, "num_devices": 1,
                         "decode_steps": 10, "slot_steps": 300,
                         "num_pages": 768},
            "spans": {"bench/step": [(0.0, 0.04), (0.04, 0.08)]},
            "samples": {"pages_mapped": [100, 384], "queue_wait_ms": [1.0],
                        "submit_late_ms": [0.5, 2.0],
                        "traced_decode_steps": [(4096, 16), (4160, 16)]},
            "kernel_dims": {"H": 16, "KV": 16, "D": 128, "L": 24},
            "config": m.config("pythia-1.4b-paged"), "peaks": m.peaks()[
                "TPU v5 lite"], "trace": None}
    base.update(extra)
    return m, base


def test_the_weights_come_from_one_program_whatever_the_seed(monkeypatch):
    """The seed is an argument of the jitted init, not a constant of it: a
    run at a seed the compile cache has not met finds the program there."""
    import jax
    import jax.numpy as jnp

    from perf import build

    m = Manifest(ROOT)
    config = m.config("pythia-1.4b-paged")
    model, _ = build.build_model(config["model"], None, True)
    programs, real_jit = [], jax.jit

    def jit(fn, **kw):
        jitted = real_jit(fn, **kw)

        def call(*args):
            programs.append(jitted.lower(*args).as_text())
            return jitted(*args)
        return call

    monkeypatch.setattr(jax, "jit", jit)

    def tree(seed):
        return build.init_params(
            model, (jnp.zeros((1, 8), jnp.int32),),
            {"method": getattr(model, config["model"]["init_method"])},
            seed, cast_to=jnp.bfloat16)

    a, b, c = tree(7), tree(7), tree(3900045001)
    assert programs[0] == programs[1] == programs[2]
    leaves = [jax.tree_util.tree_leaves(t) for t in (a, b, c)]
    assert all(x.dtype == jnp.bfloat16 for x in leaves[0])
    assert all(bool((x == y).all()) for x, y in zip(leaves[0], leaves[1]))
    assert any(bool((x != y).any()) for x, y in zip(leaves[0], leaves[2]))


@pytest.mark.parametrize("seconds", [20.0, 30.0, 45.0])
def test_an_open_loop_is_traced_at_the_windows_end_whatever_its_length(
        seconds):
    m = Manifest(ROOT)
    placement = m.entry("serve").trace_placement
    # chat, the open loop: the window's last 3 s, the profiler stopped after
    # the loop (its stop stalls the one thread for many seconds)
    at, length, stop_in_window = placement(
        m.cell("serve-pythia-1b4-chat"), seconds)
    assert (at + length, length, stop_in_window) == (seconds, 3.0, False)
    # a closed loop: from 30 % of the window, stopped there
    docs = m.cell("serve-pythia-1b4-docs")
    assert "trace_at" not in docs
    assert placement(docs, seconds) == (
        0.3 * seconds, docs.get("trace_seconds", 3.0), True)


def test_last_line_untraced_has_exactly_the_contracts_keys():
    from perf.run import assemble_result

    m, record = _record()
    line = assemble_result(m, "serve-pythia-1b4-chat", record, FOUND, False)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # chat is judged on its median gap since PR 45 (ISSUE 45 step 4 (b))
    assert line["metrics"] == {
        "gap_p50_ms": {"value": 100.125, "unit": "ms"},
        "setup_s": {"value": 33.25, "unit": "s"}}
    json.dumps(line)


def test_last_line_traced_carries_layer_metrics_busy_window_breakdown():
    from perf import trace_reduce
    from perf.run import assemble_result

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", "small_trace.json")) as f:
        reduced = trace_reduce.reduce_trace(json.load(f))
    m, record = _record(trace=reduced)
    line = assemble_result(m, "serve-pythia-1b4-chat", record, FOUND, True)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes", "busy_s",
                                   "window_s"}
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in line["breakdown"].values())
    declared = {x["name"]: x["unit"] for x in m.metrics_for(
        "serve-pythia-1b4-chat", "per_layer")}
    assert set(line["metrics"]) <= set(declared)
    assert all(v["unit"] == declared[k] for k, v in line["metrics"].items())
    assert line["metrics"]["pages_peak_share.chat"]["value"] == 50.0
    assert line["metrics"]["live_slots_mean.chat"]["value"] == 30.0
    assert line["metrics"]["serve_step_ms_p50.chat"]["value"] == \
        pytest.approx(40.0)
    assert line["metrics"]["gen_late_p99_ms.chat"]["value"] == \
        pytest.approx(1.985)
    # two custom calls of 3 ms in the small trace, each held to the bytes
    # of ~4128 cached tokens' K and V at the v5e's 819 GB/s
    least = 2 * (2 * 4128 * 16 * 128 * 2 + 2 * 16 * 16 * 128 * 2) / 8.19e11
    assert line["metrics"]["pallas_roofline.chat"]["value"] == \
        pytest.approx(100 * least / 0.003, rel=1e-6)
    assert line["metrics"]["ttft_p50_ms.chat"]["value"] == 81.5
    assert line["metrics"]["gap_p99_ms.chat"]["value"] == 160.5
    # the 90th gap is a per-layer metric of this cell since PR 45
    assert line["metrics"]["gap_p90_ms.chat"]["value"] == 120.25
    # no end-to-end metric rides on a traced line
    assert not {"gap_p50_ms", "gap_p90_ms", "setup_s"} & set(line["metrics"])
    json.dumps(line)


def test_a_traced_run_with_no_device_operation_is_not_correct():
    from perf.run import assemble_result

    m, record = _record()
    line = assemble_result(m, "serve-pythia-1b4-chat", record, FOUND, True)
    assert line["correct"] is False and "breakdown" not in line


def test_the_command_takes_the_contracts_four_flags_and_no_other():
    """No knob beside --workload/--seed/--seconds/--trace can change what a
    cell runs under the cell's name (the tools reach run_cell themselves)."""
    import subprocess

    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perf", "run.py"), "--workload",
         "serve-pythia-1b4-chat", "--override", "{}"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    assert proc.returncode == 2 and "unrecognized arguments" in proc.stderr
    assert proc.stdout == ""


def _knee_row(**over):
    row = {"failed": 0, "no_first_token": 0, "preempted": 0,
           "backlog_mid_end": [1.0, 1.5], "ttft_p50_ms": 900.0,
           "gap_mean_ms": 210.0}
    row.update(over)
    return row


@pytest.mark.parametrize("over,want", [
    ({}, True),
    ({"backlog_mid_end": [1.0, 2.5]}, False),       # the backlog grows
    ({"no_first_token": 1}, False),                 # a request unserved
    ({"preempted": 2}, False),                      # the pool overflowed
    ({"ttft_p50_ms": 1700.0}, False),               # the wait left its level
    ({"gap_mean_ms": 270.0}, False),
])
def test_knee_criterion_sees_backlog_wherever_it_waits(over, want):
    from perf.tools import find_knee

    light = _knee_row(ttft_p50_ms=800.0, gap_mean_ms=200.0)
    assert find_knee.sustained(_knee_row(**over), light, 2.0, 1.25) is want
