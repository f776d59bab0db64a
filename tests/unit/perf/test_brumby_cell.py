"""The benchmark entries and readers PR 32 brought for
``serve-brumby-14b-continue``: what the cell reports, that every per-layer
metric it lists has a reader that finds nothing (and does not raise) on a
program without a recurrent state, and the two functions the retention
roofline counts with."""

import json
import os

import pytest

from perf.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
CELL = "serve-brumby-14b-continue"


@pytest.fixture(scope="module")
def manifest():
    return Manifest(ROOT)


def test_the_cell_reports_the_gap_and_the_setup(manifest):
    names = [m["name"] for m in manifest.metrics_for(CELL, "end_to_end")]
    assert sorted(names) == ["gap_p90_ms", "setup_s"]


@pytest.mark.parametrize("name", ["decode_dev_ms_p50", "gap_p99_ms",
                                  "ttft_p50_ms", "queue_wait_p50_ms",
                                  "gen_late_p99_ms"])
def test_accepted_readers_that_move_the_gap_list_the_cell(manifest, name):
    entry, = [m for m in manifest.data["per_layer"] if m["name"] == name]
    assert entry["moves"] == "gap_p90_ms" and CELL in entry["workloads"]


def test_every_metric_of_the_cell_moves_something_it_reports(manifest):
    reported = {m["name"] for m in manifest.metrics_for(CELL, "end_to_end")}
    layer = manifest.metrics_for(CELL, "per_layer")
    assert len(layer) >= 20
    for m in layer:
        assert m["moves"] in reported, m
        assert callable(manifest.layer_reader(m["name"]))
    # a constant of the configuration is no per-layer metric
    assert "state_hbm_share" not in {m["name"] for m in layer}
    # occupancy is what the state's memory is paid for: never better lower
    for m in layer:
        if m["name"] in ("live_slots_mean.cont", "state_rows_mean",
                         "gen_tok_s", "served_tok_s"):
            assert m["better"] == "higher", m


@pytest.mark.parametrize("name", ["gen_tok_s", "served_tok_s",
                                  "retention_dev_share",
                                  "retention_roofline", "state_rows_mean"])
def test_new_readers_find_nothing_where_there_is_nothing(manifest, name):
    """The parent's record, and a K/V model's: no span attribute, no named
    call, no facts. The reader returns None and does not raise."""
    read = manifest.layer_reader(name)
    assert read({"facts": {}, "end_to_end": {}, "counters": {},
                 "samples": {}, "spans": {}}) is None


def test_tokens_a_second_are_read_from_the_window(manifest):
    record = {"facts": {"seconds": 30.0,
                        "whole_window": {"tokens_out": 15000,
                                         "tokens_in": 30000}},
              "end_to_end": {"serve_tok_s": 1500.0}}
    assert manifest.layer_reader("gen_tok_s")(record) == 500.0
    assert manifest.layer_reader("served_tok_s")(record) == 1500.0


def test_the_roofline_counts_the_rows_that_ran():
    from perf.manifest import load_module
    roof = load_module(os.path.join(ROOT, "perf", "layer_metrics",
                                    "retention_roofline.py"), "roof")
    with open(os.path.join(ROOT, "perf", "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    row = roof.state_bytes_a_row_a_layer(8, 128)
    assert row == 4 * 8 * 65 * 128 * 129            # s and z, float32
    flops, moved = roof.decode_call(16, 40, 8, 128)
    assert 2 * 16 * row < moved < 2.01 * 16 * row   # the state leads
    half, _ = roof.decode_call(8, 40, 8, 128)
    assert half == flops / 2                        # by rows, not by slots
    # bound by bytes: 1.33 ms a layer call of 16 rows at 819 GB/s
    assert 1.3e-3 < roof.least_seconds(flops, moved, peaks) < 1.4e-3
    flops, moved = roof.chunk_call(1, 128, 40, 8, 128)
    assert 2 * row < moved < 2.2 * row
    assert roof.least_seconds(flops, moved, peaks) < 2e-4
