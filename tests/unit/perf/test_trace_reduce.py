"""The trace reducer on a small recorded trace (names and lines as a TPU v5e
gave them in PR 22's first traces; times rounded so the arithmetic can be
checked by hand: see data/small_trace.json)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import trace_reduce as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small_trace.json")


@pytest.fixture(scope="module")
def reduced():
    with open(DATA) as f:
        return tr.reduce_trace(json.load(f))


def test_window_busy_and_idle(reduced):
    assert reduced["devices"] == 2
    assert reduced["window_s"] == pytest.approx(0.030)
    # chip 0: busy 0-10, 20-26, 27-30 = 19 ms; chip 1: 0-15, 20-30 = 25 ms
    assert reduced["busy_s_per_device"] == pytest.approx([0.019, 0.025])
    assert reduced["busy_s"] == pytest.approx(0.022)
    assert reduced["device0"]["busy_s"] == pytest.approx(0.019)


def test_collective_time_and_its_exposed_part(reduced):
    d0 = reduced["device0"]
    # all-gather in flight 4-8 ms, reduce-scatter 24-25 ms: 5 ms in all
    assert d0["collective_s"] == pytest.approx(0.005)
    # a fusion runs beside the gather from 4.1 to 6 ms; the rest is exposed:
    # 4-4.1, 6-8 and the whole reduce-scatter = 3.1 ms
    assert d0["collective_exposed_s"] == pytest.approx(0.0031)
    assert d0["collective_by_kind"]["all-gather"] == pytest.approx(0.004)
    assert d0["collective_by_kind"]["reduce-scatter"] == pytest.approx(0.001)


def test_custom_call_found_and_self_time_of_a_container(reduced):
    d0 = reduced["device0"]
    # (custom-call.18, zero duration, and custom-call.19, XLA's default
    # name and no Mosaic target, are XLA's own: no kernels)
    assert list(d0["custom_calls"]) == ["_lambda_.1"]
    call = d0["custom_calls"]["_lambda_.1"]
    assert call["count"] == 2
    assert call["total_s"] == pytest.approx(0.003)
    assert "f32[8,8,1024]" in call["shape"]
    assert d0["custom_call_s"] == pytest.approx(0.003)
    # the while spans 10 ms, all of it covered by its body's instructions
    whiles = [v for k, v in d0["ops"].items() if k.startswith("while.3")]
    assert whiles == [pytest.approx(0.0)]
    top = reduced["breakdown"]["device_ops"][0]
    assert top[0].startswith("convolution_tanh_fusion bf16[2048,2048]")
    assert top[1] == pytest.approx(0.008)


def test_idle_gaps_are_charged_to_what_the_host_was_doing(reduced):
    gaps = reduced["device0"]["idle_by_host_span"]
    # 10-20 ms idle: step until 10.5, idle_wait until 16.5, submit until 18,
    # then the next step's launch
    assert gaps["bench/step"] == pytest.approx(0.0025)
    assert gaps["bench/idle_wait"] == pytest.approx(0.006)
    assert gaps["bench/submit"] == pytest.approx(0.0015)
    # 26-27 ms: below what the two clocks agree to
    assert gaps["(gaps under 2 ms)"] == pytest.approx(0.001)
    assert sum(gaps.values()) == pytest.approx(0.030 - 0.019)
    assert reduced["breakdown"]["idle_gaps"][0][0] == "bench/idle_wait"


def test_modules_by_name(reduced):
    mods = reduced["device0"]["modules"]
    assert mods["jit_step_a"]["count"] == 2
    assert mods["jit_step_a"]["durations_ms"] == pytest.approx([10.0, 3.0])
    assert mods["jit_step_b"]["total_s"] == pytest.approx(0.006)


@pytest.mark.parametrize("text,want", [
    ("%fusion.3 = bf16[8,128]{1,0:T(8,128)} fusion(bf16[8] %p), kind=kLoop",
     ("fusion.3", "fusion", "bf16[8,128]{1,0:T(8,128)}")),
    ("%x.1 = (bf16[2]{0}, f32[2,(3)]{0}) custom-call(bf16[2] %a)",
     ("x.1", "custom-call", "(bf16[2]{0}, f32[2,(3)]{0})")),
    ("jit_mm(123)", ("jit_mm(123)", "", "")),
])
def test_parse_op(text, want):
    assert tr.parse_op(text) == want


def test_no_device_plane_reduces_to_nothing():
    assert tr.reduce_trace({"planes": [
        {"name": "/host:CPU", "lines": []}]}) is None


def test_interval_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                        (7, 10)]
    assert tr.self_times([["a", 0, 10], ["b", 1, 2], ["c", 5, 5]]) == \
        [3.0, 2.0, 5.0]
