"""Traffic generators are pure functions of the seed and keep inside their
clips; the due-time and percentile arithmetic on hand-made timelines."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import stats  # noqa: E402
from perf.manifest import Manifest  # noqa: E402

CONTEXT = {"vocab_size": 50304, "context_len": 2048}
SECONDS = 30.0


def _serving_requests(traffic_name, seed, **params):
    m = Manifest(ROOT)
    traffic = m.traffic(traffic_name)
    traffic["params"].update(params)
    load = m.generator(traffic["generator"]).generate(
        traffic["params"], seed, SECONDS, CONTEXT)
    if load.closed:
        # walk a closed loop: every client answered 0.5 s after it sent
        now = -traffic["params"]["lead_in_s"]
        for _ in range(4):
            for spec in load.due(now):
                load.on_finished(spec, now + 0.5)
            now += 0.5
    else:
        load.due(1e9)
    return traffic["params"], load.requests


@pytest.mark.parametrize("traffic_name", ["chat-open", "docs-closed"])
def test_serving_generators_are_seeded_and_clipped(traffic_name):
    params, a = _serving_requests(traffic_name, 11)
    _, b = _serving_requests(traffic_name, 11)
    _, c = _serving_requests(traffic_name, 12)
    assert len(a) >= 15
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    assert all(np.array_equal(x["prompt"], y["prompt"])
               and x["max_new_tokens"] == y["max_new_tokens"]
               for x, y in zip(a, b))
    assert any(len(x["prompt"]) != len(y["prompt"])
               or not np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, c))
    for r in a:
        n = len(r["prompt"])
        assert params["prompt_len"]["min"] <= n <= params["prompt_len"]["max"]
        assert 1 <= r["max_new_tokens"] <= params["output_len"]["max"]
        assert n + r["max_new_tokens"] <= CONTEXT["context_len"]
        assert r["prompt"].min() >= 1
        assert r["prompt"].max() < CONTEXT["vocab_size"]


def test_open_loop_offers_a_fixed_amount_of_work_whatever_is_drawn():
    params, a = _serving_requests("chat-open", 3, schedule_seed=3)
    _, b = _serving_requests("chat-open", 3, schedule_seed=4)
    due = np.array([r["due_s"] for r in a])
    assert due[0] >= -params["lead_in_s"] and due[0] < 0
    assert due[-1] < SECONDS + params["tail_s"]
    assert np.all(np.diff(due) >= 0)

    def window(reqs):
        return [r for r in reqs if 0.0 <= r["due_s"] < SECONDS]

    # the same count and the same multiset of lengths, in another order
    assert len(window(a)) == len(window(b)) == \
        round(params["rate_per_s"] * SECONDS)
    assert sorted(len(r["prompt"]) for r in window(a)) == \
        sorted(len(r["prompt"]) for r in window(b))
    assert sorted(r["max_new_tokens"] for r in window(a)) == \
        sorted(r["max_new_tokens"] for r in window(b))
    assert [len(r["prompt"]) for r in window(a)] != \
        [len(r["prompt"]) for r in window(b)]
    assert [r["due_s"] for r in window(a)] != [r["due_s"] for r in window(b)]
    # the stratified lengths straddle the median of the file
    lengths = sorted(len(r["prompt"]) for r in window(a))
    assert lengths[len(lengths) // 2] == pytest.approx(
        params["prompt_len"]["median"], rel=0.1)


def test_a_schedule_seed_gives_every_run_the_same_schedule():
    m = Manifest(ROOT)
    traffic = m.traffic("chat-open")
    gen = m.generator(traffic["generator"])

    def schedule(params, seed):
        load = gen.generate(params, seed, SECONDS, CONTEXT)
        return [(r["due_s"], len(r["prompt"]), r["max_new_tokens"])
                for r in load.requests], load.requests

    # the cell's own file, as it is
    assert schedule(traffic["params"], 5)[0] == \
        schedule(traffic["params"], 6)[0]
    fixed = dict(traffic["params"], schedule_seed=7)
    a, reqs_a = schedule(fixed, 3)
    b, reqs_b = schedule(fixed, 2147485999)
    # who is due when, with how long a prompt and answer: the same in every
    # run; the run's seed sets the tokens, and the same seed the same tokens
    assert a == b
    assert not any(np.array_equal(x["prompt"], y["prompt"])
                   for x, y in zip(reqs_a, reqs_b))
    assert all(np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(reqs_a, schedule(fixed, 3)[1]))
    # another schedule_seed is another schedule of the same work
    c, _ = schedule(dict(fixed, schedule_seed=8), 3)
    assert c != a and sorted(x[1:] for x in c) != sorted(x[1:] for x in a) \
        and sorted(x[1] for x in c) == sorted(x[1] for x in a)


def test_closed_loop_sends_the_next_request_when_the_last_is_answered():
    m = Manifest(ROOT)
    traffic = m.traffic("docs-closed")
    load = m.generator("closed_loop_clients").generate(
        traffic["params"], 5, SECONDS, CONTEXT)
    first = load.due(0.0)
    assert len(first) == traffic["params"]["clients"]
    assert load.due(0.0) == [] and load.next_due_s() is None
    load.on_finished(first[3], 1.25)
    assert load.next_due_s() == 1.25
    assert load.due(1.0) == []
    (nxt,) = load.due(1.25)
    assert nxt["client"] == first[3]["client"] and nxt["key"][1] == 1


@pytest.mark.parametrize("traffic_name", ["seq1k-16", "seq1k-32"])
def test_token_stream_is_seeded(traffic_name):
    m = Manifest(ROOT)
    traffic = m.traffic(traffic_name)
    gen = m.generator(traffic["generator"])
    a = gen.generate(traffic["params"], 1, SECONDS, {"vocab_size": 50257})
    b = gen.generate(traffic["params"], 1, SECONDS, {"vocab_size": 50257})
    c = gen.generate(traffic["params"], 2, SECONDS, {"vocab_size": 50257})
    ids = a.batch(0)["input_ids"]
    assert ids.shape == (traffic["params"]["sequences_per_step"], 1024)
    assert ids.dtype == np.int32 and 0 <= ids.min() and ids.max() < 50257
    assert np.array_equal(ids, b.batch(0)["input_ids"])
    assert not np.array_equal(ids, c.batch(0)["input_ids"])
    assert not np.array_equal(ids, a.batch(1)["input_ids"])
    assert not np.array_equal(ids, a.batch(-1)["input_ids"])
    assert a.tokens_per_step == ids.size


def test_a_stalled_step_raises_ttft_of_the_requests_due_during_it():
    # steps end at 0.1, 0.2, then one stalls until 1.2; requests due at
    # 0.05, 0.15 and, during the stall, 0.30 and 0.90. The last two could
    # only be SUBMITTED at 1.2 and see a token at 1.3: from their submit
    # that is 100 ms; from when they were due it is 1000 and 400 ms.
    due = [0.05, 0.15, 0.30, 0.90]
    first = [0.10, 0.20, 1.30, 1.30]
    assert stats.ttft_ms(due, first) == pytest.approx([50, 50, 1000, 400])
    assert stats.percentile(stats.ttft_ms(due, first), 50) == \
        pytest.approx(225.0)


def test_a_request_with_no_first_token_counts_as_the_largest_value():
    got = stats.ttft_ms([0.0, 1.0, 2.0], [0.2, None, 2.5])
    assert got == pytest.approx([200.0, 500.0, 500.0])
    assert stats.ttft_ms([0.0], [None]) == [float("inf")]


def test_token_gaps_only_inside_the_window_and_never_the_first_token():
    times = [[0.9, 1.0, 1.1, 1.5], [2.0], [29.9, 30.2]]
    assert stats.token_gaps_ms(times, 1.0, 30.0) == \
        pytest.approx([100.0, 100.0, 400.0])


def test_percentile_and_spread():
    assert stats.percentile([], 50) is None
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile(list(range(101)), 99) == 99.0
    # the driver's quartiles, statistics.quantiles(values, n=4): 1.5 and
    # 4.5 around a median of 3 (numpy's 2 and 4 lie closer together)
    assert stats.spread([1, 2, 3, 4, 5]) == pytest.approx(1.0)
    summary = stats.summarize_runs([{"a": 1.0}, {"a": 3.0}, {"a": 2.0}])
    assert summary["a"]["median"] == 2.0 and summary["a"]["n"] == 3


def test_spread_without_farthest_leaves_out_one_run_and_only_one():
    steady = [10.0, 10.1, 10.2, 10.3, 10.4]
    one_off = steady + [14.0]
    two_off = steady + [14.0, 14.5]
    assert stats.spread_without_farthest([1.0, 2.0]) is None
    # one far-off run in a set does no harm: the set reads as without it
    assert stats.spread_without_farthest(one_off) == \
        pytest.approx(stats.spread(steady))
    assert stats.spread(one_off) > 3 * stats.spread(steady)
    # two do
    assert stats.spread_without_farthest(two_off) > 3 * stats.spread(steady)
    # the run is left out only where that narrows the spread: here the
    # median of the five that stay falls from 11 to 10 and they read wider
    halves = [10.0, 10.0, 10.0, 12.0, 12.0, 12.0]
    assert stats.spread(halves[:-1]) > stats.spread(halves)
    assert stats.spread_without_farthest(halves) == \
        pytest.approx(stats.spread(halves)) == pytest.approx(2.0 / 11.0)


def test_spread_tool_reads_two_sets_as_the_driver_does(tmp_path, monkeypatch,
                                                       capsys):
    from perf.manifest import load_module

    tool = load_module(os.path.join(ROOT, "perf", "tools", "spread.py"),
                       "perf_tool_spread")
    gaps = {100: 10.0, 101: 10.1, 102: 10.2, 103: 10.3, 104: 10.4, 105: 14.0,
            106: 10.0, 107: 10.2, 108: 10.4, 109: 10.6, 110: 10.8, 111: 7.0}
    seen = []

    def run_once(workload, seed, seconds, trace, timeout):
        seen.append(seed)
        return {"correct": True, "failed": 0, "attempted": 3, "metrics": {
            "gap_p90_ms": {"value": gaps[seed], "unit": "ms"},
            "setup_s": {"value": 40.0 + seed % 2, "unit": "s"}}}, None

    monkeypatch.setattr(tool, "run_once", run_once)
    monkeypatch.setattr(tool, "ROOT", str(tmp_path))
    monkeypatch.setattr(sys, "argv", ["spread.py", "--workload", "a-cell",
                                      "--runs", "6", "--sets", "2"])
    assert tool.main() == 0
    assert seen == list(range(100, 112))        # other seeds in the second
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    view = last["as_the_driver_reads_it"]["gap_p90_ms"]
    first, second = ([gaps[s] for s in range(a, a + 6)] for a in (100, 106))
    assert view["medians"] == [pytest.approx(10.25), pytest.approx(10.3)]
    assert view["mean_spread_without_farthest"] == pytest.approx(
        (stats.spread(first[:5]) + stats.spread(second[:5])) / 2)
    assert view["widest_spread"] == pytest.approx(
        max(stats.spread(first), stats.spread(second)))
    assert len((tmp_path / "chiprun_out" / "spread-a-cell.jsonl")
               .read_text().splitlines()) == 12
    # the driver's own two sets have the same seeds
    seen.clear()
    monkeypatch.setattr(sys, "argv", sys.argv + ["--same-seeds", "1"])
    assert tool.main() == 0
    assert seen == list(range(100, 106)) * 2
