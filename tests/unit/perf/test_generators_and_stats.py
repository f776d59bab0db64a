"""Traffic generators are pure functions of the seed and keep inside their
clips; the due-time and percentile arithmetic on hand-made timelines."""

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


def _serving_requests(traffic_name, seed):
    m = Manifest(ROOT)
    traffic = m.traffic(traffic_name)
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


def test_open_loop_offers_a_fixed_amount_of_work_whatever_the_seed():
    params, a = _serving_requests("chat-open", 3)
    _, b = _serving_requests("chat-open", 4)
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
    # quartiles 2 and 4 around a median of 3
    assert stats.spread([1, 2, 3, 4, 5]) == pytest.approx(2 / 3)
    summary = stats.summarize_runs([{"a": 1.0}, {"a": 3.0}, {"a": 2.0}])
    assert summary["a"]["median"] == 2.0 and summary["a"]["n"] == 3
