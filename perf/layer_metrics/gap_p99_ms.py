"""gap_p99_ms (ms) - layer: server step. 99th percentile, over every
generated token after a request's first, of the time between two successive
tokens of one request becoming visible to the caller: the stutter.

ISSUE 22 meant it end to end and foresaw this place for it. Every live slot
sees the same gap in a step, so the percentile is over some 135 distinct
step lengths (200 in 45 s) and is set by the two or three slowest kinds of
step. Over 14 runs of 45 s at 0.5-1.1 request/s it read one of three levels:
243-247 ms in 3, 318 in 1, 268-277 in the rest (PERF.md section 6, PR 22),
by how many short prompts a seed's schedule has the server admit in one step
(my reading of the levels; the step kinds were not told apart in a trace).
Two low readings in a set of six spread it by 8 %, which no bound the
contract admits holds. The 90th percentile, gap_p90_ms, lies well inside the
steps that carry a prefill chunk (half of all steps at this rate) and is the
end-to-end metric; this one shows what lies beyond it."""


def read(record):
    return record.get("end_to_end", {}).get("gap_p99_ms")
