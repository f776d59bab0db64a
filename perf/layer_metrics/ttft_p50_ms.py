"""ttft_p50_ms (ms) - layer: scheduler. Median, over the requests due in the
window, of (first token visible to the caller - time the request was DUE by
the schedule); a refused or failed request counts as the largest value.

What a chat user feels first, and meant to be an end-to-end metric. It is
kept here, without a bound, because today's server takes ~220 ms a step and
sustains ~1 request/s: a window holds 24 requests (36 in 45 s) whose waits
run from 0.3 to 3.5 s by where they fall in the prefill queue, and their
median spread by 18-37 % over seeds in every window length tried (PERF.md
section 6, PR 22), where the contract admits a spread under 5 %. It goes
back among the end-to-end metrics when a window holds some hundreds of
requests. (In a traced run the profiler's start stalls the loop for about
two seconds, which this median partly sees.)"""


def read(record):
    return record.get("end_to_end", {}).get("ttft_p50_ms")
