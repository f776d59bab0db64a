"""gen_late_p99_ms (ms) - layer: the benchmark's load generator. 99th
percentile of (time a request was submitted - time it was due). The
generator submits between server steps from the one thread that drives the
server, so this is at most about one step; were it large against
ttft_p50_ms, a starved generator would read as a fast server."""

from perf import stats


def read(record):
    return stats.percentile(record["samples"].get("submit_late_ms", []), 99)
