"""queue_wait_p50_ms (ms) - layer: scheduler. Median queue wait (submit to
slot granted, both on the server's own clock: ``Request.queue_wait``) of the
requests that finished inside the window."""

from perf import stats


def read(record):
    return stats.median(record["samples"].get("queue_wait_ms", []))
