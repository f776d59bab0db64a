"""serve_step_ms_p50 (ms) - layer: server step. Median duration of the
harness's own span around each ``srv.step()`` of the window (host clock; a
step ends after its one device sync, so the span holds the device's work)."""

from perf import stats


def read(record):
    spans = record["spans"].get("bench/step", [])
    return stats.median([(b - a) * 1e3 for a, b in spans])
