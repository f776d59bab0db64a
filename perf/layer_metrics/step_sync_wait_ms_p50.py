"""step_sync_wait_ms_p50 (ms) - layer: server step. Median over the
window's steps of the program's ``serving/sync`` span: the step's one
``block_until_ready``, the time the host waits for the device. (A step that
drains twice, as a speculative step can, counts both waits.)"""

from perf import program_spans, stats


def read(record):
    events = program_spans.program_events()
    window = program_spans.place_window(record, events)
    if window is None:
        return None
    inside = program_spans.children(events, window["steps"],
                                    ("serving/sync",))
    return stats.median([sum(s["t1"] - s["t0"] for s in c["serving/sync"])
                         * 1e3 for c in inside if c["serving/sync"]])
