"""decode_dev_ms_p50 (ms) - layer: programs. Median device duration of the
decode program (the configuration's ``trace.decode_modules``) in the trace.
serve_step_ms_p50 minus this is the host's share of a step."""

from perf import stats


def read(record):
    trace = record.get("trace")
    if not trace:
        return None
    wanted = record["config"]["trace"]["decode_modules"]
    durations = [d for name, m in trace["device0"]["modules"].items()
                 if any(w in name for w in wanted)
                 for d in m["durations_ms"]]
    return stats.median(durations)
