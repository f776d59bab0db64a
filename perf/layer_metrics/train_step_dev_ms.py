"""train_step_dev_ms (ms) - layer: trainer. Median device duration of the
train-step program (the configuration's ``trace.train_step_module``) in the
traced steps, lowest-numbered chip. tokens/s/chip = tokens per step / (this
+ the idle time between steps)."""

from perf import stats


def read(record):
    trace = record.get("trace")
    if not trace:
        return None
    want = record["config"]["trace"]["train_step_module"]
    durations = [d for name, m in trace["device0"]["modules"].items()
                 if want in name for d in m["durations_ms"]]
    return stats.median(durations)
