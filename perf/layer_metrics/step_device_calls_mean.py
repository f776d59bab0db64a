"""step_device_calls_mean (count) - layer: server step. Mean over the
window's steps of ``device_calls`` on ``serving/step``: the calls that
handed the device work in the step, jitted programs, puts and eager
operations alike (a put counts once however many arrays it carries). A
count, so a CPU test pins it (``tests/unit/telemetry/
test_engine_spans.py``); a fused step lowers it."""

from perf import step_account


def read(record):
    rows = step_account.window_rows(record)
    if rows is None:
        return None
    calls = [r["device_calls"] for r in rows
             if r["device_calls"] is not None]
    return sum(calls) / len(calls) if calls else None
