"""step_enqueue_ms_p50 (ms) - layer: server step. Median over the window's
steps of the summed ``serving/enqueue`` spans of a step: the host cost of
the jitted programs' calls (the puts and eager operations that feed them
leave no span: ``step_prepare_ms_p50`` and ``step_exposed_host_ms_p50``
hold them), which one fused program a step divides and an overlapped step
hides."""

from perf import stats, step_account


def read(record):
    rows = step_account.window_rows(record)
    if rows is None:
        return None
    return stats.median([r["enqueue_ms"] for r in rows])
