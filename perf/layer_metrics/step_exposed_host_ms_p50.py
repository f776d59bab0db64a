"""step_exposed_host_ms_p50 (ms) - layer: server step. Median over the
window's steps of the host time during which the device had nothing
queued: end of the last ``serving/sync`` of step n to the END of the first
``serving/enqueue`` of kind ``program`` of step n + 1 (recomputed from the
ring; the program sets the same figure as ``exposed_ns`` on
``serving/step``). What ``step_host_serial_ms_p50`` measures, taken where
the program is actually queued: it also holds the pages boundary, the
preparation of the first dispatch, its transfers and the jitted call. A
host-bound step, whose sync did not wait, is under-counted: the device
idled before that sync too (``perf/STEP_ACCOUNT.md``)."""

from perf import stats, step_account


def read(record):
    rows = step_account.window_rows(record)
    if rows is None:
        return None
    return stats.median([r["exposed_ms"] for r in rows
                         if r["exposed_ms"] is not None])
