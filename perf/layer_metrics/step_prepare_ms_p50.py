"""step_prepare_ms_p50 (ms) - layer: server step. Median over the window's
steps of the dispatch spans' and ``serving/sample``'s time OUTSIDE their
``serving/enqueue`` children: building a dispatch's arguments on the host
and putting them on the device before the program's call (ids, table
rows, the key split), and counting after it (``pool_writes``,
``pool_reads``, ``state_rows``)."""

from perf import stats, step_account


def read(record):
    rows = step_account.window_rows(record)
    if rows is None:
        return None
    return stats.median([r["prepare_ms"] for r in rows])
