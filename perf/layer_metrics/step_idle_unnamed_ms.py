"""step_idle_unnamed_ms (ms) - layer: server step. Traced runs only: the
part of the chip's idle time a step that no program span explains. The
device's idle time in the traced stretch, ``window_s - busy_s`` of the
reduced trace (first program start to last program end on the device's
clock), over the N - 1 gaps between the N steps the ring flags
``profiled``, less the mean ``exposed`` of the stretch's interior steps
(its first and last dropped: the profiler's start and stop stall the loop;
an interior step with no ``exposed`` counts 0). What is left is launch
latency, a bubble between a step's two programs, and the idle time of a
host-bound step whose sync did not wait. Not clipped (an edge step can make
it read a little under 0) and not a share of anything."""

from perf import step_account


def read(record):
    trace = record.get("trace")
    if not trace:
        return None
    rows = step_account.window_rows(record)
    if rows is None:
        return None
    interior = step_account.traced_interior(rows)
    if interior is None:
        return None
    gaps = len(interior) + 1
    idle_ms = (trace["window_s"] - trace["busy_s"]) * 1e3 / gaps
    exposed = sum(r["exposed_ms"] or 0.0 for r in interior) / len(interior)
    return idle_ms - exposed
