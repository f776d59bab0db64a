"""state_rows_mean (count) - layer: retention state. Rows whose state a
decode dispatch reads and writes (``state_rows`` on the program's
``serving/decode`` span, from the host's running set), mean over the
window's decode dispatches. Beside ``live_slots_mean``: a gap between the
two is state traffic for rows nobody samples. A program that sets no such
attribute (a K/V model, a parent commit) returns nothing."""

from perf import program_spans


def read(record):
    events = program_spans.program_events()
    window = program_spans.place_window(record, events)
    if window is None:
        return None
    inside = program_spans.children(events, window["steps"],
                                    ("serving/decode",))
    rows = [s["args"]["state_rows"] for c in inside
            for s in c["serving/decode"] if "state_rows" in s["args"]]
    return sum(rows) / len(rows) if rows else None
