"""prefill_wait_p50_ms (ms) - layer: scheduler. Median, over the requests
whose first token fell in the window, of the time from ``admitted`` (seated
in a slot) to ``first_token``, both request events of the program's ring:
the wait in the prefill queue after seating, which ``queue_wait_p50_ms``
(submit to seat) cannot see. A request preempted and seated again counts
from its first seating."""

from perf import program_spans, stats


def read(record):
    events = program_spans.program_events()
    window = program_spans.place_window(record, events)
    if window is None:
        return None
    admitted, waits = {}, []
    for e in events:
        if e.get("cat") != "request" or e.get("ph") != "n":
            continue
        if e["name"] == "admitted":
            admitted.setdefault(e["id"], e["ts"])
        elif e["name"] == "first_token" and e["id"] in admitted \
                and window["open_s"] <= e["ts"] / 1e9 < window["close_s"]:
            waits.append((e["ts"] - admitted.pop(e["id"])) / 1e6)
    return stats.median(waits)
