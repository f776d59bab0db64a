"""retention_dev_share (%) - layer: retention state. Device time inside the
Pallas calls named ``retention_*`` (``retention_decode``: a token's state
update and read for the running rows; ``retention_chunk``: the chunk form of
a prefill) over device busy time, lowest-numbered chip. A trace with no such
call (a K/V model, a parent commit) returns nothing."""


def retention_calls(trace):
    return {name: c for name, c in trace["device0"]["custom_calls"].items()
            if name.startswith("retention_")}


def read(record):
    trace = record.get("trace")
    if not trace or trace["device0"]["busy_s"] <= 0:
        return None
    calls = retention_calls(trace)
    if not calls:
        return None
    return 100.0 * sum(c["total_s"] for c in calls.values()) \
        / trace["device0"]["busy_s"]
