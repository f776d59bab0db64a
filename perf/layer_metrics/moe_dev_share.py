"""moe_dev_share (%) - layer: routed FFN. Device time inside the Pallas
calls named ``moe_*`` (the expert products: ``moe_gate_up``, ``moe_down``)
over device busy time, lowest-numbered chip. A trace with no such call (a
dense model, a parent commit) returns nothing."""


def moe_calls(trace):
    return {name: c for name, c in trace["device0"]["custom_calls"].items()
            if name.startswith("moe_")}


def read(record):
    trace = record.get("trace")
    if not trace or trace["device0"]["busy_s"] <= 0:
        return None
    calls = moe_calls(trace)
    if not calls:
        return None
    return 100.0 * sum(c["total_s"] for c in calls.values()) \
        / trace["device0"]["busy_s"]
