"""mla_dev_share (%) - layer: latent attention. Device time inside the
Pallas calls named ``mla_*`` (the absorbed read of the latent cache:
``mla_decode``, ``mla_chunk``) over device busy time, lowest-numbered chip.
A trace with no such call (a K/V model, a parent commit) returns nothing."""


def mla_calls(trace):
    return {name: c for name, c in trace["device0"]["custom_calls"].items()
            if name.startswith("mla_")}


def read(record):
    trace = record.get("trace")
    if not trace or trace["device0"]["busy_s"] <= 0:
        return None
    calls = mla_calls(trace)
    if not calls:
        return None
    return 100.0 * sum(c["total_s"] for c in calls.values()) \
        / trace["device0"]["busy_s"]
