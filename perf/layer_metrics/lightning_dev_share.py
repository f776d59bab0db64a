"""lightning_dev_share (%) - layer: Lightning state layers. Device time
inside the Pallas calls named ``lightning_*`` (``lightning_decode``: a
token's decay, outer product and read for the running rows of a Lightning
layer; ``lightning_chunk``: the chunk form of a prefill, 128 tokens a call)
over device busy time, lowest-numbered chip. Outside it, in XLA: the
projections, the norms on q, k and the output, the rotary, the gate, and
the decay's (T, T) a chunk's products are made with (scope
``lightning_chunk_prep``). A trace with no such call (a model without
Lightning layers, a parent commit) returns nothing."""


def lightning_calls(trace):
    return {name: c for name, c in trace["device0"]["custom_calls"].items()
            if name.startswith("lightning_")}


def read(record):
    trace = record.get("trace")
    if not trace or trace["device0"]["busy_s"] <= 0:
        return None
    calls = lightning_calls(trace)
    if not calls:
        return None
    return 100.0 * sum(c["total_s"] for c in calls.values()) \
        / trace["device0"]["busy_s"]
