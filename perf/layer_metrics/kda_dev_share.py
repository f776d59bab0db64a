"""kda_dev_share (%) - layer: KDA state layers. Device time inside the
Pallas calls named ``kda_*`` (``kda_decode``: a token's delta-rule update
and read for the running rows of a KDA layer; ``kda_chunk``: the chunk form
of a prefill) over device busy time, lowest-numbered chip. Outside it, in
XLA: the projections, the convolution and its tail (scope ``ssm_conv``),
the decays, the pairs and the inverse a chunk's products are made with
(scope ``kda_chunk_prep``), the output norm and gate. A trace with no such
call (a model without KDA layers, a parent commit) returns nothing."""


def kda_calls(trace):
    return {name: c for name, c in trace["device0"]["custom_calls"].items()
            if name.startswith("kda_")}


def read(record):
    trace = record.get("trace")
    if not trace or trace["device0"]["busy_s"] <= 0:
        return None
    calls = kda_calls(trace)
    if not calls:
        return None
    return 100.0 * sum(c["total_s"] for c in calls.values()) \
        / trace["device0"]["busy_s"]
