"""ssm_dev_share (%) - layer: state-space layers. Device time inside the
Pallas calls named ``ssm_*`` (``ssm_decode``: a token's state update and
read for the running rows of a Mamba layer; ``ssm_chunk``: the chunked form
of a prefill) over device busy time, lowest-numbered chip. Outside it, in
XLA: the projections, the convolution and its tail (scope ``ssm_conv``),
the decay and ``C B^T`` a chunk's blocks are multiplied with (scope
``ssm_chunk_prep``), the gate and its norm. A trace with no such call (a
model without state-space layers, a parent commit) returns nothing."""


def ssm_calls(trace):
    return {name: c for name, c in trace["device0"]["custom_calls"].items()
            if name.startswith("ssm_")}


def read(record):
    trace = record.get("trace")
    if not trace or trace["device0"]["busy_s"] <= 0:
        return None
    calls = ssm_calls(trace)
    if not calls:
        return None
    return 100.0 * sum(c["total_s"] for c in calls.values()) \
        / trace["device0"]["busy_s"]
