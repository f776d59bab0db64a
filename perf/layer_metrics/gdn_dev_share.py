"""gdn_dev_share (%) - layer: Gated DeltaNet state layers. Device time
inside the Pallas calls named ``gdn_*`` (``gdn_decode``: a token's
delta-rule update and read for the running rows of a DeltaNet layer;
``gdn_chunk``: a block of 128 tokens of a prefill chunk in the chunk form;
both are ``ops/kda.py``'s kernels under a Gated DeltaNet layer's names) over
device busy time, lowest-numbered chip. Outside it, in XLA: the projections,
the convolution and its tail (scope ``ssm_conv``), the decays, the one
product a key head, its decay mask and the inverse a block's products are
made with (scope ``gdn_chunk_prep``), the output norm and gate. A trace
with no such call (a model without DeltaNet layers, a parent commit)
returns nothing."""


def gdn_calls(trace):
    return {name: c for name, c in trace["device0"]["custom_calls"].items()
            if name.startswith("gdn_")}


def read(record):
    trace = record.get("trace")
    if not trace or trace["device0"]["busy_s"] <= 0:
        return None
    calls = gdn_calls(trace)
    if not calls:
        return None
    return 100.0 * sum(c["total_s"] for c in calls.values()) \
        / trace["device0"]["busy_s"]
