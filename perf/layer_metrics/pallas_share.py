"""pallas_share (%) - layer: kernels. Device time inside Pallas custom calls
over device busy time, lowest-numbered chip: the most a faster kernel can
save of the step."""


def read(record):
    trace = record.get("trace")
    if not trace or trace["device0"]["busy_s"] <= 0:
        return None
    return 100.0 * trace["device0"]["custom_call_s"] \
        / trace["device0"]["busy_s"]
