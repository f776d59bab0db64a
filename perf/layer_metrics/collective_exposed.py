"""collective_exposed (%) - layer: ZeRO placement. The part of
collective_share during which no other instruction runs on that chip, over
the traced window: the only part a better overlap can win back."""


def read(record):
    trace = record.get("trace")
    if not trace or record["counters"].get("num_devices", 1) < 2:
        return None
    return 100.0 * trace["device0"]["collective_exposed_s"] \
        / trace["window_s"]
