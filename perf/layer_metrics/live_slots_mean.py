"""live_slots_mean (count) - layer: server step. Live slots per decode step,
mean over the window: ``ServingMetrics.slot_steps`` over ``decode_steps``,
both read at the two ends of the window."""


def read(record):
    steps = record["counters"].get("decode_steps")
    if not steps:
        return None
    return record["counters"]["slot_steps"] / steps
