"""collective_share (%) - layer: ZeRO placement. Lowest-numbered chip: time
inside all-gather / reduce-scatter / all-reduce / collective-permute /
all-to-all (synchronous instructions and start-to-done spans of asynchronous
ones, as a union) over the traced window. Absent on one chip."""


def read(record):
    trace = record.get("trace")
    if not trace or record["counters"].get("num_devices", 1) < 2:
        return None
    return 100.0 * trace["device0"]["collective_s"] / trace["window_s"]
