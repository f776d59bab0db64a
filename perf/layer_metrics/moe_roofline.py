"""moe_roofline (%) - layer: routed FFN. The least time the chip could take
for the expert products of one call of the routed FFN (one layer of one
program) over the time the trace measured for them.

* bytes: the three matrices (gate, up: hidden x width; down: width x hidden)
  of every expert a row chose, once, in the weights' type, plus the rows in
  and out (each assignment reads a hidden-wide row, writes and reads a
  width-wide one and writes a hidden-wide float32 one);
* operations: 2 x 3 x hidden x width an assignment (a row of an expert).

Experts touched and assignments a call are the window's means, from the
program's own counters on ``serving/step``; the measured time a call is the
trace's ``moe_*`` time over its number of ``moe_down`` calls. Bound by
bytes at serving batch sizes: a step's rows are few and every expert's
weights are read for them."""

from perf import program_spans


def expert_call(experts_touched: float, assignments: float, hidden: int,
                width: int, itemsize: int = 2):
    """``(operations, bytes)`` of one layer's expert products."""
    flops = 2.0 * 3 * hidden * width * assignments
    weights = 3.0 * hidden * width * itemsize * experts_touched
    rows = assignments * (hidden * itemsize + 2 * width * itemsize
                          + hidden * 4)
    return flops, weights + rows


def least_seconds(flops: float, bytes_moved: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops_per_s"],
               bytes_moved / peaks["hbm_bytes_per_s"])


def read(record):
    trace, peaks = record.get("trace"), record.get("peaks")
    if not trace or not peaks:
        return None
    calls = {name: c for name, c in trace["device0"]["custom_calls"].items()
             if name.startswith("moe_")}
    layer_calls = sum(c["count"] for name, c in calls.items()
                      if name.startswith("moe_down"))
    window = program_spans.place_window(record,
                                        program_spans.program_events())
    if not layer_calls or window is None:
        return None
    steps = [s["args"] for s in window["steps"]
             if s["args"].get("moe_layer_calls")]
    if not steps:
        return None
    n = sum(a["moe_layer_calls"] for a in steps)
    flops, bytes_moved = expert_call(
        sum(a["moe_experts_touched"] for a in steps) / n,
        sum(a["moe_assignments"] for a in steps) / n,
        int(record["config"]["hidden_size"]),
        int(record["config"]["moe_intermediate_size"]))
    measured = sum(c["total_s"] for c in calls.values()) / layer_calls
    return 100.0 * least_seconds(flops, bytes_moved, peaks) / measured
