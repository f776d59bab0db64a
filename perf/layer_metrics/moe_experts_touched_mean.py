"""moe_experts_touched_mean (count) - layer: routed FFN. Experts that at
least one row chose, a call of the routed FFN (one layer of one program),
mean over the window: the ``moe_experts_touched`` / ``moe_layer_calls``
attributes the program sets on ``serving/step`` from what its kernels'
wrapper counted on the device (returned with the step's tokens). The expert
weights a call reads are this many matrices of three. A program without a
routed FFN sets no such attribute: the reader returns nothing."""

from perf import program_spans


def read(record):
    window = program_spans.place_window(record,
                                        program_spans.program_events())
    if window is None:
        return None
    steps = [s["args"] for s in window["steps"]
             if s["args"].get("moe_layer_calls")]
    if not steps:
        return None
    return sum(a["moe_experts_touched"] for a in steps) \
        / sum(a["moe_layer_calls"] for a in steps)
