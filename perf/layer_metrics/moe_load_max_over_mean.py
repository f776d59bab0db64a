"""moe_load_max_over_mean (ratio) - layer: routed FFN. Rows of the fullest
expert over the mean rows an expert, for the most uneven program of a step
(``moe_load_max_over_mean`` on ``serving/step``), median over the window's
steps. 1 is a router that spreads rows evenly; the fullest expert's tiles
are what a call of the expert kernels waits for once the weights are read.
A program without a routed FFN sets no such attribute: nothing is
returned."""

from perf import program_spans, stats


def read(record):
    window = program_spans.place_window(record,
                                        program_spans.program_events())
    if window is None:
        return None
    ratios = [s["args"]["moe_load_max_over_mean"] for s in window["steps"]
              if "moe_load_max_over_mean" in s["args"]]
    return stats.median(ratios) if ratios else None
