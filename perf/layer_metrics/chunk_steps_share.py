"""chunk_steps_share (%) - layer: scheduler. Share of the window's steps
that dispatched a prefill chunk or an admission program beside the decode
(the ``chunk`` / ``admit`` attributes the program sets on ``serving/step``
at its close). The ground ``gap_p90_ms`` stands on: the 90th percentile
lies inside the steps that carry a chunk only while they are 10-50 % of all
steps."""

from perf import program_spans


def read(record):
    window = program_spans.place_window(record,
                                        program_spans.program_events())
    if window is None:
        return None
    steps = window["steps"]
    carrying = sum(1 for s in steps
                   if "chunk" in s["args"] or "admit" in s["args"])
    return 100.0 * carrying / len(steps)
