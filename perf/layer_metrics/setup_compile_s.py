"""setup_compile_s (s) - layer: programs. Sum of the program's
``setup/compile`` spans that ended before the window opened: tracing,
lowering and the backend compile (or the retrieval from the persistent
cache) of every program, the eager one-operation programs of the build
included."""

from perf import program_spans


def read(record):
    events = program_spans.program_events()
    window = program_spans.place_window(record, events)
    if window is None:
        return None
    return sum(s["t1"] - s["t0"]
               for s in program_spans.spans(events, "setup/compile")
               if s["t1"] <= window["open_s"])
