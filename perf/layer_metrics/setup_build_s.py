"""setup_build_s (s) - layer: programs. The program's ``setup/build`` spans
(``ds.initialize`` / ``ds.init_serving``, children included) less the
compiles that fell inside them, which ``setup_compile_s`` counts. Only of a
run whose window the program's spans place."""

from perf import program_spans


def read(record):
    events = program_spans.program_events()
    builds = program_spans.spans(events, "setup/build")
    if not builds or program_spans.place_window(record, events) is None:
        return None
    compiles = program_spans.spans(events, "setup/compile")
    total = 0.0
    for b in builds:
        inside = sum(min(c["t1"], b["t1"]) - max(c["t0"], b["t0"])
                     for c in compiles
                     if c["t1"] > b["t0"] and c["t0"] < b["t1"])
        total += (b["t1"] - b["t0"]) - inside
    return total
