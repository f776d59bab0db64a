"""setup_import_s (s) - layer: programs. The program's ``setup/import``
span: ``import deepspeed_tpu`` from its first line to its last (JAX itself
is already imported when the harness gets there). Only of a run whose
window the program's spans place."""

from perf import program_spans


def read(record):
    events = program_spans.program_events()
    if program_spans.place_window(record, events) is None:
        return None
    found = program_spans.spans(events, "setup/import")
    return sum(s["t1"] - s["t0"] for s in found) if found else None
