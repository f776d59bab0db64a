"""compiles_in_window (count) - layer: programs. JAX compile-request events
between the opening and the end of the window (the smoke's listener,
perf/device.py). Must read 0: a program that first appears inside the window
is compile time measured as serving or training time."""


def read(record):
    return record["counters"].get("compiles_in_window")
