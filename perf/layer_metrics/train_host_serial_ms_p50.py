"""train_host_serial_ms_p50 (ms) - layer: trainer. Median over the window's
steps of the time nothing is queued on the device: end of ``train/sync`` of
step n to the start of ``train/dispatch`` of step n + 1 (the after-step
work, the caller's batch, the stacking and placing of the micro-batches)."""

from perf import program_spans, stats


def read(record):
    events = program_spans.program_events()
    window = program_spans.place_window(record, events)
    if window is None:
        return None
    return stats.median(program_spans.host_serial_ms(
        events, window["steps"], "train/sync"))
