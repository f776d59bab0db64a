"""step_host_serial_ms_p50 (ms) - layer: server step. Median over the
window's steps of the time nothing is queued on the device: end of the
``serving/sync`` of step n to the start of the first dispatch span
(``decode``, ``prefill_chunk``, ``prefill_batch``, ``admit``) of step n + 1.
It holds the replay of the deferred callbacks, the after-step work, the
caller's own work between two steps, and the next step's boundary work and
grant: the serial host time a faster device step leaves standing."""

from perf import program_spans, stats


def read(record):
    events = program_spans.program_events()
    window = program_spans.place_window(record, events)
    if window is None:
        return None
    return stats.median(program_spans.host_serial_ms(
        events, window["steps"], "serving/sync"))
