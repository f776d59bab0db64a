"""window_pages_peak_share (%) - layer: KV pools. Highest number of pages the
window page group had mapped at the close of a step of the window over the
group's pages (the ``window_pages`` / ``window_pages_total`` attributes the
program sets on ``serving/step`` when its model has sliding-window layers).
A slot maps at most sliding_window / page_size + 1 of them however long its
request, so this follows the live slots, not the tokens. A program without
a window group sets no such attribute: the reader returns nothing."""

from perf import program_spans


def read(record):
    window = program_spans.place_window(record,
                                        program_spans.program_events())
    if window is None:
        return None
    shares = [s["args"]["window_pages"] / s["args"]["window_pages_total"]
              for s in window["steps"]
              if s["args"].get("window_pages_total")]
    return 100.0 * max(shares) if shares else None
