"""ssm_chunk_tokens_mean (count) - layer: state-space layers. REAL tokens
that a prefill dispatch runs through the chunked form of the state-space
scan (``ssm_chunk_tokens`` on the program's ``serving/prefill_chunk``,
``serving/admit`` and ``serving/prefill_batch`` spans, from the host's own
positions: a chunk's or a bucket's padding is not counted), mean over the
window's such dispatches. The ``ssm_chunk`` kernel runs whole blocks of 128:
this over 128 is the share of its work that is not padding. A program that
sets no such attribute (no state-space layer, a parent commit) returns
nothing."""

from perf import program_spans

PREFILL_SPANS = ("serving/prefill_chunk", "serving/admit",
                 "serving/prefill_batch")


def read(record):
    events = program_spans.program_events()
    window = program_spans.place_window(record, events)
    if window is None:
        return None
    inside = program_spans.children(events, window["steps"], PREFILL_SPANS)
    tokens = [s["args"]["ssm_chunk_tokens"] for c in inside
              for name in PREFILL_SPANS for s in c[name]
              if "ssm_chunk_tokens" in s["args"]]
    return sum(tokens) / len(tokens) if tokens else None
