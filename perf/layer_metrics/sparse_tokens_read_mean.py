"""sparse_tokens_read_mean (count) - layer: sparse attention. Tokens of K
and V that the equations read for one running row, a KV head a sparse layer
(``sparse_tokens_read`` over ``sparse_rows`` on the program's
``serving/decode`` span, from the host's own positions: all of a context
under ``dense_len``, else the window's and the chosen blocks', at most
``window_size + topk x block_size``), mean over the window's decode
dispatches. That it stays at 6,144 while the contexts are 16k-41k is what
the model is for. A program that sets no such attribute (a model without
sparse attention, a parent commit) returns nothing."""

from perf import program_spans

ATTRIBUTE = "sparse_tokens_read"


def read(record, attribute=ATTRIBUTE):
    events = program_spans.program_events()
    window = program_spans.place_window(record, events)
    if window is None:
        return None
    inside = program_spans.children(events, window["steps"],
                                    ("serving/decode",))
    a_row = [s["args"][attribute] / s["args"]["sparse_rows"]
             for c in inside for s in c["serving/decode"]
             if s["args"].get("sparse_rows")]
    return sum(a_row) / len(a_row) if a_row else None
