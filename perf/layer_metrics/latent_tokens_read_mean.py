"""latent_tokens_read_mean (count) - layer: latent attention. Cached rows
that a decode dispatch's running slots see, a layer
(``latent_tokens_read`` on the program's ``serving/decode`` span, from the
host's own positions: each running slot's cached positions up to and with
its own token), mean over the window's decode dispatches. Times the
configuration's ``kv_bytes_per_token_a_layer`` it is what ``mla_decode``
must read a layer. A program that sets no such attribute (a K/V model, a
parent commit) returns nothing."""

from perf import program_spans


def read(record):
    events = program_spans.program_events()
    window = program_spans.place_window(record, events)
    if window is None:
        return None
    inside = program_spans.children(events, window["steps"],
                                    ("serving/decode",))
    tokens = [s["args"]["latent_tokens_read"] for c in inside
              for s in c["serving/decode"]
              if "latent_tokens_read" in s["args"]]
    return sum(tokens) / len(tokens) if tokens else None
