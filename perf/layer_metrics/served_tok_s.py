"""served_tok_s (tokens/s) - layer: server step. ``serve_tok_s`` as the
harness computes it (prompt tokens the server prefilled, by its own counter
read at both ends, + generated tokens the callers saw appear, over the
window), kept among the per-layer metrics of a cell where it cannot hold
its bound: two thirds of these tokens are prompt tokens at a twentieth of
a generated token's cost, and how many a window holds is which prompts
the seed had the server admit in it (14-20 % over six seeds, with 24 or 48
callers, after 30 or 90 s: PERF.md section 6, PR 32). ``gen_tok_s`` is its
steadier half. A traced run reads lower (the profiler's stall)."""


def read(record):
    return record.get("end_to_end", {}).get("serve_tok_s")
