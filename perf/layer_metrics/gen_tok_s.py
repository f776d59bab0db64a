"""gen_tok_s (tokens/s) - layer: server step. Generated tokens the callers
saw appear inside the window, over the window's length: what an offline
pipeline that samples continuations is paid in. Beside the cell's 90th gap
it tells a faster step (both improve) from a server that seats fewer rows
(the gap falls, this falls with it). Sets of six seeds spread by 1.3 to
5 % (PERF.md section 6, PR 32), so it holds no bound. In a traced run the
profiler's start and stop stall the loop inside the window and this reads
about a tenth low, on both sides of a comparison alike."""


def read(record):
    whole = record.get("facts", {}).get("whole_window")
    seconds = record.get("facts", {}).get("seconds")
    if not whole or not seconds or "tokens_out" not in whole:
        return None
    return whole["tokens_out"] / seconds
