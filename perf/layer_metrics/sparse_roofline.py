"""sparse_roofline (%) - layer: sparse attention. The least time the chip
could take for the traced ``sparse_*`` calls over the time the trace
measured for them.

* ``sparse_read``, one call a sparse layer a decode dispatch. For the rows
  the dispatch RAN (``sparse_rows`` on ``serving/decode``): the tokens of K
  and V that the EQUATIONS read, a KV head (``sparse_tokens_read``: all of a
  context under ``dense_len``, else the window's 2,048 and the 64 chosen
  blocks' 4,096, whatever the implementation's page holds beside them),
  ``head_dim`` wide, K and V, in the cache's bfloat16; the operations are
  the scores' and the values' products of every query head over those
  tokens.
* ``sparse_read_chunk``, one call a sparse layer a prefill dispatch: the
  chunk's queries under the blocks they chose and over their window. A
  dispatch's operations are the two products of every REAL query over the
  tokens it reads (``sparse_tokens_read`` on ``serving/prefill_chunk``); its
  bytes are the tokens ONE query reads at least (the chunk's mean), which
  no implementation that shares a key among queries can go under.

The compressed keys' bytes and the index's operations are in neither side:
the index is XLA's (scope ``sparse_index``), outside these calls' time.
Tokens and rows are the window's means, from the program's spans; the calls
and their time are the trace's; the widths are the configuration file's,
never the implementation's page size. Whatever implements the read, the
share cannot pass 100 %."""

from perf import program_spans

KV_ITEMSIZE = 2.0       # the cache's bfloat16


def read_work(tokens: float, heads: int, kv_heads: int, d: int):
    """``(operations, bytes)`` of a read of ``tokens`` tokens a KV head by
    one query row of every head: two products a query head a token, K and V
    a KV head a token."""
    return 4.0 * tokens * heads * d, 2.0 * tokens * kv_heads * d * KV_ITEMSIZE


def least_seconds(flops: float, bytes_moved: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops_per_s"],
               bytes_moved / peaks["hbm_bytes_per_s"])


def _mean(values):
    return sum(values) / len(values) if values else None


def read(record):
    trace, peaks = record.get("trace"), record.get("peaks")
    config = record.get("config") or {}
    dims = [config.get("num_attention_heads"),
            config.get("num_key_value_heads"), config.get("head_dim")]
    if not trace or not peaks or not all(dims):
        return None
    calls = {name: c for name, c in trace["device0"]["custom_calls"].items()
             if name.startswith("sparse_")}
    events = program_spans.program_events()
    window = program_spans.place_window(record, events)
    if not calls or window is None:
        return None
    inside = program_spans.children(
        events, window["steps"], ("serving/decode", "serving/prefill_chunk"))

    def spans(name):
        return [s["args"] for c in inside for s in c[name]
                if s["args"].get("sparse_rows")]

    decode = _mean([a["sparse_tokens_read"] for a in spans("serving/decode")])
    chunks = spans("serving/prefill_chunk")
    chunk_tokens = _mean([a["sparse_tokens_read"] for a in chunks])
    chunk_rows = _mean([a["sparse_rows"] for a in chunks])
    least = measured = 0.0
    for name, call in calls.items():
        if name.startswith("sparse_read_chunk"):
            if chunk_tokens is None:
                return None
            flops, _ = read_work(chunk_tokens, *dims)
            _, moved = read_work(chunk_tokens / chunk_rows, *dims)
            work = call["count"] * least_seconds(flops, moved, peaks)
        else:
            if decode is None:
                return None
            work = call["count"] * least_seconds(*read_work(decode, *dims),
                                                 peaks)
        least += work
        measured += call["total_s"]
    return 100.0 * least / measured if measured > 0 else None
