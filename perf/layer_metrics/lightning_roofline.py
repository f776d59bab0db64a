"""lightning_roofline (%) - layer: Lightning state layers. The least time
the chip could take for the traced ``lightning_*`` calls over the time the
trace measured for them (as ``kda_roofline``).

* ``lightning_decode``, one call a Lightning layer a decode dispatch: the
  state of the rows the dispatch RAN (``state_rows`` on ``serving/decode``,
  never all slots), ``heads x d x d`` float32 a row, read once and written
  once, plus each row's vectors (``q``, ``k``, ``v`` in and ``o`` out,
  ``heads x d`` float32 each); four operations a state element (the decay,
  the outer product's product and sum, the read against ``q``), far under
  the bytes' time.
* ``lightning_chunk``, ``prefill_chunk / BLOCK`` calls a Lightning layer a
  prefill dispatch. A DISPATCH's least: the state of the row it ran read
  and written once (an implementation may carry it from block to block on
  the chip), each REAL token's vectors (``lightning_chunk_tokens``), and
  the operations of the cheaper of the two forms for the real tokens: the
  recurrence (four a state element a token) or the chunk form at ``BLOCK``
  tokens (a token a head: two products against the state, ``2 d d`` each,
  and two against a block's rows, ``2 BLOCK d`` each). The operations are
  held against the chip's bfloat16 peak while the kernel's products are
  float32 at ``Precision.HIGHEST``: a LOWER bound.

What XLA does before a chunk's kernel (scope ``lightning_chunk_prep``) is in
neither side. Rows and tokens are the window's means, from the program's
spans; the calls and their time are the trace's; the widths are the
configuration file's. Whatever implements the kernels, the share cannot
pass 100 %."""

from perf import program_spans

BLOCK = 128     # tokens a call of lightning_chunk (ops/state_space.CHUNK)
PREFILL_SPANS = ("serving/prefill_chunk", "serving/admit",
                 "serving/prefill_batch")


def state_bytes_a_row_a_layer(heads: int, d: int) -> float:
    return 4.0 * heads * d * d


def vector_bytes_a_token(heads: int, d: int) -> float:
    """``q``, ``k``, ``v`` and ``o`` (heads x d each), float32."""
    return 4.0 * 4 * heads * d


def decode_call(rows: float, heads: int, d: int):
    """``(operations, bytes)`` of one layer's ``lightning_decode``."""
    state = state_bytes_a_row_a_layer(heads, d)
    return rows * 4.0 * state / 4.0, rows * (
        2.0 * state + vector_bytes_a_token(heads, d))


def chunk_dispatch(rows: float, tokens: float, heads: int, d: int,
                   block: int = BLOCK):
    """``(operations, bytes)`` of one layer's chunk calls of ONE dispatch
    over ``tokens`` real tokens of each of ``rows`` rows."""
    a_token = heads * min(4.0 * d * d, 4.0 * d * d + 4.0 * block * d)
    return rows * tokens * a_token, rows * (
        2.0 * state_bytes_a_row_a_layer(heads, d)
        + tokens * vector_bytes_a_token(heads, d))


def least_seconds(flops: float, bytes_moved: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops_per_s"],
               bytes_moved / peaks["hbm_bytes_per_s"])


def _mean(values):
    return sum(values) / len(values) if values else None


def read(record):
    trace, peaks = record.get("trace"), record.get("peaks")
    config = record.get("config") or {}
    dims = [config.get("lightning_nh"), config.get("lightning_head_dim")]
    if not trace or not peaks or not all(dims):
        return None
    calls = {name: c for name, c in trace["device0"]["custom_calls"].items()
             if name.startswith("lightning_")}
    events = program_spans.program_events()
    window = program_spans.place_window(record, events)
    if not calls or window is None:
        return None
    inside = program_spans.children(
        events, window["steps"], ("serving/decode",) + PREFILL_SPANS)
    decode_rows = _mean([s["args"]["state_rows"] for c in inside
                         for s in c["serving/decode"]
                         if "state_rows" in s["args"]])
    prefills = [s["args"] for c in inside for name in PREFILL_SPANS
                for s in c[name] if "lightning_chunk_tokens" in s["args"]]
    tokens = _mean([a["lightning_chunk_tokens"] for a in prefills])
    chunk_rows = _mean([a.get("state_rows", 1) for a in prefills])
    chunk = int((record.get("facts") or {}).get("prefill_chunk") or BLOCK)
    least = measured = 0.0
    for name, call in calls.items():
        if name.startswith("lightning_decode"):
            if decode_rows is None:
                return None
            least += call["count"] * least_seconds(
                *decode_call(decode_rows, *dims), peaks)
        else:
            if tokens is None:
                return None
            per_dispatch = max(1, -(-chunk // BLOCK))
            least += call["count"] / per_dispatch * least_seconds(
                *chunk_dispatch(chunk_rows, tokens / chunk_rows, *dims),
                peaks)
        measured += call["total_s"]
    return 100.0 * least / measured if measured > 0 else None
