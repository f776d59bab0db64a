"""gdn_roofline (%) - layer: Gated DeltaNet state layers. The least time the
chip could take for the traced ``gdn_*`` calls over the time the trace
measured for them.

* ``gdn_decode``, one call a DeltaNet layer a decode dispatch: the state of
  the rows the dispatch RAN (``state_rows`` on ``serving/decode``, never
  all slots), ``value heads x d x d`` float32 a row, read once and written
  once, plus each row's vectors as the equations have them (``q``, ``k``:
  ``key heads x d`` each; ``v`` in and ``o`` out: ``value heads x d`` each;
  the decay and ``beta``: one number a value head; float32: what the kernel
  is handed is laid out wider); seven operations a state element (the
  decay, the read against ``k``, the outer product, the read against
  ``q``), far under the bytes' time.
* ``gdn_chunk``: a prefill dispatch's chunk of ``prefill_chunk`` tokens is
  ``ceil(prefill_chunk / BLOCK)`` calls a DeltaNet layer, the state carried
  from one to the next through HBM. What any implementation must move is a
  DISPATCH's: the state of the rows the dispatch ran (``state_rows`` on its
  span) read once and written once, each REAL token's vectors
  (``gdn_chunk_tokens``), and the operations of the CHEAPER of the two
  forms of the mathematics for the real tokens: the recurrence (seven a
  state element a token) or the chunk form at the kernel's block of
  ``BLOCK`` tokens (a token a value head: three products against the state,
  ``2 d d`` each, and two against a block's rows, ``2 BLOCK d`` each; the
  pairs and the inverse before the kernel are not counted). So the calls
  of a layer's dispatch are held together against one dispatch's least.
  The operations are held against the chip's bfloat16 peak
  (``perf/peaks.json`` has no other) while the kernel's products are
  float32 at ``Precision.HIGHEST``: a chunk's least time is understated and
  its share of this metric is a LOWER bound.

The time is the kernels' own: what XLA does before a block's kernel (scope
``gdn_chunk_prep``) is in neither side. Rows a call and tokens a chunk are
the window's means, from the program's spans; the calls and their time are
the trace's; the widths are the configuration file's
(``linear_num_key_heads``, ``linear_num_value_heads``,
``linear_key_head_dim``). Whatever implements the kernels, the share cannot
pass 100 %: the bytes are those of the rows in the spans' ``state_rows``,
once a dispatch, which any implementation must move, and the operations the
cheaper form's over real tokens alone."""

from perf import program_spans

BLOCK = 128     # tokens a block of ops/kda.py's kda_chunk (CHUNK)
PREFILL_SPANS = ("serving/prefill_chunk", "serving/admit",
                 "serving/prefill_batch")


def state_bytes_a_row_a_layer(heads: int, d: int) -> float:
    """A row's state in one layer: ``heads`` VALUE heads of (d, d) float32."""
    return 4.0 * heads * d * d


def vector_bytes_a_token(key_heads: int, heads: int, d: int) -> float:
    """``q``, ``k`` (key heads x d each), ``v`` and ``o`` (value heads x d
    each), the decay and ``beta`` (a value head each), float32."""
    return 4.0 * (2 * key_heads * d + 2 * heads * d + 2 * heads)


def decode_call(rows: float, key_heads: int, heads: int, d: int):
    """``(operations, bytes)`` of one layer's ``gdn_decode``."""
    state = state_bytes_a_row_a_layer(heads, d)
    return rows * 7.0 * state / 4.0, rows * (
        2.0 * state + vector_bytes_a_token(key_heads, heads, d))


def chunk_dispatch(rows: float, tokens: float, key_heads: int, heads: int,
                   d: int, block: int = BLOCK):
    """``(operations, bytes)`` of one layer's ``gdn_chunk`` calls of ONE
    dispatch over ``tokens`` real tokens of each of ``rows`` rows."""
    a_token = heads * min(7.0 * d * d, 6.0 * d * d + 4.0 * block * d)
    return rows * tokens * a_token, rows * (
        2.0 * state_bytes_a_row_a_layer(heads, d)
        + tokens * vector_bytes_a_token(key_heads, heads, d))


def least_seconds(flops: float, bytes_moved: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops_per_s"],
               bytes_moved / peaks["hbm_bytes_per_s"])


def _mean(values):
    return sum(values) / len(values) if values else None


def read(record):
    trace, peaks = record.get("trace"), record.get("peaks")
    config = record.get("config") or {}
    dims = [config.get("linear_num_key_heads"),
            config.get("linear_num_value_heads"),
            config.get("linear_key_head_dim")]
    if not trace or not peaks or not all(dims):
        return None
    calls = {name: c for name, c in trace["device0"]["custom_calls"].items()
             if name.startswith("gdn_")}
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
                for s in c[name] if "gdn_chunk_tokens" in s["args"]]
    tokens = _mean([a["gdn_chunk_tokens"] for a in prefills])
    chunk_rows = _mean([a.get("state_rows", 1) for a in prefills])
    width = (record.get("facts") or {}).get("prefill_chunk") or BLOCK
    blocks = max(1, -(-int(width) // BLOCK))    # calls a layer a dispatch
    least = measured = 0.0
    for name, call in calls.items():
        if name.startswith("gdn_decode"):
            if decode_rows is None:
                return None
            least += call["count"] * least_seconds(
                *decode_call(decode_rows, *dims), peaks)
        else:
            if tokens is None:
                return None
            least += call["count"] / blocks * least_seconds(
                *chunk_dispatch(chunk_rows, tokens / chunk_rows, *dims),
                peaks)
        measured += call["total_s"]
    return 100.0 * least / measured if measured > 0 else None
