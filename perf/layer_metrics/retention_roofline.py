"""retention_roofline (%) - layer: retention state. The least time the chip
could take for the traced ``retention_*`` calls over the time the trace
measured for them.

* ``retention_decode``, one call a layer a decode dispatch: the state of the
  rows the dispatch RAN (``state_rows`` on ``serving/decode``, never all
  slots), read once and written once, plus each row's ``q``, ``k``, ``v`` in
  and output out; its operations (two multiply-adds a state element for the
  update, two a query head for the read) are far under the bytes' time.
* ``retention_chunk``, one call a layer a chunk or admission dispatch: one
  row's state read and written, the chunk's ``q``, ``k``, ``v`` in and output
  out; operations: the carried read ``phi(q)^T s`` and the update
  ``v phi(k)^T`` over every feature row, and the chunk's own attention form.

Rows a call and tokens a chunk are the window's means, from the program's
spans; the calls and their time are the trace's. Whatever implements the
kernels, the share cannot pass 100 %: the bytes are those of the rows in
the spans' ``state_rows``, which any implementation must move."""

from perf import program_spans


def state_bytes_a_row_a_layer(kv_heads: int, head_dim: int) -> float:
    """float32 ``s`` (d/2 + 1, d, d) and ``z`` (d/2 + 1, d) a KV head: what
    the layer's equations need, whatever more the program's leaf holds."""
    rotations = head_dim // 2 + 1
    return 4.0 * kv_heads * rotations * head_dim * (head_dim + 1)


def decode_call(rows: float, heads: int, kv_heads: int, head_dim: int):
    """``(operations, bytes)`` of one layer's ``retention_decode``."""
    state = state_bytes_a_row_a_layer(kv_heads, head_dim)
    rep = heads // kv_heads
    flops = rows * (state / 4.0) * (4.0 + 2.0 * rep)
    vectors = rows * head_dim * (2 * (heads + 2 * kv_heads) + 4 * heads)
    return flops, 2.0 * rows * state + vectors


def chunk_call(rows: float, tokens: float, heads: int, kv_heads: int,
               head_dim: int):
    """``(operations, bytes)`` of one layer's ``retention_chunk`` over
    ``tokens`` tokens of each of ``rows`` rows."""
    state = state_bytes_a_row_a_layer(kv_heads, head_dim)
    features = (head_dim // 2 + 1) * head_dim
    carried = 2.0 * tokens * heads * features * head_dim
    update = 2.0 * tokens * kv_heads * features * head_dim
    inside = 2.0 * 2 * tokens * tokens * heads * head_dim
    vectors = tokens * head_dim * (2 * (heads + 2 * kv_heads) + 4 * heads)
    return rows * (carried + update + inside), \
        rows * (2.0 * state + vectors)


def least_seconds(flops: float, bytes_moved: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops_per_s"],
               bytes_moved / peaks["hbm_bytes_per_s"])


def _mean(values):
    return sum(values) / len(values) if values else None


def read(record):
    trace, peaks = record.get("trace"), record.get("peaks")
    dims = record.get("kernel_dims")
    if not trace or not peaks or not dims:
        return None
    calls = {name: c for name, c in trace["device0"]["custom_calls"].items()
             if name.startswith("retention_")}
    events = program_spans.program_events()
    window = program_spans.place_window(record, events)
    if not calls or window is None:
        return None
    inside = program_spans.children(
        events, window["steps"],
        ("serving/decode", "serving/prefill_chunk", "serving/admit",
         "serving/prefill_batch"))
    decode_rows = _mean([s["args"]["state_rows"] for c in inside
                         for s in c["serving/decode"]
                         if "state_rows" in s["args"]])
    chunks = [(s["args"]["state_rows"],
               s["args"].get("len") or s["args"].get("width"))
              for c in inside for name in ("serving/prefill_chunk",
                                           "serving/admit",
                                           "serving/prefill_batch")
              for s in c[name] if "state_rows" in s["args"]]
    H, KV, D = dims["H"], dims["KV"], dims["D"]
    least = measured = 0.0
    for name, call in calls.items():
        if name.startswith("retention_decode"):
            if decode_rows is None:
                return None
            work = decode_call(decode_rows, H, KV, D)
        else:
            if not chunks:
                return None
            work = chunk_call(_mean([r for r, _ in chunks]),
                              _mean([t for _, t in chunks]), H, KV, D)
        least += call["count"] * least_seconds(*work, peaks)
        measured += call["total_s"]
    return 100.0 * least / measured if measured > 0 else None
