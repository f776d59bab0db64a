"""mla_roofline (%) - layer: latent attention. The least time the chip could
take for the traced ``mla_*`` calls over the time the trace measured for
them.

* ``mla_decode``, one call a layer a decode dispatch: the cached rows its
  running slots see (``latent_tokens_read`` on ``serving/decode``: never a
  slot that rides along), each ``kv_bytes_per_token_a_layer`` wide, read
  ONCE for all heads; the rows written; the absorbed queries in (a head
  ``rank + rope`` wide) and the result out (``rank`` wide). Operations, the
  absorbed form's: a query row of a head against a cached row is ``rank +
  rope`` multiply-adds for the score and ``rank`` for the values. Bound by
  bytes.
* ``mla_chunk``, the calls of a chunk dispatch a layer: the rows up to the
  chunk's last real token (``latent_tokens_read`` on
  ``serving/prefill_chunk``) read once, and the operations of the CHEAPER of
  the two forms of the mathematics: absorbed as above over the pairs of a
  row and a position it sees, or K and V rebuilt from every row read
  (``2 x rank x heads x (nope + v)`` a row) and attended a head at
  ``nope + rope`` and ``v`` wide. A dispatch wider than the program's
  ``MAX_ROWS`` query-head rows makes several calls; its work is split
  evenly over them.

Tokens and rows a call are the window's means, from the program's spans;
the calls and their time are the trace's. Whatever implements the read, the
share cannot pass 100 %: the bytes are those of the rows the spans name,
which any implementation must move, and the operations the cheaper
form's."""

from perf import program_spans


def decode_call(tokens_read: float, rows: float, heads: int, rank: int,
                rope: int, itemsize: int = 2):
    """``(operations, bytes)`` of one layer's decode read of ``tokens_read``
    cached rows by ``rows`` slots' query rows."""
    width = rank + rope
    flops = 2.0 * heads * (width + rank) * tokens_read
    moved = itemsize * (tokens_read * width + rows * width
                        + rows * heads * (width + rank))
    return flops, moved


def chunk_call(tokens_read: float, length: float, heads: int, rank: int,
               rope: int, nope: int, v: int, itemsize: int = 2):
    """``(operations, bytes)`` of one layer's chunk read: ``length`` query
    tokens, the last of which sees ``tokens_read`` rows."""
    width = rank + rope
    pairs = length * (tokens_read - (length - 1) / 2.0)
    absorbed = 2.0 * heads * (width + rank) * pairs
    expanded = 2.0 * rank * heads * (nope + v) * tokens_read \
        + 2.0 * heads * (nope + rope + v) * pairs
    moved = itemsize * (tokens_read * width + length * width
                        + length * heads * (width + rank))
    return min(absorbed, expanded), moved


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
             if name.startswith("mla_")}
    events = program_spans.program_events()
    window = program_spans.place_window(record, events)
    if not calls or window is None:
        return None
    try:
        from deepspeed_tpu.ops.attention.latent_attention import MAX_ROWS
    except ImportError:
        return None
    config = record["config"]
    H, rank, rope = dims["H"], int(config["kv_lora_rank"]), \
        int(config["qk_rope_head_dim"])
    nope, v = int(config["qk_nope_head_dim"]), int(config["v_head_dim"])
    inside = program_spans.children(
        events, window["steps"], ("serving/decode", "serving/prefill_chunk"))

    def seen(name, also):
        return [(s["args"]["latent_tokens_read"], s["args"][also])
                for c in inside for s in c[name]
                if "latent_tokens_read" in s["args"]]

    decodes = seen("serving/decode", "live")
    chunks = seen("serving/prefill_chunk", "len")
    least = measured = 0.0
    for name, call in calls.items():
        if name.startswith("mla_decode"):
            if not decodes:
                return None
            work = decode_call(_mean([t for t, _ in decodes]),
                               _mean([n for _, n in decodes]), H, rank, rope)
            a_call = least_seconds(*work, peaks)
        else:
            if not chunks:
                return None
            work = chunk_call(_mean([t for t, _ in chunks]),
                              _mean([n for _, n in chunks]), H, rank, rope,
                              nope, v)
            width = int(record["facts"]["prefill_chunk"])
            a_call = least_seconds(*work, peaks) / -(-width * H // MAX_ROWS)
        least += call["count"] * a_call
        measured += call["total_s"]
    return 100.0 * least / measured if measured > 0 else None
