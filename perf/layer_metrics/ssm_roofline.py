"""ssm_roofline (%) - layer: state-space layers. The least time the chip
could take for the traced ``ssm_*`` calls over the time the trace measured
for them.

* ``ssm_decode``, one call a Mamba layer a decode dispatch: the state of the
  rows the dispatch RAN (``state_rows`` on ``serving/decode``, never all
  slots), ``heads x d_head x d_state`` float32 a row, read once and written
  once, plus each row's ``x`` and ``dt`` and ``B``, ``C`` in and ``y`` out
  (float32, as the equations have them: what the kernel is handed is laid
  out wider); five operations a state element (decay, the outer product's
  multiply-add, the read's multiply-add), far under the bytes' time.
* ``ssm_chunk``, one call a Mamba layer a prefill dispatch: the state of
  the rows the dispatch ran (``state_rows`` on its span: one for a chunk,
  as many as were admitted together for a bucketed admission) read and
  written, and for each REAL token (``ssm_chunk_tokens``) the dual form's
  operations at the kernel's block of ``BLOCK`` tokens: ``C B^T`` once
  (2 Q N), and a head ``(C B^T . decay) X`` (2 Q P), the carried read
  ``C H`` (2 N P) and the update ``B^T X`` (2 N P); its vectors in and
  ``y`` out. The operations are held against the chip's bfloat16 peak
  (``perf/peaks.json`` has no other) while the kernel's products are
  float32 at ``Precision.HIGHEST``, several bfloat16 passes each: a
  chunk's least time is understated and its share of this metric is a
  LOWER bound.

Rows a call and tokens a chunk are the window's means, from the program's
spans; the calls and their time are the trace's; the widths are the
configuration file's. Whatever implements the kernels, the share cannot
pass 100 %: the bytes are those of the rows in the spans' ``state_rows``,
which any implementation must move, and the operations those of real
tokens alone."""

from perf import program_spans

BLOCK = 128     # tokens a block of ops/state_space.py's ssm_chunk (CHUNK)
PREFILL_SPANS = ("serving/prefill_chunk", "serving/admit",
                 "serving/prefill_batch")


def state_bytes_a_row_a_layer(heads: int, d_head: int, d_state: int) -> float:
    return 4.0 * heads * d_head * d_state


def vector_bytes_a_token(heads: int, d_head: int, d_state: int) -> float:
    """``x`` and ``y`` (heads x d_head), ``dt`` (heads), ``B`` and ``C``
    (d_state), float32."""
    return 4.0 * (2 * heads * d_head + heads + 2 * d_state)


def decode_call(rows: float, heads: int, d_head: int, d_state: int):
    """``(operations, bytes)`` of one layer's ``ssm_decode``."""
    state = state_bytes_a_row_a_layer(heads, d_head, d_state)
    return rows * 5.0 * state / 4.0, rows * (
        2.0 * state + vector_bytes_a_token(heads, d_head, d_state))


def chunk_call(rows: float, tokens: float, heads: int, d_head: int,
               d_state: int, block: int = BLOCK):
    """``(operations, bytes)`` of one layer's ``ssm_chunk`` over ``tokens``
    real tokens of each of ``rows`` rows."""
    a_token = 2.0 * block * d_state + heads * (
        2.0 * block * d_head + 4.0 * d_state * d_head)
    return rows * tokens * a_token, rows * (
        2.0 * state_bytes_a_row_a_layer(heads, d_head, d_state)
        + tokens * vector_bytes_a_token(heads, d_head, d_state))


def least_seconds(flops: float, bytes_moved: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops_per_s"],
               bytes_moved / peaks["hbm_bytes_per_s"])


def _mean(values):
    return sum(values) / len(values) if values else None


def read(record):
    trace, peaks = record.get("trace"), record.get("peaks")
    config = record.get("config") or {}
    dims = [config.get(key) for key in ("mamba_n_heads", "mamba_d_head",
                                        "mamba_d_state")]
    if not trace or not peaks or not all(dims):
        return None
    calls = {name: c for name, c in trace["device0"]["custom_calls"].items()
             if name.startswith("ssm_")}
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
                for s in c[name] if "ssm_chunk_tokens" in s["args"]]
    tokens = _mean([a["ssm_chunk_tokens"] for a in prefills])
    chunk_rows = _mean([a.get("state_rows", 1) for a in prefills])
    least = measured = 0.0
    for name, call in calls.items():
        if name.startswith("ssm_decode"):
            if decode_rows is None:
                return None
            work = decode_call(decode_rows, *dims)
        else:
            if tokens is None:
                return None
            work = chunk_call(chunk_rows, tokens / chunk_rows, *dims)
        least += call["count"] * least_seconds(*work, peaks)
        measured += call["total_s"]
    return 100.0 * least / measured if measured > 0 else None
