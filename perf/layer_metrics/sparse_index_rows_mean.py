"""sparse_index_rows_mean (count) - layer: sparse attention. Compressed keys
visible to one running row, a KV head a sparse layer (``sparse_index_rows``
over ``sparse_rows`` on the program's ``serving/decode`` span, from the
host's own positions: one a ``kernel_stride`` positions once
``kernel_size`` have arrived), mean over the window's decode dispatches:
what the index scores a row, ~1 / 16 of the context where the read stays at
6,144 tokens. A program that sets no such attribute returns nothing."""

from perf.layer_metrics import sparse_tokens_read_mean


def read(record):
    return sparse_tokens_read_mean.read(record, "sparse_index_rows")
