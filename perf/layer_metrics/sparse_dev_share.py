"""sparse_dev_share (%) - layer: sparse attention. Device time inside the
Pallas calls named ``sparse_*`` (``sparse_read``: a decode dispatch's rows,
a (row, KV head) reading the pages of the blocks IT chose and of its
window; ``sparse_read_chunk``: a prefill chunk's queries under the blocks
they chose and over their window; one call a sparse layer each) over device
busy time, lowest-numbered chip. Outside it, in XLA: the projections, the
K/V write (``paged_write``), and the index itself (scope ``sparse_index``:
the keys joining the group means of the leaf ``kc``, the scores against a
slot's group means, the softmax, the blocks' maxima, ``top_k``, the work
lists), whose operations have no name a trace keeps. A trace
with no such call (a model without sparse attention, a parent commit)
returns nothing."""


def sparse_calls(trace):
    return {name: c for name, c in trace["device0"]["custom_calls"].items()
            if name.startswith("sparse_")}


def read(record):
    trace = record.get("trace")
    if not trace or trace["device0"]["busy_s"] <= 0:
        return None
    calls = sparse_calls(trace)
    if not calls:
        return None
    return 100.0 * sum(c["total_s"] for c in calls.values()) \
        / trace["device0"]["busy_s"]
