"""pallas_roofline (%) - layer: kernels. The least time the chip could take
for the step's Pallas calls (perf/roofline.py: the larger of operations over
peak FLOP/s and bytes over peak HBM bytes/s, from shapes) over the time the
trace measured for them. Only where every custom call of the step is of one
kernel family, named by the configuration's ``trace.kernel_family``:

* ``flash``: forward, dQ and dK/dV calls told apart by output shape, sized
  by the cell's micro-batch (bound by operations at T=1024, D=64);
* ``paged_decode``: one call per layer per step reads the K/V of every
  cached token of the live slots (bound by bytes); the cached tokens of the
  traced steps come from the harness's own per-step samples.
"""

from perf import roofline


def read(record):
    trace = record.get("trace")
    if not trace or not trace["device0"]["custom_calls"]:
        return None
    family = record["config"]["trace"].get("kernel_family")
    peaks, dims = record["peaks"], record.get("kernel_dims")
    calls = trace["device0"]["custom_calls"]
    if not dims or not peaks:
        return None
    if family == "flash":
        out = roofline.flash_roofline(calls, dims, peaks)
        return None if out is None else 100.0 * out["share"]
    if family == "paged_decode":
        samples = record["samples"].get("traced_decode_steps", [])
        n_calls = sum(c["count"] for c in calls.values())
        if not samples or n_calls == 0:
            return None
        # the trace and the host samples cover the same steps up to an
        # edge; scale the sampled steps to the calls the trace holds
        per_step = []
        for cached, live in samples:
            f, b = roofline.paged_decode_call(cached, live, dims["KV"],
                                              dims["H"], dims["D"])
            per_step.append(roofline.least_seconds(f, b, peaks)[0])
        least = sum(per_step) / len(per_step) * n_calls
        return 100.0 * least / trace["device0"]["custom_call_s"]
    return None
