"""prefill_dev_share (%) - layer: programs. Device time of the prefill
programs (chunk and bucketed; the configuration's ``trace.prefill_modules``)
over device busy time."""


def read(record):
    trace = record.get("trace")
    if not trace or trace["device0"]["busy_s"] <= 0:
        return None
    wanted = record["config"]["trace"]["prefill_modules"]
    spent = sum(m["total_s"] for name, m in
                trace["device0"]["modules"].items()
                if any(w in name for w in wanted))
    return 100.0 * spent / trace["device0"]["busy_s"]
