"""moe_local_share (%) - layer: routed FFN. Of the assignments the router
made in the window's steps (``moe_routed_assignments`` on ``serving/step``:
every row's ``k`` choices over all the experts the router knows), the share
that fell on experts this chip HOLDS and so made a row of the expert
products (``moe_assignments``). 100 x held / experts where the router is
even (12.5 % for 32 of 256); the rest is what the absent chips of the
deployment would compute. A program whose layers hold every expert sets no
``moe_routed_assignments`` (a parent commit, Mellum, Moonlight): nothing."""

from perf import program_spans


def read(record):
    window = program_spans.place_window(record,
                                        program_spans.program_events())
    if window is None:
        return None
    steps = [s["args"] for s in window["steps"]
             if s["args"].get("moe_routed_assignments")]
    if not steps:
        return None
    return 100.0 * sum(a["moe_assignments"] for a in steps) \
        / sum(a["moe_routed_assignments"] for a in steps)
