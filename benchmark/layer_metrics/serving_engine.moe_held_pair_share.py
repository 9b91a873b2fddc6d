"""Share of the routed (token, expert) pairs that this chip computes: the
window's ``serving.device_wait`` spans' ``moe_pairs_held`` (the pairs a
collected step dispatched to experts held here, summed over its expert
layers: counted by the step itself where the model holds a share of its
routed experts, and read with the step's tokens) over their
``moe_pairs_routed`` (that step's tokens x experts a token x expert
layers). A quarter where the chip holds 32 of 128 experts and the router
spreads evenly: the chip's real expert load, which uneven routing moves.
A program whose spans lack the attributes gives nothing to read."""


def read(record, cell):
    held = routed = 0
    for s in record.get("spans", ()):
        a = s.get("args") or {}
        if s["name"] == "serving.device_wait" and a.get("moe_pairs_routed"):
            held += a["moe_pairs_held"]
            routed += a["moe_pairs_routed"]
    return 100.0 * held / routed if routed else None
