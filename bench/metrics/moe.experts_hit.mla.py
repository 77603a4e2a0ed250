"""Mean share of the routed experts that received at least one token, per
MoE layer per decode step of the window, from the engine's on-device MoE
counters, in percent."""

import mla_counts


def read(ctx):
    d = ctx.data
    moe = d.get("moe")
    if not moe or not moe["steps"]:
        return None
    arch = d["arch"]
    slots = moe["steps"] * mla_counts.moe_layers(arch) * arch["n_routed_experts"]
    return 100.0 * moe["moe_decode_experts_hit"] / slots
