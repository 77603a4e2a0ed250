"""Demand faults of the parameter and moment regions per step of the
window, from the regions' own counters."""


def read(ctx):
    d = ctx.data
    return d["demand_faults"] / d["steps"] if d.get("steps") else None
