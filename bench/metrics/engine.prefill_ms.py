"""Device time per prefill program call in the window, in milliseconds."""

import rooflines


def read(ctx):
    p = rooflines.prefill(ctx)
    if not p or not p["count"]:
        return None
    return 1e3 * p["seconds"] / p["count"]
