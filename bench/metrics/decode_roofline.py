"""Bytes the window's decode steps need (weights at the served dtype once
per step, plus the live keys and values of the active lanes, counted from
lengths) over their device time at the HBM peak, in percent."""

import counts
import rooflines


def read(ctx):
    if ctx.run.peaks is None:
        return None
    p = rooflines.decode(ctx)
    if not p or not p["seconds"]:
        return None
    d = ctx.data
    dec = d["decode"]
    need = counts.decode_bytes(d["arch"], dec["steps"], dec["context_tokens"],
                               d["itemsize"])
    return 100.0 * need / (p["seconds"] * ctx.run.peaks["hbm_bytes_per_s"])
