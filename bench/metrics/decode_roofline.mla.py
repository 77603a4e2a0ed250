"""Bytes the window's decode steps need on a latent-attention MoE model
(the non-expert weights once per step, each expert the MoE counter says
was hit once, and the active lanes' live latent rows, counted from
lengths) over the decode program's device time at the HBM peak, in
percent."""

import mla_counts
import rooflines


def read(ctx):
    d = ctx.data
    if ctx.run.peaks is None or "moe" not in d or not d["moe"]:
        return None
    p = rooflines.decode(ctx)
    if not p or not p["seconds"]:
        return None
    dec = d["decode"]
    need = mla_counts.decode_bytes(d["arch"], dec["steps"],
                                   d["moe"]["moe_decode_experts_hit"],
                                   dec["context_tokens"], d["itemsize"])
    return 100.0 * need / (p["seconds"] * ctx.run.peaks["hbm_bytes_per_s"])
