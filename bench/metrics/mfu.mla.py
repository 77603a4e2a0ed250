"""Model operations of the window's prefill and decode work on a
latent-attention MoE model (active parameters: the top-k routed and the
shared experts; attention per key in the per-head form) over the traced
window at the bf16 peak, in percent."""

import mla_counts


def read(ctx):
    d = ctx.data
    if ctx.run.peaks is None or ctx.trace is None or "moe" not in d:
        return None
    arch, dec = d["arch"], d["decode"]
    flops = mla_counts.decode_flops(arch, dec["lanes"], dec["context_tokens"])
    flops += sum(mla_counts.prefill_flops(arch, new, held)
                 for new, held in d["prefills"])
    return 100.0 * flops / (ctx.trace.window_s * ctx.run.peaks["bf16_flops_per_s"])
