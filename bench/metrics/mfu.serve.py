"""Model operations of the window's work (prompt tokens not already held by
a shared prefix, and every decode step's lanes at their context lengths,
at published widths) over the traced window at the bf16 peak, in percent."""

import counts


def read(ctx):
    if ctx.run.peaks is None or ctx.trace is None:
        return None
    d = ctx.data
    arch, dec = d["arch"], d["decode"]
    flops = counts.decode_flops(arch, dec["lanes"], dec["context_tokens"])
    flops += sum(counts.prefill_flops(arch, new, held) for new, held in d["prefills"])
    return 100.0 * flops / (ctx.trace.window_s * ctx.run.peaks["bf16_flops_per_s"])
