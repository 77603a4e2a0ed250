"""6 x parameters (published) x tokens per step, over the window's time per
step at the bf16 peak, in percent."""

import counts


def read(ctx):
    if ctx.run.peaks is None:
        return None
    d = ctx.data
    flops = counts.train_flops(d["arch"], d["tokens_per_step"])
    return 100.0 * flops / (d["step_s"] * ctx.run.peaks["bf16_flops_per_s"])
