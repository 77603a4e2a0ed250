"""Programs and kernels found in a trace, for the per-layer metric readers.

The serve engine jits ``decode_step`` and ``prefill`` through
``functools.partial``, which the trace names ``jit__unknown``; where a
program carries its own name it is found by that.  Otherwise decode is the
heaviest program called once per decode step of the window, and prefill
every other program of the same name.
"""

import counts


def decode(ctx):
    t = ctx.trace
    return t.program("decode_step", ctx.data["decode"]["steps"]) if t else None


def prefill(ctx):
    t = ctx.trace
    if t is None:
        return None
    named = t.program("prefill")
    if named is not None:
        return named
    dec = decode(ctx)
    return t.others_like(dec["name"]) if dec and "name" in dec else None


def page_copy(ctx, kernel: str):
    """Roofline share of a page-copy kernel: every parameter page of the
    packed state is installed (scatter) and assembled (gather) once per
    step; each copy reads and writes its pages once."""
    t, d = ctx.trace, ctx.data
    p = t.program(kernel) if t else None
    if ctx.run.peaks is None or not p or not p["seconds"]:
        return None
    pages = counts.leaf_pages(d["param_leaf_shapes"], d["page_size"])
    need = d["steps"] * counts.page_copy_bytes(pages, d["page_size"])
    return 100.0 * need / (p["seconds"] * ctx.run.peaks["hbm_bytes_per_s"])
