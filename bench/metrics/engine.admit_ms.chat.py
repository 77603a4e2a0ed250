"""Milliseconds per admission: the host's ``umap.engine.prefill`` span
(prefix match, page allocation and the dispatch of the prefill and of the
install into pool pages) plus the device's time in the prefill program
(``jit_prefill``) and in the install's scatters into the pools
(``jit_scatter``), both averaged over the window's admissions.  The host
and device parts overlap in part, so this is at most what one admission
adds to the gap between two tokens."""

import program_spans


def read(ctx):
    recs = program_spans.window()
    if recs is None or ctx.trace is None:
        return None
    host = [r.seconds for r in recs if r.name == "umap.engine.prefill"]
    if not host:
        return None
    programs = (ctx.trace.program("jit_prefill"), ctx.trace.program("jit_scatter"))
    device = sum(p["seconds"] for p in programs if p)
    return 1e3 * (sum(host) + device) / len(host)
