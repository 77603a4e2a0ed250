"""Share of the positions that the window's prefills computed which a
shared prefix's pool pages already held, in percent: the ``held_tokens``
over the ``tokens`` counts of the ``umap.engine.prefill`` spans."""

import program_spans


def read(ctx):
    recs = program_spans.window()
    if recs is None:
        return None
    pre = [r.counts for r in recs if r.name == "umap.engine.prefill"]
    computed = sum(c.get("tokens", 0) for c in pre)
    if not computed:
        return None
    return 100.0 * sum(c.get("held_tokens", 0) for c in pre) / computed
