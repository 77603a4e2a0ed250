"""Copies between host and device per training step: the ``copies``
counts of the program's ``umap.transfer.h2d`` and ``umap.transfer.d2h``
spans (grad phase, sweep and the parameter source's page fetches)."""

import program_spans


def read(ctx):
    recs, steps = program_spans.window(), ctx.data.get("steps")
    if recs is None or not steps:
        return None
    return sum(r.counts.get("copies", 0) for r in recs
               if r.name.startswith("umap.transfer.")) / steps
