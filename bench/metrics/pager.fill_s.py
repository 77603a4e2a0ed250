"""Seconds per step that the pager's filler threads spent reading pages
from the backing store, summed over fillers (``umap.pager.fill`` spans)."""

import program_spans


def read(ctx):
    return program_spans.seconds_per_step(ctx, "umap.pager.fill")
