"""Seconds per step spent writing dirty pages back to the backing store,
summed over the threads that wrote (``umap.pager.writeback`` spans)."""

import program_spans


def read(ctx):
    return program_spans.seconds_per_step(ctx, "umap.pager.writeback")
