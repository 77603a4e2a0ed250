"""Seconds per step that the trainer's threads waited on the pager for
pages being filled (the program's ``umap.pager.fault_wait`` spans)."""

import program_spans


def read(ctx):
    return program_spans.seconds_per_step(ctx, "umap.pager.fault_wait")
