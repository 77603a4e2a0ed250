"""Seconds per step of the trainer's grad phase: parameters fetched
through the pager, loss and gradients, and their read-back
(``umap.ooc.grad`` spans)."""

import program_spans


def read(ctx):
    return program_spans.seconds_per_step(ctx, "umap.ooc.grad")
