"""Bytes the window's steps need page_scatter to copy, over its device time at
the HBM peak, in percent."""

import rooflines


def read(ctx):
    return rooflines.page_copy(ctx, "page_scatter")
