"""The program's own spans of the traced window, for the metric readers.

The program records its spans (``repro.telemetry.spans``) only while the
profiler captures, and a capture starts a new session, so the latest
session holds the traced window and nothing of set-up or the check.  A
checkout whose program records no spans gives nothing to read: every
function here then returns None, and the metric is left out.
"""


def window():
    """The span records of the traced window, or None."""
    try:
        from repro.telemetry import spans
    except ImportError:             # a program without spans
        return None
    return spans.session() or None


def seconds_per_step(ctx, name: str):
    """Seconds spent in spans ``name`` (summed over threads) per step of
    the training window."""
    recs, steps = window(), ctx.data.get("steps")
    if recs is None or not steps:
        return None
    return sum(r.seconds for r in recs if r.name == name) / steps

