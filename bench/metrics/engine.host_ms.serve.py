"""Host milliseconds per decoding engine step: each ``umap.engine.step``
that decoded, less its ``umap.engine.decode`` (dispatch and the read-back
of the tokens, which waits for the device), averaged over those steps.
What is left is the host work between two decode steps: admission (its
gates and the dispatch of prefill and install), page allocation and
copy-on-write, and the table sync."""

import program_spans


def read(ctx):
    recs = program_spans.window()
    if recs is None:
        return None
    steps = {r.span_id: r.seconds for r in recs if r.name == "umap.engine.step"}
    decoded = {}
    for r in recs:
        if r.name == "umap.engine.decode" and r.parent_id in steps:
            decoded[r.parent_id] = steps[r.parent_id] - r.seconds
    host = list(decoded.values())
    return 1e3 * sum(host) / len(host) if host else None
