"""Host-clock seconds per step of the trainer's sweep phase (the span the
benchmark puts around the sweep call), over the window's steps."""


def read(ctx):
    s = ctx.data.get("sweep_s")
    return sum(s) / len(s) if s else None
