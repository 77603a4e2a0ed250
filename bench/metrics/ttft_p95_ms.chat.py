"""95th percentile of time to first token over every request due in the
window, in milliseconds, as the open-loop driver takes it; a request with no
first token a minute after the close is charged its whole wait."""


def read(ctx):
    load = ctx.data.get("load")
    return None if load is None else load["ttft_p95_ms"]
