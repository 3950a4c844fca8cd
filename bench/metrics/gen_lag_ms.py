"""Load generator: the largest delay of a send behind its due time, over
the window's untraced part. Moves ttft_p90_ms."""


def read(ctx):
    lags = [(t.sent - t.due) * 1e3 for t in ctx.window.tracks
            if t.sent < ctx.window.trace_start]
    return max(lags) if lags else None
