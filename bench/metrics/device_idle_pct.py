"""Device: the share of the traced window in which no operation ran on
the chip (1 - the union of the op intervals over the window). Not clamped:
a busy count above the window (planes counted twice) reads below 0 and
shows. Moves tpot_p90_ms."""
from bench import trace as tr


def read(ctx):
    if ctx.trace is None or not ctx.trace["ops"]:
        return None
    window = ctx.trace_window_ns
    busy = tr.busy_ns(ctx.trace["ops"]) / max(ctx.trace["devices"], 1)
    return 100.0 * (1.0 - busy / window)
