"""Model step, decode: device time of the blocked-decode program over
the decode steps it ran in the traced window. Moves tpot_p90_ms."""
from bench import programs
from bench import trace as tr


def read(ctx):
    if ctx.trace is None:
        return None
    steps = sum(t.decode_steps for t in ctx.traced_ticks)
    ns = tr.module_ns(ctx.trace, programs.is_decode)
    if not steps or ns is None:
        return None
    return ns * 1e-6 / steps
