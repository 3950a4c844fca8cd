"""Model step, prefill: device time of the prefill-chunk program per
1,000 prompt tokens written in the traced window. Moves ttft_p90_ms."""
from bench import programs
from bench import trace as tr


def read(ctx):
    if ctx.trace is None:
        return None
    tokens = sum(t.prefill_tokens for t in ctx.traced_ticks)
    ns = tr.module_ns(ctx.trace, programs.is_prefill)
    if not tokens or ns is None:
        return None
    return ns * 1e-6 / tokens * 1e3
