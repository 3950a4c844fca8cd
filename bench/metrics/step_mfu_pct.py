"""Whole model step: the operations the traced ticks needed (valid
prompt tokens and active decode rows, attention over the keys each
query sees, counted by the configuration's reference module) over the
device time of the served programs, over the chip's int8 peak: the
narrowest arithmetic the int tiers may use (v5e lists no int4 peak), so
the share stays under 100% once the kernels feed int8 to the MXU.
Moves tpot_p90_ms."""
from bench import programs
from bench import trace as tr


def read(ctx):
    if ctx.trace is None:
        return None
    ops = sum(t.ops for t in ctx.traced_ticks)
    ns = tr.module_ns(ctx.trace, lambda m: programs.is_decode(m)
                      or programs.is_prefill(m))
    if not ops or not ns:
        return None
    return 100.0 * ops / (ns * 1e-9) / ctx.peaks["int8_ops"]
