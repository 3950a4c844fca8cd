"""Pallas kernel `fused_dequant_mm`: its share of the device's busy time
in the traced window. Moves tpot_p90_ms."""
from bench import programs
from bench import trace as tr


def read(ctx):
    if ctx.trace is None:
        return None
    busy = tr.busy_ns(ctx.trace["ops"])
    ns = sum(tr.time_by_name(ctx.trace["ops"], programs.is_fused_mm).values())
    if not busy or not ns:
        return None
    return 100.0 * ns / busy
