"""Pallas kernel `fused_dequant_mm`: the least time of its calls in the
traced window over their summed device time. A call's least time is the
larger of 2*M*K*N over the int8 peak and its bytes (stored weight,
scales, f32 input and output) over the HBM bandwidth (`bench/flops.py`),
with the M the work needs: the rows active at that decode step, and the
valid prompt tokens of a prefill dispatch, never the padded slots.
The calls are counted from the ticks: the block projections one step
makes (the configuration's reference module, `projection_calls`), once
per decode step and per prefill dispatch; where the trace holds another
number of kernel events the count is wrong and nothing is read. Moves
tpot_p90_ms."""
from bench import flops, harness, programs
from bench import trace as tr


def least_seconds(ctx):
    """(least seconds, expected kernel events) of the traced ticks."""
    c = ctx.config
    peak, bw = ctx.peaks["int8_ops"], ctx.peaks["hbm_bytes_per_s"]
    projections = harness.reference_module(c).projection_calls(c)
    rows = [m for t in ctx.traced_ticks for m in t.decode_rows]
    rows += [t.prefill_tokens for t in ctx.traced_ticks if t.prefill_calls]
    calls = sum(t.decode_steps + t.prefill_calls for t in ctx.traced_ticks)
    total = sum(
        n_calls * flops.mm_least_seconds(m, k, n, ctx.weight_bits,
                                         peak, bw)[0]
        for m in rows if m for k, n, n_calls in projections)
    return total, calls * sum(n_calls for _, _, n_calls in projections)


def read(ctx):
    if ctx.trace is None:
        return None
    ns = sum(tr.time_by_name(ctx.trace["ops"], programs.is_fused_mm).values())
    seen = tr.count(ctx.trace["ops"], programs.is_fused_mm)
    least, events = least_seconds(ctx)
    if not ns or seen != events:
        return None
    return 100.0 * least / (ns * 1e-9)
