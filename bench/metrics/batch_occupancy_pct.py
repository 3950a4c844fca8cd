"""Engine scheduler: active decode slots over slots, averaged over the
decode steps of the window's untraced part. Moves output_tokens_per_s."""


def read(ctx):
    ticks = ctx.untraced_ticks
    steps = sum(t.decode_steps for t in ticks)
    if not steps:
        return None
    return 100.0 * sum(t.harvested for t in ticks) / (steps * ctx.slots)
