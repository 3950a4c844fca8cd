"""Engine tick: host time per tick outside the engine's `host_sync` span
(admission, packing, dispatch, harvest), over the window's untraced
part. Moves tpot_p90_ms."""


def read(ctx):
    ticks = ctx.untraced_ticks
    if not ticks or ctx.host_sync_s is None:
        return None
    total = sum(t.stop - t.start for t in ticks)
    return (total - ctx.host_sync_s) / len(ticks) * 1e3
