"""Engine prefill: p90 of the time from a slot's admission to the end
of its prompt's prefill (prefill_done - admit, the engine's own stamps)
over the admitted requests submitted in the window's untraced part; a
prefill not done by the window's end counts with its time so far.
Moves ttft_p90_ms. A program without the prefill stamp reads nothing."""
from bench.harness import percentile


def read(ctx):
    win = ctx.window
    if not any(hasattr(t.req, "prefill_done_time") for t in win.tracks):
        return None
    walls = []
    for t in win.tracks:
        r = t.req
        if (r.submit_time is None or r.submit_time >= win.trace_start
                or r.admit_time is None):
            continue
        done = r.prefill_done_time
        if done is None and r.done:
            continue            # finished at admission: nothing to prefill
        end = done if done is not None and done <= win.stop else win.stop
        walls.append((end - r.admit_time) * 1e3)
    return percentile(walls, 90)
