"""Engine scheduler: p90 of the wait for a slot (admit - submit, the
engine's own stamps) over the requests submitted in the window's
untraced part; a request not admitted by the window's end counts with
its wait so far. Moves ttft_p90_ms."""
from bench.harness import percentile


def read(ctx):
    win = ctx.window
    waits = []
    for t in win.tracks:
        r = t.req
        if r.submit_time is None or r.submit_time >= win.trace_start:
            continue
        admit = r.admit_time
        end = admit if admit is not None and admit <= win.stop \
            else win.stop
        waits.append((end - r.submit_time) * 1e3)
    return percentile(waits, 90)
