"""Find an open-loop cell's knee on the chip: the highest arrival rate
the engine sustains without its backlog growing over the window.

    python3 bench/sweep.py --workload <cell> --rates 2,4,6,8 --seconds 20

One process builds the cell's engine once (weights from --seed), warms
it, and drives the cell's traffic at each rate in turn, letting the
engine drain between rates. For each rate it prints one JSON line: the
requests sent and finished, the backlog (sent but unfinished) at half
and at the end of the window, the requests still waiting for a slot at
the end, tokens per second and the p90 TTFT, and whether the rate was
sustained: no request left waiting for a slot at the end, and a backlog
at the end no more than 1.5 times (plus 2) the backlog at half time.
It stops after two rates in a row that were not sustained. The knee,
the highest rate sustained with every lower rate, is written into the
traffic file as a number; the benchmark's runs never search for a rate.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import ROOT, _configure_jax  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    cell = harness.load_cell(args.workload)
    jax = _configure_jax()
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 1
    engine = harness.build_engine(cell, args.seed, trace=False)
    harness.warm_up(engine)
    gen = harness.generator(cell.traffic)
    misses = 0
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = copy.deepcopy(cell.traffic)
        traffic["arrivals"]["rate_per_s"] = rate
        plan = gen.build(traffic, args.seed, args.seconds,
                         cell.config["vocab_size"])
        win = harness.drive(engine, plan, cell.config, args.seconds)
        mid = win.start + args.seconds / 2
        half = sum(t.sent <= mid for t in win.tracks) - sum(
            t.req.done and t.last is not None and t.last <= mid
            for t in win.tracks)
        done = sum(t.req.done for t in win.tracks)
        e2e = harness.end_to_end(win, 0.0)
        backlog_end = len(win.tracks) - done
        queue_end = len(engine.scheduler)
        sustained = queue_end == 0 and backlog_end <= 1.5 * half + 2
        misses = 0 if sustained else misses + 1
        print(json.dumps({
            "rate_per_s": rate, "sent": len(win.tracks), "finished": done,
            "backlog_half": half, "backlog_end": backlog_end,
            "queue_end": queue_end, "sustained": sustained,
            "output_tokens_per_s": e2e["output_tokens_per_s"],
            "ttft_p90_ms": e2e["ttft_p90_ms"],
            "tpot_p90_ms": e2e["tpot_p90_ms"]}), flush=True)
        if misses == 2:
            break
        engine.run_until_drained(max_ticks=1_000_000)
    return 0


if __name__ == "__main__":
    sys.exit(main())
