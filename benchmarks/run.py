"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per benchmark and writes
JSON artifacts to results/bench/ (consumed by EXPERIMENTS.md and
renderable with ``tools/roofline_table.py --bench``).

Figure/table sweeps run through the ``repro.exp`` engine: pass
``--jobs N`` to fan points out over worker processes and re-run with a
warm cache to skip every already-simulated point (``--no-cache`` to
force re-simulation).
"""
import argparse
import inspect
import sys
import time

from repro import exp
from repro.runtime.compile_cache import use_compile_cache


def main(argv=None) -> None:
    from benchmarks import (autotune_bench, fig3_error, fig7_breakdown,
                            fig8_perf, fig9_expdiff, fig10_tradeoff,
                            kernel_bench, serve_bench, table1)
    ap = argparse.ArgumentParser(description=__doc__)
    exp.add_cli_args(ap)
    ap.add_argument("--only", default=None, metavar="NAME",
                    help="run a single benchmark module (e.g. fig8_perf)")
    args = ap.parse_args(argv)
    use_compile_cache()
    engine = exp.EngineConfig.from_args(args)

    mods = (table1, fig7_breakdown, fig9_expdiff, fig8_perf,
            fig10_tradeoff, fig3_error, autotune_bench, kernel_bench,
            serve_bench)
    if args.only:
        mods = [m for m in mods if m.__name__.split(".")[-1] == args.only]
        if not mods:
            sys.exit(f"unknown benchmark {args.only!r}")
    t0 = time.time()
    print("name,us_per_call,derived")
    for mod in mods:
        name = mod.__name__.split(".")[-1]
        print(f"# --- {name} ---", flush=True)
        # wall-time benches (kernel/serve) don't sweep and take no engine
        if "engine" in inspect.signature(mod.run).parameters:
            mod.run(engine=engine)
        else:
            mod.run()
    print(f"# engine {engine.total.summary()}")
    print(f"# all benchmarks done in {time.time() - t0:.1f}s")


if __name__ == '__main__':
    main()
