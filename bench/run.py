"""Run one cell of the benchmark on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or with
`--trace 1` its per-layer metrics), `device`, with `--trace 1` a
`breakdown`, then `setup` and, last, `checks`: each number compared with
the reference beside its limit, which also close standard error. Exits
non-zero, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for, or where a file the cell needs is missing.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
# JAX's persistent compilation cache: a fixed directory inside the
# checkout, so that only a checkout's first run of a cell compiles
CACHE_DIR = ROOT / "bench" / ".out" / "jax_cache"


def _configure_jax():
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # the serving programs compile in under JAX's default 1 s threshold,
    # which would leave them out of the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program at {ROOT / 'src' / 'repro'}",
              file=sys.stderr)
        return 1
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    try:
        cell = harness.load_cell(args.workload)
        peaks_all = harness._read_json(ROOT / "bench" / "peaks.json")
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for var in ("REPRO_KERNEL_INTERPRET", "REPRO_FUSED_BACKEND"):
        if os.environ.get(var):
            print(f"bench: refusing to run with {var} set: it would take "
                  f"the kernels off the chip", file=sys.stderr)
            return 1
    jax = _configure_jax()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"bench: no TPU: JAX's first device is {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 1
    if dev.device_kind not in peaks_all:
        print(f"bench: no peaks for device kind {dev.device_kind!r} in "
              f"bench/peaks.json", file=sys.stderr)
        return 1
    watch = harness.CompileWatch()
    harness.log(f"device: platform={dev.platform} kind={dev.device_kind} "
                f"count={len(devices)}; jax {jax.__version__}")
    try:
        out = harness.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), T_PROCESS, dev, watch,
                               peaks_all[dev.device_kind])
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(f"correct: {out['correct']}", file=sys.stderr)
    for name, chk in out["checks"].items():
        rel = ">=" if chk.get("at_least") else "<="
        print(f"check {name}: {chk['value']!r} (limit {rel} "
              f"{chk['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
