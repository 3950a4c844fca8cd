"""Readings that the limits in `bench/limits/<cell>.json` are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 15

For each seed, in one process: one run of the cell as the benchmark
makes it (short window, the cell's own load), then the reference over
the sampled prompts and served tokens (with the weights at the file's
`stored_weight_bits` where it states them), and the control at the same
positions: the drawn weights with every block projection quantized to
the configuration's `control_weight_bits` (the precision below the
tier's), its gaps read against the same reference.
Prints one JSON line per seed with the program's and the control's
numbers and verdicts under the cell's limits (`harness.checks`: the
program's has to be true, the control's false), and a last line with the
lower reading (the largest the program gives), the upper reading (the
smallest the control gives), their ratio and both lists of verdicts.
The benchmark's own runs never run the control.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import ROOT, _configure_jax  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    cell = harness.load_cell(args.workload)
    jax = _configure_jax()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 1
    peaks = harness._read_json(ROOT / "bench" / "peaks.json")[dev.device_kind]
    watch = harness.CompileWatch()
    bits = cell.config["control_weight_bits"]
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(cell, seed, args.seconds, False,
                               time.monotonic(), dev, watch, peaks,
                               control_bits=bits)
        # the control put in the program's place, held to the cell's
        # own limits: it has to come out not correct
        ctrl_ok, ctrl_checks = harness.checks(cell, out["control"])
        row = {"seed": seed, "program": out["program"],
               "control": out["control"], "checks": out["checks"],
               "correct": out["correct"], "control_checks": ctrl_checks,
               "control_correct": ctrl_ok}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for stat in harness.CHECK_STATS:
        lower = max(r["program"][stat] for r in rows)
        upper = min(r["control"][stat] for r in rows)
        summary[stat] = {"lower": lower, "upper": upper,
                         "ratio": upper / lower if lower else None}
    print(json.dumps({"workload": cell.name, "control_weight_bits": bits,
                      "seeds": len(rows), "readings": summary,
                      "program_correct": [r["correct"] for r in rows],
                      "control_correct": [r["control_correct"]
                                          for r in rows]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
