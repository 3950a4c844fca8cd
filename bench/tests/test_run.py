"""The command line and its last line: no result without a TPU or
without the program, and the result object's schema (a tiny cell run on
the CPU through the harness, the chip check skipped)."""
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys
import time

import jax
import pytest

from bench import harness
from bench.tests.tiny import tiny_cell

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
PEAKS = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _run(cwd, *extra, env=None):
    cmd = [sys.executable, "bench/run.py", "--workload",
           SPEC["workloads"][0]["name"], "--seed", "3", "--seconds", "1",
           *extra]
    return subprocess.run(cmd, cwd=cwd, capture_output=True, text=True,
                          timeout=300, env=env)


def test_cpu_run_refuses_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run(ROOT, env=env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_alone_refuses(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    p = _run(tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_json_names_its_files():
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
    traffic = {w["traffic"] for w in SPEC["workloads"]}
    for w in SPEC["workloads"]:
        assert (BENCH / "limits" / f"{w['name']}.json").is_file()
    for t in traffic:
        mix = json.loads((BENCH / "traffic" / f"{t}.json").read_text())
        assert (BENCH / "generators" / f"{mix['generator']}.py").is_file()
    for m in SPEC["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    names += [w["name"] for w in SPEC["workloads"]]
    names += [c["name"] for c in SPEC["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)


@pytest.mark.parametrize("trace", [0, 1])
def test_last_line_schema(trace):
    cell = tiny_cell(limit=1.0)
    out = harness.run_cell(cell, 2**31 + 99, 3.0, bool(trace),
                           time.monotonic(), jax.devices()[0],
                           harness.CompileWatch(), PEAKS)
    json.loads(json.dumps(out))
    keys = list(out)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics",
                        "device"]
    assert keys[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    for name, chk in out["checks"].items():
        assert set(chk) >= {"value", "limit"}
    dev = out["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    assert all(set(v) == {"value", "unit"} for v in out["metrics"].values())
    want = {m["name"]: m["unit"] for m in SPEC["end_to_end"]}
    if trace:
        assert {"busy_s", "window_s"} <= set(dev)
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
        # the CPU has no device plane: every device-trace metric is left
        # out, never reported as 0
        device = {m["name"] for m in SPEC["per_layer"]
                  if m["source"] == "device_trace"}
        assert not device & set(out["metrics"])
        assert set(out["metrics"]) <= {m["name"] for m in SPEC["per_layer"]}
    else:
        assert set(out["metrics"]) == set(want)
        assert out["setup"]["window_compiles"] == 0
    for k, v in out["metrics"].items():
        assert v["unit"] == ({**want, **{m["name"]: m["unit"]
                                         for m in SPEC["per_layer"]}})[k]
