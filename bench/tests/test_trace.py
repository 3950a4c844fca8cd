"""The reduction from a trace to busy time, idle gaps, op and program
times: on hand-made events with known answers, and on a slice of a trace
recorded on a TPU v5e (`data/`)."""
import json
import pathlib

import pytest

from bench import programs
from bench import trace as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"

REC = {
    "devices": 1,
    "ops": [("a", 0.0, 10.0), ("b", 5.0, 10.0), ("c", 30.0, 5.0),
            ("a", 50.0, 2.0)],
    "modules": [("jit_run(3)", 0.0, 15.0), ("jit_wrapped(4)", 30.0, 5.0)],
    "host": [("engine.step", 0.0, 40.0), ("gen.wait", 20.0, 8.0),
             ("engine.step", 45.0, 10.0)],
}


def test_union_and_busy():
    assert tr.union(REC["ops"]) == [(0.0, 15.0), (30.0, 35.0), (50.0, 52.0)]
    assert tr.busy_ns(REC["ops"]) == 22.0


def test_idle_gaps_named_by_innermost_host_span():
    gaps = tr.idle_gaps(REC)
    # 15..30: inside engine.step and gen.wait -> gen.wait (shorter);
    # 35..50: midpoint 42.5 lies in no annotation
    assert gaps == [("host:gen.wait", pytest.approx(15e-9)),
                    ("host:none", pytest.approx(15e-9))]


def test_op_and_module_times():
    assert tr.time_by_name(REC["ops"]) == {"a": 12.0, "b": 10.0, "c": 5.0}
    assert tr.top_ops(REC, top=2) == [("a", pytest.approx(12e-9)),
                                     ("b", pytest.approx(10e-9))]
    assert tr.count(REC["ops"], lambda n: n == "a") == 2
    assert tr.module_ns(REC, programs.is_decode) == 15.0
    assert tr.module_ns(REC, programs.is_prefill) == 5.0
    assert tr.module_ns(REC, lambda n: False) is None


def test_recorded_v5e_slice():
    """12 ms of qwen2-0.5b int8 decode at 16 slots, recorded on a TPU
    v5e: ops fully inside the slice, names already shortened."""
    rec = json.loads((DATA / "v5e_decode_slice.json").read_text())
    ops = rec["ops"]
    assert len(ops) == 2235
    first = min(s for _, s, _ in ops)
    last = max(s + d for _, s, d in ops)
    busy = tr.busy_ns(ops)
    assert busy == 11_837_570.0 and busy <= last - first
    # seven fused projections a layer, 24 layers a decode step: one whole
    # step of 168 calls and part of the next
    assert tr.count(ops, programs.is_fused_mm) == 172
    assert sum(tr.time_by_name(ops, programs.is_fused_mm).values()) \
        == 3_693_843.0
    kinds = [k for k, _ in tr.top_ops(rec)]
    assert kinds[0] == "fused_dequant_mm" and "while" not in kinds
    assert [m[0].split("(")[0] for m in rec["modules"]] == ["jit_run"]
    assert programs.is_decode(rec["modules"][0][0])
    # decode runs back to back inside one engine step: no gap over 1 us
    assert all(g < 1e-6 for _, g in tr.idle_gaps(rec))
    assert all(n == "host:engine.step" for n, _ in tr.idle_gaps(rec))


def test_op_name_keeps_the_instruction():
    text = ("%fused_dequant_mm.63 = f32[128,896]{1,0} custom-call(f32[128,"
            "1024]{1,0} %pad.148), custom_call_target=\"tpu_custom_call\"")
    assert tr.op_name(text) == "fused_dequant_mm.63"
    # an op that only takes the kernel's output is not the kernel
    other = "%slice.115 = f32[16,896]{1,0} slice(%fused_dequant_mm.63)"
    assert not programs.is_fused_mm(tr.op_name(other))
