"""The program's own boundaries as the benchmark sees them: the two
readers of the engine's request stamps (`queue_wait_p90_ms`,
`prefill_wall_p90_ms`) on hand-made windows with known answers and on a
tiny cell driven through the harness; the named scopes in the served
programs' HLO; and the engine's phases on a CPU profile's host plane,
inside the harness's `engine.step`."""
import glob
import math
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, programs
from bench.tests.tiny import tiny_cell
from repro.serving import Request

NAMES = ["queue_wait_p90_ms", "prefill_wall_p90_ms"]
PHASES = {"engine.admission", "engine.prefill_dispatch",
          "engine.block_dispatch", "engine.host_sync", "engine.harvest"}


def _req(rid, submit, admit=None, prefill_done=None, done=False):
    r = Request(rid=rid, prompt=np.arange(1, 9, dtype=np.int32))
    r.submit_time, r.admit_time = submit, admit
    r.prefill_done_time, r.done = prefill_done, done
    return r


def _ctx(reqs, stop=10.0, trace_start=8.0):
    tracks = [harness.Track(req=r, due=r.submit_time, sent=r.submit_time)
              for r in reqs]
    win = harness.Window(start=0.0, stop=stop, tracks=tracks,
                         unsent_due=[], ticks=[], failed=0,
                         trace_start=trace_start)
    return harness.Context(config={}, slots=4, weight_bits=8, peaks={},
                           window=win, host_sync_s=None, trace=None,
                           memory_peak_bytes=None)


def _read(ctx):
    return harness.read_metrics(NAMES, ctx)


# ------------------------------------------------------- hand-made windows

def test_readers_on_a_hand_made_window():
    ctx = _ctx([
        _req(0, 1.0, 1.5, 2.5),          # wait 500, prefill 1000
        _req(1, 2.0, 2.0, 2.2),          # wait 0, prefill 200
        _req(2, 3.0, 6.0),               # prefill not done by stop: 4000
        _req(3, 5.0),                    # never admitted: waits 5000
        _req(4, 9.0, 9.0, 9.5),          # submitted in the traced part
        _req(5, 4.0, 4.0, None, True),   # finished at admission
    ])
    out = _read(ctx)
    assert out["queue_wait_p90_ms"] == pytest.approx(
        np.percentile([500, 0, 3000, 5000, 0], 90))
    assert out["queue_wait_p90_ms"] == pytest.approx(4200.0)
    assert out["prefill_wall_p90_ms"] == pytest.approx(
        np.percentile([1000, 200, 4000], 90))
    assert out["prefill_wall_p90_ms"] == pytest.approx(3400.0)


def test_a_stall_stays_in_the_tail():
    """One request never admitted by the window's end: its wait so far
    sets the p90 of a window that is otherwise served at once."""
    reqs = [_req(i, 1.0 + 0.1 * i, 1.0 + 0.1 * i, 1.5 + 0.1 * i)
            for i in range(9)]
    reqs.append(_req(9, 2.0))
    out = _read(_ctx(reqs))
    assert out["queue_wait_p90_ms"] == pytest.approx(0.1 * 8000.0)
    assert out["prefill_wall_p90_ms"] == pytest.approx(500.0)


def test_empty_window_reads_nothing():
    assert _read(_ctx([])) == {}
    # everything submitted in the traced part
    assert _read(_ctx([_req(0, 9.0, 9.0, 9.1)])) == {}


def test_a_program_without_the_prefill_stamp():
    """The parent of the stamp: requests carry submit and admit times
    only. The queue wait still reads; the prefill reader reads nothing
    and does not raise."""
    old = [types.SimpleNamespace(submit_time=1.0, admit_time=1.25,
                                 done=False)]
    ctx = _ctx([])
    ctx.window.tracks = [harness.Track(req=r, due=1.0, sent=1.0)
                         for r in old]
    assert _read(ctx) == {"queue_wait_p90_ms": pytest.approx(250.0)}


# ------------------------------------------------------- the tiny cell

@pytest.fixture(scope="module")
def cell():
    return tiny_cell()


@pytest.fixture(scope="module")
def traced_engine(cell):
    eng = harness.build_engine(cell, 2**31 + 7, trace=True)
    harness.warm_up(eng)
    return eng


def test_drive_reads_both_metrics(cell, traced_engine):
    eng = traced_engine
    plan = harness.generator(cell.traffic).build(
        cell.traffic, 2**31 + 11, 2.0, cell.config["vocab_size"])
    win = harness.drive(eng, plan, cell.config, 2.0)
    assert win.tracks and math.isinf(win.trace_start)
    for t in win.tracks:
        r = t.req
        if r.first_token_time is not None:
            assert (r.submit_time <= r.admit_time <= r.prefill_done_time
                    <= r.first_token_time <= win.stop)
    ctx = harness.Context(config=cell.config, slots=eng.b, weight_bits=8,
                          peaks={}, window=win, host_sync_s=None,
                          trace=None, memory_peak_bytes=None)
    out = _read(ctx)
    assert set(out) == set(NAMES)
    assert all(isinstance(v, float) and v >= 0 for v in out.values())
    eng.run_until_drained()


def test_profile_shows_engine_phases_inside_the_step(traced_engine,
                                                     tmp_path):
    """One tick under the profiler on the CPU: the engine's phases lie
    inside the harness's `engine.step` on the host plane."""
    from jax.profiler import ProfileData
    eng = traced_engine
    assert not eng.has_pending()
    # a prompt of one chunk: admitted, prefilled and decoded in one tick
    eng.submit(Request(rid=10**6, prompt=np.arange(1, 11, dtype=np.int32),
                       max_new_tokens=3))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("engine.step"):
            eng.step()
        jax.block_until_ready(eng.caches)
    finally:
        jax.profiler.stop_trace()
    eng.run_until_drained()
    files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    assert files
    host = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(files[-1]).planes
            if plane.name.startswith("/host:CPU")
            for line in plane.lines for e in line.events
            if e.name.startswith("engine.")]
    steps = [(s, e) for n, s, e in host if n == "engine.step"]
    assert len(steps) == 1
    s0, e0 = steps[0]
    inside = {n for n, s, e in host if n != "engine.step"
              and s0 <= s and e <= e0}
    assert inside == PHASES


# ------------------------------------------------------- named scopes

def _op_scopes(hlo: str):
    return {part for name in re.findall(r'op_name="([^"]*)"', hlo)
            for part in name.split("/")}


def test_served_programs_carry_the_scopes(cell):
    from repro.models import registry
    from repro.serving.config import MAX_STOP_IDS
    eng = harness.build_engine(cell, 3, trace=False)
    assert eng.fused
    b = eng.b
    z = jnp.zeros((b,), jnp.int32)
    carry = registry.DecodeCarry(
        tok=z, pos=z, rem=jnp.ones((b,), jnp.int32), taken=z,
        stops=jnp.full((b, MAX_STOP_IDS), -1, jnp.int32),
        temp=jnp.zeros((b,), jnp.float32), top_k=z,
        top_p=jnp.ones((b,), jnp.float32),
        keys=jnp.zeros((b, 2), jnp.uint32))
    decode = eng._block_decode(2, False).lower(
        eng.params, carry, eng.caches).compile().as_text()
    prefill = eng._prefill_chunk_fn.lower(
        eng.params, jnp.zeros((b, eng.prefill_chunk), jnp.int32), z, z,
        eng.caches).compile().as_text()
    module = lambda hlo: hlo.split(None, 2)[1].rstrip(",")  # noqa: E731
    assert programs.is_decode(module(decode))
    assert programs.is_prefill(module(prefill))
    common = {"embed", "norm", "attn.proj", "attn.core", "attn.kv_write",
              "mlp", "mp_linear.int8"}
    assert common | {"head", "sample"} <= _op_scopes(decode)
    assert common <= _op_scopes(prefill)
