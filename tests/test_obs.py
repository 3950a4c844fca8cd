"""Observability subsystem coverage: the span tracer (deterministic
under an injected clock, free when disabled, Chrome-trace-valid on
export), the typed metrics registry (dict-compatible counters view —
the engine's ``metrics()["counters"]`` bit-compat contract), rolling
gauges, and the measured ``ReplicaStats`` the router's online cost
correction consumes.
"""
import json

import numpy as np
import pytest

from repro.obs import (PERCENTILES, CountersView, MetricsRegistry,
                       ReplicaStats, RollingGauge, Tracer, percentile_block,
                       traced_jit, validate_chrome_trace)
from repro.obs.trace import REQUEST_LANE_BASE, TICK_LANE, _NULL_SPAN


class FakeClock:
    """Deterministic monotonic clock: +0.5s per read."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        self.t += 0.5
        return self.t


def _record_session(tracer):
    with tracer.span("admission"):
        pass
    tracer.req_begin(7, "queued", args={"prompt_len": 3})
    with tracer.span("block_dispatch", args={"n": 4}):
        pass
    tracer.req_end(7, "queued")
    tracer.req_instant(7, "first_token")
    tracer.instant("tick_done")


# --------------------------------------------------------------- tracer

class TestTracer:
    def test_deterministic_under_injected_clock(self):
        runs = []
        for _ in range(2):
            tr = Tracer(clock=FakeClock(), enabled=True)
            _record_session(tr)
            runs.append(json.dumps(tr.to_chrome(), sort_keys=True))
        assert runs[0] == runs[1]

    def test_disabled_tracer_is_a_noop(self):
        tr = Tracer(clock=FakeClock(), enabled=False)
        assert tr.span("x") is _NULL_SPAN
        _record_session(tr)
        assert tr.events == [] and tr.dropped == 0

    def test_complete_span_timestamps_microseconds(self):
        tr = Tracer(clock=FakeClock(), enabled=True)
        with tr.span("phase"):      # enter reads 0.5s, exit reads 1.0s
            pass
        ev = [e for e in tr.events if e["ph"] == "X"][0]
        assert ev["ts"] == pytest.approx(0.5e6)
        assert ev["dur"] == pytest.approx(0.5e6)
        assert ev["tid"] == TICK_LANE

    def test_request_lanes_and_metadata(self):
        tr = Tracer(clock=FakeClock(), enabled=True)
        tr.req_begin(3, "queued")
        tr.req_end(3, "queued")
        lane = tr.request_lane(3)
        assert lane == REQUEST_LANE_BASE + 3
        names = [e for e in tr.events if e["ph"] == "M"
                 and e["name"] == "thread_name"]
        assert any(e["tid"] == lane and e["args"]["name"] == "req 3"
                   for e in names)
        b = [e for e in tr.events if e["ph"] == "B"][0]
        e = [e for e in tr.events if e["ph"] == "E"][0]
        assert b["tid"] == e["tid"] == lane and b["ts"] <= e["ts"]

    def test_dump_validate_round_trip(self, tmp_path):
        tr = Tracer(clock=FakeClock(), enabled=True)
        _record_session(tr)
        path = tr.dump(str(tmp_path / "t.json"))
        with open(path) as f:
            data = json.load(f)
        assert validate_chrome_trace(data) == []
        assert data["traceEvents"]

    def test_max_events_cap_counts_drops(self):
        tr = Tracer(clock=FakeClock(), enabled=True, max_events=5)
        for _ in range(10):
            tr.instant("x")
        assert len(tr.events) == 5
        assert tr.dropped == 7          # 2 metadata events + 3 instants fit
        out = tr.to_chrome()["traceEvents"]
        assert "dropped" in out[-1]["name"]
        assert validate_chrome_trace(out) == []


class _Recorder:
    """Fake ``annotate`` sink: logs each enter and exit in order."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        log = self.log

        class _Note:
            def __enter__(self):
                log.append(("enter", name))

            def __exit__(self, *exc):
                log.append(("exit", name))

        return _Note()


class TestAnnotateSink:
    def test_spans_enter_process_dot_name_nested_in_order(self):
        rec = _Recorder()
        tr = Tracer(clock=FakeClock(), enabled=True, annotate=rec)
        with tr.span("admission"):
            pass
        with tr.span("block_dispatch"):
            with tr.span("host_sync"):
                pass
        assert rec.log == [
            ("enter", "engine.admission"), ("exit", "engine.admission"),
            ("enter", "engine.block_dispatch"),
            ("enter", "engine.host_sync"), ("exit", "engine.host_sync"),
            ("exit", "engine.block_dispatch")]
        # the Chrome-JSON spans are recorded as before
        xs = [e["name"] for e in tr.events if e["ph"] == "X"]
        assert xs == ["admission", "host_sync", "block_dispatch"]

    def test_process_name_prefixes_annotation(self):
        rec = _Recorder()
        tr = Tracer(clock=FakeClock(), enabled=True, process="replica3",
                    annotate=rec)
        with tr.span("harvest"):
            pass
        assert rec.log[0] == ("enter", "replica3.harvest")

    def test_only_spans_annotate(self):
        rec = _Recorder()
        tr = Tracer(clock=FakeClock(), enabled=True, annotate=rec)
        tr.complete("x", 0.0, 1.0)
        tr.req_begin(1, "queued")
        tr.req_end(1, "queued")
        tr.instant("tick_done")
        assert rec.log == []

    def test_disabled_tracer_never_calls_annotate(self):
        rec = _Recorder()
        tr = Tracer(clock=FakeClock(), enabled=False, annotate=rec)
        assert tr.span("admission") is _NULL_SPAN
        _record_session(tr)
        assert rec.log == [] and tr.events == []

    def test_annotation_closes_when_the_span_raises(self):
        rec = _Recorder()
        tr = Tracer(clock=FakeClock(), enabled=True, annotate=rec)
        with pytest.raises(ValueError):
            with tr.span("harvest"):
                raise ValueError("boom")
        assert rec.log == [("enter", "engine.harvest"),
                           ("exit", "engine.harvest")]
        assert [e["name"] for e in tr.events if e["ph"] == "X"] == \
            ["harvest"]


class TestTracedJit:
    def test_compile_span_once_per_signature(self):
        import jax
        import jax.numpy as jnp

        tr = Tracer(clock=FakeClock(), enabled=True)
        fn = traced_jit(jax.jit(lambda x: x + 1), "add", tr)
        fn(jnp.zeros(2))                # compiles
        fn(jnp.zeros(2))                # cached
        spans = [e for e in tr.events
                 if e["name"] == "compile:add" and e["ph"] == "X"]
        assert len(spans) == 1 and spans[0]["cat"] == "compile"
        fn(jnp.zeros(3))                # new shape: compiles again
        spans = [e for e in tr.events if e["name"] == "compile:add"]
        assert len(spans) == 2

    def test_disabled_returns_raw_callable(self):
        tr = Tracer(enabled=False)
        fn = object()
        assert traced_jit(fn, "x", tr) is fn
        assert traced_jit(fn, "x", None) is fn


class TestValidateChromeTrace:
    def test_accepts_object_and_bare_list(self):
        ev = {"name": "a", "ph": "i", "ts": 0, "pid": 1, "tid": 0}
        assert validate_chrome_trace({"traceEvents": [ev]}) == []
        assert validate_chrome_trace([ev]) == []

    def test_rejects_malformed(self):
        assert validate_chrome_trace(42)
        assert validate_chrome_trace({"nope": []})
        assert validate_chrome_trace([{"ph": "i"}])                # no name
        assert validate_chrome_trace(
            [{"name": "a", "ph": "Z", "ts": 0, "pid": 1, "tid": 0}])
        # X span without a dur
        assert validate_chrome_trace(
            [{"name": "a", "ph": "X", "ts": 0, "pid": 1, "tid": 0}])
        assert validate_chrome_trace(
            [{"name": "a", "ph": "i", "ts": "late", "pid": 1, "tid": 0}])


# ------------------------------------------------------------- registry

class TestMetricsRegistry:
    def test_counters_view_is_dict_compatible(self):
        reg = MetricsRegistry()
        view = reg.counters_view()
        view["ticks"] = 0
        view["ticks"] += 3
        view["steps"] = 2
        assert view["ticks"] == 3
        assert dict(view) == {"ticks": 3, "steps": 2}
        assert view == {"ticks": 3, "steps": 2}
        assert {"ticks": 3, "steps": 2} == view
        assert view != {"ticks": 4, "steps": 2}
        assert list(view) == ["ticks", "steps"]   # creation order
        assert len(view) == 2 and "ticks" in view
        assert repr(view) == repr({"ticks": 3, "steps": 2})
        other = MetricsRegistry().counters_view()
        other["ticks"], other["steps"] = 3, 2
        assert view == other
        del view["steps"]
        assert dict(view) == {"ticks": 3}
        # the view writes through to the typed instrument
        assert reg.counter("ticks").value == 3

    def test_percentile_block_schema(self):
        assert percentile_block([]) == {}
        assert percentile_block([None, None]) == {}
        block = percentile_block([1.0, None, 3.0])
        assert set(block) == {f"p{p}" for p in PERCENTILES} | \
            {"mean", "max"}
        assert block["mean"] == pytest.approx(2.0)
        assert block["max"] == pytest.approx(3.0)

    def test_histogram_matches_serving_percentiles(self):
        from repro.serving.metrics import percentiles
        reg = MetricsRegistry()
        h = reg.histogram("lat")
        xs = list(np.random.default_rng(0).uniform(0, 1, 50))
        for x in xs:
            h.observe(x)
        assert h.summary() == percentiles(xs)

    def test_rolling_gauge_window_and_rate(self):
        g = RollingGauge("tok", window=4)
        assert g.last is None and g.mean() is None and g.rate() is None
        for t in range(8):                    # 1 tok per 1s tick
            g.observe(float(t), 1.0)
        assert len(g) == 4                    # window bounds the deque
        assert g.last == 1.0 and g.mean() == pytest.approx(1.0)
        assert g.rate() == pytest.approx(1.0)  # 3 tokens over 3 seconds
        snap = g.snapshot()
        assert set(snap) == {"last", "mean", "rate", "n"}
        same_t = RollingGauge("x", window=4)
        same_t.observe(1.0, 5.0)
        same_t.observe(1.0, 5.0)              # zero time span
        assert same_t.rate() is None

    def test_snapshot_schema(self):
        reg = MetricsRegistry()
        reg.counter("c").inc(2)
        reg.gauge("g").set(1.5)
        reg.histogram("h").observe(1.0)
        reg.rolling("r").observe(0.0, 1.0)
        snap = reg.snapshot()
        assert snap["counters"] == {"c": 2}
        assert snap["gauges"] == {"g": 1.5}
        assert set(snap["histograms"]["h"]) >= {"p50", "mean", "max"}
        assert snap["rolling"]["r"]["last"] == 1.0


# ---------------------------------------------------------- replica stats

class TestReplicaStats:
    def test_ewma_over_per_tick_rates(self):
        st = ReplicaStats(alpha=0.5)
        assert not st.measured
        st.on_tick(0.0, 0, 0)            # first sample: no dt yet
        st.on_tick(1.0, 10, 0, active_slots=1)   # 10 tok/s
        assert st.tok_per_s == pytest.approx(10.0)
        st.on_tick(2.0, 20, 0, active_slots=1)   # 20 tok/s
        assert st.tok_per_s == pytest.approx(15.0)   # 0.5*20 + 0.5*10
        assert st.measured and st.ticks == 3

    def test_idle_and_zero_dt_ticks_excluded(self):
        st = ReplicaStats(alpha=0.5)
        st.on_tick(0.0, 0, 0)
        st.on_tick(1.0, 10, 0, active_slots=1)
        st.on_tick(2.0, 0, 0, active_slots=0)    # idle: no signal
        assert st.tok_per_s == pytest.approx(10.0)
        st.on_tick(2.0, 50, 0, active_slots=1)   # dt == 0: guarded
        assert st.tok_per_s == pytest.approx(10.0)

    def test_ttft_window_and_p95(self):
        st = ReplicaStats(window=8)
        assert st.p95_ttft_s is None
        for i in range(20):
            st.observe_ttft(float(i))
        # only the last 8 samples (12..19) survive the window
        assert st.p95_ttft_s == pytest.approx(
            float(np.percentile(np.arange(12, 20), 95)))
        assert st.snapshot()["ttft_samples"] == 8

    def test_snapshot_schema_and_validation(self):
        with pytest.raises(ValueError, match="alpha"):
            ReplicaStats(alpha=0.0)
        st = ReplicaStats()
        snap = st.snapshot()
        assert set(snap) == {"tok_per_s", "queue_depth", "active_slots",
                             "p95_ttft_s", "ttft_samples", "ticks",
                             "transported"}
        assert snap["tok_per_s"] is None
