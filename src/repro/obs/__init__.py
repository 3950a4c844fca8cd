"""Serving telemetry: span tracing, typed metrics, measured replica stats.

The measurement substrate the serving stack (and every fleet-level
ROADMAP item) consumes, mirroring the paper's own method — replace
worst-case assumptions with *observed* distributions. Three small
pieces, all dependency-free (numpy only) and clock-injectable so tests
are deterministic:

* :mod:`repro.obs.trace` — :class:`Tracer`: explicit-clock spans
  (request lifecycle, per-tick engine phases, JAX compile events)
  exported as Chrome trace-event JSON loadable in Perfetto
  (https://ui.perfetto.dev). An injected ``annotate`` sink (the engine
  passes ``jax.profiler.TraceAnnotation``) repeats each tick-phase span
  as ``engine.<phase>`` on a profiler capture's host plane, on the
  device trace's clock. ``traced_jit`` wraps a jitted callable so each
  compilation surfaces as a ``compile`` span.
* :mod:`repro.obs.registry` — :class:`MetricsRegistry`: typed
  counters/gauges/histograms plus rolling-window gauges (the engine
  samples none). The registry's counters back the engine's
  ``metrics()["counters"]`` dict bit-compatibly through
  :class:`CountersView`. This module also owns the CANONICAL
  percentile-block schema (``PERCENTILES`` + ``percentile_block``)
  that ``repro.serving.metrics`` re-exports.
* :mod:`repro.obs.stats` — :class:`ReplicaStats`: the per-replica
  measured view (EWMA tok/s, queue depth, sliding-window p95 TTFT)
  each engine publishes and the router's online cost correction
  consumes.
"""
from repro.obs.registry import (PERCENTILES, Counter,       # noqa: F401
                                CountersView, Gauge, Histogram,
                                MetricsRegistry, RollingGauge,
                                percentile_block)
from repro.obs.stats import ReplicaStats                     # noqa: F401
from repro.obs.trace import (Tracer, traced_jit,             # noqa: F401
                             validate_chrome_trace)
