"""From a JAX profiler trace to the numbers the per-layer readers use.

`load` reads the `.xplane.pb` that `jax.profiler` writes and keeps a
compact record: the device's operations ("XLA Ops"), its programs ("XLA
Modules") and the benchmark's own host annotations, each as
(name, start ns, duration ns). The reductions below work on that record
only, so they are checked on a small recorded trace
(`bench/tests/data/`) without a chip.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, float, float]            # (name, start ns, duration ns)

# the benchmark's own host annotations (jax.profiler.TraceAnnotation)
HOST_NAMES = ("engine.step", "gen.wait", "gen.submit")
# control-flow ops span the ops of their bodies: left out of op times
CONTROL_OPS = ("while", "conditional", "call")


def op_name(text: str) -> str:
    """A TPU trace names each op by its HLO text ('%fusion.3 = f32[...]
    fusion(...)'): keep the instruction's name ('fusion.3')."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(log_dir: str) -> Dict:
    """{'ops': [...], 'modules': [...], 'host': [...], 'devices': n}
    from the newest `.xplane.pb` under ``log_dir``; events of every
    device plane are pooled (the cells run on one chip), and ``devices``
    counts the planes that ran an op."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(files[-1])
    out: Dict = {"ops": [], "modules": [], "host": [], "devices": 0}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            n_ops = len(out["ops"])
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    out[key].extend((op_name(e.name), float(e.start_ns),
                                     float(e.duration_ns))
                                    for e in line.events)
            # a chip's trace also holds device planes that run no op
            out["devices"] += len(out["ops"]) > n_ops
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out["host"].extend((e.name, float(e.start_ns),
                                    float(e.duration_ns))
                                   for e in line.events
                                   if e.name in HOST_NAMES)
    for key in ("ops", "modules", "host"):
        out[key].sort(key=lambda e: e[1])
    return out


def union(events: List[Event]) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals covered by the events."""
    spans: List[List[float]] = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        e = s + d
        if spans and s <= spans[-1][1]:
            spans[-1][1] = max(spans[-1][1], e)
        else:
            spans.append([s, e])
    return [(s, e) for s, e in spans]


def busy_ns(events: List[Event]) -> float:
    return sum(e - s for s, e in union(events))


def time_by_name(events: List[Event], match=None) -> Dict[str, float]:
    """Summed duration (ns) per event name (names filtered by ``match``)."""
    out: Dict[str, float] = {}
    for name, _, d in events:
        if match is None or match(name):
            out[name] = out.get(name, 0.0) + d
    return out


def count(events: List[Event], match) -> int:
    return sum(1 for name, _, _ in events if match(name))


def idle_gaps(rec: Dict, top: int = 10) -> List[Tuple[str, float]]:
    """The longest stretches with no device op, each named by the host
    annotation that covers its midpoint ('host:none' where none does),
    longest first; seconds."""
    busy = union(rec["ops"])
    gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        mid = (s + e) / 2
        inside = [(d, n) for n, hs, d in rec["host"] if hs <= mid <= hs + d]
        name = min(inside)[1] if inside else "none"
        out.append((f"host:{name}", (e - s) * 1e-9))
    return out


def is_control(name: str) -> bool:
    return name.split(".", 1)[0] in CONTROL_OPS


def top_ops(rec: Dict, top: int = 10) -> List[Tuple[str, float]]:
    """Device ops by summed time, most first, control flow left out;
    seconds. Numbered instances of one kind ('fusion.3', 'fusion.7')
    are summed under their instruction name."""
    t: Dict[str, float] = {}
    for name, d in time_by_name(rec["ops"],
                                lambda n: not is_control(n)).items():
        kind = re.sub(r"\.\d+$", "", name)
        t[kind] = t.get(kind, 0.0) + d
    return [(n, v * 1e-9) for n, v in
            sorted(t.items(), key=lambda kv: -kv[1])[:top]]


def module_ns(rec: Dict, match) -> Optional[float]:
    """Summed device time of the programs whose name ``match`` accepts;
    None where none ran."""
    t = [d for n, _, d in rec["modules"] if match(n)]
    return sum(t) if t else None
