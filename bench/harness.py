"""One run of one cell: build the served model from the seed, warm its
shapes, drive the cell's traffic through `ServingEngine` for the window,
then compare a sample of what it served with the plain reference.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by name: `bench/configs/<config>.json` (via
BENCHMARK.json), `bench/traffic/<mix>.json`, `bench/generators/<name>.py`,
`bench/metrics/<metric>.py`, `bench/reference/<module>.py` and
`bench/limits/<cell>.json`. Adding a cell adds such files and entries in
BENCHMARK.json; no file here changes.

The harness knows no architecture. A configuration file names its
reference module by `reference` (by default its `family`), and that
module, which imports nothing of the program, gives:

- `make_params(c, seed, dtype)`: the weight tree from the seed, on the
  device, laid out as the program takes it;
- `served_gaps(params, c, prompt, served, control_bits)`: the gaps of
  the served tokens below the reference's best logit, and the control's;
- `program_fields(c)`: the program's `repro.configs.base.ModelConfig`
  fields the file states, as plain data (a nested dataclass such as
  `moe` as a dict). `model_config` lays them over the program's entry
  for `c["arch"]`. The tier and the dtype (`DEPLOYMENT_FIELDS`) are the
  file's; any other field, a size or a mechanism, that departs from the
  entry is taken only where the file's `reduced` names its key, and the
  file is refused otherwise;
- `FILE_KEYS` (optional): the file's key each such field is read from
  (`{"n_layers": "num_hidden_layers"}`, a nested field as `moe.top_k`);
  a field it does not map is named in `reduced` by its own name;
- the work a step needs: `prefill_ops(c, start, stop)`,
  `decode_ops(c, first_pos, steps)` (operations, for `step_mfu_pct`)
  and `projection_calls(c)`, the (K, N, calls) of the block projections
  one step makes (`fused_dequant_mm_roofline`).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import importlib
import json
import math
import pathlib
import shutil
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
# the profiler records the last seconds of a traced run's window
TRACE_SECONDS = 3.0
# served tokens the correctness sample aims at (the longest request
# finished in the window, then others drawn from the seed)
SAMPLE_TOKENS = 400
SAMPLE_MAX_REQUESTS = 16
# a run whose sample holds fewer served tokens proves nothing
MIN_COMPARED = 64
CHECK_STATS = ("max_gap", "mean_gap")


class BenchError(Exception):
    """The run cannot produce a result (no chip, a missing file, ...)."""


# ------------------------------------------------------------ the cell

@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    limits: dict
    end_to_end: List[str]
    per_layer: List[str]
    units: Dict[str, str]


def _read_json(path: pathlib.Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchError(f"missing {path.relative_to(ROOT)}") from None


def load_cell(workload: str, root: pathlib.Path = ROOT) -> Cell:
    spec = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r} "
                         f"(known: {sorted(cells)})")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    covers = lambda m: "workloads" not in m or workload in m["workloads"]  # noqa: E731
    return Cell(
        name=workload,
        config=_read_json(root / conf["file"]),
        traffic=_read_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        chips=int(w["chips"]),
        limits=_read_json(BENCH / "limits" / f"{workload}.json"),
        end_to_end=[m["name"] for m in spec["end_to_end"] if covers(m)],
        per_layer=[m["name"] for m in spec["per_layer"] if covers(m)],
        units={m["name"]: m["unit"]
               for m in spec["end_to_end"] + spec["per_layer"]})


# ModelConfig fields a deployment sets whatever the architecture's entry
# holds: the tier and the weights' dtype
DEPLOYMENT_FIELDS = ("precision_policy", "param_dtype")


def _lay_over(name: str, base, want, reduced, keys: dict,
              differ: List[str]):
    """``want`` (plain data) laid over the entry's value ``base``: a dict
    over a dataclass field by field (as ``name.field``); any other value
    that departs from ``base`` is taken only where ``reduced`` holds its
    key (``keys[name]``, else ``name``), and is noted in ``differ``
    otherwise."""
    if dataclasses.is_dataclass(base) and isinstance(want, dict):
        return dataclasses.replace(base, **{
            k: _lay_over(f"{name}.{k}", getattr(base, k), v, reduced, keys,
                         differ)
            for k, v in want.items()})
    if isinstance(base, tuple) and isinstance(want, list):
        want = tuple(want)
    key = keys.get(name, name)
    if want != base and key not in reduced:
        differ.append(f"{name}: program {base!r}, file {want!r} "
                      f"({key} not in reduced)")
    return want


def model_config(c: dict):
    """The program's ModelConfig for configuration file ``c``: the
    reference module's `program_fields` laid over the program's entry for
    ``c["arch"]``. Refused where a field departs from the entry and the
    file's `reduced` does not name it, where the stored weight bits are
    not the tier's, or where the vocabulary is padded."""
    from repro.configs import get_config
    base = get_config(c["arch"])
    module = reference_module(c)
    reduced = set(c.get("reduced", ()))
    keys = getattr(module, "FILE_KEYS", {})
    differ: List[str] = []
    fields = {k: v if k in DEPLOYMENT_FIELDS
              else _lay_over(k, getattr(base, k), v, reduced, keys, differ)
              for k, v in module.program_fields(c).items()}
    if differ:
        raise BenchError(f"{c['name']}: the program's {c['arch']} differs "
                         f"from the file: " + "; ".join(differ))
    stored = c.get("stored_weight_bits")
    if stored is not None and stored != weight_bits(c):
        raise BenchError(f"{c['name']}: stored_weight_bits {stored}, but "
                         f"{c['precision_policy']} stores {weight_bits(c)}")
    cfg = dataclasses.replace(base, **fields)
    if cfg.padded_vocab != cfg.vocab:
        raise BenchError(f"{c['name']}: vocab {cfg.vocab} is padded")
    return cfg


def engine_config(c: dict, trace: bool):
    """The deployment's engine settings, all from the configuration
    file: a traffic mix holds arrivals and lengths only."""
    from repro.serving import EngineConfig
    return EngineConfig(**c["engine"], trace=trace)


def reference_module(c: dict):
    """`bench/reference/<c["reference"]>.py`, by default the family's."""
    name = c.get("reference", c["family"])
    return importlib.import_module(f"bench.reference.{name}")


def generator(traffic: dict):
    return importlib.import_module(f"bench.generators.{traffic['generator']}")


# ------------------------------------------------------- compile watch

class CompileWatch:
    """Counts XLA compiles and persistent-cache hits from JAX's
    monitoring events (a program loaded from the cache counts as a hit
    and its load time as compile seconds)."""
    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0

        def on_duration(event, secs, **_):
            if event == self.COMPILE:
                self.seconds += secs
                self.compiles += 1

        def on_event(event, **_):
            if event == self.HIT:
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


# ----------------------------------------------------------- the model

def build_engine(cell: Cell, seed: int, trace: bool):
    """Weights from the seed (the reference module's generator, one
    jitted call on the device), then the engine, which prepares and
    calibrates them as a server does at start."""
    import jax
    from repro.models import registry
    from repro.serving import ServingEngine
    c = cell.config
    cfg = model_config(c)
    api = registry.build(cfg)
    params = reference_module(c).make_params(c, seed, cfg.param_dtype)
    want = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    if jax.tree.structure(want) != jax.tree.structure(params) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(params))):
        raise BenchError(f"{c['name']}: drawn weights do not match the "
                         f"program's parameter tree")
    engine = ServingEngine(cfg, api, params,
                           config=engine_config(c, trace))
    return engine


def warm_up(engine) -> None:
    """Compile every program the window uses: the prefill-chunk program
    and each decode block length 1..decode_block (greedy)."""
    from repro.serving import Request
    for n in range(1, engine.decode_block + 1):
        r = Request(rid=-n, prompt=np.array([1, 2], np.int32),
                    max_new_tokens=n)
        engine.submit(r)
        engine.run_until_drained()
        if not r.done or r.error:
            raise BenchError(f"warm-up request {n} failed: {r.error}")


# ---------------------------------------------------------- the window

@dataclasses.dataclass
class Track:
    req: object
    due: float
    sent: float
    n_tok: int = 0
    prefill_pos: int = 0
    first: Optional[float] = None
    last: Optional[float] = None


@dataclasses.dataclass
class Tick:
    start: float
    stop: float
    decode_steps: int
    harvested: int
    prefill_tokens: int
    prefill_calls: int
    ops: int
    traced: bool
    # active decode rows at each decode step of the tick
    decode_rows: Tuple[int, ...] = ()


@dataclasses.dataclass
class Window:
    start: float
    stop: float
    tracks: List[Track]
    unsent_due: List[float]
    ticks: List[Tick]
    failed: int
    trace_start: float = math.inf
    trace_stop: float = math.inf


def _no_annotation(name):
    return contextlib.nullcontext()


def drive(engine, plan, c: dict, seconds: float,
          clock: Callable[[], float] = time.monotonic,
          profile: Optional[Callable[[bool], None]] = None,
          annotate: Callable = _no_annotation) -> Window:
    """Send the plan's requests for ``seconds``: open loop at their due
    times, or closed loop with each client sending its next request as
    soon as the harness sees its previous one finish. The engine steps
    whenever it has work. ``profile(True)`` / ``profile(False)`` start
    and stop the profiler around the last TRACE_SECONDS of the window.

    Times are the harness's clock when it sees the engine's step return:
    a request's first token and each later one are stamped when the step
    that harvested them returns."""
    import jax
    from repro.serving import Request
    from repro.serving.scheduler import SchedulerFull
    work = reference_module(c)
    tracks: List[Track] = []
    inflight: List[Track] = []
    ticks: List[Tick] = []
    failed = 0
    n_plan = len(plan.prompts)
    nxt = 0
    closed = collections.deque()          # due times of closed-loop sends
    t0 = clock()
    end = t0 + seconds
    if plan.clients:
        closed.extend([t0] * plan.clients)
    trace_at = end - TRACE_SECONDS if profile else math.inf
    win = Window(start=t0, stop=t0, tracks=tracks, unsent_due=[],
                 ticks=ticks, failed=0)

    def send(due: float):
        nonlocal nxt, failed
        i = nxt % n_plan
        req = Request(rid=nxt, prompt=plan.prompts[i],
                      max_new_tokens=plan.max_new[i])
        nxt += 1
        try:
            engine.submit(req)
        except SchedulerFull:
            failed += 1
            return
        tr = Track(req=req, due=due, sent=clock())
        tracks.append(tr)
        inflight.append(tr)

    def due_now(now: float) -> Optional[float]:
        if plan.clients:
            return closed[0] if closed and closed[0] <= now else None
        if nxt < n_plan and t0 + plan.due[nxt] <= now:
            return t0 + plan.due[nxt]
        return None

    traced = False
    while True:
        now = clock()
        if now >= end:
            break
        if not traced and now >= trace_at:
            jax.block_until_ready(engine.caches)
            profile(True)
            traced = True
            win.trace_start = clock()
        with annotate("gen.submit"):
            while (due := due_now(now)) is not None:
                if plan.clients:
                    closed.popleft()
                send(due)
        if not engine.has_pending():
            wake = closed[0] if plan.clients and closed else (
                t0 + plan.due[nxt] if nxt < n_plan else end)
            with annotate("gen.wait"):
                time.sleep(max(0.0, min(wake, end) - clock()))
            continue
        c0 = dict(engine.counters)
        ta = clock()
        with annotate("engine.step"):
            engine.step()
        tb = clock()
        c1 = engine.counters
        ops = harvested = 0
        new_tokens = []
        still = []
        for tr in inflight:
            r = tr.req
            if r.prefill_pos > tr.prefill_pos:
                ops += work.prefill_ops(c, tr.prefill_pos, r.prefill_pos)
                tr.prefill_pos = r.prefill_pos
            n = r.new_tokens
            if n > tr.n_tok:
                ops += work.decode_ops(c, len(r.prompt) - 1 + tr.n_tok,
                                       n - tr.n_tok)
                harvested += n - tr.n_tok
                new_tokens.append(n - tr.n_tok)
                tr.n_tok = n
                tr.last = tb
                if tr.first is None:
                    tr.first = tb
            if r.done:
                if r.error:
                    failed += 1
                if plan.clients:
                    closed.append(tb)
            else:
                still.append(tr)
        inflight[:] = still
        steps = c1["decode_steps"] - c0["decode_steps"]
        ticks.append(Tick(
            start=ta, stop=tb,
            decode_steps=steps,
            harvested=harvested,
            prefill_tokens=c1["prefill_tokens"] - c0["prefill_tokens"],
            prefill_calls=c1["prefill_calls"] - c0["prefill_calls"],
            ops=ops, traced=traced,
            decode_rows=tuple(sum(k > j for k in new_tokens)
                              for j in range(steps))))
    win.stop = clock()
    if traced:
        jax.block_until_ready(engine.caches)
        win.trace_stop = clock()
        profile(False)
    if not plan.clients:
        while nxt < n_plan and t0 + plan.due[nxt] < win.stop:
            win.unsent_due.append(t0 + plan.due[nxt])
            nxt += 1
    win.failed = failed
    return win


def percentile(values, p: float) -> Optional[float]:
    xs = np.asarray(values, float)
    return float(np.percentile(xs, p)) if xs.size else None


def end_to_end(win: Window, setup_s: float) -> Dict[str, Optional[float]]:
    """Tokens per second over the whole window; TTFT over every request
    due in it, from its due time, a request still without a first token
    counting with its wait so far; TPOT over every request with two
    tokens or more."""
    elapsed = win.stop - win.start
    ttft = [((t.first if t.first is not None else win.stop) - t.due) * 1e3
            for t in win.tracks]
    ttft += [(win.stop - d) * 1e3 for d in win.unsent_due]
    tpot = [(t.last - t.first) / (t.n_tok - 1) * 1e3
            for t in win.tracks if t.n_tok >= 2]
    return {
        "output_tokens_per_s": sum(t.n_tok for t in win.tracks) / elapsed,
        "ttft_p90_ms": percentile(ttft, 90),
        "tpot_p90_ms": percentile(tpot, 90),
        "setup_s": setup_s,
    }


# ---------------------------------------------------------- correctness

def choose_sample(tracks: List[Track], seed: int) -> List[Track]:
    """The longest request finished in the window, then others in an
    order drawn from the seed, until SAMPLE_TOKENS served tokens."""
    done = [t for t in tracks
            if t.req.done and not t.req.error and t.n_tok >= 1]
    if not done:
        return []
    longest = max(done, key=lambda t: (len(t.req.prompt) + t.n_tok,
                                       t.req.rid))
    rest = [t for t in done if t is not longest]
    rng = np.random.default_rng([seed % 2**64, 1])
    sample, total = [longest], longest.n_tok
    for i in rng.permutation(len(rest)):
        if total >= SAMPLE_TOKENS or len(sample) >= SAMPLE_MAX_REQUESTS:
            break
        sample.append(rest[i])
        total += rest[i].n_tok
    return sample


def gap_stats(gaps: List[np.ndarray]) -> Dict[str, float]:
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    if not g.size:
        return {"compared": 0}
    return {"compared": int(g.size), "max_gap": float(g.max()),
            "mean_gap": float(g.mean())}


def compare(cell: Cell, seed: int, sample: List[Track],
            control_bits: Optional[int] = None):
    """Draw the weights again from the seed and run the reference over
    each sampled prompt with its served tokens. Returns the program's
    gap statistics and, with ``control_bits``, the control's."""
    c = cell.config
    ref = reference_module(c)
    params = ref.make_params(c, seed, c["param_dtype"])
    prog, ctrl = [], []
    for t in sample:
        r = t.req
        served = r.tokens[len(r.prompt):]
        p, q = ref.served_gaps(params, c, r.prompt, served, control_bits)
        prog.append(p)
        if q is not None:
            ctrl.append(q)
    del params
    return gap_stats(prog), (gap_stats(ctrl) if control_bits else None)


def checks(cell: Cell, stats: Dict[str, float]):
    """(correct, {name: {"value", "limit"}}): every number compared
    against its limit in the cell's limits file, and the sample's size
    against MIN_COMPARED."""
    out = {"compared_tokens": {"value": stats["compared"],
                               "limit": MIN_COMPARED, "at_least": True}}
    ok = stats["compared"] >= MIN_COMPARED
    for name, lim in cell.limits["checks"].items():
        value = stats.get(name)
        limit = lim["limit"]
        out[name] = {"value": value, "limit": limit}
        ok = ok and value is not None and limit is not None \
            and value <= limit
    return ok, out


# ------------------------------------------------------ per-layer reading

@dataclasses.dataclass
class Context:
    """What a per-layer reader (`bench/metrics/<name>.py`) reads."""
    config: dict
    slots: int
    weight_bits: int
    peaks: dict
    window: Window
    host_sync_s: Optional[float]
    trace: Optional[dict]
    memory_peak_bytes: Optional[int]

    @property
    def untraced_ticks(self) -> List[Tick]:
        return [t for t in self.window.ticks if not t.traced]

    @property
    def traced_ticks(self) -> List[Tick]:
        return [t for t in self.window.ticks if t.traced]

    @property
    def trace_window_ns(self) -> float:
        return (self.window.trace_stop - self.window.trace_start) * 1e9


def read_metrics(names: List[str], ctx: Context) -> Dict[str, float]:
    out = {}
    for name in names:
        mod = importlib.import_module(f"bench.metrics.{name}")
        value = mod.read(ctx)
        if value is not None:
            out[name] = value
    return out


def host_sync_seconds(engine, start: float, stop: float) -> float:
    """Seconds the engine spent in its `host_sync` spans between two
    clock readings (its tracer stamps microseconds on the same clock)."""
    return sum(ev["dur"] for ev in engine.tracer.events
               if ev.get("name") == "host_sync" and ev.get("ph") == "X"
               and start <= ev["ts"] * 1e-6 < stop) * 1e-6


def weight_bits(c: dict) -> int:
    return {"int8_serving": 8, "int4_serving": 4}.get(
        c["precision_policy"], 16)


# -------------------------------------------------------------- one run

TRACE_DIR = BENCH / ".out" / "trace"


def _profiler(on: bool):
    import jax
    if on:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    else:
        jax.profiler.stop_trace()


def log(msg: str):
    import sys
    print(msg, file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_process: float, device, watch: CompileWatch,
             peaks: dict, control_bits: Optional[int] = None) -> Dict:
    """One run: set-up, the window, the per-layer reading (traced runs),
    the comparison with the reference. Returns the result object."""
    import jax
    from bench import trace as tr
    c = cell.config
    engine = build_engine(cell, seed, trace)
    warm_up(engine)
    plan = generator(cell.traffic).build(cell.traffic, seed, seconds,
                                         c["vocab_size"])
    compiles0, compile_s0 = watch.compiles, watch.seconds
    setup_s = time.monotonic() - t_process
    log(f"set-up {setup_s:.3f} s: {compiles0} compiles "
        f"({compile_s0:.3f} s), {watch.hits} from the persistent cache; "
        f"fused={engine.fused}")
    win = drive(engine, plan, c, seconds,
                profile=_profiler if trace else None,
                annotate=jax.profiler.TraceAnnotation if trace
                else _no_annotation)
    window_compiles = watch.compiles - compiles0
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    e2e = end_to_end(win, setup_s)
    log(f"window {win.stop - win.start:.3f} s: {len(win.tracks)} sent, "
        f"{sum(t.req.done for t in win.tracks)} finished, "
        f"{len(win.ticks)} ticks, {window_compiles} compiles inside")
    metrics = {k: e2e[k] for k in cell.end_to_end if e2e.get(k) is not None}
    breakdown = None
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": peak}
    if trace:
        host_sync = host_sync_seconds(engine, win.start, win.trace_start)
        rec = tr.load(str(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx = Context(config=c, slots=engine.b,
                      weight_bits=weight_bits(c),
                      peaks=peaks, window=win, host_sync_s=host_sync,
                      trace=rec, memory_peak_bytes=peak)
        metrics = read_metrics(cell.per_layer, ctx)
        busy = tr.busy_ns(rec["ops"]) / max(rec["devices"], 1)
        dev["busy_s"] = busy * 1e-9
        dev["window_s"] = ctx.trace_window_ns * 1e-9
        breakdown = {"device_ops": [list(x) for x in tr.top_ops(rec)],
                     "idle_gaps": [list(x) for x in tr.idle_gaps(rec)]}
    sample = choose_sample(win.tracks, seed)
    engine = None
    gc.collect()
    prog, ctrl = compare(cell, seed, sample, control_bits)
    ok, chk = checks(cell, prog)
    failed = win.failed + sum(bool(t.req.error) for t in win.tracks)
    out = {"correct": bool(ok),
           "attempted": len(win.tracks) + len(win.unsent_due) + win.failed,
           "failed": failed,
           "metrics": {k: {"value": v, "unit": cell.units[k]}
                       for k, v in metrics.items()},
           "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["setup"] = {"compile_s": compile_s0, "compiles": compiles0,
                    "cache_hits": watch.hits,
                    "window_compiles": window_compiles,
                    "sample_requests": len(sample)}
    if ctrl is not None:
        out["control"] = ctrl
        out["program"] = prog
    out["checks"] = chk
    return out
