"""Serving throughput bench: the runtime under each precision policy.

The paper's kind is inference acceleration — this measures the actual
serving stack (``repro.serving`` batched-prefill continuous batching on
the reduced qwen2 model) across the policies the IPU datapath motivates,
on CPU wall time. Not a TPU number; the relative policy costs and the
engine overheads are the object of measurement. Engines are warmed
(one throwaway request compiles the prefill/decode programs) so the
reported tok/s is steady-state serving throughput, not jit latency.

Reports decode tok/s plus the latency distribution of the runtime —
TTFT and queue-delay percentiles per policy — and a two-replica
plan-aware router pass. Each policy is measured across the decode fast
path's block sizes (``decode_block`` in BLOCKS: a jitted scan of N
decode steps with on-device greedy selection, ONE host sync per block)
with the prepared-weight datapath and calibrated static activation
scales (the default serving configuration), plus a dynamic control
engine (per-step weight quantization, per-token activation absmax,
per-token sync — the pre-refactor behavior). ``host_syncs_per_token``
makes the sync elimination itself part of the trajectory.

Robustness: every engine of every policy is built and warmed up front,
and the best-of-3 timed passes are INTERLEAVED across policies — each
engine's samples span the whole bench wall-clock rather than one short
per-policy window, so a machine-load swing cannot silently invert the
cross-policy ratios.

The BURSTY section measures what continuous batching buys under load:
an open-loop wall-clock arrival trace (requests keep arriving on their
own schedule whether or not the engine kept up) through two engines at
the same ``decode_block`` — the continuous engine (mid-block admission
+ EOS stopping) against the flags-off PR-5-style baseline. Requests
carry harvested per-request stop ids (from a greedy pre-run) so EOS
events are guaranteed; the baseline cannot honour them and burns the
full budget. Reported: TTFT p50/p95, SLO attainment (deadline = the
baseline's own p50 TTFT) and goodput (``metrics.slo_report``).

The TRACE OVERHEAD section measures the observability tax: the same
prepared int8 engine with ``EngineConfig(trace=True)`` against trace
off, interleaved best-of-N passes. Span recording must observe, not
perturb — the ``trace_overhead`` block guards the traced throughput
within 5% of untraced.

The COLD START section measures what the fabric checkpoint buys a
restarted worker: serve-ready engine construction from raw fp32 params
(quantize + pack + calibrate on the critical path) against
``repro.fabric.build_engine`` from a prepared-weight checkpoint, per
policy, plus each checkpoint's on-disk footprint. The int4 row carries
the storage claim the paper's datapath rests on — packed projection
data bytes x 8 equals the fp32 bytes of the same projections exactly
(per-channel scales are the only overhead), asserted, not reported.

The FAILOVER section measures the fabric's recovery economics on a
deterministic two-worker fleet: recovery latency (ticks from losing a
worker to the first post-recovery token of a request it held) and
token waste (work generated twice), requeue-from-scratch against
reconnect-and-resume. Both scenarios must drain with zero loss and
reference-identical streams; resume's wasted_tokens is zero by
construction and the gap is reported as ``resume_waste_cut``.

Emits ONE artifact, ``BENCH_serving.json``: the compact trajectory row
``benchmarks/run.py`` tracks across PRs (like ``BENCH_autotune``), with
the full per-policy/router/bursty breakdown under its ``detail`` key.
(The old duplicate ``serve_bench.json`` is retired — one file, one
schema.)
"""
import dataclasses
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import emit, row
from repro.configs import reduced
from repro.serving import (EngineConfig, Request, Router, SamplingParams,
                           ServingEngine, build_replicas, slo_report)
from repro.models import registry

POLICIES = ("bf16", "int8_serving", "int4_serving", "paper_hybrid")
# decode fast-path block sizes swept per policy (1 = per-token dispatch)
BLOCKS = (1, 4, 8, 16)
# block the trajectory's block_speedup_8v1 column reads (falls back to
# the largest swept block if 8 ever leaves BLOCKS)
_HI_BLOCK = "8" if 8 in BLOCKS else str(max(BLOCKS))
N_REQUESTS = 8
PROMPT_LEN = 8
# enough decode steps that the timed region dwarfs per-tick Python
# overhead jitter (the prepared-vs-dynamic delta is the measurement);
# a multiple of every block size so block-N passes never compile a
# ragged tail program
MAX_NEW = 32


def _workload(cfg, tagged_every=0):
    rng = np.random.default_rng(0)
    reqs = []
    for rid in range(N_REQUESTS):
        reqs.append(Request(
            rid=rid,
            prompt=rng.integers(0, cfg.vocab, PROMPT_LEN, dtype=np.int32),
            max_new_tokens=MAX_NEW,
            tags=("accuracy",) if tagged_every and rid % tagged_every == 0
            else ()))
    return reqs


def _warmup(engine):
    """One throwaway request through prefill + decode so the jitted
    programs compile outside the timed window (time_fn-style warmup);
    MAX_NEW tokens so a blocked engine compiles its full-block decode
    program. The engine's request log and counters are then reset."""
    engine.submit(Request(rid=-1,
                          prompt=np.zeros(PROMPT_LEN, np.int32),
                          max_new_tokens=MAX_NEW))
    engine.run_until_drained()
    engine.completed.clear()
    for k in engine.counters:
        engine.counters[k] = 0


def _reset(engine):
    engine.completed.clear()
    for k in engine.counters:
        engine.counters[k] = 0


def _timed_pass(engine, cfg):
    """Submit the standard workload, drain, return (tok/s, ticks, dt)."""
    _reset(engine)
    for req in _workload(cfg):
        engine.submit(req)
    t0 = time.time()
    ticks = engine.run_until_drained()
    dt = time.time() - t0
    return engine.metrics()["new_tokens"] / dt, ticks, dt


def _build_policy(policy: str):
    """All engines of one policy: a prepared + calibrated engine per
    decode-block size, plus the dynamic control engine; warmed."""
    cfg = dataclasses.replace(reduced("qwen2-0.5b"),
                              precision_policy=policy)
    api = registry.build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    # the first engine calibrates ("auto": the engine itself skips the
    # pass for policies routing no int projections) and prepares; the
    # rest of the block sweep shares its scales AND its prepared tree
    # (preparation is idempotent, so their own prepare is a
    # pass-through instead of 4 independent quantize/pack walks)
    engines = {}
    calibration, block_params = "auto", params
    for blk in BLOCKS:
        eng = ServingEngine(cfg, api, block_params, config=EngineConfig(
            batch_slots=4, cache_len=128, prepare_weights=True,
            act_calibration=calibration, decode_block=blk))
        calibration = eng.act_scales
        block_params = eng.params
        engines[blk] = eng
    engines["dynamic"] = ServingEngine(cfg, api, params,
                                       config=EngineConfig(
                                           batch_slots=4, cache_len=128,
                                           prepare_weights=False))
    for eng in engines.values():
        _warmup(eng)
    return cfg, engines


def _collect_policy(cfg, engines, best):
    """Summarize one policy from its best (tok/s, ticks, seconds) per
    engine — keeping the ticks/seconds of the best pass so the reported
    latency and throughput describe the same run."""
    sweep = {blk: best[blk][0] for blk in BLOCKS}
    # the workload is deterministic per engine, so syncs/token comes
    # straight off the last pass's counters
    syncs = {blk: engines[blk].counters["host_syncs"]
             / max(MAX_NEW * N_REQUESTS, 1) for blk in BLOCKS}
    best_block = max(BLOCKS, key=lambda blk: sweep[blk])
    eng = engines[1]
    m = eng.metrics()
    return {
        "tok_per_s": sweep[1],
        "ticks": best[1][1],
        "seconds": best[1][2],
        "tok_per_s_dynamic": best["dynamic"][0],
        "block_sweep": {str(blk): sweep[blk] for blk in BLOCKS},
        "host_syncs_per_token": {str(blk): syncs[blk] for blk in BLOCKS},
        "best_block": best_block,
        "tok_per_s_best_block": sweep[best_block],
        "ttft_s": m["ttft_s"], "queue_delay_s": m["queue_delay_s"],
        "prefill_calls": m["counters"]["prefill_calls"],
        "prefill_tokens": m["counters"]["prefill_tokens"],
        "decode_steps": m["counters"]["decode_steps"],
        "weight_bytes": m["weight_bytes"]["projections"],
        "weight_bytes_total": m["weight_bytes"]["total"],
        "weight_bytes_dynamic":
            engines["dynamic"].weight_bytes()["projections"],
        "weight_quants_per_step": eng.weight_quant_trace_count(),
        "weight_quants_per_step_dynamic":
            engines["dynamic"].weight_quant_trace_count(),
        "act_quants_per_step": eng.act_quant_trace_count(),
        "act_quants_per_step_dynamic":
            engines["dynamic"].act_quant_trace_count(),
    }


def _bench_router():
    """Two-replica plan-aware pass: the routing layer's overhead and
    split on a mixed (third accuracy-tagged) workload."""
    cfg = reduced("qwen2-0.5b")
    replicas = build_replicas(cfg, ("int8_serving", "bf16"),
                              config=EngineConfig(batch_slots=2,
                                                  cache_len=128))
    router = Router(replicas, strategy="plan_aware")
    for rep in replicas:
        _warmup(rep.engine)
    for req in _workload(cfg, tagged_every=3):
        router.submit(req)
    t0 = time.time()
    ticks = router.run_until_drained()
    dt = time.time() - t0
    new_tokens = sum(r.new_tokens for r in router.completed.values())
    return {
        "tok_per_s": new_tokens / dt, "ticks": ticks, "seconds": dt,
        "counters": router.routing_counters(),
        "completed": len(router.completed),
    }


# bursty open-loop section: request count, decode block, and where in
# the greedy stream the harvested stop token sits (~1/5 of the budget,
# so EOS stopping frees ~80% of a stopped request's decode work)
BURSTY_N = 10
BURSTY_BLOCK = 8
BURSTY_STOP_AT = 6


def _precompile_blocks(eng):
    """Compile every (block length, greedy) program the continuous
    engine can dispatch (mid-block cuts produce 1..decode_block), so no
    compile lands inside the timed open-loop window. The carry is
    all-inactive: the dispatch only pad-writes positions later real
    writes overwrite."""
    from repro.serving.config import MAX_STOP_IDS
    zeros = jnp.zeros((eng.b,), jnp.int32)
    carry = registry.DecodeCarry(
        tok=zeros, pos=zeros, rem=zeros, taken=zeros,
        stops=jnp.full((eng.b, MAX_STOP_IDS), -1, jnp.int32),
        temp=jnp.zeros((eng.b,), jnp.float32), top_k=zeros,
        top_p=jnp.ones((eng.b,), jnp.float32),
        keys=jnp.zeros((eng.b, 2), jnp.uint32))
    for n in range(1, eng.decode_block + 1):
        tokens, _, eng.caches = eng._block_decode(n, False)(
            eng.params, carry, eng.caches)
    np.asarray(tokens)


def _bursty_requests(cfg, stops):
    rng = np.random.default_rng(2)
    return [Request(rid=rid,
                    prompt=rng.integers(0, cfg.vocab, PROMPT_LEN,
                                        dtype=np.int32),
                    max_new_tokens=MAX_NEW,
                    sampling=SamplingParams(stop_ids=stops.get(rid, ())))
            for rid in range(BURSTY_N)]


def _drive_open_loop(engine, reqs, arrivals):
    """Open-loop: each request submits at its wall-clock arrival time
    regardless of engine progress (the load model closed-loop draining
    can't produce — a slow engine faces a growing queue)."""
    _reset(engine)
    pending = sorted(zip(arrivals, reqs), key=lambda ar: ar[0])
    t0 = time.time()
    while pending or engine.has_pending():
        now = time.time() - t0
        while pending and pending[0][0] <= now:
            engine.submit(pending.pop(0)[1])
        if engine.has_pending():
            engine.step()
        else:
            time.sleep(1e-4)
    return time.time() - t0


def _bench_bursty():
    """Continuous engine vs flags-off baseline on the same open-loop
    arrival trace, equal decode_block; returns the BENCH_serving
    'bursty' block."""
    cfg = dataclasses.replace(reduced("qwen2-0.5b"),
                              precision_policy="int8_serving")
    api = registry.build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    cont_cfg = EngineConfig(batch_slots=2, cache_len=128,
                            decode_block=BURSTY_BLOCK,
                            act_calibration="auto")
    cont = ServingEngine(cfg, api, params, config=cont_cfg)
    base_cfg = dataclasses.replace(cont_cfg,
                                   act_calibration=cont.act_scales,
                                   mid_block_admission=False,
                                   eos_stopping=False)
    base = ServingEngine(cfg, api, cont.params, config=base_cfg)
    for eng in (cont, base):
        _warmup(eng)
        _precompile_blocks(eng)

    # greedy pre-run harvests a per-request stop id (for 2/3 of the
    # requests) so the continuous engine is guaranteed EOS events; the
    # baseline receives the SAME requests but cannot honour the stops
    harvest = _bursty_requests(cfg, {})
    for r in harvest:
        base.submit(r)
    base.run_until_drained()
    stops = {r.rid: (int(r.tokens[len(r.prompt) + BURSTY_STOP_AT]),)
             for r in harvest if r.rid % 3 != 0}

    # arrival spacing from the baseline's own measured tick time: one
    # request per ~1.2 ticks after an initial 4-request burst, so the
    # queue stays non-empty while slots are busy
    _reset(base)
    for r in _bursty_requests(cfg, {}):
        base.submit(r)
    t0 = time.time()
    ticks = base.run_until_drained()
    per_tick = (time.time() - t0) / max(ticks, 1)
    arrivals = [0.0 if i < 4 else (i - 3) * 1.2 * per_tick
                for i in range(BURSTY_N)]

    out = {"arrival_spacing_ms": per_tick * 1.2e3,
           "decode_block": BURSTY_BLOCK}
    slo = None
    for name, eng in (("baseline", base), ("continuous", cont)):
        reqs = _bursty_requests(cfg, stops)
        dt = _drive_open_loop(eng, reqs, arrivals)
        m = eng.metrics()
        if slo is None:                 # deadline = baseline p50 TTFT
            slo = m["ttft_s"]["p50"]
        rep = slo_report(eng.completed.values(), slo)
        out[name] = {
            "seconds": dt,
            "new_tokens": m["new_tokens"],
            "ttft_p50_ms": m["ttft_s"]["p50"] * 1e3,
            "ttft_p95_ms": m["ttft_s"]["p95"] * 1e3,
            "slo_attainment": rep["attainment"],
            "goodput_tok_per_s": rep["goodput_tok_per_s"],
            "counters": {k: m["counters"][k] for k in
                         ("short_blocks", "mid_block_admits",
                          "eos_stops", "decode_steps", "host_syncs")},
        }
    out["ttft_slo_ms"] = slo * 1e3
    out["ttft_p95_speedup"] = (out["baseline"]["ttft_p95_ms"]
                               / max(out["continuous"]["ttft_p95_ms"],
                                     1e-9))
    out["goodput_speedup"] = (out["continuous"]["goodput_tok_per_s"]
                              / max(out["baseline"]["goodput_tok_per_s"],
                                    1e-9))
    return out


FUSED_BLOCK = 8


def operand_bytes_per_block(engine, block: int):
    """Weight-operand memory traffic of one decode block, per datapath:
    'packed' = the stored operands (int8 rows / packed nibbles / fp
    codes + scales) the fused kernels stream on every scan step;
    'staged' = the compute-dtype (bf16) operand the staged fallback
    materializes once per block and re-reads every step. The ratio is
    the traffic the fused datapath removes."""
    from repro.quant.prepare import PreparedWeight, iter_projection_weights
    paths = registry.projection_paths(engine.cfg)
    packed = staged = 0
    for _, w in iter_projection_weights(engine.params, paths):
        if not isinstance(w, PreparedWeight) or w.kind == "fp16":
            continue
        elems = w.data.size * (2 if w.kind.endswith("_packed") else 1)
        packed += w.nbytes() * block
        staged += elems * 2 * (block + 1)    # one write + block reads
    return {"packed": int(packed), "staged": int(staged),
            "ratio": staged / max(packed, 1)}


def _bench_fused(repeats: int = 3):
    """Fused-vs-staged ablation at one decode block: the same prepared
    + calibrated int8 engine with ``fused_executors`` on vs off
    (identical params, scales and block size), interleaved best-of
    passes, plus the traced staged-materialization counts and the
    per-block operand-traffic column."""
    cfg = dataclasses.replace(reduced("qwen2-0.5b"),
                              precision_policy="int8_serving")
    api = registry.build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    fused = ServingEngine(cfg, api, params, config=EngineConfig(
        batch_slots=4, cache_len=128, decode_block=FUSED_BLOCK,
        act_calibration="auto", fused_executors="on"))
    staged = ServingEngine(cfg, api, fused.params, config=EngineConfig(
        batch_slots=4, cache_len=128, decode_block=FUSED_BLOCK,
        act_calibration=fused.act_scales, fused_executors="off"))
    engines = {"fused": fused, "staged": staged}
    mats = {k: e.staged_trace_count() for k, e in engines.items()}
    assert mats["fused"] == 0 < mats["staged"], mats
    for eng in engines.values():
        _warmup(eng)
    best = {k: 0.0 for k in engines}
    for _ in range(repeats):
        for name, eng in engines.items():
            tok_s, _, _ = _timed_pass(eng, cfg)
            best[name] = max(best[name], tok_s)
    traffic = operand_bytes_per_block(fused, FUSED_BLOCK)
    return {
        "decode_block": FUSED_BLOCK,
        "tok_per_s": best,
        "fused_speedup": best["fused"] / max(best["staged"], 1e-9),
        "staged_materializations_per_block": mats,
        "operand_bytes_per_block": traffic,
    }


def _bench_trace_overhead(repeats: int = 3):
    """Tracing must observe, not perturb: the same prepared int8
    engine with spans on vs off, interleaved best-of-``repeats`` timed
    passes. Returns the ``trace_overhead`` summary block whose
    ``within_5pct`` flag guards the observability tax."""
    cfg = dataclasses.replace(reduced("qwen2-0.5b"),
                              precision_policy="int8_serving")
    api = registry.build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    engines = {}
    calibration, p = "auto", params
    for name, trace in (("off", False), ("on", True)):
        eng = ServingEngine(cfg, api, p, config=EngineConfig(
            batch_slots=4, cache_len=128, decode_block=8,
            act_calibration=calibration, trace=trace))
        calibration, p = eng.act_scales, eng.params
        _warmup(eng)
        engines[name] = eng
    best = {k: 0.0 for k in engines}
    for _ in range(repeats):
        for name, eng in engines.items():
            tok_s, _, _ = _timed_pass(eng, cfg)
            best[name] = max(best[name], tok_s)
    overhead = 1.0 - best["on"] / max(best["off"], 1e-9)
    return {
        "tok_per_s_trace_off": best["off"],
        "tok_per_s_trace_on": best["on"],
        "overhead_frac": overhead,
        "trace_events": len(engines["on"].tracer.events),
        "within_5pct": overhead <= 0.05,
    }


def _bench_cold_start(repeats: int = 2):
    """Engine cold start per policy: raw fp32 construction (quantize +
    pack + calibrate) vs ``fabric.build_engine`` from a checkpoint.

    Best-of-``repeats`` on both paths so one-time trace/compile costs
    don't masquerade as the restart tax — the second construction
    reuses compiled quantization programs, matching a long-lived
    process picking up a new replica. Asserts the int4 storage
    identity: packed projection data bytes x 8 == the fp32 bytes of
    the same projections.
    """
    import os
    import tempfile

    from repro.fabric import build_engine, save_engine_checkpoint
    from repro.quant.prepare import PreparedWeight, iter_projection_weights

    out = {}
    with tempfile.TemporaryDirectory() as root:
        for policy in POLICIES:
            cfg = dataclasses.replace(reduced("qwen2-0.5b"),
                                      precision_policy=policy)
            api = registry.build(cfg)
            params = api.init(jax.random.PRNGKey(0))
            ecfg = EngineConfig(batch_slots=2, cache_len=128,
                                act_calibration="auto")
            raw_s, eng = float("inf"), None
            for _ in range(repeats):
                t0 = time.perf_counter()
                eng = ServingEngine(cfg, api, params, config=ecfg)
                raw_s = min(raw_s, time.perf_counter() - t0)
            ckpt = os.path.join(root, policy)
            save_engine_checkpoint(eng, ckpt)
            restore_s = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                restored = build_engine(ckpt)
                restore_s = min(restore_s, time.perf_counter() - t0)
            assert restored.prepared == eng.prepared
            disk = sum(os.path.getsize(os.path.join(dp, fn))
                       for dp, _, fns in os.walk(ckpt) for fn in fns)
            paths = registry.projection_paths(cfg)
            raw_by_path = dict(iter_projection_weights(params, paths))
            packed = packed_fp32 = 0
            for p, w in iter_projection_weights(restored.params, paths):
                if (isinstance(w, PreparedWeight)
                        and w.kind == "int4_packed"):
                    packed += int(w.data.nbytes)
                    packed_fp32 += int(raw_by_path[p].size) * 4
            if policy == "int4_serving":
                assert packed and packed * 8 == packed_fp32, \
                    (policy, packed, packed_fp32)
            out[policy] = {
                "raw_s": raw_s,
                "restore_s": restore_s,
                "speedup": raw_s / max(restore_s, 1e-9),
                "checkpoint_bytes": disk,
                "int4_packed_proj_bytes": packed,
                "int4_packed_proj_bytes_fp32": packed_fp32,
            }
    return out


# failover section: fleet shape and workload for the deterministic
# kill/sever scenarios (small enough that requeued work visibly queues
# behind the survivor's two slots)
FAILOVER_N = 6
FAILOVER_MAX_NEW = 12
FAILOVER_KILL_TICK = 3


def _bench_failover():
    """Failover economics on a deterministic two-worker fleet restored
    from one serve-ready checkpoint: recovery latency (the clock time
    from losing a worker to the first post-recovery token of a request
    it held) and token waste (tokens the fleet generates twice) for the
    two recovery paths — requeue-from-scratch (a non-resumable worker
    dies) vs reconnect-and-resume (a resumable worker's link is severed
    and it rejoins holding its engine state). ManualClock-driven, so
    both numbers are scheduling facts in ticks, not wall-clock noise;
    each scenario must still drain with zero loss and streams identical
    to the single-engine reference."""
    import tempfile

    from repro.fabric import save_engine_checkpoint
    from repro.fabric.checkpoint import build_engine
    from repro.fabric.controller import (Controller, ManualClock,
                                         reattach_local_worker,
                                         spawn_local_worker)
    from repro.fabric.smoke import _engine_streams, _make_requests, _streams

    cfg = dataclasses.replace(reduced("qwen2-0.5b"),
                              precision_policy="int4_serving")
    api = registry.build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, api, params, config=EngineConfig(
        batch_slots=2, cache_len=64, act_calibration="auto"))

    def _generated(req):
        return 0 if req.tokens is None else len(req.tokens) - len(req.prompt)

    out = {}
    with tempfile.TemporaryDirectory() as root:
        ckpt = os.path.join(root, "ckpt")
        save_engine_checkpoint(engine, ckpt, step=0)
        ref = _engine_streams(
            build_engine(ckpt, api=api),
            _make_requests(cfg, FAILOVER_N, FAILOVER_MAX_NEW, 0))
        for mode in ("requeue", "resume"):
            clock = ManualClock()
            ctrl = Controller(heartbeat_timeout=4.0, clock=clock)
            spawn_local_worker(ctrl, ckpt, name="survivor")
            victim = spawn_local_worker(ctrl, ckpt, name="victim",
                                        resumable=(mode == "resume"))
            reqs = _make_requests(cfg, FAILOVER_N, FAILOVER_MAX_NEW, 0)
            for r in reqs:
                ctrl.submit(r)
            for _ in range(FAILOVER_KILL_TICK):
                clock.advance(1.0)
                ctrl.tick()
            affected = sorted(victim.replica.in_flight)
            assert affected, "kill tick landed with nothing in flight"
            received = {rid: _generated(victim.replica.in_flight[rid])
                        for rid in affected}
            t_kill = clock()
            victim.endpoint.close()     # dead socket / severed link
            # tick until an affected request's token count GROWS again
            # (requeue resets it to zero first, so growth — not
            # exceeding the kill-time count — is the recovery event)
            by_rid = {r.rid: r for r in reqs}
            prev = dict(received)
            recovered_at = None
            reattached = False
            while ctrl.has_pending():
                clock.advance(1.0)
                ctrl.tick()
                if (mode == "resume" and not reattached
                        and victim.state == "suspect"):
                    reattach_local_worker(ctrl, victim.driver.worker)
                    reattached = True
                cur = {rid: _generated(by_rid[rid]) for rid in affected}
                if recovered_at is None and any(
                        cur[rid] > prev[rid] for rid in affected):
                    recovered_at = clock()
                prev = cur
            assert _streams(ctrl.completed) == ref, f"{mode} lost tokens"
            # requeue regenerates everything the controller already had
            # for the victim's in-flight work; resume regenerates
            # nothing (the engine kept its state across the severance)
            wasted = sum(received.values()) if mode == "requeue" else 0
            total = FAILOVER_N * FAILOVER_MAX_NEW
            out[mode] = {
                "recovery_s": recovered_at - t_kill,
                "affected_requests": len(affected),
                "tokens_at_kill": sum(received.values()),
                "wasted_tokens": wasted,
                "waste_frac": wasted / total,
                "requeued": ctrl.scheduler.requeued,
                "resumed": ctrl.resumed,
            }
            assert (ctrl.scheduler.requeued == 0) == (mode == "resume")
    out["resume_waste_cut"] = (out["requeue"]["wasted_tokens"]
                               - out["resume"]["wasted_tokens"])
    return out


def run(verbose: bool = True, repeats: int = 3):
    """Whole-bench wrapper: on the CPU the fused executors' Pallas
    backend runs in interpret mode — pure tracing overhead that would
    drown the datapath being measured. There, pin the identical-math
    XLA reference backend for the duration of the bench (unless the
    caller pinned one explicitly) so every wall-clock row, fused or
    staged, measures real compute. On an accelerator the kernels
    compile, and the bench runs them."""
    if (jax.default_backend() != "cpu"
            or os.environ.get("REPRO_FUSED_BACKEND")):
        return _run(verbose, repeats)
    os.environ["REPRO_FUSED_BACKEND"] = "xla"
    try:
        return _run(verbose, repeats)
    finally:
        os.environ.pop("REPRO_FUSED_BACKEND", None)


def _run(verbose: bool = True, repeats: int = 3):
    # build + warm every engine of every policy FIRST, then interleave
    # the timed repeat sweeps across policies: each engine's
    # best-of-``repeats`` samples span the whole bench wall-clock
    # instead of one ~10s window per policy, so a machine-load swing
    # hits every policy's best equally and cannot invert the
    # cross-policy ratios (speedup_vs_bf16 and friends)
    built = {p: _build_policy(p) for p in POLICIES}
    best = {p: {k: (0.0, 0, 0.0) for k in built[p][1]} for p in POLICIES}
    for _ in range(repeats):
        for p, (cfg, engines) in built.items():
            for name, eng in engines.items():
                tok_s, ticks, seconds = _timed_pass(eng, cfg)
                if tok_s > best[p][name][0]:
                    best[p][name] = (tok_s, ticks, seconds)
    results = {}
    for policy in POLICIES:
        cfg, engines = built[policy]
        results[policy] = r = _collect_policy(cfg, engines, best[policy])
        if verbose:
            ttft = r["ttft_s"].get("p50", 0.0) * 1e3
            qd = r["queue_delay_s"].get("p90", 0.0) * 1e3
            sweep = ", ".join(f"b{blk}={r['block_sweep'][str(blk)]:.0f}"
                              for blk in BLOCKS)
            row(f"serve/{policy}",
                r["seconds"] * 1e6 / max(MAX_NEW * N_REQUESTS, 1),
                f"{r['tok_per_s']:.1f} tok/s prepared "
                f"({r['tok_per_s_dynamic']:.1f} dynamic; {sweep}), "
                f"{r['ticks']} ticks, ttft_p50={ttft:.0f}ms, "
                f"queue_p90={qd:.0f}ms, w={r['weight_bytes']}B")
    router_r = _bench_router()
    if verbose:
        row("serve/router[int8+bf16]",
            router_r["seconds"] * 1e6 / max(MAX_NEW * N_REQUESTS, 1),
            f"{router_r['tok_per_s']:.1f} tok/s, "
            f"counters={router_r['counters']}")
    bursty = _bench_bursty()
    if verbose:
        for name in ("baseline", "continuous"):
            b = bursty[name]
            row(f"serve/bursty-{name}",
                b["seconds"] * 1e6 / max(b["new_tokens"], 1),
                f"ttft_p95={b['ttft_p95_ms']:.0f}ms "
                f"slo={b['slo_attainment']:.2f} "
                f"goodput={b['goodput_tok_per_s']:.1f} tok/s "
                f"(eos_stops={b['counters']['eos_stops']}, "
                f"mid_block={b['counters']['mid_block_admits']})")
    trace_ov = _bench_trace_overhead(repeats)
    if verbose:
        row("serve/trace-overhead",
            trace_ov["overhead_frac"] * 1e6,
            f"{trace_ov['tok_per_s_trace_on']:.1f} tok/s traced vs "
            f"{trace_ov['tok_per_s_trace_off']:.1f} untraced "
            f"({trace_ov['overhead_frac'] * 100:+.1f}%, "
            f"{trace_ov['trace_events']} events)")
        if not trace_ov["within_5pct"]:
            print("WARNING: tracing overhead exceeds the 5% budget")
    fusedr = _bench_fused(repeats)
    if verbose:
        t = fusedr["operand_bytes_per_block"]
        row("serve/fused-vs-staged",
            1e6 / max(fusedr["tok_per_s"]["fused"], 1e-9),
            f"{fusedr['tok_per_s']['fused']:.1f} tok/s fused vs "
            f"{fusedr['tok_per_s']['staged']:.1f} staged "
            f"({fusedr['fused_speedup']:.2f}x, b{fusedr['decode_block']}), "
            f"mats={fusedr['staged_materializations_per_block']}, "
            f"operand {t['packed']}B vs {t['staged']}B "
            f"({t['ratio']:.2f}x traffic cut)")
    cold = _bench_cold_start()
    if verbose:
        for p, c in cold.items():
            row(f"serve/cold-start[{p}]", c["restore_s"] * 1e6,
                f"restore {c['restore_s'] * 1e3:.0f}ms vs raw "
                f"{c['raw_s'] * 1e3:.0f}ms ({c['speedup']:.1f}x), "
                f"ckpt={c['checkpoint_bytes']}B")
    failover = _bench_failover()
    if verbose:
        for mode in ("requeue", "resume"):
            f = failover[mode]
            row(f"serve/failover-{mode}", f["recovery_s"] * 1e6,
                f"recovery={f['recovery_s']:.0f} ticks, "
                f"wasted={f['wasted_tokens']} tok "
                f"({f['waste_frac'] * 100:.0f}% of run), "
                f"affected={f['affected_requests']}")

    base = results["bf16"]["tok_per_s"]
    summary = {
        "tok_per_s": {p: results[p]["tok_per_s"] for p in POLICIES},
        "tok_per_s_dynamic": {p: results[p]["tok_per_s_dynamic"]
                              for p in POLICIES},
        "prepared_speedup": {p: results[p]["tok_per_s"]
                             / results[p]["tok_per_s_dynamic"]
                             for p in POLICIES},
        "weight_bytes": {p: results[p]["weight_bytes"]
                         for p in POLICIES},
        "weight_bytes_fp32": results["bf16"]["weight_bytes_dynamic"],
        "weight_quants_per_step": {
            p: results[p]["weight_quants_per_step"] for p in POLICIES},
        "act_quants_per_step": {
            p: results[p]["act_quants_per_step"] for p in POLICIES},
        "act_quants_per_step_dynamic": {
            p: results[p]["act_quants_per_step_dynamic"]
            for p in POLICIES},
        "block_sweep": {p: results[p]["block_sweep"] for p in POLICIES},
        "host_syncs_per_token": {p: results[p]["host_syncs_per_token"]
                                 for p in POLICIES},
        "best_block": {p: results[p]["best_block"] for p in POLICIES},
        "tok_per_s_best_block": {p: results[p]["tok_per_s_best_block"]
                                 for p in POLICIES},
        "block_speedup_8v1": {
            p: results[p]["block_sweep"][_HI_BLOCK]
            / results[p]["block_sweep"][str(min(BLOCKS))]
            for p in POLICIES},
        "speedup_vs_bf16": {p: results[p]["tok_per_s"] / base
                            for p in POLICIES},
        "speedup_vs_bf16_best_block": {
            p: results[p]["tok_per_s_best_block"]
            / results["bf16"]["tok_per_s_best_block"] for p in POLICIES},
        "ttft_p50_ms": {p: results[p]["ttft_s"].get("p50", 0.0) * 1e3
                        for p in POLICIES},
        "ttft_p90_ms": {p: results[p]["ttft_s"].get("p90", 0.0) * 1e3
                        for p in POLICIES},
        "queue_delay_p90_ms": {
            p: results[p]["queue_delay_s"].get("p90", 0.0) * 1e3
            for p in POLICIES},
        "prefill_calls": {p: results[p]["prefill_calls"]
                          for p in POLICIES},
        "router": {"tok_per_s": router_r["tok_per_s"],
                   "counters": router_r["counters"]},
        "bursty": {
            "ttft_slo_ms": bursty["ttft_slo_ms"],
            "ttft_p95_ms": {k: bursty[k]["ttft_p95_ms"]
                            for k in ("baseline", "continuous")},
            "slo_attainment": {k: bursty[k]["slo_attainment"]
                               for k in ("baseline", "continuous")},
            "goodput_tok_per_s": {k: bursty[k]["goodput_tok_per_s"]
                                  for k in ("baseline", "continuous")},
            "ttft_p95_speedup": bursty["ttft_p95_speedup"],
            "goodput_speedup": bursty["goodput_speedup"],
        },
        "trace_overhead": trace_ov,
        "failover": {
            "recovery_s": {m: failover[m]["recovery_s"]
                           for m in ("requeue", "resume")},
            "wasted_tokens": {m: failover[m]["wasted_tokens"]
                              for m in ("requeue", "resume")},
            "waste_frac": {m: failover[m]["waste_frac"]
                           for m in ("requeue", "resume")},
            "resume_waste_cut": failover["resume_waste_cut"],
        },
        "fused": fusedr,
        "operand_bytes_per_block": fusedr["operand_bytes_per_block"],
        "cold_start": {
            "restore_s": {p: cold[p]["restore_s"] for p in POLICIES},
            "raw_s": {p: cold[p]["raw_s"] for p in POLICIES},
            "speedup": {p: cold[p]["speedup"] for p in POLICIES},
            "checkpoint_bytes": {p: cold[p]["checkpoint_bytes"]
                                 for p in POLICIES},
            "int4_packed_x8_equals_fp32": True,   # asserted above
        },
        # full per-policy/router/bursty breakdown (formerly the
        # separate serve_bench.json artifact)
        "detail": {**results, "router": router_r, "bursty": bursty,
                   "fused": fusedr, "cold_start": cold,
                   "failover": failover},
    }
    emit("BENCH_serving", summary)
    if verbose:
        print("serve: " + ", ".join(
            f"{k}={v['tok_per_s']:.1f} tok/s "
            f"({v['tok_per_s'] / base:.2f}x bf16, "
            f"{summary['prepared_speedup'][k]:.2f}x dynamic)"
            for k, v in results.items()))
        print("serve blocks: " + ", ".join(
            f"{p}@b{summary['best_block'][p]}="
            f"{summary['tok_per_s_best_block'][p]:.1f} tok/s "
            f"({summary['block_speedup_8v1'][p]:.2f}x b8/b1, "
            f"{summary['speedup_vs_bf16_best_block'][p]:.2f}x bf16)"
            for p in POLICIES))
        sb = summary["bursty"]
        print(f"serve bursty: continuous ttft_p95="
              f"{sb['ttft_p95_ms']['continuous']:.0f}ms vs baseline "
              f"{sb['ttft_p95_ms']['baseline']:.0f}ms "
              f"({sb['ttft_p95_speedup']:.2f}x), slo attainment "
              f"{sb['slo_attainment']['continuous']:.2f} vs "
              f"{sb['slo_attainment']['baseline']:.2f}, goodput "
              f"{sb['goodput_speedup']:.2f}x")
        print("serve cold-start: " + ", ".join(
            f"{p}={cold[p]['speedup']:.1f}x "
            f"({cold[p]['restore_s'] * 1e3:.0f}ms restore)"
            for p in POLICIES))
    return summary


def main():
    run()


if __name__ == "__main__":
    main()
