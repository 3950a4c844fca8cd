"""Continuous-batching serving engine (vLLM-shaped).

``make_serve_fns`` builds the sharded prefill/decode artifacts the
dry-run lowers for the prefill_32k / decode_32k / long_500k cells.
``ServingEngine`` is the single-replica runtime: fixed decode slots over
one shared KV cache, an :class:`repro.serving.scheduler.AdmissionScheduler`
in front, and a steady-state loop in which prefill and decode
interleave. Engine tuning lives in one frozen
:class:`repro.serving.config.EngineConfig` (``ServingEngine(cfg, api,
params, config=EngineConfig(...))``; the legacy kwargs still map
through a deprecation shim), and per-request decoding behavior lives in
:class:`repro.serving.config.SamplingParams` on each ``Request``.

The continuous loop, per tick:

* **admission** drains the scheduler into free slots;
* **chunked prefill continuation** advances every prefilling slot by
  one ``prefill_chunk``-token wave in a SINGLE jitted dispatch
  (``api.prefill_chunk``: position-offset scatter into the live cache)
  over the smallest power-of-two bucket of cache rows that holds the
  prefilling slots (``batch_slots`` itself for a full wave); every
  bucket compiles on the first prefill tick. Long prompts
  stream through multiple waves while other slots keep decoding, so
  admission no longer requires ``prompt + generation <= cache_len``:
  oversized requests serve with trailing-window (ring) context and are
  stamped ``Request.truncated``;
* **decode** runs one block: ``decode_block`` scan steps with on-device
  selection (``models.registry.make_block_decode``), ONE host sync.
  With ``mid_block_admission`` the engine cuts the block short while
  requests are queued (boundaries chosen by queue depth), so freed
  slots admit mid-stream instead of after a full drain. With
  ``eos_stopping`` a generated stop id zeroes the slot's budget ON
  DEVICE: short completions free their slot and budget mid-block.
  Selection is per-request — greedy argmax by default, or
  temperature/top-k/top-p sampling (``models.sampling.sample_tokens``)
  with the PRNG key threaded through the scan carry, so sampled
  streams are seeded-deterministic and invariant to ``decode_block``.

Why position-offset prefill is safe here: the KV cache is
position-tagged (``layers.attention.KVCache.pos``) and attention masks
by tag, so chunk writes at absolute positions compose exactly like
decode writes, and the garbage a masked pad row writes carries tags the
next real write overwrites before any query attends them. That
invariant holds for attention caches but *not* for recurrent state
(rwkv/griffin fold every consumed token into O(1) state), so the fast
path is gated per family and everything else falls back to the
teacher-forced admission loop the engine always had.

Weights are PREPARED at construction (``quant.prepare`` via the model
family's ``api.prepare`` hook, default on) and activation scales can be
CALIBRATED (``act_calibration=``) — see quant/prepare.py and
quant/calibrate.py; the trace counters
(``weight_quant_trace_count`` / ``act_quant_trace_count``) assert the
fast path performs zero dynamic weight quants and zero per-token
activation absmax reduces. Dynamically-scaled fake-quant projections
couple batch rows through their shared per-tensor absmax and are
rejected for ``decode_block > 1`` at construction.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.configs.base import ModelConfig
from repro.core import policy as policy_mod
from repro.models import registry
from repro.obs import MetricsRegistry, ReplicaStats, Tracer, traced_jit
from repro.parallel import sharding as shd
from repro.serving.config import (MAX_STOP_IDS, EngineConfig,
                                  SamplingParams)


def _prefill_rows(api: registry.ModelAPI, params, tokens, offs, lens,
                  rows, caches):
    """``api.prefill_chunk`` over the cache rows ``rows`` alone: slice
    them out of every stacked ``(n_groups, B, ...)`` cache leaf, prefill
    the compact batch, write it back. ``rows`` must be distinct; a row
    with length 0 comes back unchanged. Every leaf leaves in the dtype
    the full-width wave gives it. One dynamic slice per row, not a
    gather: the TPU compiler expands a gather over the stacked cache
    into hundreds of ops (a 2-row wave over 12,544 positions: 2.8 MB of
    optimized HLO, against 0.37 MB this way)."""
    def take(x):
        return jnp.concatenate(
            [jax.lax.dynamic_slice_in_dim(x, rows[i], 1, axis=1)
             for i in range(rows.shape[0])], axis=1)

    def put(x, n):
        x = x.astype(n.dtype)
        for i in range(rows.shape[0]):
            x = jax.lax.dynamic_update_slice_in_dim(
                x, n[:, i:i + 1], rows[i], axis=1)
        return x

    new = api.prefill_chunk(params, {"tokens": tokens, "offsets": offs,
                                     "lengths": lens},
                            jax.tree.map(take, caches))
    return jax.tree.map(put, caches, new)


def _with_variant(fn: Callable, name: Optional[str]) -> Callable:
    """Trace ``fn`` under ``layers.mplinear.executor_variant(name)``:
    the context is held over the function *body* (which jax executes at
    trace time), so every mp_linear dispatch the program contains
    resolves against the named executor variant."""
    if name is None:
        return fn
    from repro.layers.mplinear import executor_variant

    def wrapped(*args, **kwargs):
        with executor_variant(name):
            return fn(*args, **kwargs)

    return wrapped

# families whose prefill consumes only tokens and whose caches are
# position-tagged (padding-safe): eligible for the chunked prefill path
_FAST_PREFILL_FAMILIES = ("lm",)


def make_serve_fns(api: registry.ModelAPI, mesh: Mesh,
                   batch_shape: Dict, cache_len: int, batch_size: int):
    """Returns (jitted prefill, jitted decode, cache shardings)."""
    cache_shape = jax.eval_shape(lambda: api.init_cache(batch_size,
                                                        cache_len))
    cache_shard = shd.cache_shardings(cache_shape, mesh)
    param_shape = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    param_shard = shd.param_shardings(param_shape, mesh)

    prefill_in = {k: v for k, v in batch_shape.items()
                  if k not in ("token", "pos")}
    pf_shard = shd.batch_shardings(prefill_in, mesh) if prefill_in else None

    prefill = jax.jit(
        lambda p, b, c: api.prefill(p, b, c),
        in_shardings=(param_shard, pf_shard, cache_shard),
        donate_argnums=(2,))

    # decode state sharding may differ from cache (encdec carries enc_out)
    def _decode(p, b, c):
        return api.decode_step(p, b, c)

    decode = jax.jit(_decode, in_shardings=(param_shard, None, None),
                     donate_argnums=(2,))
    return prefill, decode, cache_shard, param_shard


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (S,) int32
    max_new_tokens: int = 16
    priority: int = 0            # lower admits first (see scheduler)
    tags: Tuple[str, ...] = ()   # e.g. ("accuracy",) for router SLOs
    tokens: Optional[List[int]] = None
    done: bool = False
    error: Optional[str] = None        # set on terminal admission errors
    next_input: Optional[int] = None   # next token to feed decode
    # timestamps stamped by scheduler/engine (engine clock domain):
    # submit <= admit <= prefill_done <= first_token split the TTFT
    submit_time: Optional[float] = None
    admit_time: Optional[float] = None
    prefill_done_time: Optional[float] = None   # prompt fully consumed
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    # per-request decoding parameters (greedy by default)
    sampling: SamplingParams = SamplingParams()
    finish_reason: Optional[str] = None   # 'length' | 'stop'
    truncated: bool = False        # served with trailing-window context
    prefill_pos: int = 0           # prompt tokens consumed by prefill

    @property
    def new_tokens(self) -> int:
        return 0 if self.tokens is None else len(self.tokens) - len(self.prompt)

    @property
    def budget(self) -> int:
        """Effective generation budget: ``sampling.max_new_tokens``
        when set, else the request-level ``max_new_tokens``."""
        if self.sampling.max_new_tokens is not None:
            return self.sampling.max_new_tokens
        return self.max_new_tokens


class ServingEngine:
    """Slot-based continuous batching with chunked prefill admission.

    All slots share one decode program (fixed batch); free slots idle on
    pad tokens. Admission drains the scheduler into free slots; every
    tick one ``(rows, prefill_chunk)`` prefill wave advances all
    prefilling slots at their own position offsets while decode keeps
    running for the rest — no drain barrier between admission and
    generation.
    """

    def __init__(self, cfg: ModelConfig, api: registry.ModelAPI, params,
                 config: Optional[EngineConfig] = None, *,
                 scheduler=None,
                 clock: Callable[[], float] = time.monotonic,
                 **legacy_kwargs):
        from repro.serving.scheduler import AdmissionScheduler
        if legacy_kwargs:
            if config is not None:
                raise TypeError(
                    "pass either config=EngineConfig(...) or the legacy "
                    f"kwargs, not both: {sorted(legacy_kwargs)}")
            warnings.warn(
                "ServingEngine(batch_slots=..., cache_len=..., ...) "
                "kwargs are deprecated; pass config=EngineConfig(...) "
                "(and per-request SamplingParams instead of 'greedy')",
                DeprecationWarning, stacklevel=2)
            config = EngineConfig.from_legacy_kwargs(legacy_kwargs)
        self.config = config if config is not None else EngineConfig()
        self.cfg = cfg
        self.api = api
        self.b = self.config.batch_slots
        self.cache_len = self.config.cache_len
        self.clock = clock
        # resolve the serving policy up front: a bad policy name or a
        # missing/invalid plan file fails at engine construction, not on
        # the first decode (plan: refs load repro.autotune artifacts)
        self.policy = policy_mod.get_policy(cfg.precision_policy)
        # cheap decode_block validation FIRST: a misconfigured fast
        # path must not pay the calibration forwards below before
        # failing
        self.decode_block = self.config.decode_block
        if self.decode_block > 1 and not registry.block_decode_eligible(cfg):
            raise ValueError(
                f"family {cfg.family!r} is not eligible for blocked decode")
        # prepared-weight datapath: quantize/pack the replica's weights
        # ONCE at construction (quant.prepare) so decode never
        # re-quantizes static weights per token and int4 replicas hold
        # packed nibbles instead of fp32; calibrated static activation
        # scales ride on the prepared containers the same way
        self.prepared = bool(self.config.prepare_weights) \
            and api.prepare is not None
        self.act_scales = self._resolve_act_scales(
            self.config.act_calibration, params)
        self.params = api.prepare(params, self.policy,
                                  act_scales=self.act_scales) \
            if self.prepared else params
        # fused Pallas executors (kernels.fused): 'on'/'off' explicit,
        # 'auto' exactly when the operands the fused kernels consume
        # exist — prepared storage plus calibrated static activation
        # scales for int routes (fp8/fp4 routes need no act scale)
        self.fused = self._resolve_fused(params)
        self._variant = "fused" if self.fused else None
        self.caches = api.init_cache(self.b, self.cache_len)
        self.pos = np.zeros(self.b, np.int32)
        self.slot_req: List[Optional[Request]] = [None] * self.b
        self.scheduler = scheduler if scheduler is not None \
            else AdmissionScheduler()
        self.completed: Dict[int, Request] = {}
        prefill = self.config.prefill
        if prefill == "batched" and cfg.family not in _FAST_PREFILL_FAMILIES:
            raise ValueError(
                f"batched prefill needs a position-tagged token-only "
                f"prefill; family {cfg.family!r} is not eligible")
        self._fast_prefill = (cfg.family in _FAST_PREFILL_FAMILIES
                              if prefill == "auto" else prefill == "batched")
        if self.decode_block > 1:
            # dynamic fake-quant calibrates ONE absmax over the whole
            # (slots, 1, d) activation tensor, coupling batch rows — a
            # blocked engine's pad cadence would then leak into other
            # slots' tokens (measured). Exact int kernels quantize
            # per row and calibrated scales are elementwise, so both
            # stay per-slot independent.
            uncovered = self._dynamic_fake_int_paths(params)
            if uncovered:
                raise ValueError(
                    "decode_block > 1 needs per-slot-independent "
                    "decode, but dynamically-scaled fake-quant "
                    "projections couple batch rows through their "
                    "shared per-tensor activation absmax "
                    f"({sorted(uncovered)[:3]}...); calibrate static "
                    "activation scales (act_calibration='auto' or a "
                    "quant.calibrate dict) or serve exact int kernels")
        # observability: typed metrics behind a dict-compatible view
        # (metrics()["counters"] schema unchanged), a span tracer on the
        # engine clock (free when config.trace is off) whose tick phases
        # also enter jax.profiler annotations, so a profiler capture
        # shows them as engine.<phase> beside the device's ops, and the
        # measured per-replica stats the router's online cost
        # correction reads
        self.registry = MetricsRegistry()
        for k in ("ticks", "decode_steps", "host_syncs",
                  "prefill_calls", "prefill_tokens", "prefill_rows",
                  "teacher_forced_tokens", "admitted", "submitted",
                  "short_blocks", "mid_block_admits", "eos_stops"):
            self.registry.counter(k)
        self.counters = self.registry.counters_view()
        self.tracer = Tracer(clock=self.clock, enabled=self.config.trace,
                             annotate=jax.profiler.TraceAnnotation)
        self.stats = ReplicaStats(alpha=self.config.stats_alpha,
                                  window=self.config.stats_window)
        self._decode = traced_jit(
            jax.jit(_with_variant(
                lambda p, tok, pos, c: api.decode_step(
                    p, {"token": tok, "pos": pos}, c),
                self._variant)),
            "decode_step", self.tracer)
        # per-slot sampling state mirrored on host, scattered into the
        # decode programs per dispatch (rows reset when slots free)
        self._temp = np.zeros(self.b, np.float32)
        self._topk = np.zeros(self.b, np.int32)
        self._topp = np.ones(self.b, np.float32)
        self._stops = np.full((self.b, MAX_STOP_IDS), -1, np.int32)
        self._keys = np.zeros((self.b, 2), np.uint32)
        self._stop_sets: List[frozenset] = [frozenset()] * self.b
        from repro.models.sampling import sample_tokens
        self._select = traced_jit(jax.jit(sample_tokens), "select",
                                  self.tracer)
        # effective prefill chunk: bounded by the smallest cache ring so
        # a chunk's positions occupy distinct slots within each row
        # (SWA groups cap at their window)
        self.prefill_chunk = self.config.prefill_chunk
        if self._fast_prefill:
            caps = [c.pos.shape[-1]
                    for c in jax.tree.leaves(
                        self.caches, is_leaf=lambda x: hasattr(x, "pos"))]
            self.prefill_chunk = max(
                min(self.prefill_chunk, min(caps), self.cache_len), 1)
            self._prefill_chunk_fn = traced_jit(
                jax.jit(_with_variant(
                    lambda p, tokens, offs, lens, c: api.prefill_chunk(
                        p, {"tokens": tokens, "offsets": offs,
                            "lengths": lens}, c),
                    self._variant)),
                "prefill_chunk", self.tracer)
            # a wave with fewer prefilling slots runs on the smallest
            # power-of-two bucket of cache rows that holds them (the
            # module keeps the lambda/wrapped name of the full program)
            self._prefill_rows_fn = traced_jit(
                jax.jit(_with_variant(
                    lambda p, tokens, offs, lens, rows, c: _prefill_rows(
                        api, p, tokens, offs, lens, rows, c),
                    self._variant)),
                "prefill_chunk", self.tracer)
            self._prefill_buckets = sorted(
                {1 << i for i in range(self.b.bit_length())
                 if 1 << i < self.b} | {self.b})
            self._prefill_warm = False
        # blocked-decode programs, one jit cache entry per (block
        # length, sample?) pair — at most 2 * decode_block compiles
        self._block_fns: Dict[Tuple[int, bool], Callable] = {}
        self._last_block_short = False
        # params are immutable after preparation: walk the tree for the
        # resident-bytes report once, not on every metrics() call
        from repro.quant.prepare import weight_resident_bytes
        self._weight_bytes = weight_resident_bytes(
            self.params, registry.projection_paths(self.cfg))

    def _resolve_act_scales(self, act_calibration, params):
        """None | mapping | 'auto' -> {policy path: static scale}.

        'auto' prefers scales embedded in a ``plan:`` artifact (the
        searched plan carries its calibration — which assumes the plan
        was calibrated against the same seeded-init checkpoint this
        replica serves) and otherwise runs a short random-token
        calibration pass over the raw params."""
        if act_calibration is None:
            return None
        if not self.prepared:
            # refusing beats silently measuring the dynamic path: the
            # scales only take effect through prepared containers
            raise ValueError("act_calibration requires prepared weights "
                             "(prepare_weights=True)")
        if isinstance(act_calibration, dict):
            return dict(act_calibration)
        if act_calibration != "auto":
            raise ValueError(
                f"act_calibration must be None, a dict or 'auto', got "
                f"{act_calibration!r}")
        if not self._routes_int(params):
            # nothing would consume the scales (e.g. a pure-bf16
            # policy): skip the pass and keep act_calibrated honest
            return None
        pol = self.cfg.precision_policy
        if pol.startswith("plan:"):
            from repro.autotune.plan import load_act_scales
            scales = load_act_scales(pol[len("plan:"):])
            if scales:
                return scales
        from repro.quant.calibrate import calibrate_act_scales
        return calibrate_act_scales(self.cfg, self.api, params)

    def _resolve_fused(self, params) -> bool:
        mode = self.config.fused_executors
        if mode == "off":
            return False
        if mode == "on":
            if not self.prepared:
                raise ValueError(
                    "fused_executors='on' requires prepared weights "
                    "(the fused kernels consume prepared storage)")
            return True
        return self.prepared and (self.act_scales is not None
                                  or self._routes_fp(params))

    def _routes_fp(self, params) -> bool:
        """Does the policy route any projection to an fp storage mode
        (fp8/fp4)? Those fuse without calibrated activation scales."""
        from repro.quant.prepare import iter_projection_weights
        paths = registry.projection_paths(self.cfg)
        return any(
            self.policy.spec_for(paths(prefix)).mode in ("fp8", "fp4")
            for prefix, _ in iter_projection_weights(params, paths))

    def _routes_int(self, params) -> bool:
        """Does the policy route any projection of this param tree to an
        int mode? (Pure tree walk + spec resolution; no compute.)"""
        from repro.quant.prepare import iter_projection_weights
        paths = registry.projection_paths(self.cfg)
        return any(
            self.policy.spec_for(paths(prefix)).weight_bits
            for prefix, _ in iter_projection_weights(params, paths))

    def _dynamic_fake_int_paths(self, params) -> set:
        """Policy paths routed to fake-quant int modes whose activation
        scale stays dynamic (no calibrated scale covers them) — the
        projections whose per-tensor absmax couples batch rows. MoE
        expert stacks are exempt: ``moe.forward`` fake-quants weights
        only (activations ride the bf16 einsums untouched), so there is
        no row coupling — and no mp_linear call for calibration to ever
        cover."""
        from repro.quant.prepare import iter_projection_weights
        paths = registry.projection_paths(self.cfg)
        scales = self.act_scales or {}
        out = set()
        for prefix, _ in iter_projection_weights(params, paths):
            pol_path = paths(prefix)
            if pol_path == "block/moe/experts":
                continue
            spec = self.policy.spec_for(pol_path)
            if (spec.weight_bits and not spec.exact
                    and pol_path not in scales):
                out.add(pol_path)
        return out

    # ------------------------------------------------------- observability

    def _trace_decode(self, hook):
        """Trace ONE decode step abstractly (``jax.eval_shape`` — no
        compute runs, the KV caches are untouched) under a capture
        context manager and return whatever the context yielded. The
        shared scaffolding of every trace-time assertion surface:
        routing, weight-quant and act-quant counters.

        Traces the program the engine actually dispatches: the plain
        ``decode_step`` at ``decode_block=1``, or the blocked scan
        program — staging walk included — on the fast path, so the
        counter contracts keep covering what really runs (a staging
        regression that dropped scales or storage would fire here)."""
        with hook() as captured:
            if self.decode_block > 1:
                fn = registry.make_block_decode(self.api, 1,
                                                policy=self.policy,
                                                fused=self.fused)
                zeros = jnp.zeros((self.b,), jnp.int32)
                carry = registry.DecodeCarry(
                    tok=zeros, pos=zeros,
                    rem=jnp.ones((self.b,), jnp.int32),
                    taken=zeros,
                    stops=jnp.full((self.b, MAX_STOP_IDS), -1, jnp.int32),
                    temp=jnp.zeros((self.b,), jnp.float32),
                    top_k=zeros,
                    top_p=jnp.ones((self.b,), jnp.float32),
                    keys=jnp.zeros((self.b, 2), jnp.uint32))
                jax.eval_shape(lambda p, c: fn(p, carry, c),
                               self.params, self.caches)
            else:
                tok = jnp.zeros((self.b, 1), jnp.int32)
                pos = jnp.zeros((self.b,), jnp.int32)
                jax.eval_shape(
                    _with_variant(
                        lambda p, c: self.api.decode_step(
                            p, {"token": tok, "pos": pos}, c),
                        self._variant),
                    self.params, self.caches)
        return captured

    def routing_report(self) -> Dict[str, str]:
        """Observed (parameter path -> datapath mode) of one decode step
        under the active policy — the verification surface the
        plan-routing assertion tests use."""
        return dict(self._trace_decode(policy_mod.trace_routing))

    def weight_bytes(self) -> Dict:
        """Weight memory resident in this replica's param tree: total
        bytes, the policy-routed projection subset, and a per-storage-
        kind breakdown ('raw' = unprepared fp32/bf16). Computed once at
        construction — params are immutable after preparation."""
        return self._weight_bytes

    def weight_quant_trace_count(self) -> int:
        """Dynamic weight quantizations traced into ONE decode step —
        the counter hook the serving-smoke contract asserts is zero for
        prepared replicas."""
        from repro.layers import mplinear
        return self._trace_decode(mplinear.count_weight_quant)[0]

    def act_quant_trace_count(self) -> int:
        """Dynamic activation-scale calibrations (per-token absmax
        reduces) traced into ONE decode step — zero for calibrated
        replicas (static scales), > 0 for any dynamically-scaled int
        projection."""
        from repro.layers import mplinear
        return self._trace_decode(mplinear.count_act_quant)[0]

    def staged_trace_count(self) -> int:
        """Staged compute-dtype operand materializations traced into ONE
        decode dispatch (the ``quant.prepare.count_staged`` hook through
        the same program the engine runs). Zero on the fused datapath —
        prepared storage enters the kernels directly — and > 0 for any
        staged-path blocked engine with fake-quant int/fp projections."""
        from repro.quant import prepare
        return self._trace_decode(prepare.count_staged)[0]

    def metrics(self) -> Dict:
        """Aggregate request latency metrics + engine counters (the
        ``counters`` block keeps the pre-registry plain-dict schema),
        plus the measured replica stats the router's online cost
        correction reads."""
        from repro.serving.metrics import summarize_requests
        m = summarize_requests(self.completed.values())
        m["counters"] = dict(self.counters)
        m["queue"] = len(self.scheduler)
        m["queue_highwater"] = self.scheduler.depth_highwater
        m["active_slots"] = sum(r is not None for r in self.slot_req)
        m["prepared_weights"] = self.prepared
        m["act_calibrated"] = self.act_scales is not None
        m["fused_executors"] = self.fused
        m["decode_block"] = self.decode_block
        m["mid_block_admission"] = self.config.mid_block_admission
        m["eos_stopping"] = self.config.eos_stopping
        m["weight_bytes"] = self.weight_bytes()
        m["replica_stats"] = self.stats.snapshot()
        m["trace"] = {"enabled": self.tracer.enabled,
                      "events": len(self.tracer.events),
                      "dropped": self.tracer.dropped}
        return m

    def dump_trace(self, path: str) -> str:
        """Export the recorded spans as Chrome trace-event JSON (load
        at https://ui.perfetto.dev or ``chrome://tracing``); requires
        ``EngineConfig(trace=True)``."""
        if not self.tracer.enabled:
            raise RuntimeError(
                "tracing is off — construct the engine with "
                "EngineConfig(trace=True)")
        return self.tracer.dump(path)

    def has_pending(self) -> bool:
        return (len(self.scheduler) > 0
                or any(r is not None for r in self.slot_req))

    # ------------------------------------------------------------ admission

    def _capacity_needed(self, req: Request) -> int:
        """Cache positions the request will write: prompt prefill at
        0..S-2, decode at S-1..S-2+budget. Beyond ``cache_len`` the ring
        write (pos % capacity) overwrites early context — the request
        still serves, with trailing-window semantics, and is stamped
        ``truncated`` at admission."""
        if req.budget <= 0:
            return 0
        return max(len(req.prompt) - 1, 0) + req.budget

    def submit(self, req: Request):
        if not isinstance(req.sampling, SamplingParams):
            raise TypeError(
                f"req{req.rid}.sampling must be a SamplingParams, got "
                f"{type(req.sampling).__name__}")
        if len(self._merged_stops(req)) > MAX_STOP_IDS:
            raise ValueError(
                f"req{req.rid}: stop_ids + engine eos_id exceed the "
                f"{MAX_STOP_IDS} per-slot stop slots")
        self.scheduler.submit(req, now=self.clock())
        self.counters["submitted"] += 1
        self.tracer.req_begin(req.rid, "queued",
                              args={"prompt_len": len(req.prompt),
                                    "budget": req.budget})

    def _merged_stops(self, req: Request) -> Tuple[int, ...]:
        stops = list(req.sampling.stop_ids)
        if self.config.eos_id is not None \
                and self.config.eos_id not in stops:
            stops.append(self.config.eos_id)
        return tuple(stops)

    def _install_sampling(self, slot: int, req: Request):
        sp = req.sampling
        self._temp[slot] = sp.temperature
        self._topk[slot] = sp.top_k
        self._topp[slot] = sp.top_p
        stops = self._merged_stops(req) if self.config.eos_stopping \
            else ()
        self._stops[slot] = -1
        self._stops[slot, :len(stops)] = stops
        self._stop_sets[slot] = frozenset(stops)
        # per-request key derivation: explicit seed, else engine seed
        # folded with the rid — placement- and block-size-independent
        if sp.seed is not None:
            key = jax.random.PRNGKey(sp.seed)
        else:
            key = jax.random.fold_in(
                jax.random.PRNGKey(self.config.seed),
                req.rid & 0xFFFFFFFF)   # fold_in wants uint32-range data
        self._keys[slot] = np.asarray(key, np.uint32)

    def _clear_sampling(self, slot: int):
        self._temp[slot] = 0.0
        self._topk[slot] = 0
        self._topp[slot] = 1.0
        self._stops[slot] = -1
        self._keys[slot] = 0
        self._stop_sets[slot] = frozenset()

    def _admit(self):
        free = [s for s in range(self.b) if self.slot_req[s] is None]
        if not free:
            return
        now = self.clock()
        teacher: List[Tuple[int, Request]] = []
        for req in self.scheduler.select(len(free), now):
            req.admit_time = now
            req.tokens = [int(t) for t in req.prompt]
            self.counters["admitted"] += 1
            self.tracer.req_end(req.rid, "queued")
            if req.budget <= 0 or len(req.prompt) == 0:
                # nothing to generate: complete without holding a slot
                req.done = True
                req.finish_reason = "length"
                req.finish_time = now
                self.completed[req.rid] = req
                self.tracer.req_instant(req.rid, "finished",
                                        args={"reason": "length"})
                continue
            if self._capacity_needed(req) > self.cache_len:
                # chunked prefill lifted the old admission bound: the
                # request serves with trailing-window (ring) context
                req.truncated = True
            slot = free.pop(0)
            self.slot_req[slot] = req
            self._install_sampling(slot, req)
            if self._last_block_short:
                self.counters["mid_block_admits"] += 1
            req.prefill_pos = 0
            self.tracer.req_begin(req.rid, "prefill",
                                  args={"slot": slot})
            if len(req.prompt) == 1:
                self.pos[slot] = 0
                req.next_input = int(req.prompt[0])
                self._req_decode_start(req)
            elif self._fast_prefill:
                # chunked continuation: the slot enters the prefilling
                # state (next_input None) and advances one wave per
                # tick in _prefill_tick; pos tracks the frontier so the
                # idle decode write it receives meanwhile lands on a
                # position the next chunk overwrites
                self.pos[slot] = 0
                req.next_input = None
            else:
                # teacher-forced fallback (recurrent-state families)
                self.pos[slot] = 0
                req.next_input = int(req.prompt[-1])
                teacher.append((slot, req))
        for slot, req in teacher:
            for t in req.prompt[:-1]:
                self._step_slot_token(slot, int(t))
            req.prefill_pos = len(req.prompt) - 1
            self.counters["teacher_forced_tokens"] += len(req.prompt) - 1
            self._req_decode_start(req)

    def _req_decode_start(self, req: Request):
        """Request lifecycle transition: prompt fully consumed, the slot
        is decodable from the next tick on."""
        req.prefill_done_time = self.clock()
        if self.tracer.enabled:
            self.tracer.req_end(req.rid, "prefill")
            self.tracer.req_begin(req.rid, "decode")

    def _prefill_dispatch(self, tokens, offs, lens, rows):
        """One wave: the full-width program on every row, else the
        compact program on ``rows``. Returns the new caches."""
        if len(rows) == self.b:
            return self._prefill_chunk_fn(
                self.params, jnp.array(tokens), jnp.array(offs),
                jnp.array(lens), self.caches)
        return self._prefill_rows_fn(
            self.params, jnp.array(tokens), jnp.array(offs),
            jnp.array(lens), jnp.array(rows, jnp.int32), self.caches)

    def _warm_prefill(self):
        """Compile every row bucket before the first wave, so that no
        later wave compiles whichever bucket it takes. Each dispatch
        carries no valid token and its result is dropped: it writes
        nothing and counts in no counter."""
        for n in self._prefill_buckets:
            zeros = np.zeros(n, np.int32)
            self._prefill_dispatch(np.zeros((n, self.prefill_chunk),
                                            np.int32),
                                   zeros, zeros, np.arange(n))
        self._prefill_warm = True

    def _prefill_tick(self) -> bool:
        """Advance every prefilling slot by one chunk in ONE jitted
        dispatch over the smallest row bucket that holds them, padded
        with distinct idle slots of length 0; slots whose prompt
        completes become decodable this tick."""
        pref = [(s, r) for s, r in enumerate(self.slot_req)
                if r is not None and r.next_input is None]
        if not pref:
            return False
        if not self._prefill_warm:
            self._warm_prefill()
        n = next(b for b in self._prefill_buckets if b >= len(pref))
        busy = {s for s, _ in pref}
        idle = [s for s in range(self.b) if s not in busy]
        rows = sorted(busy.union(idle[:n - len(pref)]))
        at = {s: i for i, s in enumerate(rows)}
        chunk = self.prefill_chunk
        tokens = np.zeros((n, chunk), np.int32)
        offs = np.zeros(n, np.int32)
        lens = np.zeros(n, np.int32)
        total = 0
        for s, req in pref:
            todo = len(req.prompt) - 1 - req.prefill_pos
            take = min(chunk, todo)
            tokens[at[s], :take] = np.asarray(
                req.prompt[req.prefill_pos:req.prefill_pos + take],
                np.int32)
            offs[at[s]] = req.prefill_pos
            lens[at[s]] = take
            total += take
        with self.tracer.span("prefill_dispatch",
                              args={"tokens": total, "slots": len(pref),
                                    "rows": n}):
            self.caches = self._prefill_dispatch(tokens, offs, lens, rows)
        self.counters["prefill_calls"] += 1
        self.counters["prefill_tokens"] += total
        self.counters["prefill_rows"] += n
        for s, req in pref:
            req.prefill_pos += int(lens[at[s]])
            if req.prefill_pos >= len(req.prompt) - 1:
                self.pos[s] = len(req.prompt) - 1
                req.next_input = int(req.prompt[-1])
                self._req_decode_start(req)
            else:
                self.pos[s] = req.prefill_pos
        return True

    def _step_slot_token(self, slot: int, token: int) -> int:
        """Teacher-forced fallback: feed one prompt token through decode
        (recurrent-state families, where padded prefill is unsound)."""
        tok = np.zeros((self.b, 1), np.int32)
        tok[slot, 0] = token
        # jnp.array (never asarray): jax may alias an aligned numpy
        # buffer zero-copy, and self.pos mutates while the async decode
        # is still in flight — observed as corrupted cache position tags
        logits, self.caches = self._decode(
            self.params, jnp.array(tok), jnp.array(self.pos), self.caches)
        self.pos[slot] += 1
        self.counters["host_syncs"] += 1
        return int(np.asarray(jnp.argmax(logits[slot])))

    # --------------------------------------------------------- decode loop

    def _block_decode(self, n: int, sample: bool) -> Callable:
        fn = self._block_fns.get((n, sample))
        if fn is None:
            # pass the eagerly-resolved policy: a plan: file deleted
            # after construction must not fail the first dispatch
            kind = "sample" if sample else "greedy"
            fn = traced_jit(
                jax.jit(registry.make_block_decode(
                    self.api, n, policy=self.policy, sample=sample,
                    tracer=self.tracer, fused=self.fused)),
                f"block_decode[n={n},{kind}]", self.tracer)
            self._block_fns[(n, sample)] = fn
        return fn

    def _finish_slot(self, s: int, now: float, reason: str):
        req = self.slot_req[s]
        req.done = True
        req.finish_time = now
        req.finish_reason = reason
        if reason == "stop":
            self.counters["eos_stops"] += 1
        if self.tracer.enabled:
            self.tracer.req_end(req.rid, "decode")
            self.tracer.req_instant(
                req.rid, "finished",
                args={"reason": reason, "new_tokens": req.new_tokens})
        self.completed[req.rid] = req
        self.slot_req[s] = None
        self.pos[s] = 0
        self._clear_sampling(s)

    def _stop_hit(self, s: int, token: int) -> bool:
        return bool(self._stop_sets[s]) and token in self._stop_sets[s]

    def _choose_block(self, rem: np.ndarray) -> int:
        """Block length for this dispatch. Mid-block admission policy:
        while requests are queued, cut the block at the nearest
        completion (smallest positive budget) or the queue-depth-scaled
        boundary — ceil(decode_block / (1 + depth)) — whichever comes
        first, but never below HALF the configured block. The floor
        bounds the cost of the extra host syncs shorter blocks imply
        (on dispatch-overhead-dominated hosts unbounded cutting
        degrades both throughput and the TTFT it is meant to improve):
        queued work admits after at most ~half a block, for at most one
        extra sync per block."""
        alive = rem[rem > 0]
        full = int(min(self.decode_block, int(alive.max())))
        depth = len(self.scheduler)
        if self.config.mid_block_admission and depth > 0:
            cut = min(int(alive.min()),
                      -(-self.decode_block // (1 + depth)))
            return max(1, min(full, max(cut, self.decode_block // 2)))
        return max(full, 1)

    def _first_token(self, req: Request, now: float):
        req.first_token_time = now
        if req.submit_time is not None:
            self.stats.observe_ttft(now - req.submit_time)
        self.tracer.req_instant(req.rid, "first_token")

    def _sample_tick(self, new_tokens: int):
        """Per-tick measured stats: the ReplicaStats EWMA the router's
        online cost correction reads."""
        occupied = sum(r is not None for r in self.slot_req)
        self.stats.on_tick(self.clock(), new_tokens, len(self.scheduler),
                           active_slots=occupied)

    def step(self):
        """One engine tick: admit, advance prefilling slots one chunk,
        run one decode block (one host sync) for the decodable slots."""
        with self.tracer.span("admission"):
            self._admit()
        self.counters["ticks"] += 1
        prefilled = self._fast_prefill and self._prefill_tick()
        active = [s for s, r in enumerate(self.slot_req)
                  if r is not None and r.next_input is not None]
        if not active:
            self._sample_tick(0)
            return prefilled
        if self.decode_block > 1:
            return self._step_block(active)
        self._last_block_short = False
        tok = np.zeros((self.b, 1), np.int32)
        for s in active:
            tok[s, 0] = self.slot_req[s].next_input
        # copying jnp.array: self.pos mutates below while the dispatch
        # may still be reading it (see _step_slot_token)
        with self.tracer.span("block_dispatch", args={"n": 1}):
            logits, self.caches = self._decode(
                self.params, jnp.array(tok), jnp.array(self.pos),
                self.caches)
        self.counters["decode_steps"] += 1
        self.counters["host_syncs"] += 1
        with self.tracer.span("host_sync"):
            if any(self._temp[s] > 0 for s in active):
                keys2, nxt = self._select(
                    jnp.array(self._keys), logits, jnp.array(self._temp),
                    jnp.array(self._topk), jnp.array(self._topp))
                nxt = np.asarray(nxt)
                keys2 = np.asarray(keys2)
                for s in active:
                    self._keys[s] = keys2[s]
            else:
                nxt = np.asarray(jnp.argmax(logits, axis=-1))
        now = self.clock()
        with self.tracer.span("harvest"):
            for s in active:
                req = self.slot_req[s]
                self.pos[s] += 1
                if req.first_token_time is None:
                    self._first_token(req, now)
                t = int(nxt[s])
                req.tokens.append(t)
                req.next_input = t
                if self.config.eos_stopping and self._stop_hit(s, t):
                    self._finish_slot(s, now, "stop")
                elif req.new_tokens >= req.budget:
                    self._finish_slot(s, now, "length")
        self._sample_tick(len(active))
        return True

    def _step_block(self, active: List[int]) -> bool:
        """Fast path: run one decode block in ONE dispatch (jitted scan
        with on-device selection + active masks + stop ids) and sync
        the token trajectory once. Each slot's active prefix of the
        block comes back in ``carry.taken`` (EOS stopping means the
        host can no longer derive it from budgets alone)."""
        rem = np.zeros(self.b, np.int32)
        tok = np.zeros(self.b, np.int32)
        for s in active:
            req = self.slot_req[s]
            rem[s] = req.budget - req.new_tokens
            tok[s] = req.next_input
        n = self._choose_block(rem)
        full = int(min(self.decode_block, int(rem.max())))
        self._last_block_short = n < full
        if self._last_block_short:
            self.counters["short_blocks"] += 1
        sample = bool(any(self._temp[s] > 0 for s in active))
        carry = registry.DecodeCarry(
            tok=jnp.array(tok), pos=jnp.array(self.pos),
            rem=jnp.array(rem),
            taken=jnp.zeros(self.b, jnp.int32),
            stops=jnp.array(self._stops), temp=jnp.array(self._temp),
            top_k=jnp.array(self._topk), top_p=jnp.array(self._topp),
            keys=jnp.array(self._keys))
        with self.tracer.span("block_dispatch", args={"n": n}):
            tokens, out, self.caches = self._block_decode(n, sample)(
                self.params, carry, self.caches)
        with self.tracer.span("host_sync"):
            tokens = np.asarray(tokens)      # ONE host sync per block
            taken = np.asarray(out.taken)
            rem_after = np.asarray(out.rem)
            keys_after = np.asarray(out.keys)
        self.counters["decode_steps"] += n
        self.counters["host_syncs"] += 1
        now = self.clock()
        harvested = 0
        with self.tracer.span("harvest"):
            for s in active:
                req = self.slot_req[s]
                steps = int(taken[s])        # this slot's active prefix
                harvested += steps
                if req.first_token_time is None:
                    self._first_token(req, now)
                req.tokens.extend(int(t) for t in tokens[:steps, s])
                req.next_input = int(tokens[steps - 1, s])
                self.pos[s] += steps
                self._keys[s] = keys_after[s]
                if int(rem_after[s]) == 0:
                    last = int(tokens[steps - 1, s])
                    reason = "stop" if (self.config.eos_stopping
                                        and self._stop_hit(s, last)) \
                        else "length"
                    self._finish_slot(s, now, reason)
        self._sample_tick(harvested)
        return True

    def run_until_drained(self, max_ticks: int = 10_000):
        ticks = 0
        while self.has_pending():
            self.step()
            ticks += 1
            if ticks > max_ticks:
                raise RuntimeError("engine did not drain")
        return ticks
