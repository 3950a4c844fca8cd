"""Mixed-precision linear layer: every model projection routes through
here, and the PrecisionSpec decides which datapath executes it.

Dispatch is a registry (``spec.mode -> executor``) instead of an
``if/elif`` ladder: new modes (int12, per-group scales, fp8) plug in via
:func:`register_executor` without touching any call site. Every executor
consumes either a raw fp32 weight *or* a ``quant.prepare.PreparedWeight``
container holding the weight in its deployment storage format — the
prepared path skips the per-call weight quantization entirely (decode
stops re-quantizing static weights every token) and, for packed INT4,
feeds nibbles straight to the packed kernel.

Paths:
  bf16 / fp32  — dense jnp.dot in the compute dtype.
  int8 / int4  — fake-quant (default; MXU + shardable + STE gradients)
                 or exact integer Pallas kernels (fidelity). Prepared
                 weights dequantize (fake-quant path, bit-exact to the
                 dynamic quantize-dequantize) or ride the int kernels
                 directly (exact path).
  fp16_ipu     — exact=False: fp16-cast operands, f32 accumulation (what
                 a w>=28 IPU computes up to accumulator granularity);
                 exact=True: bit-exact kernels.ops.mp_matmul.

Activations mirror the weight story one PR later: int executors
calibrate an absmax per call (dynamic scale) unless the PreparedWeight
carries a *calibrated static scale* (``quant.calibrate`` ->
``PreparedWeight.act_scale``), in which case the per-token reduce is
skipped and the scalar scale rides straight into the quantized-matmul
epilogue.

The ``count_weight_quant`` / ``count_act_quant`` hooks count dynamic
(per-call) weight / activation quantizations entering a trace — the
observability surface the serving-smoke CI contract uses to prove
prepared replicas never quantize weights per decode step and calibrated
replicas never absmax-reduce activations. ``collect_act_stats`` is the
calibration-time hook: while open, every ``mp_linear`` call records its
input absmax under the projection's policy path.
"""
from __future__ import annotations

import contextlib
import os
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.policy import PrecisionSpec
from repro.kernels import ops as kops
from repro.layers.common import dense_init
from repro.quant.prepare import PreparedWeight
from repro.quant.quantize import (FP_FORMATS, fake_quant, fp_dequantize,
                                  fp_quantize, quantize_symmetric)

# ------------------------------------------------------------- registry

_EXECUTORS: Dict[Tuple[str, Optional[str]], Callable] = {}
_EXECUTOR_VARIANT: Optional[str] = None


def register_executor(*modes: str, variant: Optional[str] = None):
    """Register an executor for one or more policy modes. The executor
    signature is ``fn(w, x, spec, compute_dtype) -> y`` where ``w`` is a
    raw (d_in, d_out) array or a PreparedWeight and ``x`` is
    (..., d_in); it returns (..., d_out) before bias/cast.

    ``variant`` registers an alternative datapath for the same mode
    (e.g. 'fused': the Pallas fused dequant-matmul executors); dispatch
    prefers the active variant (:func:`executor_variant`) and falls
    back to the base executor when the mode has no such variant."""
    def deco(fn):
        for m in modes:
            _EXECUTORS[(m, variant)] = fn
        return fn
    return deco


def executor_for(mode: str, variant: Optional[str] = None) -> Callable:
    if variant is not None:
        fn = _EXECUTORS.get((mode, variant))
        if fn is not None:
            return fn
    try:
        return _EXECUTORS[(mode, None)]
    except KeyError:
        known = sorted({m for m, v in _EXECUTORS if v is None})
        raise ValueError(
            f"no executor registered for precision mode {mode!r} "
            f"(known: {known})") from None


@contextlib.contextmanager
def executor_variant(name: Optional[str]):
    """Route every ``mp_linear`` dispatch traced while open through the
    named executor variant (modes without that variant keep their base
    executor). The serving engine opens this around its traced programs
    when ``EngineConfig.fused_executors`` resolves on — trace-time
    scoped, like the counter hooks."""
    global _EXECUTOR_VARIANT
    prev = _EXECUTOR_VARIANT
    _EXECUTOR_VARIANT = name
    try:
        yield
    finally:
        _EXECUTOR_VARIANT = prev


# ------------------------------------------- weight-quantization counter

_WEIGHT_QUANT_COUNT: Optional[List[int]] = None


@contextlib.contextmanager
def count_weight_quant():
    """Count dynamic weight quantizations traced while open. Prepared
    weights never hit this counter; raw weights under an int/fp16 spec
    bump it once per projection per traced forward."""
    global _WEIGHT_QUANT_COUNT
    prev = _WEIGHT_QUANT_COUNT
    box = [0]
    _WEIGHT_QUANT_COUNT = box
    try:
        yield box
    finally:
        _WEIGHT_QUANT_COUNT = prev


def note_weight_quant(n: int = 1):
    """Executors (and moe.forward) call this on the dynamic
    weight-quantize branch; a no-op outside count_weight_quant()."""
    if _WEIGHT_QUANT_COUNT is not None:
        _WEIGHT_QUANT_COUNT[0] += n


# ---------------------------------------- activation-quantization hooks

_ACT_QUANT_COUNT: Optional[List[int]] = None
_ACT_STATS: Optional[Dict[str, float]] = None


@contextlib.contextmanager
def count_act_quant():
    """Count dynamic activation-scale calibrations (per-call absmax
    reduces) traced while open. Calibrated containers (a PreparedWeight
    carrying ``act_scale``) never hit this counter; every other int
    projection bumps it once per traced forward."""
    global _ACT_QUANT_COUNT
    prev = _ACT_QUANT_COUNT
    box = [0]
    _ACT_QUANT_COUNT = box
    try:
        yield box
    finally:
        _ACT_QUANT_COUNT = prev


def note_act_quant(n: int = 1):
    """Executors call this on the dynamic activation-absmax branch; a
    no-op outside count_act_quant()."""
    if _ACT_QUANT_COUNT is not None:
        _ACT_QUANT_COUNT[0] += n


@contextlib.contextmanager
def collect_act_stats():
    """Record per-projection activation absmax while open (calibration).

    Yields a dict {policy path -> running absmax over every forward run
    inside the context}. Values arrive via ``jax.debug.callback`` so
    recording works inside ``lax.scan`` over stacked blocks (one record
    per executed iteration, concrete at runtime); callers should run
    their forwards eagerly and flush (``jax.effects_barrier``) before
    reading the dict."""
    global _ACT_STATS
    prev = _ACT_STATS
    stats: Dict[str, float] = {}
    _ACT_STATS = stats
    try:
        yield stats
    finally:
        _ACT_STATS = prev


def _note_act_absmax(path: Optional[str], x: jax.Array):
    if _ACT_STATS is None or path is None:
        return

    def record(amax):
        stats = _ACT_STATS
        if stats is not None:
            stats[path] = max(stats.get(path, 0.0), float(amax))

    jax.debug.callback(record, jnp.max(jnp.abs(x.astype(jnp.float32))))


# ------------------------------------------------------------ executors

def _rounded(v: jax.Array, dtype) -> jax.Array:
    """``v`` in f32, rounded to ``dtype``'s precision by an explicit op.

    XLA may skip a bf16 rounding inside a fusion (excess precision), and
    whether it does depends on how the surrounding program fused. The
    exact int paths round their input and output explicitly, so the
    staged and fused programs quantize the same activations and hand on
    the same values, whatever XLA fused around them."""
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(v.astype(jnp.float32),
                                    exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


def _weight_scale_vec(w: PreparedWeight) -> jax.Array:
    """(N,) per-out-channel scales from the stored keepdims layout."""
    return w.scale.reshape(-1)


@register_executor("bf16", "fp32")
def _dense_executor(w, x, spec: PrecisionSpec, compute_dtype):
    dt = jnp.bfloat16 if spec.mode == "bf16" else jnp.float32
    wf = w.dequant() if isinstance(w, PreparedWeight) else w
    return jnp.dot(x.astype(dt), wf.astype(dt),
                   preferred_element_type=jnp.float32)


@register_executor("int8", "int4")
def _int_executor(w, x, spec: PrecisionSpec, compute_dtype):
    bits = spec.weight_bits
    prepared = (isinstance(w, PreparedWeight)
                and w.weight_bits == bits)
    # calibrated static activation scale (quant.calibrate): quantize
    # against the stored grid instead of absmax-reducing per call
    act_scale = w.act_scale if prepared else None
    if not spec.exact:
        # fake-quant both operands; per-out-channel weight scales.
        # Prepared weights dequantize to the identical q * scale value;
        # staged containers (quant.prepare.stage_params, blocked
        # decode) already hold it in the compute dtype.
        if prepared and w.staged:
            wq = w.data
        elif prepared:
            wq = w.dequant()
        else:
            note_weight_quant()
            wraw = w.dequant() if isinstance(w, PreparedWeight) else w
            wq = fake_quant(wraw.astype(jnp.float32), bits, axis=0)
        if act_scale is None:
            note_act_quant()
        xq = fake_quant(x.astype(jnp.float32), 8, scale=act_scale)
        return jnp.dot(xq.astype(compute_dtype), wq.astype(compute_dtype),
                       preferred_element_type=jnp.float32)
    # exact integer kernel path: weight operands straight from storage
    # when prepared; activation scale static when calibrated (the scalar
    # rides straight into the quantized-matmul epilogue), absmax per
    # token row otherwise
    if prepared and w.staged:
        raise ValueError("staged containers carry dequantized operands; "
                         "exact integer kernels need int storage "
                         "(stage_params never stages exact specs)")
    lead = x.shape[:-1]
    x2 = _rounded(x, x.dtype).reshape(-1, x.shape[-1])
    if act_scale is None:
        note_act_quant()
        aq, sa = quantize_symmetric(x2, 8, axis=1)
        sa = sa[:, 0]
    else:
        aq, sa = quantize_symmetric(x2, 8, scale=act_scale)
    if prepared and w.scale_groups > 1:
        # per-group scales vary along K: the column-scale epilogue
        # can't fold them, so the fused dequant kernel consumes the
        # stored operand directly and the act scale rides outside
        y = kops.fused_dequant_matmul(aq.astype(jnp.float32), w.data,
                                      w.scale, None, kind=w.kind)
        y = y * (sa[:, None] if sa.ndim else sa)
    elif prepared and w.kind == "int4_packed":
        y = kops.quantized_matmul_packed(aq, w.data, sa,
                                         _weight_scale_vec(w))
    elif prepared:
        y = kops.quantized_matmul(aq, w.data, sa,
                                  _weight_scale_vec(w))
    else:
        note_weight_quant()
        wraw = w.dequant() if isinstance(w, PreparedWeight) else w
        wq, sw = quantize_symmetric(wraw, bits, axis=0)
        y = kops.quantized_matmul(aq, wq, sa, sw[0, :])
    return y.reshape(*lead, -1)


_FP_STORAGE_KINDS = ("fp8", "fp4", "fp4_packed",
                     "staged_fp8", "staged_fp4")


@register_executor("fp8", "fp4")
def _fp_executor(w, x, spec: PrecisionSpec, compute_dtype):
    """fp8 (e4m3) / fp4 (e2m1) weight-storage tier: weights live as
    bit-field codes + scales and dequantize to the compute dtype;
    activations ride through unquantized (weight-only storage modes).
    Raw weights fake-quant through the codec per call (the dynamic
    control path); staged containers carry the pre-dequantized block
    operand."""
    if isinstance(w, PreparedWeight) and w.kind in _FP_STORAGE_KINDS:
        wf = w.data if w.staged else w.dequant()
    else:
        note_weight_quant()
        wraw = w.dequant() if isinstance(w, PreparedWeight) else w
        fmt = FP_FORMATS[spec.mode]
        codes, s = fp_quantize(wraw.astype(jnp.float32), fmt, axis=0)
        wf = fp_dequantize(codes, s, fmt)
    return jnp.dot(x.astype(compute_dtype), wf.astype(compute_dtype),
                   preferred_element_type=jnp.float32)


# ------------------------------------------------ fused Pallas variants

def _fused_backend() -> str:
    """Backend for the fused executors, resolved at trace time:
    'pallas' (default; interpret mode on CPU — what CI exercises) or
    'xla' via ``REPRO_FUSED_BACKEND`` — the identical-math reference
    path benchmarks use for CPU wall time, where interpreter overhead
    would drown the datapath being measured."""
    return os.environ.get("REPRO_FUSED_BACKEND", "pallas")


@register_executor("int8", "int4", variant="fused")
def _int_fused_executor(w, x, spec: PrecisionSpec, compute_dtype):
    """Fused int datapath (kernels.fused): stored int8 rows / packed
    nibbles + scales enter the kernel as operands, the calibrated
    static activation scale quantizes in-register, and the epilogue is
    fused — no staged compute-dtype operand, no materialized int
    activation tensor. Exact per-channel specs are bit-exact to the
    staged exact path; fake-quant specs match it to f32-vs-bf16
    rounding. Falls back to the base executor when the projection has
    no prepared storage or no calibrated static scale (dynamic
    per-token scales need the per-row epilogue)."""
    bits = spec.weight_bits
    fusable = (isinstance(w, PreparedWeight) and w.weight_bits == bits
               and not w.staged and w.act_scale is not None
               and w.data.ndim == 2)
    if not fusable:
        return _int_executor(w, x, spec, compute_dtype)
    lead = x.shape[:-1]
    x2 = _rounded(x, x.dtype).reshape(-1, x.shape[-1])
    sa = w.act_scale
    backend = _fused_backend()
    if spec.exact and w.scale_groups == 1:
        y = kops.fused_quantized_matmul(x2, w.data, w.scale, sa,
                                        kind=w.kind, backend=backend)
    elif spec.exact:
        y = kops.fused_dequant_matmul(x2, w.data, w.scale, sa,
                                      kind=w.kind, act="quant",
                                      backend=backend)
    else:
        y = kops.fused_dequant_matmul(x2, w.data, w.scale, sa,
                                      kind=w.kind, act="qdq",
                                      backend=backend)
    return y.reshape(*lead, -1)


@register_executor("fp8", "fp4", variant="fused")
def _fp_fused_executor(w, x, spec: PrecisionSpec, compute_dtype):
    """Fused fp8/fp4 datapath: stored e4m3/e2m1 codes decode and
    dequantize in-register inside the kernel block loop (per-channel or
    per-group scales); no staged operand. Falls back to the base
    executor for raw/staged weights."""
    fusable = (isinstance(w, PreparedWeight)
               and w.kind in ("fp8", "fp4", "fp4_packed")
               and w.data.ndim == 2)
    if not fusable:
        return _fp_executor(w, x, spec, compute_dtype)
    lead = x.shape[:-1]
    x2 = x.astype(jnp.float32).reshape(-1, x.shape[-1])
    y = kops.fused_dequant_matmul(x2, w.data, w.scale, None,
                                  kind=w.kind, act="none",
                                  backend=_fused_backend())
    return y.reshape(*lead, -1)


@register_executor("fp16_ipu")
def _fp16_ipu_executor(w, x, spec: PrecisionSpec, compute_dtype):
    if isinstance(w, PreparedWeight) and w.kind == "fp16":
        w16 = w.data
    else:
        note_weight_quant()
        wraw = w.dequant() if isinstance(w, PreparedWeight) else w
        w16 = wraw.astype(jnp.float16)
    if not spec.exact:
        return jnp.dot(x.astype(jnp.float16), w16,
                       preferred_element_type=jnp.float32)
    cfg = spec.ipu
    lead = x.shape[:-1]
    x2 = x.astype(jnp.float16).reshape(-1, x.shape[-1])
    y = kops.mp_matmul(x2, w16, cfg, backend="xla")
    return y.astype(jnp.float32).reshape(*lead, -1)


# -------------------------------------------------------------- wrapper

def linear_init(key, d_in: int, d_out: int, bias: bool = False,
                dtype=jnp.float32):
    p = {"w": dense_init(key, d_in, d_out, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def mp_linear(params, x: jax.Array, spec: PrecisionSpec,
              compute_dtype=jnp.bfloat16,
              path: Optional[str] = None) -> jax.Array:
    """y = x @ w (+ b) under the precision spec. x: (..., d_in).

    ``path`` is the projection's policy path (the same string the call
    site resolved the spec with) — only consumed by the calibration
    hook (``collect_act_stats``) to key activation statistics.

    Runs under the named scope ``mp_linear.<kind>``: the weight's
    stored ``PreparedWeight.kind``, or ``dense`` for a raw weight."""
    w = params["w"]
    kind = w.kind if isinstance(w, PreparedWeight) else "dense"
    with jax.named_scope(f"mp_linear.{kind}"):
        _note_act_absmax(path, x)
        y = executor_for(spec.mode, _EXECUTOR_VARIANT)(
            w, x, spec, compute_dtype)
        b = params.get("b")
        if b is not None:
            y = y + b.astype(y.dtype)
        if spec.exact:
            y = _rounded(y, compute_dtype)
        return y.astype(compute_dtype)
