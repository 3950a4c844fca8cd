"""Public jit'd wrappers around the Pallas kernels.

``backend`` selection:
  * 'pallas'  — pl.pallas_call. On this CPU container it runs in
    interpret mode (the kernel body executes as traced jnp ops); on TPU
    the same call compiles to Mosaic.
  * 'xla'     — the pure-jnp reference path (ref.py). Identical math;
    used for wall-time measurement on CPU (interpret mode adds
    interpreter overhead that would pollute §Perf numbers) and as the
    oracle in kernel tests.

Interpret mode follows the platform alone: the kernels compile to
Mosaic when JAX's default backend is a TPU and run in the Pallas
interpreter everywhere else. The choice reaches the kernels as a jit
*static* argument, so the wrappers' jit caches key on it and an outer
jit (e.g. the serving engine's decode program) bakes it in at trace
time.

Quantized matmul wrappers fold per-channel scales in an epilogue, which
is how the deployment path (quant/ + layers/mplinear.py) consumes them:
dynamically quantized weights through :func:`quantized_matmul`,
ahead-of-time nibble-packed weights (quant.prepare) through
:func:`quantized_matmul_packed`.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.ipu import IPUConfig
from repro.kernels import fused as _fused
from repro.kernels import mpmm as _mpmm
from repro.kernels import qmm as _qmm
from repro.kernels import ref as _ref

def kernel_interpret() -> bool:
    """Interpret the Pallas kernels unless the default backend is a
    TPU, where they compile."""
    return jax.default_backend() != "tpu"


def pack_int4(w: jax.Array) -> jax.Array:
    """Pack (..., K, N) int4-valued int8 weights into (..., K//2, N)
    bytes (two nibbles per byte along the contraction dim)."""
    if w.shape[-2] % 2:
        raise ValueError("K must be even to pack nibbles")
    return _ref.pack_int4_ref(w)


def unpack_int4(packed: jax.Array) -> jax.Array:
    return _ref.unpack_int4_ref(packed)


def pack_u4(codes: jax.Array) -> jax.Array:
    """Pack (..., K, N) UNSIGNED 4-bit codes (fp4 e2m1 bit fields) into
    (..., K//2, N) bytes — same nibble layout as :func:`pack_int4`, but
    unpacking never sign-extends."""
    if codes.shape[-2] % 2:
        raise ValueError("K must be even to pack nibbles")
    return _ref.pack_u4_ref(codes)


def unpack_u4(packed: jax.Array) -> jax.Array:
    return _ref.unpack_u4_ref(packed)


@functools.partial(jax.jit, static_argnames=("backend", "interpret"))
def _int8_matmul(a, b, *, backend: str, interpret: bool):
    if backend == "xla":
        return _ref.qmm_ref(a, b)
    return _qmm.qmm(a, b, interpret=interpret)


def int8_matmul(a: jax.Array, b: jax.Array, *, backend: str = "pallas"
                ) -> jax.Array:
    """(M,K) int8 x (K,N) int8 -> (M,N) int32."""
    return _int8_matmul(a, b, backend=backend, interpret=kernel_interpret())


@functools.partial(jax.jit, static_argnames=("backend", "interpret"))
def _int4_matmul_packed(a, b_packed, *, backend: str, interpret: bool):
    if backend == "xla":
        return _ref.qmm_ref(a, _ref.unpack_int4_ref(b_packed))
    return _qmm.qmm_packed(a, b_packed, interpret=interpret)


def int4_matmul_packed(a: jax.Array, b_packed: jax.Array, *,
                       backend: str = "pallas") -> jax.Array:
    """(M,K) int8 activations x (K//2,N) packed int4 weights -> int32."""
    return _int4_matmul_packed(a, b_packed, backend=backend,
                               interpret=kernel_interpret())


def _scale_epilogue(acc: jax.Array, scale_a: jax.Array,
                    scale_b: jax.Array) -> jax.Array:
    """Fold activation/weight scales into the int32 accumulator.

    ``scale_a`` is either per-row (M,) — dynamic per-token absmax — or a
    0-d scalar: a *calibrated static* activation scale (quant.calibrate)
    rides straight in with no broadcast and no per-row gather.

    The two scales multiply first, then the accumulator: XLA's algebraic
    simplifier reassociates ``acc * sa * sw`` into this order anyway, so
    writing it out keeps the fused kernels' epilogue bit-identical."""
    scale_a = jnp.asarray(scale_a, jnp.float32)
    if scale_a.ndim:
        scale_a = scale_a[:, None]
    return acc.astype(jnp.float32) * (
        scale_a * scale_b[None, :].astype(jnp.float32))


def quantized_matmul(a_q: jax.Array, b_q: jax.Array, scale_a: jax.Array,
                     scale_b: jax.Array, *, backend: str = "pallas"
                     ) -> jax.Array:
    """Dequantizing matmul: int8/int4-valued operands with per-row (M,)
    or scalar (static calibrated) activation scales and per-column (N,)
    weight scales -> f32."""
    return _scale_epilogue(int8_matmul(a_q, b_q, backend=backend),
                           scale_a, scale_b)


def quantized_matmul_packed(a_q: jax.Array, b_packed: jax.Array,
                            scale_a: jax.Array, scale_b: jax.Array, *,
                            backend: str = "pallas") -> jax.Array:
    """Dequantizing matmul over prepared nibble-packed weights: same
    epilogue as :func:`quantized_matmul`, so prepared int4 serving is
    bit-exact to the dynamic-quantization path on the same values."""
    return _scale_epilogue(
        int4_matmul_packed(a_q, b_packed, backend=backend),
        scale_a, scale_b)


@functools.partial(jax.jit,
                   static_argnames=("kind", "backend", "interpret"))
def _fused_quantized_matmul(x, w, sw, sa, *, kind: str, backend: str,
                            interpret: bool):
    if backend == "xla":
        return _ref.fused_qmm_ref(x, w, sw, sa, kind=kind)
    return _fused.fused_qmm(x, w, sw, sa, kind=kind, interpret=interpret)


def fused_quantized_matmul(x: jax.Array, w: jax.Array, sw: jax.Array,
                           sa, *, kind: str = "int8",
                           backend: str = "pallas") -> jax.Array:
    """Fused exact-int matmul over STORED operands: f32 activations are
    quantized in-register against the calibrated static scale ``sa``,
    the int32 accumulation runs on int8 rows (``kind='int8'``/``'int4'``)
    or nibble-packed int4 bytes (``'int4_packed'``) unpacked in the VMEM
    block loop, and the per-channel scale epilogue is fused. Bit-exact
    to ``quantize_symmetric(x, 8, scale=sa)`` + ``quantized_matmul`` /
    ``quantized_matmul_packed`` — with no staged operand and no
    materialized int activation tensor."""
    return _fused_quantized_matmul(x, w, sw, sa, kind=kind,
                                   backend=backend,
                                   interpret=kernel_interpret())


@functools.partial(jax.jit,
                   static_argnames=("kind", "act", "backend", "interpret"))
def _fused_dequant_matmul(x, w, sw, sa, *, kind: str, act: str,
                          backend: str, interpret: bool):
    if backend == "xla":
        return _ref.fused_dequant_mm_ref(x, w, sw, sa, kind=kind, act=act)
    return _fused.fused_dequant_mm(x, w, sw, sa, kind=kind, act=act,
                                   interpret=interpret)


def fused_dequant_matmul(x: jax.Array, w: jax.Array, sw: jax.Array,
                         sa=None, *, kind: str = "int8",
                         act: str = "none",
                         backend: str = "pallas") -> jax.Array:
    """General fused dequant matmul: any storage kind (int8/int4/
    int4_packed/fp8/fp4/fp4_packed) with per-channel ((1, N)) or
    per-group ((G, N)) scales decoded + dequantized in-register; the
    optional activation step (``act``: 'none' | 'qdq' fake-quant grid |
    'quant' exact int) fuses against the static scale ``sa``."""
    return _fused_dequant_matmul(x, w, sw, sa, kind=kind, act=act,
                                 backend=backend,
                                 interpret=kernel_interpret())


@functools.partial(jax.jit,
                   static_argnames=("cfg", "fused", "backend", "interpret"))
def _mp_matmul(a, b, cfg, *, fused: bool, backend: str, interpret: bool):
    if backend == "xla":
        return _ref.mp_matmul_xla(a, b, cfg, fused=fused)
    return _mpmm.mp_matmul(a, b, cfg, fused=fused, interpret=interpret)


def mp_matmul(a: jax.Array, b: jax.Array, cfg: IPUConfig = IPUConfig(),
              *, fused: bool = False, backend: str = "pallas"
              ) -> jax.Array:
    """Approximate FP-IP matmul (fidelity path): f16 x f16 -> accum fmt.

    ``fused=False`` is the paper-faithful nine-plane datapath;
    ``fused=True`` the optimized single-plane variant (§Perf)."""
    return _mp_matmul(a, b, cfg, fused=fused, backend=backend,
                      interpret=kernel_interpret())
