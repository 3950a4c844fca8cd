"""Pallas TPU kernels: fused dequant-matmul over packed storage.

The deployment gap these close: the blocked decode fast path used to
*stage* a full compute-dtype copy of every quantized projection per
decode block (``quant.prepare.stage_params``), so nibble-packed int4
weights paid bf16 bandwidth through the memory hierarchy at matmul
time. These kernels take the STORED operands — int8 rows, nibble-packed
int4 bytes, fp8 (e4m3) codes, nibble-packed fp4 (e2m1) codes — plus
per-channel or per-group scales as kernel inputs, unpack/decode and
dequantize in-register inside the VMEM block loop, and fuse the scale
epilogue. The calibrated static activation-quant step rides in the same
loop: activations arrive f32 and are quantized against the stored
scalar scale in-register, so no staged operand and no separately
materialized quantized activation ever exists.

Two kernels:

* :func:`fused_qmm` — the exact-INT datapath (per-channel scales,
  static act scale): in-register activation quantize, int32 MXU
  accumulation across k blocks, epilogue ``acc * (sa * sw)`` — BIT-EXACT
  to ``quantize_symmetric(scale=sa)`` + ``qmm.qmm[_packed]`` +
  ``ops._scale_epilogue`` (same elementwise ops in the same order).
* :func:`fused_dequant_mm` — the general f32 datapath (any storage
  kind, per-channel or per-group scales, optional in-register
  activation quantize or quantize-dequantize): weights decode to f32 in
  the block, scales broadcast over their K-groups, f32 accumulation.

Blocking mirrors qmm.py: grid (M/bm, N/bn, K/bk) with k innermost and
sequential. The f32 accumulator of :func:`fused_dequant_mm` is its
revisited output block; the int32 accumulator of :func:`fused_qmm`
lives in VMEM scratch. Int operands reach the MXU as int8 (activations
quantize to int8, int4 nibbles unpack to int8) with int32 accumulation.
Per-group scales constrain bk to a multiple of the group size (the
wrappers pick ``bk = g * max(1, 256 // g)``) so every k block covers
whole groups; the (G, N) scales are viewed as (K/bk, bk // g, N) so a
k block's scale tile is the whole trailing (bk // g, bn) slab, which
the TPU tiling rule admits for any group count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.qmm import _int8_dot, _pad_to, _unpack_int4_block
from repro.quant.quantize import FP4_E2M1, FP8_E4M3, fp_decode

# storage kinds the kernels decode in-register
KINDS = ("int8", "int4", "int4_packed", "fp8", "fp4", "fp4_packed")
# kinds whose stored K axis is halved by nibble packing
PACKED_KINDS = ("int4_packed", "fp4_packed")


def _decode_block(w, kind: str) -> jax.Array:
    """Stored block -> f32 values (packed kinds double their K axis)."""
    if kind in ("int8", "int4"):
        return w.astype(jnp.float32)
    if kind == "int4_packed":
        return _unpack_int4_block(w).astype(jnp.float32)
    if kind in ("fp8", "fp4"):
        return fp_decode(w, FP8_E4M3 if kind == "fp8" else FP4_E2M1)
    if kind == "fp4_packed":
        p = w.astype(jnp.int32)
        lo = p & 0xF
        hi = (p >> 4) & 0xF
        k2, n = p.shape
        codes = jnp.stack([lo, hi], axis=1).reshape(2 * k2, n)
        return fp_decode(codes, FP4_E2M1)
    raise ValueError(f"unknown storage kind {kind!r}")


def _quantize_act(x, sa):
    """In-register mirror of ``quantize_symmetric(x, 8, scale=sa)``."""
    return jnp.clip(jnp.round(x / sa), -128.0, 127.0)


def _fused_qmm_kernel(x_ref, w_ref, sw_ref, sa_ref, o_ref, acc_ref, *,
                      kind: str):
    """Exact INT: quantize acts in-register to int8, int8 x int8 MXU
    dot into the int32 VMEM accumulator, fused ``acc * (sa * sw)``
    epilogue at the last k step."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    sa = sa_ref[0, 0]
    aq = _quantize_act(x_ref[...].astype(jnp.float32), sa)
    w = w_ref[...]
    b = _unpack_int4_block(w) if kind == "int4_packed" else w
    acc_ref[...] += _int8_dot(aq.astype(jnp.int8), b)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _epilogue():
        # identical op order to ops._scale_epilogue with a 0-d scale_a
        o_ref[...] = acc_ref[...].astype(jnp.float32) * (
            sa * sw_ref[...].astype(jnp.float32))


def _fused_dequant_kernel(x_ref, w_ref, sw_ref, sa_ref, o_ref, *,
                          kind: str, act: str, groups_per_block: int):
    """General path: decode + dequantize weights in-register (scales
    broadcast over their K-groups), optional in-register activation
    quantize ('quant': int-valued f32 acts, sa folded in the epilogue)
    or quantize-dequantize ('qdq': the fake-quant grid), f32 dot."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)
    if act != "none":
        x = _quantize_act(x, sa_ref[0, 0])
        if act == "qdq":
            x = x * sa_ref[0, 0]
    w = _decode_block(w_ref[...], kind)            # (bk, bn) f32
    sw = sw_ref[...].astype(jnp.float32)           # (bk // g, bn)
    bk, bn = w.shape
    g = bk // groups_per_block
    wf = (w.reshape(groups_per_block, g, bn)
          * sw[:, None, :]).reshape(bk, bn)
    o_ref[...] += jax.lax.dot_general(
        x, wf, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    if act == "quant":
        @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
        def _epilogue():
            o_ref[...] = o_ref[...] * sa_ref[0, 0]


def _stored_k(w, kind: str) -> int:
    return w.shape[0] * (2 if kind in PACKED_KINDS else 1)


def _group_bk(k: int, sw, bk: int) -> int:
    """k-block size honoring the scale layout: per-channel scales
    ((1, N)) leave ``bk`` alone; per-group scales ((G, N), G groups
    along K) need bk to be a multiple of g = K / G."""
    groups = sw.shape[0]
    if groups <= 1:
        return bk
    if k % groups:
        raise ValueError(f"per-group scales: K={k} not divisible by "
                         f"G={groups}")
    g = k // groups
    return g * max(1, bk // g)


@functools.partial(jax.jit, static_argnames=("kind", "bm", "bn", "bk",
                                             "interpret"))
def fused_qmm(x: jax.Array, w: jax.Array, sw: jax.Array, sa: jax.Array,
              *, kind: str = "int8", bm: int = 128, bn: int = 128,
              bk: int = 256, interpret: bool = True) -> jax.Array:
    """Exact fused int matmul: f32 acts x stored int weights -> f32.

    x: (M, K) f32; w: (K, N) int8 rows / (K//2, N) packed int4 bytes;
    sw: (1, N) or (N,) per-channel f32 scales; sa: scalar static act
    scale. Bit-exact to ``quantize_symmetric(x, 8, scale=sa)`` followed
    by ``ops.quantized_matmul[_packed]``.
    """
    assert kind in ("int8", "int4", "int4_packed"), kind
    m, k = x.shape
    n = w.shape[1]
    assert k == _stored_k(w, kind), (x.shape, w.shape, kind)
    assert bk % 2 == 0
    packed = kind == "int4_packed"
    x = _pad_to(x.astype(jnp.float32), (bm, bk))
    w = _pad_to(w, (bk // 2 if packed else bk, bn))
    sw = _pad_to(sw.astype(jnp.float32).reshape(1, -1), (1, bn))
    sa2 = jnp.asarray(sa, jnp.float32).reshape(1, 1)
    mp, kp = x.shape
    np_ = w.shape[1]
    wb = bk // 2 if packed else bk
    out = pl.pallas_call(
        functools.partial(_fused_qmm_kernel, kind=kind),
        grid=(mp // bm, np_ // bn, kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((wb, bn), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)),
            pl.BlockSpec((1, 1), lambda mi, ni, ki: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x, w, sw, sa2)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("kind", "act", "bm", "bn",
                                             "bk", "interpret"))
def fused_dequant_mm(x: jax.Array, w: jax.Array, sw: jax.Array,
                     sa, *, kind: str = "int8", act: str = "none",
                     bm: int = 128, bn: int = 128, bk: int = 256,
                     interpret: bool = True) -> jax.Array:
    """General fused dequant matmul: f32 acts x ANY stored kind -> f32.

    x: (M, K) f32; w: stored operand ((K, N), packed kinds (K//2, N));
    sw: (G, N) scales — G == 1 is per-channel, G > 1 splits K into
    equal groups; sa: scalar static act scale, consumed per ``act``:

      'none'  — activations ride through unquantized (fp storage tier);
      'qdq'   — fake-quant grid (quantize-dequantize against sa);
      'quant' — exact int-valued activations, sa folded in the epilogue.
    """
    assert kind in KINDS, kind
    assert act in ("none", "qdq", "quant"), act
    m, k = x.shape
    n = w.shape[1]
    assert k == _stored_k(w, kind), (x.shape, w.shape, kind)
    sw = jnp.asarray(sw, jnp.float32)
    if sw.ndim == 1:
        sw = sw.reshape(1, -1)
    groups = sw.shape[0]
    if groups > 1:
        bk = _group_bk(k, sw, bk)
        groups_per_block = bk // (k // groups)
    else:
        groups_per_block = 1                         # per-channel
    assert bk % 2 == 0
    packed = kind in PACKED_KINDS
    x = _pad_to(x.astype(jnp.float32), (bm, bk))
    w = _pad_to(w, (bk // 2 if packed else bk, bn))
    # padded K rows decode to zero-valued weights, so padded (zero)
    # scale groups are harmless
    sw = _pad_to(sw, (groups_per_block, bn))
    # one (groups_per_block, N) slab per k block: the block's trailing
    # dims then equal the array's, which the TPU tiling rule accepts
    # for any group count (a (groups_per_block, bn) tile of a (G, N)
    # array would need groups_per_block % 8 == 0)
    sw = sw.reshape(-1, groups_per_block, sw.shape[-1])
    sa2 = (jnp.zeros((1, 1), jnp.float32) if sa is None
           else jnp.asarray(sa, jnp.float32).reshape(1, 1))
    mp, kp = x.shape
    np_ = w.shape[1]
    wb = bk // 2 if packed else bk
    out = pl.pallas_call(
        functools.partial(_fused_dequant_kernel, kind=kind, act=act,
                          groups_per_block=groups_per_block),
        grid=(mp // bm, np_ // bn, kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((wb, bn), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((None, groups_per_block, bn),
                         lambda mi, ni, ki: (ki if groups > 1 else 0, 0,
                                             ni)),
            pl.BlockSpec((1, 1), lambda mi, ni, ki: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(x, w, sw, sa2)
    return out[:m, :n]
