"""Pallas TPU kernel: quantized integer matmul (the deployment path).

The accelerator's INT mode maps onto the TPU MXU, which natively consumes
int8 operands with int32 accumulation. INT4 operands ride in int8 lanes
(values range-checked) or arrive as packed nibbles (two INT4 weights per
int8 byte) that the kernel unpacks in-register — halving weight HBM/VMEM
traffic exactly as the paper's nibble storage halves SRAM.

Blocking: grid (M/bm, N/bn, K/bk); A block (bm, bk) and B block (bk, bn)
live in VMEM; the int32 output block (bm, bn) is revisited across the k
steps (k is the innermost, sequential grid dimension). All dims are
MXU-aligned multiples of 128 by default (bm=bn=128, bk=256 for ~0.4 MB
VMEM per operand block).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _int8_dot(a, b):
    """int8 x int8 -> int32 on the MXU (operands stay int8)."""
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)


def _unpack_int4_block(packed) -> jax.Array:
    """(bk//2, bn) packed bytes -> (bk, bn) int8 values. Nibbles are
    sign-extended in int32 registers, interleaved back along K, and
    narrowed to int8 for the MXU."""
    p = packed.astype(jnp.int32)
    lo = ((p & 0xF) ^ 8) - 8                 # sign-extend low nibble
    hi = p >> 4                              # arithmetic: sign-extended
    k2, n = p.shape
    return jnp.stack([lo, hi], axis=1).reshape(2 * k2, n).astype(jnp.int8)


def _qmm_kernel(a_ref, b_ref, o_ref):
    """o[m,n] += sum_k a[m,k] * b[k,n] in int32."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += _int8_dot(a_ref[...], b_ref[...])


def _qmm_packed_kernel(a_ref, bp_ref, o_ref):
    """Packed-INT4 weights: bp holds two nibbles per byte along K.

    bp[k2, n] byte = (w[2*k2+1] << 4) | (w[2*k2] & 0xF).
    """
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += _int8_dot(a_ref[...], _unpack_int4_block(bp_ref[...]))


def _pad_to(x: jax.Array, mults) -> jax.Array:
    pads = [(0, -dim % m) for dim, m in zip(x.shape, mults)]
    if any(p[1] for p in pads):
        x = jnp.pad(x, pads)
    return x


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def qmm(a: jax.Array, b: jax.Array, *, bm: int = 128, bn: int = 128,
        bk: int = 256, interpret: bool = True) -> jax.Array:
    """int8 x int8 -> int32 blocked matmul. a: (M, K), b: (K, N)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    a = _pad_to(a.astype(jnp.int8), (bm, bk))
    b = _pad_to(b.astype(jnp.int8), (bk, bn))
    mp, kp = a.shape
    _, np_ = b.shape
    grid = (mp // bm, np_ // bn, kp // bk)
    out = pl.pallas_call(
        _qmm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        interpret=interpret,
    )(a, b)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def qmm_packed(a: jax.Array, b_packed: jax.Array, *, bm: int = 128,
               bn: int = 128, bk: int = 256,
               interpret: bool = True) -> jax.Array:
    """int8 activations x packed-int4 weights -> int32.

    a: (M, K) int8; b_packed: (K//2, N) int8 (see pack_int4 in ops.py).
    K must be even.
    """
    m, k = a.shape
    kh, n = b_packed.shape
    assert k == 2 * kh, (a.shape, b_packed.shape)
    assert bk % 2 == 0
    a = _pad_to(a.astype(jnp.int8), (bm, bk))
    b_packed = _pad_to(b_packed.astype(jnp.int8), (bk // 2, bn))
    mp, kp = a.shape
    _, np_ = b_packed.shape
    grid = (mp // bm, np_ // bn, kp // bk)
    out = pl.pallas_call(
        _qmm_packed_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk // 2, bn), lambda mi, ni, ki: (ki, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        interpret=interpret,
    )(a, b_packed)
    return out[:m, :n]
