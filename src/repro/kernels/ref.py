"""Pure-jnp oracles for the Pallas kernels (no pallas_call anywhere).

Each kernel has a reference that computes the same math with plain jnp
ops; tests sweep shapes/dtypes and assert bit equality (integer/emulation
kernels are exact, so assert_array_equal, not allclose).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import fixedpoint as fx, fp16 as fpmod, nibble
from repro.core.ipu import (IPUConfig, NEG_INF_EXP, _shr_i32, accumulate,
                            fp16_inner_product)


def qmm_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    """int8 x int8 -> int32 exact matmul."""
    return jax.lax.dot_general(
        a.astype(jnp.int32), b.astype(jnp.int32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)


def pack_int4_ref(w: jax.Array) -> jax.Array:
    """Pack int4 weights (..., K, N) int8 in [-8, 7] -> (..., K//2, N)
    bytes; leading (stacked-block / expert) axes pass through."""
    lo = w[..., 0::2, :].astype(jnp.int32) & 0xF
    hi = w[..., 1::2, :].astype(jnp.int32) & 0xF
    return ((hi << 4) | lo).astype(jnp.int8)


def unpack_int4_ref(packed: jax.Array) -> jax.Array:
    p = packed.astype(jnp.int32)
    lo = ((p & 0xF) ^ 8) - 8
    hi = p >> 4
    k2, n = packed.shape[-2:]
    out = jnp.stack([lo, hi], axis=-2)          # (..., K//2, 2, N)
    return out.reshape(*packed.shape[:-2], 2 * k2, n).astype(jnp.int8)


def pack_u4_ref(codes: jax.Array) -> jax.Array:
    """Pack UNSIGNED 4-bit codes (..., K, N) in [0, 15] -> (..., K//2, N)
    bytes, same (hi << 4) | lo layout as :func:`pack_int4_ref`. Used for
    fp4 (e2m1) bit-field codes, whose high bit is a sign field — the
    int4 unpack's sign extension would corrupt codes >= 8."""
    lo = codes[..., 0::2, :].astype(jnp.int32) & 0xF
    hi = codes[..., 1::2, :].astype(jnp.int32) & 0xF
    return ((hi << 4) | lo).astype(jnp.uint8)


def unpack_u4_ref(packed: jax.Array) -> jax.Array:
    p = packed.astype(jnp.int32)
    lo = p & 0xF
    hi = (p >> 4) & 0xF
    k2, n = packed.shape[-2:]
    out = jnp.stack([lo, hi], axis=-2)
    return out.reshape(*packed.shape[:-2], 2 * k2, n).astype(jnp.uint8)


def fused_qmm_ref(x: jax.Array, w: jax.Array, sw: jax.Array,
                  sa: jax.Array, *, kind: str = "int8") -> jax.Array:
    """Oracle for kernels.fused.fused_qmm: the staged exact-int path —
    static-scale activation quantize, int32 matmul, scale epilogue —
    composed from the already-verified pieces, in the same op order."""
    sa = jnp.asarray(sa, jnp.float32)
    aq = jnp.clip(jnp.round(x.astype(jnp.float32) / sa), -128, 127)
    wq = unpack_int4_ref(w) if kind == "int4_packed" else w
    acc = qmm_ref(aq.astype(jnp.int8), wq)
    return acc.astype(jnp.float32) * (
        sa * sw.reshape(-1)[None, :].astype(jnp.float32))


def fused_dequant_mm_ref(x: jax.Array, w: jax.Array, sw: jax.Array,
                         sa, *, kind: str = "int8",
                         act: str = "none") -> jax.Array:
    """Oracle for kernels.fused.fused_dequant_mm: decode storage to
    f32, broadcast (G, N) scales over their K-groups, f32 matmul."""
    from repro.quant.quantize import FP4_E2M1, FP8_E4M3, fp_decode
    if kind == "int4_packed":
        wf = unpack_int4_ref(w).astype(jnp.float32)
    elif kind == "fp4_packed":
        wf = fp_decode(unpack_u4_ref(w), FP4_E2M1)
    elif kind in ("fp8", "fp4"):
        wf = fp_decode(w, FP8_E4M3 if kind == "fp8" else FP4_E2M1)
    else:
        wf = w.astype(jnp.float32)
    sw = jnp.asarray(sw, jnp.float32)
    if sw.ndim == 1:
        sw = sw.reshape(1, -1)
    k, n = wf.shape
    groups = sw.shape[0]
    wf = (wf.reshape(groups, k // groups, n)
          * sw[:, None, :]).reshape(k, n)
    xf = x.astype(jnp.float32)
    if act != "none":
        sa = jnp.asarray(sa, jnp.float32)
        xf = jnp.clip(jnp.round(xf / sa), -128, 127)
        if act == "qdq":
            xf = xf * sa
    y = jax.lax.dot_general(xf, wf, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return y * sa if act == "quant" else y


def mp_matmul_ref(a: jax.Array, b: jax.Array,
                  cfg: IPUConfig = IPUConfig()) -> jax.Array:
    """Oracle for the faithful mpmm kernel: the (already oracle-verified)
    core.ipu inner product, broadcast over (M, N). O(M*N*K) memory — test
    sizes only."""
    a = jnp.asarray(a, jnp.float16)
    b = jnp.asarray(b, jnp.float16)
    return fp16_inner_product(a[:, None, :], jnp.swapaxes(b, 0, 1)[None],
                              cfg)


def mp_matmul_xla(a: jax.Array, b: jax.Array,
                  cfg: IPUConfig = IPUConfig(), *, fused: bool = False
                  ) -> jax.Array:
    """Blocked pure-jnp FP-IP matmul — the same math as the mpmm kernel
    structured as a fori_loop over K-groups with (M, g, N) temporaries.

    ``fused=False``: the paper-faithful nine-plane datapath (bit-exact to
    mp_matmul_ref / core.ipu).
    ``fused=True``: the optimized single-plane mode: full 22-bit mantissa
    products, EHU alignment against the group max, truncation on a
    w_f = min(w, 26)-bit fused datapath
    (aligned_k = T(d_k * 2**(w_f - 22 - shift_k))), group sums entering
    the standard accumulator with pre_shift = 1 + w_f - w.
    """
    a = jnp.asarray(a, jnp.float16)
    b = jnp.asarray(b, jnp.float16)
    m, k = a.shape
    _, n = b.shape
    g = cfg.n
    pad = -k % g
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad)))
        b = jnp.pad(b, ((0, pad), (0, 0)))
    kp = a.shape[1]
    sa, ea, ma = fpmod.decompose(a, fpmod.FP16)
    sb, eb, mb = fpmod.decompose(b, fpmod.FP16)
    ea = ea.reshape(m, kp // g, g)
    eb = eb.reshape(kp // g, g, n)

    if fused:
        da = (sa * ma).reshape(m, kp // g, g)
        db = (sb * mb).reshape(kp // g, g, n)
    else:
        pa = jnp.stack(nibble.fp16_planes(sa, ma))  # (3, m, kp)
        pb = jnp.stack(nibble.fp16_planes(sb, mb))  # (3, kp, n)
        pa = pa.reshape(3, m, kp // g, g)
        pb = pb.reshape(3, kp // g, g, n)
        pairs = cfg.iteration_pairs()
        it_i = jnp.asarray([p[0] for p in pairs], jnp.int32)
        it_j = jnp.asarray([p[1] for p in pairs], jnp.int32)

    w_f = min(cfg.w, 26)
    pre_fused = 1 + w_f - cfg.w

    def group_body(gi, carry):
        hi, lo, exp_acc = carry
        acc = fx.FX(hi, lo)
        c = (jax.lax.dynamic_index_in_dim(ea, gi, 1, keepdims=False)
             [:, :, None]
             + jax.lax.dynamic_index_in_dim(eb, gi, 0, keepdims=False)
             [None])                                     # (m, g, n)
        mx = jnp.max(c, axis=1)
        shift = mx[:, None, :] - c
        active = shift <= cfg.mask_threshold

        if fused:
            dg = (jax.lax.dynamic_index_in_dim(da, gi, 1, keepdims=False)
                  [:, :, None]
                  * jax.lax.dynamic_index_in_dim(db, gi, 0, keepdims=False)
                  [None])
            rs = shift + (22 - w_f)
            aligned = _shr_i32(dg, jnp.maximum(rs, 0), cfg.rounding)
            aligned = aligned << jnp.clip(-rs, 0, max(w_f - 22, 0))
            aligned = jnp.where(active, aligned, 0)
            s_tree = jnp.sum(aligned, axis=1)
            acc, exp_acc = accumulate(acc, exp_acc, s_tree, mx,
                                      jnp.full_like(mx, pre_fused),
                                      jnp.zeros_like(mx), cfg)
            return acc.hi, acc.lo, exp_acc

        pa_g = jax.lax.dynamic_index_in_dim(pa, gi, 2, keepdims=False)
        pb_g = jax.lax.dynamic_index_in_dim(pb, gi, 1, keepdims=False)

        def iter_body(it, carry2):
            hi2, lo2, exp2 = carry2
            acc2 = fx.FX(hi2, lo2)
            i = it_i[it]
            j = it_j[it]
            na = jax.lax.dynamic_index_in_dim(pa_g, i, 0, keepdims=False)
            nb = jax.lax.dynamic_index_in_dim(pb_g, j, 0, keepdims=False)
            d = na[:, :, None] * nb[None]
            dw = d << (cfg.w - 9)
            aligned = _shr_i32(dw, shift, cfg.rounding)
            aligned = jnp.where(active, aligned, 0)
            s_tree = jnp.sum(aligned, axis=1)
            acc2, exp2 = accumulate(acc2, exp2, s_tree, mx, 4 * (4 - i - j),
                                    jnp.zeros_like(mx), cfg)
            return acc2.hi, acc2.lo, exp2

        return jax.lax.fori_loop(0, len(pairs), iter_body, (acc.hi, acc.lo,
                                                            exp_acc))

    z = jnp.zeros((m, n), jnp.int32)
    e0 = jnp.full((m, n), NEG_INF_EXP, jnp.int32)
    hi, lo, exp_acc = jax.lax.fori_loop(0, kp // g, group_body, (z, z, e0))
    return fx.round_to_fp(fx.FX(hi, lo), exp_acc, cfg.accum_format)


def mp_matmul_fused_ref(a: jax.Array, b: jax.Array,
                        cfg: IPUConfig = IPUConfig()) -> jax.Array:
    """Oracle alias for the fused mpmm mode."""
    return mp_matmul_xla(a, b, cfg, fused=True)
