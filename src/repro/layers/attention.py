"""Grouped-query attention with the zoo's feature set: GQA/MQA/MHA,
RoPE (partial), sliding windows, gemma-2 attention softcap, QKV biases,
qwen-3 QK-norm, bidirectional (encoder) and cross-attention modes, and a
position-tagged KV cache that serves both full-attention decode and
ring-buffer sliding-window decode.

Named scopes (``jax.named_scope``; they reach each HLO op's ``op_name``
metadata and a profiler trace, and cost nothing on the device):
``attn.proj`` (q/k/v/o projections, RoPE), ``attn.core`` (scores,
mask, softmax, weighted sum) and ``attn.kv_write`` (the cache update).
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.policy import PrecisionPolicy
from repro.layers.common import apply_rope, norm_init, rms_norm, softcap
from repro.layers.mplinear import linear_init, mp_linear
from repro.parallel import act_sharding as act


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10000.0
    rotary_pct: float = 1.0
    window: Optional[int] = None       # sliding window (tokens), None=full
    attn_softcap: Optional[float] = None
    causal: bool = True                # False for encoder self-attn
    cross: bool = False                # cross-attention (no RoPE, kv=ctx)
    scale: Optional[float] = None      # default 1/sqrt(head_dim)
    # Chunked (flash-style online-softmax) attention kicks in when the KV
    # length exceeds chunk_threshold and Sq > 1 — O(S) memory, mandatory
    # for 32k prefill.
    q_chunk: int = 512
    kv_chunk: int = 1024
    chunk_threshold: int = 2048

    @property
    def q_dim(self):
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self):
        return self.n_kv_heads * self.head_dim


class KVCache(NamedTuple):
    """Position-tagged cache: ring-indexed when capacity < sequence."""

    k: jax.Array    # (B, C, Hkv, D)
    v: jax.Array    # (B, C, Hkv, D)
    pos: jax.Array  # (B, C) int32 absolute positions, -1 = empty


def init(key, cfg: AttnConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    p = {
        "wq": linear_init(ks[0], cfg.d_model, cfg.q_dim, cfg.qkv_bias, dtype),
        "wk": linear_init(ks[1], cfg.d_model, cfg.kv_dim, cfg.qkv_bias, dtype),
        "wv": linear_init(ks[2], cfg.d_model, cfg.kv_dim, cfg.qkv_bias, dtype),
        "wo": linear_init(ks[3], cfg.q_dim, cfg.d_model, False, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = norm_init("rms", cfg.head_dim, dtype)
        p["k_norm"] = norm_init("rms", cfg.head_dim, dtype)
    return p


def init_cache(batch: int, capacity: int, cfg: AttnConfig,
               dtype=jnp.bfloat16) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, capacity, cfg.n_kv_heads, cfg.head_dim), dtype),
        v=jnp.zeros((batch, capacity, cfg.n_kv_heads, cfg.head_dim), dtype),
        pos=jnp.full((batch, capacity), -1, jnp.int32),
    )


@jax.named_scope("attn.proj")
def _project_qkv(params, cfg: AttnConfig, x, positions, policy, path,
                 kv_input=None):
    spec = policy.spec_for
    b, s, _ = x.shape
    q = mp_linear(params["wq"], x, spec(f"{path}/wq"), path=f"{path}/wq").reshape(
        b, s, cfg.n_heads, cfg.head_dim)
    kv_src = x if kv_input is None else kv_input
    bk, sk, _ = kv_src.shape
    k = mp_linear(params["wk"], kv_src, spec(f"{path}/wk"), path=f"{path}/wk").reshape(
        bk, sk, cfg.n_kv_heads, cfg.head_dim)
    v = mp_linear(params["wv"], kv_src, spec(f"{path}/wv"), path=f"{path}/wv").reshape(
        bk, sk, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"]["w"])
        k = rms_norm(k, params["k_norm"]["w"])
    if not cfg.cross:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rotary_pct)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rotary_pct)
    return act.heads(q), act.heads(k), act.heads(v)


def _mask(cfg: AttnConfig, q_pos, k_pos, k_valid):
    """(B, 1, 1, Sq, Sk) boolean mask from position tags."""
    m = k_valid[:, None, None, None, :]
    if cfg.causal:
        m = m & (k_pos[:, None, None, None, :]
                 <= q_pos[:, None, None, :, None])
    if cfg.window is not None:
        m = m & (k_pos[:, None, None, None, :]
                 > q_pos[:, None, None, :, None] - cfg.window)
    return m


def _attend_dense(cfg: AttnConfig, q, k, v, q_pos, k_pos, k_valid):
    """Materialized-logits attention (short sequences / decode)."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    scale = cfg.scale if cfg.scale is not None else 1.0 / math.sqrt(d)
    qg = q.reshape(b, sq, hkv, g, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = softcap(logits, cfg.attn_softcap)
    mask = _mask(cfg, q_pos, k_pos, k_valid)  # (B,1,1,Sq,Sk)
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return out.reshape(b, sq, hq * d)


def _attend_chunked(cfg: AttnConfig, q, k, v, q_pos, k_pos, k_valid):
    """Flash-style online-softmax attention: O(S) memory via a scan over
    KV chunks inside a map over Q chunks. All accumulation in f32."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    scale = cfg.scale if cfg.scale is not None else 1.0 / math.sqrt(d)
    qc, kc = cfg.q_chunk, cfg.kv_chunk

    pad_q = -sq % qc
    q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    q_pos_p = jnp.pad(q_pos, ((0, 0), (0, pad_q)))
    sk = k.shape[1]
    pad_k = -sk % kc
    k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    k_pos = jnp.pad(k_pos, ((0, 0), (0, pad_k)))
    k_valid = jnp.pad(k_valid, ((0, 0), (0, pad_k)))
    nq, nk = q.shape[1] // qc, k.shape[1] // kc

    qg = q.reshape(b, nq, qc, hkv, g, d).astype(jnp.float32)
    qp = q_pos_p.reshape(b, nq, qc)
    kg = jnp.moveaxis(k.reshape(b, nk, kc, hkv, d), 1, 0)
    vg = jnp.moveaxis(v.reshape(b, nk, kc, hkv, d), 1, 0)
    kpg = jnp.moveaxis(k_pos.reshape(b, nk, kc), 1, 0)
    kvg = jnp.moveaxis(k_valid.reshape(b, nk, kc), 1, 0)

    def one_q_chunk(args):
        qi, qpi = args  # (B, qc, hkv, g, d), (B, qc)

        def kv_step(carry, kv):
            m, l, acc = carry
            ki, vi, kpi, kvi = kv
            logits = jnp.einsum("bqhgd,bkhd->bhgqk", qi,
                                ki.astype(jnp.float32)) * scale
            logits = softcap(logits, cfg.attn_softcap)
            msk = _mask(cfg, qpi, kpi, kvi)
            logits = jnp.where(msk, logits, -1e30)
            m_new = jnp.maximum(m, logits.max(-1))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(logits - m_new[..., None])
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vi.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, qc), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, qc), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, qc, d), jnp.float32)
        # Rematerialized backward (flash-attention style): without the
        # checkpoints, the backward keeps every chunk-pair's probability
        # tensor live at once — O(S^2) memory, hundreds of GB/device at
        # train_4k (see EXPERIMENTS.md §Perf memory iteration).
        (m, l, acc), _ = jax.lax.scan(jax.checkpoint(kv_step), (m0, l0, a0),
                                      (kg, vg, kpg, kvg))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(out, 3, 1)  # (B, qc, hkv, g, d)

    outs = jax.lax.map(jax.checkpoint(one_q_chunk),
                       (jnp.moveaxis(qg, 1, 0), jnp.moveaxis(qp, 1, 0)))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, nq * qc, hq, d)
    return out[:, :sq].reshape(b, sq, hq * d).astype(v.dtype)


@jax.named_scope("attn.core")
def _attend(cfg: AttnConfig, q, k, v, q_pos, k_pos, k_valid):
    """Core attention dispatch: q (B,Sq,Hq,D); k/v (B,Sk,Hkv,D);
    q_pos (B,Sq), k_pos (B,Sk) absolute positions; k_valid (B,Sk)."""
    if q.shape[1] > 1 and k.shape[1] > cfg.chunk_threshold:
        return _attend_chunked(cfg, q, k, v, q_pos, k_pos, k_valid)
    return _attend_dense(cfg, q, k, v, q_pos, k_pos, k_valid)


def forward(params, cfg: AttnConfig, x, positions, policy: PrecisionPolicy,
            path: str, kv_input=None, kv_valid=None):
    """Training / prefill attention over full sequences.

    x: (B, S, d); positions: (B, S). kv_input for cross-attention.
    Returns (B, S, d)."""
    q, k, v = _project_qkv(params, cfg, x, positions, policy, path,
                           kv_input)
    k_pos = positions if kv_input is None else (
        jnp.broadcast_to(jnp.arange(kv_input.shape[1], dtype=jnp.int32),
                         kv_input.shape[:2]))
    if kv_valid is None:
        kv_valid = jnp.ones(k.shape[:2], bool)
    out = _attend(cfg, q, k, v, positions, k_pos, kv_valid)
    return _project_out(params, out, policy, path)


@jax.named_scope("attn.proj")
def _project_out(params, out, policy, path):
    return mp_linear(params["wo"], out, policy.spec_for(f"{path}/wo"),
                     path=f"{path}/wo")


def prefill(params, cfg: AttnConfig, x, positions, cache: KVCache,
            policy, path):
    """Prefill: full-sequence attention + cache fill.

    Prefill always starts at position 0, so the ring slots of the
    surviving (trailing `cap`) positions form a STATIC rotation — the
    write is two contiguous dynamic_update_slices, never a gather/scatter
    (SPMD scatters onto the capacity-sharded cache would force the K/V
    tensors batch-unsharded: +8 GB/device at gemma2 prefill_32k)."""
    q, k, v = _project_qkv(params, cfg, x, positions, policy, path)
    out = _attend(cfg, q, k, v, positions,
                  positions, jnp.ones(k.shape[:2], bool))
    cap = cache.k.shape[1]
    s = k.shape[1]
    k_w, v_w, pos_w = k, v, positions
    if s > cap:  # ring: only the trailing cap positions survive
        k_w, v_w, pos_w = k[:, -cap:], v[:, -cap:], positions[:, -cap:]
    start = (s - cap) % cap if s > cap else 0

    @jax.named_scope("attn.kv_write")
    def write(buf, upd):
        buf = buf.astype(upd.dtype)
        first = upd[:, : cap - start]
        buf = jax.lax.dynamic_update_slice_in_dim(buf, first, start, axis=1)
        if start:
            buf = jax.lax.dynamic_update_slice_in_dim(
                buf, upd[:, cap - start:], 0, axis=1)
        return buf

    new_cache = KVCache(
        k=write(cache.k, k_w),
        v=write(cache.v, v_w),
        pos=write(cache.pos, pos_w),
    )
    return _project_out(params, out, policy, path), new_cache


def prefill_chunk(params, cfg: AttnConfig, x, positions, valid,
                  cache: KVCache, policy, path):
    """Prefill CONTINUATION: write one chunk of a prompt at arbitrary
    absolute positions into a LIVE cache, attending the chunk's queries
    against the whole updated cache (earlier chunks included) by
    position tags — the serving-engine path that streams a long prompt
    through multiple admission waves.

    x: (B, S, d); positions: (B, S) absolute positions; valid: (B, S)
    bool — invalid entries (padding rows/tails of the packed wave)
    write nothing and their outputs are garbage the caller discards.
    Requires S <= capacity (distinct ring slots within a chunk row).
    Same write-then-attend order as ``decode_step``: a query at
    position p sees every tag <= p already written, including its own
    chunk's earlier tokens, so chunking is invariant to chunk size.
    Scatter-indexed, unlike ``prefill``'s static rotation — this is the
    few-slot engine path, not the sharded 32k prefill."""
    q, k, v = _project_qkv(params, cfg, x, positions, policy, path)
    cap = cache.k.shape[1]
    with jax.named_scope("attn.kv_write"):
        slot = positions % cap                          # (B, S)
        bidx = jnp.arange(x.shape[0], dtype=jnp.int32)[:, None]
        vk = valid[..., None, None]
        ck = cache.k.astype(k.dtype)
        cv = cache.v.astype(v.dtype)
        ck = ck.at[bidx, slot].set(jnp.where(vk, k, ck[bidx, slot]))
        cv = cv.at[bidx, slot].set(jnp.where(vk, v, cv[bidx, slot]))
        cpos = cache.pos.at[bidx, slot].set(
            jnp.where(valid, positions, cache.pos[bidx, slot]))
    new_cache = KVCache(ck, cv, cpos)
    out = _attend(cfg, q, ck, cv, positions, cpos, cpos >= 0)
    return _project_out(params, out, policy, path), new_cache


def decode_step(params, cfg: AttnConfig, x, pos, cache: KVCache,
                policy, path):
    """One-token decode. x: (B, 1, d); pos: (B,) absolute positions.

    Writes the new KV at slot pos % capacity, masks by position tags —
    correct for both full caches (capacity >= seq) and SWA ring buffers
    (capacity == window)."""
    positions = pos[:, None]
    q, k, v = _project_qkv(params, cfg, x, positions, policy, path)
    cap = cache.k.shape[1]
    with jax.named_scope("attn.kv_write"):
        slot = pos % cap
        bidx = jnp.arange(x.shape[0], dtype=jnp.int32)
        ck = cache.k.astype(k.dtype).at[bidx, slot].set(k[:, 0])
        cv = cache.v.astype(v.dtype).at[bidx, slot].set(v[:, 0])
        cpos = cache.pos.at[bidx, slot].set(pos)
    new_cache = KVCache(ck, cv, cpos)
    out = _attend(cfg, q, ck, cv, positions, cpos, cpos >= 0)
    return _project_out(params, out, policy, path), new_cache
