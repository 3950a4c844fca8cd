"""Decoder-only transformer LM covering the dense + MoE + VLM-backbone
architectures of the zoo (qwen2, gemma2, stablelm, glm4, mixtral,
qwen3-moe, internvl2 backbone, seamless decoder reuse).

Homogeneous blocks are stacked and scanned (jax.lax.scan) so HLO size,
compile time, and remat policy are O(1) in depth; heterogeneous attention
patterns (gemma-2 local/global alternation) scan over repeating *groups*
of blocks. KV caches are stacked along the group axis and threaded as
scan xs/ys.

Every part of a served program runs under one ``jax.named_scope`` of a
fixed vocabulary, which each HLO op carries in its ``op_name`` metadata
and a profiler trace shows: ``embed``, ``norm``, ``attn.proj``,
``attn.core``, ``attn.kv_write``, ``mlp``, ``head``, ``sample``, and
``mp_linear.<kind>`` inside every projection (``kind`` the stored
``PreparedWeight.kind``, ``dense`` for a raw weight).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.policy import get_policy
from repro.layers import attention, mlp, moe
from repro.layers.attention import AttnConfig, KVCache
from repro.layers.common import apply_norm, embed_init, norm_init, softcap
from repro.layers.mplinear import linear_init
from repro.parallel import act_sharding as act


def group_kinds(cfg: ModelConfig) -> Tuple[str, ...]:
    if cfg.attn_pattern == "full":
        return ("full",)
    if cfg.attn_pattern == "swa":
        return ("swa",)
    if cfg.attn_pattern == "alt_local_global":
        return ("swa", "full")
    raise ValueError(cfg.attn_pattern)


def attn_cfg(cfg: ModelConfig, kind: str) -> AttnConfig:
    return AttnConfig(
        d_model=cfg.d_model,
        n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim_,
        qkv_bias=cfg.qkv_bias,
        qk_norm=cfg.qk_norm,
        rope_theta=cfg.rope_theta,
        rotary_pct=cfg.rotary_pct,
        window=cfg.window if kind == "swa" else None,
        attn_softcap=cfg.attn_softcap,
        causal=True,
        scale=cfg.attn_scale,
    )


def moe_cfg(cfg: ModelConfig) -> moe.MoEConfig:
    return moe.MoEConfig(cfg.d_model, cfg.moe.d_expert, cfg.moe.n_experts,
                         cfg.moe.top_k, cfg.moe.capacity_factor, cfg.act,
                         dispatch=cfg.moe.dispatch)


def _block_init(key, cfg: ModelConfig, kind: str, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "ln1": norm_init(cfg.norm, cfg.d_model, dtype),
        "attn": attention.init(k1, attn_cfg(cfg, kind), dtype),
        "ln2": norm_init(cfg.norm, cfg.d_model, dtype),
    }
    if cfg.moe:
        p["moe"] = moe.init(k2, moe_cfg(cfg), dtype)
    else:
        p["mlp"] = mlp.init(k3, cfg.d_model, cfg.d_ff, dtype)
    if cfg.post_norms:
        p["post_ln1"] = norm_init(cfg.norm, cfg.d_model, dtype)
        p["post_ln2"] = norm_init(cfg.norm, cfg.d_model, dtype)
    return p


def init(key, cfg: ModelConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    kinds = group_kinds(cfg)
    assert cfg.n_layers % len(kinds) == 0, (cfg.arch_id, kinds)
    n_groups = cfg.n_layers // len(kinds)
    ke, kb, kh = jax.random.split(key, 3)

    def group_init(gk):
        sub = jax.random.split(gk, len(kinds))
        return {f"b{i}": _block_init(sub[i], cfg, kind, dtype)
                for i, kind in enumerate(kinds)}

    params = {
        "embed": {"w": embed_init(ke, cfg.padded_vocab, cfg.d_model,
                                  dtype)},
        "blocks": jax.vmap(group_init)(jax.random.split(kb, n_groups)),
        "final_norm": norm_init(cfg.norm, cfg.d_model, dtype),
    }
    if not cfg.tied_embeddings:
        params["lm_head"] = linear_init(kh, cfg.d_model, cfg.padded_vocab,
                                        False, dtype)
    return params


@jax.named_scope("embed")
def _embed(params, cfg: ModelConfig, tokens):
    x = jnp.take(params["embed"]["w"], tokens, axis=0)
    x = x.astype(jnp.dtype(cfg.compute_dtype))
    if cfg.norm == "rms_zc":  # gemma convention: scale by sqrt(d)
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return act.batch_seq(x)


@jax.named_scope("head")
def _head(params, cfg: ModelConfig, x):
    if cfg.tied_embeddings:
        w = params["embed"]["w"]
        logits = jnp.dot(x, w.T.astype(x.dtype),
                         preferred_element_type=jnp.float32)
    else:
        w = params["lm_head"]["w"]
        logits = jnp.dot(x, w.astype(x.dtype),
                         preferred_element_type=jnp.float32)
    logits = softcap(logits.astype(jnp.float32), cfg.logit_softcap)
    if cfg.padded_vocab != cfg.vocab:  # mask the padding columns
        col = jnp.arange(cfg.padded_vocab)
        logits = jnp.where(col < cfg.vocab, logits, -1e30)
    return act.logits(logits)


def _apply_block(params, cfg: ModelConfig, kind: str, x, positions,
                 policy, mode: str, cache: Optional[KVCache], pos,
                 valid=None):
    path = f"block/{kind}/attn"
    acfg = attn_cfg(cfg, kind)
    h = apply_norm(cfg.norm, x, params["ln1"])
    new_cache = cache
    if mode == "train":
        a = attention.forward(params["attn"], acfg, h, positions, policy,
                              path)
    elif mode == "prefill":
        a, new_cache = attention.prefill(params["attn"], acfg, h,
                                         positions, cache, policy, path)
    elif mode == "chunk":
        a, new_cache = attention.prefill_chunk(params["attn"], acfg, h,
                                               positions, valid, cache,
                                               policy, path)
    else:
        a, new_cache = attention.decode_step(params["attn"], acfg, h, pos,
                                             cache, policy, path)
    if cfg.post_norms:
        a = apply_norm(cfg.norm, a, params["post_ln1"])
    x = x + a
    h = apply_norm(cfg.norm, x, params["ln2"])
    aux = jnp.zeros((), jnp.float32)
    if cfg.moe:
        f, aux = moe.forward(params["moe"], moe_cfg(cfg), h, policy,
                             "block/moe")
    else:
        f = mlp.forward(params["mlp"], h, policy, "block/mlp", cfg.act)
    if cfg.post_norms:
        f = apply_norm(cfg.norm, f, params["post_ln2"])
    return x + f, new_cache, aux


def _remat_wrap(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    policy = None
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    return jax.checkpoint(fn, policy=policy)


def _run_blocks(params, cfg: ModelConfig, x, positions, mode: str,
                caches=None, pos=None, valid=None):
    policy = get_policy(cfg.precision_policy)
    kinds = group_kinds(cfg)

    def group_step(carry, xs):
        h, aux = carry
        h = act.batch_seq(h)  # pin the scan-carry layout (SP)
        gp, gc = xs
        new_gc = {}
        for i, kind in enumerate(kinds):
            c_i = gc[f"b{i}"] if gc is not None else None
            h, nc, a = _apply_block(gp[f"b{i}"], cfg, kind, h, positions,
                                    policy, mode, c_i, pos, valid=valid)
            new_gc[f"b{i}"] = nc
            aux = aux + a
        return (h, aux), new_gc

    step = _remat_wrap(group_step, cfg) if mode == "train" else group_step
    xs = (params["blocks"], caches)
    (x, aux), new_caches = jax.lax.scan(step, (x, jnp.zeros((), jnp.float32)),
                                        xs)
    return x, aux, (new_caches if caches is not None else None)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16):
    """Stacked (n_groups, ...) caches. SWA blocks get window-sized ring
    buffers — the reason long_500k fits for swa archs."""
    kinds = group_kinds(cfg)
    n_groups = cfg.n_layers // len(kinds)

    def one(kind):
        cap = max_len
        if kind == "swa" and cfg.window is not None:
            cap = min(cfg.window, max_len)
        c = attention.init_cache(batch, cap, attn_cfg(cfg, kind), dtype)
        return KVCache(*(jnp.broadcast_to(a, (n_groups,) + a.shape)
                         for a in c))

    return {f"b{i}": one(kind) for i, kind in enumerate(kinds)}


def train_logits(params, cfg: ModelConfig, tokens):
    """tokens: (B, S) -> logits (B, S, V) f32, aux loss."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = _embed(params, cfg, tokens)
    x, aux, _ = _run_blocks(params, cfg, x, positions, "train")
    x = apply_norm(cfg.norm, x, params["final_norm"])
    return _head(params, cfg, x), aux


def hidden_states(params, cfg: ModelConfig, tokens):
    """Final normed hidden states (B, S, d) + aux loss (fused-loss path)."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = _embed(params, cfg, tokens)
    x, aux, _ = _run_blocks(params, cfg, x, positions, "train")
    return apply_norm(cfg.norm, x, params["final_norm"]), aux


def loss_fn(params, cfg: ModelConfig, batch):
    """batch: {'tokens': (B, S+1) int32} next-token xent (mean/token).

    Uses the fused chunked head+loss: the (B, S, V) logits never
    materialize (see losses.fused_chunked_xent)."""
    from repro.models.losses import fused_chunked_xent
    tokens = batch["tokens"]
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    x, aux = hidden_states(params, cfg, inp)
    mask = batch.get("mask")
    loss, m = fused_chunked_xent(
        x, lambda xc: _head(params, cfg, xc), tgt,
        mask[:, 1:] if mask is not None else None)
    return loss + 0.01 * aux, {**m, "aux": aux}


def prefill(params, cfg: ModelConfig, tokens, caches):
    """tokens: (B, S) -> (last-position logits (B, V), new caches)."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = _embed(params, cfg, tokens)
    x, _, new_caches = _run_blocks(params, cfg, x, positions, "prefill",
                                   caches=caches)
    x = apply_norm(cfg.norm, x[:, -1:], params["final_norm"])
    return _head(params, cfg, x)[:, 0], new_caches


def prefill_chunk(params, cfg: ModelConfig, tokens, offsets, lengths,
                  caches):
    """Position-offset prefill continuation for the continuous engine.

    tokens: (B, S) one chunk of each row's prompt; offsets: (B,)
    absolute position of ``tokens[:, 0]``; lengths: (B,) valid tokens
    per row (0 = row untouched). Writes the chunk's K/V into the LIVE
    ``caches`` and returns them — no logits: the engine feeds the last
    prompt token through ``decode_step``, which computes the head."""
    b, s = tokens.shape
    positions = offsets[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    valid = jnp.arange(s, dtype=jnp.int32)[None, :] < lengths[:, None]
    x = _embed(params, cfg, jnp.where(valid, tokens, 0))
    _, _, new_caches = _run_blocks(params, cfg, x, positions, "chunk",
                                   caches=caches, valid=valid)
    return new_caches


def decode_step(params, cfg: ModelConfig, token, pos, caches):
    """token: (B, 1); pos: (B,) -> (logits (B, V), new caches)."""
    x = _embed(params, cfg, token)
    x, _, new_caches = _run_blocks(params, cfg, x, pos[:, None], "decode",
                                   caches=caches, pos=pos)
    x = apply_norm(cfg.norm, x, params["final_norm"])
    return _head(params, cfg, x)[:, 0], new_caches
