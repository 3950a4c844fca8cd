"""On-device batched token selection: temperature / top-k / top-p.

``sample_tokens`` is the single selection primitive both decode paths
share — the per-token engine jits it standalone over one step's logits,
and ``registry.make_block_decode`` closes over it inside the blocked
scan (the PRNG keys thread through the scan carry, so a block of n
steps consumes exactly n key splits per active slot — the reason
sampled streams are identical at every ``decode_block``).

All parameters are per-row (B,) arrays so one program serves a batch
mixing greedy and sampled slots: rows with ``temperature <= 0`` take
the argmax (bit-identical to the greedy program — the argmax runs on
the raw, unscaled logits), every other row samples from the
temperature-scaled, top-k/top-p-truncated distribution.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.named_scope("sample")
def sample_tokens(keys, logits, temperature, top_k, top_p):
    """Select one token per batch row.

    keys: (B, 2) uint32 per-row PRNG keys; logits: (B, V) float;
    temperature/top_p: (B,) f32; top_k: (B,) int32 (0 = unrestricted).
    Returns ``(new_keys, tokens)`` — (B, 2) uint32 advanced keys (every
    row's key advances once per call, consumed or not, so key cadence
    never depends on which rows sample) and (B,) int32 tokens.

    Truncation follows the standard nucleus convention: tokens are
    ranked by scaled logit; a token survives while its rank is below
    ``top_k`` AND the cumulative probability *before* it is below
    ``top_p`` (the crossing token is kept, rank 0 always survives).
    """
    v = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    order = jnp.argsort(-scaled, axis=-1)            # desc by logit
    ranked = jnp.take_along_axis(scaled, order, axis=-1)
    probs = jax.nn.softmax(ranked, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    rank = jnp.arange(v, dtype=jnp.int32)[None, :]
    keep = rank < jnp.where(top_k > 0, top_k, v)[:, None]
    keep &= (cum - probs) < top_p[:, None]
    keep |= rank == 0
    ranked = jnp.where(keep, ranked, -jnp.inf)

    split = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    idx = jax.vmap(jax.random.categorical)(split[:, 1], ranked)
    sampled = jnp.take_along_axis(order, idx[:, None], axis=-1)[:, 0]
    tokens = jnp.where(temperature > 0.0,
                       sampled.astype(jnp.int32), greedy)
    return split[:, 0], tokens
