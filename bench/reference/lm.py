"""Plain float32 reference of the `lm` family, and its seeded weights.

A decoder-only transformer as the configuration files under
`bench/configs/` state it: token embedding, `num_hidden_layers` blocks of
RMSNorm -> grouped-query attention (q/k/v biases, rotary embedding on the
first `partial_rotary_factor` of each head, split into halves) -> residual
-> RMSNorm -> SwiGLU -> residual, a final RMSNorm and the head (the
embedding itself where `tie_word_embeddings`). Everything is computed in
float32 at `highest` matmul precision, one layer at a time and attention
in blocks of query rows, so a 12k-token prompt at glm4-9b's widths fits
beside nothing else on one chip.

`make_params` draws the weights from the seed, on the device, in one
jitted call, in bfloat16 (the dtype the published checkpoints are stored
in), laid out as the served program takes them. The benchmark hands that
tree to the program and, after the window, draws it again for this
reference: the reference takes nothing that the program made.

Where the file states `stored_weight_bits`, the deployment stores every
block projection at that many bits, symmetric per output channel, and the
reference computes with those weights (`fake_quant`; the harness refuses
a file whose bits are not its tier's); the embedding, norms and head
stay as drawn, as the int tiers keep them. Its activations stay float32:
the int8 activation scales the served path adds are one of the
departures the cell's limits cover.

Besides the reference, the module gives the harness what belongs to the
architecture (`bench/harness.py` states the contract): `program_fields`,
the program's `ModelConfig` fields the file states, `FILE_KEYS`, the
file's key of each, and the work a step needs (`projection_shapes`,
`projection_calls`, `prefill_ops`, `decode_ops`).

This module imports nothing of the program.
"""
from __future__ import annotations

import functools
import math
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# query rows per attention block, and the bucket prompts are padded to
# (causal masking makes right padding harmless), so one compile serves
# every length in a bucket
Q_BLOCK = 512
# spread of the drawn norm gains and biases around their usual values:
# large enough that a program which dropped a bias or a gain, or applied
# one twice, lands far from the reference
GAIN_STD = 0.1
BIAS_STD = 0.1


def rng_key(seed: int) -> jax.Array:
    """A threefry key from any whole number (the seeds may pass 2**32)."""
    words = np.random.SeedSequence(seed % 2**64).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def sizes(c: dict) -> dict:
    d = c["hidden_size"]
    hd = c.get("head_dim") or d // c["num_attention_heads"]
    return dict(d=d, hd=hd, hq=c["num_attention_heads"],
                hkv=c["num_key_value_heads"], f=c["intermediate_size"],
                v=c["vocab_size"], layers=c["num_hidden_layers"])


# the file's key each field of `program_fields` is read from: a field
# that departs from the program's entry is taken where `reduced` names it
FILE_KEYS = dict(
    n_layers="num_hidden_layers", d_model="hidden_size",
    n_heads="num_attention_heads", n_kv_heads="num_key_value_heads",
    d_ff="intermediate_size", vocab="vocab_size", act="hidden_act",
    qkv_bias="attention_bias", rotary_pct="partial_rotary_factor",
    tied_embeddings="tie_word_embeddings")


def program_fields(c: dict) -> dict:
    """The program's `ModelConfig` fields that file ``c`` states: its
    sizes, tier and dtype, and every mechanism of the dense block this
    module computes (RMSNorm, full causal attention, no experts, no
    q/k norm, no post-norms, no softcaps, the default attention scale)."""
    s = sizes(c)
    return dict(
        family=c["family"], n_layers=s["layers"], d_model=s["d"],
        n_heads=s["hq"], n_kv_heads=s["hkv"], head_dim=s["hd"],
        d_ff=s["f"], vocab=s["v"], act=c["hidden_act"], norm="rms",
        qkv_bias=c["attention_bias"], qk_norm=False,
        rope_theta=c["rope_theta"], rotary_pct=c["partial_rotary_factor"],
        attn_pattern="full", logit_softcap=None, attn_softcap=None,
        post_norms=False, tied_embeddings=c["tie_word_embeddings"],
        attn_scale=None, moe=None,
        precision_policy=c["precision_policy"],
        param_dtype=c["param_dtype"])


def param_shapes(c: dict) -> dict:
    """{path: shape} of the served tree: blocks stacked on axis 0."""
    s = sizes(c)
    d, hd, n = s["d"], s["hd"], s["layers"]
    q, kv = s["hq"] * hd, s["hkv"] * hd
    out = {
        "embed/w": (s["v"], d),
        "final_norm/w": (d,),
        "blocks/b0/ln1/w": (n, d),
        "blocks/b0/ln2/w": (n, d),
        "blocks/b0/attn/wq/w": (n, d, q),
        "blocks/b0/attn/wk/w": (n, d, kv),
        "blocks/b0/attn/wv/w": (n, d, kv),
        "blocks/b0/attn/wo/w": (n, q, d),
        "blocks/b0/mlp/w_gate/w": (n, d, s["f"]),
        "blocks/b0/mlp/w_up/w": (n, d, s["f"]),
        "blocks/b0/mlp/w_down/w": (n, s["f"], d),
    }
    if c["attention_bias"]:
        out["blocks/b0/attn/wq/b"] = (n, q)
        out["blocks/b0/attn/wk/b"] = (n, kv)
        out["blocks/b0/attn/wv/b"] = (n, kv)
    if not c["tie_word_embeddings"]:
        out["lm_head/w"] = (d, s["v"])
    return out


def _draw(key, path: str, shape) -> jax.Array:
    z = jax.random.normal(key, shape, jnp.float32)
    leaf = path.rsplit("/", 2)
    if leaf[-2].startswith("ln") or leaf[-2] == "final_norm":
        return 1.0 + GAIN_STD * z
    if leaf[-1] == "b":
        return BIAS_STD * z
    if path == "embed/w":
        return z * shape[-1] ** -0.5
    return z * shape[-2] ** -0.5          # (..., d_in, d_out)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = leaf
    return tree


@functools.partial(jax.jit, static_argnames=("shapes", "dtype"))
def _make(key, shapes, dtype):
    out = {}
    for i, (path, shape) in enumerate(shapes):
        out[path] = _draw(jax.random.fold_in(key, i), path,
                          shape).astype(dtype)
    return out


def make_params(c: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The whole weight tree from the seed, on the default device."""
    shapes = tuple(sorted(param_shapes(c).items()))
    return _nest(_make(rng_key(seed), shapes, jnp.dtype(dtype)))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta, pct):
    """x: (S, H, D); rotates the first pct * D dims, split into halves."""
    d = x.shape[-1]
    rot = int(d * pct)
    rot -= rot % 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang),
                            x[..., rot:]], -1)


def fake_quant(w, bits):
    """Symmetric per-output-channel quantize-dequantize over axis -2."""
    qmax = 2 ** (bits - 1) - 1
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True),
                        1e-12) / qmax
    return jnp.clip(jnp.round(w / scale), -qmax - 1, qmax) * scale


def _w(p, name, bits):
    w = p[name]["w"].astype(jnp.float32)
    return w if bits is None else fake_quant(w, bits)


@functools.partial(jax.jit, static_argnames=("st", "bits"))
def _layer(x, p, st, bits):
    """One block over the whole (padded) sequence x: (S, d) float32."""
    (hq, hkv, hd, theta, pct, eps, bias) = st
    s = x.shape[0]
    pos = jnp.arange(s, dtype=jnp.int32)
    f32 = lambda a: a.astype(jnp.float32)           # noqa: E731
    h = _rms(x, f32(p["ln1"]["w"]), eps)
    a = p["attn"]

    def proj(name):
        y = jnp.dot(h, _w(a, name, bits), precision=HIGHEST)
        return y + f32(a[name]["b"]) if bias else y

    q = _rope(proj("wq").reshape(s, hq, hd), pos, theta, pct)
    k = _rope(proj("wk").reshape(s, hkv, hd), pos, theta, pct)
    v = proj("wv").reshape(s, hkv, hd)
    g = hq // hkv
    scale = 1.0 / math.sqrt(hd)

    def block(args):
        qb, qpos = args                               # (Q, hq, hd), (Q,)
        qb = qb.reshape(-1, hkv, g, hd)
        logits = jnp.einsum("qhgd,khd->hgqk", qb, k,
                            precision=HIGHEST) * scale
        mask = pos[None, :] <= qpos[:, None]
        logits = jnp.where(mask, logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", probs, v, precision=HIGHEST)
        return o.reshape(-1, hq * hd)

    nb = s // Q_BLOCK
    o = jax.lax.map(block, (q.reshape(nb, Q_BLOCK, hq, hd),
                            pos.reshape(nb, Q_BLOCK)))
    x = x + jnp.dot(o.reshape(s, hq * hd), _w(a, "wo", bits),
                    precision=HIGHEST)
    h = _rms(x, f32(p["ln2"]["w"]), eps)
    m = p["mlp"]
    gate = jnp.dot(h, _w(m, "w_gate", bits), precision=HIGHEST)
    up = jnp.dot(h, _w(m, "w_up", bits), precision=HIGHEST)
    return x + jnp.dot(jax.nn.silu(gate) * up, _w(m, "w_down", bits),
                       precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("eps", "tied"))
def _head(x, rows, final, head, eps, tied):
    h = _rms(x[rows], final.astype(jnp.float32), eps)
    w = head.astype(jnp.float32)
    return jnp.dot(h, w.T if tied else w, precision=HIGHEST)


def logits_at(params, c: dict, tokens, rows, weight_bits=None):
    """float32 logits (len(rows), vocab) at the given positions of
    ``tokens`` (positions count from 0). ``weight_bits`` fake-quantizes
    every block projection per output channel to that many bits (the
    stored weights, or the control's); the embedding, norms and head
    stay as drawn."""
    s = sizes(c)
    n = len(tokens)
    padded = -(-n // Q_BLOCK) * Q_BLOCK
    toks = np.zeros(padded, np.int32)
    toks[:n] = tokens
    st = (s["hq"], s["hkv"], s["hd"], float(c["rope_theta"]),
          float(c["partial_rotary_factor"]), float(c["rms_norm_eps"]),
          bool(c["attention_bias"]))
    x = jnp.take(params["embed"]["w"], jnp.asarray(toks), axis=0)
    x = x.astype(jnp.float32)
    blocks = params["blocks"]["b0"]
    for i in range(s["layers"]):
        p = jax.tree.map(lambda a, i=i: a[i], blocks)
        x = _layer(x, p, st, weight_bits)
    tied = bool(c["tie_word_embeddings"])
    head = params["embed"]["w"] if tied else params["lm_head"]["w"]
    return _head(x, jnp.asarray(rows, jnp.int32), params["final_norm"]["w"],
                 head, float(c["rms_norm_eps"]), tied)


@jax.jit
def _gap_of(ref, chosen):
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
    return best - got


def served_gaps(params, c: dict, prompt, served, control_bits=None):
    """Gaps by which each served token's reference logit lies below the
    reference's best, at its position; with ``control_bits`` also the
    gaps of the tokens that the control (the reference with the drawn
    weights at that precision) puts first at the same positions. The
    reference computes with the weights the deployment stores: at the
    file's `stored_weight_bits` where it states them, else as drawn.

    Returns (program gaps, control gaps or None), numpy float32."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    tokens = np.concatenate([prompt, served[:-1]])
    rows = np.arange(len(prompt) - 1, len(tokens))
    ref = logits_at(params, c, tokens, rows,
                    weight_bits=c.get("stored_weight_bits"))
    prog = np.asarray(_gap_of(ref, jnp.asarray(served)))
    ctrl = None
    if control_bits is not None:
        low = logits_at(params, c, tokens, rows, weight_bits=control_bits)
        ctrl = np.asarray(_gap_of(ref, jnp.argmax(low, axis=-1)))
    return prog, ctrl


# ------------------------------------------------- the work a step needs
#
# Counts are of the work the algorithm needs, from the file's own sizes:
# valid prompt tokens and active decode rows, attention over the keys a
# query may see (causal), never padded rows, padded K or masked slots. A
# multiply-add counts as 2 operations. Biases, norms, rotary embedding
# and softmax are left out: they are under 1% of the operations at these
# widths.

def projection_shapes(c: dict) -> List[Tuple[int, int]]:
    """(K, N) of each projection of one layer: wq, wk, wv, wo, w_gate,
    w_up, w_down."""
    s = sizes(c)
    d, f, q, kv = s["d"], s["f"], s["hq"] * s["hd"], s["hkv"] * s["hd"]
    return [(d, q), (d, kv), (d, kv), (q, d), (d, f), (d, f), (f, d)]


def projection_calls(c: dict) -> List[Tuple[int, int, int]]:
    """(K, N, calls) of the block projections one step of the model
    makes over every row it computes: each shape once a layer."""
    layers = c["num_hidden_layers"]
    return [(k, n, layers) for k, n in projection_shapes(c)]


def projection_ops_per_token(c: dict) -> int:
    """Operations of every block projection for one token."""
    layers = c["num_hidden_layers"]
    return 2 * layers * sum(k * n for k, n in projection_shapes(c))


def head_ops_per_token(c: dict) -> int:
    return 2 * c["hidden_size"] * c["vocab_size"]


def attention_ops(c: dict, first_pos: int, count: int) -> int:
    """Operations of q.k and p.v for ``count`` queries at positions
    ``first_pos``..``first_pos + count - 1``, each over the keys at
    positions up to its own (position p sees p + 1 keys)."""
    if count <= 0:
        return 0
    s = sizes(c)
    keys = count * first_pos + count * (count + 1) // 2
    return 2 * 2 * s["layers"] * s["hq"] * s["hd"] * keys


def prefill_ops(c: dict, start: int, stop: int) -> int:
    """Prompt positions start..stop-1 written to the cache (no head:
    the prefill program computes no logits)."""
    n = stop - start
    return n * projection_ops_per_token(c) + attention_ops(c, start, n)


def decode_ops(c: dict, first_pos: int, steps: int) -> int:
    """``steps`` decode steps of one request, the first with its input
    token at position ``first_pos``; each step computes the head."""
    per = projection_ops_per_token(c) + head_ops_per_token(c)
    return steps * per + attention_ops(c, first_pos, steps)
