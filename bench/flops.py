"""Work and bytes from shapes: the operations a step needs and the least
time a kernel call can take on the chip.

Counts are of the work the algorithm needs, from the configuration's
own sizes: valid prompt tokens and active decode rows, attention over
the keys a query may see (causal), never padded rows, padded K or
masked slots. A multiply-add counts as 2 operations. Biases, norms,
rotary embedding and softmax are left out: they are under 1% of the
operations at these widths.
"""
from __future__ import annotations

from typing import List, Tuple


def _dims(c: dict) -> Tuple[int, int, int, int, int, int]:
    d = c["hidden_size"]
    hd = c.get("head_dim") or d // c["num_attention_heads"]
    q = c["num_attention_heads"] * hd
    kv = c["num_key_value_heads"] * hd
    return d, hd, q, kv, c["intermediate_size"], c["num_hidden_layers"]


def projection_shapes(c: dict) -> List[Tuple[int, int]]:
    """(K, N) of each projection of one layer: wq, wk, wv, wo, w_gate,
    w_up, w_down."""
    d, _, q, kv, f, _ = _dims(c)
    return [(d, q), (d, kv), (d, kv), (q, d), (d, f), (d, f), (f, d)]


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
    _, hd, q, _, _, layers = _dims(c)
    keys = count * first_pos + count * (count + 1) // 2
    return 2 * 2 * layers * q * keys


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


def mm_least_seconds(m: int, k: int, n: int, weight_bits: int,
                     peak_ops: float, bytes_per_s: float,
                     scale_groups: int = 1) -> Tuple[float, str]:
    """Least time of one fused dequant matmul call, and which bound sets
    it ('compute' or 'memory'): the larger of 2*M*K*N over the peak and
    the bytes the call must move over the bandwidth. The bytes are the
    stored weight (K*N*bits/8), its f32 scales (G*N*4), the f32 static
    activation scale, the f32 input (M*K*4) and the f32 output (M*N*4),
    as the kernel takes and returns them."""
    ops = 2 * m * k * n
    moved = (k * n * weight_bits // 8 + scale_groups * n * 4 + 4
             + m * k * 4 + m * n * 4)
    t_ops, t_mem = ops / peak_ops, moved / bytes_per_s
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
