"""Gated MLP (SwiGLU / GeGLU) under the mixed-precision policy."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.layers.common import activation
from repro.layers.mplinear import linear_init, mp_linear
from repro.parallel import act_sharding


def init(key, d_model: int, d_ff: int, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": linear_init(k1, d_model, d_ff, False, dtype),
        "w_up": linear_init(k2, d_model, d_ff, False, dtype),
        "w_down": linear_init(k3, d_ff, d_model, False, dtype),
    }


@jax.named_scope("mlp")
def forward(params, x, policy, path: str, act: str = "silu"):
    fn = activation(act)
    g = mp_linear(params["w_gate"], x, policy.spec_for(f"{path}/w_gate"), path=f"{path}/w_gate")
    u = mp_linear(params["w_up"], x, policy.spec_for(f"{path}/w_up"), path=f"{path}/w_up")
    h = act_sharding.ffn_hidden(
        fn(g.astype(jnp.float32)).astype(u.dtype) * u)
    return act_sharding.batch_seq(
        mp_linear(params["w_down"], h, policy.spec_for(f"{path}/w_down"), path=f"{path}/w_down"))
