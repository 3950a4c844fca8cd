"""Block-FP compressed collectives (beyond-paper: the paper's alignment
insight applied to cross-pod gradient traffic)."""
import os
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest

from hypothesis import given, settings, strategies as st

from repro.parallel.blockfp import blockfp_dequantize, blockfp_quantize

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np, re
from repro.parallel.blockfp import make_pod_exchange
from repro.launch.mesh import make_mesh
from repro.launch.roofline import parse_collectives

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
rng = np.random.default_rng(0)
grads = {"wq": {"w": jnp.asarray(rng.normal(0, 1e-3, (2, 64, 64)),
                                 jnp.float32)},
         "embed": {"w": jnp.asarray(rng.normal(0, 1e-3, (2, 512, 64)),
                                    jnp.float32)}}
shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                      grads)
ref = jax.tree.map(lambda g: jnp.broadcast_to(g.mean(0), g.shape), grads)
wire = {}
for method in ("f32", "int8", "blockfp8"):
    fn, in_sh, out_sh = make_pod_exchange(mesh, shapes, method)
    with jax.set_mesh(mesh):
        out = fn(jax.device_put(grads, in_sh))
        txt = fn.lower(shapes).compile().as_text()
    err = max(float(jnp.abs(a - b).max() / jnp.abs(b).max())
              for a, b in zip(jax.tree_util.tree_leaves(out),
                              jax.tree_util.tree_leaves(ref)))
    wire[method] = parse_collectives(txt, 8).total_bytes
    assert err < {"f32": 1e-7, "int8": 0.02, "blockfp8": 0.05}[method], \
        (method, err)
assert wire["blockfp8"] <= wire["f32"] / 3.5, wire
assert wire["int8"] <= wire["f32"] / 3.5, wire
print("EXCHANGE_OK", wire)
"""


def test_pod_exchange_subprocess():
    out = subprocess.run([sys.executable, "-c", _SCRIPT],
                         capture_output=True, text=True, timeout=420,
                         cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert out.returncode == 0, out.stdout[-1500:] + out.stderr[-1500:]
    assert "EXCHANGE_OK" in out.stdout


class TestBlockFPQuant:
    def test_roundtrip_error_bounded(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(0, 1, 5000), jnp.float32)
        m, e, n = blockfp_quantize(x, 8)
        y = blockfp_dequantize(m.astype(jnp.int32), e, n, 8, x.shape)
        # per-block error < 1 ULP of block scale = 2**(max_e - 6)
        blocks = np.asarray(x[: (5000 // 256) * 256]).reshape(-1, 256)
        scale = 2.0 ** (np.asarray(e[:len(blocks)], np.int32) - 6)
        err = np.abs(np.asarray(y)[: len(blocks) * 256].reshape(-1, 256)
                     - blocks)
        assert (err <= scale[:, None] * 1.0001).all()

    @given(st.integers(2, 8))
    @settings(max_examples=7, deadline=None)
    def test_width_sweep_monotone(self, w):
        rng = np.random.default_rng(w)
        x = jnp.asarray(rng.normal(0, 1, 2048), jnp.float32)
        m, e, n = blockfp_quantize(x, w)
        y = blockfp_dequantize(m.astype(jnp.int32), e, n, w, x.shape)
        err_w = float(jnp.abs(y - x).max())
        m2, e2, n2 = blockfp_quantize(x, min(w + 1, 8))
        y2 = blockfp_dequantize(m2.astype(jnp.int32), e2, n2,
                                min(w + 1, 8), x.shape)
        assert float(jnp.abs(y2 - x).max()) <= err_w * 1.0001

    def test_exact_on_powers_of_two(self):
        x = jnp.asarray([1.0, 0.5, 2.0, -1.0] * 64, jnp.float32)
        m, e, n = blockfp_quantize(x, 8)
        y = blockfp_dequantize(m.astype(jnp.int32), e, n, 8, x.shape)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
