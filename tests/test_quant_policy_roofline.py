"""Fast coverage: quantization properties, precision policies, cell
configs, and the HLO collective parser."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.policy import POLICIES, get_policy
from repro.launch.cell_configs import RECOMMENDED, recommended
from repro.launch.roofline import (_ring_factor, _shape_bytes,
                                   parse_collectives)
from repro.quant.quantize import (calibrate_absmax, dequantize, fake_quant,
                                  quantize_symmetric)


class TestQuant:
    @given(st.integers(0, 1000), st.sampled_from([4, 8]))
    @settings(max_examples=30, deadline=None)
    def test_roundtrip_error_bounded(self, seed, bits):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(0, 1, 256), jnp.float32)
        q, s = quantize_symmetric(x, bits)
        y = dequantize(q, s)
        # error <= scale/2 (round-to-nearest) except clipped extremes
        err = np.abs(np.asarray(y) - np.asarray(x))
        assert (err <= float(s) * 0.5 + 1e-7).all()

    def test_int_range(self):
        x = jnp.linspace(-3, 3, 100)
        for bits in (4, 8):
            q, _ = quantize_symmetric(x, bits)
            qmax = (1 << (bits - 1)) - 1
            assert int(jnp.min(q)) >= -qmax - 1
            assert int(jnp.max(q)) <= qmax

    def test_fake_quant_straight_through(self):
        import jax
        x = jnp.asarray([0.1, -0.7, 0.5])
        g = jax.grad(lambda v: fake_quant(v, 4).sum())(x)
        np.testing.assert_allclose(np.asarray(g), 1.0)  # identity STE

    def test_per_channel_axis(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(0, 1, (16, 8))
                        * np.asarray([1, 100] * 4 + [1] * 8)[None, :8],
                        jnp.float32)
        q, s = quantize_symmetric(x, 8, axis=0)
        assert s.shape == (1, 8)  # one scale per output channel


class TestPolicies:
    def test_all_policies_resolve(self):
        for name, pol in POLICIES.items():
            spec = pol.spec_for("block/full/attn/wq")
            assert spec.mode in ("bf16", "fp32", "int8", "int4", "fp16_ipu")

    def test_hybrid_keeps_sensitive_layers_fp(self):
        pol = get_policy("paper_hybrid")
        assert pol.spec_for("lm_head").mode == "fp16_ipu"
        assert pol.spec_for("block/attn/wo").mode == "fp16_ipu"
        assert pol.spec_for("block/mlp/w_gate").mode == "int4"

    def test_first_match_wins(self):
        pol = get_policy("int4_serving")
        assert pol.spec_for("router/w").mode == "bf16"
        assert pol.spec_for("block/moe/experts").mode == "int4"


class TestCellConfigs:
    def test_every_recommended_cell_is_valid(self):
        from repro.configs import ARCH_IDS
        from repro.configs.base import SHAPES
        for (arch, shape), cc in RECOMMENDED.items():
            assert arch in ARCH_IDS, arch
            assert shape in SHAPES, shape
            assert cc.microbatches >= 1
            if cc.moe_dispatch:
                assert cc.moe_dispatch in ("einsum", "gather")

    def test_defaults_for_unlisted(self):
        cc = recommended("rwkv6-1.6b", "decode_32k")
        assert cc.microbatches == 1 and cc.moe_dispatch is None


class TestRooflineParser:
    def test_shape_bytes(self):
        assert _shape_bytes("f32[128,256]") == 128 * 256 * 4
        assert _shape_bytes("bf16[8]") == 16
        assert _shape_bytes("(f32[4], s8[2,2])") == 16 + 4

    def test_ring_factors(self):
        assert _ring_factor("all-reduce", 2) == pytest.approx(1.0)
        assert _ring_factor("all-gather", 4) == pytest.approx(0.75)
        assert _ring_factor("collective-permute", 8) == 1.0
        assert _ring_factor("all-reduce", 1) == 0.0

    def test_parse_synthetic_hlo(self):
        hlo = """
  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %x), replica_groups={{0,1}}
  %ag = (f32[64,32]{1,0}) all-gather(f32[16,32]{1,0} %y), replica_groups=[2,4]<=[8]
"""
        stats = parse_collectives(hlo, default_group=8)
        assert stats.count == 2
        # all-reduce: 4096 B * 2*(1/2) = 4096
        assert stats.by_op["all-reduce"] == pytest.approx(4096)
        # all-gather: out 64*32*4 / group 4 * 3/4 = 1536
        assert stats.by_op["all-gather"] == pytest.approx(
            64 * 32 * 4 / 4 * 0.75)
