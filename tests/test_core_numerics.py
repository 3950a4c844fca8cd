"""Unit + property tests for the bit-exact IPU numerics core.

The key claim: ``repro.core.ipu`` (vectorized JAX int32 emulation) agrees
bit-for-bit with ``repro.core.exact_ref`` (independent Python-int oracle)
for every IPU configuration, and the measured approximation error obeys
the Theorem-1-style bounds.
"""
import math
from fractions import Fraction

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import exact_ref, error_bounds, fixedpoint as fx, fp16 as fpmod
from repro.core import ehu, nibble
from repro.core.ipu import IPUConfig, fp16_inner_product, int_inner_product

# ---------------------------------------------------------------- helpers

def rand_fp16(rng, n, scale=1.0, dist="normal"):
    if dist == "normal":
        x = rng.normal(0, scale, n)
    elif dist == "laplace":
        x = rng.laplace(0, scale, n)
    elif dist == "uniform":
        x = rng.uniform(-scale, scale, n)
    elif dist == "wide":
        x = rng.normal(0, 1, n) * np.exp2(rng.integers(-12, 14, n))
    else:
        raise ValueError(dist)
    x = np.asarray(x, np.float16)
    x[~np.isfinite(x)] = 0.0
    return x


finite_f16 = st.integers(min_value=0, max_value=0xFFFF).map(
    lambda b: np.uint16(b).view(np.float16)
).filter(lambda v: np.isfinite(v))


# ------------------------------------------------------------- fp16 codec

class TestCodec:
    def test_roundtrip_all_finite_fp16(self):
        bits = np.arange(1 << 16, dtype=np.uint16)
        x = bits.view(np.float16)
        finite = np.isfinite(x)
        x = jnp.asarray(x[finite])
        s, e, m = fpmod.decompose(x, fpmod.FP16)
        # value identity
        val = np.asarray(s, np.float64) * np.asarray(m, np.float64) * np.exp2(
            np.asarray(e, np.float64) - 10)
        np.testing.assert_array_equal(val, np.asarray(x, np.float64))
        # bit roundtrip (sign of -0 is dropped: compare values)
        back = fpmod.compose(s, e, m, fpmod.FP16)
        np.testing.assert_array_equal(np.asarray(back, np.float64),
                                      np.asarray(x, np.float64))

    def test_fp32_decompose_values(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(0, 1e3, 256), jnp.float32)
        s, e, m = fpmod.decompose(x, fpmod.FP32)
        val = np.asarray(s, np.float64) * np.asarray(m, np.float64) * np.exp2(
            np.asarray(e, np.float64) - 23)
        np.testing.assert_array_equal(val, np.asarray(x, np.float64))

    def test_product_exponent_range(self):
        assert fpmod.product_exponent_range(fpmod.FP16) == (-28, 30)
        assert fpmod.max_alignment(fpmod.FP16) == 58  # paper §2.2

    def test_make_inf(self):
        out = fpmod.make_inf(jnp.asarray([1, -1]), fpmod.FP16)
        assert np.isposinf(np.asarray(out[0], np.float64))
        assert np.isneginf(np.asarray(out[1], np.float64))


# ------------------------------------------------------------ fixedpoint

class TestFixedPoint:
    @given(st.integers(-(2**47), 2**47), st.integers(-(2**47), 2**47))
    @settings(max_examples=200, deadline=None)
    def test_add(self, a, b):
        if abs(a + b) >= 2**53:
            return
        fa = fx.canon(jnp.int32(a // 2**24), jnp.int32(a % 2**24))
        fb = fx.canon(jnp.int32(b // 2**24), jnp.int32(b % 2**24))
        r = fx.add(fa, fb)
        assert int(r.hi) * 2**24 + int(r.lo) == a + b

    @given(st.integers(-(2**47), 2**47), st.integers(0, 60))
    @settings(max_examples=200, deadline=None)
    def test_shr_trunc(self, a, s):
        fa = fx.canon(jnp.int32(a // 2**24), jnp.int32(a % 2**24))
        r = fx.shr_trunc(fa, jnp.int32(s))
        expect = (abs(a) >> s) * (1 if a >= 0 else -1)
        assert int(r.hi) * 2**24 + int(r.lo) == expect

    @given(st.integers(-(2**47), 2**47), st.integers(0, 60))
    @settings(max_examples=200, deadline=None)
    def test_shr_floor(self, a, s):
        fa = fx.canon(jnp.int32(a // 2**24), jnp.int32(a % 2**24))
        r = fx.shr_floor(fa, jnp.int32(s))
        assert int(r.hi) * 2**24 + int(r.lo) == a >> s

    @given(st.integers(0, 2**30), st.integers(0, 21))
    @settings(max_examples=200, deadline=None)
    def test_shl(self, a, s):
        fa = fx.canon(jnp.int32(a // 2**24), jnp.int32(a % 2**24))
        r = fx.shl(fa, s)
        assert int(r.hi) * 2**24 + int(r.lo) == a << s

    @given(st.integers(-(2**46), 2**46), st.integers(-40, 20))
    @settings(max_examples=300, deadline=None)
    def test_round_to_fp(self, mag_signed, exp):
        """round_to_fp == python-int RNE oracle for fp16 and fp32."""
        v = fx.canon(jnp.int32(mag_signed // 2**24),
                     jnp.int32(mag_signed % 2**24))
        e = jnp.int32(exp)
        for fmt_name, fmt in (("fp16", fpmod.FP16), ("fp32", fpmod.FP32)):
            got = fx.round_to_fp(v, e, fmt)
            sign = -1 if mag_signed < 0 else 1
            want = exact_ref.round_value_to_fp(sign, abs(mag_signed),
                                               exp - 30, fmt_name)
            g = np.asarray(got, np.float64)
            w = np.float64(want)
            assert (g == w) or (np.isnan(g) and np.isnan(w)), (
                f"{fmt_name}: mag={mag_signed} exp={exp}: {g} != {w}")


# --------------------------------------------------------------- nibbles

class TestNibble:
    def test_fp16_plane_identity(self):
        bits = np.arange(1 << 16, dtype=np.uint16)
        x = bits.view(np.float16)
        x = jnp.asarray(x[np.isfinite(x)])
        s, e, m = fpmod.decompose(x, fpmod.FP16)
        n0, n1, n2 = nibble.fp16_planes(s, m)
        recon = (np.asarray(n2, np.float64) * 2.0**7
                 + np.asarray(n1, np.float64) * 2.0**3
                 + np.asarray(n0, np.float64) * 0.5)
        np.testing.assert_array_equal(
            recon, np.asarray(s, np.float64) * np.asarray(m, np.float64))

    @pytest.mark.parametrize("bits", [4, 8, 12])
    def test_int_plane_identity(self, bits):
        lo, hi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
        x = jnp.arange(lo, hi + 1, dtype=jnp.int32)
        planes = nibble.int_planes(x, bits)
        recon = sum(np.asarray(p, np.int64) * 16**i
                    for i, p in enumerate(planes))
        np.testing.assert_array_equal(recon, np.asarray(x, np.int64))
        for i, p in enumerate(planes):
            p = np.asarray(p)
            if i < len(planes) - 1:
                assert p.min() >= 0 and p.max() <= 15  # unsigned low nibble
            else:
                assert p.min() >= -8 and p.max() <= 7  # signed top nibble

    def test_iteration_counts(self):
        assert nibble.num_nibble_iterations(8, 12) == 6  # paper §2.1 example
        assert nibble.num_nibble_iterations(12, 12) == 9  # FP16 mantissas


# ------------------------------------------------------------------ EHU

class TestEHU:
    def test_run_and_mask(self):
        ea = jnp.asarray([[0, 5, -3, 2]])
        eb = jnp.asarray([[0, 5, -3, 2]])
        out = ehu.run(ea, eb, sw_precision=8)
        assert int(out.max_exp[0]) == 10
        np.testing.assert_array_equal(np.asarray(out.shift[0]),
                                      [10, 0, 16, 6])
        np.testing.assert_array_equal(np.asarray(out.active[0]),
                                      [False, True, False, True])

    def test_walkthrough_fig4(self):
        """Paper Fig. 4: exponents (10,2,3,8), sp=5 -> 2 cycles; A,D in
        cycle 0 with local shifts (0,2); B,C in cycle 1 with (3,2)."""
        shift = jnp.asarray([0, 8, 7, 2])
        active = jnp.ones(4, bool)
        cycles = ehu.num_cycles(shift, active, sp=5)
        assert int(cycles) == 2
        cyc, local = ehu.service_schedule(shift, active, sp=5)
        np.testing.assert_array_equal(np.asarray(cyc), [0, 1, 1, 0])
        np.testing.assert_array_equal(np.asarray(local), [0, 3, 2, 2])

    def test_skip_empty(self):
        shift = jnp.asarray([0, 40])
        active = jnp.ones(2, bool)
        assert int(ehu.num_cycles(shift, active, sp=5)) == 9  # 40//5 + 1
        assert int(ehu.num_cycles(shift, active, sp=5, skip_empty=True)) == 2


# ------------------------------------------------------ INT-mode exactness

class TestIntMode:
    @pytest.mark.parametrize("a_bits,b_bits", [(4, 4), (8, 4), (8, 8),
                                               (8, 12), (12, 12)])
    def test_matches_integer_dot(self, a_bits, b_bits):
        rng = np.random.default_rng(1)
        a = rng.integers(-(1 << (a_bits - 1)), 1 << (a_bits - 1),
                         (16, 64)).astype(np.int32)
        b = rng.integers(-(1 << (b_bits - 1)), 1 << (b_bits - 1),
                         (16, 64)).astype(np.int32)
        got = int_inner_product(jnp.asarray(a), jnp.asarray(b),
                                a_bits, b_bits)
        want = (a.astype(np.int64) * b.astype(np.int64)).sum(-1)
        np.testing.assert_array_equal(np.asarray(got, np.int64), want)

    def test_unsigned_low_nibbles_ok(self):
        # extremes: most negative * most positive
        a = jnp.asarray([[-128, 127, -128]], jnp.int32)
        b = jnp.asarray([[127, -128, -128]], jnp.int32)
        got = int_inner_product(a, b, 8, 8)
        assert int(got[0]) == -128 * 127 * 2 + 128 * 128


# ----------------------------------------------- FP-IP vs python oracle

CONFIGS = [
    IPUConfig(n=16, w=16, accum="fp16"),
    IPUConfig(n=16, w=16, accum="fp32"),
    IPUConfig(n=16, w=28, accum="fp32"),
    IPUConfig(n=8, w=12, accum="fp32"),
    IPUConfig(n=8, w=12, accum="fp32", multi_cycle=True),
    IPUConfig(n=16, w=16, accum="fp32", multi_cycle=True),
    IPUConfig(n=16, w=12, accum="fp16", multi_cycle=True),
    IPUConfig(n=16, w=16, accum="fp32", rounding="floor"),
    IPUConfig(n=16, w=20, accum="fp32", iter_order="desc"),
]


@pytest.mark.parametrize("cfg", CONFIGS, ids=lambda c: (
    f"n{c.n}w{c.w}{c.accum}{'mc' if c.multi_cycle else ''}"
    f"{c.rounding[:2]}{c.iter_order[:1]}"))
@pytest.mark.parametrize("dist", ["normal", "wide"])
def test_fp_ip_matches_oracle(cfg, dist):
    rng = np.random.default_rng(hash((cfg.w, cfg.n, dist)) % 2**32)
    for length in (5, 33):
        a = rand_fp16(rng, length, dist=dist)
        b = rand_fp16(rng, length, dist=dist)
        got = np.asarray(fp16_inner_product(jnp.asarray(a), jnp.asarray(b),
                                            cfg))
        want = exact_ref.approx_fp_ip(a, b, cfg)
        assert got.dtype == np.dtype(np.float16 if cfg.accum == "fp16"
                                     else np.float32)
        g, w = np.float64(got), np.float64(want)
        assert (g == w) or (np.isnan(g) and np.isnan(w)), (
            f"len={length}: jax={g} oracle={w}")


def test_fp_ip_batched_matches_loop():
    rng = np.random.default_rng(7)
    cfg = IPUConfig(n=16, w=16, accum="fp32")
    a = rand_fp16(rng, 4 * 3 * 40).reshape(4, 3, 40)
    b = rand_fp16(rng, 4 * 3 * 40).reshape(4, 3, 40)
    got = np.asarray(fp16_inner_product(jnp.asarray(a), jnp.asarray(b), cfg))
    assert got.shape == (4, 3)
    for i in range(4):
        for j in range(3):
            want = exact_ref.approx_fp_ip(a[i, j], b[i, j], cfg)
            assert np.float64(got[i, j]) == np.float64(want)


def test_fp_ip_jit_and_vmap():
    cfg = IPUConfig(n=16, w=16)
    f = jax.jit(lambda a, b: fp16_inner_product(a, b, cfg))
    rng = np.random.default_rng(3)
    a = jnp.asarray(rand_fp16(rng, 8 * 32).reshape(8, 32))
    b = jnp.asarray(rand_fp16(rng, 8 * 32).reshape(8, 32))
    direct = fp16_inner_product(a, b, cfg)
    np.testing.assert_array_equal(np.asarray(f(a, b)), np.asarray(direct))
    vm = jax.vmap(lambda x, y: fp16_inner_product(x, y, cfg))(a, b)
    np.testing.assert_array_equal(np.asarray(vm), np.asarray(direct))


# --------------------------------------------------- accuracy properties

def test_high_precision_is_exactish():
    """At w=28/fp32 accumulation the paper reports CPU-level accuracy; the
    result must match the f64 dot to fp32 within 1 ulp-ish."""
    rng = np.random.default_rng(11)
    cfg = IPUConfig(n=16, w=28, accum="fp32", sw_precision=28)
    for _ in range(20):
        a = rand_fp16(rng, 64)
        b = rand_fp16(rng, 64)
        got = np.float64(np.asarray(
            fp16_inner_product(jnp.asarray(a), jnp.asarray(b), cfg)))
        want = float(exact_ref.exact_dot(a, b))
        if want == 0:
            assert abs(got) < 1e-6
        else:
            assert abs(got - want) <= 2e-6 * abs(want) + 1e-12


def test_mc_ipu_at_least_as_accurate_as_plain():
    """MC-IPU(w) with software precision P serves alignments exactly within
    each band, so its error must not exceed plain IPU(w) truncation error
    (statistically; we assert on aggregate)."""
    rng = np.random.default_rng(13)
    plain_err = mc_err = 0.0
    for _ in range(30):
        a = rand_fp16(rng, 32, dist="wide")
        b = rand_fp16(rng, 32, dist="wide")
        exact = float(exact_ref.exact_dot(a, b))
        plain = np.float64(np.asarray(fp16_inner_product(
            jnp.asarray(a), jnp.asarray(b),
            IPUConfig(n=16, w=12, accum="fp32"))))
        mc = np.float64(np.asarray(fp16_inner_product(
            jnp.asarray(a), jnp.asarray(b),
            IPUConfig(n=16, w=12, accum="fp32", multi_cycle=True))))
        plain_err += abs(plain - exact)
        mc_err += abs(mc - exact)
    assert mc_err <= plain_err + 1e-9


@given(st.lists(finite_f16, min_size=2, max_size=16),
       st.lists(finite_f16, min_size=2, max_size=16),
       st.sampled_from([12, 16, 20, 28]))
@settings(max_examples=80, deadline=None)
def test_theorem1_tight_bound_property(xs, ys, w):
    """Measured |approx - exact| <= sum of tight iteration bounds plus
    accumulator-granularity slack, for adversarial (hypothesis) inputs."""
    n = min(len(xs), len(ys))
    if n == 0:
        return
    # Pad to a fixed length so each w compiles exactly once (zeros only
    # lower exponents below max and contribute nothing).
    a = np.zeros(16, np.float16)
    b = np.zeros(16, np.float16)
    a[:n] = xs[:n]
    b[:n] = ys[:n]
    if not (np.isfinite(a).all() and np.isfinite(b).all()):
        return
    n = 16
    cfg = IPUConfig(n=16, w=w, accum="fp32", sw_precision=w)
    got = Fraction(np.float64(np.asarray(
        fp16_inner_product(jnp.asarray(a), jnp.asarray(b), cfg))))
    exact = exact_ref.exact_dot(a, b)
    prods = [exact_ref.decompose_fp16(x)[1] + exact_ref.decompose_fp16(y)[1]
             for x, y in zip(a, b)]
    max_exp = max(prods)
    # 9 iterations truncate in the tree; every acc update can truncate one
    # more ULP at 2**(max-30); 9 updates + final rounding half-ulp slack.
    bound = error_bounds.fp_ip_bound(w, max_exp, n,
                                     constant=error_bounds.TIGHT_CONSTANT,
                                     acc_granularity_updates=16)
    # final output rounding to fp32: half ULP of the result
    out_ulp = Fraction(2) ** (max_exp + 10 - 23)
    assert abs(got - exact) <= bound + out_ulp, (
        f"err={float(abs(got - exact))} bound={float(bound)}")


# ------------------------------------------------------- BF16 (Appendix B)

class TestBF16Operands:
    """Paper Appendix B: BF16 via an 8-bit-exponent EHU and four nibble
    iterations (2 planes x 2 planes)."""

    @pytest.mark.parametrize("w", [12, 16, 28])
    @pytest.mark.parametrize("dist", ["normal", "wide"])
    def test_matches_oracle(self, w, dist):
        cfg = IPUConfig(n=16, w=w, accum="fp32", operand="bf16")
        rng = np.random.default_rng(hash((w, dist)) % 2**32)
        for length in (5, 33):
            raw = rand_fp16(rng, length, dist=dist).astype(np.float32)
            a = np.asarray(jnp.asarray(raw, jnp.bfloat16))
            raw = rand_fp16(rng, length, dist=dist).astype(np.float32)
            b = np.asarray(jnp.asarray(raw, jnp.bfloat16))
            got = np.asarray(fp16_inner_product(jnp.asarray(a),
                                                jnp.asarray(b), cfg),
                             np.float32)
            want = exact_ref.approx_fp_ip(a.astype(np.float32),
                                          b.astype(np.float32), cfg)
            assert np.float64(got) == np.float64(want), (length, got, want)

    def test_iteration_count(self):
        cfg = IPUConfig(operand="bf16")
        assert len(cfg.iteration_pairs()) == 4  # paper: "four iterations"
        assert cfg.num_planes == 2

    def test_high_precision_accurate(self):
        cfg = IPUConfig(n=16, w=28, accum="fp32", operand="bf16",
                        sw_precision=28)
        rng = np.random.default_rng(5)
        raw = rng.normal(0, 1, 64).astype(np.float32)
        a = np.asarray(jnp.asarray(raw, jnp.bfloat16))
        b = np.asarray(jnp.asarray(rng.normal(0, 1, 64).astype(np.float32),
                                   jnp.bfloat16))
        got = np.float64(np.asarray(fp16_inner_product(
            jnp.asarray(a), jnp.asarray(b), cfg)))
        want = float(exact_ref.exact_dot(a.astype(np.float32),
                                         b.astype(np.float32),
                                         operand="bf16"))
        assert abs(got - want) <= 2e-6 * abs(want) + 1e-10

    def test_bf16_plane_identity(self):
        mag = jnp.arange(256, dtype=jnp.int32)
        sign = jnp.where(mag % 3 == 0, -1, 1)
        n0, n1 = nibble.bf16_planes(sign, mag)
        recon = np.asarray(n1, np.int64) * 16 + np.asarray(n0, np.int64)
        np.testing.assert_array_equal(
            recon, np.asarray(sign * mag, np.int64))


class TestTF32Operands:
    """TF32 (paper Appendix B): FP16's 11-bit magnitude planes on an
    8-bit-exponent EHU; f32 inputs RNE-rounded to TF32."""

    @pytest.mark.parametrize("w", [12, 16, 28])
    def test_matches_oracle(self, w):
        cfg = IPUConfig(n=16, w=w, accum="fp32", operand="tf32")
        rng = np.random.default_rng(w)
        for length in (5, 33):
            a = (rng.normal(0, 1, length)
                 * np.exp2(rng.integers(-20, 20, length))).astype(np.float32)
            b = (rng.normal(0, 1, length)
                 * np.exp2(rng.integers(-20, 20, length))).astype(np.float32)
            got = np.asarray(fp16_inner_product(jnp.asarray(a),
                                                jnp.asarray(b), cfg),
                             np.float32)
            want = exact_ref.approx_fp_ip(a, b, cfg)
            assert np.float64(got) == np.float64(want), (length, got, want)

    def test_high_precision_accurate(self):
        cfg = IPUConfig(n=16, w=28, accum="fp32", operand="tf32",
                        sw_precision=28)
        rng = np.random.default_rng(3)
        a = rng.normal(0, 1, 64).astype(np.float32)
        b = rng.normal(0, 1, 64).astype(np.float32)
        got = np.float64(np.asarray(fp16_inner_product(
            jnp.asarray(a), jnp.asarray(b), cfg)))
        want = float(exact_ref.exact_dot(a, b, operand="tf32"))
        assert abs(got - want) <= 2e-6 * abs(want) + 1e-10

    def test_nine_iterations(self):
        cfg = IPUConfig(operand="tf32")
        assert len(cfg.iteration_pairs()) == 9
        assert cfg.num_planes == 3
