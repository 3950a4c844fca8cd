"""The Pallas kernels of the serving path compile for a TPU v5e.

Interpret mode (every other kernel test) cannot see what Mosaic, the
TPU kernel compiler, refuses: operand types the MXU does not take,
block shapes off the (8, 128) tiling, too much VMEM. These tests compile
each kernel for one chip of a described ``v5e:2x2`` topology at the
projection widths of qwen2-0.5b (d_model 896, d_ff 4864; M = 8 decode
rows) and check that the compiled program holds the kernel
(``tpu_custom_call``). Nothing runs: the chip is described, not
attached.
"""
import functools
import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels import fused, qmm

pytestmark = pytest.mark.kernel

M = 8
WIDTHS = [(896, 4864), (4864, 896)]      # (K, N): q/gate/up, down
GROUP = 128                              # per-group scale case


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with JAX's persistent
    compilation cache off (a compile for a described chip is written to
    it but cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described here: "
                            f"{e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            compilation_cache.reset_cache()


def _int_w(k, n, packed=False):
    return ((k // 2 if packed else k, n), jnp.int8)


def _code_w(k, n, packed=False):
    return ((k // 2 if packed else k, n), jnp.uint8)


# name -> (kernel over (x, w, sw, sa) or (a, b), argument (shape, dtype)s)
CASES = {
    "qmm": (lambda a, b: qmm.qmm(a, b, interpret=False),
            lambda k, n: [((M, k), jnp.int8), _int_w(k, n)]),
    "qmm_packed": (
        lambda a, b: qmm.qmm_packed(a, b, interpret=False),
        lambda k, n: [((M, k), jnp.int8), _int_w(k, n, packed=True)]),
    "fused_qmm[int8]": (
        functools.partial(fused.fused_qmm, kind="int8", interpret=False),
        lambda k, n: [((M, k), jnp.float32), _int_w(k, n),
                      ((1, n), jnp.float32), ((), jnp.float32)]),
    "fused_qmm[int4_packed]": (
        functools.partial(fused.fused_qmm, kind="int4_packed",
                          interpret=False),
        lambda k, n: [((M, k), jnp.float32), _int_w(k, n, packed=True),
                      ((1, n), jnp.float32), ((), jnp.float32)]),
    "fused_dequant_mm[int8,qdq]": (
        functools.partial(fused.fused_dequant_mm, kind="int8", act="qdq",
                          interpret=False),
        lambda k, n: [((M, k), jnp.float32), _int_w(k, n),
                      ((1, n), jnp.float32), ((), jnp.float32)]),
    "fused_dequant_mm[int4_packed,qdq]": (
        functools.partial(fused.fused_dequant_mm, kind="int4_packed",
                          act="qdq", interpret=False),
        lambda k, n: [((M, k), jnp.float32), _int_w(k, n, packed=True),
                      ((1, n), jnp.float32), ((), jnp.float32)]),
    "fused_dequant_mm[fp8]": (
        functools.partial(fused.fused_dequant_mm, kind="fp8",
                          interpret=False),
        lambda k, n: [((M, k), jnp.float32), _code_w(k, n),
                      ((1, n), jnp.float32), ((), jnp.float32)]),
    "fused_dequant_mm[fp4_packed]": (
        functools.partial(fused.fused_dequant_mm, kind="fp4_packed",
                          interpret=False),
        lambda k, n: [((M, k), jnp.float32), _code_w(k, n, packed=True),
                      ((1, n), jnp.float32), ((), jnp.float32)]),
    f"fused_dequant_mm[fp8,group{GROUP}]": (
        functools.partial(fused.fused_dequant_mm, kind="fp8",
                          interpret=False),
        lambda k, n: [((M, k), jnp.float32), _code_w(k, n),
                      ((k // GROUP, n), jnp.float32), ((), jnp.float32)]),
}


@pytest.mark.parametrize("k,n", WIDTHS, ids=[f"K{k}-N{n}" for k, n in WIDTHS])
@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(one_chip, name, k, n):
    kernel, arg_specs = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in arg_specs(k, n)]
    compiled = jax.jit(kernel).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
