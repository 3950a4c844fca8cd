"""Subprocess entry for multi-device tests (run with forced host devices).

Modes:
  lower <arch> <mesh>    — lower+compile reduced-arch train step
  run <arch> <mesh>      — run 3 real train steps, print losses
  elastic <arch>         — checkpoint on (2,4), restore+step on (4,2)
  serve <arch> <mesh>    — lower prefill+decode on the mesh
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import reduced  # noqa: E402
from repro.configs.base import InputShape  # noqa: E402
from repro.data.pipeline import batch_for  # noqa: E402
from repro.launch.mesh import make_mesh as auto_mesh  # noqa: E402
from repro.launch.train import (TrainConfig, init_state,  # noqa: E402
                                make_train_step, state_shardings)
from repro.models import registry  # noqa: E402
from repro.parallel import sharding as shd  # noqa: E402


def make_mesh(name):
    if name == "multi":
        return auto_mesh((2, 2, 2), ("pod", "data", "model"))
    if name == "mesh42":
        return auto_mesh((4, 2), ("data", "model"))
    return auto_mesh((2, 4), ("data", "model"))


def main():
    mode, arch = sys.argv[1], sys.argv[2]
    mesh_name = sys.argv[3] if len(sys.argv) > 3 else "single"
    cfg = reduced(arch)
    api = registry.build(cfg)
    shape = InputShape("t", 32, 8, "train")
    batch_shape = registry.input_specs(cfg, shape)
    mesh = make_mesh(mesh_name)

    if mode in ("lower", "run"):
        with jax.set_mesh(mesh):
            step, st_sh, b_sh = make_train_step(api, mesh, TrainConfig(),
                                                batch_shape)
            if mode == "lower":
                state_shape = jax.eval_shape(
                    lambda k: init_state(api, k), jax.random.PRNGKey(0))
                step.lower(state_shape, batch_shape).compile()
                print("LOWER_OK")
                return
            state = init_state(api, jax.random.PRNGKey(0))
            state = jax.device_put(state, st_sh)
            losses = []
            for i in range(3):
                batch = jax.device_put(batch_for(cfg, shape, i), b_sh)
                state, metrics = step(state, batch)
                losses.append(float(metrics["loss"]))
            assert all(np.isfinite(l) for l in losses), losses
            print("RUN_OK", " ".join(f"{l:.4f}" for l in losses))
            return

    if mode == "elastic":
        import tempfile
        from repro.checkpoint import CheckpointManager
        from repro.launch.train import TrainState
        tmp = tempfile.mkdtemp()
        mesh_a = make_mesh("single")
        with jax.set_mesh(mesh_a):
            step_a, sh_a, bsh_a = make_train_step(
                api, mesh_a, TrainConfig(), batch_shape)
            state = jax.device_put(init_state(api, jax.random.PRNGKey(0)),
                                   sh_a)
            batch = jax.device_put(batch_for(cfg, shape, 0), bsh_a)
            state, m0 = step_a(state, batch)
            CheckpointManager(tmp).save(1, state)
        # restore onto a different mesh topology
        mesh_b = make_mesh("mesh42")
        with jax.set_mesh(mesh_b):
            step_b, sh_b, bsh_b = make_train_step(
                api, mesh_b, TrainConfig(), batch_shape)
            state_shape = jax.eval_shape(
                lambda k: init_state(api, k), jax.random.PRNGKey(0))
            s, st, _ = CheckpointManager(tmp).restore_latest(state_shape,
                                                             sh_b)
            assert s == 1
            st2, m1 = step_b(st, jax.device_put(batch_for(cfg, shape, 1),
                                                bsh_b))
            assert np.isfinite(float(m1["loss"]))
            print("ELASTIC_OK", f"{float(m1['loss']):.4f}")
            return

    if mode == "serve":
        cache_len = 64
        with jax.set_mesh(mesh):
            param_shape = jax.eval_shape(api.init, jax.random.PRNGKey(0))
            p_sh = shd.param_shardings(param_shape, mesh)
            cache_shape = jax.eval_shape(lambda: api.init_cache(8, cache_len))
            c_sh = shd.cache_shardings(cache_shape, mesh)
            dshape = {"token": jax.ShapeDtypeStruct((8, 1), jnp.int32),
                      "pos": jax.ShapeDtypeStruct((8,), jnp.int32)}
            fn = jax.jit(lambda p, b, c: api.decode_step(p, b, c),
                         in_shardings=(p_sh, None, c_sh))
            fn.lower(param_shape, dshape, cache_shape).compile()
            print("SERVE_OK")
            return

    raise SystemExit(f"unknown mode {mode}")


if __name__ == "__main__":
    main()
