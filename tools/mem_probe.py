"""Bisect per-device temp memory of the train step on the prod mesh."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=256"
import dataclasses
import sys

sys.path.insert(0, "src")
import jax

from repro.configs import get_config
from repro.configs.base import InputShape
from repro.launch.mesh import make_production_mesh
from repro.launch.train import TrainConfig, init_state, make_train_step
from repro.models import registry


def probe(tag, cfg, seq, batch):
    api = registry.build(cfg)
    shape = InputShape("p", seq, batch, "train")
    batch_shape = registry.input_specs(cfg, shape)
    mesh = make_production_mesh(multi_pod=False)
    with jax.set_mesh(mesh):
        step, _, _ = make_train_step(api, mesh, TrainConfig(), batch_shape)
        state_shape = jax.eval_shape(lambda k: init_state(api, k),
                                     jax.random.PRNGKey(0))
        comp = step.lower(state_shape, batch_shape).compile()
    ma = comp.memory_analysis()
    print(f"{tag:50s} temp={ma.temp_size_in_bytes/1e9:8.2f} GB")


base = get_config("qwen2-0.5b")
probe("L24 s4096 b256 remat=full", base, 4096, 256)
probe("L24 s4096 b256 remat=none",
      dataclasses.replace(base, remat="none"), 4096, 256)
probe("L2 s4096 b256 remat=full",
      dataclasses.replace(base, n_layers=2), 4096, 256)
probe("L24 s1024 b256 remat=full", base, 1024, 256)
probe("L24 s4096 b64 remat=full", base, 4096, 64)
