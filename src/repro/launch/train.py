"""Distributed training step + CLI trainer.

make_train_step builds the jitted SPMD step for a mesh: forward + grad +
AdamW + optional dynamic loss scaling, with donated state buffers and
fully sharded params/optimizer. ``compression='blockfp8'`` switches the
cross-pod gradient sync to the bounded-alignment block-FP compressed
all-reduce (parallel/blockfp.py) via a shard_map over the pod axis — the
paper's alignment insight applied to the DCI-bound roofline term.

CLI (single host, small configs):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
      --reduced --steps 100 --batch 8 --seq 128 --ckpt-dir /tmp/ck
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig
from repro.launch.mesh import make_mesh
from repro.models import registry
from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                         warmup_cosine)
from repro.optim.loss_scale import (LossScaleState, grads_finite,
                                    loss_scale_init, loss_scale_update)
from repro.parallel import sharding as shd
from repro.runtime.compile_cache import use_compile_cache


class TrainState(NamedTuple):
    params: Any
    opt: Any
    loss_scale: LossScaleState
    step: jax.Array


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    adamw: AdamWConfig = AdamWConfig()
    warmup: int = 100
    total_steps: int = 10_000
    use_loss_scaling: bool = False   # fp16-arithmetic policies
    compression: str = "none"        # none | blockfp8 | int8 (pod grads)
    # Gradient accumulation: split the global batch into this many
    # microbatches run through a checkpointed scan — divides activation
    # memory by the count at identical math (grads are exact means).
    microbatches: int = 1


def init_state(api: registry.ModelAPI, key) -> TrainState:
    params = api.init(key)
    return TrainState(params, adamw_init(params), loss_scale_init(),
                      jnp.zeros((), jnp.int32))


def init_sharded_state(api: registry.ModelAPI, key,
                       shardings: TrainState) -> TrainState:
    """``init_state`` built straight into its shardings: no device ever
    holds the whole state (the values match ``init_state``'s, as JAX's
    random bits do not depend on the partitioning)."""
    return jax.jit(functools.partial(init_state, api),
                   out_shardings=shardings)(key)


def state_shardings(state_shape: TrainState, mesh: Mesh) -> TrainState:
    return TrainState(
        params=shd.param_shardings(state_shape.params, mesh),
        opt=shd.opt_shardings(state_shape.opt, mesh),
        loss_scale=jax.tree.map(lambda _: shd.replicated(mesh),
                                state_shape.loss_scale),
        step=shd.replicated(mesh),
    )


def _grad_once(api, tc: TrainConfig, state: TrainState, batch):
    def scaled_loss(p):
        loss, metrics = api.loss_fn(p, batch)
        return loss * state.loss_scale.scale, (loss, metrics)

    if tc.use_loss_scaling:
        grads, (loss, metrics) = jax.grad(scaled_loss, has_aux=True)(
            state.params)
        inv = 1.0 / state.loss_scale.scale
        grads = jax.tree.map(lambda g: g.astype(jnp.float32) * inv, grads)
    else:
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: api.loss_fn(p, batch), has_aux=True)(state.params)
    return grads, loss, metrics


def _grad_step(api: registry.ModelAPI, tc: TrainConfig, state: TrainState,
               batch):
    if tc.microbatches <= 1:
        return _grad_once(api, tc, state, batch)
    mb = tc.microbatches

    def split(x):
        b = x.shape[0]
        assert b % mb == 0, (b, mb)
        return jnp.moveaxis(x.reshape(mb, b // mb, *x.shape[1:]), 0, 0)

    micro = jax.tree.map(split, batch)

    def mb_step(carry, mbatch):
        g_acc, l_acc = carry
        grads, loss, _ = _grad_once(api, tc, state, mbatch)
        g_acc = jax.tree.map(
            lambda a, g: a + g.astype(jnp.float32) / mb, g_acc, grads)
        return (g_acc, l_acc + loss / mb), None

    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                      state.params)
    # no checkpoint needed: each scan step runs its own fwd+bwd internally
    (grads, loss), _ = jax.lax.scan(
        mb_step, (g0, jnp.zeros((), jnp.float32)), micro)
    return grads, loss, {"nll": loss, "aux": jnp.zeros(())}


def _apply_updates(api, tc: TrainConfig, state: TrainState, grads, loss,
                   metrics):
    finite = grads_finite(grads)
    lr_scale = warmup_cosine(state.step, warmup=tc.warmup,
                             total=tc.total_steps)
    new_params, new_opt, opt_metrics = adamw_update(
        tc.adamw, state.params, grads, state.opt, lr_scale)
    if tc.use_loss_scaling:
        # skip the update on overflow; adjust the scale
        new_params = jax.tree.map(
            lambda n, o: jnp.where(finite, n, o), new_params, state.params)
        new_opt = jax.tree.map(
            lambda n, o: jnp.where(finite, n, o), new_opt, state.opt)
        new_ls = loss_scale_update(state.loss_scale, finite)
    else:
        new_ls = state.loss_scale
    new_state = TrainState(new_params, new_opt, new_ls, state.step + 1)
    out_metrics = {"loss": loss, "finite": finite.astype(jnp.float32),
                   **{k: v for k, v in metrics.items()},
                   **opt_metrics,
                   "loss_scale": state.loss_scale.scale}
    return new_state, out_metrics


def make_train_step(api: registry.ModelAPI, mesh: Mesh,
                    tc: TrainConfig = TrainConfig(),
                    batch_shape: Optional[Dict] = None,
                    donate: bool = True):
    """Returns (jitted step fn, state_shardings, batch_shardings)."""

    if tc.compression != "none" and "pod" in mesh.axis_names:
        raise NotImplementedError(
            "compressed cross-pod gradient sync is the hierarchical-DP "
            "exchange program: see parallel.blockfp.make_pod_exchange "
            "(benchmarked in tools/exchange_bench.py / §Perf)")

    def step(state: TrainState, batch):
        grads, loss, metrics = _grad_step(api, tc, state, batch)
        return _apply_updates(api, tc, state, grads, loss, metrics)

    state_shape = jax.eval_shape(
        lambda k: init_state(api, k), jax.random.PRNGKey(0))
    st_shard = state_shardings(state_shape, mesh)
    if batch_shape is None:
        batch_shard = None
        in_shardings = (st_shard, None)
    else:
        batch_shard = shd.batch_shardings(batch_shape, mesh)
        in_shardings = (st_shard, batch_shard)
    jitted = jax.jit(step,
                     in_shardings=in_shardings,
                     out_shardings=(st_shard, None),
                     donate_argnums=(0,) if donate else ())
    return jitted, st_shard, batch_shard


# ----------------------------------------------------------------- CLI

def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--policy", default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()
    use_compile_cache()

    from repro.configs import get_config, reduced
    from repro.data.pipeline import DataConfig, SyntheticLMDataset
    from repro.runtime.fault_tolerance import (FTConfig, FaultTolerantLoop)

    cfg = reduced(args.arch) if args.reduced else get_config(args.arch)
    if args.policy:
        cfg = dataclasses.replace(cfg, precision_policy=args.policy)
    api = registry.build(cfg)
    mesh = make_mesh((1, jax.device_count()), ("data", "model"))
    tc = TrainConfig(adamw=AdamWConfig(lr=args.lr),
                     total_steps=args.steps)
    with jax.set_mesh(mesh):
        step_fn, st_shard, _ = make_train_step(api, mesh, tc)
        state = init_sharded_state(api, jax.random.PRNGKey(0), st_shard)

        ds = SyntheticLMDataset(DataConfig(
            vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch))

        loop = FaultTolerantLoop(
            step_fn=lambda s, b: step_fn(s, b),
            batch_fn=ds.batch,
            ckpt_dir=args.ckpt_dir,
            cfg=FTConfig(checkpoint_every=args.ckpt_every),
        )
        t0 = time.time()
        state, step = loop.run(state, 0, args.steps)
        dt = time.time() - t0
    losses = [h["loss"] for h in loop.history]
    print(f"arch={cfg.arch_id} steps={step} time={dt:.1f}s "
          f"loss[0]={losses[0]:.4f} loss[-1]={losses[-1]:.4f} "
          f"markov_entropy={np.log(16):.4f}")


if __name__ == "__main__":
    main()
