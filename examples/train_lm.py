"""Train a small LM on the synthetic Markov stream with the full stack:
sharded train step, AdamW, LR schedule, fault-tolerant loop with
checkpointing, and a mixed-precision policy.

Default is a fast CPU demo (~2 min). Scale knobs up on real hardware:

    PYTHONPATH=src python examples/train_lm.py \
        --d-model 256 --layers 4 --steps 200 --policy int8_serving
"""
import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import reduced
from repro.data.pipeline import DataConfig, SyntheticLMDataset
from repro.launch.mesh import make_mesh
from repro.launch.train import TrainConfig, init_state, make_train_step
from repro.models import registry
from repro.optim import AdamWConfig
from repro.runtime.fault_tolerance import FTConfig, FaultTolerantLoop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--policy", default="bf16")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--resume", action="store_true",
                    help="resume from existing checkpoints (default: "
                         "start fresh)")
    args = ap.parse_args()
    if not args.resume:
        import shutil
        shutil.rmtree(args.ckpt_dir, ignore_errors=True)

    cfg = dataclasses.replace(
        reduced(args.arch),
        d_model=args.d_model, n_layers=args.layers, d_ff=4 * args.d_model,
        vocab=args.vocab, precision_policy=args.policy,
        head_dim=args.d_model // 4)
    api = registry.build(cfg)
    print(f"arch={cfg.arch_id} params~{cfg.params_count()/1e6:.1f}M "
          f"policy={args.policy}")

    mesh = make_mesh((1, 1), ("data", "model"))
    tc = TrainConfig(adamw=AdamWConfig(lr=args.lr), warmup=20,
                     total_steps=args.steps)
    with jax.set_mesh(mesh):
        step_fn, st_shard, _ = make_train_step(api, mesh, tc)
        state = init_state(api, jax.random.PRNGKey(0))

        ds = SyntheticLMDataset(DataConfig(
            vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch))
        loop = FaultTolerantLoop(
            step_fn=lambda s, b: step_fn(s, b), batch_fn=ds.batch,
            ckpt_dir=args.ckpt_dir, cfg=FTConfig(checkpoint_every=50))

        t0 = time.time()
        state, step = loop.run(state, 0, args.steps)
        dt = time.time() - t0

    losses = [h["loss"] for h in loop.history]
    ent = ds.conditional_entropy()
    print(f"steps={step} time={dt:.1f}s "
          f"({args.steps * args.batch * args.seq / dt:.0f} tok/s)")
    print(f"loss: start={losses[0]:.3f} -> end={losses[-1]:.3f} "
          f"(markov entropy floor = {ent:.3f} nats)")
    assert losses[-1] < losses[0], "no learning happened"
    if losses[-1] < 0.8 * losses[0]:
        print("model is learning the Markov structure ✓")


if __name__ == "__main__":
    main()
