import os
os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_EXTRA_XLA", "") +
                           " --xla_force_host_platform_device_count="
                           + os.environ.get("DRYRUN_DEVICES", "512")).strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

THE FIRST LINES ABOVE MUST STAY FIRST: jax locks the device count on
first init, so the 512 placeholder host devices must be configured
before any jax import (including `from repro...`).

For each cell this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. builds abstract state via jax.eval_shape (no allocation anywhere),
  3. jits the step (train_step / prefill / decode_step) with the
     sharding rules, .lower(...).compile(),
  4. records memory_analysis (fits-per-device proof), cost_analysis
     (FLOPs/bytes), and the parsed collective schedule into a JSON
     roofline record (EXPERIMENTS.md §Dry-run / §Roofline read these).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b \
      --shape train_4k --mesh single --out results/dryrun
  PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun
"""
import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ARCH_IDS, get_config          # noqa: E402
from repro.configs.base import SHAPES, shape_applicable  # noqa: E402
from repro.launch import roofline as rl                 # noqa: E402
from repro.launch.mesh import make_production_mesh      # noqa: E402
from repro.launch.train import (TrainConfig, init_state,  # noqa: E402
                                make_train_step)
from repro.models import registry                       # noqa: E402
from repro.parallel import sharding as shd              # noqa: E402


def _mem_analysis_dict(compiled):
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def _cost_analysis_dict(compiled):
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {k: float(v) for k, v in dict(ca).items()
            if isinstance(v, (int, float))}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             compression: str = "none",
             remat: str = "full", microbatches: int = 1,
             moe_dispatch: str = None) -> dict:
    cfg = get_config(arch)
    cfg = dataclasses.replace(cfg, remat=remat)
    if moe_dispatch and cfg.moe:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, dispatch=moe_dispatch))
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind, "status": "skipped"}
    if not shape_applicable(cfg, shape):
        rec["reason"] = "long_500k needs sub-quadratic attention"
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    api = registry.build(cfg)
    batch_shape = registry.input_specs(cfg, shape)
    t0 = time.time()

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            tc = TrainConfig(compression=compression,
                             microbatches=microbatches)
            step, st_shard, b_shard = make_train_step(
                api, mesh, tc, batch_shape=batch_shape, donate=True)
            state_shape = jax.eval_shape(
                lambda k: init_state(api, k), jax.random.PRNGKey(0))
            lowered = step.lower(state_shape, batch_shape)
        elif shape.kind == "prefill":
            param_shape = jax.eval_shape(api.init, jax.random.PRNGKey(0))
            p_shard = shd.param_shardings(param_shape, mesh)
            cache_shape = jax.eval_shape(
                lambda: api.init_cache(shape.global_batch, shape.seq_len))
            c_shard = shd.cache_shardings(cache_shape, mesh)
            b_shard = shd.batch_shardings(batch_shape, mesh)
            # encdec prefill returns (logits, (caches, enc_out)): pin
            # only the cache part of the state for that family.
            out_state = c_shard if cfg.family != "encdec" \
                else (c_shard, None)
            fn = jax.jit(lambda p, b, c: api.prefill(p, b, c),
                         in_shardings=(p_shard, b_shard, c_shard),
                         out_shardings=(None, out_state),
                         donate_argnums=(2,))
            lowered = fn.lower(param_shape, batch_shape, cache_shape)
        else:  # decode
            param_shape = jax.eval_shape(api.init, jax.random.PRNGKey(0))
            p_shard = shd.param_shardings(param_shape, mesh)
            cache_shape = jax.eval_shape(
                lambda: api.init_cache(shape.global_batch, shape.seq_len))
            if cfg.family == "encdec":
                enc_shape = jax.ShapeDtypeStruct(
                    (shape.global_batch, shape.seq_len // 4, cfg.d_model),
                    jnp.bfloat16)
                cache_shape = (cache_shape, enc_shape)
            c_shard = shd.cache_shardings(cache_shape, mesh)
            b_shard = shd.batch_shardings(batch_shape, mesh)
            fn = jax.jit(lambda p, b, c: api.decode_step(p, b, c),
                         in_shardings=(p_shard, b_shard, c_shard),
                         out_shardings=(None, c_shard),
                         donate_argnums=(2,))
            lowered = fn.lower(param_shape, batch_shape, cache_shape)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = _cost_analysis_dict(compiled)
    mem = _mem_analysis_dict(compiled)
    hlo = compiled.as_text()
    terms = rl.terms_from_compiled(arch, shape, mesh_name, chips, cost,
                                   hlo, cfg)
    coll = rl.parse_collectives(hlo, default_group=chips)
    rec.update({
        "status": "ok",
        "chips": chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": mem,
        "cost": {k: cost[k] for k in sorted(cost) if k in
                 ("flops", "bytes accessed", "transcendentals",
                  "utilization")},
        "collectives": {"per_chip_link_bytes": coll.total_bytes,
                        "count": coll.count, "by_op": coll.by_op},
        "roofline": terms.to_dict(),
        "params": cfg.params_count(),
        "active_params": cfg.active_params_count(),
    })
    # fits-per-device proof: argument+temp bytes under 16 GB HBM
    if mem.get("temp_size_in_bytes") is not None:
        per_dev = (mem.get("argument_size_in_bytes", 0)
                   + mem.get("temp_size_in_bytes", 0)
                   + mem.get("output_size_in_bytes", 0)
                   - mem.get("alias_size_in_bytes", 0))
        rec["per_device_bytes"] = int(per_dev)
        rec["fits_16gb"] = bool(per_dev < 16e9)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--compression", default="none")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--moe-dispatch", default=None)
    ap.add_argument("--recommended", action="store_true",
                    help="apply the per-cell production config "
                         "(launch/cell_configs.py) instead of the "
                         "paper-faithful baseline settings")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if (args.all or args.arch is None) \
        else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                mb, md = args.microbatches, args.moe_dispatch
                if args.recommended:
                    from repro.launch.cell_configs import recommended
                    cc = recommended(arch, shape)
                    mb = max(mb, cc.microbatches)
                    md = md or cc.moe_dispatch
                tag = f"{arch}__{shape}__{'multi' if multi else 'single'}"
                if args.compression != "none":
                    tag += f"__{args.compression}"
                if args.remat != "full":
                    tag += f"__remat-{args.remat}"
                if mb > 1:
                    tag += f"__mb{mb}"
                if md:
                    tag += f"__{md}"
                path = os.path.join(args.out, tag + ".json")
                try:
                    rec = run_cell(arch, shape, multi,
                                   compression=args.compression,
                                   remat=args.remat,
                                   microbatches=mb,
                                   moe_dispatch=md)
                except Exception as e:  # noqa: BLE001
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "multi" if multi else "single",
                           "status": "error", "error": repr(e),
                           "traceback": traceback.format_exc()[-4000:]}
                    failures += 1
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f" bottleneck={r['bottleneck']}"
                             f" frac={r['roofline_fraction']:.3f}"
                             f" compile={rec['compile_s']:.0f}s")
                elif status == "error":
                    extra = " " + rec["error"][:120]
                print(f"[dryrun] {tag}: {status}{extra}", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
