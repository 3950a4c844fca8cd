"""Measure the cross-pod gradient-exchange program at production scale.

Lowers parallel.blockfp.make_pod_exchange for a real architecture's full
gradient pytree on the 2x16x16 mesh and compares DCI wire bytes + derived
exchange time for f32 / int8 / blockfp8 — the §Perf collective-term
iteration (the paper's bounded-alignment insight applied to gradient
sync).

    PYTHONPATH=src python tools/exchange_bench.py --arch gemma2-9b
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse  # noqa: E402
import json      # noqa: E402
import sys       # noqa: E402

sys.path.insert(0, "src")

import jax                                   # noqa: E402
import jax.numpy as jnp                      # noqa: E402

from repro.configs import get_config         # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.roofline import LINK_BW, parse_collectives  # noqa: E402
from repro.models import registry            # noqa: E402
from repro.parallel.blockfp import make_pod_exchange  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-9b")
    ap.add_argument("--out", default="results/perf/exchange.json")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    api = registry.build(cfg)
    mesh = make_production_mesh(multi_pod=True)
    n_pods = mesh.shape["pod"]

    param_shape = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    grad_shapes = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((n_pods,) + l.shape, jnp.float32),
        param_shape)
    n_params = sum(int(jnp.prod(jnp.asarray(l.shape[1:])))
                   for l in jax.tree_util.tree_leaves(grad_shapes))

    results = {"arch": args.arch, "n_params": n_params}
    for method in ("f32", "int8", "blockfp8"):
        fn, in_sh, _ = make_pod_exchange(mesh, grad_shapes, method)
        with jax.set_mesh(mesh):
            compiled = fn.lower(grad_shapes).compile()
        coll = parse_collectives(compiled.as_text(),
                                 default_group=n_pods)
        t = coll.total_bytes / LINK_BW
        results[method] = {
            "per_chip_wire_bytes": coll.total_bytes,
            "exchange_s_at_link_bw": t,
            "by_op": coll.by_op,
        }
        print(f"{args.arch} exchange[{method}]: "
              f"{coll.total_bytes/1e6:.1f} MB/chip wire, "
              f"{t*1e3:.2f} ms at {LINK_BW/1e9:.0f} GB/s")
    base = results["f32"]["per_chip_wire_bytes"]
    for m in ("int8", "blockfp8"):
        results[f"{m}_reduction"] = base / results[m]["per_chip_wire_bytes"]
        print(f"{m}: {results[f'{m}_reduction']:.2f}x less DCI traffic")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1, default=float)


if __name__ == "__main__":
    main()
