"""Smoke test of the system on a TPU, at the full width of qwen2-0.5b.

    python chip_smoke.py             # one chip: serve under five engines
    python chip_smoke.py --chips 4   # four chips: sharded trainer steps

One chip: one seeded full-width parameter set (24 layers, d_model 896,
GQA 14/2, d_ff 4864, vocab 151936) is served through
``registry.build`` -> ``ServingEngine`` under ``bf16``, ``int8_serving``
and ``int4_serving`` (calibrated, fused Pallas executors), and
``fidelity_int8`` with the fused executors on and off. Every engine
gets the same seeded traffic twice: the first pass compiles, the second
is timed. Checks: every request finishes with in-vocabulary tokens; the
two ``fidelity_int8`` engines give identical greedy streams; the
``bf16`` prefill logits stay within ``LOGIT_TOL`` of a float32 forward
on the host CPU; the fused ``int4_serving`` decode program holds a
Pallas kernel (``tpu_custom_call``).

Four chips: a few ``launch.train.make_train_step`` steps of the same
model on the 4-device ``(data, model)`` mesh and on a one-device mesh,
in one process. The losses must agree within ``LOSS_TOL`` and the
parameters must be spread over the four devices.

Any failed check exits non-zero. The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.metadata
import json
import os
import sys
import time

ARCH = "qwen2-0.5b"
SEED = 0
# traffic, the same for every engine
N_REQUESTS = 8
PROMPT_LENS = (32, 256)          # seeded, inclusive
MAX_NEW = 64
SLOTS = 4
CACHE_LEN = 512
DECODE_BLOCK = 8
# bf16 on the chip vs float32 on the host CPU: max |logit difference|
# over the vocabulary of the checked prompts. The same bf16 forward on
# the host CPU differs from float32 by up to 0.104 on these prompts
# (logits up to 4.7 in magnitude); the tolerance is about twice that.
LOGIT_TOL = 0.2
# four chips: trainer steps and their loss agreement (1 vs 4 devices).
# The split changes the order of the bf16 reductions only; a lost or
# doubled all-reduce moves a loss near log(vocab) ~ 12 by far more.
TRAIN_STEPS = 4
TRAIN_BATCH = 8
TRAIN_SEQ = 128
LOSS_TOL = 2e-2


# XLA compile seconds (a compile loaded from the persistent cache counts
# its load time) and persistent-cache hits, summed from JAX's monitoring
# events
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_compiles = {"seconds": 0.0, "cache_hits": 0}


def _watch_compiles():
    import jax

    def on_duration(event, secs, **_):
        if event == _COMPILE_EVENT:
            _compiles["seconds"] += secs

    def on_event(event, **_):
        if event == _CACHE_HIT_EVENT:
            _compiles["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


class SmokeFailure(Exception):
    pass


def check(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str):
    print(msg, flush=True)


def _peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def _traffic(vocab: int):
    import numpy as np
    rng = np.random.default_rng(SEED)
    lo, hi = PROMPT_LENS
    return [rng.integers(0, vocab, int(rng.integers(lo, hi + 1)),
                         dtype=np.int32)
            for _ in range(N_REQUESTS)]


def _serve_once(eng, prompts, vocab: int):
    """Submit the traffic, drain, check every request; returns
    ({rid: new tokens}, wall seconds)."""
    from repro.serving import Request
    reqs = [Request(rid=rid, prompt=p, max_new_tokens=MAX_NEW)
            for rid, p in enumerate(prompts)]
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    dt = time.perf_counter() - t0
    streams = {}
    for r in reqs:
        check(r.done and r.error is None,
              f"request {r.rid}: done={r.done} error={r.error!r}")
        new = r.tokens[len(r.prompt):]
        check(len(new) == MAX_NEW,
              f"request {r.rid}: {len(new)} new tokens, want {MAX_NEW}")
        check(all(0 <= t < vocab for t in new),
              f"request {r.rid}: token outside [0, {vocab})")
        streams[r.rid] = new
    return streams, dt


def _decode_program_text(eng) -> str:
    """HLO of the engine's compiled blocked-decode program."""
    import jax
    import jax.numpy as jnp
    from repro.models import registry
    from repro.serving.config import MAX_STOP_IDS
    b = eng.b
    zeros = jnp.zeros((b,), jnp.int32)
    carry = registry.DecodeCarry(
        tok=zeros, pos=zeros, rem=jnp.ones((b,), jnp.int32), taken=zeros,
        stops=jnp.full((b, MAX_STOP_IDS), -1, jnp.int32),
        temp=jnp.zeros((b,), jnp.float32), top_k=zeros,
        top_p=jnp.ones((b,), jnp.float32),
        keys=jnp.zeros((b, 2), jnp.uint32))
    fn = registry.make_block_decode(eng.api, eng.decode_block,
                                    policy=eng.policy, fused=eng.fused)
    return jax.jit(fn).lower(eng.params, carry, eng.caches) \
        .compile().as_text()


def _check_logits(cfg, api, params, prompts, platform: str):
    """bf16 prefill logits on the chip vs the float32 forward on the
    host CPU, for two prompts."""
    import jax
    import numpy as np
    from repro.models import registry
    cpu = jax.devices("cpu")[0]
    cfg32 = dataclasses.replace(cfg, precision_policy="fp32",
                                compute_dtype="float32")
    api32 = registry.build(cfg32)
    params_cpu = jax.device_put(params, cpu)
    worst, agree = 0.0, 0
    for p in prompts[:2]:
        tokens = np.asarray(p, np.int32)[None, :]
        got, _ = jax.jit(api.prefill)(
            params, {"tokens": tokens}, api.init_cache(1, len(p)))
        got = np.asarray(got, np.float32)[0]
        with jax.default_device(cpu), \
                jax.default_matmul_precision("highest"):
            ref, _ = jax.jit(api32.prefill)(
                params_cpu, {"tokens": jax.device_put(tokens, cpu)},
                api32.init_cache(1, len(p)))
        ref = np.asarray(ref, np.float32)[0][:cfg.vocab]
        got = got[:cfg.vocab]
        check(np.isfinite(got).all(), "non-finite bf16 logits")
        diff = float(np.max(np.abs(got - ref)))
        worst = max(worst, diff)
        agree += int(np.argmax(got) == np.argmax(ref))
        log(f"  logits len={len(p)}: max|bf16[{platform}] - f32[cpu]|={diff!r} "
            f"max|f32 logit|={float(np.max(np.abs(ref)))!r} "
            f"top1 {'agrees' if np.argmax(got) == np.argmax(ref) else 'differs'}")
    log(f"  logits: worst max abs diff {worst!r} (tolerance {LOGIT_TOL}), "
        f"top-1 agreement {agree}/2")
    check(worst <= LOGIT_TOL,
          f"bf16 logits differ from the f32 CPU forward by {worst} "
          f"> {LOGIT_TOL}")


def serve_phases(dev) -> None:
    import jax
    from repro.configs import get_config
    from repro.models import registry
    from repro.serving import EngineConfig, ServingEngine

    cfg0 = get_config(ARCH)
    api0 = registry.build(cfg0)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(api0.init)(jax.random.PRNGKey(SEED)))
    log(f"phase init: {ARCH} L={cfg0.n_layers} d={cfg0.d_model} "
        f"heads={cfg0.n_heads}/{cfg0.n_kv_heads} d_ff={cfg0.d_ff} "
        f"vocab={cfg0.vocab} params={cfg0.params_count()} "
        f"seconds={time.perf_counter() - t0:.3f} "
        f"xla_compile_s={_compiles['seconds']:.3f} "
        f"peak_bytes_in_use={_peak_bytes(dev)}")
    prompts = _traffic(cfg0.vocab)
    log(f"traffic: {N_REQUESTS} requests, prompt lengths "
        f"{[len(p) for p in prompts]}, {MAX_NEW} new tokens each, greedy, "
        f"{SLOTS} slots, cache_len {CACHE_LEN}")

    base = dict(batch_slots=SLOTS, cache_len=CACHE_LEN,
                decode_block=DECODE_BLOCK)
    phases = [
        ("bf16", "bf16", {}),
        ("int8_serving", "int8_serving", {"act_calibration": "auto"}),
        ("int4_serving", "int4_serving", {"act_calibration": "auto"}),
        ("fidelity_int8/fused", "fidelity_int8",
         {"act_calibration": "auto", "fused_executors": "on"}),
        ("fidelity_int8/staged", "fidelity_int8",
         {"act_calibration": None, "fused_executors": "off"}),
    ]
    fidelity_scales, fidelity_streams = None, {}
    platform = dev.platform
    for name, policy, extra in phases:
        cfg = dataclasses.replace(cfg0, precision_policy=policy)
        api = registry.build(cfg)
        if name == "fidelity_int8/staged":
            extra = dict(extra, act_calibration=fidelity_scales)
        t0, compiled0 = time.perf_counter(), _compiles["seconds"]
        eng = ServingEngine(cfg, api, params,
                            config=EngineConfig(**base, **extra))
        build_s = time.perf_counter() - t0
        if policy in ("int8_serving", "int4_serving"):
            check(eng.fused, f"{name}: engine did not resolve the fused "
                             f"executors")
        first, first_s = _serve_once(eng, prompts, cfg.vocab)
        steady, steady_s = _serve_once(eng, prompts, cfg.vocab)
        check(first == steady, f"{name}: the second pass changed the "
                               f"greedy streams")
        new_tokens = sum(len(t) for t in steady.values())
        log(f"phase {name}: fused={eng.fused} build_s={build_s:.3f} "
            f"xla_compile_s={_compiles['seconds'] - compiled0:.3f} "
            f"first_minus_steady_s={first_s - steady_s:.3f} "
            f"first_pass_s={first_s:.3f} steady_s={steady_s:.3f} "
            f"tok_per_s[{platform}]={new_tokens / steady_s:.1f} "
            f"peak_bytes_in_use={_peak_bytes(dev)}")
        if name == "int4_serving":
            t0 = time.perf_counter()
            text = _decode_program_text(eng)
            kernels = text.count("tpu_custom_call")
            log(f"  int4_serving decode program: {kernels} "
                f"tpu_custom_call sites (compiled in "
                f"{time.perf_counter() - t0:.3f}s)")
            check(kernels > 0, "the fused int4_serving decode program "
                               "holds no Pallas kernel")
        if policy == "fidelity_int8":
            fidelity_scales = eng.act_scales
            fidelity_streams[name] = steady
        del eng
        gc.collect()
    same = fidelity_streams["fidelity_int8/fused"] \
        == fidelity_streams["fidelity_int8/staged"]
    log(f"fidelity_int8: fused and staged greedy streams "
        f"{'identical' if same else 'DIFFER'}")
    check(same, "fidelity_int8 fused and staged greedy streams differ")
    cfg = dataclasses.replace(cfg0, precision_policy="bf16")
    _check_logits(cfg, registry.build(cfg), params, prompts, platform)


def train_phase(devices) -> None:
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.data.pipeline import DataConfig, SyntheticLMDataset
    from repro.launch.mesh import make_mesh
    from repro.launch.train import (TrainConfig, init_sharded_state,
                                    make_train_step)
    from repro.models import registry
    from repro.optim import AdamWConfig

    cfg = get_config(ARCH)
    api = registry.build(cfg)
    batch_shape = {"tokens": jax.ShapeDtypeStruct(
        (TRAIN_BATCH, TRAIN_SEQ + 1), np.int32)}
    ds = SyntheticLMDataset(DataConfig(
        vocab=cfg.vocab, seq_len=TRAIN_SEQ, global_batch=TRAIN_BATCH))
    batches = [jax.device_get(ds.batch(i)) for i in range(TRAIN_STEPS)]
    tc = TrainConfig(adamw=AdamWConfig(lr=3e-4), total_steps=TRAIN_STEPS)
    losses = {}
    for n in (len(devices), 1):
        mesh = make_mesh((1, n), ("data", "model"), devices=devices[:n])
        with jax.set_mesh(mesh):
            step, st_sh, b_sh = make_train_step(api, mesh, tc, batch_shape)
            t0 = time.perf_counter()
            state = init_sharded_state(api, jax.random.PRNGKey(SEED), st_sh)
            leaves = jax.tree.leaves(state.params)
            spread = {len(leaf.sharding.device_set) for leaf in leaves}
            split = sum(not leaf.sharding.is_fully_replicated
                        for leaf in leaves)
            check(spread == {n}, f"mesh of {n}: parameter device sets "
                                 f"{spread}")
            if n > 1:
                check(split > 0, "no parameter is split over the mesh")
            out = []
            times = []
            for b in batches:
                ts = time.perf_counter()
                state, m = step(state, jax.device_put(b, b_sh))
                out.append(float(m["loss"]))
                times.append(time.perf_counter() - ts)
            check(all(np.isfinite(out)), f"mesh of {n}: losses {out}")
            losses[n] = out
            log(f"phase train mesh=(1,{n}): params on {n} device(s), "
                f"{split}/{len(leaves)} leaves split; losses {out}; "
                f"first_step_s={times[0]:.3f} "
                f"later_step_s={[round(t, 4) for t in times[1:]]} "
                f"peak_bytes_in_use={_peak_bytes(devices[0])}")
            del state
            gc.collect()
    diff = max(abs(a - b) for a, b in zip(losses[len(devices)], losses[1]))
    log(f"train: max |loss[{len(devices)} devices] - loss[1 device]| = "
        f"{diff!r} (tolerance {LOSS_TOL})")
    check(diff <= LOSS_TOL, f"losses differ by {diff} > {LOSS_TOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serving phases on one chip; 4: sharded "
                         "trainer steps on four chips vs one")
    args = ap.parse_args(argv)
    for var in ("REPRO_KERNEL_INTERPRET", "REPRO_FUSED_BACKEND"):
        if os.environ.get(var):
            print(f"chip_smoke: refusing to start with {var} set: it "
                  f"would take the kernels off the chip", file=sys.stderr)
            return 2
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no package at {src}/repro: run the script "
              f"from a checkout of the repo", file=sys.stderr)
        return 1
    sys.path.insert(0, src)
    import jax
    import jaxlib

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is on platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    from repro.runtime.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    _watch_compiles()
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(f"jax {jax.__version__} jaxlib {jaxlib.__version__} libtpu {libtpu}")
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}; compile cache {cache_dir}")
    try:
        if args.chips == 4:
            train_phase(devices[:4])
        else:
            serve_phases(dev)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"compile: xla_compile_s={_compiles['seconds']:.3f} over the run, "
        f"{_compiles['cache_hits']} programs loaded from the persistent "
        f"cache")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
