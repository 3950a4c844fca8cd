"""Uniform model API across families + dry-run input specs.

build(cfg) -> ModelAPI with:
  init(key) -> params
  loss_fn(params, batch) -> (loss, metrics)      [train]
  prefill(params, batch, caches) -> (logits, caches)
  decode_step(params, batch, caches) -> (logits, caches)
  init_cache(batch_size, max_len) -> caches

input_specs(cfg, shape) -> batch of jax.ShapeDtypeStruct — the dry-run
stand-ins (weak-type-correct, shardable, no allocation).

Frontend stubs (assignment): seamless frames_len = seq_len // 4 at
frontend_dim; internvl2 patch embeddings (n_patches, vit_dim) prepended
to the token sequence.
"""
from __future__ import annotations

import contextlib
import dataclasses
import re
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig
from repro.models import encdec, griffin, lm, rwkv, vlm


@dataclasses.dataclass(frozen=True)
class ProjGroup:
    """One tunable projection group of an architecture.

    ``pattern`` is the policy-rule regex matching every parameter path the
    group's matmuls route through (the same paths the layers pass to
    ``PrecisionPolicy.spec_for``); (d_in, d_out, count) give the matmul
    shape the accelerator models score (count = matmuls of that shape per
    forward pass).
    """

    name: str
    pattern: str
    d_in: int
    d_out: int
    count: int

    @property
    def macs_per_token(self) -> int:
        return self.d_in * self.d_out * self.count


def projection_groups(cfg: ModelConfig) -> Tuple["ProjGroup", ...]:
    """The per-layer precision-tuning units of an architecture — what
    ``repro.autotune`` enumerates candidates over. Grouping is by role
    (qkv / attn-out / ffn-in / ffn-out / head), the granularity at which
    mixed-precision schemes are actually deployed (paper Appendix B).

    Patterns must match the literal paths the layers pass to
    ``PrecisionPolicy.spec_for`` ('block/full/attn/wq', 'block/mix/w_r',
    'block/rec/w_in_rnn', 'dec/xattn/wo', ...): a pattern that matches
    nothing makes the rule dead at serve time and the divergence probe
    silently measure zero.
    """
    hd = cfg.head_dim_
    groups = []
    # layers that carry attention / per-family projection counts
    n_attn = cfg.n_layers
    n_ffn = cfg.n_layers
    if cfg.family == "griffin":
        # (rec, rec, attn) repeating pattern + trailing blocks: only the
        # 'attn' slots have attention, every block has an MLP
        pat = cfg.rec_pattern or ("rec", "rec", "attn")
        n_triples = cfg.n_layers // len(pat)
        tail = pat[:cfg.n_layers - n_triples * len(pat)]
        n_attn = n_triples * pat.count("attn") + tail.count("attn")
    elif cfg.family == "encdec":
        # encoder self + decoder self + decoder cross-attention (the
        # xattn paths match the same attn/w* patterns)
        n_enc = cfg.n_enc_layers or cfg.n_layers
        n_attn = n_enc + 2 * cfg.n_layers
        n_ffn = n_enc + cfg.n_layers
    if cfg.family in ("lm", "vlm", "griffin", "encdec"):
        groups += [
            ProjGroup("attn_qkv", r"attn/w[qkv]$", cfg.d_model,
                      (cfg.n_heads + 2 * cfg.n_kv_heads) * hd, n_attn),
            ProjGroup("attn_wo", r"attn/wo$", cfg.n_heads * hd,
                      cfg.d_model, n_attn),
        ]
    if cfg.family == "rwkv":
        groups += [
            ProjGroup("tmix_rkvg", r"mix/w_[rkvg]$", cfg.d_model,
                      cfg.d_model, 4 * cfg.n_layers),
            ProjGroup("tmix_out", r"mix/w_o$", cfg.d_model, cfg.d_model,
                      cfg.n_layers),
            ProjGroup("cmix", r"mix/c_(key|val|rec)$", cfg.d_model,
                      cfg.d_ff, 2 * cfg.n_layers),
        ]
    if cfg.family == "griffin" and cfg.d_rnn:
        n_rec = cfg.n_layers - n_attn
        groups += [
            ProjGroup("rglru_in", r"rec/w_in_(rnn|gate)$", cfg.d_model,
                      cfg.d_rnn, 2 * n_rec),
            ProjGroup("rglru_out", r"rec/w_out$", cfg.d_rnn, cfg.d_model,
                      n_rec),
        ]
    if cfg.moe:
        groups.append(ProjGroup(
            "moe_experts", r"moe/experts$", cfg.d_model, cfg.moe.d_expert,
            3 * cfg.moe.top_k * cfg.n_layers))
    elif cfg.family != "rwkv":
        groups += [
            ProjGroup("ffn_in", r"mlp/w_(gate|up)$", cfg.d_model,
                      cfg.d_ff, 2 * n_ffn),
            ProjGroup("ffn_out", r"mlp/w_down$", cfg.d_ff, cfg.d_model,
                      n_ffn),
        ]
    if cfg.family == "vlm":
        groups.append(ProjGroup(
            "projector", r"projector/fc[12]$", cfg.vit_dim or cfg.d_model,
            cfg.d_model, 2))
    groups.append(ProjGroup(
        "head", r"lm_head|embed|frontend_proj", cfg.d_model,
        cfg.padded_vocab, 1))
    return tuple(groups)


def _lm_projection_paths(cfg: ModelConfig
                         ) -> Callable[[str], Optional[str]]:
    kinds = lm.group_kinds(cfg)

    def path_for(p: str) -> Optional[str]:
        m = re.fullmatch(r"blocks/b(\d+)/attn/(w[qkvo])", p)
        if m:
            return f"block/{kinds[int(m.group(1))]}/attn/{m.group(2)}"
        m = re.fullmatch(r"blocks/b\d+/mlp/(w_(?:gate|up|down))", p)
        if m:
            return f"block/mlp/{m.group(1)}"
        if re.fullmatch(r"blocks/b\d+/moe/(?:w_gate|w_up|w_down)", p):
            return "block/moe/experts"
        return None

    return path_for


def _vlm_projection_paths(cfg: ModelConfig
                          ) -> Callable[[str], Optional[str]]:
    base = _lm_projection_paths(cfg)

    def path_for(p: str) -> Optional[str]:
        m = re.fullmatch(r"projector/(fc[12])", p)
        if m:
            return f"projector/{m.group(1)}"
        return base(p)

    return path_for


def _rwkv_projection_paths(cfg: ModelConfig
                           ) -> Callable[[str], Optional[str]]:
    def path_for(p: str) -> Optional[str]:
        m = re.fullmatch(r"blocks/mix/(w_[rkvgo]|c_(?:key|val|rec))", p)
        if m:
            return f"block/mix/{m.group(1)}"
        return None

    return path_for


def _griffin_projection_paths(cfg: ModelConfig
                              ) -> Callable[[str], Optional[str]]:
    def path_for(p: str) -> Optional[str]:
        m = re.fullmatch(
            r"(?:blocks/b\d+|tail/\d+)/rec/(w_in_rnn|w_in_gate|w_out)", p)
        if m:
            return f"block/rec/{m.group(1)}"
        m = re.fullmatch(r"(?:blocks/b\d+|tail/\d+)/attn/(w[qkvo])", p)
        if m:
            return f"block/attn/{m.group(1)}"
        m = re.fullmatch(
            r"(?:blocks/b\d+|tail/\d+)/mlp/(w_(?:gate|up|down))", p)
        if m:
            return f"block/mlp/{m.group(1)}"
        return None

    return path_for


def _encdec_projection_paths(cfg: ModelConfig
                             ) -> Callable[[str], Optional[str]]:
    def path_for(p: str) -> Optional[str]:
        if p == "frontend_proj":
            return "frontend_proj"
        m = re.fullmatch(r"enc_blocks/attn/(w[qkvo])", p)
        if m:
            return f"enc/attn/{m.group(1)}"
        m = re.fullmatch(r"enc_blocks/mlp/(w_(?:gate|up|down))", p)
        if m:
            return f"enc/mlp/{m.group(1)}"
        m = re.fullmatch(r"dec_blocks/(attn|xattn)/(w[qkvo])", p)
        if m:
            return f"dec/{m.group(1)}/{m.group(2)}"
        m = re.fullmatch(r"dec_blocks/mlp/(w_(?:gate|up|down))", p)
        if m:
            return f"dec/mlp/{m.group(1)}"
        return None

    return path_for


_PROJECTION_PATHS = {
    "lm": _lm_projection_paths,
    "vlm": _vlm_projection_paths,
    "rwkv": _rwkv_projection_paths,
    "griffin": _griffin_projection_paths,
    "encdec": _encdec_projection_paths,
}


def projection_paths(cfg: ModelConfig) -> Callable[[str], Optional[str]]:
    """Param-tree container path -> runtime policy path for every
    projection that routes through the precision policy (the map
    ``quant.prepare.prepare_params`` consumes). Paths the family never
    routes (embeddings, norms, MoE router, recurrence gates) resolve to
    None and stay untouched by preparation."""
    return _PROJECTION_PATHS[cfg.family](cfg)


def _prepare_fn(cfg: ModelConfig) -> Callable:
    def prepare(params, policy, act_scales=None):
        from repro.quant.prepare import prepare_params
        return prepare_params(params, policy, projection_paths(cfg),
                              act_scales=act_scales)

    return prepare


# families eligible for the blocked decode fast path: decode_step must
# consume a {'token', 'pos'} batch, emit last-position logits, and keep
# batch rows independent — AND the masked pad steps a budget-exhausted
# slot keeps receiving inside a block must be causally invisible. That
# holds for position-tagged KV caches (the pad write at position 0 is
# overwritten/masked exactly as under per-token dispatch) but NOT for
# recurrent state (rwkv/griffin fold every consumed token into O(1)
# state, so the block-vs-tick pad cadence difference diverges the
# token streams — measured, not hypothetical); encdec's decode state
# only exists after prefill, so it cannot serve through the engine's
# decode program at all. Mirror of
# ``repro.serving.engine._FAST_PREFILL_FAMILIES`` for new families.
_BLOCK_DECODE_FAMILIES = ("lm", "vlm")


def block_decode_eligible(cfg: ModelConfig) -> bool:
    return cfg.family in _BLOCK_DECODE_FAMILIES


class DecodeCarry(NamedTuple):
    """Per-slot scan state of the blocked decode program.

    All arrays are batch-leading (B = engine slots). ``rem`` is the
    remaining token budget (0 = inactive/freed slot); ``taken`` counts
    the steps a slot actually took inside the current block (the host
    resets it to 0 per dispatch and replays ``tokens[:taken]`` — with
    EOS stopping, ``rem`` alone no longer determines the active
    prefix). ``stops`` holds each slot's stop ids (-1 = unused slot,
    never matches a real token); ``temp``/``top_k``/``top_p`` are the
    per-slot sampling parameters and ``keys`` the (B, 2) uint32 PRNG
    keys the sampler threads through the scan."""

    tok: Any     # (B,)  int32 current input token
    pos: Any     # (B,)  int32 absolute position
    rem: Any     # (B,)  int32 remaining budget, 0 = inactive
    taken: Any   # (B,)  int32 steps taken this block
    stops: Any   # (B, K) int32 stop ids, -1 = unused
    temp: Any    # (B,)  f32 temperature, <= 0 = greedy
    top_k: Any   # (B,)  int32, 0 = unrestricted
    top_p: Any   # (B,)  f32
    keys: Any    # (B, 2) uint32 PRNG keys


def make_block_decode(api: "ModelAPI", n: int, policy=None,
                      sample: bool = False, tracer=None,
                      fused: bool = False) -> Callable:
    """Generic multi-token decode block: a ``lax.scan`` of ``n``
    ``api.decode_step`` calls with on-device token selection.

    Returns ``fn(params, carry, state) -> (tokens, carry, state)`` with
    ``carry`` a :class:`DecodeCarry` and ``tokens`` the (n, B) int32
    trajectory (rows past a slot's ``taken`` are garbage the host
    ignores). Slots with an exhausted budget are masked: they feed the
    pad token at their current position — exactly what the per-token
    engine feeds idle slots — and stop advancing, so a host driving
    blocks of n is
    token-for-token identical to one dispatching single steps, while
    syncing once per block instead of once per token. A selected token
    matching one of the slot's ``stops`` zeroes ``rem`` on device (EOS
    stopping): the slot keeps its stop token, goes inactive for the
    rest of the block, and the host frees it at the next sync. Callers
    jit the result (one compile per distinct ``(n, sample)``).

    ``sample=False`` selects greedy argmax for every slot;
    ``sample=True`` compiles ``models.sampling.sample_tokens`` into the
    scan — greedy rows (``temp <= 0``) still take the bit-identical
    argmax, so one program serves mixed batches, and every active row
    consumes exactly one key split per step (sampled streams are
    invariant to ``decode_block``).

    Weight operands are STAGED once per block
    (``quant.prepare.stage_params``): fake-quant int projections
    materialize their compute-dtype dequantized form — the identical
    array the executors rebuild from packed storage every call — before
    the scan, so the n steps reuse it instead of re-deriving it n
    times. Bit-exact, and engine storage stays packed.

    ``policy`` is the already-resolved PrecisionPolicy the staging walk
    routes specs from; engines pass their eagerly-resolved policy so a
    ``plan:`` file that disappears after construction (or a transient
    registered policy) cannot fail the first blocked dispatch. Resolved
    here — never at trace time — when omitted.

    ``fused=True`` routes the block through the fused Pallas executors
    instead of per-block staging: the staging walk is skipped entirely
    (prepared storage — packed nibbles, fp codes, int8 rows — enters
    the kernels as operands and dequantizes in-register), and the whole
    scan is traced under ``layers.mplinear.executor_variant('fused')``
    so every eligible projection takes the fused datapath. No staged
    compute-dtype operand is ever materialized
    (``quant.prepare.count_staged`` observes zero).

    ``tracer`` (an :class:`repro.obs.Tracer`) marks each jax trace of
    the program with an instant event: the body below runs exactly once
    per compile (jit caches the traced program afterwards), so the
    marker pairs with the wall-clock ``compile:*`` span the engine's
    ``traced_jit`` wrapper records around the same dispatch."""
    if not block_decode_eligible(api.cfg):
        raise ValueError(
            f"family {api.cfg.family!r} is not eligible for blocked "
            f"decode (want one of {_BLOCK_DECODE_FAMILIES})")
    if policy is None:
        from repro.core.policy import get_policy
        policy = get_policy(api.cfg.precision_policy)

    def run(params, carry, state):
        from repro.layers.mplinear import executor_variant
        from repro.models.sampling import sample_tokens
        from repro.quant.prepare import stage_params
        if tracer is not None:
            # this function body executes only while jax traces the
            # program (once per compile): an instant here timestamps
            # the trace phase of each block-decode compilation
            tracer.instant(f"jax_trace:block_decode[n={n}]",
                           cat="compile")
        variant = contextlib.nullcontext()
        if fused:
            variant = executor_variant("fused")
        else:
            params = stage_params(params, policy,
                                  projection_paths(api.cfg))
        c = carry

        def body(inner, _):
            tok, pos, rem, taken, keys, st = inner
            active = rem > 0
            # inactive rows keep their REAL position: the pad write must
            # land on the slot's current frontier (where the next real
            # write — decode or prefill chunk — overwrites it before any
            # query attends), never on position 0, which may hold live
            # prompt context for a slot still mid-prefill
            batch = {"token": jnp.where(active, tok, 0)[:, None],
                     "pos": pos}
            logits, st = api.decode_step(params, batch, st)
            if sample:
                keys2, nxt = sample_tokens(keys, logits, c.temp,
                                           c.top_k, c.top_p)
                keys = jnp.where(active[:, None], keys2, keys)
            else:
                with jax.named_scope("sample"):
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            hit = (nxt[:, None] == c.stops).any(axis=-1) & active
            tok = jnp.where(active, nxt, tok)
            pos = jnp.where(active, pos + 1, pos)
            rem = jnp.where(active, jnp.where(hit, 0, rem - 1), rem)
            taken = taken + active.astype(jnp.int32)
            return (tok, pos, rem, taken, keys, st), nxt

        with variant:
            (tok, pos, rem, taken, keys, state), tokens = jax.lax.scan(
                body, (c.tok, c.pos, c.rem, c.taken, c.keys, state),
                None, length=n)
        out = c._replace(tok=tok, pos=pos, rem=rem, taken=taken,
                         keys=keys)
        return tokens, out, state

    return run


class ModelAPI(NamedTuple):
    cfg: ModelConfig
    init: Callable
    loss_fn: Callable
    prefill: Callable
    decode_step: Callable
    init_cache: Callable
    # prepare(params, policy) -> params with each projection weight in
    # its deployment storage format (see quant/prepare.py)
    prepare: Callable = None
    # prefill_chunk(params, batch, caches) -> caches: position-offset
    # prefill continuation for the continuous engine (batch carries
    # 'tokens' (B, S), 'offsets' (B,), 'lengths' (B,)); None for
    # families whose prefill is not a pure token-cache fill
    prefill_chunk: Callable = None


def build(cfg: ModelConfig) -> ModelAPI:
    if cfg.family == "lm":
        return ModelAPI(
            cfg,
            lambda key: lm.init(key, cfg),
            lambda p, batch: lm.loss_fn(p, cfg, batch),
            lambda p, batch, caches: lm.prefill(p, cfg, batch["tokens"],
                                                caches),
            lambda p, batch, caches: lm.decode_step(
                p, cfg, batch["token"], batch["pos"], caches),
            lambda bsz, max_len: lm.init_cache(cfg, bsz, max_len),
            _prepare_fn(cfg),
            lambda p, batch, caches: lm.prefill_chunk(
                p, cfg, batch["tokens"], batch["offsets"],
                batch["lengths"], caches),
        )
    if cfg.family == "rwkv":
        return ModelAPI(
            cfg,
            lambda key: rwkv.init(key, cfg),
            lambda p, batch: rwkv.loss_fn(p, cfg, batch),
            lambda p, batch, caches: rwkv.prefill(p, cfg, batch["tokens"],
                                                  caches),
            lambda p, batch, caches: rwkv.decode_step(
                p, cfg, batch["token"], batch["pos"], caches),
            lambda bsz, max_len: rwkv.init_cache(cfg, bsz, max_len),
            _prepare_fn(cfg),
        )
    if cfg.family == "griffin":
        return ModelAPI(
            cfg,
            lambda key: griffin.init(key, cfg),
            lambda p, batch: griffin.loss_fn(p, cfg, batch),
            lambda p, batch, caches: griffin.prefill(
                p, cfg, batch["tokens"], caches),
            lambda p, batch, caches: griffin.decode_step(
                p, cfg, batch["token"], batch["pos"], caches),
            lambda bsz, max_len: griffin.init_cache(cfg, bsz, max_len),
            _prepare_fn(cfg),
        )
    if cfg.family == "encdec":
        return ModelAPI(
            cfg,
            lambda key: encdec.init(key, cfg),
            lambda p, batch: encdec.loss_fn(p, cfg, batch),
            lambda p, batch, caches: encdec.prefill(
                p, cfg, batch["tokens"], caches, batch["frames"]),
            lambda p, batch, state: encdec.decode_step(
                p, cfg, batch["token"], batch["pos"], state),
            lambda bsz, max_len: encdec.init_cache(cfg, bsz, max_len),
            _prepare_fn(cfg),
        )
    if cfg.family == "vlm":
        return ModelAPI(
            cfg,
            lambda key: vlm.init(key, cfg),
            lambda p, batch: vlm.loss_fn(p, cfg, batch),
            lambda p, batch, caches: vlm.prefill(
                p, cfg, batch["tokens"], caches, batch["patches"]),
            lambda p, batch, caches: vlm.decode_step(
                p, cfg, batch["token"], batch["pos"], caches),
            lambda bsz, max_len: vlm.init_cache(
                cfg, bsz, max_len + (cfg.n_patches or 0)),
            _prepare_fn(cfg),
        )
    raise ValueError(cfg.family)


def input_specs(cfg: ModelConfig, shape: InputShape) -> Dict[str, Any]:
    """ShapeDtypeStruct batch for (arch x shape), per step kind."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    f32 = jnp.float32

    def sds(shp, dt=i32):
        return jax.ShapeDtypeStruct(shp, dt)

    if shape.kind == "train":
        batch = {"tokens": sds((b, s + 1))}
        if cfg.family == "encdec":
            batch["frames"] = sds((b, s // 4, cfg.frontend_dim), f32)
        if cfg.family == "vlm":
            batch["patches"] = sds((b, cfg.n_patches, cfg.vit_dim), f32)
        return batch
    if shape.kind == "prefill":
        batch = {"tokens": sds((b, s))}
        if cfg.family == "encdec":
            batch["frames"] = sds((b, s // 4, cfg.frontend_dim), f32)
        if cfg.family == "vlm":
            batch["patches"] = sds((b, cfg.n_patches, cfg.vit_dim), f32)
        return batch
    if shape.kind == "decode":
        return {"token": sds((b, 1)), "pos": sds((b,))}
    raise ValueError(shape.kind)


def materialize_batch(cfg: ModelConfig, shape: InputShape, seed: int = 0
                      ) -> Dict[str, jax.Array]:
    """Concrete random batch matching input_specs (smoke tests)."""
    specs = input_specs(cfg, shape)
    key = jax.random.PRNGKey(seed)
    out = {}
    for name, spec in specs.items():
        key, k = jax.random.split(key)
        if spec.dtype == jnp.int32:
            if name == "pos":
                out[name] = jnp.full(spec.shape, shape.seq_len - 1,
                                     jnp.int32)
            else:
                out[name] = jax.random.randint(k, spec.shape, 0,
                                               min(cfg.vocab, 1000))
        else:
            out[name] = jax.random.normal(k, spec.shape, spec.dtype)
    return out
