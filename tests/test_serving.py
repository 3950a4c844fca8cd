"""Serving runtime coverage: engine drain edge cases, batched-prefill
equivalence, scheduler policies, plan-aware routing, metrics schema, and
the ``repro.launch.serve`` compat shim.

The engine contract under refactor: batched prefill admission must
produce the same per-slot cache state (and next-step logits) as the
teacher-forced loop, and ``routing_report()`` must keep satisfying the
plan→policy→routing round trip (also covered via the shim in
tests/test_autotune.py).
"""
import dataclasses

import numpy as np
import pytest

from repro.configs import reduced
from repro.serving import (AdmissionScheduler, EngineConfig, Request,
                           Router, SamplingParams, SchedulerFull,
                           ServingEngine, build_replicas, percentiles,
                           request_metrics, slo_report)

ARCH = "qwen2-0.5b"


@pytest.fixture(scope="module")
def lm_setup():
    import jax

    from repro.models import registry
    cfg = dataclasses.replace(reduced(ARCH),
                              precision_policy="int8_serving")
    api = registry.build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    return cfg, api, params


@pytest.fixture(scope="module", params=["qwen2-0.5b", "gemma2-9b"])
def decoding_engine(request):
    """Six live decode slots with written caches, on a dense model and
    on one whose local/global layers keep two cache groups (the local
    one a ring), with calibrated int8 as the chip benchmark serves."""
    import jax

    from repro.models import registry
    cfg = dataclasses.replace(reduced(request.param),
                              precision_policy="int8_serving")
    api = registry.build(cfg)
    eng = ServingEngine(cfg, api, api.init(jax.random.PRNGKey(0)),
                        config=EngineConfig(batch_slots=6, cache_len=32,
                                            prefill_chunk=8,
                                            act_calibration="auto"))
    assert len(eng.caches) == (2 if cfg.attn_pattern ==
                               "alt_local_global" else 1)
    for r in _requests(cfg, range(5, 11), [40] * 6):
        eng.submit(r)
    eng.step()
    eng.step()
    return eng


def _engine(lm_setup, **kw):
    cfg, api, params = lm_setup
    kw.setdefault("batch_slots", 3)
    kw.setdefault("cache_len", 64)
    return ServingEngine(cfg, api, params, config=EngineConfig(**kw))


def _requests(cfg, lengths, max_new):
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab, n,
                                               dtype=np.int32),
                    max_new_tokens=m)
            for i, (n, m) in enumerate(zip(lengths, max_new))]


# --------------------------------------------------------------- engine

class TestEngineDrain:
    def test_more_requests_than_slots(self, lm_setup):
        cfg = lm_setup[0]
        eng = _engine(lm_setup, batch_slots=2)
        reqs = _requests(cfg, [5, 7, 3, 9, 4, 6, 8], [3] * 7)
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
        assert len(eng.completed) == 7
        for r in reqs:
            assert r.done and r.new_tokens == 3

    def test_mixed_max_new_and_zero_generation(self, lm_setup):
        cfg = lm_setup[0]
        eng = _engine(lm_setup)
        reqs = _requests(cfg, [5, 6, 4, 7, 3], [4, 0, 1, 2, 0])
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
        assert len(eng.completed) == 5
        for r in reqs:
            assert r.new_tokens == max(r.max_new_tokens, 0)
        # zero-generation requests complete without ever decoding
        assert reqs[1].first_token_time is None
        assert reqs[1].finish_time is not None
        assert reqs[1].tokens == [int(t) for t in reqs[1].prompt]

    def test_single_token_and_empty_prompt(self, lm_setup):
        cfg = lm_setup[0]
        eng = _engine(lm_setup)
        one = Request(rid=0, prompt=np.asarray([7], np.int32),
                      max_new_tokens=2)
        empty = Request(rid=1, prompt=np.zeros(0, np.int32),
                        max_new_tokens=2)
        eng.submit(one)
        eng.submit(empty)
        eng.run_until_drained()
        assert one.new_tokens == 2
        assert empty.done and empty.new_tokens == 0
        # a 1-token prompt needs no prefill call at all
        assert eng.counters["prefill_calls"] == 0

    @pytest.mark.parametrize("prefill", ["batched", "teacher"])
    def test_lifecycle_stamps_split_ttft(self, lm_setup, prefill):
        """submit <= admit <= prefill_done <= first_token for every
        request: chunked prompts of several waves, a one-token prompt,
        and requests that queue behind full slots."""
        cfg = lm_setup[0]
        eng = ServingEngine(
            cfg, lm_setup[1], lm_setup[2],
            config=EngineConfig(batch_slots=2, cache_len=64,
                                prefill=prefill, prefill_chunk=4),
            clock=_FakeClock())
        reqs = _requests(cfg, [11, 1, 6, 9, 1], [2, 3, 2, 1, 2])
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
        for r in reqs:
            assert r.done and r.error is None
            assert (r.submit_time <= r.admit_time <= r.prefill_done_time
                    <= r.first_token_time), r.rid
        # the 11-token prompt took three 4-token waves on the fast path
        long = reqs[0]
        assert long.prefill_done_time > long.admit_time
        # queued behind the two slots: admitted after it was submitted
        assert reqs[4].admit_time > reqs[4].submit_time

    def test_oversized_requests_truncate_instead_of_rejecting(
            self, lm_setup):
        """Chunked prefill lifted the old ``prompt + generation <=
        cache_len`` admission bound: requests that would wrap the KV
        ring are now admitted with ``truncated=True`` (trailing-window
        ring semantics) and still serve their full budget, instead of
        raising at submit."""
        eng = _engine(lm_setup, cache_len=8)
        long_prompt = Request(rid=0, prompt=np.arange(12, dtype=np.int32),
                              max_new_tokens=1)
        # decode growth counts too: 5-1+5 > 8
        growth = Request(rid=1, prompt=np.arange(5, dtype=np.int32),
                         max_new_tokens=5)
        # exact fit (5-1+4 == 8) stays untruncated
        ok = Request(rid=2, prompt=np.arange(5, dtype=np.int32),
                     max_new_tokens=4)
        for r in (long_prompt, growth, ok):
            eng.submit(r)
        eng.run_until_drained()
        assert set(eng.completed) == {0, 1, 2}
        for r in (long_prompt, growth, ok):
            assert r.done and r.error is None
            assert r.new_tokens == r.max_new_tokens
            assert r.finish_reason == "length"
        assert long_prompt.truncated and growth.truncated
        assert not ok.truncated


class TestBatchedPrefill:
    def test_no_decode_per_prompt_token(self, lm_setup):
        """A prompt of length S streams through ceil((S-1)/chunk)
        prefill waves and decode runs exactly max_new steps — never S
        teacher-forced decodes."""
        cfg = lm_setup[0]
        eng = _engine(lm_setup, prefill="batched", prefill_chunk=8)
        eng.submit(_requests(cfg, [23], [4])[0])
        eng.run_until_drained()
        # 22 prompt tokens at chunk 8 -> waves of 8/8/6
        assert eng.counters["prefill_calls"] == 3
        assert eng.counters["prefill_tokens"] == 22
        assert eng.counters["decode_steps"] == 4
        assert eng.counters["teacher_forced_tokens"] == 0

    def test_matches_teacher_forced_admission(self):
        """The bucket-padded prefill + per-slot cache merge produces the
        same per-slot cache state and next-step logits as feeding the
        prompt token-by-token through decode. Compared numerically under
        the bf16 policy: greedy trajectories would amplify an argmax tie
        into divergent completions, and dynamic fake-quant policies
        legitimately differ between the paths (the per-tensor activation
        absmax spans the whole prompt in prefill but one token in
        decode)."""
        import jax
        import jax.numpy as jnp

        from repro.models import registry
        cfg = dataclasses.replace(reduced(ARCH),
                                  precision_policy="bf16")
        api = registry.build(cfg)
        params = api.init(jax.random.PRNGKey(0))
        lengths = [5, 1, 9]          # mixed: one slot needs no prefill
        engines = {}
        for mode in ("batched", "teacher"):
            eng = ServingEngine(cfg, api, params,
                                config=EngineConfig(batch_slots=3,
                                                    cache_len=64,
                                                    prefill=mode,
                                                    prefill_chunk=4))
            for r in _requests(cfg, lengths, [2] * len(lengths)):
                eng.submit(r)
            eng._admit()
            while eng._prefill_tick():   # drain the chunked waves
                pass
            engines[mode] = eng
        fast, slow = engines["batched"], engines["teacher"]
        assert np.array_equal(fast.pos, slow.pos)
        # 4 + 8 prompt tokens at chunk 4: two packed waves
        assert fast.counters["prefill_calls"] == 2
        assert slow.counters["teacher_forced_tokens"] == sum(
            n - 1 for n in lengths)

        # every cache leaf is (n_groups, slots, capacity, ...): the
        # admitted prefix of each slot must carry the same K/V and tags
        for lf, ls in zip(jax.tree.leaves(fast.caches),
                          jax.tree.leaves(slow.caches)):
            for slot, n in enumerate(lengths):
                if n <= 1:
                    continue
                a = np.asarray(lf[:, slot, :n - 1], np.float32)
                b = np.asarray(ls[:, slot, :n - 1], np.float32)
                np.testing.assert_allclose(a, b, rtol=0.05, atol=0.05)

        # and the first decode step sees the same distribution
        tok = np.zeros((fast.b, 1), np.int32)
        for s in range(fast.b):
            tok[s, 0] = fast.slot_req[s].next_input
            assert fast.slot_req[s].next_input \
                == slow.slot_req[s].next_input
        def first_logits(eng):
            logits, _ = eng._decode(eng.params, jnp.asarray(tok),
                                    jnp.asarray(eng.pos), eng.caches)
            return np.asarray(logits, np.float32)
        np.testing.assert_allclose(first_logits(fast),
                                   first_logits(slow),
                                   rtol=0.1, atol=0.1)

    @pytest.mark.parametrize("n_prefill", range(1, 7))
    def test_bucket_wave_equals_full_width_wave(self, decoding_engine,
                                                n_prefill):
        """A wave on the smallest power-of-two bucket of rows that holds
        the prefilling slots writes every cache leaf (k, v, pos), in
        every row, exactly as the full-width ``api.prefill_chunk`` wave
        does on the same caches. The bucket's pad rows are live decode
        slots whose cache rows hold their prompts and tokens."""
        import jax
        import jax.numpy as jnp
        eng = decoding_engine
        base, live, pos = eng.caches, list(eng.slot_req), eng.pos.copy()
        assert all(r is not None and r.next_input is not None
                   for r in live)
        chunk = eng.prefill_chunk
        tokens = np.zeros((eng.b, chunk), np.int32)
        offs = np.zeros(eng.b, np.int32)
        lens = np.zeros(eng.b, np.int32)
        rng = np.random.default_rng(n_prefill)
        # scattered slots, offsets and lengths: 1, 0, 5, 4, 3, 2
        for j, s in enumerate((5 * j + 1) % eng.b
                              for j in range(n_prefill)):
            off, take = chunk * (s % 2), chunk - 3 * (j % 2)
            req = Request(rid=100 + s, prompt=rng.integers(
                0, eng.cfg.vocab, off + take + 1, dtype=np.int32))
            req.tokens, req.prefill_pos = list(req.prompt), off
            eng.slot_req[s] = req
            tokens[s, :take] = req.prompt[off:off + take]
            offs[s], lens[s] = off, take
        rows0 = eng.counters["prefill_rows"]
        try:
            assert eng._prefill_tick()
            got = eng.caches
        finally:
            eng.caches, eng.slot_req[:], eng.pos[:] = base, live, pos
        want = eng._prefill_chunk_fn(eng.params, jnp.array(tokens),
                                     jnp.array(offs), jnp.array(lens), base)
        assert eng.counters["prefill_rows"] - rows0 == min(
            b for b in (1, 2, 4, 6) if b >= n_prefill)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                        strict=True):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))

    def test_waves_take_the_smallest_bucket_and_never_compile(self,
                                                              lm_setup):
        """Each wave dispatches the smallest bucket of {1, 2, 4} rows
        that holds its prefilling slots (``prefill_rows`` sums them),
        and every bucket compiles on the first prefill tick: no wave
        after it compiles, whichever bucket it takes."""
        cfg = lm_setup[0]
        eng = _engine(lm_setup, batch_slots=4, prefill_chunk=4,
                      trace=True)
        reqs = _requests(cfg, [30, 6, 20, 9, 25, 14], [3] * 6)
        TestContinuousServing()._drive(eng, reqs, [0, 1, 1, 2, 2, 6])
        events = [e for e in eng.tracer.events if e["ph"] == "X"]
        waves = [e["args"] for e in events
                 if e["name"] == "prefill_dispatch"]
        for w in waves:
            assert w["rows"] == min(b for b in (1, 2, 4)
                                    if b >= w["slots"])
        assert {w["rows"] for w in waves} == {1, 2, 4}
        assert any(w["rows"] > w["slots"] for w in waves)   # padded
        assert eng.counters["prefill_rows"] == sum(w["rows"] for w in waves)
        assert eng.counters["prefill_calls"] == len(waves)
        names = [e["name"] for e in events]
        first = names.index("prefill_dispatch")
        assert names[:first].count("compile:prefill_chunk") == 3
        assert "compile:prefill_chunk" not in names[first:]

    def test_warm_dispatches_write_and_count_nothing(self, lm_setup):
        """The first prefill tick compiles every bucket with waves of
        no valid token: only the tick's own wave is counted, and only
        its slot's positions carry cache tags afterwards."""
        cfg = lm_setup[0]
        eng = _engine(lm_setup, prefill_chunk=4)
        eng.submit(_requests(cfg, [7], [2])[0])
        eng._admit()
        assert eng._prefill_tick()
        assert eng._prefill_buckets == [1, 2, 3]
        assert (eng.counters["prefill_calls"], eng.counters["prefill_tokens"],
                eng.counters["prefill_rows"]) == (1, 4, 1)
        want = np.full((eng.b, eng.cache_len), -1)
        want[0, :4] = np.arange(4)
        for cache in eng.caches.values():
            for group in np.asarray(cache.pos):
                np.testing.assert_array_equal(group, want)

    def test_batched_rejected_for_recurrent_families(self):
        """Recurrent state is not position-tagged: padded prefill would
        corrupt it, so forcing the fast path must fail fast."""
        import jax

        from repro.models import registry
        cfg = reduced("rwkv6-1.6b")
        api = registry.build(cfg)
        params = api.init(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="not eligible"):
            ServingEngine(cfg, api, params,
                          config=EngineConfig(batch_slots=2, cache_len=16,
                                              prefill="batched"))
        # auto mode falls back to teacher forcing and still serves
        eng = ServingEngine(cfg, api, params,
                            config=EngineConfig(batch_slots=2,
                                                cache_len=16))
        assert not eng._fast_prefill
        eng.submit(Request(rid=0, prompt=np.asarray([3, 1, 4], np.int32),
                           max_new_tokens=2))
        eng.run_until_drained()
        assert eng.completed[0].new_tokens == 2
        assert eng.counters["teacher_forced_tokens"] == 2


class TestBlockedDecode:
    """The decode fast path (jitted scan + on-device argmax, one host
    sync per block) must be a pure dispatch optimization: per-request
    token streams are identical to per-token decode at every block
    size, because batch rows are independent and masked (budget-
    exhausted) slots feed exactly what the per-token engine feeds freed
    slots (a pad write at the slot's current frontier position, which
    the next real write overwrites before any query attends it)."""

    LENGTHS = [5, 7, 3, 9, 4, 6]
    BUDGETS = [6, 3, 8, 2, 5, 4]      # mixed: slots mask mid-block

    def _tokens(self, lm_setup, cfg=None, **kw):
        if cfg is None:
            cfg = dataclasses.replace(lm_setup[0],
                                      precision_policy="bf16")
        from repro.models import registry
        api = registry.build(cfg)
        eng = ServingEngine(cfg, api, lm_setup[2],
                            config=EngineConfig(batch_slots=3,
                                                cache_len=64, **kw))
        reqs = _requests(cfg, self.LENGTHS, self.BUDGETS)
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
        return {r.rid: list(r.tokens) for r in reqs}, eng

    def test_blocked_equals_per_token_all_block_sizes(self, lm_setup):
        base, _ = self._tokens(lm_setup)
        for blk in (1, 2, 3, 8):
            toks, eng = self._tokens(lm_setup, decode_block=blk)
            assert toks == base, f"decode_block={blk} diverged"
            for rid, budget in enumerate(self.BUDGETS):
                assert len(toks[rid]) == self.LENGTHS[rid] + budget

    def test_block_one_is_per_token_engine(self, lm_setup):
        """decode_block=1 must reproduce today's behavior exactly —
        same tokens AND same counters (one host sync per decode)."""
        base, eng0 = self._tokens(lm_setup)
        toks, eng1 = self._tokens(lm_setup, decode_block=1)
        assert toks == base
        assert eng1.counters == eng0.counters
        assert eng1.counters["host_syncs"] == eng1.counters["decode_steps"]

    def test_blocked_counter_contract(self, lm_setup):
        """A tick dispatches at most one block; a block syncs once."""
        blk = 4
        _, per_tok = self._tokens(lm_setup)
        _, fast = self._tokens(lm_setup, decode_block=blk)
        c, c1 = fast.counters, per_tok.counters
        assert c["decode_steps"] <= c["ticks"] * blk, c
        assert c["host_syncs"] * blk >= c["decode_steps"], c
        assert c["host_syncs"] < c1["host_syncs"], (c, c1)
        assert fast.metrics()["decode_block"] == blk

    def test_blocked_quantized_policy_matches(self, lm_setup):
        """int8 with calibrated static activation scales: the blocked
        trajectory still matches per-token exactly."""
        cfg = lm_setup[0]          # int8_serving
        from repro.quant.calibrate import calibrate_act_scales
        scales = calibrate_act_scales(cfg, lm_setup[1], lm_setup[2])
        base, _ = self._tokens(lm_setup, cfg=cfg,
                               act_calibration=scales)
        toks, eng = self._tokens(lm_setup, cfg=cfg,
                                 act_calibration=scales, decode_block=8)
        assert toks == base
        assert eng.act_quant_trace_count() == 0
        assert eng.weight_quant_trace_count() == 0

    def test_blocked_allows_moe_experts_uncovered(self):
        """MoE expert stacks quantize weights only (activations ride
        the bf16 einsums), so they cannot couple batch rows — and no
        mp_linear call exists for calibration to cover them. The
        dynamic-fake-quant guard must exempt them or MoE models could
        never use the fast path under int policies."""
        import jax

        from repro.models import registry
        from repro.quant.calibrate import calibrate_act_scales
        cfg = dataclasses.replace(reduced("mixtral-8x7b"),
                                  precision_policy="int8_serving")
        api = registry.build(cfg)
        params = api.init(jax.random.PRNGKey(0))
        scales = calibrate_act_scales(cfg, api, params)
        assert "block/moe/experts" not in scales
        eng = ServingEngine(cfg, api, params,
                            config=EngineConfig(batch_slots=2,
                                                cache_len=32,
                                                decode_block=4,
                                                act_calibration=scales))
        assert eng.act_quant_trace_count() == 0
        assert eng.weight_quant_trace_count() == 0

    def test_blocked_rejects_dynamic_fake_quant(self, lm_setup):
        """Dynamic fake-quant activations share ONE per-tensor absmax
        across batch rows, so a blocked engine's pad cadence would leak
        into other slots' tokens (measured: uncalibrated int8 diverges
        at block 4 under queue pressure) — rejected at construction."""
        cfg, api, params = lm_setup          # int8_serving, uncalibrated
        with pytest.raises(ValueError, match="per-slot-independent"):
            ServingEngine(cfg, api, params,
                          config=EngineConfig(batch_slots=2, cache_len=32,
                                              decode_block=4))
        # calibrated scales decouple the rows: construction succeeds
        from repro.quant.calibrate import calibrate_act_scales
        ServingEngine(cfg, api, params, config=EngineConfig(
            batch_slots=2, cache_len=32, decode_block=4,
            act_calibration=calibrate_act_scales(cfg, api, params)))

    def test_blocked_equals_per_token_vlm(self):
        """The other eligible family: vlm's position-tagged caches make
        masked pad writes causally invisible too."""
        import jax

        from repro.models import registry
        cfg = dataclasses.replace(reduced("internvl2-1b"),
                                  precision_policy="bf16")
        api = registry.build(cfg)
        params = api.init(jax.random.PRNGKey(0))

        def run(blk):
            eng = ServingEngine(cfg, api, params,
                                config=EngineConfig(batch_slots=2,
                                                    cache_len=32,
                                                    decode_block=blk))
            reqs = _requests(cfg, [5, 7, 3, 4], [4, 2, 5, 3])
            for r in reqs:
                eng.submit(r)
            eng.run_until_drained()
            return {r.rid: list(r.tokens) for r in reqs}

        assert run(1) == run(4)

    def test_blocked_rejected_for_recurrent_families(self):
        """Recurrent state folds every masked pad step in, so the
        block-vs-tick pad cadence diverges the token streams (measured
        on rwkv/griffin with mixed budgets) — blocked decode must fail
        fast for them rather than silently drift."""
        import jax

        from repro.models import registry
        cfg = reduced("rwkv6-1.6b")
        api = registry.build(cfg)
        params = api.init(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="not eligible"):
            ServingEngine(cfg, api, params,
                          config=EngineConfig(batch_slots=2, cache_len=16,
                                              decode_block=4))
        with pytest.raises(ValueError, match="not eligible"):
            registry.make_block_decode(api, 4)


# ------------------------------------------------- serving API surfaces

class TestServingAPI:
    """EngineConfig / SamplingParams redesign: validation at
    construction, the legacy-kwarg deprecation shim, and per-request
    sampling plumbed through ``submit()``."""

    def test_engine_config_validation(self):
        with pytest.raises(ValueError, match="batch_slots"):
            EngineConfig(batch_slots=0)
        with pytest.raises(ValueError, match="cache_len"):
            EngineConfig(cache_len=0)
        with pytest.raises(ValueError, match="prefill mode"):
            EngineConfig(prefill="bogus")
        with pytest.raises(ValueError, match="prefill_chunk"):
            EngineConfig(prefill_chunk=0)
        with pytest.raises(ValueError, match="decode_block"):
            EngineConfig(decode_block=0)
        with pytest.raises(ValueError, match="eos_id"):
            EngineConfig(eos_id=-2)
        with pytest.raises(ValueError, match="cost_correction"):
            EngineConfig(cost_correction="sometimes")
        with pytest.raises(ValueError, match="stats_window"):
            EngineConfig(stats_window=0)
        with pytest.raises(ValueError, match="stats_alpha"):
            EngineConfig(stats_alpha=0.0)

    def test_sampling_params_validation(self):
        with pytest.raises(ValueError, match="temperature"):
            SamplingParams(temperature=-0.1)
        with pytest.raises(ValueError, match="top_k"):
            SamplingParams(top_k=-1)
        with pytest.raises(ValueError, match="top_p"):
            SamplingParams(top_p=0.0)
        with pytest.raises(ValueError, match="stop_ids"):
            SamplingParams(stop_ids=(-3,))
        with pytest.raises(ValueError, match="stop_ids"):
            SamplingParams(stop_ids=tuple(range(9)))
        with pytest.raises(ValueError, match="max_new_tokens"):
            SamplingParams(max_new_tokens=-1)
        assert SamplingParams().greedy
        assert not SamplingParams(temperature=0.7).greedy
        assert SamplingParams(stop_ids=[3, 1]).stop_ids == (3, 1)

    def test_from_legacy_kwargs(self):
        legacy = EngineConfig.from_legacy_kwargs(
            {"batch_slots": 2, "decode_block": 4, "greedy": True})
        assert legacy == EngineConfig(batch_slots=2, decode_block=4)
        with pytest.raises(TypeError, match="unknown"):
            EngineConfig.from_legacy_kwargs({"slots": 2})

    def test_legacy_kwargs_deprecation_shim(self, lm_setup):
        cfg, api, params = lm_setup
        with pytest.warns(DeprecationWarning, match="EngineConfig"):
            eng = ServingEngine(cfg, api, params, batch_slots=2,
                                cache_len=32, greedy=True)
        assert eng.config == EngineConfig(batch_slots=2, cache_len=32)
        # a legacy-constructed engine still serves
        eng.submit(Request(rid=0, prompt=np.asarray([3, 1, 4], np.int32),
                           max_new_tokens=2))
        eng.run_until_drained()
        assert eng.completed[0].new_tokens == 2
        with pytest.raises(TypeError, match="not both"):
            ServingEngine(cfg, api, params, config=EngineConfig(),
                          batch_slots=2)

    def test_submit_validates_sampling(self, lm_setup):
        eng = _engine(lm_setup)
        bad = Request(rid=0, prompt=np.zeros(3, np.int32))
        bad.sampling = {"temperature": 1.0}
        with pytest.raises(TypeError, match="SamplingParams"):
            eng.submit(bad)
        # engine-wide eos_id counts against the per-slot stop slots
        eng2 = _engine(lm_setup, eos_id=5)
        full = Request(rid=1, prompt=np.zeros(3, np.int32),
                       sampling=SamplingParams(stop_ids=(1, 2, 3, 4)))
        with pytest.raises(ValueError, match="stop slots"):
            eng2.submit(full)

    def test_sampling_budget_overrides_request(self, lm_setup):
        cfg = lm_setup[0]
        eng = _engine(lm_setup)
        req = _requests(cfg, [5], [8])[0]
        req.sampling = SamplingParams(max_new_tokens=3)
        eng.submit(req)
        eng.run_until_drained()
        assert req.new_tokens == 3 and req.finish_reason == "length"


class TestSampledDecode:
    """On-device sampling: per-request seeded PRNG keys ride the decode
    carry, so sampled streams are reproducible and invariant to
    decode_block — and greedy rows in a mixed batch stay bit-identical
    to the all-greedy program (argmax on raw logits)."""

    def _run(self, lm_setup, sampling_by_rid, blk=1, engine_seed=0):
        cfg = dataclasses.replace(lm_setup[0], precision_policy="bf16")
        from repro.models import registry
        api = registry.build(cfg)
        eng = ServingEngine(cfg, api, lm_setup[2],
                            config=EngineConfig(batch_slots=2,
                                                cache_len=64,
                                                decode_block=blk,
                                                seed=engine_seed))
        reqs = _requests(cfg, [5, 7, 3], [8, 6, 7])
        for r in reqs:
            r.sampling = sampling_by_rid.get(r.rid, SamplingParams())
            eng.submit(r)
        eng.run_until_drained()
        return {r.rid: list(r.tokens) for r in reqs}

    def test_seeded_sampling_deterministic_and_block_invariant(
            self, lm_setup):
        sp = {0: SamplingParams(temperature=0.8, seed=7),
              1: SamplingParams(temperature=1.0, top_k=8, seed=7),
              2: SamplingParams(temperature=0.9, top_p=0.8, seed=7)}
        a = self._run(lm_setup, sp, blk=1)
        b = self._run(lm_setup, sp, blk=1)
        assert a == b, "same seeds must reproduce the streams"
        for blk in (2, 4):
            assert self._run(lm_setup, sp, blk=blk) == a, \
                f"decode_block={blk} changed a sampled stream"

    def test_engine_seed_fold_in_reproducible_and_distinct(
            self, lm_setup):
        hot = {i: SamplingParams(temperature=1.0) for i in range(3)}
        a = self._run(lm_setup, hot, engine_seed=0)
        b = self._run(lm_setup, hot, engine_seed=0)
        c = self._run(lm_setup, hot, engine_seed=123)
        assert a == b, "engine-seed fold_in must be reproducible"
        assert a != c, "different engine seeds should move the streams"

    def test_greedy_rows_unchanged_by_sampled_neighbors(self, lm_setup):
        base = self._run(lm_setup, {})          # all greedy
        mixed = self._run(lm_setup, {1: SamplingParams(temperature=1.0,
                                                       seed=3)})
        assert mixed[0] == base[0] and mixed[2] == base[2]
        assert mixed[1] != base[1]


class TestContinuousServing:
    """The continuous-batching loop (chunked prefill continuation +
    mid-block admission + EOS stopping) must not change greedy token
    streams — it only changes WHEN work is dispatched. Compared against
    the flags-off engine (the PR-5 between-block baseline) on the same
    staggered arrival trace."""

    LENGTHS = [6, 18, 4, 9, 5, 23]
    BUDGETS = [2, 12, 3, 12, 4, 12]   # heterogeneous: blocks cut short
    SUBMIT_TICKS = [0, 0, 1, 2, 4, 6]

    def _drive(self, eng, reqs, ticks):
        """Tick-driven open loop: submit each request at its trace tick
        while the engine keeps stepping."""
        order = sorted(range(len(reqs)), key=lambda i: ticks[i])
        i, tick = 0, 0
        while i < len(order) or eng.has_pending():
            while i < len(order) and ticks[order[i]] <= tick:
                eng.submit(reqs[order[i]])
                i += 1
            if eng.has_pending():
                eng.step()
            tick += 1
        return {r.rid: list(r.tokens) for r in reqs}

    def _run(self, lm_setup, flags_on, blk=4, stops=None):
        cfg = dataclasses.replace(lm_setup[0], precision_policy="bf16")
        from repro.models import registry
        api = registry.build(cfg)
        eng = ServingEngine(cfg, api, lm_setup[2], config=EngineConfig(
            batch_slots=2, cache_len=64, decode_block=blk,
            prefill_chunk=4, mid_block_admission=flags_on,
            eos_stopping=flags_on))
        reqs = _requests(cfg, self.LENGTHS, self.BUDGETS)
        for r in reqs:
            if stops and r.rid in stops:
                r.sampling = SamplingParams(stop_ids=(stops[r.rid],))
        toks = self._drive(eng, reqs, self.SUBMIT_TICKS)
        return toks, reqs, eng

    def test_continuous_equals_flags_off_engine(self, lm_setup):
        base, _, ref = self._run(lm_setup, flags_on=False)
        toks, _, eng = self._run(lm_setup, flags_on=True)
        assert toks == base, "continuous flags changed a greedy stream"
        for rid in range(len(self.LENGTHS)):
            assert len(base[rid]) == self.LENGTHS[rid] + self.BUDGETS[rid]
        assert ref.counters["short_blocks"] == 0
        assert ref.counters["mid_block_admits"] == 0
        assert eng.counters["short_blocks"] > 0
        assert eng.counters["mid_block_admits"] > 0
        # both stream long prompts through chunked waves, never teacher
        for e in (ref, eng):
            assert e.counters["prefill_calls"] >= 5
            assert e.counters["teacher_forced_tokens"] == 0
        # trimming blocks to admissions never costs decode work
        assert eng.counters["decode_steps"] <= ref.counters["decode_steps"]

    def test_eos_stops_blocked_equals_per_token(self, lm_setup):
        base, _, _ = self._run(lm_setup, flags_on=False)
        # harvest stop tokens from the greedy streams so they fire
        stops = {1: base[1][self.LENGTHS[1] + 3],
                 3: base[3][self.LENGTHS[3] + 2]}
        blocked, breqs, beng = self._run(lm_setup, flags_on=True,
                                         stops=stops)
        tick, treqs, teng = self._run(lm_setup, flags_on=True, blk=1,
                                      stops=stops)
        assert blocked == tick, "EOS stopping diverged blocked vs tick"
        assert beng.counters["eos_stops"] == len(stops)
        assert teng.counters["eos_stops"] == len(stops)
        for rid, stop_tok in stops.items():
            r = breqs[rid]
            assert r.finish_reason == "stop"
            assert r.tokens[-1] == stop_tok
            assert r.new_tokens < self.BUDGETS[rid]
            # cut at the FIRST occurrence, as a prefix of the free run
            gen = r.tokens[self.LENGTHS[rid]:]
            assert stop_tok not in gen[:-1]
            assert base[rid][:len(r.tokens)] == r.tokens
        for r in breqs:
            if r.rid not in stops:
                assert r.finish_reason == "length"


class TestRoutingReport:
    def test_plan_policy_routing_roundtrip(self, lm_setup, tmp_path):
        """Plan → policy → observed decode routing stays consistent
        across the serving refactor."""
        import jax

        from repro.autotune.plan import PlanRule, PrecisionPlan
        from repro.models import registry
        from repro.models.registry import projection_groups

        groups = {g.name: g for g in projection_groups(reduced(ARCH))}
        plan = PrecisionPlan(
            name="t", arch=ARCH,
            rules=(PlanRule("attn_qkv", groups["attn_qkv"].pattern,
                            "int8"),
                   PlanRule("ffn_in", groups["ffn_in"].pattern, "int4")),
            default_mode="bf16")
        path = str(tmp_path / "plan.json")
        plan.save(path)
        cfg = dataclasses.replace(reduced(ARCH),
                                  precision_policy=f"plan:{path}")
        api = registry.build(cfg)
        params = api.init(jax.random.PRNGKey(0))
        eng = ServingEngine(cfg, api, params,
                            config=EngineConfig(batch_slots=2,
                                                cache_len=16))
        routes = eng.routing_report()
        assert routes, "decode step routed no projections"
        policy = plan.to_policy()
        for p, mode in routes.items():
            assert mode == policy.spec_for(p).mode, p
        assert routes["block/full/attn/wq"] == "int8"
        assert routes["block/mlp/w_gate"] == "int4"
        assert routes["block/full/attn/wo"] == "bf16"


class TestFusedExecutors:
    """EngineConfig.fused_executors routing: the on/off/auto contract,
    the staged-materialization trace counter, and the fp storage tier
    serving end-to-end from an autotune plan through a checkpoint."""

    def test_on_requires_prepared(self, lm_setup):
        with pytest.raises(ValueError, match="prepared"):
            _engine(lm_setup, prepare_weights=False,
                    fused_executors="on")

    def test_auto_resolution_and_staged_counter(self, lm_setup):
        # prepared + calibrated resolves onto the fused datapath: zero
        # staged compute-dtype materializations in the traced program
        eng = _engine(lm_setup, act_calibration="auto", decode_block=4)
        assert eng.fused
        assert eng.staged_trace_count() == 0
        assert eng.metrics()["fused_executors"] is True
        # "off" pins the staged fallback — the counter hook is live
        off = _engine(lm_setup, act_calibration=eng.act_scales,
                      decode_block=4, fused_executors="off")
        assert not off.fused
        assert off.staged_trace_count() > 0
        assert off.metrics()["fused_executors"] is False
        # prepared int without act scales cannot fuse (the int kernels
        # need a static activation scale), nor can a dynamic engine
        assert not _engine(lm_setup).fused
        assert not _engine(lm_setup, prepare_weights=False).fused

    @pytest.mark.slow
    def test_fp_plan_serves_end_to_end(self, tmp_path):
        """The acceptance path: an autotune plan selecting fp8 (per
        -group scales) + fp4 prepares fp storage, resolves fused WITHOUT
        activation scales (fp kernels need none), survives a fabric
        checkpoint round trip, and the rebuilt engine serves identical
        greedy streams."""
        import jax

        from repro.autotune.plan import PlanRule, PrecisionPlan
        from repro.fabric.checkpoint import (build_engine,
                                             save_engine_checkpoint)
        from repro.models import registry
        from repro.models.registry import projection_groups
        from repro.quant.prepare import iter_projection_weights

        groups = {g.name: g for g in projection_groups(reduced(ARCH))}
        plan = PrecisionPlan(
            name="fp_tier", arch=ARCH,
            rules=(PlanRule("attn_qkv", groups["attn_qkv"].pattern,
                            "fp8", group_size=8),
                   PlanRule("ffn_in", groups["ffn_in"].pattern, "fp4")),
            default_mode="bf16")
        path = str(tmp_path / "plan.json")
        plan.save(path)
        cfg = dataclasses.replace(reduced(ARCH),
                                  precision_policy=f"plan:{path}")
        api = registry.build(cfg)
        params = api.init(jax.random.PRNGKey(0))
        eng = ServingEngine(cfg, api, params, config=EngineConfig(
            batch_slots=2, cache_len=64, decode_block=4))
        assert eng.prepared and eng.fused
        assert eng.staged_trace_count() == 0
        kinds = {w.kind for _, w in iter_projection_weights(
                     eng.params, registry.projection_paths(cfg))
                 if hasattr(w, "kind")}
        assert {"fp8", "fp4_packed"} <= kinds, kinds
        reqs = _requests(cfg, [5, 7], [4, 4])
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
        want = {r.rid: list(r.tokens) for r in reqs}
        assert all(len(t) >= 4 for t in want.values()), want

        ckpt = str(tmp_path / "ckpt")
        save_engine_checkpoint(eng, ckpt, step=1)
        eng2 = build_engine(ckpt)
        assert eng2.prepared and eng2.fused
        reqs2 = _requests(cfg, [5, 7], [4, 4])
        for r in reqs2:
            eng2.submit(r)
        eng2.run_until_drained()
        assert {r.rid: list(r.tokens) for r in reqs2} == want


def test_launch_serve_shim():
    from repro.launch import serve as shim
    from repro.serving import config as cfg_mod
    from repro.serving import engine as eng_mod
    assert shim.ServingEngine is eng_mod.ServingEngine
    assert shim.Request is eng_mod.Request
    assert shim.make_serve_fns is eng_mod.make_serve_fns
    assert shim.EngineConfig is cfg_mod.EngineConfig
    assert shim.SamplingParams is cfg_mod.SamplingParams


# ------------------------------------------------------------ scheduler

def _req(rid, plen=4, priority=0, submit_time=None):
    r = Request(rid=rid, prompt=np.zeros(plen, np.int32),
                priority=priority)
    r.submit_time = submit_time
    return r


class TestScheduler:
    def test_priority_then_fifo(self):
        s = AdmissionScheduler()
        s.submit(_req(0, priority=1), now=0.0)
        s.submit(_req(1, priority=0), now=0.0)
        s.submit(_req(2, priority=0), now=0.0)
        assert [r.rid for r in s.select(3, now=0.1)] == [1, 2, 0]

    def test_max_wait_promotion(self):
        s = AdmissionScheduler(max_wait=5.0)
        s.submit(_req(0, priority=9), now=0.0)     # old, low priority
        s.submit(_req(1, priority=0), now=4.0)     # fresh, high priority
        # before promotion the high-priority request wins...
        assert [r.rid for r in s.select(1, now=4.5)] == [1]
        # ...after max_wait the starved one jumps every class
        assert [r.rid for r in s.select(1, now=6.0)] == [0]

    def test_bounded_queue_raises(self):
        s = AdmissionScheduler(max_queue=2)
        s.submit(_req(0))
        s.submit(_req(1))
        with pytest.raises(SchedulerFull):
            s.submit(_req(2))
        assert len(s) == 2

    def test_prefill_budget_defers_long_prompts(self):
        s = AdmissionScheduler(prefill_budget=8)
        s.submit(_req(0, plen=9), now=0.0)    # cost 8: fills the budget
        s.submit(_req(1, plen=9), now=0.0)    # cost 8: over budget
        s.submit(_req(2, plen=3), now=0.0)    # cost 2: over budget too
        wave = s.select(3, now=0.1)
        assert [r.rid for r in wave] == [0]   # progress guarantee only
        assert [r.rid for r in s.select(3, now=0.2)] == [1]
        assert [r.rid for r in s.select(3, now=0.3)] == [2]

    def test_promoted_bypass_budget(self):
        s = AdmissionScheduler(prefill_budget=4, max_wait=1.0)
        s.submit(_req(0, plen=9), now=0.0)
        s.submit(_req(1, plen=9), now=0.0)
        assert len(s.select(2, now=5.0)) == 2  # both promoted


# --------------------------------------------------------------- router

@pytest.fixture(scope="module")
def two_replicas(lm_setup):
    cfg, _, params = lm_setup
    base = dataclasses.replace(cfg, precision_policy="bf16")
    return build_replicas(base, ("int8_serving", "bf16"), params=params,
                          config=EngineConfig(batch_slots=2,
                                              cache_len=32))


class TestRouter:
    def test_cost_model_orders_replicas(self, two_replicas):
        int8, bf16 = two_replicas
        assert int8.cost["cycles_per_token"] \
            < bf16.cost["cycles_per_token"]
        assert bf16.cost["acc_proxy"] < int8.cost["acc_proxy"]
        assert int8.cost["tops_per_w"] > 0 and bf16.cost["tops_per_w"] > 0

    def test_plan_aware_routes_by_tag(self, two_replicas):
        router = Router(two_replicas, strategy="plan_aware")
        cheap = router.route(Request(rid=0,
                                     prompt=np.zeros(4, np.int32)))
        accurate = router.route(Request(rid=1,
                                        prompt=np.zeros(4, np.int32),
                                        tags=("accuracy",)))
        assert cheap.name == "int8_serving"
        assert accurate.name == "bf16"

    def test_round_robin_alternates(self, two_replicas):
        router = Router(two_replicas, strategy="round_robin")
        names = [router.route(_req(i)).name for i in range(4)]
        assert names == ["int8_serving", "bf16", "int8_serving", "bf16"]

    def test_mixed_workload_drains_and_counts(self, two_replicas):
        router = Router(two_replicas, strategy="plan_aware")
        rng = np.random.default_rng(1)
        reqs = [Request(rid=i,
                        prompt=rng.integers(0, 512, 5, dtype=np.int32),
                        max_new_tokens=2,
                        tags=("accuracy",) if i % 2 else ())
                for i in range(6)]
        for r in reqs:
            router.submit(r)
        router.run_until_drained()
        assert len(router.completed) == 6
        counters = router.routing_counters()
        assert sum(counters.values()) == 6
        assert all(n > 0 for n in counters.values()), counters
        rep = router.report()
        assert rep["strategy"] == "plan_aware"
        for name, r in rep["replicas"].items():
            assert r["metrics"]["counters"]["teacher_forced_tokens"] == 0

    def test_invalid_strategy_and_empty(self, two_replicas):
        with pytest.raises(ValueError):
            Router(two_replicas, strategy="nope")
        with pytest.raises(ValueError):
            Router([])
        with pytest.raises(ValueError, match="cost_correction"):
            Router(two_replicas, cost_correction="maybe")
        with pytest.raises(ValueError, match="online_blend"):
            Router(two_replicas, online_blend=1.5)

    def test_online_cost_correction_shifts_routing(self, two_replicas):
        """A statically-cheap replica that MEASURES slow loses traffic
        under online correction; static costing can't see it. Stats are
        injected directly — the engine-driven path is covered by the
        serving smoke's dilated-clock contract."""
        int8, bf16 = two_replicas
        static = Router(two_replicas, strategy="plan_aware",
                        cost_correction="static")
        online = Router(two_replicas, strategy="plan_aware",
                        cost_correction="online")
        req = Request(rid=0, prompt=np.zeros(4, np.int32))
        # the module-scoped fixture's engines may carry measurements
        # from earlier routing tests — force a cold fleet first
        saved = (int8.engine.stats.tok_per_s, bf16.engine.stats.tok_per_s)
        try:
            int8.engine.stats.tok_per_s = None
            bf16.engine.stats.tok_per_s = None
            # cold fleet: no measurements, online ranks like static
            assert static.route(req).name == "int8_serving"
            assert online.route(req).name == "int8_serving"
            int8.engine.stats.tok_per_s = 1.0     # became 100x slower
            bf16.engine.stats.tok_per_s = 100.0
            assert static.route(req).name == "int8_serving"
            assert online.route(req).name == "bf16"
            rep = online.routing_report()
            assert rep["cost_correction"] == "online"
            r8, rb = (rep["replicas"]["int8_serving"],
                      rep["replicas"]["bf16"])
            assert r8["static_cycles_per_token"] \
                < rb["static_cycles_per_token"]
            assert rb["effective_cost"] < r8["effective_cost"]
            assert r8["measured"]["tok_per_s"] == 1.0
        finally:
            int8.engine.stats.tok_per_s, bf16.engine.stats.tok_per_s = saved

    def test_replica_cost_covers_every_group(self, lm_setup):
        """Every projection group must resolve to a policy mode — a
        pattern no candidate path matches would silently drop a group
        from the cost model."""
        import re

        from repro.models.registry import projection_groups
        from repro.serving.router import _CANDIDATE_PATHS
        for arch in ("qwen2-0.5b", "rwkv6-1.6b", "recurrentgemma-9b",
                     "mixtral-8x7b", "internvl2-1b",
                     "seamless-m4t-medium", "gemma2-9b"):
            for g in projection_groups(reduced(arch)):
                assert any(re.search(g.pattern, p)
                           for p in _CANDIDATE_PATHS), (arch, g.name)


# -------------------------------------------------------------- metrics

class TestMetrics:
    def test_percentiles_empty_and_none_safe(self):
        assert percentiles([]) == {}
        assert percentiles([None, None]) == {}
        block = percentiles([1.0, 2.0, 3.0, None])
        assert block["p50"] == 2.0 and block["max"] == 3.0

    def test_request_metrics_decomposition(self):
        r = Request(rid=0, prompt=np.zeros(3, np.int32))
        r.tokens = [0, 0, 0, 1, 2]
        r.submit_time, r.admit_time = 10.0, 10.5
        r.first_token_time, r.finish_time = 11.0, 12.5
        m = request_metrics(r)
        assert m["ttft_s"] == pytest.approx(1.0)
        assert m["queue_delay_s"] == pytest.approx(0.5)
        assert m["e2e_s"] == pytest.approx(2.5)
        assert m["new_tokens"] == 2
        assert m["tok_per_s"] == pytest.approx(1.0)

    def test_engine_metrics_schema(self, lm_setup):
        cfg = lm_setup[0]
        eng = _engine(lm_setup)
        for r in _requests(cfg, [4, 6], [2, 2]):
            eng.submit(r)
        eng.run_until_drained()
        m = eng.metrics()
        assert m["n"] == 2 and m["new_tokens"] == 4
        for key in ("ttft_s", "queue_delay_s", "e2e_s"):
            assert m[key] and m[key]["p50"] >= 0.0
        assert m["counters"]["prefill_calls"] >= 1
        assert m["queue"] == 0 and m["active_slots"] == 0
        for key in ("short_blocks", "mid_block_admits", "eos_stops"):
            assert key in m["counters"]

    def test_slo_report(self):
        def req(rid, submit, first, finish, n_new):
            r = Request(rid=rid, prompt=np.zeros(2, np.int32))
            r.tokens = [0, 0] + [1] * n_new
            r.submit_time = submit
            r.first_token_time, r.finish_time = first, finish
            return r

        reqs = [req(0, 0.0, 0.5, 2.0, 10),    # TTFT 0.5 <= 1.0: attains
                req(1, 0.0, 2.0, 4.0, 6),     # TTFT 2.0 > 1.0: misses
                Request(rid=2, prompt=np.zeros(2, np.int32))]  # no token
        rep = slo_report(reqs, ttft_slo_s=1.0)
        assert rep["n"] == 2                  # tokenless one excluded
        assert rep["completed"] == 2
        assert rep["attainment"] == pytest.approx(0.5)
        # goodput counts attaining tokens only, over the 0.0->4.0 span
        assert rep["goodput_tok_per_s"] == pytest.approx(10 / 4.0)
        empty = slo_report([], ttft_slo_s=1.0)
        assert empty["attainment"] is None
        assert empty["goodput_tok_per_s"] is None and empty["n"] == 0

    def test_slo_report_all_in_flight(self):
        """Mid-run snapshot with nothing finished: used to raise on the
        empty ``max()``; now reports partial goodput up to the latest
        first token."""
        r = Request(rid=0, prompt=np.zeros(2, np.int32))
        r.tokens = [0, 0, 1, 1, 1]            # 3 generated so far
        r.submit_time, r.first_token_time = 0.0, 0.5
        assert r.finish_time is None
        rep = slo_report([r], ttft_slo_s=1.0)
        assert rep["n"] == 1 and rep["completed"] == 0
        assert rep["attainment"] == pytest.approx(1.0)
        assert rep["goodput_tok_per_s"] == pytest.approx(3 / 0.5)


# -------------------------------------------------------- observability

class _FakeClock:
    """Deterministic engine clock: +0.25s per read."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 0.25
        return self.t


class TestServingObservability:
    """The obs subsystem threaded through the engine: Chrome-trace
    export, zero-perturbation tracing, deterministic spans under an
    injected clock, and the metrics() observability blocks."""

    def _run(self, lm_setup, trace, clock=None):
        cfg, api, params = lm_setup
        kw = {"clock": clock} if clock is not None else {}
        eng = ServingEngine(cfg, api, params,
                            config=EngineConfig(batch_slots=2,
                                                cache_len=64,
                                                trace=trace), **kw)
        for r in _requests(cfg, [5, 1, 7], [2, 3, 2]):
            eng.submit(r)
        eng.run_until_drained()
        return eng

    def test_traced_engine_exports_valid_chrome_trace(self, lm_setup,
                                                      tmp_path):
        import json

        from repro.obs import validate_chrome_trace
        eng = self._run(lm_setup, trace=True)
        path = eng.dump_trace(str(tmp_path / "trace.json"))
        with open(path) as f:
            data = json.load(f)
        assert validate_chrome_trace(data) == []
        names = [e["name"] for e in data["traceEvents"]]
        for phase in ("admission", "prefill_dispatch",
                      "block_dispatch", "host_sync", "harvest"):
            assert phase in names, f"missing phase span {phase!r}"
        for stage in ("queued", "prefill", "decode", "first_token",
                      "finished"):
            assert stage in names, f"missing request span {stage!r}"
        assert any(str(n).startswith("compile:") for n in names), \
            "cold engine recorded no compile spans"

    def test_tracing_does_not_perturb(self, lm_setup):
        on = self._run(lm_setup, trace=True)
        off = self._run(lm_setup, trace=False)
        assert on.counters == off.counters            # CountersView ==
        assert dict(on.counters) == dict(off.counters)
        assert {r.rid: r.tokens for r in on.completed.values()} == \
            {r.rid: r.tokens for r in off.completed.values()}
        assert off.tracer.events == []
        with pytest.raises(RuntimeError, match="trace"):
            off.dump_trace("/dev/null")

    def test_trace_deterministic_under_injected_clock(self, lm_setup):
        import json
        traces = []
        for _ in range(2):
            eng = self._run(lm_setup, trace=True, clock=_FakeClock())
            traces.append(json.dumps(eng.tracer.to_chrome(),
                                     sort_keys=True))
        assert traces[0] == traces[1]

    def test_metrics_observability_schema(self, lm_setup):
        eng = self._run(lm_setup, trace=False)
        m = eng.metrics()
        # bit-compat: the counters block is the plain pre-refactor dict
        assert m["counters"] == dict(eng.counters)
        assert isinstance(m["counters"], dict)
        assert m["replica_stats"]["ticks"] == m["counters"]["ticks"]
        assert m["replica_stats"]["ttft_samples"] == 3
        assert m["replica_stats"]["tok_per_s"] > 0
        assert m["queue_highwater"] == 3
        assert m["trace"] == {"enabled": False, "events": 0,
                              "dropped": 0}
        assert "gauges" not in m

    @pytest.mark.parametrize("trace", [False, True])
    def test_phase_annotations_only_when_tracing(self, lm_setup,
                                                 monkeypatch, trace):
        """The engine's tick phases enter jax.profiler annotations named
        engine.<phase> when tracing is on; with it off no annotation is
        made and no rolling gauge is sampled per tick."""
        import contextlib

        import jax
        entered = []

        def record(name):
            entered.append(name)
            return contextlib.nullcontext()

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", record)
        eng = self._run(lm_setup, trace=trace)
        assert eng.registry.snapshot()["rolling"] == {}
        if not trace:
            assert entered == []
            return
        assert set(entered) == {
            "engine.admission", "engine.prefill_dispatch",
            "engine.block_dispatch", "engine.host_sync",
            "engine.harvest"}
        spans = [e["name"] for e in eng.tracer.events if e["ph"] == "X"
                 and not e["name"].startswith("compile:")]
        assert entered == [f"engine.{n}" for n in spans]
