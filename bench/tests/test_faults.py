"""A run with the timed path broken underneath must come out not
correct: the harness drives a tiny cell on the CPU (the chip check
skipped) with one fault planted in the program, and the comparison with
the reference catches it, for the int8 and the int4 configuration. The
limit is the tiny cell's own: sound tiny runs read a widest gap under
0.35, these faults above 1."""
import time

import jax
import pytest

from bench import harness
from bench.tests.test_run import PEAKS
from bench.tests.tiny import tiny_cell

LIMIT = 0.5


CONFIGS = ["qwen2-0.5b-int8", "glm4-9b-int4"]


def _run(config, seed=5):
    # a closed loop keeps every slot busy, so a fault in some slots
    # reaches the sampled requests
    cell = tiny_cell(config, limit=LIMIT,
                     arrivals={"kind": "closed", "clients": 4, "pool": 64})
    out = harness.run_cell(cell, seed, 3.0, False,
                           time.monotonic(), jax.devices()[0],
                           harness.CompileWatch(), PEAKS)
    return out["correct"], out["checks"]["max_gap"]["value"]


def _altered_token(monkeypatch):
    """The decode block hands back another token than it chose."""
    from repro.models import registry
    orig = registry.make_block_decode

    def make(api, *a, **k):
        fn = orig(api, *a, **k)

        def run(params, carry, state):
            tokens, out, st = fn(params, carry, state)
            return (tokens + 1) % api.cfg.vocab, out, st
        return run
    monkeypatch.setattr(registry, "make_block_decode", make)


def _state_unchanged(monkeypatch):
    """The decode block returns the cache it was given: the decoded
    tokens' keys and values are never written."""
    from repro.models import registry
    orig = registry.make_block_decode

    def make(api, *a, **k):
        fn = orig(api, *a, **k)

        def run(params, carry, state):
            tokens, out, _ = fn(params, carry, state)
            return tokens, out, state
        return run
    monkeypatch.setattr(registry, "make_block_decode", make)


def _half_batch(monkeypatch):
    """The prefill wave leaves out the second half of the slots."""
    from repro.models import lm
    orig = lm.prefill_chunk

    def chunk(params, cfg, tokens, offsets, lengths, caches):
        half = tokens.shape[0] // 2
        return orig(params, cfg, tokens, offsets,
                    lengths.at[half:].set(0), caches)
    monkeypatch.setattr(lm, "prefill_chunk", chunk)


@pytest.mark.parametrize("config", CONFIGS)
def test_sound_run_is_correct(config):
    ok, gap = _run(config)
    assert ok and gap < LIMIT


@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged,
                                   _half_batch])
def test_fault_is_not_correct(fault, config, monkeypatch):
    fault(monkeypatch)
    ok, gap = _run(config)
    assert not ok, f"{fault.__name__}: widest gap {gap}"
