"""The configuration's reference module brings its architecture to the
harness: `model_config` lays the module's `program_fields` over the
program's entry and refuses any field that departs from it unless the
file's `reduced` names its key; `reference` picks the module; and the
int4 weights the reference computes with are the program's own int4
codes."""
import dataclasses
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.reference import lm
from bench.tests.tiny import config_file as _config
from bench.tests.tiny import tiny_config


@pytest.mark.parametrize("name", ["qwen2-0.5b-int8", "qwen2-0.5b-int8-long"])
def test_qwen2_files_give_the_model_config_they_gave(name):
    """The fields the harness set by hand before the reference module
    stated them, and the dense mechanisms it held the entry to."""
    from repro.configs import get_config
    c = _config(name)
    want = dataclasses.replace(
        get_config(c["arch"]),
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        qkv_bias=c["attention_bias"], rope_theta=c["rope_theta"],
        rotary_pct=c["partial_rotary_factor"],
        tied_embeddings=c["tie_word_embeddings"],
        precision_policy=c["precision_policy"],
        param_dtype=c["param_dtype"])
    assert harness.model_config(c) == want
    assert (want.norm, want.attn_pattern, want.moe, want.qk_norm,
            want.post_norms, want.attn_scale) == ("rms", "full", None,
                                                  False, False, None)


def test_glm4_file_gives_the_published_widths_on_ten_layers():
    c = _config("glm4-9b-int4")
    assert c["reduced"] == ["num_hidden_layers"]
    cfg = harness.model_config(c)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.vocab) == (10, 4096, 32, 2, 128,
                                                   13696, 151552)
    assert cfg.rotary_pct == 0.5 and cfg.qkv_bias
    assert not cfg.tied_embeddings
    assert cfg.precision_policy == "int4_serving"


@pytest.mark.parametrize("name,key,value", [
    ("glm4-9b-int4", "num_hidden_layers", 10),    # as the file has it
    ("qwen2-0.5b-int8", "num_hidden_layers", 12),
    ("qwen2-0.5b-int8", "vocab_size", 151552),
    ("qwen2-0.5b-int8", "intermediate_size", 2432),
    ("qwen2-0.5b-int8", "partial_rotary_factor", 0.5),
    ("qwen2-0.5b-int8", "tie_word_embeddings", False),
])
def test_a_size_or_mechanism_the_file_does_not_list_is_refused(name, key,
                                                              value):
    """Every departure from the program's entry, a number too, is the
    file's only where `reduced` names its key."""
    c = dict(_config(name), **{key: value})
    listed = harness.model_config(dict(c, reduced=[key]))
    field = {v: k for k, v in lm.FILE_KEYS.items()}[key]
    assert getattr(listed, field) == value
    with pytest.raises(harness.BenchError, match=f"{field}: .*{key} not in"):
        harness.model_config(dict(c, reduced=[]))


@pytest.mark.parametrize("name,bits", [("glm4-9b-int4", 8),
                                       ("glm4-9b-int4", 3),
                                       ("qwen2-0.5b-int8", 4)])
def test_stored_bits_other_than_the_tiers_are_refused(name, bits):
    c = dict(_config(name), stored_weight_bits=bits)
    with pytest.raises(harness.BenchError, match="stored_weight_bits"):
        harness.model_config(c)


def test_a_dense_file_on_an_expert_entry_is_refused():
    c = dict(_config("qwen2-0.5b-int8"), arch="qwen3-moe-30b-a3b")
    with pytest.raises(harness.BenchError, match="qk_norm.*moe|moe.*qk_norm"):
        harness.model_config(c)


# ------------------------------------------- a module of another block

TINY = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
            d_ff=128, vocab=512)
# what a module for each entry states: the entry's mechanisms, at tiny
# sizes and with fewer experts held
STUBS = {
    "mixtral-8x7b": dict(
        family="lm", act="silu", norm="rms", qk_norm=False,
        attn_pattern="swa", window=4096, tied_embeddings=False,
        moe={"n_experts": 4, "top_k": 2, "d_expert": 32,
             "capacity_factor": 1.25, "dispatch": "einsum"}),
    "qwen3-moe-30b-a3b": dict(
        family="lm", act="silu", norm="rms", qk_norm=True,
        attn_pattern="full", tied_embeddings=False,
        moe={"n_experts": 16, "top_k": 8, "d_expert": 32}),
}
# the stubs' cuts, which their files list (the stubs map no file keys)
CUTS = [*TINY, "moe.n_experts", "moe.d_expert"]


def _stub(monkeypatch, fields):
    mod = types.ModuleType("bench.reference.stub")
    mod.program_fields = lambda c: dict(
        fields, **TINY, precision_policy="int8_serving",
        param_dtype="bfloat16")
    monkeypatch.setitem(sys.modules, "bench.reference.stub", mod)
    return mod


def _stub_file(arch, reduced=tuple(CUTS)):
    return dict(name="tiny-stub", arch=arch, family="lm",
                reference="stub", reduced=list(reduced))


def _changed(fields, path, value):
    out = dict(fields, moe=dict(fields["moe"]))
    head, _, leaf = path.partition(".")
    if leaf:
        out[head][leaf] = value
    else:
        out[head] = value
    return out


def test_reference_picks_the_module(monkeypatch):
    mod = _stub(monkeypatch, STUBS["mixtral-8x7b"])
    assert harness.reference_module(_stub_file("mixtral-8x7b")) is mod
    assert harness.reference_module(_config("qwen2-0.5b-int8")) is lm


@pytest.mark.parametrize("arch", sorted(STUBS))
def test_a_module_of_experts_windows_and_qk_norm_is_taken(arch, monkeypatch):
    from repro.configs import MoESpec, get_config
    _stub(monkeypatch, STUBS[arch])
    cfg = harness.model_config(_stub_file(arch))
    entry = get_config(arch)
    assert {k: getattr(cfg, k) for k in TINY} == TINY
    held = STUBS[arch]["moe"]["n_experts"]
    assert cfg.moe == dataclasses.replace(entry.moe, n_experts=held,
                                          d_expert=32)
    assert isinstance(cfg.moe, MoESpec)
    assert (cfg.attn_pattern, cfg.qk_norm, cfg.window) == (
        entry.attn_pattern, entry.qk_norm, entry.window)
    assert cfg.precision_policy == "int8_serving"


@pytest.mark.parametrize("arch,cut", [
    (arch, cut) for arch in sorted(STUBS)
    for cut in ("n_layers", "d_model", "d_ff", "moe.n_experts")])
def test_a_cut_the_file_does_not_list_is_refused(arch, cut, monkeypatch):
    _stub(monkeypatch, STUBS[arch])
    unlisted = [k for k in CUTS if k != cut]
    with pytest.raises(harness.BenchError, match=f"{cut}: "):
        harness.model_config(_stub_file(arch, reduced=unlisted))


@pytest.mark.parametrize("arch,path,value", [
    ("mixtral-8x7b", "qk_norm", True),
    ("mixtral-8x7b", "attn_pattern", "full"),
    ("mixtral-8x7b", "moe", None),
    ("mixtral-8x7b", "window", None),
    ("mixtral-8x7b", "window", 16),
    ("mixtral-8x7b", "moe.top_k", 1),
    ("mixtral-8x7b", "moe.capacity_factor", 2.0),
    ("qwen3-moe-30b-a3b", "qk_norm", False),
    ("qwen3-moe-30b-a3b", "moe.top_k", 2),
    ("qwen3-moe-30b-a3b", "moe.dispatch", "gather"),
])
def test_a_module_that_disagrees_with_its_entry_is_refused(arch, path, value,
                                                           monkeypatch):
    _stub(monkeypatch, _changed(STUBS[arch], path, value))
    with pytest.raises(harness.BenchError, match=f"{path}: "):
        harness.model_config(_stub_file(arch))
    # the same change, named in the file's `reduced`, is the file's
    cfg = harness.model_config(_stub_file(arch, reduced=[*CUTS, path]))
    head, _, leaf = path.partition(".")
    got = getattr(cfg, head)
    assert (getattr(got, leaf) if leaf else got) == value


# ------------------------------------------- int4 codes, both sides

@pytest.mark.parametrize("seed", [3, 2**33 + 7])
def test_reference_int4_weights_are_the_programs_codes(seed):
    """At a glm4-shaped tiny size, `lm.fake_quant(w, 4)` equals what the
    program stores under `int4_serving` (codes times scales), leaf for
    leaf; the embedding, head and norms stay as drawn on both sides."""
    from repro.core.policy import get_policy
    from repro.kernels import ops as kops
    from repro.models import registry
    from repro.quant.prepare import PreparedWeight
    c = tiny_config("glm4-9b-int4")
    api = registry.build(harness.model_config(c))
    params = lm.make_params(c, seed, jnp.bfloat16)
    prepared = api.prepare(params, get_policy(c["precision_policy"]))
    flat = dict(jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: isinstance(x, PreparedWeight))[0])
    done = dict(jax.tree_util.tree_flatten_with_path(
        prepared, is_leaf=lambda x: isinstance(x, PreparedWeight))[0])
    projections = 0
    for path, raw in flat.items():
        name = jax.tree_util.keystr(path)
        got = done[path]
        if not isinstance(got, PreparedWeight):
            assert "blocks" not in name or name.endswith("['b']") \
                or "'ln" in name, name
            np.testing.assert_array_equal(np.asarray(got), np.asarray(raw))
            continue
        assert got.kind == "int4_packed", name
        codes = np.asarray(kops.unpack_int4(got.data))
        want = lm.fake_quant(raw.astype(jnp.float32), 4)
        scale = np.asarray(got.scale)
        assert np.abs(codes).max() <= 8
        np.testing.assert_array_equal(codes * scale, np.asarray(want))
        np.testing.assert_array_equal(
            codes, np.rint(np.asarray(want) / scale).astype(codes.dtype))
        projections += 1
    assert projections == 7
