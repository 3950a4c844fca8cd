"""The work counts of the configurations' reference modules and
bench/flops.py's least times, against counts worked out by hand."""
import pytest

from bench import flops, harness
from bench.tests.tiny import config_file as _config


def _work(c):
    return harness.reference_module(c)


# per layer: d*q + 2*d*kv + q*d + 3*d*f multiply-adds, two ops each
@pytest.mark.parametrize("name,proj,head,attn_one", [
    # qwen2: 896*896 + 2*896*128 + 896*896 + 3*896*4864 = 14,909,440
    # a layer; x 2 x 24 layers. Head 2*896*151936. One query, one key:
    # 2 (q.k, p.v) * 2 * 24 layers * 896
    ("qwen2-0.5b-int8", 715_653_120, 272_269_312, 86_016),
    # glm4, 10 layers: 4096*4096 + 2*4096*256 + 4096*4096 + 3*4096*13696
    # = 203,948,032 a layer
    ("glm4-9b-int4", 4_078_960_640, 1_241_513_984, 163_840),
])
def test_per_token_counts(name, proj, head, attn_one):
    c = _config(name)
    work = _work(c)
    assert work.projection_ops_per_token(c) == proj
    assert work.head_ops_per_token(c) == head
    assert work.attention_ops(c, 0, 1) == attn_one


def test_attention_is_causal_over_positions():
    c = _config("qwen2-0.5b-int8")
    work = _work(c)
    one = work.attention_ops(c, 0, 1)
    # queries at 10, 11, 12 see 11 + 12 + 13 = 36 keys
    assert work.attention_ops(c, 10, 3) == 36 * one
    assert work.attention_ops(c, 5, 0) == 0
    # a prompt written in two chunks costs what it costs in one
    assert (work.prefill_ops(c, 0, 32) + work.prefill_ops(c, 32, 64)
            == work.prefill_ops(c, 0, 64))
    # decode: 4 steps from position 100 (keys 101..104), each with a head
    per = 715_653_120 + 272_269_312
    assert work.decode_ops(c, 100, 4) == 4 * per + (101 + 102 + 103 + 104) * one


def test_mm_least_seconds_bound():
    # M=16, K=896, N=4864 int8: 139,460,608 ops; bytes 896*4864 weight +
    # 4864*4 scales + 4 + 16*896*4 input + 16*4864*4 output = 4,746,244
    t, bound = flops.mm_least_seconds(16, 896, 4864, 8, 393e12, 819e9)
    assert bound == "memory"
    assert t == pytest.approx(4_746_244 / 819e9)
    # int4 halves the stored weight
    t4, _ = flops.mm_least_seconds(16, 896, 4864, 4, 393e12, 819e9)
    assert t4 == pytest.approx((4_746_244 - 896 * 4864 // 2) / 819e9)
    # a prefill wave of 16 x 256 rows through glm4's w_down is bound by
    # the operations (1.17 ms against 347 MB in 0.42 ms)
    t, bound = flops.mm_least_seconds(4096, 13696, 4096, 4, 393e12, 819e9)
    assert bound == "compute"
    assert t == pytest.approx(2 * 4096 * 13696 * 4096 / 393e12)


def test_projection_shapes_cover_every_projection():
    c = _config("glm4-9b-int4")
    shapes = [(4096, 4096), (4096, 256), (4096, 256), (4096, 4096),
              (4096, 13696), (4096, 13696), (13696, 4096)]
    assert _work(c).projection_shapes(c) == shapes
    # one step makes each of them once in each of the stage's 10 layers
    assert _work(c).projection_calls(c) == [(k, n, 10) for k, n in shapes]


def test_kernel_roofline_counts_active_rows_not_slots():
    """A decode step's M is the rows active at that step and a prefill
    dispatch's M its valid prompt tokens, whatever the slots and chunk."""
    from types import SimpleNamespace

    from bench.harness import Tick
    from bench.metrics import fused_dequant_mm_roofline as roof
    c = _config("qwen2-0.5b-int8")
    peaks = {"int8_ops": 393e12, "hbm_bytes_per_s": 819e9}
    # one tick: a prefill dispatch of 300 valid tokens, then a block of
    # 3 steps in which two requests take 3 and 1 tokens (rows 2, 1, 1)
    tick = Tick(start=0.0, stop=1.0, decode_steps=3, harvested=4,
                prefill_tokens=300, prefill_calls=1, ops=0, traced=True,
                decode_rows=(2, 1, 1))
    ctx = SimpleNamespace(config=c, peaks=peaks, weight_bits=8,
                          traced_ticks=[tick], slots=16)
    least, events = roof.least_seconds(ctx)
    want = 24 * sum(
        flops.mm_least_seconds(m, k, n, 8, 393e12, 819e9)[0]
        for m in (300, 2, 1, 1) for k, n in _work(c).projection_shapes(c))
    assert least == pytest.approx(want)
    assert events == 7 * 24 * 4
