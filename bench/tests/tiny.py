"""A tiny cell for CPU tests: the `lm` family at d_model 64, two layers
and a 512-token vocabulary, served through the same harness."""
import copy
import json
import pathlib

from bench import harness

BENCH = pathlib.Path(__file__).resolve().parents[1]


def tiny_cell(tier: str = "int8_serving", arrivals=None, limit=None,
              **engine) -> harness.Cell:
    base = json.loads((BENCH / "configs" / "qwen2-0.5b-int8.json").read_text())
    c = copy.deepcopy(base)
    c.update(name="tiny", hidden_size=64, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             intermediate_size=128, vocab_size=512, precision_policy=tier,
             control_weight_bits=4 if tier == "int8_serving" else 3)
    c["engine"] = {**base["engine"], "batch_slots": 4, "cache_len": 96,
                   "prefill_chunk": 16, **engine}
    traffic = {
        "generator": "requests",
        "arrivals": arrivals or {"kind": "poisson", "rate_per_s": 8.0},
        "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                   "min": 8, "max": 48},
        "output": {"dist": "uniform", "min": 4, "max": 24},
    }
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    return harness.Cell(
        name="tiny", config=c, traffic=traffic, chips=1,
        limits={"checks": {"max_gap": {"limit": limit}}},
        end_to_end=[m["name"] for m in spec["end_to_end"]],
        per_layer=[m["name"] for m in spec["per_layer"]],
        units={m["name"]: m["unit"]
               for m in spec["end_to_end"] + spec["per_layer"]})
