"""A tiny cell for CPU tests: a configuration file's `lm` model at
d_model 64, two layers and a 512-token vocabulary, with its tier, its
stored weight bits and its control, served through the same harness."""
import copy
import json
import pathlib

from bench import harness

BENCH = pathlib.Path(__file__).resolve().parents[1]
# configuration files of the tests alone, served by no cell
DATA = pathlib.Path(__file__).resolve().parent / "data"
# the sizes of every tiny cell, which its `reduced` names
TINY = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, intermediate_size=128,
            vocab_size=512)


def config_file(name: str) -> dict:
    """A cell's configuration file (`bench/configs`) or a test's."""
    for folder in (BENCH / "configs", DATA):
        path = folder / f"{name}.json"
        if path.exists():
            return json.loads(path.read_text())
    raise FileNotFoundError(name)


def tiny_config(name: str = "qwen2-0.5b-int8") -> dict:
    c = copy.deepcopy(config_file(name))
    c.update(name="tiny", reduced=sorted({*c["reduced"], *TINY}), **TINY)
    return c


def tiny_cell(config: str = "qwen2-0.5b-int8", arrivals=None, limit=None,
              **engine) -> harness.Cell:
    c = tiny_config(config)
    c["engine"] = {**c["engine"], "batch_slots": 4, "cache_len": 96,
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
