"""The request generator: reproducible from the seed, within its clips,
the same work for every seed."""
import json
import pathlib

import numpy as np
import pytest

from bench.generators import requests

BENCH = pathlib.Path(__file__).resolve().parents[1]
TRAFFIC = BENCH / "traffic"
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json"))
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
BIG_SEED = 2**31 + 12345


def _mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def _cells():
    """(mix, configuration file) of every cell in BENCHMARK.json."""
    files = {c["name"]: c["file"] for c in SPEC["configs"]}
    return [(w["traffic"], files[w["config"]]) for w in SPEC["workloads"]]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_plan(mix):
    p = _mix(mix)
    a = requests.build(p, BIG_SEED, 30.0, 151936)
    b = requests.build(p, BIG_SEED, 30.0, 151936)
    assert a.max_new == b.max_new and a.clients == b.clients
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))
    if a.due is not None:
        assert np.array_equal(a.due, b.due)
    c = requests.build(p, BIG_SEED + 1, 30.0, 151936)
    assert any(not np.array_equal(x, y) for x, y in zip(a.prompts, c.prompts))


@pytest.mark.parametrize("mix,config", _cells())
def test_clips_and_vocabulary(mix, config):
    p = _mix(mix)
    plan = requests.build(p, 7, 30.0, 1000)
    plens = np.array([len(x) for x in plan.prompts])
    assert plens.min() >= p["prompt"]["min"]
    assert plens.max() <= p["prompt"]["max"]
    assert min(plan.max_new) >= p["output"]["min"]
    assert max(plan.max_new) <= p["output"]["max"]
    assert all(x.min() >= 0 and x.max() < 1000 for x in plan.prompts)
    cap = json.loads((BENCH.parent / config).read_text())["engine"][
        "cache_len"]
    # every request fits its slot's cache: no ring truncation
    assert plens.max() - 1 + max(plan.max_new) <= cap


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_work(mix):
    p = _mix(mix)
    a = requests.build(p, 1, 30.0, 100)
    b = requests.build(p, -5, 30.0, 100)
    assert sorted(len(x) for x in a.prompts) == sorted(len(x) for x in b.prompts)
    assert sorted(a.max_new) == sorted(b.max_new)
    if a.due is not None:
        # the same gaps in another order, every request inside the window
        assert len(a.due) == len(b.due)
        assert max(a.due[-1], b.due[-1]) < 30.0


def test_open_loop_offers_its_rate():
    p = _mix("chat")
    plan = requests.build(p, 3, 30.0, 100)
    rate = p["arrivals"]["rate_per_s"]
    assert len(plan.prompts) == round(rate * 30.0)
    assert plan.due[0] == 0.0 and np.all(np.diff(plan.due) > 0)
    # the gaps add up to the window, less the one before the first send
    assert 0.75 * 30.0 < plan.due[-1] < 30.0


def test_lognormal_median():
    d = {"dist": "lognormal", "median": 512, "sigma": 1.0, "min": 1,
         "max": 10**9}
    assert np.median(requests._quantiles(10001, d)) == 512


@pytest.mark.parametrize("mix", MIXES)
def test_order_is_drawn_from_the_seed(mix):
    """Each list is a uniform permutation: over many seeds the longest
    prompts, the longest answers and (open loop) the shortest gaps land
    in every quarter of the plan about equally often, so bursts and runs
    of long prompts come as often as in a Poisson stream."""
    p = _mix(mix)
    p = {**p, "arrivals": {**p["arrivals"], "pool": 40}}
    seconds = 40 / p["arrivals"].get("rate_per_s", 1.0)
    hits = np.zeros((3, 4))
    # pairs of neighbours both in the top decile, seen and expected of a
    # uniform permutation (t(t-1)/n for t of n): a balanced order
    # would keep them apart
    adjacent = np.zeros((3, 2))
    for seed in range(300):
        plan = requests.build(p, BIG_SEED + seed, seconds, 10)
        lists = [[len(x) for x in plan.prompts], plan.max_new]
        if plan.due is not None:
            lists.append(-np.diff(plan.due))
        for row, xs in enumerate(lists):
            xs = np.asarray(xs, float)
            top = np.flatnonzero(xs >= np.quantile(xs, 0.9))
            np.add.at(hits[row], top * 4 // len(xs), 1)
            adjacent[row] += (np.sum(np.diff(top) == 1),
                              len(top) * (len(top) - 1) / len(xs))
    used = hits[hits.sum(axis=1) > 0]
    share = used / used.sum(axis=1, keepdims=True)
    assert np.all((share > 0.2) & (share < 0.3))
    seen, expected = adjacent[adjacent[:, 1] > 0].T
    assert np.all((seen > 0.75 * expected) & (seen < 1.25 * expected))
