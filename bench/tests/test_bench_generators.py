"""The traffic generator: the seed fixes the requests; every seed asks
for the same lengths; the distributions keep their medians and clips."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench.harness.spec import load_module

BENCH = Path(__file__).resolve().parents[1]
GEN = load_module(BENCH / "generators" / "closed_loop.py", "closed_loop")
MIXES = {p.stem: json.loads(p.read_text())
         for p in (BENCH / "mixes").glob("*.json")}


def _draw(mix, seed, n=64, vocab=50304):
    t = GEN.make(mix, seed, vocab)
    return [t.next() for _ in range(n)]


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_same_seed_same_requests(mix):
    a, b = _draw(MIXES[mix], 2**31 + 7), _draw(MIXES[mix], 2**31 + 7)
    assert [(r.max_new_tokens, r.prompt.tolist()) for r in a] == \
        [(r.max_new_tokens, r.prompt.tolist()) for r in b]
    c = _draw(MIXES[mix], 5)
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_every_seed_same_lengths(mix):
    pool = MIXES[mix]["pool"]
    a, b = _draw(MIXES[mix], 1, 2 * pool), _draw(MIXES[mix], 2**40, 2 * pool)
    lengths = [(len(r.prompt), r.max_new_tokens) for r in a]
    assert lengths == [(len(r.prompt), r.max_new_tokens) for r in b]
    assert lengths[:pool] == lengths[pool:]            # the order repeats
    assert sorted(p for p, _ in lengths[:pool]) == sorted(
        GEN.lognormal_quantiles(MIXES[mix]["prompt"], pool))


def test_spread_order():
    order = GEN.spread_order(24)
    assert sorted(order) == list(range(24))
    # no two neighbours in the send order are near in length
    assert min(abs(int(x) - int(y)) for x, y in zip(order, order[1:])) >= 5


@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("side", ["prompt", "output"])
def test_medians_and_clips(mix, side):
    spec = MIXES[mix][side]
    vals = GEN.lognormal_quantiles(spec, MIXES[mix]["pool"])
    assert vals.min() >= spec["min"] and vals.max() <= spec["max"]
    assert abs(np.median(vals) - spec["median"]) <= 1


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_prompts_unique_by_first_token(mix):
    reqs = _draw(MIXES[mix], 3, 400)
    assert len({int(r.prompt[0]) for r in reqs}) == len(reqs)


def test_shipped_mixes_fit_their_configs():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
        eng = json.loads((BENCH.parent / cfg["file"]).read_text())["engine"]
        mix = MIXES[w["traffic"]]
        assert mix["prompt"]["max"] + mix["output"]["max"] <= \
            eng["max_seq_len"], w["name"]
