"""The warm-up's prefill shapes: every (chunk width, block-table width)
pair that a tick of the scheduler can dispatch for a mix's prompt
lengths is among them, checked against ticks composed at random from
rows at every chunk boundary, with the Engine's own ladders."""

import json
import math
import types
from pathlib import Path

import numpy as np
import pytest

from bench.harness import warmup
from repro.runtime.engine import Engine, EngineConfig

BENCH = Path(__file__).resolve().parents[1]
CELLS = [("qwen3-1.7b-w7", "decode-long"), ("olmo-1b-codes", "rag-long")]


def _engine(config: str):
    ec = EngineConfig(**json.loads(
        (BENCH / "configs" / f"{config}.json").read_text())["engine"])
    eng = types.SimpleNamespace(
        engine_cfg=ec, _pow2=Engine._pow2,
        cache=types.SimpleNamespace(
            max_blocks_per_seq=ec.max_seq_len // ec.block_size))
    eng._chunk_width = lambda r: Engine._chunk_width(eng, r)
    return eng


@pytest.mark.parametrize("config,mix", CELLS)
def test_every_tick_is_warmed(config, mix):
    eng = _engine(config)
    ec = eng.engine_cfg
    m = json.loads((BENCH / "mixes" / f"{mix}.json").read_text())
    pmin, pmax = m["prompt"]["min"], m["prompt"]["max"]
    warmed = set(warmup.prefill_shapes(eng, (pmin, pmax)))
    rng = np.random.default_rng(0)
    seen = set()
    for _ in range(4000):
        rows = []
        for n in rng.integers(pmin, pmax + 1, rng.integers(1, 5)):
            s0 = ec.prefill_chunk * rng.integers(0, -(-n // ec.prefill_chunk))
            rows.append((int(n), int(s0)))
        # the scheduler's tick: width from the most prompt left, table
        # width from the furthest end of this tick's chunks
        w = eng._chunk_width(max(n - s0 for n, s0 in rows))
        need = max(math.ceil((s0 + min(w, n - s0)) / ec.block_size)
                   for n, s0 in rows)
        shape = (w, min(eng._pow2(need), eng.cache.max_blocks_per_seq))
        assert shape in warmed, (rows, shape)
        seen.add(shape)
    # and the warm-up holds no shape that no tick reaches
    assert seen == warmed
