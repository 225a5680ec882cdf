"""The scheduler's readers of the Engine's spans and counters:
``sched.host_ms`` (host time of a tick: ``engine.step`` less the
``engine.wait.*`` spans in it) and ``sched.prefill_fill`` (prompt
tokens over prefill rows x width dispatched), on hand-made traces and
counters; and, on a trace recorded on a TPU v5e with the Engine's spans
(``data/trace_engine_head.json.gz``: the first 2 s of the traced window
of a 51 s run of the qwen cell, written by ``run_cell``'s ``keep_trace``
as ``bench/tools/record_trace.py`` calls it), the longest idle gaps
named by the Engine phase around them."""

import gzip
import json
import types
from pathlib import Path

import pytest

from bench.harness.spec import load_module

BENCH = Path(__file__).resolve().parents[1]
R = load_module(BENCH / "layer_metrics" / "_reduce.py", "_reduce")
HOST_MS = load_module(BENCH / "layer_metrics" / "sched.host_ms.py",
                      "bench_layer_metrics.sched.host_ms")
FILL = load_module(BENCH / "layer_metrics" / "sched.prefill_fill.py",
                   "bench_layer_metrics.sched.prefill_fill")
RECORDED = BENCH / "tests" / "data" / "trace_head.json.gz"
RECORDED_ENGINE = BENCH / "tests" / "data" / "trace_engine_head.json.gz"

MS = 1_000_000


def _run(trace=None, counters=None):
    return types.SimpleNamespace(cell=types.SimpleNamespace(root=BENCH.parent),
                                 trace=trace, counters=counters or {})


def _load(path):
    with gzip.open(path) as f:
        return R.Trace.from_json(json.load(f))


def test_host_ms_hand_made():
    host = [[0, 20 * MS, "bench.window"],
            # 10 ms tick, waits 2-6 and 5-8 ms: 6 ms waited, 4 ms host
            [0, 10 * MS, "engine.step"],
            [2 * MS, 6 * MS, "engine.wait.prefill"],
            [5 * MS, 8 * MS, "engine.wait.decode"],
            [1 * MS, 2 * MS, "engine.admit"],
            # 4 ms tick, one 2 ms wait: 2 ms host
            [10 * MS, 14 * MS, "engine.step"],
            [11 * MS, 13 * MS, "engine.wait.decode"],
            # a tick the window cuts: left out
            [15 * MS, 30 * MS, "engine.step"],
            [16 * MS, 17 * MS, "engine.wait.decode"]]
    t = R.Trace(ops={}, modules={}, host=host)
    assert HOST_MS.read(_run(t)) == pytest.approx(3.0)


def test_host_ms_none_without_engine_spans():
    assert HOST_MS.read(_run(_load(RECORDED))) is None


def test_prefill_fill():
    run = _run(counters={"engine.prefill.tokens": 300,
                         "engine.prefill.dispatched_tokens": 1200,
                         "engine.decode.steps": 9})
    assert FILL.read(run) == pytest.approx(25.0)
    assert FILL.read(_run(counters={"engine.decode.steps": 9})) is None
    assert FILL.read(_run(counters={"engine.prefill.tokens": 0,
                                    "engine.prefill.dispatched_tokens": 0})
                     ) is None


def test_recorded_gaps_name_engine_phases():
    t = _load(RECORDED_ENGINE)
    gaps = R.idle_gaps(t)
    assert len(gaps) == 10
    assert sum(name.startswith("engine.") for name, _ in gaps) >= 9, gaps
    assert HOST_MS.read(_run(t)) > 0
    # the benchmark's own spans and programs read as before
    assert {k for k, _ in R.programs(t)} <= {"prefill", "decode"}
    assert R.programs(t)
