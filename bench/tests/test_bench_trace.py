"""The trace reduction: busy time as a union of op intervals, self time
per op, time per kernel family, prefill and decode programs told apart
by the dispatch span that launched them, and idle gaps named by the
host span around them.
Checked on hand-made events and on a small trace recorded on a TPU v5e
(``data/trace_head.json.gz``: the first 2 s of a traced window of the
OLMo cell, written by ``bench/tools/record_trace.py``)."""

import gzip
import json
import re
from pathlib import Path

import pytest

from bench.harness.spec import load_module
from bench.harness.stats import nearest_rank

BENCH = Path(__file__).resolve().parents[1]
R = load_module(BENCH / "layer_metrics" / "_reduce.py", "_reduce")
RECORDED = Path(__file__).parent / "data" / "trace_head.json.gz"

MS = 1_000_000


def _hand():
    dev = "/device:TPU:0"
    ops = [[0 * MS, 6 * MS, "while.2"],                        # layer loop
           [0 * MS, 1 * MS, "fusion.1"],
           [1 * MS, 3 * MS, "lut_dequant_matmul_kernel.7"],
           [5 * MS, 6 * MS, "flash_prefill_paged_kernel.6"],
           [8 * MS, 9 * MS, "flash_prefill_paged_kernel.6"],
           [9 * MS, 10 * MS, "lut_dequant_matmul_dual_kernel.3"],
           [10 * MS, 10.5 * MS, "copy.4"]]                     # no attention
    modules = [[0 * MS, 6 * MS, "jit_fn(1)"],
               [8 * MS, 10 * MS, "jit_fn(2)"],
               [10 * MS, 10.5 * MS, "jit_copy(3)"]]
    host = [[0, 12 * MS, "bench.window"],
            [0, 0.5 * MS, "bench.dispatch.prefill"],
            [10 * MS, 12 * MS, "bench.results"],
            [6 * MS, 8 * MS, "bench.engine_step"],
            [6 * MS, 7 * MS, "bench.dispatch.decode"]]
    return R.Trace(ops={dev: ops}, modules={dev: modules}, host=host)


def test_hand_made():
    t = _hand()
    assert t.window_s == pytest.approx(0.012)
    assert R.busy_s(t) == pytest.approx(0.0085)   # 0-6, 8-10.5 ms
    assert R.family_s(t, "wmatmul") == pytest.approx(0.003)
    assert R.family_s(t, "attn") == pytest.approx(0.002)
    # a reader's own family: a regex string or a compiled pattern
    assert R.family_s(t, r"matmul_dual") == pytest.approx(0.001)
    assert R.family_s(t, re.compile(r"^flash_prefill")) == \
        pytest.approx(0.002)
    assert R.family_s(t, "no_such_kernel") == 0.0
    assert sorted(R.programs(t)) == [("decode", pytest.approx(0.002)),
                                     ("prefill", pytest.approx(0.006))]
    # gaps 6-8 ms (inside the decode dispatch, the innermost span) and
    # 10.5-12 ms
    assert R.idle_gaps(t) == [["bench.dispatch.decode", pytest.approx(0.002)],
                              ["bench.results", pytest.approx(0.0015)]]
    # self time: the loop's 6 ms less its body's 1 + 2 + 1 ms
    top = dict(R.top_ops(t))
    assert top["while"] == pytest.approx(0.002)
    assert top["flash_prefill_paged_kernel"] == pytest.approx(0.002)
    assert R.top_ops(t, 1) == [["flash_prefill_paged_kernel",
                                pytest.approx(0.002)]]
    assert sum(top.values()) == pytest.approx(R.busy_s(t))


def test_programs_by_nearest_dispatch():
    # the device clock runs 0.3 ms ahead of the host's: each program
    # starts just before the span of the dispatch that launched it
    t = _hand()
    t.host = [h for h in t.host if not h[2].startswith("bench.dispatch.")]
    t.host += [[0.2 * MS, 0.5 * MS, "bench.dispatch.prefill"],
               [8.2 * MS, 8.5 * MS, "bench.dispatch.decode"]]
    assert sorted(R.programs(t)) == [("decode", pytest.approx(0.002)),
                                     ("prefill", pytest.approx(0.006))]


def test_op_names():
    hlo = ("%lut_dequant_matmul_kernel.31 = f32[16,151936]{1,0} "
           "custom-call(bf16[16,2048]{1,0} %fusion.42)")
    assert R.op_name(hlo) == "lut_dequant_matmul_kernel.31"
    assert R.op_kind("lut_dequant_matmul_kernel.31") == \
        "lut_dequant_matmul_kernel"
    assert R.op_kind("pad.24.clone") == "pad"


def test_union_counts_overlap_once():
    assert R.union_ns([[0, 10], [5, 15], [20, 30], [21, 22]]) == 25


def test_recorded_trace():
    with gzip.open(RECORDED) as f:
        t = R.Trace.from_json(json.load(f))
    busy, win = R.busy_s(t), t.window_s
    assert 0.9 * win < busy <= win
    # self times add up to the busy union: the ops nest, none overlap
    assert sum(v for _, v in R.top_ops(t, 10**6)) == pytest.approx(busy)
    wm, at = R.family_s(t, "wmatmul"), R.family_s(t, "attn")
    assert 0 < wm < busy and 0 < at < busy and wm + at < busy
    progs = R.programs(t)
    assert {k for k, _ in progs} == {"prefill", "decode"}
    decode = [s for k, s in progs if k == "decode"]
    prefill = [s for k, s in progs if k == "prefill"]
    assert max(decode) < 1.1 * min(decode) and min(prefill) > max(decode)
    assert all(name.startswith("bench.") for name, _ in R.idle_gaps(t))


def test_nearest_rank():
    assert nearest_rank([3, 1, 2, 4], 50) == 2
    assert nearest_rank(list(range(1, 101)), 95) == 95
    assert nearest_rank([1.0] * 19 + [float("inf")], 95) == 1.0
    assert nearest_rank([1.0] * 19 + [float("inf")], 96) == float("inf")
    assert nearest_rank([], 50) is None
