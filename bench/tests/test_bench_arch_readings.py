"""The dense decoder, moved into ``bench/architectures/decoder.py`` and
reached through ``cell.arch`` and ``run.arch``, reads exactly what the
harness read when it was imported by name: the params tree drawn from
the seed, the reference's gaps on fixed tokens, every work count, and
every per-layer reader on the recorded trace
(``data/trace_head.json.gz``).  The numbers are those the code before
the move gave, on the same inputs."""

import gzip
import hashlib
import json
import types
from pathlib import Path

import jax
import numpy as np
import pytest

from bench.harness import reference, weights
from bench.harness.adapter import Dispatch
from bench.harness.cell import Run
from bench.harness.peaks import peaks_for
from bench.harness.spec import ROOT, load_cell, load_module, reader
from bench.tests.conftest import TINY_CONFIGS
from repro.configs import get_config
from repro.models import api as mapi

BENCH = Path(__file__).resolve().parents[1]
decoder = load_module(BENCH / "architectures" / "decoder.py",
                      "bench_architectures.decoder")
SEED = 2**31 + 11

PARAMS_SHA256 = {
    "olmo-tiny":
        "bff2fbfd56efa86881221177e61eea68e74887e37f7f7b80f2fa4b5b1d36a0d8",
    "qwen-tiny":
        "19581c2848bad019713363449548ded3ace32f21943de70ccf43ec4278df5149"}

PROMPT = [5, 17, 300, 42, 9, 1, 260, 77]
SERVED = [12, 400, 3, 3, 150, 511]
GAPS = {
    "olmo-tiny": {
        "control": [0.0, 0.0, 0.0, 0.0, 0.09207803010940552, 0.0],
        "served": [1.5015454292297363, 0.9366327524185181,
                   1.5204943418502808, 0.7143726348876953,
                   0.09207803010940552, 1.306081771850586]},
    "qwen-tiny": {
        "control": [0.0] * 6,
        "served": [0.9912832379341125, 1.5068703889846802,
                   1.1141703128814697, 1.1470602750778198,
                   1.220444679260254, 1.1243481636047363]}}

QS = [(0, 1), (0, 2), (10, 1), (99, 1), (0, 256), (768, 256), (1792, 256),
      (2047, 1)]
ROWS = [(1, 0), (1, 1), (16, 0), (16, 16), (4096, 16), (4096, 0)]
TOK = [(0, True), (0, False), (100, True), (2047, False)]
COUNTS = {
    "olmo-1b-codes": {
        "attn_flops": [131072, 393216, 1441792, 13107200, 4311744512,
                       30081548288, 64441286656, 268435456],
        "attn_bytes": [196608, 393216, 851968, 6684672, 50331648, 100663296,
                       167772160, 134348800],
        "matmul_flops": [2147483648, 2353528832, 34359738368, 37656461312,
                         8799389745152, 8796093022208],
        "matmul_bytes": [1177288704, 1177491968, 1185153024, 1188405248,
                         3327500288, 3324248064],
        "token_flops": [2353659904, 2147614720, 2366767104, 2415919104]},
    "olmo-tiny": {
        "attn_flops": [512, 1536, 5632, 51200, 16842752, 117506048,
                       251723776, 1048576],
        "attn_bytes": [768, 1536, 3328, 26112, 196608, 393216, 655360,
                       524800],
        "matmul_flops": [262144, 327680, 4194304, 5242880, 1074790400,
                         1073741824],
        "matmul_bytes": [165888, 168000, 196608, 230400, 8586240, 8552448],
        "token_flops": [328192, 262656, 379392, 1310720]},
    "qwen-tiny": {
        "attn_flops": [512, 1536, 5632, 51200, 16842752, 117506048,
                       251723776, 1048576],
        "attn_bytes": [768, 1536, 2048, 13440, 196608, 294912, 425984,
                       262784],
        "matmul_flops": [196608, 262144, 3145728, 4194304, 806354944,
                         805306368],
        "matmul_bytes": [134400, 136576, 184320, 219136, 13797376, 13762560],
        "token_flops": [262656, 197120, 313856, 1245184]},
    "qwen3-1.7b-w7": {
        "attn_flops": [229376, 688128, 2523136, 22937600, 7545552896,
                       52642709504, 112772251648, 469762048],
        "attn_bytes": [344064, 688128, 917504, 6021120, 88080384, 132120576,
                       190840832, 117727232],
        "matmul_flops": [2818572288, 3440902144, 45097156608, 55054434304,
                         11554829369344, 11544872091648],
        "matmul_bytes": [1721942016, 1722553856, 1744306176, 1754095616,
                         7837147136, 7827357696],
        "token_flops": [3441131520, 2818801664, 3464069120, 3288334336]}}

READERS = {"startup.compile_s": 12.5, "sched.rows_per_decode": 15.0,
           "sched.queue_wait_p50_s": 1.5, "step.decode_ms": 81.19477925,
           "step.prefill_ms": 796.6549630000001, "mfu": 1.7947849639634519,
           "wmatmul_roofline": 7.412333463976099,
           "attn_roofline": 0.788277303026665,
           "device.idle_share": 1.2356924999999963}


def _config(name):
    if name in TINY_CONFIGS:
        return TINY_CONFIGS[name]
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def _params(name):
    config = TINY_CONFIGS[name]
    cfg = get_config(config["model"], tiny=True)
    return weights.make_params(mapi.get_model(cfg).abstract_params(), config,
                               SEED, decoder)


def _sha256(tree):
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(TINY_CONFIGS))
def test_params_and_gaps_unchanged(name):
    params = _params(name)
    assert _sha256(params) == PARAMS_SHA256[name]
    gaps = reference.served_gaps(decoder.forward, params, TINY_CONFIGS[name],
                                 PROMPT, SERVED, 32, control=True)
    assert {k: [float(x) for x in v] for k, v in gaps.items()} == GAPS[name]


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_counts_unchanged(name):
    s = decoder.shapes(_config(name))
    got = {"attn_flops": [decoder.attn_flops(s, a, n) for a, n in QS],
           "attn_bytes": [decoder.attn_bytes(s, a, n) for a, n in QS],
           "matmul_flops": [decoder.matmul_flops(s, r, u) for r, u in ROWS],
           "matmul_bytes": [decoder.matmul_bytes(s, r, u) for r, u in ROWS],
           "token_flops": [decoder.token_flops(s, p, u) for p, u in TOK]}
    assert got == COUNTS[name]


def _recorded_run():
    """The OLMo cell's run over the recorded trace, with a fixed log of
    dispatches, sends and counters."""
    red = load_module(BENCH / "layer_metrics" / "_reduce.py", "_reduce")
    with gzip.open(BENCH / "tests" / "data" / "trace_head.json.gz") as f:
        trace = red.Trace.from_json(json.load(f))
    cell = load_cell("olmo-1b-codes.rag-long", ROOT)
    dispatches = [
        Dispatch("prefill", 16, 256, [(0, 256, False), (256, 256, False),
                                      (1536, 200, True)]
                 + [(512, 256, False)] * 9),
        Dispatch("decode", 16, 1, [(1000 + i, 1, True) for i in range(15)]),
        Dispatch("decode", 16, 1, [(1001 + i, 1, True) for i in range(16)]),
        Dispatch("prefill", 16, 128, [(1024, 100, True)]),
        Dispatch("decode", 16, 1, [(1002 + i, 1, True) for i in range(14)])]
    sent = [types.SimpleNamespace(queue_wait_s=q, t_send=1.0 + late,
                                  t_due=1.0)
            for q, late in [(0.5, 0.01), (2.0, 0.0), (None, 0.0),
                            (3.25, 0.125), (1.0, 0.5)]]
    return Run(cell=cell, shapes=cell.arch.shapes(cell.config),
               peaks=peaks_for("TPU v5 lite"), setup_s=30.0,
               setup_compile_s=12.5, window=None, sent=sent,
               counters={"engine.decode.steps": 3},
               dispatches=dispatches, trace=trace)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_unchanged_on_recorded_trace(metric):
    run = _recorded_run()
    assert reader(ROOT, "layer_metrics", metric).read(run) == READERS[metric]
