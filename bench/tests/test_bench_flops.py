"""The operation and byte counts against hand counts for both
configurations (from the published widths)."""

import json
from pathlib import Path

import pytest

from bench.harness import flops
from bench.harness.spec import load_module

BENCH = Path(__file__).resolve().parents[1]
CONFIGS = BENCH / "configs"
decoder = load_module(BENCH / "architectures" / "decoder.py",
                      "bench_architectures.decoder")


def shapes(name):
    return decoder.shapes(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_qwen_counts():
    s = shapes("qwen3-1.7b-w7")
    # q, o: 2048*16*128 each; k, v: 2048*8*128 each; mlp: 3*2048*6144
    assert decoder.layer_matmul_params(s) == 50_331_648
    # 28 layers plus the tied 151936 x 2048 output projection: 1.72 GB
    # of one-byte codes
    assert 28 * 50_331_648 + 151_936 * 2048 == 1_720_451_072
    assert decoder.matmul_flops(s, 1, 1) == 2 * 1_720_451_072
    assert decoder.matmul_flops(s, 16, 0) == 16 * 2 * 28 * 50_331_648
    # a query at position 99 attends 100 positions: 4*16*128*100 a layer
    assert decoder.attn_flops(s, 99, 1) == 28 * 4 * 16 * 128 * 100
    # two queries at 0 and 1: contexts 1 + 2
    assert decoder.attn_flops(s, 0, 2) == 28 * 4 * 16 * 128 * 3
    # one f8 token: KV of 8 heads x 128 x (k, v) = 2048 B read and
    # written, q in and context out in bf16: 16*128*2*2 B
    assert decoder.attn_bytes(s, 0, 1) == 28 * (2048 + 2048 + 8192)
    # decode dispatch of 16 rows: every code once, activations in/out
    per_row = 2048 + 32 * 128 + 16 * 128 + 2048 + 2048 + 6144 + 6144 + 2048
    assert decoder.matmul_bytes(s, 16, 16) == (
        1_720_451_072 + 16 * 28 * per_row * 2 + 16 * (2048 * 2 + 151_936 * 4))


def test_olmo_counts():
    s = shapes("olmo-1b-codes")
    # q, k, v, o: 2048*16*128 each; mlp: 3*2048*8192
    assert decoder.layer_matmul_params(s) == 67_108_864
    assert 16 * 67_108_864 + 50_304 * 2048 == 1_176_764_416
    assert decoder.token_flops(s, 0, True) == (
        2 * 1_176_764_416 + 16 * 4 * 16 * 128 * 1)
    # codes KV: 16 heads x 128 x 2 x 1 B per token; q and context codes
    assert decoder.attn_bytes(s, 10, 1) == 16 * (11 * 4096 + 4096 + 4096)


@pytest.mark.parametrize("f,b,which", [(197e12, 1.0, "compute"),
                                       (1.0, 819e9, "memory")])
def test_least_seconds(f, b, which):
    t, bound = flops.least_seconds(f, b, 197e12, 819e9)
    assert t == pytest.approx(1.0) and bound == which
