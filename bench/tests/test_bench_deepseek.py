"""The DeepSeek-V2-Lite cell: found by name with its architecture, its
width check, its three readers on hand-made traces and counters, and a
planted fault at the program's tiny preset coming out not correct."""

import json
import time
import types
from pathlib import Path

import pytest

from bench.harness import cell as C
from bench.harness.adapter import Dispatch
from bench.harness.peaks import PEAKS
from bench.harness.spec import load_cell, load_module
from bench.tests.conftest import ENGINE
from repro.configs import get_config
from repro.core import lama_layers as ll
from repro.runtime import engine as engine_mod

BENCH = Path(__file__).resolve().parents[1]
CELL = "deepseek-v2-lite-e16.decode-long"
R = load_module(BENCH / "layer_metrics" / "_reduce.py", "_reduce")


def _reader(name):
    return load_module(BENCH / "layer_metrics" / f"{name}.py",
                       f"bench_layer_metrics.{name}")


def test_cell_loads_its_architecture():
    cell = load_cell(CELL, BENCH.parent)
    assert cell.arch.__name__.startswith("bench_architectures.deepseek_v2@")
    assert cell.config["model"] == "deepseek-v2-lite"
    assert cell.mix == load_cell("qwen3-1.7b-w7.decode-long",
                                 BENCH.parent).mix
    assert [m["name"] for m in cell.per_layer] == [
        "moe.expert_roofline", "moe.load_imbalance", "mla.decode_roofline"]
    cell.arch.check_widths(get_config("deepseek-v2-lite"), cell.config)
    s = cell.arch.shapes(cell.config)
    assert (s.experts, s.experts_held, s.latent, s.shared_ff) == (
        64, 16, 640, 2816)


@pytest.mark.parametrize("key,value", [
    ("kv_lora_rank", 256), ("moe_intermediate_size", 1024),
    ("experts_held", [16, 32]), ("n_routed_experts", 32),
    ("norm_topk_prob", True), ("first_k_dense_replace", 2)])
def test_check_widths_refuses_a_different_config(key, value):
    cell = load_cell(CELL, BENCH.parent)
    config = dict(cell.config, **{key: value})
    with pytest.raises(ValueError, match="differs"):
        cell.arch.check_widths(get_config("deepseek-v2-lite"), config)


# ------------------------------------------------------------- readers

MS = 1_000_000


def _run(trace=None, counters=None, dispatches=()):
    cell = load_cell(CELL, BENCH.parent)
    return types.SimpleNamespace(
        cell=cell, arch=cell.arch, shapes=cell.arch.shapes(cell.config),
        peaks=PEAKS["TPU v5 lite"], trace=trace, counters=counters or {},
        dispatches=list(dispatches))


def _trace():
    """Two programs in a 100 ms window: a decode one (10-30 ms, launched
    at 9 ms) with 4 ms of attention kernel and 10 ms of expert kernels,
    a prefill one (40-90 ms, launched at 39 ms) with 20 ms of attention
    and 8 ms of expert kernels."""
    dev = "/device:TPU:0"
    ops = [[10 * MS, 30 * MS, "while.3"],
           [11 * MS, 15 * MS, "flash_prefill_paged_kernel.2"],
           [15 * MS, 21 * MS, "moe_expert_gated_kernel.1"],
           [21 * MS, 25 * MS, "moe_expert_down_kernel.1"],
           [40 * MS, 90 * MS, "while.7"],
           [41 * MS, 61 * MS, "flash_prefill_paged_kernel.5"],
           [61 * MS, 69 * MS, "moe_expert_gated_kernel.4"]]
    mods = [[10 * MS, 30 * MS, "jit_decode_step"],
            [40 * MS, 90 * MS, "jit_prefill_step"]]
    host = [[0, 100 * MS, "bench.window"],
            [9 * MS, 9 * MS + 1, "bench.dispatch.decode"],
            [39 * MS, 39 * MS + 1, "bench.dispatch.prefill"]]
    return R.Trace(ops={dev: ops}, modules={dev: mods}, host=host)


def test_expert_roofline_hand_made():
    """1,000 routed rows and 300 active (dispatch, layer, expert) triples
    over 18 ms of expert kernels: the bytes bound, 3 * 2048 * 1408 B of
    codes a triple plus 2 * (2048 + 1408) * 2 B a row."""
    run = _run(_trace(), {"engine.moe.routed_tokens": 1000,
                          "engine.moe.active_experts": 300})
    nbytes = 3 * 2048 * 1408 * 300 + 1000 * 2 * (2048 + 1408) * 2
    flops = 6 * 2048 * 1408 * 1000
    assert flops / 197e12 < nbytes / 819e9
    got = _reader("moe.expert_roofline").read(run)
    assert got == pytest.approx(100 * nbytes / 819e9 / 0.018)
    assert _reader("moe.expert_roofline").read(_run(_trace())) is None
    assert _reader("moe.expert_roofline").read(_run(
        R.Trace(ops={}, modules={}, host=[[0, MS, "bench.window"]]),
        {"engine.moe.routed_tokens": 5, "engine.moe.active_experts": 1})
    ) is None


def test_load_imbalance_hand_made():
    run = _run(counters={"engine.moe.routed_tokens": 160,
                         "engine.moe.busiest_expert_tokens": 20})
    assert _reader("moe.load_imbalance").read(run) == pytest.approx(2.0)
    even = _run(counters={"engine.moe.routed_tokens": 160,
                          "engine.moe.busiest_expert_tokens": 10})
    assert _reader("moe.load_imbalance").read(even) == pytest.approx(1.0)
    assert _reader("moe.load_imbalance").read(_run()) is None


def test_mla_decode_roofline_hand_made():
    """One decode dispatch of two live rows at positions 99 and 299
    (contexts 100 and 300 after the write): 27 layers x 400 positions x
    640 B read, over the 4 ms of attention kernel in the decode program
    (the prefill program's 20 ms are left out)."""
    run = _run(_trace(), dispatches=[
        Dispatch("decode", 16, 1, [(99, 1, True), (299, 1, True)]),
        Dispatch("prefill", 16, 256, [(0, 256, True)])])
    nbytes = 27 * 400 * 640
    assert 27 * 2 * 16 * 400 * (512 + 64 + 512) / 197e12 < nbytes / 819e9
    got = _reader("mla.decode_roofline").read(run)
    assert got == pytest.approx(100 * nbytes / 819e9 / 0.004)
    assert _reader("mla.decode_roofline").read(_run(_trace())) is None


# --------------------------------------------------- the program, tiny

# Limit of the tiny cell's mean gap, between readings of the harness's
# own runs on the CPU (seeds 1-8 and 2**31 + 11, windows of 0 s): the
# program at most 0.0096, the float8 control at least 0.032.
TINY_LIMIT = {"mean_logit_gap": 0.016}


@pytest.fixture(scope="module")
def tiny_ds_root(tiny_root):
    b = tiny_root / "bench"
    config = json.loads((b / "configs" / "deepseek-v2-lite-e16.json")
                        .read_text())
    cfg = get_config("deepseek-v2-lite", tiny=True)
    config.update({
        "preset": "tiny", "hidden_size": cfg.d_model,
        "intermediate_size": cfg.dense_d_ff,
        "moe_intermediate_size": cfg.d_ff, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "num_hidden_layers": cfg.num_layers, "vocab_size": cfg.vocab_size,
        "n_routed_experts_published": cfg.num_experts,
        "experts_held": list(cfg.held_experts),
        "n_routed_experts": cfg.held_experts[1] - cfg.held_experts[0],
        "num_experts_per_tok": cfg.experts_per_token, "engine": ENGINE})
    (b / "configs" / "ds-tiny.json").write_text(json.dumps(config))
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    if not any(c["name"] == "ds-tiny" for c in bench["configs"]):
        bench["configs"].append({"name": "ds-tiny", "source": "test",
                                 "file": "bench/configs/ds-tiny.json",
                                 "reduced": [], "why": "test"})
        bench["workloads"].append({"name": "ds-tiny.chat",
                                   "config": "ds-tiny", "traffic": "chat",
                                   "chips": 1, "why": "test"})
        (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    (b / "limits" / "ds-tiny.chat.json").write_text(json.dumps(TINY_LIMIT))
    return tiny_root


SEED = 2**31 + 11


def _run_tiny(root):
    lines = []
    cell = load_cell("ds-tiny.chat", root)
    with ll.policy(autotune=False):
        out = C.run_cell(cell, SEED, 3.0, False, time.perf_counter(),
                         log=lines.append)
    return out, lines


def test_tiny_cell_sound_run_correct(tiny_ds_root):
    out, lines = _run_tiny(tiny_ds_root)
    assert out["correct"], out["checks"]
    assert "compiles in window: 0" in lines


def test_tiny_cell_altered_token_not_correct(tiny_ds_root, monkeypatch):
    real = engine_mod._jit_decode

    def broken(step_fn):
        step = real(step_fn)

        def altered(params, view, tokens, active, cfg):
            nxt, ok, new = step(params, view, tokens, active, cfg)
            return (nxt + 1) % cfg.vocab_size, ok, new
        return altered

    monkeypatch.setattr(engine_mod, "_jit_decode", broken)
    out, _ = _run_tiny(tiny_ds_root)
    assert not out["correct"]
    assert out["checks"]["mean_logit_gap"]["value"] > \
        TINY_LIMIT["mean_logit_gap"]
