"""A checkout of the benchmark with two tiny cells (the program's tiny
presets of both configurations), built in a temporary directory, for
running the harness end to end on the CPU."""

from __future__ import annotations

import json
import os
import shutil
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]

ENGINE = {"num_slots": 4, "block_size": 16, "max_seq_len": 128,
          "prefill_chunk": 32, "num_blocks": 65, "prefix_cache": True}

TINY_CONFIGS = {
    "qwen-tiny": {
        "model": "qwen3-1.7b", "architecture": "decoder", "preset": "tiny",
        "hidden_size": 64,
        "intermediate_size": 192, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 512, "rope_theta": 1000000, "tie_word_embeddings": True,
        "reference": {"norm": "rmsnorm", "norm_eps": 1e-6, "qk_norm": True,
                      "qk_norm_eps": 1e-6},
        "representation": {"weights": "exp7", "activations": "bfloat16",
                           "kv": "float8_e4m3fn",
                           "control_activations": "float8_e4m3fn"},
        "engine": ENGINE, "reduced": []},
    "olmo-tiny": {
        "model": "olmo-1b", "architecture": "decoder", "preset": "tiny",
        "hidden_size": 64,
        "intermediate_size": 256, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "vocab_size": 512, "rope_theta": 10000.0, "tie_word_embeddings": True,
        "reference": {"norm": "nonparam_ln", "norm_eps": 1e-5,
                      "qk_norm": False},
        "representation": {"weights": "exp7", "activations": "exp7",
                           "kv": "exp7", "control_activations": "exp6"},
        "engine": ENGINE, "reduced": []},
}

TINY_MIX = {"generator": "closed_loop", "loop": "closed", "clients": 16,
            "pool": 64,
            "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                       "min": 8, "max": 60},
            "output": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                       "min": 4, "max": 40},
            "follow_to_end": True, "check_requests": 16}


# Limits of the tiny cells, on the mean gap their full-size cells
# compare, between readings of the harness's own runs on the CPU (sound
# runs at most / the control at least), over seeds 1-12 with a window of
# 0 s (the first 16 requests, 242 tokens) and six seeds with windows of
# 3 s: qwen 0.0014 / 0.0041, OLMo 0.0102 / 0.0196.  (The widest gap does
# not separate at this size.)
TINY_LIMITS = {"qwen-tiny": {"mean_logit_gap": 0.0022},
               "olmo-tiny": {"mean_logit_gap": 0.015}}


def build_tiny_root(tmp: Path) -> Path:
    """A checkout holding the benchmark's code with two tiny cells:
    ``qwen-tiny.chat`` and ``olmo-tiny.chat``."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for name, cfg in TINY_CONFIGS.items():
        (root / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(cfg))
    (root / "bench" / "mixes" / "chat.json").write_text(json.dumps(TINY_MIX))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": n, "source": "test",
                         "file": f"bench/configs/{n}.json", "reduced": [],
                         "why": "test"} for n in TINY_CONFIGS]
    cells = [f"{n}.chat" for n in TINY_CONFIGS]
    bench["workloads"] = [{"name": c, "config": c.split(".")[0],
                           "traffic": "chat", "chips": 1, "why": "test"}
                          for c in cells]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = cells
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for c in cells:
        (root / "bench" / "limits" / f"{c}.json").write_text(
            json.dumps(TINY_LIMITS[c.split(".")[0]]))
    return root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return build_tiny_root(tmp_path_factory.mktemp("bench"))
