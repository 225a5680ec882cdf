"""A configuration, an architecture, a traffic mix, a generator, a load
loop and a metric added as new files plus new ``BENCHMARK.json`` entries
are found by name, with no edit to a file the benchmark already has."""

import hashlib
import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import cell as C
from bench.harness import reference, weights
from bench.harness.spec import load_cell, load_module, reader

R = load_module(Path(__file__).resolve().parents[1] / "layer_metrics"
                / "_reduce.py", "_reduce")
MS = 1_000_000


def test_new_files_are_found(tiny_root, tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(tiny_root, root)
    b = root / "bench"
    cfg = json.loads((b / "configs" / "qwen-tiny.json").read_text())
    (b / "configs" / "qwen-other.json").write_text(json.dumps(cfg))
    (b / "generators" / "fixed.py").write_text(
        "def make(mix, seed, vocab):\n    return ('fixed', mix['n'], seed)\n")
    (b / "loops" / "open.py").write_text(
        "def make(eng, traffic, seconds, follow):\n"
        "    return ('open', traffic, seconds, follow)\n")
    (b / "mixes" / "burst.json").write_text(json.dumps(
        {"generator": "fixed", "loop": "open", "n": 3}))
    (b / "layer_metrics" / "extra.count.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "qwen-other", "source": "test",
                             "file": "bench/configs/qwen-other.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "qwen-other.burst",
                               "config": "qwen-other", "traffic": "burst",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "extra.count", "unit": "rows",
                               "better": "higher", "source": "program_counter",
                               "layer": "scheduler", "moves": "output_tok_s",
                               "workloads": ["qwen-other.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = load_cell("qwen-other.burst", root)
    assert cell.config == cfg
    assert cell.generator().make(cell.mix, 7, 512) == ("fixed", 3, 7)
    assert cell.loop().make(None, "t", 2.0, True) == ("open", "t", 2.0, True)
    assert load_cell("qwen-tiny.chat", root).loop().__name__.startswith(
        "bench_loops.closed@")
    names = [m["name"] for m in cell.per_layer]
    assert "extra.count" in names
    assert "sched.queue_wait_p50_s" not in names     # listed for other cells
    assert reader(root, "layer_metrics", "extra.count").read(None) == 42.0
    assert {m["name"] for m in cell.end_to_end} == {"output_tok_s",
                                                     "setup_s"}
    # the cells that were there are unchanged
    assert load_cell("qwen-tiny.chat", root).mix == \
        load_cell("qwen-tiny.chat", tiny_root).mix


# ------------------------------------------------ a new architecture

TOY_MOE = '''
"""A toy mixture-of-experts decoder: RMSNorm, a router over
``n_routed_experts`` with top-``num_experts_per_tok`` softmax gates,
SiLU experts, a tied output projection.  No attention."""

import dataclasses

import jax
import jax.numpy as jnp

from bench.harness import codes
from bench.harness.weights import NORM_SCALE, Codes


def check_widths(cfg, config):
    pass


def leaf(path, shape, config):
    layers = int(config["num_hidden_layers"])
    if path[-1] == "tokens":
        return Codes(0.05, None)
    if path[-1] in ("router", "w_in", "w_out"):
        return Codes(1.0 / shape[-2] ** 0.5, layers)
    if path[-1] == "scale":
        return NORM_SCALE
    raise KeyError(f"toy_moe: no draw rule for params leaf {'/'.join(path)}")


def act_tables(config):
    return {}


def _w(leaf):
    return codes.decode(leaf["codes"], leaf["qmeta"])


def forward(params, tokens, config, control=False):
    k = int(config["num_experts_per_tok"])
    emb = params["embed"]["tokens"]
    x = codes.decode(emb["codes"][tokens], emb["qmeta"])
    s = x.shape[0]

    def layer(x, p):
        h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \\
            * p["ln"]["scale"]
        if control:
            h = h.astype(jnp.bfloat16).astype(jnp.float32)
        logits = h @ _w(p["router"])
        top, idx = jax.lax.top_k(logits, k)
        gate = jnp.zeros_like(logits).at[jnp.arange(s)[:, None], idx].set(
            jax.nn.softmax(top, -1))
        up = jax.nn.silu(jnp.einsum("sd,edf->sef", h, _w(p["w_in"])))
        out = jnp.einsum("sef,efd->sed", up, _w(p["w_out"]))
        return x + jnp.einsum("se,sed->sd", gate, out), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return x @ codes.decode(emb["codes"], emb["qmeta"]).T


@dataclasses.dataclass(frozen=True)
class Shapes:
    d: int
    ff: int
    layers: int
    experts: int
    top_k: int
    vocab: int


def shapes(config):
    return Shapes(d=int(config["hidden_size"]),
                  ff=int(config["moe_intermediate_size"]),
                  layers=int(config["num_hidden_layers"]),
                  experts=int(config["n_routed_experts"]),
                  top_k=int(config["num_experts_per_tok"]),
                  vocab=int(config["vocab_size"]))


def matmul_flops(s, rows, unembed_rows):
    per_row = s.d * s.experts + s.top_k * 2 * s.d * s.ff
    return 2.0 * (rows * s.layers * per_row + unembed_rows * s.d * s.vocab)


def matmul_bytes(s, rows, unembed_rows):
    weights = s.layers * (s.d * s.experts + s.experts * 2 * s.d * s.ff)
    return float(weights + s.vocab * s.d + 4 * unembed_rows * s.vocab)


def attn_flops(s, q_start, q_len):
    return 0.0


def attn_bytes(s, q_start, q_len):
    return 0.0


def token_flops(s, position, unembed):
    return matmul_flops(s, 1, int(unembed))
'''

EXPERT_READER = '''
"""Device microseconds of the expert kernels per routed token, in the
ticks the Engine ran: this reader's own kernel pattern, the Engine's
``engine.step`` spans and its ``engine.moe.routed_tokens`` counter."""

import re

from bench.harness.spec import reader

EXPERTS = re.compile(r"moe_expert")


def read(run):
    red = reader(run.cell.root, "layer_metrics", "_reduce")
    ticks = [h for h in run.trace.host if h[2] == "engine.step"]
    tokens = run.counters.get("engine.moe.routed_tokens")
    if not ticks or not tokens:
        return None
    return 1e6 * red.family_s(run.trace, EXPERTS) / tokens
'''

TOY_CONFIG = {"architecture": "toy_moe", "model": "none",
              "hidden_size": 16, "moe_intermediate_size": 8,
              "num_hidden_layers": 2, "n_routed_experts": 4,
              "num_experts_per_tok": 2, "vocab_size": 64,
              "representation": {"weights": "exp7",
                                 "activations": "float32", "kv": "float32",
                                 "control_activations": "bfloat16"},
              "engine": {"max_seq_len": 32}}


def _toy_abstract(extra=None):
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    blocks = {"router": sds((2, 16, 4), f32), "w_in": sds((2, 4, 16, 8), f32),
              "w_out": sds((2, 4, 8, 16), f32), "ln": {"scale": sds((2, 16),
                                                                     f32)}}
    blocks.update(extra or {})
    return {"embed": {"tokens": sds((64, 16), f32)}, "blocks": blocks}


def _digests(root):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file()}


def _add_cell(root, config_name, config):
    (root / "bench" / "configs" / f"{config_name}.json").write_text(
        json.dumps(config))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": config_name, "source": "test",
                             "file": f"bench/configs/{config_name}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": f"{config_name}.chat",
                               "config": config_name, "traffic": "chat",
                               "chips": 1, "why": "test"})
    return bench


def test_new_architecture_is_found(tiny_root, tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(tiny_root, root)
    before = _digests(root)
    b = root / "bench"
    (b / "architectures" / "toy_moe.py").write_text(TOY_MOE)
    (b / "layer_metrics" / "moe.expert_us_per_token.py").write_text(
        EXPERT_READER)
    bench = _add_cell(root, "toy-moe", TOY_CONFIG)
    bench["per_layer"].append({"name": "moe.expert_us_per_token",
                               "unit": "us", "better": "lower",
                               "source": "device_trace",
                               "layer": "experts", "moves": "output_tok_s",
                               "workloads": ["toy-moe.chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = load_cell("toy-moe.chat", root)
    assert cell.arch.__name__.startswith("bench_architectures.toy_moe@")
    assert "moe.expert_us_per_token" in [m["name"] for m in cell.per_layer]
    # weights drawn by the architecture's rule: the router is codes
    params = weights.make_params(_toy_abstract(), cell.config, 7, cell.arch)
    assert params["blocks"]["router"]["codes"].dtype == jnp.uint8
    assert params["blocks"]["router"]["qmeta"].shape == (2, 4)
    assert params["blocks"]["ln"]["scale"].dtype == jnp.float32
    # the reference reads keys of its own through the frozen configuration
    gaps = reference.served_gaps(cell.arch.forward, params, cell.config,
                                 [3, 9, 27, 60], [5, 5, 40], 16,
                                 control=True)
    assert gaps["served"].shape == gaps["control"].shape == (3,)
    assert np.all(gaps["served"] >= 0) and np.any(gaps["served"] > 0)
    # the counts, through the run
    dev = "/device:TPU:0"
    trace = R.Trace(
        ops={dev: [[0, 2 * MS, "moe_expert_kernel.1"],
                   [2 * MS, 3 * MS, "lut_dequant_matmul_kernel.7"],
                   [3 * MS, 6 * MS, "moe_expert_kernel.2"]]},
        modules={dev: []},
        host=[[0, 10 * MS, "bench.window"], [0, 4 * MS, "engine.step"],
              [4 * MS, 9 * MS, "engine.step"]])
    run = C.Run(cell=cell, shapes=cell.arch.shapes(cell.config), peaks=None,
                setup_s=0.0, setup_compile_s=0.0, window=None, sent=[],
                counters={"engine.moe.routed_tokens": 50}, trace=trace)
    assert run.arch.matmul_flops(run.shapes, 1, 0) == 2.0 * 2 * (64 + 512)
    got = reader(root, "layer_metrics", "moe.expert_us_per_token").read(run)
    assert got == pytest.approx(100.0)      # 5 ms over 50 tokens
    run.counters = {}
    assert reader(root, "layer_metrics",
                  "moe.expert_us_per_token").read(run) is None
    # nothing that was there changed but BENCHMARK.json's new entries
    after = _digests(root)
    assert {p: d for p, d in after.items() if p in before
            and p.name != "BENCHMARK.json"} == \
        {p: d for p, d in before.items() if p.name != "BENCHMARK.json"}
    assert load_cell("qwen-tiny.chat", root).arch.__name__.startswith(
        "bench_architectures.decoder@")


def test_unknown_or_missing_architecture_names_the_file(tiny_root,
                                                        tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(tiny_root, root)
    config = dict(TOY_CONFIG, architecture="nope")
    bench = _add_cell(root, "nope", config)
    del config["architecture"]
    bench["configs"].append({"name": "bare", "source": "test",
                             "file": "bench/configs/bare.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "bare.chat", "config": "bare",
                               "traffic": "chat", "chips": 1, "why": "test"})
    (root / "bench" / "configs" / "bare.json").write_text(json.dumps(config))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(FileNotFoundError, match="architectures/nope.py"):
        load_cell("nope.chat", root)
    with pytest.raises(KeyError, match="bench/configs/bare.json"):
        load_cell("bare.chat", root)


@pytest.mark.parametrize("arch", ["decoder", "toy_moe"])
def test_leaf_with_no_rule_names_the_leaf(tiny_root, tmp_path, arch):
    root = tmp_path / "checkout"
    shutil.copytree(tiny_root, root)
    (root / "bench" / "architectures" / "toy_moe.py").write_text(TOY_MOE)
    module = load_module(root / "bench" / "architectures" / f"{arch}.py",
                         f"bench_architectures.{arch}")
    config = dict(TOY_CONFIG, num_attention_heads=4, num_key_value_heads=4,
                  intermediate_size=8)
    extra = {"mystery": jax.ShapeDtypeStruct((2, 16, 3), jnp.float32)}
    with pytest.raises(KeyError, match="blocks/mystery"):
        weights.make_params(_toy_abstract(extra), config, 7, module)
