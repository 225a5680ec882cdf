"""A configuration, a traffic mix, a generator, a load loop and a metric
added as new files plus new ``BENCHMARK.json`` entries are found by
name, with no edit to a file the benchmark already has."""

import json

from bench.harness.spec import load_cell, reader


def test_new_files_are_found(tiny_root, tmp_path):
    import shutil
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
