"""What a run is made of, found by name: the cell in ``BENCHMARK.json``,
its configuration file, the configuration's architecture, its traffic
mix, the mix's generator and one reader per metric.

Nothing here is keyed by a cell, a model or a metric: a new
configuration, architecture, mix, generator or metric is a new file plus
a new entry in ``BENCHMARK.json``, and this module finds it.

- configuration: the ``file`` named by its entry under ``configs``;
- architecture ``<a>`` (the configuration's ``"architecture"``):
  ``bench/architectures/<a>.py``, with ``check_widths``, ``leaf``,
  ``act_tables``, ``forward``, ``shapes`` and the work counts
  (``bench/architectures/decoder.py`` says what each does);
- traffic mix ``<t>``: ``bench/mixes/<t>.json``;
- generator ``<g>`` (the mix's ``"generator"``): ``bench/generators/<g>.py``,
  with ``make(mix, seed, vocab)``;
- load loop ``<k>`` (the mix's ``"loop"``): ``bench/loops/<k>.py``, with
  ``make(eng, traffic, seconds, follow)``;
- end-to-end metric ``<m>``: ``bench/end_to_end/<m>.py`` with ``read(run)``;
- per-layer metric ``<m>``: ``bench/layer_metrics/<m>.py`` with ``read(run)``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType

ROOT = Path(__file__).resolve().parents[2]   # the checkout: BENCHMARK.json


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, qualname: str) -> ModuleType:
    """Import a file by path (names may hold dots, as metric names do).
    The module is keyed by its path, so two checkouts never share one."""
    key = f"{qualname}@{path.resolve()}"
    if key in sys.modules:
        return sys.modules[key]
    if not path.is_file():
        raise FileNotFoundError(f"{qualname}: no file {path}")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    root: Path
    name: str
    chips: int
    config_name: str
    config: dict
    traffic: str
    mix: dict
    arch: ModuleType             # bench/architectures/<architecture>.py
    end_to_end: list[dict]       # the end-to-end metrics this cell reports
    per_layer: list[dict]        # the per-layer metrics this cell reports

    @property
    def bench_dir(self) -> Path:
        return self.root / "bench"

    def generator(self) -> ModuleType:
        kind = self.mix["generator"]
        return load_module(self.bench_dir / "generators" / f"{kind}.py",
                           f"bench_generators.{kind}")

    def loop(self) -> ModuleType:
        kind = self.mix["loop"]
        return load_module(self.bench_dir / "loops" / f"{kind}.py",
                           f"bench_loops.{kind}")

    def limits(self) -> dict:
        return load_json(self.bench_dir / "limits" / f"{self.name}.json")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")
    entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    config = load_json(root / entry["file"])
    mix = load_json(root / "bench" / "mixes" / f"{wl['traffic']}.json")
    return Cell(root=root, name=name, chips=int(wl["chips"]),
                config_name=wl["config"], config=config,
                traffic=wl["traffic"], mix=mix,
                arch=architecture(root, config, entry["file"]),
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)])


def architecture(root: Path, config: dict, file: str) -> ModuleType:
    """The module of the configuration's ``"architecture"``, read from
    configuration file ``file``."""
    if "architecture" not in config:
        raise KeyError(f"{file}: no \"architecture\" key (the harness "
                       f"looks for bench/architectures/<architecture>.py)")
    name = config["architecture"]
    return load_module(root / "bench" / "architectures" / f"{name}.py",
                       f"bench_architectures.{name}")


def reader(root: Path, kind: str, name: str) -> ModuleType:
    """The reader of metric ``name``; ``kind`` is ``end_to_end`` or
    ``layer_metrics``."""
    return load_module(root / "bench" / kind / f"{name}.py",
                       f"bench_{kind}.{name}")
