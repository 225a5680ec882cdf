"""Run one cell of the serving benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are read from
``BENCHMARK.json`` and the files it names.  Set-up (weights from the
seed, the Engine, the warm-up of the cell's program shapes) runs first;
the window then serves the mix for ``--seconds``.  With ``--trace 0`` the
result holds the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics read from a profiler trace of the window's last
seconds.  Afterwards a sample of the served requests is checked
against the plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced), and last ``checks``: each number compared, with its
limit, also printed as the last lines of standard error.  Without a TPU
(or with fewer chips than the cell asks for) it exits 2 and prints no
result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from bench.harness import cell as run_cell
    from bench.harness.spec import load_cell

    cell = load_cell(args.workload, ROOT)
    try:
        peaks = run_cell.check_devices(cell)
    except (run_cell.NoDevice, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(f"compile cache: {run_cell.enable_compile_cache(ROOT)}", flush=True)
    from repro.core import lama_layers as ll

    def log(msg):
        print(msg, flush=True)

    with ll.policy(autotune=False):
        out = run_cell.run_cell(cell, args.seed, args.seconds,
                                bool(args.trace), T_START, peaks=peaks,
                                log=log)
    for name, c in out["checks"].items():
        print(f"check {name}: value {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
