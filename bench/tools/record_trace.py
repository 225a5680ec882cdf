"""Record the head of a cell's reduced trace: one traced run of the
cell, as ``bench/run.py --trace 1`` makes it, whose reduced trace's
first seconds are written to a JSON file (gzip it to
``bench/tests/data/trace_head.json.gz``, the trace the tests read).

    python3 bench/tools/record_trace.py --workload <name> --seed <n> \
        --seconds 20 --out trace_head.json
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from bench.harness import cell as run_cell
    from bench.harness.spec import load_cell
    from repro.core import lama_layers as ll

    cell = load_cell(args.workload, ROOT)
    try:
        peaks = run_cell.check_devices(cell)
    except (run_cell.NoDevice, KeyError) as e:
        print(f"record_trace: {e}", file=sys.stderr)
        return 2
    run_cell.enable_compile_cache(ROOT)
    with ll.policy(autotune=False):
        run_cell.run_cell(cell, args.seed, args.seconds, True, T_START,
                          peaks=peaks, keep_trace=args.out,
                          log=lambda m: print(m, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
