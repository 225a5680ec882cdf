"""Read a cell's correctness numbers on many seeds in one process: the
program's gap numbers and the control's, for setting and checking the
cell's limits (``bench/limits/<cell>.json``).

    python3 bench/tools/readings.py --workload <name> --seeds 101-112 \
        --seconds 40 [--out readings.jsonl]

Each seed is a whole run of the cell as ``bench/run.py`` makes it (its
own weights and traffic, the cell's load, its window), then the check
of the served sample against the reference, and the control judged by
the same comparison on the same sample.  The compiled programs are
shared between seeds, so only the first seed pays for them.  One JSON
line per seed, then a summary; exits 1 when the control comes out
correct on any seed.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from bench.harness import cell as run_cell
    from bench.harness.spec import load_cell
    from repro.core import lama_layers as ll

    cell = load_cell(args.workload, ROOT)
    try:
        peaks = run_cell.check_devices(cell)
    except (run_cell.NoDevice, KeyError) as e:
        print(f"readings: {e}", file=sys.stderr)
        return 2
    run_cell.enable_compile_cache(ROOT)
    clock = run_cell.CompileClock()
    rows = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        with ll.policy(autotune=False):
            out = run_cell.run_cell(cell, seed, args.seconds, False, t0,
                                    control=True, peaks=peaks, clock=clock,
                                    log=lambda m: print(m, flush=True))
        row = {"seed": seed, "correct": out["correct"],
               "control_correct": out["control_correct"],
               "gaps": out["gaps"], "control_gaps": out["control_gaps"],
               "checked_tokens": out["checked_tokens"],
               "failed": out["failed"], "attempted": out["attempted"],
               "metrics": {k: v["value"] for k, v in out["metrics"].items()}}
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in rows)
    control_correct = [r["seed"] for r in rows if r["control_correct"]]
    print(json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "program_correct": sum(r["correct"] for r in rows),
        "control_correct_seeds": control_correct,
        "program_max": {k: max(r["gaps"][k] for r in rows)
                        for k in rows[0]["gaps"]},
        "control_min": {k: min(r["control_gaps"][k] for r in rows)
                        for k in rows[0]["gaps"]}}), flush=True)
    return 1 if control_correct else 0


if __name__ == "__main__":
    sys.exit(main())
