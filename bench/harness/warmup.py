"""Set-up's warm-up: every program shape the cell's traffic can reach,
and no other, run once before the window so that nothing compiles in it.

The shapes follow from the mix's length bounds and the scheduler's own
ladders (asked of the Engine through ``adapter``):

- decode: one program per block-table width, from the shortest live
  context (the shortest prompt) to the longest (longest prompt plus
  longest output);
- prefill: one program per (chunk width, block-table width) pair that a
  tick can dispatch.  A tick's width comes from the row with the most
  prompt left; below the full chunk every row finishes its prompt in
  that tick, so each row's chunks start at multiples of the full chunk.
  Its block-table width comes from the row that ends furthest, which
  has no more prompt left than the row that set the width.
"""

from __future__ import annotations

import math

from bench.harness import adapter


def _pow2_ladder(lo: int, hi: int) -> list[int]:
    out, c = [], lo
    while c <= hi:
        out.append(c)
        c *= 2
    return out


def prefill_shapes(eng, prompt_range) -> list[tuple[int, int]]:
    ec = eng.engine_cfg
    bs, chunk = ec.block_size, ec.prefill_chunk
    pmin, pmax = prompt_range
    # (tokens left, end of this tick's chunk) of every row state
    states = sorted({(n - s0, s0 + min(n - s0, chunk))
                     for n in range(pmin, pmax + 1)
                     for s0 in range(0, n, chunk)})
    out, seen_cols = set(), set()
    for left, end in states:
        # rows with no more left than this one (itself included)
        c = adapter.table_cols(eng, math.ceil(end / bs))
        seen_cols.add(c)
        w = adapter.chunk_width(eng, left)
        out.update((w, max(c, o)) for o in seen_cols)
    return sorted(out)


def shapes(eng, prompt_range, output_range):
    bs = eng.engine_cfg.block_size
    pmin, pmax = prompt_range
    omax = output_range[1]
    lo = adapter.table_cols(eng, pmin // bs + 1)
    hi = adapter.table_cols(eng, (pmax + omax - 2) // bs + 1)
    return prefill_shapes(eng, prompt_range), _pow2_ladder(lo, hi)


def warm(eng, prompt_range, output_range) -> int:
    """Dispatch each shape once; returns how many programs ran."""
    prefill, decode = shapes(eng, prompt_range, output_range)
    for w, c in prefill:
        adapter.dispatch_empty(eng, "prefill", w, c)
    for c in decode:
        adapter.dispatch_empty(eng, "decode", 1, c)
    return len(prefill) + len(decode)
