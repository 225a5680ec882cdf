"""Latent attention's share of its roofline in the decode programs: for
every decode dispatch, the least time its live rows' attention could
take over the latent pages (the larger of FLOPs over the bf16 peak and
the cache entries read once over HBM bandwidth, by the architecture's
``mla_decode_flops`` and ``mla_decode_bytes``), summed, over the device
time of the paged attention kernel inside the programs launched by a
``bench.dispatch.decode`` span in the traced window.  A kernel that
reads the pool once as keys and again as values reads at most 50%."""

import bisect

from bench.harness import flops
from bench.harness.spec import reader


def decode_kernel_s(red, trace) -> float:
    """Seconds of the attention kernels inside decode programs: each
    program's kind is that of the dispatch span that began nearest it,
    as ``_reduce.programs`` tells them apart."""
    lo, hi = trace.window()
    spans = sorted((h[0], h[2][len(red.DISPATCH):]) for h in trace.host
                   if h[2].startswith(red.DISPATCH))
    if not spans:
        return 0.0
    keep = {}
    for dev, mods in trace.modules.items():
        ops = sorted(trace.ops.get(dev, []))
        starts = [o[0] for o in ops]
        for s, e, _name in mods:
            if s < lo or e > hi:
                continue
            if min(spans, key=lambda sp: abs(sp[0] - s))[1] != "decode":
                continue
            keep.setdefault(dev, []).extend(
                ops[bisect.bisect_left(starts, s):bisect.bisect_right(starts, e)])
    return red.family_s(red.Trace(ops=keep, modules=trace.modules,
                                  host=trace.host), "attn")


def read(run):
    if run.trace is None:
        return None
    decodes = [d for d in run.dispatches if d.kind == "decode"]
    if not decodes:
        return None
    red = reader(run.cell.root, "layer_metrics", "_reduce")
    busy = decode_kernel_s(red, run.trace)
    if not busy:
        return None
    a, s, p = run.arch, run.shapes, run.peaks
    least = sum(flops.least_seconds(
        sum(a.mla_decode_flops(s, start + 1) for start, _, _ in d.valid),
        sum(a.mla_decode_bytes(s, start + 1) for start, _, _ in d.valid),
        p.bf16_flops, p.hbm_bytes_s)[0] for d in decodes)
    return 100.0 * least / busy
