"""Attention's share of its roofline: for every dispatch, the least
time the attention of its live rows could take (the larger of FLOPs
over the bf16 peak and KV and activation bytes over HBM bandwidth, by
the architecture's counts), summed, over the device time of the paged
attention kernels (prefill and decode, float and codes) in the traced
window."""

from bench.harness import flops
from bench.harness.spec import reader


def read(run):
    red = reader(run.cell.root, "layer_metrics", "_reduce")
    busy = red.family_s(run.trace, "attn")
    if not busy or not run.dispatches:
        return None
    arch, s, p = run.arch, run.shapes, run.peaks
    least = 0.0
    for d in run.dispatches:
        f = sum(arch.attn_flops(s, a, n) for a, n, _ in d.valid)
        b = sum(arch.attn_bytes(s, a, n) for a, n, _ in d.valid)
        least += flops.least_seconds(f, b, p.bf16_flops, p.hbm_bytes_s)[0]
    return 100.0 * least / busy
