"""The quantized matmuls' share of their roofline: for every dispatch,
the least time its matmuls could take at the rows dispatched (the
larger of FLOPs over the bf16 peak and bytes over HBM bandwidth, by the
architecture's counts), summed, over the device time of the
``lut_dequant_matmul`` kernels (every variant) in the traced window."""

from bench.harness import flops
from bench.harness.spec import reader


def read(run):
    red = reader(run.cell.root, "layer_metrics", "_reduce")
    busy = red.family_s(run.trace, "wmatmul")
    if not busy or not run.dispatches:
        return None
    a, s, p = run.arch, run.shapes, run.peaks
    least = sum(flops.least_seconds(
        a.matmul_flops(s, d.rows * d.width, d.rows),
        a.matmul_bytes(s, d.rows * d.width, d.rows),
        p.bf16_flops, p.hbm_bytes_s)[0] for d in run.dispatches)
    return 100.0 * least / busy
