"""Share of the traced window in which no op ran on the device:
1 - (union of device op intervals) / window."""

from bench.harness.spec import reader


def read(run):
    red = reader(run.cell.root, "layer_metrics", "_reduce")
    return 100.0 * (1.0 - red.busy_s(run.trace) / run.trace.window_s)
