"""Mean device time of one decode program (a jitted step that runs the
paged decode attention kernel) in the traced window."""

from bench.harness.spec import reader


def read(run):
    red = reader(run.cell.root, "layer_metrics", "_reduce")
    ts = [s for kind, s in red.programs(run.trace) if kind == "decode"]
    return 1e3 * sum(ts) / len(ts) if ts else None
