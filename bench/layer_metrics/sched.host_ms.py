"""Host time of one scheduler tick: for each ``engine.step`` span that
lies wholly in the traced window, its length less the union of the
``engine.wait.*`` spans inside it (the host blocked on the device's
results), averaged.  With page checksums and the drift probe off, the
host waits on the device only inside such a span."""

from bench.harness.spec import reader

STEP = "engine.step"
WAIT = "engine.wait."


def read(run):
    red = reader(run.cell.root, "layer_metrics", "_reduce")
    lo, hi = run.trace.window()
    steps = [h for h in run.trace.host
             if h[2] == STEP and lo <= h[0] and h[1] <= hi]
    if not steps:
        return None
    waits = [h for h in run.trace.host if h[2].startswith(WAIT)]
    host_ns = [(e - s) - red.union_ns([(max(a, s), min(b, e))
                                       for a, b, _ in waits
                                       if a < e and b > s])
               for s, e, _ in steps]
    return 1e-6 * sum(host_ns) / len(host_ns)
