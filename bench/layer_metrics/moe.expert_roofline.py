"""The routed experts' share of their roofline: the least time the
traced span's expert work could take (the larger of its FLOPs over the
bf16 peak and its bytes over HBM bandwidth, by the architecture's
``expert_flops`` and ``expert_bytes``: each (dispatch, layer, held
expert) given a row reads its codes once, each routed row flows in and
out), over the device time of the grouped ``moe_expert`` kernels in
the traced window.  The work is the change of the Engine's
``engine.moe.routed_tokens`` and ``engine.moe.active_experts`` over the
traced span."""

from bench.harness import flops
from bench.harness.spec import reader

KERNELS = r"moe_expert"


def read(run):
    routed = run.counters.get("engine.moe.routed_tokens")
    if not routed or run.trace is None:
        return None
    busy = reader(run.cell.root, "layer_metrics", "_reduce").family_s(
        run.trace, KERNELS)
    if not busy:
        return None
    a, s, p = run.arch, run.shapes, run.peaks
    least = flops.least_seconds(
        a.expert_flops(s, routed),
        a.expert_bytes(s, routed, run.counters["engine.moe.active_experts"]),
        p.bf16_flops, p.hbm_bytes_s)[0]
    return 100.0 * least / busy
