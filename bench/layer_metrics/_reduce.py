"""The reduction shared by the per-layer readers: a profiler trace
(``.xplane.pb``) to device busy time, time per kernel family, device
programs told apart by the dispatch that launched them, and idle gaps
named by the host span around them.

A trace is first cut down to plain lists (:class:`Trace`), which is
also the form of the small recorded traces the tests read.  Times are
nanoseconds on the trace's one clock.  The traced window is the
benchmark's ``bench.window`` host span.  Host spans kept are the
benchmark's (``bench.*``) and the program's (``engine.*``: the phases of
``Engine.step``), so that a reader of the program's spans is one new
file.

Device ops nest: a program's layer loop (``while``) is an op whose
interval holds the ops of its body.  Busy time is the union of the
intervals; time per op name is self time (its interval less its
children's), so nothing is counted twice.

The prefill and decode programs share one name (``jit_fn``) and, since
decode runs the prefill attention kernel at one token a row, one
attention kernel.  The Engine dispatches one program at a time and
waits for it, so a program's kind is that of the ``bench.dispatch.<kind>``
host span that began nearest to it: the host's and the device's clocks
in a trace agree only to a fraction of a millisecond, while two
dispatches lie a whole program (tens of milliseconds) apart.
"""

from __future__ import annotations

import bisect
import dataclasses
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIXES = ("bench.", "engine.")
WINDOW = "bench.window"
DISPATCH = "bench.dispatch."

# Kernel families, matched against an op's name.  A reader of a kernel
# not here passes its own pattern to ``family_s``.
FAMILIES = {
    "wmatmul": re.compile(r"lut_dequant_matmul"),
    "attn": re.compile(r"flash_prefill_paged|decode_gqa_paged"),
}


def op_name(hlo: str) -> str:
    """``%lut_dequant_matmul_kernel.31 = f32[16,2048] custom-call(...)``
    -> ``lut_dequant_matmul_kernel.31``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def op_kind(name: str) -> str:
    """An op's name without its instance number."""
    return re.sub(r"(\.\d+)+(\.clone)?$", "", name)


@dataclasses.dataclass
class Trace:
    # per device: [start_ns, end_ns, name]
    ops: dict[str, list[list]]
    modules: dict[str, list[list]]
    host: list[list]                      # [start_ns, end_ns, name]

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(ops=d["ops"], modules=d["modules"], host=d["host"])

    # ------------------------------------------------------------ window
    def window(self) -> tuple[float, float]:
        spans = [h for h in self.host if h[2] == WINDOW]
        if not spans:
            raise ValueError(f"trace has no {WINDOW!r} span")
        return spans[0][0], spans[0][1]

    @property
    def window_s(self) -> float:
        lo, hi = self.window()
        return (hi - lo) * 1e-9

    def clipped(self, events) -> list[tuple[float, float, str]]:
        lo, hi = self.window()
        out = []
        for s, e, name in events:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                out.append((s, e, name))
        return out


def head(trace: Trace, seconds: float) -> Trace:
    """The trace's first ``seconds`` of its window, as a smaller trace
    whose window is that span (for recording test traces)."""
    lo, _ = trace.window()
    hi = lo + seconds * 1e9

    def keep(evs):
        return [e for e in evs if e[0] >= lo and e[1] <= hi]

    host = [h for h in trace.host if h[2] != WINDOW and lo <= h[0] < hi]
    return Trace(ops={k: keep(v) for k, v in trace.ops.items()},
                 modules={k: keep(v) for k, v in trace.modules.items()},
                 host=[[lo, hi, WINDOW]] + host)


def from_xplane(path: str) -> Trace:
    """Read what the reduction needs from a profiler trace file."""
    from jax.profiler import ProfileData

    ops: dict[str, list] = {}
    modules: dict[str, list] = {}
    host: list = []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                dest = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if dest is None:
                    continue
                rows = dest.setdefault(plane.name, [])
                for ev in line.events:
                    rows.append([ev.start_ns, ev.start_ns + ev.duration_ns,
                                 op_name(ev.name)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        host.append([ev.start_ns,
                                     ev.start_ns + ev.duration_ns, ev.name])
    return Trace(ops=ops, modules=modules, host=host)


# ------------------------------------------------------------- reductions

def union_ns(intervals) -> float:
    total, end = 0.0, None
    for s, e in sorted((i[0], i[1]) for i in intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def busy_s(trace: Trace) -> float:
    """Seconds in which an op ran, averaged over the devices traced."""
    per = [union_ns(trace.clipped(evs)) for evs in trace.ops.values()]
    return sum(per) / len(per) * 1e-9 if per else 0.0


def family_s(trace: Trace, family) -> float:
    """Device seconds of a kernel family, summed over devices: a key of
    :data:`FAMILIES`, or any other regex string or compiled pattern,
    searched in each op's name."""
    pat = re.compile(FAMILIES.get(family, family))
    return sum(e - s for evs in trace.ops.values()
               for s, e, name in trace.clipped(evs)
               if pat.search(name)) * 1e-9


def programs(trace: Trace) -> list[tuple[str, float]]:
    """(kind, seconds) of every device program that lies wholly in the
    window and runs an attention kernel, its kind (``prefill`` or
    ``decode``) that of the dispatch span that launched it."""
    lo, hi = trace.window()
    spans = sorted((h[0], h[2][len(DISPATCH):]) for h in trace.host
                   if h[2].startswith(DISPATCH))
    if not spans:
        return []
    attn = FAMILIES["attn"]
    out = []
    for dev, mods in trace.modules.items():
        ops = sorted(trace.ops.get(dev, []))
        op_starts = [o[0] for o in ops]
        for s, e, _name in mods:
            if s < lo or e > hi:
                continue
            inside = ops[bisect.bisect_left(op_starts, s):
                         bisect.bisect_right(op_starts, e)]
            if any(attn.search(o[2]) for o in inside):
                kind = min(spans, key=lambda sp: abs(sp[0] - s))[1]
                out.append((kind, (e - s) * 1e-9))
    return out


def self_times(events) -> dict[str, float]:
    """Nanoseconds of self time per op kind: each op's interval less
    the intervals of the ops nested in it."""
    tot: dict[str, float] = {}
    stack: list[list] = []           # [end, kind] of the open ancestors
    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        kind = op_kind(name)
        tot[kind] = tot.get(kind, 0.0) + (e - s)
        if stack:
            end, parent = stack[-1]
            tot[parent] -= min(e, end) - s
        stack.append([e, kind])
    return tot


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` op kinds with the most device self time, in seconds,
    summed over devices."""
    tot: dict[str, float] = {}
    for evs in trace.ops.values():
        for k, v in self_times(trace.clipped(evs)).items():
            tot[k] = tot.get(k, 0.0) + v
    ranked = sorted(tot.items(), key=lambda kv: (-kv[1], kv[0]))
    return [[k, v * 1e-9] for k, v in ranked[:n]]


def idle_gaps(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` longest gaps between device ops in the window, each
    named by the innermost host span (``bench.*`` or ``engine.*``)
    around its midpoint."""
    lo, hi = trace.window()
    gaps = []
    for evs in trace.ops.values():
        end = lo
        for s, e, *_ in sorted(trace.clipped(evs)):
            if s > end:
                gaps.append((s - end, end, s))
            end = max(end, e)
        if hi > end:
            gaps.append((hi - end, end, hi))
    gaps.sort(key=lambda g: (-g[0], g[1]))
    spans = [h for h in trace.host if h[2] != WINDOW]
    out = []
    for length, s, e in gaps[:n]:
        mid = (s + e) / 2
        around = [h for h in spans if h[0] <= mid <= h[1]]
        name = (min(around, key=lambda h: h[1] - h[0])[2] if around
                else "no benchmark span")
        out.append([name, length * 1e-9])
    return out
