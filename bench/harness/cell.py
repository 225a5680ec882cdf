"""One run of one cell: set-up, the measured window, the metrics, and
the check of what the window served against the reference.

Set-up (counted in ``setup_s``, from process start to the window):
weights from the seed, the Engine, and the warm-up of every program
shape the cell's traffic can reach.  The window then runs for the given
seconds with nothing left to compile.  After it: the device's peak
memory is read, requests are followed to their end where the mix says
so, the Engine is freed, and the reference checks a sample of what was
served.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import tempfile
import time
from pathlib import Path

import jax
import numpy as np

from bench.harness import adapter, loop, reference, warmup, weights
from bench.harness.peaks import Peaks, peaks_for
from bench.harness.spec import Cell, reader

TRACE_SECONDS = 10.0     # the traced run traces the window's last seconds
KEEP_SECONDS = 2.0       # what ``keep_trace`` writes: long enough to hold
                         # a prefill and a decode program
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class NoDevice(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


def check_devices(cell: Cell) -> Peaks:
    """The peaks of the chips the cell runs on; raises ``NoDevice``
    without a TPU or with fewer chips than the cell asks for, and
    ``KeyError`` for a chip missing from the table of peaks."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < cell.chips:
        raise NoDevice(f"cell {cell.name} needs {cell.chips} chips, "
                       f"JAX found {len(devs)}")
    return peaks_for(devs[0].device_kind)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading
    from the persistent cache), and how many programs it compiled."""

    def __init__(self):
        self.seconds = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            self.events += 1


@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    cell: Cell
    shapes: object                 # arch.shapes(config)
    peaks: Peaks | None
    setup_s: float
    setup_compile_s: float
    window: loop.Window
    sent: list
    # the change of every Engine counter over the traced span, by name
    counters: dict = dataclasses.field(default_factory=dict)
    dispatches: list = dataclasses.field(default_factory=list)
    trace: object = None           # layer_metrics/_reduce.Trace

    @property
    def arch(self):
        """The architecture module: the work counts over ``shapes``."""
        return self.cell.arch


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` when set,
    else a fixed directory inside the checkout."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".cache" / "bench" / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def build_engine(cell: Cell, seed: int):
    """Weights from the seed and the Engine over them; returns
    (engine, params, split seconds).  A configuration file with
    ``"preset": "tiny"`` runs the program's tiny preset (tests only)."""
    from repro.configs import get_config
    from repro.models import api as mapi
    from repro.runtime.engine import Engine, EngineConfig

    config = cell.config
    cfg = get_config(config["model"], tiny=config.get("preset") == "tiny")
    cell.arch.check_widths(cfg, config)
    rep = config["representation"]
    t = time.perf_counter()
    params = weights.make_params(mapi.get_model(cfg).abstract_params(),
                                 config, seed, cell.arch)
    jax.block_until_ready(params)
    t_w = time.perf_counter() - t
    kv_codes = rep["kv"] == "exp7"
    eng = Engine(cfg, params=params, engine=EngineConfig(**config["engine"]),
                 kv_dtype="float32" if kv_codes else rep["kv"],
                 kv_codes=kv_codes)
    return eng, params, {"weights_s": t_w,
                         "engine_s": time.perf_counter() - t - t_w}


def _sample(sent, n: int, seed: int):
    """The longest finished request and ``n - 1`` others drawn from the
    seed."""
    done = [s for s in sent if s.ok and s.tokens is not None
            and len(s.tokens)]
    if not done:
        return []
    longest = max(done, key=lambda s: (len(s.tokens), s.uid))
    rest = [s for s in done if s is not longest]
    rng = np.random.default_rng([int(seed), 2])
    pick = rng.choice(len(rest), min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def gap_numbers(gaps: list) -> dict:
    """The numbers compared from per-token logit gaps: the widest and
    the mean over every token checked."""
    g = np.concatenate(gaps) if gaps else np.zeros(1)
    return {"logit_gap": float(np.max(g)),
            "mean_logit_gap": float(np.mean(g))}


def check(params, cell: Cell, sent, seed: int, control: bool) -> dict:
    """The reference-logit gaps of the served tokens of a sample of
    finished requests (and, with ``control``, of the control's own
    tokens), reduced by :func:`gap_numbers`."""
    length = int(cell.config["engine"]["max_seq_len"])
    gaps = {"served": [], "control": []}
    sample = _sample(sent, int(cell.mix["check_requests"]), seed)
    for s in sample:
        for k, v in reference.served_gaps(cell.arch.forward, params,
                                          cell.config, s.prompt, s.tokens,
                                          length, control=control).items():
            gaps[k].append(v)
    out = {"requests": len(sample),
           "tokens": sum(len(s.tokens) for s in sample),
           "served": gap_numbers(gaps["served"])}
    if control:
        out["control"] = gap_numbers(gaps["control"])
    return out


def judge(numbers: dict, limits: dict, failed: int,
          requests: int) -> tuple[bool, dict]:
    """``correct`` and what it was decided on, each number beside its
    limit: every gap number the cell's limits file names at most its
    limit, no failed request, at least one request checked."""
    checks = {k: {"value": numbers[k], "limit": v} for k, v in limits.items()}
    checks["failed_requests"] = {"value": failed, "limit": 0}
    checks["checked_requests"] = {"value": requests, "limit": 1}
    correct = (all(numbers[k] <= v for k, v in limits.items())
               and failed == 0 and requests >= 1)
    return bool(correct), checks


def _counters(eng) -> dict:
    """Every counter in the Engine's metrics registry, by name."""
    reg = eng.telemetry.registry
    return {k: reg.get(k).value for k in reg.keys()
            if reg.get(k).kind == "counter"}


def _read(cell: Cell, run: Run, metrics: list[dict], kind: str) -> dict:
    out = {}
    for m in metrics:
        v = reader(cell.root, kind, m["name"]).read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, control: bool = False,
             peaks: Peaks | None = None, clock: CompileClock | None = None,
             keep_trace: str | None = None, log=print) -> dict:
    """One run; returns the result line's fields (``checks`` last).
    ``control`` also judges the control on the same sample
    (``control_correct``);
    ``keep_trace`` writes the first seconds of the reduced trace there."""
    clock = clock or CompileClock()
    compile_s0 = clock.seconds
    eng, params, split = build_engine(cell, seed)
    traffic = cell.generator().make(cell.mix, seed, eng.cfg.vocab_size)
    t = time.perf_counter()
    programs = warmup.warm(eng, traffic.prompt_range, traffic.output_range)
    split["warmup_s"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start
    setup_compile_s = clock.seconds - compile_s0
    split["before_s"] = setup_s - sum(split.values())
    log(f"setup split: {' '.join(f'{k} {v:.3f}' for k, v in split.items())}"
        f" programs {programs} compile_or_load_s {setup_compile_s:.3f}")

    dlog = adapter.DispatchLog(eng) if trace else None
    state = {}
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None

    def on_tick(now, w):
        if state or now < w.t0 + seconds - min(TRACE_SECONDS, seconds):
            return
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        state["ann"] = jax.profiler.TraceAnnotation("bench.window")
        state["ann"].__enter__()
        state["counters0"] = _counters(eng)
        dlog.recording = True

    compiles0 = clock.events
    cl = cell.loop().make(eng, traffic, seconds,
                          follow=bool(cell.mix.get("follow_to_end")))
    window = cl.run(on_tick if trace else None)
    compiles = clock.events - compiles0
    run = Run(cell=cell, shapes=cell.arch.shapes(cell.config), peaks=peaks,
              setup_s=setup_s, setup_compile_s=setup_compile_s, window=window,
              sent=cl.sent)
    if trace:
        dlog.recording = False
        run.counters = {k: v - state["counters0"].get(k, 0)
                        for k, v in _counters(eng).items()}
        state["ann"].__exit__(None, None, None)
        jax.profiler.stop_trace()
        dlog.close()
        run.dispatches = dlog.dispatches
    log(f"compiles in window: {compiles}")
    dev = jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    cl.follow_to_end()
    del eng, cl.eng, dlog
    gc.collect()

    if trace:
        reduce = reader(cell.root, "layer_metrics", "_reduce")
        files = list(Path(trace_dir).rglob("*.xplane.pb"))
        run.trace = reduce.from_xplane(str(files[0]))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if keep_trace:
            with open(keep_trace, "w") as f:
                json.dump(reduce.head(run.trace, KEEP_SECONDS).to_json(), f)

    metrics = _read(cell, run, cell.per_layer if trace else cell.end_to_end,
                    "layer_metrics" if trace else "end_to_end")
    chk = check(params, cell, cl.sent, seed, control)
    attempted = len(cl.sent)
    failed = sum(1 for s in cl.sent if s.status is not None and not s.ok)
    if cl.follow:
        failed += sum(1 for s in cl.sent if s.status is None)
    limits = cell.limits()
    correct, checks = judge(chk["served"], limits, failed, chk["requests"])
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    out = {"correct": correct, "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduce.busy_s(run.trace)
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": reduce.top_ops(run.trace),
                            "idle_gaps": reduce.idle_gaps(run.trace)}
    out["gaps"] = chk["served"]
    if control:
        # the control, put in the program's place, judged by the same
        # comparison on the same sample
        out["control_gaps"] = chk["control"]
        out["control_correct"], out["control_checks"] = judge(
            chk["control"], limits, 0, chk["requests"])
    out["checked_tokens"] = chk["tokens"]
    out["checks"] = checks
    return out
