"""The Engine's phase spans on the profiler's clock, its prefill
counter and the names of its jitted steps.

- A tiny Engine serves a few requests under a profiler session on the
  CPU: the trace holds every phase span, each inside an
  ``engine.step``, and every read of a dispatch's results happens
  inside an ``engine.wait.*`` span.
- ``engine.prefill.dispatched_tokens`` counts rows x width of every
  prefill dispatch.
- The jitted steps carry stable names, so a trace names each program.
- The benchmark's reductions read on ``data/trace_head.json.gz``
  exactly what they read before the Engine had spans."""

import contextlib
import gzip
import json
import types
from pathlib import Path

import jax
import numpy as np
import pytest

from bench.harness.spec import load_module
from repro.configs import get_config
from repro.runtime import engine as engine_mod
from repro.runtime.engine import ST_OK, Engine, EngineConfig, Request

BENCH = Path(__file__).resolve().parents[1]
R = load_module(BENCH / "layer_metrics" / "_reduce.py", "_reduce")
RECORDED = Path(__file__).parent / "data" / "trace_head.json.gz"

PHASES = ["engine.step", "engine.housekeep", "engine.admit",
          "engine.prefill.prepare", "engine.decode.prepare",
          "engine.dispatch.prefill", "engine.dispatch.decode",
          "engine.dispatch.verify", "engine.wait.prefill",
          "engine.wait.decode", "engine.wait.verify",
          "engine.prefill.commit", "engine.decode.commit", "engine.retire"]


def _load(path):
    with gzip.open(path) as f:
        return R.Trace.from_json(json.load(f))


def _engine_spans(path):
    """Every ``engine.*`` host event of a profiler trace file, as
    ``[start_ns, end_ns, name]``."""
    from jax.profiler import ProfileData

    return [[ev.start_ns, ev.start_ns + ev.duration_ns, ev.name]
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("engine.")]


# ------------------------------------------------ a tiny Engine, profiled

class _FirstCallDrafter:
    """Proposes the row's last token twice on its first call and nothing
    after, so a run has one verify dispatch and vanilla decodes."""

    def __init__(self):
        self.calls = 0

    def propose(self, context, k=None):
        self.calls += 1
        if self.calls > 1:
            return np.zeros((0,), np.int32)
        return np.full((min(k or 2, 2),), context[-1], np.int32)


class _NumpyWatch:
    """Stands in for ``numpy`` inside the engine module and notes every
    ``asarray``/``array`` of a dispatch's result, with the innermost
    Engine span open at the time."""

    def __init__(self, results, stack):
        self._results, self._stack = results, stack
        self.reads: list[str | None] = []

    def __getattr__(self, name):
        return getattr(np, name)

    def _note(self, a):
        if any(a is r for r in self._results):
            self.reads.append(self._stack[-1] if self._stack else None)

    def asarray(self, a, *args, **kw):
        self._note(a)
        return np.asarray(a, *args, **kw)

    def array(self, a, *args, **kw):
        self._note(a)
        return np.array(a, *args, **kw)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Serve requests of mixed lengths through a tiny Engine with
    speculation under a profiler session; returns what was observed."""
    cfg = get_config("qwen3-1.7b", tiny=True).replace(
        num_layers=2, d_model=64, d_ff=128, compute_dtype="float32")
    eng = Engine(cfg, engine=EngineConfig(num_slots=4, block_size=8,
                                          max_seq_len=96, prefill_chunk=16,
                                          spec_k=2))
    eng.drafter = _FirstCallDrafter()
    results, stack, widths = [], [], []

    def watched(step, kind):
        def call(*args):
            if kind == "prefill":
                widths.append(args[1].shape)
            out = step(*args)
            results.extend(jax.tree_util.tree_leaves(out))
            return out
        return call

    eng._prefill = watched(eng._prefill, "prefill")
    eng._decode = watched(eng._decode, "decode")
    eng._spec_verify = watched(eng._spec_verify, "verify")
    span = eng._span

    @contextlib.contextmanager
    def tracked(name, **args):
        with span(name, **args):
            stack.append(name)
            try:
                yield
            finally:
                stack.pop()

    eng._span = tracked
    watch = _NumpyWatch(results, stack)
    rng = np.random.default_rng(0)
    for uid, n in enumerate([5, 20, 11, 30, 7, 14]):
        eng.submit(Request(uid, rng.integers(1, cfg.vocab_size, n)
                           .astype(np.int32), max_new_tokens=6))
    trace_dir = tmp_path_factory.mktemp("profile")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    real_np = engine_mod.np
    engine_mod.np = watch
    try:
        with jax.profiler.trace(str(trace_dir), profiler_options=opts):
            done = eng.run()
    finally:
        engine_mod.np = real_np
    (xplane,) = trace_dir.rglob("*.xplane.pb")
    return types.SimpleNamespace(eng=eng, done=done, reads=watch.reads,
                                 widths=widths,
                                 spans=_engine_spans(str(xplane)))


def test_served_every_request(served):
    assert len(served.done) == 6
    assert all(c.status == ST_OK for c in served.done)
    assert served.eng.spec_dispatches == 1


def test_trace_holds_every_phase_span(served):
    assert {h[2] for h in served.spans} == set(PHASES)


def test_phase_spans_nest_in_a_step(served):
    host = served.spans
    steps = [h for h in host if h[2] == "engine.step"]
    assert len(steps) == served.eng._tick_no
    for s, e, name in host:
        if name != "engine.step":
            assert any(a <= s and e <= b for a, b, _ in steps), name
    commits = [h for h in host if h[2].endswith(".commit")]
    for s, e, _ in (h for h in host if h[2] == "engine.retire"):
        assert any(a <= s and e <= b for a, b, _ in commits)


def test_results_read_only_inside_a_wait(served):
    # tokens and finite flags of every dispatch, accept counts of the
    # verify, and the lengths the decode step returns
    assert len(served.reads) >= 2 * len(served.widths) + 3
    assert all(r is not None and r.startswith("engine.wait.")
               for r in served.reads), served.reads


def test_dispatched_tokens_count_rows_times_width(served):
    reg = served.eng.telemetry.registry
    dispatched = reg.value("engine.prefill.dispatched_tokens")
    assert dispatched == sum(rows * w for rows, w in served.widths)
    assert reg.get("engine.prefill.dispatched_tokens").help == \
        "rows × width computed per prefill dispatch"
    # six prompts of 5-30 tokens, four rows: padding is dispatched
    assert dispatched > reg.value("engine.prefill.tokens") == \
        5 + 20 + 11 + 30 + 7 + 14


@pytest.mark.parametrize("build, name", [
    (engine_mod._jit_prefill, "prefill_step"),
    (engine_mod._jit_decode, "decode_step"),
    (engine_mod._jit_spec_verify, "spec_verify_step")])
def test_steps_carry_program_names(build, name):
    # the trace names the program jit_<name>(...) instead of jit_fn
    assert build(lambda *a: a).__name__ == name


# ------------------------------------------------- the benchmark's reductions

def test_existing_reductions_unchanged():
    t = _load(RECORDED)
    assert t.window_s == 2.0
    assert R.busy_s(t) == 1.97528615
    assert R.family_s(t, "wmatmul") == 0.962745807
    assert R.family_s(t, "attn") == 0.638535273
    assert R.programs(t) == [
        ("prefill", 1.3889453550000002), ("decode", 0.081215223),
        ("prefill", 0.20436457100000002), ("decode", 0.081185648),
        ("decode", 0.081191813), ("decode", 0.081186433)]
    assert R.top_ops(t) == [
        ["flash_prefill_paged_codes_kernel", 0.638535273],
        ["lut_dequant_matmul_dual_kernel", 0.573921792],
        ["lut_dequant_matmul_dual_gated_kernel", 0.375432751],
        ["fusion", 0.08968411700000001],
        ["dynamic-slice_bitcast_fusion", 0.086970081],
        ["bitcast_dynamic-update-slice_fusion", 0.082331545],
        ["copy", 0.07649523700000001],
        ["constant_dynamic-slice_fusion", 0.016935614],
        ["constant_dynamic-update-slice_fusion", 0.013808323],
        ["lut_dequant_matmul_kernel", 0.013391264000000002]]
    assert R.idle_gaps(t) == [
        ["bench.engine_step", 0.003961938],
        ["bench.engine_step", 0.003872026],
        ["bench.engine_step", 0.0038700680000000004],
        ["bench.engine_step", 0.0037998890000000003],
        ["bench.engine_step", 0.0037778250000000003],
        ["bench.engine_step", 0.0037592600000000004],
        ["bench.engine_step", 0.0015634890000000002],
        ["bench.engine_step", 7.2772e-05],
        ["bench.engine_step", 1.015e-06],
        ["bench.engine_step", 9.100000000000001e-07]]
