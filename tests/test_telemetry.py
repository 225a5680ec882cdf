"""Telemetry subsystem: the metrics registry, per-request span
tracing, Chrome-trace export + validation, the flight recorder, and
the clock/stat-shim contracts the serving stack now routes through.

The load-bearing invariants:

- every submitted request produces exactly ONE terminal span and one
  archived ``Trace`` whose stamps are monotonic on the shared clock —
  including traces that cross the prefill->decode worker boundary
  inside a ``KVHandoff`` (and survive chaos-dropped handoffs);
- the exported trace document validates: per-row monotone nested
  spans, paired handoff flows, no duplicate request spans;
- the legacy dict readouts (``fault_stats()``, ``Cluster.stats()``,
  ``chaos.stats()``) keep their frozen shapes while reading the
  registry underneath.
"""

import json

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.runtime.chaos import ChaosConfig, ChaosInjector
from repro.runtime.cluster import Cluster, ClusterConfig
from repro.runtime.engine import (ST_FAILED, ST_OK, Engine, EngineConfig,
                                  Request)
from repro.runtime.fault_tolerance import LatencyTracker
from repro.runtime.telemetry import (REQUESTS_PID, SCHED_TID,
                                     FlightRecorder, MetricsRegistry,
                                     Telemetry, Trace, Tracer, lane_tid,
                                     validate_chrome_trace)


def tiny_cfg(**kw):
    base = dict(num_layers=2, d_model=64, d_ff=128,
                compute_dtype="float32")
    base.update(kw)
    return get_config("qwen3-1.7b", tiny=True).replace(**base)


def ecfg(**kw):
    base = dict(num_slots=4, block_size=8, max_seq_len=96,
                prefill_chunk=16)
    base.update(kw)
    return EngineConfig(**base)


def reqs_for(cfg, n, seed=0, max_new=4):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(1, cfg.vocab_size,
                                    int(rng.integers(8, 20))
                                    ).astype(np.int32),
                    max_new_tokens=max_new) for i in range(n)]


# --------------------------------------------------------------- registry

class TestRegistry:
    def test_counter_gauge_histogram_basics(self):
        reg = MetricsRegistry()
        c = reg.counter("engine.prefill.chunks")
        c.inc()
        c.inc(3)
        c.inc(True)                      # bool increments like 1
        assert reg.value("engine.prefill.chunks") == 5

        state = {"depth": 7}
        reg.gauge("engine.queue.depth", fn=lambda: state["depth"])
        assert reg.value("engine.queue.depth") == 7
        state["depth"] = 2               # callback reads live state
        assert reg.value("engine.queue.depth") == 2

        h = reg.histogram("engine.tick.latency")
        for v in [0.1, 0.2, 0.3]:
            h.observe(v)
        assert h.count == 3
        assert reg.value("engine.tick.latency")["p50_s"] == \
            pytest.approx(0.2)

    def test_get_or_create_is_idempotent(self):
        reg = MetricsRegistry()
        assert reg.counter("a.b") is reg.counter("a.b")
        assert reg.gauge("a.g") is reg.gauge("a.g")

    def test_kind_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("a.b")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("a.b")

    def test_scope_prefixes_and_identity(self):
        reg = MetricsRegistry()
        s = reg.scope("prefill0")
        s.counter("engine.handoff.exported").inc(2)
        assert "prefill0.engine.handoff.exported" in reg
        assert s.value("engine.handoff.exported") == 2
        ident = reg.scope("")            # standalone engine: no prefix
        ident.counter("engine.ticks").inc()
        assert reg.value("engine.ticks") == 1

    def test_snapshot_render_and_jsonl(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("cluster.handoff.bytes").inc(1024)
        reg.gauge("router.held", fn=lambda: 3)
        snap = reg.snapshot()
        assert snap == {"cluster.handoff.bytes": 1024, "router.held": 3}
        text = reg.render("cluster.")
        assert "cluster.handoff.bytes = 1024" in text
        assert "router.held" not in text
        p = tmp_path / "metrics.jsonl"
        reg.dump_jsonl(str(p), label="t0")
        reg.dump_jsonl(str(p))           # appends
        lines = [json.loads(ln) for ln in p.read_text().splitlines()]
        assert len(lines) == 2
        assert lines[0]["label"] == "t0"
        assert lines[0]["metrics"]["cluster.handoff.bytes"] == 1024
        assert "t_wall_s" in lines[1] and "label" not in lines[1]


# --------------------------------------------------------- latency tracker

class TestLatencyTracker:
    def test_empty_percentiles_are_zero(self):
        t = LatencyTracker()
        assert t.percentile(50) == 0.0
        assert t.percentile(99) == 0.0
        assert t.mean_s == 0.0
        assert t.summary() == {"count": 0, "mean_s": 0.0,
                               "p50_s": 0.0, "p99_s": 0.0}

    def test_single_sample(self):
        t = LatencyTracker()
        t.observe(0.25)
        assert t.percentile(50) == pytest.approx(0.25)
        assert t.percentile(99) == pytest.approx(0.25)
        assert t.summary()["count"] == 1
        assert t.summary()["mean_s"] == pytest.approx(0.25)

    def test_reservoir_is_deterministic(self):
        """Two trackers fed the identical stream retain the identical
        strided subsample — percentiles are a pure function of the
        observation sequence (no rng in the reservoir)."""
        rng = np.random.default_rng(0)
        stream = rng.random(3 * 4096).tolist()
        a, b = LatencyTracker(), LatencyTracker()
        for v in stream:
            a.observe(v)
            b.observe(v)
        assert a.samples == b.samples
        assert len(a.samples) < len(stream)          # it did subsample
        assert a.count == len(stream)                # but counted all
        assert a.percentile(99) == b.percentile(99)

    def test_mean_is_exact_despite_subsampling(self):
        t = LatencyTracker()
        n = 2 * 4096
        for _ in range(n):
            t.observe(0.5)
        assert t.count == n
        assert t.mean_s == pytest.approx(0.5)


# ----------------------------------------------------------------- tracer

class TestTracer:
    def test_disabled_emits_nothing(self):
        tr = Tracer(enabled=False)
        tr.complete(0, 0, "tick", 0.0, 1.0)
        tr.instant(0, 0, "fault")
        tr.counter(0, "queue", depth=3)
        tr.flow_start(0, 0, "h", 1)
        assert tr.events == []

    def test_event_shapes_and_relative_us(self):
        now = [100.0]
        tr = Tracer(clock=lambda: now[0], enabled=True)
        tr.complete(1, lane_tid(0), "decode", 100.001, 100.003, uid=7)
        ev = tr.events[0]
        assert ev["ph"] == "X" and ev["pid"] == 1
        assert ev["ts"] == pytest.approx(1000.0)     # us past t0
        assert ev["dur"] == pytest.approx(2000.0)
        assert ev["args"]["uid"] == 7
        tr.flow_start(1, SCHED_TID, "kv_handoff", 5, 100.004)
        tr.flow_end(2, SCHED_TID, "kv_handoff", 5, 100.005)
        s, f = tr.events[1], tr.events[2]
        assert (s["ph"], f["ph"]) == ("s", "f")
        assert s["id"] == f["id"] == 5 and s["cat"] == "handoff"

    def test_ring_bound_counts_drops(self):
        tr = Tracer(enabled=True, max_events=2)
        for i in range(5):
            tr.instant(0, 0, f"e{i}", t=float(i))
        assert len(tr.events) == 2 and tr.dropped == 3
        assert tr.export()["metadata"]["dropped_events"] == 3

    def test_export_includes_track_names(self, tmp_path):
        tr = Tracer(enabled=True)
        tr.process_name(0, "prefill0")
        tr.thread_name(0, lane_tid(2), "slot2")
        tr.instant(0, SCHED_TID, "tick", t=tr._t0)
        p = tmp_path / "trace.json"
        doc = tr.export(str(p))
        metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert {m["name"] for m in metas} == {"process_name",
                                              "thread_name"}
        assert json.loads(p.read_text()) == doc      # file round-trips

    def test_span_is_one_complete_event_when_tracing(self):
        tel = Telemetry(tracing=True)
        with tel.span("engine.admit", 3, tick=7):
            pass
        (ev,) = tel.tracer.events
        assert (ev["ph"], ev["pid"], ev["tid"], ev["name"]) == \
            ("X", 3, SCHED_TID, "engine.admit")
        assert ev["args"] == {"tick": 7} and ev["dur"] >= 0

    def test_span_untraced_is_only_an_annotation(self):
        tel = Telemetry()
        span = tel.span("engine.admit", 3)
        assert isinstance(span, jax.profiler.TraceAnnotation)
        with span:
            pass
        assert tel.tracer.events == []

    def test_flow_ids_are_unique_per_export(self):
        tr = Tracer(enabled=True)
        assert tr.next_flow_id() != tr.next_flow_id()


# ------------------------------------------------------------- validation

def _span(pid, tid, name, ts, dur, **args):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name,
            "ts": ts, "dur": dur, "args": args}


class TestValidateChromeTrace:
    def test_valid_nested_doc_passes(self):
        doc = {"traceEvents": [
            _span(REQUESTS_PID, 1, "request", 0.0, 100.0, uid=1),
            _span(REQUESTS_PID, 1, "queued", 0.0, 10.0, uid=1),
            _span(REQUESTS_PID, 1, "decode", 10.0, 90.0, uid=1),
            _span(0, lane_tid(0), "prefill_chunk", 1.0, 5.0, uid=1),
            _span(1, lane_tid(0), "decode", 20.0, 5.0, uid=1),
            {"ph": "s", "cat": "handoff", "id": 1, "pid": 0, "tid": 0,
             "name": "kv_handoff", "ts": 8.0, "args": {}},
            {"ph": "f", "bp": "e", "cat": "handoff", "id": 1, "pid": 1,
             "tid": 0, "name": "kv_handoff", "ts": 9.0, "args": {}},
        ]}
        st = validate_chrome_trace(doc, require_boundary=True)
        assert st["requests"] == 1 and st["flows"] == 1
        assert st["boundary_requests"] == 1          # pids {0, 1}

    def test_overlapping_spans_raise(self):
        doc = {"traceEvents": [_span(0, 0, "a", 0.0, 10.0),
                               _span(0, 0, "b", 5.0, 10.0)]}
        with pytest.raises(ValueError, match="overlaps"):
            validate_chrome_trace(doc)

    def test_duplicate_request_span_raises(self):
        doc = {"traceEvents": [
            _span(REQUESTS_PID, 1, "request", 0.0, 1.0, uid=1),
            _span(REQUESTS_PID, 1, "request", 5.0, 1.0, uid=1)]}
        with pytest.raises(ValueError, match="multiple terminal"):
            validate_chrome_trace(doc)

    def test_orphan_flow_raises(self):
        doc = {"traceEvents": [
            {"ph": "s", "cat": "handoff", "id": 9, "pid": 0, "tid": 0,
             "name": "kv_handoff", "ts": 0.0, "args": {}}]}
        with pytest.raises(ValueError, match="orphan"):
            validate_chrome_trace(doc)

    def test_negative_ts_and_unknown_phase_raise(self):
        with pytest.raises(ValueError, match="negative ts"):
            validate_chrome_trace(
                {"traceEvents": [_span(0, 0, "a", -1.0, 1.0)]})
        with pytest.raises(ValueError, match="unknown event phase"):
            validate_chrome_trace(
                {"traceEvents": [{"ph": "Z", "ts": 0.0}]})

    def test_require_boundary(self):
        doc = {"traceEvents": [_span(0, 0, "decode", 0.0, 1.0, uid=1)]}
        validate_chrome_trace(doc)                   # fine un-required
        with pytest.raises(ValueError, match="boundary"):
            validate_chrome_trace(doc, require_boundary=True)


# --------------------------------------------------------- flight recorder

class TestFlightRecorder:
    def test_ring_is_bounded(self):
        fr = FlightRecorder(capacity=4)
        for i in range(10):
            fr.record(tick=i)
        assert len(fr) == 4 and fr.recorded == 10
        assert [r["tick"] for r in fr.dump()] == [6, 7, 8, 9]


# ----------------------------------------------------- engine trace facts

class TestEngineTracing:
    def test_every_request_one_terminal_monotonic_trace(self):
        cfg = tiny_cfg()
        tel = Telemetry(tracing=True)
        eng = Engine(cfg, engine=ecfg(), telemetry=tel)
        reqs = reqs_for(cfg, 5)
        out = eng.generate(reqs)
        assert all(c.status == ST_OK for c in out)
        assert sorted(tel.traces) == [r.uid for r in reqs]
        for tr in tel.traces.values():
            tr.assert_monotonic()
            ph = tr.phases()
            assert ph[0] == "submit" and ph[-1] == "terminal"
            assert ph.count("terminal") == 1         # exactly one
            assert tr.status == ST_OK
            assert "admit" in ph and "first_token" in ph
            assert ph.count("prefill_chunk") >= 1
            assert ph.count("decode_tick") >= 1

        doc = tel.tracer.export()
        st = validate_chrome_trace(doc)
        assert st["requests"] == len(reqs)           # one span per uid
        assert st["spans"] > 0 and st["tracks"] > 1
        # the scheduler row holds one engine.step span a tick, with the
        # tick's phases nested inside it (validated above)
        sched = [e for e in doc["traceEvents"] if e["ph"] == "X"
                 and (e["pid"], e["tid"]) == (0, SCHED_TID)]
        steps = [e for e in sched if e["name"] == "engine.step"]
        assert [e["args"]["tick"] for e in steps] == \
            list(range(1, eng.fault_stats()["ticks"] + 1))
        assert {"engine.housekeep", "engine.admit", "engine.prefill.prepare",
                "engine.dispatch.prefill", "engine.wait.prefill",
                "engine.prefill.commit", "engine.decode.prepare",
                "engine.dispatch.decode", "engine.wait.decode",
                "engine.decode.commit", "engine.retire"} <= \
            {e["name"] for e in sched}

    def test_untraced_engine_archives_nothing(self):
        cfg = tiny_cfg()
        tel = Telemetry(tracing=False)
        eng = Engine(cfg, engine=ecfg(), telemetry=tel)
        eng.generate(reqs_for(cfg, 3))
        assert tel.traces == {}
        assert tel.tracer.events == []

    def test_injected_clock_drives_stamps(self):
        """Satellite (a): ONE injectable monotonic clock.  A fake
        clock handed to Telemetry is what every stamp reads."""
        cfg = tiny_cfg()
        now = [1000.0]
        tel = Telemetry(tracing=True, clock=lambda: now[0])
        eng = Engine(cfg, engine=ecfg(), telemetry=tel)
        eng.submit(reqs_for(cfg, 1)[0])
        now[0] = 1001.0
        while eng.pending:
            eng.step()
            now[0] += 1.0
        (tr,) = tel.traces.values()
        assert tr.submit_t == 1000.0
        assert tr.last_t > 1000.0 and tr.last_t <= now[0]

    def test_fault_stats_shim_shape_and_registry_agree(self):
        cfg = tiny_cfg()
        eng = Engine(cfg, engine=ecfg(),
                     chaos=ChaosConfig(seed=0))
        eng.generate(reqs_for(cfg, 2))
        fs = eng.fault_stats()
        assert set(fs) >= {"ticks", "cancelled", "deadline_expired",
                           "shed", "failed", "starvation_pins",
                           "alloc_faults_absorbed", "nan_rows_detected",
                           "corruptions_detected", "quarantines",
                           "slow_ticks", "tick_p50_s", "tick_p99_s",
                           "tick_mean_s", "chaos_seed"}
        # counter attributes ARE registry views: writes through the
        # legacy attribute land in the store and vice versa
        eng.shed += 2
        assert eng.metrics.value("engine.lifecycle.shed") == 2
        assert eng.fault_stats()["shed"] == 2
        assert eng.metrics.value("engine.ticks") == fs["ticks"]

    def test_failed_request_artifact_carries_flight_and_trace(
            self, tmp_path):
        """Flight recorder + trace ride the chaos replay artifact on
        any ``failed`` terminal — the post-mortem black box."""
        cfg = tiny_cfg()
        tel = Telemetry(tracing=True)
        eng = Engine(cfg, engine=ecfg(num_slots=1, quarantine_ticks=1,
                                      replay_dir=str(tmp_path)),
                     telemetry=tel, chaos=ChaosConfig(seed=2,
                                                      nan_rate=1.0))
        out = eng.generate(reqs_for(cfg, 1))
        assert out[0].status == ST_FAILED
        (art,) = eng.replay_artifacts
        assert art["flight_recorder"], "flight ring missing"
        assert {"tick", "queue_depth", "live_slots",
                "free_pages"} <= set(art["flight_recorder"][-1])
        assert art["trace"]["uid"] == 0
        phases = [s["phase"] for s in art["trace"]["stamps"]]
        assert "fault" in phases
        (tr,) = tel.traces.values()
        assert tr.status == ST_FAILED


# ---------------------------------------------------- cluster trace facts

class TestClusterTracing:
    def _cluster(self, cfg, tel, params=None, chaos=None):
        return Cluster(cfg, params=params,
                       cluster=ClusterConfig(2, 2), engine=ecfg(),
                       telemetry=tel, chaos=chaos)

    def test_cross_boundary_timeline_is_contiguous(self):
        cfg = tiny_cfg()
        tel = Telemetry(tracing=True)
        clu = self._cluster(cfg, tel)
        out = clu.generate(reqs_for(cfg, 6))
        assert all(c.status == ST_OK for c in out)
        st = validate_chrome_trace(tel.tracer.export(),
                                   require_boundary=True)
        assert st["boundary_requests"] == 6
        assert st["flows"] == clu.handoffs
        for tr in tel.traces.values():
            tr.assert_monotonic()                    # across workers!
            ph = tr.phases()
            assert "route" in ph
            assert "handoff_export" in ph and "handoff_import" in ph
            assert ph.index("handoff_export") < ph.index(
                "handoff_import")

    def test_dropped_handoffs_leave_no_orphan_flows(self):
        """Chaos migration drops: the dropped export's flow closes at
        the drop site (``dropped=True``), the retry opens a fresh flow
        id, and every request still ends with ONE terminal span."""
        cfg = tiny_cfg()
        tel = Telemetry(tracing=True)
        clu = self._cluster(cfg, tel,
                            chaos=ChaosConfig(seed=11,
                                              migration_fail_rate=0.5))
        out = clu.generate(reqs_for(cfg, 5))
        assert clu.migration_faults > 0              # the site fired
        assert all(c.status == ST_OK for c in out)
        doc = tel.tracer.export()
        st = validate_chrome_trace(doc, require_boundary=True)
        assert st["requests"] == 5                   # one terminal each
        dropped = [e for e in doc["traceEvents"]
                   if e["ph"] == "f" and e["args"].get("dropped")]
        assert len(dropped) == clu.migration_faults
        for tr in tel.traces.values():
            ph = tr.phases()
            assert ph.count("terminal") == 1
            # every export either dropped in transit or was imported
            assert ph.count("handoff_export") == \
                ph.count("handoff_dropped") + ph.count("handoff_import")

    def test_cluster_stats_shim_reads_registry(self):
        cfg = tiny_cfg()
        tel = Telemetry()
        clu = self._cluster(cfg, tel)
        clu.generate(reqs_for(cfg, 4))
        cs = clu.stats()
        reg = tel.registry
        assert cs["handoffs"] == reg.value("cluster.handoff.delivered")
        assert cs["handoff_bytes"] == reg.value("cluster.handoff.bytes")
        assert cs["ticks"] == reg.value("cluster.ticks")
        # per-worker scopes landed in the one store
        assert any(k.startswith("prefill0.engine.") for k in reg.keys())
        assert any(k.startswith("decode0.engine.") for k in reg.keys())

    def test_workers_share_one_clock(self):
        cfg = tiny_cfg()
        tel = Telemetry()
        clu = self._cluster(cfg, tel)
        assert all(w._clock is tel.clock
                   for w in clu.prefill + clu.decode)
