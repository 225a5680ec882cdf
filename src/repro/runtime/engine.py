"""Serving engine: continuous batching over a paged, prefix-cached KV
cache, with chunked flash prefill.

The old ``InferenceServer.generate`` was a synchronous, length-bucketed
batch call over a contiguous ``[B, max_len, n_kv, hd]`` cache: every
request paid ``O(max_len)`` HBM on admission, every request in a bucket
decoded ``max(max_new_tokens)`` steps, and nothing could join or retire
mid-decode.  The :class:`Engine` replaces that with

- ``submit(request) -> handle``: enqueue; nothing runs yet.
- ``step() -> [Completion]``: one scheduler tick — admit waiting
  requests into free decode slots, advance every admitted-but-not-yet-
  prefilled sequence by ONE prompt chunk, run ONE batched decode step
  across all decoding slots, retire finished sequences.
- ``stream(handle)``: iterator of tokens, driving ``step`` on demand.
- ``run()``: drain everything (the batch-call convenience).

KV lives in a :class:`~repro.runtime.paged_cache.PagedKVCache`; the
decode step attends through the block-table flash-decode kernel
(``decode_gqa_paged``) with the table sliced to the live column count,
so paging never materializes a contiguous cache, dead pages cost no
grid steps, and narrow KV dtypes (``float8_e4m3fn``) still dequantize
in-kernel after the HBM→VMEM DMA.

Chunked flash prefill (the prompt-side twin of the same discipline):
prompts run through ``prefill_into_cache`` in fixed-size chunks of at
most ``prefill_chunk`` tokens, each chunk scattering its KV into the
pages and attending everything written so far through the
``flash_prefill_paged`` kernel — per-row absolute start offsets, online
softmax over pages, no ``[B, S, T]`` mask or score matrix anywhere.
Because the start offset is *data* (a per-row int), one full-width
dispatch per tick serves every prefilling slot at whatever progress it
has: there are no prompt-length admission buckets, a long prompt no
longer monopolizes a tick, and time-to-first-token for everyone else is
bounded by the chunk size instead of the longest queued prompt.

Prefix cache (the byte-not-moved tier): retirement *inserts* finished
sequences' pages into a radix trie
(:class:`~repro.runtime.prefix_cache.PrefixCache`) keyed by token
content instead of freeing them.  Admission walks the trie, pins the
longest cached prefix (refcount++), splices those page ids into the
new sequence's block table, and prefills only the uncached tail (the
chunk's start offset begins at the hit length; the boundary page is
copied before the first write — shared pages are never mutated).
Re-prefilling a shared system prompt thus costs zero FLOPs and zero
HBM traffic — the access is never issued, which the PuM literature
identifies as the only 1000x-class win.

Scheduling policy (FIFO with reservation-or-preempt):
- admission needs a free slot and pages for the *prompt tail only* —
  no worst-case reservation; up to ``max_batched_prefill`` queue heads
  admit per tick, all sharing the tick's single chunk dispatch;
- when the queue head cannot get its pages, the scheduler scans the
  next K=4 waiting requests and admits prefix-cache hits first (their
  spliced pages shrink the footprint), counting ``admission_reorders``;
- when the free list runs dry (admission or mid-decode growth), the
  scheduler first LRU-evicts unpinned trie pages, then preempts the
  youngest running sequence (pages released, sequence re-queued to be
  recomputed — greedy decoding makes the recompute token-identical);
- a sequence preempted ``max_preemptions`` times is *pinned* (the
  starvation guard): it can no longer be chosen as a victim, so the
  evict-then-preempt ladder cannot livelock one unlucky request;
- retirement moves pages into the trie (or frees them when the prefix
  cache is disabled).

Request lifecycle (the failure model — DESIGN.md "Failure model"):
every request ends in exactly one terminal status — ``ok``,
``cancelled`` (`Engine.cancel` works in every state: queued,
mid-prefill-chunk, mid-decode), ``deadline_exceeded``
(``Request.deadline_s`` is a wall-clock budget from submit),
``rejected`` (backpressure: a bounded submit queue sheds under
overload, policy ``reject-new`` or ``shed-oldest``), or ``failed``
(non-finite logits or KV corruption caught by the optional page
checksum audit).  Termination from any state frees the slot's pages
and decrements prefix-trie pins, so ``audit_partition`` holds after
every transition.  ``result``/``stream`` answer honestly for every
terminal handle — a shed request yields an empty ``rejected``
completion instead of ``None`` or a hang.

Faults (see :mod:`repro.runtime.chaos`): the jitted steps return
per-row ``isfinite`` flags, so a NaN/Inf logits row fails only that
request (replay artifact dumped, slot lane quarantined for a few
ticks, batch keeps running); the checksum audit verifies every
written page's CRC before the next dispatch; a seeded
:class:`~repro.runtime.chaos.ChaosInjector` can force each fault
deterministically.  Per-tick latency feeds a
:class:`~repro.runtime.fault_tolerance.StragglerWatchdog` and a
percentile tracker (``BENCH_serving.json`` reports p50/p99, not just
means).  ``snapshot()``/``restore()`` rebuild the bookkeeping after a
simulated crash: device KV is lost, every in-flight request re-queues
to re-prefill prompt + tokens-so-far, and greedy decoding reproduces
token-identical completions — the handoff primitive the
prefill/decode disaggregation item needs.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import time
import warnings
from collections import deque
from typing import Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import lama_layers as ll
from repro.models import api as mapi
from repro.runtime.chaos import ChaosConfig, ChaosInjector
from repro.runtime.drafter import PromptLookupDrafter
from repro.runtime.fault_tolerance import LatencyTracker, StragglerWatchdog
from repro.runtime.paged_cache import TRASH_PAGE, PagedKVCache
from repro.runtime.prefix_cache import PrefixCache, PrefixNode
from repro.runtime.telemetry import (REQUESTS_PID, SCHED_TID, FlightRecorder,
                                     Telemetry, Trace, lane_tid)

# Terminal statuses: every request ends in exactly one of these.
ST_OK = "ok"
ST_CANCELLED = "cancelled"
ST_DEADLINE = "deadline_exceeded"
ST_REJECTED = "rejected"
ST_FAILED = "failed"
TERMINAL_STATUSES = (ST_OK, ST_CANCELLED, ST_DEADLINE, ST_REJECTED,
                     ST_FAILED)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray            # [S] int32
    max_new_tokens: int = 16
    stop_token: int | None = None
    deadline_s: float | None = None  # wall-clock budget from submit()


@dataclasses.dataclass
class Completion:
    uid: int
    tokens: np.ndarray
    prefill_s: float              # this request's own prefill wall time
    decode_s: float               # wall time of the steps it was active in
    decode_steps: int = 0         # batched decode steps it participated in
    ttft_s: float = 0.0           # submit -> first token available
    queue_wait_s: float = 0.0     # submit -> first admission into a slot
    status: str = ST_OK           # terminal status (TERMINAL_STATUSES)


SHED_POLICIES = ("reject-new", "shed-oldest")

# Disaggregation roles: a "unified" engine interleaves prefill and
# decode in one tick loop (the single-host default); a "prefill" engine
# runs prompts only — when a request's last chunk lands it exports the
# KV pages as a KVHandoff instead of decoding; a "decode" engine admits
# migrated handoffs via inject_prefilled and never computes prefill.
ENGINE_ROLES = ("unified", "prefill", "decode")


@dataclasses.dataclass
class EngineConfig:
    num_slots: int = 4            # concurrent decode lanes
    block_size: int = 16          # tokens per KV page
    max_seq_len: int = 512        # per-sequence cap (prompt + generated)
    num_blocks: int | None = None  # page-pool size; None -> full occupancy
    prefix_cache: bool = True     # radix-tree KV reuse across requests
    max_batched_prefill: int = 4  # admissions per scheduler tick
    prefill_chunk: int = 256      # max prompt tokens advanced per row/tick
    max_queue: int | None = None  # waiting-queue bound; None -> unbounded
    shed_policy: str = "reject-new"  # overload: reject-new | shed-oldest
    max_preemptions: int = 3      # starvation guard: pin after N preempts
    checksum_pages: bool = False  # per-tick KV page CRC audit
    quarantine_ticks: int = 8     # lane rest after a non-finite dispatch
    replay_dir: str | None = None  # where failed-request artifacts land
    role: str = "unified"         # unified | prefill | decode (cluster)
    # Speculative decoding (prompt-lookup drafting + one verification
    # dispatch per tick).  spec_k = drafted tokens per slot per tick;
    # 0 disables the path entirely — the vanilla single-token decode
    # dispatch runs untouched.
    spec_k: int = 0
    spec_max_ngram: int = 3       # longest n-gram the drafter matches
    spec_min_ngram: int = 1       # shortest n-gram worth proposing from
    # Calibration drift guard: every N ticks re-measure per-site SQNR
    # of live traffic under the attached act-quant tables and compare
    # against the calibration report (0 disables).  Detection only —
    # a drop past drift_threshold_db logs a warning; refit is manual.
    # The report is measured on the samples the fit optimized, so
    # in-distribution traffic already sits a few dB below it
    # (generalization gap); the default leaves headroom over that.
    drift_check_every: int = 0
    drift_threshold_db: float = 6.0


@dataclasses.dataclass
class KVHandoff:
    """One finished prefill leaving a prefill worker: the request, the
    tokens sampled so far (the first token, from the final chunk's
    logits), and the raw KV page *content* in block-table order — what
    ``PagedKVCache.import_slot`` scatters into the decode worker's pool
    so decode starts without recomputing a single prompt token.
    Lifecycle stamps ride along so the merged Completion reports
    honest end-to-end TTFT/queue-wait across the worker boundary."""

    request: Request
    tokens: list[int]             # sampled so far (len 1 after prefill)
    length: int                   # KV tokens written (== prompt length)
    k_pages: np.ndarray           # [L, n_pages, bs, n_kv, hd]
    v_pages: np.ndarray
    block_size: int
    submit_t: float = 0.0
    admit_t: float | None = None
    first_token_t: float | None = None
    prefill_s: float = 0.0
    preemptions: int = 0
    source: int | None = None     # filled by the cluster: worker index
    # codes-mode pages are meaningless without the tables that decode
    # them: a CRC over the exporter's per-head attn_k/attn_v qmeta
    # (None for float pages), checked by inject_prefilled so a handoff
    # never lands in a pool keyed to different calibration tables
    kv_fingerprint: int | None = None
    # the request's Trace rides the handoff, so its timeline stays
    # contiguous across the prefill->decode worker boundary; flow_id
    # pairs the export-side trace arrow with the import side
    trace: Trace | None = None
    flow_id: int = 0

    @property
    def nbytes(self) -> int:
        """Bytes a real deployment would move across the interconnect."""
        return self.k_pages.nbytes + self.v_pages.nbytes


_QUEUED, _RUNNING, _FINISHED = "queued", "running", "finished"


# The jit wrappers are memoized per underlying model function, so every
# Engine over the same family shares one compile cache.  Greedy sampling
# happens *inside* the jitted call: one dispatch per scheduler tick
# instead of per-op host round-trips (slice + argmax) on the hot path.
# Off-CPU the view (page pools) is donated: the host adopts the returned
# arrays via update_pages, so the inputs are dead and XLA can scatter
# the new token's KV in place instead of copying the whole pool each
# tick.  (CPU lacks donation support — measured strictly slower there.)

def _donate(*argnums):
    return argnums if jax.default_backend() != "cpu" else ()


# Both wrappers also return a per-row finite flag over the logits the
# tick consumes: NaN/Inf detection must ride the same dispatch (a
# second host round-trip per tick would halve throughput), and the flag
# is what the failure model quarantines on — one poisoned row fails one
# request while the rest of the batch keeps its tokens.

@functools.lru_cache(maxsize=None)
def _jit_prefill(prefill_fn):
    def fn(params, tokens, view, start, cfg):
        logits, view = prefill_fn(params, tokens, view, cfg, start)
        last = logits[:, -1, :]
        nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
        ok = jnp.all(jnp.isfinite(last), axis=-1)
        return nxt, ok, view
    return jax.jit(fn, static_argnums=(4,), donate_argnums=_donate(2))


@functools.lru_cache(maxsize=None)
def _jit_decode(step_fn):
    def fn(params, view, tokens, active, cfg):
        logits, view = step_fn(params, view, tokens, active, cfg)
        last = logits[:, -1, :]
        nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
        ok = jnp.all(jnp.isfinite(last), axis=-1)
        return nxt, ok, view
    return jax.jit(fn, static_argnums=(4,), donate_argnums=_donate(1))


@functools.lru_cache(maxsize=None)
def _jit_spec_verify(verify_fn):
    """One speculative verify-and-commit dispatch: greedy tokens,
    accept counts, and finite flags all computed in-dispatch (same
    one-host-round-trip discipline as the decode step)."""
    def fn(params, tokens, view, start, n_tokens, cfg):
        return verify_fn(params, tokens, view, cfg, start, n_tokens)
    return jax.jit(fn, static_argnums=(5,), donate_argnums=_donate(2))


@dataclasses.dataclass
class _SeqState:
    request: Request
    seq_no: int = 0               # submission order (preemption priority)
    status: str = _QUEUED
    term: str = ST_OK             # terminal status once status==_FINISHED
    slot: int = -1
    tokens: list[int] = dataclasses.field(default_factory=list)
    next_token: int = 0
    prefix_len: int = 0           # prompt tokens served from the trie
    prefill_pos: int = 0          # tail tokens already chunk-prefilled
    prefill_done: bool = False    # all prompt chunks in the cache
    pinned: list[PrefixNode] = dataclasses.field(default_factory=list)
    preemptions: int = 0
    prefill_s: float = 0.0
    decode_s: float = 0.0
    decode_steps: int = 0
    submit_t: float = 0.0         # wall stamp at submit()
    admit_t: float | None = None  # first admission into a slot
    first_token_t: float | None = None
    # memoized trie lookup: (trie generation, prompt length, match) —
    # while the queue head stays blocked the trie only changes on
    # retire/evict events, so the per-tick re-walk is pure waste
    match_cache: tuple | None = None
    # a migrated prefill waiting for import (decode-role admission);
    # dropped once the page content is scattered into this pool
    handoff: "KVHandoff | None" = None
    trace: Trace | None = None    # per-request stamp timeline

    def full_prompt(self) -> np.ndarray:
        """Prompt plus tokens generated before a preemption: greedy
        decoding is deterministic, so re-prefilling this continues the
        stream token-identically."""
        if not self.tokens:
            return np.asarray(self.request.prompt, np.int32)
        return np.concatenate([np.asarray(self.request.prompt, np.int32),
                               np.asarray(self.tokens, np.int32)])

    def completion(self) -> Completion:
        ttft = (self.first_token_t - self.submit_t
                if self.first_token_t is not None else 0.0)
        wait = (self.admit_t - self.submit_t
                if self.admit_t is not None else 0.0)
        return Completion(self.request.uid,
                          np.asarray(self.tokens, np.int32),
                          self.prefill_s, self.decode_s, self.decode_steps,
                          ttft_s=ttft, queue_wait_s=wait, status=self.term)


class Engine:
    """Continuous-batching serving engine over a paged KV cache."""

    @staticmethod
    def supports(cfg: ModelConfig) -> bool:
        """Whether this model family has the paged serving path."""
        return (mapi.get_model(cfg).prefill_into_cache is not None
                and not cfg.frontend)

    def __init__(self, cfg: ModelConfig, params=None, rng_seed: int = 0,
                 quant_bits: int | None = None,
                 act_quant: int | None = None,
                 calib_prompts=None,
                 engine: EngineConfig | None = None,
                 kv_dtype: str | jnp.dtype = "float32",
                 kv_codes: bool = False,
                 chaos: ChaosConfig | ChaosInjector | None = None,
                 telemetry: Telemetry | None = None,
                 worker_name: str = "", worker_id: int = 0):
        self.cfg = cfg
        self.api = mapi.get_model(cfg)
        if not self.supports(cfg):
            raise ValueError(
                f"Engine supports decoder-family models without a frontend; "
                f"got family={cfg.family!r} frontend={cfg.frontend!r}")
        self.engine_cfg = engine or EngineConfig()
        ec = self.engine_cfg
        if ec.prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got "
                             f"{ec.prefill_chunk}")
        if ec.shed_policy not in SHED_POLICIES:
            raise ValueError(f"shed_policy must be one of {SHED_POLICIES}, "
                             f"got {ec.shed_policy!r}")
        if ec.role not in ENGINE_ROLES:
            raise ValueError(f"role must be one of {ENGINE_ROLES}, "
                             f"got {ec.role!r}")
        if ec.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0 (0 disables "
                             f"speculation), got {ec.spec_k}")
        if ec.drift_check_every < 0:
            raise ValueError(f"drift_check_every must be >= 0 (0 "
                             f"disables), got {ec.drift_check_every}")
        self.chaos: ChaosInjector | None = (
            ChaosInjector(chaos) if isinstance(chaos, ChaosConfig) else chaos)
        # the CRC audit is the *detector* for KV corruption: auto-arm it
        # whenever chaos can corrupt pages, else honor the config flag
        self._checksum = ec.checksum_pages or (
            self.chaos is not None and self.chaos.cfg.corrupt_rate > 0)
        self.kv_dtype = jnp.dtype(kv_dtype)
        self.kv_codes = bool(kv_codes)
        if self.kv_codes:
            # codes-mode cache: pages hold u8 DNA-TEQ exponent codes
            # (1 B/elem); the attention kernels decode them through
            # per-head LUTs in VMEM and the block is code-in/code-out
            # through attention.  The per-head tables must exist by the
            # time params are final — validated below, after the
            # calibration step has had its chance to fit them.
            self.kv_dtype = jnp.dtype(jnp.uint8)
        if params is None:
            params = self.api.init(jax.random.PRNGKey(rng_seed),
                                   dtype=jnp.float32)
        self.quant_report = None
        if quant_bits is not None:
            params, self.quant_report = ll.quantize_tree(
                params, quant_bits, axes=self.api.logical_axes())
        self.act_report = None
        if act_quant is not None:
            # DNA-TEQ activation quantization: fit per-(layer, site)
            # ExpQuantParams on sample prompts (disk-cached next to the
            # autotuner cache) and splice the tables into the params
            # tree — the serving steps then encode activations at their
            # sites and every covered matmul runs dual-LUT
            # (code-in/code-out through the MLP chain).  Calibration
            # observes the *weight-quantized* model: that is what
            # serving runs, so the fit absorbs weight-decode error too.
            from repro.runtime.calibration import calibrate_act_quant

            params, self.act_report = calibrate_act_quant(
                self.api, params, cfg, bits=act_quant,
                prompts=calib_prompts,
                seq_len=min(32, self.engine_cfg.max_seq_len))
        self.params = params

        # codes-mode needs the per-head attn_k/attn_v tables attached —
        # either fit just above (act_quant bits) or already riding the
        # params tree (a cluster worker sharing worker 0's calibrated
        # params).  The fingerprint keys cross-worker handoffs: u8
        # pages only decode correctly under the tables they were
        # encoded with.
        self._kv_fingerprint: int | None = None
        if self.kv_codes:
            from repro.runtime.calibration import kv_tables_fingerprint

            aq = (params.get("blocks", {}).get("act_q")
                  if isinstance(params, dict) else None)
            if not (isinstance(aq, dict)
                    and "attn_k" in aq and "attn_v" in aq):
                raise ValueError(
                    "kv_codes=True requires act_quant bits: the per-head "
                    "K/V code tables come from activation calibration "
                    "(pass act_quant=<bits> or params that already carry "
                    "the calibrated attn_k/attn_v tables)")
            self._kv_fingerprint = kv_tables_fingerprint(aq)

        max_blk = math.ceil(ec.max_seq_len / ec.block_size)
        num_blocks = ec.num_blocks
        if num_blocks is None:
            # full occupancy: every slot can run to max_seq_len (+ trash)
            num_blocks = ec.num_slots * max_blk + 1
        self.cache = PagedKVCache(
            num_layers=cfg.num_layers, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim, num_slots=ec.num_slots,
            block_size=ec.block_size, num_blocks=num_blocks,
            max_blocks_per_seq=max_blk, dtype=self.kv_dtype)
        self.prefix: PrefixCache | None = (
            PrefixCache(self.cache.allocator, ec.block_size)
            if ec.prefix_cache else None)

        self._queue: deque[_SeqState] = deque()
        self._slots: list[_SeqState | None] = [None] * ec.num_slots
        self._states: dict[int, _SeqState] = {}
        self._seq_counter = 0

        # -------------------------------------------------- telemetry
        # ONE bundle per process: a cluster hands the same Telemetry to
        # every worker (shared monotonic clock, shared registry under
        # per-worker key prefixes, one trace timeline); a standalone
        # engine makes its own.  Counters live in the registry as the
        # one store; the legacy attribute names (`eng.preemptions`,
        # `eng.shed`, ...) are int-returning properties over it — see
        # `_ENGINE_COUNTERS` below the class body.
        self.telemetry = telemetry or Telemetry()
        self.worker_name = worker_name
        self.worker_id = worker_id
        self._scope = self.telemetry.registry.scope(worker_name)
        self.tracer = self.telemetry.tracer
        self._c = {attr: self._scope.counter(key, help=hint)
                   for attr, (key, hint) in _ENGINE_COUNTERS.items()}
        self.tracer.process_name(worker_id, worker_name or "engine")
        self.tracer.process_name(REQUESTS_PID, "requests")
        self.tracer.thread_name(worker_id, SCHED_TID, "scheduler")
        for i in range(ec.num_slots):
            self.tracer.thread_name(worker_id, lane_tid(i), f"slot{i}")
        self.flight = FlightRecorder()
        self.outbox: list[KVHandoff] = []  # prefill role: exports ready

        # ------------------------------------------ lifecycle & faults
        # one monotonic clock for deadlines, TTFT stamps, and trace
        # spans (satellite: wall time only at the Trace submit
        # boundary); still injectable for deadline tests
        self._clock = self.telemetry.clock
        self._tick_no = 0
        self._tick_tokens = 0
        self.replay_artifacts: list[dict] = []
        self._quarantined: dict[int, int] = {}   # slot -> release tick
        self._chaos_blocked = False   # admission faulted this tick
        self._page_crc: dict[int, int] = {}      # page -> CRC32 (audit)
        self.watchdog = StragglerWatchdog(threshold=3.0)
        self.tick_latency = LatencyTracker()
        self._register_gauges()
        if self.chaos is not None:
            self.telemetry.bind_chaos(self.chaos)

        self._prefill = _jit_prefill(self.api.prefill_into_cache)
        self._decode = _jit_decode(self.api.decode_step_paged)

        # ------------------------------------------ speculative decode
        # spec_k=0 keeps this path entirely cold: no drafter, no extra
        # compile, the vanilla single-token decode dispatch untouched.
        self.drafter: PromptLookupDrafter | None = None
        self._spec_verify = None
        if ec.spec_k > 0:
            if self.api.spec_verify_into_cache is None:
                raise ValueError(
                    f"spec_k={ec.spec_k}: model family {cfg.family!r} "
                    f"has no speculative verification path "
                    f"(spec_verify_into_cache)")
            self.drafter = PromptLookupDrafter(
                ec.spec_k, max_ngram=ec.spec_max_ngram,
                min_ngram=ec.spec_min_ngram)
            self._spec_verify = _jit_spec_verify(
                self.api.spec_verify_into_cache)

        # ------------------------------------------------- drift guard
        # last-admitted prompt, fixed-size so the periodic probe shares
        # one compile; per-site SQNR results backing the gauges
        self._drift_probe: np.ndarray | None = None
        self._drift_db: dict[str, float] = {}
        self._drift_delta_db: dict[str, float] = {}
        self._drift_registered: set[str] = set()

    def _register_gauges(self) -> None:
        """Callback gauges over live engine state: evaluated at read
        time, so the registry is always current and the hot path pays
        nothing.  They close over ``self`` (not the current objects) —
        tests that swap ``tick_latency``/``watchdog`` keep working."""
        s = self._scope
        s.gauge("engine.ticks", lambda: self._tick_no,
                help="scheduler ticks run")
        s.gauge("engine.queue.depth", lambda: len(self._queue),
                help="requests waiting for admission")
        s.gauge("engine.slots.live", lambda: self.live_slots,
                help="occupied decode lanes")
        s.gauge("engine.tick.p50_s", lambda: self.tick_latency.percentile(50))
        s.gauge("engine.tick.p99_s", lambda: self.tick_latency.percentile(99))
        s.gauge("engine.tick.mean_s", lambda: self.tick_latency.mean_s)
        s.gauge("engine.spec.accept_rate",
                lambda: (self.spec_accepted / self.spec_proposed
                         if self.spec_proposed else 0.0),
                help="drafted tokens accepted / drafted tokens verified")
        self.cache.register_metrics(s)
        if self.prefix is not None:
            s.gauge("engine.prefix.queries", lambda: self.prefix.stats.queries)
            s.gauge("engine.prefix.hits", lambda: self.prefix.stats.hits)
            s.gauge("engine.prefix.hit_rate",
                    lambda: self.prefix.stats.hit_rate)
            s.gauge("engine.prefix.token_hit_rate",
                    lambda: self.prefix.stats.token_hit_rate)
            s.gauge("engine.prefix.tokens_reused",
                    lambda: self.prefix.stats.tokens_reused)
            s.gauge("engine.prefix.evicted_pages",
                    lambda: self.prefix.stats.evicted_pages)
            s.gauge("engine.prefix.cow_copies",
                    lambda: self.prefix.stats.cow_copies)
            s.gauge("engine.prefix.pages", lambda: self.prefix.num_pages)

    # ---------------------------------------------------------------- api
    def submit(self, request: Request) -> int:
        """Enqueue a request; returns its handle (the uid).

        Backpressure: with ``max_queue`` set, an over-bound submit is
        resolved by the shed policy — ``reject-new`` makes *this*
        request immediately terminal with ``status=rejected`` (the
        handle is still returned; ``result`` answers honestly), while
        ``shed-oldest`` rejects the oldest still-queued request and
        enqueues the new one.  Malformed requests raise instead: a
        rejected status means "the system was full", never "you sent
        garbage"."""
        if request.uid in self._states:
            raise ValueError(f"duplicate uid {request.uid}")
        plen = len(request.prompt)
        if plen + request.max_new_tokens > self.engine_cfg.max_seq_len:
            raise ValueError(
                f"request {request.uid}: prompt {plen} + max_new "
                f"{request.max_new_tokens} exceeds max_seq_len "
                f"{self.engine_cfg.max_seq_len}")
        st = _SeqState(request, seq_no=self._seq_counter,
                       submit_t=self._clock())
        st.trace = Trace(request.uid, st.submit_t)
        if self.engine_cfg.drift_check_every and plen:
            # drift guard probes live traffic: remember the newest
            # prompt, resized to one fixed shape so the periodic
            # calibration forward shares a single compile
            self._drift_probe = np.resize(
                np.asarray(request.prompt, np.int32), 32)
        self._seq_counter += 1
        self._states[request.uid] = st
        ec = self.engine_cfg
        if ec.max_queue is not None and len(self._queue) >= ec.max_queue:
            self.shed += 1
            if ec.shed_policy == "reject-new":
                st.status, st.term = _FINISHED, ST_REJECTED
                self._finish_trace(st, ST_REJECTED)
                return request.uid
            self._terminate(self._queue[0], ST_REJECTED)  # shed-oldest
        self._queue.append(st)
        return request.uid

    def cancel(self, handle: int) -> bool:
        """Cancel a request in ANY live state — queued, mid-prefill-
        chunk (partial pages freed, trie pins decremented), or
        mid-decode.  Returns True if the request was live (now terminal
        with ``status=cancelled``, tokens-so-far retained), False if it
        was already terminal or unknown."""
        st = self._states.get(handle)
        if st is None or st.status == _FINISHED:
            return False
        self._terminate(st, ST_CANCELLED)
        self.cancelled += 1
        return True

    def drain_queue(self, status: str = ST_REJECTED) -> int:
        """Graceful-shutdown half-step: make every *queued* (not yet
        admitted) request terminal with ``status`` while running slots
        keep decoding.  Returns the number drained.  The serve launcher
        calls this on SIGINT, then steps until the slots retire."""
        n = 0
        while self._queue:
            self._terminate(self._queue[0], status)
            self.shed += status == ST_REJECTED
            n += 1
        return n

    # -------------------------------------------------- disaggregation
    def inject_prefilled(self, handoff: KVHandoff) -> int:
        """Accept a migrated prefill (decode-worker side of the page
        handoff): the request enqueues carrying the exported KV page
        content; admission *imports* the pages into this engine's pool
        (``PagedKVCache.import_slot``) instead of prefilling, and the
        slot enters the decode loop with ``prefill_done=True`` — zero
        prompt tokens are ever recomputed here.  Lifecycle stamps from
        the prefill worker carry over so the Completion reports honest
        end-to-end latencies.  Returns the handle (uid)."""
        req = handoff.request
        if req.uid in self._states:
            raise ValueError(f"duplicate uid {req.uid}")
        if handoff.block_size != self.engine_cfg.block_size:
            raise ValueError(
                f"handoff block_size {handoff.block_size} != engine "
                f"block_size {self.engine_cfg.block_size}")
        if handoff.kv_fingerprint != self._kv_fingerprint:
            raise ValueError(
                f"request {req.uid}: handoff KV table fingerprint "
                f"{handoff.kv_fingerprint} != this worker's "
                f"{self._kv_fingerprint} — codes-mode pages only decode "
                f"under the calibration tables they were encoded with")
        if handoff.length + req.max_new_tokens > self.engine_cfg.max_seq_len:
            raise ValueError(
                f"request {req.uid}: prefilled {handoff.length} + max_new "
                f"{req.max_new_tokens} exceeds max_seq_len "
                f"{self.engine_cfg.max_seq_len}")
        st = _SeqState(req, seq_no=self._seq_counter,
                       submit_t=handoff.submit_t)
        self._seq_counter += 1
        st.tokens = list(handoff.tokens)
        if st.tokens:
            st.next_token = st.tokens[-1]
        st.admit_t = handoff.admit_t
        st.first_token_t = handoff.first_token_t
        st.prefill_s = handoff.prefill_s
        st.preemptions = handoff.preemptions
        st.handoff = handoff
        # the trace crossed the boundary inside the handoff: stamp the
        # import on the SAME timeline (shared monotonic clock) and
        # close the flow arrow the export opened
        st.trace, handoff.trace = handoff.trace, None
        if st.trace is not None:
            t = self._clock()
            st.trace.stamp("handoff_import", t,
                           worker=self.worker_name or str(self.worker_id),
                           nbytes=handoff.nbytes)
            self.tracer.flow_end(self.worker_id, SCHED_TID, "kv_handoff",
                                 handoff.flow_id, t, uid=req.uid)
            self.tracer.instant(self.worker_id, SCHED_TID, "handoff_import",
                                t, uid=req.uid)
        self._states[req.uid] = st
        self._queue.append(st)
        return req.uid

    def take_handoffs(self) -> list[KVHandoff]:
        """Drain the prefill-role outbox: every request whose last
        prompt chunk landed since the previous call, with its exported
        KV pages.  The caller (the cluster) owns delivery; a dropped
        handoff is re-queued via ``submit`` (the state was already
        removed here, so the uid is free again)."""
        out, self.outbox = self.outbox, []
        return out

    # ------------------------------------------------- crash recovery
    def snapshot(self) -> dict:
        """JSON-serializable record of the engine's request
        bookkeeping.  Device KV is deliberately NOT captured — a crash
        loses it — so the snapshot holds exactly what re-prefilling
        needs: each live request's prompt, generated tokens, and
        lifecycle stamps, plus terminal completions not yet collected.
        Greedy decoding makes the rebuilt engine's completions
        token-identical to the uninterrupted run; this is the handoff
        format the prefill/decode disaggregation work inherits."""
        reqs = []
        for st in sorted(self._states.values(), key=lambda s: s.seq_no):
            r = st.request
            reqs.append({
                "uid": int(r.uid),
                "prompt": np.asarray(r.prompt, np.int32).tolist(),
                "max_new_tokens": int(r.max_new_tokens),
                "stop_token": (None if r.stop_token is None
                               else int(r.stop_token)),
                "deadline_s": r.deadline_s,
                "tokens": [int(t) for t in st.tokens],
                "terminal": st.status == _FINISHED,
                "term": st.term,
                "preemptions": st.preemptions,
                "decode_steps": st.decode_steps,
                "submit_t": st.submit_t,
            })
        return {"version": 1, "requests": reqs}

    def restore(self, snap: dict) -> int:
        """Rebuild bookkeeping from :meth:`snapshot` into this engine:
        terminal requests keep their statuses/results; every in-flight
        request re-queues to re-prefill prompt + tokens-so-far.
        Returns the number re-queued.  TTFT/queue-wait stamps restart
        (the crash ate them); deadlines keep their original submit
        stamp, so a budget blown during the outage expires on the
        first tick.

        The engine must have no *live* work (queued or running
        requests), but restoring into a long-lived engine whose prefix
        trie is warm is the intended recovery path: re-queued requests
        go through ordinary trie-matching admission, so when the trie
        still holds their prefixes the "re-prefill" splices cached
        pages instead of recomputing — a crash costs the uncached tail,
        not the whole prompt.  (Restoring into a fresh engine works too
        and is simply cold.)  Uncollected terminal completions from
        earlier work stay collectable; snapshot uids must not collide
        with them."""
        if self.pending:
            raise RuntimeError(
                "restore() needs an engine with no live requests: drain "
                "or cancel in-flight work first (uncollected terminal "
                "completions are fine — a warm prefix trie turns the "
                "restore re-prefill into cache hits)")
        if snap.get("version") != 1:
            raise ValueError(f"unknown snapshot version {snap.get('version')}")
        for rec in snap["requests"]:
            if rec["uid"] in self._states:
                raise ValueError(
                    f"snapshot uid {rec['uid']} collides with an "
                    f"uncollected completion; collect() first")
        requeued = 0
        for rec in snap["requests"]:
            req = Request(rec["uid"],
                          np.asarray(rec["prompt"], np.int32),
                          max_new_tokens=rec["max_new_tokens"],
                          stop_token=rec["stop_token"],
                          deadline_s=rec["deadline_s"])
            st = _SeqState(req, seq_no=self._seq_counter,
                           submit_t=rec["submit_t"])
            self._seq_counter += 1
            st.tokens = list(rec["tokens"])
            if st.tokens:
                st.next_token = st.tokens[-1]
            st.preemptions = rec["preemptions"]
            st.decode_steps = rec["decode_steps"]
            self._states[req.uid] = st
            if rec["terminal"]:
                st.status, st.term = _FINISHED, rec["term"]
            else:
                self._queue.append(st)
                requeued += 1
        return requeued

    @property
    def pending(self) -> bool:
        return bool(self._queue) or any(s is not None for s in self._slots)

    @property
    def queue_depth(self) -> int:
        """Requests waiting for admission (the router's backpressure
        signal: it holds work back rather than blow a worker's
        ``max_queue``)."""
        return len(self._queue)

    @property
    def live_slots(self) -> int:
        """Occupied decode lanes (the router's load signal)."""
        return sum(s is not None for s in self._slots)

    def step(self) -> list[Completion]:
        """One scheduler tick: expire deadlines, audit checksums,
        admit, advance prefills by one chunk, decode once, retire.
        Returns the completions that finished during this tick."""
        t_tick = self._clock()
        self._tick_no += 1
        self._tick_tokens = 0         # prefill + decode tokens this tick
        self._chaos_blocked = False
        if self.chaos is not None:
            delay = self.chaos.tick_delay()
            if delay > 0.0:
                time.sleep(delay)
        self._expire_deadlines()
        self._audit_pages()
        ec = self.engine_cfg
        if ec.drift_check_every and self._tick_no % ec.drift_check_every == 0:
            self._drift_check()
        for slot in [s for s, until in self._quarantined.items()
                     if until <= self._tick_no]:
            del self._quarantined[slot]
        self._admit()
        if (self._queue and all(s is None for s in self._slots)
                and not self._chaos_blocked and not self._quarantined):
            raise RuntimeError(
                "no admissible request: head of queue needs more KV "
                "blocks than the pool can ever free")
        finished = self._prefill_tick()
        active = [(i, s) for i, s in enumerate(self._slots)
                  if s is not None and s.prefill_done]
        if active:
            finished += self._decode_tick(active)
        # chaos: flip a bit in one checksummed page at the very end of
        # the tick — the audit at the top of the NEXT tick must catch
        # it before any dispatch attends the corrupt KV
        if self.chaos is not None and self._page_crc:
            page = self.chaos.corrupt_page(sorted(self._page_crc))
            if page is not None:
                self.cache.corrupt_page(page)
        t_end = self._clock()
        dt_tick = t_end - t_tick
        self.tick_latency.observe(dt_tick)
        if self.watchdog.observe(self._tick_no, dt_tick):
            self.slow_ticks += 1
        # flight recorder: always on — one small dict per tick into a
        # bounded ring, dumped with the replay artifact on any failure
        self.flight.record(tick=self._tick_no, t=t_tick, dt_s=dt_tick,
                           queue_depth=len(self._queue),
                           live_slots=self.live_slots,
                           free_pages=self.cache.allocator.free_blocks,
                           finished=len(finished))
        if self.tracer.enabled:
            pid = self.worker_id
            self.tracer.complete(pid, SCHED_TID, "tick", t_tick, t_end,
                                 tick=self._tick_no)
            self.tracer.counter(pid, "queue_depth", t_end,
                                depth=len(self._queue))
            self.tracer.counter(pid, "live_slots", t_end,
                                live=self.live_slots)
            self.tracer.counter(pid, "free_pages", t_end,
                                free=self.cache.allocator.free_blocks)
            if dt_tick > 0:
                self.tracer.counter(pid, "tok_s", t_end,
                                    tok_s=self._tick_tokens / dt_tick)
        return finished

    def _attn_accounting(self, q_tokens: int, kv_tokens: int) -> None:
        """Analytic attention-boundary traffic for one dispatched row:
        bytes the attention kernel reads (q + touched KV pages),
        activation bytes crossing the boundary (q in + context out —
        the tensors whose width ``kv_codes`` changes), and elements
        LUT-decoded in-kernel.  Computed from shapes — the jitted
        kernels cannot count, and the model is exact for the dense
        page-block access pattern both kernels use."""
        cfg = self.cfg
        hd = cfg.resolved_head_dim
        n_kv = cfg.num_kv_heads
        bs = self.engine_cfg.block_size
        act_item = 1 if self.kv_codes else 4       # u8 codes vs f32
        q_bytes = q_tokens * cfg.num_heads * hd * act_item
        out_bytes = q_tokens * cfg.num_heads * hd * act_item
        blocks = -(-kv_tokens // bs)
        kv_bytes = blocks * bs * n_kv * hd * 2 * self.kv_dtype.itemsize
        self.attn_bytes_read += q_bytes + kv_bytes
        self.attn_act_bytes += q_bytes + out_bytes
        if self.kv_codes:
            self.attn_dequants += (q_tokens * cfg.num_heads * hd
                                   + blocks * bs * n_kv * hd * 2)

    def _decode_tick(self, active) -> list[Completion]:
        # grow any sequence whose next write crosses a block boundary —
        # oldest first, so page pressure falls on the youngest (it is
        # the one evicted/preempted if the free list runs dry)
        for i, st in sorted(active, key=lambda t: t[1].seq_no):
            if self._slots[i] is st:     # not preempted earlier this tick
                self._grow(i)
        active = [(i, s) for i, s in enumerate(self._slots)
                  if s is not None and s.prefill_done]
        if not active:
            return []

        # speculative path: when any slot has a prompt-lookup proposal
        # this tick becomes ONE verification dispatch (draftless rows
        # ride along as single-token steps); with no proposals anywhere
        # fall through to the vanilla dispatch — an adversarial stream
        # pays nothing for having speculation enabled
        if self.drafter is not None:
            drafts = self._draft(active)
            if drafts:
                return self._spec_tick(active, drafts)

        ec = self.engine_cfg
        tokens = np.zeros((ec.num_slots, 1), np.int32)
        active_mask = np.zeros((ec.num_slots,), bool)
        pre_pos: dict[int, int] = {}    # write position, for checksums
        for i, st in active:
            tokens[i, 0] = st.next_token
            active_mask[i] = True
            pre_pos[i] = int(self.cache.lengths[i])
            self._attn_accounting(1, pre_pos[i] + 1)

        t0 = self._clock()
        nxt_dev, ok_dev, view = self._decode(
            self.params, self.cache.view(cols=self._live_cols(active)),
            jnp.asarray(tokens), jnp.asarray(active_mask), self.cfg)
        nxt = np.asarray(nxt_dev)   # blocks until the step is done
        ok = np.array(ok_dev)       # writable: chaos may force a row low
        t1 = self._clock()
        dt = t1 - t0
        self.cache.update_pages(view)
        # the device-computed lengths are the single source of truth
        # for *decoding* slots; prefilling slots keep their host value
        # (their lengths ride through the decode step unchanged)
        self.cache.lengths[:] = np.asarray(view.lengths)
        self.total_decode_steps += 1
        if self.chaos is not None:
            bad = self.chaos.nan_slot([i for i, _ in active])
            if bad is not None:
                ok[bad] = False     # identical path to a real device NaN
        finished: list[Completion] = []
        bs = ec.block_size
        for i, st in active:
            if not ok[i]:
                # non-finite logits: fail THIS request, rest the lane,
                # keep the batch running
                self.nan_rows_detected += 1
                self._quarantine(i)
                self._fault(st, "nan_logits")
                continue
            st.decode_steps += 1
            st.decode_s += dt
            tok = int(nxt[i])
            st.tokens.append(tok)
            st.next_token = tok
            self._tick_tokens += 1
            if self.tracer.enabled:
                # per-lane span on this worker's slot row + a stamp on
                # the request's own timeline, every decode tick
                self.tracer.complete(self.worker_id, lane_tid(i), "decode",
                                     t0, t1, uid=st.request.uid, token=tok)
                if st.trace is not None:
                    st.trace.stamp("decode_tick", t1, slot=i)
            if self._checksum:
                page = int(self.cache.block_tables[i, pre_pos[i] // bs])
                self._page_crc[page] = self.cache.page_checksum(page)
            if self._should_stop(st):
                finished.append(self._retire(i))
        return finished

    # ------------------------------------------------ speculative decode
    def _draft(self, active) -> dict[int, np.ndarray]:
        """Per-slot prompt-lookup proposals for this tick, clamped so a
        fully accepted window can neither overflow the request's token
        budget (accept+1 committed tokens must fit ``max_new_tokens``)
        nor write past the slot's owned pages — speculation never
        allocates a page vanilla decode would not have (``_grow``
        already ran, so one free position is guaranteed)."""
        ec = self.engine_cfg
        bs = ec.block_size
        drafts: dict[int, np.ndarray] = {}
        for i, st in active:
            budget = st.request.max_new_tokens - len(st.tokens) - 1
            pos = int(self.cache.lengths[i])
            cap = len(self.cache.slot_blocks[i]) * bs - pos - 1
            k = min(ec.spec_k, budget, cap)
            if k < 1:
                continue
            d = self.drafter.propose(st.full_prompt(), k=k)
            if len(d):
                drafts[i] = d
        return drafts

    def _spec_tick(self, active, drafts) -> list[Completion]:
        """One speculative verify-and-commit dispatch across every
        active slot.  Each drafted row scores its undecoded next token
        plus its proposals through the chunked-flash window; greedy
        argmax acceptance commits ``drafts[:accept]`` plus the model's
        own token at the first divergence — exactly the tokens vanilla
        single-stepping would have produced — and the rejected tail is
        simply *not counted*: ``lengths`` advances only over committed
        positions, pages never move, and the garbage KV beyond the
        write cursor is masked out of every later attend until
        overwritten.  Mixed ticks are free: draftless rows run with a
        one-token window in the same dispatch."""
        ec = self.engine_cfg
        bs = ec.block_size
        width = ec.spec_k + 1
        toks = np.zeros((ec.num_slots, width), np.int32)
        n_tok = np.zeros((ec.num_slots,), np.int32)
        # idle rows: start = length with zero valid tokens ⇒ trash
        # writes, zero attention (same parking trick as chunked prefill)
        start = np.asarray(self.cache.lengths, np.int32).copy()
        pre_pos: dict[int, int] = {}
        cols_need = 1
        for i, st in active:
            d = drafts.get(i)
            n = 1 + (len(d) if d is not None else 0)
            toks[i, 0] = st.next_token
            if d is not None:
                toks[i, 1:1 + len(d)] = d
                self.spec_proposed += len(d)
            n_tok[i] = n
            pre_pos[i] = int(self.cache.lengths[i])
            self._attn_accounting(n, pre_pos[i] + n)
            cols_need = max(cols_need, -(-(pre_pos[i] + n) // bs))
        cols = min(self._pow2(cols_need), self.cache.max_blocks_per_seq)

        t0 = self._clock()
        # host arrays go straight into the jitted call: pjit ingests
        # them on its C fast path, and three explicit device_puts per
        # tick are measurable against a sub-millisecond dispatch
        g_dev, acc_dev, ok_dev, view = self._spec_verify(
            self.params, toks, self.cache.view(cols=cols),
            start, n_tok, self.cfg)
        g = np.asarray(g_dev)       # blocks until the dispatch is done
        acc = np.asarray(acc_dev)
        ok = np.array(ok_dev)       # writable: chaos may force a row low
        t1 = self._clock()
        dt = t1 - t0
        self.cache.update_pages(view)
        self.total_decode_steps += 1
        self.spec_dispatches += 1
        if self.chaos is not None:
            bad = self.chaos.nan_slot([i for i, _ in active])
            if bad is not None:
                ok[bad] = False     # identical path to a real device NaN
        finished: list[Completion] = []
        for i, st in active:
            if not ok[i]:
                self.nan_rows_detected += 1
                self._quarantine(i)
                self._fault(st, "nan_logits")
                continue
            d = drafts.get(i)
            a = int(acc[i]) if d is not None else 0
            self.spec_accepted += a
            committed = [int(t) for t in (d[:a] if d is not None else ())]
            committed.append(int(g[i, a]))
            stop = st.request.stop_token
            if stop is not None and stop in committed:
                # vanilla would have stopped AT the stop token: commit
                # through it and drop the (correctly verified but now
                # out-of-sequence) tokens behind it
                committed = committed[:committed.index(stop) + 1]
            st.decode_steps += 1
            st.decode_s += dt
            st.tokens.extend(committed)
            st.next_token = committed[-1]
            # the commit IS the rewind: only committed positions count;
            # position len(committed) holds the still-unwritten KV slot
            # of next_token, exactly the vanilla invariant
            self.cache.lengths[i] = pre_pos[i] + len(committed)
            self._tick_tokens += len(committed)
            if self.tracer.enabled:
                self.tracer.complete(self.worker_id, lane_tid(i),
                                     "spec_decode", t0, t1,
                                     uid=st.request.uid,
                                     tokens=len(committed), accepted=a)
                if st.trace is not None:
                    st.trace.stamp(
                        "spec_verify", t1, slot=i, accepted=a,
                        proposed=(len(d) if d is not None else 0))
            if self._checksum:
                # every page the window touched, accepted or not: the
                # rejected tail's bytes are live page content until
                # overwritten, and the audit must track what is there
                for c in range(pre_pos[i] // bs,
                               (pre_pos[i] + int(n_tok[i]) - 1) // bs + 1):
                    page = int(self.cache.block_tables[i, c])
                    self._page_crc[page] = self.cache.page_checksum(page)
            if self._should_stop(st):
                finished.append(self._retire(i))
        return finished

    def stream(self, handle: int) -> Iterator[int]:
        """Yield tokens for one request as the engine produces them,
        driving ``step()`` whenever the stream runs dry."""
        st = self._states.get(handle)
        if st is None:
            raise KeyError(
                f"unknown or already-collected handle {handle}")
        sent = 0
        while True:
            while sent < len(st.tokens):
                yield st.tokens[sent]
                sent += 1
            if st.status == _FINISHED:
                return
            self.step()

    def result(self, handle: int) -> Completion | None:
        """Completion for a finished (not yet ``run``-collected)
        request, else None."""
        st = self._states.get(handle)
        return st.completion() if st and st.status == _FINISHED else None

    def collect(self) -> list[Completion]:
        """Pop completions for every finished request not yet collected
        (including ones that finished during ``step``/``stream``
        driving), sorted by uid.  Collected requests are pruned, so a
        long-lived engine doesn't accumulate state and their uids
        become reusable.  The cluster calls this every tick to harvest
        terminal requests without draining the whole engine."""
        done = [st for st in self._states.values()
                if st.status == _FINISHED]
        for st in done:
            del self._states[st.request.uid]
        return sorted((st.completion() for st in done),
                      key=lambda c: c.uid)

    def run(self) -> list[Completion]:
        """Drain the queue, then :meth:`collect` everything finished."""
        while self.pending:
            self.step()
        return self.collect()

    def generate(self, requests: Sequence[Request]) -> list[Completion]:
        """Batch-call convenience: submit all, drain."""
        for r in requests:
            self.submit(r)
        return self.run()

    def prompt_logits(self, prompts: Sequence[np.ndarray]) -> np.ndarray:
        """Next-token logits ``[len(prompts), V]`` after each prompt.

        The prompts run through this engine's prefill step (its params,
        KV representation, page size and ``prefill_chunk``) over a
        scratch page pool, outside the scheduler: the serving numerics,
        for checks against a reference.  The step is traced afresh on
        every call, so the ``lama_layers`` policy in force applies."""
        ec = self.engine_cfg
        n, longest = len(prompts), max(len(p) for p in prompts)
        max_blk = -(-longest // ec.block_size)
        cache = PagedKVCache(
            num_layers=self.cfg.num_layers,
            num_kv_heads=self.cfg.num_kv_heads,
            head_dim=self.cfg.resolved_head_dim, num_slots=n,
            block_size=ec.block_size, num_blocks=n * max_blk + 1,
            max_blocks_per_seq=max_blk, dtype=self.kv_dtype)
        for i, p in enumerate(prompts):
            cache.bind_slot(i, len(p), reserved=False)
        step = jax.jit(lambda params, toks, view, start:
                       self.api.prefill_into_cache(params, toks, view,
                                                   self.cfg, start))
        w = min(ec.prefill_chunk, longest)
        out = [None] * n
        for s0 in range(0, longest, w):
            toks = np.zeros((n, w), np.int32)
            for i, p in enumerate(prompts):
                toks[i, :max(0, min(w, len(p) - s0))] = p[s0:s0 + w]
            # rows whose prompt already ended: start = length, no work
            start = np.asarray([min(s0, len(p)) for p in prompts], np.int32)
            logits, view = step(self.params, jnp.asarray(toks),
                                cache.view(), jnp.asarray(start))
            cache.update_pages(view)
            for i, p in enumerate(prompts):
                if s0 < len(p) <= s0 + w:
                    out[i] = np.asarray(logits[i, -1], np.float32)
        return np.stack(out)

    # ------------------------------------------------------- diagnostics
    @property
    def prefix_stats(self):
        return self.prefix.stats if self.prefix is not None else None

    def check_partition(self) -> None:
        """Assert the page-partition invariant: free ∪ slot-owned ∪
        trie ∪ {trash} is an exact, disjoint cover with consistent
        refcounts.  Cheap enough to call every tick in tests."""
        if self.prefix is not None:
            self.cache.audit_partition(self.prefix.pages(),
                                       self.prefix.pins())
        else:
            self.cache.audit_partition(set(), {})

    @property
    def metrics(self):
        """This engine's view of the process metrics registry (a
        :class:`~repro.runtime.telemetry.Scope`): the one store every
        counter/gauge below actually lives in."""
        return self._scope

    def fault_stats(self) -> dict:
        """Lifecycle / fault / latency counters for benches and logs.

        Deprecation shim: every value is a read of the metrics
        registry (the counter attributes are properties over
        ``engine.lifecycle.*`` / ``engine.faults.*`` keys, the
        percentiles mirror the ``engine.tick.*`` gauges) — the dict
        shape is frozen so existing consumers don't churn; new code
        should read ``Engine.metrics`` / the registry directly."""
        d = {"ticks": self._tick_no,
             "cancelled": self.cancelled,
             "deadline_expired": self.deadline_expired,
             "shed": self.shed,
             "failed": self.failed,
             "starvation_pins": self.starvation_pins,
             "alloc_faults_absorbed": self.alloc_faults_absorbed,
             "nan_rows_detected": self.nan_rows_detected,
             "corruptions_detected": self.corruptions_detected,
             "quarantines": self.quarantines,
             "slow_ticks": self.slow_ticks,
             "tick_p50_s": self.tick_latency.percentile(50),
             "tick_p99_s": self.tick_latency.percentile(99),
             "tick_mean_s": self.tick_latency.mean_s}
        if self.chaos is not None:
            d.update(self.chaos.stats())
        return d

    # ----------------------------------------------------------- tracing
    def _finish_trace(self, st: _SeqState, status: str) -> None:
        """Close a request's trace with its ONE terminal stamp, archive
        it, and emit the request-track spans: a ``request`` span over
        the whole lifetime plus queued/prefill/decode phase spans
        nested inside it, all on the request's own row (tid = uid) of
        the virtual ``requests`` process.  A prefill-role export
        detaches the trace into the handoff *before* retiring, so the
        terminal span is emitted exactly once, by whichever worker the
        request actually ends on."""
        tr = st.trace
        if tr is None:
            return
        st.trace = None
        t = self._clock()
        tr.stamp("terminal", t, status=status)
        tr.status = status
        self.telemetry.finish_trace(tr)
        if not self.tracer.enabled:
            return
        uid = tr.uid
        t_sub = tr.submit_t
        self.tracer.thread_name(REQUESTS_PID, uid, f"req{uid}")
        self.tracer.complete(REQUESTS_PID, uid, "request", t_sub, t,
                             uid=uid, status=status)
        admit = st.admit_t
        first = st.first_token_t
        self.tracer.complete(REQUESTS_PID, uid, "queued", t_sub,
                             admit if admit is not None else t, uid=uid)
        if admit is not None:
            self.tracer.complete(REQUESTS_PID, uid, "prefill", admit,
                                 first if first is not None else t, uid=uid)
        if first is not None:
            self.tracer.complete(REQUESTS_PID, uid, "decode", first, t,
                                 uid=uid, tokens=len(st.tokens))

    # ------------------------------------------------------ failure model
    def _terminate(self, st: _SeqState, status: str) -> None:
        """The ONE transition to a non-ok terminal state, legal from any
        live state.  Running: the slot's owned pages go back to the free
        list and its trie pins drop (the page-partition audit holds
        immediately after).  Queued: the request leaves the queue.
        Tokens generated so far are retained in the Completion."""
        assert status in TERMINAL_STATUSES, status
        if st.status == _RUNNING:
            slot = st.slot
            self._slots[slot] = None
            self.cache.release_slot(slot)
            if self.prefix is not None:
                self.prefix.unpin(st.pinned)
            st.pinned = []
            st.slot = -1
        elif st.status == _QUEUED:
            try:
                self._queue.remove(st)
            except ValueError:
                pass    # mid-submit: not enqueued yet
        st.status, st.term = _FINISHED, status
        self._finish_trace(st, status)

    def _fault(self, st: _SeqState, kind: str) -> None:
        """Fail one request on a detected fault: dump a replay artifact
        first (the state needed to reproduce), then terminate.  The
        chaos chain is walkable from either end: the fault stamp (with
        the artifact name) lands on the request's trace before the
        terminal stamp, and the artifact carries the trace + the
        flight-recorder ring back."""
        art_name = (f"replay_uid{int(st.request.uid)}_"
                    f"tick{self._tick_no}.json")
        if st.trace is not None:
            st.trace.stamp("fault", self._clock(), kind=kind,
                           artifact=art_name)
        self.tracer.instant(self.worker_id, SCHED_TID, f"fault:{kind}",
                            uid=int(st.request.uid), artifact=art_name)
        self._replay_artifact(st, kind)
        self.failed += 1
        self._terminate(st, ST_FAILED)

    def _quarantine(self, slot: int) -> None:
        """Rest a slot lane after a non-finite dispatch: admission
        skips it until the release tick.  On real hardware this is the
        window for the lane's PIM banks to be scrubbed/re-verified."""
        self._quarantined[slot] = (self._tick_no
                                   + self.engine_cfg.quarantine_ticks)
        self.quarantines += 1

    def _replay_artifact(self, st: _SeqState, kind: str) -> None:
        art = {"kind": kind,
               "tick": self._tick_no,
               "uid": int(st.request.uid),
               "prompt": np.asarray(st.request.prompt, np.int32).tolist(),
               "tokens": [int(t) for t in st.tokens],
               "seq_no": st.seq_no,
               "preemptions": st.preemptions,
               "chaos": None if self.chaos is None else self.chaos.stats(),
               # the black box: what this engine was doing over the
               # last N ticks, plus the request's own stamp timeline
               "flight_recorder": self.flight.dump(),
               "trace": None if st.trace is None else st.trace.to_dict()}
        self.replay_artifacts.append(art)
        rd = self.engine_cfg.replay_dir
        if rd:
            os.makedirs(rd, exist_ok=True)
            path = os.path.join(rd, f"replay_uid{art['uid']}_"
                                    f"tick{art['tick']}.json")
            with open(path, "w") as f:
                json.dump(art, f)

    def _expire_deadlines(self) -> None:
        """Requests past their deadline budget go terminal wherever
        they are — queued (never admitted) or mid-flight."""
        now = self._clock()
        for st in list(self._states.values()):
            d = st.request.deadline_s
            if (d is not None and st.status != _FINISHED
                    and now - st.submit_t > d):
                self._terminate(st, ST_DEADLINE)
                self.deadline_expired += 1

    def _audit_pages(self) -> None:
        """Verify recorded page checksums before this tick's dispatch.
        A mismatch fails every sequence whose block table references
        the page; if the page is cached, the trie drops its whole
        subtree (descendants spell prefixes THROUGH the corrupt page).
        Runs at the top of the tick, so corrupt KV is never attended."""
        if not self._checksum or not self._page_crc:
            return
        live: set[int] = set()
        for i, st in enumerate(self._slots):
            if st is not None:
                live.update(self.cache.slot_blocks[i])
        trie_pages = (self.prefix.pages() if self.prefix is not None
                      else set())
        live |= trie_pages
        for page in [p for p in self._page_crc if p not in live]:
            del self._page_crc[page]    # freed since recorded
        for page, crc in list(self._page_crc.items()):
            if self.cache.page_checksum(page) == crc:
                continue
            self.corruptions_detected += 1
            for i, st in enumerate(list(self._slots)):
                if st is not None and page in self.cache.slot_blocks[i]:
                    self._fault(st, "kv_corruption")
            if self.prefix is not None and page in trie_pages:
                for freed in self.prefix.drop_subtree(page):
                    self._page_crc.pop(freed, None)
            self._page_crc.pop(page, None)

    def _drift_check(self) -> None:
        """Calibration drift guard: re-measure per-site round-trip SQNR
        on a live prompt under the *attached* act-quant tables and
        compare against the calibration report's per-site mean.
        Detection only — a site whose serving SQNR fell more than
        ``drift_threshold_db`` below the report logs a warning and
        bumps ``calib.drift.warnings``; refitting stays manual (the
        ROADMAP follow-up).  Results back the ``calib.drift.<site>_db``
        / ``_delta_db`` gauges, registered lazily on first sight."""
        aq = (self.params.get("blocks", {}).get("act_q")
              if isinstance(self.params, dict) else None)
        if (aq is None or self._drift_probe is None
                or self.api.collect_act_calibration is None):
            return
        from repro.runtime.calibration import measure_sqnr, report_means

        samples = self.api.collect_act_calibration(
            self.params, jnp.asarray(self._drift_probe[None, :]), self.cfg)
        cur = measure_sqnr(samples, aq)
        ref = report_means(self.act_report)
        self.drift_checks += 1
        thr = self.engine_cfg.drift_threshold_db
        for site, db in cur.items():
            self._drift_db[site] = db
            delta = db - ref[site] if site in ref else 0.0
            self._drift_delta_db[site] = delta
            if site not in self._drift_registered:
                self._drift_registered.add(site)
                self._scope.gauge(
                    f"calib.drift.{site}_db",
                    lambda s=site: self._drift_db.get(s, 0.0),
                    help="serving-time round-trip SQNR at this site")
                self._scope.gauge(
                    f"calib.drift.{site}_delta_db",
                    lambda s=site: self._drift_delta_db.get(s, 0.0),
                    help="serving SQNR minus the calibration-report mean")
            if site in ref and delta < -thr:
                self.drift_warnings += 1
                warnings.warn(
                    f"calibration drift at {site}: serving SQNR "
                    f"{db:.1f} dB is {-delta:.1f} dB below the "
                    f"calibration report ({ref[site]:.1f} dB) — "
                    f"consider refitting the act-quant tables")

    def _free_slot(self) -> int | None:
        """Lowest free slot index that is not quarantined, else None."""
        for i, s in enumerate(self._slots):
            if s is None and i not in self._quarantined:
                return i
        return None

    # ---------------------------------------------------------- scheduler
    def _should_stop(self, st: _SeqState) -> bool:
        r = st.request
        return (len(st.tokens) >= r.max_new_tokens
                or (r.stop_token is not None
                    and st.tokens[-1] == r.stop_token))

    def _retire(self, slot: int) -> Completion:
        """Finish a sequence.  With the prefix cache on, its pages are
        inserted into the trie (keyed by the token content they hold)
        instead of freed — the next request sharing the prefix skips
        both the FLOPs and the HBM writes."""
        st = self._slots[slot]
        self._slots[slot] = None
        if self.prefix is None:
            self.cache.release_slot(slot)
        else:
            content_len = int(self.cache.lengths[slot])
            content = st.full_prompt()[:content_len]
            shared = set(self.cache.slot_shared[slot])
            blocks = self.cache.clear_slot(slot)
            self.prefix.insert(content, blocks, shared)
            self.prefix.unpin(st.pinned)
            st.pinned = []
        st.status = _FINISHED
        self._finish_trace(st, st.term)
        return st.completion()

    def _export_handoff(self, slot: int, st: _SeqState) -> None:
        """Prefill role: the request's last chunk landed — copy its KV
        page content out for migration, retire the slot (pages move
        into the trie, keeping this shard warm both for the next
        shared-prefix request and for a cheap re-prefill if the
        handoff drops in transit), and drop the request's state: from
        here the handoff record owns it, and the uid becomes free for
        a re-queue."""
        length = int(self.cache.lengths[slot])
        k, v = self.cache.export_slot(slot)
        h = KVHandoff(request=st.request, tokens=list(st.tokens),
                      length=length, k_pages=k, v_pages=v,
                      block_size=self.engine_cfg.block_size,
                      submit_t=st.submit_t, admit_t=st.admit_t,
                      first_token_t=st.first_token_t,
                      prefill_s=st.prefill_s, preemptions=st.preemptions,
                      kv_fingerprint=self._kv_fingerprint)
        # detach the trace INTO the handoff before retiring: the
        # request is not terminal — it continues on a decode worker —
        # so no terminal span here; the flow arrow (closed at import,
        # or at the drop site on a migration fault) ties the two
        # workers' timelines together
        h.trace, st.trace = st.trace, None
        if h.trace is not None:
            t = self._clock()
            h.flow_id = self.tracer.next_flow_id()
            h.trace.stamp("handoff_export", t,
                          worker=self.worker_name or str(self.worker_id),
                          nbytes=h.nbytes)
            self.tracer.flow_start(self.worker_id, SCHED_TID, "kv_handoff",
                                   h.flow_id, t, uid=int(st.request.uid))
            self.tracer.instant(self.worker_id, SCHED_TID, "handoff_export",
                                t, uid=int(st.request.uid))
        self._retire(slot)
        del self._states[st.request.uid]
        self.outbox.append(h)
        self.handoffs += 1
        self.handoff_bytes += h.nbytes

    def _preempt(self, slot: int) -> None:
        """Release a running sequence's pages and re-queue it at the
        front; its prompt *plus tokens generated so far* re-prefill on
        re-admission, so greedy output is unchanged."""
        st = self._slots[slot]
        self._slots[slot] = None
        self.cache.release_slot(slot)
        if self.prefix is not None:
            self.prefix.unpin(st.pinned)
        st.pinned = []
        st.prefix_len = 0
        st.prefill_pos = 0
        st.prefill_done = False
        st.slot = -1
        st.status = _QUEUED
        st.preemptions += 1
        if st.trace is not None:
            st.trace.stamp("preempt", self._clock(), n=st.preemptions)
        self.preemptions += 1
        if st.preemptions == self.engine_cfg.max_preemptions:
            # starvation guard trips: from now on _make_room refuses to
            # pick this sequence as a victim (it can still self-preempt
            # in _grow — yielding the pool beats a hard failure)
            self.starvation_pins += 1
        self._queue.appendleft(st)

    def _make_room(self, need: int, seq_no: int, *,
                   allow_preempt: bool = True) -> bool:
        """Eviction ladder: free list -> LRU-evict unpinned trie pages
        -> preempt the youngest running sequence submitted after
        ``seq_no``.  Returns False if ``need`` pages cannot be freed."""
        alloc = self.cache.allocator
        while alloc.free_blocks < need:
            if (self.prefix is not None
                    and self.prefix.evict(need - alloc.free_blocks)):
                continue
            if not allow_preempt:
                return False
            victim = None
            for st in self._slots:
                if (st is not None and st.seq_no > seq_no
                        and st.preemptions < self.engine_cfg.max_preemptions
                        and (victim is None or st.seq_no > victim.seq_no)):
                    victim = st
            if victim is None:
                return False
            self._preempt(victim.slot)
        return True

    def _grow(self, slot: int) -> None:
        """Allocate the next page iff this tick's write crosses a block
        boundary; under pressure, evict/preempt (or, as a last resort,
        preempt *this* sequence) rather than fail."""
        st = self._slots[slot]
        pos = int(self.cache.lengths[slot])
        bs = self.engine_cfg.block_size
        if pos == len(self.cache.slot_blocks[slot]) * bs:
            # chaos: the growth allocation transiently fails — preempt
            # THIS sequence; greedy recompute is token-identical, so an
            # allocator fault costs latency, never correctness
            if self.chaos is not None and self.chaos.alloc_fault():
                self.alloc_faults_absorbed += 1
                self._chaos_blocked = True
                self._preempt(slot)
                return
            if not self._make_room(1, st.seq_no):
                if any(s is not None and s is not st for s in self._slots):
                    self._preempt(slot)   # youngest of all: yield the pool
                    return
                raise RuntimeError(
                    f"KV pool too small: sequence {st.request.uid} cannot "
                    f"grow past {pos} tokens and nothing is evictable")
            self.cache.ensure_capacity(slot, reserved=False)
        # decode never writes a shared page: the boundary page was
        # copy-on-written at admission, later pages are fresh allocs
        page = self.cache.block_tables[slot, pos // bs]
        assert page not in self.cache.slot_shared[slot], (slot, pos, page)

    @staticmethod
    def _pow2(n: int) -> int:
        return 1 << max(0, math.ceil(math.log2(max(n, 1))))

    def _live_cols(self, active) -> int:
        """Block-table columns the decode step actually needs: enough
        to cover every live sequence's cache plus this tick's write,
        rounded up a pow2 ladder so compiles are shared.  Dead columns
        cost the paged kernel real grid steps — slicing them off makes
        short sequences pay for short tables."""
        need = max(int(self.cache.lengths[i]) // self.engine_cfg.block_size
                   + 1 for i, _ in active)
        return min(self._pow2(need), self.cache.max_blocks_per_seq)

    def _chunk_width(self, remaining: int) -> int:
        """This tick's prefill chunk width: the largest remaining tail
        rounded up a pow2 ladder (block-size multiples) so a serving
        mix of lengths shares a handful of compiles, capped at
        ``prefill_chunk`` — the token budget that bounds how long any
        single tick's prefill dispatch can run."""
        bs = self.engine_cfg.block_size
        padded = math.ceil(max(self._pow2(remaining), 8) / bs) * bs
        cap = min(self.engine_cfg.prefill_chunk,
                  self.cache.max_blocks_per_seq * bs)
        return max(min(padded, cap), 1)

    def _trie_match(self, st: _SeqState):
        """The request's trie match, cached across scheduler ticks.

        A blocked queue head (and the reorder-scan candidates behind
        it) would otherwise re-walk the trie every tick; the match only
        changes when the trie's structure does (retire inserts, evict
        removes — tracked by ``PrefixCache.generation``) or when the
        request's effective prompt grows (preemption appends generated
        tokens).  Cache hits count in ``trie_match_reuses``."""
        prompt = st.full_prompt()
        mc = st.match_cache
        gen = self.prefix.generation
        if mc is not None and mc[0] == gen and mc[1] == len(prompt):
            self.trie_match_reuses += 1
            return mc[2]
        match = self.prefix.match(prompt)
        st.match_cache = (gen, len(prompt), match)
        return match

    # ----------------------------------------------------------- admission
    def _try_place(self, st: _SeqState, *, allow_preempt: bool = True,
                   match: tuple | None = None) -> bool:
        """Match the trie, size the tail, and commit: pin the prefix,
        make room (evict/preempt), splice the block table, CoW the
        boundary page.  The sequence enters its slot with
        ``prefill_done=False``; the chunk scheduler advances it.
        ``match`` short-circuits the trie walk with a precomputed
        ``(nodes, mtokens)`` (the reorder scan already did it).
        Returns False when the pages cannot be freed."""
        prompt = st.full_prompt()
        plen = len(prompt)
        bs = self.engine_cfg.block_size
        need_total = self.cache.blocks_for(plen)
        if need_total > self.cache.max_blocks_per_seq:
            raise RuntimeError(
                f"request {st.request.uid} needs {need_total} blocks > "
                f"max_blocks_per_seq {self.cache.max_blocks_per_seq}")

        nodes: list[PrefixNode] = []
        prefix_len = 0
        if self.prefix is not None:
            matched, mtokens = (match if match is not None
                                else self.prefix.match(prompt))
            # per-node coverage: whole pages, except possibly the last
            contribs = [len(nd.key) for nd in matched]
            if matched:
                contribs[-1] = mtokens - sum(contribs[:-1])
            # reuse is capped at plen-1: the true last prompt token is
            # always recomputed so its logits exist to sample from
            allowed, cum = plen - 1, 0
            for nd, contrib in zip(matched, contribs):
                if cum >= allowed:
                    break
                nodes.append(nd)
                cum += contrib
            prefix_len = min(cum, allowed)

        first_write_col = prefix_len // bs
        cow = first_write_col < len(nodes)
        need = need_total - len(nodes) + (1 if cow else 0)

        if self.prefix is not None:
            self.prefix.pin(nodes)     # eviction-proof before make_room
        # chaos: the allocation transiently fails — the request simply
        # stays queued for the next tick (latency, never tokens)
        if (need > 0 and self.chaos is not None
                and self.chaos.alloc_fault()):
            if self.prefix is not None:
                self.prefix.unpin(nodes)
            self.alloc_faults_absorbed += 1
            self._chaos_blocked = True
            return False
        if not self._make_room(need, st.seq_no, allow_preempt=allow_preempt):
            if self.prefix is not None:
                self.prefix.unpin(nodes)
            return False
        if self.prefix is not None:    # stats count committed admissions
            self.prefix.stats.queries += 1
            if nodes:
                self.prefix.stats.hits += 1
            self.prefix.stats.tokens_reused += prefix_len
            self.prefix.stats.tokens_missed += plen - prefix_len
        slot = self._free_slot()
        assert slot is not None
        self.cache.bind_slot(slot, plen, [nd.page for nd in nodes],
                             reserved=False)
        if cow:
            # the sequence will write into the last matched page (it is
            # only partially covered by the hit): clone it, then drop
            # our pin on the original — the clone carries the KV now
            self.cache.cow_slot_page(slot, first_write_col)
            self.prefix.stats.cow_copies += 1
            cow_node = nodes.pop(first_write_col)
            self.prefix.unpin([cow_node])
        st.slot, st.status = slot, _RUNNING
        st.pinned = nodes
        st.prefix_len = prefix_len
        st.prefill_pos = 0
        st.prefill_done = False
        if st.admit_t is None:
            st.admit_t = self._clock()
            if st.trace is not None:
                st.trace.stamp("admit", st.admit_t, slot=slot,
                               prefix_len=prefix_len)
        self._slots[slot] = st
        return True

    def _admit(self) -> None:
        """FIFO admission with prefix splicing: place up to
        ``max_batched_prefill`` queue heads into free slots (no prompt
        buckets — the chunk scheduler serves every admitted row at its
        own progress in one full-width dispatch).  When the head cannot
        get its pages, the prefix-aware fallback scans the next K=4
        waiting requests and admits cache hits first."""
        admitted = 0
        while (self._queue and self._free_slot() is not None
               and admitted < self.engine_cfg.max_batched_prefill):
            # pop before placing: _try_place may preempt a victim onto
            # the queue front, so a later popleft could grab the wrong
            # element
            st = self._queue.popleft()
            if st.handoff is not None:
                if self._place_import(st):
                    admitted += 1
                    continue
                self._queue.appendleft(st)  # wait for pages
                break
            match = (self._trie_match(st) if self.prefix is not None
                     else None)
            if self._try_place(st, match=match):
                admitted += 1
                continue
            self._queue.appendleft(st)    # head-of-line: wait for pages
            self._admit_reordered(
                self.engine_cfg.max_batched_prefill - admitted)
            break

    def _place_import(self, st: _SeqState) -> bool:
        """Admit a migrated prefill: make room for its pages, scatter
        the handoff's KV content into this pool, and enter the decode
        loop directly — ``prefill_done=True`` from the first tick, so
        a decode worker never runs a prefill dispatch.  Returns False
        when the pages cannot be freed (the import waits)."""
        h = st.handoff
        need = self.cache.blocks_for(h.length)
        if need > self.cache.max_blocks_per_seq:
            raise RuntimeError(
                f"request {st.request.uid} needs {need} blocks > "
                f"max_blocks_per_seq {self.cache.max_blocks_per_seq}")
        # chaos: the import allocation transiently fails — the handoff
        # stays queued for the next tick (latency, never tokens)
        if self.chaos is not None and self.chaos.alloc_fault():
            self.alloc_faults_absorbed += 1
            self._chaos_blocked = True
            return False
        if not self._make_room(need, st.seq_no):
            return False
        slot = self._free_slot()
        assert slot is not None
        blocks = self.cache.import_slot(slot, h.length, h.k_pages,
                                        h.v_pages)
        st.slot, st.status = slot, _RUNNING
        st.prefix_len = 0
        st.prefill_pos = h.length
        st.prefill_done = True
        if st.admit_t is None:
            st.admit_t = self._clock()
        if st.trace is not None:
            st.trace.stamp("import_admit", self._clock(), slot=slot)
        self._slots[slot] = st
        self.imported_handoffs += 1
        self.imported_bytes += h.nbytes
        st.handoff = None       # content adopted; free the host copy
        if self._checksum:
            for page in blocks:
                self._page_crc[page] = self.cache.page_checksum(page)
        return True

    def _admit_reordered(self, budget: int) -> None:
        """Prefix-aware admission (lite): the queue head is blocked on
        pages; scan the next K=4 waiting requests and admit prefix-
        cache hits first — their spliced pages shrink the footprint, so
        a hit may fit where the head does not.  Reordered admissions
        never preempt (they are the youngest work in the system), so a
        failed attempt leaves the queue untouched; ``budget`` is what
        remains of the tick's ``max_batched_prefill`` admission cap."""
        if self.prefix is None:
            return
        idx, scanned = 1, 0
        while (idx < len(self._queue) and scanned < 4 and budget > 0
               and self._free_slot() is not None):
            st = self._queue[idx]
            scanned += 1
            match = self._trie_match(st)
            if match[1] == 0:
                idx += 1
                continue
            del self._queue[idx]
            if self._try_place(st, allow_preempt=False, match=match):
                self.admission_reorders += 1
                budget -= 1
                # the next candidate shifted into idx
            else:
                self._queue.insert(idx, st)
                idx += 1

    # ------------------------------------------------------ chunk prefill
    def _prefill_tick(self) -> list[Completion]:
        """Advance every prefilling slot by one chunk in ONE full-width
        dispatch.  The chunk width is the largest remaining tail
        (pow2-bucketed) capped at ``prefill_chunk``; rows that are
        decoding or empty ride along with a zero-length slice (start =
        length ⇒ nothing written, zero attention), so one compile per
        (width, cols) pair serves every mix of progress states.  Rows
        whose prompt completes this tick sample their first token from
        the dispatch's logits."""
        pref = [(i, st) for i, st in enumerate(self._slots)
                if st is not None and not st.prefill_done]
        if not pref:
            return []
        ec = self.engine_cfg
        bs = ec.block_size
        remaining = max(len(st.full_prompt()) - st.prefix_len - st.prefill_pos
                        for _, st in pref)
        w = self._chunk_width(remaining)
        toks = np.zeros((ec.num_slots, w), np.int32)
        # non-prefilling rows: start = length ⇒ zero valid tokens
        start = np.asarray(self.cache.lengths, np.int32).copy()
        takes: dict[int, int] = {}
        cols_need = 1
        for i, st in pref:
            prompt = st.full_prompt()
            s0 = st.prefix_len + st.prefill_pos
            take = min(w, len(prompt) - s0)
            toks[i, :take] = prompt[s0:s0 + take]
            start[i] = s0
            takes[i] = take
            self.prefill_tokens_computed += take
            self._tick_tokens += take
            self._attn_accounting(take, s0 + take)
            cols_need = max(cols_need, -(-(s0 + take) // bs))
        self.prefill_batches += 1
        cols = min(self._pow2(cols_need), self.cache.max_blocks_per_seq)

        t0 = self._clock()
        nxt_dev, ok_dev, view = self._prefill(
            self.params, jnp.asarray(toks), self.cache.view(cols=cols),
            jnp.asarray(start), self.cfg)
        nxt = np.asarray(nxt_dev)   # blocks until the dispatch is done
        ok = np.array(ok_dev)       # writable: chaos may force a row low
        t1 = self._clock()
        dt = t1 - t0
        self.cache.update_pages(view)

        # pages this dispatch wrote, recorded per-row BEFORE retiring /
        # faulting mutates the block tables
        row_pages: dict[int, list[int]] = {}
        if self._checksum:
            for i, st in pref:
                s0, take = int(start[i]), takes[i]
                if take:
                    row_pages[i] = [int(self.cache.block_tables[i, c])
                                    for c in range(s0 // bs,
                                                   (s0 + take - 1) // bs + 1)]
        # only rows COMPLETING their prompt this tick consume logits —
        # chaos (like a real device NaN) can only hit those
        completing = [i for i, st in pref
                      if st.prefix_len + st.prefill_pos + takes[i]
                      >= len(st.full_prompt())
                      and st.request.max_new_tokens > 0]
        if self.chaos is not None:
            bad = self.chaos.nan_slot(completing)
            if bad is not None:
                ok[bad] = False
        finished: list[Completion] = []
        faulted: set[int] = set()
        for i, st in pref:
            st.prefill_s += dt      # coalesced rows share the stamp
            st.prefill_pos += takes[i]
            if self.tracer.enabled and takes[i]:
                self.tracer.complete(self.worker_id, lane_tid(i),
                                     "prefill_chunk", t0, t1,
                                     uid=st.request.uid, tokens=takes[i])
                if st.trace is not None:
                    st.trace.stamp("prefill_chunk", t1, slot=i,
                                   tokens=takes[i])
            if st.prefix_len + st.prefill_pos < len(st.full_prompt()):
                continue            # more chunks to go
            if i in completing and not ok[i]:
                self.nan_rows_detected += 1
                self._quarantine(i)
                self._fault(st, "nan_logits")
                faulted.add(i)
                continue
            st.prefill_done = True
            r = st.request
            if r.max_new_tokens > 0 and len(st.tokens) < r.max_new_tokens:
                tok = int(nxt[i])
                st.tokens.append(tok)
                st.next_token = tok
            if st.first_token_t is None and st.tokens:
                st.first_token_t = self._clock()
                if st.trace is not None:
                    st.trace.stamp("first_token", st.first_token_t)
            if self._should_stop(st):
                finished.append(self._retire(i))
            elif self.engine_cfg.role == "prefill":
                # disaggregation: this worker's job ends at the first
                # token — export the KV pages instead of decoding
                self._export_handoff(i, st)
        for i, pages in row_pages.items():
            if i not in faulted:    # a faulted row's pages were freed
                for page in pages:
                    self._page_crc[page] = self.cache.page_checksum(page)
        return finished


# Engine counters live in the metrics registry — ONE store with stable
# namespaced keys (what benches, the serve launcher, and every future
# ROADMAP item read).  The legacy attribute names stay as int-valued
# properties over the registered Counter, so ~60 existing call sites
# (`eng.shed += 1`, `clu.handoffs > 0`, json.dump of bench rows) read
# and write the registry without knowing it exists.
_ENGINE_COUNTERS = {
    "total_decode_steps":
        ("engine.decode.steps", "batched decode dispatches run"),
    "prefill_tokens_computed":
        ("engine.prefill.tokens", "prompt tokens actually computed"),
    "prefill_batches":
        ("engine.prefill.chunks", "chunked prefill dispatches issued"),
    "preemptions":
        ("engine.sched.preemptions", "sequences preempted for pages"),
    "admission_reorders":
        ("engine.sched.reorders", "prefix hits admitted past a blocked head"),
    "trie_match_reuses":
        ("engine.sched.trie_reuses", "memoized trie matches served"),
    "starvation_pins":
        ("engine.sched.starvation_pins", "sequences pinned by the guard"),
    "handoffs":
        ("engine.handoff.exported", "prefill role: requests exported"),
    "handoff_bytes":
        ("engine.handoff.exported_bytes", "KV bytes copied out for migration"),
    "imported_handoffs":
        ("engine.handoff.imported", "decode role: migrations admitted"),
    "imported_bytes":
        ("engine.handoff.imported_bytes", "KV bytes scattered into this pool"),
    "cancelled":
        ("engine.lifecycle.cancelled", "Engine.cancel() terminations"),
    "deadline_expired":
        ("engine.lifecycle.deadline_expired", "deadline_s budgets blown"),
    "shed":
        ("engine.lifecycle.shed", "backpressure rejections"),
    "failed":
        ("engine.lifecycle.failed", "NaN/corruption terminations"),
    "alloc_faults_absorbed":
        ("engine.faults.alloc_absorbed", "injected alloc failures survived"),
    "nan_rows_detected":
        ("engine.faults.nan_rows", "non-finite logits rows quarantined"),
    "corruptions_detected":
        ("engine.faults.corruptions", "CRC mismatches caught"),
    "attn_bytes_read":
        ("engine.attn.bytes_read", "attention kernel input bytes "
                                   "(q + KV pages), analytic"),
    "attn_act_bytes":
        ("engine.attn.bytes_act", "activation bytes crossing the "
                                  "attention boundary (q in, ctx out)"),
    "attn_dequants":
        ("engine.attn.dequants", "elements LUT-decoded inside the "
                                 "attention kernels (codes mode)"),
    "slow_ticks":
        ("engine.faults.slow_ticks", "watchdog-flagged scheduler ticks"),
    "quarantines":
        ("engine.faults.quarantines", "slot lanes rested after a fault"),
    "spec_dispatches":
        ("engine.spec.dispatches", "speculative verify dispatches run"),
    "spec_proposed":
        ("engine.spec.proposed", "drafted tokens sent for verification"),
    "spec_accepted":
        ("engine.spec.accepted", "drafted tokens accepted by greedy "
                                 "verification"),
    "drift_checks":
        ("calib.drift.checks", "drift-guard SQNR probes run"),
    "drift_warnings":
        ("calib.drift.warnings", "site probes past drift_threshold_db"),
}


def _install_counter_views(cls, mapping) -> None:
    for attr in mapping:
        def _get(self, _a=attr):
            return self._c[_a].value

        def _set(self, v, _a=attr):
            self._c[_a]._value = int(v)

        setattr(cls, attr, property(_get, _set))


_install_counter_views(Engine, _ENGINE_COUNTERS)


__all__ = ["Engine", "EngineConfig", "Request", "Completion", "KVHandoff",
           "ST_OK", "ST_CANCELLED", "ST_DEADLINE", "ST_REJECTED",
           "ST_FAILED", "TERMINAL_STATUSES", "SHED_POLICIES",
           "ENGINE_ROLES"]
