"""Smoke run of the serving path on one TPU chip, at qwen3-1.7b's
published widths with random weights from seed 0.

    python chip_smoke.py

Three phases, all of which must pass:

* ``kernels``: every main-path kernel at the model's widths against its
  ``ref.py`` oracle, with the error printed beside the tolerance below.
* ``serve``: ``Engine(get_config("qwen3-1.7b"), quant_bits=7)`` serves 8
  seeded requests (chunked prefill, a shared prefix that the trie must
  hit, 32 new tokens each); first-token logits of a few prompts are
  compared with a forward that runs no Pallas kernel.
* ``serve-codes``: the same requests with exponent-coded activations and
  KV pages (``act_quant=7, kv_codes=True``).

Wall and compile seconds are those of one cold smoke run, compiles
included: not a metric.  The last line of standard output is
``{"ok": true, "device": {...}}``, printed only when every phase passed
on a TPU; otherwise the script exits nonzero.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.caches import enable_compile_cache  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import exponential_quant as eq  # noqa: E402
from repro.core import lama_layers as ll  # noqa: E402
from repro.kernels import decode_gqa as dg  # noqa: E402
from repro.kernels import flash_prefill as fp  # noqa: E402
from repro.kernels import lut_dequant_matmul as lm  # noqa: E402
from repro.kernels._codes import decode_heads  # noqa: E402
from repro.runtime.engine import (Engine, EngineConfig, Request,  # noqa: E402
                                  ST_OK)

# Kernel vs oracle, as max|kernel - oracle| / max|oracle|.  The oracles
# run at "highest" matmul precision; a kernel whose MXU passes round the
# operands to bf16 lands near 2^-9 of the largest output, an f32 one
# near 1e-6.
TOL_MATMUL = 1e-2
TOL_ATTN = 1e-2
# u8 code outputs (quantize epilogues) are checked against the oracle's
# f32 output: each code must encode a value within the same tolerance
# of it.  Exponent codes resolve small values finely, so a code-for-code
# comparison would flag rounding-level differences near zero.
# Served first-token logits vs the kernel-free forward, as RMS error
# over the reference logits' spread, per prompt: both decode the same
# weight codes, so what differs is the kernels' f32 accumulation order
# and MXU precision against the oracles', through 28 layers.
TOL_LOGITS = 5e-2


@dataclasses.dataclass(frozen=True)
class Widths:
    """Kernel shapes: model widths plus the serving geometry."""

    d: int
    ff: int
    vocab: int
    n_kv: int
    g: int
    hd: int
    slots: int = 8
    block: int = 16
    chunk: int = 256
    max_blk: int = 64     # max_seq_len 1024 / pages of 16

    @classmethod
    def of(cls, cfg, **kw):
        return cls(cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.num_kv_heads,
                   cfg.num_heads // cfg.num_kv_heads, cfg.resolved_head_dim,
                   **kw)


# ---------------------------------------------------------------- helpers

class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration


def max_rel_err(out, ref) -> float:
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    if not (np.all(np.isfinite(out)) and np.all(np.isfinite(ref))):
        return float("inf")
    return float(np.max(np.abs(out - ref)) / max(np.max(np.abs(ref)),
                                                  1e-30))


def code_levels(codes) -> np.ndarray:
    """Codes as signed levels, monotone in the decoded value:
    ..., -e1, -e0, +e0, +e1, ... -> ..., -2, -1, 0, 1, ..."""
    c = np.asarray(codes).astype(np.int32)
    e = c & 0x7F
    return np.where(c >> 7, -e - 1, e)


def rel_logit_err(got, ref) -> np.ndarray:
    """Per-row RMS logit error over the reference logits' spread."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return np.sqrt(np.mean((got - ref) ** 2, axis=-1)) / np.std(ref, axis=-1)


class Checks:
    """Collects named checks; prints each with its tolerance."""

    def __init__(self, phase: str):
        self.phase = phase
        self.failed: list[str] = []

    def close(self, name: str, err: float, tol: float) -> None:
        ok = bool(np.isfinite(err) and err <= tol)
        print(f"{self.phase} {name}: err {err:.3e} tol {tol:.0e} "
              f"{'ok' if ok else 'FAIL'}", flush=True)
        if not ok:
            self.failed.append(name)

    def codes(self, name: str, out, ref, qmeta, tol: float) -> None:
        """``out`` u8 codes vs the f32 oracle output ``ref``: every code
        must lie between the encodings of ``ref -/+ tol*max|ref|``
        (encoding is monotone in the value)."""
        delta = tol * float(jnp.max(jnp.abs(ref)))
        lo = code_levels(eq.encode_meta(ref - delta, qmeta))
        hi = code_levels(eq.encode_meta(ref + delta, qmeta))
        got = code_levels(out)
        outside = float(np.mean((got < lo) | (got > hi)))
        off = float(np.mean(got != code_levels(eq.encode_meta(ref, qmeta))))
        ok = outside == 0.0
        print(f"{self.phase} {name}: codes outside oracle +/- tol*max "
              f"{outside:.3e} (tol {tol:.0e}); codes differing "
              f"{off:.3e} {'ok' if ok else 'FAIL'}", flush=True)
        if not ok:
            self.failed.append(name)

    def true(self, name: str, cond: bool, detail: str = "") -> None:
        print(f"{self.phase} {name}: {detail} {'ok' if cond else 'FAIL'}",
              flush=True)
        if not cond:
            self.failed.append(name)


def _oracle(fn, *args, **kw):
    """An oracle call at "highest" matmul precision (the kernels run at
    the default)."""
    with jax.default_matmul_precision("highest"):
        return fn(*args, **kw)


def _qweight(key, shape, scale=0.02):
    codes, qp = eq.quantize(jax.random.normal(key, shape, jnp.float32)
                            * scale, 7)
    return codes, eq.pack_qmeta(qp)


def _per_head_meta(x):
    """One fitted parameter set per KV head of ``x`` [..., n_kv, hd]."""
    return jnp.stack([eq.pack_qmeta(eq.fit(x[..., n, :], 7))
                      for n in range(x.shape[-2])])


def _encode_heads(x, qmeta):
    return eq.encode_meta(x, qmeta[:, None, :])


# ------------------------------------------------------------ kernels --

def kernels_phase(w: Widths, seed: int = 0, autotune_m: int = 256) -> list:
    """Each main-path kernel on the device against its oracle."""
    ck = Checks("kernels")
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))

    # -- weight matmuls (decode M = slots; one prefill-sized M autotuned)
    x = jax.random.normal(next(keys), (w.slots, w.d), jnp.float32)
    wc, wq = _qweight(next(keys), (w.d, w.d))
    ref = _oracle(lm.lut_dequant_matmul_ref, x, wc, wq)
    ck.close("matmul", max_rel_err(lm.lut_dequant_matmul(x, wc, wq), ref),
             TOL_MATMUL)

    xm = jax.random.normal(next(keys), (autotune_m, w.d), jnp.float32)
    ref = _oracle(lm.lut_dequant_matmul_ref, xm, wc, wq)
    out = lm.lut_dequant_matmul(xm, wc, wq, autotune=True)
    ck.close(f"matmul M={autotune_m} autotuned", max_rel_err(out, ref),
             TOL_MATMUL)

    ec, eqm = _qweight(next(keys), (w.vocab, w.d))
    ref = _oracle(lm.lut_dequant_matmul_ref, x, ec.T, eqm)
    ck.close("matmul tied unembed",
             max_rel_err(lm.lut_dequant_matmul(x, ec, eqm,
                                               transpose_codes=True), ref),
             TOL_MATMUL)
    del ec, ref

    gc_, gq = _qweight(next(keys), (w.d, w.ff))
    uc, uq = _qweight(next(keys), (w.d, w.ff))
    ref = _oracle(lm.lut_dequant_matmul_gated_ref, x, gc_, uc, gq, uq)
    ck.close("matmul gated",
             max_rel_err(lm.lut_dequant_matmul_gated(x, gc_, uc, gq, uq),
                         ref), TOL_MATMUL)

    # -- dual-operand (activation codes in), with and without the
    #    quantize epilogue (codes out)
    xc, xq = eq.quantize(x, 7)
    xq = eq.pack_qmeta(xq)
    ref = _oracle(lm.lut_dequant_matmul_dual_ref, xc, wc, xq, wq)
    ck.close("matmul dual",
             max_rel_err(lm.lut_dequant_matmul_dual(xc, wc, xq, wq), ref),
             TOL_MATMUL)
    oq = eq.pack_qmeta(eq.fit(ref, 7))
    ck.codes("matmul dual out_quant",
             lm.lut_dequant_matmul_dual(xc, wc, xq, wq, out_qmeta=oq), ref,
             oq, TOL_MATMUL)

    ref = _oracle(lm.lut_dequant_matmul_dual_gated_ref, xc, gc_, uc, xq, gq,
                  uq)
    ck.close("matmul dual_gated",
             max_rel_err(lm.lut_dequant_matmul_dual_gated(
                 xc, gc_, uc, xq, gq, uq), ref), TOL_MATMUL)
    oq = eq.pack_qmeta(eq.fit(ref, 7))
    ck.codes("matmul dual_gated out_quant",
             lm.lut_dequant_matmul_dual_gated(xc, gc_, uc, xq, gq, uq,
                                              out_qmeta=oq), ref, oq,
             TOL_MATMUL)

    # -- paged attention over a scrambled page pool; one row inactive
    r = np.random.default_rng(seed)
    pool = w.slots * w.max_blk + 1
    cap = w.max_blk * w.block
    tables = jnp.asarray(r.permutation(np.arange(1, pool)).reshape(
        w.slots, w.max_blk), jnp.int32)
    pshape = (pool, w.block, w.n_kv, w.hd)
    kp = jax.random.normal(next(keys), pshape, jnp.float32)
    vp = jax.random.normal(next(keys), pshape, jnp.float32)
    lengths = jnp.asarray(np.r_[0, r.integers(1, cap + 1, w.slots - 1)],
                          jnp.int32)
    q1 = jax.random.normal(next(keys), (w.slots, w.n_kv, w.g, w.hd))
    chunk = min(w.chunk, cap)
    qs = jax.random.normal(next(keys), (w.slots, chunk, w.n_kv, w.g, w.hd))
    start = r.integers(0, cap - chunk + 1, w.slots)
    valid = np.r_[0, r.integers(1, chunk + 1, w.slots - 1)]
    q_start = jnp.asarray(start, jnp.int32)
    kv_lens = jnp.asarray(np.where(valid > 0, start + valid, 0), jnp.int32)

    for name, dt in (("f32", jnp.float32), ("f8", jnp.float8_e4m3fn)):
        k, v = kp.astype(dt), vp.astype(dt)
        ref = _oracle(dg.decode_gqa_paged_ref, q1, k, v, tables, lengths)
        # the dense oracle averages an all-masked row; the kernel emits
        # zeros for it (an inactive serving slot)
        ref = jnp.where((lengths > 0)[:, None, None, None], ref, 0.0)
        ck.close(f"decode_gqa_paged {name}",
                 max_rel_err(dg.decode_gqa_paged(q1, k, v, tables, lengths),
                             ref), TOL_ATTN)
        ref = _oracle(fp.flash_prefill_paged_ref, qs, k, v, tables,
                      q_start, kv_lens)
        ck.close(f"flash_prefill_paged {name} chunk={chunk}",
                 max_rel_err(fp.flash_prefill_paged(qs, k, v, tables,
                                                    q_start, kv_lens), ref),
                 TOL_ATTN)

    # -- codes mode: q, K, V as u8 codes with per-head parameters, the
    #    context re-encoded under the attn_out parameters; the f32
    #    oracles run on the decoded operands
    kq, vq = _per_head_meta(kp), _per_head_meta(vp)
    kc, vc = _encode_heads(kp, kq), _encode_heads(vp, vq)
    kd, vd = decode_heads(kq, kc), decode_heads(vq, vc)
    qq = eq.pack_qmeta(eq.fit(qs, 7))
    oq = eq.pack_qmeta(eq.fit(vp, 7))
    q1c, qsc = eq.encode_meta(q1, qq), eq.encode_meta(qs, qq)
    ref = _oracle(dg.decode_gqa_paged_ref, eq.decode_meta(q1c, qq), kd, vd,
                  tables, lengths)
    ref = jnp.where((lengths > 0)[:, None, None, None], ref, 0.0)
    ck.codes("decode_gqa_paged_codes",
             dg.decode_gqa_paged_codes(q1c, kc, vc, qq, kq, vq, oq, tables,
                                       lengths), ref, oq, TOL_ATTN)
    ref = _oracle(fp.flash_prefill_paged_ref, eq.decode_meta(qsc, qq), kd,
                  vd, tables, q_start, kv_lens)
    ck.codes(f"flash_prefill_paged_codes chunk={chunk}",
             fp.flash_prefill_paged_codes(qsc, kc, vc, qq, kq, vq, oq,
                                          tables, q_start, kv_lens), ref,
             oq, TOL_ATTN)

    stats = lm.ops.tune_stats()
    print(f"kernels autotuner: tilings tried {stats['tried']} failed "
          f"{stats['failed']}", flush=True)
    for key, tile, err in stats["failures"]:
        print(f"kernels autotuner failure {key} {tile}: {err}", flush=True)
    return ck.failed


# -------------------------------------------------------------- serve --

def serve_requests(vocab: int, lens=(64, 600, 300, 150), shared=128,
                   tails=(64, 200, 100, 400), new_tokens: int = 32,
                   seed: int = 0):
    """(first, later): 8 seeded requests.  Four prompts open with one
    ``shared``-token prefix: two in ``first``, and two in ``later``,
    submitted once the first two retired (the trie holds a sequence's
    pages only after it retires), so they hit."""
    r = np.random.default_rng(seed)
    prefix = r.integers(0, vocab, shared).astype(np.int32)

    def req(uid, tail, with_prefix):
        body = r.integers(0, vocab, tail).astype(np.int32)
        prompt = np.concatenate([prefix, body]) if with_prefix else body
        return Request(uid, prompt, max_new_tokens=new_tokens)

    first = [req(0, tails[0], True), req(1, tails[1], True)]
    first += [req(2 + i, n, False) for i, n in enumerate(lens)]
    later = [req(6, tails[2], True), req(7, tails[3], True)]
    return first, later


def drive(eng: Engine, first, later) -> dict:
    """Serve ``first``; once the prefix-sharing pair (uids 0, 1) has
    retired, submit ``later``; drain.  Returns {uid: Completion}."""
    for r in first:
        eng.submit(r)
    while eng.result(0) is None or eng.result(1) is None:
        if not eng.pending:
            raise RuntimeError("engine idle before uids 0 and 1 retired")
        eng.step()
    for r in later:
        eng.submit(r)
    return {c.uid: c for c in eng.run()}


def _check_served(ck: Checks, eng: Engine, done: dict, reqs) -> None:
    statuses = sorted({c.status for c in done.values()})
    ck.true("all requests ok", set(done) == {r.uid for r in reqs}
            and statuses == [ST_OK], f"statuses {statuses}")
    lens = {len(done[r.uid].tokens) for r in reqs if r.uid in done}
    ck.true("tokens per request", lens == {reqs[0].max_new_tokens},
            f"{sorted(lens)}")
    ck.true("finite logits", eng.nan_rows_detected == 0,
            f"non-finite rows {eng.nan_rows_detected}")
    hits = eng.prefix_stats.hits
    ck.true("prefix hits", hits > 0, f"hits {hits}")


def serve_phase(cfg, ecfg: EngineConfig, first, later, logit_uids,
                seed: int = 0):
    """Returns (failed checks, served tokens by uid)."""
    ck = Checks("serve")
    eng = Engine(cfg, quant_bits=7, rng_seed=seed, engine=ecfg)
    # start-up (f32 init, then quantization) may set the phase's peak
    print(f"serve engine built: peak_bytes_in_use "
          f"{_peak_bytes(jax.devices()[0])}", flush=True)
    done = drive(eng, first, later)
    reqs = first + later
    _check_served(ck, eng, done, reqs)

    by_uid = {r.uid: r for r in reqs}
    prompts = [by_uid[u].prompt for u in logit_uids]
    got = eng.prompt_logits(prompts)
    with ll.policy(mode="materialize", flash_decode=False), \
            jax.default_matmul_precision("highest"):
        ref = eng.prompt_logits(prompts)
    for u, err, g, rf in zip(logit_uids, rel_logit_err(got, ref), got, ref):
        ck.close(f"first-token logits uid {u} vs kernel-free forward",
                 float(err), TOL_LOGITS)
        print(f"serve uid {u}: served first token {done[u].tokens[0]}, "
              f"argmax kernels {int(np.argmax(g))}, reference "
              f"{int(np.argmax(rf))}", flush=True)
    return ck.failed, {u: c.tokens for u, c in done.items()}


def serve_codes_phase(cfg, ecfg: EngineConfig, first, later,
                      served: dict | None, seed: int = 0):
    """The serve phase's weights (same seed), with activations and KV
    pages as exponent codes; token agreement with ``served`` is printed,
    not gated."""
    ck = Checks("serve-codes")
    eng = Engine(cfg, quant_bits=7, act_quant=7, kv_codes=True,
                 rng_seed=seed, engine=ecfg)
    done = drive(eng, first, later)
    _check_served(ck, eng, done, first + later)
    if served:
        agree = np.mean([
            np.mean(np.asarray(served[u])[:n] == c.tokens[:n])
            for u, c in done.items()
            if u in served and (n := min(len(served[u]), len(c.tokens)))])
        print(f"serve-codes token agreement with serve: {agree:.4f} "
              f"(reported, not gated)", flush=True)
    return ck.failed


# --------------------------------------------------------------- main --

def _peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = CompileClock()

    cfg = get_config("qwen3-1.7b")
    ecfg = EngineConfig(num_slots=8, max_seq_len=1024, prefix_cache=True,
                        prefill_chunk=256)
    first, later = serve_requests(cfg.vocab_size)
    served: dict = {}

    def serve():
        failed, tokens = serve_phase(cfg, ecfg, first, later,
                                     logit_uids=(2, 1, 3))
        served.update(tokens)
        return failed

    phases = [("kernels", lambda: kernels_phase(Widths.of(cfg))),
              ("serve", serve),
              ("serve-codes", lambda: serve_codes_phase(
                  cfg, ecfg, first, later, served))]
    # the serving phases use the default tiling; the kernels phase
    # exercises the autotuner on one prefill-sized shape
    failed_phases = []
    for name, run in phases:
        t0, c0 = time.perf_counter(), clock.seconds
        try:
            with ll.policy(autotune=False if name != "kernels" else None):
                failed = run()
        except Exception:  # noqa: BLE001 — reported, and the run fails
            traceback.print_exc()
            failed = ["raised"]
        gc.collect()
        wall, comp = time.perf_counter() - t0, clock.seconds - c0
        print(f"phase {name}: {'ok' if not failed else 'FAILED'} "
              f"(smoke timing, not a metric: wall {wall:.1f} s, compile "
              f"{comp:.1f} s) peak_bytes_in_use {_peak_bytes(dev)}",
              flush=True)
        if failed:
            failed_phases.append(f"{name}: {', '.join(failed)}")
    stats = lm.ops.tune_stats()
    print(f"autotuner: tilings tried {stats['tried']} failed "
          f"{stats['failed']}", flush=True)
    if failed_phases:
        print("chip_smoke: FAILED " + "; ".join(failed_phases),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
