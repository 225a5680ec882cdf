"""Public wrapper: M-bucketing, autotuned tiling, padding.

Two serving-critical behaviours live here (DESIGN.md §Fused-path):

* **M-bucketing** — ``bm`` used to be derived from the raw ``m``, so
  every distinct batch/sequence length compiled a fresh ``pallas_call``.
  M is now padded up a small fixed ladder (then to multiples of 512), so
  serving sees a handful of compiled kernels regardless of batch mix.
* **Autotuning** — ``(bm, bk, bn)`` per padded shape is picked by timing
  candidate tilings on the real device and cached persistently (JSON, see
  DESIGN.md for the format), keyed by the chip's ``device_kind``.
  Tuning only triggers on a real TPU backend (or with
  ``REPRO_AUTOTUNE=1``); CPU/interpret runs use the default tiling so
  tests never pay tuning time.  A candidate that fails to compile or run
  is counted (:func:`tune_stats`), never silently dropped.
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.lut_dequant_matmul.lut_dequant_matmul import (
    lut_dequant_matmul_dual_gated_kernel,
    lut_dequant_matmul_dual_kernel,
    lut_dequant_matmul_gated_kernel,
    lut_dequant_matmul_kernel,
)
from repro.kernels.lut_dequant_matmul.ref import (
    lut_dequant_matmul_dual_gated_ref,
    lut_dequant_matmul_dual_ref,
    lut_dequant_matmul_gated_ref,
    lut_dequant_matmul_ref,
)
from repro.caches import repo_cache_path

# Fixed ladder keeps the set of compiled M shapes small; beyond the
# ladder, multiples of 512 (decode batches and prefill token counts both
# land there).
M_LADDER = (8, 16, 32, 64, 128, 256, 512)
_VMEM_BUDGET = 8 * 1024 * 1024
# v3: keys lead with the chip's device_kind (not the backend name) and
# lost the decode-mode component (one decode remains).
_TUNE_VERSION = 3

# Activation-representation tag for uint8 DNA-TEQ act codes (fp
# operands tag with their dtype name).
ACT_CODE_REP = "u8code"


def _xrep(x) -> str:
    """The activation operand's representation, as a cache-key token."""
    return ACT_CODE_REP if x.dtype == jnp.uint8 else str(x.dtype)


def bucket_m(m: int) -> int:
    """Smallest ladder entry >= m (multiples of 512 past the ladder)."""
    for b in M_LADDER:
        if m <= b:
            return b
    return -(-m // 512) * 512


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pad_axis_to(x, size, axis):
    if x.shape[axis] == size:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, size - x.shape[axis])
    return jnp.pad(x, widths)


def _default_tiling(m_pad: int, k_pad: int, n_pad: int):
    return (min(128, m_pad), min(128, k_pad), min(128, n_pad))


def _candidate_tilings(m_pad: int, k_pad: int, n_pad: int,
                       dual: bool = False):
    """Divisibility- and VMEM-feasible (bm, bk, bn) candidates.
    ``dual`` sizes for the gated kernel (two codes blocks, two
    accumulators)."""
    out = []
    n_codes = 2 if dual else 1
    n_acc = 2 if dual else 1
    for bm in (32, 64, 128, 256):
        if bm > m_pad or m_pad % bm:
            continue
        for bk in (128, 256, 512):
            if bk > k_pad or k_pad % bk:
                continue
            for bn in (128, 256, 512):
                if bn > n_pad or n_pad % bn:
                    continue
                vmem = (bm * bk * 4                     # x block
                        + n_codes * bk * bn             # codes (uint8)
                        + (n_acc + 1) * bm * bn * 4)    # acc(s) + out tile
                if vmem <= _VMEM_BUDGET:
                    out.append((bm, bk, bn))
    default = _default_tiling(m_pad, k_pad, n_pad)
    if default not in out:
        out.insert(0, default)
    return out


class Autotuner:
    """Persistent (bm, bk, bn) selection cache.

    Disk format (JSON)::

        {"version": 3,
         "entries":
            {"<device_kind>|<kind>|<m>|<k>|<n>|<xrep>|<extra>":
             {"tile": [bm, bk, bn], "us": 123.4}}}

    ``device_kind`` is the chip as JAX names it (``TPU v5 lite``), so
    a cache tuned on one TPU generation is never read on another.
    ``xrep`` is the activation operand's representation (``float32`` /
    ``bfloat16`` / ``u8code``): a dual-operand call (codes activation)
    and a fp-act call of the same geometry have different decode work
    per tile, so their tiles must never share a cache entry.

    ``tried``/``failed`` count candidate tilings timed and candidate
    tilings that raised (compile or run); ``failures`` keeps the
    (key, tile, error) of each failed candidate for the caller to print.
    """

    def __init__(self, path: str | None = None):
        self.path = path or os.environ.get(
            "REPRO_AUTOTUNE_CACHE",
            repo_cache_path("lut_dequant_matmul_tune.json"))
        self._mem: dict[str, tuple[int, int, int]] = {}
        self._disk_loaded = False
        self.tried = 0
        self.failed = 0
        self.failures: list[tuple[str, tuple[int, int, int], str]] = []

    # -- persistence ---------------------------------------------------
    def _load_disk(self):
        if self._disk_loaded:
            return
        self._disk_loaded = True
        try:
            with open(self.path) as f:
                blob = json.load(f)
            if blob.get("version") == _TUNE_VERSION:
                for key, ent in blob.get("entries", {}).items():
                    self._mem[key] = tuple(ent["tile"])
        except (OSError, ValueError, KeyError, TypeError):
            pass

    def _save_disk(self, key: str, tile, us: float):
        try:
            os.makedirs(os.path.dirname(self.path), exist_ok=True)
            blob = {"version": _TUNE_VERSION, "entries": {}}
            try:
                with open(self.path) as f:
                    old = json.load(f)
                if old.get("version") == _TUNE_VERSION:
                    blob["entries"].update(old.get("entries", {}))
            except (OSError, ValueError):
                pass
            blob["entries"][key] = {"tile": list(tile), "us": round(us, 2)}
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(blob, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except OSError:
            pass

    # -- selection -----------------------------------------------------
    def peek(self, key: str) -> tuple[int, int, int] | None:
        """Cached tiling only (memory -> disk); never times, never
        writes."""
        if key not in self._mem:
            self._load_disk()
        return self._mem.get(key)

    def get(self, key: str, candidates, bench) -> tuple[int, int, int]:
        """Best tiling for ``key``: memory cache -> disk cache -> tune.

        ``bench(tile) -> seconds`` is injectable for tests.  A candidate
        that raises is recorded in ``failed``/``failures``; when every
        candidate fails the default (``candidates[0]``) is returned
        without poisoning the cache, and the failures stay on record."""
        cached = self.peek(key)
        if cached is not None:
            return cached
        best, best_t = None, float("inf")
        for tile in candidates:
            self.tried += 1
            try:
                t = bench(tile)
            except Exception as e:  # noqa: BLE001 — recorded, reported
                self.failed += 1
                self.failures.append(
                    (key, tuple(tile), f"{type(e).__name__}: {e}"[:300]))
                continue
            if t < best_t:
                best, best_t = tile, t
        if best is None:
            return candidates[0]
        self._mem[key] = best
        self._save_disk(key, best, best_t * 1e6)
        return best


_TUNER = Autotuner()


def tune_stats() -> dict:
    """Candidate tilings the process-wide autotuner has timed and seen
    fail, with the failures themselves (key, tile, error)."""
    return {"tried": _TUNER.tried, "failed": _TUNER.failed,
            "failures": list(_TUNER.failures)}


def _autotune_enabled(autotune: bool | None, interpret: bool) -> bool:
    if autotune is not None:
        return autotune
    if os.environ.get("REPRO_AUTOTUNE") == "1":
        return True
    return (not interpret) and jax.default_backend() == "tpu"


def _bench_kernel(run, iters: int = 5) -> float:
    jax.block_until_ready(run())   # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = run()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _synth_operands(m_pad: int, k_pad: int, n_pad: int,
                    transpose_codes: bool = False, gated: bool = False,
                    act_codes: bool = False):
    """Concrete random operands of the padded shapes, for timing
    candidate tilings.  Every production call reaches this op under
    jit/vmap where the real operands are tracers — timing those would
    measure tracing, not the device — so the tuner benches on synthetic
    device-backed data of the same shapes instead (the timing of a
    tiling does not depend on operand *values*).  Runs eagerly even
    when invoked from inside a trace; the persistent cache makes it a
    once-per-shape compile-time cost."""
    r = np.random.default_rng(0)
    if act_codes:
        x = jnp.asarray(r.integers(0, 256, (m_pad, k_pad)), jnp.uint8)
    else:
        x = jnp.asarray(r.normal(size=(m_pad, k_pad)), jnp.float32)
    cshape = (n_pad, k_pad) if transpose_codes else (k_pad, n_pad)
    codes = jnp.asarray(r.integers(0, 256, cshape), jnp.uint8)
    qmeta = jnp.asarray([0.05, 0.0, 1.5, 7.0], jnp.float32)
    bias = jnp.zeros((n_pad,), jnp.float32)
    if gated:
        codes2 = jnp.asarray(r.integers(0, 256, cshape), jnp.uint8)
        return x, codes, codes2, qmeta, bias
    return x, codes, qmeta, bias


def _tune_key(kind: str, m_pad: int, k_pad: int, n_pad: int, xrep: str,
              extra: str) -> str:
    return "|".join([jax.devices()[0].device_kind, kind, str(m_pad),
                     str(k_pad), str(n_pad), xrep, extra])


def _tiling_for(kind: str, m_pad: int, k_pad: int, n_pad: int, xrep: str,
                extra: str, interpret: bool, autotune: bool | None,
                bench_factory=None):
    if not _autotune_enabled(autotune, interpret):
        return _default_tiling(m_pad, k_pad, n_pad)
    key = _tune_key(kind, m_pad, k_pad, n_pad, xrep, extra)
    cands = _candidate_tilings(
        m_pad, k_pad, n_pad, dual=kind in ("gated", "dual_gated"))
    return _TUNER.get(key, cands, bench_factory(cands))


def _meta(qmeta) -> jax.Array:
    return jnp.asarray(qmeta, jnp.float32).reshape(4)


def lut_dequant_matmul(
    x: jax.Array,          # [M, K]
    codes: jax.Array,      # [K, N] uint8 ([N, K] when transpose_codes)
    qmeta: jax.Array,      # [4] (alpha, beta, base, bits)
    *,
    epilogue: str | None = None,
    bias: jax.Array | None = None,
    transpose_codes: bool = False,
    out_dtype=None,
    interpret: bool | None = None,
    autotune: bool | None = None,
) -> jax.Array:
    """Fused decode+matmul with optional bias/activation epilogue.

    M is bucketed (see :func:`bucket_m`) so ragged serving batches reuse
    a small fixed set of compiled kernels; K/N pad to 128 lanes.
    ``transpose_codes=True`` contracts against codes stored ``[N, K]``
    (e.g. a tied embedding table) — the transpose happens per decoded
    VMEM tile inside the kernel, never on the HBM-resident table."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    out_dtype = out_dtype or x.dtype
    m, k = x.shape
    n = codes.shape[0] if transpose_codes else codes.shape[1]
    m_pad = bucket_m(m)
    xk = _pad_to(_pad_axis_to(x, m_pad, 0), 128, 1)
    ck = _pad_to(_pad_to(codes, 128, 0), 128, 1)
    if transpose_codes:
        n_pad, k_pad = ck.shape
    else:
        k_pad, n_pad = ck.shape
    has_bias = bias is not None
    bias_arr = (_pad_axis_to(bias.astype(jnp.float32), n_pad, 0)
                if has_bias else jnp.zeros((n_pad,), jnp.float32))

    def bench_factory(_cands):
        sx, sc, sqm, sb = _synth_operands(
            m_pad, k_pad, n_pad, transpose_codes=transpose_codes)

        def bench(tile):
            bm, bk, bn = tile
            return _bench_kernel(lambda: lut_dequant_matmul_kernel(
                sx, sc, sqm, sb, bm=bm, bk=bk, bn=bn, epilogue=epilogue,
                has_bias=has_bias, w_transposed=transpose_codes,
                out_dtype=jnp.float32, interpret=interpret))
        return bench

    bm, bk, bn = _tiling_for(
        "mm", m_pad, k_pad, n_pad, _xrep(x),
        f"{epilogue}|{int(has_bias)}|{int(transpose_codes)}",
        interpret, autotune, bench_factory)
    out = lut_dequant_matmul_kernel(
        xk, ck, _meta(qmeta), bias_arr, bm=bm, bk=bk, bn=bn,
        epilogue=epilogue, has_bias=has_bias, w_transposed=transpose_codes,
        out_dtype=jnp.float32, interpret=interpret)
    return out[:m, :n].astype(out_dtype)


def lut_dequant_matmul_gated(
    x: jax.Array,          # [M, K]
    codes_g: jax.Array,    # [K, N] uint8 (gate)
    codes_u: jax.Array,    # [K, N] uint8 (up)
    qmeta_g: jax.Array,    # [4]
    qmeta_u: jax.Array,    # [4]
    *,
    activation: str = "silu",
    out_dtype=None,
    interpret: bool | None = None,
    autotune: bool | None = None,
) -> jax.Array:
    """Fused ``act(x @ dec(codes_g)) * (x @ dec(codes_u))`` — the gated
    MLP front half in one kernel: one x DMA feeds both matmuls, and the
    gate intermediate never exists in HBM."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    out_dtype = out_dtype or x.dtype
    m, k = x.shape
    _, n = codes_g.shape
    m_pad = bucket_m(m)
    xk = _pad_to(_pad_axis_to(x, m_pad, 0), 128, 1)
    cg = _pad_to(_pad_to(codes_g, 128, 0), 128, 1)
    cu = _pad_to(_pad_to(codes_u, 128, 0), 128, 1)
    k_pad, n_pad = cg.shape
    qmetas = jnp.stack([_meta(qmeta_g), _meta(qmeta_u)])

    def bench_factory(_cands):
        sx, scg, scu, sqm, _sb = _synth_operands(
            m_pad, k_pad, n_pad, gated=True)
        sqms = jnp.stack([sqm, sqm])

        def bench(tile):
            bm, bk, bn = tile
            return _bench_kernel(lambda: lut_dequant_matmul_gated_kernel(
                sx, scg, scu, sqms, bm=bm, bk=bk, bn=bn,
                activation=activation, out_dtype=jnp.float32,
                interpret=interpret))
        return bench

    bm, bk, bn = _tiling_for(
        "gated", m_pad, k_pad, n_pad, _xrep(x), activation,
        interpret, autotune, bench_factory)
    out = lut_dequant_matmul_gated_kernel(
        xk, cg, cu, qmetas, bm=bm, bk=bk, bn=bn, activation=activation,
        out_dtype=jnp.float32, interpret=interpret)
    return out[:m, :n].astype(out_dtype)


def lut_dequant_matmul_dual(
    x_codes: jax.Array,    # [M, K] uint8 activation codes
    codes: jax.Array,      # [K, N] uint8 weight codes
    qmeta_x: jax.Array,    # [4] activation params
    qmeta_w: jax.Array,    # [4] weight params
    *,
    epilogue: str | None = None,
    bias: jax.Array | None = None,
    out_qmeta: jax.Array | None = None,
    out_dtype=jnp.float32,
    interpret: bool | None = None,
    autotune: bool | None = None,
) -> jax.Array:
    """Dual-operand fused matmul: BOTH operands cross HBM as uint8
    DNA-TEQ codes, each decoding through its own parameters inside the
    kernel.  ``out_qmeta`` turns on the quantize epilogue: the flushed
    tile is re-encoded against those (calibrated) output params and the
    call returns uint8 codes — consecutive quantized matmuls stay
    code-in/code-out with no f32 intermediate in HBM.

    K is padded to 128 lanes; because a zero pad *byte* is a live code
    (it decodes to ±(alpha·base^e_min + beta)), the kernel masks the
    decoded activation tile against the true contraction length."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    m, k = x_codes.shape
    n = codes.shape[1]
    m_pad = bucket_m(m)
    xk = _pad_to(_pad_axis_to(x_codes, m_pad, 0), 128, 1)
    ck = _pad_to(_pad_to(codes, 128, 0), 128, 1)
    k_pad, n_pad = ck.shape
    k_valid = k if k_pad != k else None
    out_quant = out_qmeta is not None
    qmetas = jnp.stack([
        _meta(qmeta_x), _meta(qmeta_w),
        _meta(out_qmeta) if out_quant else jnp.zeros((4,), jnp.float32)])
    has_bias = bias is not None
    bias_arr = (_pad_axis_to(bias.astype(jnp.float32), n_pad, 0)
                if has_bias else jnp.zeros((n_pad,), jnp.float32))

    def bench_factory(_cands):
        sx, sc, sqm, sb = _synth_operands(
            m_pad, k_pad, n_pad, act_codes=True)
        sqms = jnp.stack([sqm, sqm, sqm])

        def bench(tile):
            bm, bk, bn = tile
            return _bench_kernel(lambda: lut_dequant_matmul_dual_kernel(
                sx, sc, sqms, sb, bm=bm, bk=bk, bn=bn, epilogue=epilogue,
                has_bias=has_bias, out_quant=out_quant, k_valid=k_valid,
                out_dtype=jnp.float32, interpret=interpret))
        return bench

    bm, bk, bn = _tiling_for(
        "dual", m_pad, k_pad, n_pad, _xrep(x_codes),
        f"{epilogue}|{int(has_bias)}|{int(out_quant)}",
        interpret, autotune, bench_factory)
    out = lut_dequant_matmul_dual_kernel(
        xk, ck, qmetas, bias_arr, bm=bm, bk=bk, bn=bn, epilogue=epilogue,
        has_bias=has_bias, out_quant=out_quant, k_valid=k_valid,
        out_dtype=jnp.float32, interpret=interpret)
    out = out[:m, :n]
    return out if out_quant else out.astype(out_dtype)


def lut_dequant_matmul_dual_gated(
    x_codes: jax.Array,    # [M, K] uint8 activation codes
    codes_g: jax.Array,    # [K, N] uint8 (gate)
    codes_u: jax.Array,    # [K, N] uint8 (up)
    qmeta_x: jax.Array,
    qmeta_g: jax.Array,
    qmeta_u: jax.Array,
    *,
    activation: str = "silu",
    out_qmeta: jax.Array | None = None,
    out_dtype=jnp.float32,
    interpret: bool | None = None,
    autotune: bool | None = None,
) -> jax.Array:
    """Gated-MLP front half on an activation-code operand: one shared
    in-kernel act decode feeds both matmuls, and ``out_qmeta``
    re-encodes the gated flush so the down projection reads codes."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    m, k = x_codes.shape
    n = codes_g.shape[1]
    m_pad = bucket_m(m)
    xk = _pad_to(_pad_axis_to(x_codes, m_pad, 0), 128, 1)
    cg = _pad_to(_pad_to(codes_g, 128, 0), 128, 1)
    cu = _pad_to(_pad_to(codes_u, 128, 0), 128, 1)
    k_pad, n_pad = cg.shape
    k_valid = k if k_pad != k else None
    out_quant = out_qmeta is not None
    qmetas = jnp.stack([
        _meta(qmeta_x), _meta(qmeta_g), _meta(qmeta_u),
        _meta(out_qmeta) if out_quant else jnp.zeros((4,), jnp.float32)])

    def bench_factory(_cands):
        sx, scg, scu, sqm, _sb = _synth_operands(
            m_pad, k_pad, n_pad, gated=True, act_codes=True)
        sqms = jnp.stack([sqm] * 4)

        def bench(tile):
            bm, bk, bn = tile
            return _bench_kernel(
                lambda: lut_dequant_matmul_dual_gated_kernel(
                    sx, scg, scu, sqms, bm=bm, bk=bk, bn=bn,
                    activation=activation, out_quant=out_quant,
                    k_valid=k_valid, out_dtype=jnp.float32,
                    interpret=interpret))
        return bench

    bm, bk, bn = _tiling_for(
        "dual_gated", m_pad, k_pad, n_pad, _xrep(x_codes),
        f"{activation}|{int(out_quant)}", interpret, autotune,
        bench_factory)
    out = lut_dequant_matmul_dual_gated_kernel(
        xk, cg, cu, qmetas, bm=bm, bk=bk, bn=bn, activation=activation,
        out_quant=out_quant, k_valid=k_valid, out_dtype=jnp.float32,
        interpret=interpret)
    out = out[:m, :n]
    return out if out_quant else out.astype(out_dtype)


__all__ = ["lut_dequant_matmul", "lut_dequant_matmul_gated",
           "lut_dequant_matmul_dual", "lut_dequant_matmul_dual_gated",
           "lut_dequant_matmul_ref", "lut_dequant_matmul_gated_ref",
           "lut_dequant_matmul_dual_ref", "lut_dequant_matmul_dual_gated_ref",
           "bucket_m", "Autotuner", "M_LADDER", "ACT_CODE_REP",
           "tune_stats"]
