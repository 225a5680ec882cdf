"""Activation-quantization calibration: fit per-(layer, site) DNA-TEQ
``ExpQuantParams`` on sample prompts and attach them to the params tree.

The paper (§II-C, ref [25]) quantizes *both* dot-product operands to
exponent codes; weights are fit offline, activations need a short
calibration pass because their distribution depends on the data.  The
runtime does that here: one forward over sample prompts through the
model's ``collect_act_calibration`` hook captures the float tensor
feeding every quantized matmul (sites in
:data:`repro.models.layers.ACT_SITES`), and each (layer, site) gets its
own (alpha, beta, base) via the alternating-LS / base-grid search in
:mod:`repro.core.exponential_quant`.

The fitted metas ride the params tree as
``params["blocks"]["act_q"][site] = {"lut": [L, 256], "qmeta": [L, 4]}``
so ``lax.scan`` slices one table per layer and the jitted serving steps
need no new arguments.  The KV sites (``attn_k``/``attn_v`` — what the
codes-mode KV cache stores) are fit **per head**: attention heads see
very different key/value scales, so each head gets its own (alpha,
beta, base) — ``{"lut": [L, n_kv, 256], "qmeta": [L, n_kv, 4]}`` —
which is the accuracy lever when attention goes to codes.  ``attn_q``
(the roped query fed to the flash kernels) stays per-tensor: it is
consumed against all heads' K tables at once.

**Calibration cache.**  Fits are memoized on disk next to the kernel
autotuner cache (same discipline: atomic tmp+rename writes, versioned):

```json
{"version": 2,
 "entries": {
   "<cfg.name>|L<num_layers>|d<d_model>|f<d_ff>|b<bits>|"
   "c<n_prompts>x<seq_len>|p<prompts_crc32>|s<seed>|w<params_fingerprint>":
   {"sites": {"attn_in": [[alpha, beta, base, bits], ...one per layer],
              "attn_k": [[[alpha, beta, base, bits], ...one per head],
                         ...one per layer],
              ...},
    "sqnr_db": {"attn_in": [...], "attn_k": [[...per head], ...], ...}}}}
```

Version 2 added the attention-boundary sites (``attn_q`` per-layer,
``attn_k``/``attn_v`` per-layer-per-head); the version check below
cleanly invalidates v1 caches — a v1 blob is ignored on load and
overwritten wholesale on the next save, never merged.

* location: ``~/.cache/repro/act_quant_calib.json`` (override:
  ``REPRO_ACT_CALIB_CACHE``);
* the key includes a cheap fingerprint of the weight values — the same
  architecture re-initialized from another seed must not reuse metas
  fit against different weights;
* decode LUTs are NOT stored: they are rebuilt from the metas
  (``decode_meta`` over the 256 code points), so a cache hit and a
  fresh fit produce bit-identical tables.
"""

from __future__ import annotations

import json
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.caches import repo_cache_path
from repro.core import exponential_quant as eq

_CALIB_VERSION = 2

# Sites fit per-channel along a head axis of the captured sample
# (``{site: axis}`` — axis is relative to the [L, ...sample...] stack).
# attn_k/attn_v feed the codes-mode KV cache: the captured tensors are
# [L, B, S, n_kv, hd], so the head axis is -2.
PER_HEAD_SITES: dict[str, int] = {"attn_k": -2, "attn_v": -2}

# Base grid for *activation* fits: extends the weight-side default
# (2^(1/k), k ≤ 16) with much finer steps, down to 2^(1/256) ≈ 1.0027.
# Post-norm activations span a small dynamic range, so a fine base
# trades unneeded range for per-step resolution; near base → 1 the
# exponential spacing degenerates toward *uniform* over a narrow band
# (with beta as the offset), which is the right shape for the
# gated-MLP intermediate — measured +6 dB SQNR over the weight grid on
# that site, the hardest tensor in the stack.  More alternating-LS
# iterations (ACT_FIT_ITERS) are needed for the fine bases to
# converge; calibration is one-shot and disk-cached, so the extra fit
# cost is irrelevant.
ACT_BASES: tuple[float, ...] = tuple(
    float(2.0 ** (1.0 / k)) for k in (1, 2, 3, 4, 6, 8, 12, 16, 24,
                                      32, 48, 64, 96, 128, 192, 256))
ACT_FIT_ITERS = 20


def cache_path() -> str:
    return os.environ.get("REPRO_ACT_CALIB_CACHE",
                          repo_cache_path("act_quant_calib.json"))


def _params_fingerprint(params) -> str:
    """Cheap, deterministic stamp of the weight values so cached metas
    never cross weight sets: float-leaf count plus total L1 mass (one
    reduction per leaf, once at startup).  Single leaves can collide —
    init-constant norm gains are identical across seeds — so the sum
    runs over every float leaf (for a quantized tree that is the decode
    LUTs, norms and embeddings, which pin the weight codes)."""
    leaves = [l for l in jax.tree_util.tree_leaves(params)
              if hasattr(l, "dtype") and jnp.issubdtype(l.dtype,
                                                        jnp.floating)]
    if not leaves:
        return "none"
    tot = sum(float(jnp.sum(jnp.abs(l))) for l in leaves)
    return f"{len(leaves)}_{tot:.6e}"


def calib_key(cfg, bits: int, prompts: np.ndarray, seed: int,
              params) -> str:
    """Cache key: architecture, bits, the calibration prompts (shape
    AND content — a user-supplied prompt set of the same shape must
    not reuse metas fit on different data), and the weight values."""
    p = np.ascontiguousarray(np.asarray(prompts, np.int32))
    crc = zlib.crc32(p.tobytes())
    return (f"{cfg.name}|L{cfg.num_layers}|d{cfg.d_model}|f{cfg.d_ff}"
            f"|b{bits}|c{p.shape[0]}x{p.shape[1]}|p{crc:08x}|s{seed}"
            f"|w{_params_fingerprint(params)}")


def lut_from_qmeta(qmeta: jax.Array) -> jax.Array:
    """Rebuild the 256-entry decode table from packed params — the
    single construction both the fresh-fit and cache-hit paths use."""
    return eq.decode_meta(jnp.arange(256, dtype=jnp.int32), qmeta)


def _luts_from_qmeta(qmeta: jax.Array) -> jax.Array:
    """``[..., 4]`` packed metas -> ``[..., 256]`` decode tables (vmap
    over every leading dim, so per-layer and per-layer-per-head metas
    build through the same code path)."""
    f = lut_from_qmeta
    for _ in range(qmeta.ndim - 1):
        f = jax.vmap(f)
    return f(qmeta)


def fit_sites(samples: dict, bits: int):
    """Fit per-(layer, site) params on captured activations.

    ``samples`` is ``{site: [L, ...]}`` from the model's calibration
    hook.  Returns ``(act_q, report)`` where ``act_q`` maps each site
    to ``{"lut": [L, 256], "qmeta": [L, 4]}`` — or, for the per-head KV
    sites (:data:`PER_HEAD_SITES`), ``{"lut": [L, n_kv, 256], "qmeta":
    [L, n_kv, 4]}`` — and ``report`` to the round-trip SQNR in dB with
    the same nesting (per layer, or per layer per head)."""
    def fit_one(t):
        qp = eq.fit(t.reshape(-1).astype(jnp.float32), bits,
                    bases=ACT_BASES, iters=ACT_FIT_ITERS)
        return eq.pack_qmeta(qp), eq.sqnr_db(t.astype(jnp.float32), qp)

    act_q, report = {}, {}
    for site, x_l in samples.items():
        fit = jax.vmap(fit_one)
        if site in PER_HEAD_SITES:
            ax = PER_HEAD_SITES[site] % x_l.ndim
            x_l = jnp.moveaxis(x_l, ax, 1)          # [L, n_kv, ...]
            x_l = x_l.reshape(x_l.shape[0], x_l.shape[1], -1)
            fit = jax.vmap(fit)
        metas, sqnrs = fit(x_l)
        act_q[site] = {"lut": _luts_from_qmeta(metas), "qmeta": metas}
        report[site] = np.asarray(sqnrs, np.float64).tolist()
    return act_q, report


def measure_sqnr(samples: dict, act_q: dict) -> dict[str, float]:
    """Round-trip SQNR (dB) of captured float activations under
    *already-fitted* tables — the serving-time counterpart of the
    calibration report.  ``samples`` is ``{site: [L, ...]}`` from the
    model's calibration hook on live traffic; each (layer, site) —
    and each head for :data:`PER_HEAD_SITES` — round-trips through its
    own packed qmeta (``encode_meta``/``decode_meta``, exactly the
    serving encode).  Returns one scalar per site present in both
    dicts (mean over layers, and heads where applicable): the number
    the drift guard compares against the report."""
    def one(t, qmeta):
        t = t.reshape(-1).astype(jnp.float32)
        back = eq.decode_meta(eq.encode_meta(t, qmeta), qmeta)
        num = jnp.sum(t * t)
        den = jnp.sum((t - back) ** 2) + 1e-12
        return 10.0 * jnp.log10(num / den + 1e-12)

    out: dict[str, float] = {}
    for site, x_l in samples.items():
        if site not in act_q:
            continue
        qmeta = jnp.asarray(act_q[site]["qmeta"], jnp.float32)
        f = jax.vmap(one)
        if site in PER_HEAD_SITES:
            ax = PER_HEAD_SITES[site] % x_l.ndim
            x_l = jnp.moveaxis(x_l, ax, 1)          # [L, n_kv, ...]
            x_l = x_l.reshape(x_l.shape[0], x_l.shape[1], -1)
            f = jax.vmap(f)
        out[site] = float(jnp.mean(f(x_l, qmeta)))
    return out


def report_means(report: dict | None) -> dict[str, float]:
    """Per-site mean SQNR from a calibration report, flattening the
    per-head nesting — the drift guard's reference line."""
    if not report:
        return {}
    return {site: float(np.mean(np.asarray(v, np.float64)))
            for site, v in report.items()}


def kv_tables_fingerprint(act_q: dict) -> int:
    """CRC32 over the packed per-head attn_k/attn_v metas — the
    identity of a codes-mode KV byte stream.  Two engines share a
    fingerprint iff their u8 pages decode through identical tables,
    which is what makes a cross-worker page handoff legal."""
    crc = 0
    for site in ("attn_k", "attn_v"):
        q = np.ascontiguousarray(np.asarray(act_q[site]["qmeta"],
                                            np.float32))
        crc = zlib.crc32(q.tobytes(), crc)
    return crc


def _act_q_from_entry(entry: dict):
    act_q = {}
    for site, metas in entry["sites"].items():
        qmeta = jnp.asarray(metas, jnp.float32)
        act_q[site] = {"lut": _luts_from_qmeta(qmeta), "qmeta": qmeta}
    return act_q, {s: list(v) for s, v in entry.get("sqnr_db", {}).items()}


def _load_entry(path: str, key: str) -> dict | None:
    try:
        with open(path) as f:
            blob = json.load(f)
        if blob.get("version") != _CALIB_VERSION:
            return None
        return blob.get("entries", {}).get(key)
    except (OSError, ValueError):
        return None


def _save_entry(path: str, key: str, act_q: dict, report: dict) -> None:
    entry = {
        "sites": {site: np.asarray(t["qmeta"], np.float32).tolist()
                  for site, t in act_q.items()},
        "sqnr_db": report,
    }
    try:
        # dirname is '' for a bare filename (e.g. CI sets
        # REPRO_ACT_CALIB_CACHE=act_quant_calib.json) — makedirs('')
        # raises, and the best-effort except below must not eat that
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        blob = {"version": _CALIB_VERSION, "entries": {}}
        try:
            with open(path) as f:
                old = json.load(f)
            if old.get("version") == _CALIB_VERSION:
                blob["entries"].update(old.get("entries", {}))
        except (OSError, ValueError):
            pass
        blob["entries"][key] = entry
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(blob, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass


def attach_act_quant(params, act_q: dict):
    """Splice the fitted tables into the params tree (shallow copies —
    the weight leaves are shared, not duplicated)."""
    params = dict(params)
    blocks = dict(params["blocks"])
    blocks["act_q"] = act_q
    params["blocks"] = blocks
    return params


def calibrate_act_quant(api, params, cfg, bits: int,
                        prompts: np.ndarray | None = None,
                        seq_len: int = 32, n_prompts: int = 4,
                        seed: int = 0, path: str | None = None):
    """Fit (or load) per-(layer, site) act-quant params and return
    ``(params_with_act_q, report)``.

    ``prompts`` overrides the default random sample ([n_prompts,
    seq_len] token ids drawn from the model's vocab — the same
    distribution the synthetic serving benches use).  The fit is
    cached on disk; a hit skips the calibration forward entirely."""
    if api.collect_act_calibration is None:
        raise ValueError(
            f"model family {cfg.family!r} has no act-quant calibration "
            f"hook (collect_act_calibration)")
    # idempotent under re-calibration: strip previously-attached tables
    # so the cache key and the calibration forward see only the weights
    # (an Engine handed another Engine's params must hit the same entry)
    if isinstance(params.get("blocks"), dict) \
            and "act_q" in params["blocks"]:
        blocks = dict(params["blocks"])
        del blocks["act_q"]
        params = dict(params)
        params["blocks"] = blocks
    if prompts is None:
        rng = np.random.default_rng(seed)
        prompts = rng.integers(0, cfg.vocab_size,
                               (n_prompts, seq_len)).astype(np.int32)
    prompts = np.asarray(prompts, np.int32)
    path = path or cache_path()
    key = calib_key(cfg, bits, prompts, seed, params)
    entry = _load_entry(path, key)
    if entry is not None:
        act_q, report = _act_q_from_entry(entry)
        return attach_act_quant(params, act_q), report
    samples = api.collect_act_calibration(params, jnp.asarray(prompts),
                                          cfg)
    act_q, report = fit_sites(samples, bits)
    _save_entry(path, key, act_q, report)
    return attach_act_quant(params, act_q), report


__all__ = ["calibrate_act_quant", "attach_act_quant", "fit_sites",
           "cache_path", "calib_key", "lut_from_qmeta",
           "measure_sqnr", "report_means", "kv_tables_fingerprint",
           "PER_HEAD_SITES"]
