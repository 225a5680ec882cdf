"""Shared model building blocks (pure functions + ParamSpec builders).

Every matmul routes through :func:`repro.core.lama_layers.dense` /
``dense_general`` so any weight can transparently be a Lama/DNA-TEQ code
tensor (the paper's technique as a first-class feature).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import lama_layers as ll
from repro.configs.base import ModelConfig
from repro.models.params import ParamSpec

Params = Any


# ------------------------------------------------------------- norms --

def constrain_act(x: jax.Array) -> jax.Array:
    """Pin activation sharding: batch over the FSDP axes, feature dims
    replicated.  Without this XLA SPMD may propagate batch-replicated
    layouts from parameter shardings (observed: 16x redundant compute on
    the data axis).  Under CONTEXT_PARALLEL the sequence dim additionally
    shards over "model".  No-op outside a mesh context."""
    try:
        from repro.launch.mesh import get_abstract_mesh

        mesh = get_abstract_mesh()
        if mesh is None:
            return x
        fsdp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        if not fsdp or x.ndim < 2:
            return x
        if x.shape[0] % math.prod(mesh.shape[a] for a in fsdp) != 0:
            return x
        rest = [None] * (x.ndim - 1)
        if (CONTEXT_PARALLEL and x.ndim >= 3 and "model" in mesh.axis_names
                and x.shape[1] % mesh.shape["model"] == 0):
            rest[0] = "model"   # sequence dim
        spec = jax.sharding.PartitionSpec(fsdp, *rest)
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:
        return x


def norm_specs(cfg: ModelConfig, kind: str | None = None) -> dict:
    kind = kind or cfg.norm
    d = cfg.d_model
    if kind == "rmsnorm":
        return {"scale": ParamSpec((d,), ("embed",), "ones")}
    if kind == "layernorm":
        return {"scale": ParamSpec((d,), ("embed",), "ones"),
                "bias": ParamSpec((d,), ("embed",), "zeros")}
    if kind == "nonparam_ln":   # OLMo: non-parametric LayerNorm
        return {}
    raise ValueError(kind)


def apply_norm(p: Params, x: jax.Array, cfg: ModelConfig,
               kind: str | None = None, eps: float = 1e-6) -> jax.Array:
    kind = kind or cfg.norm
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps)
        if kind == "layernorm":
            out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


def head_norm_specs(cfg: ModelConfig) -> dict:
    """Per-head-dim RMS norm used by qk_norm (Qwen3-style)."""
    return {"scale": ParamSpec((cfg.resolved_head_dim,), (None,), "ones")}


def apply_head_rms(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
            ).astype(x.dtype)


# -------------------------------------------------------------- rope --

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: [..., seq, heads, head_dim]; positions: [..., seq]."""
    d = x.shape[-1]
    half = d // 2
    freq = jnp.arange(half, dtype=jnp.float32) / half
    inv = theta ** (-freq)                                # [half]
    ang = positions.astype(jnp.float32)[..., None] * inv   # [..., seq, half]
    cos = jnp.cos(ang)[..., None, :]                       # [..., seq, 1, half]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------- act quantization --
#
# DNA-TEQ activation quantization (paper §II-C): per-(layer, site)
# calibrated ExpQuantParams ride the params tree as
# ``params["blocks"]["act_q"][site] = {"lut": [L,256], "qmeta": [L,4]}``
# so lax.scan slices one site table per layer.  A site marks the float
# tensor feeding a quantized matmul; encoding there turns the matmul
# dual-operand (both sides uint8 codes, dual-LUT kernel), and the
# mlp_mid site is produced *in-kernel* by the quantize epilogue.
#
# The attention-boundary sites (attn_q / attn_k / attn_v) feed the
# codes-mode KV cache and flash kernels: attn_k/attn_v are fit PER
# HEAD (``qmeta [L, n_kv, 4]``, ``lut [L, n_kv, 256]``) and are what
# u8 KV pages store; attn_q is the roped query the kernels consume as
# codes.  The attention output re-encodes in-kernel under the existing
# attn_out site, so attention is code-in/code-out like the MLP chain.

ACT_SITES = ("attn_in", "attn_out", "mlp_in", "mlp_mid",
             "attn_q", "attn_k", "attn_v")

# The sites codes-mode attention needs beyond the PR-5 matmul sites.
KV_CODE_SITES = ("attn_q", "attn_k", "attn_v", "attn_out")


def _q(x, act_q, site: str):
    """Encode ``x`` at an act-quant site (no-op without params)."""
    return ll.maybe_encode_act(x, act_q, site)


def _mid_q(act_q):
    """The mlp_mid site entry when both present and policy-enabled —
    handed to the kernel quantize epilogue as ``out_quant``."""
    if act_q is None or not ll.get_policy().act_quant:
        return None
    return act_q.get("mlp_mid")


def _kv_codes_q(act_q):
    """The act_q dict when codes-mode attention is live (all attention-
    boundary sites present and the policy has act_quant on), else None."""
    if act_q is None or not ll.get_policy().act_quant:
        return None
    if not all(s in act_q for s in KV_CODE_SITES):
        return None
    return act_q


def encode_kv_codes(k: jax.Array, v: jax.Array, act_q: dict):
    """Quantize-at-write: encode fresh K/V ([B, S, n_kv, hd] float) to
    uint8 codes with this layer's per-head attn_k/attn_v metas
    (``qmeta [n_kv, 4]``) — what a u8 codes-mode KV page stores."""
    act_q = _kv_codes_q(act_q)
    if act_q is None:
        raise ValueError(
            "uint8 codes-mode KV pages need calibrated attn_q/attn_k/"
            "attn_v/attn_out act-quant sites with the act_quant policy "
            "on (kv_codes engines calibrate them)")
    kq = act_q["attn_k"]["qmeta"]          # [n_kv, 4]
    vq = act_q["attn_v"]["qmeta"]
    return (ll.eq.encode_meta(k, kq[:, None, :]),
            ll.eq.encode_meta(v, vq[:, None, :]))


# --------------------------------------------------------- attention --

def attention_specs(cfg: ModelConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    s = {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head"), "scaled"),
        "wk": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head"), "scaled"),
        "wv": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head"), "scaled"),
        "wo": ParamSpec((h, hd, d), ("heads", "head", "embed"), "scaled",
                        fan_in_axis=0),
    }
    if cfg.qk_norm:
        s["q_norm"] = head_norm_specs(cfg)
        s["k_norm"] = head_norm_specs(cfg)
    return s


def _mask_bias(mask: jax.Array, dtype) -> jax.Array:
    return jnp.where(mask, 0.0, -1e30).astype(jnp.float32)


def causal_mask(q_len: int, kv_len: int, q_offset) -> jax.Array:
    """[q_len, kv_len] bool; q position i attends kv j <= i + offset."""
    qp = jnp.arange(q_len) + q_offset
    kp = jnp.arange(kv_len)
    return kp[None, :] <= qp[:, None]


def local_mask(q_len: int, kv_len: int, q_offset, window: int) -> jax.Array:
    qp = jnp.arange(q_len) + q_offset
    kp = jnp.arange(kv_len)
    causal = kp[None, :] <= qp[:, None]
    near = kp[None, :] > qp[:, None] - window
    return causal & near


def prefix_lm_mask(q_len: int, kv_len: int, q_offset, prefix: int) -> jax.Array:
    """PaliGemma-style: full attention within the image/text prefix,
    causal afterwards."""
    base = causal_mask(q_len, kv_len, q_offset)
    qp = jnp.arange(q_len) + q_offset
    kp = jnp.arange(kv_len)
    in_prefix = (qp[:, None] < prefix) & (kp[None, :] < prefix)
    return base | in_prefix


# Above this many score elements, attention switches to the chunked
# online-softmax (flash) path so scores never materialize.
FLASH_THRESHOLD = 32 * 1024 * 1024
FLASH_Q_CHUNK = 1024
FLASH_K_CHUNK = 1024
# Unrolled chunk loops (larger chunks, Python loops instead of lax.scan):
# used by the dry-run cost extraction, where scan bodies are counted once.
FLASH_UNROLL = False

# §Perf iteration B (EXPERIMENTS.md): context-parallel training.  When
# enabled, activations shard their *sequence* dim over the model axis
# (constrain_act), flash attention keeps q un-chunked so the SPMD
# partitioner distributes score compute along the sharded seq dim, and
# the sharding rules drop tensor-parallel weight sharding in favour of
# 2-D FSDP.  Fixes the pathological partial-sum score all-reduces of
# archs whose head counts don't divide the model axis (qwen3-14b: 40).
CONTEXT_PARALLEL = False


def set_flash_unroll(enable: bool) -> None:
    global FLASH_UNROLL
    FLASH_UNROLL = enable


def set_context_parallel(enable: bool) -> None:
    global CONTEXT_PARALLEL
    CONTEXT_PARALLEL = enable


def _block_mask(kind: str, arg, qp: jax.Array, kp: jax.Array) -> jax.Array:
    """[Qc, Kc] bool from absolute positions for one (q-chunk, k-chunk)."""
    if kind == "full":
        return jnp.ones((qp.shape[0], kp.shape[0]), bool)
    if kind == "causal":
        return kp[None, :] <= qp[:, None]
    if kind == "local":
        return (kp[None, :] <= qp[:, None]) & (kp[None, :] > qp[:, None] - arg)
    if kind == "prefix":
        causal = kp[None, :] <= qp[:, None]
        both = (qp[:, None] < arg) & (kp[None, :] < arg)
        return causal | both
    raise ValueError(kind)


def _materialize_mask(kind: str, arg, q_len: int, kv_len: int, q_offset):
    return _block_mask(kind, arg, jnp.arange(q_len) + q_offset,
                       jnp.arange(kv_len))


def _attend_dense(q, k, v, mask, dt):
    """q: [B,S,nkv,G,hd]; k/v: [B,T,nkv,hd]; mask: [S,T] or [B,S,T] bool."""
    hd = q.shape[-1]
    logits = jnp.einsum("bsngh,btnh->bnsgt", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    if mask.ndim == 2:
        bias = _mask_bias(mask, jnp.float32)[None, None, :, None, :]
    else:
        bias = _mask_bias(mask, jnp.float32)[:, None, :, None, :]
    probs = jax.nn.softmax(logits + bias, axis=-1).astype(dt)
    return jnp.einsum("bnsgt,btnh->bsngh", probs, v)


def _attend_flash(q, k, v, kind: str, arg, q_offset, dt,
                  q_chunk=FLASH_Q_CHUNK, k_chunk=FLASH_K_CHUNK):
    """Chunked online-softmax attention (FlashAttention recurrence in
    pure JAX): scan over query chunks, inner scan over KV chunks with
    running (max, denom, acc).  Never materializes [S, T] scores —
    the pure-jnp mirror of kernels/flash_gqa."""
    b, s, n, g, hd = q.shape
    t = k.shape[1]
    if FLASH_UNROLL:   # few large chunks, Python loops (countable HLO)
        q_chunk = max(s // 4, min(s, 1024))
        k_chunk = max(t // 4, min(t, 1024))
    if CONTEXT_PARALLEL:
        # keep q un-chunked: the SPMD partitioner distributes the scores
        # along q's (model-)sharded sequence dim; only KV is streamed.
        q_chunk = s
    q_chunk = min(q_chunk, s)
    k_chunk = min(k_chunk, t)
    nq = -(-s // q_chunk)
    nk = -(-t // k_chunk)
    pad_q = nq * q_chunk - s
    pad_k = nk * k_chunk - t
    # §Perf B2: operands stay bf16 (f32 softmax stats / MXU accumulation)
    # so cross-shard K/V movement and their grad reductions are 2 B/el.
    op_dt = dt if dt == jnp.bfloat16 else jnp.float32
    qf = jnp.pad(q.astype(op_dt), ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
    kf = jnp.pad(k.astype(op_dt), ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vf = jnp.pad(v.astype(op_dt), ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    kp_valid = jnp.arange(nk * k_chunk) < t

    qs = jnp.moveaxis(qf.reshape(b, nq, q_chunk, n, g, hd), 1, 0)
    ks = jnp.moveaxis(kf.reshape(b, nk, k_chunk, n, hd), 1, 0)
    vs = jnp.moveaxis(vf.reshape(b, nk, k_chunk, n, hd), 1, 0)
    kvalid = kp_valid.reshape(nk, k_chunk)
    scale = 1.0 / math.sqrt(hd)

    def q_step(_, qi_qc):
        qi, qc = qi_qc
        qpos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, kj_kc_vc_valid):
            m, l, acc = carry
            kj, kc, vc, valid = kj_kc_vc_valid
            kpos = kj * k_chunk + jnp.arange(k_chunk)
            logit = jnp.einsum("bsngh,btnh->bnsgt", qc, kc,
                               preferred_element_type=jnp.float32) * scale
            mask = _block_mask(kind, arg, qpos, kpos) & valid[None, :]
            logit = jnp.where(mask[None, None, :, None, :], logit, -1e30)
            m_new = jnp.maximum(m, jnp.max(logit, axis=-1))
            p = jnp.exp(logit - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bnsgt,btnh->bnsgh", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32)
            return (m_new, l, acc), None

        m0 = jnp.full((b, n, q_chunk, g), -1e30, jnp.float32)
        l0 = jnp.zeros((b, n, q_chunk, g), jnp.float32)
        a0 = jnp.zeros((b, n, q_chunk, g, hd), jnp.float32)
        if FLASH_UNROLL:
            carry = (m0, l0, a0)
            for kj in range(nk):
                carry, _ = kv_step(
                    carry, (jnp.asarray(kj), ks[kj], vs[kj], kvalid[kj]))
            m, l, acc = carry
        else:
            (m, l, acc), _ = jax.lax.scan(
                kv_step, (m0, l0, a0),
                (jnp.arange(nk), ks, vs, kvalid))
        out = acc / jnp.maximum(l, 1e-30)[..., None]        # [b,n,qc,g,hd]
        return None, jnp.moveaxis(out, 2, 1)                # [b,qc,n,g,hd]

    if FLASH_UNROLL:
        chunks = [q_step(None, (jnp.asarray(qi), qs[qi]))[1]
                  for qi in range(nq)]
        outs = jnp.stack(chunks)
    else:
        _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), qs))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, nq * q_chunk, n, g, hd)
    return out[:, :s].astype(dt)


def mha(
    p: Params,
    x: jax.Array,                      # [B, S, D]
    cfg: ModelConfig,
    positions: jax.Array,              # [B, S] absolute positions
    mask,                              # bool array OR (kind, arg) descriptor
    kv: tuple[jax.Array, jax.Array] | None = None,   # external K,V ([B,T,nkv,hd])
    use_rope: bool = True,
    q_offset=0,
    act_q: dict | None = None,
    return_ctx: bool = False,
):
    """Grouped-query attention; ``kv`` overrides self-derived keys/values
    (decode-with-cache and cross-attention paths).  ``mask`` is either a
    small bool array (decode) or a (kind, arg) descriptor — descriptors
    route large shapes through the flash path.  ``act_q`` encodes the
    attn_in/attn_out activations as DNA-TEQ codes so the q/k/v/o
    projections run dual-LUT; ``return_ctx`` additionally returns the
    pre-``wo`` context (the attn_out calibration sample)."""
    dt = x.dtype
    xq = _q(x, act_q, "attn_in")      # encoded ONCE, feeds q, k and v
    q = ll.dense_general(xq, p["wq"], "bsd,dnh->bsnh", dtype=dt)
    if kv is None:
        k = ll.dense_general(xq, p["wk"], "bsd,dnh->bsnh", dtype=dt)
        v = ll.dense_general(xq, p["wv"], "bsd,dnh->bsnh", dtype=dt)
    else:
        k, v = kv
    if cfg.qk_norm:
        q = apply_head_rms(p["q_norm"], q)
        if kv is None:
            k = apply_head_rms(p["k_norm"], k)
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        if kv is None:
            k = rope(k, positions, cfg.rope_theta)

    groups = cfg.num_heads // cfg.num_kv_heads
    b, s, h, hd = q.shape
    t = k.shape[1]
    qg = q.reshape(b, s, cfg.num_kv_heads, groups, hd)

    if isinstance(mask, tuple):
        kind, arg = (mask[0], mask[1] if len(mask) > 1 else None)
        score_elems = b * h * s * t
        if score_elems > FLASH_THRESHOLD:
            out = _attend_flash(qg, k, v, kind, arg, q_offset, dt)
        else:
            out = _attend_dense(qg, k, v,
                                _materialize_mask(kind, arg, s, t, q_offset), dt)
    else:
        out = _attend_dense(qg, k, v, mask, dt)
    out = out.reshape(b, s, h, hd)
    proj = ll.dense_general(_q(out, act_q, "attn_out"), p["wo"],
                            "bsnh,nhd->bsd", dtype=dt)
    if return_ctx:
        return proj, out
    return proj


def mha_decode(
    p: Params,
    x: jax.Array,                      # [B, 1, D] — one new token
    cfg: ModelConfig,
    positions: jax.Array,              # [B, 1] absolute positions
    k_cache: jax.Array,                # [B, T, n_kv, hd] (bf16/f8/...)
    v_cache: jax.Array,
    lengths: jax.Array,                # [B] valid cache entries
    use_rope: bool = True,
    act_q: dict | None = None,
) -> jax.Array:
    """Decode-step GQA through the flash-decoding kernel: the cache is
    streamed block-wise with in-kernel dequantization (narrow KV bytes
    cross HBM), online-softmax carries in VMEM.  Numerically equals
    :func:`mha` with a causal-by-length mask."""
    from repro.kernels.decode_gqa import decode_gqa

    dt = x.dtype
    q = ll.dense_general(_q(x, act_q, "attn_in"), p["wq"],
                         "bsd,dnh->bsnh", dtype=dt)
    if cfg.qk_norm:
        q = apply_head_rms(p["q_norm"], q)
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
    b, s, h, hd = q.shape
    groups = cfg.num_heads // cfg.num_kv_heads
    qg = q[:, 0].reshape(b, cfg.num_kv_heads, groups, hd)
    out = decode_gqa(qg, k_cache, v_cache, lengths)
    out = out.reshape(b, 1, h, hd).astype(dt)
    return ll.dense_general(_q(out, act_q, "attn_out"), p["wo"],
                            "bsnh,nhd->bsd", dtype=dt)


def mha_decode_paged(
    p: Params,
    x: jax.Array,                      # [B, 1, D] — one new token
    cfg: ModelConfig,
    positions: jax.Array,              # [B, 1] absolute positions
    k_pages: jax.Array,                # [N_blocks, bs, n_kv, hd]
    v_pages: jax.Array,
    block_tables: jax.Array,           # [B, max_blk] int32
    lengths: jax.Array,                # [B] valid cache entries
    use_rope: bool = True,
    act_q: dict | None = None,
) -> jax.Array:
    """Decode-step GQA over a *paged* cache: the block table rides as a
    scalar-prefetch operand so each page's HBM→VMEM DMA is issued
    straight from the table — no [B, S] contiguous gather ever
    materializes.  ``flash_decode=False`` in the policy swaps in the
    pure-jnp paged oracle (gather + dense attend) for A/B checks.

    When the pages hold uint8 DNA-TEQ codes (codes-mode KV cache), the
    roped query is encoded at the attn_q site, the codes kernel decodes
    q/K/V with per-head parameters in VMEM and re-encodes the context under
    the attn_out meta in-kernel, and the output projection consumes the
    resulting ``QTensor`` directly — code-in/code-out through the whole
    attend, no f32 activation at the attention boundary."""
    from repro.kernels.decode_gqa import (decode_gqa_paged,
                                          decode_gqa_paged_codes,
                                          decode_gqa_paged_ref)

    dt = x.dtype
    q = roped_q(p, x, cfg, positions, use_rope=use_rope, act_q=act_q)
    b, s, h, hd = q.shape
    groups = cfg.num_heads // cfg.num_kv_heads
    qg = q[:, 0].reshape(b, cfg.num_kv_heads, groups, hd)
    if k_pages.dtype == jnp.uint8:
        aq = _kv_codes_q(act_q)
        if aq is None:
            raise ValueError(
                "uint8 codes-mode KV pages need calibrated attn_q/"
                "attn_k/attn_v/attn_out act-quant sites (kv_codes "
                "engines calibrate them; found none on this attend)")
        # codes mode ignores flash_decode: off-TPU the codes op runs
        # the page-scan oracle, the same recurrence.
        q_codes = ll.eq.encode_meta(qg, aq["attn_q"]["qmeta"])
        out = decode_gqa_paged_codes(
            q_codes, k_pages, v_pages, aq["attn_q"]["qmeta"],
            aq["attn_k"]["qmeta"], aq["attn_v"]["qmeta"],
            aq["attn_out"]["qmeta"], block_tables, lengths)
        ctx = ll.eq.QTensor(out.reshape(b, 1, h, hd),
                            aq["attn_out"]["lut"], aq["attn_out"]["qmeta"])
        return ll.dense_general(ctx, p["wo"], "bsnh,nhd->bsd", dtype=dt)
    if ll.get_policy().flash_decode:
        out = decode_gqa_paged(qg, k_pages, v_pages, block_tables, lengths)
    else:
        out = decode_gqa_paged_ref(qg, k_pages, v_pages, block_tables,
                                   lengths)
        # the dense oracle softmaxes all-masked rows to a uniform
        # average; match the kernel's emit-zeros guarantee for
        # zero-length (inactive) slots
        out = jnp.where((lengths > 0)[:, None, None, None], out,
                        jnp.zeros((), out.dtype))
    out = out.reshape(b, 1, h, hd).astype(dt)
    return ll.dense_general(_q(out, act_q, "attn_out"), p["wo"],
                            "bsnh,nhd->bsd", dtype=dt)


def mha_prefill_paged(
    p: Params,
    x: jax.Array,                      # [B, S, D] — one prompt chunk
    cfg: ModelConfig,
    positions: jax.Array,              # [B, S] absolute positions
    k_pages: jax.Array,                # [N_blocks, bs, n_kv, hd]
    v_pages: jax.Array,
    block_tables: jax.Array,           # [B, max_blk] int32
    q_start: jax.Array,                # [B] absolute position of row 0
    kv_lens: jax.Array,                # [B] cache positions written
    use_rope: bool = True,
    act_q: dict | None = None,
) -> jax.Array:
    """Chunked-prefill GQA straight from the paged KV cache: the chunk's
    queries (roped at their absolute positions) attend every written
    cache position ``<= `` their own through the ``flash_prefill_paged``
    kernel — block-table scalar prefetch, online softmax over pages,
    in-kernel dequant of narrow KV dtypes.  The caller scatters the
    chunk's own K/V into the pages *before* this runs, so within-chunk
    causality falls out of the same positional mask that covers the
    cached prefix; no ``[B, S, T]`` mask or score matrix exists at any
    point.  ``flash_decode=False`` in the policy swaps in the pure-jnp
    paged oracle, as in :func:`mha_decode_paged`.

    With uint8 codes-mode pages the chunk runs code-in/code-out exactly
    like :func:`mha_decode_paged`: attn_q-encoded queries, per-head
    closed-form decode of K/V in-kernel, attn_out re-encode epilogue, and
    a ``QTensor`` context fed straight to the output projection."""
    from repro.kernels.flash_prefill import (flash_prefill_paged,
                                             flash_prefill_paged_codes,
                                             flash_prefill_paged_ref)

    dt = x.dtype
    q = roped_q(p, x, cfg, positions, use_rope=use_rope, act_q=act_q)
    b, s, h, hd = q.shape
    groups = cfg.num_heads // cfg.num_kv_heads
    qg = q.reshape(b, s, cfg.num_kv_heads, groups, hd)
    if k_pages.dtype == jnp.uint8:
        aq = _kv_codes_q(act_q)
        if aq is None:
            raise ValueError(
                "uint8 codes-mode KV pages need calibrated attn_q/"
                "attn_k/attn_v/attn_out act-quant sites (kv_codes "
                "engines calibrate them; found none on this attend)")
        q_codes = ll.eq.encode_meta(qg, aq["attn_q"]["qmeta"])
        out = flash_prefill_paged_codes(
            q_codes, k_pages, v_pages, aq["attn_q"]["qmeta"],
            aq["attn_k"]["qmeta"], aq["attn_v"]["qmeta"],
            aq["attn_out"]["qmeta"], block_tables, q_start, kv_lens)
        ctx = ll.eq.QTensor(out.reshape(b, s, h, hd),
                            aq["attn_out"]["lut"], aq["attn_out"]["qmeta"])
        return ll.dense_general(ctx, p["wo"], "bsnh,nhd->bsd", dtype=dt)
    attend = (flash_prefill_paged if ll.get_policy().flash_decode
              else flash_prefill_paged_ref)
    out = attend(qg, k_pages, v_pages, block_tables, q_start, kv_lens)
    out = out.reshape(b, s, h, hd).astype(dt)
    return ll.dense_general(_q(out, act_q, "attn_out"), p["wo"],
                            "bsnh,nhd->bsd", dtype=dt)


def roped_q(p: Params, x: jax.Array, cfg: ModelConfig,
            positions: jax.Array, use_rope: bool = True,
            act_q: dict | None = None) -> jax.Array:
    """Project + (qk_norm) + rope the query — exactly what the paged
    attends compute before attending, factored out so the attn_q
    calibration capture and the attends themselves share one code path.
    Returns [B, S, H, hd] float."""
    dt = x.dtype
    q = ll.dense_general(_q(x, act_q, "attn_in"), p["wq"],
                         "bsd,dnh->bsnh", dtype=dt)
    if cfg.qk_norm:
        q = apply_head_rms(p["q_norm"], q)
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
    return q


def self_kv(p: Params, x: jax.Array, cfg: ModelConfig,
            positions: jax.Array, use_rope: bool = True,
            act_q: dict | None = None):
    """Project K,V for cache writes (decode path)."""
    dt = x.dtype
    xq = _q(x, act_q, "attn_in")
    k = ll.dense_general(xq, p["wk"], "bsd,dnh->bsnh", dtype=dt)
    v = ll.dense_general(xq, p["wv"], "bsd,dnh->bsnh", dtype=dt)
    if cfg.qk_norm:
        k = apply_head_rms(p["k_norm"], k)
    if use_rope:
        k = rope(k, positions, cfg.rope_theta)
    return k, v


# --------------------------------------------------------------- mlp --

def mlp_specs(cfg: ModelConfig, d_ff: int | None = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    s = {"w_down": ParamSpec((f, d), ("mlp", "embed"), "scaled", fan_in_axis=0)}
    if cfg.gated_mlp:
        s["w_gate"] = ParamSpec((d, f), ("embed", "mlp"), "scaled")
        s["w_up"] = ParamSpec((d, f), ("embed", "mlp"), "scaled")
    else:
        s["w_up"] = ParamSpec((d, f), ("embed", "mlp"), "scaled")
    return s


def apply_mlp(p: Params, x: jax.Array, cfg: ModelConfig,
              act_q: dict | None = None, return_mid: bool = False):
    """MLP block.  With ``act_q``, the chain is code-in/code-out: the
    mlp_in site encodes x once, the front half runs dual-LUT and its
    quantize epilogue re-encodes the intermediate *in-kernel* (the
    mlp_mid codes are the only HBM form of it), and the down projection
    consumes those codes through the dual kernel.  ``return_mid``
    additionally returns the float intermediate (mlp_mid calibration
    sample; calibration runs without act_q, so mid is a float there)."""
    dt = x.dtype
    xq = _q(x, act_q, "mlp_in")
    if cfg.gated_mlp:
        # Quantized weights: ONE fused dual-matmul kernel computes
        # act(x@w_gate)*(x@w_up) (gate intermediate never reaches HBM),
        # then the down projection is a second fused call — the MLP
        # chain is 2 kernel flushes instead of 3 HBM round-trips.
        h = ll.gated_mlp(xq, p["w_gate"], p["w_up"], cfg.activation,
                         dtype=dt, out_quant=_mid_q(act_q))
    else:
        h = ll.dense(xq, p["w_up"], epilogue=cfg.activation, dtype=dt,
                     out_quant=_mid_q(act_q))
    out = ll.dense(h, p["w_down"], dtype=dt)
    if return_mid:
        return out, h
    return out


# -------------------------------------------------------- embeddings --

def embed_specs(cfg: ModelConfig) -> dict:
    # modest init scale keeps tied-unembedding logits O(1) at init
    s = {"tokens": ParamSpec((cfg.vocab_size, cfg.d_model),
                             ("vocab", "embed"), "embed", scale=0.05)}
    return s


def embed_tokens(p: Params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    # qtensor tables gather code rows then LUT-decode just those rows —
    # the full-precision table never materializes.
    return ll.embed_lookup(p["tokens"], tokens, jnp.dtype(cfg.compute_dtype))


def unembed_specs(cfg: ModelConfig) -> dict:
    if cfg.tie_embeddings:
        return {}
    return {"out": ParamSpec((cfg.d_model, cfg.vocab_size),
                             ("embed", "vocab"), "scaled")}


def logits_fn(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        w = params["embed"]["tokens"]
        if ll.eq.is_qtensor(w):
            # dense_general canonicalizes 'bsd,vd->bsv' (codes transposed
            # as bytes) so a quantized tied unembedding hits the kernel.
            out = ll.dense_general(x, w, "bsd,vd->bsv", dtype=jnp.float32)
        else:
            table = ll.materialize(w, jnp.dtype(cfg.compute_dtype))
            out = jnp.einsum("bsd,vd->bsv", x, table,
                             preferred_element_type=jnp.float32)
    else:
        out = ll.dense(x, params["unembed"]["out"], dtype=x.dtype)
        out = out.astype(jnp.float32)
    if cfg.logit_softcap:
        out = cfg.logit_softcap * jnp.tanh(out / cfg.logit_softcap)
    return out.astype(jnp.float32)
