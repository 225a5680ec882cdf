"""Pure-jnp oracle for decode-step GQA over a (possibly narrow-dtype)
KV cache."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import exponential_quant as eq
from repro.kernels._codes import decode_heads


def decode_gqa_ref(q, k_cache, v_cache, lengths, out_dtype=jnp.float32):
    """q: [B, n_kv, g, hd]; caches [B, S, n_kv, hd]; lengths [B]."""
    qf = q.astype(jnp.float32)
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    hd = q.shape[-1]
    logit = jnp.einsum("bngh,bsnh->bngs", qf, kf) / math.sqrt(hd)
    s = kf.shape[1]
    valid = jnp.arange(s)[None, :] < lengths[:, None]          # [B, S]
    logit = jnp.where(valid[:, None, None, :], logit, -1e30)
    p = jax.nn.softmax(logit, axis=-1)
    return jnp.einsum("bngs,bsnh->bngh", p, vf).astype(out_dtype)


def decode_gqa_paged_ref(q, k_pages, v_pages, block_tables, lengths,
                         out_dtype=jnp.float32):
    """Paged oracle: gather pages through the block table into a
    contiguous [B, max_blk*bs, n_kv, hd] view, then run the dense
    reference.  q: [B, n_kv, g, hd]; pages [N, bs, n_kv, hd];
    block_tables [B, max_blk]; lengths [B]."""
    b, max_blk = block_tables.shape
    bs = k_pages.shape[1]
    k = k_pages[block_tables].reshape(b, max_blk * bs, *k_pages.shape[2:])
    v = v_pages[block_tables].reshape(b, max_blk * bs, *v_pages.shape[2:])
    return decode_gqa_ref(q, k, v, lengths, out_dtype)


def decode_gqa_paged_codes_ref(q_codes, k_pages, v_pages, q_qmeta, k_qmeta,
                               v_qmeta, out_qmeta, block_tables, lengths):
    """Codes-mode oracle: unlike :func:`decode_gqa_paged_ref` (which
    gathers into a dense view and softmaxes in one shot), this runs the
    same page-scan online-softmax recurrence as the kernel, with q/K/V
    decoded by the same closed form
    (:func:`repro.kernels._codes.decode_heads`) and the context
    re-encoded under ``out_qmeta``.  Returns [B, n_kv, g, hd] uint8."""
    b, n_kv, g, hd = q_codes.shape
    bs = k_pages.shape[1]
    max_blk = block_tables.shape[1]
    qf = decode_heads(q_qmeta.astype(jnp.float32).reshape(1, 4), q_codes)
    k_qmeta = k_qmeta.astype(jnp.float32)
    v_qmeta = v_qmeta.astype(jnp.float32)
    scale = 1.0 / math.sqrt(hd)

    def page_step(carry, j_tbl):
        m, l, acc = carry
        j, tbl_j = j_tbl                                    # tbl_j [B]
        k = decode_heads(k_qmeta, k_pages[tbl_j])           # [B, bs, n, h]
        v = decode_heads(v_qmeta, v_pages[tbl_j])
        logit = jnp.einsum("bngh,bsnh->bngs", qf, k,
                           preferred_element_type=jnp.float32) * scale
        pos = j * bs + jnp.arange(bs)                       # [bs]
        valid = pos[None, :] < lengths[:, None]             # [B, bs]
        logit = jnp.where(valid[:, None, None], logit, -1e30)
        m_new = jnp.maximum(m, jnp.max(logit, axis=-1))
        p = jnp.exp(logit - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bngs,bsnh->bngh", p, v, preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((b, n_kv, g), -1e30, jnp.float32)
    l0 = jnp.zeros((b, n_kv, g), jnp.float32)
    a0 = jnp.zeros((b, n_kv, g, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        page_step, (m0, l0, a0),
        (jnp.arange(max_blk), jnp.moveaxis(block_tables, 1, 0)))
    seen = m > -5e29
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.where(seen[..., None], out, 0.0)              # [B, n, g, h]
    return eq.encode_meta(out, out_qmeta.astype(jnp.float32).reshape(4))
