"""Pure-jnp oracle for chunked flash prefill over a paged KV cache.

The same recurrence as the kernel — a ``lax.scan`` over block-table
columns with online-softmax (m, l, acc) carries — so the two accumulate
in the same page order, and neither ever materializes an ``[S, T]``
score matrix: the largest score block is ``[S, block_size]``, one
page's worth.  The oracle contracts per head with einsums where the
kernel runs one folded 2-D dot per page, so the two agree to f32
rounding, not bit for bit.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import exponential_quant as eq
from repro.kernels._codes import decode_heads


def flash_prefill_paged_ref(q, k_pages, v_pages, block_tables, q_start,
                            kv_lens, out_dtype=jnp.float32):
    """q: [B, S, n_kv, g, hd]; pages [N, bs, n_kv, hd];
    block_tables [B, max_blk]; q_start/kv_lens [B].
    Returns [B, S, n_kv, g, hd]."""
    b, s, n_kv, g, hd = q.shape
    bs = k_pages.shape[1]
    max_blk = block_tables.shape[1]
    qf = q.astype(jnp.float32)
    scale = 1.0 / math.sqrt(hd)
    qpos = (q_start[:, None] + jnp.arange(s)[None, :])      # [B, S]

    def page_step(carry, j_tbl):
        m, l, acc = carry
        j, tbl_j = j_tbl                                    # tbl_j [B]
        k = k_pages[tbl_j].astype(jnp.float32)              # [B, bs, n, h]
        v = v_pages[tbl_j].astype(jnp.float32)
        logit = jnp.einsum("bsngh,btnh->bngst", qf, k,
                           preferred_element_type=jnp.float32) * scale
        kvpos = j * bs + jnp.arange(bs)                     # [bs]
        valid = ((kvpos[None, None, :] <= qpos[:, :, None])
                 & (kvpos[None, None, :] < kv_lens[:, None, None]))
        logit = jnp.where(valid[:, None, None], logit, -1e30)
        m_new = jnp.maximum(m, jnp.max(logit, axis=-1))
        p = jnp.exp(logit - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bngst,btnh->bngsh", p, v, preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((b, n_kv, g, s), -1e30, jnp.float32)
    l0 = jnp.zeros((b, n_kv, g, s), jnp.float32)
    a0 = jnp.zeros((b, n_kv, g, s, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        page_step, (m0, l0, a0),
        (jnp.arange(max_blk), jnp.moveaxis(block_tables, 1, 0)))
    seen = m > -5e29
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.where(seen[..., None], out, 0.0)              # [B, n, g, S, h]
    return jnp.transpose(out, (0, 3, 1, 2, 4)).astype(out_dtype)


def flash_prefill_paged_codes_ref(q_codes, k_pages, v_pages, q_qmeta,
                                  k_qmeta, v_qmeta, out_qmeta, block_tables,
                                  q_start, kv_lens):
    """Codes-mode oracle: identical page recurrence, but q/K/V are uint8
    DNA-TEQ codes decoded by the same closed form as the kernel
    (:func:`repro.kernels._codes.decode_heads`), and the output is the
    uint8 re-encode of the context under ``out_qmeta``.
    Returns [B, S, n_kv, g, hd] uint8."""
    b, s, n_kv, g, hd = q_codes.shape
    bs = k_pages.shape[1]
    max_blk = block_tables.shape[1]
    qf = decode_heads(q_qmeta.astype(jnp.float32).reshape(1, 4), q_codes)
    k_qmeta = k_qmeta.astype(jnp.float32)
    v_qmeta = v_qmeta.astype(jnp.float32)
    scale = 1.0 / math.sqrt(hd)
    qpos = (q_start[:, None] + jnp.arange(s)[None, :])      # [B, S]

    def page_step(carry, j_tbl):
        m, l, acc = carry
        j, tbl_j = j_tbl                                    # tbl_j [B]
        k = decode_heads(k_qmeta, k_pages[tbl_j])           # [B, bs, n, h]
        v = decode_heads(v_qmeta, v_pages[tbl_j])
        logit = jnp.einsum("bsngh,btnh->bngst", qf, k,
                           preferred_element_type=jnp.float32) * scale
        kvpos = j * bs + jnp.arange(bs)                     # [bs]
        valid = ((kvpos[None, None, :] <= qpos[:, :, None])
                 & (kvpos[None, None, :] < kv_lens[:, None, None]))
        logit = jnp.where(valid[:, None, None], logit, -1e30)
        m_new = jnp.maximum(m, jnp.max(logit, axis=-1))
        p = jnp.exp(logit - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bngst,btnh->bngsh", p, v, preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((b, n_kv, g, s), -1e30, jnp.float32)
    l0 = jnp.zeros((b, n_kv, g, s), jnp.float32)
    a0 = jnp.zeros((b, n_kv, g, s, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        page_step, (m0, l0, a0),
        (jnp.arange(max_blk), jnp.moveaxis(block_tables, 1, 0)))
    seen = m > -5e29
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.where(seen[..., None], out, 0.0)              # [B, n, g, S, h]
    out = jnp.transpose(out, (0, 3, 1, 2, 4))               # [B, S, n, g, h]
    return eq.encode_meta(out, out_qmeta.astype(jnp.float32).reshape(4))
