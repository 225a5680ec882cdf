"""Public wrapper: dtype/shape handling + oracle fallback.

Off-TPU the default execution is the pure-jnp paged oracle — the paged
grid has B*max_blk cells, so emulating every cell in interpret mode
pays O(blocks) Python overhead per call (the same tradeoff as
``decode_gqa_paged``).  The oracle runs the same online-softmax page
recurrence, so kernel-fidelity tests force the kernel with
``interpret=True`` and compare within f32 rounding.  On a TPU the
kernel always runs, compiled.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_prefill.flash_prefill import (
    flash_prefill_paged_codes_kernel,
    flash_prefill_paged_kernel,
)
from repro.kernels.flash_prefill.ref import (
    flash_prefill_paged_codes_ref,
    flash_prefill_paged_ref,
)


def flash_prefill_paged(q, k_pages, v_pages, block_tables, q_start,
                        kv_lens, *, out_dtype=None,
                        interpret: bool | None = None):
    """Chunked flash-attention prefill over a paged KV cache.

    q: [B, S, n_kv, g, hd] — a chunk of roped queries whose row 0 sits
    at absolute position ``q_start[b]``; pages [N_blocks, bs, n_kv, hd]
    or, for a single KV head (latent attention), [N_blocks, bs, hd]
    (any narrow dtype — dequant happens in-kernel); block_tables
    [B, max_blk]; ``kv_lens`` [B] caps validity at the cache positions
    actually written (trash-page columns mask out).  Rows with zero
    valid positions return zeros.  Returns [B, S, n_kv, g, hd].
    """
    out_dtype = out_dtype or jnp.float32
    b = q.shape[0]
    max_tokens = block_tables.shape[1] * k_pages.shape[1]
    q_start = jnp.broadcast_to(jnp.asarray(q_start, jnp.int32), (b,))
    kv_lens = jnp.clip(
        jnp.broadcast_to(jnp.asarray(kv_lens, jnp.int32), (b,)),
        0, max_tokens)
    if interpret is None and jax.default_backend() == "cpu":
        return flash_prefill_paged_ref(q, k_pages, v_pages, block_tables,
                                       q_start, kv_lens,
                                       out_dtype=out_dtype)
    return flash_prefill_paged_kernel(q, k_pages, v_pages, block_tables,
                                      q_start, kv_lens,
                                      out_dtype=out_dtype,
                                      interpret=bool(interpret))


def flash_prefill_paged_codes(q_codes, k_pages, v_pages, q_qmeta, k_qmeta,
                              v_qmeta, out_qmeta, block_tables, q_start,
                              kv_lens, *, interpret: bool | None = None):
    """Codes-mode chunked flash prefill: uint8 in, uint8 out.

    ``q_codes`` [B, S, n_kv, g, hd] uint8 (attn_q site codes, decoded
    under ``q_qmeta`` [4]); pages uint8 DNA-TEQ codes decoded in-kernel
    with per-head parameters (``k_qmeta``/``v_qmeta`` [n_kv, 4]); the
    attention context is re-encoded under ``out_qmeta`` (the attn_out
    site) before it leaves the kernel.  Same paging/masking contract as
    :func:`flash_prefill_paged`.  Returns [B, S, n_kv, g, hd] uint8.
    """
    b = q_codes.shape[0]
    max_tokens = block_tables.shape[1] * k_pages.shape[1]
    q_start = jnp.broadcast_to(jnp.asarray(q_start, jnp.int32), (b,))
    kv_lens = jnp.clip(
        jnp.broadcast_to(jnp.asarray(kv_lens, jnp.int32), (b,)),
        0, max_tokens)
    if interpret is None and jax.default_backend() == "cpu":
        return flash_prefill_paged_codes_ref(
            q_codes, k_pages, v_pages, q_qmeta, k_qmeta, v_qmeta, out_qmeta,
            block_tables, q_start, kv_lens)
    return flash_prefill_paged_codes_kernel(
        q_codes, k_pages, v_pages, q_qmeta, k_qmeta, v_qmeta, out_qmeta,
        block_tables, q_start, kv_lens, interpret=bool(interpret))


__all__ = ["flash_prefill_paged", "flash_prefill_paged_codes",
           "flash_prefill_paged_codes_ref", "flash_prefill_paged_ref"]
