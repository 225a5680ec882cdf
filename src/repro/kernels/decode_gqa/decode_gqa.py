"""Decode-step GQA attention with in-kernel quantized-KV dequantization
(flash-decoding over the cache; the serving hot-spot of §Perf Cell A).

One new query token per sequence attends over a cache that may be
stored in float8_e4m3fn (or any narrow dtype), or as uint8 DNA-TEQ
codes: the decode to f32 happens *inside* the kernel, after the
HBM→VMEM DMA — so the bytes that actually cross HBM are the narrow
ones.  This is the kernel-level guarantee that EXPERIMENTS.md §Perf A2
found XLA will not give you for free (it hoists dequantization above
the data movement).

A decode step is a prefill chunk of one token whose query sits at
position ``lengths[b] - 1``: it attends exactly the cache positions
``< lengths[b]``.  So every variant here runs the paged flash-prefill
kernel with S = 1 — one kernel body, one set of Mosaic layouts — and a
contiguous ``[B, S, n_kv, hd]`` cache is a paged one whose block table
is the identity.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_prefill.flash_prefill import (
    flash_prefill_paged_codes_kernel,
    flash_prefill_paged_kernel,
)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_gqa_paged_codes_kernel(
    q_codes: jax.Array,       # [B, n_kv, g, hd] uint8 — roped q codes
    k_pages: jax.Array,       # [N_blocks, bs, n_kv, hd] uint8 codes
    v_pages: jax.Array,       # [N_blocks, bs, n_kv, hd] uint8 codes
    q_qmeta: jax.Array,       # [4] f32 — attn_q parameters
    k_qmeta: jax.Array,       # [n_kv, 4] f32 — per-head K parameters
    v_qmeta: jax.Array,       # [n_kv, 4] f32 — per-head V parameters
    out_qmeta: jax.Array,     # [4] f32 — attn_out (alpha, beta, base, bits)
    block_tables: jax.Array,  # [B, max_blk] int32
    lengths: jax.Array,       # [B] int32
    *,
    interpret: bool = False,
) -> jax.Array:
    """Codes-mode flash decode: same paging/masking contract as
    :func:`decode_gqa_paged_kernel`, but every operand is uint8 DNA-TEQ
    codes, and the output is the uint8 re-encode of the context under
    ``out_qmeta``.  Returns [B, n_kv, g, hd] uint8."""
    return flash_prefill_paged_codes_kernel(
        q_codes[:, None], k_pages, v_pages, q_qmeta, k_qmeta, v_qmeta,
        out_qmeta, block_tables, lengths - 1, lengths,
        interpret=interpret)[:, 0]


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def decode_gqa_paged_kernel(
    q: jax.Array,             # [B, n_kv, g, hd]
    k_pages: jax.Array,       # [N_blocks, bs, n_kv, hd] (bf16 / f8 / ...)
    v_pages: jax.Array,       # [N_blocks, bs, n_kv, hd]
    block_tables: jax.Array,  # [B, max_blk] int32 — page id per logical block
    lengths: jax.Array,       # [B] int32 — valid tokens per sequence
    *,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Flash decode over a *paged* KV cache.

    Logical block ``j`` of sequence ``i`` lives in physical page
    ``block_tables[i, j]``; the block table rides as a scalar-prefetch
    operand so the page id is known before the HBM→VMEM DMA is issued —
    the gather happens in the BlockSpec index_map, never as a
    materialized [B, S] cache.  Rows with ``lengths[b] == 0`` return
    zeros.  Returns [B, n_kv, g, hd].
    """
    return flash_prefill_paged_kernel(
        q[:, None], k_pages, v_pages, block_tables, lengths - 1, lengths,
        out_dtype=out_dtype, interpret=interpret)[:, 0]


@functools.partial(
    jax.jit, static_argnames=("block_s", "out_dtype", "interpret"))
def decode_gqa_kernel(
    q: jax.Array,        # [B, n_kv, g, hd]
    k_cache: jax.Array,  # [B, S, n_kv, hd]  (bf16 / f8e4m3fn / ...)
    v_cache: jax.Array,  # [B, S, n_kv, hd]
    lengths: jax.Array,  # [B] int32 — valid cache entries per sequence
    *,
    block_s: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Flash decode over a contiguous cache: sequence ``b``'s blocks of
    ``block_s`` positions are pages ``b*nb ... b*nb + nb - 1``."""
    b, s, n_kv, hd = k_cache.shape
    assert s % block_s == 0, (s, block_s)
    nb = s // block_s
    tables = jnp.arange(b * nb, dtype=jnp.int32).reshape(b, nb)
    return decode_gqa_paged_kernel(
        q, k_cache.reshape(b * nb, block_s, n_kv, hd),
        v_cache.reshape(b * nb, block_s, n_kv, hd), tables, lengths,
        out_dtype=out_dtype, interpret=interpret)
