"""Public wrapper: dtype/shape handling; interpret mode on the CPU.

Any cache length works: the cache view is zero-padded up to a multiple
of the kernel block internally and the padded tail is masked out via
``lengths`` (the kernel's per-sequence validity prefetch), so serving
never has to pick ``max_len`` to please the kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.decode_gqa.decode_gqa import (
    decode_gqa_kernel,
    decode_gqa_paged_codes_kernel,
    decode_gqa_paged_kernel,
)
from repro.kernels.decode_gqa.ref import (
    decode_gqa_paged_codes_ref,
    decode_gqa_paged_ref,
    decode_gqa_ref,
)


def decode_gqa(q, k_cache, v_cache, lengths, *, block_s: int | None = None,
               out_dtype=None, interpret: bool | None = None):
    """Flash-decoding GQA with in-kernel KV dequantization.

    q: [B, n_kv, g, hd]; caches [B, S, n_kv, hd] in bf16/f8/int8-like
    dtypes; lengths [B] (or scalar, broadcast).  Any S works — the cache
    view pads to the kernel block and padding is masked via ``lengths``.
    Returns [B, n_kv, g, hd].
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    out_dtype = out_dtype or jnp.float32
    b = q.shape[0]
    s = k_cache.shape[1]
    if block_s is None:
        block_s = min(512, s)
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))
    lengths = jnp.clip(lengths, 0, s)
    if s % block_s != 0:
        pad = block_s - s % block_s
        widths = ((0, 0), (0, pad), (0, 0), (0, 0))
        k_cache = jnp.pad(k_cache, widths)
        v_cache = jnp.pad(v_cache, widths)
    return decode_gqa_kernel(q, k_cache, v_cache, lengths,
                             block_s=block_s, out_dtype=out_dtype,
                             interpret=interpret)


def decode_gqa_paged(q, k_pages, v_pages, block_tables, lengths, *,
                     out_dtype=None, interpret: bool | None = None):
    """Flash-decoding GQA over a paged KV cache.

    q: [B, n_kv, g, hd]; pages [N_blocks, bs, n_kv, hd] (any narrow
    dtype — dequant happens in-kernel); block_tables [B, max_blk] maps
    logical block j of sequence i to a physical page; lengths [B] (or
    scalar) masks ragged tails and whole unused blocks.  Page ids for
    logical blocks past a sequence's length must still be *valid*
    indices (point them at a reserved page); their contribution is
    masked.  Returns [B, n_kv, g, hd].

    Off-TPU the default execution is the pure-jnp paged oracle (gather
    through the table + dense attend, XLA-fused): the paged grid has
    B*max_blk cells, so emulating every cell in interpret mode pays
    O(blocks) Python overhead per call — unlike the O(B)-cell
    contiguous kernel, which stays on interpret.  Pass
    ``interpret=True`` to force the kernel (kernel-fidelity tests).
    """
    out_dtype = out_dtype or jnp.float32
    b = q.shape[0]
    max_tokens = block_tables.shape[1] * k_pages.shape[1]
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))
    lengths = jnp.clip(lengths, 0, max_tokens)
    if interpret is None and jax.default_backend() == "cpu":
        # Zero-length rows: match the kernel's emit-zeros guarantee.
        out = decode_gqa_paged_ref(q, k_pages, v_pages, block_tables,
                                   lengths, out_dtype=out_dtype)
        return jnp.where((lengths > 0)[:, None, None, None], out,
                         jnp.zeros((), out_dtype))
    return decode_gqa_paged_kernel(q, k_pages, v_pages, block_tables,
                                   lengths, out_dtype=out_dtype,
                                   interpret=bool(interpret))


def decode_gqa_paged_codes(q_codes, k_pages, v_pages, q_qmeta, k_qmeta,
                           v_qmeta, out_qmeta, block_tables, lengths, *,
                           interpret: bool | None = None):
    """Codes-mode flash decode over a paged KV cache: uint8 in, uint8
    out.  ``q_codes`` [B, n_kv, g, hd] uint8 (attn_q site codes, decoded
    under ``q_qmeta`` [4]); pages uint8 DNA-TEQ codes decoded in-kernel
    with per-head parameters (``k_qmeta``/``v_qmeta`` [n_kv, 4]); the
    context is re-encoded under ``out_qmeta`` (the attn_out site) before
    it leaves the kernel.  Same paging/masking contract as
    :func:`decode_gqa_paged`; off-TPU the default execution is the
    page-scan codes oracle (the same recurrence).  Returns
    [B, n_kv, g, hd] uint8.
    """
    b = q_codes.shape[0]
    max_tokens = block_tables.shape[1] * k_pages.shape[1]
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))
    lengths = jnp.clip(lengths, 0, max_tokens)
    if interpret is None and jax.default_backend() == "cpu":
        return decode_gqa_paged_codes_ref(
            q_codes, k_pages, v_pages, q_qmeta, k_qmeta, v_qmeta, out_qmeta,
            block_tables, lengths)
    return decode_gqa_paged_codes_kernel(
        q_codes, k_pages, v_pages, q_qmeta, k_qmeta, v_qmeta, out_qmeta,
        block_tables, lengths, interpret=bool(interpret))


__all__ = ["decode_gqa", "decode_gqa_paged", "decode_gqa_paged_codes",
           "decode_gqa_paged_codes_ref", "decode_gqa_paged_ref",
           "decode_gqa_ref"]
