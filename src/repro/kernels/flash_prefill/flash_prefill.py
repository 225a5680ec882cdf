"""Chunked flash-attention prefill over a *paged* KV cache.

A chunk of S query tokens per sequence (a slice of the prompt starting
at a per-row absolute position ``q_start[b]``) attends over the KV pages
named by its block table.  The table rides as a scalar-prefetch operand
so each page's HBM→VMEM DMA is issued straight from the BlockSpec
index_map — no contiguous ``[B, T]`` cache, no ``[B, S, T]`` mask, and
no ``[S, T]`` score matrix ever materializes.  Causality is positional:
query token ``i`` of sequence ``b`` sits at absolute position
``q_start[b] + i`` and attends exactly the cache positions
``<= q_start[b] + i`` (and ``< kv_lens[b]``, which caps validity at the
tokens actually written — pages past a sequence's fill point at the
trash page and are masked out).  One compiled kernel therefore serves
every mix of cold prefills, prefix-cache tail prefills, mid-prompt
chunks and — with S = 1 — decode steps (``decode_gqa`` calls it so).

Layout: every operand of the inner step is 2-D, the form Mosaic lowers.
The query tile is ``[tq·H, hd]`` rows (token-major, then query head),
one page is ``[bs·n_kv, hd]`` columns (slot-major, then KV head), and
one MXU dot scores every row against every column of the page; a
head-match mask (query head ``h`` reads KV head ``h // g``) joins the
positional mask.  With ``bs·n_kv = 128`` (pages of 16, 8 KV heads) the
scores fill exactly one lane width, so the cross-head columns cost no
MXU passes that a per-head dot would not.  The online-softmax carries
are ``[tq·H, 1]`` and ``[tq·H, hd]``; the chunk is tiled along a query
grid axis so they stay within VMEM at any chunk length.

KV pages may be stored narrow (float8_e4m3fn, bf16): the cast to f32
happens inside the kernel, after the DMA, so the bytes that cross HBM
are the narrow ones.  The codes variant takes uint8 DNA-TEQ pages and
queries, decodes them in-kernel with per-head closed-form parameters,
and re-encodes the context under the attn_out parameters.

Grid: (B, S/tq, max_blk) — batch and query tiles parallel, KV pages
"arbitrary".  A fully-masked row (zero valid positions: an inactive
slot in a full-width serving dispatch) never raises its running max off
the -1e30 init and emits zeros.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import exponential_quant as eq
from repro.kernels._codes import decode_heads

# Query rows (tokens x query heads) per grid cell: bounds the VMEM of
# the q tile, the carries and the [rows, bs*n_kv] score block.
MAX_ROWS = 1024


def q_tile(s: int, heads: int) -> int:
    """Tokens per query tile: the largest divisor of ``s`` whose row
    count ``tile * heads`` fits :data:`MAX_ROWS` and is a whole number
    of sublanes (or the whole chunk)."""
    for tq in range(min(s, max(1, MAX_ROWS // heads)), 0, -1):
        if s % tq == 0 and (tq == s or (tq * heads) % 8 == 0):
            return tq
    return s


def _page_step(q, k, v, qpos0, kv_len, j, m_ref, l_ref, acc_ref, *,
               block_s: int, n_kv: int, heads: int):
    """One online-softmax update of the row carries with one page.

    ``q`` [R, hd] rows (token ``r // heads``, query head ``r % heads``);
    ``k``/``v`` [bs*n_kv, hd] columns (slot ``c // n_kv``, KV head
    ``c % n_kv``)."""
    rows, cols = q.shape[0], k.shape[0]
    groups = heads // n_kv
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
    qpos = qpos0 + row // heads
    kvpos = j * block_s + col // n_kv
    valid = (((row % heads) // groups == col % n_kv)
             & (kvpos <= qpos) & (kvpos < kv_len))
    scale = 1.0 / math.sqrt(q.shape[-1])
    logit = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    logit = jnp.where(valid, logit, -1e30)

    m_prev = m_ref[...]                                    # [R, 1]
    m_new = jnp.maximum(m_prev, jnp.max(logit, axis=1, keepdims=True))
    p = jnp.exp(logit - m_new)                             # [R, C]
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _init_carries(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, -1e30)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _context(m_ref, l_ref, acc_ref):
    """Normalized context rows; rows that saw no valid position (never
    raised the running max off its init) are zeros."""
    seen = m_ref[...] > -5e29
    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
    return jnp.where(seen, out, 0.0)


def _fold(kv):
    """[bs, n_kv, hd] page -> [bs*n_kv, hd] columns (a free reshape:
    the merged dims are above the tiled minor pair)."""
    bs, n_kv, hd = kv.shape
    return kv.reshape(bs * n_kv, hd)


def _kernel(start_ref, len_ref, bt_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, block_s: int, tq: int, n_kv: int,
            heads: int, out_dtype):
    del bt_ref   # consumed by the index_map; the body only needs positions
    b, t, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _init_carries(m_ref, l_ref, acc_ref)

    _page_step(q_ref[0].astype(jnp.float32),
               _fold(k_ref[0].astype(jnp.float32)),
               _fold(v_ref[0].astype(jnp.float32)),
               start_ref[b] + t * tq, len_ref[b], j, m_ref, l_ref, acc_ref,
               block_s=block_s, n_kv=n_kv, heads=heads)

    @pl.when(j == pl.num_programs(2) - 1)
    def _flush():
        o_ref[0] = _context(m_ref, l_ref, acc_ref).astype(out_dtype)


def _codes_kernel(start_ref, len_ref, bt_ref, q_ref, k_ref, v_ref,
                  qq_ref, kq_ref, vq_ref, oq_ref, o_ref, m_ref, l_ref,
                  acc_ref, *, block_s: int, tq: int, n_kv: int,
                  heads: int):
    """Codes-mode body: q and KV arrive as uint8 DNA-TEQ codes and are
    decoded in VMEM *after* the HBM→VMEM DMA (the bytes that cross HBM
    are 1/elem); the flush re-encodes the attention output under the
    attn_out parameters so the kernel is code-in/code-out — no f32
    activation ever leaves it."""
    del bt_ref
    b, t, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _init_carries(m_ref, l_ref, acc_ref)

    _page_step(decode_heads(qq_ref[...], q_ref[0]),
               _fold(decode_heads(kq_ref[...], k_ref[0])),
               _fold(decode_heads(vq_ref[...], v_ref[0])),
               start_ref[b] + t * tq, len_ref[b], j, m_ref, l_ref, acc_ref,
               block_s=block_s, n_kv=n_kv, heads=heads)

    @pl.when(j == pl.num_programs(2) - 1)
    def _flush():
        o_ref[0] = eq.encode_meta(_context(m_ref, l_ref, acc_ref),
                                  oq_ref[0, :])


def _paged_call(body, q, k_pages, v_pages, block_tables, q_start, kv_lens,
                params, *, out_dtype, interpret):
    """Shared pallas_call: q [B, S, n_kv, g, hd] -> rows [B, S*H, hd];
    ``params`` are small 2-D arrays kept resident (constant index_map)."""
    b, s, n_kv, g, hd = q.shape
    heads = n_kv * g
    block_s = k_pages.shape[1]
    max_blk = block_tables.shape[1]
    tq = q_tile(s, heads)
    rows = tq * heads
    q_rows = q.reshape(b, s * heads, hd)
    page_spec = pl.BlockSpec((1, block_s, n_kv, hd),
                             lambda i, t, j, S, L, T: (T[i, j], 0, 0, 0))
    row_spec = pl.BlockSpec((1, rows, hd),
                            lambda i, t, j, S, L, T: (i, t, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,   # q_start, kv_lens, block_tables
        grid=(b, s // tq, max_blk),
        in_specs=[row_spec, page_spec, page_spec] + [
            pl.BlockSpec(p.shape, lambda i, t, j, S, L, T: (0, 0))
            for p in params],
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),     # running max
            pltpu.VMEM((rows, 1), jnp.float32),     # running denom
            pltpu.VMEM((rows, hd), jnp.float32),    # accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(body, block_s=block_s, tq=tq, n_kv=n_kv,
                          heads=heads),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s * heads, hd), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q_start.astype(jnp.int32), kv_lens.astype(jnp.int32),
      block_tables.astype(jnp.int32), q_rows, k_pages, v_pages, *params)
    return out.reshape(b, s, n_kv, g, hd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_prefill_paged_codes_kernel(
    q_codes: jax.Array,       # [B, S, n_kv, g, hd] uint8 — roped q codes
    k_pages: jax.Array,       # [N_blocks, bs, n_kv, hd] uint8 codes
    v_pages: jax.Array,       # [N_blocks, bs, n_kv, hd] uint8 codes
    q_qmeta: jax.Array,       # [4] f32 — attn_q parameters
    k_qmeta: jax.Array,       # [n_kv, 4] f32 — per-head K parameters
    v_qmeta: jax.Array,       # [n_kv, 4] f32 — per-head V parameters
    out_qmeta: jax.Array,     # [4] f32 — attn_out (alpha, beta, base, bits)
    block_tables: jax.Array,  # [B, max_blk] int32
    q_start: jax.Array,       # [B] int32
    kv_lens: jax.Array,       # [B] int32
    *,
    interpret: bool = False,
) -> jax.Array:
    """Codes-mode chunked flash prefill: same paging/masking contract as
    :func:`flash_prefill_paged_kernel`, but every operand is uint8 DNA-
    TEQ codes.  The decode parameters ride as VMEM-resident blocks
    (constant index_map — fetched once, reused by every grid cell) and
    the output is the uint8 re-encode of the attention context under
    ``out_qmeta``.  Returns [B, S, n_kv, g, hd] uint8."""
    params = [q_qmeta.astype(jnp.float32).reshape(1, 4),
              k_qmeta.astype(jnp.float32),
              v_qmeta.astype(jnp.float32),
              out_qmeta.astype(jnp.float32).reshape(1, 4)]
    return _paged_call(_codes_kernel, q_codes, k_pages, v_pages,
                       block_tables, q_start, kv_lens, params,
                       out_dtype=jnp.uint8, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def flash_prefill_paged_kernel(
    q: jax.Array,             # [B, S, n_kv, g, hd] — roped query chunk
    k_pages: jax.Array,       # [N_blocks, bs, n_kv, hd] (bf16 / f8 / ...)
    v_pages: jax.Array,       # [N_blocks, bs, n_kv, hd]
    block_tables: jax.Array,  # [B, max_blk] int32 — page id per logical block
    q_start: jax.Array,       # [B] int32 — absolute position of query row 0
    kv_lens: jax.Array,       # [B] int32 — cache positions actually written
    *,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Chunked flash prefill over a paged KV cache.

    Logical block ``j`` of sequence ``i`` lives in physical page
    ``block_tables[i, j]`` (positions ``[j*bs, (j+1)*bs)``); page ids
    past a sequence's fill must still be *valid* indices (the trash
    page) — their contribution is masked by ``kv_lens``.  Returns
    [B, S, n_kv, g, hd].
    """
    return _paged_call(functools.partial(_kernel, out_dtype=out_dtype),
                       q, k_pages, v_pages, block_tables, q_start, kv_lens,
                       [], out_dtype=out_dtype, interpret=interpret)
