"""Grouped expert matmuls over rows sorted by expert (the dropless
mixture-of-experts layer's kernels).

The rows ``x [R, K]`` come grouped by expert, each group padded to whole
tiles of ``tm`` rows; ``tile_expert[i]`` names the expert of tile ``i``
and ``used[0]`` how many tiles hold rows.  Every used tile multiplies
its rows by its own expert's weight codes ``[E, K, N]`` (uint8 DNA-TEQ
codes, one parameter set for all experts of a layer), decoded in VMEM
after the DMA exactly as ``lut_dequant_matmul`` decodes them: so only
the experts that received rows cross HBM, each once per tile.

Tiles past ``used`` are left alone: their index maps repeat the last
used tile's blocks, so they issue no DMA and write nothing back but
what that tile wrote.  Their output rows are garbage; no row of the
layer's result reads them.

- ``moe_expert_gated_kernel``: ``act(x @ Wg[e]) * (x @ Wu[e])``;
- ``moe_expert_down_kernel``: ``x @ Wd[e]``.

Grid: (tiles, N / bn), both "arbitrary" (the skip relies on the order);
the whole contraction ``K`` is one block, so a tile's weight block is
read once per output block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.exponential_quant import decode_meta
from repro.kernels.lut_dequant_matmul.lut_dequant_matmul import (
    apply_activation,
)

_ARBITRARY = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary"))


def _maps(nj: int):
    """Index maps ``(i, j, tile_expert, used)`` of the row tile, the
    expert's weight block and the output block; tiles past ``used``
    stay on the last used tile's last blocks."""
    def tile(i, u):
        return jnp.minimum(i, jnp.maximum(u[0] - 1, 0))

    def col(i, j, u):
        return jnp.where(i > tile(i, u), nj - 1, j)

    rows = lambda i, j, te, u: (tile(i, u), 0)                # noqa: E731
    weights = lambda i, j, te, u: (te[tile(i, u)], 0, col(i, j, u))  # noqa: E731
    out = lambda i, j, te, u: (tile(i, u), col(i, j, u))      # noqa: E731
    return rows, weights, out


def _gated_body(te_ref, used_ref, x_ref, cg_ref, cu_ref, qm_ref, o_ref, *,
                activation: str):
    @pl.when(pl.program_id(0) < used_ref[0])
    def _tile():
        x = x_ref[...].astype(jnp.float32)                   # [tm, K]
        g = jnp.dot(x, decode_meta(cg_ref[...], qm_ref[0, :]),
                    preferred_element_type=jnp.float32)
        u = jnp.dot(x, decode_meta(cu_ref[...], qm_ref[1, :]),
                    preferred_element_type=jnp.float32)
        o_ref[...] = (apply_activation(g, activation) * u).astype(o_ref.dtype)


def _down_body(te_ref, used_ref, x_ref, c_ref, qm_ref, o_ref):
    @pl.when(pl.program_id(0) < used_ref[0])
    def _tile():
        x = x_ref[...].astype(jnp.float32)
        o_ref[...] = jnp.dot(x, decode_meta(c_ref[...], qm_ref[0, :]),
                             preferred_element_type=jnp.float32
                             ).astype(o_ref.dtype)


def _call(body, x, codes, qmetas, tile_expert, used, *, tm, bn, out_dtype,
          interpret):
    r, k = x.shape
    _, k2, n = codes[0].shape
    assert k == k2 and r % tm == 0 and n % bn == 0, (x.shape, codes[0].shape,
                                                     tm, bn)
    nj = n // bn
    rows, weights, out = _maps(nj)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # tile_expert, used
        grid=(r // tm, nj),
        in_specs=[pl.BlockSpec((tm, k), rows)]
        + [pl.BlockSpec((None, k, bn), weights) for _ in codes]
        + [pl.BlockSpec(qmetas.shape, lambda i, j, te, u: (0, 0))],
        out_specs=pl.BlockSpec((tm, bn), out),
    )
    return pl.pallas_call(
        body, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, n), out_dtype),
        compiler_params=_ARBITRARY, interpret=interpret,
    )(tile_expert.astype(jnp.int32), used.astype(jnp.int32).reshape(1), x,
      *[c.astype(jnp.uint8) for c in codes], qmetas.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("tm", "bn", "activation",
                                             "out_dtype", "interpret"))
def moe_expert_gated_kernel(
    x: jax.Array,            # [R, K] rows grouped by expert, tm-padded
    codes_g: jax.Array,      # [E, K, N] uint8 gate codes
    codes_u: jax.Array,      # [E, K, N] uint8 up codes
    qmetas: jax.Array,       # [2, 4] f32 (gate params, up params)
    tile_expert: jax.Array,  # [R // tm] int32
    used: jax.Array,         # [] int32 — tiles holding rows
    *,
    tm: int,
    bn: int = 128,
    activation: str = "silu",
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    return _call(functools.partial(_gated_body, activation=activation),
                 x, (codes_g, codes_u), qmetas.reshape(2, 4), tile_expert,
                 used, tm=tm, bn=bn, out_dtype=out_dtype,
                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tm", "bn", "out_dtype",
                                             "interpret"))
def moe_expert_down_kernel(
    x: jax.Array,            # [R, K] rows grouped by expert, tm-padded
    codes: jax.Array,        # [E, K, N] uint8 down-projection codes
    qmeta: jax.Array,        # [4] f32
    tile_expert: jax.Array,  # [R // tm] int32
    used: jax.Array,         # [] int32
    *,
    tm: int,
    bn: int = 256,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    return _call(_down_body, x, (codes,), qmeta.reshape(1, 4), tile_expert,
                 used, tm=tm, bn=bn, out_dtype=out_dtype,
                 interpret=interpret)
