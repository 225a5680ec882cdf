"""Counting-subarray analog: signed exponent-occurrence histograms.

``hist[g, e] = sum_i sign[g, i] * [vals[g, i] == e]`` — the LamaAccel
counter update (increment/decrement by the XNOR of signs, §V-C),
vectorized: each (row-block, chunk) grid step compares a value chunk
against a lane-aligned iota of bin ids and accumulates into a resident
VMEM histogram block.  On TPU the compare+accumulate maps onto the VPU
(and the one-hot contraction form onto the MXU for large E).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(vals_ref, signs_ref, o_ref, acc_ref, *, num_bins: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    vals = vals_ref[...]                        # [bg, bm] int32
    signs = signs_ref[...].astype(jnp.float32)  # [bg, bm]
    bins = jax.lax.broadcasted_iota(jnp.int32, (1, num_bins), 1)  # [1, E]
    # one-hot contraction: [bg, bm] x [bm, E] per row via compare+dot
    onehot = (vals[..., None] == bins[None, :, :]).astype(jnp.float32)
    acc_ref[...] += jnp.einsum(
        "gm,gme->ge", signs, onehot.reshape(vals.shape + (num_bins,)),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "bg", "bm", "interpret"))
def exp_histogram_kernel(
    vals: jax.Array,     # [G, M] int32 in [0, num_bins)
    signs: jax.Array,    # [G, M] ±1
    *,
    num_bins: int,
    bg: int = 8,
    bm: int = 512,
    interpret: bool = False,
) -> jax.Array:
    g, m = vals.shape
    assert g % bg == 0 and m % bm == 0, (g, m, bg, bm)
    grid = (g // bg, m // bm)
    return pl.pallas_call(
        functools.partial(_kernel, num_bins=num_bins),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bg, bm), lambda i, j: (i, j)),
            pl.BlockSpec((bg, bm), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bg, num_bins), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((g, num_bins), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bg, num_bins), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(vals.astype(jnp.int32), signs)
