"""Pure-jnp oracle of the grouped expert matmuls: every tile times its
expert's decoded weights (tiles past ``used`` are computed too; no
caller reads them)."""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.exponential_quant import decode_meta
from repro.kernels.lut_dequant_matmul.lut_dequant_matmul import (
    apply_activation,
)


def _tiles(x, tm):
    return x.astype(jnp.float32).reshape(x.shape[0] // tm, tm, x.shape[1])


def moe_expert_gated_ref(x, codes_g, codes_u, qmetas, tile_expert, *, tm,
                         activation="silu", out_dtype=jnp.float32):
    xt = _tiles(x, tm)
    g = jnp.einsum("tmk,tkn->tmn", xt,
                   decode_meta(codes_g[tile_expert], qmetas[0]))
    u = jnp.einsum("tmk,tkn->tmn", xt,
                   decode_meta(codes_u[tile_expert], qmetas[1]))
    out = apply_activation(g, activation) * u
    return out.reshape(x.shape[0], -1).astype(out_dtype)


def moe_expert_down_ref(x, codes, qmeta, tile_expert, *, tm,
                        out_dtype=jnp.float32):
    out = jnp.einsum("tmk,tkn->tmn", _tiles(x, tm),
                     decode_meta(codes[tile_expert], qmeta))
    return out.reshape(x.shape[0], -1).astype(out_dtype)
