"""Public wrappers of the grouped expert matmuls.  Off the TPU they run
the jnp oracle unless ``interpret=True`` (kernel-fidelity tests)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.moe_expert.moe_expert import (moe_expert_down_kernel,
                                                 moe_expert_gated_kernel)
from repro.kernels.moe_expert.ref import (moe_expert_down_ref,
                                          moe_expert_gated_ref)


def _largest_block(n: int, cap: int) -> int:
    for b in (cap, 128):
        if n % b == 0:
            return b
    return n


def moe_expert_gated(x, codes_g, codes_u, qmeta_g, qmeta_u, tile_expert,
                     used, *, tm: int, activation: str = "silu",
                     out_dtype=jnp.float32, interpret: bool | None = None):
    """``act(x @ Wg[e]) * (x @ Wu[e])`` per ``tm``-row tile ``i``, with
    ``e = tile_expert[i]``, for the first ``used`` tiles."""
    qmetas = jnp.stack([qmeta_g.reshape(4), qmeta_u.reshape(4)])
    if interpret is None and jax.default_backend() == "cpu":
        return moe_expert_gated_ref(x, codes_g, codes_u, qmetas,
                                    tile_expert, tm=tm,
                                    activation=activation,
                                    out_dtype=out_dtype)
    return moe_expert_gated_kernel(
        x, codes_g, codes_u, qmetas, tile_expert, used, tm=tm,
        bn=_largest_block(codes_g.shape[-1], 128), activation=activation,
        out_dtype=out_dtype, interpret=bool(interpret))


def moe_expert_down(x, codes, qmeta, tile_expert, used, *, tm: int,
                    out_dtype=jnp.float32, interpret: bool | None = None):
    """``x @ Wd[e]`` per ``tm``-row tile, as :func:`moe_expert_gated`."""
    if interpret is None and jax.default_backend() == "cpu":
        return moe_expert_down_ref(x, codes, qmeta.reshape(4), tile_expert,
                                   tm=tm, out_dtype=out_dtype)
    return moe_expert_down_kernel(
        x, codes, qmeta, tile_expert, used, tm=tm,
        bn=_largest_block(codes.shape[-1], 256), out_dtype=out_dtype,
        interpret=bool(interpret))
