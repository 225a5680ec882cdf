"""Pure-jnp oracles for the fused decode matmuls (+ epilogues): decode
the whole operand, then one matmul."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import exponential_quant as eq
from repro.kernels.lut_dequant_matmul.lut_dequant_matmul import (
    apply_activation as _act,
)


def _decode(codes: jax.Array, qmeta: jax.Array) -> jax.Array:
    return eq.decode_meta(codes, jnp.asarray(qmeta, jnp.float32))


def lut_dequant_matmul_ref(
    x: jax.Array, codes: jax.Array, qmeta: jax.Array,
    out_dtype=jnp.float32, epilogue: str | None = None, bias=None,
) -> jax.Array:
    out = jnp.matmul(x.astype(jnp.float32), _decode(codes, qmeta),
                     preferred_element_type=jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)[None, :]
    return _act(out, epilogue).astype(out_dtype)


def lut_dequant_matmul_gated_ref(
    x: jax.Array, codes_g: jax.Array, codes_u: jax.Array,
    qmeta_g: jax.Array, qmeta_u: jax.Array, activation: str = "silu",
    out_dtype=jnp.float32,
) -> jax.Array:
    g = lut_dequant_matmul_ref(x, codes_g, qmeta_g)
    u = lut_dequant_matmul_ref(x, codes_u, qmeta_u)
    return (_act(g, activation) * u).astype(out_dtype)


def lut_dequant_matmul_dual_ref(
    x_codes: jax.Array, codes: jax.Array,
    qmeta_x: jax.Array, qmeta_w: jax.Array,
    out_qmeta: jax.Array | None = None,
    out_dtype=jnp.float32, epilogue: str | None = None, bias=None,
) -> jax.Array:
    """Decode-then-matmul oracle of the dual kernel: both operands
    decoded, one matmul, optional quantize epilogue."""
    out = jnp.matmul(_decode(x_codes, qmeta_x), _decode(codes, qmeta_w),
                     preferred_element_type=jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)[None, :]
    out = _act(out, epilogue)
    if out_qmeta is not None:
        return eq.encode_meta(out, out_qmeta)
    return out.astype(out_dtype)


def lut_dequant_matmul_dual_gated_ref(
    x_codes: jax.Array, codes_g: jax.Array, codes_u: jax.Array,
    qmeta_x: jax.Array, qmeta_g: jax.Array, qmeta_u: jax.Array,
    activation: str = "silu", out_qmeta: jax.Array | None = None,
    out_dtype=jnp.float32,
) -> jax.Array:
    a = _decode(x_codes, qmeta_x)
    g = jnp.matmul(a, _decode(codes_g, qmeta_g),
                   preferred_element_type=jnp.float32)
    u = jnp.matmul(a, _decode(codes_u, qmeta_u),
                   preferred_element_type=jnp.float32)
    out = _act(g, activation) * u
    if out_qmeta is not None:
        return eq.encode_meta(out, out_qmeta)
    return out.astype(out_dtype)
