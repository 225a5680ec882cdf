"""Exponent codes as the configurations state them, written out plainly
for the benchmark's own use (weights from the seed, the reference).

A code is one byte ``S << 7 | (e - e_min)`` standing for
``S * (alpha * base**e + beta)``, with ``e`` a signed ``bits``-wide
exponent.  Parameters travel packed as ``[..., 4]`` float32
``(alpha, beta, base, bits)``.
"""

from __future__ import annotations

import jax.numpy as jnp

BASE = 2.0 ** (1.0 / 8.0)


def meta(hi: float, bits: int = 7, base: float = BASE):
    """Parameters whose largest magnitude is ``hi``, with ``beta = 0``:
    the code covers ``hi * base**-(2**bits - 1) .. hi``."""
    e_max = 2 ** (bits - 1) - 1
    return jnp.asarray([hi / base ** e_max, 0.0, base, float(bits)],
                       jnp.float32)


def coarser(qmeta):
    """The same code with one bit fewer over the same range: the base
    squared, ``bits - 1``, the same largest magnitude (``beta = 0``)."""
    alpha, _, base, bits = (qmeta[..., i] for i in range(4))
    hi = alpha * base ** (jnp.exp2(bits - 1.0) - 1.0)
    base2, bits2 = base * base, bits - 1.0
    return jnp.stack([hi / base2 ** (jnp.exp2(bits2 - 1.0) - 1.0),
                      jnp.zeros_like(alpha), base2, bits2], -1)


def encode(x, qmeta):
    alpha, beta, base, bits = (qmeta[..., i] for i in range(4))
    e_min = -jnp.exp2(bits - 1.0)
    e_max = jnp.exp2(bits - 1.0) - 1.0
    mag = jnp.abs(x).astype(jnp.float32)
    arg = jnp.maximum((mag - beta) / alpha, 1e-30)
    e = jnp.clip(jnp.round(jnp.log(arg) / jnp.log(base)), e_min, e_max)
    return (jnp.where(x < 0, 128, 0)
            + (e - e_min).astype(jnp.int32)).astype(jnp.uint8)


def decode(codes, qmeta, dtype=jnp.float32):
    alpha, beta, base, bits = (qmeta[..., i] for i in range(4))
    c = codes.astype(jnp.int32)
    sign = 1.0 - 2.0 * (c >> 7).astype(jnp.float32)
    e = (c & 0x7F).astype(jnp.float32) - jnp.exp2(bits - 1.0)
    return (sign * (alpha * jnp.exp(e * jnp.log(base)) + beta)).astype(dtype)


def table(qmeta):
    """``[..., 4]`` parameters -> ``[..., 256]`` decoded values."""
    return decode(jnp.arange(256, dtype=jnp.int32),
                  qmeta[..., None, :])

