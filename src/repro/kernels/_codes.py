"""Shared decode helper for the codes-mode attention kernels.

KV pages in codes mode store one uint8 DNA-TEQ code per element; each
KV head owns its own (alpha, beta, base, bits) parameters (per-head
calibration is the accuracy lever when attention goes to codes).  Both
flash kernels and both jnp oracles decode through this exact helper so
the decoded f32 values — and therefore the online-softmax accumulation
inputs — are bit-identical between kernel and oracle.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.exponential_quant import decode_exp


def decode_heads(qmeta: jnp.ndarray, codes: jnp.ndarray) -> jnp.ndarray:
    """Per-row closed-form decode.

    ``qmeta``: [n, 4] packed parameters; ``codes``: [..., n, hd] uint8.
    Returns f32 of ``codes.shape`` where element ``[..., i, h]`` decodes
    under ``qmeta[i]``.  With ``n == 1`` one parameter set decodes the
    whole array (the attn_q site).  The parameters enter as ``[n, 1]``
    columns, a 2-D layout the TPU kernels lower."""
    cols = [qmeta[:, i:i + 1] for i in range(4)]
    return decode_exp(codes, *cols)


__all__ = ["decode_heads"]
