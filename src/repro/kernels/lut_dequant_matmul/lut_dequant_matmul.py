"""Fused exponent-decode + matmul Pallas TPU kernel (the Lama perf path).

``y[M, N] = x[M, K] @ decode(codes[K, N])`` where ``decode`` maps uint8
DNA-TEQ codes to ``sign * (alpha * base**e + beta)``.  The four packed
parameters (``qmeta``) live in VMEM for the whole kernel — the TPU
analog of Lama's "open row": one activation serves every tile of the
operand-coalesced batch (DESIGN.md §2).  Weights cross HBM as
1 byte/param; the f32 tensor never exists in HBM.

The decode is DNA-TEQ's closed form (:func:`decode_meta`), the one
formula every table in the repository is built from: the 256-entry
LUT of a weight or activation site is ``decode_meta(arange(256))``, so
a kernel tile decodes to exactly the values a host-side table lookup
gives.  On the TPU's vector unit an exp is cheaper than a serialized
8-bit gather, and Mosaic lowers no 256-entry gather in any case.

Fused epilogues (DESIGN.md §Fused-path): the accumulator flush can apply
``+bias`` and/or an activation (``gelu``/``silu``/``relu``) so chains
like ``act(x @ w_up)`` never round-trip an intermediate through HBM.
The gated variant runs *two* dequant matmuls against the same ``x``
block (w_gate and w_up share the [K, N] geometry in every gated MLP of
the zoo) and flushes ``act(x@w_g) * (x@w_u)`` — the 3-round-trip MLP
front half collapses into one kernel.

Dual-operand variants (the LamaAccel Eq.1 execution path): *both*
operands arrive as uint8 DNA-TEQ codes and each decodes through its own
parameters inside the kernel — activations cross HBM as 1 B/elem
exactly like weights, and the f32 activation tensor never exists in
HBM.  An optional **quantize epilogue** re-encodes the flushed output
tile against a third (calibrated) parameter set and stores uint8 codes,
so chains of quantized matmuls stay code-in/code-out: the only f32 form
of the intermediate is the VMEM accumulator tile.

Grid: (M/bm, N/bn, K/bk), K innermost ("arbitrary"); fp32 VMEM scratch
accumulator(s), flushed to the output tile on the last K step.  The
blocks are 128-multiples; the wrapper picks them by shape
(``ops._default_tiling``), and a kernel whose counted footprint
(:func:`vmem_bytes`) passes the chip's default scoped VMEM limit asks
for that much.

K-padding note: with a *float* activation operand, padded K positions
contribute zero automatically (x is zero-padded).  With a *code*
operand, the pad byte 0 decodes to ``±(alpha·base^e_min + beta) ≠ 0``,
so the dual kernels mask the decoded activation tile against the true
contraction length (``k_valid``) before the MXU op.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.exponential_quant import decode_meta, encode_meta

EPILOGUES = ("gelu", "silu", "relu")
# the chip's default scoped VMEM limit (TPU v5e); a kernel whose counted
# footprint passes it asks for what it counted
SCOPED_VMEM = 16 * 1024 * 1024


def vmem_bytes(kind: str, bm: int, bk: int, bn: int) -> int:
    """Counted VMEM of one grid step of a ``kind`` kernel (``mm``,
    ``gated``, ``dual``, ``dual_gated``) at blocks (bm, bk, bn): two
    buffers of each input block and the decoded f32 form of each code
    block (a float x block counts 8 bytes an element: two f32 buffers,
    or two bf16 buffers and their f32 cast), the f32 accumulators and
    two f32 buffers of the out tile."""
    mats = 2 if kind in ("gated", "dual_gated") else 1
    x = bm * bk * ((2 + 4) if kind.startswith("dual") else 2 * 4)
    codes = mats * bk * bn * (2 + 4)
    acc_out = (mats + 2) * bm * bn * 4
    return x + codes + acc_out


def _compiler_params(kind: str, bm: int, bk: int, bn: int):
    """K innermost and sequential; the counted VMEM where it passes the
    default limit."""
    need = vmem_bytes(kind, bm, bk, bn)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=need if need > SCOPED_VMEM else None)


def apply_activation(x: jax.Array, kind: str | None) -> jax.Array:
    """Shared epilogue-activation ladder (kernel, reference, and the
    jnp fallback in lama_layers all dispatch through this)."""
    if kind is None:
        return x
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "relu":
        return jnp.maximum(x, 0.0)
    raise ValueError(kind)


def _decode(qmetas_ref, row: int, codes: jax.Array) -> jax.Array:
    """Decode a code tile through parameter row ``row`` (f32)."""
    return decode_meta(codes, qmetas_ref[row, :])


def _kernel(x_ref, codes_ref, qmeta_ref, bias_ref, o_ref, acc_ref,
            *, epilogue: str | None, has_bias: bool, w_transposed: bool,
            out_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _decode(qmeta_ref, 0, codes_ref[...])    # [bk, bn] (or [bn, bk])
    x = x_ref[...].astype(jnp.float32)            # [bm, bk]
    if w_transposed:
        # codes stored [N, K] (e.g. a tied embedding table): decode the
        # [bn, bk] block and contract on its last axis — the transpose
        # happens on the VMEM-resident tile, never on the HBM table.
        acc_ref[...] += jax.lax.dot_general(
            x, w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        acc = acc_ref[...]
        if has_bias:
            acc = acc + bias_ref[...]
        o_ref[...] = apply_activation(acc, epilogue).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bk", "bn", "epilogue", "has_bias",
                     "w_transposed", "out_dtype", "interpret"),
)
def lut_dequant_matmul_kernel(
    x: jax.Array,        # [M, K] float
    codes: jax.Array,    # [K, N] uint8 ([N, K] when w_transposed)
    qmeta: jax.Array,    # [4] float32 (alpha, beta, base, bits)
    bias: jax.Array,     # [N] float32 (ignored unless has_bias)
    *,
    bm: int = 128,
    bk: int = 128,
    bn: int = 128,
    epilogue: str | None = None,
    has_bias: bool = False,
    w_transposed: bool = False,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    m, k = x.shape
    if w_transposed:
        n, k2 = codes.shape
    else:
        k2, n = codes.shape
    assert k == k2, (x.shape, codes.shape, w_transposed)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n, bm, bk, bn)
    grid = (m // bm, n // bn, k // bk)

    codes_spec = (pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk))
                  if w_transposed else
                  pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)))
    return pl.pallas_call(
        functools.partial(_kernel, epilogue=epilogue, has_bias=has_bias,
                          w_transposed=w_transposed, out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            codes_spec,
            pl.BlockSpec((1, 4), lambda i, j, kk: (0, 0)),   # resident params
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=_compiler_params("mm", bm, bk, bn),
        interpret=interpret,
    )(x, codes.astype(jnp.uint8), qmeta.reshape(1, 4).astype(jnp.float32),
      bias.reshape(1, n).astype(jnp.float32))


# ---------------------------------------------------------------------
# Gated dual-matmul variant: act(x @ decode(cg)) * (x @ decode(cu))
# ---------------------------------------------------------------------

def _gated_kernel(x_ref, cg_ref, cu_ref, qmetas_ref, o_ref,
                  accg_ref, accu_ref, *, activation: str, out_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    wg = _decode(qmetas_ref, 0, cg_ref[...])
    wu = _decode(qmetas_ref, 1, cu_ref[...])
    x = x_ref[...].astype(jnp.float32)
    accg_ref[...] += jnp.dot(x, wg, preferred_element_type=jnp.float32)
    accu_ref[...] += jnp.dot(x, wu, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = (apply_activation(accg_ref[...], activation)
                      * accu_ref[...]).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bk", "bn", "activation", "out_dtype",
                     "interpret"),
)
def lut_dequant_matmul_gated_kernel(
    x: jax.Array,         # [M, K] float
    codes_g: jax.Array,   # [K, N] uint8 (gate projection)
    codes_u: jax.Array,   # [K, N] uint8 (up projection)
    qmetas: jax.Array,    # [2, 4] float32 (gate params, up params)
    *,
    bm: int = 128,
    bk: int = 128,
    bn: int = 128,
    activation: str = "silu",
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    m, k = x.shape
    k2, n = codes_g.shape
    assert k == k2 and codes_u.shape == codes_g.shape, (
        x.shape, codes_g.shape, codes_u.shape)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n, bm, bk, bn)
    grid = (m // bm, n // bn, k // bk)

    return pl.pallas_call(
        functools.partial(_gated_kernel, activation=activation,
                          out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((2, 4), lambda i, j, kk: (0, 0)),   # resident params
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=_compiler_params("gated", bm, bk, bn),
        interpret=interpret,
    )(x, codes_g.astype(jnp.uint8), codes_u.astype(jnp.uint8),
      qmetas.reshape(2, 4).astype(jnp.float32))


# ---------------------------------------------------------------------
# Dual-operand variants: activation codes decoded in-kernel too
# ---------------------------------------------------------------------

def _decode_act_tile(qmetas_ref, codes, k_valid: int | None, bk: int):
    """Decode one activation code tile through parameter row 0 and zero
    the K positions past the true contraction length (pad byte 0 is a
    *live* code, unlike a zero float)."""
    a = _decode(qmetas_ref, 0, codes)
    if k_valid is not None:
        kpos = (pl.program_id(2) * bk
                + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1))
        a = jnp.where(kpos < k_valid, a, 0.0)
    return a


def _dual_kernel(xc_ref, wc_ref, qmetas_ref, bias_ref, o_ref, acc_ref, *,
                 epilogue: str | None, has_bias: bool, out_quant: bool,
                 k_valid: int | None, bk: int, out_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = _decode_act_tile(qmetas_ref, xc_ref[...], k_valid, bk)  # [bm, bk]
    w = _decode(qmetas_ref, 1, wc_ref[...])                     # [bk, bn]
    acc_ref[...] += jnp.dot(a, w, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        acc = acc_ref[...]
        if has_bias:
            acc = acc + bias_ref[...]
        acc = apply_activation(acc, epilogue)
        if out_quant:
            # quantize epilogue: re-encode against the *output* params
            # (qmetas row 2) so the next quantized matmul reads codes
            o_ref[...] = encode_meta(acc, qmetas_ref[2, :])
        else:
            o_ref[...] = acc.astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bk", "bn", "epilogue", "has_bias", "out_quant",
                     "k_valid", "out_dtype", "interpret"),
)
def lut_dequant_matmul_dual_kernel(
    x_codes: jax.Array,  # [M, K] uint8 activation codes
    codes: jax.Array,    # [K, N] uint8 weight codes
    qmetas: jax.Array,   # [3, 4] f32 (act, weight, out params)
    bias: jax.Array,     # [N] f32 (ignored unless has_bias)
    *,
    bm: int = 128,
    bk: int = 128,
    bn: int = 128,
    epilogue: str | None = None,
    has_bias: bool = False,
    out_quant: bool = False,
    k_valid: int | None = None,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """``decode_a(x_codes) @ decode_w(codes)`` with both decodes
    in-kernel; ``out_quant`` re-encodes the flush through qmetas[2]
    and emits uint8 codes (code-in/code-out)."""
    m, k = x_codes.shape
    k2, n = codes.shape
    assert k == k2, (x_codes.shape, codes.shape)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n, bm, bk, bn)
    grid = (m // bm, n // bn, k // bk)
    out_dt = jnp.uint8 if out_quant else out_dtype

    return pl.pallas_call(
        functools.partial(_dual_kernel, epilogue=epilogue,
                          has_bias=has_bias, out_quant=out_quant,
                          k_valid=k_valid, bk=bk, out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((3, 4), lambda i, j, kk: (0, 0)),   # resident params
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dt),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=_compiler_params("dual", bm, bk, bn),
        interpret=interpret,
    )(x_codes.astype(jnp.uint8), codes.astype(jnp.uint8),
      qmetas.reshape(3, 4).astype(jnp.float32),
      bias.reshape(1, n).astype(jnp.float32))


def _dual_gated_kernel(xc_ref, cg_ref, cu_ref, qmetas_ref, o_ref,
                       accg_ref, accu_ref, *, activation: str,
                       out_quant: bool, k_valid: int | None, bk: int,
                       out_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    a = _decode_act_tile(qmetas_ref, xc_ref[...], k_valid, bk)
    wg = _decode(qmetas_ref, 1, cg_ref[...])
    wu = _decode(qmetas_ref, 2, cu_ref[...])
    accg_ref[...] += jnp.dot(a, wg, preferred_element_type=jnp.float32)
    accu_ref[...] += jnp.dot(a, wu, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        out = apply_activation(accg_ref[...], activation) * accu_ref[...]
        if out_quant:
            o_ref[...] = encode_meta(out, qmetas_ref[3, :])
        else:
            o_ref[...] = out.astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bk", "bn", "activation", "out_quant", "k_valid",
                     "out_dtype", "interpret"),
)
def lut_dequant_matmul_dual_gated_kernel(
    x_codes: jax.Array,   # [M, K] uint8 activation codes
    codes_g: jax.Array,   # [K, N] uint8 (gate projection)
    codes_u: jax.Array,   # [K, N] uint8 (up projection)
    qmetas: jax.Array,    # [4, 4] (act, gate, up, out params)
    *,
    bm: int = 128,
    bk: int = 128,
    bn: int = 128,
    activation: str = "silu",
    out_quant: bool = False,
    k_valid: int | None = None,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Gated-MLP front half with an activation-code operand:
    ``act(dec_a(x) @ dec(cg)) * (dec_a(x) @ dec(cu))`` — one shared act
    decode feeds both matmuls; ``out_quant`` re-encodes the flush
    (qmetas row 3) so the down projection reads codes."""
    m, k = x_codes.shape
    k2, n = codes_g.shape
    assert k == k2 and codes_u.shape == codes_g.shape, (
        x_codes.shape, codes_g.shape, codes_u.shape)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n, bm, bk, bn)
    grid = (m // bm, n // bn, k // bk)
    out_dt = jnp.uint8 if out_quant else out_dtype

    return pl.pallas_call(
        functools.partial(_dual_gated_kernel, activation=activation,
                          out_quant=out_quant, k_valid=k_valid, bk=bk,
                          out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((4, 4), lambda i, j, kk: (0, 0)),   # resident params
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dt),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=_compiler_params("dual_gated", bm, bk, bn),
        interpret=interpret,
    )(x_codes.astype(jnp.uint8), codes_g.astype(jnp.uint8),
      codes_u.astype(jnp.uint8), qmetas.reshape(4, 4).astype(jnp.float32))
