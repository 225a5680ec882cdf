"""DNA-TEQ adaptive exponential quantization (paper §II-C, ref [25]).

Values are represented as ``S * (alpha * base**e + beta)`` where

* ``S``    : sign of the original value (+1 / -1),
* ``e``    : signed ``bits``-wide integer exponent,
* ``alpha``: per-tensor scale,
* ``beta`` : per-tensor offset,
* ``base`` : per-tensor exponential base (searched, typically in (1, 2]).

A quantized tensor is stored as a single uint8 **code** per element:
``code = S_bit << 7 | (e + 2**(bits-1))`` — the same ``{S, int}`` 8-bit
layout the paper stores in DRAM source subarrays (§V-B).  Decoding is a
pure 256-entry table lookup, which is the hook the Pallas kernels use
(the decode LUT plays the role of Lama's open DRAM row).

The fit is an alternating Lloyd-style search: given exponent assignments,
``|x| ~ alpha * base**e + beta`` is *linear* in (alpha, beta) and solved in
closed form; given (alpha, beta), assignments are a rounded log.  The base
is grid-searched (paper: "search algorithm described in [25]").
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np


class QTensor(NamedTuple):
    """Unified quantized-operand carrier: uint8 DNA-TEQ codes plus their
    256-entry decode table and packed fit parameters.

    Weights have always travelled as the structurally-identical leaf
    dict (:func:`pack_qtensor` — kept as the on-tree format so
    checkpoints/sharding rules are untouched); *activations* flow
    between layers as ``QTensor`` values.  Both satisfy
    :func:`is_qtensor` and unpack through :func:`qt_parts`, so every
    matmul dispatch site treats the two operands uniformly.  Being a
    NamedTuple it is a pytree: act codes cross jit/scan boundaries as
    bytes, never decoded outside a kernel on the fused path.
    """

    codes: jax.Array   # uint8, the logical tensor shape
    lut: jax.Array     # [256] decode table
    qmeta: jax.Array   # [4] (alpha, beta, base, bits)

    @property
    def shape(self) -> tuple[int, ...]:
        return self.codes.shape

    @property
    def ndim(self) -> int:
        return self.codes.ndim

    @property
    def dtype(self):
        """The carrier's *decode* dtype (what consumers compute in)."""
        return self.lut.dtype


class ExpQuantParams(NamedTuple):
    """Per-tensor parameters of the exponential quantizer."""

    alpha: jax.Array  # f32 scalar
    beta: jax.Array   # f32 scalar
    base: jax.Array   # f32 scalar
    bits: int         # static: exponent width (3..7 in the paper)

    @property
    def e_min(self) -> int:
        return -(2 ** (self.bits - 1))

    @property
    def e_max(self) -> int:
        return 2 ** (self.bits - 1) - 1


def _sign_bit(x: jax.Array) -> jax.Array:
    """1 where negative, else 0 (paper's S bit; XNOR convention in §V-C)."""
    return (x < 0).astype(jnp.uint8)


def exponent_of(x: jax.Array, params: ExpQuantParams) -> jax.Array:
    """Nearest exponent assignment for |x| (int32, clipped to range)."""
    mag = jnp.abs(x).astype(jnp.float32)
    # b**e ~ (|x| - beta) / alpha ;  guard the log argument.
    arg = (mag - params.beta) / params.alpha
    arg = jnp.maximum(arg, 1e-30)
    e = jnp.round(jnp.log(arg) / jnp.log(params.base))
    return jnp.clip(e, params.e_min, params.e_max).astype(jnp.int32)


def encode(x: jax.Array, params: ExpQuantParams) -> jax.Array:
    """Quantize to uint8 codes ``S<<7 | biased_exponent``."""
    e = exponent_of(x, params)
    biased = (e - params.e_min).astype(jnp.uint8)
    return (_sign_bit(x) << 7) | biased


def split_code(codes: jax.Array, params: ExpQuantParams):
    """codes -> (sign ∈ {+1,-1} int8, exponent int32)."""
    sign = jnp.where((codes >> 7) > 0, -1, 1).astype(jnp.int8)
    e = (codes & 0x7F).astype(jnp.int32) + params.e_min
    return sign, e


def decode_table(params: ExpQuantParams, dtype=jnp.float32) -> jax.Array:
    """Full 256-entry decode LUT indexed directly by the uint8 code.

    Entries outside the live exponent range decode via the same formula
    (they are never produced by :func:`encode`); this keeps the table a
    pure function of ``params`` and gather-friendly.  Built through
    :func:`decode_meta`, the closed form the kernels decode with, so a
    table lookup and an in-kernel decode give the same values.
    """
    return decode_meta(jnp.arange(256, dtype=jnp.int32), pack_qmeta(params),
                       dtype)


def decode(codes: jax.Array, params: ExpQuantParams, dtype=jnp.float32) -> jax.Array:
    """Dequantize codes via the 256-entry LUT gather."""
    return decode_table(params, dtype)[codes.astype(jnp.int32)]


def pack_qmeta(params: ExpQuantParams) -> jax.Array:
    """[4] float32 (alpha, beta, base, bits) — the packed form the
    kernels take and :func:`encode_meta`/:func:`decode_meta` consume."""
    return jnp.stack(
        [jnp.asarray(params.alpha, jnp.float32),
         jnp.asarray(params.beta, jnp.float32),
         jnp.asarray(params.base, jnp.float32),
         jnp.float32(params.bits)])


def encode_meta(x: jax.Array, qmeta: jax.Array) -> jax.Array:
    """Encode to uint8 codes from a *packed* ``[4]`` qmeta array.

    Unlike :func:`encode` this treats ``bits`` as data (a traced f32),
    which is what the in-kernel quantize epilogue and the activation
    path need: per-layer metas ride through ``lax.scan`` as arrays.
    Matches :func:`encode` bit-for-bit for the same parameters.

    ``qmeta`` may carry leading broadcast dims (``[..., 4]``): a
    per-head KV meta of shape ``[n_kv, 1, 4]`` broadcasts against
    ``x`` of shape ``[..., n_kv, hd]`` so each head encodes through
    its own (alpha, beta, base) without any reshape of ``x``.
    """
    alpha, beta, base, bits = (qmeta[..., 0], qmeta[..., 1],
                               qmeta[..., 2], qmeta[..., 3])
    e_min = -jnp.exp2(bits - 1.0)
    e_max = jnp.exp2(bits - 1.0) - 1.0
    mag = jnp.abs(x).astype(jnp.float32)
    arg = jnp.maximum((mag - beta) / alpha, 1e-30)
    e = jnp.clip(jnp.round(jnp.log(arg) / jnp.log(base)), e_min, e_max)
    # assemble the byte in int32 and narrow once: Mosaic casts neither
    # f32 nor bool straight to u8
    code = jnp.where(x < 0, 128, 0) + (e - e_min).astype(jnp.int32)
    return code.astype(jnp.uint8)


def decode_exp(codes: jax.Array, alpha, beta, base, bits,
               dtype=jnp.float32) -> jax.Array:
    """The DNA-TEQ closed form ``S * (alpha * base**e + beta)`` with the
    four parameters as arrays that broadcast against ``codes``.  Every
    decode in the repository — kernel tiles, oracles, 256-entry tables —
    is this one function."""
    e_min = -jnp.exp2(bits - 1.0)
    c = codes.astype(jnp.int32)
    sign = 1.0 - 2.0 * (c >> 7).astype(jnp.float32)
    e = (c & 0x7F).astype(jnp.float32) + e_min
    mag = alpha * jnp.exp(e * jnp.log(base)) + beta
    return (sign * mag).astype(dtype)


def decode_meta(codes: jax.Array, qmeta: jax.Array,
                dtype=jnp.float32) -> jax.Array:
    """ALU decode from a packed ``[..., 4]`` qmeta array (no table).

    Like :func:`encode_meta`, leading qmeta dims broadcast against
    ``codes`` (per-head metas decode per-head)."""
    return decode_exp(codes, qmeta[..., 0], qmeta[..., 1], qmeta[..., 2],
                      qmeta[..., 3], dtype)


def _ls_alpha_beta(powers: jax.Array, mag: jax.Array, weights: jax.Array):
    """Closed-form least squares ``mag ~ alpha*powers + beta`` (weighted)."""
    w = weights
    sw = jnp.sum(w) + 1e-12
    mx = jnp.sum(w * powers) / sw
    my = jnp.sum(w * mag) / sw
    cov = jnp.sum(w * (powers - mx) * (mag - my))
    var = jnp.sum(w * (powers - mx) ** 2) + 1e-12
    alpha = cov / var
    beta = my - alpha * mx
    return alpha, beta


def _order_stat(bits: jax.Array, k: int) -> jax.Array:
    """The ``k``-th smallest (0-indexed) of non-negative float32 values
    given as their int32 bit patterns, which order like the values.
    Bisection over the patterns: 31 counting passes over the tensor.  A
    sort would do in one, but the TPU compiler takes over half a minute
    to build a sort of even 64K elements."""
    def body(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2
        enough = jnp.sum(bits <= mid) > k      # >= k+1 values <= mid
        return jnp.where(enough, lo, mid + 1), jnp.where(enough, mid, hi)

    lo, _ = jax.lax.fori_loop(0, 31, body,
                              (jnp.int32(0), jnp.int32(0x7F800000)))
    return jax.lax.bitcast_convert_type(lo, jnp.float32)


def _percentile(mag: jax.Array, q: float) -> jax.Array:
    """``jnp.percentile(mag, q)`` (linear interpolation, the same float32
    index arithmetic) for non-negative float32 ``mag``, without a sort."""
    n = np.float32(mag.size)
    pos = np.float32(q) / np.float32(100) * (n - np.float32(1))
    low, high = np.floor(pos), np.ceil(pos)
    w_high = pos - low
    w_low = np.float32(1) - w_high
    bits = jax.lax.bitcast_convert_type(mag, jnp.int32)
    return (_order_stat(bits, int(low)) * w_low
            + _order_stat(bits, int(high)) * w_high)


def _magnitude_range(mag: jax.Array):
    """(lo, hi): the 1st and 99.5th percentiles of the magnitudes, the
    span the exponent range is first mapped onto.  A tensor holding a
    zero (or a NaN) gets the fixed span (1e-6, 1)."""
    degenerate = jnp.any(~(mag > 0))
    lo, hi = (jnp.where(degenerate, jnp.nan, _percentile(mag, q))
              for q in (1.0, 99.5))
    lo = jnp.nan_to_num(lo, nan=1e-6)
    hi = jnp.nan_to_num(hi, nan=1.0)
    return lo, jnp.maximum(hi, lo * (1.0 + 1e-3))


def _fit_one_base(mag: jax.Array, lo, hi, base: jax.Array, bits: int,
                  iters: int = 6):
    """Alternating (assign, regress) fit of flat magnitudes ``mag`` for
    one candidate base.

    Returns (alpha, beta, mse).
    """
    live = (mag > 0).astype(jnp.float32)  # zeros carry no information
    e_max = 2 ** (bits - 1) - 1

    # --- init: map magnitude quantiles onto the exponent range -----------
    # alpha*b^e_max ~ hi ; alpha*b^e_min ~ lo  (beta starts at 0)
    log_b = jnp.log(base)
    alpha0 = hi / jnp.exp(e_max * log_b)
    alpha0 = jnp.maximum(alpha0, 1e-30)
    beta0 = jnp.zeros(())

    def body(_, carry):
        alpha, beta = carry
        params = ExpQuantParams(alpha, beta, base, bits)
        e = exponent_of(mag, params).astype(jnp.float32)
        powers = jnp.exp(e * log_b)
        alpha, beta = _ls_alpha_beta(powers, mag, live)
        alpha = jnp.maximum(alpha, 1e-30)
        return alpha, beta

    alpha, beta = jax.lax.fori_loop(0, iters, body, (alpha0, beta0))
    params = ExpQuantParams(alpha, beta, base, bits)
    e = exponent_of(mag, params).astype(jnp.float32)
    rec = alpha * jnp.exp(e * log_b) + beta
    mse = jnp.sum(live * (rec - mag) ** 2) / (jnp.sum(live) + 1e-12)
    return alpha, beta, mse


@functools.partial(jax.jit, static_argnames=("bases", "bits", "iters"))
def _fit(x: jax.Array, bases: tuple[float, ...], bits: int, iters: int):
    mag = jnp.abs(x.reshape(-1)).astype(jnp.float32)
    lo, hi = _magnitude_range(mag)
    bases_arr = jnp.asarray(bases, dtype=jnp.float32)
    # one base at a time: a vmap would hold a copy of every per-element
    # temporary for each base (gigabytes for an embedding table)
    alphas, betas, mses = jax.lax.map(
        lambda b: _fit_one_base(mag, lo, hi, b, bits, iters), bases_arr)
    k = jnp.argmin(mses)
    return alphas[k], betas[k], bases_arr[k]


DEFAULT_BASES: tuple[float, ...] = tuple(
    float(b) for b in (2.0 ** (1.0 / k) for k in (1, 2, 3, 4, 6, 8, 12, 16))
)


def fit(
    x: jax.Array,
    bits: int,
    bases: Sequence[float] = DEFAULT_BASES,
    iters: int = 6,
) -> ExpQuantParams:
    """Search (base, alpha, beta) minimising magnitude-domain MSE."""
    alpha, beta, base = _fit(x, tuple(float(b) for b in bases), bits, iters)
    return ExpQuantParams(alpha, beta, base, bits)


def quantize(x: jax.Array, bits: int, **kw):
    """Convenience: fit + encode.  Returns (codes, params)."""
    params = fit(x, bits, **kw)
    return encode(x, params), params


def sqnr_db(x: jax.Array, params: ExpQuantParams) -> jax.Array:
    """Signal-to-quantization-noise ratio of the round trip, in dB."""
    xf = x.astype(jnp.float32)
    err = decode(encode(xf, params), params) - xf
    num = jnp.sum(xf * xf)
    den = jnp.sum(err * err) + 1e-30
    return 10.0 * jnp.log10(num / den + 1e-30)


def search_bitwidth(
    x: jax.Array,
    min_sqnr_db: float = 22.0,
    bit_range: Sequence[int] = (3, 4, 5, 6, 7),
) -> tuple[int, ExpQuantParams]:
    """Per-tensor bitwidth selection (paper Table VI "avg bit" machinery).

    Chooses the smallest exponent width whose round-trip SQNR clears the
    threshold; falls back to the widest otherwise.  ``min_sqnr_db ~ 22`` is
    calibrated so transformer layers land in the paper's 3.4–6.5 avg-bit
    band (<1% end metric loss).
    """
    chosen_bits, chosen_params = bit_range[-1], None
    for b in bit_range:
        params = fit(x, b)
        if float(sqnr_db(x, params)) >= min_sqnr_db:
            return b, params
        chosen_params = params
    return chosen_bits, chosen_params


def pack_qtensor(codes: jax.Array, params: ExpQuantParams, dtype=jnp.float32) -> dict:
    """Pytree leaf-dict used inside model params for quantized weights."""
    return {
        "codes": codes,
        "lut": decode_table(params, dtype),
        "qmeta": pack_qmeta(params),
    }


def is_qtensor(leaf) -> bool:
    """True for either quantized-operand carrier: the weight leaf dict
    or the activation :class:`QTensor`."""
    if isinstance(leaf, QTensor):
        return True
    return isinstance(leaf, dict) and "codes" in leaf and "lut" in leaf


def qt_parts(leaf) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(codes, lut, qmeta) from either carrier form."""
    if isinstance(leaf, QTensor):
        return leaf.codes, leaf.lut, leaf.qmeta
    return leaf["codes"], leaf["lut"], leaf["qmeta"]
