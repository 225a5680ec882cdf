"""Eq.1 equivalence: the paper-faithful counting formulation equals the
TPU-native dequant-matmul exactly (the identity justifying the fused
kernel, DESIGN.md §2)."""

import itertools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import exponent_dotprod as ed
from repro.core import exponential_quant as eq


def _pair(seed, n, bits_a, bits_w):
    r = np.random.default_rng(seed)
    a = jnp.asarray(r.normal(size=(n,)) * 0.1, jnp.float32)
    w = jnp.asarray(r.normal(size=(n,)) * 0.02, jnp.float32)
    ca, pa = eq.quantize(a, bits_a)
    pw0 = eq.fit(w, bits_w)
    # counting requires a shared base (per-layer pair, as in the paper)
    pw = eq.ExpQuantParams(pw0.alpha, pw0.beta, pa.base, bits_w)
    cw = eq.encode(w, pw)
    return (a, ca, pa), (w, cw, pw)


@pytest.mark.parametrize(
    "bits_a,bits_w", list(itertools.product([3, 5, 7], [4, 6])))
def test_counting_equals_dequant_dot(bits_a, bits_w):
    (a, ca, pa), (w, cw, pw) = _pair(0, 256, bits_a, bits_w)
    d_count = float(ed.counting_dot(ca, pa, cw, pw))
    d_deq = float(jnp.dot(eq.decode(ca, pa), eq.decode(cw, pw)))
    assert abs(d_count - d_deq) < 1e-4 * (abs(d_deq) + 1.0)


def test_counting_matmul_equals_dequant_matmul():
    r = np.random.default_rng(1)
    a = jnp.asarray(r.normal(size=(6, 32)) * 0.1, jnp.float32)
    w = jnp.asarray(r.normal(size=(32, 5)) * 0.05, jnp.float32)
    ca, pa = eq.quantize(a, 5)
    pw0 = eq.fit(w, 6)
    pw = eq.ExpQuantParams(pw0.alpha, pw0.beta, pa.base, 6)
    cw = eq.encode(w, pw)
    m_count = np.asarray(ed.counting_matmul(ca, pa, cw, pw))
    m_deq = np.asarray(ed.dequant_matmul(ca, pa, cw, pw))
    np.testing.assert_allclose(m_count, m_deq, rtol=2e-4, atol=1e-5)


def test_serving_codes_oracle_matches_eq1():
    """The codes-mode attention oracles' q·k contraction (per-head
    decode then an MXU einsum — the page-scan refs in repro.kernels)
    IS the dequant_matmul formulation, and therefore Eq.1-consistent
    with the counting formulation when the two quantizers share a base
    (per layer pair, as in the paper)."""
    from repro.kernels._codes import decode_heads

    r = np.random.default_rng(4)
    g, s, hd = 4, 32, 16
    q = jnp.asarray(r.normal(size=(g, hd)), jnp.float32)
    k = jnp.asarray(r.normal(size=(s, hd)), jnp.float32)
    cq, pq = eq.quantize(q, 7)
    pk0 = eq.fit(k, 7)
    pk = eq.ExpQuantParams(pk0.alpha, pk0.beta, pq.base, 7)
    ck = eq.encode(k, pk)
    # the serving oracle's decode path: q through its 256-entry table,
    # k through the per-head closed-form helper kernels and refs share
    qd = jnp.take(eq.decode_table(pq), cq.astype(jnp.int32), axis=0)
    kd = decode_heads(eq.pack_qmeta(pk)[None], ck[:, None, :])
    logits = jnp.einsum("gh,sh->gs", qd, kd[:, 0, :],
                        preferred_element_type=jnp.float32)
    m_deq = np.asarray(ed.dequant_matmul(cq, pq, ck.T, pk))
    np.testing.assert_allclose(np.asarray(logits), m_deq,
                               rtol=1e-6, atol=1e-6)
    m_count = np.asarray(ed.counting_matmul(cq, pq, ck.T, pk))
    np.testing.assert_allclose(m_count, m_deq, rtol=2e-4, atol=1e-4)


def test_dot_approximates_float(rng):
    (a, ca, pa), (w, cw, pw) = _pair(2, 1024, 7, 7)
    true = float(jnp.dot(a, w))
    approx = float(ed.counting_dot(ca, pa, cw, pw))
    scale = float(jnp.linalg.norm(a) * jnp.linalg.norm(w))
    assert abs(true - approx) < 0.05 * scale


def test_unique_exponent_count_matches_paper_claim():
    """§V: 'in a 6-bit precision layer, only 2^6 unique exponents have to
    be counted' for the A+W term."""
    pa = eq.ExpQuantParams(jnp.float32(1), jnp.float32(0), jnp.float32(1.3), 6)
    pw = eq.ExpQuantParams(jnp.float32(1), jnp.float32(0), jnp.float32(1.3), 6)
    n_sum = (pa.e_max + pw.e_max) - (pa.e_min + pw.e_min) + 1
    assert n_sum == 2 * 2**6 - 1  # sum-range of two 6-bit exponents
    assert ed.unique_exponent_count(pa, pw) == n_sum + 2 * 2**6 + 1


def test_signed_histogram_total_is_term4():
    r = np.random.default_rng(3)
    vals = jnp.asarray(r.integers(0, 16, 512), jnp.int32)
    signs = jnp.asarray(r.choice([-1.0, 1.0], 512), jnp.float32)
    hist = ed.signed_histogram(vals, signs, 0, 15)
    assert abs(float(hist.sum()) - float(signs.sum())) < 1e-5
