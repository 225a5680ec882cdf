"""DNA-TEQ exponential quantizer: unit + hypothesis property tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hypothesis
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import exponential_quant as eq

COMMON = dict(deadline=None, max_examples=25,
              suppress_health_check=[hypothesis.HealthCheck.too_slow])


def _tensor(seed, n=2048, scale=0.05):
    r = np.random.default_rng(seed)
    return jnp.asarray(r.normal(size=(n,)) * scale, jnp.float32)


class TestRoundTrip:
    @pytest.mark.parametrize("bits", [3, 4, 5, 6, 7])
    def test_sqnr_improves_with_bits(self, bits):
        x = _tensor(0)
        lo = eq.fit(x, max(bits - 1, 3))
        hi = eq.fit(x, bits)
        if bits > 3:
            assert float(eq.sqnr_db(x, hi)) >= float(eq.sqnr_db(x, lo)) - 0.5

    @pytest.mark.parametrize("bits,min_db", [(4, 18.0), (6, 26.0), (7, 26.0)])
    def test_sqnr_floor_gaussian(self, bits, min_db):
        """Gaussian tensors (the DNN weight case) must clear a known
        SQNR floor — the substrate of the paper's <1% accuracy claim."""
        x = _tensor(1)
        params = eq.fit(x, bits)
        assert float(eq.sqnr_db(x, params)) > min_db

    def test_codes_are_uint8_and_in_range(self):
        x = _tensor(2)
        codes, p = eq.quantize(x, 6)
        assert codes.dtype == jnp.uint8
        e = (codes & 0x7F).astype(np.int32)
        assert int(e.max()) <= p.e_max - p.e_min

    def test_encode_decode_encode_idempotent(self):
        x = _tensor(3)
        codes, p = eq.quantize(x, 6)
        rec = eq.decode(codes, p)
        codes2 = eq.encode(rec, p)
        assert np.array_equal(np.asarray(codes), np.asarray(codes2))

    def test_sign_preserved(self):
        x = _tensor(4)
        codes, p = eq.quantize(x, 6)
        rec = eq.decode(codes, p)
        big = np.abs(np.asarray(x)) > float(p.alpha) * 0.5
        assert np.all(np.sign(np.asarray(rec))[big] == np.sign(np.asarray(x))[big])


class TestDecodeTable:
    @pytest.mark.parametrize("bits", [3, 5, 7])
    def test_table_matches_decode(self, bits):
        x = _tensor(5)
        codes, p = eq.quantize(x, bits)
        table = eq.decode_table(p)
        assert table.shape == (256,)
        np.testing.assert_allclose(
            np.asarray(table[codes.astype(jnp.int32)]),
            np.asarray(eq.decode(codes, p)), rtol=0, atol=0)

    def test_table_is_odd_symmetric(self):
        p = eq.fit(_tensor(6), 6)
        t = np.asarray(eq.decode_table(p))
        np.testing.assert_allclose(t[128:], -t[:128], rtol=1e-6)


@settings(**COMMON)
@given(scale=st.floats(1e-4, 10.0), seed=st.integers(0, 2**16),
       bits=st.sampled_from([4, 5, 6, 7]))
def test_property_scale_invariance(scale, seed, bits):
    """SQNR of the fit is (approximately) invariant to tensor scale —
    alpha/beta absorb it."""
    r = np.random.default_rng(seed)
    base = r.normal(size=(512,)).astype(np.float32)
    hypothesis.assume(np.abs(base).max() > 1e-3)
    a = eq.fit(jnp.asarray(base), bits)
    b = eq.fit(jnp.asarray(base * scale), bits)
    da = float(eq.sqnr_db(jnp.asarray(base), a))
    db = float(eq.sqnr_db(jnp.asarray(base * scale), b))
    assert abs(da - db) < 6.0


@settings(**COMMON)
@given(seed=st.integers(0, 2**16), bits=st.sampled_from([4, 6]))
def test_property_decode_bounded_by_fit_range(seed, bits):
    """Decoded magnitudes never exceed alpha*b^e_max + |beta| — the LUT
    cannot invent out-of-range values."""
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(512,)).astype(np.float32))
    codes, p = eq.quantize(x, bits)
    rec = np.abs(np.asarray(eq.decode(codes, p)))
    bound = float(p.alpha) * float(p.base) ** p.e_max + abs(float(p.beta)) + 1e-5
    assert rec.max() <= bound * (1 + 1e-5)


class TestBitwidthSearch:
    def test_search_returns_smallest_sufficient(self):
        x = _tensor(7)
        bits, p = eq.search_bitwidth(x, min_sqnr_db=20.0)
        assert 3 <= bits <= 7
        if bits > 3:
            lower = eq.fit(x, bits - 1)
            assert float(eq.sqnr_db(x, lower)) < 20.0 or bits == 3

    def test_search_band_matches_paper(self):
        """Searched widths for Gaussian weight stand-ins land in the
        paper's Table VI band (3.4 - 6.5 avg bits)."""
        widths = []
        for s in range(8):
            x = _tensor(10 + s, scale=10 ** (-s % 3))
            b, _ = eq.search_bitwidth(x, min_sqnr_db=22.0)
            widths.append(b)
        avg = sum(widths) / len(widths)
        assert 3.0 <= avg <= 7.0
