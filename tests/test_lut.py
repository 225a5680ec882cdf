"""LUT machinery + coalesced-batch planning (core.lut)."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import lut as L


def test_mul_lut_exact():
    t = np.asarray(L.mul_lut(4))
    for a in (0, 3, 15):
        for b in (0, 7, 15):
            assert t[a, b] == a * b


def test_coalesced_apply_matches_elementwise():
    r = np.random.default_rng(0)
    table = L.mul_lut(5, jnp.int32)
    a = jnp.asarray(5)
    b = jnp.asarray(r.integers(0, 32, 64), jnp.int32)
    out = L.coalesced_apply(table, a, b)
    np.testing.assert_array_equal(np.asarray(out), 5 * np.asarray(b))


@settings(deadline=None, max_examples=25)
@given(seed=st.integers(0, 2**16), bits=st.sampled_from([4, 5, 6, 7, 8]))
def test_property_vector_matrix_exact(seed, bits):
    r = np.random.default_rng(seed)
    k, n = int(r.integers(1, 10)), int(r.integers(1, 64))
    v = jnp.asarray(r.integers(0, 2**bits, k), jnp.int32)
    m = jnp.asarray(r.integers(0, 2**bits, (k, n)), jnp.int32)
    out = L.vector_matrix_via_lut(v, m, bits)
    assert np.array_equal(np.asarray(out), np.asarray(v) @ np.asarray(m))


def test_plan_matches_parallelism_table():
    # one batch of 256 ops: retrievals = ceil(256/p)
    for bits, p in ((4, 16), (5, 16), (6, 8), (7, 4), (8, 2)):
        plan = L.plan_vector_matrix(1, 256, bits)
        assert plan.retrievals_per_batch == -(-256 // p)


def test_icas_and_masking_tables():
    assert [L.icas_per_retrieval(b) for b in (4, 5, 6, 7, 8)] == [1, 2, 2, 2, 2]
    assert [L.masking_msbs(b) for b in (4, 5, 6, 7, 8)] == [0, 0, 1, 2, 3]


def test_rejects_unsupported_precision():
    with pytest.raises(ValueError):
        L.lama_parallelism(9)
