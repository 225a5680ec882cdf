"""Per-kernel shape/dtype sweeps vs the pure-jnp ref oracles
(interpret=True on CPU)."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import exponential_quant as eq
from repro.core.lut import build_lut, mul_lut
from repro.kernels.exp_histogram import exp_histogram, exp_histogram_ref
from repro.kernels.lama_bulk_op import (
    lama_bulk_op,
    lama_bulk_op_ref,
    lama_vector_matrix,
)
from repro.kernels.lut_dequant_matmul import (
    lut_dequant_matmul,
    lut_dequant_matmul_ref,
)


class TestLutDequantMatmul:
    @pytest.mark.parametrize(
        "m,k,n,bits",
        [(8, 128, 128, 4), (100, 256, 384, 6), (128, 128, 256, 7),
         (33, 130, 70, 5), (16, 6144, 256, 7)])
    def test_shapes_vs_ref(self, m, k, n, bits):
        r = np.random.default_rng(m * 1000 + n)
        w = jnp.asarray(r.normal(size=(k, n)) * 0.05, jnp.float32)
        codes, qp = eq.quantize(w, bits)
        qmeta = eq.pack_qmeta(qp)
        x = jnp.asarray(r.normal(size=(m, k)), jnp.float32)
        ref = lut_dequant_matmul_ref(x, codes, qmeta)
        out = lut_dequant_matmul(x, codes, qmeta, out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=1e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        r = np.random.default_rng(7)
        w = jnp.asarray(r.normal(size=(128, 128)) * 0.1, jnp.float32)
        codes, qp = eq.quantize(w, 6)
        qmeta = eq.pack_qmeta(qp)
        x = jnp.asarray(r.normal(size=(64, 128)), dtype)
        ref = lut_dequant_matmul_ref(x, codes, qmeta)
        out = lut_dequant_matmul(x, codes, qmeta, out_dtype=jnp.float32)
        rtol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=rtol, atol=1e-3)

    def test_alu_mode_matches_gather(self):
        """The in-kernel closed-form decode gives the values of a lookup
        in the 256-entry table: the kernel equals a matmul against the
        table-gathered weight."""
        r = np.random.default_rng(9)
        w = jnp.asarray(r.normal(size=(256, 128)) * 0.02, jnp.float32)
        codes, qp = eq.quantize(w, 7)
        lut = eq.decode_table(qp)
        np.testing.assert_array_equal(
            np.asarray(lut), np.asarray(eq.decode_meta(
                jnp.arange(256), eq.pack_qmeta(qp))))
        x = jnp.asarray(r.normal(size=(32, 256)), jnp.float32)
        g = x @ lut[codes.astype(jnp.int32)]
        a = lut_dequant_matmul(x, codes, eq.pack_qmeta(qp))
        np.testing.assert_allclose(np.asarray(g), np.asarray(a),
                                   rtol=1e-4, atol=1e-5)

    def test_matches_model_dense_path(self):
        """Kernel == lama_layers.dense on a qtensor (the integration)."""
        from repro.core import lama_layers as ll
        r = np.random.default_rng(11)
        w = jnp.asarray(r.normal(size=(128, 256)) * 0.05, jnp.float32)
        codes, qp = eq.quantize(w, 6)
        leaf = eq.pack_qtensor(codes, qp)
        x = jnp.asarray(r.normal(size=(16, 128)), jnp.float32)
        dense_out = ll.dense(x, leaf, dtype=jnp.float32)
        kern_out = lut_dequant_matmul(x, codes, eq.pack_qmeta(qp),
                                      out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(dense_out),
                                   np.asarray(kern_out), rtol=2e-5, atol=1e-5)


class TestLamaBulkOp:
    @pytest.mark.parametrize("bits,g,m", [(4, 4, 128), (4, 16, 256),
                                          (6, 8, 512), (8, 2, 128)])
    def test_mul_lut_sweep(self, bits, g, m):
        r = np.random.default_rng(g * m)
        table = mul_lut(bits, jnp.int32)
        a = jnp.asarray(r.integers(0, 2**bits, g), jnp.int32)
        b = jnp.asarray(r.integers(0, 2**bits, (g, m)), jnp.int32)
        out = lama_bulk_op(a, b, table)
        assert np.array_equal(np.asarray(out),
                              np.asarray(lama_bulk_op_ref(a, b, table)))

    def test_arbitrary_function_lut(self):
        """'Lama is not limited to multiplication' (§IV): any f(a,b)."""
        r = np.random.default_rng(3)
        table = build_lut(lambda a, b: (a + b) ** 2 % 251, 5, 5, jnp.int32)
        a = jnp.asarray(r.integers(0, 32, 6), jnp.int32)
        b = jnp.asarray(r.integers(0, 32, (6, 128)), jnp.int32)
        out = lama_bulk_op(a, b, table)
        assert np.array_equal(np.asarray(out),
                              np.asarray(lama_bulk_op_ref(a, b, table)))

    @settings(deadline=None, max_examples=20)
    @given(seed=st.integers(0, 2**16), bits=st.sampled_from([4, 5, 8]))
    def test_property_vector_matrix_exact(self, seed, bits):
        r = np.random.default_rng(seed)
        k, n = int(r.integers(2, 12)), 128
        v = jnp.asarray(r.integers(0, 2**bits, k), jnp.int32)
        m = jnp.asarray(r.integers(0, 2**bits, (k, n)), jnp.int32)
        out = lama_vector_matrix(v, m, bits)
        assert np.array_equal(np.asarray(out), np.asarray(v) @ np.asarray(m))


class TestExpHistogram:
    @pytest.mark.parametrize("g,m,bins", [(8, 512, 64), (16, 1024, 128),
                                          (1, 512, 16), (24, 2048, 256)])
    def test_sweep_vs_ref(self, g, m, bins):
        r = np.random.default_rng(g + m + bins)
        vals = jnp.asarray(r.integers(0, bins, (g, m)), jnp.int32)
        signs = jnp.asarray(r.choice([-1.0, 1.0], (g, m)), jnp.float32)
        out = exp_histogram(vals, signs, bins)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(exp_histogram_ref(vals, signs, bins)))

    @settings(deadline=None, max_examples=20)
    @given(seed=st.integers(0, 2**16))
    def test_property_total_count_conserved(self, seed):
        """Sum over bins == signed element count (term-4 of Eq.1)."""
        r = np.random.default_rng(seed)
        vals = jnp.asarray(r.integers(0, 32, (8, 512)), jnp.int32)
        signs = jnp.asarray(r.choice([-1.0, 1.0], (8, 512)), jnp.float32)
        h = exp_histogram(vals, signs, 32)
        np.testing.assert_allclose(np.asarray(h.sum(axis=1)),
                                   np.asarray(signs.sum(axis=1)), atol=1e-4)


class TestDecodeGQA:
    """Flash-decoding GQA kernel with in-kernel KV dequantization."""

    @pytest.mark.parametrize(
        "b,s,nkv,g,hd", [(4, 1024, 8, 5, 128), (2, 2048, 1, 8, 64),
                         (3, 512, 4, 1, 32), (1, 768, 2, 2, 16)])
    def test_shapes_vs_ref(self, b, s, nkv, g, hd):
        from repro.kernels.decode_gqa import decode_gqa, decode_gqa_ref
        r = np.random.default_rng(b * s)
        q = jnp.asarray(r.normal(size=(b, nkv, g, hd)), jnp.float32)
        k = jnp.asarray(r.normal(size=(b, s, nkv, hd)) * 0.3, jnp.bfloat16)
        v = jnp.asarray(r.normal(size=(b, s, nkv, hd)) * 0.3, jnp.bfloat16)
        lens = jnp.asarray(r.integers(1, s, b), jnp.int32)
        out = decode_gqa(q, k, v, lens)
        ref = decode_gqa_ref(q, k, v, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("dtype", ["bfloat16", "float8_e4m3fn"])
    def test_quantized_cache_dtypes(self, dtype):
        """The paper's point on TPU: narrow KV bytes cross HBM, dequant
        happens in-kernel after the DMA (EXPERIMENTS.md §Perf A2/A5)."""
        from repro.kernels.decode_gqa import decode_gqa, decode_gqa_ref
        dt = jnp.dtype(dtype)
        r = np.random.default_rng(0)
        q = jnp.asarray(r.normal(size=(2, 4, 2, 64)), jnp.float32)
        k = jnp.asarray(r.normal(size=(2, 512, 4, 64)) * 0.3,
                        jnp.float32).astype(dt)
        v = jnp.asarray(r.normal(size=(2, 512, 4, 64)) * 0.3,
                        jnp.float32).astype(dt)
        lens = jnp.asarray([300, 512], jnp.int32)
        out = decode_gqa(q, k, v, lens)
        ref = decode_gqa_ref(q, k, v, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_ragged_lengths_mask_strictly(self):
        """Entries beyond lengths[b] must not affect the output."""
        from repro.kernels.decode_gqa import decode_gqa
        r = np.random.default_rng(1)
        q = jnp.asarray(r.normal(size=(1, 2, 2, 32)), jnp.float32)
        k = jnp.asarray(r.normal(size=(1, 256, 2, 32)), jnp.float32)
        v = jnp.asarray(r.normal(size=(1, 256, 2, 32)), jnp.float32)
        lens = jnp.asarray([100], jnp.int32)
        out1 = decode_gqa(q, k, v, lens)
        k2 = k.at[:, 100:].set(999.0)
        v2 = v.at[:, 100:].set(-999.0)
        out2 = decode_gqa(q, k2, v2, lens)
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
