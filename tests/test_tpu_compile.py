"""Ahead-of-time compiles of the serving kernels for a described TPU v5e.

Each test lowers one kernel variant at qwen3-1.7b widths (d_model 2048,
d_ff 6144, 8 KV heads x 128, 2 query heads per KV head, vocab 151,936,
pages of 16, 8 slots, prefill chunk 256) and compiles it with the TPU
compiler for one chip of a ``v5e:2x2`` topology, which needs no chip
attached.  That catches what interpret mode cannot: layouts Mosaic
refuses, casts it has no lowering for, VMEM over-use.  Nothing runs.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library, and a test-collection-time
call would make parallel workers collect different tests.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_gqa.decode_gqa import (
    decode_gqa_paged_codes_kernel,
    decode_gqa_paged_kernel,
)
from repro.kernels.flash_prefill.flash_prefill import (
    flash_prefill_paged_codes_kernel,
    flash_prefill_paged_kernel,
)
from repro.kernels.lut_dequant_matmul.lut_dequant_matmul import (
    lut_dequant_matmul_dual_gated_kernel,
    lut_dequant_matmul_dual_kernel,
    lut_dequant_matmul_gated_kernel,
    lut_dequant_matmul_kernel,
)

D, FF, VOCAB = 2048, 6144, 151_936
N_KV, G, HD = 8, 2, 128
SLOTS, BLOCK, CHUNK, MAX_BLK = 8, 16, 256, 64
POOL = SLOTS * MAX_BLK + 1


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(sharding, fn, shapes, **static):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = fn.lower(*args, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


F32, U8 = jnp.float32, jnp.uint8


@pytest.mark.parametrize("m,k,n,bm", [(8, D, 2 * D, 8), (2048, D, D, 128)])
def test_matmul(one_chip, m, k, n, bm):
    _compile(one_chip, lut_dequant_matmul_kernel,
             [((m, k), F32), ((k, n), U8), ((1, 4), F32), ((n,), F32)],
             bm=bm, bk=128, bn=128)


def test_matmul_tied_unembed(one_chip):
    _compile(one_chip, lut_dequant_matmul_kernel,
             [((8, D), F32), ((VOCAB, D), U8), ((1, 4), F32),
              ((VOCAB,), F32)],
             bm=8, bk=128, bn=128, w_transposed=True)


def test_matmul_gated(one_chip):
    _compile(one_chip, lut_dequant_matmul_gated_kernel,
             [((8, D), F32), ((D, FF), U8), ((D, FF), U8), ((2, 4), F32)],
             bm=8, bk=128, bn=128)


@pytest.mark.parametrize("out_quant", [False, True])
@pytest.mark.parametrize("m,bm", [(8, 8), (2048, 128)])
def test_matmul_dual(one_chip, out_quant, m, bm):
    _compile(one_chip, lut_dequant_matmul_dual_kernel,
             [((m, D), U8), ((D, D), U8), ((3, 4), F32), ((D,), F32)],
             bm=bm, bk=128, bn=128, out_quant=out_quant)


@pytest.mark.parametrize("out_quant", [False, True])
def test_matmul_dual_gated(one_chip, out_quant):
    _compile(one_chip, lut_dequant_matmul_dual_gated_kernel,
             [((8, D), U8), ((D, FF), U8), ((D, FF), U8), ((4, 4), F32)],
             bm=8, bk=128, bn=128, out_quant=out_quant)


_PAGES = (POOL, BLOCK, N_KV, HD)
_PAGING = [((SLOTS, MAX_BLK), jnp.int32), ((SLOTS,), jnp.int32)]


@pytest.mark.parametrize("kv_dtype", [jnp.float32, jnp.float8_e4m3fn])
def test_decode_gqa_paged(one_chip, kv_dtype):
    _compile(one_chip, decode_gqa_paged_kernel,
             [((SLOTS, N_KV, G, HD), F32), (_PAGES, kv_dtype),
              (_PAGES, kv_dtype)] + _PAGING)


def test_decode_gqa_paged_codes(one_chip):
    _compile(one_chip, decode_gqa_paged_codes_kernel,
             [((SLOTS, N_KV, G, HD), U8), (_PAGES, U8), (_PAGES, U8),
              ((4,), F32), ((N_KV, 4), F32), ((N_KV, 4), F32), ((4,), F32)]
             + _PAGING)


@pytest.mark.parametrize("kv_dtype", [jnp.float32, jnp.float8_e4m3fn])
def test_flash_prefill_paged(one_chip, kv_dtype):
    _compile(one_chip, flash_prefill_paged_kernel,
             [((SLOTS, CHUNK, N_KV, G, HD), F32), (_PAGES, kv_dtype),
              (_PAGES, kv_dtype)] + _PAGING + [((SLOTS,), jnp.int32)])


def test_flash_prefill_paged_codes(one_chip):
    _compile(one_chip, flash_prefill_paged_codes_kernel,
             [((SLOTS, CHUNK, N_KV, G, HD), U8), (_PAGES, U8), (_PAGES, U8),
              ((4,), F32), ((N_KV, 4), F32), ((N_KV, 4), F32), ((4,), F32)]
             + _PAGING + [((SLOTS,), jnp.int32)])
