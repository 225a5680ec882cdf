"""Ahead-of-time compiles of the serving kernels for a described TPU v5e.

Each test lowers one kernel variant at qwen3-1.7b widths (d_model 2048,
d_ff 6144, 8 KV heads x 128, 2 query heads per KV head, vocab 151,936,
pages of 16, 8 slots, prefill chunk 256), or at olmo-1b's (d_ff 8192) or
deepseek-v2-lite's for its head, latent attention and grouped experts, and compiles it with the TPU
compiler for one chip of a ``v5e:2x2`` topology, which needs no chip
attached.  That catches what interpret mode cannot: layouts Mosaic
refuses, casts it has no lowering for, VMEM over-use.  Nothing runs.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library, and a test-collection-time
call would make parallel workers collect different tests.
"""

import math
import os
import re
from unittest import mock

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
from repro.kernels.moe_expert.moe_expert import (
    moe_expert_down_kernel,
    moe_expert_gated_kernel,
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


# blocks: "128" is 128 x 128 (bm up to 128); "rule" is what the ops
# wrapper picks by shape with the autotuner off
OLMO_FF, HEAD_DS = 8192, 102_400


def _blocks(which, kind, m, k, n):
    from repro.kernels.lut_dequant_matmul import ops as kops

    if which == "128":
        return dict(bm=min(m, 128), bk=128, bn=128)
    bm, bk, bn = kops._default_tiling(kops.bucket_m(m), k, n, kind)
    return dict(bm=bm, bk=bk, bn=bn)


@pytest.mark.parametrize("m,k,n,which", [
    (8, D, 2 * D, "128"), (2048, D, D, "128"),
    (16, D, D, "rule"), (16, D, N_KV * HD, "rule"), (16, FF, D, "rule"),
    (2048, D, D, "rule"), (2048, FF, D, "rule"),
    (16, D, HEAD_DS, "rule")])          # deepseek-v2-lite's untied head
def test_matmul(one_chip, m, k, n, which):
    _compile(one_chip, lut_dequant_matmul_kernel,
             [((m, k), F32), ((k, n), U8), ((1, 4), F32), ((n,), F32)],
             **_blocks(which, "mm", m, k, n))


@pytest.mark.parametrize("m,which", [(8, "128"), (16, "rule")])
def test_matmul_tied_unembed(one_chip, m, which):
    _compile(one_chip, lut_dequant_matmul_kernel,
             [((m, D), F32), ((VOCAB, D), U8), ((1, 4), F32),
              ((VOCAB,), F32)],
             **_blocks(which, "mm", m, D, VOCAB), w_transposed=True)


@pytest.mark.parametrize("m,which", [(8, "128"), (16, "rule"),
                                     (2048, "rule")])
def test_matmul_gated(one_chip, m, which):
    _compile(one_chip, lut_dequant_matmul_gated_kernel,
             [((m, D), F32), ((D, FF), U8), ((D, FF), U8), ((2, 4), F32)],
             **_blocks(which, "gated", m, D, FF))


@pytest.mark.parametrize("out_quant", [False, True])
@pytest.mark.parametrize("m,k,which", [
    (8, D, "128"), (2048, D, "128"),
    (16, D, "rule"), (4096, D, "rule"),        # olmo-1b attention
    (16, OLMO_FF, "rule"), (4096, OLMO_FF, "rule")])   # and down
def test_matmul_dual(one_chip, out_quant, m, k, which):
    _compile(one_chip, lut_dequant_matmul_dual_kernel,
             [((m, k), U8), ((k, D), U8), ((3, 4), F32), ((D,), F32)],
             **_blocks(which, "dual", m, k, D), out_quant=out_quant)


@pytest.mark.parametrize("out_quant", [False, True])
@pytest.mark.parametrize("m,n,which", [
    (8, FF, "128"), (16, OLMO_FF, "rule"), (4096, OLMO_FF, "rule")])
def test_matmul_dual_gated(one_chip, out_quant, m, n, which):
    _compile(one_chip, lut_dequant_matmul_dual_gated_kernel,
             [((m, D), U8), ((D, n), U8), ((D, n), U8), ((4, 4), F32)],
             **_blocks(which, "dual_gated", m, D, n), out_quant=out_quant)


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


# deepseek-v2-lite: 16 held experts of 1408, 16 query heads over one
# latent "KV head" of 512 + 64 numbers padded to 640 lanes
EXPERTS, EXPERT_FF, LATENT, HEADS = 16, 1408, 640, 16
BF16 = jnp.bfloat16


@pytest.mark.parametrize("tm,tiles", [(16, 22), (128, 208)])
def test_moe_expert_grouped(one_chip, tm, tiles):
    """Decode-sized (16 rows, top-6) and prefill-sized (4,096 rows)
    dispatches' row buffers."""
    rows = tm * tiles
    sched = [((tiles,), jnp.int32), ((), jnp.int32)]
    _compile(one_chip, moe_expert_gated_kernel,
             [((rows, D), BF16), ((EXPERTS, D, EXPERT_FF), U8),
              ((EXPERTS, D, EXPERT_FF), U8), ((2, 4), F32)] + sched,
             tm=tm, bn=128, out_dtype=BF16)
    _compile(one_chip, moe_expert_down_kernel,
             [((rows, EXPERT_FF), BF16), ((EXPERTS, EXPERT_FF, D), U8),
              ((4,), F32)] + sched, tm=tm, bn=256)


@pytest.mark.parametrize("s", [1, CHUNK])
def test_flash_prefill_latent_pages(one_chip, s):
    """One KV head as ``[N, bs, 640]`` pages: the kernel takes the pool
    in place (at 576 lanes, or as ``[N, bs, 1, hd]``, XLA copies the
    whole pool into the kernel's layout first)."""
    pages = ((POOL, BLOCK, LATENT), jnp.float8_e4m3fn)
    compiled = _compile(
        one_chip, flash_prefill_paged_kernel,
        [((SLOTS, s, 1, HEADS, LATENT), BF16), pages, pages] + _PAGING
        + [((SLOTS,), jnp.int32)])
    assert compiled.memory_analysis().temp_size_in_bytes < \
        POOL * BLOCK * LATENT


# ------------------------------------------------ whole serving steps --
#
# The donated decode and prefill steps as the Engine jits them, at the
# same widths with 2 layers, 8 slots and a 4,097-page pool.  The page
# pools ride the layer scan as carry: the only ops that yield an array
# of one layer's pool or more are parameters, bitcasts, tuple reads and
# the in-place scatter of the new K/V.  A per-layer slice of the pool,
# a rebuilt stack of it or a whole-pool copy fails here.

LAYERS, PAGES = 2, 4097
LAYER_POOL = PAGES * BLOCK * N_KV * HD      # elements (= bytes) of one layer
_INST = re.compile(r"^\s*(?:ROOT\s+)?%[\w.\-]+\s*=\s*\w+\[([\d,]+)\]\S*\s+"
                   r"([\w\-]+)\(")
_VIEWS = ("parameter", "bitcast", "get-tuple-element", "scatter")


def _pool_sized_ops(hlo: str, layer_pool: int = LAYER_POOL,
                    minor: int = HD) -> list:
    """Instructions of ``hlo`` that yield an array of at least one
    layer's pool (minor dim the head size) and do more than view it or
    scatter into it in place (a fusion that aliases its operand)."""
    bad = []
    for line in hlo.splitlines():
        m = _INST.match(line)
        if not m:
            continue
        dims = [int(d) for d in m.group(1).split(",")]
        op = m.group(2)
        if (math.prod(dims) < layer_pool or dims[-1] != minor or op in _VIEWS
                or (op == "fusion" and '"aliasing_operands"' in line)):
            continue
        bad.append(line.strip()[:160])
    return bad


def _step_args(sharding, kv: str):
    """Abstract params (7-bit weight codes; with ``kv="codes"`` the
    activation tables that codes pages need) and the page view."""
    from repro.configs import get_config
    from repro.core import lama_layers as ll
    from repro.models import api as mapi
    from repro.models.layers import ACT_SITES
    from repro.runtime.calibration import PER_HEAD_SITES
    from repro.runtime.paged_cache import PagedView

    cfg = get_config("qwen3-1.7b").replace(num_layers=LAYERS)
    assert (cfg.d_model, cfg.d_ff, cfg.num_kv_heads) == (D, FF, N_KV)
    api = mapi.get_model(cfg)
    params, _ = ll.abstract_quantize(api.abstract_params(),
                                     api.logical_axes(), bits=7)
    pool_dtype = jnp.float8_e4m3fn
    if kv == "codes":
        pool_dtype = U8
        params["blocks"]["act_q"] = {}
        for site in ACT_SITES:
            lead = (LAYERS, N_KV) if site in PER_HEAD_SITES else (LAYERS,)
            params["blocks"]["act_q"][site] = {
                "lut": jax.ShapeDtypeStruct(lead + (256,), F32),
                "qmeta": jax.ShapeDtypeStruct(lead + (4,), F32)}
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        params)
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)
    pool = spec((LAYERS, PAGES, BLOCK, N_KV, HD), pool_dtype)
    view = PagedView(pool, pool, spec((SLOTS, MAX_BLK), jnp.int32),
                     spec((SLOTS,), jnp.int32))
    return api, cfg, params, view, spec


@pytest.mark.parametrize("kv", ["f8", "codes"])
@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_step_moves_no_pool(one_chip, step, kv):
    from repro.core import lama_layers as ll
    from repro.runtime import engine

    api, cfg, params, view, spec = _step_args(one_chip, kv)
    # the kernels take their TPU branch; the jit wrappers donate the view
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            ll.policy(autotune=False):
        if step == "decode":
            fn = engine._jit_decode.__wrapped__(api.decode_step_paged)
            lowered = fn.lower(params, view, spec((SLOTS, 1), jnp.int32),
                               spec((SLOTS,), jnp.bool_), cfg)
        else:
            fn = engine._jit_prefill.__wrapped__(api.prefill_into_cache)
            lowered = fn.lower(params, spec((SLOTS, CHUNK), jnp.int32),
                               view, spec((SLOTS,), jnp.int32), cfg)
        compiled = lowered.compile()
    assert _pool_sized_ops(compiled.as_text()) == []
    assert compiled.memory_analysis().temp_size_in_bytes < LAYER_POOL


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_latent_step_moves_no_pool(one_chip, step):
    """The deepseek-v2-lite steps (a dense layer and an MoE layer, 7-bit
    weight codes, a float32 router, a float8 latent pool of 4,097
    pages) keep their latent pool in place, as the K/V pools above (the
    prefill step's temp is the expert layer's row buffers)."""
    from repro.configs import get_config
    from repro.core import lama_layers as ll
    from repro.models import api as mapi
    from repro.runtime import engine
    from repro.runtime.paged_cache import PagedView

    cfg = get_config("deepseek-v2-lite").replace(num_layers=LAYERS)
    api = mapi.get_model(cfg)
    params, _ = ll.abstract_quantize(api.abstract_params(),
                                     api.logical_axes(), bits=7)
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype), params)
    layer_pool = PAGES * BLOCK * cfg.latent_dim
    view = PagedView(spec((LAYERS, PAGES, BLOCK, cfg.latent_dim),
                          jnp.float8_e4m3fn), None,
                     spec((SLOTS, MAX_BLK), jnp.int32),
                     spec((SLOTS,), jnp.int32))
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            ll.policy(autotune=False):
        if step == "decode":
            fn = engine._jit_decode.__wrapped__(api.decode_step_paged)
            lowered = fn.lower(params, view, spec((SLOTS, 1), jnp.int32),
                               spec((SLOTS,), jnp.bool_), cfg)
        else:
            fn = engine._jit_prefill.__wrapped__(api.prefill_into_cache)
            lowered = fn.lower(params, spec((SLOTS, CHUNK), jnp.int32),
                               view, spec((SLOTS,), jnp.int32), cfg)
        compiled = lowered.compile()
    hlo = compiled.as_text()
    assert "moe_expert_gated_kernel" in hlo and "moe_expert_down_kernel" in hlo
    assert _pool_sized_ops(hlo, layer_pool, cfg.latent_dim) == []
