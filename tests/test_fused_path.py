"""Fused-path coverage: every einsum spec the model zoo feeds through
``dense_general`` must hit the LUT-dequant kernel with parity vs the
materialize reference; epilogue fusion must be exact; a quantized
transformer forward must execute with ZERO full-weight materializations;
the ops wrapper must bucket M and autotune from its persistent cache."""

import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import RunShape
from repro.core import exponential_quant as eq
from repro.core import lama_layers as ll
from repro.kernels.lut_dequant_matmul import ops as kops
from repro.models import api as mapi

SMOKE = RunShape("smoke", 16, 2, "train")


def _qt(r, shape, bits=6):
    """(qtensor leaf, materialized f32 weight) for a random tensor."""
    w = jnp.asarray(r.normal(size=shape) * 0.05, jnp.float32)
    codes, qp = eq.quantize(w.reshape(shape[0], -1), bits)
    leaf = eq.pack_qtensor(codes.reshape(shape), qp)
    return leaf, ll.materialize(leaf, jnp.float32)


# All (spec, x_shape, w_shape) pairs the zoo uses:
#   attention projections, MoE grouped einsums (routed + dense mixture),
#   tied unembedding, plain dense.
ZOO_SPECS = [
    ("bsd,dnh->bsnh", (2, 5, 64), (64, 4, 16)),     # wq/wk/wv
    ("bsnh,nhd->bsd", (2, 5, 4, 16), (4, 16, 64)),  # wo
    ("ecd,edf->ecf", (3, 7, 32), (3, 32, 48)),      # MoE routed up/gate
    ("ecf,efd->ecd", (3, 7, 48), (3, 48, 32)),      # MoE routed down
    ("td,edf->etf", (9, 32), (3, 32, 48)),          # MoE dense mixture
    ("bsd,vd->bsv", (2, 5, 32), (40, 32)),          # tied unembedding
    ("bsd,df->bsf", (2, 5, 32), (32, 48)),          # plain dense
]


class TestDenseGeneralParity:
    @pytest.mark.parametrize("spec,xs,wsh", ZOO_SPECS,
                             ids=[s[0] for s in ZOO_SPECS])
    def test_spec_parity_vs_materialize(self, spec, xs, wsh):
        r = np.random.default_rng(hash(spec) % 2**31)
        x = jnp.asarray(r.normal(size=xs), jnp.float32)
        w, wf = _qt(r, wsh)
        ref = jnp.einsum(spec, x, wf, preferred_element_type=jnp.float32)
        with ll.policy(mode="fused"):
            out = ll.dense_general(x, w, spec, dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_unsupported_spec_falls_back(self):
        """Repeated labels can't canonicalize -> materialize fallback."""
        r = np.random.default_rng(3)
        x = jnp.asarray(r.normal(size=(4, 4)), jnp.float32)
        w, wf = _qt(r, (4, 4))
        assert ll._einsum_plan("ab,bb->ab") is None
        out = ll.dense_general(x, w, "ab,bb->ab", dtype=jnp.float32)
        ref = jnp.einsum("ab,bb->ab", x, wf,
                         preferred_element_type=jnp.float32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)


class TestEpilogueFusion:
    def test_dense_epilogues_match_unfused(self):
        r = np.random.default_rng(5)
        x = jnp.asarray(r.normal(size=(33, 130)), jnp.float32)
        w, wf = _qt(r, (130, 70))
        bias = jnp.asarray(r.normal(size=(70,)), jnp.float32)
        for ep in ("gelu", "silu", "relu"):
            fused = ll.dense(x, w, dtype=jnp.float32, epilogue=ep, bias=bias)
            with ll.policy(fuse_epilogues=False):
                unfused = ll.dense(x, w, dtype=jnp.float32, epilogue=ep,
                                   bias=bias)
            np.testing.assert_allclose(np.asarray(fused),
                                       np.asarray(unfused),
                                       rtol=2e-5, atol=2e-5)

    def test_gated_mlp_single_kernel_matches_three_ops(self):
        r = np.random.default_rng(6)
        x = jnp.asarray(r.normal(size=(17, 64)), jnp.float32)
        wg, wgf = _qt(r, (64, 96))
        wu, wuf = _qt(r, (64, 96))
        for act in ("silu", "gelu"):
            out = ll.gated_mlp(x, wg, wu, act, dtype=jnp.float32)
            ref = (jax.nn.silu(x @ wgf) if act == "silu"
                   else jax.nn.gelu(x @ wgf)) * (x @ wuf)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5)

    def test_gated_mlp_mixed_leaves_falls_back(self):
        """One fp + one quantized weight can't share the dual kernel."""
        r = np.random.default_rng(7)
        x = jnp.asarray(r.normal(size=(5, 64)), jnp.float32)
        wg, wgf = _qt(r, (64, 96))
        wu_fp = jnp.asarray(r.normal(size=(64, 96)) * 0.05, jnp.float32)
        out = ll.gated_mlp(x, wg, wu_fp, "silu", dtype=jnp.float32)
        ref = jax.nn.silu(x @ wgf) * (x @ wu_fp)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("arch", ["olmo-1b", "qwen3-1.7b",
                                  "llama4-scout-17b-a16e"])
def test_zero_materialization_forward_and_decode(arch):
    """The acceptance property: a quantized transformer prefill + one
    decode step dispatches EVERY qtensor matmul to the fused kernel —
    materialize() must never see a qtensor leaf."""
    cfg = get_config(arch, tiny=True).replace(compute_dtype="float32")
    api = mapi.get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    qparams, report = ll.quantize_tree(params, 7, axes=api.logical_axes())
    assert report, "nothing was quantized"
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 12)),
        jnp.int32)

    orig = ll.materialize

    def guarded(w, dtype=jnp.bfloat16):
        if eq.is_qtensor(w):
            raise AssertionError(
                "materialize() decoded a qtensor on the fused path")
        return orig(w, dtype)

    with mock.patch.object(ll, "materialize", guarded), \
            ll.policy(mode="fused"):
        logits, cache = api.prefill(qparams, toks, cfg, 32,
                                    cache_dtype=jnp.float32)
        lg, cache = api.decode_step(qparams, cache, toks[:, :1], cfg)
    assert bool(jnp.all(jnp.isfinite(lg)))


class TestTransposedCodes:
    def test_wrapper_parity(self):
        """codes stored [N, K] contract correctly without an HBM-side
        transpose (tied-unembedding layout)."""
        r = np.random.default_rng(11)
        wt, wtf = _qt(r, (70, 130))          # [N, K] storage
        x = jnp.asarray(r.normal(size=(33, 130)), jnp.float32)
        out = kops.lut_dequant_matmul(
            x, wt["codes"], wt["qmeta"], transpose_codes=True,
            out_dtype=jnp.float32)
        ref = x @ wtf.T
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_tied_unembed_spec_uses_kernel_transpose(self):
        """'bsd,vd->bsv' must dispatch with transpose_codes=True (the
        full code table never transposes in HBM)."""
        r = np.random.default_rng(12)
        w, wf = _qt(r, (40, 32))
        x = jnp.asarray(r.normal(size=(2, 5, 32)), jnp.float32)
        seen = []
        orig = kops.lut_dequant_matmul

        def spy(*a, **k):
            seen.append(k.get("transpose_codes", False))
            return orig(*a, **k)

        with mock.patch.object(kops, "lut_dequant_matmul", spy):
            out = ll.dense_general(x, w, "bsd,vd->bsv", dtype=jnp.float32)
        assert seen == [True]
        ref = jnp.einsum("bsd,vd->bsv", x, wf,
                         preferred_element_type=jnp.float32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestMBucketing:
    def test_ladder(self):
        assert [kops.bucket_m(m) for m in (1, 8, 9, 33, 100, 129, 512,
                                           513, 1500)] == \
            [8, 8, 16, 64, 128, 256, 512, 1024, 1536]

    def test_same_bucket_same_compiled_shape(self):
        """m=33 and m=60 both pad to the 64 bucket: the kernel sees ONE
        shape, so serving compiles once per bucket, not per batch."""
        r = np.random.default_rng(8)
        w, wf = _qt(r, (130, 70))
        shapes = set()
        orig = kops.lut_dequant_matmul_kernel

        def spy(x, *a, **k):
            shapes.add(x.shape)
            return orig(x, *a, **k)

        with mock.patch.object(kops, "lut_dequant_matmul_kernel", spy):
            for m in (33, 60, 64):
                x = jnp.asarray(r.normal(size=(m, 130)), jnp.float32)
                out = kops.lut_dequant_matmul(x, w["codes"], w["qmeta"])
                assert out.shape == (m, 70)
        assert shapes == {(64, 256)}, shapes


class TestAutotuner:
    def test_persistent_cache_roundtrip(self, tmp_path):
        path = str(tmp_path / "tune.json")
        tuner = kops.Autotuner(path)
        calls = []

        def bench(tile):
            calls.append(tile)
            return {(32, 128, 128): 2.0, (64, 128, 128): 1.0}.get(
                tile, 5.0)

        cands = [(32, 128, 128), (64, 128, 128), (128, 128, 128)]
        tile = tuner.get("cpu|mm|64|128|128|x", cands, bench)
        assert tile == (64, 128, 128)
        assert len(calls) == 3
        assert os.path.exists(path)

        # a fresh tuner instance reads the persisted choice, no timing
        tuner2 = kops.Autotuner(path)
        calls.clear()
        tile2 = tuner2.get("cpu|mm|64|128|128|x", cands, bench)
        assert tile2 == (64, 128, 128)
        assert not calls

    def test_candidates_divide_padded_dims(self):
        for bm, bk, bn in kops._candidate_tilings(256, 512, 384):
            assert 256 % bm == 0 and 512 % bk == 0 and 384 % bn == 0

    def test_disabled_on_cpu_by_default(self):
        assert not kops._autotune_enabled(None, interpret=True)
        assert kops._autotune_enabled(True, interpret=True)

    def test_tunes_with_synthetic_operands_under_jit(self, tmp_path):
        """Inside jit the real operands are tracers — timing them would
        measure tracing.  The tuner benches synthetic concrete operands
        of the padded shapes instead, so autotune fires (once, at trace
        time) even though every production call site is jitted."""
        import json

        r = np.random.default_rng(9)
        w, wf = _qt(r, (130, 70))
        x = jnp.asarray(r.normal(size=(16, 130)), jnp.float32)
        path = str(tmp_path / "tune.json")
        with mock.patch.object(kops, "_TUNER", kops.Autotuner(path)):
            out = jax.jit(lambda a: kops.lut_dequant_matmul(
                a, w["codes"], w["qmeta"], autotune=True,
                out_dtype=jnp.float32))(x)
        assert os.path.exists(path), "tuner did not persist under jit"
        (entry,) = json.load(open(path))["entries"].values()
        assert len(entry["tile"]) == 3 and entry["us"] > 0
        np.testing.assert_allclose(np.asarray(out), np.asarray(x @ wf),
                                   rtol=2e-5, atol=2e-5)

    def test_all_benches_failing_does_not_poison_cache(self, tmp_path):
        path = str(tmp_path / "tune.json")
        tuner = kops.Autotuner(path)

        def bench(tile):
            raise RuntimeError("no fit")

        cands = [(32, 128, 128), (64, 128, 128)]
        assert tuner.get("k", cands, bench) == (32, 128, 128)
        assert not os.path.exists(path)
        # every failed candidate is counted and kept, not swallowed
        assert (tuner.tried, tuner.failed) == (2, 2)
        assert [f[1] for f in tuner.failures] == cands
        assert "RuntimeError: no fit" in tuner.failures[0][2]
        # a later working bench still tunes (nothing was cached)
        assert tuner.get("k", cands, lambda t: 1.0) == (32, 128, 128)
        assert os.path.exists(path)


_KERNEL_KINDS = {"lut_dequant_matmul_kernel": "mm",
                 "lut_dequant_matmul_gated_kernel": "gated",
                 "lut_dequant_matmul_dual_kernel": "dual",
                 "lut_dequant_matmul_dual_gated_kernel": "dual_gated"}
_PUBLIC_OPS = ("lut_dequant_matmul", "lut_dequant_matmul_gated",
               "lut_dequant_matmul_dual", "lut_dequant_matmul_dual_gated")


def _served_matmuls(arch: str, act_codes: bool) -> list:
    """Every weight-code matmul that a decode step and a prefill step
    (16 slots, chunks of 256) of ``arch`` at its published widths call:
    (kind, codes as the model holds them, codes as the kernel gets
    them, x rows the kernel gets, (bm, bk, bn)).  Traced, never run."""
    from repro.models.layers import ACT_SITES
    from repro.runtime.calibration import PER_HEAD_SITES
    from repro.runtime.paged_cache import PagedView

    cfg = get_config(arch).replace(num_layers=2)
    api = mapi.get_model(cfg)
    params, _ = ll.abstract_quantize(api.abstract_params(),
                                     api.logical_axes(), bits=7)
    S = jax.ShapeDtypeStruct
    if act_codes:
        lead = {s: (2, cfg.num_kv_heads) if s in PER_HEAD_SITES else (2,)
                for s in ACT_SITES}
        params["blocks"]["act_q"] = {
            s: {"lut": S(lead[s] + (256,), jnp.float32),
                "qmeta": S(lead[s] + (4,), jnp.float32)} for s in ACT_SITES}
    slots, chunk, pages = 16, 256, (2, 33, 16)
    paging = (S((slots, 128), jnp.int32), S((slots,), jnp.int32))
    if cfg.latent_dim:
        view = PagedView(S(pages + (cfg.latent_dim,), jnp.float8_e4m3fn),
                         None, *paging)
    else:
        pool = S(pages + (cfg.num_kv_heads, cfg.resolved_head_dim),
                 jnp.uint8 if act_codes else jnp.float8_e4m3fn)
        view = PagedView(pool, pool, *paging)

    raw, seen = [], []

    def public(name):
        orig = getattr(kops, name)

        def spy(x, codes, *a, **k):
            raw.append(codes.shape)
            return orig(x, codes, *a, **k)
        return spy

    def kernel(name):
        orig = getattr(kops, name)

        def spy(x, codes, *a, **k):
            seen.append((_KERNEL_KINDS[name], raw[len(seen)], codes.shape,
                         x.shape[0], (k["bm"], k["bk"], k["bn"]),
                         k.get("w_transposed", False)))
            return orig(x, codes, *a, **k)
        return spy

    with mock.patch.multiple(
            kops, **{n: public(n) for n in _PUBLIC_OPS},
            **{n: kernel(n) for n in _KERNEL_KINDS}):
        jax.eval_shape(
            lambda p, v, t, a: api.decode_step_paged(p, v, t, a, cfg),
            params, view, S((slots, 1), jnp.int32), S((slots,), jnp.bool_))
        jax.eval_shape(
            lambda p, t, v, s: api.prefill_into_cache(p, t, v, cfg, s),
            params, S((slots, chunk), jnp.int32), view,
            S((slots,), jnp.int32))
    assert len(raw) == len(seen) and seen
    return seen


class TestDefaultTiling:
    """The tiles the wrapper picks with the autotuner off, at every
    weight-code matmul the benchmark's three models serve."""

    @pytest.mark.parametrize("arch,act_codes", [
        ("qwen3-1.7b", False), ("olmo-1b", True),
        ("deepseek-v2-lite", False)])
    def test_served_shapes(self, arch, act_codes):
        from repro.kernels.lut_dequant_matmul.lut_dequant_matmul import (
            SCOPED_VMEM, _compiler_params, vmem_bytes)

        kinds = set()
        for kind, raw, padded, m_pad, tile, transposed in _served_matmuls(
                arch, act_codes):
            kinds.add(kind)
            # no code table is padded past the next multiple of 128
            assert padded == tuple(-(-d // 128) * 128 for d in raw), (
                raw, padded)
            n_pad, k_pad = padded if transposed else padded[::-1]
            bm, bk, bn = tile
            assert m_pad % bm == 0 and k_pad % bk == 0 and n_pad % bn == 0
            assert bm == min(m_pad, 512)
            if k_pad <= 2048:
                assert bk == k_pad, (kind, padded, tile)
            else:
                assert bk == max(kops._blocks(k_pad, 2048)), (padded, tile)
            # the counted footprint, decoded f32 tiles included, fits
            # the budget and the VMEM limit the kernel asks for
            need = vmem_bytes(kind, bm, bk, bn)
            limit = (_compiler_params(kind, bm, bk, bn).vmem_limit_bytes
                     or SCOPED_VMEM)
            assert need <= min(limit, kops._VMEM_BUDGET), (kind, tile)
        assert kinds == ({"dual", "dual_gated", "mm"} if act_codes
                         else {"gated", "mm"})

    @pytest.mark.parametrize("budget_mib,tile", [
        (4, (16, 2048, 128)), (2, (16, 1024, 128))])
    def test_shrinks_bn_before_bk(self, budget_mib, tile):
        """Past the VMEM budget the rule narrows the columns first, and
        the contraction only once the columns are down to 128."""
        with mock.patch.object(kops, "_VMEM_BUDGET", budget_mib << 20):
            assert kops._default_tiling(16, 2048, 8192, "gated") == tile


class TestDecodeGQAAnyLength:
    @pytest.mark.parametrize("max_len", [77, 130, 300, 512])
    def test_odd_max_len(self, max_len):
        from repro.kernels.decode_gqa import decode_gqa, decode_gqa_ref
        r = np.random.default_rng(max_len)
        q = jnp.asarray(r.normal(size=(2, 2, 2, 32)), jnp.float32)
        k = jnp.asarray(r.normal(size=(2, max_len, 2, 32)), jnp.float32)
        v = jnp.asarray(r.normal(size=(2, max_len, 2, 32)), jnp.float32)
        lens = jnp.asarray([max_len, max_len // 2], jnp.int32)
        out = decode_gqa(q, k, v, lens)
        ref = decode_gqa_ref(q, k, v, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_zero_length_sequence_outputs_zeros(self):
        """lengths[b]==0 (empty batch slot) must yield zeros, not the
        softmax-of-all-masked mean of stale cache rows."""
        from repro.kernels.decode_gqa import decode_gqa
        r = np.random.default_rng(4)
        q = jnp.asarray(r.normal(size=(2, 2, 2, 32)), jnp.float32)
        k = jnp.asarray(r.normal(size=(2, 128, 2, 32)), jnp.float32)
        v = jnp.asarray(r.normal(size=(2, 128, 2, 32)), jnp.float32)
        out = decode_gqa(q, k, v, jnp.asarray([0, 64], jnp.int32))
        np.testing.assert_array_equal(np.asarray(out[0]),
                                      np.zeros_like(np.asarray(out[0])))
        assert float(jnp.max(jnp.abs(out[1]))) > 0

    def test_scalar_lengths_broadcast(self):
        from repro.kernels.decode_gqa import decode_gqa, decode_gqa_ref
        r = np.random.default_rng(1)
        q = jnp.asarray(r.normal(size=(3, 2, 1, 16)), jnp.float32)
        k = jnp.asarray(r.normal(size=(3, 96, 2, 16)), jnp.float32)
        v = jnp.asarray(r.normal(size=(3, 96, 2, 16)), jnp.float32)
        out = decode_gqa(q, k, v, 50)
        ref = decode_gqa_ref(q, k, v, jnp.full((3,), 50, jnp.int32))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_flash_decode_matches_dense_attend():
    """decode_step with the flash kernel == the dense masked attend."""
    cfg = get_config("qwen3-1.7b", tiny=True).replace(
        compute_dtype="float32")
    api = mapi.get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    toks = jnp.asarray(
        np.random.default_rng(2).integers(0, cfg.vocab_size, (2, 10)),
        jnp.int32)
    _, cache0 = api.prefill(params, toks, cfg, 48, cache_dtype=jnp.float32)
    with ll.policy(flash_decode=True):
        a, _ = api.decode_step(params, dict(cache0), toks[:, :1], cfg)
    with ll.policy(flash_decode=False):
        b, _ = api.decode_step(params, dict(cache0), toks[:, :1], cfg)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)


def test_quantized_server_f8_kv_close_to_fp32_kv():
    """Narrow-dtype KV serving stays logit-close to the fp32 cache.

    The f8 cache must hold exactly the f8 rounding of the f32 cache, and
    a decode step over it must match the f32 server decoding over that
    same rounded prefix.  The f8 rounding itself (3 mantissa bits) moves
    random-weight logits by 7-21% of their spread depending on the
    prompt, so comparing against the unrounded cache measures the prompt,
    not the dequant; what is left against the rounded prefix is the
    rounding of the one new token's K/V."""
    from repro.runtime.server import InferenceServer

    cfg = get_config("olmo-1b", tiny=True).replace(compute_dtype="float32")
    base = InferenceServer(cfg, max_len=40)
    f8 = InferenceServer(cfg, params=base.params, max_len=40,
                         kv_dtype="float8_e4m3fn")
    toks = jnp.asarray(
        np.random.default_rng(3).integers(0, cfg.vocab_size, (2, 8)),
        jnp.int32)
    la, ca = base._prefill(base.params, toks, None)
    lb, cb = f8._prefill(f8.params, toks, None)
    f8_round = lambda c: c.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    for key in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(f8_round(ca[key])),
            np.asarray(cb[key].astype(jnp.float32)))
    rounded = dict(ca, k=f8_round(ca["k"]), v=f8_round(ca["v"]))
    a, _ = base._decode(base.params, rounded, toks[:, :1])
    b, _ = f8._decode(f8.params, cb, toks[:, :1])
    rel = float(jnp.sqrt(jnp.mean((a - b) ** 2)) / (jnp.std(a) + 1e-9))
    assert rel < 0.2, rel
