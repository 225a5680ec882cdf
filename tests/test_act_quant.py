"""Activations as codes end-to-end (DNA-TEQ on both operands).

Covers the exponent-domain identity at the new boundary — the
paper-faithful counting formulation ≡ the dual-LUT Pallas kernel ≡ the
decode-then-matmul reference for every (bitsA, bitsW) pair — plus the
quantize epilogue (code-out), the QTensor operand carrier through
dense/dense_general/gated_mlp, the code-in/code-out MLP chain
(zero-materialization between consecutive quantized matmuls), the
runtime calibration pass with its disk cache, the autotuner cache-key
activation-representation component, the cached trie match, and the
end-to-end accuracy harness (≥ 0.95 greedy token agreement, act-quant
on vs off, on the tiny-config serving scenario)."""

import itertools
import json
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import exponent_dotprod as ed
from repro.core import exponential_quant as eq
from repro.core import lama_layers as ll
from repro.kernels.lut_dequant_matmul import ops as kops
from repro.kernels.lut_dequant_matmul.ref import (
    lut_dequant_matmul_dual_gated_ref,
    lut_dequant_matmul_dual_ref,
)
from repro.models import api as mapi
from repro.models import layers as L
from repro.runtime import calibration as cal
from repro.runtime.engine import Engine, EngineConfig, Request, _SeqState


def _coded_pair(seed, m, k, n, bits_a, bits_w, share_base=False):
    """(a, ca, pa), (w, cw, pw) with independently-fit quantizers; with
    ``share_base`` the weight re-encodes on the activation's base (the
    counting formulation needs one base per operand pair)."""
    r = np.random.default_rng(seed)
    a = jnp.asarray(r.normal(size=(m, k)) * 0.3, jnp.float32)
    w = jnp.asarray(r.normal(size=(k, n)) * 0.05, jnp.float32)
    ca, pa = eq.quantize(a, bits_a)
    if share_base:
        pw0 = eq.fit(w, bits_w)
        pw = eq.ExpQuantParams(pw0.alpha, pw0.beta, pa.base, bits_w)
        cw = eq.encode(w, pw)
    else:
        cw, pw = eq.quantize(w, bits_w)
    return (a, ca, pa), (w, cw, pw)


def _site(x, bits=7):
    """An act-quant site entry fit on ``x`` itself."""
    qp = eq.fit(jnp.reshape(x, (-1,)).astype(jnp.float32), bits)
    qm = eq.pack_qmeta(qp)
    return {"lut": cal.lut_from_qmeta(qm), "qmeta": qm}


def _qtensor(x, bits=7):
    return ll.encode_act(x, _site(x, bits))


# ------------------------------------------------ exponent identity --

class TestExponentIdentity:
    """counting_matmul ≡ dual-LUT kernel ≡ decode-then-matmul, to float
    tolerance, for every (bitsA, bitsW) pair at the kernel boundary."""

    @pytest.mark.parametrize(
        "bits_a,bits_w", list(itertools.product([3, 5, 7], [4, 6, 7])))
    def test_three_way(self, bits_a, bits_w):
        (a, ca, pa), (w, cw, pw) = _coded_pair(
            bits_a * 16 + bits_w, 6, 32, 5, bits_a, bits_w,
            share_base=True)
        counting = np.asarray(ed.counting_matmul(ca, pa, cw, pw))
        ref = np.asarray(lut_dequant_matmul_dual_ref(
            ca, cw, eq.pack_qmeta(pa), eq.pack_qmeta(pw)))
        kern = np.asarray(kops.lut_dequant_matmul_dual(
            ca, cw, eq.pack_qmeta(pa), eq.pack_qmeta(pw),
            out_dtype=jnp.float32))
        np.testing.assert_allclose(counting, ref, rtol=2e-4, atol=1e-4)
        np.testing.assert_allclose(kern, ref, rtol=2e-5, atol=2e-5)

    def test_kernel_decode_modes(self):
        """The kernel's closed-form decode of both operands equals a
        matmul of the operands looked up in their 256-entry tables."""
        (a, ca, pa), (w, cw, pw) = _coded_pair(3, 40, 96, 33, 7, 6)
        out = np.asarray(kops.lut_dequant_matmul_dual(
            ca, cw, eq.pack_qmeta(pa), eq.pack_qmeta(pw),
            out_dtype=jnp.float32))
        ref = np.asarray(eq.decode(ca, pa) @ eq.decode(cw, pw))
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("m,k,n", [(9, 100, 17), (9, 300, 17),
                                       (16, 4200, 40)])
    def test_k_padding_masked(self, m, k, n):
        """K not a lane multiple: a zero pad BYTE is a live code (it
        decodes to ±(α·base^e_min + β)) — the kernel must mask it, in a
        block that covers the whole contraction (300) and in the last of
        several (4200 -> blocks of 1408)."""
        (a, ca, pa), (w, cw, pw) = _coded_pair(4, m, k, n, 7, 6)
        out = np.asarray(kops.lut_dequant_matmul_dual(
            ca, cw, eq.pack_qmeta(pa), eq.pack_qmeta(pw),
            out_dtype=jnp.float32))
        ref = np.asarray(lut_dequant_matmul_dual_ref(
            ca, cw, eq.pack_qmeta(pa), eq.pack_qmeta(pw)))
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


# ----------------------------------------------------- dual kernel --

class TestDualKernel:
    def test_epilogue_and_bias(self):
        (a, ca, pa), (w, cw, pw) = _coded_pair(5, 24, 64, 48, 7, 6)
        bias = jnp.asarray(np.random.default_rng(6).normal(size=(48,)),
                           jnp.float32)
        out = np.asarray(kops.lut_dequant_matmul_dual(
            ca, cw, eq.pack_qmeta(pa), eq.pack_qmeta(pw), epilogue="silu",
            bias=bias, out_dtype=jnp.float32))
        ref = np.asarray(lut_dequant_matmul_dual_ref(
            ca, cw, eq.pack_qmeta(pa), eq.pack_qmeta(pw),
            epilogue="silu", bias=bias))
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_quantize_epilogue_codes_out(self):
        """out_qmeta → the kernel returns uint8 codes re-encoded
        in-kernel, matching the reference encode of the float result."""
        (a, ca, pa), (w, cw, pw) = _coded_pair(7, 16, 64, 40, 7, 6)
        ref_f = lut_dequant_matmul_dual_ref(
            ca, cw, eq.pack_qmeta(pa), eq.pack_qmeta(pw))
        qm_o = eq.pack_qmeta(eq.fit(jnp.reshape(ref_f, (-1,)), 7))
        out = kops.lut_dequant_matmul_dual(
            ca, cw, eq.pack_qmeta(pa), eq.pack_qmeta(pw), out_qmeta=qm_o)
        assert out.dtype == jnp.uint8
        ref_c = eq.encode_meta(ref_f, qm_o)
        # f32 accumulation-order deltas may flip a rounding-boundary
        # code; decoded values must still agree to the quant step
        assert float(jnp.mean((out == ref_c).astype(jnp.float32))) > 0.99
        np.testing.assert_allclose(
            np.asarray(eq.decode_meta(out, qm_o)),
            np.asarray(eq.decode_meta(ref_c, qm_o)), rtol=0.08, atol=0.02)

    def test_dual_gated(self):
        r = np.random.default_rng(8)
        (a, ca, pa), (wg, cg, pg) = _coded_pair(8, 12, 64, 56, 7, 6)
        wu = jnp.asarray(r.normal(size=(64, 56)) * 0.05, jnp.float32)
        cu, pu = eq.quantize(wu, 6)
        args = (ca, cg, cu, eq.pack_qmeta(pa), eq.pack_qmeta(pg),
                eq.pack_qmeta(pu))
        out = np.asarray(kops.lut_dequant_matmul_dual_gated(
            *args, activation="silu", out_dtype=jnp.float32))
        ref = np.asarray(lut_dequant_matmul_dual_gated_ref(
            ca, cg, cu, eq.pack_qmeta(pa), eq.pack_qmeta(pg),
            eq.pack_qmeta(pu), activation="silu"))
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
        # with the quantize epilogue the gated flush comes back as codes
        qm_o = eq.pack_qmeta(eq.fit(jnp.asarray(ref).reshape(-1), 7))
        out_c = kops.lut_dequant_matmul_dual_gated(
            *args, activation="silu", out_qmeta=qm_o)
        assert out_c.dtype == jnp.uint8
        np.testing.assert_allclose(
            np.asarray(eq.decode_meta(out_c, qm_o)), ref,
            rtol=0.1, atol=0.03)

    def test_encode_meta_matches_encode(self):
        """The traced-bits encoder (epilogue/activation path) is
        bit-identical to the static-bits weight encoder."""
        r = np.random.default_rng(9)
        x = jnp.asarray(r.normal(size=(512,)), jnp.float32)
        for bits in (4, 6, 7):
            qp = eq.fit(x, bits)
            np.testing.assert_array_equal(
                np.asarray(eq.encode(x, qp)),
                np.asarray(eq.encode_meta(x, eq.pack_qmeta(qp))))


# ------------------------------------------------- QTensor dispatch --

class TestQTensorDispatch:
    def test_dense_dual_vs_float_path(self):
        r = np.random.default_rng(10)
        x = jnp.asarray(r.normal(size=(11, 64)), jnp.float32)
        w = jnp.asarray(r.normal(size=(64, 80)) * 0.05, jnp.float32)
        cw, pw = eq.quantize(w, 7)
        wq = eq.pack_qtensor(cw, pw)
        xq = _qtensor(x)
        out = ll.dense(xq, wq, dtype=jnp.float32)
        ref = jnp.matmul(ll.materialize(xq, jnp.float32),
                         ll.materialize(wq, jnp.float32))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_dense_general_batched_spec(self):
        r = np.random.default_rng(11)
        x = jnp.asarray(r.normal(size=(2, 5, 32)), jnp.float32)
        w = jnp.asarray(r.normal(size=(32, 4, 8)) * 0.05, jnp.float32)
        cw, pw = eq.quantize(w, 7)
        wq = eq.pack_qtensor(cw, pw)
        xq = _qtensor(x)
        out = ll.dense_general(xq, wq, "bsd,dnh->bsnh", dtype=jnp.float32)
        ref = jnp.einsum("bsd,dnh->bsnh",
                         ll.materialize(xq, jnp.float32),
                         ll.materialize(wq, jnp.float32))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_tied_unembed_spec_falls_back_to_fp_act(self):
        """The transposed-codes layout has no dual variant: the act
        operand decodes and the fp-act kernel runs — output parity."""
        r = np.random.default_rng(12)
        x = jnp.asarray(r.normal(size=(2, 3, 32)), jnp.float32)
        w = jnp.asarray(r.normal(size=(40, 32)) * 0.05, jnp.float32)
        cw, pw = eq.quantize(w, 7)
        wq = eq.pack_qtensor(cw, pw)
        xq = _qtensor(x)
        out = ll.dense_general(xq, wq, "bsd,vd->bsv", dtype=jnp.float32)
        ref = jnp.einsum("bsd,vd->bsv",
                         ll.materialize(xq, jnp.float32),
                         ll.materialize(wq, jnp.float32))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_maybe_encode_act_gates(self):
        x = jnp.ones((4, 8), jnp.float32)
        aq = {"mlp_in": _site(x)}
        assert ll.maybe_encode_act(x, None, "mlp_in") is x
        assert ll.maybe_encode_act(x, aq, "attn_in") is x
        assert isinstance(ll.maybe_encode_act(x, aq, "mlp_in"),
                          eq.QTensor)
        with ll.policy(act_quant=False):
            assert ll.maybe_encode_act(x, aq, "mlp_in") is x

    def test_qtensor_is_pytree_carrier(self):
        xq = _qtensor(jnp.ones((3, 16), jnp.float32))
        leaves = jax.tree_util.tree_leaves(xq)
        assert any(l.dtype == jnp.uint8 for l in leaves)
        assert eq.is_qtensor(xq) and eq.is_qtensor(
            {"codes": xq.codes, "lut": xq.lut, "qmeta": xq.qmeta})
        roundtrip = jax.jit(lambda t: t)(xq)
        assert isinstance(roundtrip, eq.QTensor)


# ------------------------------------- code-in/code-out MLP chain --

class TestCodeInCodeOut:
    def _mlp(self, gated):
        r = np.random.default_rng(13)
        cfg = get_config("qwen3-1.7b", tiny=True).replace(
            d_model=32, d_ff=64, gated_mlp=gated,
            compute_dtype="float32")
        x = jnp.asarray(r.normal(size=(2, 4, 32)), jnp.float32)
        p = {}
        for name, spec in L.mlp_specs(cfg).items():
            w = jnp.asarray(r.normal(size=spec.shape) * 0.05, jnp.float32)
            cw, pw = eq.quantize(w, 7)
            p[name] = eq.pack_qtensor(cw, pw)
        _out, mid = L.apply_mlp(p, x, cfg, return_mid=True)
        act_q = {"mlp_in": _site(x), "mlp_mid": _site(mid)}
        return cfg, p, x, act_q

    @pytest.mark.parametrize("gated", [True, False])
    def test_down_projection_consumes_codes(self, gated):
        """The MLP intermediate must reach the down projection AS CODES
        — the structural zero-materialization property between the two
        quantized matmuls of the block."""
        cfg, p, x, act_q = self._mlp(gated)
        seen = []
        orig = ll.dense

        def spy(h, w, **kw):
            seen.append(type(h))
            return orig(h, w, **kw)

        with mock.patch.object(ll, "dense", spy), \
                mock.patch.object(L.ll, "dense", spy):
            out = L.apply_mlp(p, x, cfg, act_q=act_q)
        assert eq.QTensor in seen, (
            "down projection never saw an activation QTensor")
        ref = L.apply_mlp(p, x, cfg)
        err = (float(jnp.linalg.norm(out - ref))
               / max(float(jnp.linalg.norm(ref)), 1e-9))
        assert err < 0.25, f"act-quant MLP relative error {err:.3f}"

    def test_no_host_decode_in_fused_chain(self):
        """With fused policy on, the whole act-quant MLP chain runs
        without materialize() ever seeing a carrier."""
        cfg, p, x, act_q = self._mlp(True)
        orig = ll.materialize

        def guarded(w, dtype=jnp.bfloat16):
            if eq.is_qtensor(w):
                raise AssertionError("materialize() decoded a carrier "
                                     "on the fused act-quant path")
            return orig(w, dtype)

        with mock.patch.object(ll, "materialize", guarded), \
                ll.policy(mode="fused"):
            out = L.apply_mlp(p, x, cfg, act_q=act_q)
        assert bool(jnp.all(jnp.isfinite(out)))


# ------------------------------------------------------ calibration --

class TestCalibration:
    def _cfg(self):
        return get_config("qwen3-1.7b", tiny=True).replace(
            num_layers=2, d_model=32, d_ff=64, vocab_size=64,
            compute_dtype="float32")

    def test_fit_and_cache_roundtrip(self, tmp_path):
        cfg = self._cfg()
        api = mapi.get_model(cfg)
        params = api.init(jax.random.PRNGKey(0), dtype=jnp.float32)
        path = str(tmp_path / "calib.json")
        p1, rep1 = cal.calibrate_act_quant(api, params, cfg, bits=7,
                                           path=path)
        assert set(rep1) == set(L.ACT_SITES)
        aq = p1["blocks"]["act_q"]
        n_kv = cfg.num_kv_heads
        for site in L.ACT_SITES:
            if site in cal.PER_HEAD_SITES:
                assert aq[site]["lut"].shape == (cfg.num_layers, n_kv, 256)
                assert aq[site]["qmeta"].shape == (cfg.num_layers, n_kv, 4)
            else:
                assert aq[site]["lut"].shape == (cfg.num_layers, 256)
                assert aq[site]["qmeta"].shape == (cfg.num_layers, 4)
        assert all(s > 10.0 for v in rep1.values()
                   for s in np.asarray(v).ravel()), rep1
        # second call must be a pure cache hit with bit-identical tables
        with mock.patch.object(cal, "fit_sites",
                               side_effect=AssertionError("re-fit")):
            p2, rep2 = cal.calibrate_act_quant(api, params, cfg, bits=7,
                                               path=path)
        for site in L.ACT_SITES:
            np.testing.assert_array_equal(
                np.asarray(aq[site]["lut"]),
                np.asarray(p2["blocks"]["act_q"][site]["lut"]))
        r1 = {s: np.round(np.asarray(v, np.float64), 4)
              for s, v in rep1.items()}
        r2 = {s: np.round(np.asarray(v, np.float64), 4)
              for s, v in rep2.items()}
        assert set(r1) == set(r2)
        for s in r1:
            np.testing.assert_array_equal(r1[s], r2[s])

    def test_key_separates_weight_sets_and_prompt_content(self):
        cfg = self._cfg()
        api = mapi.get_model(cfg)
        pa = api.init(jax.random.PRNGKey(0), dtype=jnp.float32)
        pb = api.init(jax.random.PRNGKey(1), dtype=jnp.float32)
        prompts = np.arange(4 * 32, dtype=np.int32).reshape(4, 32) % 17
        ka = cal.calib_key(cfg, 7, prompts, 0, pa)
        kb = cal.calib_key(cfg, 7, prompts, 0, pb)
        assert ka != kb
        assert cal.calib_key(cfg, 6, prompts, 0, pa) != ka
        # same shape, different prompt CONTENT must not share an entry
        other = (prompts + 1) % cfg.vocab_size
        assert cal.calib_key(cfg, 7, other, 0, pa) != ka

    def test_bare_filename_cache_path_is_written(self, tmp_path,
                                                 monkeypatch):
        """CI points REPRO_ACT_CALIB_CACHE at a bare filename (no
        directory part) so the artifact lands in the workspace — the
        save path must handle dirname('') and actually write."""
        monkeypatch.chdir(tmp_path)
        cfg = self._cfg()
        api = mapi.get_model(cfg)
        params = api.init(jax.random.PRNGKey(0), dtype=jnp.float32)
        cal.calibrate_act_quant(api, params, cfg, bits=7,
                                path="calib.json")
        assert (tmp_path / "calib.json").exists()

    def test_cache_file_format(self, tmp_path):
        cfg = self._cfg()
        api = mapi.get_model(cfg)
        params = api.init(jax.random.PRNGKey(0), dtype=jnp.float32)
        path = str(tmp_path / "calib.json")
        cal.calibrate_act_quant(api, params, cfg, bits=7, path=path)
        blob = json.load(open(path))
        assert blob["version"] == 2
        (key, entry), = blob["entries"].items()
        assert f"|b7|" in key and cfg.name in key
        for site in L.ACT_SITES:
            metas = np.asarray(entry["sites"][site])
            if site in cal.PER_HEAD_SITES:
                assert metas.shape == (cfg.num_layers,
                                       cfg.num_kv_heads, 4)
            else:
                assert metas.shape == (cfg.num_layers, 4)

    def test_v1_cache_invalidated(self, tmp_path):
        """A v1 blob (pre attention-site calibration) must be ignored
        on load — the engine re-fits rather than serving stale metas
        missing the attn_q/attn_k/attn_v sites."""
        cfg = self._cfg()
        api = mapi.get_model(cfg)
        params = api.init(jax.random.PRNGKey(0), dtype=jnp.float32)
        path = str(tmp_path / "calib.json")
        prompts = np.arange(4 * 32, dtype=np.int32).reshape(4, 32) % 17
        key = cal.calib_key(cfg, 7, prompts, 0, params)
        (tmp_path / "calib.json").write_text(json.dumps(
            {"version": 1,
             "entries": {key: {"sites": {"attn_in": [[1.0, 0.0, 2.0, 7]]
                                         * cfg.num_layers},
                               "sqnr_db": {}}}}))
        p1, rep = cal.calibrate_act_quant(api, params, cfg, bits=7,
                                          prompts=prompts, path=path)
        # a real fit ran (v1 entry has no KV sites) and the rewritten
        # blob is wholesale v2 — the stale entry is gone, not merged
        assert set(rep) == set(L.ACT_SITES)
        blob = json.load(open(path))
        assert blob["version"] == 2
        assert set(blob["entries"][key]["sites"]) == set(L.ACT_SITES)


# --------------------------------------------- autotuner cache keys --

class TestAutotunerActRep:
    def test_xrep_component(self):
        assert kops._xrep(jnp.zeros((2, 2), jnp.float32)) == "float32"
        assert kops._xrep(jnp.zeros((2, 2), jnp.bfloat16)) == "bfloat16"
        assert kops._xrep(jnp.zeros((2, 2), jnp.uint8)) == kops.ACT_CODE_REP
        k_fp = kops._tune_key("mm", 8, 128, 128, "float32", "e")
        k_u8 = kops._tune_key("mm", 8, 128, 128, kops.ACT_CODE_REP, "e")
        assert k_fp != k_u8
        # keyed by the chip generation, not the backend name
        assert k_fp.split("|")[0] == jax.devices()[0].device_kind

    def test_v1_cache_invalidated(self, tmp_path):
        """Pre-xrep persisted tiles (v1 keys have no representation
        component) must not be consulted."""
        assert kops._TUNE_VERSION >= 3
        path = tmp_path / "tune.json"
        path.write_text(json.dumps(
            {"version": 1,
             "entries": {"cpu|mm|8|128|128|gather|e":
                         {"tile": [8, 128, 128], "us": 1.0}}}))
        t = kops.Autotuner(str(path))
        t._load_disk()
        assert t._mem == {}


# -------------------------------------------------- engine / serving --

@pytest.fixture
def isolated_caches(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_ACT_CALIB_CACHE",
                       str(tmp_path / "act_calib.json"))
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "tune.json"))
    return tmp_path


def _tiny_cfg():
    return get_config("qwen3-1.7b", tiny=True).replace(
        num_layers=2, d_model=64, d_ff=192, vocab_size=128,
        compute_dtype="float32")


# Greedy tokens on random weights flip on near-ties, so a token
# agreement rate measures luck; the logits are what act quantization
# moves.  7-bit codes at the calibrated sites carry about 40 dB mean SQNR
# (1% noise) and about 25 dB at the worst site (6%).  Through two layers
# that moves each prompt's logits by a few percent of their spread (up
# to 8% with KV codes on top), where a wrong table, head or site moves
# them by their whole spread.
ACT_LOGIT_TOL = 0.15


def _rel_logit_err(got, ref):
    """Per-prompt RMS logit error over the reference logits' spread."""
    return (np.sqrt(np.mean((got - ref) ** 2, axis=-1))
            / np.std(ref, axis=-1))


def _requests(cfg, lens, news=6, seed=3):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, cfg.vocab_size,
                                    int(l)).astype(np.int32),
                    max_new_tokens=news)
            for i, l in enumerate(lens)]


class TestServingActQuant:
    def test_token_agreement_and_zero_materialization(
            self, isolated_caches):
        """The acceptance harness: act-quant on vs off on the
        tiny-config serving scenario — logits within
        :data:`ACT_LOGIT_TOL` of the fp-act engine's, and with act-quant
        enabled NO carrier (weight codes or act codes) is ever decoded
        outside a kernel during the run."""
        cfg = _tiny_cfg()
        ecfg = EngineConfig(num_slots=4, block_size=16, max_seq_len=64)
        reqs = _requests(cfg, [16, 24, 32] * 4)
        clone = lambda: [Request(r.uid, r.prompt, r.max_new_tokens)
                         for r in reqs]
        fp = Engine(cfg, quant_bits=7, engine=ecfg)
        out_fp = {c.uid: c.tokens for c in fp.generate(clone())}

        act = Engine(cfg, params=fp.params, act_quant=7, engine=ecfg)
        assert act.act_report is not None
        assert set(act.act_report) == set(L.ACT_SITES)

        orig = ll.materialize

        def guarded(w, dtype=jnp.bfloat16):
            if eq.is_qtensor(w):
                raise AssertionError(
                    "materialize() decoded a carrier during act-quant "
                    "serving (f32 activation materialized between "
                    "quantized matmuls)")
            return orig(w, dtype)

        with mock.patch.object(ll, "materialize", guarded):
            out_act = {c.uid: c.tokens for c in act.generate(clone())}
            got = act.prompt_logits([r.prompt for r in reqs])
        assert {u: len(t) for u, t in out_act.items()} == {
            u: len(t) for u, t in out_fp.items()}
        rel = _rel_logit_err(got, fp.prompt_logits([r.prompt for r in reqs]))
        assert rel.max() < ACT_LOGIT_TOL, rel

    def test_policy_off_recovers_fp_act(self, isolated_caches):
        """act_quant=False in the policy A/B-disables encoding without
        re-calibrating: tokens match the fp-act engine exactly."""
        cfg = _tiny_cfg()
        ecfg = EngineConfig(num_slots=4, block_size=16, max_seq_len=64)
        reqs = _requests(cfg, [16, 24])
        clone = lambda: [Request(r.uid, r.prompt, r.max_new_tokens)
                         for r in reqs]
        fp = Engine(cfg, quant_bits=7, engine=ecfg)
        out_fp = {c.uid: c.tokens for c in fp.generate(clone())}
        act = Engine(cfg, params=fp.params, act_quant=7, engine=ecfg)
        with ll.policy(act_quant=False):
            out_off = {c.uid: c.tokens for c in act.generate(clone())}
        for u in out_fp:
            np.testing.assert_array_equal(out_fp[u], out_off[u])

    def test_calibration_cache_reused_across_engines(
            self, isolated_caches):
        cfg = _tiny_cfg()
        ecfg = EngineConfig(num_slots=2, block_size=16, max_seq_len=64)
        e1 = Engine(cfg, quant_bits=7, act_quant=7, engine=ecfg)
        with mock.patch.object(cal, "fit_sites",
                               side_effect=AssertionError("re-fit")):
            e2 = Engine(cfg, params=e1.params, act_quant=7, engine=ecfg)
        for site in L.ACT_SITES:
            np.testing.assert_array_equal(
                np.asarray(e1.params["blocks"]["act_q"][site]["lut"]),
                np.asarray(e2.params["blocks"]["act_q"][site]["lut"]))


class TestTrieMatchCache:
    def test_reuse_and_invalidation(self, isolated_caches):
        """The per-request trie match is served from cache while the
        trie generation and prompt are unchanged, and re-walked after
        retire/evict events bump the generation."""
        cfg = _tiny_cfg()
        eng = Engine(cfg, engine=EngineConfig(num_slots=2, block_size=16,
                                              max_seq_len=64))
        assert eng.prefix is not None
        st = _SeqState(Request(0, np.arange(20, dtype=np.int32),
                               max_new_tokens=2))
        m1 = eng._trie_match(st)
        assert eng.trie_match_reuses == 0
        m2 = eng._trie_match(st)
        assert eng.trie_match_reuses == 1
        assert m2 == m1
        eng.prefix.generation += 1          # a retire/evict happened
        eng._trie_match(st)
        assert eng.trie_match_reuses == 1   # re-walked, not reused
        eng._trie_match(st)
        assert eng.trie_match_reuses == 2
        # prompt growth (preemption appends tokens) also invalidates
        st.tokens.append(1)
        eng._trie_match(st)
        assert eng.trie_match_reuses == 2

    def test_counter_on_serving_stream(self, isolated_caches):
        """A stream with a shared prefix drives the reorder scan: the
        memoized match must keep the engine's output identical while
        reuses accumulate only when ticks actually repeat a walk."""
        cfg = _tiny_cfg()
        rng = np.random.default_rng(5)
        shared = rng.integers(0, cfg.vocab_size, 24).astype(np.int32)
        reqs = [Request(i, np.concatenate(
                    [shared, rng.integers(0, cfg.vocab_size, 8
                                          ).astype(np.int32)]),
                        max_new_tokens=4) for i in range(6)]
        ecfg = EngineConfig(num_slots=2, block_size=16, max_seq_len=64)
        eng = Engine(cfg, engine=ecfg)
        outs = eng.generate(reqs)
        assert len(outs) == 6 and eng.trie_match_reuses >= 0
        base = Engine(cfg, params=eng.params, engine=ecfg)
        base_outs = base.generate(
            [Request(r.uid, r.prompt, r.max_new_tokens) for r in reqs])
        for a, b in zip(sorted(outs, key=lambda c: c.uid),
                        sorted(base_outs, key=lambda c: c.uid)):
            np.testing.assert_array_equal(a.tokens, b.tokens)
