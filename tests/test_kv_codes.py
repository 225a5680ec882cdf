"""Attention as codes: exponent-coded KV cache + exponent-domain flash
attention.

Covers the codes modes of both serving kernels (uint8 DNA-TEQ pages
decoded in-kernel with per-head parameters, q consumed as codes,
context re-encoded by the quantize epilogue) — kernel == page-scan
oracle bit-for-bit INCLUDING the epilogue, and the oracle's math equals
the fp recurrence run on decoded operands.  Engine level: the
kv_codes=True engine quantizes K/V at the page write, keeps its logits
within the act-quant tolerance of the f32-KV reference on the canonical
seeded scenario, and reports the attention-boundary traffic counters
the kvcodes bench rows read."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import exponential_quant as eq
from repro.kernels.decode_gqa import (
    decode_gqa_paged_codes,
    decode_gqa_paged_codes_ref,
)
from repro.kernels.flash_prefill import (
    flash_prefill_paged_codes,
    flash_prefill_paged_codes_ref,
    flash_prefill_paged_ref,
)
from repro.models import layers as L
from repro.runtime.engine import Engine, EngineConfig, Request
from repro.runtime.server import InferenceServer

from test_act_quant import ACT_LOGIT_TOL, _rel_logit_err


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


def _head_tables(x, bits=7):
    """Fit one (alpha, beta, base) per head of ``x`` [..., n_kv, hd].

    Returns (qmeta [n_kv, 4], lut [n_kv, 256]) — the per-head table
    layout the codes kernels take."""
    n_kv = x.shape[-2]
    per_head = jnp.moveaxis(x, -2, 0).reshape(n_kv, -1)
    metas = jnp.stack([eq.pack_qmeta(eq.fit(per_head[n], bits))
                       for n in range(n_kv)])
    luts = jnp.stack([eq.decode_meta(jnp.arange(256, dtype=jnp.int32),
                                     metas[n]) for n in range(n_kv)])
    return metas, luts


def _tensor_table(x, bits=7):
    qm = eq.pack_qmeta(eq.fit(x.reshape(-1), bits))
    return qm, eq.decode_meta(jnp.arange(256, dtype=jnp.int32), qm)


# ------------------------------------------------------------ kernels --

class TestCodesKernelsBitEqual:
    """Forced kernel vs jnp page-scan oracle: identical recurrence,
    identical quantize epilogue — the uint8 outputs match bit-for-bit."""

    def _paged(self, seed=0):
        r = np.random.default_rng(seed)
        b, nkv, g, hd, bs, max_blk = 3, 2, 2, 16, 4, 6
        nblocks = 1 + b * max_blk
        kp = jnp.asarray(r.normal(size=(nblocks, bs, nkv, hd)) * 0.3,
                         jnp.float32)
        vp = jnp.asarray(r.normal(size=(nblocks, bs, nkv, hd)) * 0.3,
                         jnp.float32)
        perm = r.permutation(np.arange(1, nblocks))
        bt = jnp.asarray(perm[: b * max_blk].reshape(b, max_blk),
                         jnp.int32)
        k_qm, k_lut = _head_tables(kp)
        v_qm, v_lut = _head_tables(vp)
        kp_c = eq.encode_meta(kp, k_qm[:, None, :])
        vp_c = eq.encode_meta(vp, v_qm[:, None, :])
        out_qm = jnp.asarray([0.02, 1e-4, 1.04, 7.0], jnp.float32)
        return (r, b, nkv, g, hd, bs, max_blk, kp_c, vp_c, bt,
                k_qm, k_lut, v_qm, v_lut, out_qm)

    def test_prefill_kernel_matches_ref_bitwise(self):
        (r, b, nkv, g, hd, bs, max_blk, kp_c, vp_c, bt,
         k_qm, k_lut, v_qm, v_lut, out_qm) = self._paged()
        s = 8
        q = jnp.asarray(r.normal(size=(b, s, nkv, g, hd)), jnp.float32)
        q_qm, q_lut = _tensor_table(q)
        q_c = eq.encode_meta(q, q_qm)
        start = jnp.asarray([0, 5, 13], jnp.int32)
        kv_lens = jnp.asarray([8, 11, 0], jnp.int32)
        out_k = flash_prefill_paged_codes(
            q_c, kp_c, vp_c, q_qm, k_qm, v_qm, out_qm, bt, start,
            kv_lens, interpret=True)
        out_r = flash_prefill_paged_codes_ref(
            q_c, kp_c, vp_c, q_qm, k_qm, v_qm, out_qm, bt, start,
            kv_lens)
        assert out_k.dtype == jnp.uint8
        np.testing.assert_array_equal(np.asarray(out_k),
                                      np.asarray(out_r))

    def test_decode_kernel_matches_ref_bitwise(self):
        (r, b, nkv, g, hd, bs, max_blk, kp_c, vp_c, bt,
         k_qm, k_lut, v_qm, v_lut, out_qm) = self._paged(seed=1)
        q = jnp.asarray(r.normal(size=(b, nkv, g, hd)), jnp.float32)
        q_qm, q_lut = _tensor_table(q)
        q_c = eq.encode_meta(q, q_qm)
        lengths = jnp.asarray([9, 24, 1], jnp.int32)
        out_k = decode_gqa_paged_codes(
            q_c, kp_c, vp_c, q_qm, k_qm, v_qm, out_qm, bt, lengths,
            interpret=True)
        out_r = decode_gqa_paged_codes_ref(
            q_c, kp_c, vp_c, q_qm, k_qm, v_qm, out_qm, bt, lengths)
        assert out_k.dtype == jnp.uint8
        np.testing.assert_array_equal(np.asarray(out_k),
                                      np.asarray(out_r))

    def test_auto_path_matches_forced_kernel(self):
        """The CPU-default oracle dispatch (interpret=None) == the
        forced kernel for both codes ops."""
        (r, b, nkv, g, hd, bs, max_blk, kp_c, vp_c, bt,
         k_qm, k_lut, v_qm, v_lut, out_qm) = self._paged(seed=2)
        q = jnp.asarray(r.normal(size=(b, nkv, g, hd)), jnp.float32)
        q_qm, q_lut = _tensor_table(q)
        q_c = eq.encode_meta(q, q_qm)
        lengths = jnp.asarray([9, 24, 1], jnp.int32)
        auto = decode_gqa_paged_codes(
            q_c, kp_c, vp_c, q_qm, k_qm, v_qm, out_qm, bt, lengths)
        forced = decode_gqa_paged_codes(
            q_c, kp_c, vp_c, q_qm, k_qm, v_qm, out_qm, bt, lengths,
            interpret=True)
        np.testing.assert_array_equal(np.asarray(auto),
                                      np.asarray(forced))

    def test_codes_oracle_equals_fp_recurrence_on_decoded_operands(self):
        """Strip the quantize epilogue and the codes oracle IS the fp
        page recurrence run on LUT-decoded q/k/v — decode is an
        elementwise gather, so moving it outside the scan changes no
        bits.  This ties the serving path to the Eq.1 identity tested
        in test_exponent_dotprod."""
        (r, b, nkv, g, hd, bs, max_blk, kp_c, vp_c, bt,
         k_qm, k_lut, v_qm, v_lut, out_qm) = self._paged(seed=3)
        s = 8
        q = jnp.asarray(r.normal(size=(b, s, nkv, g, hd)), jnp.float32)
        q_qm, q_lut = _tensor_table(q)
        q_c = eq.encode_meta(q, q_qm)
        start = jnp.asarray([0, 5, 13], jnp.int32)
        kv_lens = jnp.asarray([8, 11, 0], jnp.int32)
        out_codes = flash_prefill_paged_codes_ref(
            q_c, kp_c, vp_c, q_qm, k_qm, v_qm, out_qm, bt, start,
            kv_lens)
        from repro.kernels._codes import decode_heads
        qd = eq.decode_meta(q_c, q_qm)
        kd = decode_heads(k_qm, kp_c)
        vd = decode_heads(v_qm, vp_c)
        out_fp = flash_prefill_paged_ref(qd, kd, vd, bt, start, kv_lens)
        expect = eq.encode_meta(out_fp, out_qm)
        np.testing.assert_array_equal(np.asarray(out_codes),
                                      np.asarray(expect))


# ------------------------------------------------------------- engine --

class TestEngineKVCodes:
    def _scenario(self, cfg):
        rng = np.random.default_rng(3)
        return [Request(i, rng.integers(0, cfg.vocab_size,
                                        int(l)).astype(np.int32),
                        max_new_tokens=6)
                for i, l in enumerate([16, 24, 32] * 4)]

    def test_kv_codes_requires_act_quant(self, isolated_caches):
        cfg = _tiny_cfg()
        with pytest.raises(ValueError, match="act_quant"):
            Engine(cfg, kv_codes=True)
        with pytest.raises(ValueError, match="act_quant"):
            InferenceServer(cfg, kv_codes=True)

    def test_token_agreement_vs_fp_kv(self, isolated_caches):
        """The acceptance harness: codes-mode KV vs the f32-KV engine
        (both act-quantized, same weights) on the canonical seeded
        scenario — every request served in full, and each prompt's
        logits within the act-quant tolerance of the f32-KV engine's
        (greedy tokens on random weights flip on near-ties; see
        ``test_act_quant.ACT_LOGIT_TOL``)."""
        cfg = _tiny_cfg()
        ecfg = EngineConfig(num_slots=4, block_size=16, max_seq_len=64,
                            prefix_cache=False)
        reqs = self._scenario(cfg)
        clone = lambda: [Request(r.uid, r.prompt, r.max_new_tokens)
                         for r in reqs]
        fp = Engine(cfg, quant_bits=7, act_quant=7, engine=ecfg)
        out_fp = {c.uid: c.tokens for c in fp.generate(clone())}
        codes = Engine(cfg, params=fp.params, act_quant=7,
                       kv_codes=True, engine=ecfg)
        assert codes.kv_dtype == jnp.dtype(jnp.uint8)
        assert codes.cache.k_pages.dtype == jnp.uint8
        out_c = {c.uid: c.tokens for c in codes.generate(clone())}
        assert {u: len(t) for u, t in out_c.items()} == {
            u: len(t) for u, t in out_fp.items()}
        prompts = [r.prompt for r in reqs]
        rel = _rel_logit_err(codes.prompt_logits(prompts),
                             fp.prompt_logits(prompts))
        assert rel.max() < ACT_LOGIT_TOL, rel

    def test_quantize_at_write(self, isolated_caches):
        """KV pages hold real DNA-TEQ codes: decoding a written page
        through the layer's per-head attn_k LUT reproduces the f32-KV
        engine's page to quantization error (a raw astype would decode
        to junk orders of magnitude off)."""
        cfg = _tiny_cfg()
        ecfg = EngineConfig(num_slots=2, block_size=16, max_seq_len=64,
                            prefix_cache=False)
        rng = np.random.default_rng(0)
        prompt = rng.integers(0, cfg.vocab_size, 20).astype(np.int32)
        fp = Engine(cfg, act_quant=7, engine=ecfg)
        codes = Engine(cfg, params=fp.params, act_quant=7,
                       kv_codes=True, engine=ecfg)
        # pages are trashed at retire — inspect while the request runs
        for eng in (fp, codes):
            eng.submit(Request(0, prompt, max_new_tokens=4))
            for _ in range(2):
                eng.step()
        page_fp = int(fp.cache.block_tables[0, 0])
        page_c = int(codes.cache.block_tables[0, 0])
        aq = codes.params["blocks"]["act_q"]
        for l in range(cfg.num_layers):
            got = eq.decode_meta(
                jnp.asarray(codes.cache.k_pages[l, page_c]),
                aq["attn_k"]["qmeta"][l][:, None, :])
            ref = np.asarray(fp.cache.k_pages[l, page_fp], np.float32)
            # layer 0 K is a pure function of the prompt: only the
            # write-side quantization separates the two engines there;
            # deeper layers add the bounded upstream attention error
            tol = 0.06 * float(np.abs(ref).max()) + 0.05
            assert float(np.abs(np.asarray(got) - ref).max()) < tol
        for eng in (fp, codes):
            eng.run()

    def test_attn_traffic_counters(self, isolated_caches):
        """The analytic attention-boundary counters feeding the kvcodes
        bench rows: the codes engine moves exactly 1/4 the activation
        bytes of an f32-boundary engine over the identical stream, and
        only the codes engine reports in-kernel LUT decodes."""
        cfg = _tiny_cfg()
        ecfg = EngineConfig(num_slots=4, block_size=16, max_seq_len=64,
                            prefix_cache=False)
        reqs = self._scenario(cfg)
        clone = lambda: [Request(r.uid, r.prompt, r.max_new_tokens)
                         for r in reqs]
        fp = Engine(cfg, act_quant=7, engine=ecfg)
        fp.generate(clone())
        codes = Engine(cfg, params=fp.params, act_quant=7,
                       kv_codes=True, engine=ecfg)
        codes.generate(clone())
        assert codes.attn_act_bytes > 0
        assert codes.attn_act_bytes * 4 == fp.attn_act_bytes
        assert codes.attn_bytes_read < fp.attn_bytes_read
        assert codes.attn_dequants > 0 and fp.attn_dequants == 0
        # the counters live in the metrics registry under stable keys
        reg = codes.telemetry.registry
        assert reg.value("engine.attn.bytes_act") == codes.attn_act_bytes
        assert reg.value("engine.attn.dequants") == codes.attn_dequants

    def test_server_and_policy_plumbing(self, isolated_caches):
        """InferenceServer(kv_codes=True) builds a codes-mode engine;
        generate() round-trips tokens."""
        cfg = _tiny_cfg()
        srv = InferenceServer(cfg, act_quant=7, kv_codes=True,
                              max_len=48, num_slots=2)
        rng = np.random.default_rng(1)
        out = srv.generate([Request(0, rng.integers(
            0, cfg.vocab_size, 12).astype(np.int32), max_new_tokens=4)])
        assert out[0].tokens.size == 4
        assert srv.last_engine.kv_codes
        assert srv.last_engine.cache.k_pages.dtype == jnp.uint8
