"""Chunked flash prefill over paged KV: the unified prefill path.

Covers the flash_prefill_paged kernel (block-table gather, per-row
start offsets, kv_lens masking, f8 in-kernel dequant, kernel == oracle
to f32 rounding), the unified ``prefill_into_cache`` (cold and
prefix-offset chunking across {1-page, 2-page, odd} chunk sizes equal
to the single-call prefill to f32 rounding; zero-length tails
write nothing), and the Engine's chunked-prefill scheduling (chunk
interleaving with decode is token-identical to the un-chunked engine,
long prompts stop monopolizing ticks, TTFT/queue-wait stats, and the
prefix-aware admission reorder)."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.kernels.flash_prefill import (
    flash_prefill_paged,
    flash_prefill_paged_ref,
)
from repro.models import api as mapi
from repro.runtime.engine import Engine, EngineConfig, Request
from repro.runtime.paged_cache import PagedKVCache


def tiny_cfg(**kw):
    base = dict(num_layers=2, d_model=64, d_ff=128,
                compute_dtype="float32")
    base.update(kw)
    return get_config("qwen3-1.7b", tiny=True).replace(**base)


# ------------------------------------------------------------- kernel --

class TestFlashPrefillKernel:
    def _inputs(self, dtype=jnp.float32, seed=0):
        r = np.random.default_rng(seed)
        b, s, nkv, g, hd, bs, max_blk = 3, 8, 2, 2, 16, 4, 6
        nblocks = 1 + b * max_blk
        q = jnp.asarray(r.normal(size=(b, s, nkv, g, hd)), jnp.float32)
        kp = jnp.asarray(r.normal(size=(nblocks, bs, nkv, hd)) * 0.3,
                         jnp.float32).astype(dtype)
        vp = jnp.asarray(r.normal(size=(nblocks, bs, nkv, hd)) * 0.3,
                         jnp.float32).astype(dtype)
        # a scrambled (non-contiguous) physical page assignment
        perm = r.permutation(np.arange(1, nblocks))
        bt = jnp.asarray(perm[: b * max_blk].reshape(b, max_blk), jnp.int32)
        # row 0: cold chunk from 0; row 1: prefix-offset chunk with a
        # short tail; row 2: empty (a decoding slot riding along)
        start = jnp.asarray([0, 5, 13], jnp.int32)
        valid = np.asarray([8, 6, 0])
        kv_lens = jnp.asarray(
            np.where(valid > 0, np.asarray(start) + valid, 0), jnp.int32)
        return q, kp, vp, bt, start, kv_lens

    def test_kernel_matches_ref_bitwise(self):
        """The forced kernel and the jnp oracle run the same page
        recurrence.  The kernel scores all heads of a page in one folded
        2-D dot where the oracle contracts per head, so the two agree
        to f32 rounding (the dense-oracle tolerance below), not bit for
        bit."""
        q, kp, vp, bt, start, kv_lens = self._inputs()
        out_k = flash_prefill_paged(q, kp, vp, bt, start, kv_lens,
                                    interpret=True)
        out_r = flash_prefill_paged_ref(q, kp, vp, bt, start, kv_lens)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.float8_e4m3fn])
    def test_matches_dense_oracle(self, dtype):
        """Gathering pages to a contiguous cache and running a dense
        positional-masked softmax gives the same attention — for f32
        and narrow f8 pages (dequant in-kernel)."""
        q, kp, vp, bt, start, kv_lens = self._inputs(dtype)
        b, max_blk = bt.shape
        bs = kp.shape[1]
        out = np.asarray(flash_prefill_paged(q, kp, vp, bt, start, kv_lens,
                                             interpret=True))
        k = np.asarray(kp[bt].astype(jnp.float32)).reshape(
            b, max_blk * bs, *kp.shape[2:])
        v = np.asarray(vp[bt].astype(jnp.float32)).reshape(
            b, max_blk * bs, *vp.shape[2:])
        s, hd = q.shape[1], q.shape[-1]
        qpos = np.asarray(start)[:, None] + np.arange(s)[None, :]
        kvpos = np.arange(max_blk * bs)
        for bi in range(b):
            for si in range(s):
                m = ((kvpos <= qpos[bi, si])
                     & (kvpos < int(kv_lens[bi])))
                if not m.any():
                    np.testing.assert_array_equal(out[bi, si], 0.0)
                    continue
                kk, vv = k[bi][m], v[bi][m]
                for n in range(q.shape[2]):
                    for gi in range(q.shape[3]):
                        logit = (np.asarray(q[bi, si, n, gi], np.float32)
                                 @ kk[:, n].T) / math.sqrt(hd)
                        p = np.exp(logit - logit.max())
                        p /= p.sum()
                        np.testing.assert_allclose(
                            out[bi, si, n, gi], p @ vv[:, n],
                            rtol=2e-5, atol=2e-5)

    def test_zero_valid_rows_return_zeros(self):
        q, kp, vp, bt, start, _ = self._inputs()
        kv_lens = jnp.zeros((3,), jnp.int32)
        for interpret in (True, None):
            out = np.asarray(flash_prefill_paged(
                q, kp, vp, bt, start, kv_lens, interpret=interpret))
            assert np.all(out == 0)

    def test_oracle_path_matches_kernel(self):
        """The CPU-default oracle path (interpret=None) == kernel, to
        f32 rounding (folded vs per-head contraction, as above)."""
        q, kp, vp, bt, start, kv_lens = self._inputs()
        auto = flash_prefill_paged(q, kp, vp, bt, start, kv_lens)
        forced = flash_prefill_paged(q, kp, vp, bt, start, kv_lens,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(auto), np.asarray(forced),
                                   rtol=2e-5, atol=2e-5)


# ------------------------------------------- unified prefill (model) --

class TestUnifiedPrefill:
    BS = 4

    def _setup(self, plen=11, num_slots=1):
        cfg = tiny_cfg()
        api = mapi.get_model(cfg)
        params = api.init(jax.random.PRNGKey(0), dtype=jnp.float32)
        rng = np.random.default_rng(3)
        prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
        return cfg, api, params, prompt

    def _cache(self, cfg, plen, num_slots=1):
        c = PagedKVCache(num_layers=cfg.num_layers,
                         num_kv_heads=cfg.num_kv_heads,
                         head_dim=cfg.resolved_head_dim,
                         num_slots=num_slots, block_size=self.BS,
                         num_blocks=16, max_blocks_per_seq=6)
        c.allocator.reserve(6)
        c.bind_slot(0, plen)
        return c

    def _single_call(self, cfg, api, params, prompt):
        plen = len(prompt)
        cache = self._cache(cfg, plen)
        s_pad = -(-plen // self.BS) * self.BS + self.BS
        toks = np.zeros((1, s_pad), np.int32)
        toks[0, :plen] = prompt
        logits, view = api.prefill_into_cache(
            params, jnp.asarray(toks), cache.view(), cfg)
        return logits, view

    def _chunked(self, cfg, api, params, prompt, chunk, start0=0,
                 view=None):
        """Drive prefill_into_cache in ``chunk``-token slices from
        ``start0`` to the end of the prompt."""
        plen = len(prompt)
        if view is None:
            view = self._cache(cfg, plen).view()
        logits = None
        for c0 in range(start0, plen, chunk):
            sl = np.zeros((1, chunk), np.int32)
            take = min(chunk, plen - c0)
            sl[0, :take] = prompt[c0:c0 + take]
            logits, view = api.prefill_into_cache(
                params, jnp.asarray(sl), view, cfg,
                jnp.asarray([c0], jnp.int32))
        return logits, view

    @pytest.mark.parametrize("chunk", [4, 8, 5])   # 1 page, 2 pages, odd
    def test_cold_chunked_bitwise_matches_single_call(self, chunk):
        """Chunked cold prefill == the single whole-prompt call: same
        non-trash page contents, same final logits, whatever the chunk
        size (page-aligned or odd).  The two run matmuls of different
        shapes, whose f32 summation order XLA does not fix, so they
        agree to f32 rounding (the prefix-offset tolerance below), not
        bit for bit."""
        cfg, api, params, prompt = self._setup()
        logits1, view1 = self._single_call(cfg, api, params, prompt)
        logits2, view2 = self._chunked(cfg, api, params, prompt, chunk)
        for a, b in [(view1.k_pages, view2.k_pages),
                     (view1.v_pages, view2.v_pages)]:
            np.testing.assert_allclose(np.asarray(a)[:, 1:],
                                       np.asarray(b)[:, 1:],
                                       rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(logits1), np.asarray(logits2),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("chunk", [4, 8, 5])
    def test_prefix_offset_chunked_matches_cold(self, chunk):
        """Tail prefill over pre-populated prefix pages (RoPE offsets,
        attention over the cached prefix straight from the pages) ==
        the cold whole-prompt run, for every chunk size."""
        cfg, api, params, prompt = self._setup(plen=19)
        logits_cold, view_cold = self._single_call(cfg, api, params, prompt)
        prefix_len, pblocks = 8, 2
        warm = self._cache(cfg, len(prompt))
        src = np.asarray(view_cold.block_tables[0, :pblocks])
        dst = warm.block_tables[0, :pblocks]
        warm.k_pages = warm.k_pages.at[:, dst].set(view_cold.k_pages[:, src])
        warm.v_pages = warm.v_pages.at[:, dst].set(view_cold.v_pages[:, src])
        logits_warm, view_warm = self._chunked(
            cfg, api, params, prompt, chunk, start0=prefix_len,
            view=warm.view())
        np.testing.assert_allclose(np.asarray(logits_warm[0, -1]),
                                   np.asarray(logits_cold[0, -1]),
                                   rtol=2e-5, atol=2e-5)
        tc = np.asarray(view_cold.block_tables[0, :5])
        tw = np.asarray(view_warm.block_tables[0, :5])
        kc = np.asarray(view_cold.k_pages[:, tc]).reshape(
            cfg.num_layers, 20, cfg.num_kv_heads, -1)[:, :len(prompt)]
        kw = np.asarray(view_warm.k_pages[:, tw]).reshape(
            cfg.num_layers, 20, cfg.num_kv_heads, -1)[:, :len(prompt)]
        np.testing.assert_allclose(kw, kc, rtol=2e-5, atol=2e-5)

    def test_zero_length_tail_writes_nothing(self):
        """A row whose start is at/past its length (a decoding slot
        riding in a full-width dispatch) must leave every non-trash
        page untouched."""
        cfg, api, params, prompt = self._setup()
        _, view = self._single_call(cfg, api, params, prompt)
        before_k = np.asarray(view.k_pages)
        _, after = api.prefill_into_cache(
            params, jnp.asarray(np.zeros((1, 4), np.int32)), view, cfg,
            jnp.asarray([len(prompt)], jnp.int32))
        np.testing.assert_array_equal(before_k[:, 1:],
                                      np.asarray(after.k_pages)[:, 1:])


# ------------------------------------------------- chunked scheduling --

class TestChunkedEngine:
    def _mixed(self, cfg, lens, news, seed=0):
        rng = np.random.default_rng(seed)
        return [Request(i, rng.integers(0, cfg.vocab_size,
                                        int(l)).astype(np.int32),
                        max_new_tokens=int(n))
                for i, (l, n) in enumerate(zip(lens, news))]

    def test_chunk_interleaved_token_identity(self):
        """The acceptance property: a mixed stream served with tiny
        prefill chunks (every prompt split across ticks, interleaved
        with running decodes) is token-identical to the un-chunked
        engine."""
        cfg = tiny_cfg()
        lens, news = (8, 32, 128, 17), (6, 4, 8, 5)
        outs = []
        for chunk in (256, 8):
            eng = Engine(cfg, engine=EngineConfig(
                num_slots=3, block_size=8, max_seq_len=192,
                prefill_chunk=chunk))
            outs.append(eng.generate(self._mixed(cfg, lens, news)))
        assert outs[1][0].tokens.size
        for a, b in zip(*outs):
            np.testing.assert_array_equal(a.tokens, b.tokens)

    def test_long_prompt_interleaves_with_decode(self):
        """A long prompt chunk-prefills across several ticks while a
        short request keeps decoding — the long prompt no longer
        monopolizes the scheduler, so the short request's stream
        advances during the long prefill."""
        cfg = tiny_cfg()
        eng = Engine(cfg, engine=EngineConfig(
            num_slots=2, block_size=8, max_seq_len=128,
            prefill_chunk=16, prefix_cache=False))
        rng = np.random.default_rng(2)
        short = Request(0, rng.integers(0, cfg.vocab_size,
                                        8).astype(np.int32),
                        max_new_tokens=12)
        long_ = Request(1, rng.integers(0, cfg.vocab_size,
                                        64).astype(np.int32),
                        max_new_tokens=4)
        eng.submit(short)
        eng.submit(long_)
        short_tokens_at_long_first = None
        while eng.pending:
            eng.step()
            if (short_tokens_at_long_first is None
                    and eng._states[1].tokens):
                short_tokens_at_long_first = len(eng._states[0].tokens)
        # 64-token prompt at chunk 16 -> >= 4 prefill dispatches, and
        # the short request decoded throughout
        assert eng.prefill_batches >= 4, eng.prefill_batches
        assert short_tokens_at_long_first >= 3, short_tokens_at_long_first
        # the interleaving changed nothing about the tokens
        ref = Engine(cfg, params=eng.params, engine=EngineConfig(
            num_slots=2, block_size=8, max_seq_len=128,
            prefix_cache=False))
        ref_out = ref.generate([Request(0, short.prompt, 12),
                                Request(1, long_.prompt, 4)])
        out = eng.run()
        for a, b in zip(out, ref_out):
            np.testing.assert_array_equal(a.tokens, b.tokens)

    def test_ttft_and_queue_wait_stats(self):
        """Completions carry TTFT (submit -> first token) and
        queue-wait (submit -> admission); TTFT always covers the wait
        plus at least one prefill dispatch."""
        cfg = tiny_cfg()
        eng = Engine(cfg, engine=EngineConfig(num_slots=1, block_size=8,
                                              max_seq_len=64))
        out = eng.generate(self._mixed(cfg, (8, 24), (4, 4)))
        for c in out:
            assert c.ttft_s > 0
            assert c.queue_wait_s >= 0
            assert c.ttft_s >= c.queue_wait_s
        # one slot: uid 1 waits for uid 0 to finish before admission
        assert out[1].queue_wait_s > out[0].queue_wait_s

    def test_prefix_aware_admission_reorder(self):
        """When the queue head cannot get its pages, a waiting request
        whose prefix is pinned in the trie admits first (its spliced
        pages shrink the footprint) — counted in admission_reorders and
        token-identical to a roomy cold engine."""
        cfg = tiny_cfg()
        rng = np.random.default_rng(11)
        shared = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
        eng = Engine(cfg, engine=EngineConfig(num_slots=2, block_size=8,
                                              max_seq_len=64,
                                              num_blocks=8))
        # round 0 populates the trie with the shared prefix
        eng.generate([Request(100, shared, max_new_tokens=1)])
        r_a = Request(0, np.concatenate(
            [shared, rng.integers(0, cfg.vocab_size, 8).astype(np.int32)]),
            max_new_tokens=8)
        r_head = Request(1, rng.integers(0, cfg.vocab_size,
                                         40).astype(np.int32),
                         max_new_tokens=4)
        r_hit = Request(2, np.concatenate(
            [shared, rng.integers(0, cfg.vocab_size, 8).astype(np.int32)]),
            max_new_tokens=2)
        for r in (r_a, r_head, r_hit):
            eng.submit(r)
        out = eng.run()
        assert eng.admission_reorders >= 1, eng.admission_reorders
        ref = Engine(cfg, params=eng.params,
                     engine=EngineConfig(num_slots=2, block_size=8,
                                         max_seq_len=64,
                                         prefix_cache=False))
        ref_out = ref.generate([Request(r.uid, r.prompt, r.max_new_tokens)
                                for r in (r_a, r_head, r_hit)])
        for a, b in zip(out, ref_out):
            np.testing.assert_array_equal(a.tokens, b.tokens)
        eng.check_partition()
