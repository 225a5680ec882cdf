"""Serving engine: paged KV cache + continuous batching.

Covers the paged decode_gqa kernel (block-table gather, paged-vs-
contiguous equivalence in f32 and f8, zero-length slots), the block
allocator's invariants (trash page, reservations, retirement), the
paged prefill/decode model entry points, and the Engine scheduler
(mixed-length streams token-identical to the legacy bucketed path,
block-boundary crossing mid-decode, stop-token retirement freeing
blocks, honest per-request timings, streaming)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.kernels.decode_gqa import (
    decode_gqa,
    decode_gqa_paged,
    decode_gqa_paged_ref,
)
from repro.models import api as mapi
from repro.models import layers as L
from repro.runtime.calibration import calibrate_act_quant
from repro.runtime.engine import Engine, EngineConfig, Request
from repro.runtime.paged_cache import (
    TRASH_PAGE,
    BlockAllocator,
    PagedKVCache,
    PagedView,
)
from repro.runtime.server import InferenceServer


def tiny_cfg(**kw):
    base = dict(num_layers=2, d_model=64, d_ff=128,
                compute_dtype="float32")
    base.update(kw)
    return get_config("qwen3-1.7b", tiny=True).replace(**base)


def mixed_requests(cfg, lens, news):
    rng = np.random.default_rng(0)
    return [Request(i, rng.integers(0, cfg.vocab_size,
                                    int(l)).astype(np.int32),
                    max_new_tokens=int(n))
            for i, (l, n) in enumerate(zip(lens, news))]


# ------------------------------------------------------------- kernel --

class TestPagedDecodeGQA:
    def _pages(self, dtype=jnp.float32, seed=0):
        r = np.random.default_rng(seed)
        b, nkv, g, hd, bs, max_blk = 3, 2, 2, 8, 4, 5
        nblocks = 1 + b * max_blk
        q = jnp.asarray(r.normal(size=(b, nkv, g, hd)), jnp.float32)
        kp = jnp.asarray(r.normal(size=(nblocks, bs, nkv, hd)) * 0.3,
                         jnp.float32).astype(dtype)
        vp = jnp.asarray(r.normal(size=(nblocks, bs, nkv, hd)) * 0.3,
                         jnp.float32).astype(dtype)
        # a scrambled (non-contiguous) physical page assignment
        perm = r.permutation(np.arange(1, nblocks))
        bt = jnp.asarray(perm[: b * max_blk].reshape(b, max_blk), jnp.int32)
        lens = jnp.asarray([3, 7, 20], jnp.int32)
        return q, kp, vp, bt, lens

    def test_paged_kernel_matches_ref(self):
        q, kp, vp, bt, lens = self._pages()
        out = decode_gqa_paged(q, kp, vp, bt, lens, interpret=True)
        ref = decode_gqa_paged_ref(q, kp, vp, bt, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.float8_e4m3fn])
    def test_paged_equals_contiguous(self, dtype):
        """Gathering pages through the table == the contiguous kernel
        on the gathered cache, bit-for-bit (same block accumulation
        order), for full-precision and narrow f8 KV."""
        q, kp, vp, bt, lens = self._pages(dtype)
        b, max_blk = bt.shape
        bs = kp.shape[1]
        paged = decode_gqa_paged(q, kp, vp, bt, lens, interpret=True)
        k = kp[bt].reshape(b, max_blk * bs, *kp.shape[2:])
        v = vp[bt].reshape(b, max_blk * bs, *vp.shape[2:])
        cont = decode_gqa(q, k, v, lens, block_s=bs)
        np.testing.assert_array_equal(np.asarray(paged), np.asarray(cont))

    def test_oracle_path_matches_kernel(self):
        """The CPU-default oracle path (interpret=None) == kernel."""
        q, kp, vp, bt, lens = self._pages()
        auto = decode_gqa_paged(q, kp, vp, bt, lens)
        forced = decode_gqa_paged(q, kp, vp, bt, lens, interpret=True)
        np.testing.assert_allclose(np.asarray(auto), np.asarray(forced),
                                   rtol=1e-5, atol=1e-5)

    def test_zero_length_slot_returns_zeros(self):
        q, kp, vp, bt, _ = self._pages()
        lens = jnp.asarray([0, 5, 0], jnp.int32)
        for interpret in (True, None):
            out = np.asarray(decode_gqa_paged(q, kp, vp, bt, lens,
                                              interpret=interpret))
            assert np.all(out[0] == 0) and np.all(out[2] == 0)
            assert np.any(out[1] != 0)


# ---------------------------------------------------------- allocator --

class TestBlockAllocator:
    def test_trash_page_never_allocated(self):
        a = BlockAllocator(8)
        a.reserve(7)
        got = a.alloc(7)
        assert TRASH_PAGE not in got
        assert sorted(got) == list(range(1, 8))

    def test_free_returns_blocks(self):
        a = BlockAllocator(8)
        a.reserve(3)
        blocks = a.alloc(3)
        assert a.free_blocks == 4
        a.free(blocks)
        assert a.free_blocks == 7
        assert a.blocks_in_use == 0

    def test_reservation_guards_admission(self):
        a = BlockAllocator(8)   # 7 usable
        a.reserve(5)
        assert not a.can_reserve(3)
        assert a.can_reserve(2)
        with pytest.raises(RuntimeError):
            a.reserve(3)
        # unreserved allocation cannot eat into reservations
        with pytest.raises(RuntimeError):
            a.alloc(3, reserved=False)

    def test_alloc_beyond_reservation_raises(self):
        a = BlockAllocator(8)
        a.reserve(2)
        a.alloc(2)
        with pytest.raises(RuntimeError):
            a.alloc(1)   # reservation exhausted

    def test_peak_tracking(self):
        a = BlockAllocator(16)
        a.reserve(10)
        blocks = a.alloc(10)
        a.free(blocks[:6])
        assert a.peak_in_use == 10
        assert a.blocks_in_use == 4


class TestPagedKVCache:
    def _cache(self, **kw):
        args = dict(num_layers=2, num_kv_heads=2, head_dim=8, num_slots=2,
                    block_size=4, num_blocks=16, max_blocks_per_seq=6)
        args.update(kw)
        return PagedKVCache(**args)

    def test_bind_grow_release_cycle(self):
        c = self._cache()
        c.allocator.reserve(4)
        c.bind_slot(0, prompt_tokens=6)          # 2 blocks
        assert len(c.slot_blocks[0]) == 2 and c.lengths[0] == 6
        c.lengths[0] = 8                          # simulate decode to pos 8
        c.ensure_capacity(0)                      # crosses into block 3
        assert len(c.slot_blocks[0]) == 3
        freed = c.release_slot(0)
        assert freed == 3
        assert c.allocator.blocks_in_use == 0
        assert np.all(c.block_tables[0] == TRASH_PAGE)

    def test_view_subset_and_bytes(self):
        c = self._cache()
        c.allocator.reserve(2)
        c.bind_slot(1, prompt_tokens=5)
        v = c.view(slots=[1])
        assert v.block_tables.shape == (1, 6)
        assert int(v.lengths[0]) == 5
        assert c.kv_bytes_in_use() == 2 * c.bytes_per_block
        contig = PagedKVCache.contiguous_bytes(2, 24, 2, 2, 8, "float32")
        assert c.kv_bytes_in_use() < contig


# ------------------------------------------------- model entry points --

class TestPagedModelPath:
    def test_prefill_into_cache_matches_contiguous_prefill(self):
        cfg = tiny_cfg()
        api = mapi.get_model(cfg)
        params = api.init(jax.random.PRNGKey(0), dtype=jnp.float32)
        rng = np.random.default_rng(3)
        plen, s_pad, bs = 11, 16, 4
        prompt = rng.integers(0, cfg.vocab_size, plen)
        toks = np.zeros((1, s_pad), np.int32)
        toks[0, :plen] = prompt

        cache = PagedKVCache(num_layers=cfg.num_layers,
                             num_kv_heads=cfg.num_kv_heads,
                             head_dim=cfg.resolved_head_dim, num_slots=1,
                             block_size=bs, num_blocks=8,
                             max_blocks_per_seq=4)
        cache.allocator.reserve(3)
        cache.bind_slot(0, plen)
        logits, view = api.prefill_into_cache(
            params, jnp.asarray(toks), cache.view(), cfg)

        ref_logits, ref_cache = api.prefill(
            params, jnp.asarray(prompt[None, :], jnp.int32), cfg, 32,
            cache_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(logits[0, -1]),
                                   np.asarray(ref_logits[0, -1]),
                                   rtol=2e-5, atol=2e-5)
        # gathered pages == the contiguous cache prefix, every layer
        tbl = np.asarray(view.block_tables[0, :3])
        got_k = np.asarray(view.k_pages[:, tbl]).reshape(
            cfg.num_layers, 12, cfg.num_kv_heads, -1)[:, :plen]
        ref_k = np.asarray(ref_cache["k"])[:, 0, :plen]
        np.testing.assert_allclose(got_k, ref_k, rtol=2e-5, atol=2e-5)

    # Four slots over pages of 4: slot 1 reads page 1 as a prefix shared
    # with slot 0 through the table; slot 2 is idle.  Per step: the
    # chunk width, each row's first position, and a count per row (the
    # prompt length for prefill, the window's valid tokens for verify;
    # 0 marks an idle decode row).
    TABLES = [[1, 2, 3, 0], [1, 4, 0, 0], [0, 0, 0, 0], [5, 0, 0, 0]]
    STEPS = {"decode": (1, [9, 6, 0, 2], [9, 6, 0, 2]),
             "prefill": (8, [4, 4, 0, 0], [9, 6, 0, 2]),
             "verify": (4, [9, 6, 0, 2], [3, 2, 0, 1])}

    @pytest.mark.parametrize("kv", ["f8", "codes"])
    @pytest.mark.parametrize("step", ["decode", "prefill", "verify"])
    def test_step_writes_only_addressed_cells(self, step, kv, tmp_path):
        """A paged step writes each new K/V at (layer, page, offset) of
        the block table and nowhere else: every other cell of both pools
        is bit-identical after the step (the trash page's first offset
        absorbs idle rows and padding), and the written cells hold what
        a layer-by-layer reference on each layer's own pool writes."""
        cfg = tiny_cfg(num_layers=3)
        api = mapi.get_model(cfg)
        params = api.init(jax.random.PRNGKey(0), dtype=jnp.float32)
        if kv == "codes":
            params, _ = calibrate_act_quant(
                api, params, cfg, bits=7, path=str(tmp_path / "calib.json"))
        dtype = jnp.uint8 if kv == "codes" else jnp.float8_e4m3fn
        width, start, n = self.STEPS[step]
        rng = np.random.default_rng(7)
        n_layers, n_pages, bs = cfg.num_layers, 8, 4
        shape = (n_layers, n_pages, bs, cfg.num_kv_heads,
                 cfg.resolved_head_dim)
        if kv == "codes":
            pools = [jnp.asarray(rng.integers(0, 256, shape), jnp.uint8)
                     for _ in range(2)]
        else:
            pools = [jnp.asarray(rng.normal(size=shape), jnp.float32)
                     .astype(dtype) for _ in range(2)]
        tables = jnp.asarray(self.TABLES, jnp.int32)
        start = jnp.asarray(start, jnp.int32)
        n = jnp.asarray(n, jnp.int32)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, width)),
                             jnp.int32)
        if step == "decode":
            active = n > 0
            view = PagedView(*pools, tables, start)
            _, out = jax.jit(api.decode_step_paged, static_argnums=4)(
                params, view, tokens, active, cfg)
            valid = active.astype(jnp.int32)
        elif step == "prefill":
            view = PagedView(*pools, tables, n)
            _, out = jax.jit(api.prefill_into_cache, static_argnums=3)(
                params, tokens, view, cfg, start)
            valid = jnp.clip(n - start, 0, width)
        else:
            view = PagedView(*pools, tables, start)
            *_, out = jax.jit(api.spec_verify_into_cache,
                              static_argnums=3)(
                params, tokens, view, cfg, start, n)
            valid = n

        positions = start[:, None] + jnp.arange(width)[None, :]
        ok = jnp.arange(width)[None, :] < valid[:, None]
        page = jnp.where(ok, jnp.take_along_axis(
            tables, positions // bs, axis=1), 0)
        off = jnp.where(ok, positions % bs, 0)
        kv_lens = jnp.where(valid > 0, start + valid, 0)

        @jax.jit
        def per_layer(params, k_pages, v_pages):
            x = L.embed_tokens(params["embed"], tokens, cfg)
            ks, vs = [], []
            for l in range(n_layers):
                p = jax.tree_util.tree_map(lambda a: a[l], params["blocks"])
                aq = p.get("act_q")
                h = L.apply_norm(p["ln1"], x, cfg)
                k_new, v_new = L.self_kv(p["attn"], h, cfg, positions,
                                         act_q=aq)
                if kv == "codes":
                    k_new, v_new = L.encode_kv_codes(k_new, v_new, aq)
                k = k_pages[l].at[page, off].set(k_new.astype(dtype))
                v = v_pages[l].at[page, off].set(v_new.astype(dtype))
                if step == "decode":
                    attn = L.mha_decode_paged(p["attn"], h, cfg, positions,
                                              k, v, tables, kv_lens,
                                              act_q=aq)
                else:
                    attn = L.mha_prefill_paged(p["attn"], h, cfg,
                                               positions, k, v, tables,
                                               start, kv_lens, act_q=aq)
                x = x + attn
                x = x + L.apply_mlp(p["mlp"], L.apply_norm(p["ln2"], x, cfg),
                                    cfg, act_q=aq)
                ks.append(k)
                vs.append(v)
            return jnp.stack(ks), jnp.stack(vs)

        ref = per_layer(params, *pools)
        written = np.zeros(shape[:3], bool)
        for b, s in zip(*np.nonzero(np.asarray(ok))):
            written[:, int(page[b, s]), int(off[b, s])] = True
        assert written.sum() == n_layers * int(valid.sum())
        trash = np.zeros(shape[:3], bool)
        trash[:, TRASH_PAGE, 0] = True
        bits = lambda a: np.asarray(a).view(np.uint8)
        for before, after, want in zip(pools, (out.k_pages, out.v_pages),
                                       ref):
            same = ~written & ~trash
            np.testing.assert_array_equal(bits(after)[same],
                                          bits(before)[same])
            np.testing.assert_array_equal(bits(after)[written],
                                          bits(want)[written])
            # the step really wrote: the cells it owns changed somewhere
            assert np.any(bits(after)[written] != bits(before)[written])


# -------------------------------------------------------------- engine --

class TestEngine:
    LENS = (8, 32, 128, 8, 32, 17)
    NEWS = (6, 4, 8, 3, 12, 5)

    def _serve_both(self, cfg, lens, news, **srv_kw):
        reqs = mixed_requests(cfg, lens, news)
        srv = InferenceServer(cfg, num_slots=3, block_size=8,
                              max_len=max(l + n for l, n in zip(lens, news)),
                              **srv_kw)
        fresh = lambda: [Request(r.uid, r.prompt, r.max_new_tokens,
                                 r.stop_token) for r in reqs]
        ref = srv.generate_bucketed(fresh())
        out = srv.generate(fresh())
        return srv, ref, out

    def test_mixed_stream_token_identical_to_bucketed(self):
        """The acceptance property: prompts of 8/32/128 (+ off-bucket
        lengths) with differing max_new_tokens, continuous batching
        over 3 slots == the legacy bucketed batch path, token for
        token — while peak KV stays below the contiguous footprint."""
        cfg = tiny_cfg()
        srv, ref, out = self._serve_both(cfg, self.LENS, self.NEWS)
        assert [c.uid for c in out] == [c.uid for c in ref]
        for a, b in zip(ref, out):
            np.testing.assert_array_equal(a.tokens, b.tokens)
        eng = srv.last_engine
        contig = PagedKVCache.contiguous_bytes(
            len(self.LENS), srv.max_len, cfg.num_layers, cfg.num_kv_heads,
            cfg.resolved_head_dim, srv.kv_dtype)
        assert 0 < eng.cache.peak_kv_bytes() < contig
        # retirement moved every page into the prefix trie (nothing is
        # owned by a slot any more) and the partition invariant holds
        assert eng.cache.allocator.blocks_in_use == eng.prefix.num_pages
        assert eng.cache.allocator.reserved == 0
        eng.check_partition()

    def test_f8_kv_pages_match_f8_bucketed(self):
        """f8 pages quantize KV once, at write time; *every* attend —
        prefill chunks included — dequantizes the narrow bytes
        in-kernel.  The bucketed baseline instead attends the prompt in
        full precision and only stores f8, so the paged path carries
        one extra rounding through prefill and greedy tokens diverge
        within tolerance rather than bit-for-bit."""
        cfg = tiny_cfg()
        _, ref, out = self._serve_both(cfg, self.LENS[:4], self.NEWS[:4],
                                       kv_dtype="float8_e4m3fn")
        agree = np.mean([np.mean(a.tokens == b.tokens)
                         for a, b in zip(ref, out)])
        assert agree >= 0.8, agree

    def test_f8_chunked_equals_unchunked(self):
        """The internal-consistency property the quantize-at-write
        semantic buys: a position's KV reads back identically whichever
        chunk wrote it, so the f8 engine is token-identical at any
        chunk size."""
        cfg = tiny_cfg()
        reqs = mixed_requests(cfg, self.LENS[:4], self.NEWS[:4])
        outs = []
        for chunk in (256, 8):
            eng = Engine(cfg, engine=EngineConfig(
                num_slots=3, block_size=8, max_seq_len=192,
                prefill_chunk=chunk), kv_dtype="float8_e4m3fn")
            outs.append(eng.generate(
                [Request(r.uid, r.prompt, r.max_new_tokens)
                 for r in reqs]))
        for a, b in zip(*outs):
            np.testing.assert_array_equal(a.tokens, b.tokens)

    def test_block_boundary_crossing_mid_decode(self):
        """A sequence whose decode run crosses page boundaries keeps
        producing the bucketed path's tokens, growing one page at a
        time."""
        cfg = tiny_cfg()
        reqs = mixed_requests(cfg, [6], [12])   # crosses 8 and 16 at bs=8
        eng = Engine(cfg, engine=EngineConfig(num_slots=1, block_size=8,
                                              max_seq_len=32))
        eng.submit(reqs[0])
        eng.step()                               # prefill + first decode
        assert len(eng.cache.slot_blocks[0]) == 1    # 6+1 tokens, 1 page
        grown = []
        while eng.pending:
            eng.step()
            grown.append(len(eng.cache.slot_blocks[0]))
        assert 2 in grown                        # grew one page at a time
        assert eng.cache.allocator.peak_in_use == 3   # 17 written slots
        srv = InferenceServer(cfg, params=eng.params, max_len=32)
        ref = srv.generate_bucketed(mixed_requests(cfg, [6], [12]))
        np.testing.assert_array_equal(
            eng.result(0).tokens, ref[0].tokens)

    def test_stop_token_retirement_frees_blocks(self):
        """With the prefix cache off, retirement returns pages to the
        free list (the trie-retention variant lives in
        test_prefix_cache.py)."""
        cfg = tiny_cfg()
        eng = Engine(cfg, engine=EngineConfig(num_slots=2, block_size=8,
                                              max_seq_len=64,
                                              prefix_cache=False))
        probe = Engine(cfg, params=eng.params,
                       engine=EngineConfig(num_slots=1, block_size=8,
                                           max_seq_len=64,
                                           prefix_cache=False))
        reqs = mixed_requests(cfg, [16, 24], [20, 20])
        stop = int(probe.generate([reqs[0]])[0].tokens[2])

        eng.submit(Request(0, reqs[0].prompt, 20, stop_token=stop))
        eng.submit(Request(1, reqs[1].prompt, 20))
        in_use = []
        while eng.pending:
            eng.step()
            in_use.append(eng.cache.allocator.blocks_in_use)
        a = eng.result(0)
        assert a.tokens[-1] == stop and len(a.tokens) < 20
        srv = InferenceServer(cfg, params=eng.params, max_len=64)
        ref = srv.generate_bucketed(
            [Request(0, reqs[0].prompt, 20, stop_token=stop)])
        np.testing.assert_array_equal(a.tokens, ref[0].tokens)
        # after uid 0 retires its pages return while uid 1 keeps running
        assert min(in_use[:-1]) < max(in_use)
        assert eng.cache.allocator.blocks_in_use == 0
        assert eng.cache.allocator.reserved == 0

    def test_retired_slots_stop_consuming_decode(self):
        """The _run_bucket over-decoding fix: a short request retires
        after its own steps instead of riding the batch to
        max(max_new_tokens), and timings are per-request."""
        cfg = tiny_cfg()
        reqs = mixed_requests(cfg, [8, 8], [2, 10])
        eng = Engine(cfg, engine=EngineConfig(num_slots=2, block_size=8,
                                              max_seq_len=32))
        out = eng.generate(reqs)
        short, long_ = out
        assert short.decode_steps == 1           # 2 tokens: prefill + 1 step
        assert long_.decode_steps == 9
        assert eng.total_decode_steps == 9       # not 2 * 9
        assert short.decode_s < long_.decode_s
        assert short.prefill_s > 0 and long_.prefill_s > 0

    def test_stream_yields_run_tokens(self):
        cfg = tiny_cfg()
        reqs = mixed_requests(cfg, [8, 32], [6, 4])
        eng = Engine(cfg, engine=EngineConfig(num_slots=2, block_size=8,
                                              max_seq_len=64))
        h0 = eng.submit(reqs[0])
        eng.submit(reqs[1])
        streamed = list(eng.stream(h0))
        done = eng.run()
        np.testing.assert_array_equal(streamed, done[0].tokens)
        assert len(done) == 2                    # uid 1 finished too
        srv = InferenceServer(cfg, params=eng.params, max_len=64)
        ref = srv.generate_bucketed(mixed_requests(cfg, [8, 32], [6, 4]))
        np.testing.assert_array_equal(streamed, ref[0].tokens)

    def test_more_requests_than_slots_admits_continuously(self):
        cfg = tiny_cfg()
        lens = [8, 8, 8, 8, 8, 8]
        news = [2, 2, 8, 2, 2, 2]
        reqs = mixed_requests(cfg, lens, news)
        eng = Engine(cfg, engine=EngineConfig(num_slots=2, block_size=8,
                                              max_seq_len=32,
                                              prefix_cache=False))
        out = eng.generate(reqs)
        assert [c.uid for c in out] == list(range(6))
        srv = InferenceServer(cfg, params=eng.params, max_len=32)
        ref = srv.generate_bucketed(mixed_requests(cfg, lens, news))
        for a, b in zip(ref, out):
            np.testing.assert_array_equal(a.tokens, b.tokens)
        # with 2 slots the whole stream never co-resides: peak pool
        # usage is bounded by the slots, not the 6 requests
        assert eng.cache.allocator.peak_in_use <= 2 * eng.cache.blocks_for(16)

    def test_engine_reuse_across_batches(self):
        """A long-lived engine: run() returns only the new batch's
        completions (earlier ones were collected and pruned), and uids
        become reusable after collection."""
        cfg = tiny_cfg()
        eng = Engine(cfg, engine=EngineConfig(num_slots=2, block_size=8,
                                              max_seq_len=32))
        first = eng.generate(mixed_requests(cfg, [8, 8], [4, 4]))
        assert [c.uid for c in first] == [0, 1]
        second = eng.generate(mixed_requests(cfg, [8], [4]))
        assert [c.uid for c in second] == [0]      # uid 0 reusable, no leak
        np.testing.assert_array_equal(first[0].tokens, second[0].tokens)
        assert eng.result(1) is None               # pruned after collection

    def test_max_new_zero_is_score_only(self):
        """max_new_tokens=0 emits no tokens, matching the bucketed
        path's empty completion for such requests."""
        cfg = tiny_cfg()
        eng = Engine(cfg, engine=EngineConfig(num_slots=1, block_size=8,
                                              max_seq_len=32))
        out = eng.generate(mixed_requests(cfg, [8], [0]))
        assert len(out) == 1 and out[0].tokens.size == 0
        srv = InferenceServer(cfg, params=eng.params, max_len=32)
        ref = srv.generate_bucketed(mixed_requests(cfg, [8], [0]))
        assert ref[0].tokens.size == 0
        # the scored prompt's page went to the trie, not a slot
        assert eng.cache.allocator.blocks_in_use == eng.prefix.num_pages
        eng.check_partition()

    def test_submit_validation(self):
        cfg = tiny_cfg()
        eng = Engine(cfg, engine=EngineConfig(num_slots=1, block_size=8,
                                              max_seq_len=16))
        r = mixed_requests(cfg, [8], [4])[0]
        eng.submit(r)
        with pytest.raises(ValueError):
            eng.submit(r)                        # duplicate uid
        with pytest.raises(ValueError):
            eng.submit(Request(7, r.prompt, max_new_tokens=64))  # too long

    def test_unsupported_family_raises(self):
        cfg = get_config("recurrentgemma-2b", tiny=True)
        with pytest.raises(ValueError):
            Engine(cfg)

    def test_server_falls_back_for_unsupported_family(self):
        cfg = get_config("recurrentgemma-2b", tiny=True)
        srv = InferenceServer(cfg, max_len=32)
        reqs = mixed_requests(cfg, [8, 8], [4, 4])
        out = srv.generate(reqs)
        assert [c.uid for c in out] == [0, 1]
        assert all(len(c.tokens) == 4 for c in out)
