"""DeepSeek-V2-Lite on the Engine's paged path, at the tiny preset:
YaRN rope, absorbed latent attention, the dropless expert layer that
knows its share, the latent page pool, and served logits against the
benchmark's plain reference (``bench/architectures/deepseek_v2.py``)."""

import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.harness import weights  # noqa: E402
from bench.harness.spec import load_module  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import api as mapi  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import moe as M  # noqa: E402
from repro.runtime.engine import Engine, EngineConfig, Request  # noqa: E402
from repro.runtime.paged_cache import PagedKVCache  # noqa: E402

ARCH = "deepseek-v2-lite"
DS = load_module(ROOT / "bench" / "architectures" / "deepseek_v2.py",
                 "bench_architectures.deepseek_v2")


def tiny(**kw):
    return get_config(ARCH, tiny=True).replace(**kw)


def tiny_file_config(cfg, kv="float8_e4m3fn") -> dict:
    """The benchmark's configuration file of the tiny preset."""
    c = json.loads((ROOT / "bench" / "configs"
                    / "deepseek-v2-lite-e16.json").read_text())
    c.update({"preset": "tiny", "hidden_size": cfg.d_model,
              "intermediate_size": cfg.dense_d_ff,
              "moe_intermediate_size": cfg.d_ff,
              "kv_lora_rank": cfg.kv_lora_rank,
              "qk_nope_head_dim": cfg.qk_nope_head_dim,
              "qk_rope_head_dim": cfg.qk_rope_head_dim,
              "v_head_dim": cfg.v_head_dim,
              "num_attention_heads": cfg.num_heads,
              "num_key_value_heads": cfg.num_kv_heads,
              "num_hidden_layers": cfg.num_layers,
              "vocab_size": cfg.vocab_size,
              "n_routed_experts_published": cfg.num_experts,
              "experts_held": list(cfg.held_experts),
              "n_routed_experts": cfg.held_experts[1] - cfg.held_experts[0],
              "num_experts_per_tok": cfg.experts_per_token})
    c["representation"] = dict(c["representation"], kv=kv)
    return c


# ------------------------------------------------------------------ yarn

def test_yarn_frequencies_and_scale_by_hand():
    """The published formula, computed here with plain floats: dims of
    the ramp floor(64 ln(4096 / (32 * 2 pi)) / (2 ln 1e4)) = 10 and
    ceil(64 ln(4096 / (2 pi)) / (2 ln 1e4)) = 23; below the ramp the
    original frequencies, above it those over the factor 40."""
    cfg = get_config(ARCH)
    inv = L.yarn_inv_freq(cfg)
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(1e4)))
    high = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi))
                     / (2 * math.log(1e4)))
    assert (low, high) == (10, 23)
    for i in range(32):
        orig = 1e4 ** (-2 * i / 64)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want = orig / 40 * ramp + orig * (1 - ramp)
        # float32 storage of a float64 computation
        assert inv[i] == pytest.approx(want, rel=1e-6), i
    m = 0.1 * 0.707 * math.log(40) + 1
    assert L.mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m,
                                                     rel=1e-12)
    assert L.yarn_rope_scale(cfg) == 1.0
    # the reference's own formulas agree
    r_inv, r_cos, r_scale = DS.yarn(tiny_file_config(cfg) | {
        "qk_rope_head_dim": 64, "qk_nope_head_dim": 128})
    np.testing.assert_array_equal(r_inv, inv)
    assert (r_cos, r_scale) == (1.0, L.mla_softmax_scale(cfg))


def test_plain_rope_unchanged():
    """Without YaRN the rope is the original formula, bit for bit."""
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 5, 3, 16)),
                    jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(5), (2, 5))
    half = 8
    inv = 1e6 ** (-(jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    want = jnp.concatenate([x[..., :half] * cos - x[..., half:] * sin,
                            x[..., half:] * cos + x[..., :half] * sin], -1)
    np.testing.assert_array_equal(np.asarray(L.rope(x, pos, 1e6)),
                                  np.asarray(want))


# ------------------------------------------------------------------- mla

def test_absorbed_equals_non_absorbed():
    """Absorbed MLA (queries taken into the latent space, MQA over the
    padded cache entries) against per-head k_nope and v decompressed
    from c_kv, at float32: equal to float32 reassociation."""
    cfg = tiny(compute_dtype="float32")
    p = jax.tree_util.tree_map(
        lambda a: a[0],
        mapi.get_model(cfg).init(jax.random.PRNGKey(3))["blocks"])["attn"]
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 12, 64)),
                    jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(12), (2, 12))
    got = L.mla(p, x, cfg, pos, ("causal", None))

    c, n = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    kv = x @ p["wkv_a"]
    c_kv = L.apply_head_rms(p["kv_norm"], kv[..., :c])
    inv = L.yarn_inv_freq(cfg)
    k_pe = L.rope(kv[..., None, c:], pos, cfg.rope_theta, inv)
    q_pe = L.rope(q[..., n:], pos, cfg.rope_theta, inv)
    k_nope = jnp.einsum("btc,hnc->bthn", c_kv, p["wk_b"])
    v = jnp.einsum("btc,hcv->bthv", c_kv, p["wv_b"])
    sc = (jnp.einsum("bshn,bthn->bhst", q[..., :n], k_nope)
          + jnp.einsum("bshr,btr->bhst", q_pe, k_pe[:, :, 0]))
    sc = sc * L.mla_softmax_scale(cfg)
    sc = jnp.where(jnp.tril(jnp.ones((12, 12), bool)), sc, -jnp.inf)
    ctx = jnp.einsum("bhst,bthv->bshv", jax.nn.softmax(sc, -1), v)
    want = jnp.einsum("bshv,hvd->bsd", ctx, p["wo"])
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert err < 1e-5, err


# --------------------------------------------------------------- experts

def _moe_params(cfg, seed=0):
    from repro.models.params import init_params
    return init_params(jax.random.PRNGKey(seed), M.moe_specs(cfg),
                       jnp.float32)


def _share(p, lo, hi):
    return {k: (v[lo:hi] if k in ("w_gate", "w_up", "w_down") else v)
            for k, v in p.items() if k != "shared"}


def test_shares_sum_to_the_uncut_layer():
    """The four chips' shares (2 experts each): their routed parts plus
    the shared experts, counted once, add up to the uncut layer; their
    counts add up to its counts."""
    cfg = tiny(compute_dtype="float32", experts_held=None)
    p = _moe_params(cfg)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(3, 10, 64)),
                    jnp.float32)
    whole, _, counts = M.apply_moe_dropless(p, x, cfg)
    parts, part_counts = [], []
    for lo in range(0, cfg.num_experts, 2):
        y, _, c = M.apply_moe_dropless(_share(p, lo, lo + 2), x,
                                       cfg.replace(experts_held=(lo, lo + 2)))
        parts.append(y)
        part_counts.append(c)
    shared = L.apply_mlp(p["shared"], x, cfg)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.concatenate(part_counts), counts)
    assert int(counts.sum()) == 30 * cfg.experts_per_token
    dense, _ = M.apply_moe_dense(p, x, cfg)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)


def test_no_token_dropped_when_all_pick_the_same_experts():
    """Every token's top-3 is experts 0, 1 and 2: the dropless layer
    serves all 256 of them, as the dense mixture does, where the
    capacity path (at least 128 a expert) drops half."""
    cfg = tiny(compute_dtype="float32", experts_held=None)
    p = _moe_params(cfg)
    router = np.zeros((64, cfg.num_experts), np.float32)
    router[:, :3] = [3.0, 2.0, 1.0]
    p["router"] = jnp.asarray(router)
    x = jnp.abs(jnp.asarray(
        np.random.default_rng(4).normal(size=(2, 128, 64)), jnp.float32))
    y, _, counts = M.apply_moe_dropless(p, x, cfg)
    np.testing.assert_array_equal(counts, [256, 256, 256] + [0] * 5)
    dense, _ = M.apply_moe_dense(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)
    routed, _ = M.apply_moe_routed(
        p, x, cfg.replace(moe_impl="routed", capacity_factor=0.01))
    assert float(jnp.max(jnp.abs(routed - dense))) > 1e-2


def test_topk_renormalisation_follows_config():
    cfg = tiny(compute_dtype="float32", experts_held=None)
    p = _moe_params(cfg)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(7, 64)),
                    jnp.float32)
    _, w_raw, _, _ = M._router(p, x, cfg)
    _, w_norm, _, _ = M._router(p, x, cfg.replace(norm_topk_prob=True))
    np.testing.assert_allclose(np.asarray(w_norm.sum(-1)), 1.0, rtol=1e-6)
    assert float(w_raw.sum(-1).max()) < 1.0
    np.testing.assert_allclose(
        np.asarray(w_norm),
        np.asarray(w_raw / w_raw.sum(-1, keepdims=True)), rtol=1e-6)
    assert get_config("grok-1-314b").norm_topk_prob
    assert get_config("llama4-scout-17b-a16e").norm_topk_prob


# ---------------------------------------------------------------- engine

def _engine(cfg, params=None, **kw):
    ec = dict(num_slots=4, block_size=8, max_seq_len=64, prefill_chunk=8)
    ec.update(kw)
    return Engine(cfg, params=params, engine=EngineConfig(**ec),
                  kv_dtype="float32")


def _requests(cfg, n=3, seed=0, prefix=()):
    rng = np.random.default_rng(seed)
    return [Request(i, np.concatenate([np.asarray(prefix, np.int32),
                                       rng.integers(0, cfg.vocab_size,
                                                    5 + 6 * i)]).astype(
                        np.int32), max_new_tokens=6) for i in range(n)]


def test_padding_rows_route_nowhere():
    """With every expert held, each live token meets exactly top-k held
    experts in each MoE layer, so ``engine.moe.routed_tokens`` is live
    tokens x k x MoE layers: the padding of the full-width prefill
    dispatch and the idle decode slots add nothing."""
    cfg = tiny(experts_held=None)
    eng = _engine(cfg)
    for r in _requests(cfg):
        eng.submit(r)
    done = eng.run()
    live = eng.prefill_tokens_computed + sum(len(c.tokens) - 1
                                             for c in done)
    moe_layers = cfg.num_layers - cfg.first_dense_layers
    assert eng.prefill_dispatched_tokens > eng.prefill_tokens_computed
    assert eng.moe_routed_tokens == live * cfg.experts_per_token * moe_layers
    assert eng.moe_busiest_expert_tokens <= eng.moe_routed_tokens
    assert 0 < eng.moe_active_experts
    reg = eng.telemetry.registry
    assert reg.get("engine.moe.routed_tokens").value == eng.moe_routed_tokens


def test_latent_pool_copy_on_write_export_import():
    cfg = tiny()
    cache = PagedKVCache(num_layers=3, num_kv_heads=4, head_dim=24,
                         num_slots=2, block_size=4, num_blocks=9,
                         max_blocks_per_seq=4, latent_dim=cfg.latent_dim)
    assert cache.v_pages is None
    assert cache.k_pages.shape == (3, 9, 4, cfg.latent_dim)
    assert cache.bytes_per_block == 3 * 4 * cfg.latent_dim * 4
    (a, b) = cache.bind_slot(0, 6, reserved=False)
    fill = jnp.arange(cache.k_pages[:, a].size, dtype=jnp.float32)
    cache.k_pages = cache.k_pages.at[:, a].set(
        fill.reshape(cache.k_pages[:, a].shape))
    crc = cache.page_checksum(a)
    # a second slot shares page a (pinned), then copies it on write
    cache.allocator.incref(a)
    cache.bind_slot(1, 4, shared=[a], reserved=False)
    old, new = cache.cow_slot_page(1, 0)
    assert old == a and new != a
    np.testing.assert_array_equal(np.asarray(cache.k_pages[:, new]),
                                  np.asarray(cache.k_pages[:, a]))
    assert cache.page_checksum(new) == crc
    k, v = cache.export_slot(0)
    assert v is None and k.shape == (3, 2, 4, cfg.latent_dim)
    peer = PagedKVCache(num_layers=3, num_kv_heads=4, head_dim=24,
                        num_slots=1, block_size=4, num_blocks=5,
                        max_blocks_per_seq=4, latent_dim=cfg.latent_dim)
    blocks = peer.import_slot(0, 6, k, v)
    assert peer.page_checksum(blocks[0]) == crc
    peer.corrupt_page(blocks[0])
    assert peer.page_checksum(blocks[0]) != crc


def _cold_tokens(cfg, params, prefix):
    cold = _engine(cfg, params, prefix_cache=False)
    for r in _requests(cfg, prefix=prefix):
        cold.submit(r)
    return {c.uid: c.tokens.tolist() for c in cold.run()}


def test_prefix_hits_and_speculation_keep_tokens():
    """A shared prompt prefix served from the latent pool's trie pages
    (copy-on-write at the boundary), and speculative verification, give
    the tokens a cold engine gives."""
    cfg = tiny()
    params = mapi.get_model(cfg).init(jax.random.PRNGKey(0))
    prefix = np.arange(13) % cfg.vocab_size
    want = _cold_tokens(cfg, params, prefix)
    warm = _engine(cfg, params)
    for r in _requests(cfg, prefix=prefix)[:1]:
        warm.submit(r)
    warm.run()
    for r in _requests(cfg, prefix=prefix)[1:]:
        warm.submit(r)
    got = {c.uid: c.tokens.tolist() for c in warm.run()}
    assert warm.prefix_stats.hits > 0
    assert got == {k: v for k, v in want.items() if k in got}
    spec = _engine(cfg, params, spec_k=2)
    for r in _requests(cfg, prefix=prefix):
        spec.submit(r)
    assert {c.uid: c.tokens.tolist() for c in spec.run()} == want


def test_handoff_of_latent_pages_keeps_tokens():
    """A prefill worker's latent pages imported by a decode worker."""
    cfg = tiny()
    params = mapi.get_model(cfg).init(jax.random.PRNGKey(0))
    prefix = np.arange(13) % cfg.vocab_size
    pre = _engine(cfg, params, role="prefill")
    dec = _engine(cfg, params, role="decode")
    for r in _requests(cfg, prefix=prefix):
        pre.submit(r)
    pre.run()
    assert pre.outbox and pre.outbox[0].v_pages is None
    for h in pre.outbox:
        dec.inject_prefilled(h)
    got = {c.uid: c.tokens.tolist() for c in dec.run()}
    assert got == _cold_tokens(cfg, params, prefix)


def test_engine_admits_the_chip_share():
    """The published preset is one chip's share: experts 0-15 of the
    router's 64, latent pages of 512 + 64 numbers padded to 640 lanes,
    and the Engine's paged path admits it."""
    cfg = get_config(ARCH)
    assert Engine.supports(cfg)
    assert (cfg.num_experts, cfg.held_experts, cfg.latent_dim) == \
        (64, (0, 16), 640)


@pytest.mark.parametrize("kv", ["float32", "float8_e4m3fn"])
def test_paged_prefill_and_decode_match_reference(kv):
    """Weights as the benchmark draws them (codes, a float router); a
    prompt prefilled in chunks through the paged path, then greedy
    decode steps, each step's logits against the reference's full
    forward over the same tokens.

    Tolerance 1e-4 of the logit range: the program at float32
    activations differs from the non-absorbed reference by
    reassociation (1.2e-6 read here); with float8 latent pages both
    round the cached c_kv and k_pe from values equal to float32
    rounding (9.4e-7 read here: no entry lands a float8 step apart).
    The program at bfloat16 activations reads 0.057 (float32 pool) and
    0.093 (float8 pool)."""
    cfg = tiny(compute_dtype="float32")
    config = tiny_file_config(cfg, kv=kv)
    DS.check_widths(cfg, config)
    api = mapi.get_model(cfg)
    params = weights.make_params(api.abstract_params(), config, 7, DS)
    eng = Engine(cfg, params=params, kv_dtype=kv, engine=EngineConfig(
        num_slots=2, block_size=8, max_seq_len=64, prefill_chunk=8))
    prompt = np.random.default_rng(8).integers(0, cfg.vocab_size, 13)
    cache = eng.cache
    cache.bind_slot(0, len(prompt), reserved=False)
    step = jax.jit(api.prefill_into_cache, static_argnums=3)
    tokens, logits = list(prompt), []
    for s0 in range(0, len(prompt), 8):
        chunk = np.zeros((2, 8), np.int32)
        chunk[0, :len(prompt[s0:s0 + 8])] = prompt[s0:s0 + 8]
        start = np.asarray([s0, 0], np.int32)
        lg, view = step(params, jnp.asarray(chunk), cache.view(), cfg,
                        jnp.asarray(start))
        cache.update_pages(view)
    logits.append(np.asarray(lg[0, -1]))
    decode = jax.jit(api.decode_step_paged, static_argnums=4)
    for _ in range(5):
        tokens.append(int(np.argmax(logits[-1])))
        cache.ensure_capacity(0, reserved=False)
        lg, view = decode(params, cache.view(),
                          jnp.asarray([[tokens[-1]], [0]], jnp.int32),
                          jnp.asarray([True, False]), cfg)
        cache.update_pages(view)
        cache.lengths[:] = np.asarray(view.lengths)
        logits.append(np.asarray(lg[0, -1]))
    ref = np.asarray(jax.jit(DS.forward, static_argnums=2)(
        params, jnp.asarray(tokens, jnp.int32), _Frozen(config)))
    ref = ref[len(prompt) - 1:]
    got = np.stack(logits)
    err = np.max(np.abs(got - ref)) / np.max(np.abs(ref))
    assert err < 1e-4, err


class _Frozen(dict):
    """A configuration usable as a static jit argument."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))
