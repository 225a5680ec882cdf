"""Decoder-only transformer LM (olmo/qwen3/minicpm/llama4/grok/paligemma).

Pure-functional, scan-over-layers (HLO depth-independent), KV-cache
serving path, optional MoE blocks, optional multimodal prefix with
prefix-LM masking (PaliGemma).  Every matmul is Lama-quantizable.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as M
from repro.models.params import ParamSpec, stack_specs, scan_blocks


# --------------------------------------------------------------- specs --

def block_specs(cfg: ModelConfig, dense: bool = False) -> dict:
    """One block; ``dense`` is one of an MoE model's leading dense-MLP
    layers."""
    s = {
        "ln1": L.norm_specs(cfg),
        "attn": L.mla_specs(cfg) if cfg.is_mla else L.attention_specs(cfg),
        "ln2": L.norm_specs(cfg),
    }
    if dense:
        s["mlp"] = L.mlp_specs(cfg, cfg.dense_d_ff or cfg.d_ff)
    elif cfg.is_moe:
        s["moe"] = M.moe_specs(cfg)
    else:
        s["mlp"] = L.mlp_specs(cfg)
    return s


def model_specs(cfg: ModelConfig) -> dict:
    """``blocks`` are the layers stacked for the layer scan; an MoE
    model's leading dense layers stack apart, as ``dense_blocks``."""
    n_dense = cfg.first_dense_layers
    s = {
        "embed": L.embed_specs(cfg),
        "blocks": stack_specs(block_specs(cfg), cfg.num_layers - n_dense),
        "ln_f": L.norm_specs(cfg),
    }
    if n_dense:
        s["dense_blocks"] = stack_specs(block_specs(cfg, dense=True),
                                        n_dense)
    s.update({"unembed": L.unembed_specs(cfg)} if not cfg.tie_embeddings else {})
    return s


def _segments(params) -> list:
    """The stacked block params of each run of like layers, in layer
    order: the leading dense layers (where a model has them), then the
    rest."""
    return [params[k] for k in ("dense_blocks", "blocks") if k in params]


def _scan_segments(body, carry, params, cfg, xs=None):
    """``scan_blocks`` over every segment in layer order.  ``xs``
    (leaves stacked over all layers) are split at the segment bounds;
    per-layer outputs are joined back along the layer axis."""
    segs = _segments(params)
    if len(segs) == 1:
        inp = segs[0] if xs is None else (segs[0], *xs)
        return scan_blocks(body, carry, inp, cfg)
    outs, first = [], 0
    for seg in segs:
        n = jax.tree_util.tree_leaves(seg)[0].shape[0]
        inp = seg if xs is None else (seg, *(x[first:first + n]
                                             for x in xs))
        carry, ys = scan_blocks(body, carry, inp, cfg)
        outs.append(ys)
        first += n
    if outs[0] is None:
        return carry, None
    return carry, jax.tree_util.tree_map(
        lambda *a: jnp.concatenate(a), *outs)


# --------------------------------------------------------------- cache --

def _cache_shapes(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """``k``/``v`` ``[L, B, T, n_kv, hd]``; MLA: one latent ``k``
    ``[L, B, T, latent_dim]``."""
    if cfg.is_mla:
        return {"k": (cfg.num_layers, batch, max_len, cfg.latent_dim)}
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads,
             cfg.resolved_head_dim)
    return {"k": shape, "v": shape}


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    out = {k: jnp.zeros(v, dtype)
           for k, v in _cache_shapes(cfg, batch, max_len).items()}
    return {**out, "pos": jnp.zeros((), jnp.int32)}


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    out = {k: jax.ShapeDtypeStruct(v, dtype)
           for k, v in _cache_shapes(cfg, batch, max_len).items()}
    return {**out, "pos": jax.ShapeDtypeStruct((), jnp.int32)}


# ------------------------------------------------------------- forward --

def _block(p, x, cfg: ModelConfig, positions, mask, kv=None):
    """One transformer block; returns (y, aux_loss, new_kv).

    ``kv`` merges this step's K,V into the cache view handed to
    attention (decode-with-cache); None lets mha derive K,V itself.
    When the block params carry calibrated ``act_q`` tables (DNA-TEQ
    activation quantization), the matmul inputs are encoded at their
    sites and dispatch dual-LUT — the residual stream stays float (the
    norms need it), everything feeding a quantized matmul crosses HBM
    as uint8 codes."""
    aq = p.get("act_q")
    h = L.apply_norm(p["ln1"], x, cfg)
    if cfg.is_mla:
        new_kv = L.mla_latent(p["attn"], h, cfg, positions)
        attn = L.mla(p["attn"], h, cfg, positions, mask, latent=kv)
    else:
        new_kv = L.self_kv(p["attn"], h, cfg, positions, act_q=aq)
        attn = L.mha(p["attn"], h, cfg, positions, mask, kv=kv, act_q=aq)
    x = x + attn
    h = L.apply_norm(p["ln2"], x, cfg)
    y, aux, _ = _ffn(p, h, cfg, aq)
    return x + y, aux, new_kv


def forward(
    params,
    tokens: jax.Array,                 # [B, S] int32
    cfg: ModelConfig,
    prefix_embeds: jax.Array | None = None,   # [B, P, D] (vlm/audio stub)
) -> tuple[jax.Array, jax.Array]:
    """Full-sequence forward.  Returns (logits [B,S',V], aux_loss)."""
    x = L.embed_tokens(params["embed"], tokens, cfg)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    x = L.constrain_act(x)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    if prefix_embeds is not None:
        mask = ("prefix", prefix_embeds.shape[1])
    else:
        mask = ("causal", None)

    def body(carry, blk_params):
        x, aux = carry
        y, a, _ = _block(blk_params, x, cfg, positions, mask)
        return (L.constrain_act(y), aux + a), None

    body_fn = jax.checkpoint(body) if cfg.remat == "block" else body
    (x, aux), _ = _scan_segments(body_fn, (x, jnp.zeros((), jnp.float32)),
                                 params, cfg)
    x = L.apply_norm(params["ln_f"], x, cfg)
    return L.logits_fn(params, x, cfg), aux / max(cfg.num_layers, 1)


def prefill(
    params,
    tokens: jax.Array,                 # [B, S]
    cfg: ModelConfig,
    max_len: int,
    prefix_embeds: jax.Array | None = None,
    cache_dtype=jnp.bfloat16,
):
    """Run the prompt, build the KV cache.  Returns (last_logits, cache)."""
    x = L.embed_tokens(params["embed"], tokens, cfg)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    if prefix_embeds is not None:
        mask = ("prefix", prefix_embeds.shape[1])
    else:
        mask = ("causal", None)

    def pad(a):
        widths = [(0, 0), (0, max_len - s)] + [(0, 0)] * (a.ndim - 2)
        return jnp.pad(a, widths).astype(cache_dtype)

    def body(carry, blk_params):
        x, aux = carry
        y, a, new_kv = _block(blk_params, x, cfg, positions, mask)
        y = L.constrain_act(y)
        return (y, aux + a), jax.tree_util.tree_map(pad, new_kv)

    (x, _aux), entries = _scan_segments(
        body, (x, jnp.zeros((), jnp.float32)), params, cfg)
    x = L.apply_norm(params["ln_f"], x, cfg)
    logits = L.logits_fn(params, x[:, -1:, :], cfg)
    cache = ({"k": entries} if cfg.is_mla
             else {"k": entries[0], "v": entries[1]})
    return logits, {**cache, "pos": jnp.asarray(s, jnp.int32)}


def decode_step(params, cache, tokens: jax.Array, cfg: ModelConfig):
    """One token step.  tokens: [B, 1].  Returns (logits, new_cache).

    Attention over the cache goes through the flash-decoding
    ``decode_gqa`` kernel (policy-gated): the cache is streamed
    block-wise with in-kernel dequantization, so narrow KV cache dtypes
    (f8e4m3fn) cross HBM as narrow bytes.  ``flash_decode=False`` in the
    :class:`~repro.core.lama_layers.FusedPolicy` restores the dense
    masked attend."""
    from repro.core import lama_layers as ll

    x = L.constrain_act(L.embed_tokens(params["embed"], tokens, cfg))
    b, s, _ = x.shape
    pos = cache["pos"]
    positions = jnp.broadcast_to(pos, (b, s))
    max_len = cache["k"].shape[2]
    kp = jnp.arange(max_len)
    mask = (kp[None, :] <= pos)  # [1, max_len], same for all queries
    mask = jnp.broadcast_to(mask, (s, max_len))
    flash = ll.get_policy().flash_decode and s == 1
    lengths = jnp.broadcast_to(pos + 1, (b,)).astype(jnp.int32)

    def mla_body(carry, layer_in):
        x, = carry
        blk_params, lat_cache = layer_in
        h = L.apply_norm(blk_params["ln1"], x, cfg)
        lat_cache = jax.lax.dynamic_update_slice_in_dim(
            lat_cache, L.mla_latent(blk_params["attn"], h, cfg,
                                    positions).astype(lat_cache.dtype),
            pos, axis=1)
        x = x + L.mla(blk_params["attn"], h, cfg, positions, mask,
                      latent=lat_cache.astype(x.dtype))
        h = L.apply_norm(blk_params["ln2"], x, cfg)
        y, _, _ = _ffn(blk_params, h, cfg)
        return (L.constrain_act(x + y),), lat_cache

    if cfg.is_mla:
        (x,), lat = _scan_segments(mla_body, (x,), params, cfg,
                                   xs=(cache["k"],))
        x = L.apply_norm(params["ln_f"], x, cfg)
        return L.logits_fn(params, x, cfg), {"k": lat, "pos": pos + 1}

    def body(carry, layer_in):
        x, = carry
        blk_params, k_cache, v_cache = layer_in
        aq = blk_params.get("act_q")
        h = L.apply_norm(blk_params["ln1"], x, cfg)
        k_new, v_new = L.self_kv(blk_params["attn"], h, cfg, positions,
                                 act_q=aq)
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k_new.astype(k_cache.dtype), pos, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v_new.astype(v_cache.dtype), pos, axis=1)
        if flash:
            attn = L.mha_decode(blk_params["attn"], h, cfg, positions,
                                k_cache, v_cache, lengths, act_q=aq)
        else:
            attn = L.mha(blk_params["attn"], h, cfg, positions, mask,
                         kv=(k_cache.astype(x.dtype),
                             v_cache.astype(x.dtype)), act_q=aq)
        x = x + attn
        h = L.apply_norm(blk_params["ln2"], x, cfg)
        y, _, _ = _ffn(blk_params, h, cfg, aq)
        return (L.constrain_act(x + y),), (k_cache, v_cache)

    (x,), (ks, vs) = _scan_segments(
        body, (x,), params, cfg, xs=(cache["k"], cache["v"]))
    x = L.apply_norm(params["ln_f"], x, cfg)
    logits = L.logits_fn(params, x, cfg)
    return logits, {"k": ks, "v": vs, "pos": pos + 1}


# ------------------------------------------------------- paged serving --
#
# The engine-facing entry points: instead of *owning* a contiguous
# [L, B, max_len, ...] cache, these take a PagedView (k_pages/v_pages
# page pools + per-sequence block tables + lengths — see
# repro.runtime.paged_cache) and return an updated view.  Memory is the
# engine's concern; the model only reads/writes through the table.

def _scatter_token_kv(pages, new, blk_idx, off):
    """Write one token's KV per sequence into its page.
    pages [P, bs, n_kv, hd]; new [B, n_kv, hd]; blk_idx/off [B]."""
    return pages.at[blk_idx, off].set(new.astype(pages.dtype))


def _ffn(blk_params, h, cfg: ModelConfig, aq=None, valid=None):
    """The block's feed-forward half: ``(y, aux, counts)``.  ``counts``
    are the rows routed to each held expert where the layer is the
    serving (dropless) MoE one, else None; a dense MLP has no loss."""
    if "moe" not in blk_params:
        return (L.apply_mlp(blk_params["mlp"], h, cfg, act_q=aq),
                jnp.zeros((), jnp.float32), None)
    if cfg.moe_impl == "dropless":
        return M.apply_moe_dropless(blk_params["moe"], h, cfg, valid=valid)
    y, a = M.apply_moe(blk_params["moe"], h, cfg, act_q=aq)
    return y, a, None


def _scan_paged(body, carry, params, view, cfg):
    """Run ``body`` over the layers with the page pools as scan carry.

    Each pool ``[L, N, bs, ...]`` is viewed as one flat page array
    ``[L*N, bs, ...]`` (only the major dims merge, so the reshape is a
    bitcast) and carried through the scan: every layer scatters its
    new K/V (or latent entries) into the flat pool in place.  Handed to
    the scan as per-layer ``xs`` instead, each layer's whole pool would
    be sliced out, updated and stacked back, and the stacked result
    copied once more: the whole pool moved every step to write a few
    pages.

    ``body(carry, blk_params, k_pages, v_pages, base)`` gets the flat
    pools (``v_pages`` None for a latent pool) and ``base = layer * N``:
    the layer's page ``p`` is flat page ``base + p`` (its trash page is
    ``base``), so scatter targets and block tables are offset by
    ``base``.  It returns ``(carry, k_pages, v_pages, counts)``, counts
    being the layer's routed rows per held expert or None.  Returns
    ``(carry, view)`` with the updated pools and, for an MoE model,
    ``view.expert_counts`` ``[MoE layers, held experts]``."""
    n_pages = view.k_pages.shape[1]

    def flat(pages):
        if pages is None:
            return None
        return pages.reshape(-1, *pages.shape[2:])

    def step(c, layer_in):
        inner, k_pages, v_pages = c
        blk_params, layer = layer_in
        inner, k_pages, v_pages, counts = body(
            inner, blk_params, k_pages, v_pages, layer * n_pages)
        return (inner, k_pages, v_pages), counts

    c, first = (carry, flat(view.k_pages), flat(view.v_pages)), 0
    for blocks in _segments(params):
        n = jax.tree_util.tree_leaves(blocks)[0].shape[0]
        c, counts = scan_blocks(
            step, c,
            (blocks, jnp.arange(first, first + n, dtype=jnp.int32)), cfg)
        first += n
    carry, k_pages, v_pages = c
    view = view._replace(
        k_pages=k_pages.reshape(view.k_pages.shape),
        v_pages=None if v_pages is None else v_pages.reshape(
            view.v_pages.shape))
    if counts is not None:
        view = view._replace(expert_counts=counts)
    return carry, view


def _chunk_layers(params, x, view, cfg: ModelConfig, start, valid):
    """Every layer over a chunk of tokens per row: row ``b``'s tokens
    sit at absolute positions ``start[b] + i`` and the first
    ``valid[b]`` of them are real.  Per layer, the chunk's K/V (roped at
    their positions), or its latent entries, are scattered per token
    into ``page[pos // bs]``, offset ``pos % bs`` *first* (padding and
    positions past the table go to the trash page), then attention
    reads every written position ``<=`` each query's own straight from
    the pages through the ``flash_prefill_paged`` kernel.  Returns
    ``(x, view)``."""
    b, s, _ = x.shape
    bs = view.block_size
    max_blk = view.block_tables.shape[1]
    # cache positions populated once this chunk's scatter lands; rows
    # with an empty chunk mask everything out (zero attention)
    kv_lens = jnp.where(valid > 0, start + valid, 0)          # [B]
    positions = start[:, None] + jnp.arange(s)[None, :]       # [B, S]
    in_chunk = jnp.arange(s)[None, :] < valid[:, None]        # [B, S]

    # per-token scatter targets: chunk token i of row b lands at page
    # table[b, pos // bs], offset pos % bs; padding and positions past
    # the table go to the trash page.
    tok_ok = in_chunk & (positions // bs < max_blk)           # [B, S]
    col = jnp.where(tok_ok, positions // bs, 0)
    page = jnp.where(tok_ok,
                     jnp.take_along_axis(view.block_tables, col, axis=1),
                     0)                                       # trash page
    off = jnp.where(tok_ok, positions % bs, 0)

    def body(carry, blk_params, k_pages, v_pages, base):
        x, aux = carry
        aq = blk_params.get("act_q")
        p = blk_params["attn"]
        h = L.apply_norm(blk_params["ln1"], x, cfg)
        if cfg.is_mla:
            k_pages = k_pages.at[base + page, off].set(
                L.mla_latent(p, h, cfg, positions).astype(k_pages.dtype))
            attn = L.mla_paged(p, h, cfg, positions, k_pages,
                               view.block_tables + base, start, kv_lens)
        else:
            k_new, v_new = L.self_kv(p, h, cfg, positions, act_q=aq)
            if k_pages.dtype == jnp.uint8:
                # codes-mode cache: quantize-at-write through the
                # per-head attn_k/attn_v metas (a u8 page stores DNA-TEQ
                # codes, and a raw astype would bit-truncate floats into
                # junk codes)
                k_new, v_new = L.encode_kv_codes(k_new, v_new, aq)
            k_pages = k_pages.at[base + page, off].set(
                k_new.astype(k_pages.dtype))
            v_pages = v_pages.at[base + page, off].set(
                v_new.astype(v_pages.dtype))
            attn = L.mha_prefill_paged(p, h, cfg, positions, k_pages,
                                       v_pages, view.block_tables + base,
                                       start, kv_lens, act_q=aq)
        x = x + attn
        h = L.apply_norm(blk_params["ln2"], x, cfg)
        y, a, counts = _ffn(blk_params, h, cfg, aq, in_chunk)
        return (L.constrain_act(x + y), aux + a), k_pages, v_pages, counts

    (x, _aux), view = _scan_paged(
        body, (x, jnp.zeros((), jnp.float32)), params, view, cfg)
    return x, view


def prefill_into_cache(
    params,
    tokens: jax.Array,                 # [B, S] — one prompt chunk per row
    view,                              # PagedView for the dispatched rows
    cfg: ModelConfig,
    start_pos: jax.Array | None = None,   # [B] int32 — abs pos of tokens[:,0]
):
    """Run one chunk of each row's prompt and scatter its KV into the
    paged cache — the ONE prefill path (cold, prefix-cache tail, and
    mid-prompt chunk are all the same call; only ``start_pos`` differs).

    ``tokens[b]`` holds the prompt slice covering absolute positions
    ``[start_pos[b], start_pos[b] + S)`` (``start_pos=None`` means
    zeros: a cold whole-prompt call).  ``view.lengths`` carries the
    *true total* prompt lengths, so the per-row valid token count
    within this chunk is ``clip(lengths - start_pos, 0, S)``; positions
    past it are padding whose KV is redirected to the trash page (and
    which an expert layer routes to no expert).  A row with nothing to
    do (``start_pos >= lengths``, e.g. a decoding or empty slot riding
    in a full-width serving dispatch) writes nothing and returns zero
    attention.

    The caller must size the block table to cover ``view.lengths``
    (the Engine's admission raises when a prompt exceeds
    ``max_blocks_per_seq``): the trash-page redirect exists for
    *padding* overflow only — a valid token past the table would be
    silently dropped, not an error, since the bound is dynamic
    (``start_pos``) and cannot be asserted under jit.

    Per layer (:func:`_chunk_layers`) the chunk's KV lands in the pages
    first and attention reads it back through the paged flash kernel,
    so within-chunk causality and attention over the cached prefix fall
    out of the same positional mask — no ``[B, S, T]`` mask or
    ``[S, T]`` score matrix is ever materialized, and cached prefix
    pages are never gathered into a contiguous buffer.  Writes never
    touch a shared prefix page: the scheduler copy-on-writes the
    boundary page before admission.

    Returns (last_logits [B, 1, V] taken at each row's true last token
    — meaningful only for rows whose final chunk this is — and the
    updated view).
    """
    x = L.embed_tokens(params["embed"], tokens, cfg)
    b, s, _ = x.shape
    start = (jnp.zeros((b,), jnp.int32) if start_pos is None
             else start_pos.astype(jnp.int32))                # [B]
    valid = jnp.clip(view.lengths - start, 0, s)              # [B]
    x, view = _chunk_layers(params, x, view, cfg, start, valid)
    x = L.apply_norm(params["ln_f"], x, cfg)
    idx = jnp.clip(view.lengths - 1 - start, 0, s - 1)
    x_last = jnp.take_along_axis(
        x, idx[:, None, None].astype(jnp.int32), axis=1)      # [B, 1, D]
    logits = L.logits_fn(params, x_last, cfg)
    return logits, view


def decode_step_paged(params, view, tokens: jax.Array, active: jax.Array,
                      cfg: ModelConfig):
    """One continuous-batching decode step over the paged cache.

    tokens: [B, 1] — last sampled token per slot; active: [B] bool.
    Per slot, the new token's KV (or latent entry) is scattered to page
    ``table[len // bs]``, offset ``len % bs`` (inactive slots write the
    trash page and route to no expert), then attention runs through the
    block-table flash-decode kernel with per-slot lengths (+1 for the
    token just written; 0 for inactive slots, which therefore return
    zeros).  Returns (logits [B, 1, V], updated view with active
    lengths +1).
    """
    x = L.constrain_act(L.embed_tokens(params["embed"], tokens, cfg))
    b, s, _ = x.shape
    assert s == 1, s
    bs = view.block_size
    pos = view.lengths                                     # [B]
    positions = pos[:, None]
    blk_col = jnp.clip(pos // bs, 0, view.block_tables.shape[1] - 1)
    blk_idx = jnp.where(
        active,
        jnp.take_along_axis(view.block_tables, blk_col[:, None], axis=1)[:, 0],
        0)                                                 # trash page
    off = jnp.where(active, pos % bs, 0)
    attn_lengths = jnp.where(active, pos + 1, 0).astype(jnp.int32)

    def body(carry, blk_params, k_pages, v_pages, base):
        x, = carry
        aq = blk_params.get("act_q")
        p = blk_params["attn"]
        h = L.apply_norm(blk_params["ln1"], x, cfg)
        if cfg.is_mla:
            k_pages = k_pages.at[base + blk_idx, off].set(
                L.mla_latent(p, h, cfg, positions)[:, 0].astype(
                    k_pages.dtype))
            attn = L.mla_paged(p, h, cfg, positions, k_pages,
                               view.block_tables + base, attn_lengths - 1,
                               attn_lengths)
        else:
            k_new, v_new = L.self_kv(p, h, cfg, positions, act_q=aq)
            if k_pages.dtype == jnp.uint8:
                # codes-mode cache: quantize-at-write (see _chunk_layers)
                k_new, v_new = L.encode_kv_codes(k_new, v_new, aq)
            k_pages = _scatter_token_kv(k_pages, k_new[:, 0],
                                        base + blk_idx, off)
            v_pages = _scatter_token_kv(v_pages, v_new[:, 0],
                                        base + blk_idx, off)
            attn = L.mha_decode_paged(p, h, cfg, positions, k_pages,
                                      v_pages, view.block_tables + base,
                                      attn_lengths, act_q=aq)
        x = x + attn
        h = L.apply_norm(blk_params["ln2"], x, cfg)
        y, _, counts = _ffn(blk_params, h, cfg, aq, active[:, None])
        return (L.constrain_act(x + y),), k_pages, v_pages, counts

    (x,), view = _scan_paged(body, (x,), params, view, cfg)
    x = L.apply_norm(params["ln_f"], x, cfg)
    logits = L.logits_fn(params, x, cfg)
    new_lengths = jnp.where(active, pos + 1, pos).astype(jnp.int32)
    return logits, view._replace(lengths=new_lengths)


def spec_verify_into_cache(
    params,
    tokens: jax.Array,                 # [B, S] — next token + k drafts
    view,                              # PagedView for the dispatched rows
    cfg: ModelConfig,
    start_pos: jax.Array,              # [B] int32 — abs pos of tokens[:,0]
    n_tokens: jax.Array,               # [B] int32 — valid tokens per row
):
    """Score a speculative window — the engine's verify-and-commit
    dispatch.  ``tokens[b]`` is the row's *undecoded* next token
    followed by up to ``S-1`` drafted continuations; ``n_tokens[b]``
    of them are real (0 parks an idle row in a mixed tick, 1 is an
    ordinary single-token decode step riding the spec dispatch).

    Mechanically this is :func:`prefill_into_cache` with the valid
    count supplied by the caller instead of derived from
    ``view.lengths`` (both run :func:`_chunk_layers`): every valid
    position's KV scatters into the pages first (codes-mode pages
    quantize-at-write, padding goes to the trash page), then the
    chunked flash kernel attends each position against the cached
    prefix plus the window's own causal left — so position ``i``'s
    logits are computed *as if* drafts ``< i`` were already accepted.

    The greedy commit happens in-dispatch (one host round-trip per
    tick, same policy as the decode step): returns

    - ``greedy [B, S]`` — argmax token at every window position,
    - ``accept [B]`` — leading run length where the model's argmax
      reproduces the drafts (``0 <= accept <= n_tokens-1``); the
      engine commits ``drafts[:accept]`` plus ``greedy[accept]``,
    - ``ok [B]`` — all-finite logits over the row's valid positions
      (vacuously True for parked rows),
    - the updated view (``lengths`` pass through untouched — the
      engine owns the commit/rewind arithmetic).

    Rejected positions need no undo: their KV stays in owned pages
    beyond the committed length, masked out of every later attend by
    ``kv_lens`` until the next write overwrites it.
    """
    x = L.embed_tokens(params["embed"], tokens, cfg)
    b, s, _ = x.shape
    start = start_pos.astype(jnp.int32)                       # [B]
    valid = jnp.clip(n_tokens.astype(jnp.int32), 0, s)        # [B]
    x, view = _chunk_layers(params, x, view, cfg, start, valid)
    x = L.apply_norm(params["ln_f"], x, cfg)
    logits = L.logits_fn(params, x, cfg)                      # [B, S, V]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)    # [B, S]
    # accept = length of the leading run where the model's own greedy
    # choice equals the next drafted token — exactly the tokens vanilla
    # single-step decoding would have produced, in order
    in_window = jnp.arange(s - 1)[None, :] < (valid - 1)[:, None]
    match = (greedy[:, :-1] == tokens[:, 1:]) & in_window     # [B, S-1]
    accept = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
    finite = jnp.all(jnp.isfinite(logits), axis=-1)           # [B, S]
    at_valid = jnp.arange(s)[None, :] < valid[:, None]
    ok = jnp.all(jnp.where(at_valid, finite, True), axis=1)   # [B]
    return greedy, accept.astype(jnp.int32), ok, view


# ----------------------------------------------------- act calibration --

def collect_act_calibration(params, tokens: jax.Array, cfg: ModelConfig):
    """One forward over calibration prompts, capturing per layer the
    float activation feeding each quantized-matmul site
    (:data:`repro.models.layers.ACT_SITES`): attn_in (ln1 output →
    wq/wk/wv), attn_out (attention context → wo), mlp_in (ln2 output →
    gate/up), mlp_mid (MLP intermediate → w_down; dense blocks only —
    MoE expert intermediates stay fp, see DESIGN.md), plus the
    attention-boundary sites the codes-mode KV cache needs: attn_q (the
    roped query the flash kernels consume), attn_k/attn_v (the roped
    keys / raw values a u8 KV page stores — fit per head downstream).
    Returns ``{site: [L, B, S, ...]}`` stacked by the layer scan; the
    runtime fits per-(layer, site) ``ExpQuantParams`` on these samples.
    Runs on the params as-is (no act_q consulted), so the captured
    tensors are the float values the quantizer will stand in for."""
    x = L.embed_tokens(params["embed"], tokens, cfg)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    mask = ("causal", None)

    def body(carry, blk_params):
        x, = carry
        h1 = L.apply_norm(blk_params["ln1"], x, cfg)
        attn, ctx = L.mha(blk_params["attn"], h1, cfg, positions, mask,
                          return_ctx=True)
        x = x + attn
        h2 = L.apply_norm(blk_params["ln2"], x, cfg)
        q_cal = L.roped_q(blk_params["attn"], h1, cfg, positions)
        k_cal, v_cal = L.self_kv(blk_params["attn"], h1, cfg, positions)
        sites = {"attn_in": h1, "attn_out": ctx, "mlp_in": h2,
                 "attn_q": q_cal, "attn_k": k_cal, "attn_v": v_cal}
        if cfg.is_moe:
            y, _ = M.apply_moe(blk_params["moe"], h2, cfg)
        else:
            y, mid = L.apply_mlp(blk_params["mlp"], h2, cfg,
                                 return_mid=True)
            sites["mlp_mid"] = mid
        return (L.constrain_act(x + y),), sites

    (_x,), sites = scan_blocks(body, (x,), params["blocks"], cfg)
    return sites


# ---------------------------------------------------------------- loss --

def lm_loss(params, batch: dict, cfg: ModelConfig) -> tuple[jax.Array, dict]:
    """Next-token cross-entropy with z-loss.  batch: tokens/targets [B,S]."""
    logits, aux = forward(params, batch["tokens"], cfg,
                          prefix_embeds=batch.get("prefix_embeds"))
    if "prefix_embeds" in batch and batch["prefix_embeds"] is not None:
        logits = logits[:, batch["prefix_embeds"].shape[1]:, :]
    targets = batch["targets"]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt_logit = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - tgt_logit
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones_like(nll)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum(nll * mask) / denom
    zl = cfg.z_loss * jnp.sum((logz ** 2) * mask) / denom
    total = loss + zl + 0.01 * aux
    return total, {"loss": loss, "z_loss": zl, "aux_loss": aux,
                   "ppl_proxy": jnp.exp(jnp.minimum(loss, 20.0))}
