"""Mixture-of-Experts layers (llama4-scout: 16e top-1; grok-1: 8e top-2;
deepseek-v2-lite: 64e top-6 plus shared experts).

Implementations, selected by ``cfg.moe_impl``:

* ``routed`` — llama4-scout's and grok-1's path: top-k routing with
  sort-based, capacity-dropped dispatch (GShard capacity discipline,
  MegaBlocks-style sorted grouping, no [T,E,C] one-hot blow-up): a
  token past its expert's capacity is dropped.  Expert FFNs run as
  grouped einsums over the ``experts`` axis, which shards as EP on the
  mesh "model" axis when divisible.
* ``dropless`` — the serving path (:func:`apply_moe_dropless`): no
  token is dropped.  Routes over all ``num_experts`` and computes the
  experts held here (``cfg.experts_held``) on the rows sorted by
  expert, giving the part of the output they contribute; rows that
  carry no token route nowhere; shared experts are added once.
* ``dense_mixture`` — naive oracle: every expert computes every token,
  mixed by router weights.  E/k x more FLOPs; used as the correctness
  reference and as the §Perf baseline for the MoE hillclimb cell.
* ``ep_a2a`` — expert parallelism over the mesh (``moe_ep``, training).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import lama_layers as ll
from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.params import ParamSpec


def moe_specs(cfg: ModelConfig) -> dict:
    """The router over all ``num_experts``; the weights of the experts
    held here; the shared experts as one gated MLP."""
    d, f = cfg.d_model, cfg.d_ff
    lo, hi = cfg.held_experts
    e = hi - lo
    s = {
        "router": ParamSpec((d, cfg.num_experts), ("embed", "experts"),
                            "scaled"),
        "w_gate": ParamSpec((e, d, f), ("experts", "embed", "mlp"), "scaled",
                            fan_in_axis=1),
        "w_up": ParamSpec((e, d, f), ("experts", "embed", "mlp"), "scaled",
                          fan_in_axis=1),
        "w_down": ParamSpec((e, f, d), ("experts", "mlp", "embed"), "scaled",
                            fan_in_axis=1),
    }
    if cfg.shared_experts_width:
        s["shared"] = L.mlp_specs(cfg, cfg.shared_experts_width)
    return s


def _capacity(cfg: ModelConfig, tokens: int) -> int:
    cap = int(tokens * cfg.experts_per_token * cfg.capacity_factor
              / cfg.num_experts)
    return max(128, -(-cap // 128) * 128)  # pad to a lane-friendly multiple


def _router(p, xf: jax.Array, cfg: ModelConfig, precision=None):
    """Softmax gates over all experts and the top-k of them, renormalised
    to sum to one when ``cfg.norm_topk_prob``.  ``precision`` computes
    the logits from a float router in float32 at that precision."""
    if precision is None:
        logits = ll.dense(xf, p["router"], dtype=jnp.float32)
    else:
        logits = jnp.dot(xf.astype(jnp.float32),
                         ll.materialize(p["router"], jnp.float32),
                         precision=precision)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_w, top_e = jax.lax.top_k(probs, cfg.experts_per_token)
    if cfg.norm_topk_prob:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    # load-balancing auxiliary loss (Switch/GShard)
    density = jnp.mean(
        jax.nn.one_hot(top_e[..., 0], cfg.num_experts, dtype=jnp.float32), 0)
    mean_probs = jnp.mean(probs, axis=0)
    aux = cfg.num_experts * jnp.sum(density * mean_probs)
    return probs, top_w, top_e, aux


def _expert_ffn(p, buf: jax.Array, cfg: ModelConfig,
                act_q: dict | None = None) -> jax.Array:
    """buf: [E, C, D] -> [E, C, D] through each expert's gated MLP.

    Grouped einsums go through ``dense_general``, which canonicalizes
    the per-expert batch dim and vmaps the fused dequant-matmul kernel —
    quantized expert weights never materialize in HBM.  With ``act_q``
    the dispatched buffer is encoded once at the mlp_in site (the
    capacity buffer crosses HBM as uint8 codes into the vmapped
    dual-LUT kernels); the expert *intermediate* stays fp — per-expert
    mid calibration is an open follow-up (DESIGN.md)."""
    act = jax.nn.silu if cfg.activation == "silu" else jax.nn.gelu
    bufq = ll.maybe_encode_act(buf, act_q, "mlp_in")
    g = ll.dense_general(bufq, p["w_gate"], "ecd,edf->ecf",
                         dtype=jnp.float32)
    u = ll.dense_general(bufq, p["w_up"], "ecd,edf->ecf",
                         dtype=jnp.float32)
    h = (act(g) * u).astype(buf.dtype)
    return ll.dense_general(h, p["w_down"], "ecf,efd->ecd",
                            dtype=jnp.float32).astype(buf.dtype)


def _constrain(x, *spec):
    """with_sharding_constraint against the ambient mesh (no-op outside).
    'fsdp' in the spec expands to the (pod, data) axes present."""
    import math as _math
    try:
        from repro.launch.mesh import get_abstract_mesh

        mesh = get_abstract_mesh()
        if mesh is None:
            return x
        fsdp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        out = []
        for dim, part in enumerate(spec):
            if part == "fsdp":
                part = fsdp if fsdp else None
            if part is not None:
                axes = part if isinstance(part, tuple) else (part,)
                if any(a not in mesh.axis_names for a in axes):
                    part = None
                elif x.shape[dim] % _math.prod(
                        mesh.shape[a] for a in axes) != 0:
                    part = None
            out.append(part)
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.PartitionSpec(*out))
    except Exception:
        return x


def apply_moe_routed(p, x: jax.Array, cfg: ModelConfig,
                     act_q: dict | None = None):
    """Sort-based capacity-dropped dispatch.  x: [B, S, D].

    §Perf C1 (EXPERIMENTS.md): dispatch buffers carry explicit sharding
    constraints — token-indexed arrays over the FSDP axes, the expert
    buffer over ("model" on E when divisible) x (FSDP on capacity) — so
    SPMD lowers the scatter/gather as token all-to-alls instead of
    replicating multi-GB buffers on every rank."""
    b, s, d = x.shape
    t = b * s
    k = cfg.experts_per_token
    e = cfg.num_experts
    xf = _constrain(x.reshape(t, d), "fsdp", None)

    _, top_w, top_e, aux = _router(p, xf, cfg)

    flat_e = top_e.reshape(t * k)                      # expert of each slot
    flat_w = top_w.reshape(t * k)
    slot_tok = jnp.arange(t * k) // k                  # token of each slot

    order = jnp.argsort(flat_e, stable=True)           # group slots by expert
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=e)
    starts = jnp.cumsum(counts) - counts
    within = jnp.arange(t * k) - starts[sorted_e]

    cap = _capacity(cfg, t)
    keep = within < cap
    dest = jnp.where(keep, sorted_e * cap + within, e * cap)  # drop slot
    src_tok = slot_tok[order]

    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[dest].set(xf[src_tok])
    buf = _constrain(buf[: e * cap].reshape(e, cap, d),
                     "model", "fsdp", None)
    out_buf = _constrain(_expert_ffn(p, buf, cfg, act_q=act_q),
                         "model", "fsdp", None)
    out_flat = jnp.concatenate(
        [out_buf.reshape(e * cap, d), jnp.zeros((1, d), x.dtype)], axis=0)

    y_slots = out_flat[dest] * flat_w[order][:, None].astype(x.dtype)
    y = jnp.zeros((t, d), x.dtype).at[src_tok].add(y_slots)
    return _constrain(y, "fsdp", None).reshape(b, s, d), aux


def _expert_slices(w, dtype):
    """Scan-able per-expert leaves: uint8 code slabs for qtensors (the
    decode stays in-kernel), materialized weights otherwise."""
    from repro.core import exponential_quant as eq

    if eq.is_qtensor(w):
        return w["codes"]
    return ll.materialize(w, dtype)


def _expert_leaf(w, sl):
    from repro.core import exponential_quant as eq

    if eq.is_qtensor(w):
        return {"codes": sl, "lut": w["lut"], "qmeta": w["qmeta"]}
    return sl


def apply_moe_dense(p, x: jax.Array, cfg: ModelConfig,
                    act_q: dict | None = None):
    """Oracle/baseline: all experts compute all tokens (scan over E).
    Quantized expert weights ride through the scan as uint8 code slabs
    and dispatch to the fused (gated) kernel per expert.  With
    ``act_q`` the token buffer is encoded ONCE at the mlp_in site and
    the per-expert gated kernels read the same act codes."""
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    probs, top_w, top_e, aux = _router(p, xf, cfg)
    xq = ll.maybe_encode_act(xf, act_q, "mlp_in")
    # sparse mixture weights [T, E] (zeros off the top-k support)
    w = jnp.zeros_like(probs).at[
        jnp.arange(t)[:, None], top_e
    ].set(top_w)

    def body(carry, ew):
        wg, wu, wd, we = ew
        g_leaf = _expert_leaf(p["w_gate"], wg)
        u_leaf = _expert_leaf(p["w_up"], wu)
        d_leaf = _expert_leaf(p["w_down"], wd)
        h = ll.gated_mlp(xq, g_leaf, u_leaf, cfg.activation,
                         dtype=xf.dtype)
        y = ll.dense(h, d_leaf, dtype=xf.dtype)
        return carry + y * we[:, None].astype(xf.dtype), None

    init = jnp.zeros((t, d), xf.dtype)
    y, _ = jax.lax.scan(
        body, init,
        (_expert_slices(p["w_gate"], xf.dtype),
         _expert_slices(p["w_up"], xf.dtype),
         _expert_slices(p["w_down"], xf.dtype),
         w.T.astype(jnp.float32)))
    return _with_shared(p, x, y.reshape(b, s, d), cfg, act_q), aux


def _with_shared(p, x, y, cfg: ModelConfig, act_q=None):
    """``y`` plus the shared experts' output, where the layer has them."""
    if "shared" not in p:
        return y
    return y + L.apply_mlp(p["shared"], x, cfg, act_q=act_q)


def _tile_rows(pairs: int) -> int:
    """Rows per expert tile: a bf16 sublane tile for decode-sized
    dispatches, an MXU-height tile for prefill-sized ones."""
    return 16 if pairs <= 512 else 128


def _grouped_ffn(p, xbuf, tile_expert, used, tm: int, cfg: ModelConfig):
    """Each ``tm``-row tile of ``xbuf`` through its expert's gated MLP:
    the grouped ``moe_expert`` kernels for weight codes, gathered float
    weights otherwise."""
    from repro.core import exponential_quant as eq

    wg, wu, wd = p["w_gate"], p["w_up"], p["w_down"]
    if eq.is_qtensor(wg):
        from repro.kernels.moe_expert import moe_expert_down, moe_expert_gated

        mid = moe_expert_gated(xbuf, wg["codes"], wu["codes"], wg["qmeta"],
                               wu["qmeta"], tile_expert, used, tm=tm,
                               activation=cfg.activation,
                               out_dtype=xbuf.dtype)
        return moe_expert_down(mid, wd["codes"], wd["qmeta"], tile_expert,
                               used, tm=tm, out_dtype=jnp.float32)
    act = jax.nn.silu if cfg.activation == "silu" else jax.nn.gelu
    xt = xbuf.reshape(-1, tm, xbuf.shape[-1]).astype(jnp.float32)

    def mm(a, w):
        return jnp.einsum("tmk,tkn->tmn", a, w[tile_expert].astype(
            jnp.float32), preferred_element_type=jnp.float32)

    mid = (act(mm(xt, wg)) * mm(xt, wu)).astype(xbuf.dtype)
    return mm(mid.astype(jnp.float32), wd).reshape(xbuf.shape[0], -1)


def apply_moe_dropless(p, x: jax.Array, cfg: ModelConfig,
                       valid: jax.Array | None = None):
    """Serving expert layer that drops no token.  x: [B, S, D]; ``valid``
    [B, S] marks the rows that carry a token (None: all do).

    The router (float32, ``Precision.HIGHEST``) picks each valid row's
    top-k of all ``num_experts``; the (row, expert) pairs that land on
    the experts held here, ``[lo, hi)``, are sorted by expert into a
    buffer whose groups are padded to whole tiles, sized for the worst
    case so that nothing can overflow.  The grouped kernels compute
    each tile with its own expert's weights; every pair's output comes
    back to its row weighted by its gate.  What the absent experts
    would add is not computed.  The shared experts are added once.

    Returns ``(y, aux_loss, counts)``: ``counts [hi - lo]`` int32 is the
    number of rows routed to each held expert."""
    b, s, d = x.shape
    t = b * s
    k = cfg.experts_per_token
    lo, hi = cfg.held_experts
    eh = hi - lo
    xf = x.reshape(t, d)
    _, top_w, top_e, aux = _router(p, xf, cfg,
                                   precision=jax.lax.Precision.HIGHEST)
    ok = (jnp.ones((t,), bool) if valid is None
          else valid.reshape(t).astype(bool))
    held = ((top_e >= lo) & (top_e < hi) & ok[:, None]).reshape(t * k)
    local = jnp.where(held, top_e.reshape(t * k) - lo, eh)   # eh: not here
    counts = jnp.bincount(local, length=eh + 1)[:eh].astype(jnp.int32)

    tm = _tile_rows(t * k)
    n_tiles = -(-t * min(k, eh) // tm) + eh      # sum_e ceil(count_e / tm)
    rows = n_tiles * tm
    tiles = -(-counts // tm)
    tile_end = jnp.cumsum(tiles)
    used = tile_end[-1]
    # pair -> buffer row: its expert's first row plus its rank there
    order = jnp.argsort(local, stable=True)
    sorted_e = local[order]
    e_c = jnp.minimum(sorted_e, eh - 1)
    rank = jnp.arange(t * k) - (jnp.cumsum(counts) - counts)[e_c]
    dest_sorted = jnp.where(sorted_e < eh,
                            (tile_end - tiles)[e_c] * tm + rank, rows)
    dest = jnp.zeros((t * k,), jnp.int32).at[order].set(
        dest_sorted.astype(jnp.int32))
    row_tok = jnp.full((rows,), t, jnp.int32).at[dest].set(
        jnp.arange(t * k, dtype=jnp.int32) // k, mode="drop")
    xbuf = jnp.concatenate([xf, jnp.zeros((1, d), x.dtype)])[row_tok]
    idx = jnp.arange(n_tiles)
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, idx, side="right"), eh - 1)
    tile_expert = jnp.where(idx < used, tile_expert,
                            tile_expert[jnp.maximum(used - 1, 0)])

    ybuf = _grouped_ffn(p, xbuf, tile_expert.astype(jnp.int32), used, tm,
                        cfg)
    y_pair = ybuf[jnp.minimum(dest, rows - 1)]              # [t*k, d]
    y_pair = jnp.where(held[:, None],
                       y_pair * top_w.reshape(t * k)[:, None], 0.0)
    y = jnp.sum(y_pair.reshape(t, k, d), axis=1).astype(x.dtype)
    y = _with_shared(p, x, y.reshape(b, s, d), cfg)
    return y, aux, counts


def apply_moe(p, x: jax.Array, cfg: ModelConfig,
              act_q: dict | None = None):
    if cfg.moe_impl == "ep_a2a":
        from repro.models.moe_ep import apply_moe_ep
        # EP's shard_map body manages its own dispatch buffers; act
        # codes stop at its boundary (follow-up in DESIGN.md)
        return apply_moe_ep(p, x, cfg)
    if cfg.moe_impl == "routed":
        return apply_moe_routed(p, x, cfg, act_q=act_q)
    if cfg.moe_impl == "dropless":
        y, aux, _ = apply_moe_dropless(p, x, cfg)
        return y, aux
    return apply_moe_dense(p, x, cfg, act_q=act_q)
