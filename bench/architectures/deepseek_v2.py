"""DeepSeek-V2 (the ``deepseek_v2`` ``model_type``), as
``bench/configs/deepseek-v2-lite-e16.json`` runs it: pre-norm RMSNorm
blocks, multi-head latent attention with YaRN rope, a leading dense
SiLU-gated MLP, then mixture-of-experts layers (a softmax router over
every routed expert, greedy top-k, the held experts' share, shared
experts added without a weight), an untied output projection.

What each function gives the harness is as ``decoder.py`` says.  The
reference is float32 ``jax.numpy`` at ``Precision.HIGHEST`` with no
kernel, cache, paging or batching, and imports nothing of the program.
Per layer it follows the published equations:

- ``q = h W_q`` (no q-LoRA): per head ``qk_nope_head_dim`` then
  ``qk_rope_head_dim`` numbers; ``[c_kv | k_pe] = h W_kv_a``; ``c_kv``
  through its own RMSNorm; rope (YaRN) on ``q_pe`` and the one shared
  ``k_pe``; ``k_nope`` and ``v`` per head decompressed from ``c_kv``
  (not absorbed); softmax scale ``q_head_dim ** -0.5 * m ** 2`` with
  ``m = 0.1 * mscale_all_dim * ln(factor) + 1``;
- YaRN inverse frequencies: the original and the ``/ factor``
  frequencies blended by the linear ramp between the correction dims
  of ``beta_fast`` and ``beta_slow`` over ``original_max_position_
  embeddings``; cos/sin scaled by ``m(mscale) / m(mscale_all_dim)``;
- the router's softmax over all ``n_routed_experts_published`` in
  float32, greedy top-``num_experts_per_tok``, renormalised only when
  ``norm_topk_prob``, times ``routed_scaling_factor``; the held experts
  ``experts_held`` computed densely for every token and masked by
  top-k membership; the ``n_shared_experts`` as one gated MLP of
  ``n_shared_experts * moe_intermediate_size``.

Departures, each also the program's:

- Rope rotates half-split pairs (``x[:half]``, ``x[half:]``), where the
  published model first permutes its interleaved pairs into that
  order.  Under random weights the permutation is a relabelling of the
  rope columns of ``W_q`` and ``W_kv_a``.
- Only the experts ``experts_held`` exist (one chip's share of a
  four-chip expert-parallel host): the router keeps every output and
  its top-k, and what the absent experts would add is left out.
- The cache the representation states (``float8_e4m3fn``) rounds the
  normed ``c_kv`` and the roped ``k_pe``: the reference decompresses
  ``k_nope`` and ``v`` from the rounded ``c_kv``.
- Weights are random (``leaf``): every matmul weight is exponent codes
  of ``N(0, 1/fan_in)`` (one code set per layer), the embedding and the
  output projection included.  The router is float32, as deployments
  keep the gate: drawn by the harness's one float rule, ``1 + N(0,
  0.1**2)`` a weight (``weights.NORM_SCALE``).  The 1 adds the same
  ``sum(h)`` to all of a token's router logits, which the softmax and
  the top-k ignore, so the gate acts as ``N(0, 0.1**2)``.
- The reference decodes each layer's codes inside its layer scan, so
  that it fits on the chip beside nothing else.

The control is the same forward with the activations that enter each
weight matmul (attention in and out, MLP and expert in and mid)
rounded to ``representation.control_activations``.

Counting conventions (one multiply-add is 2 FLOPs): see each count.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import codes
from bench.harness.flops import BYTES
from bench.harness.weights import NORM_SCALE, Codes

HIGHEST = jax.lax.Precision.HIGHEST
EMBED_SIGMA = 0.05
LANE = 128


# ------------------------------------------------------------- the program

def _held(config: dict) -> tuple[int, int]:
    lo, hi = config["experts_held"]
    return int(lo), int(hi)


def check_widths(cfg, config: dict) -> None:
    """Raises where the program's config ``cfg`` differs from the
    configuration file in a width, a depth, a count or a rule it runs."""
    rs = config["rope_scaling"]
    want = {
        "num_layers": config["num_hidden_layers"],
        "d_model": config["hidden_size"],
        "num_heads": config["num_attention_heads"],
        "vocab_size": config["vocab_size"],
        "d_ff": config["moe_intermediate_size"],
        "dense_d_ff": config["intermediate_size"],
        "first_dense_layers": config["first_k_dense_replace"],
        "num_experts": config["n_routed_experts_published"],
        "experts_per_token": config["num_experts_per_tok"],
        "held_experts": _held(config),
        "shared_experts_width": (config["n_shared_experts"]
                                 * config["moe_intermediate_size"]),
        "norm_topk_prob": config["norm_topk_prob"],
        "kv_lora_rank": config["kv_lora_rank"],
        "qk_nope_head_dim": config["qk_nope_head_dim"],
        "qk_rope_head_dim": config["qk_rope_head_dim"],
        "v_head_dim": config["v_head_dim"],
        "rope_theta": config["rope_theta"],
        "yarn_factor": rs["factor"],
        "yarn_original_max_position": rs["original_max_position_embeddings"],
        "yarn_beta_fast": rs["beta_fast"],
        "yarn_beta_slow": rs["beta_slow"],
        "yarn_mscale": rs["mscale"],
        "yarn_mscale_all_dim": rs["mscale_all_dim"],
        "tie_embeddings": config["tie_word_embeddings"],
    }
    got = {k: getattr(cfg, k) for k in want}
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    lo, hi = _held(config)
    if hi - lo != config["n_routed_experts"]:
        bad["n_routed_experts"] = (hi - lo, config["n_routed_experts"])
    if config["q_lora_rank"] is not None or config["routed_scaling_factor"] != 1:
        bad["q_lora_rank/routed_scaling_factor"] = (
            None, (config["q_lora_rank"], config["routed_scaling_factor"]))
    if bad:
        raise ValueError(f"program config differs from the configuration "
                         f"file (program, file): {bad}")


# ----------------------------------------------------------------- weights

def _fan_in(name: str, shape: tuple, config: dict) -> int | None:
    """A matmul weight's fan-in: the width of what it multiplies in the
    published model."""
    d = int(config["hidden_size"])
    c = int(config["kv_lora_rank"])
    heads = int(config["num_attention_heads"])
    return {"wq": d, "wkv_a": d, "wk_b": c, "wv_b": c,
            "wo": heads * int(config["v_head_dim"]), "w_gate": d, "w_up": d,
            "w_down": shape[-2], "out": d}.get(name)


def leaf(path: tuple, shape: tuple, config: dict):
    """The draw of one params leaf: each matmul weight ``N(0, 1/fan_in)``
    (one code set per layer), the embedding ``N(0, 0.05**2)``, the
    router and every norm ``scale`` float32 by the norm-scale rule."""
    name = path[-1]
    stacked = shape[0] if path[0] in ("blocks", "dense_blocks") else None
    if name == "tokens":
        return Codes(EMBED_SIGMA, None)
    if name in ("scale", "router"):
        return NORM_SCALE
    fan = _fan_in(name, shape, config)
    if fan is not None and len(shape) >= 2:
        return Codes(1.0 / float(np.sqrt(fan)), stacked)
    raise KeyError(f"deepseek_v2: no draw rule for params leaf "
                   f"{'/'.join(path)} {tuple(shape)}")


def act_tables(config: dict) -> dict:
    """Activations are float here: no activation-code tables."""
    return {}


# --------------------------------------------------------------- reference

def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _w(leaf):
    return codes.decode(leaf["codes"], leaf["qmeta"])


def _round(x, kind: str):
    if kind in ("bfloat16", "float32"):
        return x
    if kind == "float8_e4m3fn":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    raise ValueError(f"no rounding rule for {kind!r}")


def _rms(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _yarn_m(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn(config: dict) -> tuple[np.ndarray, float, float]:
    """(inverse frequencies [rope_dim / 2], cos/sin scale, softmax scale)
    by DeepSeek's YaRN formulas."""
    rs = config["rope_scaling"]
    dim = int(config["qk_rope_head_dim"])
    base = float(config["rope_theta"])
    factor = float(rs["factor"])
    orig = int(rs["original_max_position_embeddings"])

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    inv = extra / factor * ramp + extra * (1.0 - ramp)
    m_all = _yarn_m(factor, rs["mscale_all_dim"])
    cos_scale = _yarn_m(factor, rs["mscale"]) / m_all
    q_dim = int(config["qk_nope_head_dim"]) + dim
    return inv.astype(np.float32), cos_scale, q_dim ** -0.5 * m_all ** 2


def _rope(x, inv, scale: float):
    """x [S, heads, dim] at positions 0..S-1, rotate-half convention."""
    s, _, dim = x.shape
    half = dim // 2
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(inv)
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(params, tokens, config: dict, control: bool = False):
    """Logits [S, V] for one sequence ``tokens`` [S]."""
    rep = config["representation"]
    kv_kind = rep["kv"]
    eps = float(config["rms_norm_eps"])
    c = int(config["kv_lora_rank"])
    n = int(config["qk_nope_head_dim"])
    k_top = int(config["num_experts_per_tok"])
    lo, hi = _held(config)
    inv, cos_scale, sm_scale = yarn(config)
    renorm = bool(config["norm_topk_prob"])
    routed_scale = float(config["routed_scaling_factor"])

    def site(x):
        """An activation entering a weight matmul."""
        return _round(x, rep["control_activations"]) if control else x

    emb = params["embed"]["tokens"]
    x = codes.decode(emb["codes"][tokens], emb["qmeta"])        # [S, d]
    s = x.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def attention(x, p):
        a = p["attn"]
        h = site(_rms(x, p["ln1"]["scale"], eps))
        q = _mm("sd,dnh->snh", h, _w(a["wq"]))
        kv = _mm("sd,dc->sc", h, _w(a["wkv_a"]))
        c_kv = _round(_rms(kv[:, :c], a["kv_norm"]["scale"], eps), kv_kind)
        k_pe = _round(_rope(kv[:, None, c:], inv, cos_scale), kv_kind)[:, 0]
        q_pe = _rope(q[..., n:], inv, cos_scale)
        k_nope = _mm("tc,hnc->thn", c_kv, _w(a["wk_b"]))
        v = _mm("tc,hcv->thv", c_kv, _w(a["wv_b"]))
        sc = (_mm("shn,thn->hst", q[..., :n], k_nope)
              + _mm("shr,tr->hst", q_pe, k_pe)) * sm_scale
        sc = jnp.where(causal[None], sc, -jnp.inf)
        ctx = site(_mm("hst,thv->shv", jax.nn.softmax(sc, -1), v))
        return x + _mm("shv,hvd->sd", ctx, _w(a["wo"]))

    def gated(h, m, spec_in="sd,df->sf", spec_out="sf,fd->sd"):
        mid = site(jax.nn.silu(_mm(spec_in, h, _w(m["w_gate"])))
                   * _mm(spec_in, h, _w(m["w_up"])))
        return _mm(spec_out, mid, _w(m["w_down"]))

    def dense_layer(x, p):
        x = attention(x, p)
        h = site(_rms(x, p["ln2"]["scale"], eps))
        return x + gated(h, p["mlp"]), None

    def moe_layer(x, p):
        x = attention(x, p)
        m = p["moe"]
        h = site(_rms(x, p["ln2"]["scale"], eps))
        probs = jax.nn.softmax(_mm("sd,de->se", h, m["router"]), -1)
        top_w, top_e = jax.lax.top_k(probs, k_top)
        if renorm:
            top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
        gate = jnp.sum(jnp.where(
            top_e[:, :, None] == lo + jnp.arange(hi - lo)[None, None, :],
            top_w[:, :, None], 0.0), 1) * routed_scale         # [S, E_held]
        y = gated(h, m, "sd,edf->esf", "esf,efd->esd")          # [E, S, d]
        return (x + _mm("se,esd->sd", gate, y) + gated(h, m["shared"]),
                None)

    x, _ = jax.lax.scan(dense_layer, x, params["dense_blocks"])
    x, _ = jax.lax.scan(moe_layer, x, params["blocks"])
    x = _rms(x, params["ln_f"]["scale"], eps)
    out = params["unembed"]["out"]
    return _mm("sd,dv->sv", x, _w(out))


# ------------------------------------------------------------- work counts

@dataclasses.dataclass(frozen=True)
class Shapes:
    d: int
    layers: int
    dense_layers: int
    heads: int
    kv_rank: int            # c_kv
    rope_dim: int
    nope_dim: int
    v_dim: int
    latent: int             # numbers a cache page stores per token (padded)
    vocab: int
    ff: int                 # routed expert width
    dense_ff: int
    shared_ff: int
    experts: int            # router outputs
    experts_held: int
    top_k: int
    w_bytes: int
    act_bytes: int
    kv_bytes: int


def shapes(config: dict) -> Shapes:
    rep = config["representation"]
    c, r = int(config["kv_lora_rank"]), int(config["qk_rope_head_dim"])
    lo, hi = _held(config)
    ff = int(config["moe_intermediate_size"])
    return Shapes(
        d=int(config["hidden_size"]), layers=int(config["num_hidden_layers"]),
        dense_layers=int(config["first_k_dense_replace"]),
        heads=int(config["num_attention_heads"]), kv_rank=c, rope_dim=r,
        nope_dim=int(config["qk_nope_head_dim"]),
        v_dim=int(config["v_head_dim"]),
        latent=-(-(c + r) // LANE) * LANE, vocab=int(config["vocab_size"]),
        ff=ff, dense_ff=int(config["intermediate_size"]),
        shared_ff=int(config["n_shared_experts"]) * ff,
        experts=int(config["n_routed_experts_published"]),
        experts_held=hi - lo, top_k=int(config["num_experts_per_tok"]),
        w_bytes=BYTES[rep["weights"]], act_bytes=BYTES[rep["activations"]],
        kv_bytes=BYTES[rep["kv"]])


def attn_matmul_params(s: Shapes) -> int:
    """Weights one token meets in one layer's absorbed attention: W_q,
    W_kv_a, W_UK (the query's way into the latent), W_UV and W_o."""
    h = s.heads
    return (s.d * h * (s.nope_dim + s.rope_dim) + s.d * (s.kv_rank + s.rope_dim)
            + h * s.nope_dim * s.kv_rank + h * s.kv_rank * s.v_dim
            + h * s.v_dim * s.d)


def _moe_layers(s: Shapes) -> int:
    return s.layers - s.dense_layers


def routed_share(s: Shapes) -> float:
    """Expected held experts a token meets in one MoE layer under even
    routing: ``top_k * experts_held / experts``."""
    return s.top_k * s.experts_held / s.experts


def matmul_flops(s: Shapes, rows: int, unembed_rows: int) -> float:
    """``rows`` tokens through every layer's matmuls (routed experts at
    their expected share, :func:`routed_share`), plus ``unembed_rows``
    of them through the output projection."""
    per_row = (s.layers * attn_matmul_params(s)
               + s.dense_layers * 3 * s.d * s.dense_ff
               + _moe_layers(s) * (s.d * s.experts + 3 * s.d * s.shared_ff
                                   + routed_share(s) * 3 * s.d * s.ff))
    return 2.0 * rows * per_row + 2.0 * unembed_rows * s.d * s.vocab


def matmul_bytes(s: Shapes, rows: int, unembed_rows: int) -> float:
    """Every weight code held here once (the router in float32), plus
    each activation in and out once at the activation width; logits
    leave in float32."""
    weights = (s.layers * attn_matmul_params(s)
               + s.dense_layers * 3 * s.d * s.dense_ff
               + _moe_layers(s) * 3 * s.d * (s.shared_ff
                                             + s.experts_held * s.ff)
               + s.vocab * s.d) * s.w_bytes \
        + _moe_layers(s) * s.d * s.experts * 4
    h, c = s.heads, s.kv_rank
    attn_io = (s.d + h * (s.nope_dim + s.rope_dim) + s.d + c + s.rope_dim
               + h * s.nope_dim + h * c + h * c + h * s.v_dim
               + h * s.v_dim + s.d)
    mlp_io = lambda ff: 2 * s.d + 2 * ff                   # noqa: E731
    per_row = (s.layers * attn_io + s.dense_layers * mlp_io(s.dense_ff)
               + _moe_layers(s) * (mlp_io(s.shared_ff)
                                   + routed_share(s) * mlp_io(s.ff)))
    return (weights + rows * per_row * s.act_bytes
            + unembed_rows * (s.d * s.act_bytes + s.vocab * 4))


def attn_flops(s: Shapes, q_start: int, q_len: int) -> float:
    """Absorbed attention of queries at ``q_start .. q_start+q_len-1``:
    scores over ``kv_rank + rope_dim`` numbers and the mix of
    ``kv_rank`` per position and head."""
    ctx_sum = q_len * (q_start + 1) + q_len * (q_len - 1) / 2
    return (s.layers * 2.0 * s.heads
            * (2 * s.kv_rank + s.rope_dim) * ctx_sum)


def attn_bytes(s: Shapes, q_start: int, q_len: int) -> float:
    """The live context's cache entries once (at the padded width the
    pages store), the new tokens' entries written once, the absorbed
    query in and the latent context out at the activation width."""
    entry = s.latent * s.kv_bytes
    act = q_len * s.heads * s.latent * 2 * s.act_bytes
    return s.layers * ((q_start + q_len) * entry + q_len * entry + act)


def mla_decode_flops(s: Shapes, context: int) -> float:
    """One decode row over ``context`` cached positions, every layer:
    ``2 * heads * context * (kv_rank + rope_dim + kv_rank)``."""
    return s.layers * 2.0 * s.heads * context * (2 * s.kv_rank + s.rope_dim)


def mla_decode_bytes(s: Shapes, context: int) -> float:
    """One decode row's cache entries read once, every layer, at the
    padded width the pages store."""
    return s.layers * context * s.latent * s.kv_bytes


def expert_flops(s: Shapes, routed: int) -> float:
    """``routed`` (row, held expert) pairs through a gated expert MLP."""
    return 6.0 * s.d * s.ff * routed


def expert_bytes(s: Shapes, routed: int, active: int) -> float:
    """The codes of each active (dispatch, layer, expert) once, and each
    routed row in, its mid out and back in, and its output out, at the
    activation width."""
    return (3.0 * s.d * s.ff * s.w_bytes * active
            + routed * 2 * (s.d + s.ff) * s.act_bytes)


def token_flops(s: Shapes, position: int, unembed: bool) -> float:
    """One token's forward at absolute ``position``: the useful work
    ``mfu`` counts (no padding)."""
    return (matmul_flops(s, 1, int(unembed))
            + attn_flops(s, position, 1))
