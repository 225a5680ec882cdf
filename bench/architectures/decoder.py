"""The dense decoder, as both configurations use it: pre-norm blocks
(RMSNorm with scale, or OLMo's non-parametric LayerNorm), optional
per-head RMS qk-norm (Qwen3), rotary embeddings (rotate-half),
grouped-query causal attention, a SiLU-gated MLP and a tied output
projection.

An architecture module gives the harness what it cannot know of a model
by itself (``bench/harness/spec.py`` finds it by the configuration's
``"architecture"``):

- :func:`check_widths`: the program's config against the file;
- :func:`leaf`: how each params leaf is drawn from the seed;
- :func:`act_tables`: the activation-code tables, where activations are
  codes;
- :func:`forward`: the plain reference forward;
- :func:`shapes` and the work counts ``matmul_flops``, ``matmul_bytes``,
  ``attn_flops``, ``attn_bytes`` and ``token_flops``: the numerators of
  ``mfu`` and of the roofline shares.

The reference is float32 ``jax.numpy`` at ``Precision.HIGHEST``, with no
kernel, cache, paging or batching, and imports nothing of the program.
It reads the benchmark's own weights (exponent codes, decoded by
``bench.harness.codes``) and follows the configuration's representation:
KV rounded to its stated type, and, where activations are codes, every
stated site rounded through its codes.

The control is the same forward with the activations that feed every
weight matmul rounded to the precision below the stated one
(``representation.control_activations``): float8 e4m3 below bfloat16;
``exp6``, the stated site's code with one bit fewer over the same range
(base 2^(1/4) where it is 2^(1/8)), below 7-bit codes.

Counting conventions (one multiply-add is 2 FLOPs):

- a layer's matmuls: q, k, v and o projections and a gated MLP (gate,
  up, down);
- attention of one query at absolute position ``p`` reads the keys and
  values of positions ``0..p``: ``4 * heads * head_dim * (p + 1)``
  FLOPs per layer (scores and weighted sum);
- the least bytes of a matmul dispatch: every weight code once, plus
  each activation in and out once at the configuration's activation
  width; logits leave in float32;
- the least bytes of attention: the keys and values of the live
  context once, the new token's keys and values written once, the
  query in and the context out at the activation width.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import codes
from bench.harness.flops import BYTES
from bench.harness.weights import ACT_HI, NORM_SCALE, Codes

HIGHEST = jax.lax.Precision.HIGHEST
EMBED_SIGMA = 0.05
ACT_SITES = ("attn_in", "attn_out", "mlp_in", "mlp_mid", "attn_q")
KV_SITES = ("attn_k", "attn_v")


# ------------------------------------------------------------- the program

def check_widths(cfg, config: dict) -> None:
    """Raises where the program's config ``cfg`` differs from the
    configuration file in a width, a depth or a rule it runs."""
    d = int(config["hidden_size"])
    want = {"num_layers": config["num_hidden_layers"], "d_model": d,
            "num_heads": config["num_attention_heads"],
            "num_kv_heads": config["num_key_value_heads"],
            "d_ff": config["intermediate_size"],
            "vocab_size": config["vocab_size"],
            "resolved_head_dim": config.get("head_dim")
            or d // int(config["num_attention_heads"]),
            "tie_embeddings": config["tie_word_embeddings"],
            "rope_theta": config["rope_theta"]}
    got = {k: getattr(cfg, k) for k in want}
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    if bad:
        raise ValueError(f"program config differs from the configuration "
                         f"file (program, file): {bad}")


# ----------------------------------------------------------------- weights

def _fan_in(name: str, config: dict) -> int | None:
    d = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    hd = int(config.get("head_dim") or d // heads)
    return {"wq": d, "wk": d, "wv": d, "wo": heads * hd, "w_gate": d,
            "w_up": d, "w_down": int(config["intermediate_size"])}.get(name)


def leaf(path: tuple, shape: tuple, config: dict):
    """The draw of one params leaf: each matmul weight ``N(0, 1/fan_in)``
    (one code set per layer), the embedding ``N(0, 0.05**2)``, and every
    ``scale`` a norm scale."""
    name = path[-1]
    stacked = int(config["num_hidden_layers"]) if path[0] == "blocks" \
        else None
    fan = _fan_in(name, config)
    if name == "tokens":
        return Codes(EMBED_SIGMA, None)
    if fan is not None and len(shape) >= 2:
        return Codes(1.0 / float(np.sqrt(fan)), stacked)
    if name == "scale":
        return NORM_SCALE
    raise KeyError(f"decoder: no draw rule for params leaf "
                   f"{'/'.join(path)} {tuple(shape)}")


def act_tables(config: dict) -> dict:
    """One code set per layer at each activation site (per KV head for K
    and V), largest magnitude ``ACT_HI``."""
    layers = int(config["num_hidden_layers"])
    kv = int(config["num_key_value_heads"])
    m = codes.meta(ACT_HI)
    per_layer = jnp.broadcast_to(m, (layers, 4))
    per_head = jnp.broadcast_to(m, (layers, kv, 4))
    out = {s: {"lut": codes.table(per_layer), "qmeta": per_layer}
           for s in ACT_SITES}
    out.update({s: {"lut": codes.table(per_head), "qmeta": per_head}
                for s in KV_SITES})
    return out


# --------------------------------------------------------------- reference

def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _w(leaf):
    return codes.decode(leaf["codes"], leaf["qmeta"])


def _fq(x, qmeta):
    """Round ``x`` through exponent codes (``qmeta`` broadcasts)."""
    return codes.decode(codes.encode(x, qmeta), qmeta)


def _round(x, kind: str):
    if kind in ("bfloat16", "float32"):
        return x
    if kind == "float8_e4m3fn":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    raise ValueError(f"no rounding rule for {kind!r}")


def _norm(x, scale, kind: str, eps: float):
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * scale
    if kind == "nonparam_ln":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps)
    raise ValueError(f"no norm {kind!r}")


def _rope(x, theta: float):
    """x [S, heads, hd] at positions 0..S-1, rotate-half convention."""
    s, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(params, tokens, config: dict, control: bool = False):
    """Logits [S, V] for one sequence ``tokens`` [S]."""
    ref = config["reference"]
    rep = config["representation"]
    act_kind = rep["control_activations"] if control else rep["activations"]
    act_codes = rep["activations"] == "exp7"
    kv_kind = rep["kv"]
    d = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    kv = int(config["num_key_value_heads"])
    hd = int(config.get("head_dim") or d // heads)
    theta = float(config["rope_theta"])
    eps = float(ref["norm_eps"])
    norm = ref["norm"]

    def site(x, aq, name):
        """An activation entering a weight matmul."""
        if control and act_kind == "exp6":
            return _fq(x, codes.coarser(aq[name]["qmeta"]))
        if control:
            return _round(x, act_kind)
        return _fq(x, aq[name]["qmeta"]) if act_codes else x

    emb = params["embed"]["tokens"]
    x = codes.decode(emb["codes"][tokens], emb["qmeta"])        # [S, d]
    s = x.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        aq = p.get("act_q")
        a = p["attn"]
        h = site(_norm(x, p["ln1"].get("scale"), norm, eps), aq, "attn_in")
        q = _mm("sd,dnh->snh", h, _w(a["wq"]))
        k = _mm("sd,dnh->snh", h, _w(a["wk"]))
        v = _mm("sd,dnh->snh", h, _w(a["wv"]))
        if ref["qk_norm"]:
            qe = float(ref["qk_norm_eps"])
            q = _norm(q, a["q_norm"]["scale"], "rmsnorm", qe)
            k = _norm(k, a["k_norm"]["scale"], "rmsnorm", qe)
        q, k = _rope(q, theta), _rope(k, theta)
        if act_codes:
            q = _fq(q, aq["attn_q"]["qmeta"])
            k = _fq(k, aq["attn_k"]["qmeta"][:, None, :])
            v = _fq(v, aq["attn_v"]["qmeta"][:, None, :])
        else:
            k, v = _round(k, kv_kind), _round(v, kv_kind)
        qg = q.reshape(s, kv, heads // kv, hd)
        sc = _mm("skgh,tkh->kgst", qg, k) / math.sqrt(hd)
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        ctx = _mm("kgst,tkh->skgh", jax.nn.softmax(sc, -1), v)
        ctx = site(ctx.reshape(s, heads, hd), aq, "attn_out")
        x = x + _mm("snh,nhd->sd", ctx, _w(a["wo"]))
        m = p["mlp"]
        h = site(_norm(x, p["ln2"].get("scale"), norm, eps), aq, "mlp_in")
        mid = jax.nn.silu(_mm("sd,df->sf", h, _w(m["w_gate"]))) \
            * _mm("sd,df->sf", h, _w(m["w_up"]))
        mid = site(mid, aq, "mlp_mid")
        return x + _mm("sf,fd->sd", mid, _w(m["w_down"])), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = _norm(x, params["ln_f"].get("scale"), norm, eps)
    return _mm("sd,vd->sv", x, codes.decode(emb["codes"], emb["qmeta"]))


# ------------------------------------------------------------- work counts

@dataclasses.dataclass(frozen=True)
class Shapes:
    d: int
    ff: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    w_bytes: int
    act_bytes: int
    kv_bytes: int


def shapes(config: dict) -> Shapes:
    d = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    rep = config["representation"]
    return Shapes(d=d, ff=int(config["intermediate_size"]),
                  layers=int(config["num_hidden_layers"]), heads=heads,
                  kv_heads=int(config["num_key_value_heads"]),
                  head_dim=int(config.get("head_dim") or d // heads),
                  vocab=int(config["vocab_size"]),
                  w_bytes=BYTES[rep["weights"]],
                  act_bytes=BYTES[rep["activations"]],
                  kv_bytes=BYTES[rep["kv"]])


def layer_matmul_params(s: Shapes) -> int:
    """Weights one token meets in one layer's matmuls."""
    qo = 2 * s.d * s.heads * s.head_dim
    kv = 2 * s.d * s.kv_heads * s.head_dim
    return qo + kv + 3 * s.d * s.ff


def matmul_flops(s: Shapes, rows: int, unembed_rows: int) -> float:
    """``rows`` tokens through every layer's matmuls, plus
    ``unembed_rows`` of them through the output projection."""
    return (2.0 * rows * s.layers * layer_matmul_params(s)
            + 2.0 * unembed_rows * s.d * s.vocab)


def matmul_bytes(s: Shapes, rows: int, unembed_rows: int) -> float:
    hd, a = s.head_dim, s.act_bytes
    per_row = (s.d + (s.heads + 2 * s.kv_heads) * hd      # attn in, qkv out
               + s.heads * hd + s.d                       # o in, o out
               + s.d + s.ff                               # mlp in, gated out
               + s.ff + s.d)                              # down in, out
    weights = (s.layers * layer_matmul_params(s) + s.vocab * s.d) * s.w_bytes
    return (weights + rows * s.layers * per_row * a
            + unembed_rows * (s.d * a + s.vocab * 4))


def attn_flops(s: Shapes, q_start: int, q_len: int) -> float:
    """Queries at positions ``q_start .. q_start + q_len - 1``."""
    ctx_sum = q_len * (q_start + 1) + q_len * (q_len - 1) / 2
    return s.layers * 4.0 * s.heads * s.head_dim * ctx_sum


def attn_bytes(s: Shapes, q_start: int, q_len: int) -> float:
    kv_tok = s.kv_heads * s.head_dim * 2 * s.kv_bytes
    act = q_len * s.heads * s.head_dim * 2 * s.act_bytes
    return s.layers * ((q_start + q_len) * kv_tok + q_len * kv_tok + act)


def token_flops(s: Shapes, position: int, unembed: bool) -> float:
    """One token's forward at absolute ``position``: the useful work
    ``mfu`` counts (no padding)."""
    return (matmul_flops(s, 1, int(unembed))
            + attn_flops(s, position, 1))
