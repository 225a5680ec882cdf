"""The plain reference: a decoder forward in float32 ``jax.numpy`` at
``Precision.HIGHEST``, with no kernel, cache, paging or batching.  It
imports nothing of the program.  It reads the benchmark's own weights
(exponent codes, decoded here by ``bench.harness.codes``) and follows
the configuration's representation: KV rounded to its stated type, and,
where activations are codes, every stated site rounded through its
codes.

It covers the decoder family as both configurations use it:
pre-norm blocks (RMSNorm with scale, or OLMo's non-parametric
LayerNorm), optional per-head RMS qk-norm (Qwen3), rotary embeddings
(rotate-half), grouped-query causal attention, a SiLU-gated MLP and a
tied output projection.

The control is the same forward with the activations that feed every
weight matmul rounded to the precision below the stated one
(``representation.control_activations``): float8 e4m3 below bfloat16;
``exp6``, the stated site's code with one bit fewer over the same range
(base 2^(1/4) where it is 2^(1/8)), below 7-bit codes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import codes

HIGHEST = jax.lax.Precision.HIGHEST


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


@functools.partial(jax.jit, static_argnames=("config_key", "control"))
def _gaps(params, tokens, targets, mask, config_key, control):
    config = dict(config_key)
    config["reference"] = dict(config["reference"])
    config["representation"] = dict(config["representation"])
    ref = forward(params, tokens, config)
    best = jnp.max(ref, -1)
    gap = best - jnp.take_along_axis(ref, targets[:, None], -1)[:, 0]
    out = {"served": jnp.where(mask, gap, 0.0)}
    if control:
        ctl = jnp.argmax(forward(params, tokens, config, control=True), -1)
        cg = best - jnp.take_along_axis(ref, ctl[:, None], -1)[:, 0]
        out["control"] = jnp.where(mask, cg, 0.0)
    return out


def _freeze(config: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rope_theta")
    return tuple([(k, config.get(k)) for k in keys]
                 + [("reference", tuple(sorted(config["reference"].items()))),
                    ("representation",
                     tuple(sorted(config["representation"].items())))])


def served_gaps(params, config: dict, prompt, served, length: int,
                control: bool = False) -> dict:
    """Per served token, how far its reference logit lies below the
    reference's best, for a prompt and the tokens served after it.
    The sequence is padded to ``length`` (causal: padding changes no
    earlier position), so every call shares one compile.  With
    ``control``, also the same gap for the token that the control puts
    first at each position."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    if len(seq) > length:
        raise ValueError(f"sequence of {len(seq)} > reference length {length}")
    tokens = np.zeros(length, np.int32)
    tokens[:len(seq)] = seq
    targets = np.zeros(length, np.int32)
    mask = np.zeros(length, bool)
    pos = len(prompt) - 1 + np.arange(len(served))
    targets[pos], mask[pos] = served, True
    out = _gaps(params, tokens, targets, mask, _freeze(config), control)
    return {k: np.asarray(v)[pos] for k, v in out.items()}
