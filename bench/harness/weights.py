"""Weights from the seed, made on the device in one jitted call, in the
representation the configuration states: exponent codes for every
matmul weight, float32 norm scales, and, where activations are codes,
the activation tables.  The program gets them as its ``params``; the
reference reads the same arrays.

Weight values: each matmul weight ``N(0, 1/fan_in)``, the embedding
``N(0, 0.05**2)``, norm scales ``1 + N(0, 0.1**2)``.  Codes: base
``2**(1/8)``, ``beta = 0``, largest magnitude ``4 sigma``, one parameter
set per layer.  Activation codes: the same base, largest magnitude 16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import codes

EMBED_SIGMA = 0.05
NORM_SIGMA = 0.1
WEIGHT_HI = 4.0          # largest code magnitude, in weight sigmas
ACT_HI = 16.0            # largest activation-code magnitude
ACT_SITES = ("attn_in", "attn_out", "mlp_in", "mlp_mid", "attn_q")
KV_SITES = ("attn_k", "attn_v")


def jax_key(seed: int, stream: int) -> jax.Array:
    """A JAX key for ``stream`` of ``seed`` (any size of whole number)."""
    word = np.random.SeedSequence([int(seed), stream]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def _fan_in(name: str, config: dict) -> int:
    d = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    hd = int(config.get("head_dim") or d // heads)
    fan = {"wq": d, "wk": d, "wv": d, "wo": heads * hd, "w_gate": d,
           "w_up": d, "w_down": int(config["intermediate_size"])}
    if name not in fan:
        raise KeyError(f"no fan-in rule for weight {name!r}")
    return fan[name]


def _blocks(n: int) -> int:
    """Row blocks to draw an unstacked leaf in: the largest power of two
    up to 64 that divides ``n``."""
    b = 64
    while n % b:
        b //= 2
    return b


def _codes_leaf(key, shape, sigma: float, layers: int | None):
    """Codes of ``N(0, sigma**2)`` values, drawn one layer (or one block
    of rows) at a time so that no float copy of the whole leaf exists."""
    m = codes.meta(WEIGHT_HI * sigma)
    lead = layers if layers is not None else _blocks(shape[0])
    part = shape[1:] if layers is not None else (shape[0] // lead,) + shape[1:]
    enc = jax.lax.map(
        lambda k: codes.encode(jax.random.normal(k, part, jnp.float32)
                               * sigma, m),
        jax.random.split(key, lead))
    if layers is None:
        return {"codes": enc.reshape(shape), "lut": codes.table(m),
                "qmeta": m}
    m = jnp.broadcast_to(m, (layers, 4))
    return {"codes": enc, "lut": codes.table(m), "qmeta": m}


def _act_tables(config: dict) -> dict:
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


def make_params(abstract, config: dict, seed: int):
    """The params tree for a program tree shaped like ``abstract`` (its
    ``ShapeDtypeStruct`` leaves).  One jitted call on the default
    device."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    layers = int(config["num_hidden_layers"])
    act_codes = config["representation"]["activations"] == "exp7"

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (path, leaf) in zip(keys, leaves):
            names = [getattr(p, "key", None) for p in path]
            name = names[-1]
            stacked = names[0] == "blocks"
            if name == "tokens":
                out.append(_codes_leaf(k, leaf.shape, EMBED_SIGMA, None))
            elif name.startswith("w") and len(leaf.shape) >= 2:
                sigma = 1.0 / float(np.sqrt(_fan_in(name, config)))
                out.append(_codes_leaf(k, leaf.shape, sigma,
                                       layers if stacked else None))
            else:
                out.append(1.0 + NORM_SIGMA * jax.random.normal(
                    k, leaf.shape, jnp.float32))
        params = jax.tree_util.tree_unflatten(treedef, out)
        if act_codes:
            params["blocks"]["act_q"] = _act_tables(config)
        return params

    return jax.jit(build)(jax_key(seed, 1))
