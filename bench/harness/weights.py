"""Weights from the seed, made on the device in one jitted call, in the
representation the configuration states: exponent codes for every
matmul weight, float32 norm scales, and, where activations are codes,
the activation tables.  The program gets them as its ``params``; the
reference reads the same arrays.

Which leaf is drawn how is the architecture's (its ``leaf`` rule and
``act_tables``, in ``bench/architectures/<name>.py``): a leaf is codes of
``N(0, sigma**2)`` values (:class:`Codes`) or a norm scale
``1 + N(0, 0.1**2)`` (:data:`NORM_SCALE`), and a leaf with no rule is an
error.  Codes: base ``2**(1/8)``, ``beta = 0``, largest magnitude
``4 sigma``, one parameter set per layer where the leaf is stacked over
layers.  Activation codes: the same base, largest magnitude 16.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import codes

NORM_SIGMA = 0.1
WEIGHT_HI = 4.0          # largest code magnitude, in weight sigmas
ACT_HI = 16.0            # largest activation-code magnitude
NORM_SCALE = "norm_scale"


@dataclasses.dataclass(frozen=True)
class Codes:
    """A leaf of codes of ``N(0, sigma**2)`` values; ``layers`` where
    it is stacked over that many layers (one code set each)."""

    sigma: float
    layers: int | None


def jax_key(seed: int, stream: int) -> jax.Array:
    """A JAX key for ``stream`` of ``seed`` (any size of whole number)."""
    word = np.random.SeedSequence([int(seed), stream]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


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


def make_params(abstract, config: dict, seed: int, arch):
    """The params tree for a program tree shaped like ``abstract`` (its
    ``ShapeDtypeStruct`` leaves), each leaf drawn by ``arch.leaf``.  One
    jitted call on the default device."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    act_codes = config["representation"]["activations"] == "exp7"

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (path, leaf) in zip(keys, leaves):
            rule = arch.leaf(tuple(getattr(p, "key", None) for p in path),
                             leaf.shape, config)
            if isinstance(rule, Codes):
                out.append(_codes_leaf(k, leaf.shape, rule.sigma,
                                       rule.layers))
            elif rule == NORM_SCALE:
                out.append(1.0 + NORM_SIGMA * jax.random.normal(
                    k, leaf.shape, jnp.float32))
            else:
                raise TypeError(f"{jax.tree_util.keystr(path)}: rule "
                                f"{rule!r} is neither Codes nor NORM_SCALE")
        params = jax.tree_util.tree_unflatten(treedef, out)
        if act_codes:
            params["blocks"]["act_q"] = arch.act_tables(config)
        return params

    return jax.jit(build)(jax_key(seed, 1))
