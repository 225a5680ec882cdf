"""The check's comparison against the plain reference: the gap by which
each served token's reference logit lies below the reference's best.

The reference forward is the architecture's (``forward`` in
``bench/architectures/<name>.py``: float32 ``jax.numpy`` at
``Precision.HIGHEST``, importing nothing of the program); this module
pads the sequence, runs it once under ``jit`` with the whole
configuration frozen into the compiled function's key, and reads the
gaps.  The control is the same forward with ``control=True``: the
precision below the stated one, as the architecture defines it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


class _Items(tuple):
    """A configuration's dict, frozen: its sorted ``(key, value)``
    pairs."""


def _freeze(x):
    """A configuration as nested tuples, hashable, so that every key
    reaches the jitted forward: dicts as :class:`_Items`, lists as
    tuples."""
    if isinstance(x, dict):
        return _Items(sorted((k, _freeze(v)) for k, v in x.items()))
    if isinstance(x, list):
        return tuple(_freeze(v) for v in x)
    return x


def _thaw(x):
    if isinstance(x, _Items):
        return {k: _thaw(v) for k, v in x}
    if isinstance(x, tuple):
        return tuple(_thaw(v) for v in x)
    return x


@functools.partial(jax.jit,
                   static_argnames=("forward", "config_key", "control"))
def _gaps(params, tokens, targets, mask, forward, config_key, control):
    config = _thaw(config_key)
    ref = forward(params, tokens, config)
    best = jnp.max(ref, -1)
    gap = best - jnp.take_along_axis(ref, targets[:, None], -1)[:, 0]
    out = {"served": jnp.where(mask, gap, 0.0)}
    if control:
        ctl = jnp.argmax(forward(params, tokens, config, control=True), -1)
        cg = best - jnp.take_along_axis(ref, ctl[:, None], -1)[:, 0]
        out["control"] = jnp.where(mask, cg, 0.0)
    return out


def served_gaps(forward, params, config: dict, prompt, served, length: int,
                control: bool = False) -> dict:
    """Per served token, how far its reference logit (``forward``'s)
    lies below the reference's best, for a prompt and the tokens served
    after it.  The sequence is padded to ``length`` (causal: padding
    changes no earlier position), so every call shares one compile.
    With ``control``, also the same gap for the token that the control
    puts first at each position."""
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
    out = _gaps(params, tokens, targets, mask, forward, _freeze(config),
                control)
    return {k: np.asarray(v)[pos] for k, v in out.items()}
