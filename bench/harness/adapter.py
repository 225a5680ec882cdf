"""Every read of the Engine's internals that the benchmark makes sits
here, so that a public accessor can replace each one in a single place.

- :func:`progress`: how many tokens a request has so far, read the way
  ``Engine.stream`` reads them (the Engine has no public progress
  accessor).
- :func:`chunk_width`, :func:`table_cols`, :func:`dispatch_empty`: the
  scheduler's own shape ladders, and one idle dispatch of a shape, for
  the warm-up.
- :class:`DispatchLog`: the shape of every prefill and decode dispatch,
  and the rows it carries, for the roofline numerators (``--trace 1``
  runs only).  It wraps the Engine's jitted steps and leaves their
  arguments and results untouched.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


def progress(eng, uid: int) -> int:
    """Tokens request ``uid`` has so far."""
    return len(eng._states[uid].tokens)


def chunk_width(eng, remaining: int) -> int:
    """The prefill width the scheduler dispatches for ``remaining``
    prompt tokens."""
    return eng._chunk_width(remaining)


def table_cols(eng, blocks: int) -> int:
    """The block-table columns the scheduler dispatches for ``blocks``."""
    return min(eng._pow2(blocks), eng.cache.max_blocks_per_seq)


def dispatch_empty(eng, kind: str, width: int, cols: int) -> None:
    """Run one prefill (``width`` tokens a row) or decode step with
    every row idle, through the Engine's own jitted step and with the
    argument types its scheduler passes: it compiles, or loads, that
    program and writes nothing but the trash page."""
    n = eng.engine_cfg.num_slots
    view = eng.cache.view(cols=cols)
    if kind == "prefill":
        start = np.asarray(eng.cache.lengths, np.int32).copy()
        nxt, _ok, view = eng._prefill(
            eng.params, jnp.asarray(np.zeros((n, width), np.int32)), view,
            jnp.asarray(start), eng.cfg)
    else:
        nxt, _ok, view = eng._decode(
            eng.params, view, jnp.asarray(np.zeros((n, 1), np.int32)),
            jnp.asarray(np.zeros((n,), bool)), eng.cfg)
    np.asarray(nxt)
    eng.cache.update_pages(view)


@dataclasses.dataclass
class Dispatch:
    kind: str                         # "prefill" | "decode"
    rows: int                         # rows dispatched (padding included)
    width: int                        # tokens per row
    # per live row: (absolute start, tokens, whether its logits are used)
    valid: list[tuple[int, int, bool]]


class DispatchLog:
    """Records each dispatch while ``recording`` is set."""

    def __init__(self, eng):
        self.eng = eng
        self.recording = False
        self.dispatches: list[Dispatch] = []
        self._prefill, self._decode = eng._prefill, eng._decode
        eng._prefill, eng._decode = self._wrap_prefill, self._wrap_decode

    def close(self) -> None:
        self.eng._prefill, self.eng._decode = self._prefill, self._decode

    def _wrap_prefill(self, params, toks, view, start, cfg):
        if self.recording:
            rows, w = toks.shape
            valid = []
            for st in self.eng._slots:
                if st is not None and not st.prefill_done:
                    s0 = st.prefix_len + st.prefill_pos
                    n = len(st.full_prompt())
                    take = min(w, n - s0)
                    valid.append((s0, take, s0 + take == n))
            self.dispatches.append(Dispatch("prefill", rows, w, valid))
        with jax.profiler.TraceAnnotation("bench.dispatch.prefill"):
            return self._prefill(params, toks, view, start, cfg)

    def _wrap_decode(self, params, view, toks, active, cfg):
        if self.recording:
            lengths = self.eng.cache.lengths
            valid = [(int(lengths[i]), 1, True)
                     for i, st in enumerate(self.eng._slots)
                     if st is not None and st.prefill_done]
            self.dispatches.append(Dispatch("decode", toks.shape[0], 1,
                                            valid))
        with jax.profiler.TraceAnnotation("bench.dispatch.decode"):
            return self._decode(params, view, toks, active, cfg)
