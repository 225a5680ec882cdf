"""A run with the timed path broken underneath comes out not correct.

The harness is driven end to end on the CPU (its look for a chip
skipped) over the tiny cells.  Each fault a serving cell can have is
planted in the decode step that the window drives:

- ``altered_token``: the greedy tokens are shifted by one id where the
  step produces them;
- ``state_unchanged``: the step returns the KV pages and lengths it was
  given, so nothing it computed is kept;
- ``half_batch``: half of the rows are left out of each step.

The same run unbroken comes out correct, with nothing compiled inside
the window.  (One chip: there is no exchange between chips to leave
out.)"""

import itertools
import time

import jax.numpy as jnp
import pytest

from bench.harness import cell as C
from bench.harness.spec import load_cell
from bench.tests.conftest import TINY_LIMITS
from repro.core import lama_layers as ll
from repro.runtime import engine as engine_mod

SEED = 2**31 + 11


def _run(tiny_root, name, seed):
    lines = []
    cell = load_cell(f"{name}.chat", tiny_root)
    with ll.policy(autotune=False):
        out = C.run_cell(cell, seed, 3.0, False, time.perf_counter(),
                         log=lines.append)
    return out, lines


@pytest.mark.parametrize("name", sorted(TINY_LIMITS))
def test_sound_run_correct(tiny_root, name):
    out, lines = _run(tiny_root, name, SEED)
    assert out["correct"], out["checks"]
    assert "compiles in window: 0" in lines
    assert list(out)[-1] == "checks"
    assert out["metrics"]["output_tok_s"]["value"] > 0


def _altered_token(step, params, view, tokens, active, cfg):
    nxt, ok, new = step(params, view, tokens, active, cfg)
    return (nxt + 1) % cfg.vocab_size, ok, new


def _state_unchanged(step, params, view, tokens, active, cfg):
    nxt, ok, _new = step(params, view, tokens, active, cfg)
    return nxt, ok, view


_STEPS = itertools.count()


def _half_batch(step, params, view, tokens, active, cfg):
    # the half left out alternates from step to step, so that every
    # request, and so every sample the check draws, meets the fault
    keep = jnp.arange(active.shape[0]) % 2 == next(_STEPS) % 2
    return step(params, view, tokens, active & keep, cfg)


FAULTS = {"altered_token": _altered_token,
          "state_unchanged": _state_unchanged,
          "half_batch": _half_batch}


@pytest.mark.parametrize("name", sorted(TINY_LIMITS))
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_not_correct(tiny_root, name, fault, monkeypatch):
    real = engine_mod._jit_decode

    def broken(step_fn):
        step = real(step_fn)
        return lambda *a: FAULTS[fault](step, *a)

    monkeypatch.setattr(engine_mod, "_jit_decode", broken)
    out, _ = _run(tiny_root, name, SEED)
    assert not out["correct"]
    assert any(out["checks"][k]["value"] > v
               for k, v in TINY_LIMITS[name].items())
