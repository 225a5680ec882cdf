"""The reference against the Engine at the tiny presets of both
configurations, and the control, through the harness's own run: on each
seed the run comes out correct and the control, judged by the same
comparison on the same sample, does not.

The window is 0 s, so the run sends the mix's first 16 requests, follows
them all to their end and checks them all: the same requests on every
machine, however fast."""

import time

import pytest

from bench.harness import cell as C
from bench.harness.spec import load_cell
from bench.tests.conftest import TINY_LIMITS
from repro.core import lama_layers as ll


@pytest.mark.parametrize("name", sorted(TINY_LIMITS))
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_engine_within_limit_control_not(tiny_root, name, seed):
    cell = load_cell(f"{name}.chat", tiny_root)
    with ll.policy(autotune=False):
        out = C.run_cell(cell, seed, 0.0, False, time.perf_counter(),
                         control=True, log=lambda _: None)
    assert out["attempted"] == cell.mix["check_requests"]
    assert out["correct"], out["checks"]
    assert not out["control_correct"], out["control_checks"]
    assert list(out)[-1] == "checks"
