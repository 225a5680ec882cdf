"""The chip smoke's phases, run here at the tiny qwen3 preset on the CPU
(interpret-mode kernels), and its refusal to run without a TPU."""

import importlib.util
import pathlib
import sys

import pytest

from repro.configs import get_config
from repro.runtime.engine import EngineConfig

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
smoke = sys.modules["chip_smoke"] = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)


@pytest.fixture
def isolated_caches(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_ACT_CALIB_CACHE",
                       str(tmp_path / "act_calib.json"))
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    return tmp_path


def _tiny():
    cfg = get_config("qwen3-1.7b", tiny=True).replace(
        compute_dtype="float32")
    ecfg = EngineConfig(num_slots=4, block_size=4, max_seq_len=64,
                        prefill_chunk=16)
    first, later = smoke.serve_requests(
        cfg.vocab_size, lens=(8, 40, 20, 12), shared=8, tails=(4, 16, 6, 24),
        new_tokens=4)
    return cfg, ecfg, first, later


def test_requests_shape_the_traffic():
    cfg, ecfg, first, later = _tiny()
    full_first, full_later = smoke.serve_requests(151_936)
    lens = [len(r.prompt) for r in full_first + full_later]
    assert min(lens) >= 64 and max(lens) == 600 and sum(
        n > 256 for n in lens) >= 2
    shared = [r.prompt[:128] for r in full_first[:2] + full_later]
    assert all((p == shared[0]).all() for p in shared)
    assert {r.max_new_tokens for r in full_first + full_later} == {32}


def test_kernels_phase(isolated_caches):
    cfg = get_config("qwen3-1.7b", tiny=True)
    w = smoke.Widths.of(cfg, slots=2, block=4, chunk=8, max_blk=4)
    assert smoke.kernels_phase(w, autotune_m=16) == []


def test_serve_phase(isolated_caches):
    cfg, ecfg, first, later = _tiny()
    failed, tokens = smoke.serve_phase(cfg, ecfg, first, later,
                                       logit_uids=(2, 1))
    assert failed == []
    assert set(tokens) == set(range(8))


def test_serve_codes_phase(isolated_caches):
    cfg, ecfg, first, later = _tiny()
    assert smoke.serve_codes_phase(cfg, ecfg, first, later, None) == []


def test_refuses_without_tpu(capsys):
    assert smoke.main() == 2
    out = capsys.readouterr()
    assert out.out == "" and "needs a TPU" in out.err


def test_code_levels_are_monotone_in_value():
    import jax.numpy as jnp
    import numpy as np

    from repro.core import exponential_quant as eq

    codes = np.arange(256)
    vals = np.asarray(eq.decode_meta(jnp.asarray(codes),
                                     jnp.asarray([0.01, 0.0, 1.1, 7.0])))
    order = np.argsort(smoke.code_levels(codes))
    assert np.all(np.diff(vals[order]) > 0)
