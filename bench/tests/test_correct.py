"""The comparison that decides ``correct``, held against the faults the
timed path can have, and against the lower-precision control.

Each fault is planted in the program underneath a whole run at small widths
(float32, where a sound run matches the reference to rounding), with the
limit of the ``sc2-complete`` cell: the run must come out not correct.
"""

from __future__ import annotations

import json

import pytest

from bench.harness import run_cell
from bench.spec import BENCH_DIR, load_cell

from .conftest import SMALL_MIX, make_root, small_config

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
LIMIT = json.loads((BENCH_DIR / "limits" / "sc2-complete.json").read_text())[
    "worst_logit_gap"]["limit"]


def _cell(tmp_path, arch="starcoder2_3b", limit=LIMIT, **widths):
    root = make_root(tmp_path, {"small": small_config(arch, **widths)},
                     {"small": SMALL_MIX}, [("c", "small", "small")], limit)
    return load_cell("c", root)


def _state_unchanged(monkeypatch):
    import repro.serving.engine as e
    step = e._step

    def fake(params, cfg, cache, tokens):
        return step(params, cfg, cache, tokens)[0], cache
    monkeypatch.setattr(e, "_step", fake)


def _half_batch(monkeypatch):
    import repro.serving.engine as e
    step = e._step

    def fake(params, cfg, cache, tokens):
        logits, cache = step(params, cfg, cache, tokens)
        half = logits.shape[0] // 2
        if half:
            logits = logits.at[half:].set(logits[:logits.shape[0] - half])
        return logits, cache
    monkeypatch.setattr(e, "_step", fake)


def _token_altered(monkeypatch):
    import repro.serving.engine as e
    sample = e.sample_token

    def fake(logits, cfg, key):
        return (sample(logits, cfg, key) + 1) % logits.shape[-1]
    monkeypatch.setattr(e, "sample_token", fake)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "token_altered": _token_altered}


@pytest.mark.parametrize("arch", ["starcoder2_3b", "h2o_danube_1_8b"])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(tmp_path, monkeypatch, fault, arch):
    cell = _cell(tmp_path, arch)
    FAULTS[fault](monkeypatch)
    out = run_cell(cell, 5, 1.0, False, peaks=PEAKS, log=lambda s: None)
    assert not out["correct"], out["checks"]
    assert out["checks"]["worst_logit_gap"]["value"] > LIMIT


@pytest.mark.parametrize("arch", ["starcoder2_3b", "h2o_danube_1_8b"])
def test_sound_run_is_correct(tmp_path, arch):
    out = run_cell(_cell(tmp_path, arch), 6, 1.0, False, peaks=PEAKS,
                   log=lambda s: None)
    assert out["correct"], out["checks"]
    assert out["checks"]["worst_logit_gap"]["value"] < LIMIT / 10


# The limit of the control test's own size (4 layers, d_model 256, vocab
# 2048, bfloat16), set between its readings over seeds 1-12: the program's
# worst gap read at most 0.0225, the control's at least 0.162.
SMALL_LIMIT = 0.08


def test_fp8_control_is_not_correct(tmp_path):
    """In bfloat16 the served tokens stay inside the limit; with the fp8
    control in the program's place the same runs come out not correct."""
    cell = _cell(tmp_path, limit=SMALL_LIMIT, dtype="bfloat16", n_layers=4,
                 d_model=256, head_dim=64, d_ff=1024, vocab_size=2048)
    for seed in (1, 2, 3):
        sound = run_cell(cell, seed, 1.0, False, peaks=PEAKS,
                         log=lambda s: None)
        assert sound["correct"], sound["checks"]
        control = run_cell(cell, seed, 1.0, False, peaks=PEAKS,
                           control=True, log=lambda s: None)
        assert not control["correct"], control["checks"]
