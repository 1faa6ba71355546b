"""chip_smoke.py's logic at the smoke preset's size on the CPU.

The script itself refuses any backend but the TPU; these tests call its
phase functions directly with small prompts, so tier-1 covers the phases'
control flow and checks. The numbers that matter come only from the chip.
"""
import importlib.util
import pathlib

import jax
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models import build_model

ROOT = pathlib.Path(__file__).resolve().parents[1]
MAX_LEN = 128
N_NEW = 8


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model_params():
    model = build_model(get_smoke("llama3.2-1b"))
    return model, model.init(jax.random.PRNGKey(0))


def _prompts(rng, vocab, n):
    return [rng.integers(0, vocab, (1, int(rng.integers(20, 41))),
                         dtype=np.int32) for _ in range(n)]


def test_main_refuses_cpu_backend(smoke, capsys):
    assert smoke.main() != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_worst_gap_reports_row_of_largest_ratio(smoke):
    logits = np.array([[1.0, 3.0, -4.0], [0.5, 0.25, 0.0]], np.float32)
    worst = smoke.worst_gap(logits, np.array([1, 1]))
    # row 0 chose its maximum; row 1 is 0.25 short, against a tolerance
    # scaled by its own largest |logit|
    assert worst["gap"] == 0.25
    assert worst["tol"] == smoke.LOGIT_RTOL * 0.5
    assert worst["ratio"] == 0.25 / (smoke.LOGIT_RTOL * 0.5)


def test_phases_at_smoke_size(smoke, model_params):
    model, params = model_params
    vocab = model.cfg.vocab_size
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, vocab, (2, 30), dtype=np.int32)

    engine, tokens, rows, rep_a = smoke.phase_engine(model, params, prompts,
                                                     N_NEW, MAX_LEN)
    assert tokens.shape == (2, N_NEW) and rows.shape == (2, N_NEW, vocab)
    assert rep_a["decode_steps"] == N_NEW - 1
    assert rep_a["worst_score_gap"]["ratio"] <= 1.0
    # the check can fail: the least likely tokens sit far off their rows
    wrong = smoke.score_gap(engine, prompts[0], rows[0].argmin(-1))
    assert wrong["ratio"] > 1.0

    rep_b = smoke.phase_pipeline(
        model, params, _prompts(rng, vocab, 4), _prompts(rng, vocab, 4),
        _prompts(rng, vocab, 2), N_NEW, MAX_LEN, score_engine=engine,
        step_timeout=20.0)
    for name in ("healthy", "kill", "add"):
        assert rep_b[name]["failed"] == 0, rep_b
    assert rep_b["healthy"]["retries"] == rep_b["healthy"]["expired"] == 0
    assert rep_b["kill"]["fences"] > 0, rep_b        # the hang was seen
    assert rep_b["tokens"] == 10 * N_NEW
    assert all(s["cache_devices"] for s in rep_b["stages"])

    rep_c = smoke.phase_pallas(model.cfg, params, prompts, tokens, rows,
                               MAX_LEN)
    assert rep_c["rows"] == 2 * N_NEW
    assert rep_c["worst_logit_diff"]["ratio"] <= 1.0
