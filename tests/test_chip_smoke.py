"""Rehearsal of ``chip_smoke.py`` on the CPU at reduced widths: its served,
rejected-draft, kernel and fleet phases as functions (kernels in interpret
mode), and its entry point's refusal to run without a TPU."""
import importlib.util
from pathlib import Path

import pytest


@pytest.fixture(scope="module")
def chip_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served(chip_smoke):
    return chip_smoke.served_phase(reduced=True, rounds=2, prompt_len=260,
                                   verbose=False)


def test_served_phase_matches_solo_replays(served):
    report, _ = served
    assert report["sessions_answered"] == 4
    assert report["oracle_replays_matched"] == 4
    assert report["tokens_committed"] > 0
    assert report["stats"]["backend"] == "paged"
    assert report["dispatch_counts"]["verify"] >= 1
    assert report["stats"]["prefill_chunks"] >= 4 * 3   # 260 tokens / 128


def test_rejected_draft_phase_rolls_back(chip_smoke, served):
    _, r = served
    eng = r["server"].engine
    out = chip_smoke.rejected_draft_phase(eng.cfg, eng.params)
    assert len(out["accept_lens"]) == 3
    assert all(a < out["k"] for a in out["accept_lens"])


def test_kernel_phase_within_tolerance(chip_smoke):
    out = chip_smoke.kernel_phase()
    assert set(out) == {"float32", "bfloat16"}
    for row in out.values():
        assert row["max_abs_err"] <= row["tol"]


def test_fleet_phase_matches_stacked_fleet(chip_smoke):
    """The four-chip phase's comparison, on however many devices JAX sees
    (one here: both fleets stack on it and must still agree)."""
    out = chip_smoke.fleet_phase(reduced=True, rounds=1, prompt_len=260,
                                 verbose=False)
    assert out["sessions"] == 4
    assert set(out["placement"]) == {"v0", "v1", "v2", "v3"}
    assert out["generated_tokens"] > 0


def test_main_refuses_without_tpu(chip_smoke, monkeypatch, capsys):
    monkeypatch.setattr("sys.argv", ["chip_smoke.py"])
    assert chip_smoke.main() != 0
    assert '"ok"' not in capsys.readouterr().out
