"""``chip_smoke.py`` off the chip: it refuses to run, and its phases still
work end to end at the CPU preset.

The script is the quickest proof that serving starts on a TPU, and it
drives the engine through ``repro.launch.serve``'s own code plus a few of
the engine's internals (the decode jit, its operands, token selection).
Running its one-chip phase here at the smoke size — with the kernels in
the Pallas interpreter — catches a change that would break it before a
chip does.
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert capsys.readouterr().out == ""        # no result line off the chip


@pytest.mark.timeout(400)
def test_one_chip_phase_at_the_cpu_preset(monkeypatch, capsys):
    # on the CPU the engine resolves the ref backend in interpret mode,
    # which the chip check (rightly) refuses
    monkeypatch.setattr(chip_smoke, "_check_engine_kernels", lambda eng: None)
    chip_smoke.one_chip(layers=2, smoke=True)
    out = capsys.readouterr().out
    assert "decode step, pallas vs ref" in out
    assert out.count(" mpic ") == out.count(" full_recompute ") == 2


def test_greedy_comparison_allows_only_near_ties():
    import numpy as np
    row = np.linspace(-1.0, 1.0, 64).astype(np.float32)
    row[-1] = 2.0                               # a clear winner
    tie = row.copy()
    tie[-2] = tie[-1] - 1e-3                    # top two 0.001 apart
    noisy = [r + 1e-4 for r in (tie, tie)]
    chip_smoke._compare_greedy("tie", [([63, 63], [tie, tie])],
                               [([63, 62], noisy)])
    with pytest.raises(AssertionError, match="without a near-tie"):
        chip_smoke._compare_greedy("no tie", [([63, 63], [row, row])],
                                   [([63, 62], [row, row])])
    far = [r * 1.5 for r in (row, row)]         # logits 50% off
    with pytest.raises(AssertionError, match="logits differ"):
        chip_smoke._compare_greedy("far", [([63, 63], [row, row])],
                                   [([63, 63], far)])
