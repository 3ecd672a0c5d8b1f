"""The cell ``sdar_train_8k`` (PR 47) rehearsed on the CPU at the tiny
preset: ``correct`` against the reference, and the reference in bfloat16
refused. Beside ``test_chipbench_sdar.py`` and not in it: the step's and
the reference's compiles are the longest part of either."""

import json

from chipbench import run

CELL = "sdar_train_8k"


def _rehearse(capsys, *control):
    rc = run.main(["--workload", CELL, "--seed", str(2**31 + 47),
                   "--seconds", "1.0", "--trace", "0", "--rehearse",
                   *control])
    out, _ = capsys.readouterr()
    lines = out.strip().splitlines()
    return rc, json.loads(lines[-1]), lines


def test_the_rehearsal_is_correct_and_names_the_cpu(capsys):
    rc, result, lines = _rehearse(capsys)
    assert rc == 0
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"] == {}, "a CPU run reports no device metric"
    assert set(result["compared"]) == {
        "loss_gap", "first_grad_norm_gap", "param_change_norm_gap",
        "epochs_off_the_files", "final_loss_not_finite"}
    compared = [ln for ln in lines if ln.startswith("# compared ")]
    assert compared and all(ln.endswith(" ok") for ln in compared)
    window = next(ln for ln in lines if ln.startswith("# window: "))
    assert "8 steps an epoch" in window


def test_the_reference_in_bfloat16_is_not_correct(capsys):
    """``ref_bf16``: the reference in bfloat16 in the program's place. A
    1e-6 Adam step is under bfloat16's grid at a norm scale of 1 and at an
    embedding row of 1: the leaves never move."""
    rc, result, lines = _rehearse(capsys, "--control", "ref_bf16")
    assert rc == 0, "the run ran to its end"
    assert result["correct"] is False
    failed = [ln for ln in lines
              if ln.startswith("# compared ") and ln.endswith(" FAILED")]
    assert any("param_change_norm_gap" in ln for ln in failed), lines[-12:]
