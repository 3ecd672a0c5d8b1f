"""The cell ``lfm2_train_8k`` (PR 44) rehearsed on the CPU at the tiny
preset: ``correct`` against the reference, epoch 0 delivered exactly inside
the window, and both fault controls refused. Beside
``test_chipbench_lfm2.py`` and not in it: the step's and the reference's
compiles are half a minute of one worker."""

import json

import pytest

from chipbench import run

CELL = "lfm2_train_8k"


def _rehearse(capsys, *control):
    # 1.5 s, as granite's: epoch 0 ends at step 8, and under the tier-1
    # run's six workers a tiny step of five layers takes 0.1 s
    rc = run.main(["--workload", CELL, "--seed", str(2**31 + 44),
                   "--seconds", "1.5", "--trace", "0", "--rehearse",
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
    # 32 rows of 32 tokens, 4 a step: at least one whole epoch was checked
    window = next(ln for ln in lines if ln.startswith("# window: "))
    assert "8 steps an epoch" in window and "epochs ended [0" in window


@pytest.mark.parametrize("control", ["ref_bf16", "bf16_params"])
def test_a_precision_below_the_configurations_is_not_correct(capsys,
                                                             control):
    """``ref_bf16``: the reference in bfloat16 in the program's place;
    ``bf16_params``: the program on bfloat16 parameters. Each fails at
    least one limit of the tiny preset (a 1e-4 Adam step is under
    bfloat16's grid at a norm scale of 1: the leaf never moves)."""
    rc, result, lines = _rehearse(capsys, "--control", control)
    assert rc == 0, "the run ran to its end"
    assert result["correct"] is False
    failed = [ln for ln in lines
              if ln.startswith("# compared ") and ln.endswith(" FAILED")]
    assert any("param_change_norm_gap" in ln for ln in failed), lines[-12:]
