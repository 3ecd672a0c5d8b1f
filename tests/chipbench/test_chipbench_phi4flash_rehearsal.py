"""The cell ``phi4flash_train_8k`` (PR 40) rehearsed on the CPU at the
tiny preset: ``correct`` against the reference, and epoch 0 delivered
exactly inside the window. Beside ``test_chipbench_phi4flash.py`` and not
in it: the step's and the reference's compiles are half a minute of one
worker."""

import json

from chipbench import run

CELL = "phi4flash_train_8k"


def _rehearse(capsys):
    # 1.5 s, as granite's: epoch 0 ends at step 8, and under the tier-1
    # run's six workers a tiny step of six layers takes 0.1 s
    rc = run.main(["--workload", CELL, "--seed", str(2**31 + 40),
                   "--seconds", "1.5", "--trace", "0", "--rehearse"])
    out, _ = capsys.readouterr()
    assert rc == 0
    lines = out.strip().splitlines()
    return json.loads(lines[-1]), lines


def test_the_rehearsal_is_correct_and_names_the_cpu(capsys):
    result, lines = _rehearse(capsys)
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
