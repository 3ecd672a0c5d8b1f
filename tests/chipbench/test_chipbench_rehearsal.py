"""The harness end to end, on the CPU at the tiny presets: a sound run is
``correct``, and each fault the comparison is there to catch comes out as
not correct. The measurement path itself refuses to run without a TPU.

These are rehearsals: they check counts and the verdict, and print no
device metric.
"""

import json
import os
import re
import subprocess
import sys

import pytest
from chipbench_oracle import BothTrajectories

from chipbench import check, manifest, run

_ROOT = manifest.CHECKOUT

#: The digest is compared over the epochs that ended in the run. The tiny
#: preset's epoch is 256 steps of some 3 ms; this window holds one even on a
#: machine that runs other tests beside it.
_AN_EPOCH_S = 8.0


def _rehearse(capsys, cell, *extra, seed=11, seconds=1.0):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", "0", "--rehearse", *extra])
    out, err = capsys.readouterr()
    assert rc == 0
    lines = out.strip().splitlines()
    # every number compared, beside its limit, ends standard error too
    compared = [ln[2:] for ln in lines if ln.startswith("# compared ")]
    assert compared and err.strip().splitlines()[-len(compared):] == compared
    return json.loads(lines[-1]), lines


#: Sound rehearsals already made in this process, by cell: the tests of
#: where the window opens read the same run as the test of its verdict.
_SOUND = {}


#: What the reference's trajectory returned in each of them, beside what
#: the trajectory of before PR 28 returns for the same arguments.
_TRAJECTORIES = {}


def _rehearse_both(capsys, cell, *extra, **kwargs):
    """``_rehearse`` with the old trajectory run beside the new one:
    (result, lines, [(lower_precision, new, old), ...])."""
    both = BothTrajectories()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(check, "reference_trajectory", both)
        return (*_rehearse(capsys, cell, *extra, **kwargs), both.pairs)


def _sound(capsys, cell):
    if cell not in _SOUND:
        seed = 2**31 + 77 if cell == "dlrm_train" else 5
        *_SOUND[cell], _TRAJECTORIES[cell] = _rehearse_both(capsys, cell,
                                                            seed=seed)
    return _SOUND[cell]


@pytest.mark.parametrize("cell", ["dlrm_train", "bert_train",
                                  "dlrm_train_x4"])
def test_sound_rehearsal_is_correct_and_names_the_cpu(capsys, cell):
    result, lines = _sound(capsys, cell)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"] == {}, "a CPU run reports no device metric"
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"], "compared comes last"
    # the configuration's limits name the numbers: DLRM's later steps are
    # ill-conditioned at some seeds, so it holds the loss at the seeded
    # weights and the median leaf's change (chipbench/check.py)
    trajectory = ({"loss_gap", "param_change_norm_gap"}
                  if cell == "bert_train" else
                  {"first_loss_gap", "param_change_median_leaf_gap"})
    assert set(result["compared"]) == trajectory | {
        "first_grad_norm_gap", "epochs_off_the_files",
        "final_loss_not_finite"}
    if cell != "bert_train":
        assert any(ln.startswith("# not compared") and "worst leaf" in ln
                   for ln in lines), "the numbers left out are still printed"
    assert all(c["value"] <= c["limit"] for c in result["compared"].values())
    compared = [ln for ln in lines if ln.startswith("# compared ")]
    assert compared and all(ln.endswith(" ok") for ln in compared)
    assert any(ln.startswith("# window: ") for ln in lines)
    assert any(ln.startswith("# setup split s: ") for ln in lines)


@pytest.mark.parametrize("cell", ["dlrm_train", "bert_train",
                                  "dlrm_train_x4"])
def test_the_references_numbers_are_those_of_the_trajectory_before(capsys,
                                                                   cell):
    """Every number ``correct`` is decided from, at a fixed seed: the
    trajectory that holds the program's footprint returns what the one
    that held twice as much did (``chipbench_oracle.py``), on the cell's
    own reference, batches and weights."""
    _sound(capsys, cell)
    (lower_precision, new, old), = _TRAJECTORIES[cell]
    assert not lower_precision
    assert len(new["losses"]) == check.STEPS and new["change_norms"]
    assert new == old


_WINDOW_LINE = re.compile(
    r"# window: (?P<counted>\d+) steps .* warm-up (?P<warm>\d+) completions "
    r"\(at least (?P<steps>\d+) steps and (?P<ends>\d+) epoch end\(s\)\), "
    r"(?P<per_epoch>\d+) steps an epoch, epochs ended \[(?P<ended>[\d, ]*)\], "
    r"checked (?P<checked>\d+); next\(batch\) at each epoch end: "
    r"(?P<turnovers>.*)$")


@pytest.mark.parametrize("cell,opens_after", [
    ("dlrm_train_x4", 1),    # train-cached-x4 sets open_after_epoch_ends
    ("dlrm_train", 0),       # train-cached does not: opens where it did
    ("bert_train", 0),
])
def test_the_window_opens_where_the_traffic_says(capsys, cell, opens_after):
    """``dlrm_train_x4`` opens only after the consumer has met epoch 0's
    end and the run-ahead has refilled: that end is in the warm-up, none
    of the warm-up's completions is counted, and the epoch's digest is
    still checked. The other cells open after ``warmup_steps``, as ever."""
    result, lines = _sound(capsys, cell)
    found = _WINDOW_LINE.match(next(ln for ln in lines
                                    if ln.startswith("# window: ")))
    assert found, lines
    warm, steps, per_epoch = (int(found[k]) for k in
                              ("warm", "steps", "per_epoch"))
    ended = [int(e) for e in found["ended"].split(",") if e.strip()]
    in_warm_up = re.findall(r"epoch (\d+) at step (\d+) \(warm-up\)",
                            found["turnovers"])
    assert int(found["ends"]) == opens_after
    # every batch asked for in the window is a counted step, and no other
    assert result["attempted"] == int(found["counted"])
    if opens_after:
        assert [int(e) for e, _ in in_warm_up] == [0]
        # the three first steps and the run-ahead lie before the first
        # completion of the warm-up; the refill and the opening one after
        assert warm == per_epoch - 3 - 4 + 4 + 1
        assert 0 in ended and int(found["checked"]) == len(ended)
        assert any(ln.startswith("# compared epochs_off_the_files: 0 ")
                   and ln.endswith(" ok") for ln in lines)
    else:
        assert not in_warm_up
        assert warm == steps


@pytest.mark.parametrize("cell,control", [
    ("dlrm_train", "ref_bf16"),      # the reference in bfloat16
    ("dlrm_train", "bf16_params"),   # the program itself on bf16 parameters
    ("bert_train", "ref_bf16"),
])
def test_the_precision_below_is_not_correct(capsys, cell, control):
    """The train cells' control: the step in the precision below the
    float32 parameters the configurations state. ``ref_bf16`` puts the
    plain reference, computed in bfloat16, in the program's place (and
    prints the sound program's numbers on earlier lines);
    ``bf16_params`` hands the program bfloat16 parameters."""
    if (cell, control) == ("dlrm_train", "ref_bf16"):
        # the control goes through the same trajectory, after the sound
        # one, and reads what the trajectory of before read
        result, lines, trajectories = _rehearse_both(capsys, cell,
                                                     "--control", control)
        assert [lower for lower, _, _ in trajectories] == [False, True]
        assert all(new == old for _, new, old in trajectories)
    else:
        result, lines = _rehearse(capsys, cell, "--control", control)
    assert result["correct"] is False
    assert any("param_change_" in ln and ln.endswith("FAILED")
               for ln in lines if ln.startswith("# compared"))
    if control == "ref_bf16":
        sound = [ln for ln in lines if ln.startswith("# sound compared")]
        assert len(sound) == 3 and all(ln.endswith(" ok") for ln in sound)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch):
    """The timed path broken underneath: the trainer computes a loss and
    keeps its parameters and optimizer state as they were."""
    from ray_shuffling_data_loader_tpu.parallel import trainer

    def frozen_step(self, *batch):
        import jax
        params, opt_state = jax.tree.map(lambda x: x.copy(),
                                         (self.params, self.opt_state))
        _, _, loss = self._step(params, opt_state, *batch)
        return loss

    monkeypatch.setattr(trainer.SpmdTrainer, "train_step", frozen_step)
    result, lines = _rehearse(capsys, "dlrm_train")
    assert result["correct"] is False
    assert result["compared"]["param_change_median_leaf_gap"]["value"] == 1.0
    assert any("first_grad_norm_gap" in ln and ln.endswith("FAILED")
               for ln in lines)


def test_part_of_the_batch_left_out_is_not_correct(capsys, monkeypatch):
    """The loss over half the rows: what the loss's limit is there for."""
    from chipbench.adapters import dlrm as adapter
    make_loss = adapter.make_loss

    def half_batch_loss(model_cfg, sizes, mesh):
        loss = make_loss(model_cfg, sizes, mesh)
        return lambda p, features, label, step, key: loss(
            p, [f[:f.shape[0] // 2] for f in features],
            label[:label.shape[0] // 2], step, key)

    monkeypatch.setattr(adapter, "make_loss", half_batch_loss)
    result, lines = _rehearse(capsys, "dlrm_train")
    assert result["correct"] is False
    assert any("first_grad_norm_gap" in ln and ln.endswith("FAILED")
               for ln in lines)
    assert any("first_loss_gap" in ln and ln.endswith("FAILED")
               for ln in lines)


def test_a_narrowed_column_is_not_correct(capsys):
    """The loader guarantee's control: one column delivered narrower than
    its values need breaks 'delivered values equal the files' values'."""
    result, lines = _rehearse(capsys, "dlrm_train", "--control",
                              "narrow:embeddings_name16:int8",
                              seconds=_AN_EPOCH_S)
    assert result["correct"] is False
    assert any("epochs_off_the_files" in ln and ln.endswith("FAILED")
               for ln in lines)


def test_a_row_delivered_twice_is_not_correct(capsys, monkeypatch):
    """An answer altered where it is produced: every chunk's first row
    overwritten by its second."""
    from ray_shuffling_data_loader_tpu import jax_dataset
    convert = jax_dataset._BatchConverter.convert

    def doubled(self, table):
        features, label = convert(self, table)
        features = [f.copy() for f in features]
        for f in features:
            f[0] = f[1]
        return features, label

    monkeypatch.setattr(jax_dataset._BatchConverter, "convert", doubled)
    result, lines = _rehearse(capsys, "dlrm_train", seconds=_AN_EPOCH_S)
    assert result["correct"] is False
    assert any("epochs_off_the_files" in ln and ln.endswith("FAILED")
               for ln in lines)


def test_a_stalled_host_raises_the_gap_tail_and_stays_correct(capsys):
    """The tail metric's control: the harness sleeps in ``take()``, longer
    than the run-ahead's steps last, every tenth batch of the window. Four
    groups in ten then hold a gap of the sleep's length, so the tail as
    it is taken (the mean of four gaps in a row) is a quarter of the sleep
    at the least, on any machine; nothing about the answers changes."""
    result, lines = _rehearse(capsys, "dlrm_train", "--control",
                              "stall:60:10")
    assert result["correct"] is True and result["failed"] == 0
    line = next(ln for ln in lines if ln.startswith("# step gap: "))
    tail = float(re.search(r"mean of 4 in a row: p95 ([\d.]+) ms", line)[1])
    assert tail >= 60.0 / 4
    for key in ("p50 / p90 / p95 / p99", "over twice the median",
                "lag-1 autocorrelation"):
        assert key in line


@pytest.mark.parametrize("control,want", [
    (None, (0.0, 0)), ("ref_bf16", (0.0, 0)),
    ("stall:120:40", (0.12, 40)), ("stall:0.5:1", (0.0005, 1)),
])
def test_the_stall_control_is_parsed(control, want):
    from chipbench.loops import train
    assert train._stall_control(control) == pytest.approx(want)


@pytest.mark.parametrize("control", ["stall:0:40", "stall:120:0",
                                     "stall:120"])
def test_a_stall_that_stalls_nothing_is_an_error(control):
    from chipbench.loops import train
    with pytest.raises(ValueError):
        train._stall_control(control)


def test_measurement_path_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT)
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "dlrm_train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=_ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "refusing to measure" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines()
                if ln.startswith("{")], "no result line"
    assert not os.listdir(os.path.join(_ROOT, ".chipbench_scratch"))
