"""The harness end to end, on the CPU at the tiny presets: a sound run is
``correct``, and each fault the comparison is there to catch comes out as
not correct. The measurement path itself refuses to run without a TPU.

These are rehearsals: they check counts and the verdict, and print no
device metric.
"""

import json
import os
import subprocess
import sys

import pytest

from chipbench import manifest, run

_ROOT = manifest.CHECKOUT

#: The digest is compared over the epochs that ended in the run. The tiny
#: preset's epoch is 256 steps of some 3 ms; this window holds one even on a
#: machine that runs other tests beside it.
_AN_EPOCH_S = 8.0


def _rehearse(capsys, cell, *extra, seed=11, seconds=1.0):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", "0", "--rehearse", *extra])
    out = capsys.readouterr().out
    assert rc == 0
    lines = out.strip().splitlines()
    return json.loads(lines[-1]), lines


@pytest.mark.parametrize("cell", ["dlrm_train", "bert_train",
                                  "dlrm_train_x4"])
def test_sound_rehearsal_is_correct_and_names_the_cpu(capsys, cell):
    seed = 2**31 + 77 if cell == "dlrm_train" else 5
    result, lines = _rehearse(capsys, cell, seed=seed)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"] == {}, "a CPU run reports no device metric"
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    compared = [ln for ln in lines if ln.startswith("# compared ")]
    assert compared and all(ln.endswith(" ok") for ln in compared)
    assert any(ln.startswith("# window: ") for ln in lines)
    assert any(ln.startswith("# setup split s: ") for ln in lines)


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
    result, lines = _rehearse(capsys, cell, "--control", control)
    assert result["correct"] is False
    assert any("param_change_norm_gap" in ln and ln.endswith("FAILED")
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
    assert any("param_change_norm_gap" in ln and ln.endswith("FAILED")
               for ln in lines)
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
