"""The harness's own guards: the carve's warm-up fails loudly where the
program's hooks are gone, a traffic mix's ``policy_env`` reaches the
program and is put back, and the allocator probe's verdict."""

import os

import pytest

from chipbench import harness, manifest, run
from chipbench.probes import allocator_peak


class _BulkDataset:
    """A loader on its bulk re-batch path with no converter to reach."""

    device_rebatch = True


def _context(cell="dlrm_train"):
    return harness.Context(
        cell=manifest.resolve_cell(cell), seed=1, seconds=1.0, trace=False,
        rehearse=True, control=None, started_at=0.0, scratch="")


def test_warming_the_carve_fails_loudly_without_the_converter():
    with pytest.raises(AttributeError, match="_converter"):
        harness.warm_rebatch_shapes(_context(), _BulkDataset(), [], None,
                                    4096, None, 4096)


def test_warming_the_carve_fails_loudly_without_the_chunk_length(
        monkeypatch):
    from ray_shuffling_data_loader_tpu import jax_dataset
    monkeypatch.delattr(jax_dataset, "_MAX_CHUNK_BATCHES")
    ds = _BulkDataset()
    ds._converter = object()
    with pytest.raises(AttributeError, match="_MAX_CHUNK_BATCHES"):
        harness.warm_rebatch_shapes(_context(), ds, [], None, 4096, None,
                                    4096)


def test_a_loader_on_per_batch_transfers_has_nothing_to_warm(capsys):
    class PerBatch:
        device_rebatch = False

    harness.warm_rebatch_shapes(_context(), PerBatch(), [], None, 4096,
                                None, 4096)
    assert "nothing to warm" in capsys.readouterr().out


def test_policy_env_reaches_the_program_and_is_put_back(capsys, monkeypatch):
    """A traffic mix asks for the thread backend through the program's own
    knob; the run uses it and leaves the environment as it was."""
    monkeypatch.delenv("RSDL_EXECUTOR_BACKEND", raising=False)
    cell = manifest.resolve_cell("dlrm_train")
    cell.traffic["policy_env"] = {"RSDL_EXECUTOR_BACKEND": "thread"}
    monkeypatch.setattr(manifest, "resolve_cell", lambda name: cell)
    rc = run.main(["--workload", "dlrm_train", "--seed", "9",
                   "--seconds", "0.5", "--trace", "0", "--rehearse"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "# policy: RSDL_EXECUTOR_BACKEND=thread" in out
    assert "'backend': 'thread'" in out
    assert "RSDL_EXECUTOR_BACKEND" not in os.environ


def test_policy_env_takes_only_the_programs_knobs():
    ctx = _context()
    ctx.cell.traffic["policy_env"] = {"PATH": "/nowhere"}
    with pytest.raises(ValueError, match="RSDL_"):
        harness.make_dataset(ctx, [], 4096, 1, None, {}, 4096)


@pytest.mark.parametrize("temp,before,after,left_out", [
    (1_073_741_824, 134_217_728, 201_326_592, True),     # rose by the output
    (1_073_741_824, 134_217_728, 1_275_068_416, False),  # holds them all
    (1_000, 0, 249, True),
    (1_000, 0, 250, False),
])
def test_allocator_probe_verdict(temp, before, after, left_out):
    got, share = allocator_peak.verdict(temp, before, after)
    assert got is left_out
    assert share == pytest.approx((after - before) / temp)


def test_allocator_probe_refuses_off_the_chip(capsys):
    assert allocator_peak.main() == 2
    assert "not 'tpu'" in capsys.readouterr().err


def test_completions_are_kept_only_where_asked(tmp_path, monkeypatch):
    import json
    ctx = _context("dlrm_train_x4")
    kept = {"completions": [1.0, 1.5], "epoch_ends": []}
    monkeypatch.delenv("CHIPBENCH_KEEP_GAPS", raising=False)
    harness.keep_completions(ctx, kept)
    assert not list(tmp_path.iterdir())
    monkeypatch.setenv("CHIPBENCH_KEEP_GAPS", str(tmp_path / "gaps"))
    harness.keep_completions(ctx, kept)
    (path,) = (tmp_path / "gaps").iterdir()
    assert path.name.startswith("dlrm_train_x4-1-")
    assert json.loads(path.read_text()) == dict(
        kept, cell="dlrm_train_x4", seed=1)
