"""Tests for the profiler side of the span vocabulary: a span opened
through ``runtime/telemetry`` is no-op safe without an active trace, lands
under its fixed ``rsdl.*`` name in a captured trace, and the program emits
no annotation outside the documented list."""

import glob
import os

import numpy as np

from ray_shuffling_data_loader_tpu import data_generation as dg
from ray_shuffling_data_loader_tpu.jax_dataset import JaxShufflingDataset
from ray_shuffling_data_loader_tpu.runtime import telemetry
from ray_shuffling_data_loader_tpu.utils import tracing

#: The free-text names ``utils/tracing.trace_span`` used to emit.
_OLD_NAMES = ("shuffle_map", "shuffle_reduce", "batch_convert",
              "table_convert", "batch_transfer", "table_transfer",
              "spill_load", "spill_write", "train")


def _host_events(trace_dir):
    """(name, stats) of every host event of the one trace under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats) if ev.name.startswith("rsdl.") else {}
                out.append((ev.name, stats))
    return out


def test_span_noop_without_active_trace():
    telemetry.configure(enabled_flag=True)
    with telemetry.span("convert", epoch=0):
        x = 1 + 1
    assert x == 2


def test_span_lands_in_trace_and_recorder_with_the_same_epoch(tmp_path):
    telemetry.configure(enabled_flag=True)
    trace_dir = str(tmp_path / "trace")
    with tracing.profile_trace(trace_dir):
        with telemetry.span("carve", epoch=3, batch=7):
            pass
        with telemetry.span("plan_steal", epoch=3):   # recorder-only kind
            pass
    annotated = [(n, s) for n, s in _host_events(trace_dir)
                 if n.startswith("rsdl.")]
    assert [n for n, _ in annotated] == ["rsdl.feed.carve"]
    assert annotated[0][1]["epoch"] == 3 and annotated[0][1]["batch"] == 7
    recorded = [e for e in telemetry.recorder().events()
                if e["kind"] in ("carve", "plan_steal")]
    assert [(e["kind"], e["epoch"]) for e in recorded] == [
        ("carve", 3), ("plan_steal", 3)]
    assert recorded[0]["batch"] == 7


def test_emitted_annotation_names_are_the_documented_list(
        tmp_path, tmp_parquet_dir, monkeypatch):
    """A traced three-epoch run through the bulk path and one trainer
    step: every ``rsdl.*`` name in the trace is on the fixed list, the
    list's names this run can reach are all there, and none of the old
    free-text names is."""
    monkeypatch.setenv("RSDL_EXECUTOR_BACKEND", "thread")
    telemetry.configure(enabled_flag=True)
    filenames, _ = dg.generate_data_local(600, 2, 1, 0.0, tmp_parquet_dir)
    trace_dir = str(tmp_path / "trace")
    with tracing.profile_trace(trace_dir):
        ds = JaxShufflingDataset(
            filenames, num_epochs=2, num_trainers=1, batch_size=50, rank=0,
            num_reducers=2, queue_name="trace-names", device_rebatch=True,
            feature_columns=list(dg.FEATURE_COLUMNS),
            feature_types=[np.int32] * len(dg.FEATURE_COLUMNS),
            label_column=dg.LABEL_COLUMN)
        for epoch in range(2):
            ds.set_epoch(epoch)
            assert sum(label.shape[0] for _, label in ds) == 600
        with tracing.step_span(0):
            pass
    names = {n for n, _ in _host_events(trace_dir)}
    emitted = {n for n in names if n.startswith("rsdl.")}
    assert emitted <= telemetry.annotation_names()
    unreachable = {"rsdl.loader.spill_write", "rsdl.loader.spill_read",
                   "rsdl.loader.queue_fetch"}   # no spill, no served queue
    assert emitted == telemetry.annotation_names() - unreachable
    assert not [n for n in names for old in _OLD_NAMES
                if n == old or n.startswith(old + " ")]
    # Identity rides in the arguments, never in the name.
    assert all(" " not in n for n in emitted)


def test_step_span_context():
    with tracing.step_span(3):
        pass


def test_profile_trace_captures_pipeline(tmp_path, tmp_parquet_dir,
                                         monkeypatch):
    """A traced end-to-end pipeline run writes profiler artifacts and the
    annotated stages (map/reduce/convert/transfer) run under the trace."""
    filenames, _ = dg.generate_data_local(200, 2, 1, 0.0, tmp_parquet_dir)
    trace_dir = str(tmp_path / "trace")
    with tracing.profile_trace(trace_dir):
        ds = JaxShufflingDataset(
            filenames, num_epochs=1, num_trainers=1, batch_size=50, rank=0,
            num_reducers=2, queue_name="trace-test",
            feature_columns=list(dg.FEATURE_COLUMNS),
            feature_types=[np.int32] * len(dg.FEATURE_COLUMNS),
            label_column=dg.LABEL_COLUMN)
        ds.set_epoch(0)
        rows = sum(label.shape[0] for _, label in ds)
    assert rows == 200
    found = []
    for root, _dirs, files in os.walk(trace_dir):
        found.extend(os.path.join(root, f) for f in files)
    assert found, "profiler trace produced no files"
