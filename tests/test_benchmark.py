"""Tests for the benchmark harness CLI (benchmarks/benchmark.py)."""

import csv
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
import benchmark  # noqa: E402


def test_parse_args_defaults():
    args = benchmark.parse_args([])
    assert args.num_trials == 3  # default when neither trials nor timeout
    assert args.max_concurrent_epochs == 2


def test_parse_args_conflicting_data_flags():
    with pytest.raises(SystemExit):
        benchmark.parse_args(["--use-old-data", "--clear-old-data"])


def test_end_to_end_trials_with_stats(tmp_path):
    stats_dir = str(tmp_path / "results")
    benchmark.main([
        "--num-rows", "2000", "--num-files", "2",
        "--num-row-groups-per-file", "1", "--num-reducers", "2",
        "--num-trainers", "1", "--num-epochs", "2", "--batch-size", "500",
        "--num-trials", "2", "--data-dir", str(tmp_path / "data"),
        "--stats-dir", stats_dir, "--overwrite-stats",
        "--utilization-sample-period", "0.1",
    ])
    trial_csvs = [f for f in os.listdir(stats_dir)
                  if f.startswith("trial_stats")]
    epoch_csvs = [f for f in os.listdir(stats_dir)
                  if f.startswith("epoch_stats")]
    assert len(trial_csvs) == 1 and len(epoch_csvs) == 1
    with open(os.path.join(stats_dir, trial_csvs[0])) as f:
        rows = list(csv.DictReader(f))
    assert len(rows) == 2  # two trials
    assert all(float(r["row_throughput"]) > 0 for r in rows)
    with open(os.path.join(stats_dir, epoch_csvs[0])) as f:
        erows = list(csv.DictReader(f))
    assert len(erows) == 4  # 2 trials x 2 epochs


def test_trials_timeout_mode(tmp_path):
    all_stats = []
    filenames, _ = __import__(
        "ray_shuffling_data_loader_tpu.data_generation",
        fromlist=["generate_data_local"]).generate_data_local(
            1000, 2, 1, 0.0, str(tmp_path))
    all_stats = benchmark.run_trials(
        num_epochs=1, filenames=filenames, num_reducers=2, num_trainers=1,
        max_concurrent_epochs=1, collect_stats=False,
        trials_timeout=1.0)
    assert len(all_stats) >= 1


def test_use_old_data_reuses_files(tmp_path, capsys):
    data_dir = str(tmp_path / "data")
    args = [
        "--num-rows", "1000", "--num-files", "2",
        "--num-row-groups-per-file", "1", "--num-reducers", "2",
        "--num-trainers", "1", "--num-epochs", "1", "--batch-size", "250",
        "--num-trials", "1", "--data-dir", data_dir,
        "--stats-dir", str(tmp_path / "r"), "--no-stats",
    ]
    benchmark.main(args)
    mtimes = {f: os.path.getmtime(os.path.join(data_dir, f))
              for f in os.listdir(data_dir)}
    benchmark.main(args + ["--use-old-data"])
    for f, t in mtimes.items():
        assert os.path.getmtime(os.path.join(data_dir, f)) == t


def test_use_old_data_missing_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        benchmark.main([
            "--use-old-data", "--data-dir", str(tmp_path / "empty"),
            "--num-trials", "1",
        ])


def test_file_cache_flag_choices():
    args = benchmark.parse_args(["--file-cache", "disk"])
    assert args.file_cache == "disk"
    args = benchmark.parse_args(["--cold"])
    assert args.file_cache is None and args.cold
    with pytest.raises(SystemExit):
        benchmark.parse_args(["--file-cache", "bogus"])


def test_end_to_end_disk_cache(tmp_path):
    """--file-cache disk through the full harness CLI: the run completes
    and later epochs stream from the decoded-IPC tier."""
    benchmark.main([
        "--num-rows", "2000", "--num-files", "2",
        "--num-row-groups-per-file", "1", "--num-reducers", "2",
        "--num-trainers", "1", "--num-epochs", "3", "--batch-size", "500",
        "--num-trials", "1", "--file-cache", "disk",
        "--data-dir", str(tmp_path / "data"),
        "--stats-dir", str(tmp_path / "results"), "--no-stats"])
