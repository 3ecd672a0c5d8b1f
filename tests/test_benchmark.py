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


def test_bench_py_json_contract(bench_env):
    """bench.py is the driver-facing artifact: it must exit 0 and print
    ONE parseable JSON line with the contract keys, on a tiny CPU config."""
    import json
    import os
    import subprocess
    import sys

    env = bench_env(RSDL_BENCH_TRAIN_EPOCHS="2",
                    RSDL_BENCH_TRAIN_BATCH="2048")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=repo)
    assert proc.returncode == 0, proc.stderr[-2000:]
    json_lines = [l for l in proc.stdout.splitlines()
                  if l.startswith("{")]
    assert len(json_lines) == 1, proc.stdout
    record = json.loads(json_lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "stall_pct",
                "stall_s", "cache_mode", "host_cpus", "timed_epochs",
                # All three phases ride one JSON line: the cached headline,
                # the cold regime, and the contract metric (stall under a
                # REAL DLRM train step).
                "cold_rows_per_sec", "vs_baseline_cached",
                "stall_pct_under_train", "train_rows_per_sec",
                "train_step_ms_mean", "train_final_loss",
                # Executor honesty fields (ISSUE 7): the record names the
                # data plane that actually ran and normalizes per-core by
                # the effective pool width, never os.cpu_count().
                "executor_backend", "executor_workers",
                "executor_worker_pids", "rows_per_s_per_core",
                "worker_scaling"):
        assert key in record, key
    assert record["executor_backend"] in ("thread", "process")
    assert record["executor_workers"] >= 1
    assert record["rows_per_s_per_core"] == pytest.approx(
        record["value"] / record["executor_workers"], rel=1e-3)
    scaling = record["worker_scaling"]
    assert scaling["rows_per_s_by_workers"]["1"] > 0
    assert record["metric"] == "shuffle_ingest_rows_per_sec_per_chip"
    assert record["unit"] == "rows/s"
    assert record["value"] > 0 and record["vs_baseline"] > 0
    assert record["cold_rows_per_sec"] > 0
    assert record["train_rows_per_sec"] > 0
    # The real-step train phase must actually have trained (finite loss).
    assert record["train_final_loss"] is not None
    assert 0 <= record["stall_pct_under_train"] <= 100


def test_bench_py_phase_subset(bench_env):
    """RSDL_BENCH_PHASES trims phases; a cold-only run keeps the legacy
    cold headline metric name."""
    import json
    import os
    import subprocess
    import sys

    env = bench_env(RSDL_BENCH_PHASES="cold")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=repo)
    assert proc.returncode == 0, proc.stderr[-2000:]
    record = json.loads([l for l in proc.stdout.splitlines()
                         if l.startswith("{")][0])
    assert record["metric"] == "shuffle_ingest_rows_per_sec_per_chip_cold"
    assert "stall_pct_under_train" not in record
    assert record["cache_mode"] == "cold"


def test_bench_py_tenancy_phase_contract(bench_env):
    """A tenancy-only bench run (the CI contention leg in dryrun scale)
    exits 0 and reports the structural tenancy keys: fairness ratio,
    per-tenant rates, p99s and the journaled admission evidence. The
    pass/fail verdict (tenancy_ok) is NOT asserted — at smoke scale
    the ratios are scheduler-noise-bound; the nightly leg at full
    scale plus rsdl_bench_diff gate the actual values."""
    import json
    import os
    import subprocess
    import sys

    env = bench_env(RSDL_BENCH_PHASES="tenancy",
                    RSDL_BENCH_TENANCY_REDUCERS="8",
                    RSDL_BENCH_TENANCY_EPOCHS="1")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=repo)
    assert proc.returncode == 0, proc.stderr[-2000:]
    record = json.loads([l for l in proc.stdout.splitlines()
                         if l.startswith("{")][0])
    assert record["metric"] == "tenancy_hot_rows_per_sec"
    for key in ("tenancy_weight_ratio", "tenancy_fairness_ratio",
                "tenancy_hot_rows", "tenancy_cold_rows_at_hot_finish",
                "tenancy_hot_rows_per_sec", "tenancy_cold_rows_per_sec",
                "tenancy_solo_rows_per_sec", "tenancy_hot_slo_p99_ms",
                "tenancy_admitted", "tenancy_rejected",
                "tenancy_admission_replay_ok", "tenancy_ok"):
        assert key in record, key
    assert record["tenancy_weight_ratio"] == 3.0
    assert record["tenancy_hot_rows"] > 0
    assert record["tenancy_fairness_ratio"] > 0
    # The admission evidence is deterministic at ANY scale: two
    # accepts, one oversized reject, and a bit-identical replay.
    assert record["tenancy_admitted"] == 2
    assert record["tenancy_rejected"] == 1
    assert record["tenancy_admission_replay_ok"] is True


def test_run_ingest_phase_dict_contract(tmp_path):
    """run_ingest returns the phase-dict fields main() assembles into the
    JSON record, for both clock modes (cached: from first delivery;
    cold: end-to-end from launch)."""
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_mod", os.path.join(repo, "bench.py"))
    bench_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_mod)

    import jax

    from ray_shuffling_data_loader_tpu import data_generation as dg

    filenames, _ = dg.generate_data_local(8000, 2, 1, 0.0, str(tmp_path))
    for cold in (False, True):
        r = bench_mod.run_ingest(
            jax, filenames, num_epochs=2, batch_size=1000,
            num_reducers=2, prefetch_size=2, cold=cold,
            device_rebatch=False, step_ms=0,
            qname=f"ingest-contract-{cold}")
        for key in ("rows_per_s", "stall_s", "stall_pct", "wait_mean_ms",
                    "batches", "timed_epochs", "duration_s", "fill_s"):
            assert key in r, (cold, key)
        assert r["rows_per_s"] > 0
        assert r["timed_epochs"] == 2
        assert r["fill_s"] > 0
        if cold:
            # Cold clocks from launch: the window contains the fill.
            assert r["duration_s"] >= r["fill_s"]


def test_scanned_chunk_stepper_matches_sequential_micro_steps():
    """The train phase's one-jit-call-per-chunk lax.scan stepper must be
    bit-equivalent (up to float tolerance) to dispatching each micro-step
    from Python — same slices, same Adam updates, same final loss."""
    import importlib.util

    import numpy as np

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_mod2", os.path.join(repo, "bench.py"))
    bench_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_mod)

    import jax
    import jax.numpy as jnp
    import optax
    from jax import lax

    from ray_shuffling_data_loader_tpu.models import dlrm

    cfg = dlrm.DLRMConfig(vocab_sizes=(13, 7, 20), embed_dim=4,
                          top_hidden=(16, 8), compute_dtype=jnp.float32)
    opt = optax.adam(1e-3)
    mb, steps_per_chunk = 4, 3
    chunk = mb * steps_per_chunk
    rng = np.random.default_rng(0)
    cols = [jnp.asarray(rng.integers(0, v, chunk).astype(np.int32))
            for v in cfg.vocab_sizes]
    labels = jnp.asarray(rng.random((chunk, 1)).astype(np.float32))

    params = dlrm.init(cfg, jax.random.key(0))
    opt_state = opt.init(params)
    stepper = bench_mod._make_chunk_stepper(jax, dlrm, cfg, opt, mb,
                                            steps_per_chunk)
    s_params, s_opt, s_loss = stepper(params, opt_state, cols, labels)

    # Reference: the same math dispatched one micro-step at a time.
    params = dlrm.init(cfg, jax.random.key(0))
    opt_state = opt.init(params)
    loss = None
    for i in range(steps_per_chunk):
        mcols = [lax.dynamic_slice_in_dim(c, i * mb, mb, axis=0)
                 for c in cols]
        mlab = lax.dynamic_slice_in_dim(labels, i * mb, mb, axis=0)
        loss, grads = jax.value_and_grad(
            lambda p: dlrm.loss_fn(cfg, p, None, mcols, mlab))(params)
        updates, opt_state = opt.update(grads, opt_state)
        params = optax.apply_updates(params, updates)

    np.testing.assert_allclose(float(s_loss), float(loss), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a),
                                                np.asarray(b), rtol=1e-5,
                                                atol=1e-6),
        s_params, params)


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


def test_run_ingest_multi_contract(tmp_path):
    """Multi-trainer ingest: aggregate rows cover every rank's stream,
    the launch clock is recorded, and the result dict carries everything
    main() publishes."""
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_mod3", os.path.join(repo, "bench.py"))
    bench_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_mod)

    import jax

    from ray_shuffling_data_loader_tpu import data_generation as dg

    filenames, _ = dg.generate_data_local(8000, 2, 1, 0.0, str(tmp_path))
    r = bench_mod.run_ingest_multi(
        jax, filenames, num_epochs=2, batch_size=500, num_reducers=2,
        prefetch_size=2, cold=False, device_rebatch=False, step_ms=0,
        qname="ingest-multi-contract", num_trainers=2)
    for key in ("rows_per_s", "stall_s", "stall_pct", "wait_mean_ms",
                "batches", "timed_epochs", "duration_s", "fill_s",
                "num_trainers", "clock"):
        assert key in r, key
    assert r["num_trainers"] == 2
    assert r["clock"] == "launch"
    assert r["rows_per_s"] > 0
    # drop_last=True per rank: both ranks' full batches are consumed;
    # 8000 rows over 2 ranks x 2 epochs ~ 16000 minus per-rank remainders.
    assert r["rows_per_s"] * r["duration_s"] >= 14000
