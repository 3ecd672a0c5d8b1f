"""Shuffle benchmark harness.

Capability parity with the reference's harness (reference:
benchmarks/benchmark.py:1-206): generate (or reuse) synthetic Parquet data,
run N trials — or as many as fit in a time budget — of the multi-epoch
shuffle against a throwaway consumer, and write the trial/epoch stats CSVs.
CLI surface mirrors the reference's argparse flags (reference:
benchmark.py:71-98); ``--cluster`` is replaced by host-local execution on
the TPU-VM (the executor scales with host cores, SURVEY.md §7).

Usage:
    python benchmarks/benchmark.py --num-rows 4_000_000 --num-files 25 \
        --num-reducers 32 --num-trainers 4 --num-epochs 10 \
        --batch-size 250_000 --max-concurrent-epochs 2
"""

from __future__ import annotations

import argparse
import os
import sys
import timeit
from typing import List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_shuffling_data_loader_tpu import data_generation as datagen  # noqa: E402
from ray_shuffling_data_loader_tpu import stats as stats_mod  # noqa: E402
from ray_shuffling_data_loader_tpu.shuffle import (  # noqa: E402
    shuffle_no_stats, shuffle_with_stats)
from ray_shuffling_data_loader_tpu.utils.logger import setup_custom_logger  # noqa: E402

logger = setup_custom_logger(__name__)

# Defaults mirroring the reference CLI (reference: benchmark.py:16-19,73-98).
DEFAULT_UTILIZATION_SAMPLE_PERIOD = 5.0


def dummy_batch_consumer(rank: int, epoch: int, batches) -> None:
    """Throwaway consumer (reference: benchmark.py:22-23)."""
    del rank, epoch, batches


def run_trials(num_epochs: int,
               filenames: List[str],
               num_reducers: int,
               num_trainers: int,
               max_concurrent_epochs: int,
               collect_stats: bool = True,
               utilization_sample_period: float = (
                   DEFAULT_UTILIZATION_SAMPLE_PERIOD),
               num_trials: Optional[int] = None,
               trials_timeout: Optional[float] = None,
               seed: int = 0,
               map_transform=None,
               reduce_transform=None,
               file_cache="auto",
               max_inflight_bytes: Optional[int] = None,
               spill_dir: Optional[str] = None) -> List[Tuple]:
    """Run fixed-count or time-bounded trials
    (reference: benchmark.py:26-68)."""
    all_stats = []
    if num_trials is not None:
        for trial in range(num_trials):
            logger.info("Starting trial %d", trial)
            stats, store_stats = _one_trial(
                num_epochs, filenames, num_reducers, num_trainers,
                max_concurrent_epochs, collect_stats,
                utilization_sample_period, seed + trial,
                map_transform, reduce_transform, file_cache,
                max_inflight_bytes, spill_dir)
            _log_trial(trial, stats)
            all_stats.append((stats, store_stats))
    elif trials_timeout is not None:
        start = timeit.default_timer()
        trial = 0
        while timeit.default_timer() - start < trials_timeout:
            logger.info("Starting trial %d", trial)
            stats, store_stats = _one_trial(
                num_epochs, filenames, num_reducers, num_trainers,
                max_concurrent_epochs, collect_stats,
                utilization_sample_period, seed + trial,
                map_transform, reduce_transform, file_cache,
                max_inflight_bytes, spill_dir)
            _log_trial(trial, stats)
            all_stats.append((stats, store_stats))
            trial += 1
    else:
        raise ValueError("Must supply num_trials or trials_timeout")
    return all_stats


def _one_trial(num_epochs, filenames, num_reducers, num_trainers,
               max_concurrent_epochs, collect_stats,
               utilization_sample_period, seed,
               map_transform=None, reduce_transform=None,
               file_cache="auto", max_inflight_bytes=None, spill_dir=None):
    if collect_stats:
        return shuffle_with_stats(
            filenames, dummy_batch_consumer, num_epochs, num_reducers,
            num_trainers, max_concurrent_epochs, seed=seed,
            utilization_sample_period=utilization_sample_period,
            map_transform=map_transform, reduce_transform=reduce_transform,
            file_cache=file_cache, max_inflight_bytes=max_inflight_bytes,
            spill_dir=spill_dir)
    return shuffle_no_stats(
        filenames, dummy_batch_consumer, num_epochs, num_reducers,
        num_trainers, max_concurrent_epochs, seed=seed,
        map_transform=map_transform, reduce_transform=reduce_transform,
        file_cache=file_cache, max_inflight_bytes=max_inflight_bytes,
        spill_dir=spill_dir)


def _log_trial(trial, stats):
    duration = (stats.duration
                if isinstance(stats, stats_mod.TrialStats) else stats)
    logger.info("Trial %d done after %.3fs", trial, duration)


def parse_args(argv=None):
    parser = argparse.ArgumentParser(
        description="Shuffling data loader benchmark (TPU-VM host)")
    parser.add_argument("--num-rows", type=int, default=4 * (10**6))
    parser.add_argument("--num-files", type=int, default=25)
    parser.add_argument("--num-row-groups-per-file", type=int, default=5)
    parser.add_argument("--num-reducers", type=int, default=8)
    parser.add_argument("--num-trainers", type=int, default=4)
    parser.add_argument("--num-epochs", type=int, default=10)
    parser.add_argument("--max-concurrent-epochs", type=int, default=2)
    parser.add_argument("--batch-size", type=int, default=250_000)
    parser.add_argument("--num-trials", type=int, default=None)
    parser.add_argument("--trials-timeout", type=float, default=None)
    parser.add_argument("--max-row-group-skew", type=float, default=0.0)
    parser.add_argument("--utilization-sample-period", type=float,
                        default=DEFAULT_UTILIZATION_SAMPLE_PERIOD)
    parser.add_argument("--data-dir", type=str, default="./benchmark_data")
    parser.add_argument("--stats-dir", type=str, default="./results")
    parser.add_argument("--use-old-data", action="store_true",
                        help="Reuse already-generated files in --data-dir")
    parser.add_argument("--clear-old-data", action="store_true")
    parser.add_argument("--no-stats", action="store_true")
    parser.add_argument("--no-epoch-stats", action="store_true")
    parser.add_argument("--overwrite-stats", action="store_true")
    parser.add_argument("--unique-stats", action="store_true")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--workload", choices=["dlrm", "imagenet", "bert"], default="dlrm",
        help="dlrm: tabular DLRM rows (reference DATA_SPEC, default); "
             "imagenet: encoded images with decode inside shuffle reducers "
             "(BASELINE config 3 — --image-size controls H=W); bert: token "
             "sequences with the narrow-dtype cast at the map stage")
    parser.add_argument("--image-size", type=int, default=64,
                        help="imagenet workload: square image edge length")
    parser.add_argument("--seq-len", type=int, default=128,
                        help="bert workload: tokens per row")
    parser.add_argument("--cold", action="store_true",
                        help="disable the cross-epoch file-table cache: "
                             "every epoch re-reads + re-decodes Parquet "
                             "(the reference's corpus->RAM regime)")
    parser.add_argument(
        "--file-cache", choices=["auto", "none", "disk"], default=None,
        help="decoded-table cache tier: auto (RAM, default), none "
             "(re-decode every epoch; same as --cold), or disk (decode "
             "once, stream later epochs from mmap'd Arrow IPC scratch — "
             "the corpus-exceeds-RAM answer). Overrides --cold when given.")
    parser.add_argument("--max-inflight-bytes", type=int, default=None,
                        help="transient pipeline byte budget; the driver "
                             "throttles epoch launches against it")
    parser.add_argument("--spill-dir", type=str, default=None,
                        help="with --max-inflight-bytes: spill over-budget "
                             "reducer outputs to Arrow IPC files here")
    args = parser.parse_args(argv)
    if args.num_trials is None and args.trials_timeout is None:
        args.num_trials = 3
    if args.use_old_data and args.clear_old_data:
        parser.error("cannot pass both --use-old-data and --clear-old-data")
    return args


def main(argv=None) -> None:
    args = parse_args(argv)
    if args.clear_old_data:
        import glob
        logger.info("Clearing old data from %s", args.data_dir)
        for f in glob.glob(os.path.join(args.data_dir, "*.parquet.snappy")):
            os.remove(f)
    if args.use_old_data:
        import glob
        filenames = sorted(
            glob.glob(os.path.join(args.data_dir, "*.parquet.snappy")))
        if not filenames:
            raise FileNotFoundError(
                f"--use-old-data but no files in {args.data_dir}")
        logger.info("Reusing %d files from %s", len(filenames),
                    args.data_dir)
    else:
        logger.info("Generating %d rows over %d files in %s "
                    "(workload: %s)", args.num_rows, args.num_files,
                    args.data_dir, args.workload)
        start = timeit.default_timer()
        if args.workload == "imagenet":
            from ray_shuffling_data_loader_tpu.workloads import imagenet
            filenames, num_bytes = imagenet.generate_imagenet_parquet(
                args.num_rows, args.num_files, args.data_dir,
                height=args.image_size, width=args.image_size,
                seed=args.seed)
        elif args.workload == "bert":
            from ray_shuffling_data_loader_tpu.workloads import bert_mlm
            filenames, num_bytes = bert_mlm.generate_tokenized_parquet(
                args.num_rows, args.num_files, args.data_dir,
                seq_len=args.seq_len, seed=args.seed)
        else:
            filenames, num_bytes = datagen.generate_data(
                args.num_rows, args.num_files,
                args.num_row_groups_per_file, args.max_row_group_skew,
                args.data_dir, seed=args.seed)
        logger.info("Generated %.1f MB in %.2fs", num_bytes / 1e6,
                    timeit.default_timer() - start)

    # Workload hooks: ImageNet decodes encoded images inside shuffle
    # reducers (BASELINE config 3); DLRM casts to the narrowest covering
    # dtypes at the map stage so every downstream byte is narrow.
    map_transform = reduce_transform = None
    if args.workload == "imagenet":
        from ray_shuffling_data_loader_tpu.workloads import imagenet
        reduce_transform = imagenet.decode_transform(
            args.image_size, args.image_size)
    elif args.workload == "bert":
        from ray_shuffling_data_loader_tpu.jax_dataset import (
            make_cast_transform)
        from ray_shuffling_data_loader_tpu.workloads.bert_mlm import (
            bert_mlm_spec)
        spec = bert_mlm_spec(args.seq_len)
        map_transform = make_cast_transform(
            spec["feature_columns"], spec["feature_types"],
            spec["label_column"], spec["label_type"])
    elif args.workload == "dlrm":
        from ray_shuffling_data_loader_tpu.jax_dataset import (
            make_cast_transform)
        from ray_shuffling_data_loader_tpu.workloads.dlrm_criteo import (
            dlrm_spec)
        spec = dlrm_spec()
        map_transform = make_cast_transform(
            spec["feature_columns"], spec["feature_types"],
            spec["label_column"], spec["label_type"])

    all_stats = run_trials(
        args.num_epochs, filenames, args.num_reducers, args.num_trainers,
        args.max_concurrent_epochs, collect_stats=not args.no_stats,
        utilization_sample_period=args.utilization_sample_period,
        num_trials=args.num_trials, trials_timeout=args.trials_timeout,
        seed=args.seed, map_transform=map_transform,
        reduce_transform=reduce_transform,
        file_cache=({"auto": "auto", "none": None,
                     "disk": "disk"}[args.file_cache]
                    if args.file_cache is not None
                    else (None if args.cold else "auto")),
        max_inflight_bytes=args.max_inflight_bytes,
        spill_dir=args.spill_dir)

    if args.no_stats:
        durations = [d for d, _ in all_stats]
        mean = sum(durations) / len(durations)
        print(f"\nMean over {len(durations)} trials: {mean:.3f}s")
        print(f"Mean throughput: "
              f"{args.num_epochs * args.num_rows / mean:.2f} rows/s")
    else:
        stats_mod.process_stats(
            all_stats, args.overwrite_stats, args.stats_dir,
            args.no_epoch_stats, args.unique_stats, args.num_rows,
            args.num_files, args.num_row_groups_per_file, args.batch_size,
            args.num_reducers, args.num_trainers, args.num_epochs,
            args.max_concurrent_epochs)


if __name__ == "__main__":
    from ray_shuffling_data_loader_tpu.utils.compile_cache import (
        enable_compile_cache)
    enable_compile_cache()
    main()
