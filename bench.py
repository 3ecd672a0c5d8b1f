"""Headline benchmark: rows/sec/chip ingested through the full pipeline.

ONE invocation runs three timed phases and prints ONE JSON line
(``{"metric", "value", "unit", "vs_baseline", ...}``):

1. **cached** — rows/s streamed from shuffled Parquet through the
   map/reduce shuffle, re-batching, Arrow->NumPy conversion, and
   ``jax.device_put`` onto the accelerator, with the cross-epoch
   file-table cache on (decode paid once). A tiny jitted reduction per
   batch forces materialization on device. This is the headline
   ``value``.
2. **cold** — the corpus-exceeds-RAM regime: no decoded tables held in
   memory, the reference's 64 GB operating point
   (reference: benchmarks/benchmark_batch.sh:9-18). By default the run
   decodes each Parquet file ONCE inside the timed window and streams
   later epochs from memory-mapped Arrow IPC scratch on local disk
   (``file_cache="disk"`` — RSS stays reclaimable page cache;
   ``cold_cache`` in the JSON says which mode ran, and
   RSDL_BENCH_COLD_CACHE=none forces the reference's
   re-decode-every-epoch regime). ``vs_baseline`` is THIS number over
   the pandas reference algorithm, which pays full decode every pass —
   the decode-once design is the win being measured.
3. **train** — the BASELINE.md contract metric: a REAL DLRM train step
   (models/dlrm.py, Adam updates — not a mock sleep) consumes the
   stream, and the phase reports ``stall_pct_under_train`` (share of
   wall-clock the trainer spent waiting on the input pipeline,
   reference's own metric: examples/horovod/ray_torch_shuffle.py:186-218)
   plus train-gated rows/s. Contract: <= 10% stall.

The pandas baseline runs the reference's algorithm the way the reference
runs it per core — pandas ``read_parquet``, boolean-mask partitioning,
``pd.concat`` + ``sample(frac=1)``, sequential single process
(reference: shuffle.py:199-247) — on the same data and host in the same
run.

Env knobs: RSDL_BENCH_ROWS, RSDL_BENCH_FILES, RSDL_BENCH_EPOCHS,
RSDL_BENCH_BATCH, RSDL_BENCH_PREFETCH (batches in flight, default 4),
RSDL_BENCH_CPU=1 (run on the CPU backend deliberately — smoke runs;
without it, finding no accelerator is an immediate error),
RSDL_BENCH_PHASES (csv subset of
"cached,cold,train,scaling,serve,latency,remote,stream", default all;
the remote phase is the storage-plane cold leg — simulated object
store, tiered cache thrash regime, prefetch ON vs OFF at the same
seed; the stream phase is the streaming leg — synthetic event source,
windowed shuffle, served end-to-end through device transfer),
RSDL_BENCH_COLD=1 (legacy: make the cold phase the headline and skip
cached), RSDL_BENCH_COLD_EPOCHS (default 6),
RSDL_BENCH_COLD_CACHE=disk|none (default disk — see phase 2 above),
RSDL_BENCH_TRAIN_EPOCHS
(default 4), RSDL_BENCH_TRAIN_BATCH (default 131072),
RSDL_BENCH_TRAIN_MODEL=tiny|base|mlperf (DLRM scale for the train phase;
default mlperf — MLPerf-DLRM-v2-like widths; tiny on CPU),
RSDL_BENCH_TRAIN_MICROBATCH (rows per real train step; the loader chunk
is consumed as batch/microbatch on-device-sliced steps, default 2048),
RSDL_BENCH_DATA (data cache dir), RSDL_BENCH_DEVICE_REBATCH=0/1 (force
the per-batch host path / the bulk device-rebatch path; default auto),
RSDL_BENCH_STEP_MS (emulated per-batch step time in the ingest phases),
RSDL_BENCH_REDUCERS (override the reducer count),
RSDL_BENCH_TRAINERS (ingest-phase trainer ranks, default 1; >1 routes one
shuffle to N per-rank streams drained concurrently and clocks
launch-to-done — the reference-scale topology),
RSDL_BENCH_INFLIGHT_BYTES (transient-byte budget for the ingest phases),
RSDL_BENCH_SPILL_DIR (with the budget: spill tier for reducer outputs),
RSDL_BENCH_SCAN_STEPS=1 (train phase: one lax.scan call per chunk
instead of per-micro-step dispatch),
RSDL_BENCH_DEVICE_TABLE_BYTES (bulk-path per-chunk transfer cap),
RSDL_BENCH_RUNS (train-phase repeats for the median-of-N contract
fields + congestion marker; default 3 on accelerators, 1 under
RSDL_BENCH_CPU).

Chaos soak mode: ``--chaos[=RATE]`` argv flag (or RSDL_BENCH_CHAOS_RATE)
installs a seeded fault-rate spec over the recoverable sites
(``map_read`` / ``reduce_gather`` / ``device_transfer`` /
``spill_write`` / ``storage_read`` / ``storage_stall``,
runtime/faults.py) for the whole invocation: ~RATE of
each site's task keys fail once and must be recovered (lineage
recompute / in-task retry / spill degrade). The run must still complete
every selected phase — a phase that raises exits non-zero, chaos or
not — and the JSON gains the fault_stats() delta (``faults_injected``,
``fault_retries``, ``fault_recomputes``, ``fault_quarantines``,
``fault_recoveries_exhausted``, ``chaos_rate``) plus the
chaos/telemetry join evidence (``fault_events``,
``fault_events_joinable`` — fault events matched to stage events by
``(kind, epoch, task)``). An explicit
RSDL_CHAOS_SPEC wins over the rate spec (targeted reproduction:
``RSDL_CHAOS_SPEC="map_read:epoch1:file2"`` fails the same way every
run). The JSON also carries runtime-health evidence
(``watchdog_events``, ``stall_escalations``, ``fallback_engaged``) from
the bulk-path progress watchdog, and the library degradation policy
(runtime/policy.py) now owns the device-rebatch default:
RSDL_DEVICE_REBATCH=0 is the promoted, library-wide form of
RSDL_BENCH_DEVICE_REBATCH=0.

Telemetry spine (runtime/telemetry.py): the whole invocation is
flight-recorded (SIGUSR1 dumps the event ring + named-thread stacks at
any moment), and the JSON carries the bottleneck verdict computed from
recorder events — ``bottleneck_stage``, ``telemetry_stall_pct``,
``stage_latency_ms`` (p50/p95/p99 per stage), ``telemetry_events``,
and ``telemetry_overhead_pct`` (events x SELF-MEASURED full-path
per-record cost over the timed window; contract <= 1%), plus
``telemetry_overhead_off_pct`` — the same event count priced at the
RSDL_TELEMETRY=0 hard-off fast path, the proof the off switch is ~free.
RSDL_METRICS_FILE / RSDL_METRICS_PORT bring up the Prometheus
exposition so ``tools/rsdl_top.py`` can watch the run live; see
examples/observability.md.

Causal trace + profiling (runtime/trace.py, runtime/profiler.py): the
record also carries the critical-path attribution computed from the
recorder's retained events — ``critical_path`` (per-stage critical-path
ms, descending), ``self_time_ms`` (per-stage busy-union), ``whatif``
("2x faster <stage> => -X% epoch time", monotone in the speedup), and
``trace_straggler`` (the (stage, task) with the largest critical-path
share). RSDL_TRACE_DIR makes every process dump its recorder for
``tools/rsdl_trace.py`` to merge; RSDL_PROFILER=1 /
RSDL_PROFILE_FOLDED=<path> engage the stdlib sampling profiler and add
a ``profile`` summary (stage-billed samples, per-thread CPU seconds,
hottest stacks; folded stacks land at the path — flamegraph-ready).

Regression gate: ``--baseline <BENCH_rN.json>`` compares this record
against the chosen committed baseline with tools/rsdl_bench_diff.py's
thresholds and exits non-zero on a breach — the r03 -> r05 ingest
regression class can no longer land silently.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time
import timeit


# Public per-chip peak matmul throughput (bf16), keyed by substrings of
# jax's device_kind, for the MFU denominator. An accelerator that is not
# in the table is an error, not a default.
_TPU_PEAK_FLOPS = {
    "v5 lite": 197e12, "v5litepod": 197e12, "v5e": 197e12,
    "v5p": 459e12, "v4": 275e12, "v3": 123e12, "v2": 45e12,
}


def _device_peak_flops(jax) -> "float | None":
    """Peak FLOP/s of the device in use; None on the CPU backend (what
    RSDL_BENCH_CPU runs report instead is run_train's business)."""
    device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    kind = device.device_kind.lower()
    for key, peak in _TPU_PEAK_FLOPS.items():
        if key in kind:
            return peak
    raise RuntimeError(
        f"no peak-FLOP/s entry for device kind {device.device_kind!r} "
        f"(platform {device.platform!r}); add it to _TPU_PEAK_FLOPS with "
        "its source")


def _train_flops_per_row(cfg) -> float:
    """Matmul FLOPs per row of one DLRM train step (the MXU work): the
    pairwise-interaction batched matmul plus the top (and bottom, when
    present) MLP, forward + ~2x for backward. Embedding gathers/scatters
    and the Adam update are memory-bound and excluded, the conventional
    MFU numerator; one-hot-matmul lookups for small tables are likewise
    excluded, so the estimate is a floor."""
    f, d = cfg.num_interacting, cfg.embed_dim
    interact = 2.0 * f * f * d
    dims = (cfg.top_in_dim,) + tuple(cfg.top_hidden) + (1,)
    mlp = sum(2.0 * a * b for a, b in zip(dims[:-1], dims[1:]))
    if cfg.dense_dim > 0:
        bdims = ((cfg.dense_dim,) + tuple(cfg.bottom_hidden)
                 + (cfg.embed_dim,))
        mlp += sum(2.0 * a * b for a, b in zip(bdims[:-1], bdims[1:]))
    return 3.0 * (interact + mlp)


def _pandas_reference_baseline(filenames, num_reducers: int,
                               batch_size: int) -> float:
    """rows/s of the reference's shuffle algorithm, single process."""
    import numpy as np
    import pandas as pd

    start = timeit.default_timer()
    total_rows = 0
    # Map stage: read + uniform partition via boolean masks.
    reducer_parts = [[] for _ in range(num_reducers)]
    for filename in filenames:
        rows = pd.read_parquet(filename)
        total_rows += len(rows)
        # Deliberately the reference's own unseeded draw (its map stage,
        # reference: shuffle.py:213) — this is the baseline being timed,
        # not pipeline code: rsdl-lint: disable=unseeded-random
        assignment = np.random.randint(num_reducers, size=len(rows))
        for r in range(num_reducers):
            reducer_parts[r].append(rows[assignment == r])
    # Reduce stage: concat + permute.
    shuffled = [pd.concat(parts).sample(frac=1) for parts in reducer_parts]
    # Consume: exact-size re-batching with leftover carry.
    buffer = None
    for df in shuffled:
        buffer = df if buffer is None else pd.concat([buffer, df])
        while len(buffer) >= batch_size:
            batch = buffer[:batch_size]
            _ = batch.to_numpy(copy=False)
            buffer = buffer[batch_size:]
    duration = timeit.default_timer() - start
    return total_rows / duration


def _aggregate_train_runs(runs: "list[dict]") -> dict:
    """Median-of-N aggregation for the contract (train) phase, with a
    congestion marker (a single congested run used to land outside
    contract silently).

    The quiet-host envelope is the runs' own robust spread: median
    ``step_ms_mean`` with a MAD-derived sigma, floored at 5% of the
    median so a perfectly tight triple doesn't flag scheduler noise. A
    run whose step-time z-score exceeds 3 is marked congested; the
    MEDIAN run (not the mean, not the outlier) carries the contract
    fields, so one noisy-neighbor episode cannot sink or inflate the
    artifact.
    """
    import statistics
    step_ms = [r["step_ms_mean"] for r in runs]
    med = statistics.median(step_ms)
    mad = statistics.median([abs(s - med) for s in step_ms])
    sigma = max(1.4826 * mad, 0.05 * med, 1e-9)
    zs = [(s - med) / sigma for s in step_ms]
    congested = [i for i, z in enumerate(zs) if z > 3.0]
    order = sorted(range(len(runs)), key=lambda i: step_ms[i])
    median_i = order[len(runs) // 2]
    return {
        "runs": len(runs),
        "median_run_index": median_i,
        "train_step_ms_median": round(step_ms[median_i], 3),
        "train_rows_per_sec_median": round(runs[median_i]["rows_per_s"], 1),
        "train_stall_pct_median": round(runs[median_i]["stall_pct"], 3),
        "train_step_ms_runs": [round(s, 3) for s in step_ms],
        "train_step_ms_z_max": round(max(zs), 2),
        "congested_runs": len(congested),
        "congested": bool(congested),
    }


def _cold_cache_mode() -> "str | None":
    """Cold-regime cache: "disk" (default — decode parquet once per run,
    stream later epochs from mmap'd Arrow IPC scratch; RSS stays page-cache
    bounded, the honest corpus-exceeds-RAM answer) or "none"
    (RSDL_BENCH_COLD_CACHE=none: re-decode every epoch, the reference's
    regime). Each dataset resolves "disk" to a FRESH scratch dir, so the
    warm-up run can never pre-populate the timed run's cache."""
    mode = os.environ.get("RSDL_BENCH_COLD_CACHE", "disk").strip().lower()
    return None if mode in ("none", "0", "") else "disk"


def _make_dataset(filenames, *, num_epochs, batch_size, num_reducers,
                  prefetch_size, cold, device_rebatch, qname,
                  num_trainers=1, rank=0, max_inflight_bytes=None,
                  spill_dir=None):
    from ray_shuffling_data_loader_tpu.jax_dataset import JaxShufflingDataset
    from ray_shuffling_data_loader_tpu.workloads.dlrm_criteo import dlrm_spec
    # Per-chunk transfer cap for the bulk device-rebatch path
    # (RSDL_BENCH_DEVICE_TABLE_BYTES).
    table_bytes = os.environ.get("RSDL_BENCH_DEVICE_TABLE_BYTES")
    return JaxShufflingDataset(
        filenames, num_epochs=num_epochs, num_trainers=num_trainers,
        batch_size=batch_size, rank=rank,
        num_reducers=num_reducers, max_concurrent_epochs=2, seed=0,
        queue_name=qname, drop_last=True,
        prefetch_size=prefetch_size,
        file_cache=_cold_cache_mode() if cold else "auto",
        max_inflight_bytes=max_inflight_bytes, spill_dir=spill_dir,
        max_device_table_bytes=int(table_bytes) if table_bytes else None,
        device_rebatch=device_rebatch, **dlrm_spec())


def run_ingest(jax, filenames, *, num_epochs, batch_size, num_reducers,
               prefetch_size, cold, device_rebatch, step_ms, qname,
               max_inflight_bytes=None, spill_dir=None) -> dict:
    """Timed ingest: shuffle -> batches -> device, near-zero consumer.

    Timing protocol (round 4 fix): a separate ONE-epoch warm-up dataset
    pays XLA compiles and OS page-cache fill; then a FRESH dataset runs
    with nothing hidden from the clock. (The previous protocol excluded
    all of epoch 0, which let the producer front-run later epochs into
    the prefetch queue during the excluded epoch; at large batch sizes
    the queue holds multiple epochs of rows, and the "timed" window
    partly measured queue DRAIN — a 4-epoch cold run with identical
    ~4.3s total wall reported 13M or 35M "rows/s" depending only on
    batch size, both artifacts.)

    The clock differs by mode, because the work differs:

    - **cached**: clock starts at FIRST BATCH DELIVERY. By then the
      dataset's file cache is fully warm (the first reducer output
      needs every file mapped), so the window is pure steady state —
      exactly what "decode amortized" means — and only the first chunk
      (produced pre-window, its remaining batches ~1% of the window) is
      credited for free. Launch-to-first-batch is reported as
      ``fill_s``; the reference's trainers never see it because the
      driver starts the shuffle before they attach
      (reference: ray_torch_shuffle.py:316-322).
    - **cold**: clock starts at SHUFFLE LAUNCH and covers everything.
      Cold means decode recurs every epoch, so the pre-first-batch work
      (the whole epoch-0 map stage) is exactly the work being measured
      — excluding it would hand epoch 0 a free decode.
    """
    import jax.numpy as jnp

    # Tiny jitted reduction per batch: forces the batch to land on device;
    # negligible compute (sparse-feature columns arrive as one pytree
    # transfer and are consumed per-column, the DLRM access pattern).
    touch = jax.jit(
        lambda fs, y: sum(f.sum(dtype=jnp.int32) for f in fs)
        + y.sum(dtype=jnp.float32))

    # try/finally on both dataset lifetimes: a phase that raises must not
    # leave its producers/queues running to contaminate later phases (the
    # caller treats phase failures as non-fatal).
    warm = _make_dataset(filenames, num_epochs=1, batch_size=batch_size,
                         num_reducers=num_reducers,
                         prefetch_size=prefetch_size, cold=cold,
                         device_rebatch=device_rebatch,
                         qname=f"{qname}-warm",
                         max_inflight_bytes=max_inflight_bytes,
                         spill_dir=spill_dir)
    try:
        warm.set_epoch(0)
        last = None
        for features, label in warm:
            last = touch(features, label)
        jax.block_until_ready(last)
    finally:
        warm.close()

    launch = timeit.default_timer()
    ds = _make_dataset(filenames, num_epochs=num_epochs,
                       batch_size=batch_size, num_reducers=num_reducers,
                       prefetch_size=prefetch_size, cold=cold,
                       device_rebatch=device_rebatch, qname=qname,
                       max_inflight_bytes=max_inflight_bytes,
                       spill_dir=spill_dir)
    rows_consumed = 0
    start = launch if cold else None  # cold: launch-to-last-batch
    fill_s = None
    try:
        for epoch in range(num_epochs):
            ds.set_epoch(epoch)
            for features, label in ds:
                if fill_s is None:
                    fill_s = timeit.default_timer() - launch
                    if start is None:
                        # Cached: the first batch (produced pre-window) is
                        # consumed BEFORE the clock starts, so neither its
                        # production nor its consumption leaks into the
                        # window; stall stats start with batch 2's wait.
                        last = touch(features, label)
                        jax.block_until_ready(last)
                        ds.batch_wait_stats.reset()
                        start = timeit.default_timer()
                        continue
                last = touch(features, label)
                if step_ms:
                    time.sleep(step_ms / 1e3)
                rows_consumed += label.shape[0]
        jax.block_until_ready(last)
        duration = max(timeit.default_timer() - (start or launch), 1e-9)
    finally:
        ds.close()
    wait = ds.batch_wait_stats.summary()
    return {
        "rows_per_s": rows_consumed / duration,
        "stall_s": wait["total"],
        "stall_pct": 100.0 * wait["total"] / duration,
        "wait_mean_ms": wait["mean"] * 1e3,
        "batches": wait["count"],
        "timed_epochs": num_epochs,
        "duration_s": duration,
        "fill_s": fill_s if fill_s is not None else 0.0,
    }


def _run_worker_scaling(filenames, *, num_reducers, seed=0) -> dict:
    """Worker-count scaling leg: the SAME shuffle (direct driver, null
    consumer — no queue/device machinery, so the executor is the only
    variable) at pool width 1 and at the full configured width, over a
    quarter of the files x 2 epochs (one cold, one cached). The record
    carries the measured rates plus the derived parallel efficiency, so
    "near-linear scaling" is an artifact of the run, not a claim.
    """
    import importlib
    shmod = importlib.import_module("ray_shuffling_data_loader_tpu.shuffle")
    from ray_shuffling_data_loader_tpu import spill as rsdl_spill
    from ray_shuffling_data_loader_tpu.runtime import policy as rt_policy

    files = filenames[:max(1, len(filenames) // 4)]
    full = (rt_policy.resolve("executor", "executor_workers")
            or os.cpu_count() or 1)
    legs = {}
    for workers in sorted({1, full}):
        rows = [0]

        def consumer(trainer, epoch, refs):
            if refs is None:
                return
            for ref in refs:
                rows[0] += rsdl_spill.unwrap(ref.result()).num_rows

        start = timeit.default_timer()
        shmod.shuffle(files, consumer, num_epochs=2,
                      num_reducers=num_reducers, num_trainers=1,
                      seed=seed, num_workers=workers, collect_stats=False)
        duration = max(timeit.default_timer() - start, 1e-9)
        legs[str(workers)] = round(rows[0] / duration, 1)
    result = {
        "rows_per_s_by_workers": legs,
        "max_workers": full,
        "files_fraction": round(len(files) / len(filenames), 3),
    }
    if full > 1 and str(full) in legs and legs["1"]:
        result["parallel_efficiency"] = round(
            legs[str(full)] / (full * legs["1"]), 3)
    return result


def run_ingest_multi(jax, filenames, *, num_epochs, batch_size,
                     num_reducers, prefetch_size, cold, device_rebatch,
                     step_ms, qname, num_trainers,
                     max_inflight_bytes=None, spill_dir=None) -> dict:
    """Multi-trainer ingest: ONE shuffle routes batches to ``num_trainers``
    per-rank streams, each drained by its own consumer thread — the
    reference's trainers-per-node topology (reference:
    benchmark.py:championship trainer sweep, multiqueue.py:127-154) on one
    host. Rank 0 owns the queue + shuffle; ranks 1+ attach to the named
    queue, the reference's consumer-only pattern.

    The clock runs LAUNCH to last-rank-done for every mode (unlike the
    single-trainer cached protocol): with T concurrent streams there is no
    single "first delivery" that marks steady state, and this entry point
    exists for scale evidence where fill is part of the story. rows/s sums
    all ranks; stall stats aggregate across ranks (stall_pct is the mean
    per-rank batch-wait share of the run)."""
    import threading

    import jax.numpy as jnp

    touch = jax.jit(
        lambda fs, y: sum(f.sum(dtype=jnp.int32) for f in fs)
        + y.sum(dtype=jnp.float32))

    warm = _make_dataset(filenames, num_epochs=1, batch_size=batch_size,
                         num_reducers=num_reducers,
                         prefetch_size=prefetch_size, cold=cold,
                         device_rebatch=device_rebatch,
                         qname=f"{qname}-warm",
                         max_inflight_bytes=max_inflight_bytes,
                         spill_dir=spill_dir)
    try:
        warm.set_epoch(0)
        last = None
        for features, label in warm:
            last = touch(features, label)
        jax.block_until_ready(last)
    finally:
        warm.close()

    launch = timeit.default_timer()
    make = lambda rank: _make_dataset(
        filenames, num_epochs=num_epochs, batch_size=batch_size,
        num_reducers=num_reducers, prefetch_size=prefetch_size, cold=cold,
        device_rebatch=device_rebatch, qname=qname,
        num_trainers=num_trainers, rank=rank,
        max_inflight_bytes=max_inflight_bytes, spill_dir=spill_dir)
    rows = [0] * num_trainers
    fills = [None] * num_trainers
    errors = []
    # One jitted touch per rank keeps device work trivial but real; the
    # touch results are tiny scalars, safe to race on one chip.
    lasts = [None] * num_trainers

    def consume(rank: int, ds) -> None:
        try:
            for epoch in range(num_epochs):
                ds.set_epoch(epoch)
                for features, label in ds:
                    if fills[rank] is None:
                        fills[rank] = timeit.default_timer() - launch
                    lasts[rank] = touch(features, label)
                    if step_ms:
                        time.sleep(step_ms / 1e3)
                    rows[rank] += label.shape[0]
        except BaseException as e:  # noqa: BLE001 - surfaced below
            errors.append((rank, e))

    datasets = []
    threads = []
    try:
        # Rank 0 FIRST: it registers the named queue and launches the
        # shuffle the other ranks attach to. Built inside try/finally so a
        # failing later construction cannot leak rank 0's running producer
        # into later phases.
        for rank in range(num_trainers):
            datasets.append(make(rank))
        threads = [threading.Thread(target=consume, args=(r, datasets[r]),
                                    daemon=True,
                                    name=f"rsdl-bench-consume-{r}")
                   for r in range(num_trainers)]
        for t in threads:
            t.start()
        # Poll-join: one failed rank must tear the run down (via the
        # finally's closes, which unblock the surviving consumers), not
        # leave the producer back-pressured and the bench hung forever.
        while any(t.is_alive() for t in threads) and not errors:
            for t in threads:
                t.join(timeout=0.5)
        if not errors:
            for last in lasts:
                if last is not None:
                    jax.block_until_ready(last)
        duration = max(timeit.default_timer() - launch, 1e-9)
    finally:
        for ds in datasets:
            try:
                ds.close()
            # Teardown must not mask the rank error raised right below;
            # nothing is blocked on these closed datasets:
            # rsdl-lint: disable=swallowed-exception
            except Exception:  # noqa: BLE001
                pass
        for t in threads:
            t.join(timeout=60)
    if errors:
        raise RuntimeError(
            f"trainer rank {errors[0][0]} failed") from errors[0][1]
    waits = [ds.batch_wait_stats.summary() for ds in datasets]
    total_stall = sum(w["total"] for w in waits)
    total_batches = sum(w["count"] for w in waits)
    return {
        "rows_per_s": sum(rows) / duration,
        "stall_s": total_stall,
        # Mean per-rank share of the run spent waiting (T ranks each have
        # `duration` of wall to spend).
        "stall_pct": 100.0 * total_stall / (num_trainers * duration),
        "wait_mean_ms": (total_stall / total_batches * 1e3
                         if total_batches else 0.0),
        "batches": total_batches,
        "timed_epochs": num_epochs,
        "duration_s": duration,
        "fill_s": min((f for f in fills if f is not None), default=0.0),
        "num_trainers": num_trainers,
        "clock": "launch",
    }


def _make_chunk_stepper(jax, dlrm, cfg, opt, mb: int,
                        steps_per_chunk: int):
    """One jitted call per loader chunk that runs ``steps_per_chunk``
    REAL micro-steps (fwd+bwd+Adam per ``mb``-row on-device slice) via
    ``lax.scan`` — identical math to dispatching each micro-step from
    Python, minus ``steps_per_chunk - 1`` host->device dispatches per
    chunk (one traced loop, static trip count, donated carry). Returns
    ``(params, opt_state, last_loss)``."""
    import functools

    import jax.numpy as jnp
    import optax
    from jax import lax

    steps_idx = jnp.arange(steps_per_chunk, dtype=jnp.int32)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def chunk_steps(params, opt_state, cols, labels):
        def body(carry, i):
            p, o = carry
            mcols = [lax.dynamic_slice_in_dim(c, i * mb, mb, axis=0)
                     for c in cols]
            mlab = lax.dynamic_slice_in_dim(labels, i * mb, mb, axis=0)
            loss, grads = jax.value_and_grad(
                lambda pp: dlrm.loss_fn(cfg, pp, None, mcols, mlab))(p)
            updates, o = opt.update(grads, o)
            return (optax.apply_updates(p, updates), o), loss

        (params, opt_state), losses = lax.scan(
            body, (params, opt_state), steps_idx)
        return params, opt_state, losses[-1]

    return chunk_steps


def run_train(jax, filenames, *, num_epochs, batch_size, num_reducers,
              prefetch_size, device_rebatch, model_size, microbatch,
              qname) -> dict:
    """The contract phase: real jitted DLRM train steps consume the
    stream; reports stall% (batch-wait share of wall-clock) and
    train-gated rows/s. Compiles are paid by a separate warm-up dataset;
    the clock starts at the timed dataset's first chunk delivery.

    The trainer is MICRO-BATCHED, the standard large-batch recommender
    setup: the loader delivers ``batch_size``-row device chunks (bulk
    transfers at the granularity the wire likes), and the trainer runs
    one real train step (fwd+bwd+Adam update, models/dlrm.py — not a
    mock sleep) per ``microbatch``-row slice, carved on-device inside
    the jitted step. Rows/s is gated by real training work; stall% is
    the fraction of wall-clock the trainer spent blocked on the input
    pipeline — the reference's own metric, measured around its
    synchronous per-step loop (reference:
    ray_torch_shuffle.py:186-219)."""
    import functools

    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax import lax

    from ray_shuffling_data_loader_tpu.models import dlrm

    if model_size == "tiny":
        # CPU smoke path: full-cardinality embedding grads are dense
        # host-side and would swamp a tiny run.
        cfg = dlrm.DLRMConfig(
            vocab_sizes=tuple(min(v, 1000)
                              for v in dlrm.DATA_SPEC_VOCAB_SIZES),
            embed_dim=8, top_hidden=(64, 32),
            compute_dtype=jnp.float32)
    elif model_size == "base":
        cfg = dlrm.DLRMConfig()  # embed 32, top (512, 256)
    else:
        # Production-representative scale (MLPerf DLRM-v2-like MLP widths
        # on the reference's own 17-table schema): this is what a real
        # recommender train step costs per row, and the scale BASELINE's
        # >=90%-utilization contract is about.
        cfg = dlrm.mlperf_config()
    params = dlrm.init(cfg, jax.random.key(0))
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    mb = min(microbatch, batch_size)
    if batch_size % mb:
        # Round DOWN to the largest divisor so the step granularity (and
        # hence the contract stall metric) stays close to what was asked
        # for, and say so — silently training one giant step per chunk
        # would change the number being measured.
        mb = next(d for d in range(mb, 0, -1) if batch_size % d == 0)
        print(f"# train microbatch {microbatch} does not divide chunk "
              f"{batch_size}; using {mb}", file=sys.stderr)
    steps_per_chunk = batch_size // mb

    # Two step-loop forms, same math (pinned by
    # test_scanned_chunk_stepper_matches_sequential_micro_steps):
    # per-micro-step jit dispatch (default), or one lax.scan call per
    # chunk (RSDL_BENCH_SCAN_STEPS=1), which removes steps_per_chunk-1
    # host dispatches per chunk. Which form is faster on the chip is not
    # measured on current code (ROADMAP A5). Both donate params and
    # optimizer state: at the mlperf widths they are 4.5 GB, and an
    # undonated step would hold them twice.
    if os.environ.get("RSDL_BENCH_SCAN_STEPS"):
        chunk_steps = _make_chunk_stepper(jax, dlrm, cfg, opt, mb,
                                          steps_per_chunk)
    else:
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def micro_step(params, opt_state, cols, labels, i):
            mcols = [lax.dynamic_slice_in_dim(c, i * mb, mb, axis=0)
                     for c in cols]
            mlab = lax.dynamic_slice_in_dim(labels, i * mb, mb, axis=0)
            loss, grads = jax.value_and_grad(
                lambda p: dlrm.loss_fn(cfg, p, None, mcols, mlab))(params)
            updates, opt_state = opt.update(grads, opt_state)
            return optax.apply_updates(params, updates), opt_state, loss

        def chunk_steps(params, opt_state, cols, labels):
            loss = None
            for i in range(steps_per_chunk):
                params, opt_state, loss = micro_step(
                    params, opt_state, cols, labels, np.int32(i))
            return params, opt_state, loss

    # Same protocol as run_ingest: a one-epoch warm-up dataset pays the
    # model/step compiles; the timed dataset's clock and stall stats
    # start at its FIRST chunk delivery (the reference's trainers attach
    # to an already-running shuffle, so they never observe launch fill —
    # reported separately as fill_s).
    # try/finally on both dataset lifetimes — see run_ingest.
    warm = _make_dataset(filenames, num_epochs=1, batch_size=batch_size,
                         num_reducers=num_reducers,
                         prefetch_size=prefetch_size, cold=False,
                         device_rebatch=device_rebatch,
                         qname=f"{qname}-warm")
    last_chunk = None
    try:
        warm.set_epoch(0)
        loss = None
        for features, label in warm:
            params, opt_state, loss = chunk_steps(
                params, opt_state, features, label)
            last_chunk = (features, label)
        jax.block_until_ready(loss)
    finally:
        warm.close()

    # Measured model peak: pure-compute rows/s of the SAME jitted step
    # loop on one already-device-resident warm chunk — no pipeline, no
    # transfer, no batch wait. On the CPU backend (no public peak-FLOPs
    # entry) train_mfu reports achieved/compute-bound instead of going
    # silently null: 100% means the input pipeline kept the step loop
    # fully fed. The steps donate their state, so params and optimizer
    # state advance through these four chunks too.
    compute_rows_per_s = None
    if last_chunk is not None:
        warm_f, warm_l = last_chunk
        params, opt_state, lm = chunk_steps(params, opt_state, warm_f,
                                            warm_l)
        float(lm)
        best_s = None
        for _ in range(3):
            peak_t0 = timeit.default_timer()
            params, opt_state, lm = chunk_steps(params, opt_state, warm_f,
                                                warm_l)
            float(lm)
            rep_s = timeit.default_timer() - peak_t0
            best_s = rep_s if best_s is None else min(best_s, rep_s)
        # Fastest rep, not the mean: the peak is a CAPACITY estimate, and
        # any jitter in the reps only ever makes it look lower.
        compute_rows_per_s = batch_size / max(best_s, 1e-9)

    launch = timeit.default_timer()
    ds = _make_dataset(filenames, num_epochs=num_epochs,
                       batch_size=batch_size, num_reducers=num_reducers,
                       prefetch_size=prefetch_size, cold=False,
                       device_rebatch=device_rebatch, qname=qname)
    rows_consumed = 0
    steps = 0
    start = fill_s = None
    try:
        for epoch in range(num_epochs):
            ds.set_epoch(epoch)
            for features, label in ds:
                if start is None:
                    fill_s = timeit.default_timer() - launch
                    # The first chunk (produced pre-window) trains BEFORE
                    # the clock starts: params advance, but neither its
                    # production nor its compute is inside the window.
                    params, opt_state, loss = chunk_steps(
                        params, opt_state, features, label)
                    float(loss)
                    ds.batch_wait_stats.reset()
                    start = timeit.default_timer()
                    continue
                params, opt_state, loss = chunk_steps(
                    params, opt_state, features, label)
                rows_consumed += batch_size
                steps += steps_per_chunk
        # The clock stops on the loss VALUE arriving on the host, not on
        # block_until_ready alone: a device-to-host copy cannot complete
        # before the step that produces it, and that step reads the
        # params every earlier step wrote, so the fetch orders the whole
        # window behind it. Dispatch is asynchronous; without this the
        # window would time the enqueue.
        final_loss = None if loss is None else float(loss)
        duration = max(timeit.default_timer() - (start or launch), 1e-9)
    finally:
        ds.close()
    wait = ds.batch_wait_stats.summary()
    stall_s = wait["total"]
    # Compute-utilization context: dev_util_pct is
    # the non-wait share of the timed wall — an upper bound on device
    # duty cycle (it still contains host-side Python step overhead);
    # mfu_pct divides achieved matmul FLOPs by the chip's public bf16
    # peak (null off-TPU). DLRM MFU is intrinsically low: the model is
    # embedding/memory-bound, the MLP widths just bound the MXU share.
    peak = _device_peak_flops(jax)
    flops_per_row = _train_flops_per_row(cfg)
    if peak:
        mfu_pct = 100.0 * flops_per_row * rows_consumed / (duration * peak)
        mfu_basis, mfu_null_reason = "public_peak", None
    elif compute_rows_per_s:
        # CPU backend only (an accelerator without a table entry raised
        # in _device_peak_flops): report achieved rows/s against the
        # measured compute-bound ceiling of the identical step loop. Not
        # comparable to a public-peak MFU — the basis field says which
        # denominator produced the number.
        mfu_pct = 100.0 * (rows_consumed / duration) / compute_rows_per_s
        mfu_basis, mfu_null_reason = "measured_model_peak", None
    else:
        mfu_pct, mfu_basis = None, None
        mfu_null_reason = ("CPU backend has no public peak-FLOPs entry "
                           "and the warm-up delivered no chunk to measure "
                           "a model peak against")
    return {
        "rows_per_s": rows_consumed / duration,
        "stall_s": stall_s,
        "stall_pct": 100.0 * stall_s / duration,
        "dev_util_pct": 100.0 * (duration - stall_s) / duration,
        "mfu_pct": mfu_pct,
        "mfu_basis": mfu_basis,
        "mfu_null_reason": mfu_null_reason,
        "compute_rows_per_s": compute_rows_per_s,
        "flops_per_row": flops_per_row,
        "wait_mean_ms": wait["mean"] * 1e3,
        # Mean train-step time the pipeline had to beat: everything that
        # wasn't batch-wait, per micro-step.
        "step_ms_mean": ((duration - stall_s) / max(1, steps)) * 1e3,
        "batches": steps,
        "batch_size": batch_size,
        "microbatch": mb,
        # A non-finite loss means the model diverged; null the field (bare
        # NaN is not valid JSON) and flag it so the failure stays loud.
        "final_loss": (final_loss if final_loss is not None
                       and math.isfinite(final_loss) else None),
        "diverged": (final_loss is not None
                     and not math.isfinite(final_loss)),
        "timed_epochs": num_epochs,
        "duration_s": duration,
        "fill_s": fill_s if fill_s is not None else 0.0,
        "model_size": model_size,
    }


def _baseline_from_invocation() -> "str | None":
    """``--baseline PATH`` / ``--baseline=PATH`` argv flag (or
    RSDL_BENCH_BASELINE): the committed bench record this run must not
    regress from."""
    argv = sys.argv[1:]
    for i, arg in enumerate(argv):
        if arg == "--baseline" and i + 1 < len(argv):
            return argv[i + 1]
        if arg.startswith("--baseline="):
            return arg.split("=", 1)[1]
    return os.environ.get("RSDL_BENCH_BASELINE") or None


def _load_bench_diff():
    """tools/rsdl_bench_diff.py as a module (tools/ is not a package)."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "rsdl_bench_diff.py")
    spec = importlib.util.spec_from_file_location("_rsdl_bench_diff", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


#: Record keys that are deliberately informational — context the record
#: carries for forensics, not measurements a two-record gate could
#: meaningfully threshold. rsdl-lint's `ungated-bench-metric` rule
#: accepts a numeric record key only when it is covered by a
#: tools/rsdl_bench_diff.py DEFAULT_RULES prefix or listed here; a new
#: numeric emission must pick a side explicitly.
BENCH_INFORMATIONAL_KEYS = frozenset({
    # Invocation shape (identity, not measurement).
    "host_cpus", "num_workers", "num_reducers", "num_trainers",
    "batch_size", "prefetch_size", "rows", "epochs", "step_ms",
    "max_inflight_bytes", "telemetry_events", "fault_events",
    "fault_events_joinable", "chaos_rate",
    # Diagnostic refinements of quantities gated through another rule:
    # train_rows_per_sec gates the step-time/throughput family,
    # stall_pct_under_train gates the stall contract, train_diverged
    # gates convergence, telemetry_overhead_pct (ceiling) gates the ON
    # cost — the OFF cost is the proof the kill switch is free.
    "train_step_ms_mean", "train_compute_rows_per_sec",
    "train_wait_mean_ms", "train_stall_s", "train_dev_util_pct",
    "train_final_loss", "telemetry_overhead_off_pct",
    # Cold ingest is producer-bound BY CONSTRUCTION (near-zero-work
    # consumer): its stall share carries no contract.
    "cold_stall_pct",
    # Ratio against the in-run pandas reference: the reference's own
    # timing noise dominates; cold_rows_per_sec gates the regime.
    "vs_baseline_cached",
    # Rebalance leg context: the configured breach threshold and the
    # saturator's appetite are invocation shape; the raw contended /
    # recovered p99s are diagnostic refinements of the gated
    # rebalance_p99_recovery_x ratio (and the move count is pinned to 1
    # by rebalance_ok, not a threshold).
    "rebalance_slo_ms", "rebalance_sat_rows", "rebalance_moves",
    "rebalance_p99_ms_contended", "rebalance_p99_ms_recovered",
})


def _bench_provenance() -> dict:
    """Measurement provenance stamped into every record: WHAT code ran
    (git rev + dirty flag) on WHAT machine (host + CPU fingerprint).
    The r09->r10 'regression' was a slower bench host that nothing in
    the records could falsify — rsdl_regress/rsdl_bench_diff warn on
    cross-host or dirty-tree comparisons using exactly these fields.
    Every probe is fail-soft: a record without git is still a record."""
    import platform
    import socket
    import subprocess
    repo = os.path.dirname(os.path.abspath(__file__))
    prov: dict = {
        "git_rev": None,
        "tree_dirty": None,
        "host": socket.gethostname(),
        "host_cpus": os.cpu_count(),
        "cpu_model": None,
        "python": platform.python_version(),
        "platform": platform.platform(),
    }
    try:
        prov["git_rev"] = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=repo, capture_output=True,
            text=True, timeout=10, check=True).stdout.strip()
        status = subprocess.run(
            ["git", "status", "--porcelain"], cwd=repo,
            capture_output=True, text=True, timeout=10, check=True)
        prov["tree_dirty"] = bool(status.stdout.strip())
    except (OSError, subprocess.SubprocessError):
        pass
    try:
        with open("/proc/cpuinfo", encoding="utf-8") as f:
            for line in f:
                if line.lower().startswith("model name"):
                    prov["cpu_model"] = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    return prov


def _capture_round_capsule(record: dict) -> "str | None":
    """Per-round flight capsule (same layout as the runtime/health.py
    incident capsules, consumed by runtime/regress.py): the merged
    trace dumps, federated metrics, history slice, and resolved
    policy+env behind THIS record, written beside it and referenced
    from ``record["capsule"]``. Runs after every phase has finished —
    outside all timed windows, so telemetry_overhead_pct is untouched.
    Fail-soft: a capsule failure costs the forensics, never the record."""
    from ray_shuffling_data_loader_tpu.runtime import health as rt_health
    from ray_shuffling_data_loader_tpu.runtime import policy as rt_policy
    base_dir = rt_policy.resolve("bench", "bench_capsule_dir") or "."
    stem = f"bench-{os.getpid()}-r.capsule"
    # Pin a scratch trace dir ONLY for the capture window (all timed
    # phases are over): capture_incident signals the driver to dump its
    # flight-recorder ring and collects whatever lands in the resolved
    # trace dir. A run-long pin would also catch worker exit dumps, but
    # costs measured-phase CPU/IO — the workers' events already fold
    # into the driver-side attribution summary, so the trade is bad.
    pinned = None
    if not rt_policy.resolve("telemetry", "trace_dir"):
        import tempfile
        pinned = tempfile.mkdtemp(prefix="rsdl-bench-trace-")
        os.environ["RSDL_TRACE_DIR"] = pinned
    try:
        capsule = rt_health.capture_incident(
            reason="bench-round", base_dir=base_dir, profile_s=0.0,
            cooldown_s=0.0, stem=stem)
    except Exception as e:  # noqa: BLE001 - forensics must not fail the run
        print(f"# bench capsule capture FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        return None
    finally:
        if pinned is not None:
            os.environ.pop("RSDL_TRACE_DIR", None)
            import shutil
            shutil.rmtree(pinned, ignore_errors=True)
    if capsule is None:
        return None
    try:
        record["capsule"] = os.path.relpath(capsule)
    except ValueError:
        record["capsule"] = capsule
    try:
        # The record itself rides in the capsule (self-contained when
        # the directory travels without its BENCH_r*.json), and the
        # manifest's file list is refreshed to include it.
        with open(os.path.join(capsule, "record.json"), "w",
                  encoding="utf-8") as f:
            json.dump(record, f, indent=2, sort_keys=True)
        manifest_path = os.path.join(capsule, "capsule.json")
        with open(manifest_path, encoding="utf-8") as f:
            manifest = json.load(f)
        manifest["files"] = sorted(os.listdir(capsule))
        with open(manifest_path, "w", encoding="utf-8") as f:
            json.dump(manifest, f, indent=2)
    except (OSError, ValueError) as e:
        print(f"# bench capsule record embed FAILED: {e}",
              file=sys.stderr)
    return capsule


def _chaos_rate_from_invocation() -> "float | None":
    """``--chaos`` / ``--chaos=RATE`` argv flag or RSDL_BENCH_CHAOS_RATE."""
    rate = None
    for arg in sys.argv[1:]:
        if arg == "--chaos":
            rate = float(os.environ.get("RSDL_BENCH_CHAOS_RATE", "0.05"))
        elif arg.startswith("--chaos="):
            rate = float(arg.split("=", 1)[1])
    if rate is None and os.environ.get("RSDL_BENCH_CHAOS_RATE"):
        rate = float(os.environ["RSDL_BENCH_CHAOS_RATE"])
    return rate


def _install_chaos(rate: "float | None") -> "float | None":
    """Activate the soak spec unless a targeted RSDL_CHAOS_SPEC is set
    (the env spec was already honored at library import)."""
    from ray_shuffling_data_loader_tpu.runtime import faults as rt_faults
    if os.environ.get("RSDL_CHAOS_SPEC", "").strip() \
            or os.environ.get("RSDL_FAULTS_SPEC", "").strip():
        print("# chaos: honoring RSDL_CHAOS_SPEC over --chaos rate",
              file=sys.stderr)
        return rate
    if rate is None:
        return None
    seed = int(os.environ.get("RSDL_CHAOS_SEED", "0"))
    spec = ",".join(f"{site}@{rate}" for site in
                    ("map_read", "reduce_gather", "device_transfer",
                     "spill_write", "storage_read", "storage_stall"))
    rt_faults.install(spec, seed=seed)
    print(f"# chaos soak: rate={rate} seed={seed} over recoverable sites",
          file=sys.stderr)
    return rate


def _run_process_recovery_soak(seed: int) -> dict:
    """Process-level chaos soak: a REAL ``kill -9`` of the queue-server
    subprocess mid-epoch, wire chaos (connection reset mid-frame, frame
    corruption, lost acks) on the client side, and a dead-consumer lease
    expiry — recovered end to end, with the consumed stream asserted
    bit-identical to a fault-free in-process run. Runs on a small
    synthetic corpus so the soak costs seconds, not the bench budget.
    """
    import signal
    import tempfile

    from ray_shuffling_data_loader_tpu import data_generation as datagen
    from ray_shuffling_data_loader_tpu import multiqueue as mq
    from ray_shuffling_data_loader_tpu import multiqueue_service as svc
    from ray_shuffling_data_loader_tpu.dataset import ShufflingDataset
    from ray_shuffling_data_loader_tpu.runtime import faults as rt_faults
    from ray_shuffling_data_loader_tpu.runtime import supervisor as rt_sup
    from ray_shuffling_data_loader_tpu.shuffle import shuffle as run_shuffle

    epochs, reducers, soak_seed = 2, 3, 11
    tmpdir = tempfile.mkdtemp(prefix="rsdl-proc-soak-")
    filenames, _ = datagen.generate_data_local(8_000, 2, 1, 0.0, tmpdir)

    streams: dict = {}

    def reference_consumer(trainer_idx, epoch, refs):
        if refs is not None:
            streams.setdefault(epoch, []).extend(refs)

    run_shuffle(filenames, reference_consumer, epochs,
                num_reducers=reducers, num_trainers=1,
                max_concurrent_epochs=1, seed=soak_seed,
                collect_stats=False, file_cache=None)
    expected = {epoch: [tuple(r.result().column("key").to_pylist())
                        for r in refs]
                for epoch, refs in streams.items()}

    def consume_all(address_or_server, max_batch=2):
        address = (address_or_server.address
                   if hasattr(address_or_server, "address")
                   else address_or_server)
        # Deep redial budget: the restarted server re-imports the stack
        # before it listens, which can outlast the default schedule.
        remote = svc.RemoteQueue(address, retries=12, max_batch=max_batch)
        ds = ShufflingDataset(filenames, epochs, num_trainers=1,
                              batch_size=1_000, rank=0, batch_queue=remote,
                              shuffle_result=None, seed=soak_seed)
        got: dict = {}
        try:
            for epoch in range(epochs):
                ds.set_epoch(epoch)
                tables = []
                for table in ds.iter_tables():
                    tables.append(tuple(table.column("key").to_pylist()))
                    yield epoch, len(tables)
                got[epoch] = tables
        finally:
            remote.close()
        yield "done", got

    # Leg A — a REAL kill -9 of the queue-server subprocess mid-epoch:
    # the supervisor restarts it, the journal + shuffle lineage
    # regenerate the undelivered remainder, the consumer reconnects.
    # ack_lost fires client-side to prove lost acks are harmless.
    rt_faults.install("ack_lost:task0", seed=seed)
    supervisor, address = rt_sup.launch_supervised_queue_server(dict(
        filenames=filenames, num_epochs=epochs, num_trainers=1,
        num_reducers=reducers, seed=soak_seed, max_concurrent_epochs=1,
        journal_path=os.path.join(tmpdir, "watermarks.wal"),
        file_cache=None))
    result = {"ok": False, "server_restarts": 0}
    try:
        if not rt_sup.wait_for_server(address, timeout_s=60):
            raise RuntimeError("supervised queue server never came up")
        killed = False
        got_a = None
        for progress in consume_all(address):
            if progress[0] == "done":
                got_a = progress[1]
            elif not killed and progress == (0, 2):
                os.kill(supervisor.pid, signal.SIGKILL)
                killed = True
        kill_ok = killed and got_a == expected
        result["server_restarts"] = supervisor.restarts
    finally:
        rt_faults.clear()
        supervisor.stop()

    # Leg B — wire chaos against an in-process server (so the replay /
    # NACK counters land in THIS process's registry): a connection
    # reset mid-frame and a corrupted frame, both recovered.
    rt_faults.install(
        "conn_reset_midframe:task0:after1,frame_corrupt:task0:after4",
        seed=seed)
    try:
        def wire_consumer(trainer_idx, epoch, refs):
            queue_idx = epoch * 1 + trainer_idx
            if refs is None:
                wire_queue.put(queue_idx, None)
            else:
                wire_queue.put_batch(queue_idx, list(refs))

        wire_queue = mq.MultiQueue(epochs)
        run_shuffle(filenames, wire_consumer, epochs,
                    num_reducers=reducers, num_trainers=1,
                    max_concurrent_epochs=1, seed=soak_seed,
                    collect_stats=False, file_cache=None)
        with svc.serve_queue(wire_queue, num_trainers=1) as server:
            got_b = None
            for progress in consume_all(server, max_batch=2):
                if progress[0] == "done":
                    got_b = progress[1]
        wire_ok = got_b == expected
        wire_queue.shutdown()
    finally:
        rt_faults.clear()
    result["ok"] = kill_ok and wire_ok

    # Dead-consumer leg, in-process (the lease counters must land in
    # THIS process's registry for the JSON record): a consumer connects,
    # then vanishes without a goodbye; the lease expires under the
    # drain policy and its queue is freed.
    os.environ["RSDL_QUEUE_LEASE_TIMEOUT_S"] = "0.5"
    os.environ["RSDL_QUEUE_ON_DEAD_CONSUMER"] = "drain"
    try:
        import pyarrow as pa
        lease_queue = mq.MultiQueue(1)
        for i in range(4):
            lease_queue.put(0, pa.table({"x": [i]}))
        with svc.serve_queue(lease_queue) as server:
            dead = svc.RemoteQueue(server.address, max_batch=1)
            dead.get(0)
            dead.close()  # heartbeats stop; the lease must expire
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                if lease_queue.size(0) == 0:
                    break
                time.sleep(0.1)
            result["lease_drained"] = lease_queue.size(0) == 0
        lease_queue.shutdown()
    finally:
        os.environ.pop("RSDL_QUEUE_LEASE_TIMEOUT_S", None)
        os.environ.pop("RSDL_QUEUE_ON_DEAD_CONSUMER", None)
    return result


def _run_speculation_leg(seed: int) -> dict:
    """Straggler leg of ``--chaos``: an injected ``delayN`` straggler on
    one reduce task (targeted FROM the epoch plan via
    ``faults.spec_for_node`` — the chaos key and the task's lineage key
    are equal by construction), raced with speculation ON vs OFF at the
    same seed over several rounds. The contract the record carries:
    p99 epoch time improves with speculation on, the consumed stream is
    bit-identical either way, and at least one backup actually won.

    Runs on the THREAD backend deliberately: chaos key state is
    per-process, so a process-pool backup in a sibling worker would
    re-fire the injected delay and prove nothing (the process-backend
    first-wins contract is pinned in tests/test_plan.py instead — the
    bench host has 1 CPU).
    """
    import statistics
    import tempfile

    from ray_shuffling_data_loader_tpu import data_generation as datagen
    from ray_shuffling_data_loader_tpu.plan import ir as plan_ir
    from ray_shuffling_data_loader_tpu.plan import scheduler as plan_sched
    from ray_shuffling_data_loader_tpu.runtime import faults as rt_faults
    from ray_shuffling_data_loader_tpu.shuffle import shuffle as run_shuffle

    reducers, trainers, rounds, delay_ms = 3, 1, 5, 500
    tmpdir = tempfile.mkdtemp(prefix="rsdl-spec-leg-")
    filenames, _ = datagen.generate_data_local(6_000, 2, 1, 0.0, tmpdir)
    plan = plan_ir.build_epoch_plan(filenames, reducers, trainers,
                                    seed, epoch=0)
    straggler = plan.reduces()[0]
    rule = rt_faults.spec_for_node("reduce_gather", straggler,
                                   delay_ms=delay_ms)

    spec_env = {"RSDL_PLAN_SPECULATION": "1",
                "RSDL_PLAN_SPECULATION_MIN_S": "0.15",
                "RSDL_PLAN_SPECULATION_MULTIPLIER": "2.0",
                "RSDL_PLAN_SPECULATION_CHECK_S": "0.02"}

    def run_rounds(speculate: bool):
        """Per round: epoch time = start -> the consumer holds every
        reducer table of the epoch (the p99 the contract is about — a
        losing backup's discarded sleep drains during pool shutdown and
        is deliberately NOT part of epoch time)."""
        durations, streams = [], []
        for round_i in range(rounds):
            # Fresh injector per round: the delay rule fires once per
            # (site, epoch, task) key per injector, and every round must
            # see the same straggler.
            rt_faults.install(rule, seed=seed)
            try:
                stream: list = []
                done = {"t": None}
                start = time.monotonic()

                def consumer(rank, epoch, refs):
                    if refs is None:
                        return
                    for ref in refs:
                        stream.extend(
                            ref.result().column("key").to_pylist())
                    done["t"] = time.monotonic() - start

                run_shuffle(filenames, consumer, 1,
                            num_reducers=reducers, num_trainers=trainers,
                            max_concurrent_epochs=1, seed=seed,
                            collect_stats=False, file_cache=None,
                            num_workers=4, executor_backend="thread")
                durations.append(done["t"])
                streams.append(tuple(stream))
            finally:
                rt_faults.clear()
        return durations, streams

    totals_before = plan_sched.speculation_totals()
    for key, value in spec_env.items():
        os.environ[key] = value
    try:
        on_durations, on_streams = run_rounds(True)
    finally:
        for key in spec_env:
            os.environ.pop(key, None)
    totals_after = plan_sched.speculation_totals()
    off_durations, off_streams = run_rounds(False)

    def p99(values):
        ordered = sorted(values)
        return ordered[min(len(ordered) - 1,
                           int(0.99 * (len(ordered) - 1) + 0.999))]

    identical = len(set(on_streams + off_streams)) == 1
    won = (totals_after["speculative_won"]
           - totals_before["speculative_won"])
    result = {
        "rounds": rounds,
        "straggler": {"site": "reduce_gather", "rule": rule,
                      "node": straggler.id, "delay_ms": delay_ms},
        "p99_epoch_s_speculation_on": round(p99(on_durations), 4),
        "p99_epoch_s_speculation_off": round(p99(off_durations), 4),
        "median_epoch_s_speculation_on": round(
            statistics.median(on_durations), 4),
        "median_epoch_s_speculation_off": round(
            statistics.median(off_durations), 4),
        "p99_improvement_pct": round(
            100.0 * (1.0 - p99(on_durations) / p99(off_durations)), 2)
        if p99(off_durations) > 0 else 0.0,
        "speculative_launched": (totals_after["speculative_launched"]
                                 - totals_before["speculative_launched"]),
        "speculative_won": won,
        "speculative_wasted": (totals_after["speculative_wasted"]
                               - totals_before["speculative_wasted"]),
        "output_bit_identical": identical,
    }
    result["ok"] = bool(identical and won >= 1
                        and p99(on_durations) < p99(off_durations))
    return result


def _run_remote_leg(seed: int) -> dict:
    """Cold ingest against a simulated remote object store (storage/):
    plan-driven prefetch ON vs OFF at the same seed, same simulated
    latency/bandwidth draws, fresh tiered cache each side.

    The regime is deliberately a thrash shape — the tiered cache is
    budgeted below the working set, so a sequential scan under plain
    LRU misses every file every epoch. The prefetcher's idle lanes
    (the reduce tail leaves ``workers - reducers`` lanes free) re-warm
    the next epoch's head-of-plan files, which is exactly the win the
    record must carry: prefetch-on rows/s measurably above prefetch-off
    at the same seed, with the delivered stream bit-identical.

    Hermetic: generates its own small dataset, runs on the thread
    backend (a programmatic ``storage.set_source`` does not cross
    process boundaries — same caveat as programmatic chaos)."""
    import tempfile

    from ray_shuffling_data_loader_tpu import data_generation as datagen
    from ray_shuffling_data_loader_tpu import storage as rt_storage
    from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics
    from ray_shuffling_data_loader_tpu.shuffle import shuffle as run_shuffle
    from ray_shuffling_data_loader_tpu.storage.cache import (DiskTier,
                                                             TieredStore)
    from ray_shuffling_data_loader_tpu.storage.source import (
        LocalSource, SimulatedObjectStore)

    num_files, workers, reducers, epochs = 8, 4, 2, 3
    tmpdir = tempfile.mkdtemp(prefix="rsdl-remote-leg-")
    filenames, _ = datagen.generate_data_local(
        16_000, num_files, 1, 0.0, tmpdir)
    # Budget the tiers below the working set (thrash regime): one
    # decoded file's bytes, measured through the local source. Probing
    # EVERY file also warms the OS page cache, so the first-measured
    # side doesn't additionally pay cold-disk decode the second skips.
    file_bytes = max(LocalSource().read_table(f).nbytes
                     for f in filenames)
    hot_bytes = int(2.5 * file_bytes)
    disk_bytes = int(3.5 * file_bytes)

    def _storage_counts() -> dict:
        c = rt_metrics.counter
        return {
            "hot_hits": c("rsdl_storage_hits_total", tier="hot").value,
            "hot_misses": c("rsdl_storage_misses_total", tier="hot").value,
            "disk_hits": c("rsdl_storage_hits_total", tier="disk").value,
            "remote_misses": c("rsdl_storage_misses_total",
                               tier="remote").value,
            "remote_bytes": c("rsdl_storage_remote_bytes_read_total").value,
            "prefetch_issued": c("rsdl_storage_prefetch_issued_total").value,
            "prefetch_hits": c("rsdl_storage_prefetch_hits_total").value,
        }

    def run_side(prefetch: bool) -> "tuple[float, tuple, dict]":
        """(rows_per_sec, delivered key stream, storage counter delta)
        for one A/B side: fresh simulated source (same seed, so the
        same per-path latency draws), fresh tiered cache."""
        sim = SimulatedObjectStore(
            inner=LocalSource(), first_byte_ms=20.0, mb_per_s=200.0,
            jitter_pct=0.0, error_rate=0.0, seed=seed)
        store = TieredStore(hot_bytes,
                            disk=DiskTier(max_bytes=disk_bytes),
                            source=sim)
        prev_source = rt_storage.set_source(sim)
        os.environ["RSDL_STORAGE_PREFETCH"] = "1" if prefetch else "0"
        before = _storage_counts()
        stream: list = []
        rows = {"n": 0}

        def consumer(rank, epoch, refs):
            if refs is None:
                return
            for ref in refs:
                keys = ref.result().column("key").to_pylist()
                rows["n"] += len(keys)
                stream.extend(keys)

        start = time.monotonic()
        try:
            run_shuffle(filenames, consumer, epochs,
                        num_reducers=reducers, num_trainers=1,
                        max_concurrent_epochs=1, seed=seed,
                        collect_stats=False, file_cache=store,
                        num_workers=workers, executor_backend="thread")
        finally:
            duration = time.monotonic() - start
            os.environ.pop("RSDL_STORAGE_PREFETCH", None)
            rt_storage.set_source(prev_source)
            store.close()
        after = _storage_counts()
        delta = {key: after[key] - before[key] for key in after}
        return rows["n"] / duration, tuple(stream), delta

    off_rate, off_stream, _off_delta = run_side(prefetch=False)
    on_rate, on_stream, on_delta = run_side(prefetch=True)

    hot_total = on_delta["hot_hits"] + on_delta["hot_misses"]
    disk_probes = on_delta["disk_hits"] + on_delta["remote_misses"]
    issued = on_delta["prefetch_issued"]
    result = {
        "remote_rows_per_sec": round(on_rate, 1),
        "remote_prefetch_off_rows_per_sec": round(off_rate, 1),
        "remote_prefetch_speedup_x": round(on_rate / off_rate, 3)
        if off_rate > 0 else 0.0,
        "remote_cache_hit_rate_hot": round(
            on_delta["hot_hits"] / hot_total, 4) if hot_total else 0.0,
        "remote_cache_hit_rate_disk": round(
            on_delta["disk_hits"] / disk_probes, 4) if disk_probes else 0.0,
        "remote_prefetch_efficiency": round(
            on_delta["prefetch_hits"] / issued, 4) if issued else 0.0,
        "remote_prefetch_issued": int(issued),
        "remote_bytes_read": int(on_delta["remote_bytes"]),
        "remote_output_bit_identical": off_stream == on_stream,
        "remote_files": num_files,
        "remote_epochs": epochs,
    }
    result["remote_ok"] = bool(result["remote_output_bit_identical"]
                               and on_rate > off_rate and issued > 0)
    return result


def _run_serve_leg(filenames, seed: int = 0,
                   trainer_streams: int = 8,
                   shards: int = 4) -> dict:
    """Serving-plane leg: ``trainer_streams`` concurrent remote trainer
    streams draining one pre-shuffled epoch through the sharded queue
    fabric, measured three ways on the same seed —

    1. single shard, shm-handle delivery (the pre-PR-10 topology's
       process count with the new wire),
    2. ``shards`` shards, shm-handle delivery (the headline
       ``serve_rows_per_sec``; the ratio vs leg 1 is the shard-scaling
       evidence), and
    3. ``shards`` shards, streamed v2 delivery (same table flow, so the
       wire-byte ratio vs leg 2 attributes the handle win per layer,
       not by inference).

    The shuffle runs to completion BEFORE each clock starts: the leg
    times the serving plane, not the producer. Byte/handle/compression
    counters are attributed per leg by delta
    (``stats.queue_serve_totals``).
    """
    import threading

    from ray_shuffling_data_loader_tpu import multiqueue as mq
    from ray_shuffling_data_loader_tpu import multiqueue_service as svc
    from ray_shuffling_data_loader_tpu import stats as rsdl_stats
    from ray_shuffling_data_loader_tpu.plan import ir as plan_ir
    from ray_shuffling_data_loader_tpu.shuffle import shuffle as run_shuffle

    # A small sub-corpus: the leg measures the serving plane's relative
    # scaling/wire behavior, not corpus throughput.
    leg_files = filenames[:2]
    num_reducers = trainer_streams

    def _fill_queue():
        queue = mq.MultiQueue(trainer_streams)

        def consumer(rank, epoch, refs):
            queue_idx = plan_ir.queue_index(epoch, rank, trainer_streams)
            if refs is None:
                queue.put(queue_idx, None)
            else:
                queue.put_batch(queue_idx, list(refs))

        run_shuffle(leg_files, consumer, 1, num_reducers=num_reducers,
                    num_trainers=trainer_streams, max_concurrent_epochs=1,
                    seed=seed, collect_stats=False, file_cache=None)
        return queue

    def _drain(num_shards: int, delivery: str) -> "tuple[float, dict]":
        queue = _fill_queue()
        counts = [0] * trainer_streams
        errors: list = []
        before = rsdl_stats.queue_serve_totals()
        with svc.serve_queue_sharded(queue, num_shards=num_shards,
                                     num_trainers=trainer_streams
                                     ) as sharded:

            def consume(rank: int) -> None:
                try:
                    with svc.ShardedRemoteQueue(
                            sharded.shard_map, max_batch=4,
                            delivery=delivery) as remote:
                        queue_idx = plan_ir.queue_index(
                            0, rank, trainer_streams)
                        while True:
                            table = remote.get(queue_idx)
                            if table is None:
                                return
                            counts[rank] += table.num_rows
                except BaseException as e:  # noqa: BLE001 - re-raised
                    errors.append(e)

            threads = [threading.Thread(target=consume, args=(r,),
                                        daemon=True,
                                        name=f"bench-serve-{r}")
                       for r in range(trainer_streams)]
            start = timeit.default_timer()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            duration = max(timeit.default_timer() - start, 1e-9)
        queue.shutdown()
        if errors:
            raise errors[0]
        after = rsdl_stats.queue_serve_totals()
        delta = {key: after[key] - before[key]
                 for key in ("queue_payload_bytes", "queue_bytes_on_wire",
                             "queue_handle_hits", "queue_handle_misses",
                             "queue_compression_saved_bytes")}
        return sum(counts) / duration, delta

    single_rate, _single = _drain(1, "auto")
    sharded_rate, handle_delta = _drain(shards, "auto")
    _stream_rate, stream_delta = _drain(shards, "stream")

    wire_handle = max(1, handle_delta["queue_bytes_on_wire"])
    wire_stream = stream_delta["queue_bytes_on_wire"]
    saved = stream_delta["queue_compression_saved_bytes"]
    compression_ratio = (
        (wire_stream + saved) / wire_stream if wire_stream else 1.0)
    return {
        "serve_shards": shards,
        "serve_trainer_streams": trainer_streams,
        "serve_rows_per_sec": round(sharded_rate, 1),
        "serve_rows_per_sec_single_shard": round(single_rate, 1),
        "serve_speedup_vs_single_shard": round(
            sharded_rate / single_rate, 3) if single_rate else None,
        "queue_bytes_on_wire": handle_delta["queue_bytes_on_wire"],
        "queue_bytes_on_wire_stream": wire_stream,
        "serve_handle_wire_reduction_x": round(
            wire_stream / wire_handle, 1),
        "queue_handle_hits": handle_delta["queue_handle_hits"],
        "queue_handle_misses": handle_delta["queue_handle_misses"],
        "queue_compression_ratio": round(compression_ratio, 4),
    }


def _run_latency_leg(filenames, seed: int = 0,
                     trainer_streams: int = 2,
                     shards: int = 2) -> dict:
    """Delivery-latency leg (runtime/latency.py): ``trainer_streams``
    remote trainers drain one pre-shuffled epoch over the SHARDED
    serving plane, each closing the loop through a real
    ``JaxShufflingDataset`` (convert + device transfer), on BOTH
    delivery paths — shm-handle first, then streamed v2 bytes. The
    sketch's centroid deltas between snapshots attribute the quantiles
    per path exactly (cumulative counts subtract), and the headline
    ``delivery_p99_ms`` / ``freshness_p99_ms`` are gated by
    ``--baseline`` like any other metric.
    """
    import threading

    from ray_shuffling_data_loader_tpu import multiqueue as mq
    from ray_shuffling_data_loader_tpu import multiqueue_service as svc
    from ray_shuffling_data_loader_tpu.jax_dataset import JaxShufflingDataset
    from ray_shuffling_data_loader_tpu.plan import ir as plan_ir
    from ray_shuffling_data_loader_tpu.runtime import latency as rt_lat
    from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics
    from ray_shuffling_data_loader_tpu.shuffle import shuffle as run_shuffle
    from ray_shuffling_data_loader_tpu.workloads.dlrm_criteo import dlrm_spec

    leg_files = filenames[:2]
    series = "rsdl_delivery_latency_seconds_centroid"

    def _snapshot() -> dict:
        return dict(rt_metrics.parse_exposition(
            rt_metrics.render()).get(series, {}))

    def _delta(now: dict, base: dict) -> dict:
        return {labels: value - base.get(labels, 0.0)
                for labels, value in now.items()
                if value - base.get(labels, 0.0) > 0}

    def _hop_stats(delta: dict, hop: str):
        counts: dict = {}
        for labels, value in delta.items():
            d = dict(labels)
            if d.get("hop") != hop or "c" not in d:
                continue
            centroid = float(d["c"])
            counts[centroid] = counts.get(centroid, 0.0) + value
        total = int(sum(counts.values()))
        if not total:
            return None
        return {
            "count": total,
            "p50": rt_metrics._centroid_quantile(counts, total, 0.5),
            "p95": rt_metrics._centroid_quantile(counts, total, 0.95),
            "p99": rt_metrics._centroid_quantile(counts, total, 0.99),
        }

    def _drain(delivery: str) -> None:
        queue = mq.MultiQueue(trainer_streams)

        def consumer(rank, epoch, refs):
            queue_idx = plan_ir.queue_index(epoch, rank, trainer_streams)
            if refs is None:
                queue.put(queue_idx, None)
            else:
                queue.put_batch(queue_idx, list(refs))

        run_shuffle(leg_files, consumer, 1,
                    num_reducers=trainer_streams,
                    num_trainers=trainer_streams, max_concurrent_epochs=1,
                    seed=seed, collect_stats=False, file_cache=None)
        errors: list = []
        with svc.serve_queue_sharded(queue, num_shards=shards,
                                     num_trainers=trainer_streams
                                     ) as sharded:

            def consume(rank: int) -> None:
                try:
                    remote = svc.ShardedRemoteQueue(
                        sharded.shard_map, max_batch=2, delivery=delivery)
                    # Small batches + drop_last=False: the leg measures
                    # latency, not throughput, and must convert/transfer
                    # even a smoke-sized corpus so the device hops have
                    # samples.
                    ds = JaxShufflingDataset(
                        leg_files, num_epochs=1,
                        num_trainers=trainer_streams, batch_size=8_192,
                        rank=rank, batch_queue=remote,
                        shuffle_result=None, seed=seed, prefetch_size=2,
                        drop_last=False, **dlrm_spec())
                    try:
                        ds.set_epoch(0)
                        for _features, _label in ds:
                            pass
                    finally:
                        ds.close()
                        remote.close()
                except BaseException as e:  # noqa: BLE001 - re-raised
                    errors.append(e)

            threads = [threading.Thread(target=consume, args=(rank,),
                                        daemon=True,
                                        name=f"bench-latency-{rank}")
                       for rank in range(trainer_streams)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
        queue.shutdown()
        if errors:
            raise errors[0]

    before = _snapshot()
    _drain("auto")       # shm-handle path (loopback)
    after_handle = _snapshot()
    _drain("stream")     # streamed v2 bytes, same table flow
    after_stream = _snapshot()

    handle_delta = _delta(after_handle, before)
    stream_delta = _delta(after_stream, after_handle)
    whole_delta = _delta(after_stream, before)
    delivered = _hop_stats(handle_delta, rt_lat.HOP_BIRTH_TO_DELIVERED)
    delivered_stream = _hop_stats(stream_delta,
                                  rt_lat.HOP_BIRTH_TO_DELIVERED)
    device = _hop_stats(whole_delta, rt_lat.HOP_BIRTH_TO_DEVICE)
    queued = _hop_stats(whole_delta, rt_lat.HOP_BIRTH_TO_QUEUED)
    if delivered is None or delivered_stream is None:
        raise RuntimeError(
            "latency leg observed no birth_to_delivered samples on one "
            "of the delivery paths (handle "
            f"{delivered}, stream {delivered_stream})")
    per_queue = {
        dict(labels).get("queue", "?"): round(entry["p99"] * 1e3, 3)
        for labels, entry in rt_metrics.sketch_quantiles(
            {series: whole_delta}, "rsdl_delivery_latency_seconds",
            qs=(0.99,), hop=rt_lat.HOP_BIRTH_TO_DELIVERED).items()}
    result = {
        "latency_trainer_streams": trainer_streams,
        "latency_shards": shards,
        "delivery_p50_ms": round(delivered["p50"] * 1e3, 3),
        "delivery_p95_ms": round(delivered["p95"] * 1e3, 3),
        "delivery_p99_ms": round(delivered["p99"] * 1e3, 3),
        "delivery_p99_ms_stream": round(
            delivered_stream["p99"] * 1e3, 3),
        "delivery_frames": delivered["count"] + delivered_stream["count"],
        "latency_per_queue_p99_ms": per_queue,
    }
    if queued is not None:
        result["queued_p99_ms"] = round(queued["p99"] * 1e3, 3)
    if device is not None:
        result["freshness_p99_ms"] = round(device["p99"] * 1e3, 3)
    return result


def _run_stream_leg(seed: int = 0, windows: int = 3,
                    files_per_window: int = 2,
                    rows_per_file: int = 4_096) -> dict:
    """Streaming leg (streaming/): a seeded ``SyntheticEventSource``
    drives ``windows`` count-bounded windows through the
    :class:`StreamingShuffleRunner` while one remote trainer drains the
    served stream through a real ``JaxShufflingDataset`` (convert +
    device transfer) — window N+1 assembles and shuffles UNDER window
    N's serve (``max_concurrent_epochs=2``), so the per-window watermark
    lag samples measure the real pipelining gap, in stream seconds.
    Freshness is the PR 11 birth->device sketch measured on LIVE
    windows (the same plane the latency leg gates on static epochs),
    reported as ``stream_freshness_p99_ms`` so the two legs never
    collide in one record. Hermetic: the drifting click stream is
    generated into a fresh tempdir and every arrival is a pure function
    of ``(seed, event_index)``.
    """
    import tempfile
    import threading

    from ray_shuffling_data_loader_tpu import multiqueue as mq
    from ray_shuffling_data_loader_tpu import multiqueue_service as svc
    from ray_shuffling_data_loader_tpu.jax_dataset import JaxShufflingDataset
    from ray_shuffling_data_loader_tpu.plan import ir as plan_ir
    from ray_shuffling_data_loader_tpu.runtime import latency as rt_lat
    from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics
    from ray_shuffling_data_loader_tpu.streaming import (
        StreamingShuffleRunner, SyntheticEventSource)
    from ray_shuffling_data_loader_tpu.streaming import window as st_window
    from ray_shuffling_data_loader_tpu.workloads import dlrm_criteo

    num_files = windows * files_per_window
    total_rows = num_files * rows_per_file
    series = "rsdl_delivery_latency_seconds_centroid"
    close_hist = rt_metrics.histogram(
        "rsdl_stream_window_close_seconds",
        "wall time from a window's first event to its seal")

    def _snapshot() -> dict:
        return dict(rt_metrics.parse_exposition(
            rt_metrics.render()).get(series, {}))

    def _device_p99_ms(now: dict, base: dict):
        counts: dict = {}
        for labels, value in now.items():
            delta = value - base.get(labels, 0.0)
            d = dict(labels)
            if (delta <= 0 or d.get("hop") != rt_lat.HOP_BIRTH_TO_DEVICE
                    or "c" not in d):
                continue
            centroid = float(d["c"])
            counts[centroid] = counts.get(centroid, 0.0) + delta
        total = int(sum(counts.values()))
        if not total:
            return None
        return round(
            rt_metrics._centroid_quantile(counts, total, 0.99) * 1e3, 3)

    with tempfile.TemporaryDirectory(prefix="rsdl-bench-stream-") as td:
        files = dlrm_criteo.generate_drifting_stream(
            num_files, rows_per_file, td, seed=seed)
        source = SyntheticEventSource(files, seed=seed,
                                      total_events=num_files)
        queue = mq.MultiQueue(windows)
        lag_samples: list = []
        holder: dict = {}

        def consumer(rank, epoch, refs):
            queue_idx = plan_ir.queue_index(epoch, rank, 1)
            if refs is None:
                queue.put(queue_idx, None)
            else:
                queue.put_batch(queue_idx, list(refs))

        def on_window_served(_window_index: int) -> None:
            runner = holder["runner"]
            ingest = runner.assembler.ingest_watermark
            serve = runner.serve_watermark
            if ingest != float("-inf") and serve != float("-inf"):
                lag_samples.append(max(0.0, ingest - serve))

        runner = StreamingShuffleRunner(
            source, consumer, num_reducers=max(2, files_per_window),
            num_trainers=1, seed=seed, max_concurrent_epochs=2,
            policy=st_window.WindowPolicy(max_files=files_per_window),
            on_window_served=on_window_served)
        holder["runner"] = runner

        close_before = (close_hist.sum, close_hist.count)
        lat_before = _snapshot()
        rows_holder = {"rows": 0}
        errors: list = []
        start = timeit.default_timer()
        with svc.serve_queue_sharded(queue, num_shards=1,
                                     num_trainers=1) as sharded:

            def drain() -> None:
                try:
                    remote = svc.ShardedRemoteQueue(sharded.shard_map,
                                                    max_batch=2)
                    ds = JaxShufflingDataset(
                        files, num_epochs=windows, num_trainers=1,
                        batch_size=8_192, rank=0, batch_queue=remote,
                        shuffle_result=None, seed=seed, prefetch_size=2,
                        drop_last=False, **dlrm_criteo.dlrm_spec())
                    try:
                        for epoch in plan_ir.epoch_range(0, windows):
                            ds.set_epoch(epoch)
                            for _features, label in ds:
                                rows_holder["rows"] += int(label.shape[0])
                    finally:
                        ds.close()
                        remote.close()
                except BaseException as e:  # noqa: BLE001 - re-raised
                    errors.append(e)

            trainer = threading.Thread(target=drain, daemon=True,
                                       name="bench-stream-trainer")
            trainer.start()
            summary = runner.run()
            trainer.join(timeout=300)
        duration_s = timeit.default_timer() - start
        queue.shutdown()
        runner.close()
        if errors:
            raise errors[0]
        rows_delivered = rows_holder["rows"]
        if rows_delivered != total_rows:
            raise RuntimeError(
                f"stream leg delivered {rows_delivered} rows, expected "
                f"{total_rows} — the windowed stream lost or duplicated "
                "rows")

    lag_p99 = 0.0
    if lag_samples:
        ordered = sorted(lag_samples)
        lag_p99 = ordered[min(len(ordered) - 1,
                              int(0.99 * len(ordered)))]
    close_sum = close_hist.sum - close_before[0]
    close_count = close_hist.count - close_before[1]
    result = {
        "stream_windows": summary["windows_served"],
        "stream_events": summary["events_sealed"],
        "stream_rows_per_sec": round(total_rows / duration_s, 1),
        "stream_duration_s": round(duration_s, 3),
        "watermark_lag_p99_s": round(lag_p99, 6),
        "late_events": summary["late_events"],
        "window_close_ms": round(1e3 * close_sum / close_count, 3)
        if close_count else 0.0,
    }
    fresh = _device_p99_ms(_snapshot(), lat_before)
    if fresh is not None:
        result["stream_freshness_p99_ms"] = fresh
    return result


def _run_tenancy_leg(filenames, seed: int = 0, hot_weight: float = 3.0,
                     cold_weight: float = 1.0) -> dict:
    """Multi-tenant contention leg (tenancy/): a hot streaming tenant
    (weight ``hot_weight``, rank 0) and a cold batch-replay tenant
    (weight ``cold_weight``, rank 1) share ONE serving shard's
    replay-byte budget under the deficit-round-robin scheduler.

    Two phases over identical fills: the hot tenant first drains its
    rank ALONE (the solo latency baseline), then both tenants drain
    concurrently. The fairness ratio is hot rows over cold rows at the
    instant the hot tenant finishes — under equal demand and sustained
    contention the DRR should split delivery ~``hot_weight/cold_weight``
    (the one-frame-per-GET liveness floor dilutes it a few percent
    toward 1). Per-tenant p99s come from the
    ``rsdl_tenant_delivery_latency_seconds`` sketch the wire clients
    feed (both announce their identity via OP_TENANT, so the leg
    exercises the wire binding, not just the server-side rank table),
    and ``tenancy_latency_ratio_x`` is the ISSUE contract number: the
    hot tenant's contended p99 over its solo p99 (target <= 1.5x).
    Admission evidence rides along: both working sets register against
    a journaled controller sized to hold them, plus one deliberately
    oversized ask that must be rejected.
    """
    import tempfile
    import threading

    from ray_shuffling_data_loader_tpu import dataset as rsdl_dataset
    from ray_shuffling_data_loader_tpu import multiqueue as mq
    from ray_shuffling_data_loader_tpu import multiqueue_service as svc
    from ray_shuffling_data_loader_tpu import tenancy as rt_tenancy
    from ray_shuffling_data_loader_tpu.plan import ir as plan_ir
    from ray_shuffling_data_loader_tpu.runtime import latency as rt_lat
    from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics
    from ray_shuffling_data_loader_tpu.shuffle import shuffle as run_shuffle
    from ray_shuffling_data_loader_tpu.tenancy import admission as rt_adm

    leg_files = filenames[:2]
    streams = 2  # rank 0 = hot, rank 1 = cold
    # Many small frames: the DRR meters bytes per pop, and every GET
    # delivers one frame unconditionally (the liveness floor) — so the
    # weighted split is only observable when a rank's epoch spans far
    # more frames than its consumer issues GETs. 128 reducers give each
    # rank ~128 frames; with a deep max_batch most frames then move as
    # DRR grants, not floors.
    reducers = int(os.environ.get("RSDL_BENCH_TENANCY_REDUCERS", 128))
    # Sustained demand: several pre-filled epochs per rank, so the
    # contended drain spans many DRR replenish cycles instead of
    # finishing inside the first one.
    epochs = int(os.environ.get("RSDL_BENCH_TENANCY_EPOCHS", 3))
    series = "rsdl_tenant_delivery_latency_seconds_centroid"
    hot_slo_p99_ms = float(os.environ.get("RSDL_BENCH_TENANCY_SLO_MS",
                                          50.0))
    hot_ctx = rt_tenancy.TenantContext("hot", priority="interactive",
                                       weight=hot_weight,
                                       slo_p99_ms=hot_slo_p99_ms)
    cold_ctx = rt_tenancy.TenantContext("cold", priority="batch",
                                        weight=cold_weight)
    tenants_cfg = {"hot": {"weight": hot_weight, "ranks": [0]},
                   "cold": {"weight": cold_weight, "ranks": [1]}}

    def _snapshot() -> dict:
        return dict(rt_metrics.parse_exposition(
            rt_metrics.render()).get(series, {}))

    def _tenant_p99(now: dict, base: dict, tenant: str):
        counts: dict = {}
        for labels, value in now.items():
            delta = value - base.get(labels, 0.0)
            d = dict(labels)
            if (delta <= 0 or d.get("tenant") != tenant
                    or d.get("hop") != rt_lat.HOP_QUEUED_TO_DELIVERED
                    or "c" not in d):
                continue
            centroid = float(d["c"])
            counts[centroid] = counts.get(centroid, 0.0) + delta
        total = int(sum(counts.values()))
        if not total:
            return None
        return rt_metrics._centroid_quantile(counts, total, 0.99)

    hot_queues = [plan_ir.queue_index(e, 0, streams)
                  for e in plan_ir.epoch_range(0, epochs)]

    def _shuffle_refs() -> dict:
        """One shuffled corpus as {queue_idx: [ref..., None sentinel]}
        — held outside the MultiQueue so a phase can preload a rank
        (batch replay) or feed it live (a stream)."""
        refs_by_queue: dict = {}

        def consumer(rank, epoch, refs):
            queue_idx = plan_ir.queue_index(epoch, rank, streams)
            items = refs_by_queue.setdefault(queue_idx, [])
            if refs is None:
                items.append(None)
            else:
                items.extend(refs)

        run_shuffle(leg_files, consumer, epochs, num_reducers=reducers,
                    num_trainers=streams, max_concurrent_epochs=epochs,
                    seed=seed, collect_stats=False, file_cache=None)
        return refs_by_queue

    def _drain(rank: int, ctx, counts: dict, started: threading.Event,
               finished: threading.Event, errors: list, server) -> None:
        """One tenant's trainer: announce identity over the wire
        (OP_TENANT), drain the rank's epoch, count rows as they land."""
        try:
            started.wait(timeout=60)
            # max_batch deep enough that granted frames dominate the
            # one-frame liveness floor (the floor is what dilutes the
            # measured split below the configured weights).
            remote = svc.RemoteQueue(server.address, max_batch=128,
                                     num_trainers=streams, tenant=ctx)
            try:
                for epoch in plan_ir.epoch_range(0, epochs):
                    queue_idx = plan_ir.queue_index(epoch, rank, streams)
                    while True:
                        item = remote.get(queue_idx)
                        if item is None:
                            break
                        if isinstance(item, rsdl_dataset.ShuffleFailure):
                            raise RuntimeError(
                                f"tenancy leg rank {rank}: {item}")
                        counts[ctx.tenant_id] += item.num_rows
            finally:
                remote.close()
        except BaseException as e:  # noqa: BLE001 - re-raised by caller
            errors.append(e)
        finally:
            finished.set()

    def _run_phase(contended: bool, feed_dt=None) -> dict:
        """One serve-and-drain round. ``feed_dt=None``: the hot rank is
        preloaded like the cold one and drains greedily (the fairness
        measurement — equal backlog, equal appetite, the DRR decides).
        With ``feed_dt`` the hot rank is a LIVE stream: a feeder thread
        puts one frame every ``feed_dt`` seconds, so the hot tenant's
        queued->delivered dwell measures scheduling delay, not backlog
        depth (the p99 SLO measurement)."""
        refs_by_queue = _shuffle_refs()
        queue = mq.MultiQueue(epochs * streams)
        for queue_idx, items in refs_by_queue.items():
            if feed_dt is not None and queue_idx in hot_queues:
                continue  # fed live below
            for item in items:
                queue.put(queue_idx, item)
        counts = {"hot": 0, "cold": 0}
        errors: list = []
        start_gate = threading.Event()
        hot_done = threading.Event()
        cold_done = threading.Event()
        before = _snapshot()

        def _feed() -> None:
            try:
                start_gate.wait(timeout=60)
                for queue_idx in hot_queues:
                    for item in refs_by_queue.get(queue_idx, []):
                        time.sleep(feed_dt)
                        queue.put(queue_idx, item)
            except BaseException as e:  # noqa: BLE001 - re-raised
                errors.append(e)

        with svc.serve_queue(queue, num_trainers=streams,
                             tenants=tenants_cfg) as server:
            threads = [threading.Thread(
                target=_drain, args=(0, hot_ctx, counts, start_gate,
                                     hot_done, errors, server),
                daemon=True, name="bench-tenancy-hot")]
            if contended:
                threads.append(threading.Thread(
                    target=_drain, args=(1, cold_ctx, counts, start_gate,
                                         cold_done, errors, server),
                    daemon=True, name="bench-tenancy-cold"))
            if feed_dt is not None:
                threads.append(threading.Thread(
                    target=_feed, daemon=True,
                    name="bench-tenancy-feeder"))
            for t in threads:
                t.start()
            t0 = timeit.default_timer()
            start_gate.set()
            hot_done.wait(timeout=300)
            hot_elapsed = timeit.default_timer() - t0
            # The fairness sample: cold's delivery the instant hot's
            # equal-demand drain completes.
            cold_at_hot_finish = counts["cold"]
            for t in threads:
                t.join(timeout=300)
        queue.shutdown()
        if errors:
            raise errors[0]
        return {
            "hot_rows": counts["hot"],
            "hot_frames": sum(
                1 for queue_idx in hot_queues
                for item in refs_by_queue.get(queue_idx, [])
                if item is not None),
            "cold_rows_at_hot_finish": cold_at_hot_finish,
            "hot_elapsed_s": max(hot_elapsed, 1e-9),
            "hot_p99_s": _tenant_p99(_snapshot(), before, "hot"),
        }

    # Admission evidence: both tenants' working sets are journaled
    # accepts; a 64x-oversized ask must journal a reject.
    ask = sum(os.path.getsize(f) for f in leg_files) // streams + 1
    with tempfile.TemporaryDirectory(prefix="rsdl-bench-adm-") as td:
        controller = rt_adm.AdmissionController(
            capacity_bytes=4 * streams * ask,
            journal_path=os.path.join(td, "admission.jsonl"))
        accepted = sum(
            controller.register(ctx, "dataset", f"bench-{ctx.tenant_id}",
                                ask).action == "accept"
            for ctx in (hot_ctx, cold_ctx))
        rejected = int(controller.register(
            rt_tenancy.TenantContext("greedy"), "dataset", "bench-greedy",
            64 * streams * ask).action == "reject")
        replayed = rt_adm.replay(os.path.join(td, "admission.jsonl"),
                                 capacity_bytes=4 * streams * ask)
        admission_replay_ok = (replayed.journal_bytes()
                               == controller.journal_bytes())
        controller.close()

    # Pin the DRR quantum to a few frame-sizes of THIS corpus: with the
    # default 1 MiB quantum a small corpus gets more credit per
    # replenish than a whole rank queue holds, every pop is granted,
    # and the measured split collapses to the demand ratio (1:1)
    # instead of the weights. ~6 frames of credit per replenish keeps
    # granted frames dominant over the one-frame liveness floor.
    frame_est = max(1, int(2.5 * sum(os.path.getsize(f)
                                     for f in leg_files))
                    // (streams * reducers))
    quantum_key = "RSDL_QUEUE_TENANT_DRR_QUANTUM_BYTES"
    prior_quantum = os.environ.get(quantum_key)
    os.environ[quantum_key] = str(16 * frame_est)
    try:
        solo = _run_phase(contended=False)
        contended = _run_phase(contended=True)
        # Live-stream p99 phases: feed the hot rank one frame at a time
        # at half its measured solo greedy drain rate (a stream the
        # serving plane can comfortably keep up with), first alone and
        # then against the cold tenant's full greedy backlog replay.
        feed_dt = min(0.02, max(5e-4,
                                2.0 * solo["hot_elapsed_s"]
                                / max(1, solo["hot_frames"])))
        lat_solo = _run_phase(contended=False, feed_dt=feed_dt)
        lat_cont = _run_phase(contended=True, feed_dt=feed_dt)
    finally:
        if prior_quantum is None:
            os.environ.pop(quantum_key, None)
        else:
            os.environ[quantum_key] = prior_quantum

    weight_ratio = hot_weight / cold_weight
    fairness = (contended["hot_rows"]
                / max(1, contended["cold_rows_at_hot_finish"]))
    p99_solo = lat_solo["hot_p99_s"]
    p99_cont = lat_cont["hot_p99_s"]
    latency_ratio = (round(p99_cont / p99_solo, 3)
                     if p99_solo and p99_cont else None)
    # The contract checks (loosened from the deterministic +-15% the
    # fairshare unit test proves, to absorb the liveness floor and
    # loopback scheduling noise of a live multi-thread drain). The p99
    # contract accepts EITHER bound: contended <= 1.5x solo, or the
    # hot tenant's absolute slo_p99_ms — at millisecond solo baselines
    # the ratio measures thread-wakeup jitter under CPU load more than
    # queueing, and the absolute SLO is the bound a tenant actually
    # signed up for.
    fairness_ok = abs(fairness / weight_ratio - 1.0) <= 0.35
    latency_ok = (latency_ratio is None or latency_ratio <= 1.5
                  or (p99_cont is not None
                      and p99_cont * 1e3 <= hot_slo_p99_ms))
    result = {
        "tenancy_weight_ratio": round(weight_ratio, 3),
        "tenancy_fairness_ratio": round(fairness, 3),
        "tenancy_hot_rows": contended["hot_rows"],
        "tenancy_cold_rows_at_hot_finish":
            contended["cold_rows_at_hot_finish"],
        "tenancy_hot_rows_per_sec": round(
            contended["hot_rows"] / contended["hot_elapsed_s"], 1),
        "tenancy_cold_rows_per_sec": round(
            contended["cold_rows_at_hot_finish"]
            / contended["hot_elapsed_s"], 1),
        "tenancy_solo_rows_per_sec": round(
            solo["hot_rows"] / solo["hot_elapsed_s"], 1),
        "tenancy_hot_slo_p99_ms": hot_slo_p99_ms,
        "tenancy_admitted": accepted,
        "tenancy_rejected": rejected,
        "tenancy_admission_replay_ok": admission_replay_ok,
        "tenancy_ok": bool(fairness_ok and latency_ok
                           and admission_replay_ok),
    }
    if p99_solo is not None:
        result["tenancy_hot_p99_ms_solo"] = round(p99_solo * 1e3, 3)
    if p99_cont is not None:
        result["tenancy_hot_p99_ms_contended"] = round(p99_cont * 1e3, 3)
    if latency_ratio is not None:
        result["tenancy_latency_ratio_x"] = latency_ratio
    return result


def _run_elastic_leg(seed: int = 0, num_files: int = 4,
                     rows_per_file: int = 4_096,
                     num_reducers: int = 8) -> dict:
    """Elastic membership leg (membership/): a mid-run rank kill plus a
    boundary rejoin, measured end to end.

    Phase 1 — failure detection: two live local transports, a
    ``HeartbeatProber`` on host 0 watching host 1; host 1's process is
    killed (its transport closed cold, no goodbye) and the leg measures
    the wall time from the kill to the detector's DOWN verdict —
    ``member_down_detect_ms``, the real latency a production shrink
    pays before the plan rewrite can start.

    Phase 2 — resize correctness: a fixed-world
    :class:`membership.elastic.ElasticShuffleRunner` run is the
    reference; the elastic run kills rank 1 mid-epoch 0 via the
    ``member_crash`` chaos site (survivors recompute its undelivered
    reducers from lineage) and rejoins it — plus a NEW rank, growing
    the world uneven — at the epoch boundary. ``rows_lost`` MUST be 0
    and the merged stream bit-identical (reducer outputs are pure in
    ``(seed, epoch, reducer)``); ``resize_stall_ms`` is the recompute
    tax from the first death to epoch completion. Hermetic: synthetic
    parquet in a fresh tempdir, chaos installed and cleared locally.
    """
    import tempfile
    import threading

    import pyarrow as pa
    import pyarrow.parquet as pq

    from ray_shuffling_data_loader_tpu import membership as mem
    from ray_shuffling_data_loader_tpu.membership import detector as md
    from ray_shuffling_data_loader_tpu.membership import elastic as me
    from ray_shuffling_data_loader_tpu.parallel import transport as tp
    from ray_shuffling_data_loader_tpu.runtime import faults as rt_faults

    # Phase 1: detection latency over real sockets. Host 0 probes, so
    # host 1 observes its heartbeat frames; host 1's detector tracks
    # rank 0 and the leg measures kill -> DOWN wall time.
    transports = tp.create_local_transports(2, recv_timeout_s=30.0)
    down = threading.Event()
    beats = threading.Semaphore(0)
    det0 = md.FailureDetector([0], heartbeat_s=0.05, suspect_s=0.4,
                              on_down=lambda rank: down.set())

    def _observe(src, inc, view, hb):
        det0.beat(src)
        beats.release()

    transports[1].set_frame_observer(_observe)
    prober = md.HeartbeatProber(transports[0], det0, interval_s=0.05)
    prober.start()
    # Warm up until a handful of real heartbeats have landed, so the
    # detector's smoothed inter-arrival window reflects the live link
    # before the kill (phi is trivially 0.0 at arm time).
    for _ in range(5):
        beats.acquire(timeout=2.0)
    kill_at = timeit.default_timer()
    prober.stop()          # host 0 goes silent: the kill
    detect_deadline = timeit.default_timer() + 5.0
    while not down.is_set() and timeit.default_timer() < detect_deadline:
        det0.poll()
        time.sleep(0.01)
    detect_ms = ((timeit.default_timer() - kill_at) * 1e3
                 if down.is_set() else None)
    for t in transports:
        t.close()

    # Phase 2: shrink + grow correctness and the resize stall.
    with tempfile.TemporaryDirectory(prefix="rsdl_elastic_") as tmpdir:
        filenames = []
        for i in range(num_files):
            start = i * rows_per_file
            table = pa.table({"key": pa.array(
                range(start, start + rows_per_file), type=pa.int64())})
            path = os.path.join(tmpdir, f"elastic_{i}.parquet")
            pq.write_table(table, path)
            filenames.append(path)

        rt_faults.clear()
        fixed = me.ElasticShuffleRunner(
            filenames, num_reducers, seed=seed,
            manager=mem.MembershipManager([0, 1, 2, 3])).run(2)

        rt_faults.install("member_crash:rank1:epoch0", seed=seed)
        manager = mem.MembershipManager([0, 1, 2, 3])
        runner = me.ElasticShuffleRunner(filenames, num_reducers,
                                         seed=seed, manager=manager)
        start_t = timeit.default_timer()
        epoch0 = runner.run_epoch(0)
        stall_ms = float(runner.last_stats.get("resize_stall_ms", 0.0))
        recomputed = int(runner.last_stats.get("recomputed", 0))
        shrunk_view = manager.current_view()
        # Boundary grow: the killed rank rejoins with a bumped
        # incarnation AND a brand-new rank joins — 5 ranks, uneven.
        manager.member_join(1, reason="bench rejoin")
        manager.member_join(4, reason="bench grow")
        epoch1 = runner.run_epoch(1)
        elapsed = timeit.default_timer() - start_t
        rt_faults.clear()

        expected = sum(t.num_rows for epoch in fixed for t in epoch)
        delivered = me.total_rows(epoch0) + me.total_rows(epoch1)
        rows_lost = expected - delivered
        identical = (all(a.equals(b) for a, b in zip(fixed[0], epoch0))
                     and all(a.equals(b)
                             for a, b in zip(fixed[1], epoch1)))

    result = {
        "elastic_rows_per_sec": round(delivered / elapsed, 1),
        "resize_stall_ms": round(stall_ms, 3),
        "rows_lost": int(rows_lost),
        "elastic_shrunk_to": len(shrunk_view.ranks),
        "elastic_grew_to": len(manager.current_view().ranks),
        "elastic_recomputed": recomputed,
        "elastic_ok": bool(rows_lost == 0 and identical
                           and detect_ms is not None),
    }
    if detect_ms is not None:
        result["member_down_detect_ms"] = round(detect_ms, 1)
    return result


def _run_rebalance_leg(seed: int = 0) -> dict:
    """Self-healing serving-plane leg (rebalance/): a hot tenant
    saturates shard 0, the co-located SLO tenant's delivery p99
    breaches, and the journaled controller live-migrates the breaching
    rank to the idle shard — measuring the whole loop end to end.

    Topology: 4 trainer ranks over 2 in-process shards (static
    placement: ranks 0/2 on shard 0, ranks 1/3 on shard 1). Rank 0 is
    the saturator — a feeder pumps frames continuously and a greedy
    deep-batch drain keeps shard 0's serve path busy for the whole leg.
    Rank 2 is the SLO tenant: fed live at a fixed cadence, so its
    queued->delivered dwell measures scheduling delay, not backlog
    depth (the tenancy leg's live-feed protocol). Phase 1 measures the
    contended p99; the breach (against ``rebalance_slo_p99_s``) drives
    a journaled decision and ``rebalance.migrate`` moves rank 2 to
    shard 1 mid-stream — the consumer follows the MOVED redirect, the
    handoff manifest carries the seq cursors — and phase 2 re-measures
    on the now-private shard. ``rebalance_p99_recovery_x`` is
    contended-over-recovered (> 1 is the contract);
    ``rebalance_stall_ms`` is the migrate() wall time (the seal
    window); ``rows_lost`` MUST be 0 with every row offset delivered
    exactly once, in order, across the live move.
    """
    import tempfile
    import threading

    import numpy as np
    import pyarrow as pa

    from ray_shuffling_data_loader_tpu import multiqueue as mq
    from ray_shuffling_data_loader_tpu import multiqueue_service as svc
    from ray_shuffling_data_loader_tpu import rebalance as rb
    from ray_shuffling_data_loader_tpu import tenancy as rt_tenancy
    from ray_shuffling_data_loader_tpu.plan import ir as plan_ir
    from ray_shuffling_data_loader_tpu.runtime import latency as rt_lat
    from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics

    trainers, hot_rank, slo_rank = 4, 0, 2
    rows_per_frame = 512
    slo_frames = int(os.environ.get("RSDL_BENCH_REBALANCE_FRAMES", 60))
    warmup_frames = 8
    feed_dt = 0.004
    # The breach threshold: comfortably above an idle shard's dwell
    # (sub-millisecond on loopback), comfortably below a saturated one.
    slo_s = float(os.environ.get("RSDL_BENCH_REBALANCE_SLO_MS", 2.0)) / 1e3
    series = "rsdl_tenant_delivery_latency_seconds_centroid"
    sat_ctx = rt_tenancy.TenantContext("sat", priority="batch")
    slo_ctx = rt_tenancy.TenantContext("slo", priority="interactive",
                                       slo_p99_ms=slo_s * 1e3)

    q_hot = plan_ir.queue_index(0, hot_rank, trainers)
    q_slo = plan_ir.queue_index(0, slo_rank, trainers)
    frame = pa.table({"key": pa.array(range(rows_per_frame),
                                      type=pa.int64())})
    # The saturator's frames are large and incompressible: each one
    # costs the serving shard's (per-server, single-threaded) codec
    # pool tens of milliseconds of zlib, so shard 0's pool stays
    # backlogged and the co-located SLO tenant's small frames queue
    # behind the saturator's jobs — contention that is genuinely
    # SHARD-LOCAL (the sibling shard's pool is idle), which is exactly
    # what the migration escapes. zlib releases the GIL on large
    # buffers, so this load does not blur the measurement with
    # interpreter noise the way a pure-Python spin loop would.
    sat_rows_per_frame = 1 << 16
    sat_frame = pa.table({"key": pa.array(
        np.random.default_rng(seed).integers(
            0, 1 << 62, size=sat_rows_per_frame, dtype=np.int64))})

    def _snapshot() -> dict:
        return dict(rt_metrics.parse_exposition(
            rt_metrics.render()).get(series, {}))

    def _slo_p99(now: dict, base: dict):
        counts: dict = {}
        for labels, value in now.items():
            delta = value - base.get(labels, 0.0)
            d = dict(labels)
            if (delta <= 0 or d.get("tenant") != "slo"
                    or d.get("hop") != rt_lat.HOP_QUEUED_TO_DELIVERED
                    or "c" not in d):
                continue
            centroid = float(d["c"])
            counts[centroid] = counts.get(centroid, 0.0) + delta
        total = int(sum(counts.values()))
        if not total:
            return None
        return rt_metrics._centroid_quantile(counts, total, 0.99)

    queue = mq.MultiQueue(trainers)
    stop = threading.Event()
    errors: list = []
    sat_rows = [0]

    def _feed_hot() -> None:
        # Depth-capped: the drain rate is codec-pool-bound (each frame
        # is a ~30ms compress), so an unpaced feeder would grow the
        # backlog without bound. A modest cap keeps the pool saturated
        # without hoarding memory — and keeps this thread asleep most
        # of the time, off the interpreter lock.
        try:
            while not stop.is_set():
                if queue.sizes([q_hot])[0] < 16:
                    queue.put(q_hot, sat_frame)
                else:
                    time.sleep(0.005)
        except BaseException as e:  # noqa: BLE001 - re-raised by caller
            errors.append(e)
        finally:
            queue.put(q_hot, None)

    def _drain_hot(remote) -> None:
        try:
            while True:
                item = remote.get(q_hot)
                if item is None:
                    break
                sat_rows[0] += item.num_rows
        except BaseException as e:  # noqa: BLE001 - re-raised by caller
            errors.append(e)

    def _slo_phase(remote, offsets: list) -> "float | None":
        """Feed warmup + ``slo_frames`` frames live, drain them as they
        land, and return the p99 of the measured span from the tenant
        sketch. The warmup frames are delivered (and position-checked)
        but excluded from the p99: they absorb one-time costs — the
        first dial, and after a migration the MOVED redirect — so the
        phase measures steady-state scheduling delay on its shard."""
        before = None
        fed = threading.Event()
        total = warmup_frames + slo_frames

        def _feed_slo() -> None:
            try:
                for _ in range(total):
                    time.sleep(feed_dt)
                    queue.put(q_slo, frame)
            except BaseException as e:  # noqa: BLE001 - re-raised
                errors.append(e)
            finally:
                fed.set()

        feeder = threading.Thread(target=_feed_slo, daemon=True,
                                  name="bench-rebalance-slo-feeder")
        feeder.start()
        drained = 0
        while drained < total:
            item, row_offset = remote.get_positioned(q_slo)
            if item is None:
                break
            offsets.append(row_offset)
            drained += 1
            if drained == warmup_frames:
                before = _snapshot()
        feeder.join(timeout=120)
        if not fed.is_set() or before is None:
            return None
        return _slo_p99(_snapshot(), before)

    with tempfile.TemporaryDirectory(prefix="rsdl_rebalance_") as tmpdir:
        journal_path = os.path.join(tmpdir, "rebalance.journal")
        # Frame compression ON and delivery pinned to streamed for this
        # leg (zlib is stdlib, always present): the per-server codec
        # pool is the shard-local resource the saturator exhausts, and
        # shm-handle delivery would bypass it entirely on loopback.
        # Scoped to server construction — policy is read in __init__.
        comp_env = {"RSDL_QUEUE_COMPRESSION": "zlib",
                    "RSDL_QUEUE_CODEC_THREADS": "1",
                    "RSDL_QUEUE_DELIVERY": "stream"}
        saved_env = {k: os.environ.get(k) for k in comp_env}
        os.environ.update(comp_env)
        try:
            sss_cm = svc.ShardedQueueServer(queue, 2,
                                            num_trainers=trainers)
        finally:
            for k, v in saved_env.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        with sss_cm as sss:
            controller = rb.RebalanceController(
                sss.shard_map, journal_path=journal_path,
                rebalance_slo_p99_s=slo_s)
            sat_remote = svc.RemoteQueue(sss.servers[0].address,
                                         num_trainers=trainers,
                                         max_batch=4, tenant=sat_ctx)
            slo_remote = svc.ShardedRemoteQueue(sss.shard_map,
                                                max_batch=4,
                                                tenant=slo_ctx)
            offsets: list = []
            threads = [threading.Thread(target=_feed_hot, daemon=True,
                                        name="bench-rebalance-hot-feeder"),
                       threading.Thread(target=_drain_hot,
                                        args=(sat_remote,), daemon=True,
                                        name="bench-rebalance-hot-drain")]
            try:
                for t in threads:
                    t.start()
                t0 = timeit.default_timer()
                p99_contended = _slo_phase(slo_remote, offsets)
                # The breach drives the journaled decision: the measured
                # p99 over the threshold is exactly what the
                # tenant_delivery_slo detector judges in a live server.
                breached = (p99_contended is not None
                            and p99_contended > slo_s)
                move_t0 = timeit.default_timer()
                state = rb.migrate(
                    controller, slo_rank,
                    target=controller.pick_target(slo_rank),
                    reason=f"slo p99 {p99_contended or -1:.4f}s over "
                           f"{slo_s:.4f}s")
                stall_ms = (timeit.default_timer() - move_t0) * 1e3
                p99_recovered = _slo_phase(slo_remote, offsets)
            finally:
                stop.set()
                for t in threads:
                    t.join(timeout=120)
                sat_remote.close()
                slo_remote.close()
                controller.close()
            elapsed = timeit.default_timer() - t0
        queue.shutdown()
        # The decision journal must re-derive the committed placement
        # byte-identically (the crash-recovery contract, checked live).
        # Replayed here, while the tmpdir still holds the journal.
        replay_ok = (state is not None
                     and rb.replay(journal_path) == state)
    if errors:
        raise errors[0]

    # Exactly-once across the live move: every offset delivered, in
    # order, no gap and no duplicate — offsets are cumulative row
    # counts, so the contiguity check IS the loss/dup check.
    expected = [i * rows_per_frame
                for i in range(2 * (warmup_frames + slo_frames))]
    delivered_rows = len(offsets) * rows_per_frame
    rows_lost = abs(len(expected) - len(offsets)) * rows_per_frame
    exactly_once = offsets == expected
    recovery_x = (round(p99_contended / p99_recovered, 3)
                  if p99_contended and p99_recovered else None)
    result = {
        "rebalance_moves": int(controller.moves_total),
        "rebalance_stall_ms": round(stall_ms, 3),
        "rows_lost": int(rows_lost if exactly_once else
                         max(rows_lost, rows_per_frame)),
        "rebalance_slo_ms": round(slo_s * 1e3, 3),
        "rebalance_slo_rows_per_sec": round(delivered_rows
                                            / max(elapsed, 1e-9), 1),
        "rebalance_sat_rows": int(sat_rows[0]),
        "rebalance_ok": bool(exactly_once and breached and replay_ok
                             and state is not None
                             and controller.moves_total == 1
                             and recovery_x is not None
                             and recovery_x > 1.0),
    }
    if p99_contended is not None:
        result["rebalance_p99_ms_contended"] = round(p99_contended * 1e3,
                                                     3)
    if p99_recovered is not None:
        result["rebalance_p99_ms_recovered"] = round(p99_recovered * 1e3,
                                                     3)
    if recovery_x is not None:
        result["rebalance_p99_recovery_x"] = recovery_x
    return result


def main() -> None:
    if os.environ.get("RSDL_BENCH_CPU"):
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=1")
        import jax
        jax.config.update("jax_platforms", "cpu")
    else:
        import jax
    from ray_shuffling_data_loader_tpu.utils.compile_cache import (
        enable_compile_cache)
    enable_compile_cache()
    device = jax.devices()[0]
    if device.platform == "cpu" and not os.environ.get("RSDL_BENCH_CPU"):
        raise SystemExit(
            "bench.py: JAX found no accelerator (platform 'cpu'); set "
            "RSDL_BENCH_CPU=1 to run on the CPU deliberately")
    print(f"# bench device: {device}", file=sys.stderr)

    from ray_shuffling_data_loader_tpu import data_generation as datagen
    from ray_shuffling_data_loader_tpu.utils.config import default_num_reducers

    num_rows = int(os.environ.get("RSDL_BENCH_ROWS", 2_000_000))
    num_files = int(os.environ.get("RSDL_BENCH_FILES", 8))
    # 8 timed epochs (every one in the window; compiles are paid by a
    # separate warm-up dataset — see run_ingest's protocol docstring).
    num_epochs = int(os.environ.get("RSDL_BENCH_EPOCHS", 8))
    # 131072-row batches measured fastest under the END-TO-END protocol
    # (round 4 sweep: 131k -> 4.6M rows/s, 262k -> 4.3M, 524k -> 3.8M on
    # the 1-core bench host). Larger batches only "won" under the old
    # excluded-warm-up window, by letting the prefetch queue pre-produce
    # into the untimed epoch — an artifact, not throughput.
    batch_size = int(os.environ.get("RSDL_BENCH_BATCH", 131_072))
    data_dir = os.environ.get("RSDL_BENCH_DATA", "/tmp/rsdl_bench_data")

    marker = os.path.join(data_dir, f".rows_{num_rows}_files_{num_files}")
    if not os.path.exists(marker):
        import shutil
        if os.path.isdir(data_dir):
            shutil.rmtree(data_dir)
        filenames, _ = datagen.generate_data(
            num_rows, num_files, num_row_groups_per_file=4,
            max_row_group_skew=0.0, data_dir=data_dir, seed=0)
        with open(marker, "w") as f:
            f.write("\n".join(filenames))
    with open(marker) as f:
        filenames = f.read().splitlines()

    # At least 4 reducers (even on small hosts, finer reducer granularity
    # pipelines read/partition/permute against consumption) — but not so
    # many that reducer outputs shrink below ~2 batches: device re-batching
    # moves batch-aligned spans of whole reducer outputs in bulk, and
    # gather threads (not reducer count) now carry many-core parallelism.
    # The cap wins over the floor of 4: a smoke config whose rows fit in a
    # couple of batches gets fewer reducers rather than sub-batch outputs
    # that would silently disable the bulk path being measured.
    # Multi-trainer ingest (reference-scale evidence): one shuffle routing
    # to N per-rank streams, each drained by its own consumer thread.
    num_trainers = max(1, int(os.environ.get("RSDL_BENCH_TRAINERS", 1)))
    # Byte budget + spill tier, so the scale runs exercise the reference's
    # bounded-memory operating point (cluster.yaml object-store sizing).
    max_inflight_bytes = (int(os.environ["RSDL_BENCH_INFLIGHT_BYTES"])
                          if os.environ.get("RSDL_BENCH_INFLIGHT_BYTES")
                          else None)
    spill_dir = os.environ.get("RSDL_BENCH_SPILL_DIR") or None

    reducer_cap = max(1, num_rows // (2 * batch_size))
    num_reducers = int(os.environ.get(
        "RSDL_BENCH_REDUCERS",
        min(max(4, default_num_reducers(num_trainers=num_trainers)),
            reducer_cap)))

    # Deeper prefetch keeps more host->device transfers in flight.
    prefetch_size = int(os.environ.get("RSDL_BENCH_PREFETCH", 4))

    # RSDL_BENCH_DEVICE_REBATCH=0 forces the per-batch host path for
    # apples-to-apples comparisons of the bulk-chunk transfer design.
    # Unset, the choice defers to the LIBRARY degradation policy
    # (runtime/policy.py): RSDL_DEVICE_REBATCH=0 — the promoted form of
    # this old bench-only mitigation — now turns the per-batch path on
    # for the library and the bench together.
    from ray_shuffling_data_loader_tpu.runtime import policy as rt_policy
    rebatch_env = os.environ.get("RSDL_BENCH_DEVICE_REBATCH", "").strip()
    device_rebatch = rt_policy.resolve("bench", "device_rebatch") \
        if rebatch_env == "" \
        else rebatch_env not in ("0", "false", "False")

    # Optional per-batch train-step emulation in the ingest phases (the
    # train phase uses the real model instead).
    step_ms = float(os.environ.get("RSDL_BENCH_STEP_MS", 0))

    phases = [p.strip() for p in os.environ.get(
        "RSDL_BENCH_PHASES",
        "cached,cold,train,scaling,serve,latency,remote,stream,tenancy,"
        "elastic,rebalance"
        ).split(",")
        if p.strip()]
    if os.environ.get("RSDL_BENCH_COLD"):
        # Legacy knob: the cold regime IS the headline; skip cached.
        phases = [p for p in phases if p != "cached"]
        if "cold" not in phases:
            phases.insert(0, "cold")

    from ray_shuffling_data_loader_tpu import executor as rsdl_ex
    from ray_shuffling_data_loader_tpu import stats as rsdl_stats
    from ray_shuffling_data_loader_tpu.runtime import health as rt_health
    from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics
    from ray_shuffling_data_loader_tpu.runtime import profiler as rt_profiler
    from ray_shuffling_data_loader_tpu.runtime import telemetry as rt_tel
    from ray_shuffling_data_loader_tpu.runtime import trace as rt_trace
    from ray_shuffling_data_loader_tpu.utils.tracing import maybe_profile

    # Telemetry spine: the whole invocation is flight-recorded (SIGUSR1
    # dumps the ring + named-thread stacks at any point; SIGUSR2 captures
    # a full incident capsule on demand), and the exposition exporter
    # comes up when RSDL_METRICS_FILE / RSDL_METRICS_PORT are set so
    # `tools/rsdl_top.py` can watch live.
    rt_tel.install_signal_dump()
    rt_health.install_incident_signal()
    rt_metrics.maybe_start_shard_writer()
    # Per-round flight capsule (runtime/regress.py), captured after the
    # last phase. No trace dir is pinned here: arming RSDL_TRACE_DIR for
    # the whole run makes every pool worker write an exit dump and
    # routes mid-run incident dumps to disk — measurable perturbation of
    # the serve/remote legs on the 1-core host. The capture itself pins
    # a scratch dir only for the duration of the dump (see
    # _capture_round_capsule). RSDL_BENCH_CAPSULE=0 skips capture,
    # restoring the pre-capsule bench byte for byte.
    bench_capsule = rt_policy.resolve("bench", "bench_capsule")
    if (rt_policy.resolve("metrics", "metrics_file")
            or rt_policy.resolve("metrics", "metrics_port")):
        rt_metrics.start_exporter()
    telemetry_per_event_s = rt_tel.measure_record_overhead()
    events_before = rt_tel.recorder().total_recorded

    # Watchdog/stall totals are monotonic process counters; the JSON
    # reports this invocation's delta.
    wd_before = rsdl_stats.watchdog_stats().snapshot()
    chaos_rate = _install_chaos(_chaos_rate_from_invocation())
    fs_before = rsdl_stats.fault_stats().snapshot()
    recovery_before = rsdl_stats.process_recovery_totals()

    cached = cold = train = train_agg = scaling = serve = latency = None
    remote = stream = tenancy = elastic = rebalance = None

    failed_phases = []

    def _phase(name, fn):
        """Run one phase. A phase that raises is reported with its
        traceback and omitted from the JSON, so the phases that did run
        still print their partial record — and then the run exits
        non-zero (see the end of main)."""
        try:
            return fn()
        except Exception as e:  # noqa: BLE001 - reported, then fatal
            import traceback
            traceback.print_exc()
            print(f"# {name} phase FAILED: {type(e).__name__}: {e}",
                  file=sys.stderr)
            failed_phases.append(name)
            return None

    def _ingest(qname, *, cold, epochs):
        if num_trainers > 1:
            return run_ingest_multi(
                jax, filenames, num_epochs=epochs, batch_size=batch_size,
                num_reducers=num_reducers, prefetch_size=prefetch_size,
                cold=cold, device_rebatch=device_rebatch, step_ms=step_ms,
                qname=qname, num_trainers=num_trainers,
                max_inflight_bytes=max_inflight_bytes, spill_dir=spill_dir)
        # Same budget/spill plumbing as the multi-trainer path: the JSON
        # record claims these knobs were engaged whenever they are set,
        # so the single-trainer phases must actually apply them too.
        return run_ingest(
            jax, filenames, num_epochs=epochs, batch_size=batch_size,
            num_reducers=num_reducers, prefetch_size=prefetch_size,
            cold=cold, device_rebatch=device_rebatch, step_ms=step_ms,
            qname=qname, max_inflight_bytes=max_inflight_bytes,
            spill_dir=spill_dir)

    # Ops plane (runtime/health.py): SLO detectors armed fresh per timed
    # phase — the phases have deliberately different rate regimes, so a
    # droop baseline must never span a phase boundary. The ingest phases
    # run a near-zero-work consumer whose stall is ~100% of wall BY
    # CONSTRUCTION, so stall_breach arms only under train (the phase the
    # <=10% contract governs). A detector fire auto-captures an incident
    # capsule, lands in the record's `health` section, and — with
    # --baseline — fails the invocation like any other regression.
    health_by_phase = {}

    # delivery_latency_breach / freshness_stall are deliberately NOT
    # armed here: the ingest/train phases pre-produce whole epochs
    # (max_concurrent_epochs=2), so tables dwell in the queue for tens
    # of seconds BY DESIGN — birth->delivered there measures buffer
    # depth, not serving health. The delivery SLOs are judged where
    # they mean something (the serving plane); the bench's latency leg
    # reports the p99s and --baseline gates them.
    def _armed_phase(name, fn, with_stall=False):
        detectors = [d for d in ("throughput_droop", "stall_breach",
                                 "ledger_creep", "queue_saturation",
                                 "lease_churn", "straggler_drift")
                     if with_stall or d != "stall_breach"]
        rt_health.arm(component="bench", detectors=detectors)
        try:
            return _phase(name, fn)
        finally:
            finished = rt_health.disarm()
            if finished is not None:
                finished.wait_captures(timeout_s=15.0)
                health_by_phase[name] = finished.summary()
                if finished.total_fires:
                    print(f"# health: {finished.total_fires} detector "
                          f"fire(s) during {name} "
                          f"({sorted(d for d, s in finished.summary()['detectors'].items() if s['fires'])})",
                          file=sys.stderr)

    # Host-side sampling profiler next to the JAX device profiler: one
    # window, two views (RSDL_PROFILER=1 / RSDL_PROFILE_FOLDED=<path>).
    with maybe_profile(), rt_profiler.maybe_sample() as sampling_prof:
        if "cached" in phases:
            cached = _armed_phase("cached", lambda: _ingest(
                "bench-cached", cold=False, epochs=num_epochs))
            if cached is not None:
                print(f"# cached: {cached['rows_per_s']:,.0f} rows/s, stall "
                      f"{cached['stall_pct']:.2f}% over {cached['batches']} "
                      "batches", file=sys.stderr)
        if "cold" in phases:
            # 6 epochs: enough steady-state mmap epochs that the one-time
            # in-window decode+IPC-write doesn't dominate the average.
            cold_epochs = int(os.environ.get("RSDL_BENCH_COLD_EPOCHS",
                                             min(6, num_epochs)))
            cold = _armed_phase("cold", lambda: _ingest(
                "bench-cold", cold=True, epochs=cold_epochs))
            if cold is not None:
                print(f"# cold: {cold['rows_per_s']:,.0f} rows/s, stall "
                      f"{cold['stall_pct']:.2f}% over {cold['batches']} "
                      "batches", file=sys.stderr)
        if "scaling" in phases:
            scaling = _phase("worker-scaling", lambda: _run_worker_scaling(
                filenames, num_reducers=num_reducers))
            if scaling is not None:
                print("# worker scaling: "
                      + ", ".join(f"{w}w -> {r:,.0f} rows/s" for w, r in
                                  scaling["rows_per_s_by_workers"].items())
                      + (f" (efficiency "
                         f"{scaling['parallel_efficiency']:.2f})"
                         if "parallel_efficiency" in scaling else ""),
                      file=sys.stderr)
        if "serve" in phases:
            serve = _phase("serve", lambda: _run_serve_leg(filenames))
            if serve is not None:
                print(f"# serve: {serve['serve_rows_per_sec']:,.0f} rows/s "
                      f"aggregate over {serve['serve_trainer_streams']} "
                      f"remote streams on {serve['serve_shards']} shards "
                      f"({serve['serve_speedup_vs_single_shard']}x of 1 "
                      f"shard); handle delivery cut wire bytes "
                      f"{serve['serve_handle_wire_reduction_x']}x",
                      file=sys.stderr)
        if "remote" in phases:
            remote = _phase("remote", lambda: _run_remote_leg(
                int(os.environ.get("RSDL_BENCH_SEED", "0"))))
            if remote is not None:
                print(f"# remote: "
                      f"{remote['remote_rows_per_sec']:,.0f} rows/s "
                      f"prefetch-on vs "
                      f"{remote['remote_prefetch_off_rows_per_sec']:,.0f} "
                      f"off ({remote['remote_prefetch_speedup_x']}x); "
                      f"hot hit {remote['remote_cache_hit_rate_hot']:.0%} "
                      f"disk hit {remote['remote_cache_hit_rate_disk']:.0%}"
                      f"; prefetch efficiency "
                      f"{remote['remote_prefetch_efficiency']:.0%}; "
                      f"bit_identical="
                      f"{remote['remote_output_bit_identical']}",
                      file=sys.stderr)
        if "latency" in phases:
            latency = _phase("latency", lambda: _run_latency_leg(filenames))
            if latency is not None:
                print(f"# latency: delivery p99 "
                      f"{latency['delivery_p99_ms']}ms (handle) / "
                      f"{latency['delivery_p99_ms_stream']}ms (stream) "
                      f"over {latency['delivery_frames']} frames on "
                      f"{latency['latency_shards']} shards; freshness "
                      f"p99 {latency.get('freshness_p99_ms', 'n/a')}ms",
                      file=sys.stderr)
        if "stream" in phases:
            stream = _phase("stream", lambda: _run_stream_leg(
                int(os.environ.get("RSDL_BENCH_SEED", "0"))))
            if stream is not None:
                print(f"# stream: "
                      f"{stream['stream_rows_per_sec']:,.0f} rows/s "
                      f"end-to-end over {stream['stream_windows']} "
                      f"windows ({stream['stream_events']} events); "
                      f"watermark lag p99 "
                      f"{stream['watermark_lag_p99_s']}s; window close "
                      f"{stream['window_close_ms']}ms; late "
                      f"{stream['late_events']}; freshness p99 "
                      f"{stream.get('stream_freshness_p99_ms', 'n/a')}ms",
                      file=sys.stderr)
        if "tenancy" in phases:
            tenancy = _phase("tenancy", lambda: _run_tenancy_leg(
                filenames, int(os.environ.get("RSDL_BENCH_SEED", "0"))))
            if tenancy is not None:
                print(f"# tenancy: fairness "
                      f"{tenancy['tenancy_fairness_ratio']}x at "
                      f"{tenancy['tenancy_weight_ratio']}x weights; hot "
                      f"{tenancy['tenancy_hot_rows_per_sec']:,.0f} rows/s "
                      f"vs cold "
                      f"{tenancy['tenancy_cold_rows_per_sec']:,.0f}; hot "
                      f"p99 "
                      f"{tenancy.get('tenancy_hot_p99_ms_contended', 'n/a')}"
                      f"ms contended vs "
                      f"{tenancy.get('tenancy_hot_p99_ms_solo', 'n/a')}ms "
                      f"solo "
                      f"({tenancy.get('tenancy_latency_ratio_x', 'n/a')}x)"
                      f"; admitted {tenancy['tenancy_admitted']} rejected "
                      f"{tenancy['tenancy_rejected']}; "
                      f"ok={tenancy['tenancy_ok']}", file=sys.stderr)
        if "elastic" in phases:
            elastic = _phase("elastic", lambda: _run_elastic_leg(
                int(os.environ.get("RSDL_BENCH_SEED", "0"))))
            if elastic is not None:
                print(f"# elastic: down detected in "
                      f"{elastic.get('member_down_detect_ms', 'n/a')}ms; "
                      f"resize stall {elastic['resize_stall_ms']}ms "
                      f"({elastic['elastic_recomputed']} reducers "
                      f"recomputed on survivors); world "
                      f"4 -> {elastic['elastic_shrunk_to']} -> "
                      f"{elastic['elastic_grew_to']}; rows lost "
                      f"{elastic['rows_lost']}; "
                      f"ok={elastic['elastic_ok']}", file=sys.stderr)
        if "rebalance" in phases:
            rebalance = _phase("rebalance", lambda: _run_rebalance_leg(
                int(os.environ.get("RSDL_BENCH_SEED", "0"))))
            if rebalance is not None:
                print(f"# rebalance: slo p99 "
                      f"{rebalance.get('rebalance_p99_ms_contended', 'n/a')}"
                      f"ms contended -> "
                      f"{rebalance.get('rebalance_p99_ms_recovered', 'n/a')}"
                      f"ms after the live move "
                      f"({rebalance.get('rebalance_p99_recovery_x', 'n/a')}x"
                      f" recovery); {rebalance['rebalance_moves']} move(s),"
                      f" stall {rebalance['rebalance_stall_ms']}ms; rows "
                      f"lost {rebalance['rows_lost']}; "
                      f"ok={rebalance['rebalance_ok']}", file=sys.stderr)
        if "train" in phases:
            train_epochs = int(os.environ.get("RSDL_BENCH_TRAIN_EPOCHS", 4))
            train_batch = int(os.environ.get("RSDL_BENCH_TRAIN_BATCH",
                                             131_072))
            model_size = os.environ.get(
                "RSDL_BENCH_TRAIN_MODEL",
                "tiny" if os.environ.get("RSDL_BENCH_CPU") else "mlperf")
            train_mb = int(os.environ.get("RSDL_BENCH_TRAIN_MICROBATCH",
                                          2048))
            # Median-of-N for the CONTRACT phase (default 3 on real
            # accelerators; 1 on the CPU smoke path, where wall-clock per
            # run dominates CI budgets and there is no shared-host chip).
            n_runs = max(1, int(os.environ.get(
                "RSDL_BENCH_RUNS",
                "1" if os.environ.get("RSDL_BENCH_CPU") else "3")))
            train_runs = []

            def _run_train_phase():
                for run_i in range(n_runs):
                    r = _phase(f"train[{run_i}]",
                               lambda run_i=run_i: run_train(
                                   jax, filenames, num_epochs=train_epochs,
                                   batch_size=train_batch,
                                   num_reducers=num_reducers,
                                   prefetch_size=prefetch_size,
                                   device_rebatch=device_rebatch,
                                   model_size=model_size,
                                   microbatch=train_mb,
                                   qname=f"bench-train-r{run_i}"))
                    if r is not None:
                        train_runs.append(r)
                return train_runs or None

            # One armed window across the median-of-N runs: the stall
            # contract phase judges stall_breach too.
            _armed_phase("train", _run_train_phase, with_stall=True)
            train_agg = None
            if train_runs:
                train_agg = _aggregate_train_runs(train_runs)
                train = train_runs[train_agg.pop("median_run_index")]
            if train is not None:
                loss_txt = (f"{train['final_loss']:.4f}"
                            if train["final_loss"] is not None
                            else ("DIVERGED" if train.get("diverged")
                                  else "n/a"))
                congestion_txt = ""
                if train_agg is not None and train_agg["runs"] > 1:
                    congestion_txt = (
                        f" [median of {train_agg['runs']} runs"
                        + (f", {train_agg['congested_runs']} CONGESTED"
                           if train_agg["congested"] else "")
                        + "]")
                print(f"# train: {train['rows_per_s']:,.0f} rows/s over "
                      f"{train['batches']} real DLRM micro-steps "
                      f"({train['microbatch']} rows, "
                      f"{train['step_ms_mean']:.2f}ms each), stall "
                      f"{train['stall_pct']:.2f}% "
                      f"(contract: <=10%), loss={loss_txt}"
                      f"{congestion_txt}",
                      file=sys.stderr)

    # The pandas baseline is a LOADER rate; it only makes sense against an
    # ingest phase. A train-only run (contract metric alone) skips it — a
    # compute-gated-over-decode-bound ratio would mean nothing.
    baseline_files = filenames[:max(1, len(filenames) // 4)]
    baseline_rows_per_s = None
    if cached is not None or cold is not None:
        # Best of two runs: the first warms the page cache, and taking the
        # max is fairest to the reference on a noisy shared host. Failure
        # here must not destroy the already-measured phases: the ratio is
        # then omitted (vs_baseline: null), not the artifact.
        baseline_rows_per_s = _phase(
            "pandas-baseline",
            lambda: max(_pandas_reference_baseline(
                baseline_files, num_reducers=max(2, num_reducers // 4),
                batch_size=batch_size) for _ in range(2)))
        if baseline_rows_per_s is not None:
            print(f"# pandas reference algo: "
                  f"{baseline_rows_per_s:,.0f} rows/s", file=sys.stderr)

    if cached is not None:
        headline, metric = cached, "shuffle_ingest_rows_per_sec_per_chip"
    elif cold is not None:
        headline = cold
        metric = "shuffle_ingest_rows_per_sec_per_chip_cold"
    elif train is not None:
        # Train-only run: the headline is the train-gated rate (the train
        # phase runs with the cache ON, so the cold metric name would lie).
        headline, metric = train, "train_gated_rows_per_sec_per_chip"
    elif serve is not None:
        # Serve-only run (RSDL_BENCH_PHASES=serve): the headline is the
        # serving plane's aggregate remote-stream rate; the ingest-phase
        # stall/fill fields do not exist here and report as zero.
        headline = {"rows_per_s": serve["serve_rows_per_sec"],
                    "stall_pct": 0.0, "stall_s": 0.0,
                    "wait_mean_ms": 0.0, "timed_epochs": 1,
                    "duration_s": 0.0}
        metric = "serve_rows_per_sec_aggregate"
    elif latency is not None:
        # Latency-only run (RSDL_BENCH_PHASES=latency): the headline is
        # the end-to-end delivery p99 itself (note the unit: ms, and
        # LOWER is better — the bench-diff `value` rule judges the
        # metric-specific key `delivery_p99_ms` instead).
        headline = {"rows_per_s": latency["delivery_p99_ms"],
                    "stall_pct": 0.0, "stall_s": 0.0,
                    "wait_mean_ms": 0.0, "timed_epochs": 1,
                    "duration_s": 0.0}
        metric = "delivery_p99_ms"
    elif remote is not None:
        # Remote-only run (RSDL_BENCH_PHASES=remote): the headline is
        # the prefetch-on cold-ingest rate against the simulated object
        # store (the storage plane's tentpole number).
        headline = {"rows_per_s": remote["remote_rows_per_sec"],
                    "stall_pct": 0.0, "stall_s": 0.0,
                    "wait_mean_ms": 0.0, "timed_epochs":
                        remote["remote_epochs"],
                    "duration_s": 0.0}
        metric = "remote_cold_rows_per_sec"
    elif stream is not None:
        # Stream-only run (RSDL_BENCH_PHASES=stream): the headline is
        # the windowed end-to-end rate — assemble -> shuffle -> serve ->
        # device — over the synthetic stream (streaming/).
        headline = {"rows_per_s": stream["stream_rows_per_sec"],
                    "stall_pct": 0.0, "stall_s": 0.0,
                    "wait_mean_ms": 0.0,
                    "timed_epochs": stream["stream_windows"],
                    "duration_s": stream["stream_duration_s"]}
        metric = "stream_rows_per_sec"
    elif tenancy is not None:
        # Tenancy-only run (RSDL_BENCH_PHASES=tenancy): the headline is
        # the hot tenant's contended drain rate — the number the QoS
        # plane exists to protect under a cold co-tenant's pressure.
        headline = {"rows_per_s": tenancy["tenancy_hot_rows_per_sec"],
                    "stall_pct": 0.0, "stall_s": 0.0,
                    "wait_mean_ms": 0.0, "timed_epochs": 1,
                    "duration_s": 0.0}
        metric = "tenancy_hot_rows_per_sec"
    elif elastic is not None:
        # Elastic-only run (RSDL_BENCH_PHASES=elastic): the headline is
        # the delivered-row rate of the shrink+grow run — the rate the
        # elastic plane sustains while paying the resize tax.
        headline = {"rows_per_s": elastic["elastic_rows_per_sec"],
                    "stall_pct": 0.0, "stall_s": 0.0,
                    "wait_mean_ms": 0.0, "timed_epochs": 2,
                    "duration_s": 0.0}
        metric = "elastic_rows_per_sec"
    elif rebalance is not None:
        # Rebalance-only run (RSDL_BENCH_PHASES=rebalance): the headline
        # is the SLO tenant's delivered rate across the live move — the
        # stream the self-healing plane exists to keep whole.
        headline = {"rows_per_s": rebalance["rebalance_slo_rows_per_sec"],
                    "stall_pct": 0.0, "stall_s": 0.0,
                    "wait_mean_ms": 0.0, "timed_epochs": 1,
                    "duration_s": 0.0}
        metric = "rebalance_slo_rows_per_sec"
    else:
        print(f"no phase produced a result (selected: {phases!r}; a "
              "'# <name> phase FAILED' line above means the phase ran "
              "and died; otherwise the selection matched nothing)",
              file=sys.stderr)
        sys.exit(2)
    headline_cold = headline is cold
    # vs_baseline is the HONEST ratio: the cold pipeline (decode every
    # epoch) against the pandas reference algorithm, which also pays full
    # decode. The cached ratio is reported separately.
    if baseline_rows_per_s is None:
        vs_baseline = None
    elif cold is not None:
        vs_baseline = cold["rows_per_s"] / baseline_rows_per_s
    else:
        vs_baseline = headline["rows_per_s"] / baseline_rows_per_s

    record = {
        "metric": metric,
        "value": round(headline["rows_per_s"], 1),
        "unit": "ms" if metric == "delivery_p99_ms" else "rows/s",
        "vs_baseline": (round(vs_baseline, 3)
                        if vs_baseline is not None else None),
        # Headline-phase stall stats (near-zero consumer: stall% ~= 100%
        # is expected there; the contract number is the train phase's).
        "stall_pct": round(headline["stall_pct"], 3),
        # The ingest phases run a deliberately near-zero-work consumer, so
        # nearly all wall time is batch-wait BY CONSTRUCTION — stall_pct
        # there measures producer throughput, not a pipeline failure. The
        # <=10% contract applies only to stall_pct_under_train.
        "producer_bound": headline is not train,
        "stall_s": round(headline["stall_s"], 3),
        "batch_wait_mean_ms": round(headline["wait_mean_ms"], 3),
        "step_ms": step_ms,
        "cache_mode": "cold" if headline_cold else "cached",
        # Fairness note: the pandas baseline is a rate over a quarter of
        # the files (it is single-process and O(minutes) on the full set).
        "baseline_files_fraction": round(len(baseline_files) /
                                         len(filenames), 3),
        # Hardware context: the shuffle is host-CPU work, so rows/s scales
        # with cores; cross-round comparisons need this. (Round-1's 17.2M
        # was a many-core host; a 1-core host sustains ~4M.)
        "host_cpus": os.cpu_count(),
        "timed_epochs": headline["timed_epochs"],
        # Launch-to-first-delivery latency of the headline phase (outside
        # the timed window for cached/train, inside it for cold).
        "fill_s": round(headline.get("fill_s", 0.0), 3),
    }
    # Executor honesty (ISSUE 7 satellite): report the EFFECTIVE data
    # plane — backend, pool width, worker pids — and normalize per-core
    # by the pool width that actually ran, not os.cpu_count() (the old
    # field claimed full-host normalization even when the pool was 1
    # worker wide, or when the process pool ran fewer workers than
    # cores).
    pool_info = rsdl_ex.last_worker_pool()
    executor_workers = pool_info["workers"] or (os.cpu_count() or 1)
    record["executor_backend"] = pool_info["backend"] or "thread"
    record["executor_workers"] = executor_workers
    record["executor_worker_pids"] = pool_info["pids"]
    record["rows_per_s_per_core"] = round(
        headline["rows_per_s"] / max(1, executor_workers), 1)
    if scaling is not None:
        # Worker-count scaling leg (1 -> N): near-linear scaling must be
        # an artifact in the record, not a claim in prose.
        record["worker_scaling"] = scaling
    if latency is not None:
        # Delivery-latency leg (runtime/latency.py): flat keys so the
        # bench-diff gate reads delivery_p99_ms / freshness_p99_ms like
        # any other metric — the observability prerequisite ROADMAP
        # items 2 and 5 consume ("bounded p99 delivery latency").
        record.update(latency)
    if serve is not None:
        # Serving-plane leg (multiqueue_service v3): flat keys so the
        # bench-diff gate and the trial CSV read them like any other
        # metric — shard-scaling ratio, per-layer wire bytes
        # (handle vs stream on the SAME table flow), and the
        # compression ratio. A serve_rows_per_sec drop fails --baseline
        # like any other regression.
        record.update(serve)
    if remote is not None:
        # Storage-plane cold leg (storage/): flat keys so the bench-diff
        # gate reads remote_rows_per_sec / remote_prefetch_speedup_x
        # like any other metric — the prefetch-on-beats-off contract is
        # an artifact in the record, not a claim in prose.
        record.update(remote)
    if stream is not None:
        # Streaming leg (streaming/): flat keys so the bench-diff gate
        # reads stream_rows_per_sec / watermark_lag_p99_s /
        # window_close_ms like any other metric — the rules skip
        # cleanly against pre-streaming baselines that lack them.
        record.update(stream)
    if tenancy is not None:
        # Tenancy contention leg (tenancy/): flat keys so the bench-diff
        # gate reads tenancy_fairness_ratio / tenancy_latency_ratio_x
        # like any other metric — the weighted-fair split and the hot
        # tenant's contended-over-solo p99 are artifacts in the record,
        # not claims in prose.
        record.update(tenancy)
    if elastic is not None:
        # Elastic-membership leg (membership/): flat keys so the
        # bench-diff gate reads member_down_detect_ms / resize_stall_ms
        # / rows_lost / elastic_ok like any other metric — the rules
        # skip cleanly against pre-elastic baselines that lack them.
        record.update(elastic)
    if rebalance is not None:
        # Self-healing serving-plane leg (rebalance/): flat keys so the
        # bench-diff gate reads rebalance_p99_recovery_x /
        # rebalance_stall_ms / rebalance_ok like any other metric — the
        # rules skip cleanly against pre-rebalance baselines. rows_lost
        # is shared with the elastic leg under one ceiling-0 rule:
        # max-merge so neither leg can launder the other's loss.
        if "rows_lost" in record:
            rebalance = dict(rebalance, rows_lost=max(
                record["rows_lost"], rebalance["rows_lost"]))
        record.update(rebalance)
    # Runtime-health evidence (runtime/watchdog.py): deadline misses on
    # the supervised bulk transfer/carve path, escalations (a stall
    # persisting past further deadline multiples), and whether the
    # automatic per-batch fallback engaged during this invocation.
    wd_after = rsdl_stats.watchdog_stats().snapshot()
    record["watchdog_events"] = (wd_after["watchdog_events"]
                                 - wd_before["watchdog_events"])
    record["stall_escalations"] = (wd_after["stall_escalations"]
                                   - wd_before["stall_escalations"])
    record["fallback_engaged"] = (wd_after["fallbacks_engaged"]
                                  > wd_before["fallbacks_engaged"])
    # Fault/recovery evidence (runtime/faults.py + runtime/retry.py):
    # this invocation's delta of the process fault counters. Reported
    # whenever anything fired (an env chaos spec counts), always under
    # --chaos soak.
    fs_after = rsdl_stats.fault_stats().snapshot()
    fs_delta = {key: fs_after[key] - fs_before[key] for key in
                ("injected", "retries", "recomputes", "quarantines",
                 "exhausted")}
    if chaos_rate is not None or any(fs_delta.values()):
        record["faults_injected"] = fs_delta["injected"]
        record["fault_retries"] = fs_delta["retries"]
        record["fault_recomputes"] = fs_delta["recomputes"]
        record["fault_quarantines"] = fs_delta["quarantines"]
        record["fault_recoveries_exhausted"] = fs_delta["exhausted"]
    if chaos_rate is not None:
        record["chaos_rate"] = chaos_rate
    # Process-level crash soak (PR 5): under --chaos, a real kill -9 of
    # the queue-server subprocess plus wire chaos and a lease expiry —
    # the record carries the recovery evidence the acceptance gate reads.
    process_soak = None
    if chaos_rate is not None:
        process_soak = _phase("process-recovery-soak",
                              lambda: _run_process_recovery_soak(
                                  int(os.environ.get("RSDL_CHAOS_SEED",
                                                     "0"))))
        recovery_after = rsdl_stats.process_recovery_totals()
        record["replayed_frames"] = (
            recovery_after["queue_frames_replayed"]
            - recovery_before["queue_frames_replayed"])
        record["server_restarts"] = (
            recovery_after["queue_server_restarts"]
            - recovery_before["queue_server_restarts"])
        record["lease_expiries"] = (
            recovery_after["queue_lease_expiries"]
            - recovery_before["queue_lease_expiries"])
        record["process_soak_ok"] = bool(process_soak
                                         and process_soak.get("ok"))
        if process_soak:
            print(f"# process soak: stream bit-identical={process_soak['ok']}"
                  f" server_restarts={process_soak['server_restarts']}"
                  f" lease_drained={process_soak.get('lease_drained')}",
                  file=sys.stderr)
    # Speculation evidence (plan/scheduler.py): always report the plan
    # scheduler's process-wide race/steal totals; under --chaos, run the
    # injected-straggler leg and fold its p99 on-vs-off verdict in.
    from ray_shuffling_data_loader_tpu.plan import (
        scheduler as plan_scheduler)
    record["speculation"] = {
        "enabled": bool(rt_policy.resolve("plan", "plan_speculation")),
        "stealing": bool(rt_policy.resolve("plan", "plan_stealing")),
        **plan_scheduler.speculation_totals(),
    }
    speculation_leg = None
    if chaos_rate is not None:
        speculation_leg = _phase(
            "speculation-straggler-leg",
            lambda: _run_speculation_leg(
                int(os.environ.get("RSDL_CHAOS_SEED", "0")) + 17))
        if speculation_leg:
            record["speculation"]["straggler_leg"] = speculation_leg
            # The leg's races land in the process-wide totals; refresh
            # so the block's counters cover the whole invocation.
            record["speculation"].update(
                plan_scheduler.speculation_totals())
            print("# speculation leg: p99 "
                  f"{speculation_leg['p99_epoch_s_speculation_off']}s off"
                  f" -> {speculation_leg['p99_epoch_s_speculation_on']}s"
                  f" on ({speculation_leg['p99_improvement_pct']}%),"
                  f" won={speculation_leg['speculative_won']}"
                  f" bit_identical="
                  f"{speculation_leg['output_bit_identical']}",
                  file=sys.stderr)
    # Telemetry-spine evidence (runtime/telemetry.py): the bottleneck
    # verdict and per-stage latency decomposition are computed from
    # flight-recorder events — not from log scraping — plus the
    # recorder's own measured share of the timed window.
    verdict = rt_tel.attribution().run_summary() or {}
    record["bottleneck_stage"] = verdict.get("bottleneck_stage")
    record["telemetry_stall_pct"] = verdict.get("stall_pct")
    record["stage_latency_ms"] = {
        stage: {q: s[q] for q in ("p50_ms", "p95_ms", "p99_ms")}
        for stage, s in verdict.get("stages", {}).items()}
    events_delta = rt_tel.recorder().total_recorded - events_before
    timed_s = sum(p["duration_s"] for p in (cached, cold, train) if p)
    record["telemetry_events"] = events_delta
    record["telemetry_enabled"] = rt_tel.enabled()
    record["telemetry_overhead_pct"] = (
        round(100.0 * events_delta * telemetry_per_event_s / timed_s, 4)
        if timed_s else 0.0)
    # The RSDL_TELEMETRY=0 hard-off fast path, priced at THIS run's
    # event volume: the proof the off switch costs ~nothing (with
    # telemetry off, events_delta itself is ~0 and both fields pin to 0).
    record["telemetry_overhead_off_pct"] = (
        round(100.0 * events_delta * rt_tel.measure_disabled_overhead()
              / timed_s, 6) if timed_s else 0.0)
    # Causal critical-path attribution over the recorder's retained
    # events (runtime/trace.py): which stages/tasks the epochs actually
    # waited on, and what a 2x speedup of each would buy.
    record.update(rt_trace.bench_fields(rt_tel.recorder().events()))
    # Ops-plane evidence (runtime/health.py): per-phase detector
    # episodes. `fires` > 0 means an SLO detector saw a sustained breach
    # DURING a timed phase — with --baseline this fails the invocation
    # (below); the auto-captured capsules name the evidence either way.
    health_fires = sum(s.get("fires", 0) for s in health_by_phase.values())
    record["health"] = {
        "armed": bool(health_by_phase),
        "fires": health_fires,
        "by_phase": health_by_phase,
        "capsules": [c for s in health_by_phase.values()
                     for c in s.get("capsules", [])],
    }
    if sampling_prof is not None:
        record["profile"] = sampling_prof.summary()
    if chaos_rate is not None or any(fs_delta.values()):
        # Chaos <-> telemetry correlation: a fault event (kind = the
        # fault-site name) is JOINABLE when a non-fault telemetry event
        # shares its (kind, epoch, task) key.
        events = rt_tel.recorder().events()
        plain_keys = {(e["kind"], e.get("epoch"), e.get("task"))
                      for e in events if "fault" not in e}
        fault_events = [e for e in events if e.get("fault")]
        record["fault_events"] = len(fault_events)
        record["fault_events_joinable"] = sum(
            1 for e in fault_events
            if (e["kind"], e.get("epoch"), e.get("task")) in plain_keys)
    if cold is not None:
        # "disk": parquet decoded ONCE inside the timed window, later
        # epochs stream from mmap'd Arrow IPC scratch (fresh dir per
        # run — nothing pre-warmed). "none": re-decode every epoch. A
        # single-epoch run maps each file once, so resolve_file_cache
        # engages no tier — report what actually ran, not the env mode.
        record["cold_cache"] = (_cold_cache_mode() or "none"
                                if cold["timed_epochs"] > 1 else "none")
    if num_trainers > 1:
        record["num_trainers"] = num_trainers
        # Multi-trainer phases clock launch-to-done (see run_ingest_multi).
        record["clock"] = headline.get("clock", "first-delivery")
    if max_inflight_bytes:
        record["max_inflight_bytes"] = max_inflight_bytes
        record["spill"] = bool(spill_dir)
    if cached is not None:
        # Mirror the vs_baseline handling: a failed (fail-soft) baseline
        # phase leaves baseline_rows_per_s None — omit the ratio, never
        # destroy the already-measured phases with a TypeError.
        record["vs_baseline_cached"] = (
            round(cached["rows_per_s"] / baseline_rows_per_s, 3)
            if baseline_rows_per_s is not None else None)
    if cold is not None and not headline_cold:
        record.update({
            "cold_rows_per_sec": round(cold["rows_per_s"], 1),
            "cold_stall_pct": round(cold["stall_pct"], 3),
            "cold_producer_bound": True,
            "cold_timed_epochs": cold["timed_epochs"],
            "cold_fill_s": round(cold.get("fill_s", 0.0), 3),
        })
    if train is not None:
        record.update({
            # The BASELINE.md contract metric: <= 10% stall under a real
            # train step (>= 90% input-pipeline utilization).
            "stall_pct_under_train": round(train["stall_pct"], 3),
            "train_rows_per_sec": round(train["rows_per_s"], 1),
            "train_step_ms_mean": round(train["step_ms_mean"], 3),
            "train_batch_size": train["batch_size"],
            "train_microbatch": train["microbatch"],
            "train_steps": train["batches"],
            "train_stall_s": round(train["stall_s"], 3),
            "train_dev_util_pct": round(train["dev_util_pct"], 3),
            "train_mfu_pct": (round(train["mfu_pct"], 4)
                              if train["mfu_pct"] is not None else None),
            "train_mfu_basis": train.get("mfu_basis"),
            "train_mfu_null_reason": train.get("mfu_null_reason"),
            "train_compute_rows_per_sec": (
                round(train["compute_rows_per_s"], 1)
                if train.get("compute_rows_per_s") else None),
            "train_flops_per_row": train["flops_per_row"],
            "train_wait_mean_ms": round(train["wait_mean_ms"], 3),
            "train_fill_s": round(train.get("fill_s", 0.0), 3),
            "train_final_loss": (round(train["final_loss"], 5)
                                 if train["final_loss"] is not None
                                 else None),
            "train_diverged": bool(train.get("diverged", False)),
            "train_model": f"dlrm-{train['model_size']}",
        })
        if train_agg is not None:
            # Median-of-N contract fields + congestion marker: the
            # per-run train_* fields above already come from the MEDIAN
            # run; these expose the spread and flag noisy-host episodes.
            record.update(train_agg)

    # Measurement honesty: what code ran on what machine, so two
    # records can be judged comparable BEFORE their deltas are believed
    # (rsdl_regress / rsdl_bench_diff cross-check these).
    record["provenance"] = _bench_provenance()
    if bench_capsule:
        _capture_round_capsule(record)

    print(json.dumps(record))

    if failed_phases:
        print(f"# bench FAILED: phase(s) {failed_phases} raised; the "
              "record above is partial", file=sys.stderr)
        sys.exit(1)
    if record["fallback_engaged"]:
        print("# bench FAILED: a bulk device transfer stalled and the "
              "loader fell back to per-batch transfers "
              f"({record['watchdog_events']} watchdog event(s)); the "
              "record above describes the fallback, not the path",
              file=sys.stderr)
        sys.exit(1)
    if chaos_rate is not None:
        # The soak contract: injected faults are RECOVERED, not survived
        # by luck — every selected phase must still complete, and the
        # process-level soak's stream must come back bit-identical.
        missing = [name for name, result in
                   (("cached", cached), ("cold", cold), ("train", train))
                   if name in phases and result is None]
        if missing:
            print(f"# chaos soak FAILED: phase(s) {missing} did not "
                  f"complete under fault rate {chaos_rate}",
                  file=sys.stderr)
            sys.exit(1)
        if not (process_soak and process_soak.get("ok")):
            print("# chaos soak FAILED: process-recovery soak did not "
                  "recover a bit-identical stream", file=sys.stderr)
            sys.exit(1)
        if not (speculation_leg and speculation_leg.get("ok")):
            print("# chaos soak FAILED: speculation straggler leg did "
                  "not improve p99 with a bit-identical stream "
                  f"({speculation_leg})", file=sys.stderr)
            sys.exit(1)
        print(f"# chaos soak OK: {fs_delta['injected']} injected, "
              f"{fs_delta['recomputes']} recomputed, "
              f"{fs_delta['exhausted']} exhausted, "
              f"{record['server_restarts']} server restarts, "
              f"{record['replayed_frames']} frames replayed, "
              f"{record['lease_expiries']} lease expiries",
              file=sys.stderr)

    # Regression gate (--baseline <BENCH_rN.json>): compare THIS record
    # against the chosen committed baseline; a threshold breach fails
    # the invocation so an r03->r05-class throughput drop is loud.
    baseline_path = _baseline_from_invocation()
    if baseline_path:
        diff_mod = _load_bench_diff()
        findings = diff_mod.compare_records(
            diff_mod.load_record(baseline_path), record)
        for line in diff_mod.render_findings(findings):
            print(f"# bench-diff: {line}", file=sys.stderr)
        regressions = [f for f in findings if not f["ok"]]
        if regressions:
            print(f"# bench-diff FAILED vs {baseline_path}: "
                  f"{len(regressions)} metric(s) regressed",
                  file=sys.stderr)
            sys.exit(1)
        # The gate extends to live health: a sustained SLO breach during
        # a timed phase is a regression even when the aggregate numbers
        # survive (a droop the window averaged away, a leak still
        # climbing at exit). The capsules in record["health"] are the
        # forensic record of exactly what fired.
        if health_fires:
            print(f"# health gate FAILED vs {baseline_path}: "
                  f"{health_fires} detector fire(s) during timed phases "
                  f"(capsules: {record['health']['capsules']})",
                  file=sys.stderr)
            sys.exit(1)
        print(f"# bench-diff OK vs {baseline_path} (health: 0 fires)",
              file=sys.stderr)


if __name__ == "__main__":
    main()
