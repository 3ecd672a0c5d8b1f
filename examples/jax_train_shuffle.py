"""End-to-end distributed training example on the shuffling pipeline.

TPU-native replacement for the reference's Horovod example (reference:
examples/horovod/ray_torch_shuffle.py:1-336): the driver creates the batch
queue and kicks off the multi-epoch shuffle before training starts
(consumer-only trainers, reference: :316-322); the trainer is a
``jax.jit``'d step over a device mesh — DP gradient sync is an XLA psum
over ICI instead of Horovod NCCL allreduce (reference: :173-177) — and the
example records **batch wait times**, the north-star stall metric
(reference: :186-218).

Like the reference, the train step can be mocked with a fixed sleep
(``--mock-train-step-time``, reference: :91,199-200) to measure the input
pipeline alone, or run for real (DLRM on the DATA_SPEC schema).

Single host (drives all local devices):
    python examples/jax_train_shuffle.py --num-rows 200000 --num-files 8 \
        --num-epochs 3 --batch-size 8192

Multi-host (one process per TPU-VM host, launched on every host):
    RSDL_HOSTS="host0:18515,host1:18515" \
    python examples/jax_train_shuffle.py --distributed ...
    # rank/world come from jax.distributed. With RSDL_HOSTS set, hosts run
    # the GLOBAL distributed shuffle (cross-host reducer exchange over the
    # host network, parallel/distributed.py); otherwise each host shuffles
    # only its own contiguous shard of the file list. Either way the train
    # step is one jit program over the global mesh: every host assembles
    # the global batch from its local shard
    # (jax.make_array_from_process_local_data) and XLA psums gradients
    # over ICI/DCN. A per-step all-hosts continue-vote keeps collective
    # programs aligned when hosts' epochs have unequal batch counts.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
import timeit

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--num-rows", type=int, default=200_000)
    p.add_argument("--num-files", type=int, default=8)
    p.add_argument("--num-row-groups-per-file", type=int, default=2)
    p.add_argument("--num-epochs", type=int, default=3)
    p.add_argument("--batch-size", type=int, default=8192)
    p.add_argument("--num-reducers", type=int, default=None)
    p.add_argument("--max-concurrent-epochs", type=int, default=2)
    p.add_argument("--mock-train-step-time", type=float, default=None,
                   help="Replace the real train step with a sleep of this "
                        "many seconds (input-pipeline-only measurement)")
    p.add_argument("--data-dir", type=str, default="./example_data")
    p.add_argument("--use-old-data", action="store_true")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--learning-rate", type=float, default=1e-3)
    p.add_argument("--file-cache", choices=["auto", "none", "disk"],
                   default="auto",
                   help="decoded-table cache tier: auto (RAM), none "
                        "(re-decode every epoch), disk (decode once, "
                        "stream later epochs from mmap'd Arrow IPC — the "
                        "corpus-exceeds-RAM regime)")
    p.add_argument("--max-inflight-bytes", type=int, default=None,
                   help="transient pipeline memory budget (bytes); see "
                        "examples/memory_budget.md")
    p.add_argument("--spill-dir", type=str, default=None,
                   help="with --max-inflight-bytes: spill over-budget "
                        "reducer outputs to Arrow IPC files here")
    p.add_argument("--cpu", action="store_true",
                   help="Force the CPU backend (smoke runs)")
    p.add_argument("--tiny-model", action="store_true",
                   help="Cap embedding vocabularies and widths so the real "
                        "train step compiles quickly on small hosts")
    p.add_argument("--distributed", action="store_true",
                   help="Initialize jax.distributed (one process per host)")
    p.add_argument("--stats-dir", type=str, default=None,
                   help="Write a per-rank CSV of epoch stats (steps, "
                        "rows/s, loss, batch waits) here; local path or "
                        "any utils/fileio URI (gs://, s3://, memory://)")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    if args.cpu:
        os.environ.setdefault("XLA_FLAGS",
                              "--xla_force_host_platform_device_count=8")
        import jax
        jax.config.update("jax_platforms", "cpu")
    else:
        import jax
    from ray_shuffling_data_loader_tpu.utils.compile_cache import (
        enable_compile_cache)
    enable_compile_cache()
    if args.distributed:
        # On TPU pods initialize() self-configures from the metadata
        # service; elsewhere (the slice launcher's SSH/local fan-out) the
        # coordinates come from env vars it sets per host.
        if os.environ.get("JAX_NUM_PROCESSES"):
            jax.distributed.initialize(
                coordinator_address=os.environ["JAX_COORDINATOR_ADDRESS"],
                num_processes=int(os.environ["JAX_NUM_PROCESSES"]),
                process_id=int(os.environ["JAX_PROCESS_ID"]))
        else:
            jax.distributed.initialize()

    import numpy as np
    import optax

    from ray_shuffling_data_loader_tpu import data_generation as dg
    from ray_shuffling_data_loader_tpu.jax_dataset import JaxShufflingDataset
    from ray_shuffling_data_loader_tpu.models import dlrm
    from ray_shuffling_data_loader_tpu.parallel import mesh as mesh_mod
    from ray_shuffling_data_loader_tpu.parallel.trainer import SpmdTrainer

    rank, world = mesh_mod.local_data_shard_info()

    if args.use_old_data:
        import glob
        filenames = sorted(
            glob.glob(os.path.join(args.data_dir, "*.parquet.snappy")))
    else:
        # Every host generates the same seeded files locally — the no-shared-
        # filesystem pattern of the reference's dummy_data_generator
        # (reference: examples/dummy_data_generator.py:11-32), made exact by
        # the seeded generator.
        filenames, _ = dg.generate_data(
            args.num_rows, args.num_files, args.num_row_groups_per_file,
            0.0, args.data_dir, seed=args.seed)
    # Global ("data",) DP mesh over every chip of every host. In
    # distributed mode each host contributes its local shard of each global
    # batch; single-host this is just the local devices.
    mesh = mesh_mod.make_mesh()
    multi_host = world > 1
    if args.tiny_model:
        # Indices above the capped vocab are clipped by jnp.take's default
        # out-of-bounds mode — fine for a smoke run.
        cfg = dlrm.DLRMConfig(
            vocab_sizes=tuple(min(v, 1000)
                              for v in dlrm.DATA_SPEC_VOCAB_SIZES),
            embed_dim=8, top_hidden=(64, 32))
    else:
        cfg = dlrm.DLRMConfig()
    trainer = None
    if args.mock_train_step_time is None:
        params = dlrm.init(cfg, jax.random.key(args.seed))
        trainer = SpmdTrainer(
            mesh, lambda p, s, y: dlrm.loss_fn(cfg, p, None, s, y, mesh),
            params, optax.adam(args.learning_rate))

    sorted_files = sorted(filenames)
    dataset_kwargs = dict(
        num_epochs=args.num_epochs, num_trainers=1,
        batch_size=args.batch_size, rank=0,
        feature_columns=list(dg.FEATURE_COLUMNS),
        feature_types=[np.int32] * len(dg.FEATURE_COLUMNS),
        label_column=dg.LABEL_COLUMN,
        max_concurrent_epochs=args.max_concurrent_epochs, seed=args.seed,
        drop_last=True, queue_name=f"example-queue-{rank}",
        max_inflight_bytes=args.max_inflight_bytes,
        spill_dir=args.spill_dir,
        file_cache={"auto": "auto", "none": None,
                    "disk": "disk"}[args.file_cache])
    transport = None
    if multi_host and os.environ.get("RSDL_HOSTS"):
        # GLOBAL shuffle: rows from any host's files can reach any trainer
        # (the reference's cluster-wide semantics). RSDL_HOSTS lists every
        # host's shuffle endpoint, same order as jax.process_index.
        from ray_shuffling_data_loader_tpu.parallel.distributed import (
            create_distributed_batch_queue_and_shuffle)
        from ray_shuffling_data_loader_tpu.parallel.transport import TcpTransport
        addresses = []
        for spec in os.environ["RSDL_HOSTS"].split(","):
            host, _, port = spec.strip().rpartition(":")
            addresses.append((host, int(port)))
        assert len(addresses) == world, "RSDL_HOSTS entries != process count"
        transport = TcpTransport(rank, addresses)
        transport.start()
        transport.connect()
        # The ShardPlan (hence every send/recv tag) is a function of
        # num_reducers, so the value must be identical on every host.
        # default_num_reducers() depends on the *local* cpu count, which can
        # differ across hosts — derive the default from world only.
        num_reducers = args.num_reducers or 8 * world
        batch_queue, shuffle_result = (
            create_distributed_batch_queue_and_shuffle(
                sorted_files, args.num_epochs,
                num_reducers, transport,
                max_concurrent_epochs=args.max_concurrent_epochs,
                seed=args.seed, queue_name=dataset_kwargs["queue_name"],
                max_inflight_bytes=args.max_inflight_bytes,
                spill_dir=args.spill_dir,
                file_cache=dataset_kwargs["file_cache"]))
        ds = JaxShufflingDataset(
            sorted_files, batch_queue=batch_queue,
            shuffle_result=shuffle_result,
            # In multi-host mode batches stay host-local numpy; the global
            # device array is assembled below.
            device_put=False, **dataset_kwargs)
    else:
        # Per-host shuffle of a contiguous file shard (deterministic shard
        # routing — no cross-host exchange, weaker global mixing).
        local_files = [f for i, f in enumerate(sorted_files)
                       if i % world == rank]
        # A 1-device mesh needs no sharded transfers; passing mesh=None
        # lets the dataset use device re-batching (bulk chunk transfers +
        # on-device slicing). jit resolves the trivial sharding itself.
        dataset_mesh = (None if multi_host or len(mesh.devices.flat) == 1
                        else mesh)
        ds = JaxShufflingDataset(
            local_files, num_reducers=args.num_reducers,
            mesh=dataset_mesh,
            device_put=not multi_host, **dataset_kwargs)

    import jax.numpy as jnp
    if multi_host:
        from jax.experimental import multihost_utils
        from jax.sharding import NamedSharding, PartitionSpec as P

        def to_global(arr):
            return jax.make_array_from_process_local_data(
                NamedSharding(mesh, P("data", *([None] * (arr.ndim - 1)))),
                np.asarray(arr))

    epoch_rows = []
    run_wait_total, run_wait_count = 0.0, 0
    for epoch in range(args.num_epochs):
        ds.set_epoch(epoch)
        epoch_start = timeit.default_timer()
        steps, last_loss = 0, float("nan")
        it = iter(ds)
        while True:
            batch = next(it, None)
            if multi_host:
                # Continue-vote: all hosts step, or none do — keeps every
                # host issuing the same sequence of collective programs
                # even when per-host batch counts differ by one.
                votes = multihost_utils.process_allgather(
                    np.array([batch is not None]))
                if not votes.all():
                    break
            elif batch is None:
                break
            features, label = batch
            if args.mock_train_step_time is not None:
                time.sleep(args.mock_train_step_time)
            else:
                sparse = jnp.concatenate(features, axis=1)
                if multi_host:
                    sparse, label = to_global(sparse), to_global(label)
                last_loss = trainer.train_step(sparse, label)
            steps += 1
        if trainer is not None:
            trainer.block_until_ready()
            last_loss = float(last_loss)
        duration = timeit.default_timer() - epoch_start
        # Per-EPOCH waits: reset after each epoch so rows/prints aren't
        # cumulative; totals for the DONE line are kept by hand.
        waits = ds.batch_wait_stats.summary()
        run_wait_total += waits["total"]
        run_wait_count += waits["count"]
        ds.batch_wait_stats.reset()
        print(f"[rank {rank}] epoch {epoch}: {steps} steps in "
              f"{duration:.2f}s ({steps * args.batch_size / duration:,.0f} "
              f"rows/s), loss={last_loss:.4f}, "
              f"batch-wait mean={waits['mean'] * 1e3:.1f}ms "
              f"max={waits['max'] * 1e3:.1f}ms total={waits['total']:.2f}s")
        epoch_rows.append({
            "rank": rank, "epoch": epoch, "steps": steps,
            "duration_s": round(duration, 4),
            "rows_per_s": round(steps * args.batch_size / duration, 1),
            "loss": (round(last_loss, 6)
                     if isinstance(last_loss, float) else ""),
            "batch_wait_mean_ms": round(waits["mean"] * 1e3, 3),
            "batch_wait_max_ms": round(waits["max"] * 1e3, 3),
            "batch_wait_total_s": round(waits["total"], 4),
        })
    print(f"[rank {rank}] DONE: {run_wait_count} batches, "
          f"total stall {run_wait_total:.2f}s "
          f"(mean {run_wait_total / max(1, run_wait_count) * 1e3:.1f}"
          "ms/batch)")
    if args.stats_dir:
        # Per-host stats CSV — what the slice launcher
        # (examples/launch_slice.py) gathers after a run (the reference's
        # per-trial CSVs role, reference: ray_torch_shuffle.py:228-237).
        import csv

        from ray_shuffling_data_loader_tpu.utils import fileio
        fileio.makedirs(args.stats_dir)
        path = fileio.join(args.stats_dir, f"host_{rank}_epochs.csv")
        with fileio.open_text(path, "w") as f:
            writer = csv.DictWriter(f, fieldnames=list(epoch_rows[0])
                                    if epoch_rows else ["rank"])
            writer.writeheader()
            for row in epoch_rows:
                writer.writerow(row)
        print(f"[rank {rank}] stats written to {path}")
    # Release the persistent prefetch producer (no-op if it already exited
    # after the final epoch).
    ds.close()
    if transport is not None:
        transport.close()


if __name__ == "__main__":
    main()
