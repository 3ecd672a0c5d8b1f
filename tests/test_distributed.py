"""Multi-host distributed shuffle: transport, plan, equivalence, e2e.

The killer property (SURVEY.md §7 "determinism"): because map/reduce PRNG
streams are keyed by global file/reducer indices, the distributed shuffle
over N hosts produces bit-identical per-trainer batch streams to the
single-host shuffle — verified here — so scaling out never changes what the
model trains on.
"""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from ray_shuffling_data_loader_tpu import data_generation as dg
from ray_shuffling_data_loader_tpu.shuffle import shuffle as run_shuffle
from ray_shuffling_data_loader_tpu.parallel import distributed as dist
from ray_shuffling_data_loader_tpu.parallel import transport as tp


# ---------------------------------------------------------------------------
# transport


def test_transport_send_recv_roundtrip():
    world = tp.create_local_transports(2, recv_timeout_s=10.0)
    try:
        world[0].send(1, (0, 3, 5), b"hello")
        assert world[1].recv(0, (0, 3, 5)) == b"hello"
        # Out-of-order tags resolve independently.
        world[1].send(0, (1, 0, 0), b"b")
        world[1].send(0, (0, 0, 0), b"a")
        assert world[0].recv(1, (0, 0, 0)) == b"a"
        assert world[0].recv(1, (1, 0, 0)) == b"b"
    finally:
        for t in world:
            t.close()


def test_transport_self_send_and_large_payload():
    world = tp.create_local_transports(2, recv_timeout_s=10.0)
    try:
        world[0].send(0, (0, 0, 0), b"self")
        assert world[0].recv(0, (0, 0, 0)) == b"self"
        big = os.urandom(8 << 20)
        world[0].send(1, (9, 9, 9), big)
        assert world[1].recv(0, (9, 9, 9)) == big
    finally:
        for t in world:
            t.close()


def test_transport_recv_timeout():
    world = tp.create_local_transports(2, recv_timeout_s=10.0)
    try:
        with pytest.raises(tp.TransportTimeout):
            world[0].recv(1, (0, 0, 0), timeout_s=0.2)
    finally:
        for t in world:
            t.close()


def test_table_ipc_roundtrip():
    import pyarrow as pa
    table = pa.table({
        "a": np.arange(100),
        "b": np.random.default_rng(0).random(100)
    })
    out = dist.deserialize_table(dist.serialize_table(table))
    assert out.equals(table)
    empty = table.slice(0, 0)
    assert dist.deserialize_table(dist.serialize_table(empty)).equals(empty)


# ---------------------------------------------------------------------------
# shard plan


def test_shard_plan_alignment():
    plan = dist.ShardPlan(num_files=10, num_reducers=13, world=4,
                          trainers_per_host=2)
    assert plan.num_trainers == 8
    # Every reducer owned exactly once, by the host of its trainer group.
    seen = []
    for h in range(4):
        local = plan.local_reducers(h)
        for r in local:
            assert plan.reducer_host(r) == h
        seen.extend(local)
    assert sorted(seen) == list(range(13))
    # Files covered exactly once, contiguously.
    all_files = [f for h in range(4) for f in plan.local_files(h)]
    assert all_files == list(range(10))
    for f in range(10):
        assert f in plan.local_files(plan.file_host(f))
    # Trainer groups match the reference's array_split arithmetic.
    expected = [len(a) for a in np.array_split(np.arange(13), 8)]
    assert [len(g) for g in plan.trainer_reducers] == expected


# ---------------------------------------------------------------------------
# in-process worlds (threads as hosts)


def _run_world(filenames, num_epochs, num_reducers, world_size, seed,
               trainers_per_host=1, recv_timeout_s=60.0):
    """Drive world_size distributed shuffles in threads; returns
    per-global-trainer {epoch: [key, ...]} consumed through resolved refs."""
    transports = tp.create_local_transports(world_size,
                                            recv_timeout_s=recv_timeout_s)
    results = {}
    errors = []

    def host_main(host_id):
        collected = {}

        def consumer(local_rank, epoch, refs):
            if refs is not None:
                collected.setdefault((local_rank, epoch), []).extend(refs)

        try:
            dist.shuffle_distributed(
                filenames, consumer, num_epochs, num_reducers,
                transports[host_id], trainers_per_host=trainers_per_host,
                max_concurrent_epochs=2, seed=seed, num_workers=4)
            for (local_rank, epoch), refs in collected.items():
                trainer = host_id * trainers_per_host + local_rank
                keys = []
                for ref in refs:
                    keys.extend(ref.result().column("key").to_pylist())
                results.setdefault(trainer, {})[epoch] = keys
        except BaseException as e:  # noqa: BLE001 - surfaced to the test
            errors.append((host_id, e))

    threads = [
        threading.Thread(target=host_main, args=(h,), daemon=True)
        for h in range(world_size)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive(), "distributed shuffle hung"
    for t in transports:
        t.close()
    if errors:
        raise errors[0][1]
    return results


@pytest.fixture(scope="module")
def small_dataset(tmp_path_factory):
    data_dir = str(tmp_path_factory.mktemp("dist_data"))
    filenames, _ = dg.generate_data_local(
        num_rows=6000, num_files=6, num_row_groups_per_file=2,
        max_row_group_skew=0.0, data_dir=data_dir, seed=3)
    return filenames


def test_distributed_exactly_once_and_mixing(small_dataset):
    filenames = small_dataset
    num_epochs, num_reducers, world_size = 2, 8, 3
    results = _run_world(filenames, num_epochs, num_reducers, world_size,
                         seed=11)
    # Which keys came from which host's file shard.
    plan = dist.ShardPlan(len(filenames), num_reducers, world_size)
    rows_per_file = 1000
    for epoch in range(num_epochs):
        union = []
        for trainer in range(world_size):
            union.extend(results[trainer][epoch])
        assert sorted(union) == list(range(6000)), "lost or duplicated rows"
        # Cross-host mixing: every trainer sees keys from remote file shards.
        for trainer in range(world_size):
            local_files = set(plan.local_files(trainer))
            origins = {k // rows_per_file for k in results[trainer][epoch]}
            assert origins - local_files, (
                f"trainer {trainer} epoch {epoch} saw only local keys — "
                "no cross-host exchange happened")


def test_distributed_matches_single_host_bit_exact(small_dataset):
    """The equivalence guarantee: N hosts == 1 host, same batches, same
    order, per global trainer."""
    filenames = small_dataset
    num_epochs, num_reducers, world_size, seed = 2, 6, 3, 23

    distributed = _run_world(filenames, num_epochs, num_reducers, world_size,
                             seed=seed)

    # Single-host run with num_trainers = world_size.
    collected = {}

    def consumer(trainer, epoch, refs):
        if refs is not None:
            collected.setdefault((trainer, epoch), []).extend(refs)

    run_shuffle(filenames, consumer, num_epochs, num_reducers,
                num_trainers=world_size, max_concurrent_epochs=2, seed=seed,
                collect_stats=False)
    for (trainer, epoch), refs in collected.items():
        keys = []
        for ref in refs:
            keys.extend(ref.result().column("key").to_pylist())
        assert distributed[trainer][epoch] == keys, (
            f"trainer {trainer} epoch {epoch}: distributed order diverged "
            "from single-host order")


def test_distributed_trainers_per_host(small_dataset):
    results = _run_world(small_dataset, 1, 8, 2, seed=5, trainers_per_host=2)
    union = []
    for trainer in range(4):
        union.extend(results[trainer][0])
    assert sorted(union) == list(range(6000))


def _run_world_datasets(filenames, num_epochs, num_reducers, world, tph,
                        seed, batch_size, spill_dir):
    """The same world through the path a trainer uses: every host makes
    its batch queue and shuffle, every local rank drains its stream
    through a ``ShufflingDataset`` on that queue. One epoch at a time
    (the throttle engages) and an in-flight budget of one byte, so that
    every reducer output takes the spill tier. Returns
    ``{global trainer: {epoch: [key, ...]}}``."""
    from ray_shuffling_data_loader_tpu.dataset import ShufflingDataset

    transports = tp.create_local_transports(world, recv_timeout_s=120.0)
    results = {}
    errors = []

    def consume_rank(host_id, local_rank, queue, result):
        try:
            ds = ShufflingDataset(
                filenames, num_epochs, num_trainers=tph,
                batch_size=batch_size, rank=local_rank, batch_queue=queue,
                shuffle_result=result if local_rank == 0 else None,
                drop_last=False)
            for epoch in range(num_epochs):
                ds.set_epoch(epoch)
                keys = [k for table in ds
                        for k in table.column("key").to_pylist()]
                results.setdefault(host_id * tph + local_rank,
                                   {})[epoch] = keys
        except BaseException as e:  # noqa: BLE001 - surfaced to the test
            errors.append((host_id, e))

    def host_main(host_id):
        try:
            queue, result = dist.create_distributed_batch_queue_and_shuffle(
                filenames, num_epochs, num_reducers=num_reducers,
                transport=transports[host_id], trainers_per_host=tph,
                max_concurrent_epochs=1, seed=seed, queue_name=None,
                num_workers=2, file_cache=None, max_inflight_bytes=1,
                spill_dir=spill_dir)
            ranks = [threading.Thread(
                target=consume_rank, args=(host_id, r, queue, result),
                daemon=True) for r in range(tph)]
            for t in ranks:
                t.start()
            for t in ranks:
                t.join(timeout=120)
                assert not t.is_alive(), f"host {host_id} consumer hung"
            result.result()
            queue.shutdown()
        except BaseException as e:  # noqa: BLE001 - surfaced to the test
            errors.append((host_id, e))

    threads = [threading.Thread(target=host_main, args=(h,), daemon=True)
               for h in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    # Closed before the liveness check: a host that died early leaves its
    # peers in recv, and the real error would hide behind "hung".
    for t in transports:
        t.close()
    if errors:
        raise errors[0][1]
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive(), "distributed shuffle hung"
    return results


@pytest.mark.parametrize("through", ["refs", "spilled_datasets"])
def test_distributed_world4_tph2_multiepoch_bit_exact(small_dataset,
                                                      tmp_path, through):
    """The widest topology: world=4 with trainers_per_host=2 (8 global
    trainers), 2 epochs, 10 reducers split unevenly over the 8 trainers —
    every stream bit-identical to the single-host num_trainers=8 shuffle,
    as the consumer's refs and through each rank's dataset on its host's
    batch queue with every reducer output spilled on the way."""
    from ray_shuffling_data_loader_tpu import spill as spill_mod

    filenames = small_dataset
    num_epochs, num_reducers, world, tph, seed = 2, 10, 4, 2, 41
    if through == "refs":
        distributed = _run_world(filenames, num_epochs, num_reducers, world,
                                 seed=seed, trainers_per_host=tph)
    else:
        # asked of the managers' totals, not of a log line
        spills_before, _ = spill_mod.process_spill_totals()
        distributed = _run_world_datasets(
            filenames, num_epochs, num_reducers, world, tph, seed,
            batch_size=125, spill_dir=str(tmp_path / "spill"))
        spills, spilled_bytes = spill_mod.process_spill_totals()
        assert spills - spills_before >= 1, (
            "max_inflight_bytes=1 with a spill_dir and no manager recorded "
            f"a spilled reducer output ({spills} spills, {spilled_bytes} B)")

    collected = {}

    def consumer(trainer, epoch, refs):
        if refs is not None:
            collected.setdefault((trainer, epoch), []).extend(refs)

    run_shuffle(filenames, consumer, num_epochs, num_reducers,
                num_trainers=world * tph, max_concurrent_epochs=2,
                seed=seed, collect_stats=False)
    assert len(collected) == world * tph * num_epochs
    for (trainer, epoch), refs in collected.items():
        keys = []
        for ref in refs:
            keys.extend(ref.result().column("key").to_pylist())
        assert distributed[trainer][epoch] == keys, (
            f"trainer {trainer} epoch {epoch}: world=4x2 stream diverged")


def test_distributed_single_host_degenerate(small_dataset):
    """world=1: no peers, everything local, still correct."""
    results = _run_world(small_dataset, 1, 4, 1, seed=2)
    assert sorted(results[0][0]) == list(range(6000))


def test_reduce_failure_propagates(small_dataset):
    """A reducer that cannot get its chunks fails the trial loudly
    (transport timeout), not a silent hang."""
    transports = tp.create_local_transports(2, recv_timeout_s=1.0)
    # Kill host 1 before it ever maps: host 0's reducers must time out.
    transports[1].close()

    def consumer(rank, epoch, refs):
        pass

    try:
        with pytest.raises(tp.TransportError):
            dist.shuffle_distributed(
                small_dataset, consumer, 1, 4, transports[0],
                max_concurrent_epochs=1, seed=0, num_workers=2)
    finally:
        transports[0].close()


# ---------------------------------------------------------------------------
# real multi-process world


def test_distributed_multiprocess(tmp_path):
    """3 OS processes, each a full loader host: generate -> shuffle ->
    consume via ShufflingDataset -> verify global exactly-once + mixing."""
    data_dir = str(tmp_path / "data")
    out_dir = str(tmp_path / "out")
    os.makedirs(out_dir)
    num_rows, num_files, world_size = 4500, 6, 3
    num_epochs, num_reducers, batch_size = 2, 6, 128
    dg.generate_data_local(num_rows, num_files, 2, 0.0, data_dir, seed=1)

    # Reserve ephemeral ports, then release them for the workers.
    import socket
    socks = []
    ports = []
    for _ in range(world_size):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        socks.append(s)
    for s in socks:
        s.close()
    ports_csv = ",".join(map(str, ports))

    worker = os.path.join(os.path.dirname(__file__), "distributed_worker.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(h), str(world_size), ports_csv,
             data_dir, str(num_epochs), str(num_reducers), str(batch_size),
             out_dir],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for h in range(world_size)
    ]
    outputs = [p.communicate(timeout=180)[0] for p in procs]
    for p, out in zip(procs, outputs):
        assert p.returncode == 0, out.decode(errors="replace")

    per_host = []
    for h in range(world_size):
        with open(os.path.join(out_dir, f"host{h}.json")) as f:
            per_host.append(json.load(f))
    rows_per_file = num_rows // num_files
    for epoch in range(num_epochs):
        union = []
        for h in range(world_size):
            union.extend(per_host[h][str(epoch)])
        assert sorted(union) == list(range(num_rows))
        plan = dist.ShardPlan(num_files, num_reducers, world_size)
        for h in range(world_size):
            origins = {k // rows_per_file for k in per_host[h][str(epoch)]}
            assert origins - set(plan.local_files(h))


# ---------------------------------------------------------------------------
# resume on a world


def test_distributed_resume_start_epoch(small_dataset):
    """start_epoch replays exactly the remaining epochs on every host."""
    full = _run_world(small_dataset, 2, 6, 2, seed=9)

    transports = tp.create_local_transports(2, recv_timeout_s=60.0)
    results = {}
    errors = []

    def host_main(host_id):
        collected = {}

        def consumer(local_rank, epoch, refs):
            if refs is not None:
                collected.setdefault(epoch, []).extend(refs)

        try:
            dist.shuffle_distributed(
                small_dataset, consumer, 2, 6, transports[host_id],
                max_concurrent_epochs=2, seed=9, num_workers=4,
                start_epoch=1)
            for epoch, refs in collected.items():
                keys = []
                for ref in refs:
                    keys.extend(ref.result().column("key").to_pylist())
                results.setdefault(host_id, {})[epoch] = keys
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=host_main, args=(h,), daemon=True)
               for h in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    for t in transports:
        t.close()
    if errors:
        raise errors[0]
    for host in range(2):
        assert list(results[host]) == [1]
        assert results[host][1] == full[host][1], (
            "resumed epoch 1 diverged from the original epoch 1")


def _world_dataset_run(filenames, num_epochs, num_reducers, world, seed,
                       batch_size, start_epoch=0, trainer0_consume=None):
    """Dataset-level in-process world: every host consumes through the
    real ShufflingDataset path. ``trainer0_consume(ds)`` runs on host 0
    (global trainer 0) and its return value is returned; the other hosts
    simply drain epochs ``[start_epoch, num_epochs)``."""
    from ray_shuffling_data_loader_tpu.dataset import ShufflingDataset

    transports = tp.create_local_transports(world, recv_timeout_s=120.0)
    out = {}
    errors = []

    def host_main(h):
        try:
            queue, res = dist.create_distributed_batch_queue_and_shuffle(
                filenames, num_epochs, num_reducers, transports[h],
                max_concurrent_epochs=2, seed=seed, num_workers=4,
                start_epoch=start_epoch)
            d = ShufflingDataset(
                filenames, num_epochs, num_trainers=1,
                batch_size=batch_size, rank=0, batch_queue=queue,
                shuffle_result=res, seed=seed, start_epoch=start_epoch)
            if h == 0 and trainer0_consume is not None:
                out[0] = trainer0_consume(d)
            else:
                for epoch in range(start_epoch, num_epochs):
                    d.set_epoch(epoch)
                    for _ in d:
                        pass
        except BaseException as e:  # noqa: BLE001 - surfaced to the test
            errors.append((h, e))

    threads = [threading.Thread(target=host_main, args=(h,), daemon=True)
               for h in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
        assert not t.is_alive(), "dataset-level world host hung"
    for t in transports:
        t.close()
    if errors:
        raise errors[0][1]
    return out.get(0)


def _consume_recording_checkpoint(ckpt_mod, seed, num_epochs, world,
                                  batch_size, crash_point, path):
    """Returns a trainer0_consume fn that iterates via resume_iterator
    from a fresh checkpoint, saves the checkpoint when it reaches
    ``crash_point`` = (epoch, batches_consumed), and records the full
    per-batch key stream tagged with checkpoint positions."""

    def consume(d):
        c = ckpt_mod.LoaderCheckpoint(
            seed=seed, epoch=0, batches_consumed=0, num_epochs=num_epochs,
            num_trainers=world, rank=0, batch_size=batch_size)
        stream = []
        for batch in ckpt_mod.resume_iterator(d, c):
            stream.append((c.epoch, c.batches_consumed,
                           tuple(batch.column("key").to_pylist())))
            if (c.epoch, c.batches_consumed) == crash_point:
                c.save(path)
        return stream

    return consume


def test_checkpoint_resume_world3_to_world1(small_dataset, tmp_path):
    """The payoff of global-index PRNG keying (distributed.py docstring):
    a LoaderCheckpoint saved MID-EPOCH under world=3 resumes under a
    single-host (world=1) topology with a bit-identical remaining batch
    stream — something the reference's unseeded shuffle can never do
    (reference: shuffle.py:213,240)."""
    from ray_shuffling_data_loader_tpu import checkpoint as ckpt
    from ray_shuffling_data_loader_tpu.dataset import ShufflingDataset

    filenames = small_dataset
    num_epochs, num_reducers, world, seed, bs = 3, 6, 3, 31, 128
    crash_point = (1, 3)  # "crash" after 3 batches of epoch 1
    path = str(tmp_path / "ckpt.json")

    full = _world_dataset_run(
        filenames, num_epochs, num_reducers, world, seed, bs,
        trainer0_consume=_consume_recording_checkpoint(
            ckpt, seed, num_epochs, world, bs, crash_point, path))
    expected = [keys for (e, i, keys) in full if (e, i) > crash_point]
    assert expected, "crash point must leave a non-empty remainder"

    loaded = ckpt.LoaderCheckpoint.load(path)
    assert (loaded.epoch, loaded.batches_consumed) == crash_point
    # world=1 resume: one host owns the whole shuffle; the same GLOBAL
    # topology (num_trainers=3) keeps trainer 0's stream identity.
    d = ShufflingDataset(
        filenames, num_epochs, num_trainers=world, batch_size=bs, rank=0,
        num_reducers=num_reducers, seed=seed, start_epoch=loaded.epoch,
        queue_name="xtopo-w3-to-w1")
    resumed = [tuple(b.column("key").to_pylist())
               for b in ckpt.resume_iterator(d, loaded)]
    assert resumed == expected, (
        "world=1 resume diverged from the world=3 stream remainder")


def test_checkpoint_resume_world1_to_world3(small_dataset, tmp_path):
    """Reverse direction: checkpoint saved mid-epoch under a single-host
    run resumes under world=3 bit-identically (scale-out after a crash)."""
    from ray_shuffling_data_loader_tpu import checkpoint as ckpt
    from ray_shuffling_data_loader_tpu.dataset import ShufflingDataset

    filenames = small_dataset
    num_epochs, num_reducers, world, seed, bs = 3, 6, 3, 47, 128
    crash_point = (1, 4)
    path = str(tmp_path / "ckpt.json")

    d = ShufflingDataset(
        filenames, num_epochs, num_trainers=world, batch_size=bs, rank=0,
        num_reducers=num_reducers, seed=seed, queue_name="xtopo-w1-full")
    consume = _consume_recording_checkpoint(
        ckpt, seed, num_epochs, world, bs, crash_point, path)
    full = consume(d)
    expected = [keys for (e, i, keys) in full if (e, i) > crash_point]
    assert expected

    loaded = ckpt.LoaderCheckpoint.load(path)
    resumed = _world_dataset_run(
        filenames, num_epochs, num_reducers, world, seed, bs,
        start_epoch=loaded.epoch,
        trainer0_consume=lambda ds: [
            tuple(b.column("key").to_pylist())
            for b in ckpt.resume_iterator(ds, loaded)])
    assert resumed == expected, (
        "world=3 resume diverged from the world=1 stream remainder")


def test_distributed_shuffle_applies_reduce_transform(tmp_path):
    """reduce_transform runs inside distributed reduce tasks too, exactly
    once per row per epoch across all hosts."""
    import threading

    import pyarrow as pa

    from ray_shuffling_data_loader_tpu import data_generation as dg
    from ray_shuffling_data_loader_tpu.parallel import distributed as dist
    from ray_shuffling_data_loader_tpu.parallel import transport as tr

    filenames, _ = dg.generate_data_local(120, 4, 1, 0.0,
                                          str(tmp_path / "pq"))
    seen = []
    lock = threading.Lock()

    def tag_and_record(table: pa.Table) -> pa.Table:
        with lock:
            seen.extend(table.column(dg.KEY_COLUMN).to_pylist())
        return table.append_column(
            "tagged", pa.array([True] * table.num_rows))

    world = 2
    transports = tr.create_local_transports(world)
    collected = {h: [] for h in range(world)}

    def run_host(host):
        def consumer(rank, epoch, refs):
            if refs is not None:
                collected[host].extend(refs)

        dist.shuffle_distributed(
            filenames, consumer, num_epochs=1, num_reducers=4,
            transport=transports[host], max_concurrent_epochs=1, seed=5,
            reduce_transform=tag_and_record)

    threads = [threading.Thread(target=run_host, args=(h,))
               for h in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    for t in transports:
        t.close()
    keys = []
    for host_refs in collected.values():
        for ref in host_refs:
            table = ref.result()
            assert "tagged" in table.column_names
            keys.extend(table.column(dg.KEY_COLUMN).to_pylist())
    assert sorted(keys) == list(range(120))
    assert sorted(seen) == list(range(120))


def test_distributed_shuffle_collects_per_host_stats(tmp_path):
    """collect_stats=True returns this host's TrialStats with the local
    map/reduce/consume counts (per-host observability parity)."""
    import threading

    from ray_shuffling_data_loader_tpu import data_generation as dg
    from ray_shuffling_data_loader_tpu import stats as stats_mod
    from ray_shuffling_data_loader_tpu.parallel import distributed as dist
    from ray_shuffling_data_loader_tpu.parallel import transport as tr

    filenames, _ = dg.generate_data_local(120, 4, 1, 0.0,
                                          str(tmp_path / "pq"))
    world = 2
    transports = tr.create_local_transports(world)
    results = {}

    def run_host(host):
        def consumer(rank, epoch, refs):
            if refs is not None:
                for ref in refs:
                    ref.result()

        results[host] = dist.shuffle_distributed(
            filenames, consumer, num_epochs=2, num_reducers=4,
            transport=transports[host], max_concurrent_epochs=1, seed=1,
            collect_stats=True)

    threads = [threading.Thread(target=run_host, args=(h,))
               for h in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    for t in transports:
        t.close()
    for host in range(world):
        stats = results[host]
        assert isinstance(stats, stats_mod.TrialStats)
        assert stats.duration > 0
        assert len(stats.epoch_stats) == 2
        epoch0 = stats.epoch_stats[0]
        assert len(epoch0.map_stats.task_durations) == 2   # 4 files / 2
        assert len(epoch0.reduce_stats.task_durations) == 2  # 4 red / 2
