"""Tests for the self-healing serving plane (rebalance/ + the wire
actuator in multiqueue_service.py): the pure placement fold, the crc'd
decision journal (byte-identical replay, torn tails, tamper), the
SLO-breach detector's one-fire-per-episode hysteresis, the live
two-phase queue migration, the zombie-source generation fence, and the
kill -9 churn matrix (source mid-PREPARE, target mid-COMMIT, driver
mid-decision — each recovering to a bit-identical delivered stream)."""

import os
import threading
import time

import pyarrow as pa
import pytest

from ray_shuffling_data_loader_tpu import data_generation as dg
from ray_shuffling_data_loader_tpu import multiqueue as mq
from ray_shuffling_data_loader_tpu import multiqueue_service as svc
from ray_shuffling_data_loader_tpu import rebalance as rb
from ray_shuffling_data_loader_tpu.dataset import ShufflingDataset
from ray_shuffling_data_loader_tpu.plan import ir as plan_ir
from ray_shuffling_data_loader_tpu.runtime import faults as rt_faults
from ray_shuffling_data_loader_tpu.runtime import health as rt_health
from ray_shuffling_data_loader_tpu.runtime import history as rt_history
from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics
from ray_shuffling_data_loader_tpu.runtime import supervisor as rt_sup
from ray_shuffling_data_loader_tpu.runtime import telemetry as rt_telemetry
from ray_shuffling_data_loader_tpu.shuffle import shuffle as run_shuffle


@pytest.fixture(autouse=True)
def _no_chaos_leak():
    yield
    rt_faults.clear()


def _shard_map(num_trainers=4, num_shards=2):
    return plan_ir.ShardMap(
        num_trainers=num_trainers,
        addresses=[("127.0.0.1", 9000 + s) for s in range(num_shards)])


# ---------------------------------------------------------------------------
# apply_decision is THE pure placement transition
# ---------------------------------------------------------------------------


class TestPlacementFold:

    def test_intent_commit_moves_rank_and_bumps_generation(self):
        state = rb.PlacementState.bootstrap(_shard_map())
        intent = rb.PlacementDecision("intent", rank=1, source=1, target=0)
        pending = rb.apply_decision(state, intent)
        assert pending.pending == (1, 1, 0)
        assert pending.generation == 0  # intent moves nothing yet
        committed = rb.apply_decision(
            pending, rb.PlacementDecision("commit", rank=1, source=1,
                                          target=0))
        assert committed.overrides == ((1, 0),)
        assert committed.generation == 1
        assert committed.pending is None
        assert committed.shard_for_rank(1) == 0
        assert committed.shard_for_rank(3) == 1  # static arithmetic

    def test_commit_back_home_drops_the_override(self):
        state = rb.PlacementState(num_trainers=4, num_shards=2,
                                  generation=1, overrides=((1, 0),))
        back = rb.apply_decision(
            state, rb.PlacementDecision("intent", rank=1, source=0,
                                        target=1))
        back = rb.apply_decision(
            back, rb.PlacementDecision("commit", rank=1, source=0,
                                       target=1))
        assert back.overrides == ()  # 1 % 2 == 1: static home again
        assert back.generation == 2

    def test_abort_restores_source_authoritative(self):
        state = rb.PlacementState.bootstrap(_shard_map())
        pending = rb.apply_decision(
            state, rb.PlacementDecision("intent", rank=1, source=1,
                                        target=0))
        aborted = rb.apply_decision(
            pending, rb.PlacementDecision("abort", rank=1, source=1,
                                          target=0))
        assert aborted == state

    def test_noop_and_protocol_violations(self):
        state = rb.PlacementState.bootstrap(_shard_map())
        # Moving a rank to its own home never journals.
        assert rb.apply_decision(
            state, rb.PlacementDecision("intent", rank=2, source=0,
                                        target=0)) is state
        pending = rb.apply_decision(
            state, rb.PlacementDecision("intent", rank=1, source=1,
                                        target=0))
        with pytest.raises(ValueError, match="one move in flight"):
            rb.apply_decision(
                pending, rb.PlacementDecision("intent", rank=3, source=1,
                                              target=0))
        with pytest.raises(ValueError, match="pending"):
            rb.apply_decision(
                pending, rb.PlacementDecision("commit", rank=3, source=1,
                                              target=0))
        with pytest.raises(ValueError, match="carry their own state"):
            rb.apply_decision(
                state, rb.PlacementDecision("bootstrap"))
        with pytest.raises(ValueError, match="source"):
            rb.apply_decision(
                state, rb.PlacementDecision("intent", rank=1, source=0,
                                            target=0))


# ---------------------------------------------------------------------------
# journal: crc'd append-only + torn tail + tamper + bit-identical replay
# ---------------------------------------------------------------------------


class TestRebalanceJournal:

    def _churn(self, journal_path):
        controller = rb.RebalanceController(_shard_map(),
                                            journal_path=journal_path,
                                            rebalance_max_moves=8)
        controller.begin(1, target=0, reason="hot tenant")
        controller.commit(1, reason="hot tenant")
        controller.begin(3, target=0, reason="second thought")
        controller.abort(3, reason="second thought")
        controller.close()
        return controller

    def test_journal_replays_bit_identically(self, tmp_path):
        journal_path = str(tmp_path / "rebalance.journal")
        controller = self._churn(journal_path)
        with open(journal_path, "rb") as f:
            original = f.read()
        assert controller.journal.journal_bytes() == original
        state = rb.replay(journal_path)
        assert state == controller.current_state()
        assert state.generation == 1
        assert state.overrides == ((1, 0),)
        assert state.pending is None

    def test_torn_tail_is_skipped_interior_corruption_raises(self, tmp_path):
        journal_path = str(tmp_path / "rebalance.journal")
        self._churn(journal_path)
        with open(journal_path, "ab") as f:
            f.write(b'{"torn":')  # crash mid-write
        assert rb.replay(journal_path).generation == 1
        with open(journal_path, encoding="utf-8") as f:
            lines = f.read().splitlines()
        lines[1] = '{"forged": 1}'
        with open(journal_path, "w", encoding="utf-8") as f:
            f.write("\n".join(lines) + "\n")
        with pytest.raises(ValueError, match="interior corruption"):
            rb.replay(journal_path)

    def test_replay_rejects_crc_tamper(self, tmp_path):
        journal_path = str(tmp_path / "rebalance.journal")
        self._churn(journal_path)
        with open(journal_path, encoding="utf-8") as f:
            lines = f.read().splitlines()
        # Flip a byte inside an interior crc'd line: with intact lines
        # after it, the load must refuse.
        lines[1] = 'X' + lines[1][1:]
        with open(journal_path, "w", encoding="utf-8") as f:
            f.write("\n".join(lines) + "\n")
        with pytest.raises(ValueError):
            rb.replay(journal_path)

    def test_replay_detects_divergent_but_valid_line(self, tmp_path):
        journal_path = str(tmp_path / "rebalance.journal")
        self._churn(journal_path)
        with open(journal_path, encoding="utf-8") as f:
            lines = f.read().splitlines()
        # Forge a whole VALID line (crc and all) whose recorded placement
        # disagrees with the fold: replay must catch the divergence.
        forged = rb.PlacementState(num_trainers=4, num_shards=2,
                                   generation=99, overrides=((3, 0),))
        lines[2] = rb.RebalanceJournal.encode(
            rb.PlacementDecision("commit", rank=1, source=1, target=0),
            forged)
        with open(journal_path, "w", encoding="utf-8") as f:
            f.write("\n".join(lines) + "\n")
        with pytest.raises(ValueError, match="diverged"):
            rb.replay(journal_path)

    def test_compact_collapses_to_one_snapshot(self, tmp_path):
        journal_path = str(tmp_path / "rebalance.journal")
        controller = self._churn(journal_path)
        expected = controller.current_state()
        journal = rb.RebalanceJournal(journal_path)
        journal.compact()
        with open(journal_path, encoding="utf-8") as f:
            lines = [line for line in f.read().splitlines() if line]
        assert len(lines) == 1
        assert rb.replay(journal_path) == expected
        # A compacted journal keeps accepting decisions that replay.
        resumed = rb.RebalanceController(_shard_map(),
                                         journal_path=journal_path,
                                         rebalance_max_moves=8)
        resumed.begin(3, target=0)
        resumed.commit(3)
        resumed.close()
        assert rb.replay(journal_path).generation == 2

    def test_restart_with_uncommitted_intent_journals_abort(self, tmp_path):
        journal_path = str(tmp_path / "rebalance.journal")
        controller = rb.RebalanceController(_shard_map(),
                                            journal_path=journal_path)
        controller.begin(1, target=0, reason="about to crash")
        assert controller.current_state().pending == (1, 1, 0)
        controller.close()  # driver dies between intent and commit
        recovered = rb.RebalanceController(_shard_map(),
                                           journal_path=journal_path)
        assert recovered.current_state().pending is None
        assert recovered.current_state().generation == 0
        recovered.close()
        kinds = [r["decision"].kind
                 for r in rb.RebalanceJournal.load(journal_path)]
        assert kinds == ["bootstrap", "intent", "abort"]
        # The recovered journal still replays clean end to end.
        assert rb.replay(journal_path).overrides == ()

    def test_commit_budget_blocks_ping_pong(self):
        controller = rb.RebalanceController(_shard_map(),
                                            rebalance_max_moves=1,
                                            rebalance_cooldown_s=3600.0)
        assert controller.begin(1, target=0) is not None
        controller.commit(1)
        # Budget spent: the hot tenant cannot bounce straight back.
        assert controller.begin(1, target=1) is None
        assert controller.moves_total == 1


# ---------------------------------------------------------------------------
# chaos grammar: the rebalance_* sites
# ---------------------------------------------------------------------------


class TestRebalanceChaosSites:

    @pytest.mark.parametrize("site", ["rebalance_prepare",
                                      "rebalance_commit",
                                      "rebalance_abort"])
    def test_rebalance_sites_known(self, site):
        assert site in rt_faults.SITES

    def test_selectors_parse_as_generation_and_rank(self):
        injector = rt_faults.install("rebalance_prepare@0.5:rank2:epoch1",
                                     seed=0)
        rule = injector.rules[0]
        assert rule.site == "rebalance_prepare"
        assert rule.rate == 0.5
        assert rule.task == 2
        rt_faults.clear()

    def test_driver_mid_decision_aborts_on_restart(self, tmp_path):
        """rebalance_abort fires AFTER the intent is durable and before
        any actuator byte moves: the journal tail is an uncommitted
        intent, and the restarted controller recovers it as an abort —
        source authoritative, placement unchanged."""
        journal_path = str(tmp_path / "rebalance.journal")
        rt_faults.install("rebalance_abort:rank1:epoch1", seed=0)
        controller = rb.RebalanceController(_shard_map(),
                                            journal_path=journal_path)
        with pytest.raises(rt_faults.InjectedFault):
            controller.begin(1, target=0, reason="slo breach")
        controller.close()
        rt_faults.clear()
        kinds = [r["decision"].kind
                 for r in rb.RebalanceJournal.load(journal_path)]
        assert kinds == ["bootstrap", "intent"]  # died mid-decision
        recovered = rb.RebalanceController(_shard_map(),
                                           journal_path=journal_path)
        state = recovered.current_state()
        recovered.close()
        assert state.pending is None
        assert state.generation == 0
        assert state.overrides == ()


# ---------------------------------------------------------------------------
# detector: sustained per-tenant p99 breach fires once per episode
# ---------------------------------------------------------------------------

TENANT_CENTROIDS = "rsdl_tenant_delivery_latency_seconds_centroid"


def _tenant_centroid_labels(c, tenant="team-a"):
    return (("c", str(c)), ("hop", "birth_to_delivered"),
            ("tenant", tenant))


def _tenant_snap(t, samples):
    return {"t": t, "t_unix": 1.7e9 + t, "samples": samples}


def test_tenant_slo_detector_fires_once_per_episode_under_noise():
    ring = rt_history.HistoryRing(capacity=400, interval_s=0.1)
    fired = []
    mon = rt_health.HealthMonitor(
        ring,
        detectors=rt_health.default_detectors(
            names=["tenant_delivery_slo"],
            rebalance_slo_p99_s=1.0, slo_droop_window_ticks=3),
        fire_ticks=2, clear_ticks=4, capture=False,
        on_fire=lambda v: fired.append(v))
    fast, slow, t = 0, 0, 0.0
    # Healthy: all of team-a's mass at 10ms.
    for _ in range(8):
        fast, t = fast + 5, t + 0.1
        ring.append_snapshot(_tenant_snap(t, {TENANT_CENTROIDS: {
            _tenant_centroid_labels(0.01): float(fast)}}))
        mon.tick()
    assert mon.total_fires == 0
    # Breach episode with NOISE: the slow mass trickles in unevenly.
    for i in range(10):
        slow, t = slow + (4 if i % 3 == 0 else 1), t + 0.1
        ring.append_snapshot(_tenant_snap(t, {TENANT_CENTROIDS: {
            _tenant_centroid_labels(0.01): float(fast),
            _tenant_centroid_labels(5.0): float(slow)}}))
        mon.tick()
    assert mon.total_fires == 1, mon.summary()
    assert fired[0]["detector"] == "tenant_delivery_slo"
    assert "team-a" in fired[0]["detail"]
    # Recovery (fast-only traffic) re-arms; a SECOND episode fires again.
    for _ in range(8):
        fast, t = fast + 5, t + 0.1
        ring.append_snapshot(_tenant_snap(t, {TENANT_CENTROIDS: {
            _tenant_centroid_labels(0.01): float(fast),
            _tenant_centroid_labels(5.0): float(slow)}}))
        mon.tick()
    for _ in range(6):
        slow, t = slow + 5, t + 0.1
        ring.append_snapshot(_tenant_snap(t, {TENANT_CENTROIDS: {
            _tenant_centroid_labels(0.01): float(fast),
            _tenant_centroid_labels(5.0): float(slow)}}))
        mon.tick()
    assert mon.total_fires == 2, mon.summary()


# ---------------------------------------------------------------------------
# live migration, in-process topology: redirect + exactly-once + twins
# ---------------------------------------------------------------------------


def _feed_rank(queue, rank, num_trainers, tables, sentinel=True):
    q = plan_ir.queue_index(0, rank, num_trainers)
    for table in tables:
        queue.put(q, table)
    if sentinel:
        queue.put(q, None)
    return q


def _tables(n, rows=10):
    return [pa.table({"key": list(range(i * rows, (i + 1) * rows))})
            for i in range(n)]


def test_live_migration_mid_stream_is_exactly_once(tmp_path):
    """The headline happy path: a rank's LIVE stream migrates between
    in-process shards mid-consumption — the consumer follows the MOVED
    redirect transparently and sees every row offset exactly once, in
    order, with zero loss and zero duplication."""
    trainers = 2
    queue = mq.MultiQueue(trainers, name=None)
    tables = _tables(8)
    with svc.ShardedQueueServer(queue, 2, num_trainers=trainers) as sss:
        q1 = _feed_rank(queue, 1, trainers, tables)
        controller = rb.RebalanceController(
            sss.shard_map, journal_path=str(tmp_path / "rb.journal"))
        remote = svc.ShardedRemoteQueue(sss.shard_map, max_batch=2)
        try:
            stream = []
            for _ in range(3):
                item, row_offset = remote.get_positioned(q1)
                stream.append((row_offset,
                               tuple(item.column("key").to_pylist())))
            state = rb.migrate(controller, 1, target=0,
                               reason="test migration")
            assert state is not None and state.generation == 1
            while True:
                item, row_offset = remote.get_positioned(q1)
                if item is None:
                    break
                stream.append((row_offset,
                               tuple(item.column("key").to_pylist())))
        finally:
            remote.close()
            controller.close()
    # Exactly-once, in order, across the handoff.
    assert [offset for offset, _ in stream] == [i * 10 for i in range(8)]
    assert [keys for _, keys in stream] == \
        [tuple(t.column("key").to_pylist()) for t in tables]
    # The consumer's shard map learned the move.
    assert sss.shard_map.overrides == {1: 0}
    assert sss.shard_map.generation == 1
    # Telemetry twins join the decision records by (kind, epoch=the
    # move's target generation, task=rank) — the chaos-site key.
    events = rt_telemetry.recorder().events()
    for kind in ("rebalance_intent", "rebalance_prepare",
                 "rebalance_commit", "rebalance_release"):
        assert any(e["kind"] == kind and e["epoch"] == 1
                   and e["task"] == 1 for e in events), kind
    # The decision journal replays the whole episode byte-identically.
    assert rb.replay(str(tmp_path / "rb.journal")).overrides == ((1, 0),)


def test_zombie_source_frames_are_fenced_and_counted():
    """A source that missed RELEASE (driver died post-commit) keeps
    serving the migrated rank with the STALE generation: a consumer
    whose fence floor was raised by the move drops every such frame
    loudly — counted, telemetry-recorded, stream uncorrupted — while a
    consumer on the target drains the remainder exactly once."""
    trainers = 2
    queue = mq.MultiQueue(trainers, name=None)
    tables = _tables(4)
    fenced = rt_metrics.counter(
        "rsdl_rebalance_fenced_frames_total",
        "frames dropped below the placement-generation fence")
    with svc.ShardedQueueServer(queue, 2, num_trainers=trainers) as sss:
        q1 = _feed_rank(queue, 1, trainers, tables, sentinel=False)
        source_addr = sss.servers[1].address
        target_addr = sss.servers[0].address
        # The pre-move consumer: manual acks, so everything it fetched
        # stays in the source's replay buffer (unacked).
        first = svc.RemoteQueue(source_addr, num_trainers=trainers,
                                max_batch=4, prefetch=False,
                                ack_mode="manual")
        try:
            item, row_offset = first.get_positioned(q1)
            assert row_offset == 0
            # PREPARE + ADOPT, but the driver dies before RELEASE: the
            # source keeps its state and, once unsealed, serves it again
            # — the zombie.
            manifest = svc.rebalance_prepare(source_addr, 1, generation=1)
            svc.rebalance_adopt(target_addr, manifest)
            svc.rebalance_unseal(source_addr, 1)
            positions = first.export_positions(1)
        finally:
            first.close()
        # A consumer that already learned generation 1 dials the zombie:
        # every replayed data frame sits below its floor and is fenced.
        before = fenced.value
        zombie_view = svc.RemoteQueue(source_addr, num_trainers=trainers,
                                      max_batch=8, prefetch=False)
        try:
            zombie_view.adopt_positions({}, generation=1, rank=1)
            items, _ = zombie_view._fetch_batch(q1)
        finally:
            zombie_view.close()
        assert items == []
        assert fenced.value >= before + 4
        fence_events = [e for e in rt_telemetry.recorder().events()
                        if e["kind"] == "rebalance_fence"]
        assert fence_events
        assert fence_events[-1]["generation"] == 0
        assert fence_events[-1]["floor"] == 1
        # The TARGET serves the remainder exactly once: the adopted
        # cursors + the consumer's transferred positions dedup the one
        # already-delivered table.
        second = svc.RemoteQueue(target_addr, num_trainers=trainers,
                                 max_batch=4, prefetch=False)
        try:
            second.adopt_positions(positions, generation=1, rank=1)
            offsets = []
            for _ in range(3):
                item, row_offset = second.get_positioned(q1)
                offsets.append(row_offset)
        finally:
            second.close()
        assert offsets == [10, 20, 30]


def test_bare_remote_queue_surfaces_moved_redirect():
    """After RELEASE the source answers GETs with a MOVED redirect; a
    bare RemoteQueue (no router) surfaces it as QueueMoved carrying the
    target address and generation — exactly the cached-address failure
    the shard-affinity-assumption lint rule exists to catch."""
    trainers = 2
    queue = mq.MultiQueue(trainers, name=None)
    with svc.ShardedQueueServer(queue, 2, num_trainers=trainers) as sss:
        q1 = _feed_rank(queue, 1, trainers, _tables(2))
        source_addr = sss.servers[1].address
        target_addr = sss.servers[0].address
        manifest = svc.rebalance_prepare(source_addr, 1, generation=1)
        svc.rebalance_adopt(target_addr, manifest)
        svc.rebalance_release(source_addr, 1, generation=1,
                              target=target_addr)
        with svc.RemoteQueue(source_addr, num_trainers=trainers,
                             prefetch=False) as stale:
            with pytest.raises(svc.QueueMoved) as excinfo:
                stale.get(q1)
        assert excinfo.value.rank == 1
        assert excinfo.value.address == (target_addr[0], target_addr[1])
        assert excinfo.value.generation == 1


# ---------------------------------------------------------------------------
# kill -9 churn matrix: supervised process topology
# ---------------------------------------------------------------------------


def _reference_streams(filenames, epochs, reducers, trainers, seed):
    """Fault-free per-(rank, epoch) key streams off the deterministic
    shuffle lineage."""
    streams: dict = {}

    def consumer(rank, epoch, refs):
        if refs is not None:
            streams.setdefault((rank, epoch), []).extend(refs)

    run_shuffle(filenames, consumer, epochs, num_reducers=reducers,
                num_trainers=trainers, max_concurrent_epochs=1, seed=seed,
                collect_stats=False, file_cache=None)
    return {key: [tuple(r.result().column("key").to_pylist())
                  for r in refs]
            for key, refs in streams.items()}


def _drain_rank(shard_map, filenames, epochs, trainers, seed, rank,
                on_table=None):
    """One consumer draining ``rank``'s whole run; returns the
    per-epoch key-tuple streams keyed like ``_reference_streams``."""
    got = {}
    remote = svc.ShardedRemoteQueue(shard_map, retries=12, max_batch=2)
    ds = ShufflingDataset(filenames, epochs, num_trainers=trainers,
                          batch_size=50, rank=rank, batch_queue=remote,
                          shuffle_result=None, seed=seed)
    try:
        for epoch in range(epochs):
            ds.set_epoch(epoch)
            tables = []
            for table in ds.iter_tables():
                tables.append(tuple(table.column("key").to_pylist()))
                if on_table is not None:
                    on_table(len(tables))
            got[(rank, epoch)] = tables
    finally:
        remote.close()
    return got


def _launch_with_chaos(tmp_parquet_dir, filenames, trainers, reducers,
                       seed, chaos_spec):
    return rt_sup.launch_supervised_queue_shards(dict(
        filenames=filenames, num_epochs=1, num_trainers=trainers,
        num_reducers=reducers, seed=seed, max_concurrent_epochs=1,
        journal_path=os.path.join(tmp_parquet_dir, "wm-rebalance.wal"),
        file_cache=None,
        child_env={"RSDL_CHAOS_SPEC": chaos_spec,
                   "RSDL_CHAOS_SEED": "0"}), num_shards=2)


def test_kill9_source_mid_prepare_aborts_and_stream_bit_identical(
        tmp_parquet_dir, tmp_path):
    """kill -9 of the SOURCE shard mid-PREPARE: the handoff dies before
    the manifest exists, the driver journals an abort (source stays
    authoritative), the supervisor restarts the source from its
    watermark journal, and the consumer's stream is bit-identical to
    the fault-free run — zero missed or duplicated rows."""
    trainers, epochs, reducers, seed = 2, 1, 4, 13
    filenames, _ = dg.generate_data_local(600, 2, 1, 0.0, tmp_parquet_dir)
    expected = _reference_streams(filenames, epochs, reducers, trainers,
                                  seed)
    supervisors, shard_map = _launch_with_chaos(
        tmp_parquet_dir, filenames, trainers, reducers, seed,
        "rebalance_prepare:rank0:epoch1")
    controller = rb.RebalanceController(
        shard_map, journal_path=str(tmp_path / "rb.journal"))
    migration_error = []

    def on_table(count):
        if count == 1 and not migration_error:
            try:
                rb.migrate(controller, 0, target=1, reason="churn test")
            except (OSError, RuntimeError) as e:
                migration_error.append(e)

    try:
        for address in shard_map.addresses:
            assert rt_sup.wait_for_server(tuple(address), timeout_s=60)
        got = _drain_rank(shard_map, filenames, epochs, trainers, seed,
                          rank=0, on_table=on_table)
        # The consumer can finish from what it had prefetched before the
        # supervisor's thread has run on the child's death, and a stop()
        # that comes first leaves the death uncounted: give it its turn.
        deadline = time.monotonic() + 30.0
        while supervisors[0].restarts < 1 and time.monotonic() < deadline:
            time.sleep(0.05)
    finally:
        for supervisor in supervisors:
            supervisor.stop()
        controller.close()
    # The prepare really died on the wire and was really aborted.
    assert migration_error, "chaos site never fired"
    assert supervisors[0].restarts >= 1
    state = rb.replay(str(tmp_path / "rb.journal"))
    assert state.pending is None
    assert state.generation == 0 and state.overrides == ()
    # Bit-identical: list equality catches loss, duplication and
    # reordering at once, across the kill.
    assert got == {k: v for k, v in expected.items() if k[0] == 0}


def test_kill9_target_mid_commit_aborts_and_both_streams_bit_identical(
        tmp_parquet_dir, tmp_path):
    """kill -9 of the TARGET shard mid-COMMIT (during ADOPT, before the
    commit is journaled): the driver aborts and un-seals the still-live
    source, the supervisor restarts the target, and BOTH ranks' streams
    — the un-migrated rank on the source and the restarted target's own
    rank — are bit-identical to the fault-free run."""
    trainers, epochs, reducers, seed = 2, 1, 4, 29
    filenames, _ = dg.generate_data_local(600, 2, 1, 0.0, tmp_parquet_dir)
    expected = _reference_streams(filenames, epochs, reducers, trainers,
                                  seed)
    supervisors, shard_map = _launch_with_chaos(
        tmp_parquet_dir, filenames, trainers, reducers, seed,
        "rebalance_commit:rank0:epoch1")
    controller = rb.RebalanceController(
        shard_map, journal_path=str(tmp_path / "rb.journal"))
    try:
        for address in shard_map.addresses:
            assert rt_sup.wait_for_server(tuple(address), timeout_s=60)
        # The ADOPT call dies on the target's crash site.
        with pytest.raises((OSError, RuntimeError)):
            rb.migrate(controller, 0, target=1, reason="churn test")
        got = _drain_rank(shard_map, filenames, epochs, trainers, seed,
                          rank=0)
        got.update(_drain_rank(shard_map, filenames, epochs, trainers,
                               seed, rank=1))
    finally:
        for supervisor in supervisors:
            supervisor.stop()
        controller.close()
    assert supervisors[1].restarts >= 1
    assert supervisors[0].restarts == 0
    state = rb.replay(str(tmp_path / "rb.journal"))
    assert state.pending is None
    assert state.generation == 0 and state.overrides == ()
    assert got == expected


def test_driver_mid_decision_leaves_live_stream_untouched(tmp_path):
    """The third churn-matrix leg end to end: the DRIVER dies between
    journaling the intent and touching any shard; a restarted
    controller recovers the abort, no actuator byte ever moved, and the
    in-process serving plane delivers its stream bit-identically."""
    trainers = 2
    queue = mq.MultiQueue(trainers, name=None)
    tables = _tables(4)
    journal_path = str(tmp_path / "rb.journal")
    with svc.ShardedQueueServer(queue, 2, num_trainers=trainers) as sss:
        q1 = _feed_rank(queue, 1, trainers, tables)
        rt_faults.install("rebalance_abort:rank1:epoch1", seed=0)
        controller = rb.RebalanceController(sss.shard_map,
                                            journal_path=journal_path)
        with pytest.raises(rt_faults.InjectedFault):
            rb.migrate(controller, 1, target=0, reason="driver dies")
        controller.close()
        rt_faults.clear()
        # Driver restart: the uncommitted intent aborts.
        recovered = rb.RebalanceController(sss.shard_map,
                                           journal_path=journal_path)
        assert recovered.current_state().pending is None
        assert recovered.current_state().generation == 0
        recovered.close()
        # The serving plane never heard about any of it.
        stream = []
        with svc.ShardedRemoteQueue(sss.shard_map, max_batch=2) as remote:
            while True:
                item, row_offset = remote.get_positioned(q1)
                if item is None:
                    break
                stream.append((row_offset,
                               tuple(item.column("key").to_pylist())))
    assert [offset for offset, _ in stream] == [i * 10 for i in range(4)]
    assert [keys for _, keys in stream] == \
        [tuple(t.column("key").to_pylist()) for t in tables]
