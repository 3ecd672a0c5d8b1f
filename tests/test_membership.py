"""Tests for the membership plane: journaled views (membership/),
phi-style failure detection (membership/detector.py), elastic resize
(membership/elastic.py), generation-fenced transport
(parallel/transport.py), the member_* chaos sites, and the queue
server's view-aware lease sweep."""

import os
import threading

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from ray_shuffling_data_loader_tpu import membership as mem
from ray_shuffling_data_loader_tpu import multiqueue as mq
from ray_shuffling_data_loader_tpu import multiqueue_service as svc
from ray_shuffling_data_loader_tpu.membership import detector as md
from ray_shuffling_data_loader_tpu.membership import elastic as me
from ray_shuffling_data_loader_tpu.parallel import transport as tp
from ray_shuffling_data_loader_tpu.plan import ir as plan_ir
from ray_shuffling_data_loader_tpu.plan import scheduler as plan_sched
from ray_shuffling_data_loader_tpu.runtime import faults as rt_faults
from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics
from ray_shuffling_data_loader_tpu.streaming import window as st_window


@pytest.fixture(autouse=True)
def _no_chaos_leak():
    yield
    rt_faults.clear()


def _make_files(directory, num_files=3, rows=64):
    os.makedirs(directory, exist_ok=True)
    files = []
    for i in range(num_files):
        table = pa.table({"key": pa.array(
            range(i * rows, (i + 1) * rows), type=pa.int64())})
        path = os.path.join(directory, f"part_{i:03d}.parquet")
        pq.write_table(table, path)
        files.append(path)
    return files


# ---------------------------------------------------------------------------
# views: apply_event is THE pure transition function
# ---------------------------------------------------------------------------


class TestViewTransitions:

    def test_bootstrap_sorts_and_dedups(self):
        view = mem.MembershipView.bootstrap([3, 1, 1, 0])
        assert view.view_id == 0
        assert view.ranks == (0, 1, 3)
        assert view.incarnation(3) == 0

    def test_down_removes_rank_and_bumps_view(self):
        view = mem.MembershipView.bootstrap([0, 1, 2])
        after = mem.apply_event(view, mem.MembershipEvent("down", rank=1))
        assert after.ranks == (0, 2)
        assert after.view_id == 1
        # The departed rank's incarnation is REMEMBERED for the fence.
        assert after.incarnation(1) == 0

    def test_down_absent_rank_is_noop(self):
        view = mem.MembershipView.bootstrap([0, 1])
        assert mem.apply_event(
            view, mem.MembershipEvent("down", rank=7)) is view

    def test_rejoin_requires_current_incarnation(self):
        view = mem.MembershipView.bootstrap([0, 1, 2],
                                            incarnations={1: 2})
        down = mem.apply_event(view, mem.MembershipEvent("down", rank=1))
        # An OLDER generation knocking again is a zombie, not a rejoin —
        # the view remembers the departed rank's incarnation floor.
        assert mem.apply_event(
            down, mem.MembershipEvent("join", rank=1,
                                      incarnation=1)) is down
        rejoined = mem.apply_event(
            down, mem.MembershipEvent("join", rank=1, incarnation=2))
        assert rejoined.ranks == (0, 1, 2)
        assert rejoined.incarnation(1) == 2

    def test_join_new_rank_grows_world(self):
        view = mem.MembershipView.bootstrap([0, 1])
        grown = mem.apply_event(
            view, mem.MembershipEvent("join", rank=5, incarnation=0))
        assert grown.ranks == (0, 1, 5)
        assert grown.view_id == 1

    def test_join_live_rank_same_generation_is_noop(self):
        view = mem.MembershipView.bootstrap([0, 1])
        assert mem.apply_event(
            view, mem.MembershipEvent("join", rank=1,
                                      incarnation=0)) is view

    def test_base_records_rejected_by_apply_event(self):
        view = mem.MembershipView.bootstrap([0])
        with pytest.raises(ValueError, match="carry their own view"):
            mem.apply_event(view, mem.MembershipEvent("bootstrap"))
        with pytest.raises(ValueError, match="unknown"):
            mem.apply_event(view, mem.MembershipEvent("promote", rank=0))

    def test_next_incarnation(self):
        view = mem.MembershipView.bootstrap([0, 1], incarnations={1: 3})
        assert mem.next_incarnation(view, 1) == 4
        assert mem.next_incarnation(view, 9) == 0


# ---------------------------------------------------------------------------
# journal: crc'd append-only + torn tail + compact + bit-identical replay
# ---------------------------------------------------------------------------


class TestMembershipJournal:

    def _churn(self, journal_path):
        manager = mem.MembershipManager([0, 1, 2, 3],
                                        journal_path=journal_path)
        manager.member_down(2, reason="detector verdict")
        manager.member_join(2, reason="rejoin")
        manager.member_join(4, reason="grow")
        manager.close()
        return manager

    def test_journal_replays_bit_identically(self, tmp_path):
        journal_path = str(tmp_path / "membership.journal")
        manager = self._churn(journal_path)
        with open(journal_path, "rb") as f:
            original = f.read()
        assert manager.journal.journal_bytes() == original
        view = mem.replay(journal_path)
        assert view == manager.current_view()
        assert view.ranks == (0, 1, 2, 3, 4)
        assert view.incarnation(2) == 1  # died once, rejoined bumped

    def test_torn_tail_is_skipped_interior_corruption_raises(self, tmp_path):
        journal_path = str(tmp_path / "membership.journal")
        self._churn(journal_path)
        with open(journal_path, "ab") as f:
            f.write(b'{"torn":')  # crash mid-write
        view = mem.replay(journal_path)
        assert view.ranks == (0, 1, 2, 3, 4)
        # An interior bad line with intact lines after it is corruption.
        with open(journal_path, encoding="utf-8") as f:
            lines = f.read().splitlines()
        lines[1] = '{"forged": 1}'
        with open(journal_path, "w", encoding="utf-8") as f:
            f.write("\n".join(lines) + "\n")
        with pytest.raises(ValueError, match="interior corruption"):
            mem.replay(journal_path)

    def test_compact_collapses_to_one_snapshot(self, tmp_path):
        journal_path = str(tmp_path / "membership.journal")
        manager = self._churn(journal_path)
        expected = manager.current_view()
        manager.journal.compact()
        with open(journal_path, encoding="utf-8") as f:
            lines = [line for line in f.read().splitlines() if line]
        assert len(lines) == 1
        assert mem.replay(journal_path) == expected
        # A compacted journal keeps accepting transitions that replay.
        resumed = mem.MembershipManager(
            expected.ranks, journal_path=journal_path,
            incarnations=dict(expected.incarnations))
        resumed.member_down(4)
        resumed.close()

    def test_replay_detects_tampered_view(self, tmp_path):
        journal_path = str(tmp_path / "membership.journal")
        self._churn(journal_path)
        with open(journal_path, encoding="utf-8") as f:
            lines = f.read().splitlines()
        # Forge a whole VALID line (crc and all) whose view disagrees
        # with the fold: replay must catch the divergence, not the crc.
        forged_view = mem.MembershipView(view_id=99, ranks=(7,),
                                         incarnations=((7, 0),))
        lines[1] = mem.MembershipJournal.encode(
            mem.MembershipEvent("down", rank=2), forged_view)
        with open(journal_path, "w", encoding="utf-8") as f:
            f.write("\n".join(lines) + "\n")
        with pytest.raises(ValueError, match="diverged"):
            mem.replay(journal_path)

    def test_replay_rejects_crc_tamper_and_noop_records(self, tmp_path):
        journal_path = str(tmp_path / "membership.journal")
        manager = mem.MembershipManager([0, 1],
                                        journal_path=journal_path)
        manager.member_down(1)
        manager.close()
        with open(journal_path, encoding="utf-8") as f:
            lines = f.read().splitlines()
        # Flip a byte inside the first (crc'd) line: with an intact line
        # after it, load() must refuse — that is interior corruption.
        lines_tampered = ['X' + lines[0][1:]] + lines[1:]
        with open(journal_path, "w", encoding="utf-8") as f:
            f.write("\n".join(lines_tampered) + "\n")
        with pytest.raises(ValueError):
            mem.replay(journal_path)
        # A journaled NO-OP (downing an absent rank) is also a lie: the
        # manager never journals unchanged views.
        view = mem.MembershipView.bootstrap([0])
        noop_line = mem.MembershipJournal.encode(
            mem.MembershipEvent("down", rank=9), view)
        base_line = mem.MembershipJournal.encode(
            mem.MembershipEvent("bootstrap"), view)
        with open(journal_path, "w", encoding="utf-8") as f:
            f.write(base_line + "\n" + noop_line + "\n")
        with pytest.raises(ValueError):
            mem.replay(journal_path)

    def test_manager_never_journals_noops(self, tmp_path):
        journal_path = str(tmp_path / "membership.journal")
        manager = mem.MembershipManager([0, 1],
                                        journal_path=journal_path)
        view = manager.member_down(9)  # absent rank: no-op
        assert view.view_id == 0
        manager.close()
        with open(journal_path, encoding="utf-8") as f:
            lines = [line for line in f.read().splitlines() if line]
        assert len(lines) == 1  # bootstrap only


# ---------------------------------------------------------------------------
# failure detector: fake clock, zero sleeps
# ---------------------------------------------------------------------------


class _FakeClock:
    def __init__(self, now=100.0):
        self.now = now

    def __call__(self):
        return self.now


class TestFailureDetector:

    def _detector(self, **kwargs):
        clock = _FakeClock()
        events = []
        det = md.FailureDetector(
            [1], heartbeat_s=0.5, suspect_s=3.0, phi_threshold=4.0,
            clock=clock,
            on_suspect=lambda r: events.append(("suspect", r)),
            on_down=lambda r: events.append(("down", r)),
            on_alive=lambda r: events.append(("alive", r)), **kwargs)
        return det, clock, events

    def test_suspect_then_down_at_deadlines(self):
        det, clock, events = self._detector()
        for _ in range(4):
            clock.now += 0.5
            det.beat(1)
        assert det.state(1) == md.ALIVE
        # Silence: phi crosses the threshold first (SUSPECT), then the
        # hard suspect_s deadline declares DOWN.
        clock.now += 2.5  # phi = 2.5 / 0.5 = 5.0 >= 4.0
        det.poll()
        assert det.state(1) == md.SUSPECT
        assert events == [("suspect", 1)]
        clock.now += 0.6  # total silence 3.1 >= 3.0
        det.poll()
        assert det.state(1) == md.DOWN
        assert events == [("suspect", 1), ("down", 1)]
        # DOWN is final until revive: late beats are ignored.
        det.beat(1)
        assert det.state(1) == md.DOWN
        det.revive(1)
        assert det.state(1) == md.ALIVE

    def test_flapping_link_fires_once(self):
        det, clock, events = self._detector()
        # A steady 0.5s cadence pins the smoothed interval at the floor.
        for _ in range(15):
            clock.now += 0.5
            det.beat(1)
        clock.now += 2.5  # phi = 5.0 -> SUSPECT
        det.poll()
        assert events == [("suspect", 1)]
        clock.now += 0.1
        det.beat(1)  # recovers -> alive, arms the hysteresis window
        assert events[-1] == ("alive", 1)
        # Re-suspicion INSIDE one suspect_s of the clear: a flap — the
        # suspect callback must NOT fire again.
        clock.now += 2.6
        transitions = det.poll()
        assert transitions == {1: "flap"}
        assert [e for e in events if e[0] == "suspect"] == [("suspect", 1)]
        # ...but the DOWN deadline is never delayed by the flapping.
        clock.now += 0.5
        det.poll()
        assert det.state(1) == md.DOWN

    def test_phi_scales_with_observed_cadence(self):
        det, clock, _ = self._detector()
        # A slow-but-steady 1s cadence widens the smoothed interval, so
        # the same absolute silence scores a lower phi.
        for _ in range(8):
            clock.now += 1.0
            det.beat(1)
        assert det.phi(1) == 0.0
        clock.now += 2.0
        assert det.phi(1) == pytest.approx(2.0)  # 2s / 1s cadence
        det2, clock2, _ = self._detector()
        for _ in range(8):
            clock2.now += 0.5
            det2.beat(1)
        clock2.now += 2.0
        assert det2.phi(1) == pytest.approx(4.0)  # 2s / 0.5s cadence

    def test_forget_drops_rank(self):
        det, clock, events = self._detector()
        det.forget(1)
        clock.now += 100.0
        assert det.poll() == {}
        assert events == []


# ---------------------------------------------------------------------------
# generation-fenced transport
# ---------------------------------------------------------------------------


class TestFencedTransport:

    def test_stale_incarnation_frame_fenced_loudly(self):
        world = tp.create_local_transports(2, recv_timeout_s=10.0)
        fenced = rt_metrics.counter("rsdl_member_fenced_frames_total",
                                    "frames rejected by the fence")
        before = fenced.value
        try:
            # The reborn generation announces incarnation 1; its frame
            # teaches the receiver the floor.
            world[0].announce(incarnation=1, view_id=1)
            world[0].send(1, (0, 0, 0), b"new-gen")
            assert world[1].recv(0, (0, 0, 0)) == b"new-gen"
            # A zombie pre-kill process (incarnation 0) resends: the
            # frame is read off the socket, dropped, and counted — and
            # the stream is NOT corrupted.
            world[0].announce(incarnation=0, view_id=1)
            world[0].send(1, (0, 1, 0), b"zombie")
            world[0].announce(incarnation=1, view_id=1)
            world[0].send(1, (0, 2, 0), b"after")
            assert world[1].recv(0, (0, 2, 0)) == b"after"
            assert fenced.value == before + 1
            with pytest.raises(tp.TransportTimeout):
                world[1].recv(0, (0, 1, 0), timeout_s=0.2)
        finally:
            for t in world:
                t.close()

    def test_view_fence_rejects_old_world_stragglers(self):
        world = tp.create_local_transports(2, recv_timeout_s=10.0)
        try:
            world[1].fence_view(2)
            world[0].set_view(1)  # straggler from the pre-resize world
            world[0].send(1, (0, 0, 0), b"old")
            world[0].set_view(2)
            world[0].send(1, (0, 1, 0), b"current")
            assert world[1].recv(0, (0, 1, 0)) == b"current"
            with pytest.raises(tp.TransportTimeout):
                world[1].recv(0, (0, 0, 0), timeout_s=0.2)
        finally:
            for t in world:
                t.close()

    def test_heartbeats_feed_observer_and_never_inbox(self):
        world = tp.create_local_transports(2, recv_timeout_s=10.0)
        seen = []
        got = threading.Event()

        def observe(src, incarnation, view, is_heartbeat):
            seen.append((src, incarnation, view, is_heartbeat))
            got.set()

        try:
            world[1].set_frame_observer(observe)
            world[0].announce(incarnation=2, view_id=3)
            world[0].send_heartbeat(1)
            assert got.wait(5.0)
            assert seen[0] == (0, 2, 3, True)
            assert world[1]._inbox == {}  # control frames never inboxed
            # Data frames piggyback a heartbeat observation too.
            got.clear()
            world[0].send(1, (0, 0, 0), b"data")
            assert world[1].recv(0, (0, 0, 0)) == b"data"
            assert (0, 2, 3, False) in seen
        finally:
            for t in world:
                t.close()

    def test_connect_unreachable_peer_structured(self):
        # Port 1 is unbindable/unroutable: the dial must fail fast with
        # a STRUCTURED error naming the peer — the old behavior raised a
        # bare OSError with no indication of which peer was down.
        addresses = [("127.0.0.1", 0), ("127.0.0.1", 1)]
        transport = tp.TcpTransport(0, addresses, recv_timeout_s=5.0)
        transport.start()
        transport.addresses[0] = ("127.0.0.1", transport.bound_port())
        try:
            with pytest.raises(tp.PeerUnreachable) as excinfo:
                transport.connect(retries=1, initial_backoff_s=0.01)
            assert excinfo.value.peer == 1
            assert excinfo.value.attempts == 2
            assert "peer 1" in str(excinfo.value)
            # skip mode: a dead peer is a view fact, not a fatal error.
            unreachable = transport.connect(retries=1,
                                            initial_backoff_s=0.01,
                                            on_unreachable="skip")
            assert unreachable == [1]
            with pytest.raises(ValueError, match="raise|skip"):
                transport.connect(on_unreachable="explode")
        finally:
            transport.close()


# ---------------------------------------------------------------------------
# chaos grammar: the member_* sites
# ---------------------------------------------------------------------------


class TestMemberChaosSites:

    def test_rank_selector_parses_as_task(self):
        injector = rt_faults.install("member_crash@0.5:rank2", seed=0)
        rule = injector.rules[0]
        assert rule.site == "member_crash"
        assert rule.rate == 0.5
        assert rule.task == 2
        rt_faults.clear()

    @pytest.mark.parametrize("site", ["member_crash", "member_partition",
                                      "member_flap"])
    def test_member_sites_known(self, site):
        assert site in rt_faults.SITES

    def test_member_crash_downs_rank_through_manager(self):
        rt_faults.install("member_crash:rank1:epoch0", seed=0)
        manager = mem.MembershipManager([0, 1, 2])
        assert manager.maybe_crash(0, 0) is False
        assert manager.maybe_crash(0, 1) is True
        assert manager.current_view().ranks == (0, 2)
        # Fire-once per (site, epoch, task): the dead stay dead, the
        # crash does not re-fire.
        assert manager.maybe_crash(0, 1) is False

    def test_member_partition_swallows_sends_silently(self):
        world = tp.create_local_transports(2, recv_timeout_s=10.0)
        try:
            rt_faults.install("member_partition:task1", seed=0)
            world[0].send(1, (0, 0, 0), b"lost")  # swallowed, no raise
            with pytest.raises(tp.TransportTimeout):
                world[1].recv(0, (0, 0, 0), timeout_s=0.2)
            rt_faults.clear()
            world[0].send(1, (0, 0, 0), b"healed")
            assert world[1].recv(0, (0, 0, 0)) == b"healed"
        finally:
            for t in world:
                t.close()


# ---------------------------------------------------------------------------
# elastic resize: shrink mid-epoch, grow at the boundary, bit-identical
# ---------------------------------------------------------------------------


class TestElasticResize:

    @pytest.mark.parametrize("num_reducers, crashed, newcomer", [
        (6, 2, 7),
        (8, 1, 4)])     # two reducers a rank: the growth leaves 4 x 2
    def test_shrink_recomputes_and_grow_is_bit_identical(
            self, tmp_path, num_reducers, crashed, newcomer):
        files = _make_files(str(tmp_path / "data"))
        fixed = me.ElasticShuffleRunner(
            files, num_reducers, seed=11,
            manager=mem.MembershipManager([0, 1, 2, 3])).run(2)

        survivors = tuple(r for r in range(4) if r != crashed)
        journal_path = str(tmp_path / "membership.journal")
        rt_faults.install(f"member_crash:rank{crashed}:epoch0", seed=0)
        manager = mem.MembershipManager([0, 1, 2, 3],
                                        journal_path=journal_path)
        runner = me.ElasticShuffleRunner(files, num_reducers, seed=11,
                                         manager=manager)
        epoch0 = runner.run_epoch(0)
        assert manager.current_view().ranks == survivors
        assert runner.last_stats["recomputed"] >= 1
        assert runner.last_stats["resize_stall_ms"] > 0.0
        # Grow past the original world at the boundary: the dead rank
        # rejoins at a bumped incarnation, plus a brand-new rank -> an
        # uneven 5-rank world.
        manager.member_join(crashed)
        manager.member_join(newcomer)
        epoch1 = runner.run_epoch(1)
        grown = manager.current_view()
        assert grown.ranks == tuple(sorted(survivors + (crashed, newcomer)))
        assert grown.incarnation(crashed) == 1
        manager.close()
        rt_faults.clear()
        hosts = list(plan_ir.reduce_placement(num_reducers,
                                              grown.ranks).values())
        assert len({hosts.count(r) for r in grown.ranks}) > 1, hosts

        # Placement moved; CONTENT did not (lineage purity), and every
        # row arrives once in either epoch.
        for before, after in zip(fixed, (epoch0, epoch1)):
            assert len(before) == len(after) == num_reducers
            assert all(a.equals(b) for a, b in zip(before, after))
            assert sorted(k for table in after
                          for k in table.column("key").to_pylist()) == \
                list(range(3 * 64))
        assert me.total_rows(epoch0) == me.total_rows(fixed[0])

        # The views this run went through, chaos verdict included, replay
        # from the journal to the live view, byte for byte.
        assert mem.replay(journal_path) == grown
        with open(journal_path, "rb") as f:
            assert manager.journal.journal_bytes() == f.read()

    def test_every_rank_dead_driver_backstop_completes(self, tmp_path):
        files = _make_files(str(tmp_path / "data"), num_files=2)
        rt_faults.install(
            "member_crash:rank0:epoch0,member_crash:rank1:epoch0", seed=0)
        manager = mem.MembershipManager([0, 1])
        runner = me.ElasticShuffleRunner(files, 4, seed=3,
                                         manager=manager)
        outputs = runner.run_epoch(0)  # the epoch NEVER ends with a hole
        assert len(outputs) == 4
        assert me.total_rows(outputs) == 2 * 64
        rt_faults.clear()

    def test_trainer_streams_follow_route_slices(self):
        outputs = [object() for _ in range(5)]
        streams = me.trainer_streams(outputs, 2)
        spans = plan_ir.route_slices(5, 2)
        assert [len(s) for s in streams] == \
            [stop - start for start, stop in spans]
        assert sum(streams, []) == outputs


# ---------------------------------------------------------------------------
# plan rewrite + streaming window resize + lease sweep
# ---------------------------------------------------------------------------


def test_rewrite_for_view_moves_dead_ranks_hosts():
    plan = plan_ir.build_epoch_plan(seed=1, epoch=0,
                                    filenames=["a", "b"],
                                    num_reducers=4, num_trainers=2)
    assert plan_sched.rewrite_for_view(plan, [0, 1, 2, 3]) == 0
    moved = plan_sched.rewrite_for_view(plan, [0, 2, 3])
    assert moved > 0
    placement = plan_ir.reduce_placement(4, [0, 2, 3])
    for node in plan.reduces():
        assert node.meta["host"] == placement[node.key.task]
        assert node.meta["host"] != 1


def test_epoch_spec_num_reducers_round_trips_through_dicts():
    spec = plan_ir.EpochSpec(epoch=3, filenames=("a",), num_reducers=6)
    plain = plan_ir.EpochSpec(epoch=4, filenames=("b",))
    dicts = st_window.specs_to_dicts([spec, plain])
    assert dicts[0]["num_reducers"] == 6
    assert dicts[1].get("num_reducers") is None
    back = st_window.specs_from_dicts(dicts)
    assert back[0].num_reducers == 6
    assert back[1].num_reducers is None


def test_reducers_for_view_scales_with_live_ranks():
    view = mem.MembershipView.bootstrap([0, 1, 2])
    assert mem.reducers_for_view(8, 4, view) == 6  # 2 per rank x 3
    lone = mem.MembershipView.bootstrap([0])
    assert mem.reducers_for_view(1, 4, lone) == 1  # floor 1
    with pytest.raises(ValueError):
        mem.reducers_for_view(8, 0, view)


def test_streaming_window_boundary_resize_exactly_once(tmp_path):
    """A member_crash at a window boundary retopologizes the NEXT
    window's reducer count; the merged stream still delivers every key
    exactly once (exactly-once is per-row_offset, not per-reducer)."""
    from ray_shuffling_data_loader_tpu import streaming as st

    files = []
    for i in range(8):
        table = pa.table({"key": pa.array(
            range(i * 32, (i + 1) * 32), type=pa.int64())})
        path = os.path.join(str(tmp_path), f"w_{i:03d}.parquet")
        pq.write_table(table, path)
        files.append(path)

    delivered = {}

    def consumer(rank, epoch, refs):
        if refs is None:
            return
        for ref in refs:
            table = ref.result() if hasattr(ref, "result") else ref
            delivered.setdefault(epoch, []).extend(
                table.column("key").to_pylist())

    rt_faults.install("member_crash:rank1:epoch1", seed=0)
    manager = mem.MembershipManager([0, 1, 2, 3])
    runner = st.StreamingShuffleRunner(
        st.SyntheticEventSource(files, seed=5, total_events=8),
        consumer, num_reducers=8, num_trainers=1, seed=5,
        policy=st.WindowPolicy(max_files=2), max_windows=4,
        membership=manager)
    runner.run()
    runner.close()
    rt_faults.clear()

    assert manager.current_view().ranks == (0, 2, 3)
    keys = [k for epoch in sorted(delivered) for k in delivered[epoch]]
    assert sorted(keys) == list(range(8 * 32))
    assert len(set(keys)) == len(keys)


def test_member_down_sweeps_leases_for_dead_rank(monkeypatch):
    """The detector's seconds-scale DOWN verdict beats the lease clock:
    notify_member_down force-expires exactly the leases holding the dead
    rank's queues."""
    monkeypatch.setenv("RSDL_QUEUE_ON_DEAD_CONSUMER", "drain")
    queue = mq.MultiQueue(2)
    server = svc.QueueServer(queue, ("127.0.0.1", 0), num_trainers=2)
    try:
        server._lease_beat(0xA, plan_ir.queue_index(0, 0, 2))
        server._lease_beat(0xB, plan_ir.queue_index(0, 1, 2))
        manager = mem.MembershipManager([0, 1])
        server.attach_membership(manager)
        manager.member_down(0, reason="detector verdict")
        with server._lease_lock:
            assert server._leases[0xA].expired
            assert not server._leases[0xB].expired
    finally:
        server.close()
        queue.shutdown(force=True)
