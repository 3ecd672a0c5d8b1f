"""runtime/ subsystem: watchdog supervision, release events, policy.

Covers the two regression scenarios the subsystem exists for:
a wedged bulk ``device_put`` must degrade to the per-batch path with
every batch still delivered in order (no hang, no loss), and a consumer
releasing a table must wake a budget-blocked epoch launch immediately —
event-driven, with no ``gc.collect()`` anywhere in the wait path.
"""

import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from ray_shuffling_data_loader_tpu import jax_dataset as jd
from ray_shuffling_data_loader_tpu import multiqueue as mq
from ray_shuffling_data_loader_tpu import native
from ray_shuffling_data_loader_tpu import stats as stats_mod
from ray_shuffling_data_loader_tpu.runtime import policy, release, watchdog
from ray_shuffling_data_loader_tpu.spill import make_budget_state


@pytest.fixture(autouse=True)
def fresh_registry():
    mq._REGISTRY.clear()
    yield
    mq._REGISTRY.clear()


# ---------------------------------------------------------------------------
# policy
# ---------------------------------------------------------------------------


def test_policy_defaults_and_unknown_keys():
    assert policy.resolve("anything", "device_rebatch") == "auto"
    assert policy.resolve("anything", "stall_action") == "degrade"
    with pytest.raises(ValueError):
        policy.resolve("anything", "no_such_knob")
    with pytest.raises(ValueError):
        policy.resolve_all("anything", no_such_knob=1)


def test_policy_env_precedence(monkeypatch):
    monkeypatch.setenv("RSDL_BULK_TRANSFER_DEADLINE_S", "7.5")
    assert policy.resolve("jax_dataset",
                          "bulk_transfer_deadline_s") == 7.5
    # Component-scoped env beats the global env.
    monkeypatch.setenv("RSDL_JAX_DATASET_BULK_TRANSFER_DEADLINE_S", "2.0")
    assert policy.resolve("jax_dataset",
                          "bulk_transfer_deadline_s") == 2.0
    assert policy.resolve("shuffle", "bulk_transfer_deadline_s") == 7.5
    # Explicit kwarg beats both.
    assert policy.resolve("jax_dataset", "bulk_transfer_deadline_s",
                          override=1.25) == 1.25


def test_policy_global_device_rebatch_is_the_library_default(monkeypatch):
    """RSDL_DEVICE_REBATCH=0 forces the per-batch path for every
    component; a component-scoped name wins for that component only."""
    monkeypatch.setenv("RSDL_DEVICE_REBATCH", "0")
    assert policy.resolve("jax_dataset", "device_rebatch") is False
    monkeypatch.setenv("RSDL_JAX_DATASET_DEVICE_REBATCH", "auto")
    assert policy.resolve("jax_dataset", "device_rebatch") == "auto"
    assert policy.resolve("shuffle", "device_rebatch") is False


def test_policy_register_defaults_env_still_wins(monkeypatch):
    policy.register_defaults("test_component", trim_cooldown_s=3.0)
    assert policy.resolve("test_component", "trim_cooldown_s") == 3.0
    monkeypatch.setenv("RSDL_TEST_COMPONENT_TRIM_COOLDOWN_S", "9.0")
    assert policy.resolve("test_component", "trim_cooldown_s") == 9.0


# ---------------------------------------------------------------------------
# release events
# ---------------------------------------------------------------------------


def test_notify_wakes_wait_while_immediately():
    """The heartbeat is set far above the asserted latency, so the wake
    can only come from the release event itself."""
    flag = [True]
    woken = []

    def waiter():
        start = time.monotonic()
        ok = release.wait_while(lambda: flag[0], timeout_s=10.0,
                                heartbeat_s=5.0)
        woken.append((ok, time.monotonic() - start))

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    time.sleep(0.1)  # let the waiter block
    flag[0] = False
    release.notify_release()
    t.join(timeout=5)
    assert not t.is_alive()
    ok, elapsed = woken[0]
    assert ok
    assert elapsed < 1.0  # event wake, not the 5s heartbeat


def test_ledger_decref_notifies_release():
    ledger = native.buffer_ledger()
    before = release.release_seq()
    buf_id = ledger.register(4096)
    ledger.decref(buf_id)
    assert release.release_seq() > before


def test_table_release_wakes_blocked_budget_wait_without_gc():
    """The satellite regression: a consumer dropping its table must wake
    a budget-blocked epoch launch within ~10ms, with no gc.collect
    anywhere (the table is cycle-free, so the finalizer fires on the
    refcount drop and the decref notifies the waiter)."""
    over_budget, _ = make_budget_state(None, max_inflight_bytes=1,
                                       spill_dir=None)
    table = pa.table({"x": np.arange(200_000, dtype=np.int64)})
    native.account_table(table)
    assert over_budget()

    released_at = []
    woken = []

    def waiter():
        ok = release.wait_while(over_budget, timeout_s=10.0,
                                heartbeat_s=5.0)
        woken.append((ok, time.monotonic()))

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    time.sleep(0.1)
    released_at.append(time.monotonic())
    del table  # consumer done: finalize -> decref -> notify
    t.join(timeout=5)
    assert not t.is_alive()
    ok, woke_at = woken[0]
    assert ok and not over_budget()
    # Event-driven wake: far under both the 5s heartbeat and the old
    # ~1s gc.collect cadence. 250ms bound absorbs CI scheduling jitter;
    # the typical latency is sub-millisecond.
    assert woke_at - released_at[0] < 0.25


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------


def test_watchdog_fires_and_escalates():
    wd = watchdog.Watchdog(poll_interval_s=0.01)
    stalls = []
    before = stats_mod.watchdog_stats().snapshot()
    with wd.watch("test.slow_step", deadline_s=0.05,
                  on_stall=stalls.append,
                  detail_fn=lambda: "queue_depth=0") as handle:
        time.sleep(0.3)
    assert handle.stalled
    assert handle.escalations >= 2  # 0.3s across a 0.05s deadline
    assert stalls and stalls[0].name == "test.slow_step"
    assert stalls[0].escalation == 1
    assert stalls[0].detail == "queue_depth=0"
    after = stats_mod.watchdog_stats().snapshot()
    assert after["watchdog_events"] - before["watchdog_events"] >= 2
    assert (after["stall_escalations"]
            - before["stall_escalations"]) >= 1


def test_watchdog_beat_resets_deadline():
    wd = watchdog.Watchdog(poll_interval_s=0.01)
    with wd.watch("test.heartbeat", deadline_s=0.15) as handle:
        for _ in range(4):
            time.sleep(0.05)
            handle.beat()
    assert not handle.stalled


def test_watchdog_fast_step_never_flagged():
    wd = watchdog.Watchdog(poll_interval_s=0.01)
    with wd.watch("test.fast", deadline_s=5.0) as handle:
        pass
    assert not handle.stalled and handle.report is None


# ---------------------------------------------------------------------------
# the stalled-transfer regression (tentpole wiring)
# ---------------------------------------------------------------------------


def _write_files(tmp_path, num_files=2, rows_per_file=128):
    filenames = []
    for i in range(num_files):
        n = rows_per_file
        rng = np.random.default_rng(i)
        table = pa.table({
            "key": pa.array(range(i * n, (i + 1) * n), type=pa.int64()),
            "emb": pa.array(rng.integers(0, 100, n), type=pa.int64()),
            "labels": pa.array(rng.random(n), type=pa.float64()),
        })
        path = str(tmp_path / f"input_{i}.parquet")
        pq.write_table(table, path)
        filenames.append(path)
    return filenames


def _make_ds(filenames, qname, device_rebatch, runtime_policy=None,
             num_epochs=2):
    return jd.JaxShufflingDataset(
        filenames, num_epochs=num_epochs, num_trainers=1, batch_size=16,
        rank=0, feature_columns=["emb"], feature_types=[np.int32],
        label_column="labels", num_reducers=2, seed=5,
        queue_name=qname, device_rebatch=device_rebatch,
        runtime_policy=runtime_policy)


def _drain(ds, num_epochs=2):
    labels = []
    for epoch in range(num_epochs):
        ds.set_epoch(epoch)
        for features, label in ds:
            labels.append(np.asarray(label).ravel().copy())
    return labels


def test_wedged_bulk_transfer_degrades_and_loses_nothing(tmp_path):
    """Simulated wedged bulk device_put: the watchdog fires while the
    consumer is blocked, the producer auto-degrades to the per-batch
    path with a logged reason, and the consumer still receives every
    batch — bit-identical, in order, both epochs."""
    filenames = _write_files(tmp_path)
    before = stats_mod.watchdog_stats().snapshot()

    ds = _make_ds(filenames, "runtime-wedged", device_rebatch=True,
                  runtime_policy={"bulk_transfer_deadline_s": 0.05})
    assert ds._converter.watchdog is not None
    orig = ds._converter.transfer_table
    wedged_once = []

    def wedged(arrays_label, n_batches, batch_size):
        if not wedged_once:
            wedged_once.append(True)
            time.sleep(0.5)  # 10x the deadline: the watchdog must fire
        return orig(arrays_label, n_batches, batch_size)

    ds._converter.transfer_table = wedged
    got = _drain(ds)

    reference = _make_ds(filenames, "runtime-reference",
                         device_rebatch=False)
    want = _drain(reference)

    assert len(got) == len(want) == 2 * (256 // 16)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)

    # The fallback engaged and is permanent for this dataset.
    assert ds._converter.device_rebatch is False
    assert ds._converter.fallback_engaged
    after = stats_mod.watchdog_stats().snapshot()
    assert after["watchdog_events"] > before["watchdog_events"]
    assert after["fallbacks_engaged"] > before["fallbacks_engaged"]
    names = [s["name"] for s in after["recent_stalls"]]
    assert "jax_dataset.bulk_transfer" in names


def test_stall_action_warn_keeps_bulk_path(tmp_path):
    """stall_action="warn": the stall is recorded and bulk bytes capped,
    but the bulk path keeps running (operator opted out of degrade)."""
    filenames = _write_files(tmp_path)
    ds = _make_ds(filenames, "runtime-warn", device_rebatch=True,
                  runtime_policy={"bulk_transfer_deadline_s": 0.05,
                                  "stall_action": "warn"})
    cap_before = ds._converter.max_table_bytes
    orig = ds._converter.transfer_table
    wedged_once = []

    def wedged(arrays_label, n_batches, batch_size):
        if not wedged_once:
            wedged_once.append(True)
            time.sleep(0.3)
        return orig(arrays_label, n_batches, batch_size)

    ds._converter.transfer_table = wedged
    got = _drain(ds)
    assert len(got) == 2 * (256 // 16)
    assert ds._converter.device_rebatch is True
    assert not ds._converter.fallback_engaged
    assert ds._converter.max_table_bytes < cap_before  # in-flight cap


def test_healthy_bulk_path_untouched_by_watchdog(tmp_path):
    """No stall: the supervised bulk path produces the identical stream
    and engages no fallback."""
    filenames = _write_files(tmp_path)
    ds = _make_ds(filenames, "runtime-healthy", device_rebatch=True,
                  runtime_policy={"bulk_transfer_deadline_s": 30.0})
    got = _drain(ds)
    reference = _make_ds(filenames, "runtime-healthy-ref",
                         device_rebatch=False)
    want = _drain(reference)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert ds._converter.device_rebatch is True
    assert not ds._converter.fallback_engaged


def test_watchdog_disabled_by_policy(tmp_path):
    filenames = _write_files(tmp_path)
    ds = _make_ds(filenames, "runtime-nowd", device_rebatch=True,
                  runtime_policy={"watchdog": False})
    try:
        assert ds._converter.watchdog is None
    finally:
        ds.close()
