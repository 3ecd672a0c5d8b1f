"""rsdl-lint: one positive + one negative fixture per rule, framework
behavior (pragmas, baseline, CLI/exit codes), and a clean run over the
real tree.

Fixtures live in string literals, which the analyzer's AST walk never
sees when it scans THIS file — so seeding a violation here cannot fail
the real-tree gate below.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from ray_shuffling_data_loader_tpu.analysis import baseline as baseline_mod
from ray_shuffling_data_loader_tpu.analysis import cli, core

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: The trees the format.sh gate runs over.
GATE_PATHS = ["ray_shuffling_data_loader_tpu", "tests", "benchmarks",
              "examples", "chip_smoke.py", "__graft_entry__.py", "tools"]


def lint(source, path="pkg/mod.py", **config_kwargs):
    config = core.Config(**config_kwargs) if config_kwargs else None
    violations = core.check_source(textwrap.dedent(source), path, config)
    return [v.rule for v in violations], violations


# ---------------------------------------------------------------------------
# Rule fixtures: (rule id, flagged source, clean source)
# ---------------------------------------------------------------------------

LOCK_MUTATION_BAD = """
    import threading

    class Cache:
        def __init__(self):
            self._lock = threading.Lock()
            self._bytes = 0

        def put(self, n):
            with self._lock:
                self._bytes += n

        def reset(self):
            self._bytes = 0  # unguarded write to a guarded attribute
"""

LOCK_MUTATION_OK = """
    import threading

    class Cache:
        def __init__(self):
            self._lock = threading.Lock()
            self._bytes = 0

        def put(self, n):
            with self._lock:
                self._bytes += n

        def reset(self):
            with self._lock:
                self._bytes = 0
"""

LOCK_BLOCKING_BAD = """
    import threading

    class Pipeline:
        def __init__(self, queue):
            self._lock = threading.Lock()
            self._queue = queue

        def drain(self, ref):
            with self._lock:
                table = ref.result()
                item = self._queue.get(0)
            return table, item
"""

LOCK_BLOCKING_OK = """
    import threading

    class Pipeline:
        def __init__(self, queue):
            self._lock = threading.Lock()
            self._queue = queue

        def drain(self, ref):
            table = ref.result()
            item = self._queue.get(0, timeout=5.0)
            with self._lock:
                self._held = (table, item)
            return table, item
"""

ONESHOT_BAD = """
    def reduce_task(transport, tag):
        payload = transport.recv(0, tag)
        return payload

    def launch(pool, transport, tag):
        return pool.submit(reduce_task, transport, tag)
"""

ONESHOT_OK = """
    def reduce_task(transport, tag):
        payload = transport.recv(0, tag)
        return payload

    def launch(pool, transport, tag):
        return pool.submit_once(reduce_task, transport, tag)
"""

UNSEEDED_BAD = """
    import numpy as np

    def assign(num_rows, num_reducers):
        return np.random.randint(num_reducers, size=num_rows)
"""

UNSEEDED_OK = """
    import numpy as np

    def assign(num_rows, num_reducers, seed, epoch, task):
        rng = np.random.Generator(np.random.Philox(
            np.random.SeedSequence(entropy=seed,
                                   spawn_key=(epoch, task))))
        return rng.integers(num_reducers, size=num_rows)
"""

HOST_SYNC_JIT_BAD = """
    import jax
    import numpy as np

    @jax.jit
    def step(x):
        scale = float(x.sum())  # trace-time host sync
        return x * scale
"""

HOST_SYNC_LOOP_BAD = """
    def producer(dataset, out):
        for batch in dataset:
            batch.block_until_ready()
            out.put(batch)
"""

HOST_SYNC_OK = """
    import jax

    @jax.jit
    def step(x):
        return x * x.sum()

    def producer(dataset, out):
        for batch in dataset:
            out.put(batch)
"""

DEVICE_PUT_BAD = """
    import jax

    def ship(batch):
        return jax.device_put(batch)
"""

DEVICE_PUT_OK = """
    import jax

    def ship(batch, sharding):
        return jax.device_put(batch, sharding)
"""

CONCAT_BAD = """
    import pyarrow as pa

    def rebatch(carry):
        return pa.concat_tables(carry)
"""

CONCAT_OK = """
    import pyarrow as pa

    def rebatch(carry):
        return pa.concat_tables(carry, promote_options="permissive")
"""

ZERO_COPY_BAD = """
    def to_host(column):
        return column.to_numpy(zero_copy_only=True)
"""

ZERO_COPY_OK = """
    def to_host(column):
        return column.combine_chunks().to_numpy(zero_copy_only=False)
"""

SWALLOWED_BAD = """
    def worker(queue):
        try:
            queue.put(1)
        except Exception:
            pass
"""

SWALLOWED_OK = """
    def worker(queue, logger):
        try:
            queue.put(1)
        except OSError:
            pass  # narrow, best-effort cleanup
        except Exception as e:
            logger.exception("worker failed: %s", e)
            raise
"""

GC_WAIT_BAD = """
    import gc
    import time

    def wait_for_budget(over_budget, deadline):
        while over_budget():
            gc.collect()  # flush cycle-stuck frees every poll tick
            if time.monotonic() >= deadline:
                break
            time.sleep(0.02)
"""

GC_WAIT_OK = """
    import gc

    def wait_for_budget(over_budget, timeout_s, release):
        gc.collect()  # one-off, outside any wait loop: not flagged
        return release.wait_while(over_budget, timeout_s=timeout_s)
"""

RETRY_WHILE_BAD = """
    import time

    def fetch(conn):
        while True:
            try:
                return conn.fetch()
            except ConnectionError:
                time.sleep(1.0)
"""

RETRY_FIXED_SLEEP_BAD = """
    import time

    def fetch(conn, retries):
        for _ in range(retries):
            try:
                return conn.fetch()
            except ConnectionError:
                time.sleep(0.5)  # fixed interval: lockstep re-dial
"""

RETRY_OK = """
    from ray_shuffling_data_loader_tpu.runtime.retry import RetryPolicy

    def fetch(conn):
        # the sanctioned shape: bounded attempts, jittered backoff
        return RetryPolicy.for_component("queue").call(conn.fetch)

    def drain(queue, out):
        while True:  # drain loop, not a retry: the handler exits
            try:
                out.append(queue.get_nowait())
            except LookupError:
                return
"""

WALLCLOCK_DIRECT_BAD = """
    import time

    def wait_for(predicate, timeout_s):
        deadline = time.time() + timeout_s  # deadline on wall clock
        while not predicate():
            if time.time() - deadline > 0:
                return False
        return True
"""

WALLCLOCK_VAR_BAD = """
    import time

    def measure(fn):
        start = time.time()
        fn()
        return time.monotonic() - start  # mixes clocks via the variable
"""

WALLCLOCK_OK = """
    import time

    def sample():
        # serialized timestamp, no interval arithmetic: not flagged
        return {"timestamp": time.time()}

    def measure(fn):
        start = time.monotonic()
        fn()
        return time.monotonic() - start
"""

SOCKET_TIMEOUT_BAD = """
    import socket

    def serve(address):
        listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        listener.bind(address)
        listener.listen(4)
        conn, peer = listener.accept()  # blocks forever on a wedged peer
        return conn.recv(1024)
"""

SOCKET_TIMEOUT_OK = """
    import socket

    def serve(address, timeout_s):
        listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        listener.settimeout(1.0)
        listener.bind(address)
        listener.listen(4)
        conn, peer = listener.accept()
        # settimeout(None) would also count: an EXPLICIT infinite wait
        # is a reviewed decision, the silent default is the bug.
        conn.settimeout(timeout_s)
        return conn.recv(1024)

    def dial(address):
        sock = socket.create_connection(address, timeout=30)
        return sock.recv(4)
"""

SPAN_NO_END_BAD = """
    from ray_shuffling_data_loader_tpu.runtime import telemetry

    def drain(queue, epoch):
        token = telemetry.span_begin("queue_wait", epoch=epoch)
        item = queue.get()  # a raising get() loses the span forever
        return item
"""

SPAN_NO_FINALLY_BAD = """
    from ray_shuffling_data_loader_tpu.runtime import telemetry

    def drain(queue, epoch):
        token = telemetry.span_begin("queue_wait", epoch=epoch)
        item = queue.get()
        telemetry.span_end(token)  # skipped when get() raises
        return item
"""

SPAN_BALANCED_OK = """
    from ray_shuffling_data_loader_tpu.runtime import telemetry

    def drain(queue, epoch):
        token = telemetry.span_begin("queue_wait", epoch=epoch)
        try:
            return queue.get()
        finally:
            telemetry.span_end(token)

    def open_wait_span(epoch):
        # Token handed to the caller: the close obligation moves with it.
        return telemetry.span_begin("queue_wait", epoch=epoch)
"""

COPY_HOT_PATH_BAD = """
    import numpy as np

    def gather(table, perm, dtype):
        col = table.column("x")
        arr = col.to_numpy(zero_copy_only=False)
        combined = col.combine_chunks()
        return arr[perm].astype(dtype)
"""

COPY_HOT_PATH_OK = """
    import numpy as np

    def gather(table, perm, dtype):
        col = table.column("x")
        # Blessed cached site. rsdl-lint: disable=copy-in-hot-path
        arr = col.to_numpy(zero_copy_only=False)
        plain = col.to_numpy()  # zero_copy_only defaults to True
        return arr[perm].astype(dtype, copy=False)
"""

COPY_HOT_PATH_OTHER_FILE_OK = """
    def gather(table, perm, dtype):
        arr = table.column("x").to_numpy(zero_copy_only=False)
        return arr[perm].astype(dtype)
"""

UNREGISTERED_METRIC_BAD = """
    from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics

    def wire():
        rt_metrics.counter("rsdl_made_up_total", "not in the catalog").inc()
"""

UNREGISTERED_METRIC_OK = """
    from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics

    def wire(depth):
        # catalog names pass; derived histogram series resolve through
        # their base name; test_*/dynamic names are out of scope
        rt_metrics.gauge("rsdl_queue_depth", "d", queue="0").set(depth)
        rt_metrics.get("rsdl_stage_seconds_count")
        rt_metrics.counter("test_probe_total", "t").inc()
        name = "rsdl_dynamic"
        rt_metrics.get(name)
"""

METRIC_LABEL_CARD_BAD = """
    from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics

    def serve(task_id, seq):
        # task/seq are unbounded identities — one child series per value
        rt_metrics.counter("rsdl_queue_frames_replayed_total", "r",
                           task=str(task_id)).inc()
        rt_metrics.sketch("rsdl_delivery_latency_seconds", "lat",
                          hop="birth_to_delivered",
                          seq=str(seq)).observe(0.1)
"""

METRIC_LABEL_CARD_OK = """
    from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics

    def serve(shard_index, rank):
        # catalog-declared labels pass, whatever expression builds the
        # value; histogram config kwargs are not labels; uncataloged
        # names are unregistered-metric's finding, not this rule's
        rt_metrics.counter("rsdl_queue_handle_hits_total", "h",
                           shard=str(shard_index)).inc()
        rt_metrics.sketch("rsdl_delivery_latency_seconds", "lat",
                          hop="birth_to_delivered",
                          queue=str(rank)).observe(0.1)
        rt_metrics.histogram("rsdl_batch_wait_seconds", "w",
                             buckets=(0.1, 1.0)).observe(0.2)
"""

LINEAGE_PLAN_ROUTE_BAD = """
    def route(epoch, rank, num_trainers):
        return epoch * num_trainers + rank
"""

LINEAGE_PLAN_INVERSE_BAD = """
    class Server:
        def epoch_of(self, queue_idx):
            return queue_idx // self._num_trainers
"""

LINEAGE_PLAN_SEEDSEQ_BAD = """
    import numpy as np

    def my_rng(seed, epoch, task):
        seq = np.random.SeedSequence(entropy=seed,
                                     spawn_key=(epoch, task))
        return np.random.Generator(np.random.Philox(seq))
"""

LINEAGE_PLAN_OK = """
    from ray_shuffling_data_loader_tpu.plan import ir as plan_ir

    def route(epoch, rank, num_trainers):
        # plan queries, and non-route arithmetic, both pass
        host = rank // 4
        return plan_ir.queue_index(epoch, rank, num_trainers), host
"""

BYTES_CONCAT_AUG_BAD = """
    def read_all(sock, n):
        buf = b""
        while len(buf) < n:
            buf += sock.recv(n - len(buf))
        return buf
"""

BYTES_CONCAT_REBIND_BAD = """
    def join_frames(frames):
        out = bytes()
        for frame in frames:
            out = out + frame.payload
        return out
"""

BYTES_CONCAT_OK = """
    def read_all(sock, n):
        # bytearray accumulates in place; join pays one copy total
        buf = bytearray()
        while len(buf) < n:
            buf += sock.recv(n - len(buf))
        chunks = []
        for _ in range(3):
            chunks.append(sock.recv(n))
        total = 0
        for chunk in chunks:
            total += len(chunk)  # int +=, not a bytes accumulator
        return bytes(buf) + b"".join(chunks)
"""

SENDALL_LOOP_BAD = """
    def send_frames(conn, frames):
        for frame in frames:
            conn.sendall(frame.header)
            conn.sendall(frame.payload)
"""

SENDALL_LOOP_OK = """
    def send_frames(conn, frames):
        vecs = []
        for frame in frames:
            vecs.append(frame.header)
            vecs.append(frame.payload)
        _sendmsg_all(conn, vecs)

    def heartbeat(sock, stop):
        # while-loop protocol exchange: one message per beat, nothing
        # to gather — deliberately not flagged
        while not stop.is_set():
            sock.sendall(b"ping")
"""

RAW_DATASET_READ_BAD = """
    import pyarrow.parquet as pq

    def load(path):
        table = pq.read_table(path)
        meta = pq.ParquetFile(path)
        return table, meta
"""

RAW_DATASET_READ_OK = """
    from ray_shuffling_data_loader_tpu import storage

    def load(path, epoch, task):
        table = storage.read_table(path, epoch=epoch, task=task)
        meta = storage.open_parquet(path, epoch=epoch, task=task)
        return table, meta
"""

STATIC_EPOCH_RANGE_BAD = """
    def drive(dataset):
        for epoch in range(dataset.num_epochs):
            dataset.set_epoch(epoch)
"""

STATIC_EPOCH_SUBSCRIPT_BAD = """
    def first_window_refs(epoch_refs):
        return epoch_refs[0]
"""

STATIC_EPOCH_OK = """
    from ray_shuffling_data_loader_tpu.plan import ir as plan_ir

    def drive(dataset, epoch_refs):
        # plan-derived epoch sequence; dynamic per-epoch indexing
        for epoch in plan_ir.epoch_range(dataset.start_epoch,
                                         dataset.num_epochs):
            dataset.set_epoch(epoch)
            current = epoch_refs[epoch]
        for step in range(3):  # non-epoch ranges pass
            pass
        return current
"""

FIXED_WORLD_RANGE_BAD = """
    def fan_out(self):
        for rank in range(self.world):
            self.submit(rank)
        for peer in range(len(self.addresses)):
            self.dial(peer)
"""

FIXED_WORLD_SCALE_BAD = """
    def shares(self, total, world):
        per_rank = total // world
        owner = total % world
        return per_rank, owner
"""

FIXED_WORLD_OK = """
    from ray_shuffling_data_loader_tpu.plan import ir as plan_ir

    def fan_out(self, view, num_reducers):
        # live ranks from the membership view; shares via plan/
        placement = plan_ir.reduce_placement(num_reducers, view.ranks)
        for rank in view.ranks:
            self.submit(rank)
        for step in range(3):  # non-world ranges pass
            pass
        return placement
"""

SHARD_AFFINITY_MOD_BAD = """
    def route(self, rank):
        # static placement formula: stale after a live migration
        shard = rank % self.num_shards
        return shard
"""

SHARD_AFFINITY_ADDR_BAD = """
    def __init__(self, shard_map, queue_idx):
        # caches a (host, port) a committed migration invalidates
        shard = shard_map.shard_for_queue(queue_idx)
        self._addr = shard_map.addresses[shard]
"""

SHARD_AFFINITY_OK = """
    from ray_shuffling_data_loader_tpu.plan import ir as plan_ir

    def route(self, shard_map, queue_idx):
        # placement + address queried from the live shard map per call
        shard = shard_map.shard_for_queue(queue_idx)
        host, port = shard_map.address_for_queue(queue_idx)
        return shard, (host, port)
"""

TENANT_BYPASS_BAD = """
    def register(self, kind, name, nbytes):
        # A shared-plane entry point admitting work with no idea whose
        # work it is: lands on the default ledger, dodges fair-share.
        self._ledger[name] = nbytes
        return True
"""

TENANT_BYPASS_PARAM_OK = """
    def register(self, tenant, kind, name, nbytes):
        self._ledger[(tenant.tenant_id, name)] = nbytes
        return True
"""

TENANT_BYPASS_AMBIENT_OK = """
    from ray_shuffling_data_loader_tpu import tenancy

    def register(self, kind, name, nbytes):
        ctx = tenancy.current_tenant()
        self._ledger[(ctx.tenant_id, name)] = nbytes
        return True
"""

CASES = [
    ("lock-mutation", LOCK_MUTATION_BAD, LOCK_MUTATION_OK, {}),
    ("lock-blocking-call", LOCK_BLOCKING_BAD, LOCK_BLOCKING_OK, {}),
    ("oneshot-submit", ONESHOT_BAD, ONESHOT_OK, {}),
    ("unseeded-random", UNSEEDED_BAD, UNSEEDED_OK, {}),
    ("jax-host-sync", HOST_SYNC_JIT_BAD, HOST_SYNC_OK, {}),
    ("jax-host-sync", HOST_SYNC_LOOP_BAD, HOST_SYNC_OK, {}),
    ("device-put-unsharded", DEVICE_PUT_BAD, DEVICE_PUT_OK,
     {"path": "pkg/parallel/mod.py"}),
    ("arrow-concat-promote", CONCAT_BAD, CONCAT_OK, {}),
    ("arrow-zero-copy", ZERO_COPY_BAD, ZERO_COPY_OK, {}),
    ("swallowed-exception", SWALLOWED_BAD, SWALLOWED_OK, {}),
    ("gc-collect-in-wait", GC_WAIT_BAD, GC_WAIT_OK, {}),
    ("unbounded-retry", RETRY_WHILE_BAD, RETRY_OK, {}),
    ("unbounded-retry", RETRY_FIXED_SLEEP_BAD, RETRY_OK, {}),
    ("wallclock-interval", WALLCLOCK_DIRECT_BAD, WALLCLOCK_OK, {}),
    ("wallclock-interval", WALLCLOCK_VAR_BAD, WALLCLOCK_OK, {}),
    ("socket-op-no-timeout", SOCKET_TIMEOUT_BAD, SOCKET_TIMEOUT_OK, {}),
    ("span-unbalanced", SPAN_NO_END_BAD, SPAN_BALANCED_OK, {}),
    ("span-unbalanced", SPAN_NO_FINALLY_BAD, SPAN_BALANCED_OK, {}),
    ("copy-in-hot-path", COPY_HOT_PATH_BAD, COPY_HOT_PATH_OK,
     {"path": "pkg/shuffle.py"}),
    ("bytes-concat-in-loop", BYTES_CONCAT_AUG_BAD, BYTES_CONCAT_OK, {}),
    ("bytes-concat-in-loop", BYTES_CONCAT_REBIND_BAD, BYTES_CONCAT_OK, {}),
    ("sendall-in-loop", SENDALL_LOOP_BAD, SENDALL_LOOP_OK, {}),
    ("unregistered-metric", UNREGISTERED_METRIC_BAD, UNREGISTERED_METRIC_OK,
     {"path": "ray_shuffling_data_loader_tpu/multiqueue.py"}),
    ("metric-label-cardinality", METRIC_LABEL_CARD_BAD,
     METRIC_LABEL_CARD_OK,
     {"path": "ray_shuffling_data_loader_tpu/multiqueue_service.py"}),
    ("lineage-outside-plan", LINEAGE_PLAN_ROUTE_BAD, LINEAGE_PLAN_OK,
     {"path": "ray_shuffling_data_loader_tpu/dataset.py"}),
    ("lineage-outside-plan", LINEAGE_PLAN_INVERSE_BAD, LINEAGE_PLAN_OK,
     {"path": "ray_shuffling_data_loader_tpu/multiqueue_service.py"}),
    ("lineage-outside-plan", LINEAGE_PLAN_SEEDSEQ_BAD, LINEAGE_PLAN_OK,
     {"path": "ray_shuffling_data_loader_tpu/workers.py"}),
    ("raw-dataset-read", RAW_DATASET_READ_BAD, RAW_DATASET_READ_OK,
     {"path": "ray_shuffling_data_loader_tpu/shuffle.py"}),
    ("static-epoch-assumption", STATIC_EPOCH_RANGE_BAD, STATIC_EPOCH_OK,
     {"path": "ray_shuffling_data_loader_tpu/jax_dataset.py"}),
    ("static-epoch-assumption", STATIC_EPOCH_SUBSCRIPT_BAD,
     STATIC_EPOCH_OK,
     {"path": "ray_shuffling_data_loader_tpu/multiqueue_service.py"}),
    ("fixed-world-assumption", FIXED_WORLD_RANGE_BAD, FIXED_WORLD_OK,
     {"path": "ray_shuffling_data_loader_tpu/multiqueue_service.py"}),
    ("fixed-world-assumption", FIXED_WORLD_SCALE_BAD, FIXED_WORLD_OK,
     {"path": "ray_shuffling_data_loader_tpu/shuffle.py"}),
    ("shard-affinity-assumption", SHARD_AFFINITY_MOD_BAD,
     SHARD_AFFINITY_OK,
     {"path": "ray_shuffling_data_loader_tpu/dataset.py"}),
    ("shard-affinity-assumption", SHARD_AFFINITY_ADDR_BAD,
     SHARD_AFFINITY_OK,
     {"path": "ray_shuffling_data_loader_tpu/runtime/supervisor.py"}),
    ("tenant-context-bypass", TENANT_BYPASS_BAD, TENANT_BYPASS_PARAM_OK,
     {"path": "ray_shuffling_data_loader_tpu/storage/remote.py"}),
    ("tenant-context-bypass", TENANT_BYPASS_BAD, TENANT_BYPASS_AMBIENT_OK,
     {"path": "ray_shuffling_data_loader_tpu/multiqueue_service.py"}),
]


def test_tenant_bypass_scoped_to_shared_planes():
    """Only the serving/storage planes' entry points must be
    tenant-aware; a `register` helper elsewhere (a metrics registry, a
    test fixture) is not an admission point and never flags. Nor does a
    non-entry-point function inside a covered file."""
    for exempt in ("pkg/mod.py", "tests/test_x.py",
                   "ray_shuffling_data_loader_tpu/runtime/metrics.py"):
        flagged, _ = lint(TENANT_BYPASS_BAD, path=exempt)
        assert "tenant-context-bypass" not in flagged, exempt
    flagged, _ = lint("""
        def helper(self, name, nbytes):
            self._ledger[name] = nbytes
    """, path="ray_shuffling_data_loader_tpu/storage/remote.py")
    assert "tenant-context-bypass" not in flagged


def test_lineage_outside_plan_scoped_to_library_code():
    """plan/ and ops/partition.py are the blessed homes of the key
    arithmetic; tests and tools re-derive freely."""
    for exempt in ("ray_shuffling_data_loader_tpu/plan/ir.py",
                   "ray_shuffling_data_loader_tpu/ops/partition.py",
                   "tests/test_x.py", "tools/rsdl_plan.py"):
        flagged, _ = lint(LINEAGE_PLAN_ROUTE_BAD, path=exempt)
        assert "lineage-outside-plan" not in flagged, exempt
    flagged, _ = lint(LINEAGE_PLAN_ROUTE_BAD,
                      path="ray_shuffling_data_loader_tpu/dataset.py")
    assert "lineage-outside-plan" in flagged


def test_static_epoch_assumption_scoped_to_library_code():
    """plan/ enumerates epoch schedules and streaming/ derives epochs
    from windows — both exempt; tests and tools count epochs freely."""
    for exempt in ("ray_shuffling_data_loader_tpu/plan/ir.py",
                   "ray_shuffling_data_loader_tpu/streaming/runner.py",
                   "tests/test_x.py", "tools/rsdl_plan.py"):
        flagged, _ = lint(STATIC_EPOCH_RANGE_BAD, path=exempt)
        assert "static-epoch-assumption" not in flagged, exempt
    flagged, _ = lint(STATIC_EPOCH_RANGE_BAD,
                      path="ray_shuffling_data_loader_tpu/jax_dataset.py")
    assert "static-epoch-assumption" in flagged


def test_shard_affinity_assumption_scoped_to_library_code():
    """plan/ owns placement arithmetic, rebalance/ rewrites it, and the
    serving plane implements the MOVED redirect — all exempt; tests and
    tools derive shards freely."""
    for exempt in ("ray_shuffling_data_loader_tpu/plan/ir.py",
                   "ray_shuffling_data_loader_tpu/rebalance/__init__.py",
                   "ray_shuffling_data_loader_tpu/multiqueue_service.py",
                   "tests/test_x.py", "tools/rsdl_top.py"):
        flagged, _ = lint(SHARD_AFFINITY_MOD_BAD, path=exempt)
        assert "shard-affinity-assumption" not in flagged, exempt
    flagged, _ = lint(SHARD_AFFINITY_ADDR_BAD,
                      path="ray_shuffling_data_loader_tpu/dataset.py")
    assert "shard-affinity-assumption" in flagged


def test_fixed_world_assumption_scoped_to_library_code():
    """membership/ defines views and plan/ owns the rebalance
    arithmetic — both exempt; tests and tools fan out freely."""
    for exempt in ("ray_shuffling_data_loader_tpu/membership/elastic.py",
                   "ray_shuffling_data_loader_tpu/plan/ir.py",
                   "tests/test_x.py", "tools/rsdl_top.py"):
        flagged, _ = lint(FIXED_WORLD_RANGE_BAD, path=exempt)
        assert "fixed-world-assumption" not in flagged, exempt
    flagged, _ = lint(
        FIXED_WORLD_RANGE_BAD,
        path="ray_shuffling_data_loader_tpu/multiqueue_service.py")
    assert "fixed-world-assumption" in flagged


def test_unregistered_metric_scoped_to_library_code():
    # The same uncataloged name in a test file is not flagged (tests may
    # mint throwaway metrics); library paths are.
    flagged, _ = lint(UNREGISTERED_METRIC_BAD, path="tests/test_x.py")
    assert "unregistered-metric" not in flagged
    flagged, _ = lint(UNREGISTERED_METRIC_BAD,
                      path="ray_shuffling_data_loader_tpu/shuffle.py")
    assert "unregistered-metric" in flagged


def test_raw_dataset_read_scoped_and_exempt():
    """storage/ and utils/fileio.py are the blessed homes of raw
    parquet IO; tests and tools read datasets freely."""
    for exempt in ("ray_shuffling_data_loader_tpu/storage/source.py",
                   "ray_shuffling_data_loader_tpu/utils/fileio.py",
                   "tests/test_x.py", "tools/rsdl_top.py"):
        flagged, _ = lint(RAW_DATASET_READ_BAD, path=exempt)
        assert "raw-dataset-read" not in flagged, exempt
    flagged, violations = lint(
        RAW_DATASET_READ_BAD, path="ray_shuffling_data_loader_tpu/shuffle.py")
    assert "raw-dataset-read" in flagged
    # read_table and ParquetFile are each their own finding.
    assert sum(1 for v in violations
               if v.rule == "raw-dataset-read") == 2


def test_metric_catalog_covers_every_registered_name():
    """Every name in the catalog is well-formed; and the analyzer over
    the real tree (the gate test below) proves every call site is in the
    catalog — together: catalog == code, no silent drift."""
    from ray_shuffling_data_loader_tpu.runtime.metric_names import (
        METRIC_NAMES)
    for name, (kind, labels) in METRIC_NAMES.items():
        assert name.startswith("rsdl_"), name
        assert kind in ("counter", "gauge", "histogram", "sketch"), \
            (name, kind)
        assert isinstance(labels, tuple), name


def test_metric_label_cardinality_scoped_to_library_code():
    # Tests may mint throwaway labels; library code may not. The two
    # BAD label keys (task=, seq=) are each their own finding.
    flagged, _ = lint(METRIC_LABEL_CARD_BAD, path="tests/test_x.py")
    assert "metric-label-cardinality" not in flagged
    flagged, violations = lint(
        METRIC_LABEL_CARD_BAD,
        path="ray_shuffling_data_loader_tpu/multiqueue_service.py")
    assert "metric-label-cardinality" in flagged
    assert sum(1 for v in violations
               if v.rule == "metric-label-cardinality") == 2


def test_copy_in_hot_path_scoped_to_hot_path_modules():
    # The same copying code outside the hot-path modules is not flagged
    # (and jax_dataset.py IS covered while torch_dataset.py is not).
    flagged, _ = lint(COPY_HOT_PATH_OTHER_FILE_OK, path="pkg/utils.py")
    assert "copy-in-hot-path" not in flagged
    flagged, _ = lint(COPY_HOT_PATH_OTHER_FILE_OK,
                      path="pkg/torch_dataset.py")
    assert "copy-in-hot-path" not in flagged
    flagged, _ = lint(COPY_HOT_PATH_OTHER_FILE_OK,
                      path="pkg/jax_dataset.py")
    assert "copy-in-hot-path" in flagged


@pytest.mark.parametrize("rule_id,bad,good,kwargs",
                         CASES, ids=[f"{c[0]}-{i}"
                                     for i, c in enumerate(CASES)])
def test_rule_positive_and_negative(rule_id, bad, good, kwargs):
    path = kwargs.get("path", "pkg/mod.py")
    flagged, _ = lint(bad, path=path)
    assert rule_id in flagged, f"{rule_id} missed its seeded violation"
    clean, violations = lint(good, path=path)
    assert rule_id not in clean, \
        f"{rule_id} false-positive on the clean fixture: {violations}"


def test_at_least_eight_distinct_rules_registered():
    assert len(core.all_rules()) >= 8


def test_rule_count_matches_fixture_coverage():
    assert set(core.all_rules()) == {case[0] for case in CASES}


def test_lock_blocking_ignores_dict_get():
    _, violations = lint("""
        import threading

        class Registry:
            def __init__(self):
                self._lock = threading.Lock()
                self._entries = {}

            def lookup(self, key):
                with self._lock:
                    return self._entries.get(key)
    """)
    assert violations == []


def test_lock_mutation_skips_init_and_nested_defs():
    _, violations = lint("""
        import threading

        class Cache:
            def __init__(self):
                self._lock = threading.Lock()
                self._bytes = 0  # pre-publication write: exempt

            def put(self, n):
                with self._lock:
                    self._bytes += n

                def rollback():
                    # runs later on another thread, not under this lock
                    self._bytes -= n
                return rollback
    """)
    assert [v.rule for v in violations] == ["lock-mutation"]


def test_device_put_rule_scoped_to_parallel_paths():
    flagged, _ = lint(DEVICE_PUT_BAD, path="pkg/jax_dataset.py")
    assert "device-put-unsharded" not in flagged


def test_pragma_suppresses_on_line_and_from_line_above():
    src = """
        import pyarrow as pa

        def rebatch(carry, tail):
            a = pa.concat_tables(carry)  # rsdl-lint: disable=arrow-concat-promote
            # schema is homogeneous here: rsdl-lint: disable=arrow-concat-promote
            b = pa.concat_tables(tail)
            return a, b
    """
    flagged, _ = lint(src)
    assert flagged == []


def test_pragma_file_level_and_all():
    src = """
        # rsdl-lint: disable-file=arrow-concat-promote
        import pyarrow as pa

        def rebatch(carry):
            return pa.concat_tables(carry)
    """
    assert lint(src)[0] == []
    src_all = """
        import pyarrow as pa

        def rebatch(carry):
            return pa.concat_tables(carry)  # rsdl-lint: disable=all
    """
    assert lint(src_all)[0] == []


def test_pragma_does_not_leak_to_other_rules():
    src = """
        import pyarrow as pa

        def rebatch(carry):
            return pa.concat_tables(carry)  # rsdl-lint: disable=unseeded-random
    """
    assert lint(src)[0] == ["arrow-concat-promote"]


def test_parse_error_is_reported_not_raised():
    flagged, violations = lint("def broken(:\n")
    assert flagged == ["parse-error"]
    assert violations[0].line >= 1


def test_baseline_roundtrip_suppresses_exact_occurrences(tmp_path):
    _, violations = lint(CONCAT_BAD)
    assert len(violations) == 1
    path = tmp_path / "baseline.json"
    baseline_mod.write_baseline(str(path), violations)
    allowed = baseline_mod.load_baseline(str(path))
    remaining, suppressed = baseline_mod.apply_baseline(violations, allowed)
    assert remaining == [] and suppressed == 1
    # A SECOND occurrence of the same finding is NOT grandfathered.
    doubled = violations + violations
    remaining, suppressed = baseline_mod.apply_baseline(doubled, allowed)
    assert len(remaining) == 1 and suppressed == 1


def _write(tmp_path, name, source):
    target = tmp_path / name
    target.parent.mkdir(parents=True, exist_ok=True)
    target.write_text(textwrap.dedent(source))
    return target


def test_cli_exit_codes_and_json(tmp_path, monkeypatch, capsys):
    _write(tmp_path, "dirty.py", CONCAT_BAD)
    monkeypatch.chdir(tmp_path)
    assert cli.main(["dirty.py"]) == core.EXIT_VIOLATIONS
    capsys.readouterr()
    assert cli.main(["dirty.py", "--format", "json"]) \
        == core.EXIT_VIOLATIONS
    payload = json.loads(capsys.readouterr().out)
    assert payload["files_checked"] == 1
    assert [v["rule"] for v in payload["violations"]] \
        == ["arrow-concat-promote"]
    # Baseline it, then the same tree gates clean.
    assert cli.main(["dirty.py", "--write-baseline"]) == core.EXIT_CLEAN
    capsys.readouterr()
    assert cli.main(["dirty.py"]) == core.EXIT_CLEAN
    assert cli.main(["dirty.py", "--no-baseline"]) == core.EXIT_VIOLATIONS
    capsys.readouterr()
    assert cli.main(["no/such/path.py"]) == core.EXIT_ERROR
    assert cli.main(["dirty.py", "--select", "not-a-rule"]) \
        == core.EXIT_ERROR


def test_cli_select_and_disable(tmp_path, monkeypatch, capsys):
    _write(tmp_path, "dirty.py", CONCAT_BAD)
    monkeypatch.chdir(tmp_path)
    assert cli.main(["dirty.py", "--disable", "arrow-concat-promote"]) \
        == core.EXIT_CLEAN
    capsys.readouterr()
    assert cli.main(["dirty.py", "--select", "unseeded-random"]) \
        == core.EXIT_CLEAN


def test_cli_config_override(tmp_path, monkeypatch):
    _write(tmp_path, "parallelish.py", DEVICE_PUT_BAD)
    config = tmp_path / "lint.json"
    config.write_text(json.dumps({"sharded_path_globs": ["*parallelish*"]}))
    monkeypatch.chdir(tmp_path)
    assert cli.main(["parallelish.py", "--config", str(config)]) \
        == core.EXIT_VIOLATIONS
    assert cli.main(["parallelish.py"]) == core.EXIT_CLEAN
    bad_config = tmp_path / "bad.json"
    bad_config.write_text(json.dumps({"no_such_knob": 1}))
    assert cli.main(["parallelish.py", "--config", str(bad_config)]) \
        == core.EXIT_ERROR


def test_real_tree_is_clean_modulo_baseline():
    """The acceptance gate: the analyzer over the actual repo trees exits
    0, in-process (fast) — every deliberate exception is pragma'd."""
    rc = cli.main(["--baseline",
                   os.path.join(REPO_ROOT, ".rsdl-lint-baseline.json")]
                  + [os.path.join(REPO_ROOT, p) for p in GATE_PATHS])
    assert rc == core.EXIT_CLEAN


def test_module_entry_point_runs():
    """`python -m ray_shuffling_data_loader_tpu.analysis` works as the
    format.sh gate invokes it (subprocess, repo root cwd)."""
    proc = subprocess.run(
        [sys.executable, "-m", "ray_shuffling_data_loader_tpu.analysis",
         "--list-rules"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == core.EXIT_CLEAN, proc.stderr
    assert "arrow-concat-promote" in proc.stdout
