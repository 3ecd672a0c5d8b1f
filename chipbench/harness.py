"""What a loop is built from: the run's context, its scratch directory, the
loader as a cell's traffic sets it, the per-epoch digest of what reached
the device, and the traced window."""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import uuid
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from chipbench import data as bench_data
from chipbench import digest as bench_digest
from chipbench import manifest

clock = time.perf_counter

#: Under the checkout, listed in .gitignore: generated Parquet and traces.
#: Each run makes its own directory below it and removes it on exit.
SCRATCH_ROOT = os.path.join(manifest.CHECKOUT, ".chipbench_scratch")


def info(message: str) -> None:
    """An earlier output line: facts that explain a number."""
    print(f"# {message}", flush=True)


@dataclasses.dataclass
class Context:
    cell: manifest.Cell
    seed: int
    seconds: float
    trace: bool
    rehearse: bool               # CPU rehearsal at the tiny presets (tests)
    control: Optional[str]       # a named fault or lower precision (tests, limits)
    started_at: float            # host clock at process start
    scratch: str
    devices: Sequence[Any] = ()
    setup_split: Dict[str, float] = dataclasses.field(default_factory=dict)

    @functools.cached_property
    def sizes(self) -> Dict[str, Any]:
        """The configuration as it is run: the file's own keys, with the
        tiny preset's laid over them in a rehearsal."""
        config = dict(self.cell.config)
        if self.rehearse:
            tiny = dict(config["rehearsal"])
            data_over = tiny.pop("data", {})
            config.update(tiny)      # "batching" among them
            if "vocab_cap" in config:
                config["vocab_sizes"] = [min(v, config["vocab_cap"])
                                         for v in config["vocab_sizes"]]
            config["data"] = _tiny_data(config["data"], data_over, config)
        return config

    def traffic(self, key: str, default: Any = None) -> Any:
        """A parameter of the cell's traffic: from the traffic file or,
        for what depends on the rows' width (batch, reducer output, warm-up),
        from the configuration's ``batching``."""
        traffic = self.cell.traffic
        if self.rehearse and key in traffic.get("rehearsal", {}):
            return traffic["rehearsal"][key]
        if key in traffic:
            return traffic[key]
        return self.sizes.get("batching", {}).get(key, default)

    def limits(self) -> Dict[str, float]:
        """The comparison's limits: the configuration's, by traffic mix."""
        limits = self.sizes["limits"]    # the tiny preset has its own
        return limits.get(self.cell.traffic_name, limits["default"])

    def note_setup(self, part: str, seconds: float) -> None:
        self.setup_split[part] = self.setup_split.get(part, 0.0) + seconds


def _tiny_data(data: Dict[str, Any], over: Dict[str, Any],
               config: Dict[str, Any]) -> Dict[str, Any]:
    data = dict(data, **{k: v for k, v in over.items()
                         if k in ("rows", "files", "row_groups_per_file")})
    columns = []
    for column in data["columns"]:
        column = dict(column)
        if column["kind"] == "int" and "vocab_cap" in config:
            column["cardinality"] = min(column["cardinality"],
                                        config["vocab_cap"])
        if column["kind"] == "tokens":
            column["width"] = over.get("token_width", column["width"])
            column["vocab"] = over.get("token_vocab", column["vocab"])
        columns.append(column)
    data["columns"] = columns
    return data


@contextlib.contextmanager
def scratch_dir() -> Iterator[str]:
    os.makedirs(SCRATCH_ROOT, exist_ok=True)
    path = tempfile.mkdtemp(prefix="run-", dir=SCRATCH_ROOT)
    try:
        yield path
    finally:
        shutil.rmtree(path, ignore_errors=True)


def seed_key(seed: int):
    """A PRNG key from any whole number: the low 31 bits seed it and the
    rest is folded in, so seeds past 2**31 neither overflow nor collide."""
    import jax
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


class DataJob:
    """Makes the Parquet files and reads them back the plain way, on a
    thread of its own while the main thread brings the chip up."""

    def __init__(self, ctx: Context):
        self._ctx = ctx
        self.filenames: List[str] = []
        self.rows = 0
        self.digest = 0
        self._error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._run, name="chipbench-data")
        self._thread.start()

    def _run(self) -> None:
        try:
            ctx = self._ctx
            data = ctx.sizes["data"]
            t0 = clock()
            self.filenames = bench_data.generate(
                data, os.path.join(ctx.scratch, "data"), ctx.seed)
            t1 = clock()
            features, label = bench_data.delivered(data)
            self.rows, self.digest = bench_digest.files_digest_reference(
                self.filenames, features, label)
            ctx.note_setup("data_generate", t1 - t0)
            ctx.note_setup("data_reference_digest", clock() - t1)
        except BaseException as e:  # noqa: BLE001 - re-raised by wait()
            self._error = e

    def wait_quietly(self) -> None:
        """Join the thread on the way out, whatever it did."""
        self._thread.join()

    def wait(self) -> "DataJob":
        self._thread.join()
        if self._error is not None:
            raise self._error
        if self.rows != self._ctx.sizes["data"]["rows"]:
            raise RuntimeError(f"the files hold {self.rows} rows, the "
                               "configuration says "
                               f"{self._ctx.sizes['data']['rows']}")
        return self


#: What ``policy_env`` replaced, put back by ``close_dataset`` (the tests
#: rehearse several cells in one process).
_POLICY_ENV_BEFORE: Dict[str, Optional[str]] = {}


def make_dataset(ctx: Context, filenames: Sequence[str], batch_size: int,
                 num_epochs: int, mesh, loader_spec: Dict[str, Any],
                 reducer_rows: int):
    """The loader as the cell's traffic sets it; library defaults for
    everything the traffic file does not name."""
    from ray_shuffling_data_loader_tpu.jax_dataset import JaxShufflingDataset
    rows = ctx.sizes["data"]["rows"]
    reducers = max(1, rows // reducer_rows)
    cache = ctx.traffic("file_cache")
    # The program's own operational knobs (runtime/policy.py reads
    # RSDL_<KEY> from the environment), as a user would export them.
    for name, value in ctx.traffic("policy_env", {}).items():
        if not name.startswith("RSDL_"):
            raise ValueError(f"policy_env names {name!r}: only the "
                             "program's RSDL_* knobs belong there")
        _POLICY_ENV_BEFORE.setdefault(name, os.environ.get(name))
        os.environ[name] = str(value)
        info(f"policy: {name}={value}")
    if ctx.control and ctx.control.startswith("narrow:"):
        # The loader guarantee's control: deliver one column narrower than
        # its values need. The digest has to catch it.
        _, column, dtype = ctx.control.split(":")
        index = loader_spec["feature_columns"].index(column)
        loader_spec = dict(loader_spec, feature_types=list(
            loader_spec["feature_types"]))
        loader_spec["feature_types"][index] = np.dtype(dtype)
    return JaxShufflingDataset(
        list(filenames), num_epochs=num_epochs, num_trainers=1,
        batch_size=batch_size, rank=0, seed=ctx.seed & 0x7FFFFFFF,
        num_reducers=reducers, drop_last=True,
        max_concurrent_epochs=ctx.traffic("max_concurrent_epochs", 2),
        file_cache=None if cache in (None, "none") else cache,
        queue_name=f"chipbench-{ctx.cell.name}-{uuid.uuid4().hex[:8]}",
        mesh=mesh if (mesh is not None and mesh.devices.size > 1) else None,
        **loader_spec)


def watchdog_snapshot() -> Dict[str, Any]:
    from ray_shuffling_data_loader_tpu import stats as rsdl_stats
    return rsdl_stats.watchdog_stats().snapshot()


def loader_health(ds, watchdog_before: Dict[str, Any], attempted: int,
                  short_batches: int) -> Tuple[int, bool, int]:
    """(failed, fallback engaged, watchdog events): a batch that came short
    failed; once the bulk path fell back or the watchdog fired, every batch
    asked for in the window counts as failed."""
    events = (watchdog_snapshot()["watchdog_events"]
              - watchdog_before["watchdog_events"])
    fallback = bool(ds.fallback_engaged)
    failed = attempted if (fallback or events) else short_batches
    return failed, fallback, events


def close_dataset(ds) -> None:
    """Stop the loader with epochs still to run. ``close()`` stops the
    device feed's producer; shutting the shuffle's queue down wakes a
    shuffle driver that waits on a bounded queue, so that its thread and
    the pool end instead of outliving the run."""
    ds.close()
    inner = getattr(ds, "_dataset", None)
    if hasattr(inner, "shutdown"):
        inner.shutdown()
    while _POLICY_ENV_BEFORE:
        name, before = _POLICY_ENV_BEFORE.popitem()
        if before is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = before


def warm_rebatch_shapes(ctx: Context, ds, features, label, batch_size: int,
                        mesh, reducer_rows: int) -> None:
    """Compile (or load) the loader's on-device carve for every chunk
    length this cell can meet, so that none compiles inside the window: a
    reducer output's batch-aligned middle moves to the device in chunks of
    up to ``_MAX_CHUNK_BATCHES`` batches, and which lengths an epoch meets
    depends on its shuffle. An output of about ``reducer_rows`` rows holds
    at most ``reducer_rows // batch_size + 1`` whole batches.

    Reaches into the program (``ds._converter.slice_batch``,
    ``jax_dataset._MAX_CHUNK_BATCHES``): it has no public way to warm the
    carve. A program that renames either fails the run here, instead of
    compiling inside the window unseen."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_shuffling_data_loader_tpu import jax_dataset
    if not ds.device_rebatch:
        info("re-batch shapes: nothing to warm (the loader resolved to "
             "per-batch transfers)")
        return
    converter = ds._converter
    longest = min(jax_dataset._MAX_CHUNK_BATCHES,
                  reducer_rows // batch_size + 1)
    t0 = clock()
    sharded = mesh is not None and mesh.devices.size > 1
    out = None
    for nb in range(1, longest + 1):
        def chunk(a):
            # Host zeros put on the device: no program to compile for them.
            if not sharded:
                return jax.device_put(
                    np.zeros((nb * batch_size, *a.shape[1:]), a.dtype))
            spec = P(None, "data", *([None] * (a.ndim - 1)))
            return jax.device_put(
                np.zeros((nb, batch_size, *a.shape[1:]), a.dtype),
                NamedSharding(mesh, spec))
        table = ([chunk(f) for f in features], chunk(label))
        out = converter.slice_batch(table, nb - 1, batch_size)
    jax.block_until_ready(out)
    info(f"re-batch shapes: carve warmed for chunks of 1..{longest} batches")
    ctx.note_setup("rebatch_shapes", clock() - t0)


class EpochDigests:
    """Row count and digest of what reached the device, per epoch. The
    digest runs on the device, one small jitted program per batch, into an
    accumulator that is fetched only when the run is over."""

    def __init__(self):
        import jax
        import jax.numpy as jnp
        self._jnp = jnp
        self._add = jax.jit(
            lambda acc, features, label: acc + bench_digest.rows_digest_device(
                list(features) + [label]), donate_argnums=(0,))
        self._acc: Dict[int, Any] = {}
        self.rows: Dict[int, int] = {}
        self.short_batches = 0

    def add(self, epoch: int, features, label, batch_size: int) -> None:
        if epoch not in self._acc:
            self._acc[epoch] = self._jnp.zeros((2,), self._jnp.uint32)
            self.rows[epoch] = 0
        self._acc[epoch] = self._add(self._acc[epoch], features, label)
        self.rows[epoch] += int(label.shape[0])
        if label.shape[0] != batch_size:
            self.short_batches += 1

    def fetch(self, epoch: int) -> int:
        """Waits for the epoch's last digest program; the 64-bit digest."""
        return bench_digest.combine(np.asarray(self._acc[epoch]))

    def check(self, finished: Sequence[int], want_rows: int,
              want_digest: int) -> Tuple[int, int]:
        """(epochs checked, epochs that differ from the files)."""
        wrong = 0
        for epoch in finished:
            got = self.fetch(epoch)
            if self.rows[epoch] != want_rows or got != want_digest:
                wrong += 1
                info(f"epoch {epoch}: delivered {self.rows[epoch]} rows, "
                     f"digest {got:#018x}; the files hold {want_rows} rows, "
                     f"digest {want_digest:#018x}")
        return len(finished), wrong


class TracedWindow:
    """``jax.profiler`` around the window of a ``--trace 1`` run, with the
    harness's own span ``chipbench.window`` marking its edges."""

    def __init__(self, ctx: Context):
        self._dir = os.path.join(ctx.scratch, "trace") if ctx.trace else None
        self._span = None
        self.started = False
        self.path: Optional[str] = None

    def start(self) -> None:
        self.started = True
        if self._dir is None:
            return
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # host spans, not every Python call
        options.host_tracer_level = 2
        jax.profiler.start_trace(self._dir, profiler_options=options)
        self._span = jax.profiler.TraceAnnotation("chipbench.window")
        self._span.__enter__()

    def stop(self) -> None:
        if self._dir is None:
            return
        import jax

        from chipbench import xplane
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.path = xplane.find_xplane(self._dir)
        keep = os.environ.get("CHIPBENCH_KEEP_TRACE")
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(self.path, keep)


def keep_completions(ctx: Context, kept: Dict[str, Any]) -> None:
    """Where ``CHIPBENCH_KEEP_GAPS`` names a directory: every completion
    time of the run there as JSON, for whoever reads a noisy check."""
    keep = os.environ.get("CHIPBENCH_KEEP_GAPS")
    if not keep:
        return
    os.makedirs(keep, exist_ok=True)
    name = f"{ctx.cell.name}-{ctx.seed}-{os.getpid()}.json"
    with open(os.path.join(keep, name), "w") as f:
        json.dump(dict(kept, cell=ctx.cell.name, seed=ctx.seed), f)


def span(name: str):
    """A host span in the profiler's trace (free when nothing traces)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def device_facts(devices: Sequence[Any]) -> Dict[str, Any]:
    """The device as JAX reports it. ``memory_peak_bytes`` starts as the
    allocator's ``peak_bytes_in_use`` on the fullest chip: the buffers
    the process held (arguments, outputs, what the loader keeps in
    flight). On the v5e's runtime it leaves out what a running program
    holds in temporaries (``chipbench/probes/allocator_peak.py``); a loop
    that runs a large program adds those, see ``loops/train.py``."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    import jax
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": max(peaks) if peaks else 0}


def pool_facts() -> Dict[str, Any]:
    from ray_shuffling_data_loader_tpu import executor
    pool = executor.last_worker_pool() or {}
    return {"backend": pool.get("backend"), "workers": pool.get("workers")}


def print_result(result: Dict[str, Any]) -> None:
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
