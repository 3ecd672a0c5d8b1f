"""The train loop: files -> shuffle -> device feed -> re-batch -> real train
step, through ``JaxShufflingDataset`` and ``SpmdTrainer``.

Set-up builds ONE trainer, drives it from the seed through its first
steps (which the comparison with the plain reference reads), warms the
pipeline up, and hands that same trainer to the window. A step completes
when its loss has arrived on the host; the host runs a fixed number of
steps ahead of the last completion. ``train_rows_per_s`` is whole steps
over the time between two completions (``chipbench/window.py``).

Where the traffic mix sets ``open_after_epoch_ends``, the warm-up runs on
until the consumer has met that many epoch ends and the run-ahead has
refilled: what a process pays once (the first epoch's end) is then part of
``setup_s`` and not of the window.
"""

from __future__ import annotations

import collections
import dataclasses
import importlib
import math
import os
import statistics
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

import numpy as np

from chipbench import check, harness, manifest, window, xplane
from chipbench.harness import clock, info, span
from chipbench.readers import program as program_readers


@dataclasses.dataclass
class _Run:
    """What set-up built and the window drives."""

    ctx: harness.Context
    ref: Any                 # the configuration's plain reference (module)
    sizes: Dict[str, Any]
    trainer: Any             # the one SpmdTrainer of this run
    ds: Any                  # the loader
    mesh: Any
    batch: int               # rows per step, all chips
    init: Any                # jitted seeded initialiser of the parameters
    key: Any                 # the seed's PRNG key
    mask_key: Any
    data_job: harness.DataJob
    watchdog_before: Dict[str, Any]
    dataset_built_at: float


def _stall_control(control: Optional[str]) -> Tuple[float, int]:
    """``stall:<ms>:<every>`` -> (seconds to sleep, every how many batches
    of the window); (0.0, 0) for any other control."""
    if not control or not control.startswith("stall:"):
        return 0.0, 0
    _, ms, every = control.split(":")
    if float(ms) <= 0 or int(every) < 1:
        raise ValueError(f"--control {control}: a stall of more than 0 ms, "
                         "every 1 batch or more")
    return float(ms) / 1e3, int(every)


def _step_gap_tail(gaps_ms: List[float], group: int, strict: bool) -> float:
    """``step_gap_p95_ms``: the 95th percentile of the mean gap of ``group``
    steps in a row, with the line that says what it was read from. NaN
    where a window that reports no tail is too short for one group."""
    means = window.group_means(gaps_ms, group)
    if strict or means:
        tail = window.group_tail(
            gaps_ms, group, 95,
            least_beyond=window.LEAST_BEYOND if strict else 0)
    else:
        tail = math.nan
    median = statistics.median(gaps_ms)
    # An epoch end's own gaps (one long, the next ones short) are events,
    # not the stamps' noise, and would swamp it: left out of this one.
    near = [g for g in gaps_ms if abs(g - median) < 0.25 * median]
    lag1 = (window.lag1_autocorrelation(near) if len(near) >= 3
            else math.nan)
    info(f"step gap: mean of {group} in a row: p95 {tail:.4f} ms over "
         f"{len(means)} groups ({window.samples_beyond(means, 95)} beyond "
         f"the 95th); single gaps: p50 / p90 / p95 / p99 " + " / ".join(
             f"{window.percentile(gaps_ms, q):.4f}" for q in (50, 90, 95, 99))
         + f" ms over {len(gaps_ms)} gaps, max {max(gaps_ms):.4f} ms, "
         f"{sum(g > 2 * median for g in gaps_ms)} over twice the median, "
         f"lag-1 autocorrelation {lag1:.4f} over the {len(near)} within a "
         f"quarter of the median")
    return tail


def _stream(ds, num_epochs: int, ended: List[int]
            ) -> Iterator[Tuple[int, Any, Any]]:
    for epoch in range(num_epochs):
        ds.set_epoch(epoch)
        for features, label in ds:
            yield epoch, features, label
        ended.append(epoch)


def run(ctx: harness.Context, data_job: harness.DataJob) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_shuffling_data_loader_tpu.parallel import mesh as mesh_mod
    from ray_shuffling_data_loader_tpu.parallel.trainer import SpmdTrainer

    sizes = ctx.sizes
    adapter = importlib.import_module(sizes["adapter"])
    ref = importlib.import_module(sizes["reference"])
    model_cfg = manifest.load_object(sizes["program_builder"])()
    adapter.check_sizes(model_cfg, sizes)
    opt_cfg = sizes["optimizer"]
    batch = ctx.traffic("batch_per_device") * len(ctx.devices)
    rows = sizes["data"]["rows"]
    if rows % batch:
        raise ValueError(f"{rows} rows are not a multiple of the batch "
                         f"{batch}: drop_last would drop rows every epoch")
    key = harness.seed_key(ctx.seed)
    mask_key = jax.random.fold_in(key, 1)

    mesh = mesh_mod.make_mesh(devices=list(ctx.devices))
    replicated = NamedSharding(mesh, P())

    # -- the loader: its pool spawns and its first epoch starts while the
    # parameters are made ---------------------------------------------------
    data_job.wait()
    # Enough epochs that neither set-up nor the window reaches the end.
    num_epochs = ctx.traffic("num_epochs")
    wd_before = harness.watchdog_snapshot()
    t_ds = clock()
    ds = harness.make_dataset(ctx, data_job.filenames, batch, num_epochs,
                              mesh, adapter.loader_spec(sizes["data"]),
                              ctx.traffic("reducer_rows"))
    try:
        # -- parameters: made on the device from the seed, in one call ------------
        t0 = clock()
        init = jax.jit(lambda k: ref.init_params(sizes, k),
                       out_shardings=replicated)
        params = init(jax.random.fold_in(key, 0))
        if ctx.control == "bf16_params":
            # The control of the train cells: the step the configuration
            # states in float32 parameters, run in the precision below.
            params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
        trainer = SpmdTrainer(
            mesh, adapter.make_loss(model_cfg, sizes, mesh), params,
            optax.adam(opt_cfg["learning_rate"], b1=opt_cfg["b1"],
                       b2=opt_cfg["b2"], eps=opt_cfg["eps"]))
        del params
        jax.block_until_ready(trainer.params)
        ctx.note_setup("param_init", clock() - t0)

        return _drive(_Run(
            ctx=ctx, ref=ref, sizes=sizes, trainer=trainer, ds=ds, mesh=mesh,
            batch=batch, init=init, key=key, mask_key=mask_key,
            data_job=data_job, watchdog_before=wd_before,
            dataset_built_at=t_ds))
    finally:
        harness.close_dataset(ds)


def _drive(run: _Run) -> Dict[str, Any]:
    import jax

    ctx, ref, sizes, trainer, ds = (run.ctx, run.ref, run.sizes, run.trainer,
                                    run.ds)
    batch, key, mask_key, data_job = (run.batch, run.key, run.mask_key,
                                      run.data_job)
    opt_cfg = sizes["optimizer"]
    chips = len(ctx.devices)
    run_ahead = ctx.traffic("run_ahead_steps")
    warmup_steps = ctx.traffic("warmup_steps")
    open_after = ctx.traffic("open_after_epoch_ends", 0)
    num_epochs = ctx.traffic("num_epochs")
    steps_per_epoch = sizes["data"]["rows"] // batch
    stall_s, stall_every = _stall_control(ctx.control)
    digests = harness.EpochDigests()
    ended: List[int] = []
    # One entry for each epoch end the consumer met: the harness's clock
    # around that next(batch), and the program's own histograms as they
    # stood when it returned (readers/program.py tells one epoch's sample
    # from the next's by them).
    epoch_ends: List[Dict[str, Any]] = []
    histograms_before = program_readers.histogram_totals()
    stream = _stream(ds, num_epochs, ended)
    step_no = 0
    wait_s = 0.0
    opened = False
    attempted = 0

    def take():
        """The next batch of the feed, digested on the device."""
        nonlocal wait_s
        if stall_every and opened and attempted % stall_every == 0:
            # The tail metric's control: the host late with a batch. Longer
            # than the run-ahead's steps, it starves the device.
            time.sleep(stall_s)
        t_a = clock()
        with span("chipbench.next_batch"):
            epoch, features, label = next(stream)
        took = clock() - t_a
        wait_s += took
        if len(ended) != len(epoch_ends):
            epoch_ends.append({
                "epoch": ended[-1], "step": step_no, "in_window": opened,
                "next_batch_s": took,
                "program": program_readers.histogram_totals(
                    since=histograms_before)})
        with span("chipbench.digest_dispatch"):
            digests.add(epoch, features, label, batch)
        return features, label

    def step(features, label):
        nonlocal step_no
        loss = trainer.train_step(features, label, np.int32(step_no),
                                  mask_key)
        step_no += 1
        return loss

    # -- first steps, through the window's own call and feed ------------------
    first = [take()]
    first_batch_s = clock() - run.dataset_built_at
    ctx.note_setup("pool_spawn_to_first_batch", first_batch_s)
    harness.warm_rebatch_shapes(ctx, ds, first[0][0], first[0][1], batch,
                                run.mesh, ctx.traffic("reducer_rows"))
    first += [take() for _ in range(check.STEPS - 1)]
    host_batches = [([np.asarray(f) for f in features], np.asarray(label))
                    for features, label in first]
    touched = ref.touched_rows(sizes, host_batches)
    take_rows = jax.jit(ref.take_rows)
    p0_small = (take_rows(trainer.params, touched)
                if touched is not None else None)
    program = {"losses": []}
    t0 = clock()
    for i, (features, label) in enumerate(first):
        program["losses"].append(float(step(features, label)))
        if i == 0:
            mu = trainer.opt_state[0].mu
            program["grad_norms"] = {
                k: v / (1.0 - opt_cfg["b1"])
                for k, v in check.leaf_norms(mu).items()}
            del mu
    if touched is not None:
        program["change_norms"] = check.diff_norms(
            take_rows(trainer.params, touched), p0_small)
    else:
        p0 = run.init(jax.random.fold_in(key, 0))
        program["change_norms"] = check.diff_norms(trainer.params, p0)
        del p0
    compiles_before = trainer.step_fn._cache_size()
    ctx.note_setup("first_steps_and_compile", clock() - t0)
    del first

    # -- warm-up, then the window ----------------------------------------------
    seconds = ctx.seconds
    if ctx.trace:
        seconds = min(seconds, ctx.traffic("trace_seconds"))
    pending = collections.deque()
    completions: List[float] = []
    warm_completions: List[float] = []
    warm_left = warmup_steps
    # Completions still to see once the consumer has met the epoch ends the
    # traffic asks for, before the run-ahead counts as refilled.
    refill_left = run_ahead if open_after else 0
    last_loss = math.nan
    traced = harness.TracedWindow(ctx)
    setup_s = None
    t_warm = clock()
    while True:
        attempted += opened
        features, label = take()
        with span("chipbench.step_dispatch"):
            pending.append(step(features, label))
        if len(pending) <= run_ahead:
            continue
        with span("chipbench.run_ahead_wait"):
            last_loss = float(pending.popleft())
        now = clock()
        if not opened:
            warm_completions.append(now)
            warm_left -= 1
            if len(ended) >= open_after and refill_left > 0:
                refill_left -= 1
                continue
            if warm_left > 0 or len(ended) < open_after:
                continue
            if ctx.trace and not traced.started:
                # Tracing starts one completion ahead of the window, so
                # that the window still opens AT a completion.
                traced.start()
                warm_left = 1
                continue
            ctx.note_setup("warmup", now - t_warm)
            setup_s = now - ctx.started_at
            completions.append(now)
            opened = True
            wait_s = 0.0
            ds.batch_wait_stats.reset()
            continue
        completions.append(now)
        if window.closes(completions, seconds):
            break
    final_loss = last_loss
    # Steps still in flight are outside the window; drain them so that the
    # trace and the memory reading cover whole steps.
    while pending:
        float(pending.popleft())
    traced.stop()
    win = window.cut(completions, seconds)
    compiles_after = trainer.step_fn._cache_size()
    device = harness.device_facts(ctx.devices)
    # Two facts, printed apart. The allocator's peak is what the runtime
    # measured: the buffers this process held. On the v5e it leaves out
    # what a running program holds in temporaries
    # (chipbench/probes/allocator_peak.py shows it on the chip), and the
    # step's are most of a matmul-bound job's memory; they come from the
    # compiled step's own memory analysis (a second lowering, after the
    # window, that loads the program from the compile cache).
    # ``memory_peak_bytes`` is their sum: the chip's peak while a step
    # runs over the resident state.
    t0 = clock()
    step_program = trainer.step_fn.lower(
        trainer.params, trainer.opt_state, features, label, np.int32(0),
        mask_key).compile()
    step_temp = int(step_program.memory_analysis().temp_size_in_bytes)
    # The same text names each instruction's scope for the trace's readers.
    step_op_names = (xplane.hlo_op_names(step_program.as_text())
                     if ctx.trace else {})
    del step_program
    device["allocator_peak_bytes"] = device["memory_peak_bytes"]
    device["step_temp_bytes"] = step_temp
    device["memory_peak_bytes"] += step_temp
    info(f"memory: allocator peak {device['allocator_peak_bytes']} B "
         f"(measured by the runtime) + the train step's temporaries "
         f"{step_temp} B (the compiled step's memory analysis, "
         f"{clock() - t0:.2f} s after the window) = "
         f"{device['memory_peak_bytes']} B")

    # -- the loader's guarantee, over every epoch that ended -------------------
    epochs_checked, epochs_wrong = digests.check(
        list(ended), data_job.rows, data_job.digest)
    failed, fallback, watchdog_events = harness.loader_health(
        ds, run.watchdog_before, attempted, digests.short_batches)
    pool = harness.pool_facts()

    # -- the reference, once the window is closed ---------------------------------
    t0 = clock()
    trainer.params = trainer.opt_state = None   # free the program's state
    # The trajectory holds what the program's state held and no copy of the
    # starting parameters beside it: it makes them anew when it needs them.
    if touched is not None:
        ref_batches = [(ref.remap(f, touched), y) for f, y in host_batches]
        ref_p0 = check.copy_of(p0_small)
    else:
        ref_batches = host_batches
        ref_init = jax.jit(lambda k: ref.init_params(sizes, k))

        def ref_p0():
            return ref_init(jax.random.fold_in(key, 0))
    reference = check.reference_trajectory(ref, sizes, ref_p0, ref_batches,
                                           opt_cfg, mask_key)
    if ctx.control == "ref_bf16":
        # The control: the reference in the precision below, put in the
        # program's place. The sound program's own numbers go to earlier
        # lines, so that one run gives both readings a limit is set from.
        for c in check.compare(program, reference, ctx.limits()):
            info("sound " + c.line()[2:])
        program = check.reference_trajectory(
            ref, sizes, ref_p0, ref_batches, opt_cfg, mask_key,
            lower_precision=True)
    compared = check.compare(program, reference, ctx.limits())
    compared.append(check.Compared("epochs_off_the_files", float(epochs_wrong),
                                   0.0))
    compared.append(check.Compared(
        "final_loss_not_finite", 0.0 if math.isfinite(final_loss) else 1.0,
        0.0))
    reference_s = clock() - t0
    for c in compared:
        info(c.line()[2:])
    left_out = check.not_compared(program, reference, ctx.limits())
    if left_out:
        info("not compared (the limits name other numbers): " + left_out)
    info(f"losses program {program['losses']} reference "
         f"{reference['losses']}")

    gaps_ms = [g * 1e3 for g in win.gaps]
    rate = win.rate(batch)
    turnovers = ", ".join(
        f"epoch {e['epoch']} at step {e['step']} "
        f"({'window' if e['in_window'] else 'warm-up'}) "
        f"{e['next_batch_s'] * 1e3:.1f} ms" for e in epoch_ends)
    info(f"window: {win.counted} steps of {batch} rows counted over "
         f"{win.elapsed:.4f} s (asked {seconds} s), run-ahead {run_ahead}, "
         f"warm-up {len(warm_completions)} completions (at least "
         f"{warmup_steps} steps and {open_after} epoch end(s)), "
         f"{steps_per_epoch} steps an epoch, epochs ended {ended}, checked "
         f"{epochs_checked}; next(batch) at each epoch end: "
         f"{turnovers or 'none met'}")
    # Only a run that reports the tail insists on ten groups beyond it;
    # the line prints what there is in any run.
    reports_tail = (not ctx.trace and not ctx.rehearse and any(
        m["name"] == "step_gap_p95_ms" for m in ctx.cell.end_to_end))
    gap_p95 = _step_gap_tail(gaps_ms, run_ahead, strict=reports_tail)
    harness.keep_completions(ctx, {
        "seconds": seconds, "rows_per_step": batch, "run_ahead": run_ahead,
        "warmup_steps": warmup_steps, "setup_s": setup_s,
        "warm_completions": warm_completions, "completions": completions,
        "epoch_ends": epoch_ends})
    queue_wait = ds.batch_wait_stats.summary()["total"]
    info(f"host: {os.cpu_count()} CPUs, pool {pool}; loader "
         f"batches asked {attempted}, short {digests.short_batches}, "
         f"fallback {fallback}, watchdog events {watchdog_events}; in "
         f"next(batch) {100 * wait_s / win.elapsed:.3f} % of the window, of "
         f"which blocked on the loader's queue "
         f"{100 * queue_wait / win.elapsed:.3f} %; "
         f"reference check {reference_s:.2f} s (outside setup_s)")
    info("setup split s: " + ", ".join(
        f"{k} {v:.2f}" for k, v in ctx.setup_split.items()))

    facts = {
        "kind": "train", "sizes": sizes, "reference": ref,
        "chips": chips, "rows_per_step": batch, "rate_rows_per_s": rate,
        "window_elapsed_s": win.elapsed, "input_wait_s": wait_s,
        "first_batch_s": first_batch_s,
        "step_compiles": compiles_after - compiles_before,
        "device": device, "trace_path": traced.path,
        "setup_s": setup_s, "epoch_ends": epoch_ends,
        "step_module": "^jit_train_step$", "step_op_names": step_op_names,
    }
    metrics = {
        "train_rows_per_s": rate,
        "step_gap_p95_ms": gap_p95,
        "setup_s": setup_s,
    }
    return {"correct": all(c.ok for c in compared), "compared": compared,
            "attempted": attempted,
            "failed": failed, "metrics": metrics, "facts": facts,
            "device": device}
