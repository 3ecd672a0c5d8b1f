"""The readings a train cell's limits are set from, many seeds in one
process: the program's first three steps against the plain reference, and
the control (the reference in bfloat16, put in the program's place).

    python3 -m chipbench.probes.first_steps --workload dlrm_train_x4 \
        --first-seed 2700001001 --seeds 14 --control-seeds 4 [--out <file>]

A run of the benchmark pays its whole set-up for one seed's numbers; this
pays chip bring-up and the compiles once. Per seed it makes the weights
from the seed as a run does (``reference.init_params`` under the run's
key), steps the cell's own trainer (``SpmdTrainer`` over the cell's mesh,
the adapter's loss, the configuration's optimizer) three times at the
cell's batch, and compares as ``loops/train.py`` does, through the same
functions of ``chipbench/check.py``. What differs from a run: the three
batches are drawn from the seed with the files' own distribution (every
column uniform over its cardinality, the label uniform in [0, 1)) and put
on the device directly, not through the loader, so a seed's numbers here
are not that seed's numbers in a run; their spread over seeds is the same.

Prints one JSON line per seed with every number the comparison knows,
compared or not (loss gap by step, worst and median leaf of the first
gradient and of the parameters' change, and the three worst leaves), then
``# largest`` / ``# smallest`` lines over the seeds. Exit code 2 without a
TPU with the cell's chips (``--allow-cpu`` for a witness off the chip).
"""

from __future__ import annotations

import argparse
import importlib
import json
import statistics
import sys
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench import check, harness, manifest


def draw_batches(data: Dict[str, Any], seed: int, batch: int, steps: int):
    """``steps`` batches of ``batch`` rows with the files' distribution,
    as the loader delivers them: one ``(batch, 1)`` array a column."""
    rng = np.random.Generator(np.random.Philox(
        np.random.SeedSequence([seed, 0x5EED])))
    features = [c for c in data["columns"] if c.get("role") == "feature"]
    label = next(c for c in data["columns"] if c.get("role") == "label")
    if any(c["kind"] != "int" for c in features) or label["kind"] != "float":
        raise ValueError("the probe draws integer feature columns and a "
                         "float label")
    out = []
    for _ in range(steps):
        out.append((
            [rng.integers(0, c["cardinality"], size=(batch, 1))
             .astype(np.dtype(c["deliver_as"])) for c in features],
            rng.random((batch, 1)).astype(np.dtype(label["deliver_as"]))))
    return out


def summary(program: Dict[str, Any], reference: Dict[str, Any]
            ) -> Dict[str, Any]:
    """Every number the comparison knows, whichever the limits name."""
    out: Dict[str, Any] = {
        "loss_gap_by_step": check.step_loss_gaps(program, reference)}
    for short, key in (("first_grad", "grad_norms"),
                       ("param_change", "change_norms")):
        gaps = check.leaf_gaps(program[key], reference[key])
        ranked = sorted(gaps.items(), key=lambda kv: kv[1], reverse=True)
        out[f"{short}_worst_leaf_gap"] = ranked[0][1]
        out[f"{short}_median_leaf_gap"] = statistics.median(gaps.values())
        out[f"{short}_worst_leaves"] = [[k, v] for k, v in ranked[:3]]
    return out


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(prog="chipbench.probes.first_steps")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--first-seed", type=int, required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--control-seeds", type=int, default=4)
    parser.add_argument("--out", default=None)
    parser.add_argument("--allow-cpu", action="store_true")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_shuffling_data_loader_tpu.parallel import mesh as mesh_mod
    from ray_shuffling_data_loader_tpu.parallel.trainer import SpmdTrainer
    from ray_shuffling_data_loader_tpu.utils.compile_cache import (
        enable_compile_cache)

    cell = manifest.resolve_cell(args.workload)
    devices = jax.devices()
    if ((devices[0].platform != "tpu" and not args.allow_cpu)
            or len(devices) < cell.chips):
        print(f"first_steps: the cell asks for {cell.chips} TPU chip(s), JAX "
              f"reports {len(devices)} x {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    devices = devices[:cell.chips]
    if devices[0].platform == "tpu":
        enable_compile_cache()
    ctx = harness.Context(cell=cell, seed=0, seconds=0.0, trace=False,
                          rehearse=False, control=None, started_at=0.0,
                          scratch="", devices=devices)
    sizes = ctx.sizes
    adapter = importlib.import_module(sizes["adapter"])
    ref = importlib.import_module(sizes["reference"])
    model_cfg = manifest.load_object(sizes["program_builder"])()
    adapter.check_sizes(model_cfg, sizes)
    opt_cfg = sizes["optimizer"]
    batch = ctx.traffic("batch_per_device") * len(devices)
    mesh = mesh_mod.make_mesh(devices=list(devices))
    sharded = mesh.devices.size > 1
    replicated = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P("data", None))
    init = jax.jit(lambda k: ref.init_params(sizes, k),
                   out_shardings=replicated)
    take_rows = jax.jit(ref.take_rows)
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t),
                    donate_argnums=(0,))
    seeds = [args.first_seed + i for i in range(args.seeds)]
    trainer = None
    records = []
    for n, seed in enumerate(seeds):
        key = harness.seed_key(seed)
        mask_key = jax.random.fold_in(key, 1)
        params = init(jax.random.fold_in(key, 0))
        if trainer is None:
            trainer = SpmdTrainer(
                mesh, adapter.make_loss(model_cfg, sizes, mesh), params,
                optax.adam(opt_cfg["learning_rate"], b1=opt_cfg["b1"],
                           b2=opt_cfg["b2"], eps=opt_cfg["eps"]))
        else:
            trainer.params = params
            trainer.opt_state = zeros(trainer.opt_state)
        del params
        host_batches = draw_batches(sizes["data"], seed, batch, check.STEPS)
        touched = ref.touched_rows(sizes, host_batches)
        p0_small = take_rows(trainer.params, touched)
        program: Dict[str, Any] = {"losses": []}
        for i, (features, label) in enumerate(host_batches):
            on_device = jax.device_put(
                (features, label), rows if sharded else devices[0])
            program["losses"].append(float(trainer.train_step(
                *on_device, np.int32(i), mask_key)))
            if i == 0:
                program["grad_norms"] = {
                    k: v / (1.0 - opt_cfg["b1"]) for k, v in
                    check.leaf_norms(trainer.opt_state[0].mu).items()}
        program["change_norms"] = check.diff_norms(
            take_rows(trainer.params, touched), p0_small)
        ref_batches = [(ref.remap(f, touched), y) for f, y in host_batches]
        ref_p0 = check.copy_of(p0_small)
        reference = check.reference_trajectory(
            ref, sizes, ref_p0, ref_batches, opt_cfg, mask_key)
        record = {"workload": cell.name, "seed": seed,
                  "losses": program["losses"],
                  "sound": summary(program, reference)}
        if n < args.control_seeds:
            record["control"] = summary(check.reference_trajectory(
                ref, sizes, ref_p0, ref_batches, opt_cfg, mask_key,
                lower_precision=True), reference)
        del p0_small, ref_p0
        records.append(record)
        print(json.dumps(record), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(record) + "\n")

    for side, pick, word in (("sound", max, "largest"),
                             ("control", min, "smallest")):
        have = [r[side] for r in records if side in r]
        if not have:
            continue
        print(f"# {word} over {len(have)} {side} seed(s): first_loss_gap "
              f"{pick(h['loss_gap_by_step'][0] for h in have):.6g}, loss_gap "
              f"{pick(max(h['loss_gap_by_step']) for h in have):.6g}, " +
              ", ".join(f"{name} {pick(h[name] for h in have):.6g}"
                        for name in ("first_grad_worst_leaf_gap",
                                     "first_grad_median_leaf_gap",
                                     "param_change_worst_leaf_gap",
                                     "param_change_median_leaf_gap")),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
