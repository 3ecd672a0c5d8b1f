"""The readings a decoder cell's limits are set from, many seeds in one
process, at a configuration that fills the chip: the program's first three
steps against the plain reference, and two controls.

    python3 -m chipbench.probes.decoder_steps --workload granite_train_8k \
        --first-seed 3800002001 --seeds 8 --control-seeds 3 [--out <file>]
    python3 -m chipbench.probes.decoder_steps --workload granite_train_8k \
        --first-seed 3800002001 --seeds 3 --without-carry [--out <file>]

``probes/first_steps.py`` with two differences. The rows are token ids
(one list column, drawn uniformly as the files draw them), and the chip
holds one side at a time, as in a run: a seed's trainer state is freed
before its reference's trajectory starts, and made again from the seed for
the next one. ``--control-seeds`` runs the reference in bfloat16 in the
program's place on the first seeds (``check.reference_trajectory``'s
``lower_precision``). ``--without-carry`` is the control of the chunked
state-space scan: the program with the state each chunk starts from
zeroed (``ops/ssd.py:_carries`` replaced before anything is traced), which
the comparison has to refuse; it says nothing about a sound program.

Prints one JSON line per seed with every number the comparison knows, then
``# largest`` / ``# smallest`` lines over the seeds. Exit code 2 without a
TPU (``--allow-cpu`` for a witness off the chip, at the tiny preset).
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench import check, harness, manifest
from chipbench.probes.first_steps import summary


def draw_batches(data: Dict[str, Any], seed: int, batch: int, steps: int):
    """``steps`` batches of ``batch`` rows of token ids with the files'
    distribution (``chipbench/data.py``: ``first``, ids uniform in
    [4, vocab), ``last``), as the loader delivers them."""
    rng = np.random.Generator(np.random.Philox(
        np.random.SeedSequence([seed, 0x5EED])))
    column, = [c for c in data["columns"] if c.get("role") == "feature"]
    if column["kind"] != "tokens":
        raise ValueError("the probe draws one column of token ids")
    out = []
    for _ in range(steps):
        tokens = rng.integers(4, column["vocab"], (batch, column["width"]))
        tokens[:, 0], tokens[:, -1] = column["first"], column["last"]
        out.append(([tokens.astype(np.dtype(column["deliver_as"]))],
                    np.zeros((batch,), np.int32)))
    return out


def _drop_the_carry() -> None:
    """The scan's control: every chunk starts from a state of zero."""
    import jax.numpy as jnp

    from ray_shuffling_data_loader_tpu.ops import ssd
    ssd._carries = lambda states, end_decay: jnp.zeros_like(states)


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(prog="chipbench.probes.decoder_steps")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--first-seed", type=int, required=True)
    parser.add_argument("--seeds", type=int, default=8)
    parser.add_argument("--control-seeds", type=int, default=0)
    parser.add_argument("--without-carry", action="store_true")
    parser.add_argument("--out", default=None)
    parser.add_argument("--allow-cpu", action="store_true")
    args = parser.parse_args(argv)

    import jax
    import optax

    from ray_shuffling_data_loader_tpu.parallel import mesh as mesh_mod
    from ray_shuffling_data_loader_tpu.parallel.trainer import SpmdTrainer
    from ray_shuffling_data_loader_tpu.utils.compile_cache import (
        enable_compile_cache)

    cell = manifest.resolve_cell(args.workload)
    devices = jax.devices()
    on_chip = devices[0].platform == "tpu"
    if (not on_chip and not args.allow_cpu) or cell.chips != 1:
        print("decoder_steps: a one-chip cell on a TPU; JAX reports "
              f"{len(devices)} x {devices[0].platform!r}", file=sys.stderr)
        return 2
    devices = devices[:1]
    if on_chip:
        enable_compile_cache()
    if args.without_carry:
        _drop_the_carry()
    ctx = harness.Context(cell=cell, seed=0, seconds=0.0, trace=False,
                          rehearse=not on_chip, control=None, started_at=0.0,
                          scratch="", devices=devices)
    sizes = ctx.sizes
    adapter = importlib.import_module(sizes["adapter"])
    ref = importlib.import_module(sizes["reference"])
    model_cfg = manifest.load_object(sizes["program_builder"])()
    adapter.check_sizes(model_cfg, sizes)
    opt_cfg = sizes["optimizer"]
    batch = ctx.traffic("batch_per_device")
    mesh = mesh_mod.make_mesh(devices=list(devices))
    init = jax.jit(lambda k: ref.init_params(sizes, k))
    optimizer = optax.adam(opt_cfg["learning_rate"], b1=opt_cfg["b1"],
                           b2=opt_cfg["b2"], eps=opt_cfg["eps"])
    fresh_moments = jax.jit(optimizer.init)
    trainer = None
    records = []
    for n in range(args.seeds):
        seed = args.first_seed + n
        key = harness.seed_key(seed)
        mask_key = jax.random.fold_in(key, 1)

        def params0():
            return init(jax.random.fold_in(key, 0))

        if trainer is None:
            trainer = SpmdTrainer(
                mesh, adapter.make_loss(model_cfg, sizes, mesh), params0(),
                optimizer)
        else:
            trainer.params = params0()
            trainer.opt_state = fresh_moments(trainer.params)
        host_batches = draw_batches(sizes["data"], seed, batch, check.STEPS)
        program: Dict[str, Any] = {"losses": []}
        for i, (features, label) in enumerate(host_batches):
            on_device = jax.device_put((features, label), devices[0])
            program["losses"].append(float(trainer.train_step(
                *on_device, np.int32(i), mask_key)))
            if i == 0:
                program["grad_norms"] = {
                    k: v / (1.0 - opt_cfg["b1"]) for k, v in
                    check.leaf_norms(trainer.opt_state[0].mu).items()}
        trainer.opt_state = None
        start = params0()
        program["change_norms"] = check.diff_norms(trainer.params, start)
        del start
        trainer.params = None           # the chip holds one side at a time
        reference = check.reference_trajectory(
            ref, sizes, params0, host_batches, opt_cfg, mask_key)
        record = {"workload": cell.name, "seed": seed,
                  "without_carry": args.without_carry,
                  "losses": program["losses"],
                  "reference_losses": reference["losses"],
                  "sound": summary(program, reference)}
        if n < args.control_seeds:
            record["control"] = summary(check.reference_trajectory(
                ref, sizes, params0, host_batches, opt_cfg, mask_key,
                lower_precision=True), reference)
        records.append(record)
        print(json.dumps(record), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(record) + "\n")

    said = "without the carry" if args.without_carry else "sound"
    for side, pick, word in (("sound", max, "largest"),
                             ("sound", min, "smallest"),
                             ("control", min, "smallest")):
        have = [r[side] for r in records if side in r]
        if not have:
            continue
        name = said if side == "sound" else "reference in bfloat16"
        print(f"# {word} over {len(have)} seed(s), {name}: loss_gap "
              f"{pick(max(h['loss_gap_by_step']) for h in have):.6g}, " +
              ", ".join(f"{key} {pick(h[key] for h in have):.6g}"
                        for key in ("first_grad_worst_leaf_gap",
                                    "first_grad_median_leaf_gap",
                                    "param_change_worst_leaf_gap",
                                    "param_change_median_leaf_gap")),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
