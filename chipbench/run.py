"""The benchmark's entry point: one run of one cell.

    python -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process per run. It refuses to start without a TPU (and without as
many chips as the cell asks for), makes its inputs and weights from
``--seed``, warms up, measures, checks what the timed path produced, and
prints the contract's one JSON line last. ``--trace 0`` prints the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics and a breakdown
from the profiler's trace of a shorter window.

``--rehearse`` is for the tests only: the same path on the CPU at the
repo's tiny presets, which prints counts and ``correct`` and names the
device as ``cpu``; it reports no device metric. ``--control`` runs a named
fault or lower precision that ``correct`` has to catch.
"""

from __future__ import annotations

import time

_STARTED_AT = time.perf_counter()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

# A hang must end inside the driver's limit for a run, not hold the chip.
DEADLINE_S = 900


def _parse(argv: Optional[List[str]]) -> argparse.Namespace:
    parser = argparse.ArgumentParser(prog="chipbench.run")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--control", default=None)
    return parser.parse_args(argv)


def _devices(cell_chips: int, rehearse: bool):
    """The chips this run uses, or ``None`` with the reason on stderr."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if rehearse:
        if platform != "cpu":
            print("chipbench: --rehearse is a CPU rehearsal; JAX reports "
                  f"{platform!r}", file=sys.stderr)
            return None
    elif platform != "tpu":
        print(f"chipbench: JAX reports platform {platform!r}, not 'tpu'; "
              "refusing to measure", file=sys.stderr)
        return None
    if len(devices) < cell_chips:
        print(f"chipbench: the cell asks for {cell_chips} chip(s), JAX "
              f"finds {len(devices)}", file=sys.stderr)
        return None
    return devices[:cell_chips]


def per_layer_metrics(cell, facts: Dict[str, Any], manifest_mod
                      ) -> Dict[str, Dict[str, Any]]:
    """Each per-layer metric of the cell through its own reader; a reader
    that finds nothing to read leaves its metric out."""
    out: Dict[str, Dict[str, Any]] = {}
    for metric in cell.per_layer:
        value = manifest_mod.layer_reader(metric["name"])(facts)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def print_op_scopes(device_ops: List[List[Any]], facts: Dict[str, Any]
                    ) -> None:
    """Beside each of the breakdown's operations, the scope the program
    ran it under (its instruction's ``op_name``), where the loop kept its
    step's compiled text: what tells ``fusion.9`` from ``fusion.11``."""
    from chipbench import harness, xplane
    names = facts.get("step_op_names")
    scopes = (xplane.op_scopes(facts["trace"], facts["trace_window"], names,
                               facts["step_module"]) if names else {})
    for name, seconds in device_ops:
        harness.info(f"device op {name}: {seconds:.6f} s under "
                     f"{scopes.get(name, '(no op_name)')}")


def _with_compared(line: Dict[str, Any], result: Dict[str, Any]) -> None:
    """Each number that decided ``correct`` beside its limit: the last
    lines on standard error, and the last key of the result's line (the
    leaf a worst gap was found at is in the ``# compared`` lines)."""
    compared = result.get("compared", ())
    sys.stdout.flush()
    for c in compared:
        print(c.line()[2:], file=sys.stderr, flush=True)
    line["compared"] = {c.name.partition("[")[0]: {"value": c.value,
                                                   "limit": c.limit}
                        for c in compared}


def main(argv: Optional[List[str]] = None) -> int:
    args = _parse(argv)
    from chipbench import harness, manifest
    cell = manifest.resolve_cell(args.workload)

    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={cell.chips}")

    with harness.scratch_dir() as scratch:
        ctx = harness.Context(
            cell=cell, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), rehearse=args.rehearse,
            control=args.control, started_at=_STARTED_AT, scratch=scratch)
        # The files are written and read back on a thread of their own
        # while the main thread brings the chip up.
        data_job = harness.DataJob(ctx)
        try:
            devices = _devices(cell.chips, args.rehearse)
            if devices is None:
                return 1
            ctx.devices = devices
            ctx.note_setup("imports_and_chip_up", harness.clock() - _STARTED_AT)
            from ray_shuffling_data_loader_tpu.utils.compile_cache import (
                enable_compile_cache)
            if not args.rehearse:
                cache_dir = enable_compile_cache()
                harness.info(f"compile cache: {cache_dir}")
            harness.info(f"cell {cell.name}: config {cell.config_name}, "
                         f"traffic {cell.traffic_name}, {cell.chips} chip(s), "
                         f"seed {args.seed}, {devices[0].device_kind}")
            loop = importlib.import_module(cell.traffic["loop"])
            result = loop.run(ctx, data_job)
        finally:
            data_job.wait_quietly()

        facts = result.pop("facts")
        device = dict(result["device"])
        line: Dict[str, Any] = {
            "correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
        }
        if args.rehearse:
            # Counts and the verdict only: a CPU run gives no device metric.
            line["metrics"] = {}
            line["device"] = {"platform": device["platform"],
                              "kind": device["kind"],
                              "count": device["count"]}
            _with_compared(line, result)
            harness.print_result(line)
            return 0
        if args.trace:
            from chipbench import xplane
            trace = xplane.load(facts["trace_path"])
            win = xplane.window_of(trace)
            facts["trace"], facts["trace_window"] = trace, win
            busy = xplane.busy_seconds(trace, win)
            if not busy or sum(busy.values()) <= 0:
                raise RuntimeError("no operation ran on the device in the "
                                   "traced window")
            device["busy_s"] = sum(busy.values()) / len(busy)
            device["window_s"] = win[1] - win[0]
            line["metrics"] = per_layer_metrics(cell, facts, manifest)
            line["breakdown"] = xplane.breakdown(trace, win)
            print_op_scopes(line["breakdown"]["device_ops"], facts)
        else:
            units = {m["name"]: m["unit"] for m in cell.end_to_end}
            line["metrics"] = {
                name: {"value": result["metrics"][name], "unit": unit}
                for name, unit in units.items()}
        line["device"] = device
        _with_compared(line, result)
        harness.print_result(line)
    return 0


if __name__ == "__main__":
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    sys.exit(main())
