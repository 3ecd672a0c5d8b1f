#!/usr/bin/env python
"""rsdl-trace: merge per-process recorder dumps; critical path + Perfetto.

The flight recorder (runtime/telemetry.py) dumps one JSONL per process
(``RSDL_TRACE_DIR`` makes every process — driver, trainers, supervised
queue servers — dump at exit). This tool merges those dumps onto one
clock and answers the questions one process can't:

- which tasks on which process form each epoch's **critical path**;
- per-stage **self time** vs the consumer's wait;
- the **straggler ranking** ((stage, task) by critical-path share);
- **what-if attribution**: "2x faster <stage> => -X% epoch time";
- the **epoch turnovers**: the device feed's `epoch_turnover` events,
  each consumer stall between two epochs split into its parts
  (`epoch_end`, `set_epoch`, `first_get`, ..., `other`);
- a **Perfetto export** (`--perfetto out.json`): chrome-trace JSON with
  real pid/tid mapping, loadable in ui.perfetto.dev / chrome://tracing.

Usage::

    tools/rsdl_trace.py /run/rsdl-trace/              # dir of dumps
    tools/rsdl_trace.py dump1.jsonl dump2.jsonl --epoch 3
    tools/rsdl_trace.py /run/rsdl-trace/ --perfetto trace.json
    tools/rsdl_trace.py /run/rsdl-trace/ --json       # machine-readable

Stdlib-only: the analyzer (``runtime/trace.py``) is loaded straight by
file path, so this runs on hosts without numpy/pyarrow/jax (the
rsdl_top pattern — a monitoring sidecar, an operator laptop).
"""

import argparse
import glob
import importlib.util
import json
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TRACE_PATH = os.path.join(_REPO_ROOT, "ray_shuffling_data_loader_tpu",
                           "runtime", "trace.py")


def _load_trace_module():
    """Load runtime/trace.py WITHOUT importing the package (whose
    __init__ pulls numpy/pyarrow); trace.py itself is stdlib-only."""
    spec = importlib.util.spec_from_file_location("_rsdl_trace",
                                                  _TRACE_PATH)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _expand_paths(args_paths):
    paths = []
    for p in args_paths:
        if os.path.isdir(p):
            paths.extend(sorted(glob.glob(os.path.join(p, "*.jsonl"))))
        else:
            paths.append(p)
    return paths


def render(analysis, processes) -> str:
    lines = []
    lines.append(f"processes: {len(processes)} "
                 f"(pids {', '.join(str(m['pid']) for m in processes)})")
    lines.append(f"epochs analyzed: {analysis['epochs']}  "
                 f"wall {analysis['wall_ms']:.1f} ms")
    lines.append("")
    header = f"{'stage':<18} {'critical-path ms':>16} {'%':>6} " \
             f"{'self ms':>10}"
    lines.append(header)
    lines.append("-" * len(header))
    self_ms = analysis["self_time_ms"]
    for entry in analysis["critical_path"]:
        lines.append(f"{entry['stage']:<18} {entry['cp_ms']:>16.1f} "
                     f"{entry['pct']:>6.1f} "
                     f"{self_ms.get(entry['stage'], 0.0):>10.1f}")
    stragglers = [s for s in analysis["stragglers"] if s["cp_ms"] > 0][:5]
    if stragglers:
        lines.append("")
        lines.append("stragglers (by critical-path share):")
        for i, s in enumerate(stragglers):
            lines.append(f"  {i + 1}. {s['stage']} task {s['task']}: "
                         f"{s['cp_ms']:.1f} ms on the path "
                         f"({s['self_ms']:.1f} ms self)")
    if analysis["whatif"]:
        lines.append("")
        lines.append("what-if (2x faster stage => epoch time saved):")
        for stage, w in sorted(analysis["whatif"].items(),
                               key=lambda kv: -kv[1]
                               ["epoch_time_saved_pct"]):
            lines.append(f"  {stage:<18} -{w['epoch_time_saved_pct']:.1f}%")
    return "\n".join(lines)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="merge recorder dumps; critical path, stragglers, "
                    "what-if, Perfetto export")
    parser.add_argument("paths", nargs="+",
                        help="dump files and/or directories of *.jsonl")
    parser.add_argument("--epoch", type=int, default=None,
                        help="analyze one epoch only")
    parser.add_argument("--speedup", type=float, default=2.0,
                        help="what-if speedup factor (default 2)")
    parser.add_argument("--perfetto", metavar="OUT",
                        help="write chrome-trace JSON here")
    parser.add_argument("--json", action="store_true",
                        help="machine-readable analysis")
    args = parser.parse_args(argv)

    trace = _load_trace_module()
    paths = _expand_paths(args.paths)
    if not paths:
        print("no dump files found", file=sys.stderr)
        return 2
    merged = trace.merge_dumps(paths)
    if not merged["events"]:
        print("dumps parsed but contain no events", file=sys.stderr)
        return 2
    seeds = [m.get("trace_seed") for m in merged["processes"]
             if m.get("trace_seed") is not None]
    seed = seeds[0] if seeds else 0
    analysis = trace.analyze(merged["events"], epoch=args.epoch,
                             whatif_speedup=args.speedup)
    if args.perfetto:
        with open(args.perfetto, "w", encoding="utf-8") as f:
            json.dump(trace.to_perfetto(merged, seed=seed), f)
        print(f"perfetto trace -> {args.perfetto} "
              f"({len(merged['events'])} events)", file=sys.stderr)
    turnovers = trace.turnovers(merged["events"], epoch=args.epoch)
    if args.json:
        analysis = dict(analysis)
        analysis.pop("path_segments", None)
        analysis["turnovers"] = turnovers
        analysis["processes"] = [
            {"pid": m["pid"], "role": m.get("role"),
             "trace_seed": m.get("trace_seed")}
            for m in merged["processes"]]
        print(json.dumps(analysis))
    else:
        print(render(analysis, merged["processes"]))
        if turnovers:
            print("\nepoch turnovers (consumer stall, ms):")
            for t in turnovers:
                split = ", ".join(f"{k} {v:.1f}"
                                  for k, v in t["parts"].items())
                print(f"  epoch {t['epoch']} -> {t['epoch'] + 1}: "
                      f"{t['total_ms']:.1f} ({split})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
