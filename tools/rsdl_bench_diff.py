#!/usr/bin/env python
"""rsdl-bench-diff: per-metric regression gate between two bench records.

A past round's record showed cached ingest 65% below the round before
and NOTHING in the repo noticed — the record format carries the numbers
but no machinery compared them. This tool is that machinery:

    tools/rsdl_bench_diff.py BENCH_r10.json BENCH_r11.json
        # rc 1 and 'value ... REGRESSED' when a threshold is breached

    tools/rsdl_bench_diff.py --check [DIR]
        # informational mode for format.sh: compares the two newest
        # committed BENCH_r*.json records, prints the verdict, rc 0
        # (add --strict to make it a hard gate)

    python bench.py --baseline BENCH_r11.json
        # the hard gate at measurement time: bench loads this module
        # and exits non-zero on a threshold breach

Records are either a raw bench JSON line (``{"metric", "value", ...}``)
or the committed ``BENCH_r*.json`` wrapper (``{"parsed": {...}}``) —
both load. Only metrics present in BOTH records are compared (schemas
grew over rounds), except ceilings, which apply to the current record
alone. Thresholds are relative drops/rises chosen to sit above host
noise (the committed records span 1-core and many-core hosts) but far
below the regressions worth catching; override any of them with
``--threshold key=pct``.

Exit codes: 0 clean, 1 regression (two-file mode / --strict), 2 usage.
Stdlib-only (runs on a bare CI image, the rsdl_top pattern).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Any, Dict, List, Optional

#: (key, mode, threshold). Modes:
#:   lower_bad:  fail when cur < base * (1 - pct/100)
#:   higher_bad: fail when cur > base * (1 + pct/100) AND cur - base
#:               exceeds the absolute slack in ``slack`` (noise floor)
#:   ceiling:    fail when cur > threshold (current record alone)
#:   require_true: fail when the key is present but falsy (current
#:               record alone; absent key skips — pre-feature records)
DEFAULT_RULES: List[Dict[str, Any]] = [
    {"key": "value", "mode": "lower_bad", "pct": 10.0},
    {"key": "rows_per_s_per_core", "mode": "lower_bad", "pct": 10.0},
    {"key": "cold_rows_per_sec", "mode": "lower_bad", "pct": 10.0},
    {"key": "train_rows_per_sec", "mode": "lower_bad", "pct": 10.0},
    {"key": "train_mfu_pct", "mode": "lower_bad", "pct": 10.0},
    {"key": "stall_pct_under_train", "mode": "higher_bad", "pct": 20.0,
     "slack": 2.0},
    {"key": "fill_s", "mode": "higher_bad", "pct": 50.0, "slack": 1.0},
    {"key": "telemetry_overhead_pct", "mode": "ceiling", "limit": 1.0},
    # Serving-plane leg (multiqueue_service v3): aggregate remote-stream
    # throughput on the sharded fabric, and the shard-scaling ratio
    # itself — a shard-placement regression can keep absolute rows/s
    # afloat on a faster host while the scaling evidence collapses.
    {"key": "serve_rows_per_sec", "mode": "lower_bad", "pct": 10.0},
    {"key": "serve_speedup_vs_single_shard", "mode": "lower_bad",
     "pct": 15.0},
    # Handle delivery must keep beating v2 streaming on wire bytes by a
    # wide margin (the >= 10x acceptance ratio, with noise headroom).
    {"key": "serve_handle_wire_reduction_x", "mode": "lower_bad",
     "pct": 50.0},
    # Delivery-latency leg (runtime/latency.py): end-to-end
    # birth->delivered p99 over the sharded serving plane, and the
    # birth->device freshness p99. Latency on a shared 1-core host is
    # noisy, so the relative threshold is wide and the absolute slack
    # (ms) absorbs scheduler jitter; a real regression (a serialization
    # copy creeping back in, a replay-path stall) blows through both.
    {"key": "delivery_p99_ms", "mode": "higher_bad", "pct": 150.0,
     "slack": 100.0},
    {"key": "freshness_p99_ms", "mode": "higher_bad", "pct": 150.0,
     "slack": 150.0},
    # Storage-plane cold leg (storage/): prefetch-on ingest rate against
    # the simulated object store, and the prefetch A/B speedup itself —
    # a prefetch regression (lanes never idle, warms the wrong files,
    # cancels everything) can hide behind a faster host's absolute
    # rows/s while the on-vs-off ratio collapses toward 1.0.
    # Tightened from 20/25 after the r08->r09 drift (-17.1% rows/s,
    # -13.5% speedup — the streaming PR's shuffled map-read order halved
    # prefetch efficiency, see examples/performance.md) sailed UNDER the
    # old thresholds: both metrics now fail the diff well before a
    # regression of that size lands silently again.
    {"key": "remote_rows_per_sec", "mode": "lower_bad", "pct": 10.0},
    {"key": "remote_prefetch_speedup_x", "mode": "lower_bad",
     "pct": 8.0},
    # Streaming leg (streaming/): windowed end-to-end rate over the
    # synthetic stream, the pipelining watermark lag (stream seconds —
    # deterministic arrivals, so a lag jump means the assembler or the
    # serve path stalled, not the host), and the window seal cost.
    # Records older than r09 lack these keys, so the relative rules
    # skip cleanly against pre-streaming baselines.
    {"key": "stream_rows_per_sec", "mode": "lower_bad", "pct": 20.0},
    {"key": "watermark_lag_p99_s", "mode": "higher_bad", "pct": 100.0,
     "slack": 5.0},
    {"key": "window_close_ms", "mode": "higher_bad", "pct": 150.0,
     "slack": 200.0},
    # Tenancy contention leg (tenancy/): the hot:cold delivered-rows
    # ratio under a 3:1 weight split must track the weights (fairness
    # physics, not host speed — wide but meaningful), and the hot
    # tenant's contended p99 must stay within its solo multiple.
    # Records older than r10 lack these keys; relative rules skip.
    {"key": "tenancy_fairness_ratio", "mode": "lower_bad", "pct": 25.0},
    {"key": "tenancy_hot_rows_per_sec", "mode": "lower_bad", "pct": 20.0},
    {"key": "tenancy_latency_ratio_x", "mode": "higher_bad", "pct": 50.0,
     "slack": 0.5},
    # Elastic-membership leg (membership/): kill->DOWN detection wall
    # time and the shrink's recompute stall are latency physics (wide
    # relative thresholds + absolute slack for shared-host scheduler
    # jitter), while rows_lost and elastic_ok are hard invariants — a
    # single lost row or a failed leg is a gate failure at ANY host
    # speed. Records older than r10 lack these keys; the relative and
    # require_true rules skip cleanly, and the ceiling judges the
    # current record alone (absence there is a non-finding).
    {"key": "member_down_detect_ms", "mode": "higher_bad", "pct": 100.0,
     "slack": 250.0},
    {"key": "resize_stall_ms", "mode": "higher_bad", "pct": 200.0,
     "slack": 250.0},
    {"key": "rows_lost", "mode": "ceiling", "limit": 0.0},
    {"key": "elastic_ok", "mode": "require_true"},
    # Self-healing serving-plane leg (rebalance/): the SLO tenant's p99
    # recovery across the live migration must stay well above 1x (the
    # leg's own rebalance_ok already pins > 1.0 — the relative rule
    # catches the slow slide a boolean can't), the seal window is
    # latency physics (wide + slack for shared-host jitter), and the
    # delivered rate across the move is feed-paced and so nearly
    # deterministic. rows_lost rides the elastic ceiling above
    # (max-merged in bench.py when both legs run); rebalance_ok is the
    # exactly-once + breach-driven-decision + journal-replay verdict.
    # Records older than r12 lack these keys; relative and require_true
    # rules skip cleanly.
    {"key": "rebalance_p99_recovery_x", "mode": "lower_bad", "pct": 50.0},
    {"key": "rebalance_stall_ms", "mode": "higher_bad", "pct": 200.0,
     "slack": 250.0},
    {"key": "rebalance_slo_rows_per_sec", "mode": "lower_bad",
     "pct": 25.0},
    {"key": "rebalance_ok", "mode": "require_true"},
]


def load_record(path: str) -> Dict[str, Any]:
    """A bench record from disk: raw bench JSON or BENCH_r* wrapper."""
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    if isinstance(data, dict) and isinstance(data.get("parsed"), dict):
        return data["parsed"]
    if not isinstance(data, dict) or "value" not in data:
        raise ValueError(f"{path}: not a bench record "
                         "(no 'value' and no 'parsed' wrapper)")
    return data


def derive_metrics(record: Dict[str, Any]) -> Dict[str, Any]:
    """Fill in metrics computable from what the record does carry.

    ``rows_per_s_per_core`` only started being emitted in r05, but
    earlier records already carried ``value`` (rows/s) and ``host_cpus`` — and
    the per-core rule is the one that survives a host-width change, so
    silently skipping it against pre-r05 baselines hides exactly the
    normalization it exists for. Derive it (value / host_cpus) when
    absent; emitted values always win over derived ones.
    """
    if _num(record, "rows_per_s_per_core") is None:
        value = _num(record, "value")
        cpus = _num(record, "host_cpus")
        if value is not None and cpus is not None and cpus > 0:
            record = dict(record)
            record["rows_per_s_per_core"] = value / cpus
    return record


def _num(record: Dict[str, Any], key: str) -> Optional[float]:
    value = record.get(key)
    if isinstance(value, bool) or not isinstance(value, (int, float)):
        return None
    return float(value)


def compare_records(base: Dict[str, Any], cur: Dict[str, Any],
                    overrides: Optional[Dict[str, float]] = None
                    ) -> List[Dict[str, Any]]:
    """Apply the rule table; returns one finding dict per applicable
    rule: ``{key, mode, base, cur, delta_pct, threshold_pct, ok,
    reason}``. ``overrides`` replaces a rule's pct/limit by key."""
    overrides = overrides or {}
    findings: List[Dict[str, Any]] = []
    for rule in DEFAULT_RULES:
        key, mode = rule["key"], rule["mode"]
        threshold = overrides.get(key, rule.get("pct", rule.get("limit")))
        if mode == "require_true":
            # Boolean verdicts (elastic_ok): judged on the current
            # record alone; an absent key is a pre-feature record and
            # skips cleanly (absence is NOT the "disappeared" failure —
            # these legs are opt-in via RSDL_BENCH_PHASES).
            if key not in cur:
                continue
            ok = bool(cur.get(key))
            findings.append({
                "key": key, "mode": mode, "base": None,
                "cur": 1.0 if ok else 0.0, "delta_pct": None,
                "threshold_pct": None, "ok": ok,
                "reason": ("verdict true" if ok
                           else "verdict false (leg failed its own "
                                "invariants)"),
            })
            continue
        cur_v = _num(cur, key)
        if cur_v is None:
            # A metric the baseline measured but the current record lost
            # is a gate failure, not a skip — BENCH_r05 went out with
            # train_mfu_pct silently null and nothing flagged it.
            # Ceilings apply to the current record alone, so absence
            # there stays a non-finding.
            if mode != "ceiling":
                base_v = _num(base, key)
                if base_v is not None:
                    findings.append({
                        "key": key, "mode": mode, "base": base_v,
                        "cur": None, "delta_pct": None,
                        "threshold_pct": threshold, "ok": False,
                        "reason": f"metric disappeared (base {base_v:g}, "
                                  "current record has no numeric value)",
                    })
            continue
        if mode == "ceiling":
            ok = cur_v <= threshold
            findings.append({
                "key": key, "mode": mode, "base": None, "cur": cur_v,
                "delta_pct": None, "threshold_pct": threshold, "ok": ok,
                "reason": (f"{cur_v:g} <= ceiling {threshold:g}" if ok
                           else f"{cur_v:g} exceeds ceiling {threshold:g}"),
            })
            continue
        base_v = _num(base, key)
        if base_v is None or base_v == 0:
            continue
        delta_pct = 100.0 * (cur_v - base_v) / base_v
        if mode == "lower_bad":
            ok = delta_pct >= -threshold
        else:  # higher_bad: relative rise AND absolute slack both breached
            slack = rule.get("slack", 0.0)
            ok = delta_pct <= threshold or (cur_v - base_v) <= slack
        findings.append({
            "key": key, "mode": mode, "base": base_v, "cur": cur_v,
            "delta_pct": round(delta_pct, 2), "threshold_pct": threshold,
            "ok": ok,
            "reason": f"{base_v:g} -> {cur_v:g} ({delta_pct:+.1f}%, "
                      f"threshold {'-' if mode == 'lower_bad' else '+'}"
                      f"{threshold:g}%)",
        })
    return findings


def render_findings(findings: List[Dict[str, Any]]) -> List[str]:
    lines = []
    for f in findings:
        verdict = "ok        " if f["ok"] else "REGRESSED "
        lines.append(f"{verdict}{f['key']:<26} {f['reason']}")
    if not findings:
        lines.append("no comparable metrics between the two records")
    return lines


def _latest_records(directory: str) -> List[str]:
    paths = sorted(glob.glob(os.path.join(directory, "BENCH_r*.json")))
    return paths[-2:]


def _load_regress():
    """runtime/regress.py by file path (fail-soft: the gate's verdict
    never depends on the forensics plane loading)."""
    import importlib.util
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "ray_shuffling_data_loader_tpu",
                        "runtime", "regress.py")
    spec = importlib.util.spec_from_file_location("_rsdl_regress", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def forensic_lines(base_path: str, cur_path: str) -> List[str]:
    """The differential forensics footer printed under a failed gate:
    the runtime/regress.py suspect ranking when both records carry
    flight capsules, its loud record-only degrade when they don't.
    Never raises — thresholds and exit codes stay this tool's only
    contract; the footer is evidence, not verdict."""
    try:
        regress = _load_regress()
        report = regress.diff_rounds(base_path, cur_path)
        return regress.render_report(report)
    except Exception as e:  # noqa: BLE001 - evidence, not verdict
        return [f"forensics unavailable: {type(e).__name__}: {e}"]


def provenance_lines(base: Dict[str, Any],
                     cur: Dict[str, Any]) -> List[str]:
    """Hard comparability warnings (dirty tree, cross-host) from the
    records' provenance stamps — printed even when every threshold
    passes, because a cross-host pair passing the gate is as misleading
    as one failing it (the r09->r10 lesson)."""
    try:
        regress = _load_regress()
        return [f"WARNING {w}" for w in regress.provenance_warnings(
            base, cur, include_missing=False)]
    except Exception:  # noqa: BLE001 - evidence, not verdict
        return []


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        description="per-metric regression gate between two bench records")
    parser.add_argument("baseline", nargs="?",
                        help="baseline record (e.g. BENCH_r11.json)")
    parser.add_argument("current", nargs="?",
                        help="current record (e.g. BENCH_r05.json)")
    parser.add_argument("--check", metavar="DIR", nargs="?", const=".",
                        default=None,
                        help="informational mode: compare the two newest "
                             "BENCH_r*.json in DIR (default .), rc 0")
    parser.add_argument("--strict", action="store_true",
                        help="with --check: regressions exit non-zero")
    parser.add_argument("--threshold", action="append", default=[],
                        metavar="KEY=PCT",
                        help="override one rule's threshold, repeatable")
    parser.add_argument("--json", action="store_true",
                        help="machine-readable findings")
    args = parser.parse_args(argv)

    overrides: Dict[str, float] = {}
    for spec in args.threshold:
        if "=" not in spec:
            parser.error(f"--threshold wants KEY=PCT, got {spec!r}")
        key, pct = spec.split("=", 1)
        try:
            overrides[key] = float(pct)
        except ValueError:
            parser.error(f"--threshold {spec!r}: {pct!r} is not a number")

    if args.check is not None:
        latest = _latest_records(args.check)
        if len(latest) < 2:
            print(f"bench-diff check: fewer than two BENCH_r*.json in "
                  f"{args.check!r}; nothing to compare")
            return 0
        base_path, cur_path = latest
        hard = args.strict
    else:
        if not args.baseline or not args.current:
            parser.error("need BASELINE and CURRENT records "
                         "(or --check [DIR])")
        base_path, cur_path = args.baseline, args.current
        hard = True

    try:
        base = derive_metrics(load_record(base_path))
        cur = derive_metrics(load_record(cur_path))
    except (OSError, ValueError) as e:
        print(f"bench-diff: {e}", file=sys.stderr)
        return 2

    findings = compare_records(base, cur, overrides)
    regressions = [f for f in findings if not f["ok"]]
    if args.json:
        print(json.dumps({"baseline": base_path, "current": cur_path,
                          "findings": findings,
                          "regressed": len(regressions)}))
    else:
        print(f"bench-diff: {base_path} -> {cur_path}")
        for line in provenance_lines(base, cur):
            print(f"  {line}")
        for line in render_findings(findings):
            print(f"  {line}")
        if regressions:
            print(f"  {len(regressions)} metric(s) REGRESSED")
            for line in forensic_lines(base_path, cur_path):
                print(f"  {line}")
    return 1 if regressions and hard else 0


if __name__ == "__main__":
    sys.exit(main())
