#!/usr/bin/env python
"""rsdl-report: standalone-HTML run report over the ops-plane artifacts.

One self-contained HTML file (inline CSS/SVG, zero dependencies, opens
from a file:// path on an operator laptop) assembling the run story the
individual tools tell separately:

- **throughput / stall sparklines** from a history slice
  (``--history history.json``, or the one embedded in a capsule);
- **critical path + what-if** from recorder dumps (``--trace-dir``, or
  the capsule's ``traces/``);
- **health**: the detector verdict of an incident capsule;
- **delivery latency & freshness** from the capsule and the history.

Usage::

    tools/rsdl_report.py --history hist.json --trace-dir /tmp/rsdl-trace \
        -o report.html
    tools/rsdl_report.py --capsule <capsule-dir> -o report.html
    tools/rsdl_report.py --check          # schema-only smoke, no HTML

``--check`` validates whatever inputs exist (history slices load, trace
dumps merge, the capsule's manifest parses) and prints one line per source
— informational mode for format.sh, always exit 0 unless the arguments
themselves are unusable.

Stdlib-only: loads ``runtime/{trace,history}.py`` by file path (the
rsdl_top pattern).
"""

import argparse
import glob
import html
import importlib.util
import json
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_RUNTIME = os.path.join(_REPO_ROOT, "ray_shuffling_data_loader_tpu",
                        "runtime")


def _load_by_path(stem: str):
    try:
        import importlib
        return importlib.import_module(
            f"ray_shuffling_data_loader_tpu.runtime.{stem}")
    except ImportError:
        spec = importlib.util.spec_from_file_location(
            f"_rsdl_{stem}", os.path.join(_RUNTIME, f"{stem}.py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module


# ---------------------------------------------------------------------------
# Input loading (each loader returns None when its source is absent)
# ---------------------------------------------------------------------------


def load_history(path):
    if not path or not os.path.isfile(path):
        return None
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    if data.get("schema") != "rsdl-history-v1":
        raise ValueError(f"{path}: not an rsdl-history-v1 slice")
    return _load_by_path("history").load_slice(data)


def load_traces(trace_dir):
    if not trace_dir or not os.path.isdir(trace_dir):
        return None
    paths = sorted(glob.glob(os.path.join(trace_dir, "*.jsonl")))
    if not paths:
        return None
    trace = _load_by_path("trace")
    merged = trace.merge_dumps(paths)
    if not merged["events"]:
        return None
    return {
        "pids": sorted({m["pid"] for m in merged["processes"]}),
        "analysis": trace.analyze(merged["events"]),
    }


def load_capsule_manifest(capsule_dir):
    if not capsule_dir:
        return None
    path = os.path.join(capsule_dir, "capsule.json")
    with open(path, encoding="utf-8") as f:
        manifest = json.load(f)
    if manifest.get("schema") != "rsdl-incident-v1":
        raise ValueError(f"{path}: unknown capsule schema")
    return manifest


# ---------------------------------------------------------------------------
# HTML assembly (method: single-series sparklines carry no legend — the
# title names the series; values wear text ink, never the series color;
# every chart has a table twin; hover via native SVG <title> tooltips;
# light/dark from one custom-property block)
# ---------------------------------------------------------------------------

_CSS = """
.rsdl-report { color-scheme: light;
  --surface-1: #fcfcfb; --surface-2: #f1f0ee;
  --text-primary: #0b0b0b; --text-secondary: #52514e;
  --grid: #d8d7d3; --series-1: #2a78d6; --bad: #e34948;
  background: var(--surface-1); color: var(--text-primary);
  font: 14px/1.5 system-ui, sans-serif; max-width: 72rem;
  margin: 0 auto; padding: 1.5rem; }
@media (prefers-color-scheme: dark) {
  :root:where(:not([data-theme="light"])) .rsdl-report {
    color-scheme: dark;
    --surface-1: #1a1a19; --surface-2: #242423;
    --text-primary: #ffffff; --text-secondary: #c3c2b7;
    --grid: #3a3a38; --series-1: #3987e5; --bad: #e66767; } }
.rsdl-report h1 { font-size: 1.3rem; margin: 0 0 .25rem; }
.rsdl-report h2 { font-size: 1.05rem; margin: 1.75rem 0 .5rem; }
.rsdl-report .sub { color: var(--text-secondary); margin: 0 0 1rem; }
.rsdl-report table { border-collapse: collapse; margin: .5rem 0; }
.rsdl-report th, .rsdl-report td { padding: .3rem .75rem;
  border-bottom: 1px solid var(--grid); text-align: right; }
.rsdl-report th:first-child, .rsdl-report td:first-child {
  text-align: left; }
.rsdl-report th { color: var(--text-secondary); font-weight: 600; }
.rsdl-report .spark { display: block; margin: .25rem 0 .5rem; }
.rsdl-report .spark .line { fill: none; stroke: var(--series-1);
  stroke-width: 2; stroke-linejoin: round; }
.rsdl-report .spark .dot { fill: var(--series-1); }
.rsdl-report .spark .grid { stroke: var(--grid); stroke-width: 1; }
.rsdl-report .spark text { fill: var(--text-secondary); font-size: 11px; }
.rsdl-report .breach { color: var(--bad); font-weight: 600; }
.rsdl-report .stat { font-size: 1.6rem; font-weight: 650; }
.rsdl-report .stat small { font-size: .85rem; font-weight: 400;
  color: var(--text-secondary); margin-left: .35rem; }
"""


def _fmt(value) -> str:
    if value is None:
        return "–"
    if isinstance(value, float):
        return f"{value:,.1f}" if abs(value) >= 10 else f"{value:,.3f}"
    if isinstance(value, int):
        return f"{value:,}"
    return html.escape(str(value))


def spark_svg(points, width=560, height=72, unit="") -> str:
    """Single-series sparkline: 2px line, baseline grid, last-value dot
    with a direct label, per-point native tooltips."""
    if len(points) < 2:
        return "<p class='sub'>not enough points</p>"
    xs = [p[0] for p in points]
    ys = [p[1] for p in points]
    x_lo, x_hi = min(xs), max(xs)
    y_lo, y_hi = min(ys), max(ys)
    x_span = (x_hi - x_lo) or 1.0
    y_span = (y_hi - y_lo) or 1.0
    pad, label_w = 6, 84
    plot_w, plot_h = width - pad - label_w, height - 2 * pad

    def sx(x):
        return pad + (x - x_lo) / x_span * plot_w

    def sy(y):
        return pad + (1.0 - (y - y_lo) / y_span) * plot_h

    path = " ".join(f"{sx(x):.1f},{sy(y):.1f}" for x, y in points)
    dots = "".join(
        f"<circle cx='{sx(x):.1f}' cy='{sy(y):.1f}' r='6' fill='none' "
        f"pointer-events='all'><title>{_fmt(y)}{unit}</title></circle>"
        for x, y in points)
    last_x, last_y = points[-1]
    return (
        f"<svg class='spark' width='{width}' height='{height}' "
        f"viewBox='0 0 {width} {height}' role='img'>"
        f"<line class='grid' x1='{pad}' y1='{sy(y_lo):.1f}' "
        f"x2='{pad + plot_w}' y2='{sy(y_lo):.1f}'/>"
        f"<polyline class='line' points='{path}'/>"
        f"<circle class='dot' cx='{sx(last_x):.1f}' "
        f"cy='{sy(last_y):.1f}' r='4'/>"
        f"<text x='{sx(last_x) + 8:.1f}' y='{sy(last_y) + 4:.1f}'>"
        f"{_fmt(last_y)}{unit}</text>"
        f"<text x='{pad}' y='{height - 1}'>min {_fmt(y_lo)}{unit} · "
        f"max {_fmt(y_hi)}{unit}</text>"
        f"{dots}</svg>")


def _table(headers, rows) -> str:
    head = "".join(f"<th>{html.escape(str(h))}</th>" for h in headers)
    body = "".join(
        "<tr>" + "".join(f"<td>{cell}</td>" for cell in row) + "</tr>"
        for row in rows)
    return f"<table><thead><tr>{head}</tr></thead><tbody>{body}</tbody></table>"


def _section_history(ring) -> str:
    if ring is None:
        return ""
    parts = ["<h2>Time series (history ring)</h2>"]
    rates = ring.rate("rsdl_events_total", window_ticks=1)
    if len(rates) >= 2:
        parts.append("<p class='sub'>pipeline activity — recorder "
                     "events/s</p>")
        parts.append(spark_svg(rates, unit="/s"))
    waits = ring.series("rsdl_batch_wait_seconds_sum")
    stall_pts = []
    for i in range(1, len(waits)):
        (t0, w0), (t1, w1) = waits[i - 1], waits[i]
        if t1 - t0 > 0:
            stall_pts.append(
                (t1, min(100.0, 100.0 * max(0.0, w1 - w0) / (t1 - t0))))
    if len(stall_pts) >= 2:
        parts.append("<p class='sub'>consumer stall — batch-wait share "
                     "of wall clock</p>")
        parts.append(spark_svg(stall_pts, unit="%"))
    rss = ring.series("rsdl_process_rss_bytes")
    if len(rss) >= 2:
        parts.append("<p class='sub'>resident set size</p>")
        parts.append(spark_svg([(t, v / (1 << 20)) for t, v in rss],
                               unit=" MiB"))
    if len(parts) == 1:
        return ""
    return "".join(parts)


def _section_traces(traced) -> str:
    if not traced:
        return ""
    analysis = traced["analysis"]
    parts = [f"<h2>Critical path ({len(traced['pids'])} process(es): "
             f"{html.escape(str(traced['pids']))})</h2>"]
    self_ms = analysis.get("self_time_ms", {})
    rows = [(html.escape(e["stage"]), _fmt(e["cp_ms"]), _fmt(e["pct"]),
             _fmt(self_ms.get(e["stage"])))
            for e in analysis.get("critical_path", [])]
    parts.append(_table(("stage", "critical-path ms", "%", "self ms"),
                        rows))
    whatif = analysis.get("whatif") or {}
    if whatif:
        rows = [(html.escape(stage),
                 f"-{w['epoch_time_saved_pct']:.1f}%")
                for stage, w in sorted(
                    whatif.items(),
                    key=lambda kv: -kv[1]["epoch_time_saved_pct"])]
        parts.append("<p class='sub'>what-if: 2× faster stage → epoch "
                     "time saved</p>")
        parts.append(_table(("stage", "epoch time"), rows))
    return "".join(parts)


def _section_health(manifest) -> str:
    if not manifest:
        return ""
    verdict = manifest.get("verdict") or {}
    return (
        "<h2>Incident</h2>"
        "<p><span class='breach'>"
        + html.escape(str(verdict.get("detector")
                          or manifest.get("reason", "incident")))
        + " FIRED</span> — "
        + html.escape(str(verdict.get("detail", "")))
        + f" (pids {html.escape(str(manifest.get('pids')))})</p>")


def _section_latency(ring, manifest) -> str:
    """Delivery latency & freshness: the capsule's frozen per-queue
    quantiles and a freshness sparkline (worst queue per tick) from the
    history ring."""
    capsule_latency = (manifest or {}).get("latency") or {}
    fresh_pts = []
    if ring is not None:
        for snap in ring.snapshots():
            series = snap["samples"].get(
                "rsdl_delivery_freshness_seconds")
            if series:
                fresh_pts.append((snap["t"], max(series.values())))
    if not capsule_latency and len(fresh_pts) < 2:
        return ""
    parts = ["<h2>Delivery latency &amp; freshness</h2>"]
    if capsule_latency:
        parts.append("<p class='sub'>capsule snapshot — per hop/queue "
                     "(seconds)</p>")
        parts.append(_table(
            ("series", "p50", "p95", "p99", "n"),
            [(html.escape(key), _fmt(entry.get("p50")),
              _fmt(entry.get("p95")), _fmt(entry.get("p99")),
              _fmt(entry.get("count")))
             for key, entry in sorted(capsule_latency.items())]))
    if len(fresh_pts) >= 2:
        parts.append("<p class='sub'>freshness — worst queue's payload "
                     "age at the consumer's last hop</p>")
        parts.append(spark_svg(fresh_pts, unit="s"))
    return "".join(parts)


def build_html(ring, traced, manifest) -> str:
    body = (
        "<h1>rsdl run report</h1>"
        + _section_health(manifest)
        + _section_latency(ring, manifest)
        + _section_history(ring)
        + _section_traces(traced))
    return ("<!DOCTYPE html><html><head><meta charset='utf-8'>"
            "<title>rsdl run report</title>"
            f"<style>{_CSS}</style></head>"
            f"<body class='rsdl-report'>{body}</body></html>")


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="standalone-HTML run report over history slices, "
                    "trace dumps and incident capsules")
    parser.add_argument("--history", default=None,
                        help="history slice JSON (rsdl-history-v1)")
    parser.add_argument("--trace-dir", default=None,
                        help="directory of recorder dumps")
    parser.add_argument("--capsule", default=None,
                        help="incident capsule directory (uses its "
                             "embedded history + traces unless given "
                             "explicitly)")
    parser.add_argument("-o", "--out", default="rsdl_report.html",
                        help="output HTML path")
    parser.add_argument("--check", action="store_true",
                        help="schema-only smoke over the inputs; no "
                             "HTML write; informational rc 0")
    args = parser.parse_args(argv)

    history_path, trace_dir = args.history, args.trace_dir
    if args.capsule:
        if history_path is None:
            history_path = os.path.join(args.capsule, "history.json")
        if trace_dir is None:
            trace_dir = os.path.join(args.capsule, "traces")

    sources = []
    failures = []

    def _load(name, fn):
        try:
            value = fn()
        except (ValueError, OSError, KeyError) as e:
            failures.append(f"{name}: {e}")
            return None
        sources.append(f"{name}: "
                       + ("ok" if value is not None else "absent"))
        return value

    ring = _load("history", lambda: load_history(history_path))
    traced = _load("traces", lambda: load_traces(trace_dir))
    manifest = _load("capsule",
                     lambda: load_capsule_manifest(args.capsule))

    if args.check:
        for line in sources:
            print(f"rsdl-report: {line}")
        for line in failures:
            print(f"rsdl-report: INVALID {line}")
        print(f"rsdl-report: check done ({len(sources)} source(s), "
              f"{len(failures)} invalid)")
        return 0
    if failures:
        for line in failures:
            print(f"rsdl-report: INVALID {line}", file=sys.stderr)
        return 1
    if not any((ring, traced, manifest)):
        print("rsdl-report: no inputs found (no history, traces, or "
              "capsule)", file=sys.stderr)
        return 2
    text = build_html(ring, traced, manifest)
    with open(args.out, "w", encoding="utf-8") as f:
        f.write(text)
    print(f"rsdl-report: {args.out} ({len(text)} bytes; "
          + "; ".join(sources) + ")")
    return 0


if __name__ == "__main__":
    sys.exit(main())
