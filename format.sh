#!/usr/bin/env bash
# Format/lint gate (reference: format.sh — yapf 0.23.0 + flake8 3.7.7 over
# changed files). Uses yapf/flake8 when installed; always runs a bytecode
# compile check so the gate works on TPU-VM images without lint tools.
set -euo pipefail
cd "$(dirname "$0")"

PY_DIRS=(ray_shuffling_data_loader_tpu tests benchmarks examples)

echo "-- compile check"
python -m compileall -q "${PY_DIRS[@]}" chip_smoke.py __graft_entry__.py setup.py

if python -c 'import yapf' 2>/dev/null; then
    echo "-- yapf (diff mode)"
    python -m yapf --style .style.yapf --recursive --diff "${PY_DIRS[@]}"
else
    echo "-- yapf not installed, skipping"
fi

if python -c 'import flake8' 2>/dev/null; then
    echo "-- flake8"
    python -m flake8 "${PY_DIRS[@]}"
else
    echo "-- flake8 not installed, skipping"
fi

# Project-invariant analyzer (analysis/ is stdlib-only, but importing it
# goes through the package __init__, which needs numpy/pyarrow — skip
# gracefully on images without them, same pattern as yapf/flake8 above).
if python -c 'import ray_shuffling_data_loader_tpu.analysis' 2>/dev/null; then
    # --concurrency adds the whole-program pass (interprocedural
    # locksets, lock-order cycle detection). When the archived runtime
    # order graph is present, the run also cross-checks it against the
    # static graph: dynamic acquisition edges the static pass missed
    # are findings, static cycles confirmed at runtime hard-fail.
    if [ -f .rsdl-locksan-graph.json ]; then
        echo "-- rsdl-lint (concurrency + locksan cross-check)"
        python -m ray_shuffling_data_loader_tpu.analysis --concurrency \
            --locksan-graph .rsdl-locksan-graph.json \
            "${PY_DIRS[@]}" chip_smoke.py __graft_entry__.py tools
    else
        echo "-- rsdl-lint (concurrency)"
        python -m ray_shuffling_data_loader_tpu.analysis --concurrency \
            "${PY_DIRS[@]}" chip_smoke.py __graft_entry__.py tools
    fi
else
    echo "-- rsdl-lint deps not importable, skipping"
fi

# Locksan archival run (RSDL_LOCKSAN_SUITE=1): replay tier-1 with every
# package lock wrapped in the runtime sanitizer and rewrite the
# committed .rsdl-locksan-graph.json artifact that the lint gate above
# cross-checks. Off by default — it costs a full suite run; flip it on
# after changing lock structure in the threaded modules so the archived
# graph's construction-site keys stay in sync with the source.
if [ "${RSDL_LOCKSAN_SUITE:-0}" = "1" ]; then
    echo "-- locksan suite run (rewriting .rsdl-locksan-graph.json)"
    RSDL_LOCKSAN=1 RSDL_LOCKSAN_OUT=.rsdl-locksan-graph.json \
        python -m pytest tests/ -q -m 'not slow' \
        -p no:cacheprovider >/dev/null
    python -m ray_shuffling_data_loader_tpu.analysis --concurrency \
        --locksan-graph .rsdl-locksan-graph.json \
        "${PY_DIRS[@]}" chip_smoke.py __graft_entry__.py tools
fi

# Epoch-plan IR self-test (tools/rsdl_plan.py, stdlib-only): builds a
# demo plan, round-trips it through JSON byte-stably, and proves the
# validator rejects a corrupt lineage key — so a schema drift in
# plan/ir.py surfaces here before any consumer trips over it.
echo "-- rsdl-plan (check mode)"
python tools/rsdl_plan.py --check >/dev/null

# Delivery-latency sketch self-test (tools/rsdl_top.py, stdlib-only):
# observes disjoint values in two registries, merges them through the
# shard-federation path, and requires the merged quantiles to equal a
# directly-merged sketch's — a schema drift in the sketch exposition
# (series suffix, centroid label, merge math) fails here, not in a
# silently-wrong p99 on a dashboard.
echo "-- rsdl-top (check-latency mode)"
python tools/rsdl_top.py --check-latency >/dev/null

echo "OK"
