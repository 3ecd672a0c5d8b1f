#!/usr/bin/env python
"""rsdl-top: live per-stage throughput + stall table for a running loader.

Tails the Prometheus exposition a pipeline exports (file via
``RSDL_METRICS_FILE=/run/rsdl.prom``, or the localhost endpoint via
``RSDL_METRICS_PORT``) and renders the one view that matters online:
which stage is doing the work, at what rate, at what latency, and how
much of the consumer's time is stalled waiting on the loader.

Usage::

    tools/rsdl_top.py --file /run/rsdl.prom            # refresh loop
    tools/rsdl_top.py --url http://127.0.0.1:9200/metrics
    tools/rsdl_top.py --file /run/rsdl.prom --once     # one snapshot
    tools/rsdl_top.py --dir /run/rsdl-shards           # federated view

``--dir`` (default ``$RSDL_TELEMETRY_DIR``) reads the per-pid metric
shards every federated process writes (driver, procpool workers,
supervised queue servers), renders the table over the MERGED totals,
and appends a per-process line for every shard — pool-worker pids (the
``rsdl_executor_worker_up`` gauge) are marked, so the processes doing
the map/reduce work are visible instead of under-counted.

Stdlib-only: the exposition parser is loaded straight from
``runtime/metrics.py`` by file path, so this tool runs on hosts without
numpy/pyarrow/jax installed (a monitoring sidecar, an operator laptop).
Rates and interval percentiles come from deltas between consecutive
samples; ``--once`` prints process-lifetime totals instead.
"""

import argparse
import importlib.util
import os
import sys
import time
import urllib.request

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_METRICS_PATH = os.path.join(_REPO_ROOT, "ray_shuffling_data_loader_tpu",
                             "runtime", "metrics.py")


def _load_metrics_module():
    """Load runtime/metrics.py WITHOUT importing the package (whose
    __init__ pulls numpy/pyarrow); metrics.py itself is stdlib-only."""
    spec = importlib.util.spec_from_file_location("_rsdl_metrics",
                                                  _METRICS_PATH)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_metrics = _load_metrics_module()
parse_exposition = _metrics.parse_exposition

#: Stage display order (mirrors runtime/telemetry.py STAGES).
STAGES = ("map_read", "reduce", "queue_wait", "fetch", "convert",
          "device_transfer", "train_step")


def read_exposition(file: str = None, url: str = None) -> dict:
    if url:
        with urllib.request.urlopen(url, timeout=5) as resp:
            return parse_exposition(resp.read().decode())
    with open(file, encoding="utf-8") as f:
        return parse_exposition(f.read())


def read_shard_dir(directory: str) -> "tuple[dict, dict]":
    """``(merged_samples, per_pid_shards)`` over a federation shard dir
    (runtime/metrics.py read_shards/merge_series, loaded by path)."""
    shards = _metrics.read_shards(directory)
    merged, _ = _metrics.merge_series(list(shards.values()))
    return merged, shards


def render_processes(shards: dict, merged: dict) -> str:
    """Per-process lines: one row per shard pid, pool-worker pids (the
    rsdl_executor_worker_up gauge) marked — the blind spot the
    federation exists to close."""
    worker_pids = {dict(labels).get("pid")
                   for labels, value in merged.get(
                       "rsdl_executor_worker_up", {}).items()
                   if value >= 1}
    header = (f"{'pid':<9} {'role':<8} {'events':>10} {'tasks':>7} "
              f"{'stage s':>9} {'age':>6}")
    lines = ["", header, "-" * len(header)]
    for pid, (samples, _types, age_s) in sorted(shards.items()):
        events = sum(samples.get("rsdl_events_total", {}).values())
        tasks = sum(samples.get("rsdl_worker_tasks_total", {}).values())
        stage_s = sum(samples.get("rsdl_stage_seconds_sum", {}).values())
        role = "worker" if str(pid) in worker_pids else "proc"
        lines.append(f"{pid:<9} {role:<8} {int(events):>10} "
                     f"{int(tasks):>7} {stage_s:>9.2f} {age_s:>5.0f}s")
    return "\n".join(lines)


def _series(parsed: dict, name: str, **want) -> dict:
    """{labels_dict_frozen: value} for samples of ``name`` matching the
    given label filters (ignoring extra labels like ``le``)."""
    out = {}
    for labels, value in parsed.get(name, {}).items():
        d = dict(labels)
        if all(d.get(k) == v for k, v in want.items()):
            out[labels] = value
    return out


def _stage_scalar(parsed: dict, suffix: str, stage: str) -> float:
    for labels, value in parsed.get(f"rsdl_stage_seconds{suffix}",
                                    {}).items():
        if dict(labels).get("stage") == stage:
            return value
    return 0.0


def _stage_buckets(parsed: dict, stage: str) -> dict:
    """{le_bound_float: cumulative_count} for one stage."""
    out = {}
    for labels, value in parsed.get("rsdl_stage_seconds_bucket",
                                    {}).items():
        d = dict(labels)
        if d.get("stage") != stage or "le" not in d:
            continue
        le = float("inf") if d["le"] == "+Inf" else float(d["le"])
        out[le] = value
    return out


def _p95_from_bucket_delta(now: dict, before: dict) -> float:
    """p95 (seconds) of the interval distribution between two cumulative
    bucket snapshots, by linear interpolation in the winning bucket."""
    bounds = sorted(now)
    deltas = []
    prev_now = prev_before = 0.0
    for bound in bounds:
        d = ((now[bound] - prev_now)
             - (before.get(bound, 0.0) - prev_before))
        deltas.append((bound, max(0.0, d)))
        prev_now, prev_before = now[bound], before.get(bound, 0.0)
    total = sum(d for _, d in deltas)
    if total <= 0:
        return 0.0
    rank = 0.95 * total
    seen = 0.0
    lo = 0.0
    for bound, d in deltas:
        if d and seen + d >= rank:
            hi = bound if bound != float("inf") else lo
            return lo + (hi - lo) * ((rank - seen) / d)
        seen += d
        if bound != float("inf"):
            lo = bound
    return lo


def _scalar(parsed: dict, name: str) -> float:
    return sum(parsed.get(name, {}).values())


def _by_label(parsed: dict, name: str, label: str) -> dict:
    """{label_value: summed value} for one metric's samples."""
    out = {}
    for labels, value in parsed.get(name, {}).items():
        key = dict(labels).get(label)
        if key is not None:
            out[key] = out.get(key, 0.0) + value
    return out


def _human_bytes(n: float) -> str:
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024 or unit == "TB":
            return f"{n:.0f} {unit}" if unit == "B" else f"{n:.1f} {unit}"
        n /= 1024.0
    return f"{n:.1f} TB"


def render_shards(parsed: dict) -> list:
    """One line per serving-plane shard (multiqueue_service v3): queue
    depth, handle-hit share of table frames, actual wire bytes, and the
    compression saving — the per-shard federated view the shard map
    spreads across processes."""
    depth = _by_label(parsed, "rsdl_queue_shard_depth", "shard")
    hits = _by_label(parsed, "rsdl_queue_handle_hits_total", "shard")
    misses = _by_label(parsed, "rsdl_queue_handle_misses_total", "shard")
    wire = _by_label(parsed, "rsdl_queue_bytes_on_wire_total", "shard")
    saved = _by_label(parsed, "rsdl_queue_compression_saved_bytes_total",
                      "shard")
    shards = sorted(set(depth) | set(hits) | set(misses) | set(wire),
                    key=lambda s: (len(s), s))
    if not shards:
        return []
    lines = ["serving shards:"]
    for shard in shards:
        h, m = hits.get(shard, 0.0), misses.get(shard, 0.0)
        hit_pct = 100.0 * h / (h + m) if h + m else 0.0
        line = (f"  shard {shard}: depth {int(depth.get(shard, 0)):>5}  "
                f"handle-hit {hit_pct:5.1f}%  "
                f"wire {_human_bytes(wire.get(shard, 0.0)):>10}")
        if saved.get(shard):
            line += f"  saved {_human_bytes(saved[shard])}"
        lines.append(line)
    return lines


def render_storage(parsed: dict) -> list:
    """Per-tier storage-cache lines (storage/cache.py): hit share,
    eviction/corruption counts and resident bytes per tier, plus the
    prefetch plane's issued/hit/canceled efficiency and total remote
    bytes — the "is the cold path actually caching" one-liner."""
    hits = _by_label(parsed, "rsdl_storage_hits_total", "tier")
    misses = _by_label(parsed, "rsdl_storage_misses_total", "tier")
    evictions = _by_label(parsed, "rsdl_storage_evictions_total", "tier")
    corrupt = _by_label(parsed, "rsdl_storage_corrupt_total", "tier")
    tier_bytes = _by_label(parsed, "rsdl_storage_tier_bytes", "tier")
    tiers = [t for t in ("hot", "disk", "remote")
             if t in set(hits) | set(misses) | set(tier_bytes)]
    if not tiers:
        return []
    lines = ["storage tiers:"]
    for tier in tiers:
        h, m = hits.get(tier, 0.0), misses.get(tier, 0.0)
        hit_pct = 100.0 * h / (h + m) if h + m else 0.0
        line = (f"  {tier:<6} hit {hit_pct:5.1f}% ({int(h)}/{int(h + m)})"
                f"  bytes {_human_bytes(tier_bytes.get(tier, 0.0)):>10}")
        if evictions.get(tier):
            line += f"  evicted {int(evictions[tier])}"
        if corrupt.get(tier):
            line += f"  CORRUPT {int(corrupt[tier])}"
        lines.append(line)
    issued = _scalar(parsed, "rsdl_storage_prefetch_issued_total")
    if issued:
        p_hits = _scalar(parsed, "rsdl_storage_prefetch_hits_total")
        canceled = _scalar(parsed, "rsdl_storage_prefetch_canceled_total")
        lines.append(f"  prefetch: {int(issued)} issued  "
                     f"{int(p_hits)} hit "
                     f"({100.0 * p_hits / issued:.0f}% efficient)  "
                     f"{int(canceled)} canceled")
    remote = _scalar(parsed, "rsdl_storage_remote_bytes_read_total")
    if remote:
        lines.append(f"  remote bytes read: {_human_bytes(remote)}")
    return lines


def render_streaming(parsed: dict) -> list:
    """One streaming line (streaming/): current window, watermark lag in
    stream seconds (the watermark_lag detector's series), and the late-
    event count — the "is online training keeping up" one-liner. Silent
    when the process never streamed."""
    closed = _scalar(parsed, "rsdl_stream_windows_closed_total")
    events = _scalar(parsed, "rsdl_stream_events_admitted_total")
    if not closed and not events:
        return []
    window = _scalar(parsed, "rsdl_stream_window")
    lag = _scalar(parsed, "rsdl_stream_watermark_lag_seconds")
    late = _scalar(parsed, "rsdl_stream_late_events_total")
    line = (f"streaming: window {int(window)} ({int(closed)} closed, "
            f"{int(events)} events)   lag {lag:.1f}s")
    if late:
        by_policy = _by_label(parsed, "rsdl_stream_late_events_total",
                              "policy")
        detail = " ".join(f"{policy}={int(n)}"
                          for policy, n in sorted(by_policy.items()))
        line += f"   late {int(late)} ({detail})"
    return [line]


def render_tenants(parsed: dict) -> list:
    """Per-tenant QoS lines (tenancy/): delivered bytes, in-flight
    replay vs fair-share budget, cache residency vs quota, prefetch
    throttles, and the delivery-latency sketch's p50/p99 — the "is the
    weighted-fair scheduler actually honoring the weights" view.
    Silent in single-tenant deployments (no tenant series minted)."""
    delivered = _by_label(parsed, "rsdl_tenant_bytes_delivered_total",
                          "tenant")
    replay = _by_label(parsed, "rsdl_tenant_replay_bytes", "tenant")
    budget = _by_label(parsed, "rsdl_tenant_budget_bytes", "tenant")
    cache = _by_label(parsed, "rsdl_tenant_cache_bytes", "tenant")
    quota = _by_label(parsed, "rsdl_tenant_cache_quota_bytes", "tenant")
    throttled = _by_label(parsed, "rsdl_tenant_prefetch_throttled_total",
                          "tenant")
    tenants = sorted(set(delivered) | set(replay) | set(cache))
    if not tenants:
        return []
    sketch = parsed.get("rsdl_tenant_delivery_latency_seconds_centroid", {})
    stats = _metrics.sketch_quantiles(
        {"rsdl_tenant_delivery_latency_seconds_centroid": sketch},
        "rsdl_tenant_delivery_latency_seconds",
        hop="queued_to_delivered") if sketch else {}
    by_tenant_lat = {}
    for labels, entry in stats.items():
        tenant = dict(labels).get("tenant")
        if tenant is not None:
            by_tenant_lat[tenant] = entry
    lines = ["tenants:"]
    for tenant in tenants:
        line = (f"  {tenant:<12} "
                f"delivered {_human_bytes(delivered.get(tenant, 0.0)):>10}")
        if tenant in budget:
            line += (f"  inflight {_human_bytes(replay.get(tenant, 0.0))}"
                     f"/{_human_bytes(budget[tenant])}")
        if tenant in cache:
            line += f"  cache {_human_bytes(cache[tenant])}"
            if quota.get(tenant):
                line += f"/{_human_bytes(quota[tenant])}"
        if throttled.get(tenant):
            line += f"  throttled {int(throttled[tenant])}"
        entry = by_tenant_lat.get(tenant)
        if entry:
            line += (f"  p50 {entry['p50'] * 1e3:.1f}ms "
                     f"p99 {entry['p99'] * 1e3:.1f}ms")
        lines.append(line)
    waiting = _scalar(parsed, "rsdl_admission_waiting")
    used = _scalar(parsed, "rsdl_admission_used_bytes")
    rejected = sum(v for labels, v in
                   parsed.get("rsdl_admission_decisions_total", {}).items()
                   if dict(labels).get("action") == "reject")
    if waiting or used or rejected:
        lines.append(f"  admission: {_human_bytes(used)} charged  "
                     f"{int(waiting)} waiting  {int(rejected)} rejected")
    return lines


def render_membership(parsed: dict) -> list:
    """One membership line (membership/): current view id, live vs
    suspect rank counts, per-rank incarnations, the fenced-frame count,
    and the age of the last view transition — the "did the world just
    resize, and is anything flapping" one-liner. Silent when the
    process never ran elastic membership."""
    import time as _time
    view = _scalar(parsed, "rsdl_member_view_id")
    live = _scalar(parsed, "rsdl_member_live")
    transitions = sum(
        parsed.get("rsdl_member_transitions_total", {}).values())
    if not live and not transitions:
        return []
    suspect = _scalar(parsed, "rsdl_member_suspect")
    fenced = _scalar(parsed, "rsdl_member_fenced_frames_total")
    flaps = _scalar(parsed, "rsdl_member_flaps_total")
    incarnations = _by_label(parsed, "rsdl_member_incarnation", "rank")
    line = (f"membership: view {int(view)}   live {int(live)}"
            f"  suspect {int(suspect)}")
    if incarnations:
        detail = " ".join(
            f"r{rank}:{int(inc)}"
            for rank, inc in sorted(incarnations.items(),
                                    key=lambda kv: int(kv[0])))
        line += f"   incarnations {detail}"
    last = _scalar(parsed, "rsdl_member_last_transition_unixtime")
    if last:
        # Cross-process age: the gauge IS a serialized wall-clock
        # timestamp, so wall clock is the only comparable clock here.
        # rsdl-lint: disable=wallclock-interval
        age = max(0.0, _time.time() - last)
        line += f"   last transition {age:.0f}s ago"
    if fenced:
        line += f"   FENCED {int(fenced)}"
    if flaps:
        line += f"   flaps {int(flaps)}"
    return [line]


def render_rebalance(parsed: dict) -> list:
    """One rebalance line (rebalance/): placement generation, live
    override count, decisions by kind, fenced zombie frames, and the
    age of the last committed move — the "did the serving plane just
    move a rank, and did anything leak" one-liner. Silent when no
    rebalance controller ever ran."""
    import time as _time
    generation = _scalar(parsed, "rsdl_rebalance_generation")
    decisions = _by_label(parsed, "rsdl_rebalance_decisions_total", "kind")
    if not generation and not decisions:
        return []
    overrides = _scalar(parsed, "rsdl_rebalance_overrides")
    moves = _scalar(parsed, "rsdl_rebalance_moves_total")
    fenced = _scalar(parsed, "rsdl_rebalance_fenced_frames_total")
    line = (f"rebalance: generation {int(generation)}   "
            f"moves {int(moves)}   overrides {int(overrides)}")
    if decisions:
        detail = " ".join(f"{kind}={int(n)}"
                          for kind, n in sorted(decisions.items()))
        line += f"   decisions {detail}"
    last = _scalar(parsed, "rsdl_rebalance_last_move_unixtime")
    if last:
        # Cross-process age: the gauge IS a serialized wall-clock
        # timestamp, so wall clock is the only comparable clock here.
        # rsdl-lint: disable=wallclock-interval
        age = max(0.0, _time.time() - last)
        line += f"   last move {age:.0f}s ago"
    if fenced:
        line += f"   FENCED {int(fenced)}"
    return [line]


def render_step(parsed: dict) -> list:
    """One step line from the train step's own counters
    (utils/tracing.step_stat, folded by runtime/telemetry): the share of
    the sparse layers' (token, pick) pairs whose expert this chip holds,
    against the held share of the experts an even routing gives; the
    tiles the expert walk took in the last folded step and in the mean
    step, with the mean by layer, and the rounds a walk (over 1: a
    routing that outgrew the buffer an even one fills); the fullest held
    expert's pairs against a tile's rows. Silent when no step has folded
    a walk."""
    pairs = _scalar(parsed, "rsdl_moe_pairs_total")
    steps = _scalar(parsed, "rsdl_moe_tiles_per_step_count")
    if not pairs or not steps:
        return []
    held = _scalar(parsed, "rsdl_moe_pairs_held_total")
    line = f"step: held pairs {100.0 * held / pairs:.1f}%"
    routed = _scalar(parsed, "rsdl_moe_experts_routed")
    if routed:
        even = _scalar(parsed, "rsdl_moe_experts_held") / routed
        line += f" (even routing {100.0 * even:.1f}%)"
    line += (f"   tiles/step last "
             f"{int(_scalar(parsed, 'rsdl_moe_tiles_last_step'))} mean "
             f"{_scalar(parsed, 'rsdl_moe_tiles_per_step_sum') / steps:.1f}")
    by_layer = _by_label(parsed, "rsdl_moe_tiles_total", "layer")
    if by_layer:
        line += " (by layer " + "/".join(
            f"{by_layer[layer] / steps:.1f}"
            for layer in sorted(by_layer, key=int)) + ")"
        rounds = _scalar(parsed, "rsdl_moe_rounds_total")
        line += f"   rounds/walk {rounds / (steps * len(by_layer)):.2f}"
    fullest = _by_label(parsed, "rsdl_moe_fullest_expert_rows", "layer")
    if fullest:
        line += f"   fullest expert {int(max(fullest.values()))} rows"
        tile = _scalar(parsed, "rsdl_moe_tile_rows")
        if tile:
            line += f" (tile {int(tile)})"
    line += (f"   over {int(steps)} of "
             f"{int(_scalar(parsed, 'rsdl_step_stats_folded_total'))} "
             "folded steps")
    return [line]


def render_latency(parsed: dict, before: dict = None) -> list:
    """Per-queue delivery-latency lines (runtime/latency.py sketch):
    p50/p95/p99 of the end-to-end birth->delivered hop plus the queue's
    freshness gauge. In refresh mode the quantiles are computed over the
    INTERVAL's centroid deltas (cumulative counts subtract exactly);
    ``--once`` shows lifetime quantiles."""
    series = "rsdl_delivery_latency_seconds_centroid"
    now = parsed.get(series, {})
    if not now:
        return []
    if before is not None:
        base = before.get(series, {})
        now = {labels: value - base.get(labels, 0.0)
               for labels, value in now.items()
               if value - base.get(labels, 0.0) > 0}
        if not now:
            return []
    stats = _metrics.sketch_quantiles(
        {series: now}, "rsdl_delivery_latency_seconds",
        hop="birth_to_delivered")
    if not stats:
        return []
    fresh = _by_label(parsed, "rsdl_delivery_freshness_seconds", "queue")
    lines = ["delivery latency (birth->delivered):"]
    for labels, entry in sorted(stats.items()):
        queue = dict(labels).get("queue", "?")
        line = (f"  queue {queue}: p50 {entry['p50'] * 1e3:7.1f}ms  "
                f"p95 {entry['p95'] * 1e3:7.1f}ms  "
                f"p99 {entry['p99'] * 1e3:7.1f}ms  "
                f"n {int(entry['count'])}")
        if queue in fresh:
            line += f"  fresh {fresh[queue]:.1f}s"
        lines.append(line)
    return lines


def check_latency() -> int:
    """Sketch merge self-test (``--check-latency``, wired into
    format.sh's informational block): observe disjoint values in two
    registries, render -> parse -> federation-merge, and require the
    merged quantiles to equal a directly-merged sketch's — so a schema
    drift anywhere in the sketch's shard exposition (series suffix,
    centroid label, merge math) fails fast, before a real run's p99
    silently reads wrong."""
    name = "rsdl_delivery_latency_seconds"
    values_a = [0.002, 0.004, 0.008, 0.05]
    values_b = [0.1, 0.9, 2.0]
    regs = [_metrics.Registry(), _metrics.Registry()]
    for reg, values in zip(regs, (values_a, values_b)):
        sk = reg.sketch(name, "self-test", hop="birth_to_delivered",
                        queue="0")
        for v in values:
            sk.observe(v)
    shards = [_metrics.parse_exposition_typed(reg.render())
              for reg in regs]
    merged, types = _metrics.merge_series(shards)
    if types.get(name) != "sketch":
        print(f"check-latency: TYPE line lost (got {types.get(name)!r})")
        return 1
    stats = _metrics.sketch_quantiles(merged, name)
    direct = _metrics.Sketch()
    for v in values_a + values_b:
        direct.observe(v)
    for labels, entry in stats.items():
        if int(entry["count"]) != direct.count:
            print(f"check-latency: merged count {entry['count']} != "
                  f"direct {direct.count}")
            return 1
        for q, key in ((0.5, "p50"), (0.95, "p95"), (0.99, "p99")):
            if abs(entry[key] - direct.percentile(q)) > 1e-12:
                print(f"check-latency: merged {key} {entry[key]} != "
                      f"direct {direct.percentile(q)}")
                return 1
    if not stats:
        print("check-latency: no sketch series survived the round-trip")
        return 1
    # The merged exposition must itself round-trip (the federation file
    # and HTTP endpoint serve render_merged output).
    reparsed, _ = _metrics.parse_exposition_typed(
        _metrics.render_merged(merged, types))
    if reparsed.get(f"{name}_centroid") != merged.get(f"{name}_centroid"):
        print("check-latency: render_merged did not round-trip")
        return 1
    print("check-latency: sketch merge/exposition round-trip OK "
          f"(p99 {direct.percentile(0.99)}s over {direct.count} samples)")
    return 0


def render(parsed: dict, before: dict = None, interval_s: float = None
           ) -> str:
    """One table: per-stage events/s (or totals), busy share, p95."""
    lines = []
    rate_mode = before is not None and interval_s
    header = (f"{'stage':<16} {'events/s':>10} {'busy%':>7} {'p95 ms':>9}"
              if rate_mode else
              f"{'stage':<16} {'events':>10} {'total s':>8} {'mean ms':>9}")
    lines.append(header)
    lines.append("-" * len(header))
    for stage in STAGES:
        count = _stage_scalar(parsed, "_count", stage)
        total = _stage_scalar(parsed, "_sum", stage)
        if rate_mode:
            d_count = count - _stage_scalar(before, "_count", stage)
            d_sum = total - _stage_scalar(before, "_sum", stage)
            if d_count == 0 and count == 0:
                continue
            p95_s = _p95_from_bucket_delta(
                _stage_buckets(parsed, stage), _stage_buckets(before, stage))
            lines.append(f"{stage:<16} {d_count / interval_s:>10.1f} "
                         f"{100.0 * d_sum / interval_s:>6.1f}% "
                         f"{p95_s * 1e3:>9.1f}")
        else:
            if count == 0:
                continue
            mean_ms = total / count * 1e3
            lines.append(f"{stage:<16} {int(count):>10} "
                         f"{total:>8.2f} {mean_ms:>9.1f}")
    wait_sum = _scalar(parsed, "rsdl_batch_wait_seconds_sum")
    wait_count = _scalar(parsed, "rsdl_batch_wait_seconds_count")
    if rate_mode:
        d_wait = wait_sum - _scalar(before, "rsdl_batch_wait_seconds_sum")
        d_batches = (wait_count
                     - _scalar(before, "rsdl_batch_wait_seconds_count"))
        lines.append("")
        lines.append(f"stall: {100.0 * d_wait / interval_s:.1f}% of wall "
                     f"({d_batches / interval_s:.1f} batches/s)")
    elif wait_count:
        lines.append("")
        lines.append(f"stall: {wait_sum:.2f}s total batch-wait over "
                     f"{int(wait_count)} batches")
    stalls = _scalar(parsed, "rsdl_watchdog_events_total")
    faults = _scalar(parsed, "rsdl_faults_injected_total")
    if stalls or faults:
        lines.append(f"watchdog stalls: {int(stalls)}   "
                     f"faults injected: {int(faults)}")
    # Queue-service consumer health (multiqueue_service leases): live
    # consumers, expired leases (dead trainers), and crash-recovery
    # activity — the operator's first question when ranks go quiet.
    alive = _scalar(parsed, "rsdl_queue_consumers_alive")
    expiries = _scalar(parsed, "rsdl_queue_lease_expiries_total")
    replayed = _scalar(parsed, "rsdl_queue_frames_replayed_total")
    restarts = _scalar(parsed, "rsdl_queue_server_restarts_total")
    if alive or expiries or replayed or restarts:
        lines.append(
            f"consumers: {int(alive)} alive   "
            f"dead (lease expired): {int(expiries)}   "
            f"frames replayed: {int(replayed)}   "
            f"server restarts: {int(restarts)}")
    lines.extend(render_shards(parsed))
    lines.extend(render_storage(parsed))
    lines.extend(render_tenants(parsed))
    lines.extend(render_membership(parsed))
    lines.extend(render_rebalance(parsed))
    lines.extend(render_streaming(parsed))
    lines.extend(render_step(parsed))
    lines.extend(render_latency(parsed, before=before if rate_mode
                                else None))
    # Critical-path line (runtime/trace.py gauges, refreshed per epoch):
    # the top-3 stages by critical-path self time plus the current
    # straggler task — the "what do I optimize" one-liner.
    cp = [(dict(labels).get("stage"), value) for labels, value in
          parsed.get("rsdl_trace_cp_seconds", {}).items()]
    cp = sorted(((s, v) for s, v in cp if s), key=lambda kv: -kv[1])[:3]
    if cp:
        line = "critical path: " + " > ".join(
            f"{stage} {value:.2f}s" for stage, value in cp)
        strag = [(dict(labels).get("stage"), value) for labels, value in
                 parsed.get("rsdl_trace_straggler_seconds", {}).items()]
        strag = sorted(((s, v) for s, v in strag if s),
                       key=lambda kv: -kv[1])
        if strag:
            stage = strag[0][0]
            task = None
            for labels, value in parsed.get("rsdl_trace_straggler_task",
                                            {}).items():
                if dict(labels).get("stage") == stage:
                    task = int(value)
            line += (f"   straggler: {stage}"
                     + (f" task {task}" if task is not None else "")
                     + f" ({strag[0][1]:.2f}s)")
        lines.append(line)
    return "\n".join(lines)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="live per-stage throughput + stall table over the "
                    "rsdl Prometheus exposition")
    parser.add_argument("--file", default=os.environ.get("RSDL_METRICS_FILE")
                        or None, help="exposition file path "
                        "(default: $RSDL_METRICS_FILE)")
    parser.add_argument("--url", default=None,
                        help="exposition HTTP URL, e.g. "
                             "http://127.0.0.1:9200/metrics")
    parser.add_argument("--dir", default=os.environ.get(
        "RSDL_TELEMETRY_DIR") or None,
        help="federation shard directory: merged table + per-process "
             "lines (default: $RSDL_TELEMETRY_DIR)")
    parser.add_argument("--interval", type=float, default=2.0,
                        help="refresh seconds (default 2)")
    parser.add_argument("--once", action="store_true",
                        help="print one lifetime-totals snapshot and exit")
    parser.add_argument("--check-latency", action="store_true",
                        help="run the latency-sketch merge/exposition "
                             "self-test and exit (0 = OK)")
    args = parser.parse_args(argv)
    if args.check_latency:
        return check_latency()
    if not args.file and not args.url and not args.dir:
        parser.error("need --file, --url or --dir "
                     "(or set RSDL_METRICS_FILE / RSDL_TELEMETRY_DIR)")

    def _read():
        if args.file or args.url:
            parsed = read_exposition(args.file, args.url)
            shards = (_metrics.read_shards(args.dir) if args.dir else {})
            return parsed, shards
        return read_shard_dir(args.dir)

    try:
        parsed, shards = _read()
    except (OSError, ValueError) as e:
        print(f"cannot read exposition: {e}", file=sys.stderr)
        return 1
    if args.once:
        print(render(parsed))
        if shards:
            print(render_processes(shards, parsed))
        return 0
    before = parsed
    # Monotonic interval timing (the exposition may come from another
    # host; never trust wall clock for rates).
    last = time.monotonic()
    try:
        # Refresh loop, not a retry: a top-style tool runs until ^C, and
        # a transient exposition-read failure skips one frame rather
        # than re-attempting an operation: rsdl-lint: disable=unbounded-retry
        while True:
            time.sleep(args.interval)
            try:
                parsed, shards = _read()
            except (OSError, ValueError) as e:
                print(f"read failed: {e}", file=sys.stderr)
                continue
            now = time.monotonic()
            sys.stdout.write("\x1b[2J\x1b[H")  # clear + home
            print(render(parsed, before=before, interval_s=now - last))
            if shards:
                print(render_processes(shards, parsed))
            sys.stdout.flush()
            before, last = parsed, now
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main())
