"""The selective scan's carry as a metric, with the differential attention
layers' ``lambda`` printed beside it: both ride out of the jitted step as
its own statistics (``utils/tracing.step_stat``'s ``ssm_scan``, which
Mamba-1's scan records as Mamba-2's does, and ``diff_attention``, from
``models/mellum.py``).

The number is ``readers/ssm_stats.py``'s (every layer that recorded a
scan, the traced window's steps); what this module adds is one ``# step
stats`` line a step with each differential layer's ``lambda``. A program
without the channel or without the statistic gives it nothing to read: no
line, and the metric as ``ssm_stats`` has it.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

from chipbench.readers import ssm_stats, step_stats

LAMBDA = "diff_attention"
_PRINTED_KEY = "_diff_lambda_printed"

Entry = Dict[str, Any]


def series_lines(entries: Sequence[Entry], window_steps: Sequence[int]
                 ) -> List[str]:
    """One ``# step stats`` line a step that recorded a ``lambda``: each
    differential layer's; a ``*`` marks a step of the traced window."""
    inside = set(window_steps)
    lines = []
    for e in entries:
        rows = e["stats"].get(LAMBDA)
        if rows:
            lines.append(
                f"# step stats {e['step']}"
                f"{'*' if e['step'] in inside else ''}: lambda "
                + "/".join(f"{row['lambda']:.6f}" for row in rows)
                + " (layers " + "/".join(str(row["layer"]) for row in rows)
                + ")")
    return lines


def _print_lambdas(facts: Dict[str, Any]) -> None:
    """Once a run: the series of every step the ring still holds."""
    if facts.get(_PRINTED_KEY):
        return
    facts[_PRINTED_KEY] = True
    ring = step_stats.channel()
    path, window = facts.get("trace_path"), facts.get("trace_window")
    if ring is None or not path or window is None:
        return
    steps = step_stats.annotated_steps(path, window)
    ring.fold_step_stats(wait=True)
    for line in series_lines(ring.step_stats(), steps):
        print(line, flush=True)


def sscan_carry_pct(facts: Dict[str, Any]) -> Optional[float]:
    """``ssm_stats.ssm_carry_pct`` (of the state a chunk of the scan
    starts from, the share that reaches the chunk's end, in per cent,
    over the traced window's steps and the layers that scan), after the
    differential layers' ``lambda`` lines."""
    _print_lambdas(facts)
    return ssm_stats.ssm_carry_pct(facts)
