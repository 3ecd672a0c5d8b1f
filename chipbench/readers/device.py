"""Per-layer metrics read from the device's side of the profiler trace of
a ``--trace 1`` run. A reader returns ``None`` where the trace, or this
cell, gives it nothing to read. A share over 100 % is an error here,
before anything is printed."""

from __future__ import annotations

import statistics
from typing import Any, Dict, Optional

from chipbench import peaks, xplane


def _share(name: str, value: float) -> float:
    if value > 100.0:
        raise ValueError(f"{name} reads {value:.3f} %: the operations or "
                         "bytes are counted too high, or the time leaves "
                         "out part of the work")
    return value


def device_idle_pct(facts: Dict[str, Any], kind: str) -> Optional[float]:
    """1 - union of the operations' intervals over the traced window,
    averaged over the chips."""
    if facts.get("trace") is None or facts.get("kind") != kind:
        return None
    return 100.0 * xplane.idle_share(facts["trace"], facts["trace_window"])


def device_step_ms(facts: Dict[str, Any], module: str) -> Optional[float]:
    """Median device time of one run of the jitted train step."""
    if facts.get("trace") is None:
        return None
    runs = xplane.module_durations(facts["trace"], facts["trace_window"],
                                   module)
    return 1e3 * statistics.median(runs) if runs else None


def model_flops_util_pct(facts: Dict[str, Any]) -> Optional[float]:
    """Forward and backward matrix-multiply FLOPs per row, from the
    configuration's shapes, times the rows per second of the traced
    window, over the chips' published bf16 peak."""
    if facts.get("trace") is None or facts.get("kind") != "train":
        return None
    flops = facts["reference"].train_flops_per_row(facts["sizes"])
    peak = peaks.peaks_of(facts["device"]["kind"])["bf16_flops_per_s"]
    return _share("model_flops_util_pct", 100.0 * flops
                  * facts["rate_rows_per_s"] / (facts["chips"] * peak))


def step_roofline_pct(facts: Dict[str, Any], module: str) -> Optional[float]:
    """The least time one chip could take for its share of one step (the
    larger of FLOPs over peak and bytes over HBM bandwidth, from the
    configuration's shapes and stated optimizer) over the step's device
    time. An earlier line says which bound it is."""
    step_ms = device_step_ms(facts, module)
    if step_ms is None:
        return None
    ref, sizes = facts["reference"], facts["sizes"]
    rows = facts["rows_per_step"] // facts["chips"]
    least_s, bound = peaks.roofline_seconds(
        ref.train_flops_per_row(sizes) * rows,
        ref.train_step_bytes(sizes, rows), facts["device"]["kind"])
    print(f"# step roofline: bound by {bound}, least {least_s * 1e3:.4f} ms "
          f"a step of {rows} rows a chip, measured {step_ms:.4f} ms",
          flush=True)
    return _share("step_roofline_pct", 100.0 * least_s * 1e3 / step_ms)


def kernel_pct_of_step(facts: Dict[str, Any], pattern: str, module: str
                       ) -> Optional[float]:
    """Device time of the operations whose HLO text matches ``pattern``
    over the device time of the jitted step's runs."""
    if facts.get("trace") is None:
        return None
    trace, win = facts["trace"], facts["trace_window"]
    steps = sum(xplane.module_durations(trace, win, module))
    kernel = xplane.matching_seconds(trace, win, pattern)
    if steps <= 0 or kernel <= 0:
        return None
    return _share("kernel_pct_of_step", 100.0 * kernel / steps)


def scope_pct_of_step(facts: Dict[str, Any], scope: str, module: str
                      ) -> Optional[float]:
    """Device time of the operations the program ran under ``scope`` (a
    ``jax.named_scope`` of its own) over the device time of the jitted
    step's runs. Which operations those are comes from the compiled
    step's text (``facts["step_op_names"]``): whatever XLA made of the
    scope's work, fusions and collectives alike."""
    if facts.get("trace") is None or not facts.get("step_op_names"):
        return None
    trace, win = facts["trace"], facts["trace_window"]
    runs = xplane.module_durations(trace, win, module)
    by_op = xplane.scope_op_seconds(trace, win, scope,
                                    facts["step_op_names"], module)
    under = sum(by_op.values())
    if not runs or under <= 0:
        return None
    print(f"# scope {scope}: {1e3 * under / len(runs):.4f} ms a step of "
          f"{1e3 * sum(runs) / len(runs):.4f} ms over {len(runs)} steps: "
          + ", ".join(f"{what or '(itself)'} {1e3 * s / len(runs):.4f}"
                      for what, s in sorted(by_op.items(),
                                            key=lambda kv: -kv[1])),
          flush=True)
    return _share("scope_pct_of_step", 100.0 * under / sum(runs))


def collective_exposed_pct(facts: Dict[str, Any]) -> Optional[float]:
    """Collective time during which no other operation runs on that chip,
    over the traced window."""
    if facts.get("trace") is None or facts["chips"] < 2:
        return None
    trace, win = facts["trace"], facts["trace_window"]
    return _share("collective_exposed_pct",
                  100.0 * xplane.exposed_collective_seconds(trace, win)
                  / (win[1] - win[0]))
