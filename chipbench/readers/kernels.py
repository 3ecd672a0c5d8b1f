"""A kernel's share of its roofline, from the device's side of the
profiler trace of a ``--trace 1`` run: the least time the chip could take
for the kernel's mathematics over the device time its operations took.

The mathematics' FLOPs and least HBM bytes come from the configuration's
reference (``moe_work(sizes, rows)``, ``attention_work(sizes, rows)``:
whatever implements it, recomputation not counted); the time is that of
the operations the program ran under a ``jax.named_scope`` of its own
(``xplane.scope_op_seconds``), a step. A reader returns ``None`` where the
trace, the reference or the program gives it nothing to read (a program
without the scope, a reference without the function); a share over 100 %
is an error here, before anything is printed.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from chipbench import peaks, xplane


def scope_roofline_pct(facts: Dict[str, Any], scope: str, work: str,
                       module: str) -> Optional[float]:
    """The larger of FLOPs over the chip's peak and bytes over its HBM
    bandwidth, of ``reference.<work>(sizes, rows a chip)`` for a step,
    over the device time a step spends under ``scope`` in the runs of
    ``module``."""
    work_of = getattr(facts.get("reference"), work, None)
    if (facts.get("trace") is None or not facts.get("step_op_names")
            or work_of is None):
        return None
    trace, win = facts["trace"], facts["trace_window"]
    runs = xplane.module_durations(trace, win, module)
    under = xplane.scope_seconds(trace, win, scope, facts["step_op_names"],
                                 module)
    if not runs or under <= 0:
        return None
    rows = facts["rows_per_step"] // facts["chips"]
    flops, hbm_bytes = work_of(facts["sizes"], rows)
    least_s, bound = peaks.roofline_seconds(flops, hbm_bytes,
                                            facts["device"]["kind"])
    step_s = under / len(runs)
    print(f"# roofline of {scope}: {flops / 1e12:.4f} TFLOP and "
          f"{hbm_bytes / 1e9:.4f} GB a step of {rows} rows a chip, bound by "
          f"{bound}, least {least_s * 1e3:.4f} ms, measured "
          f"{step_s * 1e3:.4f} ms over {len(runs)} steps", flush=True)
    value = 100.0 * least_s / step_s
    if value > 100.0:
        raise ValueError(f"the roofline share of {scope} reads {value:.3f} "
                         "%: the operations or bytes are counted too high, "
                         "or the time leaves out part of the work")
    return value
