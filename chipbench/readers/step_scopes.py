"""The step's device time by the program's scopes, each operation billed
once, and the share that is billed to none: ``step_unscoped_pct``.

``readers/device.py:scope_pct_of_step`` reads one scope, every operation
that has it anywhere on its path; summed over the scopes a nested
operation counts twice and the remainder means nothing. Here each leaf
operation of the step's runs goes to the INNERMOST ``rsdl.*`` component of
its ``op_name`` (``wrapped_scopes.unwrapped`` first: a scope entered
outside a ``jit`` is inside the transform's name on the forward pass), or
to one of two rows that are no scope: ``(none, named)``, an ``op_name``
without one, which the program can still name, and ``(no op_name)``,
what XLA emits without (``copy-start`` / ``-done``, ``async-done``), which
it cannot. A ``while``, a ``conditional`` and a ``call`` are left out:
their bodies' operations are in the trace beside them
(``probes/unscoped_ops.py`` breaks the unnamed rows down by path and
opcode; this gives the share). A scope is known by its ``rsdl.`` prefix
and not by the program's registry, so this reads whatever implements the
step, a program from before the registry too.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Iterable, Optional

from chipbench import xplane
from chipbench.probes.unscoped_ops import CONTAINERS
from chipbench.readers.wrapped_scopes import unwrapped

NAMED, UNNAMED = "(none, named)", "(no op_name)"


@functools.lru_cache(maxsize=None)
def billed_to(op_name: str) -> str:
    """The row an operation with this ``op_name`` is billed to."""
    if not op_name:
        return UNNAMED
    scopes = [part for part in unwrapped(op_name).split("/")
              if part.startswith("rsdl.")]
    return scopes[-1] if scopes else NAMED


def billed_seconds(ops: Iterable[xplane.Op], names: Dict[str, str]
                   ) -> Dict[str, float]:
    """Row -> seconds over the operations ``ops``, containers left out,
    their ``op_name`` looked up in ``names`` by their instruction's
    name."""
    rows: Dict[str, float] = {}
    for op in ops:
        if op.opcode in CONTAINERS:
            continue
        row = billed_to(names.get(xplane.hlo_name(op.text), ""))
        rows[row] = rows.get(row, 0.0) + op.end - op.start
    return rows


def unscoped_pct_of_step(facts: Dict[str, Any], module: str
                         ) -> Optional[float]:
    """Device time of the step's operations billed to no scope over the
    device time of the jitted step's runs (``scope_pct_of_step``'s
    denominator), with the whole table on earlier lines. An untraced run,
    or one that kept no compiled text of its step: ``None``."""
    names = facts.get("step_op_names")
    if facts.get("trace") is None or not names:
        return None
    trace, win = facts["trace"], facts["trace_window"]
    runs = xplane.module_durations(trace, win, module)
    if not runs:
        return None
    chips = max(1, len(trace.ops))
    rows = {row: seconds / chips for row, seconds in billed_seconds(
        xplane._ops_in_runs(trace, win, module), names).items()}
    steps_s, step_ms = sum(runs), 1e3 / len(runs)
    for row, seconds in sorted(rows.items(), key=lambda kv: -kv[1]):
        print(f"# step scope {row}: {step_ms * seconds:.4f} ms a step, "
              f"{100.0 * seconds / steps_s:.4f} % of step", flush=True)
    print(f"# step scopes: billed {step_ms * sum(rows.values()):.4f} ms of "
          f"{step_ms * steps_s:.4f} ms a step over {len(runs)} steps "
          "(more where operations overlap)", flush=True)
    return 100.0 * (rows.get(NAMED, 0.0) + rows.get(UNNAMED, 0.0)) / steps_s


def main(argv=None) -> int:
    """``chipbench.run`` with the table (and ``probes/unscoped_ops``'s
    lines) after a traced run's, for a cell whichever metrics it lists:

        python3 -m chipbench.readers.step_scopes --workload sdar_train_8k \\
            --seed 4900001001 --seconds 30 --trace 1
    """
    from chipbench import run
    from chipbench.probes import unscoped_ops
    printed = run.print_op_scopes

    def and_the_table(device_ops, facts):
        printed(device_ops, facts)
        unscoped_ops.print_unscoped(facts)
        share = unscoped_pct_of_step(facts, facts["step_module"])
        if share is not None:
            print(f"# step scopes: {share:.4f} % of the step billed to no "
                  "scope", flush=True)

    run.print_op_scopes = and_the_table
    return run.main(argv)


if __name__ == "__main__":
    import sys
    sys.exit(main())
