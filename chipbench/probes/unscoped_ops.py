"""What a train cell's step runs under no scope of the program's: a traced
run of the cell through the harness, and beside its lines the device time
of the step's operations whose ``op_name`` carries no ``rsdl.*`` scope (as
a component of its own or inside a transform's name:
``readers/wrapped_scopes.py``), by the rest of their path (the ``jit``,
transform and function names the program ran them under) and their
opcode.

    python3 -m chipbench.probes.unscoped_ops --workload sdar_train_8k \
        --seed 4700009001 --seconds 30 --trace 1

Every argument is ``chipbench.run``'s and so is every line but the
``# no scope`` ones, which come before the result's. The step's time less
its scopes' says how much lies under none; this says what that is. A
``while`` and a ``conditional`` are left out: their bodies' operations are
in the trace beside them.
"""

from __future__ import annotations

import sys
from typing import Any, Dict, List, Optional, Tuple

TOP = 40
CONTAINERS = frozenset({"while", "conditional", "call"})


def path_outside_jit(op_name: str) -> str:
    """``op_name`` without the ``jit(...)`` names it starts with."""
    parts = op_name.split("/")
    while parts and parts[0].startswith("jit("):
        parts.pop(0)
    return "/".join(parts)


def unscoped_seconds(ops, names: Dict[str, str]
                     ) -> Tuple[Dict[Tuple[str, str], float], float]:
    """``({(path, opcode): seconds}, seconds under some rsdl scope)`` over
    the operations ``ops`` (``xplane.Op``), their ``op_name`` looked up in
    ``names`` by their instruction's name."""
    from chipbench import xplane
    from chipbench.readers.wrapped_scopes import unwrapped
    outside: Dict[Tuple[str, str], float] = {}
    inside = 0.0
    for op in ops:
        if op.opcode in CONTAINERS:
            continue
        op_name = unwrapped(names.get(xplane.hlo_name(op.text), ""))
        seconds = op.end - op.start
        if any(part.startswith("rsdl.") for part in op_name.split("/")):
            inside += seconds
            continue
        key = (path_outside_jit(op_name) or "(no op_name)", op.opcode)
        outside[key] = outside.get(key, 0.0) + seconds
    return outside, inside


def print_unscoped(facts: Dict[str, Any]) -> None:
    from chipbench import harness, xplane
    names = facts.get("step_op_names")
    if not names or facts.get("trace") is None:
        harness.info("no scope: the loop kept no compiled text of its step")
        return
    trace, win, module = (facts["trace"], facts["trace_window"],
                          facts["step_module"])
    runs = xplane.module_durations(trace, win, module)
    chips = max(1, len(trace.ops))
    outside, inside = unscoped_seconds(
        xplane._ops_in_runs(trace, win, module), names)
    if not runs:
        return
    per_step = 1e3 / (len(runs) * chips)
    harness.info(
        f"no scope: {per_step * sum(outside.values()):.4f} ms a step under "
        f"no rsdl scope and {per_step * inside:.4f} under one, of "
        f"{1e3 * sum(runs) / len(runs):.4f} ms over {len(runs)} steps; "
        f"{len(outside)} paths, the first {TOP}:")
    for (path, opcode), seconds in sorted(outside.items(),
                                          key=lambda kv: -kv[1])[:TOP]:
        harness.info(f"no scope {per_step * seconds:9.4f} ms  {opcode:<12s} "
                     f"{path}")


def main(argv: Optional[List[str]] = None) -> int:
    from chipbench import run
    printed = run.print_op_scopes

    def and_the_rest(device_ops, facts):
        printed(device_ops, facts)
        print_unscoped(facts)

    run.print_op_scopes = and_the_rest
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
