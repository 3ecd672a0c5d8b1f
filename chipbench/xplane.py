"""The reduction from a profiler trace (``.xplane.pb``) to numbers.

What a TPU trace holds (looked at by hand, PR 23): one plane per chip,
``/device:TPU:<n>``, with a line ``XLA Modules`` (one event per run of a
jitted program, named ``jit_<function>(<fingerprint>)``) and a line
``XLA Ops`` (one event per HLO operation as it ran, named by the
operation's whole HLO text); a plane ``/host:CPU`` with one line per host
thread, which holds ``jax.profiler.TraceAnnotation`` spans by their own
names. Times are nanoseconds on one clock, device and host within about a
millisecond of each other.

Everything below works on plain lists of ``(start_s, end_s)`` intervals, so
the tests can check the arithmetic on made-up intervals as well as on the
recorded trace.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"

COLLECTIVE_OPCODES = frozenset({
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
    "all-reduce-start", "all-reduce-done", "all-gather-start",
    "all-gather-done", "collective-permute-start",
    "collective-permute-done", "async-start", "async-done",
})


@dataclasses.dataclass(frozen=True)
class Op:
    name: str        # "<hlo name>_<opcode>_<dtype>_<dims>_"
    opcode: str
    text: str        # the whole HLO text
    start: float     # seconds
    end: float


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Op]]                  # chip -> operations as run
    modules: Dict[int, List[Op]]              # chip -> program runs
    spans: List[Tuple[str, float, float]]     # host annotations (name, start, end)


# -- HLO text ----------------------------------------------------------------

def _skip_type(text: str, i: int) -> int:
    """Index just past the result type that starts at ``text[i]``."""
    depth = 0
    while i < len(text):
        c = text[i]
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == " " and depth == 0:
            return i
        i += 1
    return i


def parse_hlo(text: str) -> Tuple[str, str, str]:
    """(hlo name, opcode, first result shape as ``dtype_d0_d1_``) of one
    ``XLA Ops`` event name. Text that is not HLO comes back as its own
    name with an empty opcode."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text, "", ""
    name = head.lstrip("%").strip()
    end = _skip_type(rest, 0)
    result_type, tail = rest[:end], rest[end:].lstrip()
    opcode = tail.split("(", 1)[0].strip()
    shape = re.search(r"([a-z]+\d*)\[([\d,]*)\]", result_type)
    label = ""
    if shape:
        dims = shape.group(2).replace(",", "_")
        label = f"{shape.group(1)}_{dims}_" if dims else f"{shape.group(1)}_"
    return name, opcode, label


def hlo_name(text: str) -> str:
    """``parse_hlo``'s first result alone, for a pass over every operation
    of a trace."""
    return text.partition(" = ")[0].lstrip("%").strip()


def op_label(text: str) -> Tuple[str, str]:
    """(display name, opcode) for an operation's HLO text."""
    name, opcode, shape = parse_hlo(text)
    if not opcode:
        return name, ""
    return f"{name}_{opcode}_{shape}", opcode


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


def hlo_op_names(module_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` of its ``metadata``, over the text of
    a compiled module (``compiled.as_text()``): the path of ``jit``,
    transform and ``jax.named_scope`` names the program ran that
    operation under, ``jit(step)/transpose(jvp())/<scope>/scatter-add``.
    An instruction that has none and calls a computation (a fusion the
    compiler made around an asynchronous collective) takes the ``op_name``
    most of that computation's instructions have. The names are the ones
    the trace's operations carry (``hlo_name``)."""
    own: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    inside: Dict[str, List[str]] = {}
    computation = None
    for line in module_text.splitlines():
        if not line.startswith(" "):
            found = _COMPUTATION.match(line)
            computation = found.group(1) if found else None
            continue
        found = _INSTRUCTION.match(line)
        if not found or computation is None:
            continue
        name = found.group(1)
        op_name = _OP_NAME.search(line)
        if op_name:
            own[name] = op_name.group(1)
            inside.setdefault(computation, []).append(op_name.group(1))
        else:
            called = _CALLS.search(line)
            if called:
                calls[name] = called.group(1)
    for name, called in calls.items():
        held = inside.get(called)
        if held:
            own[name] = max(sorted(set(held)), key=held.count)
    return own


def under_scope(op_name: str, scope: str) -> bool:
    """Whether ``scope`` (one ``jax.named_scope`` name, or several joined
    by ``/``) is on the path ``op_name``."""
    return f"/{scope}/" in f"/{op_name}/"


# -- reading -------------------------------------------------------------------

def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if len(paths) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {paths}")
    return paths[0]


def load(path: str, span_prefixes: Sequence[str] = ("chipbench.",),
         span_names: Sequence[str] = ()) -> Trace:
    """Read ``path``. Host spans are kept when their name starts with one
    of ``span_prefixes`` or is in ``span_names``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    trace = Trace(ops={}, modules={}, spans=[])
    wanted = set(span_names)
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            chip = int(match.group(1))
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                target = (trace.ops if line.name == OPS_LINE
                          else trace.modules).setdefault(chip, [])
                for ev in line.events:
                    start = ev.start_ns * 1e-9
                    if line.name == OPS_LINE:
                        name, opcode = op_label(ev.name)
                    else:
                        name, opcode = ev.name.split("(", 1)[0], "module"
                    target.append(Op(name, opcode, ev.name, start,
                                     start + ev.duration_ns * 1e-9))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted or ev.name.startswith(
                            tuple(span_prefixes)):
                        start = ev.start_ns * 1e-9
                        trace.spans.append(
                            (ev.name, start, start + ev.duration_ns * 1e-9))
    trace.spans.sort(key=lambda s: s[1])
    return trace


# -- interval arithmetic ---------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    merged: List[List[float]] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(intervals: Iterable[Interval], holes: Iterable[Interval]
             ) -> List[Interval]:
    """The parts of ``intervals`` (as a union) not covered by ``holes``."""
    out: List[Interval] = []
    holes = union(holes)
    for start, end in union(intervals):
        cursor = start
        for h_start, h_end in holes:
            if h_end <= cursor:
                continue
            if h_start >= end:
                break
            if h_start > cursor:
                out.append((cursor, h_start))
            cursor = max(cursor, h_end)
            if cursor >= end:
                break
        if cursor < end:
            out.append((cursor, end))
    return out


def gaps(busy: Iterable[Interval], window: Interval) -> List[Interval]:
    """Where, inside ``window``, nothing of ``busy`` runs."""
    return subtract([window], busy)


# -- the reductions --------------------------------------------------------------

def window_of(trace: Trace, span_name: str = "chipbench.window") -> Interval:
    """The traced window: the span the harness put around it, or, in a
    trace without one, from the first device operation to the last."""
    for name, start, end in trace.spans:
        if name == span_name:
            return (start, end)
    ops = [op for chip in trace.ops.values() for op in chip]
    if not ops:
        raise ValueError("the trace holds no device operation")
    return (min(op.start for op in ops), max(op.end for op in ops))


def busy_seconds(trace: Trace, window: Interval) -> Dict[int, float]:
    """Per chip: seconds of ``window`` in which an operation ran."""
    return {chip: total(union(clip(((op.start, op.end) for op in ops),
                                   window)))
            for chip, ops in trace.ops.items()}


def idle_share(trace: Trace, window: Interval) -> float:
    """1 - busy/window, averaged over the chips."""
    busy = busy_seconds(trace, window)
    if not busy:
        raise ValueError("the trace holds no device plane")
    length = window[1] - window[0]
    return 1.0 - sum(busy.values()) / (len(busy) * length)


def op_seconds(trace: Trace, window: Interval) -> Dict[str, float]:
    """Seconds by operation name, averaged over the chips."""
    sums: Dict[str, float] = {}
    for ops in trace.ops.values():
        for op in ops:
            inside = clip([(op.start, op.end)], window)
            if inside:
                sums[op.name] = sums.get(op.name, 0.0) + total(inside)
    chips = max(1, len(trace.ops))
    return {name: s / chips for name, s in sums.items()}


def matching_seconds(trace: Trace, window: Interval, pattern: str
                     ) -> float:
    """Seconds of operations whose HLO text matches ``pattern``, averaged
    over the chips."""
    rx = re.compile(pattern)
    per_chip = [total(clip(((op.start, op.end) for op in ops
                            if rx.search(op.text)), window))
                for ops in trace.ops.values()]
    return sum(per_chip) / max(1, len(per_chip))


def _ops_in_runs(trace: Trace, window: Interval, module: str
                 ) -> Iterable[Op]:
    """The operations that ran inside a run of a program whose name
    matches ``module``, the run wholly inside ``window``. Another program
    of the window (the loader's carve, the digest) has instructions of the
    same names, ``fusion.3``: a name says which instruction only within
    its own program."""
    rx = re.compile(module)
    for chip, ops in trace.ops.items():
        runs = sorted((m.start, m.end) for m in trace.modules.get(chip, ())
                      if rx.search(m.name) and m.start >= window[0]
                      and m.end <= window[1])
        starts = [r[0] for r in runs]
        for op in ops:
            at = bisect.bisect_right(starts, op.start) - 1
            if at >= 0 and op.start < runs[at][1]:
                yield op


def scope_op_seconds(trace: Trace, window: Interval, scope: str,
                     names: Dict[str, str], module: str) -> Dict[str, float]:
    """Seconds of the operations the program ran under ``scope``
    (``jax.named_scope``), averaged over the chips, by what follows the
    scope on their path (``scatter-add``, ``all_gather``): those of the
    runs of ``module`` inside ``window`` whose instruction ``names``
    (``hlo_op_names`` of that program's compiled text) puts under it."""
    sums: Dict[str, float] = {}
    for op in _ops_in_runs(trace, window, module):
        op_name = names.get(hlo_name(op.text), "")
        if under_scope(op_name, scope):
            what = f"/{op_name}/".partition(f"/{scope}/")[2].strip("/")
            sums[what] = sums.get(what, 0.0) + op.end - op.start
    chips = max(1, len(trace.ops))
    return {what: s / chips for what, s in sums.items()}


def scope_seconds(trace: Trace, window: Interval, scope: str,
                  names: Dict[str, str], module: str) -> float:
    """``scope_op_seconds`` summed: all the scope's device time."""
    return sum(scope_op_seconds(trace, window, scope, names,
                                module).values())


def op_scopes(trace: Trace, window: Interval, names: Dict[str, str],
              module: str) -> Dict[str, str]:
    """Display name (``op_seconds``' keys) -> ``op_name`` for the
    operations of ``module``'s runs that have one."""
    out: Dict[str, str] = {}
    for op in _ops_in_runs(trace, window, module):
        if op.name not in out:
            op_name = names.get(hlo_name(op.text))
            if op_name:
                out[op.name] = op_name
    return out


def module_durations(trace: Trace, window: Interval, name_pattern: str
                     ) -> List[float]:
    """Device seconds of each run of the programs whose name matches,
    on the first chip (every chip runs the same program in step)."""
    rx = re.compile(name_pattern)
    if not trace.modules:
        return []
    chip = min(trace.modules)
    return [m.end - m.start for m in trace.modules[chip]
            if rx.search(m.name) and m.start >= window[0]
            and m.end <= window[1]]


def is_collective(op: Op) -> bool:
    return op.opcode in COLLECTIVE_OPCODES


def exposed_collective_seconds(trace: Trace, window: Interval) -> float:
    """Seconds in which a collective ran on a chip and no other operation
    did, averaged over the chips."""
    per_chip = []
    for ops in trace.ops.values():
        coll = clip(((o.start, o.end) for o in ops if is_collective(o)),
                    window)
        compute = clip(((o.start, o.end) for o in ops
                        if not is_collective(o)), window)
        per_chip.append(total(subtract(coll, compute)))
    return sum(per_chip) / max(1, len(per_chip))


def attribute_gaps(idle: Sequence[Interval],
                   spans: Sequence[Tuple[str, float, float]],
                   skip: Sequence[str] = ("chipbench.window",)
                   ) -> Dict[str, float]:
    """Idle seconds by what the host was doing: each part of a gap goes to
    the innermost (latest started) span that covers it, the rest to
    ``no_span``."""
    out: Dict[str, float] = {}
    spans = [s for s in spans if s[0] not in skip]
    for gap in idle:
        remaining = [gap]
        # Latest-started spans first: an inner span starts after the one
        # that encloses it.
        for name, start, end in sorted(spans, key=lambda s: -s[1]):
            if end <= gap[0] or start >= gap[1]:
                continue
            covered = total(clip(remaining, (start, end)))
            if covered > 0:
                out[name] = out.get(name, 0.0) + covered
                remaining = subtract(remaining, [(start, end)])
            if not remaining:
                break
        rest = total(remaining)
        if rest > 0:
            out["no_span"] = out.get("no_span", 0.0) + rest
    return out


def breakdown(trace: Trace, window: Interval, top: int = 10
              ) -> Dict[str, List[List[object]]]:
    """The contract's ``breakdown``: the device operations that took most
    time and the idle gaps by host span, ten of each at most."""
    ops = sorted(op_seconds(trace, window).items(), key=lambda kv: -kv[1])
    idle: List[Interval] = []
    if trace.ops:
        first = min(trace.ops)
        idle = gaps(((o.start, o.end) for o in trace.ops[first]), window)
    by_span = sorted(attribute_gaps(idle, trace.spans).items(),
                     key=lambda kv: -kv[1])
    return {"device_ops": [[n, s] for n, s in ops[:top]],
            "idle_gaps": [[n, s] for n, s in by_span[:top]]}
