"""The reduction from a profiler trace to numbers, on a small trace
recorded on a TPU v5e (``data/probe.xplane.pb``: twenty runs of a jitted
two-matmul step with a 10 ms host sleep after the eleventh, host spans
``probe.dispatch`` / ``probe.fetch`` / ``probe.sleep``) and on made-up
intervals."""

import os

import pytest

from chipbench import xplane

TRACE = os.path.join(os.path.dirname(__file__), "data", "probe.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return xplane.load(TRACE, span_prefixes=("probe.",))


def test_recorded_trace_has_one_chip_with_ops_modules_and_host_spans(trace):
    assert sorted(trace.ops) == [0] and sorted(trace.modules) == [0]
    assert len(trace.modules[0]) == 20
    assert len(trace.ops[0]) == 120           # six operations a run
    names = {name for name, _, _ in trace.spans}
    assert names == {"probe.dispatch", "probe.fetch", "probe.sleep"}


def test_busy_union_and_idle_share_of_the_recorded_trace(trace):
    win = xplane.window_of(trace)
    busy = xplane.busy_seconds(trace, win)[0]
    # Twenty runs of about 48.6 us each; the operations of a run abut.
    assert busy == pytest.approx(20 * 48.4e-6, rel=0.02)
    per_run = xplane.module_durations(trace, win, "^jit_step$")
    # the window runs from the first operation to the last, and the first
    # run's module event starts a little ahead of its first operation
    assert len(per_run) == 19
    assert busy <= sum(per_run) + 48.7e-6
    assert xplane.idle_share(trace, win) == pytest.approx(
        1 - busy / (win[1] - win[0]))
    assert 0.9 < xplane.idle_share(trace, win) < 1.0


def test_per_name_kernel_time_of_the_recorded_trace(trace):
    win = xplane.window_of(trace)
    by_name = xplane.op_seconds(trace, win)
    two = sorted(by_name.items(), key=lambda kv: -kv[1])[:2]
    assert {n for n, _ in two} == {"fusion_fusion_bf16_1024_1024_",
                                   "fusion.1_fusion_bf16_"}
    # each matmul fusion is about 24 us a run
    for _, seconds in two:
        assert seconds == pytest.approx(20 * 24.2e-6, rel=0.03)
    assert xplane.matching_seconds(trace, win, r"kind=kOutput") == \
        pytest.approx(sum(s for _, s in two))
    assert sum(by_name.values()) == pytest.approx(
        xplane.busy_seconds(trace, win)[0], rel=1e-6)


def test_gap_attribution_of_the_recorded_trace(trace):
    win = xplane.window_of(trace)
    bd = xplane.breakdown(trace, win)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    gaps = dict(bd["idle_gaps"])
    # the host slept 10 ms inside probe.sleep with the device idle
    assert gaps["probe.sleep"] == pytest.approx(0.0105, abs=0.001)
    idle = (win[1] - win[0]) - xplane.busy_seconds(trace, win)[0]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
    assert gaps.get("no_span", 0.0) < 0.1 * idle


def test_window_is_the_harness_span_when_there_is_one(trace):
    marked = xplane.Trace(ops=trace.ops, modules=trace.modules,
                          spans=[("chipbench.window", 0.05, 0.06)])
    assert xplane.window_of(marked) == (0.05, 0.06)
    runs = xplane.module_durations(marked, (0.05, 0.06), "jit_step")
    assert 0 < len(runs) < 20


@pytest.mark.parametrize("text,want", [
    ("%fusion.11 = f32[945195,128]{1,0:T(8,128)} fusion(f32[945195,128]{1,0} "
     "%p), kind=kLoop, calls=%fused_computation.11",
     ("fusion.11", "fusion", "f32_945195_128_")),
    ("%fusion.1 = (bf16[]{:T(256)}, bf16[2048,1024]{1,0:T(8,128)(2,1)S(1)}) "
     "fusion(bf16[2048,1024]{1,0} %copy-done), kind=kOutput",
     ("fusion.1", "fusion", "bf16_")),
    ("%psum.63 = f32[945195,128]{1,0} all-reduce(f32[945195,128]{1,0} %g), "
     "replica_groups={{0,1,2,3}}, to_apply=%add",
     ("psum.63", "all-reduce", "f32_945195_128_")),
    ("%copy-start = (bf16[8]{0}, bf16[8]{0}, u32[]{:S(2)}) copy-start("
     "bf16[8]{0} %x)", ("copy-start", "copy-start", "bf16_8_")),
    ("not hlo at all", ("not hlo at all", "", "")),
])
def test_parse_hlo(text, want):
    assert xplane.parse_hlo(text) == want


def test_interval_arithmetic():
    assert xplane.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert xplane.total(xplane.union([(0, 1), (0.5, 2), (3, 4)])) == 3
    assert xplane.clip([(0, 2), (3, 5)], (1, 4)) == [(1, 2), (3, 4)]
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert xplane.gaps([(1, 2), (4, 5)], (0, 6)) == [(0, 1), (2, 4), (5, 6)]


def _op(name, opcode, start, end):
    return xplane.Op(name, opcode, f"%{name} = f32[8]{{0}} {opcode}()",
                     start, end)


def test_exposed_collective_time_is_what_no_compute_covers():
    ops = {
        0: [_op("f0", "fusion", 0.0, 1.0), _op("ar", "all-reduce", 1.0, 3.0),
            _op("f1", "fusion", 3.0, 4.0)],
        # on chip 1 a fusion overlaps the second half of the all-reduce
        1: [_op("ar", "all-reduce", 1.0, 3.0), _op("f2", "fusion", 2.0, 3.5)],
    }
    trace = xplane.Trace(ops=ops, modules={}, spans=[])
    assert xplane.exposed_collective_seconds(trace, (0.0, 4.0)) == \
        pytest.approx((2.0 + 1.0) / 2)
    # clipped to the window
    assert xplane.exposed_collective_seconds(trace, (0.0, 2.0)) == \
        pytest.approx((1.0 + 1.0) / 2)
    assert xplane.is_collective(ops[0][1]) and not xplane.is_collective(
        ops[0][0])


def test_gap_goes_to_the_innermost_span_and_the_rest_to_no_span():
    spans = [("outer", 0.0, 10.0), ("inner", 2.0, 4.0),
             ("chipbench.window", 0.0, 20.0)]
    got = xplane.attribute_gaps([(1.0, 5.0), (11.0, 12.0)], spans)
    assert got == {"inner": pytest.approx(2.0), "outer": pytest.approx(2.0),
                   "no_span": pytest.approx(1.0)}


# -- operations by the program's own scope ------------------------------------

@pytest.fixture(scope="module")
def compiled_step():
    """(text of a small compiled module, its entry computation's
    instructions as the trace would name them). The scatter-add and what
    feeds it run under ``jax.named_scope("probe.exchange")`` and end up
    inside fusions; the tanh and the sums do not."""
    import jax
    import jax.numpy as jnp

    def step(w, x):
        h = jnp.tanh(x @ w)
        with jax.named_scope("probe.exchange"):
            z = jnp.zeros((64, 8), jnp.float32).at[jnp.arange(16) * 3].add(
                h[:16] * 2.0 + 1.0)
        return jnp.sum(z) + jnp.sum(h)

    text = jax.jit(step).lower(jnp.ones((8, 8)),
                               jnp.ones((32, 8))).compile().as_text()
    entry = text[text.index("\nENTRY "):].splitlines()[2:]
    entry = [ln.strip().removeprefix("ROOT ")
             for ln in entry[:entry.index("}")]]
    return text, entry


def test_a_compiled_modules_text_names_each_instructions_scope(compiled_step):
    text, entry = compiled_step
    names = xplane.hlo_op_names(text)
    by_name = {xplane.parse_hlo(ln)[0]: xplane.parse_hlo(ln)[1]
               for ln in entry}
    under = {n for n in by_name
             if xplane.under_scope(names.get(n, ""), "probe.exchange")}
    fusions = {n for n, opcode in by_name.items() if opcode == "fusion"}
    assert under and under <= fusions, "the scope's work is inside fusions"
    assert any(names[n].endswith("probe.exchange/scatter-add")
               for n in under)
    assert fusions - under, "and other fusions are not under it"
    tanh = [n for n in by_name if names.get(n, "").endswith("/tanh")]
    assert tanh and not set(tanh) & under
    assert all(names[n].startswith("jit(step)/") for n in under)


@pytest.mark.parametrize("op_name,scope,want", [
    ("jit(f)/transpose(jvp())/shard_map/rsdl.a.b/scatter-add", "rsdl.a.b",
     True),
    ("jit(f)/rsdl.a.b2/add", "rsdl.a.b", False),
    ("jit(f)/rsdl.a.b/add", "a.b", False),
    ("jit(f)/outer/inner/add", "outer/inner", True),
    ("rsdl.a.b", "rsdl.a.b", True),
    ("", "rsdl.a.b", False),
])
def test_a_scope_is_whole_names_on_the_path(op_name, scope, want):
    assert xplane.under_scope(op_name, scope) is want


def test_an_instruction_with_no_op_name_takes_its_computations():
    text = """HloModule jit_f

%fused_computation.7 (p: s32[8]) -> s32[32] {
  %p = s32[8]{0} parameter(0)
  %all-gather.1 = s32[32]{0} all-gather(%p), dimensions={0}, metadata={op_name="jit(f)/rsdl.x/all_gather" stack_frame_id=2}
  ROOT %custom-call.7 = s32[32]{0} custom-call(%all-gather.1)
}

ENTRY %main (a: s32[8]) -> s32[32] {
  %a = s32[8]{0} parameter(0)
  %copy-start.1 = s32[8]{0} copy-start(%a)
  ROOT %async-collective-start = s32[32]{0} fusion(%a), kind=kCustom, calls=%fused_computation.7
}
"""
    names = xplane.hlo_op_names(text)
    assert names["async-collective-start"] == "jit(f)/rsdl.x/all_gather"
    assert names["all-gather.1"] == "jit(f)/rsdl.x/all_gather"
    assert "copy-start.1" not in names and "a" not in names


def test_scope_seconds_counts_the_scopes_operations_in_the_modules_runs(
        compiled_step):
    text, entry = compiled_step
    names = xplane.hlo_op_names(text)
    under = [ln for ln in entry if xplane.under_scope(
        names.get(xplane.parse_hlo(ln)[0], ""), "probe.exchange")]

    def run_of(start):
        """Every entry instruction for 0.01 s, one after the other."""
        return [xplane.Op(*xplane.op_label(ln), ln, start + 0.01 * i,
                          start + 0.01 * (i + 1))
                for i, ln in enumerate(entry)]

    def module(name, start):
        return xplane.Op(name, "module", f"{name}(123)", start, start + 1.0)

    # Two runs of the step; between them another program whose
    # instructions carry the same names, which says nothing about them.
    chip = {"ops": run_of(0.0) + run_of(1.0) + run_of(2.0),
            "modules": [module("jit_step", 0.0), module("jit_other", 1.0),
                        module("jit_step", 2.0)]}
    trace = xplane.Trace(ops={0: chip["ops"], 1: list(chip["ops"])},
                         modules={0: chip["modules"], 1: chip["modules"]},
                         spans=[])
    got = xplane.scope_seconds(trace, (0.0, 3.5), "probe.exchange", names,
                               "^jit_step$")
    assert got == pytest.approx(2 * 0.01 * len(under))
    # a run that the window cuts is left out, as module_durations leaves it
    assert xplane.scope_seconds(trace, (0.0, 2.5), "probe.exchange", names,
                                "^jit_step$") == pytest.approx(
                                    0.01 * len(under))
    assert xplane.scope_seconds(trace, (0.0, 3.5), "probe.other", names,
                                "^jit_step$") == 0.0
    scopes = xplane.op_scopes(trace, (0.0, 3.5), names, "^jit_step$")
    for ln in under:
        assert xplane.under_scope(scopes[xplane.op_label(ln)[0]],
                                  "probe.exchange")
    from chipbench.readers import device
    facts = {"trace": trace, "trace_window": (0.0, 3.5),
             "step_op_names": names, "step_module": "^jit_step$"}
    assert device.scope_pct_of_step(
        facts, "probe.exchange", "^jit_step$") == pytest.approx(
            100 * 2 * 0.01 * len(under) / 2.0)
    by_op = xplane.scope_op_seconds(trace, (0.0, 3.5), "probe.exchange",
                                    names, "^jit_step$")
    assert "scatter-add" in by_op and sum(by_op.values()) == pytest.approx(
        got)
    assert device.scope_pct_of_step(facts, "probe.other",
                                    "^jit_step$") is None
    assert device.scope_pct_of_step(dict(facts, step_op_names={}),
                                    "probe.exchange", "^jit_step$") is None


def test_the_run_prints_each_breakdown_operations_scope(compiled_step,
                                                        capsys):
    from chipbench import run
    text, entry = compiled_step
    names = xplane.hlo_op_names(text)
    ops = [xplane.Op(*xplane.op_label(ln), ln, 0.01 * i, 0.01 * (i + 1))
           for i, ln in enumerate(entry)]
    trace = xplane.Trace(
        ops={0: ops}, spans=[],
        modules={0: [xplane.Op("jit_step", "module", "jit_step(1)", 0.0,
                               1.0)]})
    facts = {"trace": trace, "trace_window": (0.0, 1.0),
             "step_op_names": names, "step_module": "^jit_step$"}
    top = xplane.breakdown(trace, (0.0, 1.0))["device_ops"]
    run.print_op_scopes(top, facts)
    lines = capsys.readouterr().out.splitlines()
    assert len(lines) == len(top) <= 10
    assert all(ln.startswith("# device op ") for ln in lines)
    assert any(ln.endswith("probe.exchange/scatter-add") for ln in lines)
    assert any(ln.endswith("jit(step)/tanh") for ln in lines)
    # a loop that keeps no compiled text: the names alone, as before
    run.print_op_scopes(top[:1], dict(facts, step_op_names={}))
    assert capsys.readouterr().out.strip().endswith("under (no op_name)")
