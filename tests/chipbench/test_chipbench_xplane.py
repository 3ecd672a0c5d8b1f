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
