"""The readers of the program's own spans and counters
(``chipbench/readers/program.py``): their arithmetic on made-up intervals,
what they do with a program that has no such span or counter, and all five
on the trace of a CPU rehearsal of ``dlrm_train``. Nothing here computes or
prints a device metric."""

import json
import os

import pytest

from chipbench import manifest, run, xplane
from chipbench.readers import program

_NAMES = ("feed_carve_pct", "feed_queue_wait_pct", "feed_offcpu_pct",
          "feed_transfer_ms", "idle_under_feed_pct")

#: The free-text annotation names the program emitted before the one
#: vocabulary (``utils/tracing.trace_span``).
_OLD_NAMES = ("shuffle_map", "shuffle_reduce", "batch_convert",
              "table_convert", "batch_transfer", "table_transfer", "train")

WINDOW = (10.0, 12.0)
SPANS = [
    ("chipbench.window", 10.0, 12.0),
    ("chipbench.next_batch", 10.0, 10.5),
    ("rsdl.feed.queue_get", 10.01, 10.1),
    ("rsdl.feed.carve", 10.1, 10.4),
    ("rsdl.feed.carve", 9.9, 10.05),       # clipped to 0.05 s at the edge
    ("rsdl.feed.carve", 10.3, 10.5),       # overlaps: the union counts once
    ("rsdl.feed.transfer", 10.2, 10.3),
    ("rsdl.feed.transfer", 10.6, 10.9),
    ("rsdl.feed.transfer", 11.0, 11.5),
    ("rsdl.feed.transfer", 11.9, 12.4),    # runs past the window: left out
    ("rsdl.loader.reduce", 11.0, 11.2),
]


def _op(start, end):
    return xplane.Op("fusion", "fusion", "fusion", start, end)


def test_span_share_is_the_union_clipped_to_the_window():
    carve = program.span_share_pct(SPANS, "rsdl.feed.carve", WINDOW)
    assert carve == pytest.approx(100.0 * (0.05 + 0.4) / 2.0)
    get = program.span_share_pct(SPANS, "rsdl.feed.queue_get", WINDOW)
    assert get == pytest.approx(4.5)
    assert program.span_share_pct(SPANS, "rsdl.feed.set_epoch",
                                  WINDOW) is None
    with pytest.raises(ValueError, match="more than the window"):
        program._share("x", 100.5)


def test_span_median_takes_the_spans_wholly_inside_the_window():
    assert program.span_median_ms(SPANS, "rsdl.feed.transfer",
                                  WINDOW) == pytest.approx(300.0)
    assert program.span_median_ms(SPANS, "rsdl.feed.transfer",
                                  (0.0, 1.0)) is None


def test_offcpu_share_of_two_counters():
    assert program.counter_offcpu_pct(2.0, 1.5) == pytest.approx(25.0)
    assert program.counter_offcpu_pct(2.0, 2.0000001) == 0.0
    assert program.counter_offcpu_pct(None, None) is None
    assert program.counter_offcpu_pct(0.0, 0.0) is None


def test_idle_goes_to_the_innermost_span_program_spans_included():
    # The chip is busy 10.5-12.0; idle 10.0-10.5 lies under next_batch,
    # of which 0.09 s under queue_get, 0.3 s under the carves and 0.1 s
    # under the transfer that started inside one (on another thread: the
    # latest-started span takes the gap).
    trace = xplane.Trace(ops={0: [_op(10.5, 12.0)]}, modules={}, spans=SPANS)
    by_span = program.idle_by_span(trace, WINDOW)
    assert by_span["rsdl.feed.queue_get"] == pytest.approx(0.09)
    assert by_span["rsdl.feed.carve"] == pytest.approx(0.3)
    assert by_span["rsdl.feed.transfer"] == pytest.approx(0.1)
    assert by_span["chipbench.next_batch"] == pytest.approx(0.01)
    assert sum(by_span.values()) == pytest.approx(0.5)
    assert program.idle_under_pct(by_span, "rsdl.feed.",
                                  WINDOW) == pytest.approx(24.5)
    assert program.idle_under_pct(by_span, "rsdl.loader.", WINDOW) == 0.0
    assert program.idle_by_span(
        xplane.Trace(ops={}, modules={}, spans=SPANS), WINDOW) == {}


def test_first_sample_is_told_apart_by_the_totals_at_each_epoch_end():
    name = "rsdl_epoch_turnover_seconds"

    def first(*totals):
        return program.first_turnover_ms(
            {"epoch_ends": [{"program": t} for t in totals]}, histogram=name)

    assert first({name: (1, 0.4668)}, {name: (2, 0.4846)}) == pytest.approx(
        466.8)
    # no end met yet; a program that samples nothing; a program from
    # before the histogram; two samples by the first reading
    assert first() is None
    assert program.first_turnover_ms({"kind": "train"}, histogram=name) is None
    assert first({name: (0, 0.0)}) is None
    assert first({}) is None
    assert first({name: (2, 0.9)}) is None
    # the first end may hold nothing and the second the first sample
    assert first({}, {name: (1, 0.02)}) == pytest.approx(20.0)


def test_histogram_totals_follow_the_programs_registry():
    from ray_shuffling_data_loader_tpu.runtime import metrics
    name = "rsdl_epoch_turnover_seconds"
    metrics.histogram(name).observe(0.0)
    count, total = program.histogram_totals()[name]
    metrics.histogram(name).observe(0.25)
    after = program.histogram_totals()
    assert after[name] == (count + 1, pytest.approx(total + 0.25))
    # less an earlier reading: what came since
    assert program.histogram_totals(since={name: (count, total)})[name] == (
        1, pytest.approx(0.25))
    # unlabelled histograms of the catalog only
    assert "rsdl_stage_seconds" not in after
    assert "rsdl_events_total" not in after


@pytest.mark.parametrize("cell,seconds,met", [
    ("dlrm_train_x4", 0.3, True),    # the warm-up holds epoch 0's end
    ("dlrm_train", 0.2, False),      # 256 steps an epoch: none met
])
def test_first_turnover_on_a_rehearsal(cell, seconds, met, monkeypatch,
                                       capsys):
    """``first_turnover_ms`` through its layer file, on the facts of a CPU
    rehearsal: the program's own first sample where the consumer has met
    an epoch's end, nothing where it has not."""
    from chipbench.loops import train
    kept = {}
    loop_run = train.run

    def keeping(ctx, data_job):
        result = loop_run(ctx, data_job)
        kept.update(result["facts"])
        return result

    monkeypatch.setattr(train, "run", keeping)
    assert run.main(["--workload", cell, "--seed", "31", "--seconds",
                     str(seconds), "--trace", "0", "--rehearse"]) == 0
    capsys.readouterr()
    value = manifest.layer_reader("first_turnover_ms")(kept)
    if met:
        first = kept["epoch_ends"][0]
        assert first["epoch"] == 0 and first["in_window"] is False
        assert 0.0 < value <= 1e3 * first["next_batch_s"]
    else:
        assert kept["epoch_ends"] == [] and value is None
    entry = next(m for m in manifest.load_manifest()["per_layer"]
                 if m["name"] == "first_turnover_ms")
    assert entry["workloads"] == ["dlrm_train_x4"]
    assert (entry["moves"], entry["layer"]) == ("setup_s", "device feed")


@pytest.mark.parametrize("name", _NAMES + ("first_turnover_ms",))
def test_a_program_without_the_span_or_counter_gives_nothing(
        name, monkeypatch):
    """The parent commit's side of a traced run: the recorded TPU trace
    holds no ``rsdl.*`` span and the registry no such counter; every
    reader returns ``None`` and raises nothing."""
    from ray_shuffling_data_loader_tpu.runtime import metrics
    monkeypatch.setattr(metrics, "get", lambda name, labels=None: None)
    path = os.path.join(os.path.dirname(__file__), "data", "probe.xplane.pb")
    facts = {"kind": "train", "trace_path": path,
             "trace_window": xplane.window_of(xplane.load(path))}
    assert manifest.layer_reader(name)(facts) is None
    assert manifest.layer_reader(name)({"kind": "train",
                                        "trace_path": None}) is None


def test_the_manifest_names_the_five_in_every_cell():
    entries = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    for cell in ("dlrm_train", "bert_train", "dlrm_train_x4"):
        reported = {m["name"] for m in manifest.resolve_cell(cell).per_layer}
        assert set(_NAMES) <= reported
    assert {entries[n]["layer"] for n in _NAMES} == {"device feed", "loader",
                                                     "device"}
    assert {entries[n]["source"] for n in _NAMES} == {"program_span",
                                                      "program_counter"}


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """One traced CPU rehearsal of ``dlrm_train`` with its trace kept."""
    keep = str(tmp_path_factory.mktemp("kept-trace"))
    os.environ["CHIPBENCH_KEEP_TRACE"] = keep
    try:
        rc = run.main(["--workload", "dlrm_train", "--seed", "23",
                       "--seconds", "1", "--trace", "1", "--rehearse"])
    finally:
        del os.environ["CHIPBENCH_KEEP_TRACE"]
    assert rc == 0
    (name,) = os.listdir(keep)
    path = os.path.join(keep, name)
    harness_view = xplane.load(path)
    return {"kind": "train", "trace_path": path,
            "trace_window": xplane.window_of(harness_view)}


def test_every_reader_on_the_rehearsal_trace(rehearsal, capsys):
    values = {name: manifest.layer_reader(name)(dict(rehearsal))
              for name in _NAMES}
    for name, value in values.items():
        assert value is None or 0.0 <= value, (name, value)
        if value is not None and name.endswith("_pct"):
            assert value <= 100.0, (name, value)
    # On the CPU the loader resolves to per-batch transfers (nothing to
    # carve) and the trace has no device plane; the consumer's wait on
    # the queue, the transfers and the counters are there.
    assert values["feed_queue_wait_pct"] is not None
    assert values["feed_transfer_ms"] is not None
    assert values["feed_offcpu_pct"] is not None
    assert values["feed_carve_pct"] is None
    assert values["idle_under_feed_pct"] is None
    json.dumps(values)


def test_the_rehearsal_trace_holds_only_documented_program_spans(rehearsal):
    """The program emits no annotation outside its fixed list: every
    ``rsdl.*`` span of the rehearsal is on it, and none of the old
    free-text names is in the trace."""
    from jax.profiler import ProfileData

    from ray_shuffling_data_loader_tpu.runtime import telemetry
    names = set()
    for plane in ProfileData.from_file(rehearsal["trace_path"]).planes:
        if plane.name == xplane.HOST_PLANE:
            for line in plane.lines:
                names.update(ev.name for ev in line.events)
    program_names = {n for n in names if n.startswith("rsdl.")}
    assert program_names and program_names <= telemetry.annotation_names()
    assert {"rsdl.feed.queue_get", "rsdl.feed.convert", "rsdl.feed.transfer",
            "rsdl.trainer.step"} <= program_names
    assert not [n for n in names for old in _OLD_NAMES
                if n == old or n.startswith(old + " ")]
