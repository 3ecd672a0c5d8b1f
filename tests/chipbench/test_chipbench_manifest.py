"""``BENCHMARK.json`` holds together: every entry resolves to files that
exist, names and lengths keep to the contract, and a later PR can add a
configuration, a traffic mix, a cell and a per-layer metric by adding
files and entries only."""

import copy
import importlib
import json
import os
import re
import shutil
import textwrap

import pytest

from chipbench import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return manifest.load_manifest()


def test_manifest_has_exactly_the_contract_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["chipbench", "tests/chipbench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(manifest.MANIFEST) <= 64 * 1024
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  manifest.load_manifest()["workloads"]])
def test_every_workload_resolves_to_files_that_exist(cell):
    resolved = manifest.resolve_cell(cell)
    assert resolved.config["name"] == resolved.config_name
    assert resolved.traffic["chips"] == resolved.chips
    assert resolved.traffic["kind"] == "train"
    importlib.import_module(resolved.traffic["loop"])
    for key in ("adapter", "reference"):
        importlib.import_module(resolved.config[key])
    reported = {m["name"] for m in resolved.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert resolved.per_layer, "a cell reports at least one per-layer metric"
    for metric in resolved.per_layer:
        assert metric["moves"] in reported
        assert callable(manifest.layer_reader(metric["name"]))


@pytest.mark.parametrize("cell,want", [
    ("dlrm_train_x4", 1), ("dlrm_train", 0), ("bert_train", 0)])
@pytest.mark.parametrize("rehearse", [False, True])
def test_open_after_epoch_ends_is_0_where_a_traffic_file_omits_it(
        cell, want, rehearse):
    """Only ``train-cached-x4`` names the parameter; a mix that does not
    opens its window after ``warmup_steps``, as before there was one."""
    from chipbench import harness
    resolved = manifest.resolve_cell(cell)
    assert ("open_after_epoch_ends" in resolved.traffic) is bool(want)
    ctx = harness.Context(cell=resolved, seed=1, seconds=1.0, trace=False,
                          rehearse=rehearse, control=None, started_at=0.0,
                          scratch="")
    assert ctx.traffic("open_after_epoch_ends", 0) == want


def test_names_units_and_lengths_keep_to_the_contract(bench):
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[section]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((section in ("end_to_end", "per_layer"),
                          section, entry["name"]))
    metric_names = [n for is_metric, _, n in names if is_metric]
    assert len(metric_names) == len(set(metric_names))
    for cell in bench["workloads"]:
        assert NAME.match(cell["config"]) and NAME.match(cell["traffic"])
        assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    pairs = [(c["config"], c["traffic"]) for c in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(1 for c in bench["workloads"] if c["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 4)
    for config in bench["configs"]:
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert config["file"].startswith("chipbench/")
        assert 1 <= len(config["source"]) <= 200
        assert 1 <= len(config["why"]) <= 200
    for metric in bench["end_to_end"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                               "bound", "source"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    for metric in bench["per_layer"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                               "source", "layer", "moves"}
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
    for metric in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(metric["unit"]) and metric["better"] in (
            "lower", "higher")


def test_the_issues_metrics_and_cells(bench):
    assert [m["name"] for m in bench["end_to_end"]] == [
        "train_rows_per_s", "step_gap_p95_ms", "setup_s"]
    assert {c["name"] for c in bench["configs"]} == {"dlrm-mlperf",
                                                     "bert-base-mlm"}
    first = next(m for m in bench["per_layer"]
                 if m["name"] == "first_batch_s")
    assert first["moves"] == "setup_s"
    cells = {c["name"] for c in bench["workloads"]}
    assert cells <= {"dlrm_train", "bert_train", "dlrm_train_x4"}


def test_layer_files_and_manifest_name_each_other(bench):
    """Every per-layer metric has its reader's file, and no file is left
    under ``layers`` that the manifest does not name."""
    files = {f[:-len(".json")] for f in
             os.listdir(os.path.join(manifest.BENCH_DIR, "layers"))}
    assert files == {m["name"] for m in bench["per_layer"]}
    used = {c["traffic"] for c in bench["workloads"]}
    assert used == {f[:-len(".json")] for f in
                    os.listdir(os.path.join(manifest.BENCH_DIR, "traffic"))}


def test_configuration_files_state_what_is_run(bench):
    for config in bench["configs"]:
        with open(os.path.join(manifest.CHECKOUT, config["file"])) as f:
            held = json.load(f)
        assert held["name"] == config["name"]
        assert held["reduced"] == config["reduced"]
        assert held["guarantees"] and held["optimizer"]["name"] == "adam"
        assert held["data"]["rows"] % held["data"]["files"] == 0


def test_a_later_pr_adds_config_traffic_cell_and_metric_as_files(tmp_path,
                                                                 bench):
    """Copy the benchmark's data files aside, add one of each, and resolve
    them: nothing that was there is edited."""
    bench_dir = tmp_path / "chipbench"
    for sub in ("configs", "traffic", "layers"):
        shutil.copytree(os.path.join(manifest.BENCH_DIR, sub),
                        bench_dir / sub)
    before = {p: p.read_bytes() for p in bench_dir.rglob("*.json")}

    config = json.loads((bench_dir / "configs" / "dlrm-mlperf.json")
                        .read_text())
    config["name"] = "dlrm-wide"
    config["embed_dim"] = 256
    (bench_dir / "configs" / "dlrm-wide.json").write_text(json.dumps(config))
    traffic = json.loads((bench_dir / "traffic" / "train-cached.json")
                         .read_text())
    traffic["max_concurrent_epochs"] = 4
    (bench_dir / "traffic" / "train-deep.json").write_text(
        json.dumps(traffic))
    (tmp_path / "later_pr_reader.py").write_text(textwrap.dedent("""
        def chunks_per_s(facts, scale=1.0):
            return scale * facts["chunks"] / facts["window_elapsed_s"]
    """))
    (bench_dir / "layers" / "chunks_per_s.json").write_text(json.dumps(
        {"module": "later_pr_reader", "function": "chunks_per_s",
         "args": {"scale": 2.0}}))

    later = copy.deepcopy(bench)
    later["configs"].append({"name": "dlrm-wide", "source": "x",
                             "file": "chipbench/configs/dlrm-wide.json",
                             "reduced": [], "why": "y"})
    later["workloads"].append({"name": "wide_train_deep",
                               "config": "dlrm-wide",
                               "traffic": "train-deep", "chips": 1,
                               "why": "z"})
    later["per_layer"].append({"name": "chunks_per_s", "unit": "1/s",
                               "better": "higher", "source": "host_clock",
                               "layer": "loader", "moves": "train_rows_per_s",
                               "workloads": ["wide_train_deep"]})
    for metric in later["end_to_end"]:
        if metric["name"] == "train_rows_per_s":
            metric["workloads"] = metric["workloads"] + ["wide_train_deep"]

    cell = manifest.resolve_cell("wide_train_deep", later,
                                 bench_dir=str(bench_dir))
    assert cell.config["embed_dim"] == 256
    assert cell.traffic["max_concurrent_epochs"] == 4
    assert "chunks_per_s" in {m["name"] for m in cell.per_layer}
    # a metric with no list of cells is the new cell's too; one whose list
    # leaves the cell out, or that moves a metric it does not report, is not
    assert "device_idle_pct.train" in {m["name"] for m in cell.per_layer}
    assert "gather_kernel_pct" not in {m["name"] for m in cell.per_layer}
    assert "step_gap_p95_ms" not in {m["name"] for m in cell.end_to_end}
    import sys
    sys.path.insert(0, str(tmp_path))
    try:
        reader = manifest.layer_reader("chunks_per_s",
                                       bench_dir=str(bench_dir))
        assert reader({"chunks": 30, "window_elapsed_s": 10.0}) == 6.0
    finally:
        sys.path.remove(str(tmp_path))
    # the old cells still resolve, and no file that was there changed
    assert manifest.resolve_cell("dlrm_train", later,
                                 bench_dir=str(bench_dir)).chips == 1
    assert all(p.read_bytes() == b for p, b in before.items())


def test_unknown_names_are_errors(bench):
    with pytest.raises(manifest.ManifestError):
        manifest.resolve_cell("no_such_cell", bench)
    broken = copy.deepcopy(bench)
    broken["workloads"][0]["traffic"] = "no-such-traffic"
    with pytest.raises(manifest.ManifestError):
        manifest.resolve_cell(broken["workloads"][0]["name"], broken)
