"""The cell ``phi4flash_train_8k`` (PR 40): its files resolve by name, the
precision below the configuration's is not ``correct``, the configuration
states its cut, every published width and every assumption, and the
differential layers' ``lambda`` reads into its lines. Its rehearsal on the
CPU has a file of its own (``test_chipbench_phi4flash_rehearsal.py``)."""

import importlib
import json
import os

import pytest

from chipbench import manifest
from chipbench.readers import diff_stats

CELL = "phi4flash_train_8k"
CONFIG = "phi-4-mini-flash-j6"
SOURCE = ("https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/"
          "main/config.json")
SCOPE_LAYERS = {"lm_sscan_pct": "rsdl.lm.sscan",
                "lm_sscan_roofline_pct": "rsdl.lm.sscan",
                "lm_gmu_pct": "rsdl.lm.gmu"}
NEW_LAYERS = set(SCOPE_LAYERS) | {"sscan_carry_pct"}
#: The accepted metrics that list their cells and gain this one.
SHARED_LAYERS = ["feed_carve_pct", "feed_queue_wait_pct", "feed_offcpu_pct",
                 "feed_transfer_ms", "idle_under_feed_pct", "optimizer_pct",
                 "lm_attention_pct", "lm_attention_roofline_pct",
                 "lm_head_pct", "lm_mlp_pct", "lm_mlp_roofline_pct",
                 "lm_proj_pct", "lm_proj_roofline_pct"]
#: What the catalog's entry states of the published model: every key but
#: the two the cut changes (``num_hidden_layers``, ``vocab_size``).
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False}


def _by_name(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


def test_the_reference_in_bfloat16_is_caught():
    """What ``--control ref_bf16`` puts in the program's place: the plain
    reference computed in bfloat16, through the harness's own trajectory
    and comparison at the tiny preset's sizes and limits, is not
    ``correct``."""
    import jax
    import numpy as np

    from chipbench import check, harness
    from chipbench.references import phi4flash as reference
    ctx = harness.Context(cell=manifest.resolve_cell(CELL), seed=0,
                          seconds=0.0, trace=False, rehearse=True,
                          control="ref_bf16", started_at=0.0, scratch="")
    sizes = ctx.sizes
    rng = np.random.default_rng(40)
    batches = [([rng.integers(4, sizes["vocab_size"],
                              (ctx.traffic("batch_per_device"),
                               sizes["seq_len"]), dtype=np.int32)],
                np.zeros((4,), np.int32)) for _ in range(check.STEPS)]
    key = jax.random.key(40)

    def params0():
        return reference.init_params(sizes, key)

    sound, low = (check.reference_trajectory(
        reference, sizes, params0, batches, sizes["optimizer"], key,
        lower_precision=lower) for lower in (False, True))
    compared = check.compare(low, sound, ctx.limits())
    failed = [c.name for c in compared if not c.ok]
    assert failed and any(n.startswith("param_change_norm_gap")
                          for n in failed), [c.line() for c in compared]
    assert all(c.ok for c in check.compare(sound, sound, ctx.limits()))


def test_the_manifest_resolves_the_cell_and_its_entries_by_name():
    bench = manifest.load_manifest()
    cell = manifest.resolve_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        CONFIG, "train-cached-long-step", 1)
    entry = _by_name(bench["configs"], CONFIG)
    assert entry["source"] == SOURCE and len(entry["source"]) <= 200
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert len(entry["why"]) <= 200
    assert len(_by_name(bench["workloads"], CELL)["why"]) <= 200
    # the decoder cells share the traffic file, unchanged
    assert cell.traffic == manifest.resolve_cell("granite_train_8k").traffic
    assert [m["name"] for m in cell.end_to_end] == ["train_rows_per_s",
                                                    "setup_s"]
    reported = {m["name"] for m in cell.per_layer}
    assert NEW_LAYERS | set(SHARED_LAYERS) | {
        "model_flops_util_pct", "step_roofline_pct", "device_step_ms",
        "peak_hbm_gb.train", "device_idle_pct.train", "input_wait_pct",
        "step_compiles", "first_batch_s"} == reported
    # not granite's three: the kernel is another
    assert not reported & {"lm_ssm_pct", "lm_ssm_roofline_pct",
                           "ssm_carry_pct"}
    metrics = bench["end_to_end"] + bench["per_layer"]
    for name in SHARED_LAYERS + ["train_rows_per_s"]:
        listed = _by_name(metrics, name)["workloads"]
        assert CELL in listed and listed.index(CELL) > listed.index(
            "granite_train_8k"), name
    for other in (w["name"] for w in bench["workloads"]):
        if other != CELL:
            theirs = {m["name"]
                      for m in manifest.resolve_cell(other).per_layer}
            assert not theirs & NEW_LAYERS, other
    for name, scope in SCOPE_LAYERS.items():
        entry = _by_name(bench["per_layer"], name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_rows_per_s" and entry["unit"] == "%"
        assert entry["source"] == "device_trace"
        with open(os.path.join(manifest.BENCH_DIR, "layers",
                               f"{name}.json")) as f:
            assert json.load(f)["args"]["scope"] == scope
        # no trace (an untraced run), or a program without the scope (the
        # parent): nothing to read, nothing raised
        reader = manifest.layer_reader(name)
        assert reader({"trace": None}) is None
        assert reader({"trace": object(), "step_op_names": {}}) is None
    with open(os.path.join(manifest.BENCH_DIR, "layers",
                           "lm_sscan_roofline_pct.json")) as f:
        assert json.load(f)["args"]["work"] == "sscan_work"
    carry = _by_name(bench["per_layer"], "sscan_carry_pct")
    assert carry["source"] == "program_counter"
    assert carry["workloads"] == [CELL]
    assert manifest.layer_reader("sscan_carry_pct")({"trace": None}) is None


def test_the_configuration_states_its_cut_and_what_it_assumes():
    config = manifest.resolve_cell(CELL).config
    entry = _by_name(manifest.load_manifest()["configs"], CONFIG)
    assert config["source"].startswith(entry["source"])
    assert "arXiv:2507.06607" in config["source"]
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers",
                                                     "vocab_size"]
    assert set(config["reduced_how"]) == set(config["reduced"])
    # every published number and switch under its own key, unchanged
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert (config["num_hidden_layers"], config["vocab_size"]) == (6, 25008)
    assert config["published_layer_indices"] == [0, 1, 16, 17, 18, 19]
    assert config["layer_types"] == [
        "mamba1", "sliding_attention", "mamba1", "full_attention", "gmu",
        "cross"]
    # the widths the config is silent on, at the family's convention
    assert (config["head_dim"], config["mamba_d_state"],
            config["mamba_d_conv"], config["mamba_expand"],
            config["mamba_dt_rank"]) == (64, 16, 4, 2, 160)
    assert config["published"] == {
        **config["published"], "num_hidden_layers": 32,
        "vocab_size": 200064, "vocabulary_shares": 8}
    assert "3.85 B" in config["published"]["parameters"]
    for said in ("head_dim", "mamba1", "mamba_init", "layer_kinds",
                 "differential_attention", "attention", "init", "scan_chunk",
                 "optimizer", "precision", "tokens", "dropout", "recompute"):
        assert config["assumed"][said], said
    for word in ("[0.001, 0.1]", "A[c, n] = n + 1", "D 1"):
        assert word in config["assumed"]["mamba_init"], word
    for word in ("arXiv:2410.05258", "adjacent heads", "N(0, 0.1)"):
        assert word in config["assumed"]["differential_attention"], word
    for word in ("pipeline stages", "eighths", "26 absent layers",
                 "fan-in of their cotangents is 2 and not 8",
                 "2 of 6 layers here and 9 of 32"):
        assert word in config["deployment"], word
    data = config["data"]
    # the issue's traffic, letter for letter: 32 rows in 8 files of 2 row
    # groups, batch 1, so an epoch is 32 steps
    assert (data["rows"], data["files"], data["row_groups_per_file"]) == (
        32, 8, 2)
    assert config["batching"] == {"batch_per_device": 1, "reducer_rows": 16,
                                  "warmup_steps": 8}
    traffic = manifest.resolve_cell(CELL).traffic
    assert (traffic["num_epochs"], traffic["max_concurrent_epochs"],
            traffic["run_ahead_steps"], traffic["trace_seconds"]) == (
                64, 2, 4, 8)
    assert data["columns"][0]["width"] == config["seq_len"] == 8192
    assert data["columns"][0]["vocab"] == config["vocab_size"]
    assert config["seq_len"] % config["scan_chunk"] == 0
    assert set(config["limits"]["default"]) == {
        "loss_gap", "first_grad_norm_gap", "param_change_norm_gap"}
    assert config["limits_set_from"]
    # the program builds what the file says, at both sizes
    adapter = importlib.import_module(config["adapter"])
    adapter.check_sizes(manifest.load_object(config["program_builder"])(),
                        config)
    tiny = {**config, **{k: v for k, v in config["rehearsal"].items()
                         if k not in ("data", "batching", "limits")}}
    adapter.check_sizes(manifest.load_object(tiny["program_builder"])(), tiny)
    with pytest.raises(ValueError, match="vocab_size=512"):
        adapter.check_sizes(
            manifest.load_object(tiny["program_builder"])(), config)
    with pytest.raises(ValueError, match="sliding_window"):
        adapter.check_sizes(
            manifest.load_object(config["program_builder"])(),
            {**config, "sliding_window": 1024})
    with pytest.raises(ValueError, match="published_layer_indices"):
        adapter.check_sizes(
            manifest.load_object(config["program_builder"])(),
            {**config, "published_layer_indices": [0, 1, 2, 3, 4, 5]})


def test_the_count_is_the_issues():
    from chipbench.references import phi4flash as reference
    config = manifest.resolve_cell(CELL).config
    assert reference.param_count(config) == 697_073_792
    assert "697,073,792" in config["reduced_how"]["num_hidden_layers"]


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(manifest.BENCH_DIR, "references", "phi4flash.py")
    with open(path) as f:
        text = f.read()
    assert "ray_shuffling_data_loader_tpu" not in text
    assert "selective_scan" not in text and "pallas" not in text
    # ``highest`` is the trajectory's (check.reference_trajectory)
    from chipbench import check
    import inspect
    assert '"highest"' in inspect.getsource(check.reference_trajectory)


# -- the differential layers' lambda beside the scan's carry -------------------------


def _entry(step, lambdas):
    return {"step": step, "fold_s": 0.0002, "stats": {
        "ssm_scan": [{"layer": "0", "end_decay_mean": 0.15,
                      "carry_abs_max": 2.0}],
        "diff_attention": [{"layer": str(layer), "lambda": value}
                           for layer, value in lambdas]}}


def test_the_lambda_lines_name_each_differential_layer():
    entries = [_entry(10, [(1, 0.355), (3, 0.796), (5, 0.798)]),
               _entry(11, [(1, 0.356), (3, 0.797), (5, 0.799)]),
               {"step": 12, "fold_s": 0.0, "stats": {"moe_walk": []}}]
    lines = diff_stats.series_lines(entries, [11])
    assert lines == [
        "# step stats 10: lambda 0.355000/0.796000/0.798000 (layers 1/3/5)",
        "# step stats 11*: lambda 0.356000/0.797000/0.799000 "
        "(layers 1/3/5)"]
    # nothing to read: no trace of the run, nothing raised, nothing printed
    assert diff_stats.sscan_carry_pct({"trace_path": None,
                                       "trace_window": None}) is None
