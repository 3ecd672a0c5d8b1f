"""The cell ``granite_train_8k`` (PR 38): its files resolve by name, the
precision below the configuration's is not ``correct``, the configuration
states its cut and every published width, and the scan's statistics read
into ``ssm_carry_pct``. Its rehearsal on the CPU has a file of its own
(``test_chipbench_granite_rehearsal.py``)."""

import importlib
import json
import os

import pytest

from chipbench import manifest
from chipbench.readers import ssm_stats

CELL = "granite_train_8k"
CONFIG = "granite-4.0-h-micro-p1"
SOURCE = ("https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/"
          "config.json")
NEW_LAYERS = {"lm_ssm_pct": "rsdl.lm.ssm",
              "lm_ssm_roofline_pct": "rsdl.lm.ssm"}
#: The accepted metrics that list their cells and gain this one.
SHARED_LAYERS = ["feed_carve_pct", "feed_queue_wait_pct", "feed_offcpu_pct",
                 "feed_transfer_ms", "idle_under_feed_pct", "optimizer_pct",
                 "lm_attention_pct", "lm_attention_roofline_pct",
                 "lm_head_pct", "lm_mlp_pct", "lm_mlp_roofline_pct",
                 "lm_proj_pct", "lm_proj_roofline_pct"]
#: What the catalog's entry states of the published model, every width.
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "logits_scaling": 8, "mamba_chunk_size": 256,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 0, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True}


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
    from chipbench.references import granite as reference
    ctx = harness.Context(cell=manifest.resolve_cell(CELL), seed=0,
                          seconds=0.0, trace=False, rehearse=True,
                          control="ref_bf16", started_at=0.0, scratch="")
    sizes = ctx.sizes
    rng = np.random.default_rng(38)
    batches = [([rng.integers(4, sizes["vocab_size"],
                              (ctx.traffic("batch_per_device"),
                               sizes["seq_len"]), dtype=np.int32)],
                np.zeros((4,), np.int32)) for _ in range(check.STEPS)]
    key = jax.random.key(38)

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
    assert len(_by_name(bench["workloads"], CELL)["why"]) <= 200
    # the two decoder cells share the traffic file, unchanged
    assert cell.traffic == manifest.resolve_cell("laguna_train_8k").traffic
    assert [m["name"] for m in cell.end_to_end] == ["train_rows_per_s",
                                                    "setup_s"]
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW_LAYERS) | {"ssm_carry_pct"} | set(SHARED_LAYERS) | {
        "model_flops_util_pct", "step_roofline_pct", "device_step_ms",
        "peak_hbm_gb.train", "device_idle_pct.train", "input_wait_pct",
        "step_compiles", "first_batch_s"} == reported
    metrics = bench["end_to_end"] + bench["per_layer"]
    for name in SHARED_LAYERS + ["train_rows_per_s"]:
        listed = _by_name(metrics, name)["workloads"]
        assert CELL in listed and listed.index(CELL) > listed.index(
            "laguna_train_8k"), name
    for other in (w["name"] for w in bench["workloads"]):
        if other != CELL:
            theirs = {m["name"]
                      for m in manifest.resolve_cell(other).per_layer}
            assert not theirs & (set(NEW_LAYERS) | {"ssm_carry_pct"}), other
    for name, scope in NEW_LAYERS.items():
        entry = _by_name(bench["per_layer"], name)
        assert entry["workloads"] == [CELL] and entry["layer"] == "kernels"
        assert entry["moves"] == "train_rows_per_s" and entry["unit"] == "%"
        with open(os.path.join(manifest.BENCH_DIR, "layers",
                               f"{name}.json")) as f:
            assert json.load(f)["args"]["scope"] == scope
        # no trace (an untraced run), or a program without the scope (the
        # parent): nothing to read, nothing raised
        reader = manifest.layer_reader(name)
        assert reader({"trace": None}) is None
        assert reader({"trace": object(), "step_op_names": {}}) is None
    with open(os.path.join(manifest.BENCH_DIR, "layers",
                           "lm_ssm_roofline_pct.json")) as f:
        assert json.load(f)["args"]["work"] == "ssm_work"
    carry = _by_name(bench["per_layer"], "ssm_carry_pct")
    assert carry["source"] == "program_counter"
    assert carry["workloads"] == [CELL]
    assert manifest.layer_reader("ssm_carry_pct")({"trace": None}) is None


def test_the_configuration_states_its_cut():
    config = manifest.resolve_cell(CELL).config
    entry = _by_name(manifest.load_manifest()["configs"], CONFIG)
    assert config["source"].startswith(entry["source"])
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types", "vocab_size"]
    # every published number and switch under its own key, unchanged
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert (config["num_hidden_layers"], config["vocab_size"]) == (10, 12544)
    assert config["layer_types"] == 5 * ["mamba"] + ["attention"] \
        + 4 * ["mamba"]
    assert config["published"] == {
        **config["published"], "num_hidden_layers": 40,
        "vocab_size": 100352, "pipeline_stages": 4, "vocabulary_shares": 8}
    assert "3.19 B" in config["published"]["parameters"]
    for said in ("head_dim", "mamba_init", "init", "mamba_norm", "attention",
                 "mlp", "optimizer", "precision", "tokens", "dropout",
                 "recompute"):
        assert config["assumed"][said], said
    for word in ("[0.001, 0.1]", "[1, 16]", "D 1", "dt_bias = 1"):
        assert word in config["assumed"]["mamba_init"], word
    assert "four pipeline stages" in config["deployment"]
    assert "eighths" in config["deployment"]
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
    assert config["seq_len"] % config["mamba_chunk_size"] == 0
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
    with pytest.raises(ValueError, match="attention_multiplier"):
        adapter.check_sizes(
            manifest.load_object(config["program_builder"])(),
            {**config, "attention_multiplier": 0.125})


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(manifest.BENCH_DIR, "references", "granite.py")
    with open(path) as f:
        text = f.read()
    assert "ray_shuffling_data_loader_tpu" not in text
    assert "ops.ssd" not in text and "ops/ssd" not in text
    # ``highest`` is the trajectory's (check.reference_trajectory)
    from chipbench import check
    import inspect
    assert '"highest"' in inspect.getsource(check.reference_trajectory)


# -- the scan's statistics as a metric ---------------------------------------------


def _entry(step, decays):
    return {"step": step, "fold_s": 0.0002, "stats": {"ssm_scan": [
        {"layer": str(i), "end_decay_mean": d, "carry_abs_max": 1.0 + i}
        for i, d in enumerate(decays)]}}


class _Ring:
    def __init__(self, entries):
        self.entries, self.waited = entries, False

    def fold_step_stats(self, wait=False):
        self.waited = wait
        return 0

    def step_stats(self, first=None, last=None):
        return [e for e in self.entries
                if (first is None or e["step"] >= first)
                and (last is None or e["step"] <= last)]


def test_the_carry_metric_is_the_windows_mean_decay():
    entries = [_entry(10, [0.1, 0.3]), _entry(11, [0.2, 0.4]),
               _entry(12, [0.9, 0.9])]
    ring = _Ring(entries)
    scans = ssm_stats.scans_of(ring, [10, 11])
    assert ring.waited and [e["step"] for e in scans] == [10, 11]
    assert ssm_stats.carry_pct(scans) == pytest.approx(25.0)
    lines = ssm_stats.series_lines(entries, [10, 11])
    assert lines[0].startswith("# step stats 10*: scan crossing "
                               "10.000/30.000 % (layers 0/1)")
    assert lines[2].startswith("# step stats 12: ")
    # nothing to read: no ring, no steps, a ring whose steps hold no scan
    assert ssm_stats.scans_of(None, [10]) is None
    assert ssm_stats.scans_of(ring, []) is None
    walks = _Ring([{"step": 10, "fold_s": 0.0,
                    "stats": {"moe_walk": [{"tiles": 3}]}}])
    assert ssm_stats.scans_of(walks, [10]) is None
    assert ssm_stats.ssm_carry_pct({"trace_path": None,
                                    "trace_window": None}) is None
