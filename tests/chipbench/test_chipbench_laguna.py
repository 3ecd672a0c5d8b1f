"""The cell ``laguna_train_8k`` (PR 34): its files resolve, the precision
below the configuration's is not ``correct``, the configuration states its
cut, and the manifest's entries for it come after what was there (by name,
not by being last: the next configuration is appended behind them). Its
rehearsal on the CPU has a file of its own
(``test_chipbench_laguna_rehearsal.py``): together they take more than a
minute of one worker."""

import importlib
import json
import os

import pytest

from chipbench import manifest

CELL = "laguna_train_8k"
CONFIG = "laguna-xs.2-ep8"
SOURCE = "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"
NEW_LAYERS = {"lm_mlp_pct": "rsdl.lm.mlp",
              "lm_mlp_roofline_pct": "rsdl.lm.mlp",
              "lm_proj_pct": "rsdl.lm.proj",
              "lm_proj_roofline_pct": "rsdl.lm.proj"}
#: The decoder's and the feed's metrics that list their cells: this one is
#: appended to each.
SHARED_LAYERS = ["moe_pct", "lm_attention_pct", "lm_head_pct",
                 "moe_roofline_pct", "lm_attention_roofline_pct",
                 "feed_carve_pct", "feed_queue_wait_pct", "feed_offcpu_pct",
                 "feed_transfer_ms", "idle_under_feed_pct"]


#: The cells the benchmark had before this one.
ACCEPTED_CELLS = ["dlrm_train", "bert_train", "dlrm_train_x4",
                  "mellum_train_8k"]


def _config_entry(bench):
    return next(c for c in bench["configs"] if c["name"] == CONFIG)


def test_the_reference_in_bfloat16_is_caught():
    """What ``--control ref_bf16`` puts in the program's place: the plain
    reference computed in bfloat16, through the harness's own trajectory
    and comparison at the tiny preset's sizes and limits, fails the
    parameters' change (a norm's scale of 1 + 1e-4 is 1 in bfloat16: it
    never moves) and is not ``correct``."""
    import jax
    import numpy as np

    from chipbench import check, harness
    from chipbench.references import laguna as reference
    ctx = harness.Context(cell=manifest.resolve_cell(CELL), seed=0,
                          seconds=0.0, trace=False, rehearse=True,
                          control="ref_bf16", started_at=0.0, scratch="")
    sizes = ctx.sizes
    rng = np.random.default_rng(34)
    batches = [([rng.integers(4, sizes["vocab_size"],
                              (ctx.traffic("batch_per_device"),
                               sizes["seq_len"]), dtype=np.int32)],
                np.zeros((4,), np.int32)) for _ in range(check.STEPS)]
    key = jax.random.key(34)

    def params0():
        return reference.init_params(sizes, key)

    sound, low = (check.reference_trajectory(
        reference, sizes, params0, batches, sizes["optimizer"], key,
        lower_precision=lower) for lower in (False, True))
    compared = check.compare(low, sound, ctx.limits())
    failed = [c.name for c in compared if not c.ok]
    assert failed and all(n.startswith("param_change_norm_gap")
                          for n in failed), [c.line() for c in compared]


def test_the_manifest_appends_the_cell_and_its_entries():
    bench = manifest.load_manifest()
    cell = manifest.resolve_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        CONFIG, "train-cached-long-step", 1)
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(CELL) == len(ACCEPTED_CELLS), "after the accepted"
    assert cells[:len(ACCEPTED_CELLS)] == ACCEPTED_CELLS
    assert _config_entry(bench)["source"] == SOURCE
    layers = [m["name"] for m in bench["per_layer"]]
    first = layers.index("lm_mlp_pct")
    assert layers[first:first + 4] == list(NEW_LAYERS)
    assert first > layers.index("lm_attention_roofline_pct")
    # the other decoder's cell shares the configuration's traffic file
    assert cell.traffic == manifest.resolve_cell("mellum_train_8k").traffic
    assert [m["name"] for m in cell.end_to_end] == ["train_rows_per_s",
                                                    "setup_s"]
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW_LAYERS) | set(SHARED_LAYERS) | {
        "model_flops_util_pct", "step_roofline_pct", "device_step_ms",
        "peak_hbm_gb.train", "device_idle_pct.train", "input_wait_pct",
        "step_compiles", "first_batch_s"} == reported
    by_name = {m["name"]: m for m in
               bench["end_to_end"] + bench["per_layer"]}
    for name in SHARED_LAYERS + ["train_rows_per_s"]:
        listed = by_name[name]["workloads"]
        assert listed.index(CELL) == listed.index("mellum_train_8k") + 1, name
    for other in ACCEPTED_CELLS:
        theirs = {m["name"] for m in manifest.resolve_cell(other).per_layer}
        assert not theirs & set(NEW_LAYERS), other
    for name, scope in NEW_LAYERS.items():
        entry = by_name[name]
        assert entry["workloads"][0] == CELL
        assert entry["moves"] == "train_rows_per_s" and entry["unit"] == "%"
        with open(os.path.join(manifest.BENCH_DIR, "layers",
                               f"{name}.json")) as f:
            assert json.load(f)["args"]["scope"] == scope
        # no trace (an untraced run), or a program without the scope (the
        # parent): nothing to read, nothing raised
        reader = manifest.layer_reader(name)
        assert reader({"trace": None}) is None
        assert reader({"trace": object(), "step_op_names": {}}) is None


def test_the_configuration_states_its_cut():
    config = manifest.resolve_cell(CELL).config
    entry = _config_entry(manifest.load_manifest())
    assert config["source"].startswith(entry["source"])
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer", "num_experts", "vocab_size"]
    # the widths are the published ones; the cuts state both sides
    assert (config["hidden_size"], config["intermediate_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["moe_intermediate_size"],
            config["shared_expert_intermediate_size"],
            config["num_experts_per_tok"], config["sliding_window"],
            config["moe_routed_scaling_factor"], config["gating"]) == (
                2048, 8192, 48, 8, 128, 512, 512, 8, 512, 2.5, True)
    assert (config["num_hidden_layers"], config["num_experts"],
            config["num_experts_routed"], config["vocab_size"]) == (
                5, 32, 256, 12544)
    assert config["published"] == {
        **config["published"], "num_hidden_layers": 40, "num_experts": 256,
        "vocab_size": 100352, "chips_sharing_a_layer": 8}
    assert config["layer_types"] == ["full_attention"] + 3 * [
        "sliding_attention"] + ["full_attention"]
    assert config["mlp_layer_types"] == ["dense"] + 4 * ["sparse"]
    assert config["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    for said in ("gating", "qk_norm", "router", "shared_expert",
                 "balance_loss", "rotary", "init", "tokens", "optimizer",
                 "precision", "recompute"):
        assert config["assumed"][said], said
    assert "33.44 B" in config["assumed"]["gating"]
    assert "eight" in config["deployment"]
    from chipbench.references import laguna as reference
    assert reference.param_count(config) == 691_623_936     # 11.07 GB
    data = config["data"]
    assert data["rows"] % config["batching"]["batch_per_device"] == 0
    # ISSUE 34's traffic: 32 steps an epoch, so that epoch 0 ends inside a
    # 30 s window of 0.6 s steps that opens after 8 (PERF.md section 6 has
    # what the rows' second pass does to the window's last twenty-five)
    assert (data["rows"], data["files"]) == (64, 8)
    assert data["rows"] // config["batching"]["batch_per_device"] == 32
    assert data["columns"][0]["width"] == config["seq_len"] == 8192
    assert data["columns"][0]["vocab"] == config["vocab_size"]
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
