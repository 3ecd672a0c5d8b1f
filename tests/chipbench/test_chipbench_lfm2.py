"""The cell ``lfm2_train_8k`` (PR 44): its files resolve by name, the
precision below the configuration's is not ``correct``, the configuration
states its cut, every published width and every assumption, and its size
fits the chip beside its own comparison. Every entry is found by its name,
never by its place in a list. Its rehearsal on the CPU has a file of its
own (``test_chipbench_lfm2_rehearsal.py``)."""

import importlib
import json
import os

import pytest

from chipbench import manifest

CELL = "lfm2_train_8k"
CONFIG = "lfm2-24b-a2b-ep8"
SOURCE = ("https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/"
          "config.json")
NEW_LAYERS = {"lm_sconv_pct": "lower", "lm_sconv_roofline_pct": "higher"}
SCOPE = "rsdl.lm.sconv"
#: The accepted metrics that list their cells and gain this one.
SHARED_LAYERS = ["feed_carve_pct", "feed_queue_wait_pct", "feed_offcpu_pct",
                 "feed_transfer_ms", "idle_under_feed_pct", "optimizer_pct",
                 "lm_attention_pct", "lm_attention_roofline_pct",
                 "lm_head_pct", "lm_mlp_pct", "lm_mlp_roofline_pct",
                 "lm_proj_pct", "lm_proj_roofline_pct", "moe_pct",
                 "moe_roofline_pct", "moe_held_pairs_pct",
                 "moe_tiles_per_step", "moe_tiles_drift_pct"]
#: What the catalog's entry states of the published model: every key but
#: the five the cut changes (``reduced``).
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 4, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True}
REDUCED = ["num_hidden_layers", "layer_types", "num_dense_layers",
           "num_experts", "vocab_size"]


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
    from chipbench.references import lfm2 as reference
    ctx = harness.Context(cell=manifest.resolve_cell(CELL), seed=0,
                          seconds=0.0, trace=False, rehearse=True,
                          control="ref_bf16", started_at=0.0, scratch="")
    sizes = ctx.sizes
    rng = np.random.default_rng(44)
    batches = [([rng.integers(4, sizes["vocab_size"],
                              (ctx.traffic("batch_per_device"),
                               sizes["seq_len"]), dtype=np.int32)],
                np.zeros((4,), np.int32)) for _ in range(check.STEPS)]
    key = jax.random.key(44)

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
    # on both sides the selection bias takes no gradient, and the router
    # none either (this chip holds it); the bias moves by its balancing
    # update alone: three steps of the tiny preset's 0.0001 x an expert's
    # shortfall of the mean load, which at 32 picks an expert is a few
    # tenths
    speed = sizes["expert_bias_update_speed"]
    assert speed == 0.0001 and sizes["num_experts_routed"] == 8
    for side in (sound, low):
        for leaf in ("expert_bias", "router"):
            norms = {k: v for k, v in side["grad_norms"].items()
                     if leaf in k}
            assert len(norms) == 4 and set(norms.values()) == {0.0}, leaf
        moved = [v for k, v in side["change_norms"].items()
                 if "expert_bias" in k]
        assert len(moved) == 4
        assert all(0.05 * speed < v < 3 * speed for v in moved), moved
        assert {v for k, v in side["change_norms"].items()
                if "router" in k} == {0.0}


def test_the_manifest_resolves_the_cell_and_its_entries_by_name():
    bench = manifest.load_manifest()
    cell = manifest.resolve_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        CONFIG, "train-cached-long-step", 1)
    entry = _by_name(bench["configs"], CONFIG)
    # the catalog's source_url as it stands; the file's own says the cut
    assert entry["source"] == SOURCE
    cut = cell.config["source"]
    assert cut.startswith(SOURCE)
    assert "one of 8 expert-parallel chips" in cut
    assert "layers 1-5 of 40" in cut
    assert len(cut) <= 200 and len(entry["why"]) <= 200
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    why = _by_name(bench["workloads"], CELL)["why"]
    assert len(why) <= 200
    for word in ("8,192-token rows", "batch 2", "1,024 tokens", "36%",
                 "33%", "9.5%", "8%"):
        assert word in why, word
    # the decoder cells share the traffic file, unchanged
    assert cell.traffic == manifest.resolve_cell("laguna_train_8k").traffic
    assert [m["name"] for m in cell.end_to_end] == ["train_rows_per_s",
                                                    "setup_s"]
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW_LAYERS) | set(SHARED_LAYERS) | {
        "model_flops_util_pct", "step_roofline_pct", "device_step_ms",
        "peak_hbm_gb.train", "device_idle_pct.train", "input_wait_pct",
        "step_compiles", "first_batch_s"} == reported
    # neither state-space mixer's, nor the memory unit's
    assert not reported & {"lm_ssm_pct", "lm_ssm_roofline_pct",
                           "ssm_carry_pct", "lm_sscan_pct",
                           "lm_sscan_roofline_pct", "sscan_carry_pct",
                           "lm_gmu_pct"}
    metrics = bench["end_to_end"] + bench["per_layer"]
    for name in SHARED_LAYERS + ["train_rows_per_s"]:
        listed = _by_name(metrics, name)["workloads"]
        assert listed.count(CELL) == 1, name
        # appended: behind every cell the accepted benchmark listed there
        assert listed.index(CELL) == len(listed) - 1 or all(
            bench["workloads"].index(_by_name(bench["workloads"], later))
            > bench["workloads"].index(_by_name(bench["workloads"], CELL))
            for later in listed[listed.index(CELL) + 1:]), name
    for other in (w["name"] for w in bench["workloads"]):
        if other != CELL:
            theirs = {m["name"]
                      for m in manifest.resolve_cell(other).per_layer}
            assert not theirs & set(NEW_LAYERS), other
    for name, better in NEW_LAYERS.items():
        assert _by_name(bench["per_layer"], name) == {
            "name": name, "unit": "%", "better": better,
            "source": "device_trace", "layer": "kernels",
            "moves": "train_rows_per_s", "workloads": [CELL]}
        with open(os.path.join(manifest.BENCH_DIR, "layers",
                               f"{name}.json")) as f:
            assert json.load(f)["args"]["scope"] == SCOPE
        # no trace (an untraced run), or a program without the scope (the
        # parent): nothing to read, nothing raised
        reader = manifest.layer_reader(name)
        assert reader({"trace": None}) is None
        assert reader({"trace": object(), "step_op_names": {}}) is None
    with open(os.path.join(manifest.BENCH_DIR, "layers",
                           "lm_sconv_roofline_pct.json")) as f:
        spec = json.load(f)
    assert spec["args"]["work"] == "sconv_work"
    assert spec["function"] == "scope_roofline_pct"
    # a reference without the function (another configuration's): nothing
    assert manifest.layer_reader("lm_sconv_roofline_pct")(
        {"trace": object(), "step_op_names": {"a": "b"},
         "reference": object()}) is None


def test_the_configuration_states_its_cut():
    config = manifest.resolve_cell(CELL).config
    entry = _by_name(manifest.load_manifest()["configs"], CONFIG)
    assert config["name"] == CONFIG and config["source"].startswith(SOURCE)
    assert entry["reduced"] == config["reduced"] == REDUCED
    assert set(config["reduced_how"]) == set(REDUCED)
    # every published number and switch under its own key, unchanged
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert not set(PUBLISHED) & set(REDUCED)
    # the cut: depth with its two patterns and the dense count, the
    # experts held, the vocabulary
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (5, 1, 8, 8192)
    assert config["layer_types"] == ["conv", "full_attention", "conv",
                                     "conv", "conv"]
    assert (config["num_experts_routed"], config["experts_held_first"],
            config["num_experts_per_tok"]) == (64, 0, 4)
    assert config["published"] == {
        **config["published"], "num_hidden_layers": 40,
        "num_dense_layers": 2, "num_experts": 64, "vocab_size": 65536,
        "chips_sharing_a_layer": 8}
    assert "23.84 B" in config["published"]["parameters"]
    assert "1-5" in config["published"]["layers_here"]
    for said in ("tie_word_embeddings", "head_dim", "qk_layernorm", "conv",
                 "router", "expert_bias", "router_trains", "rotary",
                 "attention",
                 "optimizer", "precision", "init", "tokens", "dropout",
                 "balance_loss", "recompute"):
        assert config["assumed"][said], said
    for word in ("1e-6", "float32", "score + expert_bias"):
        assert word in config["assumed"]["router"], word
    for word in ("NO gradient", "BALANCING UPDATE", "1 - picks / mean picks",
                 "2408.15664", "NON-zero", "N(0, 0.002)", "At zero"):
        assert word in config["assumed"]["expert_bias"], word
    assert (config["expert_bias_update_speed"], config["router_trains"]) \
        == (0.02, False)
    for word in ("sum over the eight chips", "stop_gradient"):
        assert word in config["assumed"]["router_trains"], word
    assert "+-1/sqrt(3)" in config["assumed"]["conv"]
    assert "BEFORE rotary" in config["assumed"]["qk_layernorm"]
    for word in ("eight TPU v5e chips share each layer", "8 of the 64",
                 "an eighth of the tied vocabulary", "replicated",
                 "Layer 0 and layers 6-39", "pipeline stages",
                 "no code stands in"):
        assert word in config["deployment"], word
    assert (config["head_dim"], config["qk_layernorm"],
            config["tie_word_embeddings"]) == (64, True, True)
    data = config["data"]
    # the issue's traffic, letter for letter: 64 rows in 8 files of 2 row
    # groups, batch 2, so an epoch is 32 steps
    assert (data["rows"], data["files"], data["row_groups_per_file"]) == (
        64, 8, 2)
    assert config["batching"] == {"batch_per_device": 2, "reducer_rows": 16,
                                  "warmup_steps": 8}
    # a tenth of the other decoder cuts': the configuration says why
    assert config["optimizer"]["learning_rate"] == 1e-5
    for word in ("1e-5", "continued pre-training", "warm-up"):
        assert word in config["assumed"]["optimizer"], word
    assert (config["compute_dtype"], config["param_dtype"]) == (
        "bfloat16", "float32")
    traffic = manifest.resolve_cell(CELL).traffic
    assert (traffic["num_epochs"], traffic["max_concurrent_epochs"],
            traffic["run_ahead_steps"], traffic["trace_seconds"]) == (
                64, 2, 4, 8)
    assert data["columns"][0]["width"] == config["seq_len"] == 8192
    assert data["columns"][0]["vocab"] == config["vocab_size"]
    # the loss at the seeded weights alone, between its two readings
    # (3.9e-5 sound, 1.7e-4 with half the batch); the bias's change, which
    # the balancing update makes the worst leaf, between 0.009 and 0.29
    assert config["limits"]["default"] == {
        "first_loss_gap": 0.0001, "first_grad_norm_gap": 0.006,
        "param_change_norm_gap": 0.05}
    for word in ("PR 44", "HALF THE BATCH", "0.540", "1.246", "0.291"):
        assert word in config["limits_set_from"], word
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
    for key, other in (("use_expert_bias", False), ("conv_L_cache", 4),
                       ("router_trains", True),
                       ("expert_bias_update_speed", 0.0),
                       ("qk_layernorm", False), ("num_dense_layers", 2),
                       ("tie_word_embeddings", False)):
        with pytest.raises(ValueError, match=key):
            adapter.check_sizes(
                manifest.load_object(config["program_builder"])(),
                {**config, key: other})
    with pytest.raises(ValueError, match="rope_theta"):
        adapter.check_sizes(
            manifest.load_object(config["program_builder"])(),
            {**config, "rope_parameters": {"rope_type": "default",
                                           "rope_theta": 10000}})


def test_the_size_fits_beside_its_own_comparison():
    """469.3 M parameters, the issue's arithmetic leaf by leaf: 16 bytes
    a parameter of state (7.51 GB) and the comparison's 20 (9.39 GB) both
    fit a 16 GB chip; the published model counts 23.84 B tied."""
    import jax

    from chipbench.references import lfm2 as reference
    config = manifest.resolve_cell(CELL).config
    h = 2048
    conv = h * 3 * h + 3 * h + h * h + h
    attention = 2 * h * 2048 + 2 * h * 512 + h + 2 * 64
    dense = 3 * h * 11776 + h
    sparse = 8 * 3 * h * 1536 + h * 64 + 64 + h
    want = (8192 * h + h + (conv + dense) + (attention + sparse)
            + 3 * (conv + sparse))
    count = reference.param_count(config)
    assert count == want == 469_285_248
    assert round(count / 1e6, 1) == 469.3
    assert 16 * count < 7.52e9 and 20 * count < 9.4e9 < 16e9
    program = manifest.load_object(config["program_builder"])()
    from ray_shuffling_data_loader_tpu.models import mellum
    shapes = jax.eval_shape(lambda k: mellum.init(program, k),
                            jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == count
    assert jax.tree.map(lambda x: x.shape, shapes) == jax.tree.map(
        lambda x: x.shape, jax.eval_shape(
            lambda k: reference.init_params(config, k), jax.random.key(0)))
    whole = {**config, "num_hidden_layers": 40, "num_dense_layers": 2,
             "num_experts": 64, "vocab_size": 65536,
             "layer_types": 10 * ["conv", "conv", "full_attention", "conv"]}
    assert round(reference.param_count(whole) / 1e9, 2) == 23.84


def test_the_flop_shares_are_the_cells_why():
    """Forward FLOPs a token 405.8 M: the dense layer's MLP 36 %, the four
    ``conv`` operators' projections 33 %, the held experts and routers
    9.5 %, attention's products 8 % and projections 5 %, the head 8 %;
    9.97 TFLOP a row; and the least bytes of the gated convolution."""
    from chipbench.references import lfm2 as reference
    config = manifest.resolve_cell(CELL).config
    parts = reference._forward_flops_per_token(config)
    total = sum(parts.values())
    assert round(total / 1e6, 1) == 405.8
    shares = {k: round(100 * v / total, 1) for k, v in parts.items()}
    assert shares["dense"] == 35.7 and shares["conv_projections"] == 33.1
    assert round(shares["experts"] + shares["router"], 1) == 9.6
    assert (shares["attention"], shares["projections"], shares["head"]) == (
        8.3, 5.2, 8.3)
    assert round(reference.train_flops_per_row(config) / 1e12, 2) == 9.97
    # a held expert sees 1,024 tokens a step on an even routing
    assert 2 * 8192 * 4 / 64 == 1024
    flops, least = reference.sconv_work(config, 2)
    tokens = 2 * 8192
    assert least == 4 * (11 * 2 * tokens * 2048 + 4 * 3 * 2048)
    assert flops == 3 * 4 * tokens * 2048 * 7
    for work in ("attention_work", "mlp_work", "proj_work", "moe_work"):
        flops, least = getattr(reference, work)(config, 2)
        assert flops > 0 and least > 0, work
    # proj_work holds the conv operators' two projections
    assert reference.proj_work(config, 2)[0] == 3 * tokens * (
        parts["conv_projections"] + parts["projections"])


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(manifest.BENCH_DIR, "references", "lfm2.py")
    with open(path) as f:
        text = f.read()
    assert "ray_shuffling_data_loader_tpu" not in text
    assert "ops.moe" not in text and "ops/sconv" not in text
    # ``highest`` is the trajectory's (check.reference_trajectory)
    import inspect

    from chipbench import check
    assert '"highest"' in inspect.getsource(check.reference_trajectory)
