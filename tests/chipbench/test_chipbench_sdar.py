"""The cell ``sdar_train_8k`` (PR 47): its files resolve by name, the
configuration states its cut, every published key and every assumption,
the adapter refuses a changed size, its FLOPs and its attention's work are
the counts written out by hand, its size fits the chip beside its own
comparison, and the new reader finds nothing to read where the program has
nothing to show. Every entry is found by its name, never by its place in a
list. Its rehearsal on the CPU has a file of its own
(``test_chipbench_sdar_rehearsal.py``)."""

import json
import os

import pytest

from chipbench import manifest

CELL = "sdar_train_8k"
CONFIG = "sdar-30b-a3b-ep8"
SOURCE = ("https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/"
          "config.json")
#: The accepted metrics that list their cells and gain this one.
SHARED_LAYERS = ["feed_carve_pct", "feed_queue_wait_pct", "feed_offcpu_pct",
                 "feed_transfer_ms", "idle_under_feed_pct", "optimizer_pct",
                 "lm_attention_pct", "lm_attention_roofline_pct",
                 "lm_head_pct", "lm_proj_pct", "lm_proj_roofline_pct",
                 "moe_pct", "moe_roofline_pct", "moe_held_pairs_pct",
                 "moe_tiles_per_step", "moe_tiles_drift_pct"]
#: The catalog's ``config`` of SDAR-30B-A3B-Chat, every key but the three
#: the cut changes (``reduced``).
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]


def _by_name(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


def test_the_manifest_resolves_the_cell_and_its_entries_by_name():
    bench = manifest.load_manifest()
    cell = manifest.resolve_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        CONFIG, "train-cached-long-step", 1)
    entry = _by_name(bench["configs"], CONFIG)
    # the catalog's source_url as it stands; the file's own says the cut
    assert entry["source"] == SOURCE
    assert entry["reduced"] == REDUCED
    cut = cell.config["source"]
    assert cut.startswith(SOURCE)
    assert "one of 8 expert-parallel chips" in cut and "6 of 48" in cut
    assert len(cut) <= 200 and len(entry["why"]) <= 200
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    why = _by_name(bench["workloads"], CELL)["why"]
    assert len(why) <= 200
    for word in ("8,192-token rows", "16,384 positions", "batch 1", "1,024",
                 "1/8 of a deployment's 8,192", "57%"):
        assert word in why, word
    # the decoder cells share the traffic file, unchanged
    assert cell.traffic == manifest.resolve_cell("mellum_train_8k").traffic
    assert [m["name"] for m in cell.end_to_end] == ["train_rows_per_s",
                                                    "setup_s"]
    new = {"lm_noise_pct": ("lower", "device_trace", "model"),
           "lm_attention_live_pct": ("higher", "program_counter",
                                     "kernels"),
           # the review round's two: what lay under no scope
           "lm_norm_pct": ("lower", "device_trace", "model"),
           "lm_rope_pct": ("lower", "device_trace", "model")}
    reported = {m["name"] for m in cell.per_layer}
    assert set(new) | set(SHARED_LAYERS) | {
        "model_flops_util_pct", "step_roofline_pct", "device_step_ms",
        "peak_hbm_gb.train", "device_idle_pct.train", "input_wait_pct",
        "step_compiles", "first_batch_s"} == reported
    # no dense MLP, no mixer, no memory unit, no convolution
    assert not reported & {"lm_mlp_pct", "lm_mlp_roofline_pct", "lm_ssm_pct",
                           "lm_sscan_pct", "lm_gmu_pct", "lm_sconv_pct"}
    metrics = bench["end_to_end"] + bench["per_layer"]
    for name in SHARED_LAYERS + ["train_rows_per_s"]:
        assert _by_name(metrics, name)["workloads"].count(CELL) == 1, name
    for other in (w["name"] for w in bench["workloads"]):
        if other != CELL:
            theirs = {m["name"]
                      for m in manifest.resolve_cell(other).per_layer}
            assert not theirs & set(new), other
    for name, (better, source, layer) in new.items():
        assert _by_name(bench["per_layer"], name) == {
            "name": name, "unit": "%", "better": better, "source": source,
            "layer": layer, "moves": "train_rows_per_s",
            "workloads": [CELL]}
    for name, scope, module in (
            ("lm_noise_pct", "rsdl.lm.noise", "chipbench.readers.device"),
            ("lm_norm_pct", "rsdl.lm.norm",
             "chipbench.readers.wrapped_scopes"),
            ("lm_rope_pct", "rsdl.lm.rope",
             "chipbench.readers.wrapped_scopes")):
        with open(os.path.join(manifest.BENCH_DIR, "layers",
                               f"{name}.json")) as f:
            reads = json.load(f)
        assert (reads["args"]["scope"], reads["module"]) == (scope, module)
        reader = manifest.layer_reader(name)
        assert reader({"trace": None}) is None
        assert reader({"trace": object(), "step_op_names": {}}) is None
    # no trace (an untraced run), or a program without the scope (the
    # parent): nothing to read, nothing raised
    reader = manifest.layer_reader("lm_noise_pct")
    assert reader({"trace": None}) is None
    assert reader({"trace": object(), "step_op_names": {}}) is None


def test_the_configuration_states_its_cut():
    config = manifest.resolve_cell(CELL).config
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert config["reduced"] == REDUCED == sorted(
        config["reduced_how"], key=REDUCED.index)
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (6, 16, 19072)
    assert config["published"]["num_hidden_layers"] == 48
    assert config["published"]["num_experts"] == 128
    assert config["published"]["vocab_size"] == 151936
    assert config["published"]["chips_sharing_a_layer"] == 8
    assert (config["num_experts_routed"], config["experts_held_first"],
            config["num_experts_per_tok"]) == (128, 0, 8)
    assert 8 * config["vocab_size"] == 152576 == 149 * 8 * 128
    assert "eight TPU v5e chips share each layer" in config["deployment"]
    assert "64 chips in all" in config["deployment"]
    # the objective's three, each assumed with its reason
    assert (config["block_length"], config["mask_token_id"],
            config["noise_eps"]) == (4, 3, 1e-3)
    for key in ("block_length", "noise", "mask_token_id", "mask_row",
                "qk_norm", "head_dim", "router", "router_trains", "seq_len",
                "optimizer", "init", "precision", "tokens", "recompute",
                "attention", "head"):
        assert len(config["assumed"][key]) > 40, key
    assert config["router_trains"] is False and config["qk_norm"] is True
    assert config["seq_len"] == 8192 and config["param_dtype"] == "float32"
    data = config["data"]
    tokens = _by_name(data["columns"], "tokens")
    assert (data["rows"], data["files"], tokens["width"], tokens["vocab"],
            tokens["first"], tokens["last"]) == (128, 8, 8192, 19072, 1, 2)
    assert config["batching"] == {"batch_per_device": 1, "reducer_rows": 16,
                                  "warmup_steps": 8}
    assert config["guarantees"] == manifest.resolve_cell(
        "lfm2_train_8k").config["guarantees"]
    assert set(config["limits"]["default"]) >= {"first_grad_norm_gap",
                                                "param_change_norm_gap"}
    for module in ("adapter", "reference"):
        assert config[module] == f"chipbench.{module}s.sdar"


def test_the_adapter_refuses_a_changed_size():
    from chipbench.adapters import sdar as adapter
    config = manifest.resolve_cell(CELL).config
    program = manifest.load_object(config["program_builder"])()
    adapter.check_sizes(program, config)
    for key, other in (("hidden_size", 1024), ("num_experts", 8),
                       ("num_experts_routed", 64), ("head_dim", 64),
                       ("block_length", 8), ("mask_token_id", 0),
                       ("noise_eps", 0.1), ("rope_theta", 10000),
                       ("rope_scaling", {"type": "yarn"}),
                       ("use_sliding_window", True), ("qk_norm", False),
                       ("router_trains", True), ("vocab_size", 18992),
                       ("tie_word_embeddings", True),
                       ("decoder_sparse_step", 2),
                       ("compute_dtype", "float32")):
        with pytest.raises(ValueError, match=key):
            adapter.check_sizes(program, {**config, key: other})
    import dataclasses
    with pytest.raises(ValueError, match="no key for"):
        adapter.check_sizes(dataclasses.replace(program, attention_gate=True),
                            config)
    with pytest.raises(ValueError, match="whole blocks"):
        adapter.check_sizes(program, {**config, "seq_len": 8190})
    # the tiny preset's builder against the tiny preset's sizes
    tiny = {**config, **{k: v for k, v in config["rehearsal"].items()
                         if k not in ("data", "batching", "limits")}}
    adapter.check_sizes(manifest.load_object(tiny["program_builder"])(),
                        tiny)


def test_the_flops_and_the_attentions_work_by_hand():
    """A row of 8,192 tokens is 16,384 positions through six layers: the
    counts written out, and the shares the cell's ``why`` states."""
    from chipbench.references import sdar as reference
    config = manifest.resolve_cell(CELL).config
    length, positions, layers, h = 8192, 16384, 6, 2048
    pairs = length * length + 4 * length          # L^2 + B L
    assert reference.live_pairs(config) == pairs == 67_141_632
    projections = layers * positions * 2 * h * (4096 + 512 + 512 + 4096)
    router = layers * positions * 2 * h * 128
    experts = layers * positions * (8 * 16 / 128) * 3 * 2 * h * 768
    attention = layers * pairs * 32 * 4 * 128
    head = (length * 1.001 / 2) * 2 * h * 19072   # the masked share 0.5005
    parts = reference._forward_flops_per_row(config)
    assert parts == {"projections": projections, "attention": attention,
                     "experts": experts, "router": router, "head": head}
    total = projections + router + experts + attention + head
    assert reference.train_flops_per_row(config) == 3 * total
    assert round(3 * total / 1e12, 1) == 34.8
    shares = {k: round(100 * v / total) for k, v in parts.items()}
    assert (shares["attention"], shares["projections"], shares["head"],
            round(100 * (experts + router) / total)) == (57, 32, 3, 8)
    # a held expert sees 1,024 positions a step on an even routing
    assert positions * 8 / 128 == 1024
    flops, least = reference.attention_work(config, 1)
    assert flops == 3 * attention
    assert least == layers * 6 * 2 * positions * (4096 + 512)
    flops, least = reference.proj_work(config, 1)
    assert flops == 3 * projections and least > 0
    flops, least = reference.moe_work(config, 1)
    assert flops == 3 * (experts + router)
    assert least > 3 * 4 * layers * 16 * 3 * h * 768
    assert reference.train_step_bytes(config, 1) > 28 * 645e6


def test_the_size_fits_beside_its_own_comparison():
    """645.95 M parameters, the issue's arithmetic leaf by leaf: 16 bytes
    a parameter of state (10.34 GB) and the comparison's 20 (12.92 GB)
    both fit a 16 GB chip; the published model counts 30.5 B."""
    import jax

    from chipbench.references import sdar as reference
    config = manifest.resolve_cell(CELL).config
    h = 2048
    attention = 2 * h * 4096 + 2 * h * 512 + h + 2 * 128
    sparse = 16 * 3 * h * 768 + h * 128 + h
    assert round((attention + sparse) / 1e6, 2) == 94.64
    want = 2 * 19072 * h + h + 6 * (attention + sparse)
    count = reference.param_count(config)
    assert count == want == 645_950_976
    assert 16 * count < 10.34e9 and 20 * count < 12.92e9 < 16e9
    assert count < 700e6 < reference.param_count(
        {**config, "num_hidden_layers": 7})
    program = manifest.load_object(config["program_builder"])()
    from ray_shuffling_data_loader_tpu.models import mellum
    shapes = jax.eval_shape(lambda k: mellum.init(program, k),
                            jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == count
    assert jax.tree.map(lambda x: x.shape, shapes) == jax.tree.map(
        lambda x: x.shape, jax.eval_shape(
            lambda k: reference.init_params(config, k), jax.random.key(0)))
    whole = {**config, "num_hidden_layers": 48, "num_experts": 128,
             "vocab_size": 151936}
    assert round(reference.param_count(whole) / 1e9, 1) == 30.5


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(manifest.BENCH_DIR, "references", "sdar.py")
    with open(path) as f:
        text = f.read()
    assert "import ray_shuffling" not in text
    assert "from ray_shuffling" not in text
    # ``highest`` is the trajectory's (check.reference_trajectory), and
    # the reference's docstring says so
    import inspect

    from chipbench import check
    assert '"highest"' in inspect.getsource(check.reference_trajectory)
    assert 'default_matmul_precision("highest")' in text


def test_the_live_share_reads_the_programs_gauges_or_nothing(capsys):
    """``lm_attention_live_pct`` is the program's own count: without the
    gauges (the parent's program, an inline attention) nothing to read and
    nothing raised; with them the live pairs over the pairs in the tiles
    visited, forward and backward together."""
    from chipbench.readers import diffusion_stats
    from ray_shuffling_data_loader_tpu.runtime import metrics
    reader = manifest.layer_reader("lm_attention_live_pct")
    gauges = [(name, d) for name in (
        diffusion_stats.LIVE_PAIRS, diffusion_stats.TILE_PAIRS,
        diffusion_stats.TILES_VISITED, diffusion_stats.TILES_COMPARED)
        for d in diffusion_stats.DIRECTIONS]
    if all(metrics.get(n, {"direction": d}) is None for n, d in gauges):
        assert reader({}) is None
    live = 8192 * 8192 + 4 * 8192
    for direction, tiles, side in (("forward", 80, 1024),
                                   ("backward", 288, 512)):
        metrics.gauge(diffusion_stats.LIVE_PAIRS, "",
                      direction=direction).set(live)
        metrics.gauge(diffusion_stats.TILE_PAIRS, "",
                      direction=direction).set(tiles * side * side)
        metrics.gauge(diffusion_stats.TILES_VISITED, "",
                      direction=direction).set(tiles)
        metrics.gauge(diffusion_stats.TILES_COMPARED, "",
                      direction=direction).set(24)
    value = reader({})      # no trace: no noise lines, the share all the same
    assert value == pytest.approx(
        100 * 2 * live / (80 * 1024 ** 2 + 288 * 512 ** 2))
    assert 80 < value < 89
    assert "visited 80 / 288" in capsys.readouterr().out
    assert diffusion_stats.live_pct([live], [80 * 1024 ** 2]) \
        == pytest.approx(80.04, abs=0.01)
    lines = diffusion_stats.series_lines(
        [{"step": 7, "stats": {"lm_noise": [
            {"masked": 4096.0, "weight_sum": 8000.0}]}}], [7], 8192)
    assert lines == ["# step stats 7*: noise masked 4096 of 8192 tokens "
                     "(50.000 %), weights sum to 0.9766 a token"]


# -- what lies under no scope (chipbench/probes/unscoped_ops.py) --------------


def _traced_step():
    """A step of four operations and a loop around one of them, run twice:
    ``(trace, names)``."""
    from chipbench import xplane
    names = {
        "fusion.1": "jit(train_step)/jit(main)/jvp(rsdl.lm.proj)/dot_general",
        "fusion.2": "jit(train_step)/jit(main)/checkpoint/rms_norm/mul",
        "fusion.3": "jit(train_step)/jit(main)/transpose(jvp(checkpoint))/"
                    "rms_norm/mul",
        "while.4": "jit(train_step)/jit(main)/while",
        "copy.5": "",
    }

    def run_of(start):
        ops, at = [], start
        for name, opcode, seconds in (("fusion.1", "fusion", 0.04),
                                      ("fusion.2", "fusion", 0.02),
                                      ("fusion.3", "fusion", 0.03),
                                      ("while.4", "while", 0.5),
                                      ("copy.5", "copy", 0.01)):
            ops.append(xplane.Op(f"{name}_{opcode}", opcode,
                                 f"%{name} = f32[8]{{0}} {opcode}()", at,
                                 at + seconds))
            at += seconds
        return ops

    module = lambda start: xplane.Op(               # noqa: E731
        "jit_train_step", "module", "jit_train_step(1)", start, start + 1.0)
    trace = xplane.Trace(ops={0: run_of(0.0) + run_of(1.0)},
                         modules={0: [module(0.0), module(1.0)]}, spans=[])
    return trace, names


def test_unscoped_seconds_go_by_path_and_leave_the_loops_out():
    from chipbench.probes import unscoped_ops
    trace, names = _traced_step()
    outside, inside = unscoped_ops.unscoped_seconds(trace.ops[0], names)
    assert inside == pytest.approx(2 * 0.04)
    assert outside == {
        ("checkpoint/rms_norm/mul", "fusion"): pytest.approx(2 * 0.02),
        ("transpose(jvp(checkpoint))/rms_norm/mul", "fusion"):
            pytest.approx(2 * 0.03),
        ("(no op_name)", "copy"): pytest.approx(2 * 0.01)}
    assert unscoped_ops.path_outside_jit(
        "jit(a)/jit(b)/jvp(jit(c))/mul") == "jvp(jit(c))/mul"


def test_the_probe_prints_the_unscoped_paths_a_step(capsys):
    from chipbench.probes import unscoped_ops
    trace, names = _traced_step()
    facts = {"trace": trace, "trace_window": (0.0, 2.0),
             "step_op_names": names, "step_module": "^jit_train_step$"}
    unscoped_ops.print_unscoped(facts)
    lines = capsys.readouterr().out.splitlines()
    assert lines[0].startswith("# no scope: 60.0000 ms a step under no rsdl "
                               "scope and 40.0000 under one, of 1000.0000 ms "
                               "over 2 steps; 3 paths")
    assert [ln.split()[3] for ln in lines[1:]] == ["30.0000", "20.0000",
                                                   "10.0000"]
    assert lines[1].endswith("transpose(jvp(checkpoint))/rms_norm/mul")
    # a loop that kept no compiled text has nothing to say
    unscoped_ops.print_unscoped(dict(facts, step_op_names={}))
    assert "kept no compiled text" in capsys.readouterr().out


def test_the_kernels_probe_times_each_tiling_and_says_what_is_refused(
        capsys):
    """``chipbench/probes/sdar_kernels.py`` off the chip: the kernels
    interpreted at 64 tokens twice; a tiling the mask does not fit is
    named and the sweep goes on; without a TPU and unasked it ends at
    once."""
    from chipbench.probes import sdar_kernels
    assert sdar_kernels.main(["--allow-cpu", "--calls", "1", "--tiles",
                              "16x16", "128x128"]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert len(lines) == 4
    assert lines[0].startswith("# tiles 16 x 16 diffusion: forward ")
    assert lines[0].endswith("tiles (24, 12, 4352)")    # 64^2 + 4 x 64
    assert lines[1].startswith("# tiles 128 x 128 diffusion: refused: ")
    assert lines[3].startswith("# tiles 128 x 128 causal over 2L: forward ")
    assert sdar_kernels.main([]) == 2


def test_the_noise_probe_names_the_heavy_token_of_a_seed(capsys):
    """``chipbench/probes/sdar_noise.py``: the compared steps' draws at a
    seed, off the chip. At seed 4700002104, the one seed of twenty-nine
    whose first gradient stood 0.37 off the reference's on the chip, step
    0 masks a token that weighs 242, half the sum of the squares; the
    traced run of 4700001002 printed 4,055 masked tokens and weights
    summing to 0.9892 a token for the same step (PERF.md section 6)."""
    from chipbench.probes import sdar_noise
    assert sdar_noise.main(["--seeds", "4700002104", "--steps", "1"]) == 0
    line, = capsys.readouterr().out.splitlines()
    assert line.startswith("# noise seed 4700002104 step 0: masked 4011 of "
                           "8192, heaviest weight 242.1, 49.4 % of")
    sizes = manifest.resolve_cell(CELL).config
    got = sdar_noise.step_weights(sizes, 4700001002, 0, 1)
    assert got["masked"] == 4055
    assert round(got["sum_a_token"], 4) == 0.9892


def test_a_scope_inside_a_transforms_name_is_read_as_the_scope(capsys):
    """``readers/wrapped_scopes.py``: ``jvp(rsdl.lm.norm)/mul``, as JAX
    names the forward pass's operations of a scope entered outside any
    jit, counts for ``rsdl.lm.norm`` beside the recomputed and the
    backward ones, whose path holds the scope as a component; the
    accepted reader alone sees the second form only."""
    from chipbench import xplane
    from chipbench.readers import device, wrapped_scopes
    for written, read in (
            ("jit(s)/jvp(rsdl.lm.norm)/mul", "jit(s)/jvp()/rsdl.lm.norm/mul"),
            ("jit(s)/transpose(jvp(rsdl.lm.rope))/bshd,de->bshe/dot_general",
             "jit(s)/transpose(jvp())/rsdl.lm.rope/bshd,de->bshe/"
             "dot_general"),
            ("jit(s)/jvp(jit(_project))/rsdl.lm.proj/dot_general",) * 2,
            ("jit(s)/transpose(jvp(jvp()))/checkpoint/add_any",) * 2):
        assert wrapped_scopes.unwrapped(written) == read
    trace, names = _traced_step()
    names = dict(names, **{
        "fusion.2": "jit(train_step)/jvp(rsdl.lm.norm)/mul",
        "fusion.3": "jit(train_step)/transpose(jvp(jvp()))/checkpoint/"
                    "rsdl.lm.norm/mul"})
    facts = {"trace": trace, "trace_window": (0.0, 2.0),
             "step_op_names": names, "step_module": "^jit_train_step$"}
    module = "^jit_train_step$"
    assert device.scope_pct_of_step(facts, "rsdl.lm.norm",
                                    module) == pytest.approx(3.0)
    assert wrapped_scopes.scope_pct_of_step(
        facts, "rsdl.lm.norm", module) == pytest.approx(5.0)
    assert wrapped_scopes.scope_pct_of_step(facts, "rsdl.lm.rope",
                                            module) is None
    assert xplane.under_scope(names["fusion.3"], "rsdl.lm.norm")
    capsys.readouterr()
