"""The cell ``mellum_train_8k`` (PR 32): its files resolve, its rehearsal
on the CPU at the tiny preset is ``correct``, the precision below is not,
and the kernels' roofline reader reads nothing where there is nothing and
refuses a share over 100 %."""

import json
import os

import pytest

from chipbench import manifest, run, xplane
from chipbench.readers import kernels

CELL = "mellum_train_8k"
CONFIG = "mellum2-12b-a2.5b-ep4"
NEW_LAYERS = {"moe_pct": "rsdl.lm.moe", "lm_attention_pct":
              "rsdl.lm.attention", "lm_head_pct": "rsdl.lm.head",
              "moe_roofline_pct": "rsdl.lm.moe",
              "lm_attention_roofline_pct": "rsdl.lm.attention"}


def _rehearse(capsys, *extra):
    rc = run.main(["--workload", CELL, "--seed", str(2**31 + 32),
                   "--seconds", "0.5", "--trace", "0", "--rehearse", *extra])
    out, _ = capsys.readouterr()
    assert rc == 0
    lines = out.strip().splitlines()
    return json.loads(lines[-1]), lines


def test_the_rehearsal_is_correct_and_names_the_cpu(capsys):
    result, lines = _rehearse(capsys)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"] == {}, "a CPU run reports no device metric"
    assert set(result["compared"]) == {
        "loss_gap", "first_grad_norm_gap", "param_change_norm_gap",
        "epochs_off_the_files", "final_loss_not_finite"}
    compared = [ln for ln in lines if ln.startswith("# compared ")]
    assert compared and all(ln.endswith(" ok") for ln in compared)
    # 64 rows of 32 tokens, 4 a step: at least one whole epoch was checked
    window = next(ln for ln in lines if ln.startswith("# window: "))
    assert "16 steps an epoch" in window and "epochs ended [0" in window


def test_the_reference_in_bfloat16_is_caught():
    """What ``--control ref_bf16`` puts in the program's place (the
    rehearsals of two other cells drive the flag itself): the plain
    reference computed in bfloat16, through the harness's own trajectory
    and comparison at the tiny preset's sizes and limits, fails the
    parameters' change (a norm's scale of 1 + 1e-4 is 1 in bfloat16: it
    never moves) and is not ``correct``."""
    import jax
    import numpy as np

    from chipbench import check, harness
    from chipbench.references import mellum as reference
    ctx = harness.Context(cell=manifest.resolve_cell(CELL), seed=0,
                          seconds=0.0, trace=False, rehearse=True,
                          control="ref_bf16", started_at=0.0, scratch="")
    sizes = ctx.sizes
    rng = np.random.default_rng(32)
    batches = [([rng.integers(4, sizes["vocab_size"],
                              (ctx.traffic("batch_per_device"),
                               sizes["seq_len"]), dtype=np.int32)],
                np.zeros((4,), np.int32)) for _ in range(check.STEPS)]
    key = jax.random.key(32)

    def params0():
        return reference.init_params(sizes, key)

    sound, low = (check.reference_trajectory(
        reference, sizes, params0, batches, sizes["optimizer"], key,
        lower_precision=lower) for lower in (False, True))
    compared = check.compare(low, sound, ctx.limits())
    failed = [c.name for c in compared if not c.ok]
    assert failed and all(n.startswith("param_change_norm_gap")
                          for n in failed), [c.line() for c in compared]


def test_the_manifest_resolves_the_cell_and_its_files():
    bench = manifest.load_manifest()
    cell = manifest.resolve_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        CONFIG, "train-cached-long-step", 1)
    assert bench["workloads"][-1]["name"] == CELL, "added at the end"
    assert bench["configs"][-1]["name"] == CONFIG
    assert [m["name"] for m in bench["per_layer"][-5:]] == list(NEW_LAYERS)
    # the traffic is train-cached with a longer traced window, no more
    with open(os.path.join(manifest.BENCH_DIR, "traffic",
                           "train-cached.json")) as f:
        cached = json.load(f)
    ours = dict(cell.traffic)
    assert ours.pop("assumed") and ours.pop("trace_seconds") == 8
    cached.pop("trace_seconds")
    assert ours == cached
    assert [m["name"] for m in cell.end_to_end] == ["train_rows_per_s",
                                                    "setup_s"]
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW_LAYERS) | {
        "feed_carve_pct", "feed_queue_wait_pct", "feed_offcpu_pct",
        "feed_transfer_ms", "idle_under_feed_pct", "model_flops_util_pct",
        "step_roofline_pct", "device_step_ms", "peak_hbm_gb.train",
        "device_idle_pct.train", "input_wait_pct", "step_compiles",
        "first_batch_s"} == reported
    for other in bench["workloads"][:-1]:
        theirs = {m["name"] for m in
                  manifest.resolve_cell(other["name"]).per_layer}
        assert not theirs & set(NEW_LAYERS), other["name"]
    for name, scope in NEW_LAYERS.items():
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
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
    entry = manifest.load_manifest()["configs"][-1]
    assert config["source"].startswith(entry["source"])
    assert set(entry["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers", "layer_types", "mlp_layer_types", "num_experts",
        "vocab_size"}
    # the widths are the published ones; the three cuts state both sides
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["sliding_window"]) == (2304, 32, 4, 128, 896, 8, 1024)
    assert (config["num_hidden_layers"], config["num_experts"],
            config["num_experts_routed"], config["vocab_size"]) == (
                4, 16, 64, 24576)
    assert config["published"] == {
        **config["published"], "num_hidden_layers": 28, "num_experts": 64,
        "vocab_size": 98304, "chips_sharing_a_layer": 4}
    assert config["layer_types"] == 3 * ["sliding_attention"] + [
        "full_attention"]
    from chipbench.references import mellum as reference
    assert reference.param_count(config) == 595_153_152
    data = config["data"]
    assert data["rows"] % config["batching"]["batch_per_device"] == 0
    assert data["columns"][0]["width"] == config["seq_len"] == 8192
    assert data["columns"][0]["vocab"] == config["vocab_size"]
    # the program builds what the file says, at both sizes
    import importlib
    adapter = importlib.import_module(config["adapter"])
    adapter.check_sizes(manifest.load_object(config["program_builder"])(),
                        config)
    tiny = {**config, **{k: v for k, v in config["rehearsal"].items()
                         if k not in ("data", "batching", "limits")}}
    adapter.check_sizes(manifest.load_object(tiny["program_builder"])(), tiny)


class _Reference:
    @staticmethod
    def attention_work(sizes, rows):
        return 197e12 * 0.010 * rows, 1.0       # 10 ms a row at the peak


@pytest.mark.parametrize("under_s,want", [
    (0.080, 50.0),      # 4 rows: least 40 ms, measured 80 ms a step
    (0.0, None),        # the program has no such scope: nothing to read
    (0.039, "raises"),  # a share over 100 % is an error, not a number
])
def test_the_kernels_reader(monkeypatch, under_s, want):
    steps = 5
    monkeypatch.setattr(xplane, "module_durations",
                        lambda trace, win, module: [0.5] * steps)
    monkeypatch.setattr(xplane, "scope_seconds",
                        lambda trace, win, scope, names, module:
                        under_s * steps)
    facts = {"trace": object(), "trace_window": (0.0, 3.0),
             "step_op_names": {"fusion.1": "jit(train_step)/x"},
             "reference": _Reference, "sizes": {}, "rows_per_step": 4,
             "chips": 1, "device": {"kind": "TPU v5 lite"}}
    read = manifest.layer_reader("lm_attention_roofline_pct")
    if want == "raises":
        with pytest.raises(ValueError, match="counted too high"):
            read(facts)
    else:
        assert read(facts) == (None if want is None
                               else pytest.approx(want))
    assert read(dict(facts, trace=None)) is None
    assert kernels.scope_roofline_pct(
        facts, "rsdl.lm.attention", "no_such_work", "^jit_train_step$"
    ) is None, "a reference without the function gives nothing to read"
