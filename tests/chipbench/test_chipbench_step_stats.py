"""The readers of the train step's own counters
(``chipbench/readers/step_stats.py``): their arithmetic on a made-up ring
and a made-up list of step numbers, what they do with a program that has no
channel, the manifest's four entries of PR 36, and all three on the trace
of a tiny decoder's steps on the CPU. Nothing here computes or prints a
device metric."""

import functools
import json
import os

import pytest

from chipbench import manifest, xplane
from chipbench.readers import step_stats

CELLS = ["mellum_train_8k", "laguna_train_8k"]
NAMES = ["moe_held_pairs_pct", "moe_tiles_per_step", "moe_tiles_drift_pct"]


def _entry(step, tiles_by_layer, held=16):
    return {"step": step, "fold_s": 0.0005, "stats": {"moe_walk": [
        {"layer": str(layer), "pairs": 64, "pairs_held": held,
         "tiles": tiles, "rounds": 1, "fullest_expert_rows": 9 + layer}
        for layer, tiles in enumerate(tiles_by_layer)]}}


class _Ring:
    """What the readers ask of ``utils/tracing``."""

    def __init__(self, entries):
        self.entries, self.folds = entries, []

    def fold_step_stats(self, wait=False):
        self.folds.append(wait)
        return 0

    def step_stats(self, first=None, last=None):
        return [e for e in self.entries
                if (first is None or e["step"] >= first)
                and (last is None or e["step"] <= last)]


def test_the_three_numbers_of_a_made_up_window():
    """Eight steps of two layers: the walk grows from 4 tiles a step to
    8, the held share from a quarter to a half."""
    entries = [_entry(10 + i, [2 + i // 2] * 2, held=16 + 2 * i + i % 2)
               for i in range(8)]
    held = sum(2 * (16 + 2 * i + i % 2) for i in range(8))
    assert step_stats.held_pairs_pct(entries) == pytest.approx(
        100.0 * held / (8 * 2 * 64))
    tiles = [4, 4, 6, 6, 8, 8, 10, 10]
    assert step_stats.tiles_per_step(entries) == pytest.approx(
        sum(tiles) / 8)
    assert step_stats.tiles_drift_pct(entries) == pytest.approx(
        100.0 * (10 / 4 - 1))
    steady = [_entry(i, [32, 33, 32, 32]) for i in range(13)]
    assert step_stats.held_pairs_pct(steady) == 25.0
    assert step_stats.tiles_per_step(steady) == 129.0
    assert step_stats.tiles_drift_pct(steady) == 0.0
    assert step_stats.tiles_drift_pct(steady[:1]) == 0.0


def test_the_windows_steps_are_asked_of_the_ring_by_number():
    """The ring's entries of the listed steps and no others, after a fold
    that waits; nothing without a ring, without steps, or where the steps
    recorded no walk."""
    ring = _Ring([_entry(s, [3, 4]) for s in range(20)]
                 + [{"step": 20, "fold_s": 0.0, "stats": {}}])
    walks = step_stats.walks_of(ring, [7, 8, 9, 11])
    assert [e["step"] for e in walks] == [7, 8, 9, 11]
    assert ring.folds == [True]
    assert step_stats.walks_of(None, [7, 8]) is None
    assert step_stats.walks_of(ring, []) is None
    assert step_stats.walks_of(ring, [20]) is None
    assert step_stats.walks_of(ring, [40, 41]) is None


def test_the_series_prints_a_line_a_step():
    lines = step_stats.series_lines(
        [_entry(11, [32, 33], held=16400), _entry(12, [40, 41])], [12])
    assert lines == [
        "# step stats 11: held 16400/16400 of 64 pairs a layer (layers "
        "0/1), tiles 32/33 (65), rounds 1/1, fullest expert 10 rows; fold "
        "0.500 ms",
        "# step stats 12*: held 16/16 of 64 pairs a layer (layers 0/1), "
        "tiles 40/41 (81), rounds 1/1, fullest expert 10 rows; fold 0.500 "
        "ms"]


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_channel_gives_nothing(name, monkeypatch):
    """The parent commit's side of a traced run: ``utils/tracing`` has no
    ring, so every reader returns ``None`` and raises nothing; the same
    in a run that traced nothing."""
    from ray_shuffling_data_loader_tpu.utils import tracing
    monkeypatch.delattr(tracing, "step_stats")
    assert step_stats.channel() is None
    path = os.path.join(os.path.dirname(__file__), "data", "probe.xplane.pb")
    facts = {"kind": "train", "trace_path": path,
             "trace_window": xplane.window_of(xplane.load(path))}
    assert manifest.layer_reader(name)(facts) is None
    monkeypatch.undo()
    assert step_stats.channel() is tracing
    assert manifest.layer_reader(name)({"kind": "train",
                                        "trace_path": None}) is None


def test_the_manifest_ends_with_the_four_entries():
    bench = manifest.load_manifest()
    last = bench["per_layer"][-4:]
    assert [m["name"] for m in last] == NAMES + ["optimizer_pct"]
    for entry in last[:3]:
        assert entry == {"name": entry["name"], "unit": entry["unit"],
                         "better": "lower", "source": "program_counter",
                         "layer": "kernels", "moves": "train_rows_per_s",
                         "workloads": CELLS}
    assert [m["unit"] for m in last[:3]] == ["%", "count", "%"]
    cells = [w["name"] for w in bench["workloads"]]
    assert last[3] == {"name": "optimizer_pct", "unit": "%",
                       "better": "lower", "source": "device_trace",
                       "layer": "trainer", "moves": "train_rows_per_s",
                       "workloads": cells}
    for cell in cells:
        reported = {m["name"] for m in manifest.resolve_cell(cell).per_layer}
        assert "optimizer_pct" in reported
        assert set(NAMES) <= reported or cell not in CELLS
        assert not (set(NAMES) & reported) or cell in CELLS


def test_the_optimizers_share_reads_the_trainers_scope():
    """``optimizer_pct`` is data: the accepted scope reader pointed at the
    name ``make_train_step`` puts around the update."""
    from chipbench.readers import device
    from ray_shuffling_data_loader_tpu.parallel import trainer
    with open(os.path.join(manifest.BENCH_DIR, "layers",
                           "optimizer_pct.json")) as f:
        spec = json.load(f)
    assert spec == {"module": "chipbench.readers.device",
                    "function": "scope_pct_of_step",
                    "args": {"scope": trainer.OPTIMIZER_SCOPE,
                             "module": "^jit_train_step$"}}
    assert device.scope_pct_of_step({"trace": None}, **spec["args"]) is None


@pytest.fixture(scope="module")
def traced_steps(tmp_path_factory):
    """A tiny decoder's trainer stepped on the CPU under the profiler,
    five steps of them inside a ``chipbench.window`` span."""
    import jax
    import optax

    from chipbench import harness
    from ray_shuffling_data_loader_tpu.models import mellum
    from ray_shuffling_data_loader_tpu.parallel import mesh as mesh_mod
    from ray_shuffling_data_loader_tpu.parallel.trainer import SpmdTrainer
    from ray_shuffling_data_loader_tpu.utils import tracing
    tracing.reset_step_stats()
    cfg = mellum.mellum_tiny()
    trainer = SpmdTrainer(
        mesh_mod.make_mesh(devices=jax.devices()[:1]),
        functools.partial(mellum.loss_fn, cfg),
        mellum.init(cfg, jax.random.key(0)), optax.adam(1e-3))
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0,
                                cfg.vocab_size)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    trainer.train_step(tokens)                      # step 0 compiles
    with tracing.profile_trace(trace_dir):
        trainer.train_step(tokens)                  # step 1: before it
        with harness.span("chipbench.window"):
            losses = [trainer.train_step(tokens) for _ in range(5)]
            jax.block_until_ready(losses)
        trainer.train_step(tokens)                  # step 7: after it
    path = xplane.find_xplane(trace_dir)
    yield {"kind": "train", "trace_path": path,
           "trace_window": xplane.window_of(xplane.load(path))}
    tracing.reset_step_stats()


def test_the_windows_steps_are_found_by_the_annotations_number(traced_steps):
    assert step_stats.annotated_steps(
        traced_steps["trace_path"], traced_steps["trace_window"]) == [
            2, 3, 4, 5, 6]


def test_every_reader_on_a_traced_decoder(traced_steps, capsys):
    """Through the manifest's layer files: the three numbers of the
    window's five steps, the series of every step the ring holds with the
    window's marked, and the fold's cost on one line."""
    facts = dict(traced_steps)
    values = {name: manifest.layer_reader(name)(facts) for name in NAMES}
    # mellum_tiny holds 2 of 8 experts in each of four sparse layers
    assert 5.0 < values["moe_held_pairs_pct"] < 60.0
    assert values["moe_tiles_per_step"] == 8.0      # a tile a held expert
    assert values["moe_tiles_drift_pct"] == 0.0
    json.dumps(values)
    printed = capsys.readouterr().out.splitlines()
    series = [line for line in printed if line.startswith("# step stats ")]
    assert len(series) == 8                  # steps 0-7, printed once
    assert [line.split(":")[0] for line in series] == [
        f"# step stats {s}{'*' if 2 <= s <= 6 else ''}" for s in range(8)]
    assert any(line.startswith("# step stats: 5 steps of the traced "
                               "window (2-6) of 8 in the ring; the fold "
                               "took")
               for line in printed)
