"""Which numbers decide ``correct`` for a train cell follows the names in
the configuration's ``limits`` (``chipbench/check.compare``), and the two
DLRM takes (loss at the seeded weights, median leaf's change) let an
ill-conditioned seed through while every fault they are there for fails.

The reference's trajectory (``check.reference_trajectory``) holds what the
program's state holds and no more, and computes what the one before it
(``chipbench_oracle.py``) computed, digit for digit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chipbench_oracle import old_reference_trajectory

from chipbench import check, manifest
from chipbench.probes import check_footprint, first_steps
from chipbench.references import dlrm as dlrm_reference

_WORST = {"loss_gap": 0.006, "first_grad_norm_gap": 0.1,
          "param_change_norm_gap": 0.013}


def _dlrm_limits():
    cell = manifest.resolve_cell("dlrm_train_x4")
    return cell.config["limits"]["default"]


def _leaves(tables=19, mlp=10):
    return ([f"table_{i}" for i in range(tables)]
            + [f"top_{i}" for i in range(mlp)])


def _trajectory(change_off=None, losses=(0.6937, 0.6937, 0.6941),
                change=1.0):
    """A reference and a program whose change norms are off by
    ``change_off[leaf]`` (relative), all leaves of norm 1 otherwise."""
    change_off = change_off or {}
    reference = {"losses": [0.6937, 0.6937, 0.6941],
                 "grad_norms": {k: 1.0 for k in _leaves()},
                 "change_norms": {k: 1.0 for k in _leaves()}}
    program = {"losses": list(losses),
               "grad_norms": dict(reference["grad_norms"]),
               "change_norms": {k: change * (1.0 + change_off.get(k, 0.0))
                                for k in _leaves()}}
    return program, reference


def _verdict(program, reference, limits):
    return {c.name.partition("[")[0]: c.ok
            for c in check.compare(program, reference, limits)}


#: What seed 580997687 read in ``dlrm_train_x4`` (the driver's check of
#: PR 27): the three big leaves of the top MLP off by 4-6 %, the third
#: step's loss by 0.6 %, every gradient and every other leaf sound.
_ILL_CONDITIONED = dict(
    change_off={"top_1": 0.061, "top_2": 0.04, "top_3": 0.037,
                "top_0": 0.01, "table_3": 0.001},
    losses=(0.6937, 0.6937, 0.6941 * 1.0063))


@pytest.mark.parametrize("case,kwargs,fails_dlrm,fails_worst", [
    ("sound", dict(change_off={"top_1": 0.004}), set(), set()),
    ("ill-conditioned seed, sound program", _ILL_CONDITIONED, set(),
     {"loss_gap", "param_change_norm_gap"}),
    ("bfloat16 parameters: every leaf's change off by 2-4 %",
     dict(change_off={k: 0.02 + 0.001 * i
                      for i, k in enumerate(_leaves())}),
     {"param_change_median_leaf_gap"}, {"param_change_norm_gap"}),
    ("a step that returns its state unchanged", dict(change=0.0),
     {"param_change_median_leaf_gap"}, {"param_change_norm_gap"}),
    ("half the leaves never updated",
     dict(change_off={k: -1.0 for k in _leaves()[:15]}),
     {"param_change_median_leaf_gap"}, {"param_change_norm_gap"}),
    ("loss over part of the batch: off at the seeded weights",
     dict(losses=(0.6937 * 1.0006, 0.6937, 0.6941)),
     {"first_loss_gap"}, set()),
])
def test_the_limits_names_decide_what_is_compared(case, kwargs, fails_dlrm,
                                                  fails_worst):
    program, reference = _trajectory(**kwargs)
    dlrm = _verdict(program, reference, _dlrm_limits())
    assert set(dlrm) == {"first_loss_gap", "first_grad_norm_gap",
                         "param_change_median_leaf_gap"}
    assert {k for k, ok in dlrm.items() if not ok} == fails_dlrm, case
    worst = _verdict(program, reference, _WORST)
    assert set(worst) == set(_WORST)
    assert {k for k, ok in worst.items() if not ok} == fails_worst, case


def test_the_numbers_left_out_are_named():
    program, reference = _trajectory(**_ILL_CONDITIONED)
    said = check.not_compared(program, reference, _dlrm_limits())
    assert "loss gap by step" in said and "worst leaf's change 0.061" in said
    assert "median" not in said
    assert "median leaf's change" in check.not_compared(program, reference,
                                                        _WORST)


@pytest.mark.parametrize("limits,complaint", [
    ({"loss_gap": 1, "first_grad_norm_gap": 1, "param_change_gap": 1},
     "param_change_gap"),
    ({"first_grad_norm_gap": 1, "param_change_norm_gap": 1}, "a loss"),
    ({"loss_gap": 1, "first_grad_norm_gap": 1}, "a change"),
    ({"loss_gap": 1, "param_change_norm_gap": 1}, "first_grad_norm_gap"),
])
def test_limits_that_do_not_hold_together_are_refused(limits, complaint):
    program, reference = _trajectory()
    with pytest.raises(ValueError, match=complaint):
        check.compare(program, reference, limits)


def test_a_leaf_that_is_no_number_is_the_worst_and_moves_the_median():
    program, reference = _trajectory()
    program["change_norms"]["table_0"] = float("nan")
    gap, leaf = check.worst_leaf_gap(program["change_norms"],
                                     reference["change_norms"])
    assert gap == float("inf") and leaf == "table_0"
    verdict = _verdict(program, reference, _WORST)
    assert verdict["param_change_norm_gap"] is False


def test_the_probe_draws_the_files_distribution_from_the_seed():
    data = manifest.resolve_cell("dlrm_train").config["data"]
    one = first_steps.draw_batches(data, 2**31 + 5, 256, 3)
    again = first_steps.draw_batches(data, 2**31 + 5, 256, 3)
    other = first_steps.draw_batches(data, 2**31 + 6, 256, 3)
    features = [c for c in data["columns"] if c.get("role") == "feature"]
    assert len(one) == 3 and len(one[0][0]) == len(features)
    for (cols, label), (cols2, label2) in zip(one, again):
        assert all((a == b).all() for a, b in zip(cols, cols2))
        assert (label == label2).all()
        assert label.shape == (256, 1) and str(label.dtype) == "float32"
        for col, spec in zip(cols, features):
            assert col.shape == (256, 1)
            assert str(col.dtype) == spec["deliver_as"]
            assert 0 <= col.min() and col.max() < spec["cardinality"]
    assert not (one[0][1] == other[0][1]).all()
    assert not (one[0][1] == one[1][1]).all(), "three batches that differ"


def test_the_probes_summary_holds_every_number_compared_or_not():
    program, reference = _trajectory(**_ILL_CONDITIONED)
    got = first_steps.summary(program, reference)
    assert got["loss_gap_by_step"][0] == 0.0
    assert got["param_change_worst_leaf_gap"] == pytest.approx(0.061)
    assert got["param_change_median_leaf_gap"] == 0.0
    assert got["param_change_worst_leaves"][0][0] == "top_1"
    assert got["first_grad_worst_leaf_gap"] == 0.0


def test_the_probe_refuses_off_the_chip(capsys):
    assert first_steps.main(["--workload", "dlrm_train", "--first-seed", "1",
                             "--seeds", "1"]) == 2
    assert "TPU chip" in capsys.readouterr().err


# -- the reference's trajectory: the old one's numbers at the program's footprint

_ADAM = {"name": "adam", "learning_rate": 1e-3, "b1": 0.9, "b2": 0.999,
         "eps": 1e-8}


class _Synthetic:
    """A plain reference over a seeded tree of leaves of mixed shapes: a
    loss whose gradient reaches every element and differs from batch to
    batch, next to no activations."""

    def __init__(self, shapes):
        self.shapes = shapes
        self._value_and_grad = jax.jit(jax.value_and_grad(self._loss))
        self.init = jax.jit(self._init)

    def _init(self):
        keys = jax.random.split(jax.random.key(28), len(self.shapes))
        leaves = [jax.random.normal(k, s, jnp.float32)
                  for k, s in zip(keys, self.shapes)]
        return {"a": {"w": leaves[0], "b": leaves[1]},
                "rest": tuple(leaves[2:])}

    @staticmethod
    def _loss(params, x, y):
        z = jnp.stack([jnp.mean(jnp.tanh(p))
                       for p in jax.tree.leaves(params)])
        pred = x[:, :z.shape[0]].astype(z.dtype) @ z
        decay = sum(jnp.mean(jnp.square(p)) for p in jax.tree.leaves(params))
        return (jnp.mean(jnp.square(pred - y.astype(z.dtype)))
                + 1e-3 * decay).astype(jnp.float32)

    def value_and_grad(self, sizes, params, features, labels, step,
                       seed_key):
        return self._value_and_grad(params, features[0], labels)

    def batches(self, steps=check.STEPS):
        rng = np.random.default_rng(5)
        return [([rng.normal(size=(16, len(self.shapes))).astype(np.float32)],
                 rng.normal(size=(16,)).astype(np.float32))
                for _ in range(steps)]


def _live_bytes():
    return sum(x.nbytes for x in jax.live_arrays())


_MIXED = [(300, 64), (64,), (7, 5, 3), (1,), (1000, 16), (33, 9), (2, 2)]


@pytest.mark.parametrize("lower_precision", [False, True],
                         ids=["sound", "lower_precision"])
def test_the_trajectory_computes_what_the_old_one_did(lower_precision,
                                                      capsys):
    ref = _Synthetic(_MIXED)
    new = check.reference_trajectory(ref, {}, ref.init, ref.batches(), _ADAM,
                                     None, lower_precision=lower_precision)
    old = old_reference_trajectory(ref, {}, ref.init(), ref.batches(), _ADAM,
                                   None, lower_precision=lower_precision)
    assert len(new["losses"]) == check.STEPS
    assert len(new["change_norms"]) == len(_MIXED)
    # (a step of 1e-3 is under bfloat16's grid around 1: the control moves
    # only the elements near nought, and a leaf of one element not at all)
    assert all(v > 0 for v in new["change_norms"].values()) != lower_precision
    assert new == old, "not one digit may differ"


def test_the_trajectory_holds_what_the_program_holds_where_the_old_held_eight(
        monkeypatch, capsys):
    """Live bytes over the footprint probe's own reference (a dozen
    unequal leaves) when each gradient has just been made and around each
    update (its inputs still held by the wrapper, its outputs made): the
    parameters, two moments and one gradient. The ceiling is five copies
    and a leaf; the old function reads eight."""
    seen = []

    class Watched(check_footprint.Reference):
        def value_and_grad(self, *args, **kwargs):
            out = super().value_and_grad(*args, **kwargs)
            seen.append(_live_bytes())
            return out

    before = _live_bytes()
    ref = Watched(1_200_000)
    copy = 4 * ref.params
    leaf = 4 * max(check_footprint.leaf_sizes(1_200_000))
    key = jax.random.key(2)
    batches = [([], np.float32(0.25 * (i + 1))) for i in range(check.STEPS)]
    adam_in_place = check._adam_in_place

    def watched(count, opt):
        update = adam_in_place(count, opt)

        def around(p, g, m, v):
            out = update(p, g, m, v)
            seen.append(_live_bytes())
            return out
        return around

    monkeypatch.setattr(check, "_adam_in_place", watched)
    check.reference_trajectory(ref, {}, lambda: ref.init(key), batches,
                               _ADAM, None)
    monkeypatch.undo()
    assert len(seen) == 2 * check.STEPS
    new_peak = max(seen) - before
    assert new_peak <= 5 * copy + leaf
    assert 3.9 * copy <= new_peak <= 4.1 * copy
    del seen[:]
    old_reference_trajectory(ref, {}, ref.init(key), batches, _ADAM, None,
                             observe=lambda: seen.append(_live_bytes()))
    assert max(seen) - before >= 7.9 * copy


def test_a_kept_starting_tree_outlives_two_trajectories(capsys):
    """The ``touched`` path of ``dlrm-mlperf``: the cut tables are small,
    are read again by the control and the probe, and so are copied for
    the trajectory, which gives away what it is handed."""
    sizes = {"vocab_sizes": [50, 30, 1000, 7], "embed_dim": 8,
             "top_hidden": [16, 8]}
    rng = np.random.default_rng(3)
    batches = [([rng.integers(0, v, size=(32, 1)).astype(np.int32)
                 for v in sizes["vocab_sizes"]],
                rng.random((32, 1)).astype(np.float32)) for _ in range(3)]
    touched = dlrm_reference.touched_rows(sizes, batches)
    p0_small = jax.jit(dlrm_reference.take_rows)(
        dlrm_reference.init_params(sizes, jax.random.key(1)), touched)
    kept = jax.tree.map(np.asarray, p0_small)
    cut = [(dlrm_reference.remap(f, touched), y) for f, y in batches]
    make = check.copy_of(p0_small)
    sound = check.reference_trajectory(dlrm_reference, sizes, make, cut,
                                       _ADAM, None)
    control = check.reference_trajectory(dlrm_reference, sizes, make, cut,
                                         _ADAM, None, lower_precision=True)
    for leaf, was in zip(jax.tree.leaves(p0_small), jax.tree.leaves(kept)):
        assert not leaf.is_deleted()
        assert (np.asarray(leaf) == was).all()
    assert check.median_leaf_gap(control["change_norms"],
                                 sound["change_norms"]) > 0.005


def test_the_footprint_probe_holds_the_trajectory_to_twenty_bytes(capsys):
    """``chipbench/probes/check_footprint.py`` off the chip: it refuses to
    read a peak the CPU cannot give; its leaves are a dozen unequal ones;
    its verdict is 20 bytes a parameter (its reference drives the
    trajectory in the test of the footprint above)."""
    assert check_footprint.main(["--params", "1e6"]) == 2
    assert "allocator" in capsys.readouterr().err
    sizes = check_footprint.leaf_sizes(640_000_000)
    assert len(sizes) == 12 and len(set(sizes)) == 12
    assert abs(sum(sizes) - 640_000_000) < 12 * 1024
    assert max(sizes) == 160_000_000
    assert check_footprint.verdict(20 * 640_000_000, 640_000_000)
    assert not check_footprint.verdict(20 * 640_000_000 + 1, 640_000_000)
