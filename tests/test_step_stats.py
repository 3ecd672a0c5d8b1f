"""The train step's own counters: what the device computes at run time
leaves the jitted step as an output, waits in ``utils/tracing``'s ring
until it is ready, and is folded into the registry and the flight recorder
without the step path ever waiting for the device. The expert walk
(``ops/moe.py``) is the first to record: held pairs, tiles, rounds."""

import functools
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_shuffling_data_loader_tpu.models import mellum
from ray_shuffling_data_loader_tpu.ops import moe
from ray_shuffling_data_loader_tpu.parallel import mesh as mesh_mod
from ray_shuffling_data_loader_tpu.parallel import trainer as trainer_mod
from ray_shuffling_data_loader_tpu.runtime import (metric_names, metrics,
                                                   telemetry)
from ray_shuffling_data_loader_tpu.utils import compile_cache, tracing

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_HIDDEN = _EXPERTS = 8
_WIDTH, _TOP_K, _TILE = 4, 2, 8
_FIELDS = telemetry.STEP_STAT_FIELDS["moe_walk"]


@pytest.fixture(autouse=True)
def empty_ring():
    tracing.reset_step_stats()
    yield
    tracing.reset_step_stats()


# -- the walk's counts -------------------------------------------------------

def _routed(picks):
    """Tokens and a router under which token ``t`` picks exactly
    ``picks[t]``: its features are its two experts' one-hots, the first
    the larger."""
    x = np.zeros((len(picks), _HIDDEN), np.float32)
    for t, (a, b) in enumerate(picks):
        x[t, a], x[t, b] = 2.0, 1.0
    return jnp.asarray(x), 4.0 * jnp.eye(_HIDDEN, _EXPERTS)


def _weights(count):
    ks = jax.random.split(jax.random.key(3), 3)
    return (jax.random.normal(ks[0], (count, _HIDDEN, _WIDTH)),
            jax.random.normal(ks[1], (count, _HIDDEN, _WIDTH)),
            jax.random.normal(ks[2], (count, _WIDTH, _HIDDEN)))


#: 30 tokens; experts 2, 3 and 4 are held. Expert 2 is picked by 19 tokens
#: (three tiles of 8), 3 by 8 (one), 4 by 1 (one).
_PICKS = ([(2, 3)] * 4 + [(2, 4)] + [(2, 0)] * 14 + [(5, 3)] * 4
          + [(6, 7)] * 7)
_HELD = (2, 3)
_WANT = {"pairs": 60, "pairs_held": 28, "tiles": 5,
         "fullest_expert_rows": 19}


def _walk_of(picks, held=_HELD, tile=_TILE):
    x, router = _routed(picks)
    out, walk = moe.moe_counted(x, router, *_weights(held[1]), held, _TOP_K,
                                tile)
    return out, dict(zip(_FIELDS, np.asarray(walk).tolist()))


def test_a_known_routing_gives_its_counts():
    """Pairs, held pairs, tiles, rounds and the fullest expert of a
    routing written down by hand."""
    x, router = _routed(_PICKS)
    assert np.asarray(moe.route(x @ router, _TOP_K)[0]).tolist() == [
        list(p) for p in _PICKS]
    _, walk = _walk_of(_PICKS)
    per_round = moe.round_rows(len(_PICKS), _TOP_K, _HELD[1], _EXPERTS,
                               _TILE) // _TILE
    assert per_round == 7
    assert walk == dict(_WANT, rounds=1)
    assert len(_FIELDS) == len(walk) == 5


@pytest.mark.parametrize("picks,want", [
    ([(0, 1)] * 30, dict(pairs_held=0, tiles=0, rounds=0,
                         fullest_expert_rows=0)),
    # every pick held: 60 pairs in 8 tiles, more than one round's 7
    ([(2, 3)] * 30, dict(pairs_held=60, tiles=8, rounds=2,
                         fullest_expert_rows=30)),
], ids=["none_held", "a_second_round"])
def test_the_counts_at_the_edges(picks, want):
    _, walk = _walk_of(picks)
    assert walk == dict(want, pairs=60)


def test_the_counts_cross_a_checkpoint_and_a_gradient_once():
    """Through ``jax.value_and_grad`` of a checkpointed layer the counts
    come out as they do forward alone, and the program that results
    computes them once: the half made again in the backward makes none."""
    x, router = _routed(_PICKS)
    weights = _weights(_HELD[1])

    def half(x, router):
        out, walk = moe.moe_counted(x, router, *weights, _HELD, _TOP_K,
                                    _TILE)
        tracing.step_stat("moe_walk", walk, layer=7)
        return x + out

    def loss(x, router):
        y = tracing.step_stats_of(jax.checkpoint(
            tracing.with_step_stats(half)))(x, router)
        return jnp.sum(y * y)

    step = jax.jit(jax.value_and_grad(tracing.with_step_stats(loss),
                                      argnums=(0, 1), has_aux=True))
    (value, stats), grads = step(x, router)
    (key, walk), = stats.items()
    assert key == ("moe_walk", (("layer", "7"),))
    assert dict(zip(_FIELDS, np.asarray(walk).tolist())) == dict(_WANT,
                                                                 rounds=1)
    plain = jax.jit(jax.value_and_grad(
        lambda x, r: jnp.sum(jnp.square(x + moe.moe(
            x, r, *weights, _HELD, _TOP_K, _TILE))), argnums=(0, 1)))
    want_value, want_grads = plain(x, router)
    assert float(value) == float(want_value)
    for got, want in zip(grads, want_grads):
        np.testing.assert_array_equal(got, want)
    # the vector of five is assembled once in the whole program
    text = step.lower(x, router).as_text()
    assert len(re.findall(r"stablehlo\.concatenate.*tensor<5xi32>",
                          text)) == 1


def test_counting_changes_no_number_of_the_layer():
    """``moe`` is ``moe_counted`` without the counts, bit for bit, output
    and gradients, eager and under one ``jit``."""
    key = jax.random.key(5)
    x = jnp.abs(jax.random.normal(key, (24, _HIDDEN)))
    router = jax.random.normal(jax.random.fold_in(key, 1),
                               (_HIDDEN, _EXPERTS))
    weights = _weights(_HELD[1])
    mix = jax.random.normal(jax.random.fold_in(key, 2), x.shape)

    def plain(x, router, weights):
        return jnp.sum(mix * moe.moe(x, router, *weights, _HELD, _TOP_K,
                                     _TILE, 2.5))

    def counted(x, router, weights):
        out, walk = moe.moe_counted(x, router, *weights, _HELD, _TOP_K,
                                    _TILE, 2.5)
        return jnp.sum(mix * out), walk

    for wrap in (lambda f: f, jax.jit):
        want = wrap(jax.value_and_grad(plain, (0, 1, 2)))(x, router, weights)
        (value, walk), grads = wrap(jax.value_and_grad(
            counted, (0, 1, 2), has_aux=True))(x, router, weights)
        assert walk.dtype == jnp.int32 and walk.shape == (5,)
        for got, exp in zip(jax.tree.leaves((value, grads)),
                            jax.tree.leaves(want)):
            np.testing.assert_array_equal(got, exp)


# -- the channel through the train step --------------------------------------

def _parents_train_step(loss_fn, optimizer):
    """``make_train_step`` as it stood before the channel."""

    def train_step(params, opt_state, *batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, *batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return train_step


def _quadratic(params, x):
    return jnp.sum((x @ params["w"] + params["b"]) ** 2)


def _counting(params, x):
    tracing.step_stat("moe_walk", jnp.arange(5, dtype=jnp.int32)
                      + jnp.int32(x.shape[0]), layer=0)
    return _quadratic(params, x)


def test_a_loss_that_records_nothing_keeps_the_three_output_step():
    """The step of a model with no stat is the program it was, text for
    text; one that records some returns them as a fourth output."""
    opt = optax.adam(1e-3)
    params = {"w": jnp.ones((4, 3)), "b": jnp.zeros((3,))}
    state = opt.init(params)
    x = jnp.ones((5, 4))

    def text(make, loss):
        def train_step(*args):      # both under one name in the text
            return make(loss, opt)(*args)
        return jax.jit(train_step).lower(params, state, x).as_text()

    assert text(trainer_mod.make_train_step, _quadratic) == text(
        _parents_train_step, _quadratic)
    out = jax.jit(trainer_mod.make_train_step(_quadratic, opt))(
        params, state, x)
    assert len(out) == 3
    out = jax.jit(trainer_mod.make_train_step(_counting, opt))(
        params, state, x)
    assert len(out) == 4
    assert list(out[3]) == [("moe_walk", (("layer", "0"),))]
    np.testing.assert_array_equal(out[3]["moe_walk", (("layer", "0"),)],
                                  np.arange(5) + 5)
    # the scope is metadata: it names the optimizer's operations and
    # changes none
    named = jax.jit(trainer_mod.make_train_step(_quadratic, opt)).lower(
        params, state, x).as_text(debug_info=True)
    assert trainer_mod.OPTIMIZER_SCOPE in named


def test_an_unknown_stat_or_one_recorded_twice_is_refused():
    with pytest.raises(ValueError, match="unknown step stat"):
        tracing.step_stat("moe_wlak", 1)

    def twice():
        tracing.step_stat("moe_walk", 1, layer=0)
        tracing.step_stat("moe_walk", 2, layer=0)

    with pytest.raises(ValueError, match="recorded twice"):
        tracing.with_step_stats(twice)()
    # outside a collector a stat is dropped, not kept for a later step
    tracing.step_stat("moe_walk", 1, layer=0)
    assert tracing.with_step_stats(lambda: 7)() == (7, {})


class _Late:
    """A device value that is not ready until told so."""

    def __init__(self, values):
        self.values, self.ready, self.asked = values, False, 0

    def is_ready(self):
        self.asked += 1
        return self.ready

    def __array__(self, dtype=None, copy=None):
        assert self.ready, "read before it was ready: the fold waited"
        return np.asarray(self.values, dtype)


def _walk_stats(tiles, layer=0, late=False):
    values = [64, 16, tiles, 1, 9]
    return {("moe_walk", (("layer", str(layer)),)):
            _Late(values) if late else np.asarray(values, np.int32)}


def test_the_step_path_never_waits_for_the_device():
    """Entries whose arrays are not ready stay in the ring, in order,
    behind the oldest of them; a later call folds them once they are, and
    ``fold_step_stats(wait=True)`` folds what is left."""
    first, second = _walk_stats(3, late=True), _walk_stats(4, late=True)
    tracing.keep_step_stats(0, first)
    tracing.keep_step_stats(1, second)
    tracing.keep_step_stats(2, _walk_stats(5))      # ready, behind two late
    assert tracing.step_stats() == []
    (late0,), (late1,) = first.values(), second.values()
    assert late0.asked >= 1 and late1.asked == 0    # the oldest decides
    late0.ready = True
    tracing.keep_step_stats(3, _walk_stats(6))
    assert [e["step"] for e in tracing.step_stats()] == [0]
    assert tracing.fold_step_stats() == 0
    late1.ready = True                      # what wait=True would wait for
    assert tracing.fold_step_stats(wait=True) == 3
    entries = tracing.step_stats()
    assert [e["step"] for e in entries] == [0, 1, 2, 3]
    assert [e["stats"]["moe_walk"][0]["tiles"] for e in entries] == [
        3, 4, 5, 6]
    assert [e["step"] for e in tracing.step_stats(1, 2)] == [1, 2]
    assert all(e["fold_s"] >= 0 for e in entries)


def _registry_totals():
    def value(name, **labels):
        held = metrics.get(name, labels or None)
        return 0 if held is None else held.value

    tiles = metrics.get("rsdl_moe_tiles_per_step")
    return {"folded": value("rsdl_step_stats_folded_total"),
            "pairs": value("rsdl_moe_pairs_total"),
            "held": value("rsdl_moe_pairs_held_total", layer="0")
            + value("rsdl_moe_pairs_held_total", layer="1"),
            "tiles": value("rsdl_moe_tiles_total", layer="0")
            + value("rsdl_moe_tiles_total", layer="1"),
            "rounds": value("rsdl_moe_rounds_total", layer="0")
            + value("rsdl_moe_rounds_total", layer="1"),
            "steps": 0 if tiles is None else tiles.count,
            "step_tiles": 0 if tiles is None else tiles.sum}


def test_the_ring_is_bounded_and_the_registry_holds_what_was_folded():
    """The ring keeps the newest ``STEP_STATS_KEPT`` steps; the
    registry's totals grow by the sum of every folded entry, kept or not,
    and each fold leaves a ``step_stats`` event with the step's number."""
    before = _registry_totals()
    extra = 40
    for step in range(tracing.STEP_STATS_KEPT + extra):
        tracing.keep_step_stats(
            step, {**_walk_stats(step % 7, layer=0),
                   **_walk_stats(3, layer=1)})
    entries = tracing.step_stats()
    assert len(entries) == tracing.STEP_STATS_KEPT
    assert entries[0]["step"] == extra
    assert entries[-1]["step"] == tracing.STEP_STATS_KEPT + extra - 1
    after = _registry_totals()
    grown = {k: after[k] - before[k] for k in after}
    steps = tracing.STEP_STATS_KEPT + extra
    tiles = sum(s % 7 + 3 for s in range(steps))
    assert grown == {"folded": steps, "pairs": 2 * 64 * steps,
                     "held": 2 * 16 * steps, "tiles": tiles,
                     "rounds": 2 * steps, "steps": steps,
                     "step_tiles": tiles}
    kept = sum(row["tiles"] for e in entries
               for row in e["stats"]["moe_walk"])
    assert kept == sum(s % 7 + 3 for s in range(extra, steps))
    assert metrics.get("rsdl_moe_tiles_last_step").value == (steps - 1) % 7 + 3
    assert metrics.get("rsdl_moe_fullest_expert_rows",
                       {"layer": "1"}).value == 9
    last = [e for e in telemetry.recorder().events()
            if e["kind"] == "step_stats"][-1]
    assert last["step"] == steps - 1
    assert last["stats"]["moe_walk"][1] == {
        "layer": "1", "pairs": 64, "pairs_held": 16, "tiles": 3,
        "rounds": 1, "fullest_expert_rows": 9}


@pytest.mark.parametrize("builder,sparse_layers", [
    (mellum.mellum_tiny, ["0", "1", "2", "3"]),
    (mellum.laguna_tiny, ["1", "2", "3", "4"]),     # layer 0 is dense
], ids=["mellum_tiny", "laguna_tiny"])
def test_the_decoders_step_reports_every_sparse_layers_walk(builder,
                                                             sparse_layers):
    """Through ``SpmdTrainer``: the loss comes back alone, the jitted step
    has a fourth output, and each step's entry holds one walk a sparse
    layer whose counts hold together."""
    cfg = builder()
    params = mellum.init(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0,
                                cfg.vocab_size)
    trainer = trainer_mod.SpmdTrainer(
        mesh_mod.make_mesh(devices=jax.devices()[:1]),
        functools.partial(mellum.loss_fn, cfg), params, optax.adam(1e-3))
    losses = [trainer.train_step(tokens) for _ in range(3)]
    assert all(loss.shape == () for loss in losses)
    assert len(jax.eval_shape(trainer.step_fn, trainer.params,
                              trainer.opt_state, tokens)) == 4
    trainer.block_until_ready()
    entries = tracing.step_stats()
    assert [e["step"] for e in entries] == [0, 1, 2]
    for entry in entries:
        walks = entry["stats"]["moe_walk"]
        assert [w["layer"] for w in walks] == sparse_layers
        for w in walks:
            assert w["pairs"] == 2 * 32 * cfg.top_k
            assert 0 < w["pairs_held"] < w["pairs"]
            assert w["fullest_expert_rows"] <= w["pairs_held"]
            assert w["tiles"] >= 1 and w["rounds"] == 1


# -- names, the operator's line, the cache's key -----------------------------

_NEW_NAMES = {
    "rsdl_step_stats_folded_total": ("counter", ()),
    "rsdl_moe_pairs_total": ("counter", ()),
    "rsdl_moe_pairs_held_total": ("counter", ("layer",)),
    "rsdl_moe_tiles_total": ("counter", ("layer",)),
    "rsdl_moe_rounds_total": ("counter", ("layer",)),
    "rsdl_moe_fullest_expert_rows": ("gauge", ("layer",)),
    "rsdl_moe_tiles_per_step": ("histogram", ()),
    "rsdl_moe_tiles_last_step": ("gauge", ()),
}


def test_every_new_name_is_in_the_catalog():
    for name, entry in _NEW_NAMES.items():
        assert metric_names.METRIC_NAMES[name] == entry
    tracing.keep_step_stats(0, _walk_stats(3))
    for name in _NEW_NAMES:
        assert metrics.get(name) is not None, name
    assert "step_stats" not in telemetry.SPAN_NAMES     # an event, no span


def _rsdl_top():
    spec = importlib.util.spec_from_file_location(
        "rsdl_top", os.path.join(_REPO, "tools", "rsdl_top.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_rsdl_top_renders_the_step_line_from_the_registry():
    top = _rsdl_top()
    registry = metrics.Registry()
    assert top.render_step(metrics.parse_exposition(registry.render())) == []
    registry.gauge("rsdl_moe_experts_held").set(16)
    registry.gauge("rsdl_moe_experts_routed").set(64)
    registry.gauge("rsdl_moe_tile_rows").set(1152)
    registry.counter("rsdl_step_stats_folded_total").inc(2)
    registry.counter("rsdl_moe_pairs_total").inc(4 * 1000)
    for layer, held in (("0", 300), ("1", 250)):
        registry.counter("rsdl_moe_pairs_held_total", layer=layer).inc(held)
        registry.counter("rsdl_moe_tiles_total", layer=layer).inc(
            held // 20)
        registry.counter("rsdl_moe_rounds_total", layer=layer).inc(3)
        registry.gauge("rsdl_moe_fullest_expert_rows", layer=layer).set(
            held // 2)
    tiles = registry.histogram("rsdl_moe_tiles_per_step", buckets=(8, 64))
    tiles.observe(10)
    tiles.observe(20)
    registry.gauge("rsdl_moe_tiles_last_step").set(20)
    line, = top.render_step(metrics.parse_exposition(registry.render()))
    assert line == ("step: held pairs 13.8% (even routing 25.0%)   "
                    "tiles/step last 20 mean 15.0 (by layer 7.5/6.0)   "
                    "rounds/walk 1.50   fullest expert 150 rows (tile "
                    "1152)   over 2 of 2 folded steps")
    assert line in top.render(metrics.parse_exposition(registry.render()))


def test_the_compile_cache_keys_on_the_scopes_names(monkeypatch):
    """A program read by scope: a change that only names a scope must not
    load the executable compiled before it, wherever the cache lives."""
    option = compile_cache.NAMES_IN_KEY_OPTION
    for placed in ("/some/dir", None):
        if placed:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        was = {name: getattr(jax.config, name) for name in (
            option, compile_cache.CACHE_DIR_OPTION,
            "jax_persistent_cache_min_compile_time_secs")}
        try:
            jax.config.update(option, False)
            compile_cache.enable_compile_cache()
            assert getattr(jax.config, option) is True
        finally:
            for name, value in was.items():
                jax.config.update(name, value)
