"""Tests for ops/embedding.py: all lookup modes agree bit-for-bit, grads
match, and the DLRM flagship is invariant to the lookup strategy."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_shuffling_data_loader_tpu.models import dlrm
from ray_shuffling_data_loader_tpu.ops import embedding

MODES = ["take", "one_hot", "pallas"]


@pytest.fixture
def table_and_indices(rng):
    table = jnp.asarray(rng.standard_normal((96, 32)), jnp.float32)
    indices = jnp.asarray(rng.integers(0, 96, 64), jnp.int32)
    return table, indices


@pytest.mark.parametrize("mode", MODES)
def test_lookup_matches_take_f32(table_and_indices, mode):
    table, indices = table_and_indices
    want = np.asarray(table)[np.asarray(indices)]
    got = embedding.lookup(table, indices, jnp.float32, mode=mode)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("mode", MODES)
def test_lookup_matches_take_bf16(table_and_indices, mode):
    """A one-hot row selects exactly one table row, so even bf16 results
    are bit-identical to the gather."""
    table, indices = table_and_indices
    want = np.asarray(embedding.take_lookup(table, indices, jnp.bfloat16))
    got = np.asarray(embedding.lookup(table, indices, jnp.bfloat16,
                                      mode=mode))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("mode", MODES)
def test_lookup_clips_out_of_range(table_and_indices, mode):
    table, _ = table_and_indices
    indices = jnp.asarray([-5, 0, 95, 96, 1000], jnp.int32)
    got = np.asarray(embedding.lookup(table, indices, jnp.float32,
                                      mode=mode))
    want = np.asarray(table)[[0, 0, 95, 95, 95]]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("mode", MODES)
def test_lookup_grad_is_scatter_add(table_and_indices, mode):
    table, _ = table_and_indices
    # Repeated indices: the table grad must accumulate.
    indices = jnp.asarray([3, 3, 7, 0, 3], jnp.int32)

    def loss(t):
        out = embedding.lookup(t, indices, jnp.float32, mode=mode)
        return (out * out).sum()

    got = np.asarray(jax.grad(loss)(table))
    want = np.zeros_like(got)
    t = np.asarray(table)
    for i in np.asarray(indices):
        want[i] += 2 * t[i]
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_auto_mode_dispatch():
    small = jnp.zeros((16, 8), jnp.float32)
    large = jnp.zeros((embedding.ONE_HOT_MAX_VOCAB + 1, 8), jnp.float32)
    idx = jnp.zeros((4,), jnp.int32)
    # Both paths produce the right shape; dispatch itself is exercised by
    # jit-compiling each (one_hot would OOM-scale with the large table if
    # auto mis-dispatched, but correctness is shape/value-checked here).
    assert embedding.lookup(small, idx, jnp.float32).shape == (4, 8)
    assert embedding.lookup(large, idx, jnp.float32).shape == (4, 8)


def test_unknown_mode_raises():
    with pytest.raises(ValueError, match="unknown lookup mode"):
        embedding.lookup(jnp.zeros((4, 4)), jnp.zeros((2,), jnp.int32),
                         jnp.float32, mode="nope")


@pytest.mark.parametrize("mode", MODES + ["auto"])
def test_dlrm_forward_invariant_to_lookup_mode(rng, mode):
    base = dlrm.DLRMConfig(vocab_sizes=(40, 7, 3000), embed_dim=16,
                           top_hidden=(32,), compute_dtype=jnp.float32)
    params = dlrm.init(base, jax.random.key(0))
    sparse = jnp.asarray(
        np.stack([rng.integers(0, v, 8) for v in base.vocab_sizes], axis=1),
        jnp.int32)
    want = dlrm.apply(
        dlrm.DLRMConfig(**{**base.__dict__, "lookup_mode": "take"}),
        params, None, sparse)
    got = dlrm.apply(
        dlrm.DLRMConfig(**{**base.__dict__, "lookup_mode": mode}),
        params, None, sparse)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6)


def test_dlrm_train_step_with_pallas_lookup(rng):
    """End-to-end grad step through the Pallas kernel's custom VJP."""
    import optax
    cfg = dlrm.DLRMConfig(vocab_sizes=(50, 20), embed_dim=8,
                          top_hidden=(16,), compute_dtype=jnp.float32,
                          lookup_mode="pallas")
    params = dlrm.init(cfg, jax.random.key(0))
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)
    sparse = jnp.asarray(
        np.stack([rng.integers(0, v, 16) for v in cfg.vocab_sizes], axis=1),
        jnp.int32)
    labels = jnp.asarray(rng.random((16, 1)), jnp.float32)

    @jax.jit
    def step(params, opt_state):
        loss, grads = jax.value_and_grad(
            lambda p: dlrm.loss_fn(cfg, p, None, sparse, labels))(params)
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_auto_mode_dispatch_rules(monkeypatch):
    small_v = embedding.ONE_HOT_MAX_VOCAB
    large_v = embedding.ONE_HOT_MAX_VOCAB + 1
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert embedding._auto_mode(small_v, 128) == "one_hot"
    assert embedding._auto_mode(large_v, 128) == "take"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert embedding._auto_mode(small_v, 128) == "one_hot"
    assert embedding._auto_mode(large_v, 128) == "pallas"
    assert embedding._auto_mode(large_v, 32) == "take"  # unaligned rows


def _mesh_table_indices(rng):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_shuffling_data_loader_tpu.parallel import mesh as mesh_mod
    mesh = mesh_mod.make_mesh()
    table = jax.device_put(
        jnp.asarray(rng.standard_normal((640, 128)), jnp.float32),
        NamedSharding(mesh, P()))
    indices = jax.device_put(
        jnp.asarray(rng.integers(0, 640, 64), jnp.int32),
        NamedSharding(mesh, P("data")))
    return mesh, table, indices


def test_pallas_lookup_under_a_mesh_matches_take(rng):
    """Batch-sharded indices, replicated table: one gather per data shard
    under shard_map, same rows and same table gradient as XLA take."""
    mesh, table, indices = _mesh_table_indices(rng)
    weights = jnp.asarray(rng.standard_normal((64, 128)), jnp.float32)

    def loss(mode, mesh):
        def fn(table, indices):
            out = embedding.lookup(table, indices, jnp.float32, mode=mode,
                                   mesh=mesh)
            return jnp.sum(out * weights), out
        return jax.jit(jax.value_and_grad(fn, has_aux=True))

    (_, got), got_grad = loss("pallas", mesh)(table, indices)
    (_, want), want_grad = loss("take", None)(table, indices)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_allclose(np.asarray(got_grad), np.asarray(want_grad),
                               atol=1e-6)
    assert got.sharding.shard_shape(got.shape) == (64 // mesh.size, 128)


def test_pallas_lookup_lowers_for_the_chip_only_with_the_mesh(
        rng, monkeypatch):
    """GSPMD cannot partition a Mosaic kernel: lowering a multi-device
    step for the TPU fails unless the gather was told the mesh."""
    mesh, table, indices = _mesh_table_indices(rng)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def lower(mesh):
        fn = jax.jit(lambda t, i: embedding.lookup(
            t, i, jnp.float32, mode="pallas", mesh=mesh))
        return fn.trace(table, indices).lower(
            lowering_platforms=("tpu",)).as_text()

    assert lower(mesh).count("tpu_custom_call") == 1
    with pytest.raises(NotImplementedError, match="shard_map"):
        lower(None)
