"""Tests for the ResNet and BERT model families (models/resnet.py, bert.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_shuffling_data_loader_tpu.models import bert, resnet
from ray_shuffling_data_loader_tpu.parallel import mesh as mesh_mod
from ray_shuffling_data_loader_tpu.parallel.trainer import SpmdTrainer


def test_resnet_forward_shape():
    cfg = resnet.resnet18_cifar()
    params = resnet.init(cfg, jax.random.key(0))
    images = jnp.ones((2, 32, 32, 3), jnp.float32)
    logits = resnet.apply(cfg, params, images)
    assert logits.shape == (2, 10)
    assert logits.dtype == jnp.float32


def test_resnet_specs_match_tree():
    cfg = resnet.resnet18_cifar()
    params = resnet.init(cfg, jax.random.key(0))
    specs = resnet.param_specs(cfg)
    jax.tree.map(lambda a, b: None, params, specs,
                 is_leaf=lambda x: isinstance(x, P))


def test_resnet_loss_and_grad_finite():
    cfg = resnet.resnet18_cifar()
    params = resnet.init(cfg, jax.random.key(0))
    images = jnp.asarray(
        np.random.default_rng(0).normal(size=(4, 32, 32, 3)),
        jnp.float32)
    labels = jnp.asarray([0, 1, 2, 3], jnp.int32)
    loss, grads = jax.value_and_grad(
        lambda p: resnet.loss_fn(cfg, p, images, labels))(params)
    assert np.isfinite(float(loss))
    flat = jax.tree.leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in flat)


def test_resnet_learns_tiny():
    cfg = resnet.resnet18_cifar(num_classes=2)
    params = resnet.init(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    # Class 0 = dark images, class 1 = bright images.
    images = np.concatenate([
        rng.normal(-1, 0.1, (8, 16, 16, 3)),
        rng.normal(1, 0.1, (8, 16, 16, 3))]).astype(np.float32)
    labels = np.array([0] * 8 + [1] * 8, np.int32)
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)
    step = jax.jit(lambda p, o: _step(cfg, p, o, opt, images, labels))
    first = None
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state)
        if first is None:
            first = float(loss)
    assert float(loss) < first


def _step(cfg, params, opt_state, opt, images, labels):
    loss, grads = jax.value_and_grad(
        lambda p: resnet.loss_fn(cfg, p, images, labels))(params)
    updates, opt_state = opt.update(grads, opt_state, params)
    params = optax.apply_updates(params, updates)
    return params, opt_state, loss


def test_resnet50_config():
    cfg = resnet.resnet50()
    assert cfg.stage_sizes == (3, 4, 6, 3)
    assert cfg.num_classes == 1000


def test_bert_forward_shape_and_mask():
    cfg = bert.bert_tiny()
    params = bert.init(cfg, jax.random.key(0))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16)),
        jnp.int32)
    logits = bert.apply(cfg, params, tokens)
    assert logits.shape == (2, 16, cfg.vocab_size)
    mask = jnp.ones((2, 16), jnp.int32).at[:, 8:].set(0)
    logits_masked = bert.apply(cfg, params, tokens, mask)
    assert logits_masked.shape == (2, 16, cfg.vocab_size)
    assert not np.allclose(np.asarray(logits), np.asarray(logits_masked))


def test_bert_mlm_loss_ignores_unmasked():
    cfg = bert.bert_tiny()
    params = bert.init(cfg, jax.random.key(0))
    tokens = jnp.zeros((2, 8), jnp.int32)
    # Only one position per row is a target.
    targets = jnp.full((2, 8), bert.IGNORE_ID, jnp.int32)
    targets = targets.at[:, 3].set(7)
    loss = bert.loss_fn(cfg, params, tokens, targets)
    assert np.isfinite(float(loss))
    # All-ignored targets: loss must not NaN (count clamps to 1).
    loss0 = bert.loss_fn(cfg, params, tokens,
                         jnp.full((2, 8), bert.IGNORE_ID, jnp.int32))
    assert float(loss0) == 0.0


def test_bert_specs_match_tree():
    cfg = bert.bert_tiny()
    params = bert.init(cfg, jax.random.key(0))
    specs = bert.param_specs(cfg)
    jax.tree.map(lambda a, b: None, params, specs,
                 is_leaf=lambda x: isinstance(x, P))


def test_bert_tp_train_step_on_mesh():
    mesh = mesh_mod.make_mesh(model_parallel=2)
    cfg = bert.bert_tiny()
    params = bert.init(cfg, jax.random.key(0))
    trainer = SpmdTrainer(
        mesh,
        lambda p, t, y: bert.loss_fn(cfg, p, t, y),
        params, optax.adam(1e-3), param_specs=bert.param_specs(cfg))
    qkv = trainer.params["layer_0"]["qkv_w"]
    assert qkv.sharding.is_equivalent_to(
        NamedSharding(mesh, P(None, "model")), qkv.ndim)
    rng = np.random.default_rng(0)
    tokens = jax.device_put(
        jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)), jnp.int32),
        mesh_mod.batch_sharding(mesh))
    targets = jnp.full((8, 16), bert.IGNORE_ID, jnp.int32).at[:, 2].set(5)
    targets = jax.device_put(targets, mesh_mod.batch_sharding(mesh))
    loss = trainer.train_step(tokens, targets)
    assert np.isfinite(float(loss))


def test_bert_base_config():
    cfg = bert.bert_base()
    assert cfg.hidden_dim == 768 and cfg.num_layers == 12
    assert cfg.head_dim == 64


def _assert_grads_match(g0, g1):
    """remat re-runs the identical XLA program, so its grads equal the
    exact grads bit for bit."""
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bert_remat_matches_exact_grads():
    """remat=True changes memory behavior only: loss and grads are
    identical to the non-remat graph."""
    import optax  # noqa: F401 - parity with sibling tests
    rng = np.random.default_rng(0)
    base = dict(vocab_size=64, hidden_dim=32, num_layers=2, num_heads=4,
                ffn_dim=64, max_seq_len=16, compute_dtype=jnp.float32)
    cfg = bert.BertConfig(**base)
    cfg_remat = bert.BertConfig(**base, remat=True)
    params = bert.init(cfg, jax.random.key(0))
    tokens = jnp.asarray(rng.integers(4, 64, (2, 16)), jnp.int32)
    targets = jnp.where(jnp.asarray(rng.random((2, 16))) < 0.2, tokens,
                        bert.IGNORE_ID).astype(jnp.int32)

    def loss(cfg, p):
        return bert.loss_fn(cfg, p, tokens, targets)

    l0, g0 = jax.value_and_grad(lambda p: loss(cfg, p))(params)
    l1, g1 = jax.value_and_grad(lambda p: loss(cfg_remat, p))(params)
    assert float(l0) == float(l1)
    _assert_grads_match(g0, g1)


def test_resnet_remat_matches_exact_grads():
    rng = np.random.default_rng(0)
    base = dict(stage_sizes=(1, 1), width=8, num_classes=2, num_groups=4,
                compute_dtype=jnp.float32)
    cfg = resnet.ResNetConfig(**base)
    cfg_remat = resnet.ResNetConfig(**base, remat=True)
    params = resnet.init(cfg, jax.random.key(0))
    images = jnp.asarray(rng.random((2, 16, 16, 3)), jnp.float32)
    labels = jnp.asarray([0, 1], jnp.int32)

    def loss(cfg, p):
        return resnet.loss_fn(cfg, p, images, labels)

    l0, g0 = jax.value_and_grad(lambda p: loss(cfg, p))(params)
    l1, g1 = jax.value_and_grad(lambda p: loss(cfg_remat, p))(params)
    assert float(l0) == float(l1)
    _assert_grads_match(g0, g1)
