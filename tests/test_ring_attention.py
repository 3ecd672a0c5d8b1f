"""Sequence-parallel attention: ring and Ulysses vs full attention.

All tests run on the 8-device virtual CPU mesh (conftest.py), with the
sequence axis sharded 8 ways. The reference implementation is the plain
full-sequence softmax attention (`_full_attention`), replicated.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_shuffling_data_loader_tpu.models import bert
from ray_shuffling_data_loader_tpu.ops import ring_attention as ra

B, H, S, D = 2, 8, 64, 16


def _seq_mesh(n=8):
    return Mesh(np.asarray(jax.devices()[:n]), ("seq",))


def _qkv(rng, dtype=jnp.float32):
    q, k, v = (jnp.asarray(rng.standard_normal((B, H, S, D)), dtype)
               for _ in range(3))
    return q, k, v


def _padding_bias(rng):
    mask = jnp.asarray(rng.integers(0, 2, (B, S)))
    return jnp.where(mask[:, None, None, :] > 0, 0.0, ra.NEG_INF).astype(
        jnp.float32)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_full_attention(rng, causal):
    q, k, v = _qkv(rng)
    mesh = _seq_mesh()
    got = ring_out = ra.ring_self_attention(q, k, v, mesh, "seq",
                                            causal=causal)
    pos = jnp.arange(S)
    bias = ra.causal_bias(pos, pos) if causal else None
    want = ra._full_attention(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert ring_out.shape == q.shape


def test_ring_with_padding_bias(rng):
    q, k, v = _qkv(rng)
    bias = _padding_bias(rng)
    mesh = _seq_mesh()
    got = ra.ring_self_attention(q, k, v, mesh, "seq", bias=bias)
    want = ra._full_attention(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ring_under_jit_with_sharded_inputs(rng):
    q, k, v = _qkv(rng)
    mesh = _seq_mesh()
    sharding = NamedSharding(mesh, P(None, None, "seq", None))
    q_s, k_s, v_s = (jax.device_put(x, sharding) for x in (q, k, v))

    @jax.jit
    def fn(q, k, v):
        return ra.ring_self_attention(q, k, v, mesh, "seq")

    got = fn(q_s, k_s, v_s)
    want = ra._full_attention(q, k, v, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert got.sharding.is_equivalent_to(sharding, got.ndim)


def test_ring_gradients_match(rng):
    q, k, v = _qkv(rng)
    mesh = _seq_mesh()

    def ring_loss(q, k, v):
        return jnp.sum(ra.ring_self_attention(q, k, v, mesh, "seq") ** 2)

    def full_loss(q, k, v):
        return jnp.sum(ra._full_attention(q, k, v, None) ** 2)

    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    g_full = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v)
    for gr, gf in zip(g_ring, g_full):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full_attention(rng, causal):
    q, k, v = _qkv(rng)
    mesh = _seq_mesh()
    got = ra.ulysses_attention(q, k, v, mesh, "seq", causal=causal)
    pos = jnp.arange(S)
    bias = ra.causal_bias(pos, pos) if causal else None
    want = ra._full_attention(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ulysses_with_padding_bias(rng):
    q, k, v = _qkv(rng)
    bias = _padding_bias(rng)
    mesh = _seq_mesh()
    got = ra.ulysses_attention(q, k, v, mesh, "seq", bias=bias)
    want = ra._full_attention(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ring_causal_gradients_match(rng):
    """Backward through the causal skip-cond and the bias rotation.

    Key 0 stays unpadded so every query has at least one causally-visible
    live key — with all visible keys masked, attention is ill-defined and
    implementations legitimately disagree on the degenerate rows.
    """
    q, k, v = _qkv(rng)
    bias = _padding_bias(rng).at[:, :, :, 0].set(0.0)
    mesh = _seq_mesh()
    pos = jnp.arange(S)
    full_bias = bias + ra.causal_bias(pos, pos)

    def ring_loss(q, k, v, bias):
        return jnp.sum(ra.ring_self_attention(
            q, k, v, mesh, "seq", bias=bias, causal=True) ** 2)

    def full_loss(q, k, v):
        return jnp.sum(ra._full_attention(q, k, v, full_bias) ** 2)

    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v, bias)
    g_full = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v)
    for gr, gf in zip(g_ring, g_full):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf),
                                   rtol=1e-4, atol=1e-4)


def test_ulysses_gradients_match(rng):
    """Reverse mode through the all_to_all pair and the bias all_gather."""
    q, k, v = _qkv(rng)
    bias = _padding_bias(rng)
    mesh = _seq_mesh()

    def ulysses_loss(q, k, v, bias):
        return jnp.sum(
            ra.ulysses_attention(q, k, v, mesh, "seq", bias=bias) ** 2)

    def full_loss(q, k, v, bias):
        return jnp.sum(ra._full_attention(q, k, v, bias) ** 2)

    g_u = jax.grad(ulysses_loss, argnums=(0, 1, 2))(q, k, v, bias)
    g_f = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v, bias)
    for gu, gf in zip(g_u, g_f):
        np.testing.assert_allclose(np.asarray(gu), np.asarray(gf),
                                   rtol=1e-4, atol=1e-4)


def test_ulysses_rejects_indivisible_heads(rng):
    q, k, v = _qkv(rng)
    mesh = _seq_mesh()
    with pytest.raises(ValueError, match="divisible"):
        ra.ulysses_attention(q[:, :3], k[:, :3], v[:, :3], mesh, "seq")


def test_ring_with_data_and_seq_axes(rng):
    """Batch sharded over 'data' AND sequence over 'seq' simultaneously."""
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4), ("data", "seq"))
    q, k, v = _qkv(rng)
    got = ra.ring_self_attention(q, k, v, mesh, "seq", batch_axis="data")
    want = ra._full_attention(q, k, v, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("strategy", ["ring", "ulysses"])
def test_bert_with_sequence_parallel_attention(rng, strategy):
    """BERT forward with sequence-parallel attention == standard forward."""
    config = bert.BertConfig(vocab_size=128, hidden_dim=32, num_layers=2,
                             num_heads=8, ffn_dim=64, max_seq_len=S,
                             compute_dtype=jnp.float32)
    params = bert.init(config, jax.random.key(0))
    token_ids = jnp.asarray(rng.integers(0, 128, (B, S)), jnp.int32)
    attention_mask = jnp.asarray(rng.integers(0, 2, (B, S)), jnp.int32)
    mesh = _seq_mesh()
    attention_fn = ra.make_attention_fn(mesh, "seq", strategy=strategy)
    want = bert.apply(config, params, token_ids, attention_mask)
    got = bert.apply(config, params, token_ids, attention_mask,
                     attention_fn=attention_fn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_bert_seq_parallel_loss_and_grads(rng):
    """Full MLM loss + grads through ring attention stay finite and close."""
    config = bert.BertConfig(vocab_size=64, hidden_dim=32, num_layers=1,
                             num_heads=8, ffn_dim=64, max_seq_len=S,
                             compute_dtype=jnp.float32)
    params = bert.init(config, jax.random.key(1))
    token_ids = jnp.asarray(rng.integers(0, 64, (B, S)), jnp.int32)
    targets = jnp.where(jnp.asarray(rng.random((B, S)) < 0.15),
                        token_ids, bert.IGNORE_ID)
    mesh = _seq_mesh()
    attention_fn = ra.make_attention_fn(mesh, "seq")

    loss_ring, grads_ring = jax.value_and_grad(
        lambda p: bert.loss_fn(config, p, token_ids, targets,
                               attention_fn=attention_fn))(params)
    loss_full, grads_full = jax.value_and_grad(
        lambda p: bert.loss_fn(config, p, token_ids, targets))(params)
    np.testing.assert_allclose(float(loss_ring), float(loss_full), rtol=1e-4)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-4),
        grads_ring, grads_full)


def test_ring_flash_matches_full_attention(rng):
    """Ring with per-hop Pallas flash kernels (interpret mode on CPU)
    equals replicated full attention."""
    q, k, v = _qkv(rng)
    mesh = _seq_mesh()
    got = ra.ring_self_attention(q, k, v, mesh, "seq", use_flash=True)
    want = ra._full_attention(q, k, v, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ring_flash_with_padding_bias(rng):
    q, k, v = _qkv(rng)
    bias = _padding_bias(rng)
    mesh = _seq_mesh()
    got = ra.ring_self_attention(q, k, v, mesh, "seq", bias=bias,
                                 use_flash=True)
    want = ra._full_attention(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ring_flash_gradients_match(rng):
    q, k, v = _qkv(rng)
    bias = _padding_bias(rng)
    mesh = _seq_mesh()

    def flash_loss(q, k, v, bias):
        return jnp.sum(ra.ring_self_attention(
            q, k, v, mesh, "seq", bias=bias, use_flash=True) ** 2)

    def full_loss(q, k, v, bias):
        return jnp.sum(ra._full_attention(q, k, v, bias) ** 2)

    g_flash = jax.grad(flash_loss, argnums=(0, 1, 2, 3))(q, k, v, bias)
    g_full = jax.grad(full_loss, argnums=(0, 1, 2, 3))(q, k, v, bias)
    for gr, gf in zip(g_flash, g_full):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf),
                                   rtol=1e-4, atol=1e-4)


def test_ring_flash_rejects_causal(rng):
    q, k, v = _qkv(rng)
    mesh = _seq_mesh()
    with pytest.raises(ValueError, match="causal"):
        ra.ring_self_attention(q, k, v, mesh, "seq", causal=True,
                               use_flash=True)


def test_ring_flash_bert_train_step(rng):
    """BERT MLM train step whose SP attention runs ring+flash end to end."""
    import optax

    mesh = _seq_mesh()
    seq_len = S
    cfg = bert.BertConfig(vocab_size=64, hidden_dim=32, num_layers=1,
                          num_heads=4, ffn_dim=64, max_seq_len=seq_len,
                          compute_dtype=jnp.float32)
    params = bert.init(cfg, jax.random.key(0))
    attention_fn = ra.make_attention_fn(mesh, "seq", use_flash=True)
    tokens = jnp.asarray(rng.integers(4, 64, (2, seq_len)), jnp.int32)
    targets = jnp.where(jnp.asarray(rng.random((2, seq_len))) < 0.15,
                        tokens, bert.IGNORE_ID).astype(jnp.int32)

    def loss_fn(p):
        return bert.loss_fn(cfg, p, tokens, targets,
                            attention_fn=attention_fn)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert np.isfinite(float(loss))
    opt = optax.adam(1e-3)
    updates, _ = opt.update(grads, opt.init(params))
    params = optax.apply_updates(params, updates)
    loss2 = loss_fn(params)
    assert np.isfinite(float(loss2))


def test_ulysses_flash_matches_full_attention(rng):
    q, k, v = _qkv(rng)
    bias = _padding_bias(rng)
    mesh = _seq_mesh()
    got = ra.ulysses_attention(q, k, v, mesh, "seq", bias=bias,
                               use_flash=True)
    want = ra._full_attention(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ulysses_flash_gradients_match(rng):
    q, k, v = _qkv(rng)
    mesh = _seq_mesh()

    def flash_loss(q, k, v):
        return jnp.sum(ra.ulysses_attention(q, k, v, mesh, "seq",
                                            use_flash=True) ** 2)

    def full_loss(q, k, v):
        return jnp.sum(ra._full_attention(q, k, v, None) ** 2)

    g_flash = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    g_full = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v)
    for gr, gf in zip(g_flash, g_full):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf),
                                   rtol=1e-4, atol=1e-4)


def test_ulysses_flash_rejects_causal(rng):
    q, k, v = _qkv(rng)
    mesh = _seq_mesh()
    with pytest.raises(ValueError, match="causal"):
        ra.ulysses_attention(q, k, v, mesh, "seq", causal=True,
                             use_flash=True)
