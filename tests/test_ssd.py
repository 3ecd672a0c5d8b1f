"""The chunked state-space scan (ops/ssd.py) against the recurrence it
computes, written a position at a time, in float32 on the CPU: output and
every gradient, at several chunk lengths; what the carry between chunks is
worth at the benchmark configuration's init and at the published one; the
convolution and the gated norm beside it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_shuffling_data_loader_tpu.ops import ssd

_B, _S, _H, _P, _N = 2, 32, 4, 8, 16
_NAMES = ("x", "dt", "a_log", "b", "c", "d")


def _recurrence(x, dt, a_log, b, c, d):
    """h_t = exp(dt_t A) h_(t-1) + dt_t x_t (x) B_t; y_t = h_t . C_t +
    D x_t, one row at a time and one position at a time."""
    a = -jnp.exp(a_log)

    def row(x, dt, b, c):
        def step(h, at):
            x_t, dt_t, b_t, c_t = at
            h = (jnp.exp(dt_t * a)[:, None, None] * h
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t)
            return h, jnp.sum(h * c_t, axis=-1) + d[:, None] * x_t

        return jax.lax.scan(step, jnp.zeros(x.shape[1:] + b.shape[-1:]),
                            (x, dt, b, c))[1]

    return jax.vmap(row)(x, dt, b, c)


def _operands(init: str, seed: int = 0, seq: int = _S):
    """Random operands; ``dt`` and ``A`` as the configuration's ``assumed``
    init draws them (``dt`` log-uniform in [0.001, 0.1], ``A`` uniform in
    [1, 16]) or as the published model's (``dt_bias`` 1, ``A`` from 1 to 64
    over its 64 heads, 16 a head: four heads here)."""
    keys = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(keys[0], (_B, seq, _H, _P))
    b = jax.random.normal(keys[1], (_B, seq, _N))
    c = jax.random.normal(keys[2], (_B, seq, _N))
    d = 1.0 + 0.1 * jax.random.normal(keys[3], (_H,))
    if init == "assumed":
        dt = jnp.exp(jax.random.uniform(
            keys[4], (_B, seq, _H), minval=jnp.log(0.001),
            maxval=jnp.log(0.1)))
        a_log = jnp.log(jax.random.uniform(keys[5], (_H,), minval=1.0,
                                           maxval=16.0))
    else:
        dt = jax.nn.softplus(1.0 + 0.1 * jax.random.normal(
            keys[4], (_B, seq, _H)))
        a_log = jnp.log(jnp.linspace(1.0, 16.0 * _H, _H))
    return x, dt, a_log, b, c, d


def _weighted(fn, weights):
    """A scalar of ``fn``'s output under fixed random weights, so that
    every output position's gradient is exercised."""
    return lambda *operands: jnp.sum(fn(*operands) * weights)


@pytest.mark.parametrize("chunk", [4, 8, _S])
def test_output_and_every_gradient_match_the_recurrence(chunk):
    operands = _operands("assumed")
    want = _recurrence(*operands)
    got, stats = ssd.ssd_counted(*operands, chunk)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert stats.shape == (2,) and 0.0 < float(stats[0]) <= 1.0
    weights = jax.random.normal(jax.random.key(9), want.shape)
    want_grads = jax.grad(_weighted(_recurrence, weights),
                          argnums=range(6))(*operands)
    got_grads = jax.grad(
        _weighted(lambda *ops: ssd.ssd(*ops, chunk), weights),
        argnums=range(6))(*operands)
    for name, got_g, want_g in zip(_NAMES, got_grads, want_grads):
        scale = float(jnp.max(jnp.abs(want_g)))
        np.testing.assert_allclose(got_g, want_g, rtol=2e-4,
                                   atol=2e-5 * max(scale, 1.0), err_msg=name)


def test_the_chunk_length_does_not_change_the_answer():
    operands = _operands("assumed", seed=1)
    whole = ssd.ssd(*operands, _S)
    for chunk in (4, 8):
        np.testing.assert_allclose(ssd.ssd(*operands, chunk), whole,
                                   rtol=2e-5, atol=2e-5)
    # the whole sequence in one chunk starts from nothing and carries
    # nothing
    assert float(ssd.ssd_counted(*operands, _S)[1][1]) == 0.0
    assert float(ssd.ssd_counted(*operands, 8)[1][1]) > 0.0


def _without_carry(monkeypatch):
    monkeypatch.setattr(ssd, "_carries",
                        lambda states, end_decay: jnp.zeros_like(states))
    # the jitted forward was traced with the carry: a function of its own
    # is traced again
    plain = ssd._ssd_fwd.__wrapped__
    monkeypatch.setattr(ssd, "_ssd_fwd", jax.jit(
        lambda *operands: plain(*operands), static_argnums=(6,)))


@pytest.mark.parametrize("init,caught", [("assumed", True),
                                         ("published", False)])
def test_a_scan_without_its_carry_is_caught_at_the_assumed_init(
        init, caught, monkeypatch):
    """The control: the state each chunk starts from zeroed. At the
    configuration's init (slow heads: ``dt A`` from 0.001 a position) the
    output is off by a tenth of its norm, at nearly half of its values; at
    the published ``dt_bias = 1, A = 1..64`` a head keeps exp(-1.3 A) of its
    state a position, so what a chunk hands on is gone within a few
    positions of the next (1.4 % of the output's norm here, in under 2 % of
    its values, all of the one head with A = 1) and a comparison of norms
    has little to see the fault by: why the configuration does not use
    it."""
    operands = _operands(init, seed=2, seq=256)
    want = _recurrence(*operands)
    chunk = 64
    np.testing.assert_allclose(ssd.ssd(*operands, chunk), want, rtol=1e-4,
                               atol=1e-4)
    _without_carry(monkeypatch)
    got = ssd.ssd(*operands, chunk)
    off = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    values_off = float(jnp.mean(jnp.abs(got - want) > 1e-3))
    if caught:
        assert off > 0.05 and values_off > 0.3, (off, values_off)
    else:
        assert off < 0.02 and values_off < 0.02, (off, values_off)


def test_a_sequence_of_part_chunks_is_refused():
    operands = _operands("assumed")
    with pytest.raises(ValueError, match="not whole chunks of 5"):
        ssd.ssd(*operands, 5)
    with pytest.raises(ValueError, match="not whole chunks of 64"):
        jax.grad(lambda x: jnp.sum(ssd.ssd(x, *operands[1:], 64)))(
            operands[0])


def test_the_statistics_are_the_decay_and_the_largest_carry():
    x, dt, a_log, b, c, d = _operands("assumed", seed=3)
    chunk = 8
    _, stats = ssd.ssd_counted(x, dt, a_log, b, c, d, chunk)
    a = -np.exp(np.asarray(a_log))
    decay = np.exp((np.asarray(dt) * a).reshape(_B, _S // chunk, chunk,
                                                _H).sum(axis=2))
    np.testing.assert_allclose(float(stats[0]), decay.mean(), rtol=1e-5)
    # the states the recurrence holds where a chunk ends, but the last
    h = np.zeros((_B, _H, _P, _N))
    largest = 0.0
    for t in range(_S - chunk):
        step = np.asarray(dt)[:, t]                            # (B, H)
        h = (np.exp(step * a)[:, :, None, None] * h
             + (step[:, :, None] * np.asarray(x)[:, t])[..., None]
             * np.asarray(b)[:, t][:, None, None, :])
        if (t + 1) % chunk == 0:
            largest = max(largest, np.abs(h).max())
    np.testing.assert_allclose(float(stats[1]), largest, rtol=1e-4)
    # no gradient flows from the statistics
    grad = jax.grad(lambda x: jnp.sum(
        ssd.ssd_counted(x, dt, a_log, b, c, d, chunk)[1]))(x)
    assert float(jnp.max(jnp.abs(grad))) == 0.0


def test_bfloat16_operands_accumulate_in_float32():
    operands = _operands("assumed", seed=4)
    want = _recurrence(*operands)
    x, dt, a_log, b, c, d = operands
    got = ssd.ssd(x.astype(jnp.bfloat16), dt, a_log, b.astype(jnp.bfloat16),
                  c.astype(jnp.bfloat16), d, 8)
    assert got.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) < 0.05 * \
        float(jnp.max(jnp.abs(want)))


def test_the_convolution_is_causal_depthwise_and_silu():
    keys = jax.random.split(jax.random.key(5), 3)
    x = jax.random.normal(keys[0], (2, 16, 6))
    weight = jax.random.normal(keys[1], (4, 6))
    bias = jax.random.normal(keys[2], (6,))
    got = ssd.causal_conv_silu(x, weight, bias)
    xs, w = np.asarray(x), np.asarray(weight)
    want = np.zeros_like(xs)
    for t in range(16):
        for k in range(4):
            if t - 3 + k >= 0:
                want[:, t] += w[k] * xs[:, t - 3 + k]
    want = want + np.asarray(bias)
    np.testing.assert_allclose(got, want / (1 + np.exp(-want)), rtol=1e-5,
                               atol=1e-6)
    # position t reads nothing after t
    later = x.at[:, 9:].set(0.0)
    np.testing.assert_array_equal(
        ssd.causal_conv_silu(later, weight, bias)[:, :9], got[:, :9])


def test_the_gated_norm_runs_over_the_whole_width():
    keys = jax.random.split(jax.random.key(6), 3)
    y = jax.random.normal(keys[0], (2, 5, 12))
    z = jax.random.normal(keys[1], (2, 5, 12))
    scale = 1.0 + 0.1 * jax.random.normal(keys[2], (12,))
    gated = np.asarray(y) * np.asarray(z) / (1 + np.exp(-np.asarray(z)))
    want = gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5) \
        * np.asarray(scale)
    np.testing.assert_allclose(ssd.gated_rms_norm(y, z, scale, 1e-5), want,
                               rtol=1e-5, atol=1e-6)
