"""Mamba-1's selective scan (ops/selective_scan.py) against the recurrence
it computes, written a position at a time, in float32 on the CPU: output
and every gradient, at several chunk lengths, by XLA's loops and
(channels of whole tiles, interpreted) by the kernels that keep the state
in VMEM; the carry between chunks replaced by zeros as the control that
must fail; which shapes the kernels take; the passes beside the scan."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_shuffling_data_loader_tpu.ops import selective_scan as sscan
from ray_shuffling_data_loader_tpu.ops import ssd

_NAMES = ("u", "dt", "a_log", "b", "c", "d")
_LOOPS, _IN_VMEM = "loops", "in_vmem"
#: (B, S, C, N) by path: the kernels take channels of whole (8, 128) tiles.
_SHAPES = {_LOOPS: (2, 32, 24, 4), _IN_VMEM: (2, 32, 1024, 4)}


def _recurrence(u, dt, a_log, b, c, d):
    """h_t = exp(dt_t A) h_(t-1) + dt_t u_t B_t; y_t = h_t . C_t + D u_t,
    one row at a time and one position at a time."""
    a = -jnp.exp(a_log)

    def row(u, dt, b, c):
        def step(h, at):
            u_t, dt_t, b_t, c_t = at
            h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * u_t)[:, None] * b_t
            return h, jnp.sum(h * c_t, axis=-1) + d * u_t

        return jax.lax.scan(step, jnp.zeros(a.shape), (u, dt, b, c))[1]

    return jax.vmap(row)(u, dt, b, c)


def _operands(path: str, seed: int = 0):
    """Random operands; ``dt`` and ``A`` as the configuration's ``assumed``
    init draws them: ``dt`` log-uniform in [0.001, 0.1], ``A[c, n] = n +
    1``."""
    batch, seq, channels, state = _SHAPES[path]
    keys = jax.random.split(jax.random.key(seed), 6)
    u = jax.random.normal(keys[0], (batch, seq, channels))
    b = jax.random.normal(keys[1], (batch, seq, state))
    c = jax.random.normal(keys[2], (batch, seq, state))
    d = 1.0 + 0.1 * jax.random.normal(keys[3], (channels,))
    dt = jnp.exp(jax.random.uniform(keys[4], (batch, seq, channels),
                                    minval=jnp.log(0.001),
                                    maxval=jnp.log(0.1)))
    a_log = jnp.log(jnp.broadcast_to(
        jnp.arange(1, state + 1, dtype=jnp.float32), (channels, state)))
    weights = jax.random.normal(keys[5], (batch, seq, channels))
    return (u, dt, a_log, b, c, d), weights


def _value_and_grads(fn, operands, weights):
    return jax.value_and_grad(
        lambda *ops: jnp.sum(fn(*ops) * weights), argnums=range(6))(
            *operands)


def _scan(path: str, chunk: int):
    return lambda *ops: sscan._sscan(*ops, chunk, path == _IN_VMEM)[0]


def _gaps(got, want):
    (loss, grads), (want_loss, want_grads) = got, want
    gaps = {"y": abs(float(loss - want_loss)) / abs(float(want_loss))}
    for name, g, w in zip(_NAMES, grads, want_grads):
        gaps[name] = float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w)))
    return gaps


@pytest.mark.parametrize("path,chunk", [
    (_LOOPS, 4), (_LOOPS, 8), (_LOOPS, 32), (_IN_VMEM, 8), (_IN_VMEM, 16)])
def test_the_scan_is_the_recurrence_forward_and_backward(path, chunk):
    operands, weights = _operands(path)
    want = _value_and_grads(_recurrence, operands, weights)
    got = _value_and_grads(_scan(path, chunk), operands, weights)
    for name, gap in _gaps(got, want).items():
        assert gap < 2e-5, (name, gap)
    np.testing.assert_allclose(_scan(path, chunk)(*operands),
                               _recurrence(*operands), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("path", [_LOOPS, _IN_VMEM])
def test_without_the_carry_the_comparison_fails(path, monkeypatch):
    """The control: every chunk starts from a state of zero, and hands no
    gradient back. The output and every gradient but ``D``'s (which sees
    no state) leave the recurrence's by far more than rounding; one chunk
    over the whole row has nothing to lose."""
    operands, weights = _operands(path)
    want = _value_and_grads(_recurrence, operands, weights)
    monkeypatch.setattr(sscan, "_handed_on", jnp.zeros_like)
    # the jitted passes were traced with the carry: trace them again
    jax.clear_caches()
    try:
        gaps = _gaps(_value_and_grads(_scan(path, 8), operands, weights),
                     want)
        whole = _gaps(_value_and_grads(_scan(path, 32), operands, weights),
                      want)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    for name in ("y", "u", "dt", "a_log", "b", "c"):
        assert gaps[name] > 1e-3, (name, gaps[name])
    assert gaps["d"] < 2e-5
    assert all(gap < 2e-5 for gap in whole.values()), whole


@pytest.mark.parametrize("path", [_LOOPS, _IN_VMEM])
def test_the_statistics_say_how_much_state_crosses(path, monkeypatch):
    (u, dt, a_log, b, c, d), _ = _operands(path)
    if path == _IN_VMEM:
        monkeypatch.setattr(sscan, "scans_in_vmem", lambda *shape: True)
    y, stats = sscan.selective_scan_counted(u, dt, a_log, b, c, d, 8)
    np.testing.assert_allclose(y, _recurrence(u, dt, a_log, b, c, d),
                               rtol=2e-5, atol=2e-5)
    batch, seq, channels, state = _SHAPES[path]
    whole = jnp.sum(dt.reshape(batch, seq // 8, 8, channels), axis=2)
    want = jnp.mean(jnp.exp(-whole[..., None] * jnp.exp(a_log)))
    assert float(stats[0]) == pytest.approx(float(want), rel=1e-5)
    assert 0.3 < float(stats[0]) < 1.0 and float(stats[1]) > 0.0
    # no gradient flows through the statistics
    grad = jax.grad(lambda dt: jnp.sum(sscan.selective_scan_counted(
        u, dt, a_log, b, c, d, 8)[1]))(dt)
    assert float(jnp.max(jnp.abs(grad))) == 0.0


def test_part_chunks_are_refused_and_the_kernels_say_what_they_take():
    (u, dt, a_log, b, c, d), _ = _operands(_LOOPS)
    with pytest.raises(ValueError, match="not whole chunks of 5"):
        sscan.selective_scan_counted(u, dt, a_log, b, c, d, 5)
    # the cell's: 5,120 channels are 40 sublane rows, all in one grid step
    assert sscan.vmem_takes(5120, 16, 64)
    assert sscan._rows_block(40, 16, 64) == 40
    assert sscan._rows_block(40, 16, 256) == 8      # the states of a chunk
    assert not sscan.vmem_takes(128, 4, 8), "not whole (8, 128) tiles"
    assert not sscan.vmem_takes(5120, 16, 32), "512 scalars a chunk"
    assert not sscan.scans_in_vmem(5120, 16, 64), "not on the chip"


def test_the_passes_beside_the_scan():
    keys = jax.random.split(jax.random.key(1), 4)
    x = jax.random.normal(keys[0], (2, 16, 24))
    weight = jax.random.normal(keys[1], (4, 24))
    bias = jax.random.normal(keys[2], (24,))
    np.testing.assert_array_equal(sscan.causal_conv_silu(x, weight, bias),
                                  ssd.causal_conv_silu(x, weight, bias))
    np.testing.assert_allclose(sscan.softplus_step(x, bias),
                               jax.nn.softplus(x + bias), rtol=1e-6)
    z = jax.random.normal(keys[3], (2, 16, 24))
    np.testing.assert_allclose(sscan.gated(x, z), x * jax.nn.silu(z),
                               rtol=1e-6)
    text = jax.jit(sscan.gated).lower(x, z).as_text(debug_info=True)
    assert sscan.SCOPE in text and ssd.SCOPE not in text
