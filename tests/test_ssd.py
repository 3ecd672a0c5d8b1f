"""The chunked state-space scan (ops/ssd.py) against the recurrence it
computes, written a position at a time, in float32 on the CPU: output and
every gradient, at several chunk lengths, by XLA's einsums and (shapes of
whole lanes, interpreted) by the kernels that keep a chunk's tiles in
VMEM; what the carry between chunks is worth at the benchmark
configuration's init and at the published one; which shapes the kernels
take; the convolution (plain, and by its kernels, interpreted, against
the plain one and autodiff) and the gated norm beside it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_shuffling_data_loader_tpu.ops import ssd

_B, _S, _H, _P, _N = 2, 32, 4, 8, 16
_NAMES = ("x", "dt", "a_log", "b", "c", "d")
#: The smallest shape the kernels take, (B, S, H, P, N): heads that fill
#: whole lanes, a state of whole lanes; chunks of 128, so two are crossed.
_LANE_ALIGNED = (1, 256, 4, 64, 128)
_EINSUMS, _IN_VMEM = "einsums", "in_vmem"


@pytest.fixture
def in_vmem(monkeypatch):
    """The kernels engaged off the chip (interpreted there)."""
    monkeypatch.setattr(ssd, "scans_in_vmem", lambda *shape: True)


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


def _operands(init: str, seed: int = 0, seq: int = _S,
              shape=(_B, None, _H, _P, _N)):
    """Random operands of ``shape`` (B, S, H, P, N; ``seq`` where S is
    None); ``dt`` and ``A`` as the configuration's ``assumed`` init draws
    them (``dt`` log-uniform in [0.001, 0.1], ``A`` uniform in [1, 16]) or
    as the published model's (``dt_bias`` 1, ``A`` from 1 to 64 over its 64
    heads, 16 a head: four heads here)."""
    batch, seq, heads, width, state = (seq if n is None else n for n in shape)
    keys = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(keys[0], (batch, seq, heads, width))
    b = jax.random.normal(keys[1], (batch, seq, state))
    c = jax.random.normal(keys[2], (batch, seq, state))
    d = 1.0 + 0.1 * jax.random.normal(keys[3], (heads,))
    if init == "assumed":
        dt = jnp.exp(jax.random.uniform(
            keys[4], (batch, seq, heads), minval=jnp.log(0.001),
            maxval=jnp.log(0.1)))
        a_log = jnp.log(jax.random.uniform(keys[5], (heads,), minval=1.0,
                                           maxval=16.0))
    else:
        dt = jax.nn.softplus(1.0 + 0.1 * jax.random.normal(
            keys[4], (batch, seq, heads)))
        a_log = jnp.log(jnp.linspace(1.0, 16.0 * heads, heads))
    return x, dt, a_log, b, c, d


def _as(dtype, operands):
    """``x``, ``b`` and ``c`` in the compute ``dtype``; the rest float32."""
    x, dt, a_log, b, c, d = operands
    return x.astype(dtype), dt, a_log, b.astype(dtype), c.astype(dtype), d


def _weighted(fn, weights):
    """A scalar of ``fn``'s output under fixed random weights, so that
    every output position's gradient is exercised."""
    return lambda *operands: jnp.sum(fn(*operands) * weights)


def _grads(fn, weights, operands):
    return jax.grad(_weighted(fn, weights), argnums=range(6))(*operands)


def _assert_close(got, want, rtol, atol, name):
    """``atol`` as a share of ``want``'s largest magnitude (at least 1)."""
    want = np.asarray(want, np.float32)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), want, rtol=rtol,
        atol=atol * max(float(np.max(np.abs(want))), 1.0), err_msg=name)


@pytest.mark.parametrize("chunk,path", [
    (4, _EINSUMS), (8, _EINSUMS), (_S, _EINSUMS), (128, _IN_VMEM),
    (256, _IN_VMEM)])
def test_output_and_every_gradient_match_the_recurrence(chunk, path,
                                                        request):
    if path == _IN_VMEM:
        request.getfixturevalue("in_vmem")
        operands = _operands("assumed", shape=_LANE_ALIGNED)
    else:
        operands = _operands("assumed")
    want = _recurrence(*operands)
    got, stats = ssd.ssd_counted(*operands, chunk)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert stats.shape == (2,) and 0.0 < float(stats[0]) <= 1.0
    weights = jax.random.normal(jax.random.key(9), want.shape)
    want_grads = _grads(_recurrence, weights, operands)
    got_grads = _grads(lambda *ops: ssd.ssd(*ops, chunk), weights, operands)
    for name, got_g, want_g in zip(_NAMES, got_grads, want_grads):
        _assert_close(got_g, want_g, 2e-4, 2e-5, name)


@pytest.mark.parametrize("dtype,chunk,shape", [
    (jnp.float32, 128, _LANE_ALIGNED), (jnp.bfloat16, 128, _LANE_ALIGNED),
    (jnp.bfloat16, 256, _LANE_ALIGNED),
    # four heads of 32 side by side in a tile, and a head a tile
    (jnp.float32, 128, (1, 256, 16, 32, 128)),
    (jnp.float32, 128, (1, 256, 8, 128, 128))],
    ids=["float32-128", "bfloat16-128", "bfloat16-256", "heads-of-32",
         "heads-of-128"])
def test_the_kernels_match_the_einsums(dtype, chunk, shape, monkeypatch):
    """Output, both statistics and all six gradients, path against path
    on the same operands: the kernels round where the einsums round (bf16
    operands into every product, float32 ``cum``, ``exp``, accumulators
    and carry), so the two differ by the order of their float32 sums
    alone; bf16 rounds ``d b`` and ``d c`` after a sum over the heads
    that the kernels make a head at a time."""
    operands = _as(dtype, _operands("assumed", seed=5, shape=shape))
    weights = jax.random.normal(jax.random.key(9), operands[0].shape)
    scan = lambda *ops: ssd.ssd(*ops, chunk).astype(jnp.float32)
    want, want_stats = ssd.ssd_counted(*operands, chunk)
    want_grads = _grads(scan, weights, operands)
    monkeypatch.setattr(ssd, "scans_in_vmem", lambda *shape: True)
    got, got_stats = ssd.ssd_counted(*operands, chunk)
    got_grads = _grads(scan, weights, operands)
    assert got.dtype == want.dtype == dtype
    exact = dtype == jnp.float32
    _assert_close(got, want, 1e-5, 1e-6 if exact else 8e-3, "y")
    np.testing.assert_allclose(got_stats, want_stats, rtol=1e-5)
    for name, got_g, want_g in zip(_NAMES, got_grads, want_grads):
        assert got_g.dtype == want_g.dtype, name
        _assert_close(got_g, want_g, 1e-4, 1e-5 if exact else 8e-3, name)


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
        lambda *operands: plain(*operands), static_argnums=(6, 7)))


@pytest.mark.parametrize("init,caught,path", [
    ("assumed", True, _EINSUMS), ("published", False, _EINSUMS),
    ("assumed", True, _IN_VMEM)])
def test_a_scan_without_its_carry_is_caught_at_the_assumed_init(
        init, caught, path, monkeypatch, request):
    """The control: the state each chunk starts from zeroed. At the
    configuration's init (slow heads: ``dt A`` from 0.001 a position) the
    output is off by a tenth of its norm, at nearly half of its values; at
    the published ``dt_bias = 1, A = 1..64`` a head keeps exp(-1.3 A) of its
    state a position, so what a chunk hands on is gone within a few
    positions of the next (1.4 % of the output's norm here, in under 2 % of
    its values, all of the one head with A = 1) and a comparison of norms
    has little to see the fault by: why the configuration does not use
    it. The kernels call the same ``_carries`` between their two halves:
    the seam is the einsums'."""
    if path == _IN_VMEM:
        request.getfixturevalue("in_vmem")
        # four chunks: three of them start from a state
        operands, chunk = _operands(
            init, seed=2, seq=512, shape=(1, None) + _LANE_ALIGNED[2:]), 128
    else:
        operands, chunk = _operands(init, seed=2, seq=256), 64
    want = _recurrence(*operands)
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


def test_which_shapes_the_kernels_take(monkeypatch):
    """In VMEM on the chip where a chunk and the state are whole lanes,
    the heads block into whole lanes and the operands are bf16 or
    float32; XLA's einsums otherwise, and everywhere off the chip."""
    cell = (256, 64, 64, 128, jnp.bfloat16)    # granite_train_8k's layers
    assert not ssd.scans_in_vmem(*cell)        # the CPU
    assert ssd.vmem_takes(*cell)
    monkeypatch.setattr(ssd, "on_tpu", lambda: True)
    assert ssd.scans_in_vmem(*cell)
    assert ssd.scans_in_vmem(128, 2, 64, 128, jnp.float32)
    assert ssd.scans_in_vmem(128, 4, 128, 256, jnp.bfloat16)
    assert not ssd.scans_in_vmem(8, 64, 64, 128, jnp.bfloat16)  # granite_tiny
    assert not ssd.scans_in_vmem(192, 64, 64, 128, jnp.bfloat16)
    assert not ssd.scans_in_vmem(256, 3, 64, 128, jnp.bfloat16)    # 192 lanes
    assert not ssd.scans_in_vmem(256, 4, 8, 128, jnp.bfloat16)     # 32 lanes
    assert not ssd.scans_in_vmem(256, 4, 256, 128, jnp.bfloat16)   # two tiles
    assert not ssd.scans_in_vmem(256, 64, 64, 16, jnp.bfloat16)    # the state
    assert not ssd.scans_in_vmem(256, 64, 64, 128, jnp.float16)
    # a grid step takes 512 lanes of heads where it can, in whole sublanes
    # of their rows and whole tiles of heads side by side
    assert [ssd._head_block(*heads) for heads in (
        (64, 64), (4, 64), (2, 64), (32, 128), (12, 64), (3, 64),
        (32, 32), (64, 8))] == [8, 4, 2, 8, 12, 0, 16, 32]


@pytest.mark.parametrize("reverse", [False, True],
                         ids=["carries", "carried_back"])
def test_the_carrys_kernel_is_the_loop(reverse, monkeypatch):
    """What each chunk starts from (or hands back), by the kernel that
    holds it in VMEM across a sequential chunk axis, interpreted: bit for
    bit the loop's, and the seam's two functions take it on the chip."""
    keys = jax.random.split(jax.random.key(7), 2)
    given = jax.random.normal(keys[0], (2, 5, 4, 16, 128))
    decay = jax.random.uniform(keys[1], (2, 5, 4))
    want = ssd._passed(given, decay, reverse)
    np.testing.assert_array_equal(
        ssd._passed_in_vmem(given, decay, reverse, True), want)
    assert float(jnp.max(jnp.abs(want[:, 0 if not reverse else -1]))) == 0.0
    seam = ssd._carried_back if reverse else ssd._carries
    np.testing.assert_array_equal(seam(given, decay), want)
    assert not ssd.passes_in_vmem(64, 64, 128)      # the CPU
    monkeypatch.setattr(ssd, "on_tpu", lambda: True)
    assert ssd.passes_in_vmem(64, 64, 128) and ssd.passes_in_vmem(4, 16, 128)
    assert not ssd.passes_in_vmem(4, 8, 16)         # granite_tiny's state
    assert not ssd.passes_in_vmem(4, 12, 128)
    # 2 MB of state a grid step: all of the cell's heads
    assert ssd._heads_passed(64, 64, 128) == 64
    assert ssd._heads_passed(64, 128, 256) == 16


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


def _conv_operands(shape, dtype, taps: int = 4, seed: int = 8):
    keys = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(keys[0], shape, dtype)
    weight = jax.random.normal(keys[1], (taps, shape[-1])) * taps ** -0.5
    bias = 0.1 * jax.random.normal(keys[2], shape[-1:])
    return x, weight, bias, jax.random.normal(keys[3], shape)


def _conv_with_grads(conv, x, weight, bias, mix):
    """``(y, d x, d w, d b)`` of ``sum(conv(x, w, b) * mix)``."""
    def loss(x, weight, bias):
        y = conv(x, weight, bias)
        return jnp.sum(y.astype(jnp.float32) * mix), y

    grads, y = jax.grad(loss, (0, 1, 2), has_aux=True)(x, weight, bias)
    return (y, *grads)


def _conv_kernels(x, weight, bias):
    return ssd._conv_silu_in_vmem(x, weight, bias, ssd.SCOPE)


@pytest.fixture
def small_conv_blocks(monkeypatch):
    """Blocks of 64 positions x 128 channels, so that a small array is
    several of them."""
    monkeypatch.setattr(ssd, "_CONV_ROWS", 64)
    monkeypatch.setattr(ssd, "_CONV_LANES", 128)


#: (B, S, C) by what the grid is over, at blocks of 64 x 128.
_CONV_SHAPES = {"one_block": (1, 64, 128),
                "sequence_blocks": (1, 192, 128),
                "lane_blocks": (1, 64, 384),
                "batch_2": (2, 128, 256)}


@pytest.mark.parametrize("shape", list(_CONV_SHAPES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_convolutions_kernels_are_the_plain_one_and_autodiff(
        dtype, shape, small_conv_blocks):
    """The output, ``d x``, the taps' and the bias's gradients by the two
    kernels, interpreted: float32 equal to rounding, bfloat16 the same
    values to its step (``d w`` and ``d b`` are float32 sums either
    way)."""
    dims = _CONV_SHAPES[shape]
    assert ssd._conv_block(*dims[1:]) == (64, 128)
    x, weight, bias, mix = _conv_operands(dims, dtype)
    got = _conv_with_grads(_conv_kernels, x, weight, bias, mix)
    want = _conv_with_grads(ssd.conv_silu, x, weight, bias, mix)
    step = {jnp.float32: 1e-6, jnp.bfloat16: 2.0 ** -8}[dtype]
    for name, g, w, tol in zip(("y", "d x", "d w", "d b"), got, want,
                               (step, step, 2e-6, 2e-6)):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.abs(g - w).max() <= tol * np.abs(w).max(), name


@pytest.mark.parametrize("taps", [1, 2, 8])
def test_the_convolutions_kernels_take_other_taps(taps, small_conv_blocks):
    x, weight, bias, mix = _conv_operands((1, 128, 128), jnp.float32, taps)
    for g, w in zip(_conv_with_grads(_conv_kernels, x, weight, bias, mix),
                    _conv_with_grads(ssd.conv_silu, x, weight, bias, mix)):
        np.testing.assert_allclose(g, w, rtol=0, atol=2e-6 * float(
            jnp.max(jnp.abs(w))))


def test_an_impulse_crosses_the_seam_between_blocks_and_comes_back(
        small_conv_blocks):
    """Ones in a block's last three rows reach the next block's first
    three positions and no further, and the cotangent of those three
    positions comes back across the seam to the rows that fed them."""
    _, weight, bias, _ = _conv_operands((1, 128, 128), jnp.float32)
    x = jnp.zeros((1, 128, 128)).at[:, 61:64].set(1.0)
    silent = jax.nn.silu(bias)
    got = _conv_kernels(x, weight, bias)
    np.testing.assert_allclose(got, ssd.conv_silu(x, weight, bias),
                               rtol=1e-6, atol=1e-7)
    for t in (64, 65, 66):
        assert float(jnp.max(jnp.abs(got[0, t] - silent))) > 1e-3, t
    np.testing.assert_allclose(got[0, 67:], jnp.broadcast_to(
        silent, got[0, 67:].shape), rtol=1e-6, atol=1e-7)
    # a loss that sees the second block alone
    mix = jnp.zeros((1, 128, 128)).at[:, 64:67].set(1.0)

    def d_x(conv):
        return jax.grad(lambda x: jnp.sum(conv(x, weight, bias) * mix))(x)

    back = d_x(_conv_kernels)
    np.testing.assert_allclose(back, d_x(ssd.conv_silu), rtol=1e-5,
                               atol=1e-7)
    for t in (61, 62, 63):
        assert float(jnp.max(jnp.abs(back[0, t]))) > 1e-3, t
    assert float(jnp.max(jnp.abs(back[0, :61]))) == 0.0
    assert float(jnp.max(jnp.abs(back[0, 67:]))) == 0.0


def test_the_first_positions_see_zeros_and_the_last_hand_nothing_on(
        small_conv_blocks):
    """Before a row's first position the taps read zeros, not the tile
    the first block's grid step fetches in that place (its own rows 8 to
    15); after its last position no gradient comes back from the tile the
    last block fetches (its own first rows). Row by row of the batch."""
    x, weight, bias, mix = _conv_operands((2, 128, 128), jnp.float32)
    y, d_x, _, _ = _conv_with_grads(_conv_kernels, x, weight, bias, mix)
    for t in range(3):
        pre = bias + sum(weight[3 - back] * x[:, t - back]
                         for back in range(t + 1))
        np.testing.assert_allclose(y[:, t], jax.nn.silu(pre), rtol=1e-6,
                                   atol=1e-7)
    pre = bias + sum(weight[3 - back] * x[:, 127 - back]
                     for back in range(4))
    sig = jax.nn.sigmoid(pre)
    np.testing.assert_allclose(
        d_x[:, 127], weight[3] * mix[:, 127] * sig * (1 + pre * (1 - sig)),
        rtol=1e-5, atol=1e-7)
    # what stands in the fetched tiles does not matter
    loud = x.at[:, 8:16].multiply(100.0)
    np.testing.assert_array_equal(_conv_kernels(loud, weight, bias)[:, :5],
                                  y[:, :5])


def test_which_shapes_the_convolutions_kernels_take(monkeypatch):
    """On the chip, bfloat16 or float32, channels of whole lanes, a
    sequence of whole blocks, at most 8 taps; XLA's pad and slices
    otherwise, and everywhere off the chip."""
    cell = (8192, 4352, 4, jnp.bfloat16)       # granite_train_8k's xBC
    assert not ssd.convs_in_vmem(*cell)        # the CPU
    assert ssd.conv_takes(*cell)
    monkeypatch.setattr(ssd, "on_tpu", lambda: True)
    assert ssd.convs_in_vmem(*cell)
    assert ssd.convs_in_vmem(8192, 5120, 4, jnp.bfloat16)  # phi4flash's u
    assert ssd.convs_in_vmem(64, 128, 8, jnp.float32)
    assert not ssd.convs_in_vmem(32, 64, 4, jnp.bfloat16)   # granite_tiny
    assert not ssd.convs_in_vmem(8192, 4352 + 64, 4, jnp.bfloat16)
    assert not ssd.convs_in_vmem(8192 + 8, 4352, 4, jnp.bfloat16)
    assert not ssd.convs_in_vmem(8192, 4352, 9, jnp.bfloat16)
    assert not ssd.convs_in_vmem(8192, 4352, 4, jnp.float16)
    # a grid step takes the most whole strips and whole 128s that divide
    assert [ssd._conv_block(*dims) for dims in (
        (8192, 4352), (8192, 5120), (96, 384), (131 * 32, 128),
        (48, 128), (64, 100))] == [
            (2048, 256), (2048, 512), (96, 384), (32, 128), (0, 128),
            (64, 0)]
    # the wrappers take them of their own accord, or leave them
    x, weight, bias, _ = _conv_operands((1, 64, 128), jnp.float32)
    monkeypatch.setattr(ssd, "_conv_silu_in_vmem",
                        lambda *a: "the kernels")
    assert ssd.causal_conv_silu(x, weight, bias) == "the kernels"
    assert ssd.causal_conv_silu(x[:, :, :64], weight[:, :64],
                                bias[:64]).shape == (1, 64, 64)


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
