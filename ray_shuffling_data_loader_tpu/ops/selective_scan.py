"""Mamba-1's selective scan (Gu & Dao 2023), forward and backward, in
chunks, with the passes a Mamba-1 mixer runs beside it: the depthwise
causal convolution with its ``silu`` (``ops/ssd.py``'s), the step's
softplus and the gate ``y * silu(z)``.

The recurrence, a channel at a time (``C`` channels, a state of ``N`` a
channel; ``B`` and ``C`` shared by every channel)::

    h_t[c, n] = exp(dt_t[c] A[c, n]) h_(t-1)[c, n] + dt_t[c] B_t[n] u_t[c]
    y_t[c]    = sum_n C_t[n] h_t[c, n] + D[c] u_t[c]      A = -exp(A_log) < 0

The decay is a different number for each channel and state, so a chunk is
no matrix product (``ops/ssd.py``'s scan needs one scalar a head for
that): it is elementwise work on a (C, N) float32 state a position, on the
vector units. It is walked a chunk of ``chunk`` positions at a time. The
forward pass keeps the float32 state each chunk starts from (``chunks`` x
C x N, not S x C x N); the backward pass, written out, walks the chunks
last to first, makes a chunk's states again from its start, and reduces
``d A_log``, ``d D`` and the gradients of ``dt``, ``B`` and ``C`` as it
makes them. The state crosses a chunk's end through :func:`_handed_on`.

Two paths compute it, chosen by :func:`scans_in_vmem` from what the trace
can see. On the TPU, where the channels are whole (8, 128) float32 tiles,
two Pallas kernels: a position's channels are ``C / 128`` sublane rows of
128 lanes, a state is one such array a ``n``, ``B_t[n]`` and ``C_t[n]``
are scalars out of SMEM, the chunk axis of the grid is sequential and the
state lives in a VMEM scratch; the backward kernel keeps a chunk's states
in VMEM and leaves of ``dB`` and ``dC`` the sums over a position's
sublane rows to XLA's over the lanes. Everywhere else (every CPU run) a
``lax.scan`` over the chunks with one over a chunk's positions inside:
the fallback, and the kernels' oracle (tests/test_selective_scan.py).
What the mixer's part costs is read from a trace under ``SCOPE``
(``lm_sscan_pct``, ``lm_sscan_roofline_pct``).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_shuffling_data_loader_tpu.ops import on_tpu, ssd
from ray_shuffling_data_loader_tpu.runtime import telemetry

#: The name a device trace shows the mixer's convolution, softplus, scan
#: and gate under (the projections around them are the decoder's).
SCOPE = telemetry.step_scope("rsdl.lm.sscan")

_F32 = jnp.float32
_LANES, _SUBLANES = 128, 8
#: What a kernel may hold in VMEM; a v5e has 128 MiB.
_VMEM_BYTES = 64 * 1024 * 1024
#: Bytes of a chunk's states the backward kernel keeps at the most.
_STATES_BYTES = 24 * 1024 * 1024


def _handed_on(state):
    """What crosses a chunk's end: the state the next chunk starts from,
    and in the backward pass its gradient, handed to the chunk before
    (linear and its own transpose). The carry's control puts zeros here
    (``chipbench/probes/phi4flash_controls.py``, the tests)."""
    return state


def _check(seq: int, chunk: int) -> int:
    if seq % chunk:
        raise ValueError(f"a sequence of {seq} positions is not whole "
                         f"chunks of {chunk}")
    return seq // chunk


# -- a loop of XLA's over the chunks, one over a chunk's positions inside -------


def _by_chunk(m, chunk: int):
    """(B, S, ...) -> (chunks, chunk, B, ...), float32."""
    batch, seq = m.shape[:2]
    return jnp.moveaxis(
        m.astype(_F32).reshape(batch, seq // chunk, chunk, *m.shape[2:]),
        (1, 2), (0, 1))


def _seq_major(m):
    """(chunks, chunk, B, ...) -> (B, S, ...)."""
    m = jnp.moveaxis(m, 2, 0)
    return m.reshape(m.shape[0], -1, *m.shape[3:])


def _moved(h, a, u_t, dt_t, b_t):
    """``(h_t, the decay)`` from ``h_(t-1)`` (B, C, N)."""
    decay = jnp.exp(dt_t[..., None] * a)
    return decay * h + (dt_t * u_t)[..., None] * b_t[:, None, :], decay


def _fwd_positions(u, dt, a, b, c, chunk: int):
    """``(y (B, S, C) float32 short of D u, carries (B, chunks, C, N))``:
    ``carries`` is the state each chunk starts from."""
    batch, _, channels = u.shape

    def position(h, at):
        u_t, dt_t, b_t, c_t = at
        h, _ = _moved(h, a, u_t, dt_t, b_t)
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    def one_chunk(h, of):
        start = _handed_on(h)
        end, y = jax.lax.scan(position, start, of)
        return end, (y, start)

    _, (y, carries) = jax.lax.scan(
        one_chunk, jnp.zeros((batch, channels, a.shape[1]), _F32),
        tuple(_by_chunk(m, chunk) for m in (u, dt, b, c)))
    return _seq_major(y), jnp.moveaxis(carries, 0, 1)


def _bwd_positions(u, dt, a, b, c, carries, dy, chunk: int):
    """``(d u short of D dy, d dt (B, S, C), d A (C, N), d B, d C
    (B, S, N))``, float32: the chunks last to first, each chunk's states
    made again from its start, then its positions last to first."""

    def before_each(h, at):
        return _moved(h, a, *at)[0], h

    def back(held, at):
        g, d_a = held       # what the later positions hand h_t; d A so far
        u_t, dt_t, b_t, c_t, dy_t, h_before = at
        h, decay = _moved(h_before, a, u_t, dt_t, b_t)
        g = g + dy_t[..., None] * c_t[:, None, :]
        d_dtu = jnp.sum(g * b_t[:, None, :], axis=-1)
        flowing = g * decay
        through = flowing * h_before        # d (dt A)
        return (flowing, d_a + jnp.sum(through * dt_t[..., None], axis=0)), (
            d_dtu * dt_t, jnp.sum(through * a, axis=-1) + d_dtu * u_t,
            jnp.sum(g * (dt_t * u_t)[..., None], axis=1),
            jnp.sum(dy_t[..., None] * h, axis=1))

    def one_chunk(held, of):
        *inputs, start = of
        u_c, dt_c, b_c, _, _ = inputs
        _, h_before = jax.lax.scan(before_each, start, (u_c, dt_c, b_c))
        (g, d_a), grads = jax.lax.scan(back, held, (*inputs, h_before),
                                       reverse=True)
        return (_handed_on(g), d_a), grads

    zero = jnp.zeros(carries.shape[:1] + carries.shape[2:], _F32)
    (_, d_a), grads = jax.lax.scan(
        one_chunk, (zero, jnp.zeros_like(a)),
        (*(_by_chunk(m, chunk) for m in (u, dt, b, c, dy)),
         jnp.moveaxis(carries, 1, 0)), reverse=True)
    d_u, d_dt, d_b, d_c = (_seq_major(m) for m in grads)
    return d_u, d_dt, d_a, d_b, d_c


# -- the same in VMEM ----------------------------------------------------------------
# A position's channels are (rows, 128) float32, ``rows = C / 128`` sublane
# rows; a state is one such array a ``n``, so the recurrence is whole-tile
# multiply-adds under scalars of ``B_t`` and ``C_t`` and nothing crosses a
# lane until ``dB`` and ``dC`` sum over the channels.


def _rows_block(rows: int, state: int, chunk: int) -> int:
    """Sublane rows a grid step takes: all of them, or the most that are
    whole tiles, divide ``rows`` and keep a chunk's states within
    ``_STATES_BYTES``; 0 where none does."""
    a_row = (chunk + 1) * state * _LANES * 4
    counts = [n for n in range(_SUBLANES, rows + 1, _SUBLANES)
              if rows % n == 0 and n * a_row <= _STATES_BYTES]
    return max(counts, default=0)


#: Scalars in a tile of a one-dimensional float32 array in SMEM, as XLA
#: lays it out: a chunk's ``B_t[n]`` are whole tiles of it.
_SCALARS_TILE = 1024


def vmem_takes(channels: int, state: int, chunk: int) -> bool:
    """Whether the kernels below can compute such a scan: channels of
    whole (8, 128) tiles, a block of them whose states a chunk fit VMEM
    (:func:`_rows_block`), and a chunk's scalars whole tiles in SMEM."""
    return (channels % (_SUBLANES * _LANES) == 0
            and (chunk * state) % _SCALARS_TILE == 0
            and _rows_block(channels // _LANES, state, chunk) > 0)


def scans_in_vmem(channels: int, state: int, chunk: int) -> bool:
    """Whether the scan's state stays in VMEM, from what the trace can
    see: on the TPU, where :func:`vmem_takes` the shapes."""
    return on_tpu() and vmem_takes(channels, state, chunk)


def _tiled(m):
    """(B, S, C) -> (B, S, C / 128, 128) float32."""
    return m.astype(_F32).reshape(*m.shape[:2], -1, _LANES)


def _scalars(m):
    """(B, S, N) -> (B x S x N,) float32, for SMEM."""
    return m.astype(_F32).reshape(-1)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_BYTES)


def _fwd_in_vmem(u, dt, a, b, c, chunk: int, interpret: bool):
    """:func:`_fwd_positions` by one kernel; ``carries`` comes back as
    (B, chunks, N, C / 128, 128)."""
    batch, seq, channels = u.shape
    state, chunks, rows = a.shape[1], seq // chunk, channels // _LANES
    block = _rows_block(rows, state, chunk)

    def kernel(b_ref, c_ref, u_ref, dt_ref, a_ref, y_ref, carry_ref, h):
        @pl.when(pl.program_id(2) == 0)
        def _():
            h[...] = jnp.zeros_like(h)

        start = _handed_on(h[...])
        carry_ref[0, 0] = start
        h[...] = start

        def position(t, _):
            dt_t = dt_ref[0, t]
            dtu = dt_t * u_ref[0, t]
            y = jnp.zeros_like(dtu)
            for n in range(state):
                h_n = (jnp.exp(dt_t * a_ref[n]) * h[n]
                       + dtu * b_ref[t * state + n])
                h[n] = h_n
                y = y + h_n * c_ref[t * state + n]
            y_ref[0, t] = y

        jax.lax.fori_loop(0, chunk, position, None)

    scalars = pl.BlockSpec((chunk * state,), lambda i, j, k: (i * chunks + k,),
                           memory_space=pltpu.SMEM)
    positions = pl.BlockSpec((1, chunk, block, _LANES),
                             lambda i, j, k: (i, k, j, 0))
    y, carries = pl.pallas_call(
        kernel, grid=(batch, rows // block, chunks),
        in_specs=[scalars, scalars, positions, positions,
                  pl.BlockSpec((state, block, _LANES),
                               lambda i, j, k: (0, j, 0))],
        out_specs=[positions,
                   pl.BlockSpec((1, 1, state, block, _LANES),
                                lambda i, j, k: (i, k, 0, j, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((batch, seq, rows, _LANES), _F32),
            jax.ShapeDtypeStruct((batch, chunks, state, rows, _LANES), _F32)],
        scratch_shapes=[pltpu.VMEM((state, block, _LANES), _F32)],
        compiler_params=_params(), interpret=interpret,
    )(_scalars(b), _scalars(c), _tiled(u), _tiled(dt),
      a.T.reshape(state, rows, _LANES))
    return y.reshape(u.shape), carries


def _bwd_in_vmem(u, dt, a, b, c, carries, dy, chunk: int, interpret: bool):
    """:func:`_bwd_positions` by one kernel, the chunks last to first.
    Of ``dB`` and ``dC`` it leaves (B, row blocks, S, N, 128), the sums
    over a block's sublane rows; XLA sums the lanes and the blocks."""
    batch, seq, channels = u.shape
    state, chunks, rows = a.shape[1], seq // chunk, channels // _LANES
    block = _rows_block(rows, state, chunk)

    def kernel(b_ref, c_ref, u_ref, dt_ref, dy_ref, a_ref, carry_ref,
               du_ref, ddt_ref, db_ref, dc_ref, da_ref, states, g):
        @pl.when(pl.program_id(2) == 0)
        def _():
            g[...] = jnp.zeros_like(g)
            da_ref[...] = jnp.zeros_like(da_ref)

        # the chunk's states again: states[t] is what position t starts from
        states[0] = carry_ref[0, 0]

        def again(t, _):
            dt_t = dt_ref[0, t]
            dtu = dt_t * u_ref[0, t]
            for n in range(state):
                states[t + 1, n] = (jnp.exp(dt_t * a_ref[n]) * states[t, n]
                                    + dtu * b_ref[t * state + n])

        jax.lax.fori_loop(0, chunk, again, None)

        def back(i, _):
            t = chunk - 1 - i
            dt_t, u_t, dy_t = dt_ref[0, t], u_ref[0, t], dy_ref[0, t]
            dtu = dt_t * u_t
            d_dtu = jnp.zeros_like(dtu)
            d_dt = jnp.zeros_like(dtu)
            for n in range(state):
                a_n = a_ref[n]
                g_n = g[n] + dy_t * c_ref[t * state + n]
                dc_ref[0, 0, t, n:n + 1] = jnp.sum(
                    dy_t * states[t + 1, n], axis=0, keepdims=True)
                db_ref[0, 0, t, n:n + 1] = jnp.sum(g_n * dtu, axis=0,
                                                   keepdims=True)
                d_dtu = d_dtu + g_n * b_ref[t * state + n]
                flowing = g_n * jnp.exp(dt_t * a_n)
                g[n] = flowing
                through = flowing * states[t, n]        # d (dt A)
                d_dt = d_dt + through * a_n
                da_ref[0, n] += through * dt_t
            du_ref[0, t] = d_dtu * dt_t
            ddt_ref[0, t] = d_dt + d_dtu * u_t

        jax.lax.fori_loop(0, chunk, back, None)
        g[...] = _handed_on(g[...])

    def at(k):
        return chunks - 1 - k

    scalars = pl.BlockSpec((chunk * state,),
                           lambda i, j, k: (i * chunks + at(k),),
                           memory_space=pltpu.SMEM)
    positions = pl.BlockSpec((1, chunk, block, _LANES),
                             lambda i, j, k: (i, at(k), j, 0))
    sums = pl.BlockSpec((1, 1, chunk, state, _LANES),
                        lambda i, j, k: (i, j, at(k), 0, 0))
    summed = jax.ShapeDtypeStruct(
        (batch, rows // block, seq, state, _LANES), _F32)
    d_u, d_dt, d_b, d_c, d_a = pl.pallas_call(
        kernel, grid=(batch, rows // block, chunks),
        in_specs=[scalars, scalars, positions, positions, positions,
                  pl.BlockSpec((state, block, _LANES),
                               lambda i, j, k: (0, j, 0)),
                  pl.BlockSpec((1, 1, state, block, _LANES),
                               lambda i, j, k: (i, at(k), 0, j, 0))],
        out_specs=[positions, positions, sums, sums,
                   pl.BlockSpec((1, state, block, _LANES),
                                lambda i, j, k: (i, 0, j, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((batch, seq, rows, _LANES), _F32),
            jax.ShapeDtypeStruct((batch, seq, rows, _LANES), _F32),
            summed, summed,
            jax.ShapeDtypeStruct((batch, state, rows, _LANES), _F32)],
        scratch_shapes=[pltpu.VMEM((chunk + 1, state, block, _LANES), _F32),
                        pltpu.VMEM((state, block, _LANES), _F32)],
        compiler_params=_params(), interpret=interpret,
    )(_scalars(b), _scalars(c), _tiled(u), _tiled(dt), _tiled(dy),
      a.T.reshape(state, rows, _LANES), carries)
    return (d_u.reshape(u.shape), d_dt.reshape(u.shape),
            jnp.sum(d_a, axis=0).reshape(state, channels).T,
            jnp.sum(d_b, axis=(1, 4)), jnp.sum(d_c, axis=(1, 4)))


# -- the scan, by either path ----------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _sscan(u, dt, a_log, b, c, d, chunk, in_vmem):
    return _sscan_fwd(u, dt, a_log, b, c, d, chunk, in_vmem)[0]


# Jitted for the scope's sake (models/mellum.py:_swiglu_fwd): inside a
# program of its own the name reaches the compiled step as written, the
# kernels' calls with it.
@functools.partial(jax.jit, static_argnums=(6, 7))
def _sscan_fwd(u, dt, a_log, b, c, d, chunk, in_vmem):
    chunks = _check(u.shape[1], chunk)
    with jax.named_scope(SCOPE):
        a = -jnp.exp(a_log.astype(_F32))
        if in_vmem:
            y, carries = _fwd_in_vmem(u, dt, a, b, c, chunk, not on_tpu())
        else:
            y, carries = _fwd_positions(u, dt, a, b, c, chunk)
        y = y + d.astype(_F32) * u.astype(_F32)
        # of a chunk's starting state, what reaches its end
        whole = jnp.sum(dt.astype(_F32).reshape(
            u.shape[0], chunks, chunk, -1), axis=2)
        stats = jnp.stack([jnp.mean(jnp.exp(whole[..., None] * a)),
                           jnp.max(jnp.abs(carries))]).astype(_F32)
        return (y.astype(u.dtype), stats), (u, dt, a_log, b, c, d, carries)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _sscan_bwd(chunk, in_vmem, residuals, cotangents):
    u, dt, a_log, b, c, d, carries = residuals
    # the scan's two statistics have no cotangent
    dy = cotangents[0].astype(_F32)
    with jax.named_scope(SCOPE):
        a = -jnp.exp(a_log.astype(_F32))
        if in_vmem:
            d_u, d_dt, d_a, d_b, d_c = _bwd_in_vmem(
                u, dt, a, b, c, carries, dy, chunk, not on_tpu())
        else:
            d_u, d_dt, d_a, d_b, d_c = _bwd_positions(
                u, dt, a, b, c, carries, dy, chunk)
        u32 = u.astype(_F32)
        # A = -exp(A_log): dA/dA_log = A
        return ((d_u + d.astype(_F32) * dy).astype(u.dtype),
                d_dt.astype(dt.dtype), (d_a * a).astype(a_log.dtype),
                d_b.astype(b.dtype), d_c.astype(c.dtype),
                jnp.sum(dy * u32, axis=(0, 1)).astype(d.dtype))


_sscan.defvjp(_sscan_fwd, _sscan_bwd)


def selective_scan_counted(u, dt, a_log, b, c, d, chunk: int
                           ) -> Tuple[jax.Array, jax.Array]:
    """The selective scan, and how much state crossed its chunks.

    Args:
        u: (B, S, C), in the compute dtype.
        dt: (B, S, C) float32, positive (after its softplus).
        a_log: (C, N) float32; ``A = -exp(a_log)``.
        b, c: (B, S, N), in the compute dtype: every channel's.
        d: (C,) float32, the skip ``D u``.
        chunk: positions a chunk; ``S`` is whole chunks of it.

    Returns ``(y, stats)``: ``y`` (B, S, C) in ``u``'s dtype; ``stats``
    float32, one value a field of ``telemetry.STEP_STAT_FIELDS["ssm_scan"]``
    (``ops/ssd.py``'s two): the mean over rows, chunks, channels and states
    of a chunk's whole decay (the share of a chunk's starting state that
    reaches its end) and the largest ``|carry|``. ``stats`` has no
    gradient. A sequence that is not whole chunks is refused. Where
    :func:`scans_in_vmem` the kernels compute it, else XLA's loops."""
    return _sscan(u, dt, a_log, b, c, d, chunk,
                  scans_in_vmem(u.shape[-1], a_log.shape[-1], chunk))


# -- the passes beside the scan ---------------------------------------------------
# Under the scope, float32 inside. The convolution is ``ops/ssd.py``'s, a
# pair of kernels where the shapes fit; the others are plain ``jax.numpy``
# and their backward autodiff's, jitted for the scope's sake, as the scan.


def causal_conv_silu(x, weight, bias):
    """``ssd.causal_conv_silu``'s values under this mixer's scope."""
    return ssd.causal_conv_silu(x, weight, bias, SCOPE)


@jax.jit
def softplus_step(r, bias):
    """``dt = softplus(r + bias)``, float32: ``r`` (B, S, C) is what
    ``W_dt`` made, ``bias`` (C,) float32."""
    with jax.named_scope(SCOPE):
        return jax.nn.softplus(r.astype(_F32) + bias)


@jax.jit
def gated(y, z):
    """``y * silu(z)``, float32 inside, in ``y``'s dtype."""
    with jax.named_scope(SCOPE):
        return (y.astype(_F32) * jax.nn.silu(z.astype(_F32))).astype(y.dtype)
