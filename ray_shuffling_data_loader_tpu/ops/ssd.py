"""A chunked state-space scan (Mamba-2's SSD, Dao & Gu 2024), forward and
backward, with the two passes a Mamba-2 mixer runs beside it: a depthwise
causal convolution and an RMSNorm gated by ``silu(z)``.

The recurrence, a head at a time (``P`` values a head, a state of
``P x N``; ``B`` and ``C`` in one group, shared by every head)::

    h_t = exp(dt_t A) h_(t-1) + dt_t x_t (x) B_t        A = -exp(A_log) < 0
    y_t = h_t . C_t + D x_t

is computed in chunks of ``chunk`` positions, nothing of length S x S and
no loop over positions. With ``cum_i`` the running sum of ``dt A`` inside a
chunk: within the chunk ``(L o (C B^T)) (dt x)`` where ``L[i, j] =
exp(cum_i - cum_j)`` for ``i >= j``; the chunk's own end state ``sum_j
exp(cum_end - cum_j) dt_j x_j (x) B_j``; the carry across the chunks, one
multiply-add a chunk (:func:`_carries`); and what the carried state gives
a position, ``exp(cum_i) C_i . carry``. ``cum``, every ``exp`` and the
carry are float32; the products' operands are in ``x``'s dtype (bf16 in
the benchmark's cell) and accumulate in float32. The backward pass
(:func:`_ssd_bwd`) is written out chunk by chunk the same way: it keeps
the inputs and the carries, makes ``cum``, ``L`` and ``C B^T`` again, and
carries the states' gradient back across the chunks.

This first version is XLA's einsums over the chunks, not a Pallas kernel:
the heads' ``L o (C B^T)``, (B, chunks, H, chunk, chunk), goes through
HBM once forward and three times backward. What it costs is read from a
trace under ``SCOPE`` (``lm_ssm_pct``, ``lm_ssm_roofline_pct``).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

#: The name a device trace shows the mixer's convolution, scan and gated
#: norm under (the projections around them are the decoder's).
SCOPE = "rsdl.lm.ssm"

_F32 = jnp.float32


def _einsum(spec: str, *operands):
    """Operands as given (the compute dtype), accumulated in float32."""
    return jnp.einsum(spec, *operands, preferred_element_type=_F32)


def _carries(states, end_decay):
    """The state each chunk starts from, (B, chunks, H, P, N) float32:
    zero for the first, then ``end_decay_c x carry_c + states_c``, from
    each chunk's own end state ``states`` (B, chunks, H, P, N) and its
    whole decay ``end_decay`` (B, chunks, H)."""
    def step(carry, chunk):
        state, decay = chunk
        return decay[..., None, None] * carry + state, carry

    _, carries = jax.lax.scan(
        step, jnp.zeros_like(states[:, 0]),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(end_decay, 1, 0)))
    return jnp.moveaxis(carries, 0, 1)


def _carried_back(d_carries, end_decay):
    """The gradient of each chunk's END state, (B, chunks, H, P, N): zero
    for the last, then what the next chunk's carry hands back,
    ``d_carries_(c+1) + end_decay_(c+1) x d_end_(c+1)``."""
    def step(d_next, chunk):
        d_carry, decay = chunk
        return d_carry + decay[..., None, None] * d_next, d_next

    _, d_ends = jax.lax.scan(
        step, jnp.zeros_like(d_carries[:, 0]),
        (jnp.moveaxis(d_carries, 1, 0), jnp.moveaxis(end_decay, 1, 0)),
        reverse=True)
    return jnp.moveaxis(d_ends, 0, 1)


def _by_chunk(x, dt, a_log, b, c, chunk: int):
    """The operands a chunk at a time and a head at a time, with the
    running decay: ``(xh (B, c, H, Q, P), dth (B, c, H, Q) float32, bc,
    cc (B, c, Q, N), cum (B, c, H, Q) float32, A (H,) float32)``."""
    batch, seq, heads, width = x.shape
    if seq % chunk:
        raise ValueError(f"a sequence of {seq} positions is not whole "
                         f"chunks of {chunk}")
    chunks = seq // chunk
    a = -jnp.exp(a_log.astype(_F32))
    xh = x.reshape(batch, chunks, chunk, heads, width).transpose(0, 1, 3, 2, 4)
    dth = dt.astype(_F32).reshape(batch, chunks, chunk, heads).transpose(
        0, 1, 3, 2)
    bc, cc = (m.reshape(batch, chunks, chunk, -1) for m in (b, c))
    return xh, dth, bc, cc, jnp.cumsum(dth * a[:, None], axis=-1), a


def _within(cum):
    """``L``: exp(cum_i - cum_j) for i >= j, else 0; (..., Q, Q) float32.
    Masked before the ``exp``: above the diagonal the difference is
    positive and may overflow."""
    size = cum.shape[-1]
    below = jnp.tril(jnp.ones((size, size), bool))
    return jnp.exp(jnp.where(below, cum[..., :, None] - cum[..., None, :],
                             -jnp.inf))


def _seq_major(yh):
    """(B, c, H, Q, P) -> (B, S, H, P)."""
    batch, chunks, heads, chunk, width = yh.shape
    return yh.transpose(0, 1, 3, 2, 4).reshape(batch, chunks * chunk, heads,
                                               width)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd(x, dt, a_log, b, c, d, chunk):
    return _ssd_fwd(x, dt, a_log, b, c, d, chunk)[0]


# Jitted for the scope's sake (models/mellum.py:_swiglu_fwd): inside a
# program of its own the name reaches the compiled step as written.
@functools.partial(jax.jit, static_argnums=(6,))
def _ssd_fwd(x, dt, a_log, b, c, d, chunk):
    with jax.named_scope(SCOPE):
        xh, dth, bc, cc, cum, _ = _by_chunk(x, dt, a_log, b, c, chunk)
        xdt = (xh.astype(_F32) * dth[..., None]).astype(x.dtype)
        scores = _einsum("bcin,bcjn->bcij", cc, bc)
        mixed = (_within(cum) * scores[:, :, None]).astype(x.dtype)
        yh = _einsum("bchij,bchjp->bchip", mixed, xdt)
        to_end = jnp.exp(cum[..., -1:] - cum)
        states = _einsum(
            "bchjp,bcjn->bchpn",
            (xdt.astype(_F32) * to_end[..., None]).astype(x.dtype), bc)
        end_decay = jnp.exp(cum[..., -1])
        carries = _carries(states, end_decay)
        yh = yh + jnp.exp(cum)[..., None] * _einsum(
            "bcin,bchpn->bchip", cc, carries.astype(x.dtype))
        y = _seq_major(yh) + d.astype(_F32)[:, None] * x.astype(_F32)
        stats = jnp.stack([jnp.mean(end_decay),
                           jnp.max(jnp.abs(carries))]).astype(_F32)
        return (y.astype(x.dtype), stats), (x, dt, a_log, b, c, d, carries)


@functools.partial(jax.jit, static_argnums=(0,))
def _ssd_bwd(chunk, residuals, cotangents):
    x, dt, a_log, b, c, d, carries = residuals
    dy = cotangents[0]              # the scan's two statistics have none
    dtype = x.dtype
    with jax.named_scope(SCOPE):
        xh, dth, bc, cc, cum, a = _by_chunk(x, dt, a_log, b, c, chunk)
        batch, chunks, heads, _, width = xh.shape
        dyh = dy.reshape(batch, chunks, chunk, heads, width).transpose(
            0, 1, 3, 2, 4).astype(dtype)
        xh32, dyh32 = xh.astype(_F32), dyh.astype(_F32)
        xdt = (xh32 * dth[..., None]).astype(dtype)
        xdt32 = xdt.astype(_F32)
        within = _within(cum)
        scores = _einsum("bcin,bcjn->bcij", cc, bc)
        from_start, to_end = jnp.exp(cum), jnp.exp(cum[..., -1:] - cum)
        end_decay = jnp.exp(cum[..., -1])
        carries16 = carries.astype(dtype)

        # the carried state: y_i has e_i C_i . carry, the end state has
        # end_decay x carry; its gradient goes back a chunk at a time
        dy_decayed = (dyh32 * from_start[..., None]).astype(dtype)
        d_ends = _carried_back(
            _einsum("bchip,bcin->bchpn", dy_decayed, cc), end_decay)
        d_ends16 = d_ends.astype(dtype)

        # y_i = sum_j (L o G)_ij xdt_j, end = sum_j f_j xdt_j (x) B_j
        mixed = (within * scores[:, :, None]).astype(dtype)
        xdt_to_end = (xdt32 * to_end[..., None]).astype(dtype)
        end_to_x = _einsum("bchpn,bcjn->bchjp", d_ends16, bc)
        d_xdt = (_einsum("bchij,bchip->bchjp", mixed, dyh)
                 + to_end[..., None] * end_to_x)
        d_mixed = _einsum("bchip,bchjp->bchij", dyh, xdt)
        d_scores = jnp.sum(d_mixed * within, axis=2).astype(dtype)
        d_c = (_einsum("bcij,bcjn->bcin", d_scores, bc)
               + _einsum("bchip,bchpn->bcin", dy_decayed, carries16))
        d_b = (_einsum("bcij,bcin->bcjn", d_scores, cc)
               + _einsum("bchjp,bchpn->bcjn", xdt_to_end, d_ends16))

        # cum, through every exp it stands in: L_ij (rows less columns),
        # exp(cum_i) before the carry, exp(cum_end - cum_j) and exp(cum_end)
        through = d_mixed * within * scores[:, :, None]
        carried = from_start[..., None] * _einsum(
            "bcin,bchpn->bchip", cc, carries16)
        ending = to_end * jnp.sum(xdt32 * end_to_x, axis=-1)
        d_cum = (jnp.sum(through, axis=-1) - jnp.sum(through, axis=-2)
                 + jnp.sum(dyh32 * carried, axis=-1) - ending)
        d_cum_end = (jnp.sum(ending, axis=-1)
                     + end_decay * jnp.sum(d_ends * carries, axis=(-1, -2)))
        d_cum = d_cum.at[..., -1].add(d_cum_end)
        # cum_i = sum_(j <= i) dt_j A: each dt_j A gathers the later cums
        d_a = jnp.flip(jnp.cumsum(jnp.flip(d_cum, -1), axis=-1), -1)

        d_dt = d_a * a[:, None] + jnp.sum(d_xdt * xh32, axis=-1)
        d_x = _seq_major(d_xdt * dth[..., None]) \
            + d.astype(_F32)[:, None] * dy.astype(_F32)
        # A = -exp(A_log): dA/dA_log = A
        d_a_log = a * jnp.sum(d_a * dth, axis=(0, 1, 3))
        d_d = jnp.sum(dy.astype(_F32) * x.astype(_F32), axis=(0, 1, 3))
        return (d_x.astype(dtype),
                d_dt.transpose(0, 1, 3, 2).reshape(dt.shape).astype(dt.dtype),
                d_a_log.astype(a_log.dtype),
                d_b.reshape(b.shape).astype(b.dtype),
                d_c.reshape(c.shape).astype(c.dtype), d_d.astype(d.dtype))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_counted(x, dt, a_log, b, c, d, chunk: int) -> Tuple[jax.Array,
                                                            jax.Array]:
    """The state-space scan, and how much state crossed its chunks.

    Args:
        x: (B, S, H, P), in the compute dtype.
        dt: (B, S, H) float32, positive (after its softplus).
        a_log: (H,) float32; ``A = -exp(a_log)``, one scalar a head.
        b, c: (B, S, N), in the compute dtype: one group, every head's.
        d: (H,) float32, the skip ``D x``.
        chunk: positions a chunk; ``S`` is whole chunks of it.

    Returns ``(y, stats)``: ``y`` (B, S, H, P) in ``x``'s dtype; ``stats``
    float32, one value a field of ``telemetry.STEP_STAT_FIELDS["ssm_scan"]``:
    the mean over rows, chunks and heads of a chunk's whole decay
    ``exp(cum_end)`` (the share of a chunk's starting state that reaches
    its end) and the largest ``|carry|``. ``stats`` has no gradient. A
    sequence that is not whole chunks is refused (``_by_chunk``)."""
    return _ssd(x, dt, a_log, b, c, d, chunk)


def ssd(x, dt, a_log, b, c, d, chunk: int) -> jax.Array:
    """:func:`ssd_counted`'s ``y``."""
    return ssd_counted(x, dt, a_log, b, c, d, chunk)[0]


# -- the passes beside the scan ------------------------------------------------
# Plain ``jax.numpy`` under the scope, float32 inside; the backward is
# autodiff's. Jitted for the scope's sake, as the scan.


@jax.jit
def causal_conv_silu(x, weight, bias):
    """``silu`` of a depthwise causal convolution: ``x`` (B, S, C),
    ``weight`` (K, C) float32, ``bias`` (C,) float32; position t sees
    ``x_(t-K+1) .. x_t`` under ``weight[0] .. weight[K-1]`` (a
    ``Conv1d(C, C, K, groups=C, padding=K-1)`` cut to S)."""
    with jax.named_scope(SCOPE):
        taps, seq = weight.shape[0], x.shape[1]
        padded = jnp.pad(x.astype(_F32), ((0, 0), (taps - 1, 0), (0, 0)))
        out = bias.astype(_F32) + sum(
            weight[k].astype(_F32) * padded[:, k:k + seq]
            for k in range(taps))
        return jax.nn.silu(out).astype(x.dtype)


@functools.partial(jax.jit, static_argnums=(3,))
def gated_rms_norm(y, z, scale, eps: float):
    """``RMSNorm(y x silu(z)) x scale`` over the whole last axis (one
    group), float32 inside."""
    with jax.named_scope(SCOPE):
        gated = y.astype(_F32) * jax.nn.silu(z.astype(_F32))
        normed = gated * jax.lax.rsqrt(
            jnp.mean(gated * gated, axis=-1, keepdims=True) + eps)
        return (normed * scale).astype(y.dtype)
