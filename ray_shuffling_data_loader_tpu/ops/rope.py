"""The placing of an attention half's q or k heads: each head's RMSNorm
(where the configuration norms them) and its rotary, over a projection
``x`` (B, S, heads x D) as the product left it.

Per head, float32 from the read to the write::

    n   = x * rsqrt(mean(x^2) + eps) * scale          (n = x without a scale)
    out = n * cos + turned(n) * sin

with ``turned(n) = concat(-n2, n1, 0)`` for ``n = concat(n1, n2, rest)``,
n1 and n2 the halves of the first ``rotated`` of the head's D lanes (the
rotate-half convention; the rest passes under cos 1, sin 0), and ``cos``,
``sin`` (S, D) float32 by position.

On the chip, where :func:`place_takes` the shapes, a Pallas kernel each way
(:func:`places_in_vmem`): a grid step reads a block of rows by a group of
whole heads once and writes it once, and the rotation is two rolls of the
lanes against tables that carry its signs and its range, so that one body
serves every ``rotated <= D`` and two heads of 64 side by side in a
register. Backward one kernel reads ``x`` and ``dy`` and writes ``dx`` with
the scale's gradient as sums a block, which XLA finishes; it needs nothing
the forward made. Everywhere else the decoder's own passes
(``models/mellum.py``: ``_head_norm`` then ``_rope``), which round to x's
dtype once more, between the two; :func:`placed_plain` is what the kernels
compute, written out, and their oracle (tests/test_rope.py, chip_smoke.py).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_shuffling_data_loader_tpu.ops import on_tpu
from ray_shuffling_data_loader_tpu.runtime import telemetry

#: The name a device trace shows the rotary under, XLA's passes and the
#: kernels (which hold the heads' norms too).
SCOPE = telemetry.step_scope("rsdl.lm.rope")

_F32 = jnp.float32
_LANES, _SUBLANES = 128, 8
#: Rows the kernels' inner loop takes at a time: whole tiles of bfloat16
#: (16 rows) and of float32 (8), four float32 registers a head.
_STRIP = 32
#: The most rows and lanes of a grid step's block: 1 MB of bfloat16, so
#: that ``x``, ``dy`` and ``dx`` twice buffered and a block's tables stay
#: under 10 MB of VMEM.
_BLOCK_ROWS, _BLOCK_LANES = 512, 1024
_VMEM_BYTES = 32 * 1024 * 1024


def placed_plain(x, heads: int, cos, sin, rotated: int, scale=None,
                 eps: float = 0.0):
    """The placing written out, float32 inside and rounded once: (B, S,
    heads x D) -> the same in ``x``'s dtype."""
    b, s, width = x.shape
    n = x.astype(_F32).reshape(b, s, heads, width // heads)
    if scale is not None:
        n = n * jax.lax.rsqrt(jnp.mean(n * n, axis=-1, keepdims=True)
                              + eps) * scale
    half = rotated // 2
    turned = jnp.concatenate(
        [-n[..., half:rotated], n[..., :half],
         jnp.zeros_like(n[..., rotated:])], axis=-1)
    out = n * cos[:, None, :] + turned * sin[:, None, :]
    return out.astype(x.dtype).reshape(b, s, width)


def _block(seq: int, width: int) -> Tuple[int, int]:
    """``(rows, lanes)`` of a grid step's block: the most whole strips that
    divide ``seq`` within ``_BLOCK_ROWS`` and the most whole registers that
    divide ``width`` within ``_BLOCK_LANES``; 0 where none does."""
    rows = max((n for n in range(_STRIP, min(seq, _BLOCK_ROWS) + 1, _STRIP)
                if seq % n == 0), default=0)
    lanes = max((n for n in range(_LANES, min(width, _BLOCK_LANES) + 1,
                                  _LANES) if width % n == 0), default=0)
    return rows, lanes


def place_takes(seq: int, width: int, head_dim: int, rotated: int,
                dtype) -> bool:
    """Whether the kernels below can place such heads: bfloat16 or float32,
    heads of 128 lanes or of 64 (two a register: the lanes a roll brings in
    from the neighbour are the ones the tables zero), an even ``rotated``
    within the head, a width of whole registers and a sequence of whole
    strips."""
    return (jnp.dtype(dtype) in (jnp.float32, jnp.bfloat16)
            and head_dim in (_LANES // 2, _LANES)
            and 0 < rotated <= head_dim and rotated % 2 == 0
            and width % head_dim == 0 and all(_block(seq, width)))


def places_in_vmem(seq: int, width: int, head_dim: int, rotated: int,
                   dtype) -> bool:
    """Whether q's or k's heads are placed by a Pallas kernel each way,
    from what the trace can see: on the TPU, where :func:`place_takes` the
    shapes."""
    return on_tpu() and place_takes(seq, width, head_dim, rotated, dtype)


def _tables(cos, sin, rotated: int, transposed: bool):
    """``(cos, ((shift, table), ...))``, (S, 128) float32 each, such that
    the rotation of a register ``n`` is ``n * cos + sum(roll(n, shift) *
    table)``: ``-sin`` on a head's lanes ``d < rotated / 2`` against the
    lanes ``rotated / 2`` above, ``sin`` on ``rotated / 2 <= d < rotated``
    against those below, zero elsewhere; one table where both rolls are the
    same (a whole head of 128 rotated). ``transposed``: the rotation's
    transpose, the opposite rolls of the products, as rolls of ``dy``
    against the tables rolled. Heads of 64 read their tables twice side by
    side."""
    dim, half = cos.shape[1], rotated // 2
    lane = jnp.arange(dim)
    pairs = [(-half, jnp.where(lane < half, -sin, 0.0)),
             (half, jnp.where((lane >= half) & (lane < rotated), sin, 0.0))]
    if transposed:
        pairs = [(-shift, jnp.roll(table, -shift, axis=1))
                 for shift, table in pairs]
    pairs = [(shift % _LANES, jnp.tile(table, (1, _LANES // dim)))
             for shift, table in pairs]
    if pairs[0][0] == pairs[1][0]:
        pairs = [(pairs[0][0], pairs[0][1] + pairs[1][1])]
    return jnp.tile(cos, (1, _LANES // dim)), tuple(pairs)


def _turned(n, cos, shifts, tables):
    acc = n * cos
    for shift, table in zip(shifts, tables):
        acc = acc + pltpu.roll(n, shift, 1) * table
    return acc


def _mean_matrix(dim: int):
    """(128, 128) bfloat16, ``1 / dim`` where row and column lie in the
    same head: ``v @ it`` is the mean of ``v`` over each head's lanes at
    every lane of the head (a power of two, exact in bfloat16)."""
    head = jnp.arange(_LANES) // dim
    return jnp.where(head[:, None] == head[None, :], 1.0 / dim,
                     0.0).astype(jnp.bfloat16)


def _head_mean(v, dim: int, matrix=None):
    """The mean of ``v`` (R, 128) float32 over each head's ``dim`` lanes,
    at every lane of the head: by a lane reduction, or with ``matrix``
    (:func:`_mean_matrix`) as three bfloat16 passes of the MXU, which has
    nothing else to do here (``v = hi + mid + low`` exactly, and the
    matrix is exact). On a v5e the forward kernel is bound by the lane
    reductions and runs 1.7 times faster with the product (0.78 against
    1.30 ms over 16,384 x 4,096), the backward one by its float32
    arithmetic and runs 1.7 times slower with it (2.64 against 1.57 ms:
    the split costs it nine more operations a value), so each takes its
    own (PERF.md section 6, PR 48)."""
    if matrix is not None:
        hi = v.astype(jnp.bfloat16)
        rest = v - hi.astype(_F32)
        mid = rest.astype(jnp.bfloat16)
        low = (rest - mid.astype(_F32)).astype(jnp.bfloat16)
        return sum(jnp.dot(part, matrix, preferred_element_type=_F32)
                   for part in (hi, mid, low))
    if dim == _LANES:
        return jnp.mean(v, axis=-1, keepdims=True)
    first = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1) < dim
    return jnp.where(
        first, jnp.sum(jnp.where(first, v, 0.0), axis=-1, keepdims=True),
        jnp.sum(jnp.where(first, 0.0, v), axis=-1, keepdims=True)) / dim


def _specs(rows: int, lanes: int, turns: int):
    """Block specs over the grid (B, row blocks, head groups), the groups
    innermost so that a row block's tables stay where they are while its
    groups go by: ``x`` (B, S, W) a block, ``scale`` (1, 128) and the
    means' ``matrix`` (128, 128) whole, the ``1 + turns`` tables (S, 128) by
    row block."""
    return dict(
        x=pl.BlockSpec((1, rows, lanes), lambda i, s, g: (i, s, g)),
        scale=pl.BlockSpec((1, _LANES), lambda i, s, g: (0, 0)),
        matrix=pl.BlockSpec((_LANES, _LANES), lambda i, s, g: (0, 0)),
        tables=(1 + turns) * [
            pl.BlockSpec((rows, _LANES), lambda i, s, g: (s, 0))])


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"),
    vmem_limit_bytes=_VMEM_BYTES)


def _tiled_scale(scale):
    return jnp.tile(scale.astype(_F32).reshape(1, -1),
                    (1, _LANES // scale.shape[-1]))


def _fwd_in_vmem(x, scale, cos, sin, rotated: int, eps: float,
                 interpret: bool, transposed: bool = False):
    """:func:`placed_plain` as one kernel (``scale`` None: the rotation
    alone; ``transposed``: its transpose, what the backward of heads
    without a norm is): a strip of rows of a register of lanes at a time
    widens to float32, is normed (the heads' mean squares on the MXU:
    :func:`_head_mean`), turned, cast and written; a strip's tables are
    read once for the heads of its group."""
    batch, seq, width = x.shape
    dim = cos.shape[1]
    rows, lanes = _block(seq, width)
    cos, pairs = _tables(cos, sin, rotated, transposed)
    shifts = tuple(shift for shift, _ in pairs)
    normed = scale is not None

    def kernel(x_ref, *refs):
        *table_refs, out_ref = refs
        if normed:
            scale_ref, matrix_ref, *table_refs = table_refs
            matrix = matrix_ref[...]

        def a_strip(i, carry):
            at = pl.ds(pl.multiple_of(i * _STRIP, _STRIP), _STRIP)
            cos_rows, *tables = [ref[at, :] for ref in table_refs]
            for first in range(0, lanes, _LANES):
                column = slice(first, first + _LANES)
                n = x_ref[0, at, column].astype(_F32)
                if normed:
                    n = n * jax.lax.rsqrt(_head_mean(n * n, dim, matrix) + eps
                                          ) * scale_ref[...]
                out_ref[0, at, column] = _turned(
                    n, cos_rows, shifts, tables).astype(out_ref.dtype)
            return carry

        jax.lax.fori_loop(0, rows // _STRIP, a_strip, 0)

    specs = _specs(rows, lanes, len(pairs))
    return pl.pallas_call(
        kernel, grid=(batch, seq // rows, width // lanes),
        in_specs=([specs["x"]] + normed * [specs["scale"], specs["matrix"]]
                  + specs["tables"]),
        out_specs=specs["x"],
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=_PARAMS, interpret=interpret,
    )(x, *([_tiled_scale(scale), _mean_matrix(dim)] if normed else []), cos,
      *(table for _, table in pairs))


def _bwd_in_vmem(x, scale, cos, sin, dy, rotated: int, eps: float,
                 interpret: bool):
    """``(dx, d scale)`` of normed heads as one kernel: ``d n`` by the
    rotation's transpose of ``dy``, ``xhat = x * r`` made again, ``dx =
    r * (g - xhat * mean(g * xhat))`` with ``g = d n * scale``, and ``d
    scale = sum d n * xhat`` down a block's rows and heads as one (8, 128)
    float32 tile a grid step, folded over blocks, sublanes and a
    register's heads once, outside."""
    batch, seq, width = x.shape
    dim = cos.shape[1]
    rows, lanes = _block(seq, width)
    cos, pairs = _tables(cos, sin, rotated, True)
    shifts = tuple(shift for shift, _ in pairs)

    def kernel(x_ref, dy_ref, scale_ref, *refs):
        *table_refs, dx_ref, sums_ref = refs

        def a_strip(i, sums):
            at = pl.ds(pl.multiple_of(i * _STRIP, _STRIP), _STRIP)
            cos_rows, *tables = [ref[at, :] for ref in table_refs]
            for first in range(0, lanes, _LANES):
                column = slice(first, first + _LANES)
                xf = x_ref[0, at, column].astype(_F32)
                r = jax.lax.rsqrt(_head_mean(xf * xf, dim) + eps)
                xhat = xf * r
                d_n = _turned(dy_ref[0, at, column].astype(_F32), cos_rows,
                              shifts, tables)
                g = d_n * scale_ref[...]
                dx_ref[0, at, column] = (
                    r * (g - xhat * _head_mean(g * xhat, dim))
                ).astype(dx_ref.dtype)
                fold = d_n * xhat
                for tile in range(0, _STRIP, _SUBLANES):
                    sums = sums + fold[tile:tile + _SUBLANES]
            return sums

        sums_ref[0, 0, 0] = jax.lax.fori_loop(
            0, rows // _STRIP, a_strip, jnp.zeros((_SUBLANES, _LANES), _F32))

    specs = _specs(rows, lanes, len(pairs))
    blocks = (batch, seq // rows, width // lanes)
    dx, sums = pl.pallas_call(
        kernel, grid=blocks,
        in_specs=[specs["x"], specs["x"], specs["scale"]] + specs["tables"],
        out_specs=[specs["x"],
                   pl.BlockSpec((1, 1, 1, _SUBLANES, _LANES),
                                lambda i, s, g: (i, s, g, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(blocks + (_SUBLANES, _LANES), _F32)],
        compiler_params=_PARAMS, interpret=interpret,
    )(x, dy, _tiled_scale(scale), cos, *(table for _, table in pairs))
    return dx, jnp.sum(sums.reshape(-1, _LANES // dim, dim),
                       axis=(0, 1)).astype(scale.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def placed_in_vmem(x, scale, cos, sin, rotated: int, eps: float,
                   interpret: bool):
    """:func:`placed_plain` by the kernels; ``scale`` None or (D,),
    ``cos`` and ``sin`` (S, D) float32 constants of the step (no gradient
    reaches them)."""
    return _placed_fwd(x, scale, cos, sin, rotated, eps, interpret)[0]


# Jitted for the scope's sake, as ``ops/sconv.py``'s passes: inside a
# program of its own the name reaches the compiled step as written.
@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _placed_fwd(x, scale, cos, sin, rotated, eps, interpret):
    with jax.named_scope(SCOPE):
        out = _fwd_in_vmem(x, scale, cos, sin, rotated, eps, interpret)
    # x is all the backward reads of what the step made (what the half's
    # checkpoint keeps or makes again already); without a norm not that
    return out, ((x if scale is not None else None), scale, cos, sin)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _placed_bwd(rotated, eps, interpret, residuals, dy):
    x, scale, cos, sin = residuals
    with jax.named_scope(SCOPE):
        if scale is None:
            return (_fwd_in_vmem(dy, None, cos, sin, rotated, eps, interpret,
                                 transposed=True), None, None, None)
        dx, d_scale = _bwd_in_vmem(x, scale, cos, sin, dy.astype(x.dtype),
                                   rotated, eps, interpret)
    return dx, d_scale, None, None


placed_in_vmem.defvjp(_placed_fwd, _placed_bwd)
