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

Two paths compute it, chosen by :func:`scans_in_vmem` from what the trace
can see. On the TPU, where a chunk and the state are whole 128-lane tiles,
the heads (of a width that divides 128) block into whole lanes and the
operands are bf16 or float32, six Pallas kernels a layer, Mamba-2's three
steps each way: the chunks' end states (:func:`_outer`), the carry across
them (:func:`_carries`), the chunks' outputs (:func:`_scanned`); backward
the carries' gradient (:func:`_outer`), :func:`_carried_back`, and
everything else in one pass (:func:`_scanned_back`). They make a chunk's
``C B^T`` once for its heads and each head's ``L o (C B^T)`` a (chunk,
chunk) float32 tile in VMEM that never reaches HBM, forward or backward,
and read ``x`` and write ``y`` and their gradients where the mixer has
them, (B, S, H x P), with no transpose on either side. Everywhere else
(every CPU run, a chunk of 8) XLA's einsums over the chunks, which send
the heads' ``L o (C B^T)``, (B, chunks, H, chunk, chunk), through HBM once
forward and three times backward: the fallback, and the kernels' oracle
(tests/test_ssd.py, chip_smoke.py). Both round alike and call the same
``_carries`` / ``_carried_back`` between their chunk-parallel halves (one
kernel with the chunk axis sequential where :func:`passes_in_vmem`, else a
loop of XLA's). The convolution has its own pair of kernels and its own
question, :func:`convs_in_vmem` (below, "the passes beside the scan").
What the three cost is read from a trace under ``SCOPE`` (``lm_ssm_pct``,
``lm_ssm_roofline_pct``).
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

#: The name a device trace shows the mixer's convolution, scan and gated
#: norm under (the projections around them are the decoder's).
SCOPE = telemetry.step_scope("rsdl.lm.ssm")

_F32 = jnp.float32


def _einsum(spec: str, *operands):
    """Operands as given (the compute dtype), accumulated in float32."""
    return jnp.einsum(spec, *operands, preferred_element_type=_F32)


def _carries(states, end_decay):
    """The state each chunk starts from, (B, chunks, H, P, N) float32:
    zero for the first, then ``end_decay_c x carry_c + states_c``, from
    each chunk's own end state ``states`` (B, chunks, H, P, N) and its
    whole decay ``end_decay`` (B, chunks, H)."""
    return _passed(states, end_decay, False)


def _carried_back(d_carries, end_decay):
    """The gradient of each chunk's END state, (B, chunks, H, P, N): zero
    for the last, then what the next chunk's carry hands back,
    ``d_carries_(c+1) + end_decay_(c+1) x d_end_(c+1)``."""
    return _passed(d_carries, end_decay, True)


def _passed(given, end_decay, reverse: bool):
    """What is held before each chunk, walking them in order (or last to
    first): zero, then ``end_decay_c x held + given_c``. One kernel on
    the chip (:func:`passes_in_vmem`), else a loop of XLA's."""
    if passes_in_vmem(*given.shape[2:]):
        return _passed_in_vmem(given, end_decay, reverse, False)

    def step(held, chunk):
        piece, decay = chunk
        return decay[..., None, None] * held + piece, held

    _, before = jax.lax.scan(
        step, jnp.zeros_like(given[:, 0]),
        (jnp.moveaxis(given, 1, 0), jnp.moveaxis(end_decay, 1, 0)),
        reverse=reverse)
    return jnp.moveaxis(before, 0, 1)


def _running(dt, a_log, chunk: int):
    """``(dth, cum (B, c, H, Q) float32, A (H,) float32)``: ``dt`` a
    head's row a chunk, and the running sum of ``dt A`` along it."""
    batch, seq, heads = dt.shape
    if seq % chunk:
        raise ValueError(f"a sequence of {seq} positions is not whole "
                         f"chunks of {chunk}")
    a = -jnp.exp(a_log.astype(_F32))
    dth = dt.astype(_F32).reshape(batch, seq // chunk, chunk,
                                  heads).transpose(0, 1, 3, 2)
    return dth, jnp.cumsum(dth * a[:, None], axis=-1), a


def _by_chunk(x, dt, a_log, b, c, chunk: int):
    """The operands a chunk at a time and a head at a time, with the
    running decay: ``(xh (B, c, H, Q, P), dth (B, c, H, Q) float32, bc,
    cc (B, c, Q, N), cum (B, c, H, Q) float32, A (H,) float32)``; ``x``
    (B, S, H x P) as the mixer has it."""
    dth, cum, a = _running(dt, a_log, chunk)
    batch, chunks, heads, _ = dth.shape
    xh = x.reshape(batch, chunks, chunk, heads, -1).transpose(0, 1, 3, 2, 4)
    bc, cc = (m.reshape(batch, chunks, chunk, -1) for m in (b, c))
    return xh, dth, bc, cc, cum, a


def _within(cum):
    """``L``: exp(cum_i - cum_j) for i >= j, else 0; (..., Q, Q) float32.
    Masked before the ``exp``: above the diagonal the difference is
    positive and may overflow."""
    size = cum.shape[-1]
    below = jnp.tril(jnp.ones((size, size), bool))
    return jnp.exp(jnp.where(below, cum[..., :, None] - cum[..., None, :],
                             -jnp.inf))


def _seq_major(yh):
    """(B, c, H, Q, P) -> (B, S, H x P)."""
    batch, chunks, heads, chunk, width = yh.shape
    return yh.transpose(0, 1, 3, 2, 4).reshape(batch, chunks * chunk,
                                               heads * width)


def _by_head(d, width: int):
    """A value a head, (H,), over the head's lanes of (B, S, H x P)."""
    return jnp.repeat(d.astype(_F32), width)


def _through_cum(d_cum, ending, d_ends, carries, end_decay, a, dth, d_xdt_x,
                 dt):
    """``(d dt`` in ``dt``'s shape and dtype, ``d a_log`` float32) from
    ``d cum`` (B, c, H, Q) short of its last position's own terms: the
    ``ending`` summed (``exp(cum_end - cum_j)`` holds ``cum_end``) and
    ``exp(cum_end)`` before the carry; ``d_xdt_x`` is ``d(dt x) . x``."""
    d_cum_end = (jnp.sum(ending, axis=-1)
                 + end_decay * jnp.sum(d_ends * carries, axis=(-1, -2)))
    d_cum = d_cum.at[..., -1].add(d_cum_end)
    # cum_i = sum_(j <= i) dt_j A: each dt_j A gathers the later cums
    d_a = jnp.flip(jnp.cumsum(jnp.flip(d_cum, -1), axis=-1), -1)
    d_dt = d_a * a[:, None] + d_xdt_x
    # A = -exp(A_log): dA/dA_log = A
    d_a_log = a * jnp.sum(d_a * dth, axis=(0, 1, 3))
    return (d_dt.transpose(0, 1, 3, 2).reshape(dt.shape).astype(dt.dtype),
            d_a_log)


def _skip_grad(x, dy, d):
    """``d D``: ``y`` holds ``D x`` a head."""
    heads = d.shape[0]
    return jnp.sum(
        (dy.astype(_F32) * x.astype(_F32)).reshape(-1, heads,
                                                   x.shape[-1] // heads),
        axis=(0, 2)).astype(d.dtype)


# -- the chunk's masked products in VMEM --------------------------------------
# Mamba-2's own three steps: the chunks' end states (one kernel, every chunk
# at once), the carry across the chunks (``_carries``, as on the einsum
# path), the chunks' outputs (one kernel); and the same three backwards.
# ``x``, ``y`` and their gradients are read and written where the mixer has
# them, (B, S, H x P), a block of ``_head_block`` heads' lanes a grid step;
# a (chunk, chunk) array lives in VMEM only.

_LANES, _SUBLANES = 128, 8
#: What a kernel may hold in VMEM: blocks of up to 2 MB, twice buffered, and
#: a tile's float32 temporaries; a v5e has 128 MiB.
_VMEM_BYTES = 64 * 1024 * 1024
#: Lanes of ``x`` a grid step takes where it can choose: 8 heads of 64.
_BLOCK_LANES = 512


def _head_block(heads: int, head_dim: int) -> int:
    """Heads a grid step takes: a count that divides ``heads``, fills
    whole lanes of ``x`` (heads of up to 128 lanes, side by side in a
    tile: :func:`_lane_group`) and whole sublanes of the heads' (heads,
    chunk) float32 rows, and whose three columns a head fit beside each
    other (:func:`_rows_of`); the most within ``_BLOCK_LANES``, else the
    fewest; 0 where no count does."""
    if head_dim < 1 or _LANES % head_dim:
        return 0
    group = _LANES // head_dim
    counts = [n for n in range(group, heads + 1, group)
              if heads % n == 0 and (n % _SUBLANES == 0 or n == heads)
              and 3 * (n // group) <= head_dim]
    within = [n for n in counts if n * head_dim <= _BLOCK_LANES]
    return max(within) if within else min(counts, default=0)


def vmem_takes(chunk: int, heads: int, head_dim: int, state: int,
               dtype) -> bool:
    """Whether the kernels below can compute such a scan: bfloat16 or
    float32, a chunk and a state of whole lanes, heads that block into
    whole lanes (:func:`_head_block`)."""
    return (jnp.dtype(dtype) in (jnp.float32, jnp.bfloat16)
            and chunk % _LANES == 0 and state % _LANES == 0
            and _head_block(heads, head_dim) > 0)


def scans_in_vmem(chunk: int, heads: int, head_dim: int, state: int,
                  dtype) -> bool:
    """Whether the scan keeps a head's ``L o (C B^T)`` tile in VMEM and
    not in HBM, from what the trace can see: on the TPU, where
    :func:`vmem_takes` the shapes."""
    return on_tpu() and vmem_takes(chunk, heads, head_dim, state, dtype)


def passes_in_vmem(heads: int, head_dim: int, state: int) -> bool:
    """Whether the carry crosses the chunks inside one kernel, the state
    held in VMEM, and not as a loop of XLA's with a launch a chunk: on the
    TPU, where a head's (P, N) float32 state is whole tiles."""
    return (on_tpu() and head_dim % _SUBLANES == 0 and state % _LANES == 0
            and _heads_passed(heads, head_dim, state) > 0)


#: Bytes of state a grid step of the carry's kernel holds at the most.
_PASS_BYTES = 2 * 1024 * 1024


def _heads_passed(heads: int, head_dim: int, state: int) -> int:
    """Heads whose states a grid step of the carry's kernel holds: the
    most that divide ``heads`` within ``_PASS_BYTES``."""
    return max((n for n in range(1, heads + 1) if heads % n == 0
                and n * head_dim * state * 4 <= _PASS_BYTES), default=0)


def _passed_in_vmem(given, end_decay, reverse: bool, interpret: bool):
    """:func:`_passed` as one kernel: the chunk axis of the grid is
    sequential and what is held stays in a VMEM scratch, so a chunk costs
    one read of ``given`` and one write, and no launch."""
    batch, chunks, heads, width, state = given.shape
    block = _heads_passed(heads, width, state)

    def at(c):
        return chunks - 1 - c if reverse else c

    def kernel(decay_ref, given_ref, before_ref, held):
        i, h, c = (pl.program_id(axis) for axis in range(3))

        @pl.when(c == 0)
        def _():
            held[...] = jnp.zeros_like(held)

        first = (i * chunks + at(c)) * heads + h * block

        def head(j, _):
            before_ref[0, 0, j] = held[j]
            held[j] = decay_ref[first + j] * held[j] + given_ref[0, 0, j]

        jax.lax.fori_loop(0, block, head, None)

    spec = pl.BlockSpec((1, 1, block, width, state),
                        lambda i, h, c: (i, at(c), h, 0, 0))
    return pl.pallas_call(
        kernel, grid=(batch, heads // block, chunks),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), spec],
        out_specs=spec, out_shape=jax.ShapeDtypeStruct(given.shape, _F32),
        scratch_shapes=[pltpu.VMEM((block, width, state), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(end_decay.astype(_F32).reshape(-1), given.astype(_F32))


def _dot(a, b, contract):
    """``a`` and ``b`` contracted over one axis each, float32 out."""
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=_F32)


_NN, _NT, _TN = (1, 0), (1, 1), (0, 0)     # a @ b, a @ b^T, a^T @ b


def _columns(dth, cum, block: int):
    """A head's ``cum`` and ``dt`` with the positions down the sublanes,
    which is how a kernel multiplies a (chunk, P) block's rows by them:
    (B, c, H / block, Q, 2 x block) float32, a head block's ``cum`` then
    its ``dt``. (Not (S, 1) columns: a 128-lane tile a value.)"""
    def turned(a):
        batch, chunks, heads, chunk = a.shape
        return a.reshape(batch, chunks, heads // block, block,
                         chunk).swapaxes(-1, -2)

    return jnp.concatenate([turned(cum), turned(dth)], axis=-1)


def _lane_group(width: int) -> int:
    """Heads side by side in one 128-lane tile of a (chunk, H x P) array
    (``width`` divides 128: :func:`_head_block`): the kernels take them
    together, so that every elementwise pass, load and store is of whole
    tiles."""
    return _LANES // width


def _rows_of(columns, block: int, width: int):
    """:func:`_scanned_back`'s ``columns`` (B, c, H / block, Q, 128) as its
    three (B, c, H, Q) arrays of rows: quantity ``q`` of the head ``k`` of
    lane group ``g`` of a block sits in lane ``k x P + q x groups + g``."""
    batch, chunks, blocks, chunk, _ = columns.shape
    group = _lane_group(width)
    groups = block // group
    packed = columns.reshape(batch, chunks, blocks, chunk, group,
                             width)[..., :3 * groups]
    packed = packed.reshape(batch, chunks, blocks, chunk, group, 3, groups)
    # (B, c, blocks, Q, k, q, g) -> (q, B, c, blocks, g, k, Q)
    return packed.transpose(5, 0, 1, 2, 6, 4, 3).reshape(
        3, batch, chunks, blocks * block, chunk)


class _Group:
    """What a kernel reads of lane group ``g`` of its block of heads, the
    group's heads side by side in (Q, lanes) float32 arrays: ``cum`` and
    ``dt`` spread over each head's lanes and the decays made of them."""

    def __init__(self, cols_ref, g: int, block: int, width: int):
        self.count = _lane_group(width)
        self.width = width
        self.lanes = self.count * width
        self.first = g * self.count
        self.at = slice(g * self.lanes, (g + 1) * self.lanes)
        cols = cols_ref[0, 0, 0]
        chunk = cols.shape[0]
        self.lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, self.lanes),
                                             1)
        self.cums = [cols[:, self.first + k:self.first + k + 1]
                     for k in range(self.count)]
        self.cum = self.spread(self.cums)
        self.dt = self.spread([
            cols[:, block + self.first + k:block + self.first + k + 1]
            for k in range(self.count)])

    def spread(self, values):
        """A value a head (a (Q, 1) column, or a scalar) over the head's
        lanes."""
        out = jnp.broadcast_to(values[0], self.lane.shape)
        for k in range(1, self.count):
            out = jnp.where(self.lane >= k * self.width, values[k], out)
        return out

    def skips(self, d_ref, first):
        """``D`` of the group's heads, scalars out of SMEM (``first``:
        the block's first head), over each head's lanes."""
        return self.spread([d_ref[first + self.first + k]
                            for k in range(self.count)])

    def only(self, k: int, a):
        """``a`` with every other head's lanes zeroed: a product over the
        group's lanes is then head ``k``'s."""
        if self.count == 1:
            return a
        mine = (self.lane >= k * self.width) & (
            self.lane < (k + 1) * self.width)
        return jnp.where(mine, a, jnp.zeros_like(a))

    @property
    def from_start(self):
        return jnp.exp(self.cum)

    @property
    def to_end(self):
        return jnp.exp(self.cum[-1:] - self.cum)

    def states(self, ref):
        """The heads' (P, N) states out of ``ref`` as one (lanes, N)
        operand."""
        states = ref[0, 0, self.first:self.first + self.count]
        return states.reshape(self.lanes, states.shape[-1])

    def sums(self, a):
        """Each head's sum over its lanes, left in the head's LAST lane
        (the others hold partial sums): log2(P) rolls of whole tiles."""
        if self.count == 1:
            return jnp.broadcast_to(jnp.sum(a, axis=1, keepdims=True),
                                    a.shape)
        shift = self.width // 2
        while shift:
            a = a + pltpu.roll(a, shift, 1)
            shift //= 2
        return a


def _within_tile(cum, rows_ref, j: int, below):
    """``L`` of head ``j`` of the block, (Q, Q) float32, from its ``cum``
    as a (Q, 1) column and as a row; masked before the ``exp``."""
    return jnp.exp(jnp.where(below, cum - rows_ref[0, 0, j:j + 1],
                             -jnp.inf))


def _below(chunk: int):
    at = lambda axis: jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), axis)
    return at(0) >= at(1)


def _specs(batch, chunks, chunk, heads, width, state, block):
    """Block specs over the grid (B, chunks, head blocks), by what they
    cut: ``x`` (B, S, H x P), ``cols`` (:func:`_columns`), ``rows``
    (B, c, H, Q), ``group`` (B, S, N), ``states`` (B, c, H, P, N)."""
    return dict(
        x=pl.BlockSpec((1, chunk, block * width), lambda i, c, h: (i, c, h)),
        cols=pl.BlockSpec((1, 1, 1, chunk, 2 * block),
                          lambda i, c, h: (i, c, h, 0, 0)),
        out_cols=pl.BlockSpec((1, 1, 1, chunk, _LANES),
                              lambda i, c, h: (i, c, h, 0, 0)),
        rows=pl.BlockSpec((1, 1, block, chunk), lambda i, c, h: (i, c, h, 0)),
        group=pl.BlockSpec((1, chunk, state), lambda i, c, h: (i, c, 0)),
        states=pl.BlockSpec((1, 1, block, width, state),
                            lambda i, c, h: (i, c, h, 0, 0)),
        scalars=pl.BlockSpec(memory_space=pltpu.SMEM))


def _params(sequential: bool):
    """A three-axis grid's, its last axis (the scan's head blocks, the
    convolution's sequence blocks) walked in order or not."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel",
                             "arbitrary" if sequential else "parallel"),
        vmem_limit_bytes=_VMEM_BYTES)


def _outer(v, cols, group, chunk: int, block: int, ending: bool,
           interpret: bool):
    """Every head's ``(decay o v)^T @ group`` a chunk, (B, c, H, P, N)
    float32, with no (Q, Q) array in it. ``ending``: a chunk's own end
    state, ``v`` the mixer's ``x`` (``dt x`` is made here) under
    ``exp(cum_end - cum)`` and ``group`` B; else the carries' gradient,
    ``v`` the output's cotangent under ``exp(cum)`` and ``group`` C."""
    batch, seq, lanes = v.shape
    heads = cols.shape[2] * block
    width, state, chunks = lanes // heads, group.shape[-1], seq // chunk
    dtype = v.dtype

    def kernel(v_ref, cols_ref, group_ref, out_ref):
        for g in range(block // _lane_group(width)):
            heads_ = _Group(cols_ref, g, block, width)
            v32 = v_ref[0, :, heads_.at].astype(_F32)
            if ending:
                scaled = ((v32 * heads_.dt).astype(dtype).astype(_F32)
                          * heads_.to_end)
            else:
                scaled = v32 * heads_.from_start
            out_ref[0, 0, heads_.first:heads_.first + heads_.count] = _dot(
                scaled.astype(dtype), group_ref[0], _TN).reshape(
                    heads_.count, width, state)

    specs = _specs(batch, chunks, chunk, heads, width, state, block)
    return pl.pallas_call(
        kernel, grid=(batch, chunks, heads // block),
        in_specs=[specs["x"], specs["cols"], specs["group"]],
        out_specs=specs["states"],
        out_shape=jax.ShapeDtypeStruct(
            (batch, chunks, heads, width, state), _F32),
        compiler_params=_params(False), interpret=interpret,
    )(v, cols, group)


def _scanned(x, cols, rows, b, c, carries, d, chunk: int, block: int,
             interpret: bool):
    """``y`` (B, S, H x P) from the carries: a chunk's ``C B^T`` made
    once for its heads, then a head at a time ``L o (C B^T)`` in VMEM, its
    product with ``dt x``, what the carried state gives and ``D x``."""
    batch, seq, lanes = x.shape
    heads = rows.shape[2]
    width, state, chunks = lanes // heads, b.shape[-1], seq // chunk
    dtype = x.dtype

    def kernel(x_ref, cols_ref, rows_ref, b_ref, c_ref, carries_ref, d_ref,
               y_ref, scores):
        first = pl.program_id(2) * block

        @pl.when(first == 0)
        def _():
            scores[...] = _dot(c_ref[0], b_ref[0], _NT)

        below = _below(chunk)
        for g in range(block // _lane_group(width)):
            heads_ = _Group(cols_ref, g, block, width)
            x32 = x_ref[0, :, heads_.at].astype(_F32)
            xdt = (x32 * heads_.dt).astype(dtype)
            y = 0.0
            for k in range(heads_.count):
                mixed = (_within_tile(heads_.cums[k], rows_ref,
                                      heads_.first + k, below)
                         * scores[...]).astype(dtype)
                y = y + _dot(mixed, heads_.only(k, xdt), _NN)
            y = y + heads_.from_start * _dot(
                c_ref[0], heads_.states(carries_ref).astype(dtype), _NT)
            y_ref[0, :, heads_.at] = (
                y + x32 * heads_.skips(d_ref, first)).astype(y_ref.dtype)

    specs = _specs(batch, chunks, chunk, heads, width, state, block)
    return pl.pallas_call(
        kernel, grid=(batch, chunks, heads // block),
        in_specs=[specs["x"], specs["cols"], specs["rows"], specs["group"],
                  specs["group"], specs["states"], specs["scalars"]],
        out_specs=specs["x"],
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((chunk, chunk), _F32)],
        compiler_params=_params(True), interpret=interpret,
    )(x, cols, rows, b, c, carries, d)


def _scanned_back(x, dy, cols, rows, b, c, carries, d_ends, d, chunk: int,
                  block: int, interpret: bool):
    """The chunk scan's backward, a (chunk, head) tile at a time: ``L``
    and ``L o (C B^T)`` made again in VMEM, ``d(dt x)`` from them and from
    the end states' gradient, ``d(C B^T)`` summed over the chunk's heads
    in a float32 scratch and turned into ``dB`` and ``dC`` once a chunk.

    Returns ``(d_x (B, S, H x P), d_b, d_c (B, S, N), minus_columns
    (B, c, H, Q), columns (B, c, H / block, Q, 128))``: of ``dM o L o C
    B^T`` the column sums, negated, as rows; and as columns
    (:func:`_rows_of` has where), a head's three: its row sums plus ``dy
    . (exp(cum) C . carry)``, the ending's ``exp(cum_end - cum) (dt x . B
    d_end)`` and ``d(dt x) . x``, which is all ``d cum`` and ``d dt``
    need."""
    batch, seq, lanes = x.shape
    heads = rows.shape[2]
    width, state, chunks = lanes // heads, b.shape[-1], seq // chunk
    dtype = x.dtype
    groups = block // _lane_group(width)

    def kernel(x_ref, dy_ref, cols_ref, rows_ref, b_ref, c_ref, carries_ref,
               d_ends_ref, d_ref, dx_ref, db_ref, dc_ref, minus_ref,
               columns_ref, scores, d_scores, d_b, d_c):
        first = pl.program_id(2) * block

        @pl.when(first == 0)
        def _():
            scores[...] = _dot(c_ref[0], b_ref[0], _NT)
            d_scores[...] = jnp.zeros_like(d_scores)
            d_b[...] = jnp.zeros_like(d_b)
            d_c[...] = jnp.zeros_like(d_c)

        below = _below(chunk)
        columns = jnp.zeros((chunk, _LANES), _F32)
        for g in range(groups):
            heads_ = _Group(cols_ref, g, block, width)
            from_start, to_end = heads_.from_start, heads_.to_end
            x32 = x_ref[0, :, heads_.at].astype(_F32)
            xdt = (x32 * heads_.dt).astype(dtype)
            xdt32 = xdt.astype(_F32)
            dy16 = dy_ref[0, :, heads_.at].astype(dtype)
            dy32 = dy16.astype(_F32)
            carry = heads_.states(carries_ref).astype(dtype)
            d_end = heads_.states(d_ends_ref).astype(dtype)

            end_to_x = _dot(b_ref[0], d_end, _NT)
            d_xdt = 0.0
            # dy . (exp(cum) C . carry), and to it each head's row sums
            rows_and_carried = dy32 * (from_start * _dot(c_ref[0], carry,
                                                          _NT))
            for k in range(heads_.count):
                j = heads_.first + k
                within = _within_tile(heads_.cums[k], rows_ref, j, below)
                mixed = (within * scores[...]).astype(dtype)
                d_xdt = d_xdt + _dot(mixed, heads_.only(k, dy16), _TN)
                d_within = _dot(heads_.only(k, dy16), xdt, _NT) * within
                d_scores[...] += d_within
                through = d_within * scores[...]
                minus_ref[0, 0, j:j + 1] = -jnp.sum(through, axis=0,
                                                    keepdims=True)
                rows_and_carried = rows_and_carried + heads_.only(
                    k, _folded(through, width))
            d_xdt = d_xdt + to_end * end_to_x
            dx_ref[0, :, heads_.at] = (
                d_xdt * heads_.dt
                + dy32 * heads_.skips(d_ref, first)).astype(dtype)
            # a head's sum sits in its last lane: to lane q x groups + g
            for q, summed in enumerate((
                    heads_.sums(rows_and_carried),
                    to_end * heads_.sums(xdt32 * end_to_x),
                    heads_.sums(d_xdt * x32))):
                to = q * groups + g
                placed = pltpu.roll(summed, (to - (width - 1)) % _LANES, 1)
                columns = jnp.where(heads_.lane % width == to, placed,
                                    columns)
            d_c[...] += _dot((dy32 * from_start).astype(dtype), carry, _NN)
            d_b[...] += _dot((xdt32 * to_end).astype(dtype), d_end, _NN)
        columns_ref[0, 0, 0] = columns

        @pl.when(first + block == heads)
        def _():
            d_scores16 = d_scores[...].astype(dtype)
            dc_ref[0] = (_dot(d_scores16, b_ref[0], _NN)
                         + d_c[...]).astype(dc_ref.dtype)
            db_ref[0] = (_dot(d_scores16, c_ref[0], _TN)
                         + d_b[...]).astype(db_ref.dtype)

    specs = _specs(batch, chunks, chunk, heads, width, state, block)
    return pl.pallas_call(
        kernel, grid=(batch, chunks, heads // block),
        in_specs=[specs["x"], specs["x"], specs["cols"], specs["rows"],
                  specs["group"], specs["group"], specs["states"],
                  specs["states"], specs["scalars"]],
        out_specs=[specs["x"], specs["group"], specs["group"], specs["rows"],
                   specs["out_cols"]],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, dtype),
            jax.ShapeDtypeStruct(b.shape, b.dtype),
            jax.ShapeDtypeStruct(c.shape, c.dtype),
            jax.ShapeDtypeStruct(rows.shape, _F32),
            jax.ShapeDtypeStruct(cols.shape[:-1] + (_LANES,), _F32)],
        scratch_shapes=[pltpu.VMEM((chunk, chunk), _F32),
                        pltpu.VMEM((chunk, chunk), _F32),
                        pltpu.VMEM((chunk, state), _F32),
                        pltpu.VMEM((chunk, state), _F32)],
        compiler_params=_params(True), interpret=interpret,
    )(x, dy, cols, rows, b, c, carries, d_ends, d)


def _folded(a, width: int):
    """(Q, Q) -> (Q, 128): the row sums of ``a`` as partial sums, every
    run of ``width`` lanes summing to its row's: whole tiles added, then
    halves rolled onto each other."""
    out = a[:, :_LANES]
    for at in range(_LANES, a.shape[1], _LANES):
        out = out + a[:, at:at + _LANES]
    span = _LANES // 2
    while span >= width:
        out = out + pltpu.roll(out, span, 1)
        span //= 2
    return out


def _fwd_in_vmem(x, dt, a_log, b, c, d, chunk: int):
    """``(y, end_decay, carries)`` by the kernels."""
    interpret = not on_tpu()
    heads = dt.shape[-1]
    block = _head_block(heads, x.shape[-1] // heads)
    dth, cum, _ = _running(dt, a_log, chunk)
    cols = _columns(dth, cum, block)
    end_decay = jnp.exp(cum[..., -1])
    carries = _carries(_outer(x, cols, b, chunk, block, True, interpret),
                       end_decay)
    y = _scanned(x, cols, cum, b, c, carries, d.astype(_F32), chunk, block,
                 interpret)
    return y, end_decay, carries


def _bwd_in_vmem(x, dt, a_log, b, c, d, carries, dy, chunk: int):
    """The six gradients by the kernels; what is left to ``jax.numpy``
    works on (B, c, H, Q) arrays."""
    interpret = not on_tpu()
    heads = dt.shape[-1]
    width = x.shape[-1] // heads
    block = _head_block(heads, width)
    dth, cum, a = _running(dt, a_log, chunk)
    cols = _columns(dth, cum, block)
    end_decay = jnp.exp(cum[..., -1])
    d_ends = _carried_back(
        _outer(dy, cols, c, chunk, block, False, interpret), end_decay)
    d_x, d_b, d_c, minus_columns, columns = _scanned_back(
        x, dy, cols, cum, b, c, carries, d_ends, d.astype(_F32), chunk,
        block, interpret)
    rows_and_carried, ending, d_xdt_x = _rows_of(columns, block, width)
    d_dt, d_a_log = _through_cum(
        rows_and_carried + minus_columns - ending, ending, d_ends, carries,
        end_decay, a, dth, d_xdt_x, dt)
    return (d_x, d_dt, d_a_log.astype(a_log.dtype), d_b, d_c,
            _skip_grad(x, dy, d))


# -- the scan, by either path -------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd(x, dt, a_log, b, c, d, chunk, in_vmem):
    return _ssd_fwd(x, dt, a_log, b, c, d, chunk, in_vmem)[0]


# Jitted for the scope's sake (models/mellum.py:_swiglu_fwd): inside a
# program of its own the name reaches the compiled step as written, the
# kernels' calls with it.
@functools.partial(jax.jit, static_argnums=(6, 7))
def _ssd_fwd(x, dt, a_log, b, c, d, chunk, in_vmem):
    with jax.named_scope(SCOPE):
        if in_vmem:
            y, end_decay, carries = _fwd_in_vmem(x, dt, a_log, b, c, d, chunk)
        else:
            y, end_decay, carries = _fwd_einsums(x, dt, a_log, b, c, d, chunk)
        stats = jnp.stack([jnp.mean(end_decay),
                           jnp.max(jnp.abs(carries))]).astype(_F32)
        return (y, stats), (x, dt, a_log, b, c, d, carries)


def _fwd_einsums(x, dt, a_log, b, c, d, chunk: int):
    """``(y, end_decay, carries)`` by XLA's einsums over the chunks."""
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
    y = _seq_major(yh) + _by_head(d, xh.shape[-1]) * x.astype(_F32)
    return y.astype(x.dtype), end_decay, carries


@functools.partial(jax.jit, static_argnums=(0, 1))
def _ssd_bwd(chunk, in_vmem, residuals, cotangents):
    x, dt, a_log, b, c, d, carries = residuals
    # the scan's two statistics have no cotangent
    dy = cotangents[0].astype(x.dtype)
    with jax.named_scope(SCOPE):
        if in_vmem:
            return _bwd_in_vmem(x, dt, a_log, b, c, d, carries, dy, chunk)
        return _bwd_einsums(x, dt, a_log, b, c, d, carries, dy, chunk)


def _bwd_einsums(x, dt, a_log, b, c, d, carries, dy, chunk: int):
    """The six gradients by XLA's einsums over the chunks."""
    dtype = x.dtype
    xh, dth, bc, cc, cum, a = _by_chunk(x, dt, a_log, b, c, chunk)
    batch, chunks, heads, _, width = xh.shape
    dyh = dy.reshape(batch, chunks, chunk, heads, width).transpose(
        0, 1, 3, 2, 4)
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
    d_dt, d_a_log = _through_cum(
        d_cum, ending, d_ends, carries, end_decay, a, dth,
        jnp.sum(d_xdt * xh32, axis=-1), dt)
    d_x = _seq_major(d_xdt * dth[..., None]) \
        + _by_head(d, width) * dy.astype(_F32)
    return (d_x.astype(dtype), d_dt, d_a_log.astype(a_log.dtype),
            d_b.reshape(b.shape).astype(b.dtype),
            d_c.reshape(c.shape).astype(c.dtype), _skip_grad(x, dy, d))


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
    sequence that is not whole chunks is refused (``_running``). Where
    :func:`scans_in_vmem` the kernels compute it, else XLA's einsums."""
    y, stats = _ssd(x.reshape(*dt.shape[:2], -1), dt, a_log, b, c, d, chunk,
                    scans_in_vmem(chunk, *x.shape[2:], b.shape[-1], x.dtype))
    return y.reshape(x.shape), stats


def ssd(x, dt, a_log, b, c, d, chunk: int) -> jax.Array:
    """:func:`ssd_counted`'s ``y``."""
    return ssd_counted(x, dt, a_log, b, c, d, chunk)[0]


# -- the passes beside the scan ------------------------------------------------
# Under the scope, float32 inside, jitted for the scope's sake, as the scan.
# The convolution is a pair of Pallas kernels with a written backward where
# :func:`convs_in_vmem` (the benchmark's cells), else :func:`conv_silu`'s
# plain ``jax.numpy`` with autodiff's backward, which is also the kernels'
# oracle (tests/test_ssd.py, chip_smoke.py); the gated norm is plain
# ``jax.numpy`` and its backward autodiff's.


def conv_silu(x, weight, bias):
    """``silu`` of a depthwise causal convolution: ``x`` (B, S, C),
    ``weight`` (K, C) float32, ``bias`` (C,) float32; position t sees
    ``x_(t-K+1) .. x_t`` under ``weight[0] .. weight[K-1]`` (a
    ``Conv1d(C, C, K, groups=C, padding=K-1)`` cut to S)."""
    taps, seq = weight.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(_F32), ((0, 0), (taps - 1, 0), (0, 0)))
    out = bias.astype(_F32) + sum(
        weight[k].astype(_F32) * padded[:, k:k + seq] for k in range(taps))
    return jax.nn.silu(out).astype(x.dtype)


#: Rows a grid step reads before its block (backward: after it too): a
#: bf16 tile's depth, so that a block starts on a tile in either dtype.
#: Its last (first) ``_SUBLANES`` rows are what the taps reach.
_HALO = 16
#: Positions and channels a grid step takes at the most, and the rows its
#: inner loop takes at a time (what is live between two stores stays in
#: vector registers, a 128-lane column at a time).
_CONV_ROWS, _CONV_LANES, _CONV_STRIP = 2048, 512, 32


def _conv_block(seq: int, channels: int) -> Tuple[int, int]:
    """``(rows, lanes)`` of a grid step's block: the most whole strips
    that divide ``seq`` and the most whole 128s that divide ``channels``,
    within ``_CONV_ROWS`` and ``_CONV_LANES``; 0 where none does."""
    def most(size, step, limit):
        return max((n for n in range(step, min(size, limit) + 1, step)
                    if size % n == 0), default=0)

    return (most(seq, _CONV_STRIP, _CONV_ROWS),
            most(channels, _LANES, _CONV_LANES))


def conv_takes(seq: int, channels: int, taps: int, dtype) -> bool:
    """Whether the kernels below can compute such a convolution: bfloat16
    or float32, channels of whole lanes, a sequence of whole blocks, taps
    that reach no further back than a tile (at most 8)."""
    return (jnp.dtype(dtype) in (jnp.float32, jnp.bfloat16)
            and 1 <= taps <= _SUBLANES and all(_conv_block(seq, channels)))


def convs_in_vmem(seq: int, channels: int, taps: int, dtype) -> bool:
    """Whether the convolution reads a block of ``x`` once, in a Pallas
    kernel each way, from what the trace can see: on the TPU, where
    :func:`conv_takes` the shapes. XLA's pad, shifted slices and autodiff
    otherwise."""
    return on_tpu() and conv_takes(seq, channels, taps, dtype)


def _reaching(cur, tail, taps: int):
    """``[x_t, x_(t-1), .. x_(t-K+1)]`` for the rows t of ``cur`` (R, L)
    float32, ``tail`` (8, L) the rows before them: shifted on float32
    tiles, down the sublanes."""
    joined = jnp.concatenate([tail, cur], axis=0)
    return [cur] + [pltpu.roll(joined, back, 0)[_SUBLANES:]
                    for back in range(1, taps)]


def _pre(reaching, w, bias):
    """``bias + sum_k w[k] x_(t-K+1+k)``, summed in :func:`conv_silu`'s
    order; ``w`` (K, L) and ``bias`` (1, L) float32."""
    taps = len(reaching)
    acc = w[0:1] * reaching[taps - 1]
    for k in range(1, taps):
        acc = acc + w[k:k + 1] * reaching[taps - 1 - k]
    return bias + acc


def _conv_specs(rows: int, lanes: int, taps: int):
    """Block specs over the grid (B, lane blocks, sequence blocks), by
    what they cut: ``x`` (B, S, C) a block, the ``_HALO`` rows ``before``
    it (the first block's: its own, unread) and ``after`` it (the last
    block's: its own), ``w`` (K, C), ``bias`` (1, C)."""
    halos = rows // _HALO
    return dict(
        x=pl.BlockSpec((1, rows, lanes), lambda i, l, s: (i, s, l)),
        before=pl.BlockSpec(
            (1, _HALO, lanes),
            lambda i, l, s: (i, jnp.maximum(s * halos - 1, 0), l)),
        after=lambda blocks: pl.BlockSpec(
            (1, _HALO, lanes),
            lambda i, l, s: (i, jnp.minimum(s + 1, blocks - 1) * halos, l)),
        w=pl.BlockSpec((taps, lanes), lambda i, l, s: (0, l)),
        bias=pl.BlockSpec((1, lanes), lambda i, l, s: (0, l)))


def _conv_fwd_in_vmem(x, weight, bias, interpret: bool):
    """:func:`conv_silu` as one kernel: a grid step reads its block of
    ``x`` once with the tile of rows before it, and a strip of rows at a
    time widens to float32, shifts, sums, ``silu``, casts and writes. No
    padded float32 copy of ``x`` reaches HBM."""
    batch, seq, channels = x.shape
    taps = weight.shape[0]
    rows, lanes = _conv_block(seq, channels)
    strip = _CONV_STRIP

    def kernel(x_ref, before_ref, w_ref, bias_ref, y_ref):
        w, b = w_ref[...], bias_ref[...]
        # zeros before a row's first position
        tail = jnp.where(pl.program_id(2) == 0, 0.0,
                         before_ref[0].astype(_F32)[_HALO - _SUBLANES:])

        def a_strip(n, tail):
            at = pl.ds(pl.multiple_of(n * strip, strip), strip)
            cur = x_ref[0, at, :].astype(_F32)
            pre = _pre(_reaching(cur, tail, taps), w, b)
            y_ref[0, at, :] = (pre * jax.nn.sigmoid(pre)).astype(y_ref.dtype)
            return cur[strip - _SUBLANES:]

        jax.lax.fori_loop(0, rows // strip, a_strip, tail)

    specs = _conv_specs(rows, lanes, taps)
    return pl.pallas_call(
        kernel, grid=(batch, channels // lanes, seq // rows),
        in_specs=[specs["x"], specs["before"], specs["w"], specs["bias"]],
        out_specs=specs["x"],
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=_params(True), interpret=interpret,
    )(x, x, weight.astype(_F32), bias.astype(_F32).reshape(1, channels))


def _conv_bwd_in_vmem(x, weight, bias, dy, interpret: bool):
    """``(d x, d weight, d bias)`` as one kernel, the strips of a block
    walked last to first: ``pre`` made again, ``d pre = dy silu'(pre)``,
    ``d x_t = sum_k w[k] d pre_(t+K-1-k)`` from the strip's own ``d pre``
    and the first rows of the strip after it (of the block after it: made
    from the tiles of ``x`` and ``dy`` read after the block; zeros after a
    row's last position), and the taps' and the bias's sums down the rows
    as whole (8, L) float32 tiles, which stay in VMEM across a row's
    sequence blocks (the output's block does not move) and are folded,
    over sublanes and rows of the batch, once, outside."""
    batch, seq, channels = x.shape
    taps = weight.shape[0]
    rows, lanes = _conv_block(seq, channels)
    strip, blocks = _CONV_STRIP, seq // rows
    strips = rows // strip

    def d_pre_of(cur, tail, dy_rows, w, b):
        reaching = _reaching(cur, tail, taps)
        pre = _pre(reaching, w, b)
        sig = jax.nn.sigmoid(pre)
        return dy_rows * (sig * (1.0 + pre * (1.0 - sig))), reaching

    def folded(a):
        """(R, L) -> (8, L): whole tiles added."""
        out = a[:_SUBLANES]
        for at in range(_SUBLANES, a.shape[0], _SUBLANES):
            out = out + a[at:at + _SUBLANES]
        return out

    def kernel(x_ref, dy_ref, before_ref, x_after_ref, dy_after_ref, w_ref,
               bias_ref, dx_ref, sums_ref):
        s = pl.program_id(2)
        w, b = w_ref[...], bias_ref[...]
        # d pre of the tile after the block
        head, _ = d_pre_of(
            x_after_ref[0].astype(_F32)[:_SUBLANES],
            x_ref[0, rows - _HALO:, :].astype(_F32)[_HALO - _SUBLANES:],
            dy_after_ref[0].astype(_F32)[:_SUBLANES], w, b)
        head = jnp.where(s == blocks - 1, 0.0, head)

        def a_strip(n, held):
            head, sums = held
            first = (strips - 1 - n) * strip
            at = pl.ds(pl.multiple_of(first, strip), strip)
            earlier = x_ref[0, pl.ds(pl.multiple_of(
                jnp.maximum(first - _HALO, 0), _HALO), _HALO), :]
            tail = jnp.where(first == 0, before_ref[0], earlier).astype(
                _F32)[_HALO - _SUBLANES:]
            tail = jnp.where((first == 0) & (s == 0), 0.0, tail)
            d_pre, reaching = d_pre_of(
                x_ref[0, at, :].astype(_F32), tail,
                dy_ref[0, at, :].astype(_F32), w, b)
            joined = jnp.concatenate([d_pre, head], axis=0)
            d_x = w[taps - 1:taps] * d_pre
            for ahead in range(1, taps):
                d_x = d_x + w[taps - 1 - ahead:taps - ahead] * pltpu.roll(
                    joined, strip + _SUBLANES - ahead, 0)[:strip]
            dx_ref[0, at, :] = d_x.astype(dx_ref.dtype)
            # d w[k] = sum_t d pre_t x_(t-K+1+k), then d b = sum_t d pre_t
            terms = [d_pre * reaching[taps - 1 - k] for k in range(taps)]
            sums = tuple(acc + folded(term)
                         for acc, term in zip(sums, terms + [d_pre]))
            return d_pre[:_SUBLANES], sums

        zeros = jnp.zeros((_SUBLANES, lanes), _F32)
        _, sums = jax.lax.fori_loop(0, strips, a_strip,
                                    (head, (zeros,) * (taps + 1)))

        @pl.when(s == 0)
        def _():
            sums_ref[...] = jnp.zeros_like(sums_ref)

        for k, acc in enumerate(sums):
            sums_ref[0, k] += acc

    specs = _conv_specs(rows, lanes, taps)
    d_x, sums = pl.pallas_call(
        kernel, grid=(batch, channels // lanes, blocks),
        in_specs=[specs["x"], specs["x"], specs["before"],
                  specs["after"](blocks), specs["after"](blocks), specs["w"],
                  specs["bias"]],
        out_specs=[specs["x"],
                   pl.BlockSpec((1, taps + 1, _SUBLANES, lanes),
                                lambda i, l, s: (i, 0, 0, l))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(
                       (batch, taps + 1, _SUBLANES, channels), _F32)],
        compiler_params=_params(True), interpret=interpret,
    )(x, dy, x, x, dy, weight.astype(_F32),
      bias.astype(_F32).reshape(1, channels))
    sums = jnp.sum(sums, axis=(0, 2))
    return (d_x, sums[:taps].astype(weight.dtype),
            sums[taps].astype(bias.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv_silu_in_vmem(x, weight, bias, scope):
    return _conv_silu_fwd(x, weight, bias, scope)[0]


# Jitted for the scope's sake, as the scan's two.
@functools.partial(jax.jit, static_argnums=(3,))
def _conv_silu_fwd(x, weight, bias, scope):
    with jax.named_scope(scope):
        return (_conv_fwd_in_vmem(x, weight, bias, not on_tpu()),
                (x, weight, bias))


@functools.partial(jax.jit, static_argnums=(0,))
def _conv_silu_bwd(scope, residuals, dy):
    x, weight, bias = residuals
    with jax.named_scope(scope):
        return _conv_bwd_in_vmem(x, weight, bias, dy.astype(x.dtype),
                                 not on_tpu())


_conv_silu_in_vmem.defvjp(_conv_silu_fwd, _conv_silu_bwd)


@functools.partial(jax.jit, static_argnums=(3,))
def _conv_silu_plain(x, weight, bias, scope):
    with jax.named_scope(scope):
        return conv_silu(x, weight, bias)


def causal_conv_silu(x, weight, bias, scope: str = SCOPE):
    """:func:`conv_silu` under a mixer's scope (this one's;
    ``ops/selective_scan.py`` gives its own): by the kernels where
    :func:`convs_in_vmem`, else as it is."""
    if convs_in_vmem(x.shape[1], x.shape[2], weight.shape[0], x.dtype):
        return _conv_silu_in_vmem(x, weight, bias, scope)
    return _conv_silu_plain(x, weight, bias, scope)


@functools.partial(jax.jit, static_argnums=(3,))
def gated_rms_norm(y, z, scale, eps: float):
    """``RMSNorm(y x silu(z)) x scale`` over the whole last axis (one
    group), float32 inside."""
    with jax.named_scope(SCOPE):
        gated = y.astype(_F32) * jax.nn.silu(z.astype(_F32))
        normed = gated * jax.lax.rsqrt(
            jnp.mean(gated * gated, axis=-1, keepdims=True) + eps)
        return (normed * scale).astype(y.dtype)
