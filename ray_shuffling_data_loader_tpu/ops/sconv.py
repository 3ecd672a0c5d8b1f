"""A gated short convolution (LFM2's ``conv`` operator, Liquid AI 2025):
what lies between the operator's two projections.

The first projection gives ``B | C | u`` side by side, (B, S, 3 x C). Per
channel, with ``v = B * u``::

    c_t = sum_k w[k] v_(t-K+1+k)        zeros before a row's first position
    y_t = C_t * c_t

a depthwise causal convolution of ``K`` taps (three, published) with
neither bias nor activation, between two multiplicative gates. No state, no
step, no scan: not a Mamba mixer. The products and the sum are float32
inside; ``y`` leaves in the operands' dtype.

On the chip, where :func:`conv_takes` the shapes, a Pallas kernel each way
(:func:`convs_in_vmem`): forward a grid step reads its block of ``B | C |
u`` once, with the tile of rows before it, and writes ``y``; backward one
pass reads the block and ``dy`` and writes ``d (B | C | u)`` as the one
array ``W_in``'s backward reads, with the taps' sums. A block is the whole
``3 x C`` width of a few hundred positions, walked a column of lanes at a
time, so that ``B``, ``C`` and ``u`` of a channel meet in one grid step;
the strips, the halo and the shift down the sublanes are ``ops/ssd.py``'s
depthwise convolution's. Everywhere else plain ``jax.numpy`` with
autodiff's backward (XLA's pad, shifted slices and products), which is
also the kernels' oracle (tests/test_lfm2.py, chip_smoke.py);
:func:`causal_gated_conv` holds that form's gates between barriers, so
that a trace shows the whole operator under ``SCOPE`` either way.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_shuffling_data_loader_tpu.ops import on_tpu, ssd
from ray_shuffling_data_loader_tpu.ops.ssd import (_CONV_STRIP, _HALO,
                                                   _SUBLANES, _reaching)
from ray_shuffling_data_loader_tpu.runtime import telemetry

#: The name a device trace shows the operator's gates and convolution
#: under (the projections around them are the decoder's).
SCOPE = telemetry.step_scope("rsdl.lm.sconv")

_F32 = jnp.float32


def gated_conv(bcu, weight):
    """``C * conv(B * u)``: ``bcu`` (B, S, 3 x C) holds ``B | C | u``,
    ``weight`` (K, C) float32; position t sees ``v_(t-K+1) .. v_t`` under
    ``weight[0] .. weight[K-1]`` (a ``Conv1d(C, C, K, groups=C,
    padding=K-1, bias=False)`` cut to S); (B, S, C) out in ``bcu``'s
    dtype."""
    taps, seq = weight.shape[0], bcu.shape[1]
    b, c, u = jnp.split(bcu.astype(_F32), 3, axis=-1)
    padded = jnp.pad(b * u, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(weight[k].astype(_F32) * padded[:, k:k + seq]
               for k in range(taps))
    return (c * conv).astype(bcu.dtype)


#: Bytes of ``B | C | u`` a grid step takes at the most: with the block of
#: ``d (B | C | u)`` and ``dy``, each twice buffered, 16 MB of VMEM.
_BLOCK_BYTES = 4 * 1024 * 1024


def _block(seq: int, channels: int, dtype) -> Tuple[int, int]:
    """``(rows, lanes)``: the positions of a grid step's block of the
    whole ``3 x channels`` width (the most whole strips that divide
    ``seq`` within ``_BLOCK_BYTES``) and the lanes of the column its inner
    loops take at a time (``ops/ssd.py``'s); 0 where none does."""
    limit = _BLOCK_BYTES // (3 * channels * jnp.dtype(dtype).itemsize)
    rows = max((n for n in range(_CONV_STRIP, min(seq, limit) + 1,
                                 _CONV_STRIP) if seq % n == 0), default=0)
    return rows, ssd._conv_block(seq, channels)[1]


def conv_takes(seq: int, channels: int, taps: int, dtype) -> bool:
    """Whether the kernels below can compute such an operator: bfloat16 or
    float32, channels of whole lanes, a sequence of whole blocks, taps
    that reach no further back than a tile (at most 8)."""
    return (jnp.dtype(dtype) in (jnp.float32, jnp.bfloat16)
            and 1 <= taps <= _SUBLANES and all(_block(seq, channels, dtype)))


def convs_in_vmem(seq: int, channels: int, taps: int, dtype) -> bool:
    """Whether the operator reads a block of ``B | C | u`` once, in a
    Pallas kernel each way, from what the trace can see: on the TPU, where
    :func:`conv_takes` the shapes."""
    return on_tpu() and conv_takes(seq, channels, taps, dtype)


def _conv_sum(reaching, w):
    """``sum_k w[k] v_(t-K+1+k)``, summed in :func:`gated_conv`'s order;
    ``w`` (K, L) float32."""
    taps = len(reaching)
    acc = w[0:1] * reaching[taps - 1]
    for k in range(1, taps):
        acc = acc + w[k:k + 1] * reaching[taps - 1 - k]
    return acc


def _params(sequential: bool):
    """The grid's (batch, sequence blocks), the second walked in order or
    not."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",
                             "arbitrary" if sequential else "parallel"),
        vmem_limit_bytes=ssd._VMEM_BYTES)


def _specs(rows: int, channels: int, taps: int):
    """Block specs over the grid (B, sequence blocks): ``bcu`` (B, S, 3C)
    a block of the whole width, the ``_HALO`` rows ``before`` it (the
    first block's: its own, unread) and ``after`` it (the last block's:
    its own), the same of an array one ``C`` wide (``y``, ``dy``), ``w``
    (K, C) whole."""
    halos = rows // _HALO

    def cut(width):
        return dict(
            block=pl.BlockSpec((1, rows, width), lambda i, s: (i, s, 0)),
            before=pl.BlockSpec(
                (1, _HALO, width),
                lambda i, s: (i, jnp.maximum(s * halos - 1, 0), 0)),
            after=lambda blocks: pl.BlockSpec(
                (1, _HALO, width),
                lambda i, s: (i, jnp.minimum(s + 1, blocks - 1) * halos, 0)))

    return dict(bcu=cut(3 * channels), y=cut(channels),
                w=pl.BlockSpec((taps, channels), lambda i, s: (0, 0)))


def _columns(channels: int, lanes: int):
    """A block's columns: the lanes of ``y`` and of ``B``, ``C`` and ``u``
    in ``B | C | u``, as static slices."""
    for at in range(0, channels, lanes):
        yield (slice(at, at + lanes),) + tuple(
            slice(part * channels + at, part * channels + at + lanes)
            for part in range(3))


def _fwd_in_vmem(bcu, weight, interpret: bool):
    """:func:`gated_conv` as one kernel: a grid step reads its block of
    ``B | C | u`` once with the tile of rows before it, and a strip of
    rows of a column at a time widens to float32, gates, shifts, sums,
    gates again, casts and writes."""
    batch, seq, width = bcu.shape
    channels, taps = width // 3, weight.shape[0]
    rows, lanes = _block(seq, channels, bcu.dtype)
    strip = _CONV_STRIP

    def kernel(bcu_ref, before_ref, w_ref, y_ref):
        first_block = pl.program_id(1) == 0
        for at_y, at_b, at_c, at_u in _columns(channels, lanes):
            w = w_ref[:, at_y]
            # zeros before a row's first position
            tail = jnp.where(
                first_block, 0.0,
                (before_ref[0, :, at_b].astype(_F32)
                 * before_ref[0, :, at_u].astype(_F32))[_HALO - _SUBLANES:])

            def a_strip(n, tail, w=w, at_y=at_y, at_b=at_b, at_c=at_c,
                        at_u=at_u):
                at = pl.ds(pl.multiple_of(n * strip, strip), strip)
                cur = (bcu_ref[0, at, at_b].astype(_F32)
                       * bcu_ref[0, at, at_u].astype(_F32))
                conv = _conv_sum(_reaching(cur, tail, taps), w)
                y_ref[0, at, at_y] = (bcu_ref[0, at, at_c].astype(_F32)
                                      * conv).astype(y_ref.dtype)
                return cur[strip - _SUBLANES:]

            jax.lax.fori_loop(0, rows // strip, a_strip, tail)

    specs = _specs(rows, channels, taps)
    return pl.pallas_call(
        kernel, grid=(batch, seq // rows),
        in_specs=[specs["bcu"]["block"], specs["bcu"]["before"], specs["w"]],
        out_specs=specs["y"]["block"],
        out_shape=jax.ShapeDtypeStruct((batch, seq, channels), bcu.dtype),
        compiler_params=_params(False), interpret=interpret,
    )(bcu, bcu, weight.astype(_F32))


def _bwd_in_vmem(bcu, weight, dy, interpret: bool):
    """``(d bcu, d weight)`` as one kernel, the strips of a block's column
    walked last to first: ``v = B * u`` and its convolution made again,
    ``d C = dy conv``, ``g = dy C``, ``d v_t = sum_k w[k] g_(t+K-1-k)``
    from the strip's own ``g`` and the first rows of the strip after it
    (of the block after it: made from the tiles of ``C`` and ``dy`` read
    after the block; zeros after a row's last position), ``d B = d v u``,
    ``d u = d v B``, and the taps' sums ``sum_t g_t v_(t-K+1+k)`` down the
    rows as whole (8, L) float32 tiles, which stay in VMEM across a row's
    sequence blocks (the output's block does not move) and are folded,
    over sublanes and rows of the batch, once, outside."""
    batch, seq, width = bcu.shape
    channels, taps = width // 3, weight.shape[0]
    rows, lanes = _block(seq, channels, bcu.dtype)
    strip, blocks = _CONV_STRIP, seq // rows
    strips = rows // strip

    def folded(a):
        """(R, L) -> (8, L): whole tiles added."""
        out = a[:_SUBLANES]
        for at in range(_SUBLANES, a.shape[0], _SUBLANES):
            out = out + a[at:at + _SUBLANES]
        return out

    def kernel(bcu_ref, dy_ref, before_ref, after_ref, dy_after_ref, w_ref,
               d_ref, sums_ref):
        s = pl.program_id(1)

        @pl.when(s == 0)
        def _():
            sums_ref[...] = jnp.zeros_like(sums_ref)

        for at_y, at_b, at_c, at_u in _columns(channels, lanes):
            w = w_ref[:, at_y]
            # g of the tile after the block
            head = jnp.where(
                s == blocks - 1, 0.0,
                (dy_after_ref[0, :, at_y].astype(_F32)
                 * after_ref[0, :, at_c].astype(_F32))[:_SUBLANES])

            def a_strip(n, held, w=w, at_y=at_y, at_b=at_b, at_c=at_c,
                        at_u=at_u):
                head, sums = held
                first = (strips - 1 - n) * strip
                at = pl.ds(pl.multiple_of(first, strip), strip)
                earlier = pl.ds(pl.multiple_of(
                    jnp.maximum(first - _HALO, 0), _HALO), _HALO)
                tail = (jnp.where(first == 0, before_ref[0, :, at_b],
                                  bcu_ref[0, earlier, at_b]).astype(_F32)
                        * jnp.where(first == 0, before_ref[0, :, at_u],
                                    bcu_ref[0, earlier, at_u]).astype(_F32)
                        )[_HALO - _SUBLANES:]
                tail = jnp.where((first == 0) & (s == 0), 0.0, tail)
                b = bcu_ref[0, at, at_b].astype(_F32)
                u = bcu_ref[0, at, at_u].astype(_F32)
                dy_rows = dy_ref[0, at, at_y].astype(_F32)
                reaching = _reaching(b * u, tail, taps)
                d_ref[0, at, at_c] = (
                    dy_rows * _conv_sum(reaching, w)).astype(d_ref.dtype)
                g = dy_rows * bcu_ref[0, at, at_c].astype(_F32)
                joined = jnp.concatenate([g, head], axis=0)
                d_v = w[taps - 1:taps] * g
                for ahead in range(1, taps):
                    d_v = d_v + w[taps - 1 - ahead:taps - ahead] * pltpu.roll(
                        joined, strip + _SUBLANES - ahead, 0)[:strip]
                d_ref[0, at, at_b] = (d_v * u).astype(d_ref.dtype)
                d_ref[0, at, at_u] = (d_v * b).astype(d_ref.dtype)
                # d w[k] = sum_t g_t v_(t-K+1+k)
                sums = tuple(acc + folded(g * reaching[taps - 1 - k])
                             for k, acc in enumerate(sums))
                return g[:_SUBLANES], sums

            zeros = jnp.zeros((_SUBLANES, lanes), _F32)
            _, sums = jax.lax.fori_loop(0, strips, a_strip,
                                        (head, (zeros,) * taps))
            for k, acc in enumerate(sums):
                sums_ref[0, k, :, at_y] += acc

    specs = _specs(rows, channels, taps)
    d_bcu, sums = pl.pallas_call(
        kernel, grid=(batch, blocks),
        in_specs=[specs["bcu"]["block"], specs["y"]["block"],
                  specs["bcu"]["before"], specs["bcu"]["after"](blocks),
                  specs["y"]["after"](blocks), specs["w"]],
        out_specs=[specs["bcu"]["block"],
                   pl.BlockSpec((1, taps, _SUBLANES, channels),
                                lambda i, s: (i, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(bcu.shape, bcu.dtype),
                   jax.ShapeDtypeStruct((batch, taps, _SUBLANES, channels),
                                        _F32)],
        compiler_params=_params(True), interpret=interpret,
    )(bcu, dy, bcu, bcu, dy, weight.astype(_F32))
    return d_bcu, jnp.sum(sums, axis=(0, 2)).astype(weight.dtype)


@jax.custom_vjp
def _gated_in_vmem(bcu, weight):
    return _gated_fwd(bcu, weight)[0]


# Jitted for the scope's sake, as ``ops/ssd.py``'s passes: inside a program
# of its own the name reaches the compiled step as written.
@jax.jit
def _gated_fwd(bcu, weight):
    with jax.named_scope(SCOPE):
        return _fwd_in_vmem(bcu, weight, not on_tpu()), (bcu, weight)


@jax.jit
def _gated_bwd(residuals, dy):
    bcu, weight = residuals
    with jax.named_scope(SCOPE):
        return _bwd_in_vmem(bcu, weight, dy.astype(bcu.dtype), not on_tpu())


_gated_in_vmem.defvjp(_gated_fwd, _gated_bwd)


@jax.jit
def _gated_plain(bcu, weight):
    """:func:`gated_conv` under ``SCOPE``, between barriers each way (a
    barrier's cotangent passes one too). Left to XLA ``B * u`` becomes the
    epilogue of ``W_in``'s product and ``C *`` the prologue of ``W_out``'s,
    and their transposes likewise, under the projections' name: two fifths
    of the operator's time in ``lfm2_train_8k``'s step (PR 44). What the
    barriers cost is ``B | C | u`` and ``y`` through HBM once more each
    way."""
    with jax.named_scope(SCOPE):
        bcu = jax.lax.optimization_barrier(bcu)
        return jax.lax.optimization_barrier(gated_conv(bcu, weight))


def causal_gated_conv(bcu, weight):
    """:func:`gated_conv` under ``SCOPE``: by the kernels where
    :func:`convs_in_vmem`, else as it is."""
    if convs_in_vmem(bcu.shape[1], bcu.shape[2] // 3, weight.shape[0],
                     bcu.dtype):
        return _gated_in_vmem(bcu, weight)
    return _gated_plain(bcu, weight)
