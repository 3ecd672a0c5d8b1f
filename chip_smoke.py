#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, at the
full width of one model the repo supports, and checks what comes out:

1. kernels: the Pallas embedding gather (bit-equal to XLA ``take`` at the
   largest DLRM table) and flash attention forward + backward (against
   the plain-XLA reference, at an aligned and at a padded sequence
   length; and the decoder's path: eight query heads of 128 over one
   key/value head, causal and under a window, off their projections;
   and two key heads' maps over one value head of twice their width,
   differential attention's launch), compiled by Mosaic — not interpreted; the sparse-expert layer's walk
   (``ops/moe.py``) against its float32 loop of dense products at the
   decoder's widths, its rows moved by DMA (a tile's fetch and a round's
   combine, each also alone: equal to XLA's gather); the state-space
   scan's, the selective scan's, the mixers' convolution's and the gated
   short convolution's kernels against XLA's paths, every gradient.
   Checked, not timed: the benchmark reads the same kernels in the step;
2. loader -> device feed -> train step: seeded DLRM Parquet
   (``data_generation.generate_data``) through ``JaxShufflingDataset`` at
   library defaults into ``parallel.trainer.SpmdTrainer`` over
   ``parallel.mesh.make_mesh()``, DLRM at the MLPerf widths
   (``models.dlrm.mlperf_config()``), two short epochs. Every row must
   arrive exactly once per epoch, on the accelerator, over the bulk
   device path, with a finite falling loss and one compilation of the step.

It refuses to run unless JAX reports a TPU: no CPU run, no flag to
continue. One process holds the chip; the loader's pool workers are
pinned to the CPU. On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
and the exit code is 0; any failed check raises and the exit code is 1.

    python3 chip_smoke.py        # from the root of a checkout, on the chip

Module import stays free of jax on purpose: the loader's process pool
spawns, and every worker re-imports this file as its ``__main__``.
"""

from __future__ import annotations

import dataclasses
import faulthandler
import functools
import json
import os
import shutil
import statistics
import sys
import tempfile
import timeit
from typing import Any, List, Optional, Sequence, Tuple

# A hang must fail inside the driver's 1200 s limit instead of burning
# chip budget: at the deadline every thread's stack goes to stderr and
# the process exits non-zero.
DEADLINE_S = 1100


class SmokeFailure(AssertionError):
    """A chip_smoke check did not hold."""


def _check(condition: bool, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


def _info(message: str) -> None:
    print(f"# {message}", flush=True)


@dataclasses.dataclass(frozen=True)
class SmokeSize:
    """What one run covers. ``full_size`` is what the chip runs;
    ``tiny_size`` is the same body cut down for the CPU test."""

    model: Any                        # models.dlrm.DLRMConfig
    batch_per_device: int
    steps_per_epoch: int
    num_files: int
    learning_rate: float
    gather_table: Tuple[int, int]     # (vocab, embed_dim)
    gather_batch: int
    attention_shape: Tuple[int, int, int]  # (batch, heads, head_dim)
    attention_seqs: Tuple[int, ...]   # in blocks, padded, one block
    # the decoder's attention, heads of ``masked_attention_dim``: (query
    # heads, key/value heads, window (None: the whole triangle), whether a
    # gate a query head multiplies the output, the lengths it is checked
    # at: in blocks, padded)
    masked_attention_dim: int
    masked_attention: Tuple[Tuple[int, int, Optional[int], bool,
                                  Tuple[int, ...]], ...]
    # its attention over wide values, two key heads' maps over one value
    # head of twice a key's width (differential attention's launch):
    # (query heads, key heads, a key's width, window, lengths)
    wide_attention: Tuple[Tuple[int, int, int, Optional[int],
                                Tuple[int, ...]], ...]
    # its attention under block diffusion's mask, one row that holds a
    # sequence twice: (query heads, key/value heads, block length, clean
    # length)
    diffusion_attention: Tuple[Tuple[int, int, int, int], ...]
    # its expert layer: (tokens, hidden, width, experts, held, top_k, tile,
    # what a token's weights sum to)
    moe_shapes: Tuple[Tuple[int, int, int, int, int, int, int, float], ...]
    # its state-space scan: (rows, positions, heads, head width, state,
    # chunk)
    ssd_shapes: Tuple[Tuple[int, int, int, int, int, int], ...]
    sscan_shapes: Tuple[Tuple[int, int, int, int, int], ...]
    # the mixers' depthwise convolution: (rows, positions, channels, taps)
    conv_shapes: Tuple[Tuple[int, int, int, int], ...]
    # a ``conv`` layer's gated short convolution: the same four
    sconv_shapes: Tuple[Tuple[int, int, int, int], ...]
    # the placing of an attention half's q heads, normed and rotated:
    # (rows, positions, heads, head width)
    place_shapes: Tuple[Tuple[int, int, int, int], ...]
    epochs: int = 2


def full_size() -> SmokeSize:
    from ray_shuffling_data_loader_tpu.models import dlrm
    cfg = dlrm.mlperf_config()
    return SmokeSize(
        model=cfg, batch_per_device=2048, steps_per_epoch=32, num_files=8,
        learning_rate=1e-3,
        gather_table=(max(cfg.vocab_sizes), cfg.embed_dim),
        gather_batch=2048, attention_shape=(2, 4, 64),
        attention_seqs=(2048, 1000, 512),
        masked_attention_dim=128,
        masked_attention=(
            (8, 1, None, False, (4096, 1000)),
            (8, 1, 1024, False, (4096, 1000)),
            # laguna_train_8k's layers: gated, 48 : 8 over the triangle and
            # 64 : 8 under a window of 512
            (48, 8, None, True, (2048,)),
            (64, 8, 512, True, (2048,))),
        # phi4flash_train_8k's differential layers: 40 maps over 20 key
        # heads of 64 and 10 value heads of 128
        wide_attention=((40, 20, 64, None, (2048,)),
                        (40, 20, 64, 512, (2048,))),
        # sdar_train_8k's layers: one row of 16,384 positions
        diffusion_attention=((32, 4, 4, 8192),),
        moe_shapes=((8192, 2304, 896, 64, 16, 8, 1152, 1.0),
                    (16384, 2048, 512, 256, 32, 8, 640, 2.5)),
        # granite_train_8k's nine Mamba layers
        ssd_shapes=((1, 8192, 64, 64, 128, 256),),
        # phi4flash_train_8k's two Mamba-1 layers
        sscan_shapes=((1, 8192, 5120, 16, 64),),
        # granite_train_8k's xBC and phi4flash_train_8k's u
        conv_shapes=((1, 8192, 4352, 4), (1, 8192, 5120, 4)),
        # lfm2_train_8k's four conv operators
        sconv_shapes=((2, 8192, 2048, 3),),
        # sdar_train_8k's and lfm2_train_8k's q heads
        place_shapes=((1, 16384, 32, 128), (2, 8192, 32, 64)))


def tiny_size() -> SmokeSize:
    import jax.numpy as jnp

    from ray_shuffling_data_loader_tpu.models import dlrm
    cfg = dlrm.DLRMConfig(
        vocab_sizes=tuple(min(v, 512) for v in dlrm.DATA_SPEC_VOCAB_SIZES),
        embed_dim=8, top_hidden=(32, 16), compute_dtype=jnp.float32)
    return SmokeSize(
        model=cfg, batch_per_device=16, steps_per_epoch=8, num_files=2,
        learning_rate=1e-2, gather_table=(4096, 128), gather_batch=64,
        attention_shape=(1, 2, 32), attention_seqs=(256, 200, 64),
        masked_attention_dim=16,
        masked_attention=((4, 1, 24, False, (40,)), (6, 2, 8, True, (40,))),
        wide_attention=((8, 4, 16, 8, (40,)),),
        diffusion_attention=((4, 2, 4, 32),),
        moe_shapes=((48, 16, 8, 8, 2, 2, 8, 2.5),
                    (48, 256, 8, 8, 2, 2, 8, 1.0)),     # rows of whole lanes
        ssd_shapes=((1, 256, 2, 64, 128, 128),),
        sscan_shapes=((1, 32, 1024, 4, 8),),
        conv_shapes=((2, 64, 256, 4),), sconv_shapes=((2, 64, 256, 3),),
        place_shapes=((2, 64, 2, 128), (2, 64, 4, 64)))


# -- kernels ---------------------------------------------------------------


def _mosaic_calls(jitted, *args) -> int:
    """How many Mosaic kernels the lowered program holds."""
    return jitted.lower(*args).as_text().count("tpu_custom_call")


def _plain_attention(q, k, v, causal: bool = False,
                     window: Optional[int] = None):
    """Reference XLA attention in float32: full (B, H, S, S) scores; ``k``
    and ``v`` of H heads or of a divisor of H."""
    import jax
    import jax.numpy as jnp
    scale = q.shape[-1] ** -0.5
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(x.astype(jnp.float32), group, axis=1) for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k) * scale
    if causal:
        ahead = (jnp.arange(s.shape[-2])[:, None]
                 - jnp.arange(s.shape[-1])[None, :])
        seen = ahead >= 0 if window is None else (ahead >= 0) & (
            ahead < window)
        s = jnp.where(seen, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _check_attention(flash, seq_len: int, b: int, h: int, d: int,
                     fwd_tol: float = 2e-2, grad_tol: float = 2e-2,
                     kv_heads: Optional[int] = None, plain=None,
                     what: str = "flash attention",
                     relative: bool = False) -> None:
    """Max-abs-error of the compiled flash forward AND backward against
    the float32 XLA reference, checked, not just printed.

    The interpret-mode tests prove the algorithm; this proves the
    Mosaic-compiled kernel's numerics on the device (bf16 inputs, fp32
    accumulation: the tolerance is the bf16 resolution bound that
    tests/test_flash_attention.py uses for bf16 inputs). Errors are
    computed on the device and fetched as scalars; ``relative`` takes each
    as a share of its reference's largest magnitude (a key/value head's
    gradient sums eight query heads': bf16 resolves it more coarsely).
    """
    import jax
    import jax.numpy as jnp

    shape = (b, h, seq_len, d)
    kv_shape = (b, kv_heads or h, seq_len, d)
    plain = plain or _plain_attention
    kq, kk, kv = jax.random.split(jax.random.key(42), 3)
    q = jax.random.normal(kq, shape, jnp.bfloat16)
    k = jax.random.normal(kk, kv_shape, jnp.bfloat16)
    v = jax.random.normal(kv, kv_shape, jnp.bfloat16)

    @jax.jit
    def errors(q, k, v):
        out_f = flash(q, k, v).astype(jnp.float32)
        out_r = plain(q, k, v)
        fwd_err = jnp.max(jnp.abs(out_f - out_r)) / (
            jnp.max(jnp.abs(out_r)) if relative else 1.0)
        # Grads of a non-trivial scalar (weighted sum keeps the cotangent
        # dense and non-uniform) through both implementations.
        w = jax.random.normal(jax.random.key(7), shape, jnp.float32)

        def loss(attn, q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32) * w)

        gf = jax.grad(functools.partial(loss, flash), (0, 1, 2))(q, k, v)
        gr = jax.grad(functools.partial(loss, plain), (0, 1, 2))(q, k, v)
        grad_err = jnp.max(jnp.asarray(
            [jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
             / (jnp.max(jnp.abs(b)) if relative else 1.0)
             for a, b in zip(gf, gr)]))
        return fwd_err, grad_err

    fwd_err, grad_err = (float(x) for x in errors(q, k, v))
    _info(f"kernels: {what} S={seq_len} max|flash-xla| fwd "
          f"{fwd_err:.3e} (tol {fwd_tol:.0e}), grad {grad_err:.3e} "
          f"(tol {grad_tol:.0e})")
    _check(fwd_err <= fwd_tol,
           f"{what}: forward differs from the XLA reference at "
           f"S={seq_len}: {fwd_err} > {fwd_tol}")
    _check(grad_err <= grad_tol,
           f"{what}: backward differs from the XLA reference at "
           f"S={seq_len}: {grad_err} > {grad_tol}")


def kernels_phase(size: SmokeSize, interpret: bool) -> None:
    """Gather and flash attention against their references, compiled for
    the device in use (``interpret`` only where there is no chip)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_shuffling_data_loader_tpu.ops import embedding
    from ray_shuffling_data_loader_tpu.ops import flash_attention as fa

    vocab, embed = size.gather_table
    table = jax.random.normal(jax.random.key(1), (vocab, embed), jnp.float32)
    indices = jax.random.randint(jax.random.key(2), (size.gather_batch,),
                                 0, vocab, jnp.int32)
    gather = jax.jit(lambda t, i: embedding.lookup(t, i, jnp.float32,
                                                   mode="pallas"))
    take = jax.jit(lambda t, i: embedding.lookup(t, i, jnp.float32,
                                                 mode="take"))
    if not interpret:
        _check(_mosaic_calls(gather, table, indices) == 1,
               "the Pallas gather did not lower to a Mosaic kernel")
    _check(np.array_equal(np.asarray(gather(table, indices)),
                          np.asarray(take(table, indices))),
           f"Pallas gather differs from XLA take at {vocab}x{embed}")
    _info(f"kernels: gather {vocab}x{embed} batch {size.gather_batch} "
          f"bit-equal to take ({'interpreted' if interpret else 'Mosaic'})")

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, interpret=interpret)

    b, h, d = size.attention_shape
    for seq in size.attention_seqs:
        if not interpret:
            probe = jnp.zeros((b, h, seq, d), jnp.bfloat16)
            _check(_mosaic_calls(jax.jit(flash), probe, probe, probe) == 1,
                   f"flash attention at S={seq} did not lower to a Mosaic "
                   "kernel")
        _check_attention(flash, seq, b, h, d)
    _masked_attention_checks(size, interpret)
    _wide_attention_checks(size, interpret)
    _diffusion_attention_checks(size, interpret)
    _check_partial_rotary(size.masked_attention_dim)
    for shape in size.moe_shapes:
        _check_moe(shape, interpret)
    for shape in size.ssd_shapes:
        _check_ssd(shape, interpret)
    for shape in size.sscan_shapes:
        _check_sscan(shape, interpret)
    for shape in size.conv_shapes:
        _check_conv(shape, interpret)
    for shape in size.sconv_shapes:
        _check_sconv(shape, interpret)
    for shape in size.place_shapes:
        _check_place(shape, interpret)


def _packed(x):
    """(B, H, S, D) -> (B, S, H x D), as a projection leaves its heads."""
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _masked_attention_checks(size: SmokeSize, interpret: bool) -> None:
    """The decoder's attention off its projections (B, S, H x D): grouped
    heads, causal over the row and under a window, with and without the
    gate a query head, at a length in blocks and at a padded one, against
    the masked float32 softmax."""
    import jax
    import jax.numpy as jnp

    from ray_shuffling_data_loader_tpu.models import mellum

    d = size.masked_attention_dim

    def unpacked(x, h):     # (B, S, H x D) -> (B, H, S, D)
        b, s, _ = x.shape
        return x.reshape(b, s, h, d).transpose(0, 2, 1, 3)

    def gate_of(q):
        # (B, H, S) float32, a function of q: the gate's own gradient is
        # then checked through dq
        return jax.nn.sigmoid(q.astype(jnp.float32).mean(axis=-1))

    for heads, kv_heads, span, gated, seqs in size.masked_attention:
        for seq in seqs:
            # the decoder's own attention: its kernels, tiles and custom_vjp
            def flash(q, k, v):
                gate = gate_of(q).transpose(0, 2, 1) if gated else None
                return unpacked(mellum._flash_attention(
                    _packed(q), _packed(k), _packed(v), gate, heads,
                    kv_heads, span), heads)

            def plain(q, k, v):
                out = _plain_attention(q, k, v, causal=True, window=span)
                return out * gate_of(q)[..., None] if gated else out

            if not interpret:
                probe = jnp.zeros((1, heads, seq, d), jnp.bfloat16)
                kv_probe = jnp.zeros((1, kv_heads, seq, d), jnp.bfloat16)
                _check(_mosaic_calls(jax.jit(flash), probe, kv_probe,
                                     kv_probe) == 1,
                       f"masked attention at S={seq} did not lower to a "
                       "Mosaic kernel")
            _check_attention(
                flash, seq, 1, heads, d, kv_heads=kv_heads, relative=True,
                plain=plain,
                what=(f"{heads}:{kv_heads} heads of {d}, causal"
                      + (f", window {span}" if span else "")
                      + (", gated" if gated else "")))


def _wide_attention_checks(size: SmokeSize, interpret: bool) -> None:
    """The decoder's attention where two key heads share a value head of
    twice their width, (B, S, Hkv / 2 x 2 D): a query head's output is
    its map over both halves side by side, and the half that is its own
    key head's values is plain grouped attention's output, which it is
    checked against (the other half's cotangent is zero)."""
    import jax
    import jax.numpy as jnp

    from ray_shuffling_data_loader_tpu.models import mellum

    for heads, kv_heads, d, span, seqs in size.wide_attention:
        own = jnp.arange(heads) // (heads // kv_heads) % 2

        def flash(q, k, v):
            both = mellum._flash_attention(
                _packed(q), _packed(k), _packed(v), None, heads, kv_heads,
                span, None, jnp.float32, kv_heads // 2)
            b, s, _ = both.shape
            halves = both.reshape(b, s, heads, 2, d).transpose(0, 2, 1, 3, 4)
            return jnp.take_along_axis(
                halves, own[None, :, None, None, None], axis=3)[:, :, :, 0]

        for seq in seqs:
            if not interpret:
                probe = jnp.zeros((1, heads, seq, d), jnp.bfloat16)
                kv_probe = jnp.zeros((1, kv_heads, seq, d), jnp.bfloat16)
                _check(_mosaic_calls(jax.jit(flash), probe, kv_probe,
                                     kv_probe) == 1,
                       f"wide-value attention at S={seq} did not lower to "
                       "a Mosaic kernel")
            _check_attention(
                flash, seq, 1, heads, d, kv_heads=kv_heads, relative=True,
                plain=functools.partial(_plain_attention, causal=True,
                                        window=span),
                what=(f"{heads}:{kv_heads}:{kv_heads // 2} heads of {d} "
                      f"over values of {2 * d}, causal"
                      + (f", window {span}" if span else "")))


def _diffusion_attention_checks(size: SmokeSize, interpret: bool,
                                tol: float = 2e-2) -> None:
    """The decoder's attention under block diffusion's mask, one row of 2
    L positions off its projections, the decoder's own kernels, tiles and
    custom_vjp: output and the three gradients against the inline form
    (``mellum._inline_attention`` under the same booleans), which is run a
    query head at a time (a head's (2 L, 2 L) float32 scores are 1 GB at
    the cell's shape, and autodiff keeps four such) and summed over a
    key/value head's group."""
    import jax
    import jax.numpy as jnp

    from ray_shuffling_data_loader_tpu.models import mellum
    from ray_shuffling_data_loader_tpu.ops import flash_attention as fa

    d = size.masked_attention_dim
    for heads, kv_heads, block, length in size.diffusion_attention:
        diffusion, group = (block, length), heads // kv_heads
        keys = jax.random.split(jax.random.key(43), 4)
        q, k, v, w = (jax.random.normal(key, (1, 2 * length, n * d),
                                        jnp.bfloat16)
                      for key, n in zip(keys, (heads, kv_heads, kv_heads,
                                               heads)))

        def kernels(q, k, v):
            return mellum._flash_attention(q, k, v, None, heads, kv_heads,
                                           None, None, None, None, diffusion)

        if not interpret:
            _check(_mosaic_calls(jax.jit(kernels), q, k, v) == 1,
                   "attention under block diffusion's mask did not lower "
                   "to a Mosaic kernel")
        @jax.jit
        def both(q, k, v):
            out, vjp = jax.vjp(kernels, q, k, v)
            return (out, *vjp(w))

        seen = jax.jit(lambda: fa.diffusion_seen(*diffusion))()

        @jax.jit
        def one_head(q, k, v, w):
            out, vjp = jax.vjp(
                lambda q, k, v: mellum._inline_attention(
                    q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32), None, 1, 1, None, None, None,
                    None, seen), q, k, v)
            return (out, *vjp(w.astype(jnp.float32)))

        got = both(q, k, v)
        worst = {name: 0.0 for name in ("out", "dq", "dk", "dv")}

        def gap(mine, theirs):
            return float(jnp.max(jnp.abs(mine.astype(jnp.float32) - theirs))
                         / jnp.max(jnp.abs(theirs)))

        for kv in range(kv_heads):
            cols = slice(kv * d, (kv + 1) * d)
            dk = dv = 0.0
            for head in range(kv * group, (kv + 1) * group):
                mine = slice(head * d, (head + 1) * d)
                out, dq, dk_h, dv_h = one_head(q[..., mine], k[..., cols],
                                               v[..., cols], w[..., mine])
                worst["out"] = max(worst["out"], gap(got[0][..., mine], out))
                worst["dq"] = max(worst["dq"], gap(got[1][..., mine], dq))
                dk, dv = dk + dk_h, dv + dv_h
            worst["dk"] = max(worst["dk"], gap(got[2][..., cols], dk))
            worst["dv"] = max(worst["dv"], gap(got[3][..., cols], dv))
        what = (f"{heads}:{kv_heads} heads of {d} under block diffusion's "
                f"mask, {length} tokens twice in blocks of {block}")
        _info(f"kernels: {what}: max|kernels-inline| / max|inline| "
              + ", ".join(f"{name} {value:.3e}"
                          for name, value in worst.items())
              + f" (tol {tol:.0e})")
        for name, value in worst.items():
            _check(value <= tol, f"{what}: {name} differs from the inline "
                   f"form: {value} > {tol}")


def _check_partial_rotary(dim: int, tol: float = 2e-2) -> None:
    """The decoder's rotary positions over the first half of a head (the
    rotation as a bf16 product with a signed permutation, the other half
    passing) against the float32 formula written out."""
    import jax
    import jax.numpy as jnp

    from ray_shuffling_data_loader_tpu.models import mellum

    cfg = mellum.DecoderConfig(head_dim=dim, full_rotary_factor=0.5)
    heads, seq, rotated = 4, 64, dim // 2
    x = jax.random.normal(jax.random.key(5), (2, seq, heads * dim),
                          jnp.bfloat16)
    cos, sin = mellum._rope_tables(cfg, mellum.FULL, seq)
    got = jax.jit(lambda x: mellum._rope(x, heads, cos, sin, rotated))(x)
    xf = x.astype(jnp.float32).reshape(2, seq, heads, dim)
    turn = xf[..., :rotated]
    turned = jnp.concatenate([-turn[..., rotated // 2:],
                              turn[..., :rotated // 2]], -1)
    want = jnp.concatenate([
        turn * cos[:, None, :rotated] + turned * sin[:, None, :rotated],
        xf[..., rotated:]], -1).reshape(x.shape)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))
                / jnp.max(jnp.abs(want)))
    same = bool(jnp.array_equal(
        got.reshape(xf.shape)[..., rotated:],
        x.reshape(xf.shape)[..., rotated:]))
    _info(f"kernels: rotary over {rotated} of {dim} dimensions a head: "
          f"max|bf16-f32| / max|f32| {err:.3e} (tol {tol:.0e}), the rest "
          f"passed unchanged: {same}")
    _check(err <= tol and same, "partial rotary differs from the formula: "
           f"{err} > {tol}, or the passed dimensions changed")


def _check_moe(shape: Tuple[int, ...], interpret: bool,
               tol: float = 2e-2) -> None:
    """The expert layer's walk (bf16 operands, tiles of one expert) against
    the plain float32 loop of dense products under the routing's weights,
    forward and the gradients of the tokens and of two of the weights,
    as shares of each one's largest magnitude. On the chip the walk's rows
    of whole lanes must move by DMA; such rows' movers are also run
    alone."""
    import jax
    import jax.numpy as jnp

    from ray_shuffling_data_loader_tpu.ops import moe

    tokens, hidden, width, experts, held, top_k, tile, scale = shape
    keys = jax.random.split(jax.random.key(11), 6)
    x = jax.random.normal(keys[0], (tokens, hidden), jnp.bfloat16)
    router = 0.02 * jax.random.normal(keys[1], (hidden, experts))
    gate, up = (0.02 * jax.random.normal(k, (held, hidden, width))
                for k in keys[2:4])
    down = 0.02 * jax.random.normal(keys[4], (held, width, hidden))
    mix = jax.random.normal(keys[5], (tokens, hidden))

    def walked(x, router, gate, up, down):
        return moe.moe(x, router, gate, up, down, (0, held), top_k, tile,
                       scale)

    def plain(x, router, gate, up, down):
        with jax.default_matmul_precision("highest"):
            xf = x.astype(jnp.float32)
            ids, weights = moe.route(xf @ router, top_k, scale)
            out = jnp.zeros_like(xf)
            for e in range(held):
                w = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1,
                            keepdims=True)
                out += w * ((jax.nn.silu(xf @ gate[e]) * (xf @ up[e]))
                            @ down[e])
            return out

    @jax.jit
    def errors(*args):
        def loss(f, *a):
            return jnp.sum(f(*a).astype(jnp.float32) * mix)

        got = (walked(*args), *jax.grad(
            functools.partial(loss, walked), (0, 2, 4))(*args))
        want = (plain(*args), *jax.grad(
            functools.partial(loss, plain), (0, 2, 4))(*args))
        return [jnp.max(jnp.abs(g.astype(jnp.float32) - w))
                / jnp.max(jnp.abs(w)) for g, w in zip(got, want)]

    by_dma = moe.dma_takes(tokens, hidden, tile, x.dtype)
    if not interpret:   # every shape the chip checks is a cell's
        _check(by_dma and moe.rows_by_dma(tokens, hidden, tile, x.dtype)
               and _mosaic_calls(jax.jit(walked), x, router, gate, up,
                                 down) == 2,
               f"the expert layer's rows at {tokens}x{hidden} in tiles of "
               f"{tile} do not move by DMA (a tile's fetch and a round's "
               "combine: two Mosaic kernels)")
    errs = [float(e) for e in errors(x, router, gate, up, down)]
    _info(f"kernels: expert layer {tokens} tokens x {hidden}, {held} of "
          f"{experts} experts of {width}, top-{top_k}, weights summing to "
          f"{scale}, tiles of {tile}: "
          "max|walk-f32| / max|f32| out, d tokens, d gate, d down "
          + ", ".join(f"{e:.3e}" for e in errs) + f" (tol {tol:.0e})")
    _check(max(errs) <= tol, "the expert layer's walk differs from the "
           f"float32 loop of dense products: {errs} > {tol}")
    if by_dma:
        _check_row_movers(shape, x, interpret)


def _check_row_movers(shape: Tuple[int, ...], x, interpret: bool) -> None:
    """The walk's two row kernels alone, over a tile's worth of ``x``'s
    rows and the first round of a random routing's walk: the fetch equal
    to XLA's gather of the same rows, the combine by runs equal to XLA's
    gathers' sums to float32's rounding (the same additions, a chunk of
    the slab summed before it joins the chunks before it)."""
    import jax
    import jax.numpy as jnp

    from ray_shuffling_data_loader_tpu.ops import moe

    tokens, hidden, _, experts, held, top_k, tile, _ = shape
    rows = moe.round_rows(tokens, top_k, held, experts, tile)
    keys = jax.random.split(jax.random.key(12), 5)
    token = jnp.sort(jax.random.randint(keys[0], (tile,), 0, tokens))
    ids = jax.lax.top_k(jax.random.uniform(keys[1], (tokens, experts)),
                        top_k)[1].astype(jnp.int32)
    plan, position = moe._dispatch(ids, 0, held, tile)
    runs = moe._runs(ids, plan, 0, held, tile, moe._block(tokens))
    index = jnp.where((position >= 0) & (position < rows), position, rows)
    buffer = jax.random.normal(keys[3], (rows + tile, hidden),
                               x.dtype).at[rows:].set(0)
    acc = jax.random.normal(keys[4], (tokens, hidden))
    fetched = moe._fetch([moe._words(x)], token, x.dtype, interpret)[0]
    _check(bool(jnp.array_equal(fetched, x[token])),
           f"the row fetch differs from XLA's gather at {tokens}x{hidden}")
    want = moe._combined(acc, buffer, index, rows)
    got = moe._combine_runs(acc, buffer, index, rows, runs, 0, interpret)
    gap = float(jnp.max(jnp.abs(got - want) / (1.0 + jnp.abs(want))))
    _check(gap <= 1e-6,
           f"the combine by runs differs from XLA's gathers' sums at "
           f"{tokens}x{hidden}: {gap:.3e} > 1e-06")
    _info(f"kernels: expert layer's rows by DMA at {tokens}x{hidden} "
          f"({'interpreted' if interpret else 'Mosaic'}): a tile's fetch "
          "equal to XLA's gather, a round's combine by runs to XLA's "
          f"gathers' sums within {gap:.1e}")


def _check_scan_paths(what: str, other: str, names: str, operands, mix,
                      scan, mosaic_calls: Tuple[int, int], takes: bool,
                      interpret: bool, tol: float) -> None:
    """A scan's (or the convolution's) kernels (``scan(True)``) against
    its XLA path (``scan(False)``, ``other``): the output and every
    operand's gradient, as shares of each one's largest magnitude. On the
    chip the scan must take the kernels of its own accord (``takes``, and
    ``mosaic_calls`` Mosaic kernels forward / forward + backward)."""
    import jax
    import jax.numpy as jnp

    def with_grads(in_vmem: bool):
        def loss(*a):
            y = scan(in_vmem)(*a)
            return jnp.sum(y.astype(jnp.float32) * mix), y

        def all_of(*a):
            grads, y = jax.grad(loss, range(len(operands)),
                                has_aux=True)(*a)
            return (y, *grads)

        return jax.jit(all_of)

    # three programs a shape: each lowered once, for every count and
    # comparison that wants it
    forward, kernels, xla = (jax.jit(scan(True)), with_grads(True),
                             with_grads(False))
    if not interpret:   # every shape the chip checks is a cell's
        _check(takes
               and _mosaic_calls(forward, *operands) == mosaic_calls[0]
               and _mosaic_calls(kernels, *operands) == mosaic_calls[1],
               f"the {what} does not run in VMEM ({mosaic_calls[0]} Mosaic "
               f"kernels forward, {mosaic_calls[1]} with the backward)")
    errs = [float(jnp.max(jnp.abs(g.astype(jnp.float32)
                                  - w.astype(jnp.float32)))
                  / jnp.max(jnp.abs(w.astype(jnp.float32))))
            for g, w in zip(kernels(*operands), xla(*operands))]
    _info(f"kernels: {what}: max|vmem-{other}| / max|{other}| {names} "
          + ", ".join(f"{e:.3e}" for e in errs) + f" (tol {tol:.0e})")
    _check(max(errs) <= tol, f"the {what}'s kernels differ from XLA's "
           f"{other}: {errs} > {tol}")


def _check_sscan(shape: Tuple[int, ...], interpret: bool,
                 tol: float = 2e-2) -> None:
    """Mamba-1's selective scan's kernels (the state in VMEM) against
    XLA's loops over the chunks and their positions, bf16 ``u``, ``B`` and
    ``C``, ``dt`` and ``A`` as the decoder's init draws them
    (:func:`_check_scan_paths`)."""
    import jax
    import jax.numpy as jnp

    from ray_shuffling_data_loader_tpu.ops import selective_scan as sscan

    rows, seq, channels, state, chunk = shape
    keys = jax.random.split(jax.random.key(17), 6)
    u = jax.random.normal(keys[0], (rows, seq, channels), jnp.bfloat16)
    b, c = (jax.random.normal(k, (rows, seq, state), jnp.bfloat16)
            for k in keys[1:3])
    d = 1.0 + 0.1 * jax.random.normal(keys[3], (channels,))
    dt = jnp.exp(jax.random.uniform(keys[4], (rows, seq, channels),
                                    minval=jnp.log(0.001),
                                    maxval=jnp.log(0.1)))
    a_log = jnp.log(jnp.broadcast_to(
        jnp.arange(1, state + 1, dtype=jnp.float32), (channels, state)))
    _check_scan_paths(
        f"selective scan {rows} x {seq} positions, {channels} channels, "
        f"state {state}, chunks of {chunk}", "loops",
        "y, d u, d dt, d a_log, d b, d c, d d", (u, dt, a_log, b, c, d),
        jax.random.normal(keys[5], u.shape),
        lambda in_vmem: lambda *a: sscan._sscan(*a, chunk, in_vmem)[0],
        (1, 2), sscan.scans_in_vmem(channels, state, chunk), interpret, tol)


def _check_ssd(shape: Tuple[int, ...], interpret: bool,
               tol: float = 2e-2) -> None:
    """The state-space scan's kernels (a head's ``L o (C B^T)`` tile in
    VMEM: the chunks' end states, the carry across them and their outputs,
    and the same backwards) against XLA's einsums over the chunks, bf16
    operands, ``dt`` and ``A`` as the decoder's init draws them
    (:func:`_check_scan_paths`)."""
    import jax
    import jax.numpy as jnp

    from ray_shuffling_data_loader_tpu.ops import ssd

    rows, seq, heads, width, state, chunk = shape
    keys = jax.random.split(jax.random.key(13), 7)
    # x as the mixer has it, (B, S, H x P): the scan's own layout
    x = jax.random.normal(keys[0], (rows, seq, heads * width), jnp.bfloat16)
    b, c = (jax.random.normal(k, (rows, seq, state), jnp.bfloat16)
            for k in keys[1:3])
    d = 1.0 + 0.1 * jax.random.normal(keys[3], (heads,))
    dt = jnp.exp(jax.random.uniform(keys[4], (rows, seq, heads),
                                    minval=jnp.log(0.001),
                                    maxval=jnp.log(0.1)))
    a_log = jnp.log(jax.random.uniform(keys[5], (heads,), minval=1.0,
                                       maxval=16.0))
    _check_scan_paths(
        f"state-space scan {rows} x {seq} positions, {heads} heads of "
        f"{width}, state {state}, chunks of {chunk}", "einsums",
        "y, d x, d dt, d a_log, d b, d c, d d", (x, dt, a_log, b, c, d),
        jax.random.normal(keys[6], x.shape),
        lambda in_vmem: lambda *a: ssd._ssd(*a, chunk, in_vmem)[0],
        (3, 6), ssd.scans_in_vmem(chunk, heads, width, state, x.dtype)
        and ssd.passes_in_vmem(heads, width, state), interpret, tol)


def _check_conv(shape: Tuple[int, ...], interpret: bool,
                tol: float = 2e-2) -> None:
    """The mixers' depthwise convolution and ``silu`` by its kernels (a
    block of ``x`` read once each way, ``d x`` and the taps' and the
    bias's sums in one backward pass) against XLA's pad, shifted slices
    and autodiff, bf16 ``x`` (:func:`_check_scan_paths`)."""
    import jax
    import jax.numpy as jnp

    from ray_shuffling_data_loader_tpu.ops import ssd

    rows, seq, channels, taps = shape
    keys = jax.random.split(jax.random.key(19), 4)
    x = jax.random.normal(keys[0], (rows, seq, channels), jnp.bfloat16)
    weight = jax.random.normal(keys[1], (taps, channels)) * taps ** -0.5
    bias = 0.1 * jax.random.normal(keys[2], (channels,))
    _check_scan_paths(
        f"depthwise convolution {rows} x {seq} positions, {channels} "
        f"channels, {taps} taps", "pad and slices", "y, d x, d w, d b",
        (x, weight, bias), jax.random.normal(keys[3], x.shape),
        lambda in_vmem: (
            (lambda *a: ssd._conv_silu_in_vmem(*a, ssd.SCOPE)) if in_vmem
            else ssd.conv_silu),
        (1, 2), ssd.convs_in_vmem(seq, channels, taps, x.dtype), interpret,
        tol)


def _check_sconv(shape: Tuple[int, ...], interpret: bool,
                 tol: float = 2e-2) -> None:
    """A ``conv`` layer's gated short convolution, ``C * conv(B * u)``, by
    its kernels (a block of ``B | C | u`` read once each way, ``d (B | C |
    u)`` and the taps' sums in one backward pass) against XLA's pad,
    shifted slices and autodiff, bf16 ``B | C | u``
    (:func:`_check_scan_paths`)."""
    import jax
    import jax.numpy as jnp

    from ray_shuffling_data_loader_tpu.ops import sconv

    rows, seq, channels, taps = shape
    keys = jax.random.split(jax.random.key(23), 3)
    bcu = jax.random.normal(keys[0], (rows, seq, 3 * channels), jnp.bfloat16)
    weight = jax.random.uniform(keys[1], (taps, channels), jnp.float32,
                                -taps ** -0.5, taps ** -0.5)
    _check_scan_paths(
        f"gated short convolution {rows} x {seq} positions, {channels} "
        f"channels, {taps} taps", "pad and slices", "y, d bcu, d w",
        (bcu, weight), jax.random.normal(keys[2], (rows, seq, channels)),
        lambda in_vmem: (sconv._gated_in_vmem if in_vmem
                         else sconv.gated_conv),
        (1, 2), sconv.convs_in_vmem(seq, channels, taps, bcu.dtype),
        interpret, tol)


def _check_place(shape: Tuple[int, ...], interpret: bool,
                 tol: float = 1e-2) -> None:
    """The placing of q heads, each head's RMSNorm and the rotary over
    the whole of it, by its kernels (the projection read once each way,
    ``d x`` and the scale's sums in one backward pass) against the same
    written out in float32 with autodiff's backward, bf16 ``x``
    (:func:`_check_scan_paths`)."""
    import jax
    import jax.numpy as jnp

    from ray_shuffling_data_loader_tpu.models import mellum
    from ray_shuffling_data_loader_tpu.ops import rope

    rows, seq, heads, dim = shape
    cfg = mellum.DecoderConfig(head_dim=dim, yarn=None)
    cos, sin = mellum._rope_tables(cfg, mellum.FULL, seq)
    keys = jax.random.split(jax.random.key(29), 3)
    x = jax.random.normal(keys[0], (rows, seq, heads * dim), jnp.bfloat16)
    scale = jax.random.uniform(keys[1], (dim,), minval=0.5, maxval=1.5)
    eps = cfg.rms_norm_eps
    _check_scan_paths(
        f"placing of {rows} x {seq} positions' {heads} heads of {dim}",
        "float32 passes", "out, d x, d scale", (x, scale),
        jax.random.normal(keys[2], x.shape),
        lambda in_vmem: (
            (lambda x, scale: rope.placed_in_vmem(
                x, scale, cos, sin, dim, eps, interpret)) if in_vmem
            else (lambda x, scale: rope.placed_plain(
                x, heads, cos, sin, dim, scale, eps))),
        (1, 2), rope.places_in_vmem(seq, heads * dim, dim, dim, x.dtype),
        interpret, tol)


# -- loader -> device feed -> train step -------------------------------------

_MIX = 0x9E3779B97F4A7C15


def _rows_digest(columns: Sequence[Any], labels: Any) -> int:
    """Order-independent digest of a set of rows: one 64-bit hash per row
    over all its columns, summed modulo 2**64. Equal for two row sets
    exactly when (up to hash collisions) they hold the same rows the same
    number of times, whatever the order and the batch boundaries."""
    import numpy as np
    mix = np.uint64(_MIX)
    with np.errstate(over="ignore"):
        h = np.asarray(labels, np.float32).reshape(-1).view(
            np.uint32).astype(np.uint64)
        for col in columns:
            values = np.asarray(col).reshape(-1).astype(np.int64)
            h = (h ^ values.view(np.uint64)) * mix
            h ^= h >> np.uint64(29)
        return int(h.sum(dtype=np.uint64))


def _files_digest(filenames: Sequence[str]) -> Tuple[int, int]:
    """(row count, digest) of what the Parquet files hold."""
    import numpy as np
    import pyarrow.parquet as pq

    from ray_shuffling_data_loader_tpu import data_generation as dg
    rows, digest = 0, 0
    for filename in filenames:
        table = pq.read_table(
            filename, columns=list(dg.FEATURE_COLUMNS) + [dg.LABEL_COLUMN])
        digest += _rows_digest(
            [table.column(c).to_numpy() for c in dg.FEATURE_COLUMNS],
            table.column(dg.LABEL_COLUMN).to_numpy().astype(np.float32))
        rows += table.num_rows
    return rows, digest % 2**64


def train_phase(size: SmokeSize, data_dir: str) -> dict:
    """Generate data, feed it through the loader to the devices, train.
    Returns the numbers printed as information."""
    import jax
    import optax

    from ray_shuffling_data_loader_tpu import data_generation as dg
    from ray_shuffling_data_loader_tpu import executor
    from ray_shuffling_data_loader_tpu import stats as rsdl_stats
    from ray_shuffling_data_loader_tpu.jax_dataset import JaxShufflingDataset
    from ray_shuffling_data_loader_tpu.models import dlrm
    from ray_shuffling_data_loader_tpu.parallel import mesh as mesh_mod
    from ray_shuffling_data_loader_tpu.parallel.trainer import SpmdTrainer
    from ray_shuffling_data_loader_tpu.utils.config import (
        default_num_reducers)
    from ray_shuffling_data_loader_tpu.workloads.dlrm_criteo import dlrm_spec

    devices = jax.devices()
    n = len(devices)
    platform = devices[0].platform
    batch = size.batch_per_device * n
    rows = batch * size.steps_per_epoch
    cfg = size.model

    filenames, _ = dg.generate_data(rows, size.num_files, 2, 0.0, data_dir,
                                    seed=0)
    want_rows, want_digest = _files_digest(filenames)
    _check(want_rows == rows, f"generated {want_rows} rows, asked {rows}")

    mesh = mesh_mod.make_mesh()
    trainer = SpmdTrainer(
        mesh, lambda p, cols, y: dlrm.loss_fn(cfg, p, None, cols, y, mesh),
        dlrm.init(cfg, jax.random.key(0)), optax.adam(size.learning_rate))

    wd_before = rsdl_stats.watchdog_stats().snapshot()
    # Library defaults throughout (device_rebatch="auto", executor backend
    # auto). Only the reducer count is capped: the
    # host-core default would cut this small corpus into reducer outputs
    # shorter than a few batches, and the bulk path moves batch-aligned
    # spans of whole reducer outputs.
    ds = JaxShufflingDataset(
        filenames, num_epochs=size.epochs, num_trainers=1, batch_size=batch,
        rank=0, seed=0, queue_name="chip-smoke",
        num_reducers=max(1, min(default_num_reducers(1),
                                size.steps_per_epoch // 4)),
        # As examples/jax_train_shuffle.py: one device needs no sharded
        # transfers, several get every batch split over the data axis.
        mesh=mesh if n > 1 else None, **dlrm_spec())
    losses: List[float] = []
    step_s: List[float] = []
    lowered_kernels: Optional[int] = None
    try:
        _check(ds.device_rebatch == (platform != "cpu"),
               f"device_rebatch='auto' resolved to {ds.device_rebatch} on "
               f"platform {platform!r}")
        run_start = timeit.default_timer()
        for epoch in range(size.epochs):
            ds.set_epoch(epoch)
            got_rows, got_digest = 0, 0
            for features, label in ds:
                for array in (*features, label):
                    _check(isinstance(array, jax.Array)
                           and {d.platform for d in array.devices()}
                           == {platform},
                           f"a batch array is not a jax.Array on "
                           f"{platform}: {type(array)}")
                    _check(len(array.devices()) == n
                           and array.sharding.shard_shape(array.shape)[0]
                           == size.batch_per_device,
                           f"batch array {array.shape} is not split "
                           f"{size.batch_per_device} rows per device over "
                           f"{n} device(s): {array.sharding}")
                if lowered_kernels is None:
                    lowered_kernels = _mosaic_calls(
                        trainer.step_fn, trainer.params, trainer.opt_state,
                        features, label)
                t0 = timeit.default_timer()
                # The loss comes to the host every step: the fetch cannot
                # finish before the step has, so this times the step and
                # not its enqueue.
                losses.append(float(trainer.train_step(features, label)))
                step_s.append(timeit.default_timer() - t0)
                got_rows += label.shape[0]
                got_digest += _rows_digest(features, label)
            _check(got_rows == rows and got_digest % 2**64 == want_digest,
                   f"epoch {epoch} delivered {got_rows} rows (digest "
                   f"{got_digest % 2**64:#x}); the files hold {rows} "
                   f"(digest {want_digest:#x})")
        wall_s = timeit.default_timer() - run_start
        _check(ds.device_rebatch == (platform != "cpu")
               and not ds.fallback_engaged,
               "the bulk device path did not stay on "
               f"(fallback_engaged={ds.fallback_engaged})")
    finally:
        ds.close()
    wd_after = rsdl_stats.watchdog_stats().snapshot()
    watchdog_events = (wd_after["watchdog_events"]
                       - wd_before["watchdog_events"])
    _check(watchdog_events == 0, f"{watchdog_events} watchdog event(s)")

    steps = len(losses)
    _check(steps == size.epochs * size.steps_per_epoch,
           f"ran {steps} steps, expected "
           f"{size.epochs * size.steps_per_epoch}")
    _check(all(loss == loss and abs(loss) != float("inf")
               for loss in losses), f"non-finite loss in {losses}")
    head = statistics.fmean(losses[:max(1, steps // 8)])
    tail = statistics.fmean(losses[-max(1, steps // 8):])
    _check(tail < head, f"loss did not fall: first steps {head:.4f}, "
                        f"last steps {tail:.4f}")
    compiles = trainer.step_fn._cache_size()
    _check(compiles == 1, f"the train step compiled {compiles} times")
    on_chip_tables = sum(
        1 for v in cfg.vocab_sizes
        if v > 2048 and cfg.embed_dim % 128 == 0) if platform == "tpu" else 0
    _check(lowered_kernels == on_chip_tables,
           f"the lowered step holds {lowered_kernels} Mosaic gather(s), "
           f"expected {on_chip_tables}")

    pool = executor.last_worker_pool()
    peak = (devices[0].memory_stats() or {}).get("peak_bytes_in_use")
    return {
        "devices": n, "batch": batch, "rows_per_epoch": rows, "steps": steps,
        "first_step_s": round(step_s[0], 3),
        "median_step_ms": round(
            1e3 * statistics.median(step_s[1:] or step_s), 3),
        "rows_per_s": round(rows * size.epochs / wall_s, 1),
        "loss_first": round(head, 4), "loss_last": round(tail, 4),
        "step_compiles": compiles, "step_mosaic_gathers": lowered_kernels,
        "executor_backend": pool["backend"],
        "executor_workers": pool["workers"],
        "device_rebatch": ds.device_rebatch,
        "batch_wait": {k: round(v, 4) if isinstance(v, float) else v
                       for k, v in ds.batch_wait_stats.summary().items()},
        "peak_bytes_in_use": peak,
    }


def run(size: SmokeSize, interpret: bool) -> None:
    """Every phase, in one process; raises on the first failed check."""
    start = timeit.default_timer()
    kernels_phase(size, interpret)
    _info(f"kernels phase: {timeit.default_timer() - start:.1f}s")
    start = timeit.default_timer()
    data_dir = tempfile.mkdtemp(prefix="chip-smoke-data-")
    try:
        report = train_phase(size, data_dir)
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)
    _info(f"train phase: {timeit.default_timer() - start:.1f}s "
          f"{json.dumps(report)}")


def _version(dist: str) -> str:
    import importlib.metadata
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def main() -> int:
    start = timeit.default_timer()
    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: JAX reports platform {device['platform']!r}, "
              "not 'tpu'; refusing to run", file=sys.stderr)
        return 1
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)

    from ray_shuffling_data_loader_tpu import native
    from ray_shuffling_data_loader_tpu import procpool
    from ray_shuffling_data_loader_tpu.utils.compile_cache import (
        enable_compile_cache)
    cache_dir = enable_compile_cache()

    def cache_entries() -> int:
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    _info(f"device: {json.dumps(device)}")
    _info(f"versions: jax {jax.__version__}, jaxlib {_version('jaxlib')}, "
          f"libtpu {_version('libtpu')}, python "
          f"{sys.version.split()[0]}")
    _info(f"host: {os.cpu_count()} CPUs, executor backend "
          f"{procpool.resolve_backend()}")
    _info(f"native.available(): {native.available()}")
    _info(f"compile cache: {cache_dir} ({cache_entries()} entries at start)")
    _check(native.available(), "the native library is switched off")

    run(full_size(), interpret=False)

    _info(f"compile cache: {cache_entries()} entries at end")
    _info(f"wall: {timeit.default_timer() - start:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
