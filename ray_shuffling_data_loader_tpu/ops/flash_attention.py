"""Pallas TPU flash attention: scores and weights never leave VMEM.

The hot op of the BERT-MLM family (BASELINE.json config 4). XLA's inline
attention (models/bert.py) writes float32 (B, H, S, S) scores and bf16
weights to HBM and reads them back, forward and backward: 52 ms of
``bert_train``'s 121 ms step at PR 30. These kernels keep a block's
scores in VMEM. The operands go to the MXU in the dtype they arrive in
(bf16 in ``bert_train``) and accumulate in float32; scale, maximum,
exponentials, sums, lse and delta are float32; p and ds are cast to the
operands' dtype for the products that consume them (where the inline
path and its autodiff cast them). Float32 operands pass through untouched.

Two families, chosen by shape at trace time:

- *The sequence in one block of each side* (``_fwd1_kernel``,
  ``_bwd1_kernel``; S <= 1024 by default): grid (B, heads / group), no
  online-softmax recurrence and no accumulator, and a single backward
  kernel that makes s, p, dp, ds once and writes dq, dk, dv (and dbias).
  The blocks are (S, 128 lanes): cut from (B, H, S, D) arrays one head a
  step, or straight from the fused projection (B, S, 3 x H x D) with as
  many heads side by side as fill the lanes (``qkv_forward`` /
  ``qkv_backward``: no (B, S, H, D) -> (B, H, S, D) copies on either side
  of the kernel). This is what ``models/bert.py`` takes.
- *The sequence in blocks* (``_fwd_kernel``, ``_bwd_kernel``): grid
  (B, H, q-blocks, k-blocks) with the inner dimension carrying running
  max / denominator / accumulator in VMEM scratch. The backward is one
  kernel too: a live block's s, p, dp and ds are made once and feed dq
  (scratch, a query block's steps), dk and dv (a key/value head's whole
  sequence in float32 scratch, gathered over its query heads and their
  blocks) and dbias: five products and one exponential pass a block.
  Where a head's dk and dv outgrow VMEM (``_fused_fits``: past 16,384
  keys of 128 in bf16) the backward is the older pair (``_dq_kernel``
  streaming K; ``_dkv_kernel`` streaming Q), which each make s and p
  again: seven products. This family takes a
  structural mask, ``causal`` and ``window`` as static arguments (the
  inner dimension runs over the blocks some query of the outer block
  sees and no further; only the diagonal's and the window edge's blocks
  compare positions) or block diffusion's over a row read twice
  (``diffusion``: a clean and a noised copy side by side, a noised query
  seeing its own noised block and the clean blocks before it; the walk
  crosses the gap between them in one step), query heads in groups over
  fewer key/value heads
  (fetched once a block, repeated nowhere), values of a width and a head
  count of their own where key heads share them (differential
  attention's two maps a pair over values of 2 D: a map's scores are
  made once), and heads of whole lanes (128) cut straight from
  (B, S, H x D) projections (``grouped_forward`` / ``grouped_backward``).
  This is what ``models/mellum.py`` takes.

Both take a key-side bias (B, 1, 1, S) and an lse that may cover more keys
than the call holds (ring attention's hops, ops/ring_attention.py). Mosaic
wants (8, 128)-aligned tiles, so on the chip the sequence is padded to
aligned block multiples (padded keys masked, padded query rows sliced off).
Off the chip the same kernels run under the Pallas interpreter.

Measured on a v5e (PR 31, PERF.md section 6; forward + backward of one
layer's attention, 16,384 tokens, 12 heads of 64, bf16, in a ``lax.scan``):
at S = 512 these kernels off the fused projection take 1.44-1.54 ms where
the inline path takes 4.58, the kernels as PR 30 left them (float32
operands, two-kernel backward, head-major copies) 3.62 and JAX's bundled
``pallas.ops.tpu.flash_attention`` 4.97. The blocked family under a
mask (PR 32; forward / backward of one layer, 4 rows of 8,192 tokens, 32
query heads over 4 key/value heads of 128, bf16, ms): the whole triangle in
1024 x 1024 tiles 22.1 / 61.6 (112 / 140 TFLOP/s over the live tiles), a
window of 1,024 in 1024 x 1024 tiles 10.1 / 29.0, in 512 x 512 12.9 / 27.6.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ray_shuffling_data_loader_tpu.ops import on_tpu
from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics

_NEG = -1e30   # accumulator init
_MASK = -1e9   # padded-key bias (finite, matches ring_attention.NEG_INF)
_LANES = 128   # a vector register's width: a block narrower leaves lanes idle

# dot_general's contracting dimensions, for 2-D operands.
_NT = ((1,), (1,))   # a @ b.T
_NN = ((1,), (0,))   # a @ b
_TN = ((0,), (0,))   # a.T @ b


def _dot(a, b, dims):
    """The operands go to the MXU as they are (bf16 stays bf16); the
    product accumulates in float32."""
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


# The kernels' scoped VMEM. A step of the one-block kernels holds a handful
# of float32 (Sq, Sk) arrays, 4 MiB each at 1024 x 1024: more than Mosaic's
# default of 16 MiB. The v5e has 128 MiB.
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _compiler_params(interpret: bool, semantics):
    """Which grid dimensions carry state from one step to the next
    ("arbitrary": an accumulator in scratch, or an output block that stays
    resident) and which do not ("parallel")."""
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT_BYTES)


_BLOCKED = ("parallel", "parallel", "parallel", "arbitrary")


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# -- kernels: one block holds the sequence -----------------------------------
#
# A grid step sees all of one row's queries and keys for a group of
# ``heads`` heads that lie side by side along the lanes: q/k/v/o/do blocks
# are (S, heads * D), whatever array they are cut from (``_Layout``). A
# head's products run at the full block width with the other heads' lanes
# zeroed in one operand (the MXU is 128 wide: a 64-wide contraction or
# output costs it the same passes as a 128-wide one), and its lanes of the
# result are kept. Nothing is sliced along the lanes and nothing
# accumulates across grid steps. lse and delta travel as rows, (heads, Sq),
# lane-dense: a (Sq, 1) column costs a 128-lane tile a value in HBM and in
# the DMA, four times the q block itself at D = 64.


def _head_lanes(width: int, heads: int):
    """[(h, mask (1, width) of head h's lanes)], mask None for one head."""
    if heads == 1:
        return [(0, None)]
    lane_head = jax.lax.broadcasted_iota(
        jnp.int32, (1, width), 1) // (width // heads)
    return [(h, lane_head == h) for h in range(heads)]


def _only(x, mask):
    return x if mask is None else jnp.where(mask, x, jnp.zeros_like(x))


def _keep(new, mask, old):
    return new if mask is None or old is None else jnp.where(mask, new, old)


def _as_row(column):
    """(S, 1) -> (1, S): through a full-width transpose, the one Mosaic
    has for 32-bit values."""
    s = column.shape[0]
    return jnp.transpose(jnp.broadcast_to(column, (s, _LANES)))[:1]


def _fwd1_kernel(q_ref, k_ref, v_ref, *rest, scale: float, heads: int,
                 has_bias: bool):
    """Grid (B, H / heads). Blocks: q/o (Sq, W), k/v (Sk, W), bias
    (1, Sk) or absent, lse (heads, Sq)."""
    bias_ref = rest[0] if has_bias else None
    o_ref, lse_ref = rest[-2:]
    q, k, v = q_ref[...], k_ref[...], v_ref[...]
    out = None
    for h, mine in _head_lanes(q.shape[-1], heads):
        s = _dot(_only(q, mine), k, _NT) * scale          # (Sq, Sk)
        if has_bias:
            s = s + bias_ref[...]
        m = s.max(axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = p.sum(axis=-1, keepdims=True)
        out = _keep(_dot(p.astype(v.dtype), v, _NN) / l, mine, out)
        lse_ref[h:h + 1, :] = _as_row(m + jnp.log(l))
    o_ref[...] = out.astype(o_ref.dtype)


def _bwd1_kernel(*refs, scale: float, heads: int, has_delta: bool,
                 has_bias: bool):
    """Grid (B, H / heads): dq, dk, dv (and dbias) of one block from one
    s, p, dp, ds, all four held transposed, (Sk, Sq), so that lse and
    delta broadcast as the rows they are stored as and only dq's product
    contracts a leading dimension. Blocks as ``_fwd1_kernel``; bias and
    dbias are (Sk, 1) columns, dbias one block a row that stays resident
    while the heads' steps add to it.

    Without a delta operand (``lse`` is of these keys alone) delta is the
    softmax backward's own sum_k p dp in float32, so a query's ds sum to
    zero up to float32 rounding, as XLA's inline path has it. The flash
    identity sum_d do o (``_delta``) reads the rounded output, which at
    bf16 leaves every query a residue of 2^-9 delta; summed over queries
    and rows that is the key bias's whole gradient (zero in exact
    arithmetic), and Adam steps on it at full size."""
    refs = list(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref = refs[:5]
    del refs[:5]
    delta_ref = refs.pop(0) if has_delta else None
    bias_ref = refs.pop(0) if has_bias else None
    dq_ref, dk_ref, dv_ref = refs[:3]
    q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
    dq = dk = dv = dbias = None
    for h, mine in _head_lanes(q.shape[-1], heads):
        st = _dot(_only(k, mine), q, _NT) * scale         # (Sk, Sq)
        if has_bias:
            st = st + bias_ref[...]
        pt = jnp.exp(st - lse_ref[h:h + 1, :])            # softmax weights
        dpt = _dot(_only(v, mine), do, _NT)
        delta = (delta_ref[h:h + 1, :] if has_delta
                 else (pt * dpt).sum(axis=0, keepdims=True))
        dst = pt * (dpt - delta)
        if has_bias:
            total = dst.sum(axis=-1, keepdims=True)
            dbias = total if dbias is None else dbias + total
        dst = dst.astype(q.dtype)
        dv = _keep(_dot(pt.astype(do.dtype), do, _NN), mine, dv)
        dk = _keep(_dot(dst, q, _NN) * scale, mine, dk)
        dq = _keep(_dot(dst, k, _TN) * scale, mine, dq)
    dq_ref[...] = dq.astype(dq_ref.dtype)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)
    if has_bias:
        dbias_ref = refs[3]

        @pl.when(pl.program_id(1) == 0)
        def _init():
            dbias_ref[...] = jnp.zeros_like(dbias_ref)

        dbias_ref[...] += dbias


# -- kernels: the sequence in blocks -----------------------------------------
#
# A grid step sees one (bq, D) block of one head's queries, one (bk, D)
# block of its keys and one (bk, Dv) block of its values, whatever arrays
# they are cut from (``_rows``); lse and delta are (bq, 1) columns.
# ``group`` query heads read one key head and ``v_group`` of them one value
# head (the specs divide the head's index: nothing is repeated in HBM). The
# values have a width and a head count of their own: Dv = D and as many
# heads as the keys in plain grouped attention; wider and fewer where key
# heads share their values (differential attention's two maps a pair).
#
# The structural mask (``_Mask``) is static. The innermost grid dimension
# runs over the blocks that some query of the outer block can see, counted
# from the first one (``_k_span`` / ``_q_span``): a window's grid is as
# long as its band, not as the sequence. A step past the outer block's
# last live block does nothing, and its specs name the block already
# resident, so nothing is fetched for it either. A live block that every
# query of it sees whole runs unmasked; the diagonal's and the window
# edge's blocks compare positions.


class _Mask(NamedTuple):
    """Which keys a query sees: all (the default), those at or before it
    (``causal``), and of those the last ``window`` (itself included); or,
    with ``block``, block diffusion's mask (Arriola et al. 2025) over a
    row of ``2 x clean_len`` positions that holds a sequence twice, the
    clean copy and then the noised one. With
    ``blk(i) = (i mod clean_len) // block``: a clean query i sees the
    clean keys j with ``blk(j) <= blk(i)``; a noised query i sees the
    clean keys j with ``blk(j) < blk(i)`` and the noised keys j with
    ``blk(j) == blk(i)``; nothing else."""
    causal: bool = False
    window: Optional[int] = None
    block: int = 0
    clean_len: int = 0


#: ``(block length, clean length)``.
Diffusion = Tuple[int, int]


def _mask_of(causal: bool, window: Optional[int],
             diffusion: Optional[Diffusion] = None) -> _Mask:
    if window is not None and (not causal or window < 1):
        raise ValueError("a window is a causal mask's: pass causal=True and "
                         f"a window of at least 1 (got {causal=}, {window=})")
    if diffusion is None:
        return _Mask(bool(causal), window)
    block, clean_len = diffusion
    if causal or block < 1 or clean_len % block:
        raise ValueError(
            "block diffusion's mask is neither causal nor a window's: pass "
            "causal=False and a block length of at least 1 that divides the "
            f"clean length (got {causal=}, {window=}, {diffusion=})")
    return _Mask(False, None, int(block), int(clean_len))


def _k_span(mask: _Mask, g, bq: int, bk: int, num_k: int, lo=min, hi=max):
    """(first, last) key block some query of query block ``g`` sees."""
    if not mask.causal:
        return 0, num_k - 1
    first = (0 if mask.window is None
             else hi(g * bq - mask.window + 1, 0) // bk)
    return first, lo(((g + 1) * bq - 1) // bk, num_k - 1)


def _q_span(mask: _Mask, t, bq: int, bk: int, num_q: int, lo=min, hi=max):
    """(first, last) query block that sees some key of key block ``t``."""
    if not mask.causal:
        return 0, num_q - 1
    last = (num_q - 1 if mask.window is None
            else lo(((t + 1) * bk + mask.window - 2) // bq, num_q - 1))
    return lo((t * bk) // bq, num_q - 1), last


def _longest(span, count: int) -> int:
    """The longest ``span(block)`` over ``count`` outer blocks: the inner
    grid dimension's extent."""
    return max(last - first + 1 for first, last in map(span, range(count)))


def _choose(condition, a, b):
    """``jnp.where`` for Python's own numbers: the walk below is counted
    with them before anything is traced."""
    return a if condition else b


def _half(block_index, per_half: int, where=jnp.where):
    """``(1 where block ``block_index`` of ``per_half`` a copy lies in the
    noised copy, the second, else 0; its index within its copy)``."""
    second = block_index >= per_half
    return (where(second, 1, 0),
            where(second, block_index - per_half, block_index))


def _diffusion_visit(mask: _Mask, g, t, bq: int, bk: int, where=jnp.where,
                     lo=jnp.minimum):
    """``(key block, whether the step is live)`` of step ``t`` of query
    block ``g``'s walk under block diffusion's mask. A query block's live
    key blocks are a run of clean ones from the first on (up to the one
    that holds its own positions: whole before it, cut by the mask there)
    and, for a noised query block, past a gap of dead ones the noised
    blocks that hold its own positions, which the walk reaches in the step
    after the run's last. A step past the walk names its last block
    again."""
    q_noised, gl = _half(g, mask.clean_len // bq, where)
    # the last clean key a query of this block sees ends its own diffusion
    # block (a clean query's) or the one before it (a noised query's)
    run_last = ((gl + 1) * bq - 1 - q_noised * mask.block) // bk
    own_first, own_last = gl * bq // bk, ((gl + 1) * bq - 1) // bk
    steps = run_last + 1 + q_noised * (own_last - own_first + 1)
    in_run = t <= run_last
    local = where(in_run, t, where(
        q_noised == 1, lo(own_first + t - run_last - 1, own_last), run_last))
    k_noised = where(in_run, 0, q_noised)
    return local + k_noised * (mask.clean_len // bk), t < steps


def _steps(mask: _Mask, bq: int, bk: int, num_q: int, num_k: int) -> int:
    """The innermost grid dimension's extent: the longest walk of a query
    block over its live key blocks."""
    if not mask.block:
        return _longest(lambda g: _k_span(mask, g, bq, bk, num_k), num_q)
    return max(sum(_diffusion_visit(mask, g, t, bq, bk, _choose, min)[1]
                   for t in range(num_k)) for g in range(num_q))


def _walk(mask: _Mask, g, t, bq: int, bk: int, num_k: int):
    """``(key block, live)`` of step ``t`` of query block ``g``'s walk;
    ``live()`` says whether the step does anything (asked where a kernel
    needs it: the causal walk's programs stay as they were traced)."""
    if mask.block:
        kb, live = _diffusion_visit(mask, g, t, bq, bk)
        return kb, lambda: live
    first, last = _k_span(mask, g, bq, bk, num_k, jnp.minimum, jnp.maximum)
    kb = first + t
    return kb, lambda: kb <= last


def _block_index(rows, block: int):
    """``rows // block`` for a vector of positions: a shift where the
    block length is a power of two."""
    if block & (block - 1) == 0:
        return jax.lax.shift_right_logical(rows, block.bit_length() - 1)
    return rows // block


def _diffusion_seen(mask: _Mask, g, kb, bq: int, bk: int):
    """``_seen`` under block diffusion's mask, the block's two halves as
    scalars: a key's diffusion block between two bounds off the query's."""
    q_noised, gl = _half(g, mask.clean_len // bq)
    k_noised, kl = _half(kb, mask.clean_len // bk)
    per_tile = (bq // mask.block, bk // mask.block)
    q_blk = gl * per_tile[0] + _block_index(
        jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0), mask.block)
    k_blk = kl * per_tile[1] + _block_index(
        jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1), mask.block)
    blocks = mask.clean_len // mask.block
    # clean keys: up to the query's block (a clean query) or the one
    # before (a noised one), from the first; noised keys: a noised query's
    # own block alone, a clean query none
    upper = q_blk - q_noised * (1 - k_noised)
    lower = q_blk + blocks * (k_noised * (1 - q_noised) - (1 - k_noised))
    return (k_blk <= upper) & (k_blk >= lower)


def diffusion_seen(block: int, clean_len: int):
    """Block diffusion's mask as a plain ``(2 L, 2 L)`` boolean array,
    ``[i, j]``: query i sees key j (``_Mask``): for XLA's inline attention
    and the tests."""
    index = jnp.arange(2 * clean_len)
    clean = index < clean_len
    blk = (index % clean_len) // block
    q_clean, k_clean = clean[:, None], clean[None, :]
    q_blk, k_blk = blk[:, None], blk[None, :]
    return ((q_clean & k_clean & (k_blk <= q_blk))
            | (~q_clean & k_clean & (k_blk < q_blk))
            | (~q_clean & ~k_clean & (k_blk == q_blk)))


def _whole(mask: _Mask, g, kb, bq: int, bk: int, where=jnp.where):
    """Whether every query of block ``g`` sees every key of the live block
    ``kb`` under block diffusion's mask: a clean key block that ends
    before the query block's first diffusion block does (a noised query
    block's: begins)."""
    q_noised, gl = _half(g, mask.clean_len // bq, where)
    k_noised, kl = _half(kb, mask.clean_len // bk, where)
    return ((k_noised == 0)
            & ((kl + 1) * bk <= gl * bq + mask.block * (1 - q_noised)))


def diffusion_tiles(block: int, clean_len: int, bq: int, bk: int
                    ) -> Tuple[int, int, int]:
    """``(tiles the walk visits, of them those that compare positions,
    live pairs)`` of one head's attention under block diffusion's mask in
    tiles of ``bq`` x ``bk``: what the kernels
    do, counted from the shapes. The live pairs are the mask's own,
    ``L^2 + block x L``, whatever the tiles."""
    mask = _Mask(False, None, block, clean_len)
    num_q, num_k = 2 * clean_len // bq, 2 * clean_len // bk
    visited = compared = 0
    for g in range(num_q):
        for t in range(num_k):
            kb, live = _diffusion_visit(mask, g, t, bq, bk, _choose, min)
            visited += live
            compared += live and not _whole(mask, g, kb, bq, bk, _choose)
    return visited, compared, clean_len * (clean_len + block)


def _seen(mask: _Mask, g, kb, bq: int, bk: int):
    """(bq, bk) booleans: query g * bq + r sees key kb * bk + c."""
    if mask.block:
        return _diffusion_seen(mask, g, kb, bq, bk)
    ahead = (g * bq - kb * bk
             + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
             - jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1))
    seen = ahead >= 0
    return seen if mask.window is None else seen & (ahead < mask.window)


def _on_live(mask: _Mask, g, kb, live, bq: int, bk: int, step) -> None:
    """Run ``step(masked)`` for the (g, kb) block if it is live: unmasked
    where every query of it sees every key of it."""
    if mask.block:
        whole = _whole(mask, g, kb, bq, bk)
    elif not mask.causal:
        step(False)
        return
    else:
        whole = (kb + 1) * bk - 1 <= g * bq
        if mask.window is not None:
            whole &= (g + 1) * bq - 1 - kb * bk < mask.window
    pl.when(live & whole)(lambda: step(False))
    pl.when(live & jnp.logical_not(whole))(lambda: step(True))


def _fwd_kernel(*refs, scale: float, has_bias: bool, mask: _Mask, bq: int,
                bk: int, num_k: int, steps: int):
    """Grid (B, H, num_q, steps), keys innermost. Blocks: q (bq, D), k
    (bk, D), v (bk, Dv), o (bq, Dv); bias (1, bk) or absent; lse (bq, 1).
    Scratch m/l (bq, 1), acc (bq, Dv) persist across the steps of one
    q-block."""
    q_ref, k_ref, v_ref = refs[:3]
    bias_ref = refs[3] if has_bias else None
    o_ref, lse_ref, m_scr, l_scr, acc_scr = refs[3 + has_bias:]
    g, t = pl.program_id(2), pl.program_id(3)
    kb, live = _walk(mask, g, t, bq, bk, num_k)

    @pl.when(t == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def step(masked: bool):
        q, k, v = q_ref[...], k_ref[...], v_ref[...]     # (bq, D), (bk, D)
        s = _dot(q, k, _NT) * scale                      # (bq, bk)
        if has_bias:
            s = s + bias_ref[...]
        if masked:
            # A query that sees nothing of this block adds weights of 1 at
            # the running maximum's start; the diagonal's block, which
            # comes after, scales them away (corr = exp(_NEG - m) = 0).
            s = jnp.where(_seen(mask, g, kb, bq, bk), s, _NEG)
        m_prev, l_prev = m_scr[:], l_scr[:]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_prev * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + _dot(p.astype(v.dtype), v, _NN)

    _on_live(mask, g, kb, live(), bq, bk, step)

    @pl.when(t == steps - 1)
    def _finish():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[...] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[...] = m_scr[:] + jnp.log(l)


def _dq_kernel(*refs, scale: float, has_bias: bool, mask: _Mask, bq: int,
               bk: int, num_k: int, steps: int):
    """Grid (B, H, num_q, steps), keys innermost: dq for one q-block."""
    q_ref, k_ref, v_ref = refs[:3]
    bias_ref = refs[3] if has_bias else None
    do_ref, lse_ref, delta_ref, dq_ref, dq_scr = refs[3 + has_bias:]
    g, t = pl.program_id(2), pl.program_id(3)
    kb, live = _walk(mask, g, t, bq, bk, num_k)

    @pl.when(t == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def step(masked: bool):
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        s = _dot(q, k, _NT) * scale
        if has_bias:
            s = s + bias_ref[...]
        if masked:
            s = jnp.where(_seen(mask, g, kb, bq, bk), s, _NEG)
        p = jnp.exp(s - lse_ref[...])                    # softmax weights
        dp = _dot(do, v, _NT)                            # (bq, bk)
        ds = p * (dp - delta_ref[...])
        dq_scr[:] = dq_scr[:] + _dot(ds.astype(k.dtype), k, _NN) * scale

    _on_live(mask, g, kb, live(), bq, bk, step)

    @pl.when(t == steps - 1)
    def _finish():
        dq_ref[...] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale: float, has_bias: bool, mask: _Mask, bq: int,
                bk: int, num_q: int, live_q: int, steps: int):
    """Grid (B, Hkv, num_k, steps), queries innermost: dk/dv/dbias for one
    k-block, over the ``steps // live_q`` query heads that read this
    key/value head, ``live_q`` query blocks each. dbias is emitted per
    key/value head (summed over heads by the caller)."""
    q_ref, k_ref, v_ref = refs[:3]
    bias_ref = refs[3] if has_bias else None
    do_ref, lse_ref, delta_ref, dk_ref, dv_ref = refs[3 + has_bias:][:5]
    rest = refs[8 + has_bias:]
    dbias_ref = rest[0] if has_bias else None
    dk_scr, dv_scr = rest[has_bias:][:2]
    dbias_scr = rest[has_bias + 2] if has_bias else None
    t, u = pl.program_id(2), pl.program_id(3)
    first, last = _q_span(mask, t, bq, bk, num_q, jnp.minimum, jnp.maximum)
    g = first + u % live_q

    @pl.when(u == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)
        if has_bias:
            dbias_scr[:] = jnp.zeros_like(dbias_scr)

    def step(masked: bool):
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        s = _dot(q, k, _NT) * scale
        if has_bias:
            s = s + bias_ref[...]
        if masked:
            s = jnp.where(_seen(mask, g, t, bq, bk), s, _NEG)
        p = jnp.exp(s - lse_ref[...])                    # (bq, bk)
        dv_scr[:] = dv_scr[:] + _dot(p.astype(do.dtype), do, _TN)
        dp = _dot(do, v, _NT)
        ds = p * (dp - delta_ref[...])
        dk_scr[:] = dk_scr[:] + _dot(ds.astype(q.dtype), q, _TN) * scale
        if has_bias:
            dbias_scr[:] = dbias_scr[:] + ds.sum(axis=0, keepdims=True)

    _on_live(mask, g, t, g <= last, bq, bk, step)

    @pl.when(u == steps - 1)
    def _finish():
        dk_ref[...] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[:].astype(dv_ref.dtype)
        if has_bias:
            dbias_ref[...] = dbias_scr[:]


def _bwd_kernel(*refs, scale: float, has_bias: bool, has_delta: bool,
                mask: _Mask, group: int, v_group: int, bq: int, bk: int,
                num_q: int, num_k: int, steps: int):
    """Grid (B, H, num_q, steps), keys innermost: dq, dk, dv (and dbias)
    from one s, p, dp and ds a live block. dq gathers over a query block's
    steps in scratch, as in ``_dq_kernel``. dk of a key head gathers over
    everything that reads it, its ``group`` query heads (which the grid
    visits one after the other) times their query blocks, in float32
    scratch that holds the head's whole sequence; dv of a value head
    likewise over its ``v_group`` query heads (``group`` of them, or a
    multiple where key heads share values). Their output blocks are the
    whole sequence too, stay resident meanwhile and are written at the
    head's last step. Blocks as ``_dq_kernel``'s, but v and do (., Dv),
    dk (Sk, D), dv (Sk, Dv), dbias (num_k, 1, bk) per key head, and in
    place of delta, unless the caller brings one (``has_delta``), the
    forward's output (bq, Dv): delta = sum_d do o is taken here, in
    float32, once a query block."""
    q_ref, k_ref, v_ref = refs[:3]
    bias_ref = refs[3] if has_bias else None
    do_ref, lse_ref, aux_ref, dq_ref, dk_ref, dv_ref = refs[3 + has_bias:][:6]
    rest = refs[9 + has_bias:]
    dbias_ref = rest[0] if has_bias else None
    dq_scr, delta_scr, dk_scr, dv_scr = rest[has_bias:]
    j, g, t = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    kb, live = _walk(mask, g, t, bq, bk, num_k)
    keys = pl.ds(pl.multiple_of(kb * bk, bk), bk)

    def run_of(heads: int):
        """(first, last) step of a run of ``heads`` query heads."""
        return ((j % heads == 0) & (g == 0) & (t == 0),
                (j % heads == heads - 1) & (g == num_q - 1)
                & (t == steps - 1))

    opens, closes = run_of(group)
    shared = v_group != group     # key heads share a value head

    @pl.when(opens)
    def _init_head():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        if not shared:
            dv_scr[...] = jnp.zeros_like(dv_scr)
        if has_bias:
            dbias_ref[...] = jnp.zeros_like(dbias_ref)

    if shared:
        v_opens, v_closes = run_of(v_group)

        @pl.when(v_opens)
        def _init_value_head():
            dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(t == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        delta_scr[...] = (aux_ref[...] if has_delta
                          else _delta(do_ref[...], aux_ref[...], True))

    def step(masked: bool):
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        s = _dot(q, k, _NT) * scale                      # (bq, bk)
        if has_bias:
            s = s + bias_ref[...]
        if masked:
            s = jnp.where(_seen(mask, g, kb, bq, bk), s, _NEG)
        p = jnp.exp(s - lse_ref[...])                    # softmax weights
        dp = _dot(do, v, _NT)
        ds = p * (dp - delta_scr[...])
        if has_bias:
            dbias_ref[kb] += ds.sum(axis=0, keepdims=True)
        ds = ds.astype(q.dtype)
        dv_scr[keys, :] += _dot(p.astype(do.dtype), do, _TN)
        dk_scr[keys, :] += _dot(ds, q, _TN) * scale
        dq_scr[...] += _dot(ds, k, _NN) * scale

    _on_live(mask, g, kb, live(), bq, bk, step)

    @pl.when(t == steps - 1)
    def _finish():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)

    @pl.when(closes)
    def _finish_head():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        if not shared:
            dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)

    if shared:
        @pl.when(v_closes)
        def _finish_value_head():
            dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


# -- block planning & padding ----------------------------------------------


def _pick_block(seq: int, preferred: int) -> int:
    """Largest divisor of ``seq`` that is <= preferred (>= 1)."""
    block = min(preferred, seq)
    while seq % block:
        block -= 1
    return block


def _pick_aligned_block(seq: int, preferred: int, align: int) -> int:
    """Aligned tile size: the largest multiple of ``align`` <= preferred
    that divides ``round_up(seq, align)`` (no padding beyond alignment) —
    a fixed preferred block would pad e.g. S=768 up to 1024 with 512
    blocks (~33% wasted FLOPs); this picks 384. But never a DEGENERATE
    divisor: below ~64 rows the MXU runs mostly idle per pass (S=1016 =
    8*127 has no nontrivial aligned divisor), and padding up to the
    preferred block is far cheaper than 8-row tiles — so when only
    tiny divisors exist, fall back to the preferred block and pad."""
    target = _round_up(seq, align)
    cap = min(_round_up(preferred, align), target)
    floor = max(align, min(cap, 64))
    block = cap
    while target % block and block > floor:
        block -= align
    if target % block:
        return cap  # only degenerate divisors: pad instead
    return block


# Default VMEM tile sizes, shared by every public entry point here and by
# the ring-attention flash hops (ops/ring_attention.py): retune in ONE
# place. A sequence up to them takes the one-block kernels. Measured on a
# v5e (PR 31; forward + backward, 16,384 tokens, 12 heads of 64, bf16, ms):
# at S = 1024 blocks 512/512 5.49, 512/1024 4.49, 1024/1024 (one block)
# 3.16, and 2.39 off the fused projection; at S = 2048 9.22, 7.24 and
# 6.99 (1024/2048: 6.96).
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024


def _plan(sq: int, sk: int, block_q: int, block_k: int, interpret: bool):
    """(bq, bk, sq_pad, sk_pad). Interpret mode: any divisor works.
    TPU: blocks must be (8, 128)-tile aligned, so pad the sequence dims
    up to aligned block multiples instead of shrinking blocks."""
    if interpret:
        return (_pick_block(sq, block_q), _pick_block(sk, block_k), sq, sk)
    bq = _pick_aligned_block(sq, block_q, 8)
    sq_pad = _round_up(sq, bq)
    bk = _pick_aligned_block(sk, block_k, 128)
    sk_pad = _round_up(sk, bk)
    return bq, bk, sq_pad, sk_pad


def planned_blocks(seq: int, block_q: int, block_k: int,
                   interpret: bool = False) -> Tuple[int, int]:
    """The tiles ``(bq, bk)`` the blocked kernels cut a self-attention
    over ``seq`` positions into, given the preferred ones."""
    return _plan(seq, seq, block_q, block_k, interpret)[:2]


def _pad_rows(x, target: int, axis: int = 2):
    """Zero-pad ``x`` along ``axis`` (the S of (B, H, S, D) by default) to
    ``target`` rows."""
    if x.shape[axis] == target:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, target - x.shape[axis])
    return jnp.pad(x, widths)


def _prep_bias(bias, b: int, sk: int, sk_pad: int):
    """Validated f32 bias padded to sk_pad (padded keys masked), or None
    when there is neither a bias nor key padding."""
    if bias is not None and bias.shape != (b, 1, 1, sk):
        raise ValueError(
            f"flash_attention bias must be key-side (B, 1, 1, S) = "
            f"{(b, 1, 1, sk)}, got {bias.shape}; a full (.., S, S) bias is "
            "not supported: a causal mask or a window is structural, the "
            "``causal`` / ``window`` arguments, not a tensor")
    if bias is None and sk_pad == sk:
        return None
    base = (jnp.zeros((b, 1, 1, sk), jnp.float32) if bias is None
            else bias.astype(jnp.float32))
    if sk_pad != sk:
        base = jnp.pad(base, ((0, 0), (0, 0), (0, 0), (0, sk_pad - sk)),
                       constant_values=_MASK)
    return base


# -- one block: layouts and launches ------------------------------------------


class _Layout(NamedTuple):
    """How the one-block kernels' (S, heads * D) blocks are cut from the
    arrays: ``rows(n, which)`` is the BlockSpec of an n-row block of the
    q (0), k (1) or v (2) operand (outputs and ``do`` are cut like q),
    ``shape(n)`` the array an n-row output has."""
    grid: Tuple[int, int]          # (B, H // heads)
    heads: int
    scale: float                   # 1 / sqrt(D)
    rows: Callable[[int, int], Any]
    shape: Callable[[int], Tuple[int, ...]]


def _bhsd_layout(b: int, h: int, d: int) -> _Layout:
    """(B, H, S, D) arrays, one head a grid step."""
    return _Layout(
        (b, h), 1, d ** -0.5,
        lambda n, which: pl.BlockSpec((None, None, n, d),
                                      lambda i, j: (i, j, 0, 0)),
        lambda n: (b, h, n, d))


def _packed_layout(b: int, h: int, d: int, heads: int) -> _Layout:
    """The (B, S, 3 x H x D) projection as it leaves its matmul, and
    (B, S, H x D) outputs: a block is ``heads`` heads' columns, q, k and v
    a third of the columns apart. No (B, S, H, D) -> (B, H, S, D) copy on
    either side of the kernel."""
    groups = h // heads
    return _Layout(
        (b, groups), heads, d ** -0.5,
        lambda n, which: pl.BlockSpec(
            (None, n, heads * d), lambda i, j: (i, 0, which * groups + j)),
        lambda n: (b, n, h * d))


def _packed_heads(num_heads: int, d: int) -> Optional[int]:
    """Heads to a block of the packed projection: as many as fill the
    lanes. None where whole heads do not make lane-aligned blocks."""
    if d % _LANES == 0:
        return 1
    heads = _LANES // d
    if _LANES % d == 0 and num_heads % heads == 0:
        return heads
    return None


def _stats_spec(heads: int, n: int):
    """lse / delta, (B, H // heads, heads, S) float32: a group's rows."""
    return pl.BlockSpec((None, None, heads, n), lambda i, j: (i, j, 0, 0))


def _one_block(sq: int, sk: int, block_q: int, block_k: int) -> bool:
    """Whether ``_plan`` gives one q block and one k block."""
    return sq <= block_q and sk <= block_k


def _pad_one_block(s: int, interpret: bool) -> int:
    """Rows of the one block: whole lanes on the chip (lse is a row of
    them, and the keys are the scores' lanes)."""
    return s if interpret else _round_up(s, _LANES)


def _forward1(layout: _Layout, q, k, v, bias, sq: int, sk: int,
              interpret: bool):
    """One-block forward over operands already padded to ``sq`` / ``sk``
    rows; ``bias`` (B, 1, 1, sk) float32 or None. Returns the output in
    the layout's shape and lse (B, H // heads, heads, sq)."""
    b, groups = layout.grid
    in_specs = [layout.rows(sq, 0), layout.rows(sk, 1), layout.rows(sk, 2)]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(pl.BlockSpec((None, 1, sk), lambda i, j: (i, 0, 0)))
        args.append(bias.reshape(b, 1, sk))
    return pl.pallas_call(
        functools.partial(_fwd1_kernel, scale=layout.scale,
                          heads=layout.heads, has_bias=bias is not None),
        grid=layout.grid,
        in_specs=in_specs,
        out_specs=[layout.rows(sq, 0), _stats_spec(layout.heads, sq)],
        out_shape=[
            jax.ShapeDtypeStruct(layout.shape(sq), q.dtype),
            jax.ShapeDtypeStruct((b, groups, layout.heads, sq), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_compiler_params(interpret,
                                         ("parallel", "parallel")),
    )(*args)


def _backward1(layout: _Layout, q, k, v, do, lse, delta, bias, sq: int,
               sk: int, interpret: bool):
    """One-block backward; operands as ``_forward1``'s, lse and delta
    (B, H // heads, heads, sq), delta None where the kernel is to take
    its own (``_bwd1_kernel``). Returns dq, dk, dv in the layout's shape
    and dbias (B, 1, 1, sk) float32 or None."""
    b, groups = layout.grid
    stats = _stats_spec(layout.heads, sq)
    column = pl.BlockSpec((None, sk, 1), lambda i, j: (i, 0, 0))
    in_specs = [layout.rows(sq, 0), layout.rows(sk, 1), layout.rows(sk, 2),
                layout.rows(sq, 0), stats]
    args = [q, k, v, do, lse]
    out_specs = [layout.rows(sq, 0), layout.rows(sk, 0), layout.rows(sk, 0)]
    out_shape = [jax.ShapeDtypeStruct(layout.shape(sq), q.dtype),
                 jax.ShapeDtypeStruct(layout.shape(sk), k.dtype),
                 jax.ShapeDtypeStruct(layout.shape(sk), v.dtype)]
    if delta is not None:
        in_specs.append(stats)
        args.append(delta)
    if bias is not None:
        in_specs.append(column)
        args.append(bias.reshape(b, sk, 1))
        out_specs.append(column)
        out_shape.append(jax.ShapeDtypeStruct((b, sk, 1), jnp.float32))
    results = pl.pallas_call(
        functools.partial(_bwd1_kernel, scale=layout.scale,
                          heads=layout.heads, has_delta=delta is not None,
                          has_bias=bias is not None),
        grid=layout.grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        # dbias's block stays put while a row's heads add to it.
        compiler_params=_compiler_params(
            interpret,
            ("parallel", "parallel" if bias is None else "arbitrary")),
    )(*args)
    dbias = None if bias is None else results[3].reshape(b, 1, 1, sk)
    return results[0], results[1], results[2], dbias


# -- the sequence in blocks: layouts and launches ------------------------------


def _rows(packed: bool, n: int, d: int, index):
    """BlockSpec of an (n, D) block of one head's rows, cut from a
    (B, H, S, D) array or, ``packed``, from (B, S, H x D) as a projection
    leaves it (D whole lanes); ``index(*grid ids) -> (batch, head, row
    block)``."""
    if packed:
        def at(*ids):
            batch, head, block = index(*ids)
            return batch, block, head
        return pl.BlockSpec((None, n, d), at)
    return pl.BlockSpec((None, None, n, d), lambda *ids: (*index(*ids), 0))


def _stats(n: int, index):
    """lse / delta, (B, H, S, 1) float32: an (n, 1) column."""
    return pl.BlockSpec((None, None, n, 1), lambda *ids: (*index(*ids), 0))


class _Dims(NamedTuple):
    """The operands' sizes: H query heads and Hkv key heads of D, Hv value
    heads of Dv."""
    b: int
    h: int
    hkv: int
    hv: int
    sq: int
    sk: int
    d: int
    dv: int

    @property
    def group(self) -> int:
        """Query heads to a key head."""
        return self.h // self.hkv

    @property
    def v_group(self) -> int:
        """Query heads to a value head."""
        return self.h // self.hv


def _dims(packed: bool, q, k, v, num_heads: Optional[int],
          num_kv_heads: Optional[int], num_v_heads: Optional[int],
          packed_v: bool) -> _Dims:
    """The sizes of the operands, q and k in one layout and v in one
    (``packed``, ``packed_v``); packed arrays say their head counts beside
    them (``num_v_heads`` ``None``: the keys')."""
    if not packed:
        (b, h, sq, d), (_, hkv, sk, _) = q.shape, k.shape
    else:
        (b, sq, width), sk = q.shape, k.shape[1]
        h, hkv, d = num_heads, num_kv_heads, width // num_heads
    if not packed_v:
        hv, dv = v.shape[1], v.shape[3]
    else:
        hv = hkv if num_v_heads is None else num_v_heads
        dv = v.shape[-1] // hv
    if h % hkv:
        raise ValueError(f"{h} query heads do not share {hkv} key/value "
                         "heads evenly")
    if hkv % hv:
        raise ValueError(f"{hkv} key heads do not share {hv} value heads "
                         "evenly")
    return _Dims(b, h, hkv, hv, sq, sk, d, dv)


def _blocked_plan(mask: _Mask, bias, b: int, sq: int, sk: int, block_q: int,
                  block_k: int, interpret: bool):
    """(bq, bk, sq_pad, sk_pad, bias array or None) of a blocked launch. A
    causal mask is self-attention's: a padded key lies after every real
    query and needs no bias to hide it."""
    if mask.causal and sq != sk:
        raise ValueError(f"a causal mask needs as many queries as keys, "
                         f"got {sq} and {sk}")
    bq, bk, sq_pad, sk_pad = _plan(sq, sk, block_q, block_k, interpret)
    if mask.block:
        if not sq == sk == 2 * mask.clean_len:
            raise ValueError(
                f"block diffusion's mask over {mask.clean_len} clean "
                f"positions needs {2 * mask.clean_len} queries and as many "
                f"keys, got {sq} and {sk}")
        if ((sq_pad, sk_pad) != (sq, sk) or mask.clean_len % bq
                or mask.clean_len % bk or bq % mask.block
                or bk % mask.block):
            raise ValueError(
                f"tiles of {bq} x {bk} do not fit block diffusion's mask "
                f"over {mask.clean_len} clean positions in blocks of "
                f"{mask.block}: a tile lies in one copy (its sides divide "
                "the clean length) and holds whole blocks (the block length "
                "divides its sides); other block lengths need a walk that "
                "cuts a block between tiles, which does not exist")
    bias_arr = (None if bias is None and mask.causal
                else _prep_bias(bias, b, sk, sk_pad))
    return bq, bk, sq_pad, sk_pad, bias_arr


def _query_major(mask: _Mask, dims: _Dims, bq: int, bk: int, num_k: int):
    """Index maps of a (B, H, num_q, live key blocks) grid: ``(q_at, k_at,
    v_at)`` give (batch, head, row block) of the query-side, the keys' and
    the values' blocks; a step past the query block's last live key block
    names that one again."""
    def q_at(i, j, g, t):
        return i, j, g

    def key_side(group: int):
        def at(i, j, g, t):
            if mask.block:
                return i, j // group, _diffusion_visit(mask, g, t, bq, bk)[0]
            first, last = _k_span(mask, g, bq, bk, num_k, jnp.minimum,
                                  jnp.maximum)
            return i, j // group, jnp.minimum(first + t, last)
        return at

    return q_at, key_side(dims.group), key_side(dims.v_group)


def _blocked_forward(q, k, v, bias, mask: _Mask, block_q: int, block_k: int,
                     interpret: bool, packed: bool = False,
                     num_heads: Optional[int] = None,
                     num_kv_heads: Optional[int] = None,
                     scale: Optional[float] = None, out_dtype=None,
                     num_v_heads: Optional[int] = None,
                     packed_v: Optional[bool] = None):
    """``(out, lse (B, H, Sq, 1))`` from the blocked kernels; the operands
    (B, H, S, D) with k of H or fewer heads and v of as many or fewer
    again, at a width Dv of its own, or ``packed`` (B, S, H x D); v, and
    ``out`` with it (as q at Dv), in a layout of their own where
    ``packed_v`` says so (``None``: q's). ``scale`` multiplies the scores
    (``None``: 1 / sqrt(D)); ``out`` is in ``out_dtype`` (``None``: q's)."""
    packed_v = packed if packed_v is None else packed_v
    dims = _dims(packed, q, k, v, num_heads, num_kv_heads, num_v_heads,
                 packed_v)
    b, h, sq, sk, d, dv = (dims.b, dims.h, dims.sq, dims.sk, dims.d,
                           dims.dv)
    axis, v_axis = 1 if packed else 2, 1 if packed_v else 2
    bq, bk, sq_pad, sk_pad, bias_arr = _blocked_plan(
        mask, bias, b, sq, sk, block_q, block_k, interpret)
    qp = _pad_rows(q, sq_pad, axis)
    kp, vp = _pad_rows(k, sk_pad, axis), _pad_rows(v, sk_pad, v_axis)
    num_q, num_k = sq_pad // bq, sk_pad // bk
    steps = _steps(mask, bq, bk, num_q, num_k)
    q_at, k_at, v_at = _query_major(mask, dims, bq, bk, num_k)
    in_specs = [_rows(packed, bq, d, q_at), _rows(packed, bk, d, k_at),
                _rows(packed_v, bk, dv, v_at)]
    args = [qp, kp, vp]
    if bias_arr is not None:
        in_specs.append(pl.BlockSpec(
            (None, None, 1, bk), lambda *ids: (ids[0], 0, 0, k_at(*ids)[2])))
        args.append(bias_arr)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel,
                          scale=d ** -0.5 if scale is None else scale,
                          has_bias=bias_arr is not None, mask=mask, bq=bq,
                          bk=bk, num_k=num_k, steps=steps),
        grid=(b, h, num_q, steps),
        in_specs=in_specs,
        out_specs=[_rows(packed_v, bq, dv, q_at), _stats(bq, q_at)],
        out_shape=[
            jax.ShapeDtypeStruct(
                (b, sq_pad, h * dv) if packed_v else (b, h, sq_pad, dv),
                q.dtype if out_dtype is None else out_dtype),
            jax.ShapeDtypeStruct((b, h, sq_pad, 1), jnp.float32),
        ],
        scratch_shapes=[
            _vmem((bq, 1), jnp.float32),
            _vmem((bq, 1), jnp.float32),
            _vmem((bq, dv), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_compiler_params(interpret, _BLOCKED),
    )(*args)
    if sq_pad != sq:
        out = jax.lax.slice_in_dim(out, 0, sq, axis=v_axis)
        lse = lse[:, :, :sq]
    return out, lse


def _fused_fits(bq: int, bk: int, sk_pad: int, d: int, dtype,
                dv: Optional[int] = None) -> bool:
    """Whether ``_bwd_kernel``'s scoped VMEM holds a key head's whole dk
    (D wide) and a value head's whole dv (Dv wide; ``None``: D), float32
    scratch and the output's two buffers each, beside the handful of
    float32 (bq, bk) arrays of a step. 8,192 keys of D = Dv = 128 in bf16
    are 16 MiB, 16,384 are 32, and as many at D = 64, which a tile pads
    to its 128 lanes; past that the backward is the split pair, whose
    blocks do not grow with the sequence."""
    columns = _round_up(d, _LANES) + _round_up(d if dv is None else dv,
                                               _LANES)
    resident = sk_pad * columns * (4 + 2 * jnp.dtype(dtype).itemsize)
    return resident + 6 * 4 * bq * bk <= _VMEM_LIMIT_BYTES


def _blocked_kind(sq: int, sk: int, d: int, dtype, block_q: int,
                  block_k: int, interpret: bool,
                  dv: Optional[int] = None) -> str:
    """Which backward the blocked family launches for these sizes."""
    bq, bk, _, sk_pad = _plan(sq, sk, block_q, block_k, interpret)
    return "fused" if _fused_fits(bq, bk, sk_pad, d, dtype, dv) else "split"


def _blocked_backward(q, k, v, bias, out, lse, do, mask: _Mask,
                      block_q: int, block_k: int, interpret: bool,
                      packed: bool = False, num_heads: Optional[int] = None,
                      num_kv_heads: Optional[int] = None, delta=None,
                      scale: Optional[float] = None,
                      num_v_heads: Optional[int] = None,
                      packed_v: Optional[bool] = None):
    """``(dq, dk, dv, dbias per key head (B, Hkv, 1, Sk) or None)`` from
    the blocked kernels: one (``_bwd_kernel``) where a key head's dk and a
    value head's dv fit in VMEM (``_fused_fits``), else the split pair,
    which takes values shaped as the keys only. Operands as
    ``_blocked_forward``'s, ``out`` and ``do`` laid out as ``q`` at Dv (in
    v's layout), ``lse`` (B, H, Sq, 1). ``delta`` (B, H, Sq, 1) float32 is
    the caller's sum_d do o where it has one already; ``out`` is then not
    read."""
    packed_v = packed if packed_v is None else packed_v
    dims = _dims(packed, q, k, v, num_heads, num_kv_heads, num_v_heads,
                 packed_v)
    b, h, hkv, sq, sk, d, dv = (dims.b, dims.h, dims.hkv, dims.sq, dims.sk,
                                dims.d, dims.dv)
    group, axis, v_axis = dims.group, 1 if packed else 2, 1 if packed_v else 2
    bq, bk, sq_pad, sk_pad, bias_arr = _blocked_plan(
        mask, bias, b, sq, sk, block_q, block_k, interpret)
    fused = _fused_fits(bq, bk, sk_pad, d, k.dtype, dv)
    if not fused and (dims.hv, dv) != (hkv, d):
        raise ValueError(
            f"{sk_pad} keys' dk and dv do not fit in VMEM, and the dq + "
            "dk/dv pair gathers dv a key head: it takes values of the "
            f"keys' width and head count, not {dims.hv} heads of {dv} "
            f"beside {hkv} of {d}")
    if not fused and mask.block:
        raise ValueError(
            f"{sk_pad} keys' dk and dv do not fit in VMEM, and the dq + "
            "dk/dv pair does not take block diffusion's mask: under it a "
            "clean key block is seen by two runs of query blocks, and "
            "``_dkv_kernel`` walks one")
    if delta is None and not fused:
        heads = (b, sq, h, dv) if packed_v else do.shape
        delta = _delta(do.reshape(heads), out.reshape(heads), True)
        if packed_v:
            delta = delta.transpose(0, 2, 1, 3)          # (B, H, Sq, 1)
    qp, dop = _pad_rows(q, sq_pad, axis), _pad_rows(do, sq_pad, v_axis)
    kp, vp = _pad_rows(k, sk_pad, axis), _pad_rows(v, sk_pad, v_axis)
    aux = (_pad_rows(out, sq_pad, v_axis) if delta is None
           else _pad_rows(delta, sq_pad))
    num_q, num_k = sq_pad // bq, sk_pad // bk
    has_bias = bias_arr is not None
    static = dict(scale=d ** -0.5 if scale is None else scale,
                  has_bias=has_bias, mask=mask, bq=bq, bk=bk)

    def specs(q_at, k_at, v_at):
        rows = [_rows(packed, bq, d, q_at), _rows(packed, bk, d, k_at),
                _rows(packed_v, bk, dv, v_at)]
        if has_bias:
            rows.append(pl.BlockSpec(
                (None, None, 1, bk),
                lambda *ids: (ids[0], 0, 0, k_at(*ids)[2])))
        return rows + [_rows(packed_v, bq, dv, q_at), _stats(bq, q_at),
                       _stats(bq, q_at) if delta is not None
                       else _rows(packed_v, bq, dv, q_at)]

    args = [qp, kp, vp] + ([bias_arr] if has_bias else []) \
        + [dop, _pad_rows(lse, sq_pad), aux]
    # Query-major: grid (B, H, num_q, live key blocks), K innermost.
    steps = _steps(mask, bq, bk, num_q, num_k)
    q_at, k_at, v_at = _query_major(mask, dims, bq, bk, num_k)
    dq_shape = jax.ShapeDtypeStruct(qp.shape, q.dtype)
    dkv_shape = [jax.ShapeDtypeStruct(kp.shape, k.dtype),
                 jax.ShapeDtypeStruct(vp.shape, v.dtype)]
    dq_scratch = _vmem((bq, d), jnp.float32)

    if fused:
        def head_of(group: int):
            return lambda i, j, g, t: (i, j // group, 0)

        out_specs = [_rows(packed, bq, d, q_at),
                     _rows(packed, sk_pad, d, head_of(group)),
                     _rows(packed_v, sk_pad, dv, head_of(dims.v_group))]
        out_shape = [dq_shape] + dkv_shape
        if has_bias:
            out_specs.append(pl.BlockSpec(
                (None, None, num_k, 1, bk),
                lambda i, j, g, t: (i, j // group, 0, 0, 0)))
            out_shape.append(
                jax.ShapeDtypeStruct((b, hkv, num_k, 1, bk), jnp.float32))
        dq, dk, dv, *dbias = pl.pallas_call(
            functools.partial(_bwd_kernel, has_delta=delta is not None,
                              group=group, v_group=dims.v_group,
                              num_q=num_q, num_k=num_k, steps=steps,
                              **static),
            grid=(b, h, num_q, steps),
            in_specs=specs(q_at, k_at, v_at),
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[dq_scratch, _vmem((bq, 1), jnp.float32),
                            _vmem((sk_pad, d), jnp.float32),
                            _vmem((sk_pad, dv), jnp.float32)],
            interpret=interpret,
            # Only the rows are independent: a key head's dk and a value
            # head's dv gather over their query heads and their blocks.
            compiler_params=_compiler_params(
                interpret, ("parallel", "arbitrary", "arbitrary",
                            "arbitrary")),
        )(*args)
        dbias = dbias[0].reshape(b, hkv, 1, sk_pad) if has_bias else None
    else:
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, num_k=num_k, steps=steps, **static),
            grid=(b, h, num_q, steps),
            in_specs=specs(q_at, k_at, v_at),
            out_specs=_rows(packed, bq, d, q_at),
            out_shape=dq_shape,
            scratch_shapes=[dq_scratch],
            interpret=interpret,
            compiler_params=_compiler_params(interpret, _BLOCKED),
        )(*args)

        # dk, dv: grid (B, Hkv, num_k, query heads of the group x live
        # query blocks), Q innermost.
        live_q = _longest(lambda t: _q_span(mask, t, bq, bk, num_q), num_k)
        steps = group * live_q

        def q_of(i, j, t, u):
            first, last = _q_span(mask, t, bq, bk, num_q, jnp.minimum,
                                  jnp.maximum)
            return (i, j * group + u // live_q,
                    jnp.minimum(first + u % live_q, last))

        def k_of(i, j, t, u):
            return i, j, t

        out_specs = [_rows(packed, bk, d, k_of), _rows(packed_v, bk, d, k_of)]
        scratch = [_vmem((bk, d), jnp.float32), _vmem((bk, d), jnp.float32)]
        if has_bias:
            # Per key/value head: indexed by the head grid dim, unlike the
            # input bias (which broadcasts over heads from index 0).
            out_specs.append(pl.BlockSpec((None, None, 1, bk),
                                          lambda i, j, t, u: (i, j, 0, t)))
            dkv_shape.append(
                jax.ShapeDtypeStruct((b, hkv, 1, sk_pad), jnp.float32))
            scratch.append(_vmem((1, bk), jnp.float32))
        dk, dv, *dbias = pl.pallas_call(
            functools.partial(_dkv_kernel, num_q=num_q, live_q=live_q,
                              steps=steps, **static),
            grid=(b, hkv, num_k, steps),
            in_specs=specs(q_of, k_of, k_of),
            out_specs=out_specs,
            out_shape=dkv_shape,
            scratch_shapes=scratch,
            interpret=interpret,
            compiler_params=_compiler_params(interpret, _BLOCKED),
        )(*args)
        dbias = dbias[0] if has_bias else None
    if sq_pad != sq:
        dq = jax.lax.slice_in_dim(dq, 0, sq, axis=axis)
    if sk_pad != sk:
        dk = jax.lax.slice_in_dim(dk, 0, sk, axis=axis)
        dv = jax.lax.slice_in_dim(dv, 0, sk, axis=v_axis)
    return dq, dk, dv, dbias[..., :sk] if has_bias else None


# -- forward / backward dispatch --------------------------------------------


def _flash_forward(q, k, v, bias, block_q: int, block_k: int,
                   interpret: bool, mask: _Mask = _Mask()):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if _takes_one_block(q, k, block_q, block_k, mask):
        sq_pad = _pad_one_block(sq, interpret)
        sk_pad = _pad_one_block(sk, interpret)
        out, lse = _forward1(
            _bhsd_layout(b, h, d), _pad_rows(q, sq_pad), _pad_rows(k, sk_pad),
            _pad_rows(v, sk_pad), _prep_bias(bias, b, sk, sk_pad), sq_pad,
            sk_pad, interpret)
        return out[:, :, :sq], lse.reshape(b, h, sq_pad, 1)[:, :, :sq]
    return _blocked_forward(q, k, v, bias, mask, block_q, block_k, interpret)


def _takes_one_block(q, k, block_q: int, block_k: int, mask: _Mask) -> bool:
    """The one-block kernels serve an unmasked sequence that fits one
    block of each side, every query head with key/value heads of its
    own; a mask or shared heads take the blocked kernels at any length."""
    return (_one_block(q.shape[2], k.shape[2], block_q, block_k)
            and not mask.causal and q.shape[1] == k.shape[1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def flash_attention(q: jax.Array,
                    k: jax.Array,
                    v: jax.Array,
                    bias: Optional[jax.Array] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False,
                    causal: bool = False,
                    window: Optional[int] = None) -> jax.Array:
    """Exact attention via the Pallas flash kernels.

    Args:
        q, k, v: (B, H, S, D), any float dtype: the products take them as
            they are and accumulate in float32; the softmax is float32.
            ``k`` and ``v`` may have fewer heads than ``q``, a divisor of
            its count: query head h reads key/value head h // (H / Hkv).
        bias: optional additive key-side bias, strictly (B, 1, 1, S).
        block_q/block_k: preferred VMEM tile sizes (``DEFAULT_BLOCK_*``).
            A sequence that fits one block of each takes the one-block
            kernels: a single backward kernel, no accumulator.
        interpret: run under the Pallas interpreter (CPU tests).
        causal: query i sees keys j <= i only. Static: the mask is
            structural, not a tensor; key blocks no query of a block
            sees are not visited, forward or backward.
        window: of those, the last ``window`` only (j > i - window).

    Neither forward nor backward materializes an O(S²) tensor in HBM.
    """
    out, _ = _flash_forward(q, k, v, bias, block_q, block_k, interpret,
                            _mask_of(causal, window))
    return out


def flash_forward(q, k, v, bias=None, block_q: int = DEFAULT_BLOCK_Q,
                  block_k: int = DEFAULT_BLOCK_K, interpret: bool = False):
    """Forward kernels only: returns ``(out, lse)`` with lse
    (B, H, Sq, 1) float32 — the partial-softmax residual ring attention
    needs to merge per-hop results (ops/ring_attention.py)."""
    return _flash_forward(q, k, v, bias, block_q, block_k, interpret)


def _flash_fwd(q, k, v, bias, block_q, block_k, interpret, causal, window):
    out, lse = _flash_forward(q, k, v, bias, block_q, block_k, interpret,
                              _mask_of(causal, window))
    return out, (q, k, v, bias, out, lse)


def _flash_bwd(block_q, block_k, interpret, causal, window, residuals, do):
    q, k, v, bias, out, lse = residuals
    return flash_backward(q, k, v, bias, out, lse, do, block_q, block_k,
                          interpret, causal, window)


def _delta(do, out, keepdims: bool = False):
    """delta_i = sum_d do_i * o_i, the softmax backward's correction term,
    over the trailing axis in float32."""
    return jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
                   keepdims=keepdims)


def flash_backward(q, k, v, bias, out, lse, do, block_q: int = DEFAULT_BLOCK_Q,
                   block_k: int = DEFAULT_BLOCK_K, interpret: bool = False,
                   causal: bool = False, window: Optional[int] = None,
                   delta=None):
    """Backward kernels: ``(dq, dk, dv, dbias)`` from the standard flash
    residuals. ``lse`` may be global (covering MORE keys than ``k``) — the
    ring backward exploits this: with the global logsumexp, the recomputed
    per-hop weights ``exp(s - lse)`` are the global softmax restricted to
    this hop's keys, so per-hop grads sum to the exact global gradient.
    ``delta`` (B, H, Sq) float32 is sum_d do o where the caller has it
    already (the ring takes it once for all its hops); ``out`` is then
    not read."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    mask = _mask_of(causal, window)
    if _takes_one_block(q, k, block_q, block_k, mask):
        sq_pad = _pad_one_block(sq, interpret)
        sk_pad = _pad_one_block(sk, interpret)
        if delta is None:
            delta = _delta(do, out)
        dq, dk, dv, dbias = _backward1(
            _bhsd_layout(b, h, d), _pad_rows(q, sq_pad), _pad_rows(k, sk_pad),
            _pad_rows(v, sk_pad), _pad_rows(do, sq_pad),
            _pad_rows(lse.reshape(b, h, 1, sq), sq_pad, 3),
            _pad_rows(delta.reshape(b, h, 1, sq), sq_pad, 3),
            _prep_bias(bias, b, sk, sk_pad), sq_pad, sk_pad, interpret)
        dbias = (None if bias is None
                 else dbias[..., :sk].astype(bias.dtype))
        return dq[:, :, :sq], dk[:, :, :sk], dv[:, :, :sk], dbias
    dq, dk, dv, dbias = _blocked_backward(
        q, k, v, bias, out, lse[..., None] if lse.ndim == 3 else lse, do,
        mask, block_q, block_k, interpret,
        delta=None if delta is None else delta[..., None])
    if bias is None:
        return dq, dk, dv, None
    return dq, dk, dv, dbias.sum(axis=1, keepdims=True).astype(bias.dtype)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# -- grouped heads off their projections -------------------------------------


def _reads_in_place(head_dim: int, interpret: bool) -> bool:
    """Whether the blocked kernels cut a head's (rows, D) blocks straight
    from a (B, S, H x D) projection: where D is whole lanes (the
    interpreter cuts anything)."""
    return interpret or head_dim % _LANES == 0


def _value_heads(q, v, num_heads: int, num_kv_heads: int,
                 num_v_heads: Optional[int], interpret: bool):
    """``(Hv, whether the kernels read (B, S, H x D) q and k in place,
    whether (B, S, Hv x Dv) v)``: each where its width is whole lanes."""
    hv = num_kv_heads if num_v_heads is None else num_v_heads
    return (hv, _reads_in_place(q.shape[-1] // num_heads, interpret),
            _reads_in_place(v.shape[-1] // hv, interpret))


def grouped_forward(q, k, v, num_heads: int, num_kv_heads: int,
                    causal: bool = True, window: Optional[int] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K, interpret: bool = False,
                    scale: Optional[float] = None, out_dtype=None,
                    num_v_heads: Optional[int] = None,
                    diffusion: Optional[Diffusion] = None):
    """Self-attention of projections as their matmuls leave them: ``q``
    (B, S, H x D), ``k`` (B, S, Hkv x D) and ``v`` (B, S, Hv x Dv), a
    head's columns side by side; query head h reads key head h // (H /
    Hkv) and value head h // (H / Hv), each fetched once a block and
    repeated nowhere. ``num_v_heads`` (``None``: Hkv, and then Dv = D:
    plain grouped attention) divides Hkv: where key heads share their
    values (differential attention's two maps a pair, values of 2 D) a
    map's scores are made once, not once a part of its values. Returns
    ``(out (B, S, H x Dv), lse (B, H, S, 1))``. Where D is whole lanes
    (128) the kernels read q and k and write dq and dk in these arrays in
    place: no (B, S, H, D) -> (B, H, S, D) copy on either side; narrower
    heads (64) are copied head-major around the same kernels; and so v,
    ``out``, ``do`` and dv by Dv. ``scale`` multiplies
    the scores (``None``: 1 / sqrt(D)); ``out_dtype`` is ``out``'s
    (``None``: q's; float32 where what follows subtracts two outputs that
    nearly cancel). ``diffusion`` = ``(block length, clean length)``
    with ``causal`` off: block
    diffusion's mask over a row that holds a sequence twice (``_Mask``).
    Without a custom_vjp of its own: the caller pairs it
    with :func:`grouped_backward` under its own scope (models/mellum.py
    does)."""
    mask = _mask_of(causal, window, diffusion)
    hv, in_place, v_in_place = _value_heads(q, v, num_heads, num_kv_heads,
                                            num_v_heads, interpret)
    if not in_place:
        q, k = _split_heads(q, num_heads), _split_heads(k, num_kv_heads)
    out, lse = _blocked_forward(
        q, k, v if v_in_place else _split_heads(v, hv), None, mask, block_q,
        block_k, interpret, in_place, num_heads, num_kv_heads, scale,
        out_dtype, hv, v_in_place)
    return out if v_in_place else _merge_heads(out), lse


def grouped_backward(q, k, v, out, lse, do, num_heads: int,
                     num_kv_heads: int, causal: bool = True,
                     window: Optional[int] = None,
                     block_q: int = DEFAULT_BLOCK_Q,
                     block_k: int = DEFAULT_BLOCK_K, interpret: bool = False,
                     scale: Optional[float] = None,
                     num_v_heads: Optional[int] = None,
                     diffusion: Optional[Diffusion] = None):
    """``(dq, dk, dv)`` in the operands' layouts from
    :func:`grouped_forward`'s operands and results and the output's
    cotangent ``do`` (B, S, H x Dv)."""
    mask = _mask_of(causal, window, diffusion)
    hv, in_place, v_in_place = _value_heads(q, v, num_heads, num_kv_heads,
                                            num_v_heads, interpret)
    if not in_place:
        q, k = _split_heads(q, num_heads), _split_heads(k, num_kv_heads)
    if not v_in_place:
        v, out, do = (_split_heads(x, heads) for x, heads in (
            (v, hv), (out, num_heads), (do, num_heads)))
    dq, dk, dv, _ = _blocked_backward(
        q, k, v, None, out, lse, do, mask, block_q, block_k, interpret,
        in_place, num_heads, num_kv_heads, scale=scale, num_v_heads=hv,
        packed_v=v_in_place)
    if not in_place:
        dq, dk = _merge_heads(dq), _merge_heads(dk)
    return dq, dk, dv if v_in_place else _merge_heads(dv)


def grouped_backward_kind(q, k, num_heads: int,
                          block_q: int = DEFAULT_BLOCK_Q,
                          block_k: int = DEFAULT_BLOCK_K,
                          interpret: bool = False,
                          value_dim: Optional[int] = None) -> str:
    """Which backward :func:`grouped_backward` launches for these
    operands, a value head ``value_dim`` wide (``None``: as a query's):
    ``"fused"`` (one kernel) or ``"split"`` (dq, then dk/dv)."""
    s, d = q.shape[1], q.shape[-1] // num_heads
    return _blocked_kind(s, s, d, k.dtype, block_q, block_k, interpret,
                         value_dim)


def count_backward(kind: str) -> None:
    """One attention backward of ``kind`` (``one_block`` | ``fused`` |
    ``split``) traced: the models call it where their backward rule runs,
    once a layer (the jitted program under it is traced once a shape)."""
    rt_metrics.counter(
        "rsdl_attention_backward_total",
        "Attention backwards traced, by the kernels that compute them: "
        "one for a sequence in one block, one for a sequence in blocks "
        "whose dk and dv fit in VMEM, or the dq and dk/dv pair",
        kind=kind).inc()


# -- attention straight off the fused projection -----------------------------


def _split_heads(x, num_heads: int):
    """(B, S, H x D) -> (B, H, S, D)."""
    b, s, width = x.shape
    return x.reshape(b, s, num_heads, width // num_heads).transpose(0, 2, 1, 3)


def _split_qkv(qkv, num_heads: int):
    """(B, S, 3 x H x D) -> q, k, v, each (B, H, S, D)."""
    return [_split_heads(x, num_heads) for x in jnp.split(qkv, 3, axis=-1)]


def _merge_heads(x):
    """(B, H, S, D) -> (B, S, H x D)."""
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _packs(qkv, num_heads: int) -> Optional[int]:
    """Heads to a block where the one-block kernels can read ``qkv`` in
    place, else None: the heads then go through (B, H, S, D) copies."""
    _, s, width = qkv.shape
    heads = _packed_heads(num_heads, width // (3 * num_heads))
    if not _one_block(s, s, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K):
        return None
    return heads


def qkv_forward(qkv, bias, num_heads: int, interpret: bool = False):
    """Self-attention of a fused projection ``qkv`` (B, S, 3 x H x D), the
    columns [q | k | v] with a head's D side by side; ``bias`` key-side
    (B, 1, 1, S) or None. Returns ``(out (B, S, H x D), lse (B, H, S))``.
    Without a custom_vjp of its own: the caller pairs it with
    :func:`qkv_backward` (models/bert.py does, under its scope)."""
    b, s, width = qkv.shape
    heads = _packs(qkv, num_heads)
    if heads is None:
        q, k, v = _split_qkv(qkv, num_heads)
        out, lse = _flash_forward(q, k, v, bias, DEFAULT_BLOCK_Q,
                                  DEFAULT_BLOCK_K, interpret)
        return _merge_heads(out), lse[..., 0]
    s_pad = _pad_one_block(s, interpret)
    padded = _pad_rows(qkv, s_pad, 1)
    out, lse = _forward1(
        _packed_layout(b, num_heads, width // (3 * num_heads), heads),
        padded, padded, padded, _prep_bias(bias, b, s, s_pad), s_pad, s_pad,
        interpret)
    return out[:, :s], lse.reshape(b, num_heads, s_pad)[..., :s]


def qkv_backward(qkv, bias, out, lse, do, num_heads: int,
                 interpret: bool = False):
    """``(dqkv, dbias)`` from :func:`qkv_forward`'s operands and results
    and the output's cotangent ``do`` (B, S, H x D). ``out`` is read only
    where the heads go through (B, H, S, D) copies (:func:`saves_out`)."""
    b, s, width = qkv.shape
    heads = _packs(qkv, num_heads)
    if heads is None:
        q, k, v = _split_qkv(qkv, num_heads)
        dq, dk, dv, dbias = flash_backward(
            q, k, v, bias, _split_heads(out, num_heads), lse,
            _split_heads(do, num_heads), interpret=interpret)
        return jnp.concatenate([_merge_heads(x) for x in (dq, dk, dv)],
                               axis=-1), dbias
    s_pad = _pad_one_block(s, interpret)
    padded = _pad_rows(qkv, s_pad, 1)
    dq, dk, dv, dbias = _backward1(
        _packed_layout(b, num_heads, width // (3 * num_heads), heads),
        padded, padded, padded, _pad_rows(do, s_pad, 1),
        _pad_rows(lse.reshape(b, num_heads // heads, heads, s), s_pad, 3),
        None, _prep_bias(bias, b, s, s_pad), s_pad, s_pad, interpret)
    dbias = None if bias is None else dbias[..., :s].astype(bias.dtype)
    return jnp.concatenate([dq, dk, dv], axis=-1)[:, :s], dbias


def saves_out(qkv, num_heads: int) -> bool:
    """Whether :func:`qkv_backward` reads the forward's output: not where
    the one-block kernel takes delta from its own p and dp."""
    return _packs(qkv, num_heads) is None


def qkv_backward_kind(qkv, num_heads: int, interpret: bool = False) -> str:
    """Which backward :func:`qkv_backward` launches for this projection."""
    _, s, width = qkv.shape
    if _one_block(s, s, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K):
        return "one_block"
    return _blocked_kind(s, s, width // (3 * num_heads), qkv.dtype,
                         DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K, interpret)


# From this sequence length the kernels beat XLA's inline attention on the
# chip. Measured on a v5e (PR 31, PERF.md section 6; forward + backward of
# a layer's attention off the fused projection, 16,384 tokens, 12 heads of
# 64, bf16, ms, inline / kernels): S = 128 1.38 / 1.39, 192 2.55 / 1.90,
# 256 2.21 / 1.24, 512 4.58 / 1.44, 1024 8.49 / 2.39, 2048 16.28 / 7.13.
FLASH_MIN_SEQ_LEN = 192


def beats_inline(seq_len: int) -> bool:
    """Whether a model should take these kernels over XLA's inline
    attention: on the chip (elsewhere they run interpreted), from the
    measured crossover up."""
    return on_tpu() and seq_len >= FLASH_MIN_SEQ_LEN


def make_flash_attention_fn(block_q: int = DEFAULT_BLOCK_Q,
                            block_k: int = DEFAULT_BLOCK_K,
                            interpret: Optional[bool] = None):
    """An ``attention_fn(q, k, v, bias)`` closure for models/bert.py.

    ``interpret=None`` auto-selects the Pallas interpreter off-TPU.
    """
    if interpret is None:
        interpret = not on_tpu()

    def attention_fn(q, k, v, bias=None):
        return flash_attention(q, k, v, bias, block_q, block_k, interpret)

    return attention_fn
