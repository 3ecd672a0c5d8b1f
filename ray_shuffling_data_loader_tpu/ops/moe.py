"""A sparse-expert layer over the experts this chip holds.

A language model's expert layer routes every token over ALL of its experts
(softmax over the router's logits, the ``top_k`` largest, their weights
renormalised; or, given a selection bias, sigmoid scores, the ``top_k``
largest of score plus bias, the scores of the picks renormalised:
:func:`route`) and adds up what the picked experts give. Under expert
parallelism a chip holds a share of the experts, ``held = (first, count)``
of the router's width, as ``ops/embedding.py:lookup`` is told its mesh:
:func:`moe` computes the part of the sum that the held experts give, for
the tokens routed to them, and leaves out what the absent ones would add.
On one chip the layer runs without its exchange; nothing here stands in
for the absent chips.

Exact for any routing: no capacity, no dropped token. The (token, pick)
pairs whose expert is held are sorted by expert and cut into tiles of
``tile`` rows of ONE expert each (an expert's last tile is part empty), so
the occupied tiles are a prefix of a static worst case, and a loop walks
them under a bound computed on the device (the idiom of
``models/bert.py:_masked_nll``): every pick held walks ``tokens x top_k /
tile`` tiles and more, none held walks none. A tile fetches its tokens'
rows, runs the expert's three products on bf16 operands (float32
accumulation: SwiGLU, ``silu(x G) * (x U)`` then ``D``), weighs the rows
and puts them side by side in a buffer of one round's tiles
(``round_rows``: what an even routing fills, and a quarter); when a round's
tiles are done each token adds up its picks' rows of it, and a routing
that fills more than one buffer takes another round. Nothing of ``tokens
x top_k`` rows is ever materialised, and nothing is scattered: XLA's
scatter-add of a tile's rows into their tokens' took 0.87 ms a tile on a
v5e, fifteen times the tile's products (PERF.md section 6, PR 32). The
backward is written out (``_moe_bwd``): the same walk, a tile's ``x G``
and ``x U`` made again, eight products a tile, the experts' gradients
added in float32 in place, the tokens' combined as the forward's sums.

The rows move by DMA where they can (``rows_by_dma``: on the TPU, rows of
whole lanes). A tile's rows come in by ``_fetch``, one copy a row out of
HBM: on a v5e a fetched row of 4.6 KB takes 21 ns, 14 where two arrays are
fetched off one index (the backward's ``x`` and ``dout``). The tokens' sums
are made by ``_combine_runs`` (``sums_by_runs``): the sort above is stable,
so the picks that the tokens of one block of 128 consecutive tokens send to
one held expert lie in consecutive rows of the round's buffer, and a block
needs one copy a whole tile of 16 bf16 rows that such a run touches, not
one a live pick: 31 copies a block at ``mellum_train_8k``'s shapes (runs of
16 rows; 255 live picks), 23 at ``sdar_train_8k``'s (8; 128), 37 at
``laguna_train_8k``'s (4; 128), 11 at ``lfm2_train_8k``'s (8; 63). The
copies land side by side in a slab in VMEM, a block ahead of the sums; a
token's sum is a product on the MXU of a matrix of ones, where its picks
lie, with the slab, 128 rows at a time; the float32 sums are written once.
The scalar core tests no pick. Measured on a v5e, a round's combine alone,
20 in a loop on the device with what XLA makes for it (``_landing``) and
the loop's own copy of the sums (1.00 ms at the first shape, 0.46 at the
others): 1.83 ms at ``mellum_train_8k``'s shapes, 0.75 at
``sdar_train_8k``'s, 0.91 at ``laguna_train_8k``'s, 0.48 at
``lfm2_train_8k``'s, 0.53 at runs of ONE row (32 of 256 experts, top-2: 16
rows landed a live one), that is 28 / 46 / 56 / 60 / 128 ns a live row.
What bounds it now is the HBM: the float32 sums read and written and the
tiles landed, 2.3 MB a block for 1.2 MB of live rows at the first shape,
2.4 for 0.5 at the third (PERF.md section 6, PR 50). Its parent,
``_combine_dma`` (PR 37), issued one copy a LIVE pick of a one-row word
array and read 3.55 / 1.21 / 1.20 / 0.83 / 0.72 ms in the same loop (68 ns
a live row at two live picks a token, 102 at one, by PR 37's own measure: a
block of 128 tokens cost it 7 us of tests and masked adds whatever it
fetched, a copy 35 ns of the scalar core's time; the HBM bounded neither),
and ITS parent XLA's gather: 45 ns a row inside the walk whatever the row
holds, a dead pick's row of zeros too, bent round by sorting the tokens by
their count of live picks, 104 ns a live row (PERF.md section 6, PR 37).
Off the chip and at other shapes the fallback is the plain one: every
pick's row gathered, the spare row of zeros for a dead pick, added in
ascending order of the rows. The kernel makes the same float32 additions of
the same rows, but a chunk of the slab is summed before it joins the chunks
before it: equal to the gathers' sums to float32's rounding, and to the
bit where the order cannot matter (two picks a token; rows whose sums
float32 holds exactly, which bf16 rows of like size are: every shape above
read equal to the bit on the chip).

The grouped products are XLA's dense ``dot_general`` on a tile, not a
Pallas kernel and not ``jax.lax.ragged_dot``: one expert a tile makes each
a plain (tile, hidden) x (hidden, expert width) product the MXU takes at
full width (PERF.md section 6, PR 32, has what was measured).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_shuffling_data_loader_tpu.ops import on_tpu
from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics
from ray_shuffling_data_loader_tpu.runtime import telemetry

#: The name a device trace shows the layer's operations under: router,
#: sort, grouped products and combine, forward and backward.
SCOPE = telemetry.step_scope("rsdl.lm.moe")

#: The name around the walk's two ``jit``s, so outside its loops: what XLA
#: makes under the ``jit``'s or a ``while``'s own name and no ``with``
#: inside them reaches (the zero-fills of the buffers a loop carries, the
#: layout copies in and out of them, a loop's counter and bounds: 19.7 ms
#: of ``sdar_train_8k``'s 460, PERF.md section 6, PR 49). A name of its
#: own and not ``SCOPE``: a reader that sums ``SCOPE`` (``moe_pct``) would
#: count each ``while`` and its body both.
LOOPS_SCOPE = telemetry.step_scope("rsdl.lm.moe_loops")

#: The most rows of one expert an even routing puts in a tile of the walk
#: (``tile_rows``).
_TILE_LOAD = 1024

_LANES, _SUBLANES = 128, 8

#: Rows a grid step of the fetch moves, all of their copies in flight at
#: once, and tokens a grid step of the combine sums.
_BLOCK = 128

#: Rows of the landing slab that one product of the runs' combine takes.
_CHUNK = 128

_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


#: A router's scoring, by name: softmax over all the experts, or a sigmoid
#: an expert with a selection bias (:func:`route`).
SOFTMAX, SIGMOID_BIAS = "softmax", "sigmoid_bias"

#: What a sigmoid router adds to the sum of a token's picked scores before
#: it divides by it.
_SIGMOID_SUM_EPS = 1e-6


def route(logits, top_k: int, scale: float = 1.0, bias=None):
    """``(ids (N, top_k) int32, weights (N, top_k) float32)``, float32
    throughout, differentiable in the weights. Without ``bias`` the router
    scores by softmax over all the experts: the ``top_k`` largest,
    renormalised to sum to ``scale`` (``norm_topk_prob``, then a model's
    ``moe_routed_scaling_factor``). With ``bias`` (experts,) it scores by a
    sigmoid an expert (``SIGMOID_BIAS``): the picks are the ``top_k``
    largest of ``score + bias``, the weights the picks' SCORES, the bias
    left out, over their sum plus 1e-6, times ``scale``. The bias only
    chooses: it takes no gradient (its balancing update moves it, by the
    experts' :func:`loads`: ``models/mellum.py:_experts``)."""
    if bias is None:
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        top, ids = jax.lax.top_k(probs, top_k)
        return (ids.astype(jnp.int32),
                scale * top / top.sum(axis=-1, keepdims=True))
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    # only the picks' ids are read off the biased scores: nothing flows
    # back to the bias
    _, ids = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    top = jnp.take_along_axis(scores, ids, axis=-1)
    return (ids.astype(jnp.int32),
            scale * top / (top.sum(axis=-1, keepdims=True)
                           + _SIGMOID_SUM_EPS))


def _router_logits(x, router):
    """Float32 all the way: at the default precision the chip would round
    the router's weights to bf16, and a pick flipped between two experts
    of near-equal weight moves a token's whole output."""
    return jax.lax.dot_general(x.astype(jnp.float32), router, _NN,
                               precision=jax.lax.Precision.HIGHEST)


def _router_weights(x, router, top_k: int, scale: float, bias=None):
    return route(_router_logits(x, router), top_k, scale, bias)[1]


@functools.partial(jax.jit, static_argnums=(3,))
def loads(x, router, bias, top_k: int):
    """(experts,) int32: how many of the tokens ``x`` (N, hidden) pick
    each of the router's experts, held here or not, under :func:`route`.
    What a selection bias's balancing update reads."""
    with jax.named_scope(SCOPE):
        ids, _ = route(_router_logits(x, router), top_k, 1.0, bias)
        # counted by comparison, as ``_dispatch`` counts the held ones
        return jnp.sum(ids.reshape(-1, 1) == jnp.arange(router.shape[1]),
                       axis=0, dtype=jnp.int32)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def tile_rows(tokens: int, top_k: int, experts: int) -> int:
    """Rows of one expert in a tile of the walk, from the static shapes:
    an expert's expected rows under an even routing (``tokens x top_k /
    experts``) cut into as few tiles as hold ``_TILE_LOAD`` rows each, and
    an eighth of room, in whole lanes. Larger tiles leave more of each
    expert's last tile empty; smaller ones make the loop long, and a
    tile's fixed cost (its index arithmetic, its row fetches' start, the
    in-place adds of three expert gradients) is 0.26 ms in the backward
    on a v5e. The room and not the bare share: a power of two at the
    expected load puts every expert on a tile's edge, one tile more or
    fewer by the seed's luck, and the walk is longer or shorter for it.

    Measured on a v5e, a layer alone, forward / forward and backward, ms,
    the rows moved by DMA (PR 37; PR 32 and PR 34 measured the same with
    XLA's gathers and chose the same tiles). At ``mellum_train_8k``'s
    shapes (32,768 tokens x 2304, 16 of 64 experts of 896, top-8: 4,096
    rows an expert, 3,730-4,530 under a balanced router): tiles of 512
    18.8 / 57.2, 768 18.4 / 52.7, 1024 18.0 / 50.7, 1152 17.7 / 49.5,
    1280 18.3 / 51.2, 2304 17.1 / 47.3; the rule gives 1152 (20.4 / 56.5
    with XLA's gathers), and 4 x 1152 holds every such load. 2304, two
    tiles an expert, is 4 % faster still at this routing: the rule is
    left as it is until a PR of its own weighs that against the rounds a
    less even routing then takes. At ``laguna_train_8k``'s (16,384 tokens
    x 2048, 32 of 256 experts of 512, top-8: 512 rows an expert, 413-581
    under a balanced router): 256 7.2 / 19.9, 384 7.5 / 19.9, 512 7.0 /
    18.6, 640 6.6 / 16.9, 768 6.9 / 18.0, 1024 7.7 / 20.0; the rule gives
    640, one tile an expert (7.8 / 20.0 with XLA's gathers)."""
    expected = -(-tokens * top_k // experts)
    tiles = -(-expected // _TILE_LOAD)
    share = -(-expected // tiles)
    return _round_up(share + share // 8, 128)


def round_rows(tokens: int, top_k: int, count: int, experts: int,
               tile: int) -> int:
    """Rows of the buffer that holds one round's tiles: what an even
    routing sends to the held experts and a quarter more, and a part-empty
    tile an expert; the worst case (every pick held) if that is less. A
    routing that sends more takes further rounds, none is dropped."""
    worst = _round_up(tokens * top_k, tile)
    even = -(-tokens * top_k * count // experts)
    return min(worst, _round_up(even + even // 4, tile)) + count * tile


def _held(ids, first: int, count: int):
    """Each pick's held expert, from 0; ``count`` for an absent one's."""
    local = ids - first
    return jnp.where((local >= 0) & (local < count), local, count)


def _dispatch(ids, first: int, count: int, tile: int):
    """The walk's plan from the picks ``ids`` (N, top_k). The pairs are
    sorted by held expert (pairs of absent experts last) and each held
    expert's run is padded to whole tiles: ``(order, sizes, starts,
    tile_ends)`` are the pairs' flat indices in that order, each held
    expert's pair count and start among them, and the running count of
    tiles by expert; ``position`` (N, top_k) is each pair's row in the
    padded order, -1 for an absent expert's."""
    expert = _held(ids.reshape(-1), first, count)
    order = jnp.argsort(expert, stable=True).astype(jnp.int32)
    # counted by comparison: a scatter-add of every pair into a handful
    # of bins is the serial kind
    sizes = jnp.sum(expert[:, None] == jnp.arange(count + 1)[None, :],
                    axis=0, dtype=jnp.int32)
    starts = jnp.cumsum(sizes) - sizes
    tiles = (sizes[:count] + tile - 1) // tile
    tile_ends = jnp.cumsum(tiles)
    rank = jnp.argsort(order).astype(jnp.int32)
    padded_start = jnp.append((tile_ends - tiles) * tile, 0)
    position = jnp.where(expert < count,
                         padded_start[expert] + rank - starts[expert], -1)
    return (order, sizes, starts, tile_ends), position.reshape(ids.shape)


def _walk_counts(plan, position, count: int, per_round: int):
    """What the walk does for this routing, int32, in the order of
    ``telemetry.STEP_STAT_FIELDS["moe_walk"]``."""
    _, sizes, _, tile_ends = plan
    tiles = tile_ends[-1]
    counts = {
        "pairs": jnp.int32(position.size),
        "pairs_held": jnp.sum(sizes[:count]),
        "tiles": tiles,
        "rounds": (tiles + per_round - 1) // per_round,
        "fullest_expert_rows": jnp.max(sizes[:count]),
    }
    return jnp.stack([counts[field] for field in
                      telemetry.STEP_STAT_FIELDS["moe_walk"]]
                     ).astype(jnp.int32)


def _tile(i, plan, flat_weights, top_k: int, tile: int):
    """Tile ``i`` of the walk: its expert, each row's token (0 for an empty
    row) and its pick's weight (0 for an empty row)."""
    order, sizes, starts, tile_ends = plan
    expert = jnp.sum(tile_ends <= i).astype(jnp.int32)
    first_tile = tile_ends[expert] - (sizes[expert] + tile - 1) // tile
    offset = (i - first_tile) * tile
    row = jnp.arange(tile, dtype=jnp.int32)
    occupied = row < sizes[expert] - offset
    pair = order[jnp.minimum(starts[expert] + offset + row,
                             order.shape[0] - 1)]
    return (expert, jnp.where(occupied, pair // top_k, 0),
            jnp.where(occupied, flat_weights[pair], 0.0)[:, None])


def _of(weights, expert):
    return jax.lax.dynamic_index_in_dim(weights, expert, 0, keepdims=False)


def _put(buffer, rows, at):
    return jax.lax.dynamic_update_slice_in_dim(
        buffer, rows.astype(buffer.dtype), at, axis=0)


def _walk(plan, position, rows: int, tile: int, tile_fn, gather_fn, carry,
          buffers):
    """The walk, in rounds of ``rows // tile`` tiles: ``tile_fn(i, carry,
    buffers, at)`` computes tile ``i`` and puts its rows at row ``at`` of
    the round's ``buffers``; after a round's tiles ``gather_fn(carry,
    buffers, index)`` adds to each token what its picks' rows hold
    (``index`` (N, top_k): a pick's row in the buffers, or ``rows``, a
    row nobody writes, for a pick outside the round). No scatter: a tile's
    rows land side by side, and the tokens fetch them."""
    per_round = rows // tile
    tiles = plan[3][-1]

    def one_round(r, state):
        carry, buffers = state

        def one_tile(i, state):
            with jax.named_scope(SCOPE):
                return tile_fn(i, *state, (i - r * per_round) * tile)

        carry, buffers = jax.lax.fori_loop(
            r * per_round, jnp.minimum((r + 1) * per_round, tiles),
            one_tile, (carry, buffers))
        with jax.named_scope(SCOPE):
            first_row = r * rows
            local = position - first_row
            index = jnp.where((local >= 0) & (local < rows), local, rows)
        return gather_fn(carry, buffers, index, first_row), buffers

    return jax.lax.fori_loop(0, (tiles + per_round - 1) // per_round,
                             one_round, (carry, buffers))[0]


def dma_takes(tokens: int, hidden: int, tile: int, dtype) -> bool:
    """Whether the kernels below can move such rows: float32 or bfloat16,
    whole lanes of 32-bit words a row, in arrays of whole sublanes of
    rows."""
    dtype = jnp.dtype(dtype)
    return (dtype in (jnp.float32, jnp.bfloat16)
            and hidden * dtype.itemsize % (4 * _LANES) == 0
            and tokens % _SUBLANES == 0 and tile % _SUBLANES == 0)


def rows_by_dma(tokens: int, hidden: int, tile: int, dtype) -> bool:
    """Whether the walk moves its rows by DMA and not by XLA's gather,
    from what the trace can see: on the TPU, where :func:`dma_takes` the
    rows."""
    return on_tpu() and dma_takes(tokens, hidden, tile, dtype)


def _words(a):
    """``a`` (N, hidden) as rows of 32-bit words, which is what a one-row
    DMA takes (a sublane of bf16 holds two rows, and Mosaic slices no
    half sublane out of HBM): float32 as it is; bfloat16 as (N, hidden /
    2) uint32, word ``j`` holding column ``j`` in its low half and column
    ``j + hidden / 2`` in its high half. Halves and not neighbours: an
    elementwise pass over two contiguous slices, no shuffle of the lanes,
    and a half widened to float32 is one shift or one mask
    (:func:`_halves`)."""
    if a.dtype == jnp.float32:
        return a
    half = a.shape[1] // 2
    # The bits as they are: widened through float32 instead, XLA drops
    # the rounding to bf16 that the caller's cast asked for (excess
    # precision is allowed it), and the words hold truncated values.
    # Each half sliced before it is widened: one fusion, where widening
    # the whole row first wrote it out at twice the size and read it back.
    low, high = (jax.lax.bitcast_convert_type(part, jnp.uint16
                                              ).astype(jnp.uint32)
                 for part in (a[:, :half], a[:, half:]))
    return low | (high << 16)


def _halves(words):
    """The two halves of words of :func:`_words`, float32: a bfloat16 is
    the high half of the float32 of its value."""
    return (jax.lax.bitcast_convert_type(words << 16, jnp.float32),
            jax.lax.bitcast_convert_type(words & jnp.uint32(0xFFFF0000),
                                         jnp.float32))


def _tiled(words):
    """``words`` (N, lanes) as (N / 8, lanes / 128, 8, 128): the (8, 128)
    tiles the array is made of in HBM, in the order they lie there, so
    XLA makes this a bitcast and moves nothing. Mosaic slices one row out
    of a tile that is the array's whole width and refuses it of a wider
    array (``Slice shape along dimension 0 must be aligned to tiling
    (8)``): row ``r`` is ``[r // 8, :, r % 8]`` here, ``lanes / 128``
    pieces of 512 bytes that one strided copy moves."""
    rows, lanes = words.shape
    return words.reshape(rows // _SUBLANES, _SUBLANES, lanes // _LANES,
                         _LANES).transpose(0, 2, 1, 3)


def _row_of(tiled_ref, row):
    return tiled_ref.at[row // _SUBLANES, :, pl.ds(row % _SUBLANES, 1), :]


def _block(rows: int) -> int:
    """Rows a grid step of the kernels below moves, all of their copies
    in flight at once: ``_BLOCK``, fewer where there are fewer, in whole
    sublanes of bf16."""
    return min(_BLOCK, _round_up(rows, 2 * _SUBLANES))


def _fetch(sources, index, dtype, interpret: bool):
    """``[s[index] for s in sources]`` in ``dtype``, each row by a DMA of
    its own straight out of HBM: ``sources`` are (N, lanes) arrays of
    :func:`_words`, ``index`` (rows,) int32, scalar-prefetched. A grid
    step starts a block's copies back to back, each into its row of a
    landing buffer of 128-lane pieces, waits for them, and writes the
    pieces side by side, a bf16 row's words widened on the way."""
    packed = jnp.dtype(dtype) != sources[0].dtype
    lanes = sources[0].shape[1]
    pieces = lanes // _LANES
    hidden = 2 * lanes if packed else lanes
    rows, count = index.shape[0], len(sources)
    block = _block(rows)
    padded = _round_up(rows, block)
    if padded != rows:
        index = jnp.pad(index, (0, padded - rows))

    def kernel(index_ref, *refs):
        srcs, outs = refs[:count], refs[count:2 * count]
        landings, sem = refs[2 * count:-1], refs[-1]
        base = pl.program_id(0) * block

        def copies(row, j):
            return [pltpu.make_async_copy(
                _row_of(src, row), landing.at[:, pl.ds(j, 1), :], sem)
                for src, landing in zip(srcs, landings)]

        def start(group, _):
            for j in range(_SUBLANES):      # unrolled: static strides
                j = group * _SUBLANES + j
                for copy in copies(index_ref[base + j], j):
                    copy.start()

        def wait(group, _):
            for _ in range(_SUBLANES):
                for copy in copies(0, 0):
                    copy.wait()

        jax.lax.fori_loop(0, block // _SUBLANES, start, None)
        jax.lax.fori_loop(0, block // _SUBLANES, wait, None)
        for landing, out in zip(landings, outs):
            for piece in range(pieces):
                at = piece * _LANES
                parts = (_halves(landing[piece]) if packed
                         else (landing[piece],))
                for k, part in enumerate(parts):
                    out[:, k * lanes + at:k * lanes + at + _LANES] = (
                        part.astype(out.dtype))

    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(padded // block,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * count,
            out_specs=[pl.BlockSpec((block, hidden), lambda i, _: (i, 0))
                       ] * count,
            scratch_shapes=[pltpu.VMEM((pieces, block, _LANES),
                                       sources[0].dtype)] * count
            + [pltpu.SemaphoreType.DMA(())]),
        out_shape=[jax.ShapeDtypeStruct((padded, hidden), dtype)] * count,
        interpret=interpret,
    )(index, *(_tiled(source) for source in sources))
    return [out[:rows] for out in outs]


def _group(dtype) -> int:
    """Rows of one tile of an array of ``dtype`` as it lies in HBM (8 of
    float32, 16 of bfloat16, by 128 lanes): the least that one copy of
    whole rows moves in one piece, whatever the array's width."""
    return _SUBLANES * 4 // jnp.dtype(dtype).itemsize


def _runs(ids, plan, first: int, count: int, tile: int, block: int):
    """The combine's plan, beside :func:`_dispatch`'s: ``(starts, counts,
    expert)``. The sort is stable, so inside a held expert's run of the
    padded order the pairs stand by ascending token, and the picks that
    the tokens of one block of ``block`` consecutive tokens send to one
    held expert lie in consecutive rows: ``starts`` and ``counts``
    (blocks, count) are each such run's first padded row and its rows
    (counted by comparison, as ``sizes`` is); ``expert`` (N, top_k) is
    each pick's held expert, ``count`` for an absent one's."""
    tokens = ids.shape[0]
    _, sizes, _, tile_ends = plan
    expert = _held(ids, first, count)
    blocks = -(-tokens // block)
    by_block = jnp.pad(expert, ((0, blocks * block - tokens), (0, 0)),
                       constant_values=count).reshape(blocks, -1, 1)
    counts = jnp.sum(by_block == jnp.arange(count), axis=1, dtype=jnp.int32)
    padded_start = (tile_ends - (sizes[:count] + tile - 1) // tile) * tile
    return (padded_start + jnp.cumsum(counts, axis=0) - counts, counts,
            expert)


def _landing(runs, index, first_row, rows: int, group: int, block: int):
    """Where a round's runs land in a block's slab: ``(first_group,
    groups, slot)``. A run is clipped to the round's ``rows`` (one may
    straddle two rounds) and fetched as the ``groups`` whole groups of
    ``group`` rows it touches, from group ``first_group`` of the buffer
    on, the held experts' one after another in ascending order (both
    (blocks x count,)); ``slot`` (blocks x block, top_k) is the slab's
    row that each pick of ``index`` then lies in, -1 for a pick with
    nothing in the round. One value a pick out of a table of (block,
    expert), by comparison: XLA's gather of single values is the serial
    kind."""
    starts, counts, expert = runs
    blocks, count = starts.shape
    tokens, top_k = index.shape
    low = jnp.clip(starts - first_row, 0, rows)
    high = jnp.clip(starts + counts - first_row, 0, rows)
    first_group = low // group
    groups = jnp.where(high > low, (high - 1) // group - first_group + 1, 0)
    shift = (jnp.cumsum(groups, axis=1) - groups - first_group) * group
    pad = ((0, blocks * block - tokens), (0, 0))
    of_pick = (jnp.pad(expert, pad, constant_values=count).reshape(
        blocks, block, top_k, 1) == jnp.arange(count))
    shift = jnp.sum(jnp.where(of_pick, shift[:, None, None, :], 0), axis=-1)
    index = jnp.pad(index, pad, constant_values=rows)
    slot = jnp.where(index < rows, index + shift.reshape(index.shape), -1)
    return first_group.reshape(-1), groups.reshape(-1), slot


def _slab_rows(block: int, top_k: int, count: int, group: int) -> int:
    """Rows of the slab that holds whatever a block's runs land: a run of
    ``n`` rows touches ``(n - 1) // group + 2`` groups at most, a token
    picks an expert once (``n <= block``), and the block's runs hold
    ``block x top_k`` rows between them; in whole chunks."""
    groups = min(block * top_k // group + 2 * count,
                 count * (block // group + 1))
    return _round_up(groups * group, _CHUNK)


def _slabs_bytes(tokens: int, hidden: int, dtype, top_k: int,
                 count: int) -> int:
    """Bytes of VMEM that the two halves of such a slab take."""
    dtype = jnp.dtype(dtype)
    return (2 * _slab_rows(_block(tokens), top_k, count, _group(dtype))
            * hidden * dtype.itemsize)


#: The most bytes that the two halves of the runs' landing slab may take
#: of a v5e's 128 MiB of VMEM (`mellum_train_8k`'s take 14.2 MB,
#: `laguna_train_8k`'s 16.8).
_SLAB_BYTES = 48 << 20


def sums_by_runs(tokens: int, hidden: int, tile: int, dtype, top_k: int,
                 count: int) -> bool:
    """Whether the tokens' sums are made by :func:`_combine_runs` and not
    by XLA's gathers, from what the trace can see: where the rows move by
    DMA (:func:`rows_by_dma`) and a slab of whatever a block's runs can
    land fits VMEM twice."""
    return (rows_by_dma(tokens, hidden, tile, dtype)
            and _slabs_bytes(tokens, hidden, dtype, top_k, count)
            <= _SLAB_BYTES)


def _combine_runs(acc, buffer, index, spare: int, runs, first_row,
                  interpret: bool):
    """:func:`_combined` by runs. ``buffer`` holds the round's rows as
    they are. A grid step takes a block of tokens. It starts one copy a
    group of rows that a run of the block touches (:func:`_landing`),
    whole groups side by side into a slab in VMEM, and a step ahead:
    block ``i + 1``'s copies are started before block ``i``'s are waited
    for, into the slab's other half. The rows of a group that are another
    block's are landed and ignored (a zero times a row is zero while the
    row is finite: a NaN in one token's row reaches the tokens of the
    blocks that land it). Each token's sum is then a product on
    the MXU, ``_CHUNK`` rows of the slab at a time: a matrix of ones where
    ``slot`` says a pick of the token lies, times the rows (exact ones
    times the rows' own values, float32 accumulation: the same float32
    additions as :func:`_combined`'s, a chunk's made before it joins the
    chunks before it). The scalar core tests no pick and issues a copy a
    group, not a row. ``acc`` plus the sums is written once, in ``acc``'s
    place."""
    tokens, top_k = index.shape
    hidden = acc.shape[1]
    block = _block(tokens)
    group = _group(buffer.dtype)
    first_group, groups, slot = _landing(runs, index, first_row, spare,
                                         group, block)
    padded = slot.shape[0]
    steps = padded // block
    count = groups.shape[0] // steps
    slab_rows = _slab_rows(block, top_k, count, group)
    if padded != tokens:
        acc = jnp.pad(acc, ((0, padded - tokens), (0, 0)))
    # float32 rows (no cell's: the tests') as they are, not rounded to
    # the MXU's bf16
    precision = (jax.lax.Precision.HIGHEST if buffer.dtype == jnp.float32
                 else None)

    def kernel(first_ref, groups_ref, slot_ref, acc_ref, buffer_ref,
               out_ref, slab, landed, sems):
        i = pl.program_id(0)

        def copy(source, at, half):
            return pltpu.make_async_copy(
                buffer_ref.at[pl.ds(pl.multiple_of(source * group, group),
                                    group), :],
                slab.at[half, pl.ds(pl.multiple_of(at * group, group),
                                    group), :],
                sems.at[half])

        def start(step, half):
            def run(e, at):
                source = first_ref[step * count + e]
                n = groups_ref[step * count + e]
                jax.lax.fori_loop(
                    0, n, lambda k, _: copy(source + k, at + k,
                                            half).start(), None)
                return at + n

            landed[half] = jax.lax.fori_loop(0, count, run, jnp.int32(0))

        @pl.when(i == 0)
        def _():
            # what no copy lands may hold anything, a NaN's bits too, and
            # a chunk's product reads whole chunks: zeros times zeros
            def clear(c, _):
                at = pl.ds(pl.multiple_of(c * _CHUNK, _CHUNK), _CHUNK)
                for half in range(2):
                    slab[half, at, :] = jnp.zeros((_CHUNK, hidden),
                                                  slab.dtype)

            jax.lax.fori_loop(0, slab_rows // _CHUNK, clear, None)
            start(0, 0)

        @pl.when(i + 1 < steps)
        def _():
            start(i + 1, (i + 1) % 2)

        half = i % 2
        jax.lax.fori_loop(0, landed[half],
                          lambda _, c: copy(0, 0, half).wait(), None)
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.float32)
        lane = jax.lax.broadcasted_iota(jnp.int32, (block, _CHUNK), 1)

        def add(c, _):
            at = pl.ds(pl.multiple_of(c * _CHUNK, _CHUNK), _CHUNK)
            picks = slot_ref[...] - c * _CHUNK
            ones = picks[:, 0:1] == lane
            for j in range(1, top_k):
                ones = ones | (picks[:, j:j + 1] == lane)
            out_ref[...] += jax.lax.dot_general(
                jnp.where(ones, 1.0, 0.0).astype(slab.dtype),
                slab[half, at, :], _NN, precision=precision,
                preferred_element_type=jnp.float32)

        jax.lax.fori_loop(
            0, (landed[half] * group + _CHUNK - 1) // _CHUNK, add, None)
        out_ref[...] = acc_ref[...] + out_ref[...]

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(steps,),
            in_specs=[
                pl.BlockSpec((block, top_k), lambda i, *_: (i, 0)),
                pl.BlockSpec((block, hidden), lambda i, *_: (i, 0)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block, hidden), lambda i, *_: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, slab_rows, hidden), buffer.dtype),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((padded, hidden), jnp.float32),
        # acc is the fourth operand, after the two prefetched and slot
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            # a step starts the next one's copies: in order
            dimension_semantics=("arbitrary",),
            # the slabs, the blocks of the sums in and out, and room
            vmem_limit_bytes=_slabs_bytes(tokens, hidden, buffer.dtype,
                                          top_k, count)
            + 8 * block * hidden * 4 + (8 << 20)),
        interpret=interpret,
    )(first_group, groups, slot, acc, buffer)
    return out[:tokens]


def _movable(a, dma: bool):
    """The rows that a tile fetches, as what moves them takes them: words
    for the DMA, as they are for XLA's gather."""
    return _words(a) if dma else a


def _taken(sources, index, dtype, dma: bool):
    """``[s[index] for s in sources]`` in ``dtype``: a tile's rows of the
    tokens, out of :func:`_movable` ``sources``."""
    if dma:
        return _fetch(sources, index, dtype, not on_tpu())
    return [source[index] for source in sources]


def _combined(acc, buffer, index, spare: int, runs=None, first_row=0):
    """``acc`` (N, hidden) float32 plus, for each token, the float32 sum
    of its picks' rows of ``buffer``, in ascending order of the rows;
    ``index`` (N, top_k) holds ``spare`` for a pick with nothing in the
    buffer, which costs XLA's gather a row of zeros (``spare`` is a row
    nobody writes) and the kernel nothing. Given the layer's ``runs``
    (:func:`_runs`; ``first_row``: the round's first row of the padded
    order) the rows move by :func:`_combine_runs`."""
    with jax.named_scope(SCOPE):
        if runs is not None:
            return _combine_runs(acc, buffer, index, spare, runs,
                                 first_row, not on_tpu())
        index = jnp.sort(index, axis=1)       # live picks first: < spare
        summed = jnp.zeros(acc.shape, jnp.float32)
        for j in range(index.shape[1]):
            summed = summed + buffer[index[:, j]].astype(jnp.float32)
        return acc + summed


def moe(x, router, gate, up, down, held: Tuple[int, int], top_k: int,
        tile: int, scale: float = 1.0, bias=None):
    """:func:`moe_counted`'s first result alone: the held experts' part
    of a sparse-expert layer."""
    return moe_counted(x, router, gate, up, down, held, top_k, tile,
                       scale, bias)[0]


def moe_counted(x, router, gate, up, down, held: Tuple[int, int],
                top_k: int, tile: int, scale: float = 1.0, bias=None):
    """The held experts' part of a sparse-expert layer, and what the walk
    did for it.

    Args:
        x: (N, hidden) tokens, in the compute dtype.
        router: (hidden, experts) float32, over ALL the experts.
        gate, up: (count, hidden, width); down: (count, width, hidden):
            the held experts' SwiGLU weights, float32 (cast to ``x``'s
            dtype for the products).
        held: ``(first, count)``: this chip holds experts ``first ..
            first + count`` of the router's.
        top_k: experts a token picks.
        tile: rows of one expert in a tile of the walk (the caller's:
            :func:`tile_rows` has the rule the decoder follows).
        scale: what a token's weights sum to (``route``).
        bias: ``None`` for a softmax router; (experts,) float32, the
            selection bias of a sigmoid router (``route``). It takes no
            gradient.

    Returns ``(out, walk)``: ``out`` (N, hidden) in ``x``'s dtype, for
    each token the sum over its picks that are held of weight x
    expert(token); ``walk`` int32, this call's routing as :func:`_dispatch`
    planned it, one value a field of
    ``telemetry.STEP_STAT_FIELDS["moe_walk"]``: all ``N x top_k`` pairs,
    the pairs held, the tiles and the rounds the walk takes and the
    fullest held expert's pairs. It has no gradient, and is not made again
    where a ``jax.checkpoint`` makes the layer again.
    """
    dma = rows_by_dma(*x.shape, tile, x.dtype)
    runs = sums_by_runs(*x.shape, tile, x.dtype, top_k, held[1])
    # Counted when a layer is traced, not when it runs.
    rt_metrics.counter(
        "rsdl_moe_gather_total",
        "Sparse-expert layers traced, by what moves the walk's rows (a "
        "tile's tokens in, the tokens' picks out): one DMA a row from a "
        "Pallas kernel, or XLA's gather",
        kind="dma" if dma else "xla").inc()
    rt_metrics.counter(
        "rsdl_moe_combine_total",
        "Sparse-expert layers traced, by what makes the tokens' sums of a "
        "round's rows: whole runs of a block's rows landed in VMEM and a "
        "product on the MXU, or XLA's gather of every pick's row",
        kind="runs" if runs else "xla").inc()
    rt_metrics.counter(
        "rsdl_moe_router_total",
        "Sparse-expert layers traced, by the router's scoring: softmax "
        "over all the experts, or a sigmoid an expert picked under a "
        "selection bias and weighed without it",
        kind=SOFTMAX if bias is None else SIGMOID_BIAS).inc()
    with jax.named_scope(LOOPS_SCOPE):
        return _moe(x, router, gate, up, down, bias, held, top_k, tile,
                    scale, dma, runs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _moe(x, router, gate, up, down, bias, held, top_k, tile, scale, dma,
         runs):
    return _moe_fwd(x, router, gate, up, down, bias, held, top_k, tile,
                    scale, dma, runs)[0]


# Jitted for the scope's sake, as models/bert.py's head: inside a program
# of its own (and inside a loop's body) the name reaches the compiled step
# as written. The ``while`` instructions themselves are outside ``SCOPE``
# (under ``LOOPS_SCOPE`` alone, which no metric sums), so nothing is counted
# twice.
@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11))
def _moe_fwd(x, router, gate, up, down, bias, held, top_k, tile, scale,
             dma, runs):
    first, count = held
    tokens, hidden = x.shape
    if gate.shape[0] != count:
        raise ValueError(f"held names {count} experts, the weights are of "
                         f"{gate.shape[0]}")
    rows = round_rows(tokens, top_k, count, router.shape[1], tile)
    with jax.named_scope(SCOPE):
        ids, weights = route(_router_logits(x, router), top_k, scale, bias)
        plan, position = _dispatch(ids, first, count, tile)
        flat = weights.reshape(-1)
        g16, u16, d16 = (w.astype(x.dtype) for w in (gate, up, down))
        out = jnp.zeros((tokens, hidden), jnp.float32)
        x_rows = _movable(x, dma)
    # The carried buffer's zero-fill, as ever under ``LOOPS_SCOPE`` alone
    # (its parent's, of words, lost the name to XLA's folding): only the
    # spare tile's zeros are ever read, and only by XLA's gathers.
    ys = jnp.zeros((rows + tile, hidden), x.dtype)
    with jax.named_scope(SCOPE):
        walk = _walk_counts(plan, position, count, rows // tile)
        # made where the combine reads it, and nowhere else
        runs = (_runs(ids, plan, first, count, tile, _block(tokens))
                if runs else None)

    def tile_fn(i, out, ys, at):
        expert, token, w = _tile(i, plan, flat, top_k, tile)
        xs, = _taken([x_rows], token, x.dtype, dma)
        h = (jax.nn.silu(_dot(xs, _of(g16, expert), _NN))
             * _dot(xs, _of(u16, expert), _NN))
        y = _dot(h.astype(x.dtype), _of(d16, expert), _NN) * w
        return out, _put(ys, y, at)

    def gather_fn(out, ys, index, first_row):
        return _combined(out, ys, index, rows, runs, first_row)

    out = _walk(plan, position, rows, tile, tile_fn, gather_fn, out, ys)
    with jax.named_scope(SCOPE):
        out = out.astype(x.dtype)
    return (out, walk), (x, router, gate, up, down, bias, weights, plan,
                         position, runs)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def _moe_bwd(held, top_k, tile, scale, dma, _, residuals, cotangents):
    (x, router, gate, up, down, bias, weights, plan, position,
     runs) = residuals
    dout = cotangents[0]            # the walk's counts have none
    tokens, hidden = x.shape
    rows = round_rows(tokens, top_k, held[1], router.shape[1], tile)
    with jax.named_scope(SCOPE):
        flat = weights.reshape(-1)
        g16, u16, d16 = (w.astype(x.dtype) for w in (gate, up, down))
        x_rows = _movable(x, dma)
        dout_rows = _movable(dout.astype(x.dtype), dma)
        grads = (jnp.zeros((tokens, hidden), jnp.float32),
                 jnp.zeros((tokens, top_k), jnp.float32),
                 jnp.zeros(gate.shape, jnp.float32),
                 jnp.zeros(up.shape, jnp.float32),
                 jnp.zeros(down.shape, jnp.float32))
    buffers = (jnp.zeros((rows + tile, hidden), x.dtype),   # as ``ys``
               jnp.zeros((rows + tile, 1), jnp.float32))

    def tile_fn(i, grads, buffers, at):
        d_x, d_weights, d_gate, d_up, d_down = grads
        expert, token, w = _tile(i, plan, flat, top_k, tile)
        xs, dy = _taken([x_rows, dout_rows], token, x.dtype, dma)
        ge, ue, de = _of(g16, expert), _of(u16, expert), _of(d16, expert)
        g, u = _dot(xs, ge, _NN), _dot(xs, ue, _NN)
        sig = jax.nn.sigmoid(g)
        act = g * sig
        h = act * u
        # y = w * (h D): dw = dy . (h D) = (dy D^T) . h
        dh = _dot(dy, de, _NT)
        dw = jnp.sum(dh * h, axis=-1, keepdims=True)
        dh = dh * w
        du = (dh * act).astype(x.dtype)
        dg = (dh * u * sig * (1.0 + g * (1.0 - sig))).astype(x.dtype)
        dxs = _dot(dg, ge, _NT) + _dot(du, ue, _NT)
        return ((d_x, d_weights,
                 d_gate.at[expert].add(_dot(xs, dg, _TN)),
                 d_up.at[expert].add(_dot(xs, du, _TN)),
                 d_down.at[expert].add(
                     _dot((h * w).astype(x.dtype), dy, _TN))),
                (_put(buffers[0], dxs, at), _put(buffers[1], dw, at)))

    def gather_fn(grads, buffers, index, first_row):
        d_x = _combined(grads[0], buffers[0], index, rows, runs, first_row)
        with jax.named_scope(SCOPE):
            return d_x, grads[1] + buffers[1][index, 0], *grads[2:]

    d_x, d_weights, d_gate, d_up, d_down = _walk(
        plan, position, rows, tile, tile_fn, gather_fn, grads, buffers)
    with jax.named_scope(SCOPE):
        _, router_vjp = jax.vjp(
            lambda x, r: _router_weights(x, r, top_k, scale, bias), x,
            router)
        d_x_router, d_router = router_vjp(d_weights)
        d_x = (d_x + d_x_router.astype(jnp.float32)).astype(x.dtype)
        # the selection bias chooses and does not weigh: no gradient
        d_bias = None if bias is None else jnp.zeros_like(bias)
        # float32 sums, handed back as the weights are held
        d_gate, d_up, d_down = (d.astype(w.dtype) for d, w in (
            (d_gate, gate), (d_up, up), (d_down, down)))
    return d_x, d_router, d_gate, d_up, d_down, d_bias


_moe.defvjp(_moe_fwd, _moe_bwd)
