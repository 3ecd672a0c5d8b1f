"""A sparse-expert layer over the experts this chip holds.

A language model's expert layer routes every token over ALL of its experts
(softmax over the router's logits, the ``top_k`` largest, their weights
renormalised) and adds up what the picked experts give. Under expert
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
tile`` tiles and more, none held walks none. A tile gathers its tokens'
rows, runs the expert's three products on bf16 operands (float32
accumulation: SwiGLU, ``silu(x G) * (x U)`` then ``D``), weighs the rows
and puts them side by side in a buffer of one round's tiles
(``round_rows``: what an even routing fills, and a quarter); when a round's
tiles are done each token gathers its picks' rows from it and adds them
up, and a routing that fills more than one buffer takes another round.
Nothing of ``tokens x top_k`` rows is ever materialised, and nothing is
scattered: XLA's scatter-add of a tile's rows into their tokens' took
0.87 ms a tile on a v5e, fifteen times the tile's products (PERF.md
section 6, PR 32). The backward is written out (``_moe_bwd``): the same
walk, a tile's ``x G`` and ``x U`` made again, eight products a tile, the
experts' gradients added in float32 in place, the tokens' gathered.

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

from ray_shuffling_data_loader_tpu.runtime import telemetry

#: The name a device trace shows the layer's operations under: router,
#: sort, grouped products and combine, forward and backward.
SCOPE = "rsdl.lm.moe"

#: The most rows of one expert an even routing puts in a tile of the walk
#: (``tile_rows``).
_TILE_LOAD = 1024

_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def route(logits, top_k: int, scale: float = 1.0):
    """``(ids (N, top_k) int32, weights (N, top_k) float32)``: softmax over
    all the experts in float32, the ``top_k`` largest, renormalised to sum
    to ``scale`` (``norm_topk_prob``, then a model's
    ``moe_routed_scaling_factor``). Differentiable in the weights."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top, ids = jax.lax.top_k(probs, top_k)
    return (ids.astype(jnp.int32),
            scale * top / top.sum(axis=-1, keepdims=True))


def _router_logits(x, router):
    """Float32 all the way: at the default precision the chip would round
    the router's weights to bf16, and a pick flipped between two experts
    of near-equal weight moves a token's whole output."""
    return jax.lax.dot_general(x.astype(jnp.float32), router, _NN,
                               precision=jax.lax.Precision.HIGHEST)


def _router_weights(x, router, top_k: int, scale: float):
    return route(_router_logits(x, router), top_k, scale)[1]


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def tile_rows(tokens: int, top_k: int, experts: int) -> int:
    """Rows of one expert in a tile of the walk, from the static shapes:
    an expert's expected rows under an even routing (``tokens x top_k /
    experts``) cut into as few tiles as hold ``_TILE_LOAD`` rows each, and
    an eighth of room, in whole lanes. Larger tiles leave more of each
    expert's last tile empty; smaller ones make the loop long, and a
    tile's fixed cost (its index arithmetic, its gathers, the in-place
    adds of three expert gradients) is 0.26 ms in the backward on a v5e.
    The room and not the bare share: a power of two at the expected load
    puts every expert on a tile's edge, one tile more or fewer by the
    seed's luck, and the walk is longer or shorter for it.

    Measured on a v5e, forward / forward and backward, ms. At
    ``mellum_train_8k``'s shapes (PR 32; 32,768 tokens x 2304, 16 of 64
    experts of 896, top-8: 4,096 rows an expert, 3,730-4,530 under a
    balanced router): tiles of 256 33.3 / 125.0, 512 31.4 / 82.8, 1024
    30.9 / 64.7; the rule gives 1152, and 4 x 1152 holds every such load
    (PR 34: 1024 21.3 / 54.8, 1152 21.0 / 52.4). At ``laguna_train_8k``'s
    (PR 34; 16,384 tokens x 2048, 32 of 256 experts of 512, top-8: 512
    rows an expert, 413-581 under a balanced router): 256 8.7 / 17.9, 384
    9.3 / 17.9, 512 8.6 / 16.1, 576 8.4 / 15.2, 640 8.4 / 15.1, 768
    8.7 / 15.7, 1024 9.8 / 17.3, 1152 10.0 / 17.6; the rule gives 640,
    one tile an expert."""
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


def _dispatch(ids, first: int, count: int, tile: int):
    """The walk's plan from the picks ``ids`` (N, top_k). The pairs are
    sorted by held expert (pairs of absent experts last) and each held
    expert's run is padded to whole tiles: ``(order, sizes, starts,
    tile_ends)`` are the pairs' flat indices in that order, each held
    expert's pair count and start among them, and the running count of
    tiles by expert; ``position`` (N, top_k) is each pair's row in the
    padded order, -1 for an absent expert's."""
    local = ids.reshape(-1) - first
    expert = jnp.where((local >= 0) & (local < count), local, count)
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
    rows land side by side, and the tokens gather them."""
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
            local = position - r * rows
            index = jnp.where((local >= 0) & (local < rows), local, rows)
        return gather_fn(carry, buffers, index), buffers

    return jax.lax.fori_loop(0, (tiles + per_round - 1) // per_round,
                             one_round, (carry, buffers))[0]


#: The tokens gather their picks' rows in this many blocks, each as many
#: times as its fullest token has picks in the round.
_GATHER_BLOCKS = 16


def _gathered(buffer, index, spare: int):
    """For each token the float32 sum of its picks' rows of ``buffer``;
    ``index`` (N, top_k) holds ``spare``, a row of zeros, for a pick with
    nothing in the buffer. Three picks in four are such where the chip
    holds a quarter of the experts, and a row gathered costs the same
    whatever it holds (45 ns on a v5e): so the tokens are taken in the
    order of how many live picks they have, a block of them at a time,
    and a block gathers only as many times as its first token's count.
    At 32,768 tokens, top-8 and a quarter held that is 2.3 rows a token
    and one more to put the sums back in the tokens' order, not 8."""
    tokens, hidden = index.shape[0], buffer.shape[1]
    blocks = _GATHER_BLOCKS if tokens % _GATHER_BLOCKS == 0 else 1
    size = tokens // blocks
    with jax.named_scope(SCOPE):
        index = jnp.sort(index, axis=1)       # live picks first: < spare
        count = jnp.sum(index < spare, axis=1, dtype=jnp.int32)
        order = jnp.argsort(-count, stable=True)
        index, count = index[order], count[order]
        summed = jnp.zeros((tokens, hidden), jnp.float32)

    def one_block(b, summed):
        def add_pick(j, acc):
            with jax.named_scope(SCOPE):
                rows = jax.lax.dynamic_slice(index, (b * size, j),
                                             (size, 1))[:, 0]
                return acc + buffer[rows].astype(jnp.float32)

        with jax.named_scope(SCOPE):
            acc = jnp.zeros((size, hidden), jnp.float32)
        acc = jax.lax.fori_loop(0, count[b * size], add_pick, acc)
        with jax.named_scope(SCOPE):
            return jax.lax.dynamic_update_slice_in_dim(summed, acc,
                                                       b * size, axis=0)

    summed = jax.lax.fori_loop(0, blocks, one_block, summed)
    with jax.named_scope(SCOPE):
        return summed[jnp.argsort(order)]


def moe(x, router, gate, up, down, held: Tuple[int, int], top_k: int,
        tile: int, scale: float = 1.0):
    """:func:`moe_counted`'s first result alone: the held experts' part
    of a sparse-expert layer."""
    return moe_counted(x, router, gate, up, down, held, top_k, tile,
                       scale)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def moe_counted(x, router, gate, up, down, held: Tuple[int, int],
                top_k: int, tile: int, scale: float = 1.0):
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

    Returns ``(out, walk)``: ``out`` (N, hidden) in ``x``'s dtype, for
    each token the sum over its picks that are held of weight x
    expert(token); ``walk`` int32, this call's routing as :func:`_dispatch`
    planned it, one value a field of
    ``telemetry.STEP_STAT_FIELDS["moe_walk"]``: all ``N x top_k`` pairs,
    the pairs held, the tiles and the rounds the walk takes and the
    fullest held expert's pairs. It has no gradient, and is not made again
    where a ``jax.checkpoint`` makes the layer again.
    """
    return _moe_fwd(x, router, gate, up, down, held, top_k, tile, scale)[0]


# Jitted for the scope's sake, as models/bert.py's head: inside a program
# of its own (and inside a loop's body) the name reaches the compiled step
# as written. The ``while`` instructions themselves carry no scope of the
# program's, so nothing is counted twice.
@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _moe_fwd(x, router, gate, up, down, held, top_k, tile, scale):
    first, count = held
    tokens, hidden = x.shape
    if gate.shape[0] != count:
        raise ValueError(f"held names {count} experts, the weights are of "
                         f"{gate.shape[0]}")
    rows = round_rows(tokens, top_k, count, router.shape[1], tile)
    with jax.named_scope(SCOPE):
        ids, weights = route(_router_logits(x, router), top_k, scale)
        plan, position = _dispatch(ids, first, count, tile)
        flat = weights.reshape(-1)
        g16, u16, d16 = (w.astype(x.dtype) for w in (gate, up, down))
        out = jnp.zeros((tokens, hidden), jnp.float32)
        ys = jnp.zeros((rows + tile, hidden), x.dtype)
        walk = _walk_counts(plan, position, count, rows // tile)

    def tile_fn(i, out, ys, at):
        expert, token, w = _tile(i, plan, flat, top_k, tile)
        xs = x[token]
        h = (jax.nn.silu(_dot(xs, _of(g16, expert), _NN))
             * _dot(xs, _of(u16, expert), _NN))
        return out, _put(ys, _dot(h.astype(x.dtype), _of(d16, expert), _NN)
                         * w, at)

    def gather_fn(out, ys, index):
        summed = _gathered(ys, index, rows)
        with jax.named_scope(SCOPE):
            return out + summed

    out = _walk(plan, position, rows, tile, tile_fn, gather_fn, out, ys)
    with jax.named_scope(SCOPE):
        out = out.astype(x.dtype)
    return (out, walk), (x, router, gate, up, down, weights, plan, position)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _moe_bwd(held, top_k, tile, scale, residuals, cotangents):
    x, router, gate, up, down, weights, plan, position = residuals
    dout = cotangents[0]            # the walk's counts have none
    tokens, hidden = x.shape
    rows = round_rows(tokens, top_k, held[1], router.shape[1], tile)
    with jax.named_scope(SCOPE):
        flat = weights.reshape(-1)
        g16, u16, d16 = (w.astype(x.dtype) for w in (gate, up, down))
        dout = dout.astype(x.dtype)
        grads = (jnp.zeros((tokens, hidden), jnp.float32),
                 jnp.zeros((tokens, top_k), jnp.float32),
                 jnp.zeros(gate.shape, jnp.float32),
                 jnp.zeros(up.shape, jnp.float32),
                 jnp.zeros(down.shape, jnp.float32))
        buffers = (jnp.zeros((rows + tile, hidden), x.dtype),
                   jnp.zeros((rows + tile, 1), jnp.float32))

    def tile_fn(i, grads, buffers, at):
        d_x, d_weights, d_gate, d_up, d_down = grads
        expert, token, w = _tile(i, plan, flat, top_k, tile)
        xs, dy = x[token], dout[token]
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

    def gather_fn(grads, buffers, index):
        summed = _gathered(buffers[0], index, rows)
        with jax.named_scope(SCOPE):
            return (grads[0] + summed, grads[1] + buffers[1][index, 0],
                    *grads[2:])

    d_x, d_weights, d_gate, d_up, d_down = _walk(
        plan, position, rows, tile, tile_fn, gather_fn, grads, buffers)
    with jax.named_scope(SCOPE):
        _, router_vjp = jax.vjp(
            lambda x, r: _router_weights(x, r, top_k, scale), x, router)
        d_x_router, d_router = router_vjp(d_weights)
        d_x = (d_x + d_x_router.astype(jnp.float32)).astype(x.dtype)
    return d_x, d_router, d_gate, d_up, d_down


moe_counted.defvjp(_moe_fwd, _moe_bwd)
