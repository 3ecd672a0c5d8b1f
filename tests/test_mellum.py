"""The sparse-expert decoder (models/mellum.py) at both of its
configurations, its expert layer (ops/moe.py) and the masked, grouped
attention kernels (ops/flash_attention.py) against the plain references
(chipbench/references/mellum.py, chipbench/references/laguna.py), at tiny
sizes on the CPU."""

import dataclasses
import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import xplane
from chipbench.references import laguna as laguna_ref
from chipbench.references import mellum as ref
from ray_shuffling_data_loader_tpu.models import mellum
from ray_shuffling_data_loader_tpu.ops import flash_attention as fa
from ray_shuffling_data_loader_tpu.ops import moe, rope
from ray_shuffling_data_loader_tpu.runtime import metric_names, metrics
from tests.test_flash_attention import _pallas_calls


def _sizes(cfg: mellum.DecoderConfig, seq_len: int, held=None):
    """Either reference's view of a program configuration."""
    first, count = cfg.experts_held if held is None else held
    yarn = cfg.yarn
    layers = range(cfg.num_layers)
    return {
        "hidden_size": cfg.hidden_size, "head_dim": cfg.head_dim,
        "num_attention_heads": cfg.num_heads,
        "num_attention_heads_per_layer": [cfg.heads(i) for i in layers],
        "num_key_value_heads": cfg.num_kv_heads,
        "num_hidden_layers": cfg.num_layers,
        "layer_types": list(cfg.layer_types),
        "mlp_layer_types": [cfg.mlp_type(i) for i in layers],
        "gating": cfg.attention_gate,
        "sliding_window": cfg.sliding_window,
        "intermediate_size": cfg.intermediate_size,
        "moe_intermediate_size": cfg.expert_width,
        "shared_expert_intermediate_size": cfg.shared_expert_width,
        "moe_routed_scaling_factor": cfg.routed_scale,
        "num_experts": count, "experts_held_first": first,
        "num_experts_routed": cfg.num_experts,
        "num_experts_per_tok": cfg.top_k, "vocab_size": cfg.vocab_size,
        "rms_norm_eps": cfg.rms_norm_eps, "seq_len": seq_len,
        "published": {"num_hidden_layers": cfg.published_layers},
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": cfg.rope_theta,
                "factor": yarn.factor,
                "original_max_position_embeddings":
                    yarn.original_max_position_embeddings,
                "beta_fast": yarn.beta_fast, "beta_slow": yarn.beta_slow,
                "attention_factor": yarn.attention_factor,
                "partial_rotary_factor": cfg.full_rotary_factor},
            "sliding_attention": {
                "rope_type": "default",
                "rope_theta": (cfg.rope_theta
                               if cfg.sliding_rope_theta is None
                               else cfg.sliding_rope_theta)}},
    }


# -- attention: the masks, grouped heads, heads of 128 ----------------------------


def _plain_attention(q, k, v, causal, window):
    """(B, H, S, D) masked softmax attention, k and v of fewer heads."""
    b, h, s, d = q.shape
    group = h // k.shape[1]
    k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
    ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    seen = jnp.ones((s, s), bool)
    if causal:
        seen &= ahead >= 0
    if window is not None:
        seen &= ahead < window
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", weights, v)


def _packed(x):
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


@pytest.mark.parametrize("seq,heads,kv_heads,dim,window,bq,bk,packed", [
    (100, 2, 2, 16, None, 32, 32, True),   # causal alone, a head its own
                                           # k/v, a length no block divides
    (48, 4, 2, 16, 100, 16, 32, False),    # a window longer than the row
    (32, 8, 1, 128, 12, 16, 8, True),      # a window shorter than the row,
                                           # 8 : 1 grouped heads of 128
], ids=["causal_ragged_len", "window_over_seq", "window_gqa8_d128"])
def test_masked_grouped_attention_matches_the_masked_softmax(
        seq, heads, kv_heads, dim, window, bq, bk, packed, monkeypatch):
    """Forward and the three gradients, through the (B, H, S, D) entry
    point or through the packed one the decoder calls (at a head of 128
    in place, as on the chip; at a narrower one through head-major
    copies, as the chip would take it)."""
    if packed and dim % 128:
        monkeypatch.setattr(fa, "_reads_in_place", lambda d, interpret: False)
    key = jax.random.key(seq + heads + dim)
    q, k, v, w = (jax.random.normal(jax.random.fold_in(key, i), shape)
                  for i, shape in enumerate([
                      (1, heads, seq, dim), (1, kv_heads, seq, dim),
                      (1, kv_heads, seq, dim), (1, heads, seq, dim)]))

    def plain(q, k, v):
        return _plain_attention(q, k, v, True, window)

    want = plain(q, k, v)
    want_grads = jax.grad(lambda *a: jnp.sum(plain(*a) * w), (0, 1, 2))(
        q, k, v)
    if packed:
        out, lse = fa.grouped_forward(
            _packed(q), _packed(k), _packed(v), heads, kv_heads, True,
            window, bq, bk, True)
        got_grads = fa.grouped_backward(
            _packed(q), _packed(k), _packed(v), out, lse, _packed(w), heads,
            kv_heads, True, window, bq, bk, True)
        want, want_grads = _packed(want), [_packed(g) for g in want_grads]
    else:
        def flash(q, k, v):
            return fa.flash_attention(q, k, v, None, bq, bk, True, True,
                                      window)

        out = flash(q, k, v)
        got_grads = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(
            q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-5)
    for got, grad in zip(got_grads, want_grads):
        np.testing.assert_allclose(got, grad, atol=5e-5)


@pytest.mark.parametrize("window,bq,bk,fwd_steps,dkv_steps", [
    (None, 8, 8, 8, 8),      # the triangle: every key block of some query
    (16, 8, 8, 3, 3),        # a band of 16 keys in tiles of 8: three live
    (16, 16, 16, 2, 2),
    (8, 16, 8, 3, 2),
    (1024, 512, 512, 3, 3),  # the cell's window layers
])
def test_the_grid_is_as_long_as_the_band(window, bq, bk, fwd_steps,
                                         dkv_steps):
    """Key blocks wholly outside the mask are not in the grid: the inner
    dimension runs over the blocks some query of the outer block sees."""
    mask = fa._Mask(True, window)
    seq = 64 if bq < 512 else 8192
    num_q, num_k = seq // bq, seq // bk
    assert fa._longest(lambda g: fa._k_span(mask, g, bq, bk, num_k),
                       num_q) == fwd_steps
    assert fa._longest(lambda t: fa._q_span(mask, t, bq, bk, num_q),
                       num_k) == dkv_steps
    # every (query, key) pair the mask lets through lies in a live block
    for g in range(num_q):
        first, last = fa._k_span(mask, g, bq, bk, num_k)
        for t in range(num_k):
            rows = np.arange(g * bq, (g + 1) * bq)[:, None]
            cols = np.arange(t * bk, (t + 1) * bk)[None, :]
            seen = (cols <= rows) & (window is None or cols > rows - window)
            assert seen.any() == (first <= t <= last), (g, t)
            q_first, q_last = fa._q_span(mask, t, bq, bk, num_q)
            assert seen.any() == (q_first <= g <= q_last), (g, t)


def test_a_window_needs_a_causal_mask_and_a_mask_is_no_tensor():
    q = jnp.zeros((1, 2, 16, 8))
    with pytest.raises(ValueError, match="causal"):
        fa.flash_attention(q, q, q, None, 8, 8, True, False, 4)
    with pytest.raises(ValueError, match="key-side"):
        fa.flash_attention(q, q, q, jnp.zeros((1, 1, 16, 16)), 8, 8, True)


# -- rotary positions ----------------------------------------------------------------


@pytest.mark.parametrize(
    "build,reference,dim,factor,original,fast,plain_base,scale", [
        (mellum.mellum2_ep4_share, ref, 128, 16.0, 8192, 32.0, 500_000.0,
         1.2772588722239782),
        # half a head rotates in a full layer: 64 dimensions take the
        # head's place in the formulas; the window layers' theta is 10,000
        (mellum.laguna_xs2_ep8_share, laguna_ref, 64, 64.0, 4096, 64.0,
         10_000.0, 1.4158883083359672),
    ], ids=["mellum", "laguna_half_a_head"])
def test_yarn_frequencies_are_the_formula_written_out(
        build, reference, dim, factor, original, fast, plain_base, scale):
    """Peng et al. 2023 as the source's ``rope_parameters`` state it: below
    ``beta_slow`` turns over the original context a frequency is
    interpolated (divided by ``factor``), above ``beta_fast`` it is kept,
    between the two a linear ramp over the dimensions."""
    cfg = build()
    base = 500_000.0
    want = []
    for i in range(dim // 2):
        plain = base ** (-2.0 * i / dim)

        def where(turns):
            return dim * math.log(original / (turns * 2 * math.pi)) / (
                2 * math.log(base))

        low, high = math.floor(where(fast)), math.ceil(where(1.0))
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(plain / factor * ramp + plain * (1.0 - ramp))
    assert 0 < low < high < dim // 2      # all three regimes are present
    sizes = _sizes(cfg, 8192)
    for got, got_scale in (mellum.rope_inv_freq(cfg, mellum.FULL),
                           reference.inv_freq(sizes, ref.FULL)):
        np.testing.assert_allclose(got, want, rtol=1e-5)
        assert got_scale == scale
        assert abs(scale - (0.1 * math.log(factor) + 1.0)) < 1e-12
    for got, got_scale in (mellum.rope_inv_freq(cfg, mellum.SLIDING),
                           reference.inv_freq(sizes, ref.SLIDING)):
        np.testing.assert_allclose(
            got, [plain_base ** (-2.0 * i / 128) for i in range(64)],
            rtol=1e-5)
        assert got_scale == 1.0


# -- the expert layer ----------------------------------------------------------------

_HIDDEN, _WIDTH, _EXPERTS, _TOP_K = 16, 8, 8, 2


def _expert_weights(key, held, hidden=_HIDDEN):
    ks = jax.random.split(key, 3)
    return {"gate": jax.random.normal(ks[0], (held, hidden, _WIDTH)),
            "up": jax.random.normal(ks[1], (held, hidden, _WIDTH)),
            "down": jax.random.normal(ks[2], (held, _WIDTH, hidden))}


def _moe_sizes(first, count):
    return {"num_experts": count, "experts_held_first": first,
            "num_experts_per_tok": _TOP_K}


def _favouring(experts, hidden=_HIDDEN):
    """A router under which every token of positive features picks
    ``experts`` (and only them, where there are ``_TOP_K``)."""
    router = jnp.zeros((hidden, _EXPERTS))
    for rank, e in enumerate(experts):
        router = router.at[:, e].set(1.0 + 0.1 * rank)
    return router


def _tied(experts, hidden=_HIDDEN):
    router = jnp.zeros((hidden, _EXPERTS))
    return router.at[:, jnp.asarray(experts)].set(1.0)


#: (held, the router's maker, tile, scale)
_ROUTINGS = pytest.mark.parametrize("held,router,tile,scale", [
    ((2, 2), functools.partial(_favouring, [2, 3]), 8, 1.0),   # every
                                                               # pick is held
    ((2, 2), functools.partial(_favouring, [0, 7]), 8, 1.0),   # none is
    ((2, 2), functools.partial(_favouring, [7, 2]), 8, 1.0),   # one held
                                            # expert takes every token
    ((2, 2), functools.partial(_tied, [2, 3, 4]), 8, 1.0),     # ties: three
                                                    # equal, two picked
    ((2, 2), None, 8, 1.0),                 # any routing, a share held
    ((0, 8), None, 8, 1.0),                 # any routing, all held
    ((2, 2), None, None, 2.5),              # the tile the shapes give (one
                                            # of 128 rows an expert), the
                                            # routed sum scaled
    ((2, 2), functools.partial(_favouring, [7, 2]), None, 2.5),
], ids=["every_pick_held", "none_held", "one_expert_takes_all", "ties",
        "random_share", "random_all_held", "ruled_tile_scaled",
        "ruled_tile_one_expert_takes_all"])


def _routed_layer(held, router, hidden=_HIDDEN, dtype=jnp.float32):
    """24 tokens of positive features, the router, the held experts'
    weights and a mix for the gradients' loss."""
    key = jax.random.key(held[1])
    x = jnp.abs(jax.random.normal(key, (24, hidden))).astype(dtype)
    router = (jax.random.normal(jax.random.fold_in(key, 1),
                                (hidden, _EXPERTS)) if router is None
              else router(hidden=hidden))
    weights = _expert_weights(jax.random.fold_in(key, 2), held[1], hidden)
    return x, router, weights, jax.random.normal(
        jax.random.fold_in(key, 3), x.shape)


@_ROUTINGS
def test_the_expert_layer_is_exact_for_any_routing(held, router, tile,
                                                   scale):
    """No capacity, no dropped token: output and all five gradients equal
    the plain loop of dense products under masks, walked in tiles of 8
    rows (several tiles an expert, the last part empty) or of what
    ``moe.tile_rows`` gives."""
    x, router, weights, mix = _routed_layer(held, router)
    if tile is None:
        tile = moe.tile_rows(x.shape[0], _TOP_K, _EXPERTS)

    def program(x, router, w):
        return moe.moe(x, router, w["gate"], w["up"], w["down"], held,
                       _TOP_K, tile, scale)

    def plain(x, router, w):
        return scale * ref._experts(_moe_sizes(*held), x,
                                    dict(w, router=router))

    np.testing.assert_allclose(program(x, router, weights),
                               plain(x, router, weights), rtol=2e-5,
                               atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(program(*a) * mix), (0, 1, 2))(
        x, router, weights)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * mix), (0, 1, 2))(
        x, router, weights)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)
    picked = np.asarray(moe.route(x @ router, _TOP_K)[0])
    held_picks = ((picked >= held[0]) & (picked < sum(held))).sum()
    assert (held_picks == 0) == (
        not np.asarray(program(x, router, weights)).any())


def _bits(a):
    return np.asarray(a.astype(jnp.float32))


@pytest.mark.parametrize("dtype,hidden", [(jnp.float32, 128),
                                          (jnp.bfloat16, 256)],
                         ids=["float32", "bfloat16"])
@_ROUTINGS
def test_rows_moved_by_dma_move_no_bit(held, router, tile, scale, dtype,
                                       hidden, monkeypatch):
    """The same routings at rows of whole lanes, the walk's rows moved by
    the kernels (interpreted here) and by XLA's gather: a gather is a
    copy and the combine adds the same rows in float32 (two picks a
    token here, one sum whatever the order), so output and all five
    gradients are equal bit for bit. A fetched bf16 row travels as words
    of two halves; ``every_pick_held`` takes a second round."""
    x, router, weights, mix = _routed_layer(held, router, hidden, dtype)
    if tile is None:
        tile = moe.tile_rows(x.shape[0], _TOP_K, _EXPERTS)

    def program(x, router, w):
        return moe.moe(x, router, w["gate"], w["up"], w["down"], held,
                       _TOP_K, tile, scale)

    def results(dma):
        monkeypatch.setattr(moe, "rows_by_dma", lambda *shape: dma)
        return [program(x, router, weights), *jax.tree.leaves(jax.grad(
            lambda *a: jnp.sum(program(*a).astype(jnp.float32) * mix),
            (0, 1, 2))(x, router, weights))]

    def kernels(dma):
        monkeypatch.setattr(moe, "rows_by_dma", lambda *shape: dma)
        # a function of its own each time: a trace is kept by function
        return _pallas_calls(jax.make_jaxpr(lambda *a: program(*a))(
            x, router, weights).jaxpr)

    # a tile's fetch and a round's combine
    assert (kernels(False), kernels(True)) == (0, 2)
    got, want = results(True), results(False)
    assert len(got) == 6
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(_bits(g), _bits(w))


@pytest.mark.parametrize("dtype,hidden", [(jnp.float32, 128),
                                          (jnp.float32, 384),
                                          (jnp.bfloat16, 256),
                                          (jnp.bfloat16, 768)],
                         ids=["f32x128", "f32x384", "bf16x256", "bf16x768"])
def test_the_row_fetch_is_a_copy(dtype, hidden):
    """``source[index]`` a row a DMA: duplicate rows, the first and the
    last, fewer rows than a block and more (two grid steps, the last
    part empty), two sources off one index."""
    first, second = (jax.random.normal(jax.random.key(k), (40, hidden)
                                       ).astype(dtype) for k in (0, 1))
    for index in ([0, 39, 7, 7, 7, 0, 39, 21, 3], list(range(39, -1, -1)) * 4):
        index = jnp.asarray(index, jnp.int32)
        got = moe._fetch([moe._words(first), moe._words(second)], index,
                         dtype, True)
        for g, source in zip(got, (first, second)):
            assert g.dtype == dtype and g.shape == (len(index), hidden)
            np.testing.assert_array_equal(_bits(g), _bits(source[index]))


@_ROUTINGS
def test_the_plan_of_runs_is_what_the_positions_say(held, router, tile,
                                                    scale):
    """In blocks of eight tokens: the picks that a block's tokens send to
    one held expert lie in consecutive rows of the padded order, by
    ascending token, from the run's start on, and the runs of a block are
    exactly its live picks."""
    x, router, _, _ = _routed_layer(held, router)
    if tile is None:
        tile = moe.tile_rows(x.shape[0], _TOP_K, _EXPERTS)
    ids, _ = moe.route(moe._router_logits(x, router), _TOP_K, scale)
    plan, position = moe._dispatch(ids, *held, tile)
    starts, counts, expert = (np.asarray(a) for a in moe._runs(
        ids, plan, *held, tile, 8))
    position = np.asarray(position)
    assert starts.shape == counts.shape == (3, held[1])
    assert np.array_equal(expert < held[1], position >= 0)
    assert counts.sum() == (position >= 0).sum()
    for block in range(3):
        tokens = slice(8 * block, 8 * block + 8)
        rows = []
        for e in range(held[1]):
            run = position[tokens][expert[tokens] == e]    # by token
            first = starts[block, e]
            assert run.tolist() == list(range(first,
                                              first + counts[block, e]))
            rows += run.tolist()
        assert sorted(rows) == sorted(position[tokens][position[tokens]
                                                       >= 0])


def _round_of_runs(top_k, experts, held, tokens, dtype, hidden, round_):
    """A routing's round as the combine takes it: the first block's tokens
    pick ``top_k`` of ``experts`` at random, the second block's (where
    there is one) none that is held, the rest at random again, and nobody
    picks the second held expert."""
    rng = np.random.default_rng(top_k)
    first, count = held
    open_to_all = [e for e in range(experts) if e != first + 1]
    absent = [e for e in open_to_all if not first <= e < first + count]
    ids = np.stack([rng.choice(absent if 128 <= t < 256 else open_to_all,
                               top_k, replace=False)
                    for t in range(tokens)]).astype(np.int32)
    tile = 16
    plan, position = moe._dispatch(jnp.asarray(ids), first, count, tile)
    runs = moe._runs(jnp.asarray(ids), plan, first, count, tile,
                     moe._block(tokens))
    starts, counts, _ = (np.asarray(a) for a in runs)
    # three rounds hold the tiles or more: the most rows a round whose
    # first two edges both cut a run
    rows = next(rows for rows in range(int(plan[3][-1]) // 3 * tile, 0,
                                       -tile)
                if all(((starts < edge) & (starts + counts > edge)).any()
                       for edge in (rows, 2 * rows)))
    local = np.asarray(position) - round_ * rows
    index = np.where((local >= 0) & (local < rows), local, rows)
    buffer = jax.random.normal(jax.random.key(round_), (rows + tile, hidden)
                               ).astype(dtype)
    return runs, rows, jnp.asarray(index), buffer


@pytest.mark.parametrize("second_round", [False, True],
                         ids=["first_round", "second_round"])
@pytest.mark.parametrize("top_k,experts,held,tokens", [
    (8, 16, (4, 8), 264),       # three blocks, the last of eight tokens
    (2, 8, (2, 4), 300)],       # the last of 44
    ids=["eight_picks", "two_picks"])
@pytest.mark.parametrize("dtype,hidden", [(jnp.float32, 128),
                                          (jnp.bfloat16, 256),
                                          (jnp.bfloat16, 768)],
                         ids=["f32x128", "bf16x256", "bf16x768"])
def test_the_combine_by_runs_is_the_sum_of_the_live_picks(
        dtype, hidden, top_k, experts, held, tokens, second_round):
    """The runs' kernel, interpreted, against XLA's gathers of the same
    round: the same float32 additions of the same rows, a slab's chunk
    summed before it joins the chunks before it, so equal to float32's
    rounding (to the last bit where a token has two picks). Onto a sum
    that is not zero; a block with no live pick and a token with none
    keep theirs to the bit; an expert gets no row; runs start inside a
    group of rows and one straddles the round's edge; what lies past the
    round's rows is never read (NaNs here, zeros on XLA's side)."""
    round_ = int(second_round)
    runs, rows, index, buffer = _round_of_runs(top_k, experts, held, tokens,
                                               dtype, hidden, round_)
    starts, counts, _ = (np.asarray(a) for a in runs)
    group = moe._group(dtype)
    edge = (round_ + 1) * rows
    assert ((starts < edge) & (starts + counts > edge)).any()
    assert ((counts > 0) & (starts % group != 0)).any()
    assert (counts[:, 1] == 0).all() and (counts[:, 0] > 0).any()
    acc = jax.random.normal(jax.random.key(2), (tokens, hidden))
    want = moe._combined(acc, buffer.at[rows:].set(0), index, rows)
    got = moe._combined(acc, buffer.at[rows:].set(jnp.nan), index, rows,
                        runs, round_ * rows)
    assert got.dtype == jnp.float32 and got.shape == acc.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=4e-6)
    live = np.asarray(index < rows).sum(axis=1)
    assert (live == 0).any() and not live[128:256].any()
    np.testing.assert_array_equal(np.asarray(got)[live == 0],
                                  np.asarray(acc)[live == 0])
    if top_k == 2:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        assert live.max() > 2       # sums of several rows


def test_the_runs_slab_holds_whatever_a_block_lands():
    """A run of ``n`` rows from any row on touches ``(n - 1) // group +
    2`` groups at most; the slab is sized for the block whose every pick
    is held, in whole chunks."""
    for top_k, count, group in ((8, 16, 16), (8, 32, 16), (4, 8, 16),
                                (2, 2, 8), (8, 256, 16)):
        slab = moe._slab_rows(128, top_k, count, group)
        assert slab % moe._CHUNK == 0
        # the fullest: every expert's run starts on a group's last row
        for runs in ([128 * top_k // count] * count,
                     [128] * top_k + [0] * (count - top_k)):
            assert sum(runs) <= 128 * top_k
            touched = sum((group - 1 + n - 1) // group + 1
                          for n in runs if n)
            assert touched * group <= slab


def test_what_moves_the_rows_follows_the_shapes(monkeypatch):
    """By DMA on the chip where a row is whole lanes of 32-bit words of a
    type the kernels widen, in arrays of whole sublanes; XLA's gather
    otherwise, and everywhere off the chip."""
    mellum_cell = (4 * 8192, 2304, 1152, jnp.bfloat16)
    laguna_cell = (2 * 8192, 2048, 640, jnp.bfloat16)
    assert not moe.rows_by_dma(*mellum_cell)        # the CPU
    monkeypatch.setattr(moe, "on_tpu", lambda: True)
    assert moe.rows_by_dma(*mellum_cell) and moe.rows_by_dma(*laguna_cell)
    assert moe.rows_by_dma(24, 128, 8, jnp.float32)
    assert not moe.rows_by_dma(24, 128, 8, jnp.bfloat16)    # half a word's
    assert not moe.rows_by_dma(24, 16, 8, jnp.float32)      # lanes
    assert not moe.rows_by_dma(24, 256, 8, jnp.float16)
    assert not moe.rows_by_dma(20, 128, 8, jnp.float32)
    assert not moe.rows_by_dma(24, 128, 12, jnp.float32)


def test_what_makes_the_sums_follows_the_shapes(monkeypatch):
    """By runs on the chip at every cell's shapes (the rows move by DMA
    and two slabs of whatever a block can land fit VMEM: 14.2 MB at
    ``mellum_train_8k``'s, 16.8 at ``laguna_train_8k``'s), by XLA's
    gathers off it, where the rows do not move by DMA and where the slabs
    would not fit."""
    cells = {"mellum": (4 * 8192, 2304, 1152, jnp.bfloat16, 8, 16),
             "sdar": (2 * 8192, 2048, 1152, jnp.bfloat16, 8, 16),
             "laguna": (2 * 8192, 2048, 640, jnp.bfloat16, 8, 32),
             "lfm2": (2 * 8192, 2048, 1152, jnp.bfloat16, 4, 8)}
    assert moe.tile_rows(2 * 8192, 8, 128) == moe.tile_rows(
        2 * 8192, 4, 64) == 1152
    assert not any(moe.sums_by_runs(*cell) for cell in cells.values())
    monkeypatch.setattr(moe, "on_tpu", lambda: True)
    assert all(moe.sums_by_runs(*cell) for cell in cells.values())
    assert moe.sums_by_runs(24, 128, 8, jnp.float32, 2, 2)
    assert not moe.sums_by_runs(24, 16, 8, jnp.float32, 2, 2)   # no DMA
    # every one of 256 experts of rows of 8,192 held: 300 MB of slabs
    assert moe.rows_by_dma(2 * 8192, 8192, 640, jnp.bfloat16)
    assert not moe.sums_by_runs(2 * 8192, 8192, 640, jnp.bfloat16, 8, 256)
    slabs = {name: 2 * moe._slab_rows(128, cell[4], cell[5], 16) * cell[1]
             * 2 for name, cell in cells.items()}
    assert slabs == {"mellum": 14_155_776, "sdar": 12_582_912,
                     "laguna": 16_777_216, "lfm2": 6_291_456}


def test_the_walks_tile_follows_the_shapes():
    """1,152 rows at ``mellum_train_8k``'s shapes (4,096 rows an expert in
    four tiles with an eighth of room), 640 at ``laguna_train_8k``'s (512
    rows an expert in one), whole lanes at any."""
    assert moe.tile_rows(4 * 8192, 8, 64) == 1152
    assert moe.tile_rows(2 * 8192, 8, 256) == 640
    assert moe.tile_rows(24, _TOP_K, _EXPERTS) == 128
    for tokens, top_k, experts in ((4 * 8192, 8, 64), (2 * 8192, 8, 256),
                                   (8192, 8, 256), (100, 2, 8)):
        tile = moe.tile_rows(tokens, top_k, experts)
        expected = -(-tokens * top_k // experts)
        tiles = -(-expected // tile)
        assert tile % 128 == 0 and tiles * tile >= expected * 1.1
        assert tile <= 1152 + 128


@pytest.mark.parametrize("shares,scale,shared", [
    (4, 1.0, False),    # mellum2-12b-a2.5b-ep4: two of eight experts a chip
    (8, 2.5, True),     # laguna-xs.2-ep8: one of eight a chip, the routed
                        # sum scaled, a shared expert every chip computes
], ids=["four_shares", "eight_shares_and_the_shared_expert_once"])
def test_the_shares_add_up_to_the_uncut_layer(shares, scale, shared):
    """Eight experts over the chips that share a layer: the shares'
    routed parts summed, with what every chip computes alike (the router;
    the shared expert) counted once, are what the uncut reference gives
    for the whole layer."""
    key = jax.random.key(7)
    x = jax.random.normal(key, (24, _HIDDEN))
    router = jax.random.normal(jax.random.fold_in(key, 1),
                               (_HIDDEN, _EXPERTS))
    whole = _expert_weights(jax.random.fold_in(key, 2), _EXPERTS)
    each = _EXPERTS // shares
    firsts = range(0, _EXPERTS, each)

    def held(first):
        return {n: whole[n][first:first + each]
                for n in ("gate", "up", "down")}

    if not shared:
        total = sum(moe.moe(x, router, *held(first).values(), (first, each),
                            _TOP_K, 8, scale) for first in firsts)
        uncut = ref._experts(_moe_sizes(0, _EXPERTS), x,
                             dict(whole, router=router))
    else:
        # a chip's sparse half as the program computes it, less the
        # residual: its routed part and the shared expert
        cfg = dataclasses.replace(
            mellum.laguna_tiny(), hidden_size=_HIDDEN, expert_width=_WIDTH,
            shared_expert_width=_WIDTH, compute_dtype=jnp.float32)
        assert (cfg.routed_scale, cfg.top_k, cfg.mlp_type(1)) == (
            scale, _TOP_K, mellum.SPARSE)
        lone = {f"shared_{n}": w[0] for n, w in _expert_weights(
            jax.random.fold_in(key, 3), 1).items()}
        lp = dict(whole, router=router, moe_norm=jnp.ones((_HIDDEN,)),
                  **lone)
        halves = [mellum._mlp_half(
            dataclasses.replace(cfg, experts_held=(first, each)), 1,
            x[None], {**lp, **held(first)})[0] - x for first in firsts]
        once = laguna_ref._swiglu(
            laguna_ref._rms_norm(x, lp["moe_norm"], cfg.rms_norm_eps),
            *lone.values())
        total = sum(half - once for half in halves) + once
        uncut = laguna_ref.mlp_half(_sizes(cfg, 24, (0, _EXPERTS)), 1, lp,
                                    x) - x
    # float32 sums of values near 50: eight halves less seven shared experts
    tol = 1e-4 if shared else 2e-5
    np.testing.assert_allclose(total, uncut, rtol=tol, atol=tol)


# -- the decoder against the reference ----------------------------------------------

_SEQ = 32


def _mellum_f32():
    # one window layer and one full one: both kinds, half the compiling
    return ref, dataclasses.replace(
        mellum.mellum_tiny(), compute_dtype=jnp.float32,
        layer_types=(mellum.SLIDING, mellum.FULL))


def _laguna_f32():
    # the dense leading layer (full attention, 6 heads), a window layer of
    # 8 heads and a full one of 6, both sparse with the shared expert
    return laguna_ref, dataclasses.replace(
        mellum.laguna_tiny(), compute_dtype=jnp.float32,
        layer_types=(mellum.FULL, mellum.SLIDING, mellum.FULL),
        mlp_layer_types=(mellum.DENSE, mellum.SPARSE, mellum.SPARSE),
        heads_per_layer=(6, 8, 6))


@pytest.fixture(scope="module", params=[_mellum_f32, _laguna_f32],
                ids=["mellum", "laguna"])
def tiny_f32(request):
    reference, cfg = request.param()
    sizes = _sizes(cfg, _SEQ)
    params = reference.init_params(sizes, jax.random.key(3))
    tokens = jax.random.randint(jax.random.key(4), (2, _SEQ), 4,
                                cfg.vocab_size, jnp.int32)
    return cfg, sizes, params, tokens, reference.value_and_grad(
        sizes, params, [tokens], None, 0), reference


def test_loss_and_every_gradient_match_the_reference(tiny_f32, monkeypatch):
    """Seeded weights from the reference's own initialiser, the program's
    tree: the loss and every leaf's gradient, the Pallas kernels
    (interpreted, the one-kernel backward) under the model's own
    custom_vjp. XLA's inline attention, which a CPU run takes, is held to
    the same references by the cells' rehearsals
    (tests/chipbench/test_chipbench_mellum.py, test_chipbench_laguna.py)."""
    cfg, sizes, params, tokens, (want_loss, want_grads), reference = tiny_f32
    assert jax.tree.structure(params) == jax.tree.structure(
        mellum.init(cfg, jax.random.key(0)))
    monkeypatch.setattr(fa, "beats_inline", lambda seq_len: True)

    def backwards(kind):
        metric = metrics.get("rsdl_attention_backward_total", {"kind": kind})
        return 0 if metric is None else metric.value

    before = backwards("fused"), backwards("split")
    loss, grads = jax.value_and_grad(
        lambda p: mellum.loss_fn(cfg, p, tokens))(params)
    # one backward kernel a layer, counted where the layer's rule ran
    assert (backwards("fused"), backwards("split")) == (
        before[0] + cfg.num_layers, before[1])
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), want in zip(flat, jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(
            got, want, rtol=2e-3, atol=2e-6,
            err_msg=jax.tree_util.keystr(path))
    assert reference.param_count(sizes) == sum(
        x.size for x in jax.tree.leaves(params))


# -- what an attention half's checkpoint keeps ---------------------------------------


def _counts(name, kinds, **labels):
    """The registry's counter ``name`` at each of ``kinds`` (and the other
    ``labels`` it has), 0 where it was never counted."""
    found = (metrics.get(name, {"kind": kind, **labels}) for kind in kinds)
    return [0 if metric is None else metric.value for metric in found]


def _loss_under_plain_checkpoints(cfg, params, tokens):
    """``mellum.loss_fn`` with the policy taken off: the same halves, each
    under a ``jax.checkpoint`` that keeps its input and nothing else, so
    the backward pass runs an attention half's forward kernel again."""
    x = jnp.take(params["embed"], tokens, axis=0,
                 mode="clip").astype(cfg.compute_dtype)
    for layer in range(cfg.num_layers):
        lp = params[f"layer_{layer}"]
        x = jax.checkpoint(functools.partial(
            mellum._attention_half, cfg, layer))(x, lp)
        x = jax.checkpoint(functools.partial(
            mellum._mlp_half, cfg, layer))(x, lp)
    x = mellum._rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    targets = mellum.next_token_targets(tokens)
    return mellum._nll(x, params["head"], targets) / jnp.maximum(
        jnp.sum(targets != mellum.IGNORE_ID), 1)


def _assert_equal_to_the_last_bit(got, want):
    """Two ``(loss, gradients)``: the loss and every leaf."""
    (loss, grads), (want_loss, want_grads) = got, want
    assert float(loss) == float(want_loss)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, leaf), want_leaf in zip(flat, jax.tree.leaves(want_grads)):
        np.testing.assert_array_equal(leaf, want_leaf,
                                      err_msg=jax.tree_util.keystr(path))


def _assert_equal_to_plain_checkpoints(cfg, params, tokens):
    """The loss and every leaf's gradient, to the last bit."""
    _assert_equal_to_the_last_bit(
        jax.value_and_grad(
            lambda p: mellum.loss_fn(cfg, p, tokens))(params),
        jax.value_and_grad(
            lambda p: _loss_under_plain_checkpoints(cfg, p, tokens))(params))


def test_the_gradient_runs_a_layers_forward_kernel_once(tiny_f32,
                                                        monkeypatch):
    """A forward and a backward kernel a layer, gated or not, window or
    full: the half's checkpoint keeps the forward kernel's results, where
    a plain one runs it a second time to have them."""
    cfg, _, params, tokens, _, _ = tiny_f32
    monkeypatch.setattr(fa, "beats_inline", lambda seq_len: True)
    for loss, kernels in ((mellum.loss_fn, 2),
                          (_loss_under_plain_checkpoints, 3)):
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda p: loss(cfg, p, tokens)))(params)
        assert _pallas_calls(jaxpr.jaxpr) == kernels * cfg.num_layers, loss


def test_keeping_the_kernels_results_moves_no_bit(tiny_f32, monkeypatch):
    """The kept output and log-sum-exp are the arrays the kernel run again
    would return: the loss and every leaf's gradient are equal to the
    last bit (operation by operation: under one ``jit`` XLA fuses the two
    programs differently)."""
    cfg, _, params, tokens, _, _ = tiny_f32
    monkeypatch.setattr(fa, "beats_inline", lambda seq_len: True)
    _assert_equal_to_plain_checkpoints(cfg, params, tokens)


@pytest.mark.parametrize("flash", [False, True], ids=["inline", "kernels"])
def test_the_kept_counter_counts_the_kernels_layers(tiny_f32, flash,
                                                    monkeypatch):
    """One a layer the kernels compute, by its kind, beside
    ``rsdl_lm_attention_total``; the inline path has no kernel's results
    to keep."""
    cfg, _, params, tokens, _, _ = tiny_f32
    monkeypatch.setattr(fa, "beats_inline", lambda seq_len: flash)
    counts = functools.partial(_counts, kinds=("window", "full", "inline"))
    before = counts("rsdl_lm_attention_kept_total")
    traced_before = counts("rsdl_lm_attention_total", values="same")
    jax.eval_shape(lambda p: mellum.loss_fn(cfg, p, tokens), params)
    windows = sum(kind == mellum.SLIDING for kind in cfg.layer_types)
    rose = [after - b for after, b in zip(
        counts("rsdl_lm_attention_kept_total"), before)]
    assert rose == ([windows, cfg.num_layers - windows, 0] if flash
                    else [0, 0, 0])
    traced = [after - b for after, b in zip(
        counts("rsdl_lm_attention_total", values="same"), traced_before)]
    # every layer the kernels compute engages, and no other
    assert traced == rose[:2] + [0 if flash else cfg.num_layers]


# -- what an MLP half's checkpoint keeps -----------------------------------------------


def _swiglus_by_kind(cfg):
    """The SwiGLUs a trace of ``cfg`` runs: a dense layer's MLP, a sparse
    layer's shared expert."""
    sparse = sum(cfg.mlp_type(i) == mellum.SPARSE
                 for i in range(cfg.num_layers))
    return {"dense": cfg.num_layers - sparse,
            "shared": sparse if cfg.shared_expert_width else 0}


def _dense_f32():
    # every layer's MLP the dense SwiGLU
    return dataclasses.replace(
        mellum.laguna_tiny(), compute_dtype=jnp.float32,
        layer_types=(mellum.FULL, mellum.SLIDING),
        mlp_layer_types=(mellum.DENSE, mellum.DENSE), heads_per_layer=(6, 8))


def _shared_f32():
    # every layer sparse with the shared expert beside the routed sum
    return dataclasses.replace(
        _dense_f32(), mlp_layer_types=(mellum.SPARSE, mellum.SPARSE))


def _neither_f32():
    # every layer sparse, no shared expert: no SwiGLU to keep anything of
    return _mellum_f32()[1]


@pytest.fixture(scope="module", params=[_dense_f32, _shared_f32, _neither_f32],
                ids=["dense", "shared", "neither"])
def swiglus(request):
    """A tiny configuration, its seeded parameters and tokens, and the
    SwiGLUs a trace of it runs by kind."""
    cfg = request.param()
    params = mellum.init(cfg, jax.random.key(3))
    tokens = jax.random.randint(jax.random.key(4), (2, _SEQ), 4,
                                cfg.vocab_size, jnp.int32)
    return cfg, params, tokens, _swiglus_by_kind(cfg)


def _products(jaxpr, scope: str) -> int:
    """``dot_general`` equations under ``scope`` in ``jaxpr`` and the
    programs it calls."""
    return sum(
        (eqn.primitive.name == "dot_general"
         and scope in str(eqn.source_info.name_stack))
        + sum(_products(sub, scope)
              for sub in jax.core.jaxprs_in_params(eqn.params))
        for eqn in jaxpr.eqns)


def test_the_gradient_runs_nine_products_a_swiglu(swiglus):
    """Three forward and the written-out backward's six, a dense layer's
    MLP or a shared expert: the half's checkpoint keeps ``x G`` and ``x U``
    where a plain one runs the two a second time to have them (the third,
    ``h D``, is dead there)."""
    cfg, params, tokens, by_kind = swiglus
    for loss, products in ((mellum.loss_fn, 9),
                           (_loss_under_plain_checkpoints, 11)):
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda p: loss(cfg, p, tokens)))(params)
        assert _products(jaxpr.jaxpr, mellum.MLP_SCOPE) == (
            products * sum(by_kind.values())), loss


@pytest.mark.parametrize("policy,products", [
    (None, 11),
    (jax.checkpoint_policies.save_only_these_names(
        mellum.KEPT_GATE, mellum.KEPT_UP), 9),
    (jax.checkpoint_policies.save_only_these_names(mellum.KEPT_GATE), 10)],
    ids=["plain", "both_kept", "one_kept"])
def test_a_swiglu_under_a_checkpoint_runs_what_is_not_kept_again(policy,
                                                                 products):
    """The SwiGLU alone: its ``custom_vjp``'s residuals do not outlive a
    checkpoint that does not name them."""
    x = jnp.ones((2, 8, 16), jnp.float32)
    weights = [jnp.ones(shape, jnp.float32)
               for shape in ((16, 32), (16, 32), (32, 16))]

    def loss(x, gate, up, down):
        return jnp.sum(jax.checkpoint(mellum._swiglu, policy=policy)(
            x, gate, up, down))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3)))(x, *weights)
    assert _products(jaxpr.jaxpr, mellum.MLP_SCOPE) == products


def test_keeping_the_products_moves_no_bit(swiglus):
    """The kept ``x G`` and ``x U`` are the arrays the half made again
    would hold: the loss and every leaf's gradient are equal to the last
    bit (operation by operation, as the kernels' results above)."""
    cfg, params, tokens, _ = swiglus
    _assert_equal_to_plain_checkpoints(cfg, params, tokens)


_mlp_counts = functools.partial(_counts, kinds=("dense", "shared"))


def test_the_kept_counter_counts_the_swiglus_by_kind(swiglus):
    """One a SwiGLU whose products the checkpoint keeps, by its kind,
    beside ``rsdl_lm_mlp_total``: off the chip every one, and none where
    no layer has a SwiGLU."""
    cfg, params, tokens, by_kind = swiglus
    before = _mlp_counts("rsdl_lm_mlp_kept_total")
    traced_before = _mlp_counts("rsdl_lm_mlp_total")
    jax.eval_shape(lambda p: mellum.loss_fn(cfg, p, tokens), params)
    rose = [after - b for after, b in zip(
        _mlp_counts("rsdl_lm_mlp_kept_total"), before)]
    assert rose == [by_kind["dense"], by_kind["shared"]]
    assert rose == [after - b for after, b in zip(
        _mlp_counts("rsdl_lm_mlp_total"), traced_before)]


def _memory_with_room(cfg, tokens: int, room: int):
    """An allocator's ``(bytes_limit, bytes_in_use)`` that leaves ``room``
    bytes for kept products under ``mellum.keep_room``'s rule."""
    limit = 1 << 30
    rest = (mellum.STEP_ROWS_A_TOKEN * tokens * cfg.hidden_size
            * jnp.dtype(cfg.compute_dtype).itemsize)
    memory = limit, limit - limit // 16 - rest - room
    assert mellum.keep_room(cfg, tokens, memory) == room
    return memory


_LAYER_KINDS = (mellum.SLIDING, mellum.FULL, mellum.MAMBA, mellum.MAMBA1,
                mellum.GMU, mellum.CROSS, mellum.CONV)
_proj_counts = functools.partial(_counts, kinds=_LAYER_KINDS)
# every SwiGLU of ``_laguna_f32`` (below): the dense layer's 2 x 64 x 128
# float32 and both shared experts' 2 x 64 x 32
_SWIGLUS = 65536 + 2 * 16384
# its first halves' q, k, v and head gate over 64 tokens in float32: 6 or 8
# heads and 2 x 2 key/value heads of 16, a gate a query head
_SIX_HEADS, _EIGHT_HEADS = 64 * 4 * (6 * 17 + 64), 64 * 4 * (8 * 17 + 64)
_NONE = (False, False, False)


@pytest.mark.parametrize("room,kept,first", [
    (_SWIGLUS, (True, True, True), _NONE),
    (65536 + 16384, (True, True, False), _NONE),
    # not the wide one: the two after it
    (2 * 16384, (False, True, True), _NONE),
    (16383, _NONE, _NONE),
    (-(1 << 20), _NONE, _NONE),
    # the SwiGLUs first, all of them; the first halves of what is left
    (_SWIGLUS + 2 * _SIX_HEADS + _EIGHT_HEADS, (True, True, True),
     (True, True, True)),
    (_SWIGLUS + _SIX_HEADS + _EIGHT_HEADS, (True, True, True),
     (True, True, False)),
    (_SWIGLUS + _SIX_HEADS + _EIGHT_HEADS - 1, (True, True, True),
     (True, False, True)),
    (_SWIGLUS + _SIX_HEADS - 1, (True, True, True), _NONE),
    # a SwiGLU goes without before any first half keeps
    (65536 + 16384 + 16383, (True, True, False), _NONE)],
    ids=["fits", "fits_but_the_last", "fits_but_the_widest", "fits_not",
         "the_step_alone_does_not", "every_first_half_fits_too",
         "first_halves_but_the_last", "first_halves_but_the_widest",
         "no_first_half_fits", "a_swiglu_short_and_no_first_half"])
def test_the_layers_that_keep_are_those_the_memory_has_room_for(
        room, kept, first, monkeypatch):
    """What is kept follows the device's memory and the shapes: the
    SwiGLUs take their room in layer order, then the first halves'
    in-projections theirs of what is left, a half there is none for is
    made again whole, the counters say which did, and the gradient is the
    same to the last bit whichever did."""
    _, cfg = _laguna_f32()      # a dense layer, two with the shared expert
    params = mellum.init(cfg, jax.random.key(3))
    tokens = jax.random.randint(jax.random.key(4), (2, _SEQ), 4,
                                cfg.vocab_size, jnp.int32)
    memory = _memory_with_room(cfg, tokens.size, room)
    assert mellum.mlp_halves_kept(cfg, tokens.size, room) == kept
    assert mellum.first_halves_kept(cfg, tokens.size, room) == first
    assert [mellum.in_projections_bytes(cfg, i, tokens.size)
            for i in range(cfg.num_layers)] == [_SIX_HEADS, _EIGHT_HEADS,
                                                _SIX_HEADS]
    monkeypatch.setattr(mellum, "_device_memory", lambda mesh: memory)
    before = _mlp_counts("rsdl_lm_mlp_kept_total")
    first_before = _proj_counts("rsdl_lm_proj_kept_total")
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: mellum.loss_fn(cfg, p, tokens)))(params)
    rose = [after - b for after, b in zip(
        _mlp_counts("rsdl_lm_mlp_kept_total"), before)]
    kinds = [cfg.mlp_type(i) for i, keeps in enumerate(kept) if keeps]
    assert rose == [kinds.count(mellum.DENSE), kinds.count(mellum.SPARSE)]
    first_rose = [after - b for after, b in zip(
        _proj_counts("rsdl_lm_proj_kept_total"), first_before)]
    kinds = [cfg.layer_types[i] for i, keeps in enumerate(first) if keeps]
    assert first_rose == [kinds.count(mellum.SLIDING),
                          kinds.count(mellum.FULL), 0, 0, 0, 0, 0]
    needs = [mellum.kept_products_bytes(cfg, i, tokens.size)
             for i in range(cfg.num_layers)]
    # the room before anything is taken
    assert metrics.get("rsdl_lm_mlp_keep_room_bytes").value == room
    swiglus = sum(need > 0 for need in needs)
    assert _products(jaxpr.jaxpr, mellum.MLP_SCOPE) == (
        11 * swiglus - 2 * sum(kept))
    # q, k, v and the gate four products where they are made again, three
    # where they are kept, ``wo`` three
    assert _products(jaxpr.jaxpr, mellum.PROJ_SCOPE) == (
        (4 * 4 + 3) * cfg.num_layers - 4 * sum(first))
    _assert_equal_to_plain_checkpoints(cfg, params, tokens)


def test_without_an_allocator_to_ask_every_swiglu_keeps():
    """The CPU's devices keep no memory statistics: ``None``, and the
    rule keeps every SwiGLU and nothing of a layer that has none."""
    assert mellum._device_memory(None) is None
    cfg = mellum.laguna_tiny()
    assert mellum.keep_room(cfg, 64, None) is None
    assert mellum.mlp_halves_kept(cfg, 64, None) == (True,) * 5
    assert mellum.mlp_halves_kept(mellum.mellum_tiny(), 64, None) == (
        False,) * 4
    # and every first half, of any kind
    assert mellum.first_halves_kept(cfg, 64, None) == (True,) * 5
    assert mellum.first_halves_kept(mellum.phi4flash_tiny(), 64, None) == (
        True,) * 6


# -- what a first half's checkpoint keeps ------------------------------------------------

# (tiny configuration, projection weights, in-projections among them)
_DECODERS = [
    (mellum.lfm2_tiny, 12, 7), (mellum.granite_tiny, 10, 6),
    (mellum.phi4flash_tiny, 20, 14), (mellum.laguna_tiny, 25, 20),
    (mellum.mellum_tiny, 16, 12)]
_DECODER_IDS = ["lfm2", "granite", "phi4flash", "laguna", "mellum"]


@pytest.fixture(scope="module", params=_DECODERS, ids=_DECODER_IDS)
def decoder(request):
    """A tiny configuration of each decoder, seeded parameters and tokens,
    and its counts of projection weights and of in-projections."""
    build, weights, in_projections = request.param
    cfg = build()
    params = mellum.init(cfg, jax.random.key(3))
    tokens = jax.random.randint(jax.random.key(4), (2, _SEQ), 4,
                                cfg.vocab_size, jnp.int32)
    return cfg, params, tokens, weights, in_projections


def _no_room(mesh):
    return 1 << 30, 1 << 30


@pytest.mark.parametrize("memory,again", [(None, 0), (_no_room, 1)],
                         ids=["kept", "made_again"])
def test_the_gradient_runs_three_products_a_projection_weight(
        decoder, memory, again, monkeypatch):
    """Forward and autodiff's two backward, for every weight under
    ``rsdl.lm.proj``, where the first half's checkpoint keeps its
    in-projections (off the chip, every half); one more an in-projection
    where there is no room and the half makes it again. The projection
    that writes into the residual stream is dead in the half made again,
    and runs three either way."""
    cfg, params, tokens, weights, in_projections = decoder
    if memory is not None:
        monkeypatch.setattr(mellum, "_device_memory", memory)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: mellum.loss_fn(cfg, p, tokens)))(params)
    assert _products(jaxpr.jaxpr, mellum.PROJ_SCOPE) == (
        3 * weights + again * in_projections)


def _loss_and_grads(cfg, params, tokens):
    return jax.value_and_grad(
        lambda p: mellum.loss_fn(cfg, p, tokens))(params)


def test_keeping_the_in_projections_moves_no_bit(decoder, monkeypatch):
    """A kept in-projection is the array the half made again would hold,
    whatever the half's kind: the loss and every leaf's gradient are equal
    to the last bit to those of plain checkpoints, which keep a half's
    input and nothing else (operation by operation, as the kernels'
    results above)."""
    cfg, params, tokens, _, _ = decoder
    got = _loss_and_grads(cfg, params, tokens)
    monkeypatch.setattr(mellum, "_keeping", lambda *names: None)
    _assert_equal_to_the_last_bit(got, _loss_and_grads(cfg, params, tokens))


def test_the_kept_counter_counts_the_first_halves_by_kind(decoder,
                                                          monkeypatch):
    """One a first half whose checkpoint keeps its in-projections, by the
    layer's kind: off the chip every layer, and none where there is no
    room."""
    cfg, params, tokens, _, _ = decoder
    assert metric_names.METRIC_NAMES["rsdl_lm_proj_kept_total"] == (
        "counter", ("kind",))

    def rose():
        before = _proj_counts("rsdl_lm_proj_kept_total")
        jax.eval_shape(lambda p: mellum.loss_fn(cfg, p, tokens), params)
        return [after - b for after, b in zip(
            _proj_counts("rsdl_lm_proj_kept_total"), before)]

    assert rose() == [cfg.layer_types.count(kind) for kind in _LAYER_KINDS]
    monkeypatch.setattr(mellum, "_device_memory", _no_room)
    assert rose() == [0] * len(_LAYER_KINDS)


@pytest.mark.parametrize("build,tokens,kinds,total", [
    # a conv layer's B | C | u of 3 x 2,048, the attention layer's q, k, v
    (mellum.lfm2_24b_a2b_ep8_share, 16_384,
     {mellum.CONV: 201_326_592, mellum.FULL: 100_663_296}, 905_969_664),
    # a Mamba layer's z | x B C | dt of 4,096 + 4,352 + 64
    (mellum.granite4_h_micro_period, 8192,
     {mellum.MAMBA: 139_460_608, mellum.FULL: 50_331_648}, 1_305_477_120),
    # u | z, r | B | C and r W_dt of 10,240 + 192 + 5,120; the full layer's
    # k and v, which the cross layer reads, in the full layer's and not
    # again in the cross layer's
    (mellum.phi4_mini_flash_junction, 8192,
     {mellum.MAMBA1: 254_803_968, mellum.SLIDING: 83_886_080,
      mellum.FULL: 83_886_080, mellum.GMU: 83_886_080,
      mellum.CROSS: 41_943_040}, 803_209_216),
    # 48 or 64 heads of 128, 8 key/value heads, a gate a query head
    (mellum.laguna_xs2_ep8_share, 16_384,
     {mellum.FULL: 270_008_320, mellum.SLIDING: 337_641_472},
     1_552_941_056),
    (mellum.mellum2_ep4_share, 32_768,
     {mellum.SLIDING: 335_544_320, mellum.FULL: 335_544_320},
     1_342_177_280)],
    ids=_DECODER_IDS)
def test_a_first_halfs_bytes_follow_the_widths(build, tokens, kinds, total):
    """What the in-projections of each kind of first half give at the
    8,192-token cells' sizes in bf16, and a full differential layer's keys
    and values counted once: where they are made."""
    cfg = build()
    needs = [mellum.in_projections_bytes(cfg, i, tokens)
             for i in range(cfg.num_layers)]
    assert dict(zip(cfg.layer_types, needs)) == kinds
    assert sum(needs) == total


@pytest.mark.parametrize("build,tokens,in_use,mlp,first", [
    # 12 bytes a parameter in use when the step is traced
    (mellum.lfm2_24b_a2b_ep8_share, 16_384, 12 * 469_285_248, 1, 5),
    (mellum.phi4_mini_flash_junction, 8192, 12 * 697_073_792, 6, 6),
    (mellum.laguna_xs2_ep8_share, 16_384, 12 * 691_623_936, 5, 1),
    (mellum.mellum2_ep4_share, 32_768, 12 * 595_153_152, 0, 0)],
    ids=["lfm2", "phi4flash", "laguna", "mellum"])
def test_the_cells_first_halves_on_a_v5e(build, tokens, in_use, mlp, first):
    """The rule at the cells' sizes under a v5e's allocator limit: every
    SwiGLU keeps as it did, and the first halves keep in layer order until
    the room ends (all of two cells', the first of ``laguna_train_8k``'s,
    none where the room is negative)."""
    cfg = build()
    room = mellum.keep_room(cfg, tokens, (16_909_336_064, in_use))
    kept = mellum.mlp_halves_kept(cfg, tokens, room)
    assert sum(kept) == mlp == sum(
        mellum.kept_products_bytes(cfg, i, tokens) > 0
        for i in range(cfg.num_layers))
    assert mellum.first_halves_kept(cfg, tokens, room) == (
        (True,) * first + (False,) * (cfg.num_layers - first))


def test_granites_first_halves_skip_the_one_that_does_not_fit():
    """``granite_train_8k`` on a v5e: four Mamba layers' in-projections
    fit after the ten SwiGLUs, the fifth's do not, the attention layer's
    (a third of their size) do."""
    cfg = mellum.granite4_h_micro_period()
    room = mellum.keep_room(cfg, 8192, (16_909_336_064, 12 * 772_160_448))
    assert mellum.mlp_halves_kept(cfg, 8192, room) == (True,) * 10
    assert mellum.first_halves_kept(cfg, 8192, room) == (
        (True,) * 4 + (False, True) + (False,) * 4)


def test_lagunas_parameter_count_and_flops():
    """691,623,936 parameters in the cut (11.07 GB at 16 bytes) and 33.44 B
    at the published sizes, the published 33.4 B: the gate is one value a
    query head; 19.7 TFLOP a row of 8,192 tokens, of which the
    projections 43 %, attention's products 31 %, the dense layer 12.55 %,
    the head 6.4 %, the shared and the routed experts 3.1 % each, the
    router 0.5 %; 448 keys a query in a window layer."""
    cfg = mellum.laguna_xs2_ep8_share()
    sizes = _sizes(cfg, 8192)
    assert laguna_ref.param_count(sizes) == 691_623_936
    assert sum(x.size for x in jax.tree.leaves(jax.eval_shape(
        lambda: mellum.init(cfg, jax.random.key(0))))) == 691_623_936
    period = [(mellum.FULL, 48), (mellum.SLIDING, 64), (mellum.SLIDING, 64),
              (mellum.SLIDING, 64)]
    published = {
        **sizes, "num_hidden_layers": 40, "num_experts": 256,
        "vocab_size": 100_352,
        "layer_types": [t for t, _ in period] * 10,
        "num_attention_heads_per_layer": [n for _, n in period] * 10,
        "mlp_layer_types": [mellum.DENSE] + 39 * [mellum.SPARSE]}
    assert round(laguna_ref.param_count(published) / 1e9, 2) == 33.44
    gates = 2048 * 10 * (48 + 3 * 64)
    assert round((laguna_ref.param_count(published) + 127 * gates) / 1e9,
                 1) == 34.1, "a gate as wide as q would not be 33.4 B"
    assert round(laguna_ref._keys_per_query(sizes, ref.SLIDING)) == 496
    assert abs(laguna_ref.train_flops_per_row(sizes) / 1e12 - 19.70) < 0.02
    parts = laguna_ref._forward_flops_per_token(sizes)
    shares = {k: round(100 * v / sum(parts.values()), 1)
              for k, v in parts.items()}
    assert shares == {"projections": 43.0, "attention": 31.2, "dense": 12.6,
                      "shared": 3.1, "experts": 3.1, "router": 0.5,
                      "head": 6.4}
    # 512 rows an expert: the routed experts' float32 weights, read twice
    # and their gradients written, outweigh their products
    for work, names, bound in (
            (laguna_ref.moe_work, ("experts", "router"), "bytes"),
            (laguna_ref.mlp_work, ("dense", "shared"), "flops"),
            (laguna_ref.proj_work, ("projections",), "flops"),
            (laguna_ref.attention_work, ("attention",), "flops")):
        flops, hbm_bytes = work(sizes, 2)
        assert flops == 3.0 * 2 * 8192 * sum(parts[n] for n in names)
        assert hbm_bytes > 0
        assert (hbm_bytes / 819e9 > flops / 197e12) == (bound == "bytes")


def test_the_cells_parameter_count_and_flops():
    """595,153,152 parameters in the cut; 12.2 TFLOP a row of 8,192
    tokens, of which experts 20 %, attention's products 23 %, the
    projections 34 %, the head 23 %; 960 keys a query in a window layer."""
    sizes = _sizes(mellum.mellum2_ep4_share(), 8192)
    assert ref.param_count(sizes) == 595_153_152
    assert round(ref._keys_per_query(sizes, ref.SLIDING)) == 960
    assert abs(ref.train_flops_per_row(sizes) / 1e12 - 12.23) < 0.02
    parts = ref._forward_flops_per_token(sizes)
    shares = {k: round(100 * v / sum(parts.values())) for k, v in
              parts.items()}
    assert shares == {"projections": 34, "attention": 23, "experts": 20,
                      "head": 23}
    for work, part in ((ref.moe_work, "experts"),
                       (ref.attention_work, "attention")):
        flops, hbm_bytes = work(sizes, 4)
        assert flops == 3.0 * 4 * 8192 * parts[part]
        assert 0 < hbm_bytes / 819e9 < flops / 197e12      # FLOP-bound


@pytest.mark.parametrize("seq", [32, 21])
def test_the_blocked_next_token_loss_is_the_dense_one(seq, monkeypatch):
    """Value and both gradients, at a count of tokens that blocks of 16
    divide and at one they do not (padded with ignored tokens)."""
    monkeypatch.setattr(mellum, "HEAD_BLOCK_TOKENS", 16)
    key = jax.random.key(seq)
    x = jax.random.normal(key, (3, seq, 16))
    head = jax.random.normal(jax.random.fold_in(key, 1), (16, 50))
    targets = mellum.next_token_targets(jax.random.randint(
        jax.random.fold_in(key, 2), (3, seq), 0, 50, jnp.int32))
    assert (targets[:, -1] == mellum.IGNORE_ID).all()
    assert mellum.head_block_size(3 * seq) == 16
    got, got_grads = jax.value_and_grad(
        lambda x, w: mellum._nll(x, w, targets), (0, 1))(x, head)
    want, want_grads = jax.value_and_grad(
        lambda x, w: mellum._block_nll(x.reshape(-1, 16), w,
                                       targets.reshape(-1)), (0, 1))(x, head)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


def test_the_heads_block_is_2048_tokens():
    assert mellum.head_block_size(4 * 8192) == 2048
    assert mellum.head_block_size(2 * 32) == 64
    assert mellum.head_block_size(3 * 7) == 24


@pytest.mark.parametrize("build,layer_type,rotated", [
    (mellum.mellum_tiny, mellum.FULL, 16),
    (mellum.laguna_tiny, mellum.FULL, 8),       # half a head, the rest pass
    (mellum.laguna_tiny, mellum.SLIDING, 16),
], ids=["mellum_full", "laguna_full_half_a_head", "laguna_sliding"])
def test_rotary_positions_are_the_rotate_half_formula(build, layer_type,
                                                      rotated):
    """The rotation as a product with a signed permutation equals
    ``x cos + concat(-x2, x1) sin`` over the rotated dimensions, exactly,
    and leaves the others as they were; the reference's slices agree."""
    cfg = build()
    assert mellum.rotated_dims(cfg, layer_type) == rotated
    x = jax.random.normal(jax.random.key(0), (2, 8, 4 * cfg.head_dim))
    cos, sin = mellum._rope_tables(cfg, layer_type, 8)
    heads = x.reshape(2, 8, 4, cfg.head_dim)
    turn, rest = heads[..., :rotated], heads[..., rotated:]
    half = rotated // 2
    want = jnp.concatenate([
        turn * cos[:, None, :rotated] + jnp.concatenate(
            [-turn[..., half:], turn[..., :half]], -1)
        * sin[:, None, :rotated], rest], -1)
    got = mellum._rope(x, 4, cos, sin, rotated)
    np.testing.assert_allclose(got, want.reshape(x.shape), rtol=1e-6)
    np.testing.assert_array_equal(
        got.reshape(heads.shape)[..., rotated:], rest)
    inv_freq, scale = mellum.rope_inv_freq(cfg, layer_type)
    assert inv_freq.shape == (half,)
    angles = jnp.arange(8)[:, None] * jnp.concatenate([inv_freq, inv_freq])
    np.testing.assert_allclose(
        laguna_ref._rotate(heads[0], jnp.cos(angles) * scale,
                           jnp.sin(angles) * scale),
        want[0], rtol=1e-6)


@pytest.mark.parametrize("flash", [False, True], ids=["inline", "kernels"])
def test_the_head_gate_is_the_written_out_product(flash, monkeypatch):
    """``(g_h a_h)_h`` for one value a query head: the gated attention is
    the ungated one times the gate, and its gradients are those of that
    product, through XLA's inline attention and through the kernels'
    custom_vjp."""
    monkeypatch.setattr(fa, "beats_inline", lambda seq_len: flash)
    cfg = dataclasses.replace(mellum.laguna_tiny(),
                              compute_dtype=jnp.float32)
    heads, kv_heads, d, seq = 6, cfg.num_kv_heads, cfg.head_dim, 16
    key = jax.random.key(5)
    q, k, v, mix = (jax.random.normal(jax.random.fold_in(key, i),
                                      (2, seq, n * d))
                    for i, n in enumerate((heads, kv_heads, kv_heads,
                                           heads)))
    gate = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 9),
                                            (2, seq, heads)))

    def gated(q, k, v, gate):
        return mellum._attention(cfg, q, k, v, gate, mellum.SLIDING, heads)

    def written_out(q, k, v, gate):
        plain = mellum._attention(cfg, q, k, v, None, mellum.SLIDING, heads)
        return (plain.reshape(2, seq, heads, d)
                * gate[..., None]).reshape(2, seq, heads * d)

    np.testing.assert_allclose(gated(q, k, v, gate),
                               written_out(q, k, v, gate), atol=1e-6)
    got = jax.grad(lambda *a: jnp.sum(gated(*a) * mix), (0, 1, 2, 3))(
        q, k, v, gate)
    want = jax.grad(lambda *a: jnp.sum(written_out(*a) * mix),
                    (0, 1, 2, 3))(q, k, v, gate)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5)


def test_scopes_and_counters_reach_the_compiled_step(tiny_f32):
    """The scopes name operations of the compiled gradient as written
    (forward and backward, not ``jvp(scope)``), never a ``while`` (a
    reader that sums under a scope counts each operation once), and the
    trace counted what it compiled."""
    cfg, _, params, tokens, _, _ = tiny_f32
    swiglus = _swiglus_by_kind(cfg)
    sparse = cfg.num_layers - swiglus["dense"]

    def count(name, **labels):
        metric = metrics.get(name, labels or None)
        return 0 if metric is None else metric.value

    before = {kind: count("rsdl_lm_attention_total", kind=kind,
                          values="same")
              for kind in ("inline", "window", "full")}
    layers_before = count("rsdl_moe_layer_total", kind="share")
    gathers_before = {kind: count("rsdl_moe_gather_total", kind=kind)
                      for kind in ("dma", "xla")}
    mlps_before = {kind: count("rsdl_lm_mlp_total", kind=kind)
                   for kind in swiglus}
    text = jax.jit(jax.grad(lambda p, t: mellum.loss_fn(cfg, p, t))).lower(
        params, tokens).compile().as_text()
    names = xplane.hlo_op_names(text)
    scopes = [mellum.PROJ_SCOPE, mellum.ATTENTION_SCOPE, mellum.MOE_SCOPE,
              mellum.HEAD_SCOPE]
    if any(swiglus.values()):
        scopes.append(mellum.MLP_SCOPE)
    else:
        assert mellum.MLP_SCOPE not in text
    for scope in scopes:
        under = {name: op_name for name, op_name in names.items()
                 if xplane.under_scope(op_name, scope)}
        assert any(name.startswith(("dot", "fusion")) for name in under), (
            scope, sorted(under))
        assert not any(name.startswith("while") for name in under), scope
        assert not re.search(rf"jvp\({re.escape(scope)}\)", text)
    # CPU: every layer's attention is XLA's inline one
    assert count("rsdl_lm_attention_total", kind="inline",
                 values="same") == before["inline"] + cfg.num_layers
    assert count("rsdl_lm_attention_total", kind="window",
                 values="same") == before["window"]
    assert count("rsdl_moe_layer_total", kind="share") == (
        layers_before + sparse)
    # CPU: every walk's rows move by XLA's gather
    assert count("rsdl_moe_gather_total", kind="xla") == (
        gathers_before["xla"] + sparse)
    assert count("rsdl_moe_gather_total", kind="dma") == (
        gathers_before["dma"])
    for kind, traced in swiglus.items():
        assert count("rsdl_lm_mlp_total", kind=kind) == (
            mlps_before[kind] + traced), kind
    assert count("rsdl_moe_experts_held") == 2
    assert count("rsdl_moe_experts_routed") == 8
    assert count("rsdl_moe_top_k") == 2
    assert count("rsdl_moe_tile_rows") == 128


@pytest.mark.parametrize("build", [_mellum_f32, _laguna_f32],
                         ids=["mellum", "laguna_half_a_head"])
def test_where_the_chip_would_the_kernels_place_the_heads(build, placings,
                                                          monkeypatch):
    """Heads of 128 as the cells', rotated and not normed (Laguna's full
    layers over half a head under YaRN's tables, its window layers over
    the whole of it, 6 or 8 heads a layer): with ``ops.rope.on_tpu`` true
    the two kernels (interpreted) place every layer's q and k, the loss
    and every gradient are the XLA passes' to float32's rounding, and the
    compiled gradient names the kernels' operations under
    ``rsdl.lm.rope`` in a form the benchmark's reader knows."""
    from chipbench.readers import wrapped_scopes
    reference, cfg = build()
    cfg = dataclasses.replace(cfg, head_dim=128)
    params = reference.init_params(_sizes(cfg, _SEQ), jax.random.key(3))
    tokens = jax.random.randint(jax.random.key(4), (2, _SEQ), 4,
                                cfg.vocab_size, jnp.int32)
    step = jax.value_and_grad(lambda p: mellum.loss_fn(cfg, p, tokens))
    want_loss, want_grads = step(params)
    assert placings() == {"vmem": 0, "xla": 2 * cfg.num_layers}
    monkeypatch.setattr(rope, "on_tpu", lambda: True)
    loss, grads = step(params)
    assert placings() == {"vmem": 2 * cfg.num_layers,
                          "xla": 2 * cfg.num_layers}
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), want in zip(flat, jax.tree.leaves(want_grads)):
        scale = max(float(jnp.max(jnp.abs(want))), 1e-30)
        np.testing.assert_allclose(got / scale, want / scale, rtol=1e-4,
                                   atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))
    names = xplane.hlo_op_names(jax.jit(step).lower(
        params).compile().as_text()).values()
    placed = [name for name in names if mellum.ROPE_SCOPE in name]
    assert any("transpose" in name for name in placed), "the backward's"
    assert any("transpose" not in name for name in placed), "the forward's"
    assert all(xplane.under_scope(wrapped_scopes.unwrapped(name),
                                  mellum.ROPE_SCOPE) for name in placed)
    assert not any("dot_general" in name for name in placed)


@pytest.mark.parametrize("build,windows,fulls", [
    (mellum.mellum_tiny, 3, 1), (mellum.laguna_tiny, 3, 2)],
    ids=["mellum", "laguna"])
def test_the_attention_counter_tells_window_from_full(build, windows, fulls,
                                                      monkeypatch):
    """On the chip the kernels take both kinds; a window that covers the
    row is the triangle."""
    monkeypatch.setattr(fa, "beats_inline", lambda seq_len: True)
    cfg = build()

    def count(kind):
        metric = metrics.get("rsdl_lm_attention_total",
                             {"kind": kind, "values": "same"})
        return 0 if metric is None else metric.value

    before = count("window"), count("full")
    jax.eval_shape(lambda p, t: mellum.loss_fn(cfg, p, t),
                   mellum.init(cfg, jax.random.key(0)),
                   jnp.zeros((1, 16), jnp.int32))
    assert (count("window"), count("full")) == (before[0] + windows,
                                                before[1] + fulls)
    jax.eval_shape(lambda p, t: mellum.loss_fn(cfg, p, t),
                   mellum.init(cfg, jax.random.key(0)),
                   jnp.zeros((1, 8), jnp.int32))
    assert (count("window"), count("full")) == (
        before[0] + windows, before[1] + fulls + windows + fulls)


@pytest.mark.parametrize("chip,hidden,dma", [
    (False, 256, False),    # every CPU run
    (True, 256, True),      # the chip: bf16 rows of 128 words
    (True, 64, False)],     # rows of a quarter of a lane's words
    ids=["cpu", "chip", "chip_narrow_rows"])
@pytest.mark.parametrize("build", [mellum.mellum_tiny, mellum.laguna_tiny],
                         ids=["mellum", "laguna"])
def test_the_gather_counter_tells_dma_from_xla(build, chip, hidden, dma,
                                               monkeypatch):
    """One count a sparse layer traced, by what moves its walk's rows:
    nothing but the backend and the shapes chooses."""
    monkeypatch.setattr(moe, "on_tpu", lambda: chip)
    cfg = dataclasses.replace(build(), hidden_size=hidden)
    sparse = sum(cfg.mlp_type(i) == mellum.SPARSE
                 for i in range(cfg.num_layers))

    def count(kind):
        metric = metrics.get("rsdl_moe_gather_total", {"kind": kind})
        return 0 if metric is None else metric.value

    before = count("dma"), count("xla")
    jax.eval_shape(lambda p, t: mellum.loss_fn(cfg, p, t),
                   mellum.init(cfg, jax.random.key(0)),
                   jnp.zeros((1, 16), jnp.int32))
    assert (count("dma") - before[0], count("xla") - before[1]) == (
        (sparse, 0) if dma else (0, sparse))


@pytest.mark.parametrize("chip,hidden,runs", [
    (False, 256, False),    # every CPU run
    (True, 256, True),      # the chip: whole groups of bf16 rows
    (True, 64, False)],     # rows that no DMA moves
    ids=["cpu", "chip", "chip_narrow_rows"])
@pytest.mark.parametrize("build", [mellum.mellum_tiny, mellum.laguna_tiny],
                         ids=["mellum", "laguna"])
def test_the_combine_counter_tells_runs_from_xla(build, chip, hidden, runs,
                                                 monkeypatch):
    """One count a sparse layer traced, by what makes the tokens' sums of
    a round's rows: nothing but the backend and the shapes chooses."""
    monkeypatch.setattr(moe, "on_tpu", lambda: chip)
    cfg = dataclasses.replace(build(), hidden_size=hidden)
    sparse = sum(cfg.mlp_type(i) == mellum.SPARSE
                 for i in range(cfg.num_layers))

    def count(kind):
        metric = metrics.get("rsdl_moe_combine_total", {"kind": kind})
        return 0 if metric is None else metric.value

    before = count("runs"), count("xla")
    jax.eval_shape(lambda p, t: mellum.loss_fn(cfg, p, t),
                   mellum.init(cfg, jax.random.key(0)),
                   jnp.zeros((1, 16), jnp.int32))
    assert (count("runs") - before[0], count("xla") - before[1]) == (
        (sparse, 0) if runs else (0, sparse))


@pytest.mark.parametrize("build", [mellum.mellum_tiny, mellum.laguna_tiny],
                         ids=["mellum", "laguna"])
def test_a_mesh_of_several_devices_is_refused(build):
    """Nothing stands in for the absent chips: the decoder runs one chip's
    share and says so when handed more."""
    from ray_shuffling_data_loader_tpu.parallel import mesh as mesh_mod
    cfg = build()
    assert mesh_mod.EXPERT_AXIS == "expert"
    with pytest.raises(NotImplementedError, match="exchange"):
        mellum.loss_fn(cfg, mellum.init(cfg, jax.random.key(0)),
                       jnp.zeros((2, 16), jnp.int32),
                       mesh_mod.make_mesh(num_devices=2))


def test_per_layer_lists_must_name_every_layer():
    cfg = dataclasses.replace(mellum.laguna_tiny(), heads_per_layer=(6, 8))
    with pytest.raises(ValueError, match="heads_per_layer names 2 layers"):
        mellum.loss_fn(cfg, {}, jnp.zeros((1, 16), jnp.int32))
    cfg = dataclasses.replace(
        mellum.mellum_tiny(), mlp_layer_types=("dense", "sparse", "sparse",
                                               "wide"))
    with pytest.raises(ValueError, match="mlp_layer_types entry 'wide'"):
        mellum.loss_fn(cfg, {}, jnp.zeros((1, 16), jnp.int32))
