"""The decoder (models/mellum.py) trained by block diffusion, at
``sdar_tiny`` on the CPU, against the plain reference
(chipbench/references/sdar.py): the mask against a table written out, the
blocked kernels under it (ops/flash_attention.py, interpreted) against
XLA's inline attention under the same booleans, the noise, the loss and
every gradient, what the two copies of a row may see of each other, the
four shares of an expert layer against the uncut layer, and the five older
configurations' programs against the parent commit's."""

import dataclasses
import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chipbench.references import sdar as ref
from ray_shuffling_data_loader_tpu.models import mellum
from ray_shuffling_data_loader_tpu.ops import flash_attention as fa
from ray_shuffling_data_loader_tpu.ops import moe, rope
from ray_shuffling_data_loader_tpu.parallel import mesh as mesh_mod
from ray_shuffling_data_loader_tpu.parallel import trainer as trainer_mod
from ray_shuffling_data_loader_tpu.runtime import metrics
from ray_shuffling_data_loader_tpu.utils import tracing

_SEQ = 32

# L = 16 tokens in blocks of 4, the clean copy first: query by key. A clean
# query (rows 0-15) sees the clean blocks up to and with its own; a noised
# one (rows 16-31) the clean blocks before its own and its own noised block.
_TABLE = (
    "####............................",
    "####............................",
    "####............................",
    "####............................",
    "########........................",
    "########........................",
    "########........................",
    "########........................",
    "############....................",
    "############....................",
    "############....................",
    "############....................",
    "################................",
    "################................",
    "################................",
    "################................",
    "................####............",
    "................####............",
    "................####............",
    "................####............",
    "####................####........",
    "####................####........",
    "####................####........",
    "####................####........",
    "########................####....",
    "########................####....",
    "########................####....",
    "########................####....",
    "############................####",
    "############................####",
    "############................####",
    "############................####",
)


def _table() -> np.ndarray:
    return np.array([[c == "#" for c in row] for row in _TABLE])


# -- the mask -----------------------------------------------------------------


def test_the_reference_keeps_the_published_order():
    """The reference's mask is the table with its copies swapped: the
    noised copy first, ``[x_t ; x_0]``, as published."""
    swap = np.r_[16:32, 0:16]
    np.testing.assert_array_equal(ref.block_diffusion_mask(16, 4),
                                  _table()[np.ix_(swap, swap)])


@pytest.mark.parametrize("tiles", [(8, 8), (4, 8), (16, 16), (8, 4)],
                         ids=lambda t: f"{t[0]}x{t[1]}")
def test_the_mask_is_the_table_written_out(tiles):
    want = _table()
    np.testing.assert_array_equal(fa.diffusion_seen(4, 16), want)
    # every tile of the kernels' walk, and no live pair outside it
    mask = fa._mask_of(False, None, (4, 16))
    for bq, bk in (tiles,):
        covered = np.zeros_like(want)
        for g in range(32 // bq):
            for t in range(fa._steps(mask, bq, bk, 32 // bq, 32 // bk)):
                kb, live = fa._diffusion_visit(mask, g, t, bq, bk)
                if not bool(live):
                    continue
                kb = int(kb)
                tile = np.asarray(fa._seen(mask, g, kb, bq, bk))
                rows = slice(g * bq, (g + 1) * bq)
                cols = slice(kb * bk, (kb + 1) * bk)
                np.testing.assert_array_equal(tile, want[rows, cols])
                assert tile.any(), "a visited tile holds a live pair"
                assert bool(fa._whole(mask, g, kb, bq, bk)) == tile.all()
                assert not covered[rows, cols].any(), "visited once"
                covered[rows, cols] = True
        assert not (want & ~covered).any()
        visited, compared, live = fa.diffusion_tiles(4, 16, bq, bk)
        assert visited * bq * bk == covered.sum()
        assert live == int(want.sum()) == 16 * 16 + 4 * 16
    assert fa.diffusion_tiles(4, 16, 8, 8)[:2] == (8, 6)


def test_the_cells_tiles_by_count():
    """At the cell's shape, 8,192 tokens in blocks of 4: of the 256 tiles
    of 1,024 x 1,024 the walk visits 80 (the two lower triangles with
    their diagonals and the noised copy's own diagonal), 24 of which
    compare positions; the live pairs are L^2 + 4 L whatever the tiles."""
    assert fa.diffusion_tiles(4, 8192, 1024, 1024) == (
        80, 24, 8192 * 8192 + 4 * 8192)
    visited, _, live = fa.diffusion_tiles(4, 8192, 512, 512)
    assert visited == 288 and round(100 * live / (288 * 512 * 512)) == 89
    assert round(100 * live / (80 * 1024 * 1024)) == 80
    assert ref.live_pairs({"seq_len": 8192, "block_length": 4}) == live


def test_masks_the_kernels_do_not_take_are_refused_by_name():
    q = jnp.zeros((1, 64, 32))
    with pytest.raises(ValueError, match="neither causal nor a window"):
        fa.grouped_forward(q, q, q, 2, 2, True, None, 16, 16, True,
                           diffusion=(4, 32))
    with pytest.raises(ValueError, match="divides"):
        fa.grouped_forward(q, q, q, 2, 2, False, None, 16, 16, True,
                           diffusion=(5, 32))
    # a block of 8 does not divide a tile of 4; a tile of 64 straddles
    # the two copies of 32
    for block, tile in ((8, 4), (4, 64)):
        with pytest.raises(ValueError, match="block length divides"):
            fa.grouped_forward(q, q, q, 2, 2, False, None, tile, tile, True,
                               diffusion=(block, 32))
    with pytest.raises(ValueError, match="needs 64 queries"):
        fa.grouped_forward(q[:, :32], q[:, :32], q[:, :32], 2, 2, False,
                           None, 16, 16, True, diffusion=(4, 32))


def test_the_split_backward_refuses_the_mask_by_name(monkeypatch):
    monkeypatch.setattr(fa, "_fused_fits", lambda *a, **k: False)
    q = jnp.zeros((1, 64, 32))
    out, lse = fa.grouped_forward(q, q, q, 2, 2, False, None, 16, 16, True,
                                  diffusion=(4, 32))
    with pytest.raises(ValueError, match="does not take block diffusion"):
        fa.grouped_backward(q, q, q, out, lse, q, 2, 2, False, None, 16, 16,
                            True, diffusion=(4, 32))


# -- the kernels under the mask -----------------------------------------------


def _operands(length: int, heads: int, kv_heads: int, dim: int):
    keys = jax.random.split(jax.random.key(0), 4)
    shape = lambda h: (2, 2 * length, h * dim)          # noqa: E731
    return (jax.random.normal(keys[0], shape(heads)),
            jax.random.normal(keys[1], shape(kv_heads)),
            jax.random.normal(keys[2], shape(kv_heads)),
            jax.random.normal(keys[3], shape(heads)))


@pytest.mark.parametrize("tiles", [(16, 16), (8, 16), (32, 32), (16, 8),
                                   (8, 8), (64, 64)],
                         ids=lambda t: f"{t[0]}x{t[1]}")
def test_the_kernels_are_the_inline_attention_under_the_mask(tiles):
    """64 tokens in blocks of 4, 4:2 heads of 16: one to eight tiles a
    copy's side, so a noised query block's walk crosses the gap between
    its run of clean key blocks and its own noised block; output and the
    three gradients against XLA's softmax under the same booleans."""
    length, heads, kv_heads = 64, 4, 2
    q, k, v, do = _operands(length, heads, kv_heads, 16)
    diffusion = (4, length)
    seen = fa.diffusion_seen(*diffusion)
    want, vjp = jax.vjp(
        lambda q, k, v: mellum._inline_attention(
            q, k, v, None, heads, kv_heads, None, None, None, None, seen),
        q, k, v)
    out, lse = fa.grouped_forward(q, k, v, heads, kv_heads, False, None,
                                  *tiles, True, diffusion=diffusion)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    got = fa.grouped_backward(q, k, v, out, lse, do, heads, kv_heads, False,
                              None, *tiles, True, diffusion=diffusion)
    for mine, theirs in zip(got, vjp(do)):
        np.testing.assert_allclose(mine, theirs, rtol=2e-4, atol=2e-4)


# -- the noise ----------------------------------------------------------------


def test_the_noise_follows_its_key_and_its_levels():
    cfg = mellum.sdar_tiny()
    tokens = jax.random.randint(jax.random.key(1), (32, 4096), 4,
                                cfg.vocab_size, jnp.int32)
    both, masked, weights = mellum.diffusion_noise(cfg, tokens,
                                                   jax.random.key(2))
    again = mellum.diffusion_noise(cfg, tokens, jax.random.key(2))
    for a, b in zip((both, masked, weights), again):
        np.testing.assert_array_equal(a, b)
    other = mellum.diffusion_noise(cfg, tokens, jax.random.key(3))[1]
    assert float(jnp.mean(masked != other)) > 0.2
    # the clean copy first, then the row with its masked tokens replaced
    np.testing.assert_array_equal(both[:, :4096], tokens)
    np.testing.assert_array_equal(
        both[:, 4096:], jnp.where(masked, cfg.mask_token_id, tokens))
    # one level a block of four, p in [eps, 1), the weight its inverse
    prob = 1.0 / np.asarray(weights)
    np.testing.assert_array_equal(prob.reshape(32, -1, 4)[..., :1].repeat(
        4, axis=-1).reshape(prob.shape), prob)
    assert cfg.diffusion_eps <= prob.min() and prob.max() < 1.0
    assert abs(prob.mean() - (0.5 + cfg.diffusion_eps / 2)) < 0.01
    # each token masked with its block's p: by decile of p
    for low in np.arange(0.0, 1.0, 0.1):
        inside = (prob >= low) & (prob < low + 0.1)
        assert abs(np.asarray(masked)[inside].mean()
                   - prob[inside].mean()) < 0.02
    # the reference draws the same
    noised, ref_masked, ref_weights = ref.noise(
        tokens, jax.random.key(2), cfg.diffusion_block, cfg.mask_token_id,
        cfg.diffusion_eps)
    np.testing.assert_array_equal(noised, both[:, 4096:])
    np.testing.assert_array_equal(ref_masked, masked)
    # to the last bit but for the division, fused in one and not the other
    np.testing.assert_allclose(ref_weights, weights, rtol=1e-6)
    with pytest.raises(ValueError, match="whole blocks"):
        mellum.diffusion_noise(cfg, tokens[:, :30], jax.random.key(2))


# -- the program against the reference ----------------------------------------


def _sizes(cfg: mellum.DecoderConfig, seq_len: int = _SEQ):
    """The reference's view of a program configuration."""
    return {
        "hidden_size": cfg.hidden_size, "vocab_size": cfg.vocab_size,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "num_hidden_layers": cfg.num_layers,
        "num_experts_routed": cfg.num_experts,
        "experts_held_first": cfg.experts_held[0],
        "num_experts": cfg.experts_held[1],
        "num_experts_per_tok": cfg.top_k,
        "moe_intermediate_size": cfg.expert_width, "norm_topk_prob": True,
        "rope_theta": cfg.rope_theta, "rope_scaling": None,
        "router_trains": cfg.router_trains,
        "rms_norm_eps": cfg.rms_norm_eps, "seq_len": seq_len,
        "block_length": cfg.diffusion_block,
        "mask_token_id": cfg.mask_token_id, "noise_eps": cfg.diffusion_eps,
        "published": {"num_hidden_layers": cfg.published_layers},
    }


def _seeded(sizes, key):
    """The reference's seeded weights with every norm's scale moved off 1
    (the q and k heads' too)."""
    params = ref.init_params(sizes, key)
    keys = iter(jax.random.split(jax.random.fold_in(key, 1), 64))
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    moved = [leaf * jax.random.uniform(next(keys), leaf.shape, minval=0.5,
                                       maxval=1.5)
             if jax.tree_util.keystr(path).endswith("norm']") else leaf
             for path, leaf in flat]
    return jax.tree_util.tree_unflatten(tree, moved)


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(mellum.sdar_tiny(), compute_dtype=jnp.float32)
    sizes = _sizes(cfg)
    params = _seeded(sizes, jax.random.key(3))
    tokens = jax.random.randint(jax.random.key(4), (2, _SEQ), 4,
                                cfg.vocab_size, jnp.int32)
    seed_key = jax.random.key(7)
    return cfg, sizes, params, tokens, seed_key, ref.value_and_grad(
        sizes, params, [tokens], None, 5, seed_key)


@pytest.mark.parametrize("flash", [False, True], ids=["inline", "kernels"])
def test_loss_and_every_gradient_match_the_reference(tiny, flash,
                                                     monkeypatch):
    """Seeded weights from the reference's own initialiser, the program's
    tree, the noise from the same key: the loss and every leaf's gradient
    in float32, with XLA's inline attention and with the Pallas kernels
    (interpreted). The program reads [clean ; noised], the reference the
    published [noised ; clean]: the loss does not depend on the order.
    2e-3 of a leaf's largest value: both sides are float32 and differ by
    the order of their sums alone."""
    cfg, sizes, params, tokens, seed_key, (want_loss, want_grads) = tiny
    assert jax.tree.structure(params) == jax.tree.structure(
        mellum.init(cfg, jax.random.key(0)))
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(
        jnp.shape, mellum.init(cfg, jax.random.key(0)))
    if flash:
        monkeypatch.setattr(fa, "beats_inline", lambda seq_len: True)
    loss, grads = jax.value_and_grad(lambda p: mellum.loss_fn(
        cfg, p, tokens, None, jax.random.fold_in(seed_key, 5)))(params)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), want in zip(flat, jax.tree.leaves(want_grads)):
        scale = max(float(jnp.max(jnp.abs(want))), 1e-30)
        np.testing.assert_allclose(
            got / scale, want / scale, rtol=2e-3, atol=2e-5,
            err_msg=jax.tree_util.keystr(path))
    assert ref.param_count(sizes) == sum(
        x.size for x in jax.tree.leaves(params))
    for layer in range(cfg.num_layers):          # router_trains is false
        assert float(jnp.max(jnp.abs(
            grads[f"layer_{layer}"]["router"]))) == 0.0
    assert float(jnp.max(jnp.abs(grads["head"]))) > 0


def test_the_loss_is_the_weighted_sum_over_the_masked_positions(tiny):
    """From the decoder's own hidden states, by hand: the noised copy's
    logits at the masked positions, each negative log-likelihood over its
    block's p, over rows x L; and a key is asked for."""
    cfg, _, params, tokens, seed_key, _ = tiny
    key = jax.random.fold_in(seed_key, 5)
    both, masked, weights = mellum.diffusion_noise(cfg, tokens, key)
    x = mellum.decode(cfg, params, both)[:, _SEQ:]
    logits = mellum._rms_norm(x, params["final_norm"],
                              cfg.rms_norm_eps) @ params["head"]
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                               tokens[..., None], axis=-1)[..., 0]
    want = jnp.sum(jnp.where(masked, nll * weights, 0.0)) / tokens.size
    np.testing.assert_allclose(
        float(mellum.loss_fn(cfg, params, tokens, None, key)), float(want),
        rtol=1e-5)
    assert int(masked.sum()) > 0
    with pytest.raises(ValueError, match="from a key"):
        mellum.loss_fn(cfg, params, tokens)


def test_bfloat16_compute_stays_by_the_reference(tiny):
    """The step as the cell runs it, float32 parameters under bfloat16
    compute: the loss within a part in a thousand and the median leaf's
    gradient within a tenth of the float32 reference's. At these sizes an
    expert sees some thirty positions: one pick that the rounding flips
    in the first layer moves that layer's experts' gradients several
    times over and every leaf after it by a few per cent, so no worst
    leaf is held here (the cell's limits are the chip's readings)."""
    cfg, _, params, tokens, seed_key, (want_loss, want_grads) = tiny
    loss, grads = jax.value_and_grad(lambda p: mellum.loss_fn(
        dataclasses.replace(cfg, compute_dtype=jnp.bfloat16), p, tokens,
        None, jax.random.fold_in(seed_key, 5)))(params)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-3)
    gaps = []
    for got, want in zip(jax.tree.leaves(grads),
                         jax.tree.leaves(want_grads)):
        assert got.dtype == jnp.float32
        if float(jnp.linalg.norm(want)) > 0:
            gaps.append(float(jnp.linalg.norm(got - want)
                              / jnp.linalg.norm(want)))
    assert np.median(gaps) < 0.1, sorted(gaps)


def _norm_gaps(grads, want_grads):
    """chipbench/check.py's number: each leaf's gap between the two norms
    over the larger of the reference's norm of it and of its median
    leaf."""
    from chipbench import check
    return check.leaf_gaps(check.leaf_norms(grads),
                           check.leaf_norms(want_grads))


@pytest.mark.parametrize("step", [1846, 16790],
                         ids=lambda s: f"step_{s}")
def test_one_heavy_token_is_weighed_as_the_reference_weighs_it(tiny, step):
    """At a floor of 1e-3 a token masked in a block near the floor weighs
    hundreds: at these two steps' keys one masked token of the 64 weighs
    919 and 584, 99.9 % of the step's sum of squared weights, so the
    step's gradient is that token's. In float32 the program's loss and
    every leaf's gradient norm stay by the reference's to parts in a
    million: the weight is applied where and as the reference applies
    it. Under bfloat16 compute the same step is off by per cents in some
    leaf and more where a pick flips, with nothing to average it: what a
    one-row step at 16,384 positions shows at a seed in twenty (PERF.md
    section 6, PR 47), and the precision's, not the program's."""
    cfg, sizes, params, tokens, seed_key, _ = tiny
    key = jax.random.fold_in(seed_key, step)
    _, masked, weights = mellum.diffusion_noise(cfg, tokens, key)
    squares = jnp.where(masked, weights, 0.0) ** 2
    assert float(jnp.max(squares) / jnp.sum(squares)) > 0.99
    assert float(jnp.sqrt(jnp.max(squares))) > 500
    want_loss, want_grads = ref.value_and_grad(sizes, params, [tokens],
                                               None, step, seed_key)

    def program(dtype):
        return jax.value_and_grad(lambda p: mellum.loss_fn(
            dataclasses.replace(cfg, compute_dtype=dtype), p, tokens, None,
            key))(params)

    loss, grads = program(jnp.float32)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-6)
    assert max(_norm_gaps(grads, want_grads).values()) < 1e-5
    loss16, grads16 = program(jnp.bfloat16)
    np.testing.assert_allclose(float(loss16), float(want_loss), rtol=2e-2)
    assert max(_norm_gaps(grads16, want_grads).values()) > 2e-3


@pytest.mark.parametrize("flash", [False, True], ids=["inline", "kernels"])
def test_nothing_clean_sees_anything_noised(tiny, flash, monkeypatch):
    """Other noise, and other tokens in the noised copy altogether: the
    clean copy's hidden states do not move; the noised copy's do."""
    cfg, _, params, tokens, seed_key, _ = tiny
    if flash:
        monkeypatch.setattr(fa, "beats_inline", lambda seq_len: True)
    both = mellum.diffusion_noise(cfg, tokens, seed_key)[0]
    scrambled = both.at[:, _SEQ:].set(
        jax.random.randint(jax.random.key(9), tokens.shape, 4,
                           cfg.vocab_size, jnp.int32))
    x, y = (mellum.decode(cfg, params, ids) for ids in (both, scrambled))
    np.testing.assert_allclose(x[:, :_SEQ], y[:, :_SEQ], rtol=1e-6,
                               atol=1e-6)
    assert float(jnp.max(jnp.abs(x[:, _SEQ:] - y[:, _SEQ:]))) > 1e-2
    # and a noised position sees its own block of the noised copy only:
    # tokens changed in the LAST noised block leave every other position
    last = both.at[:, -4:].set(5)
    z = mellum.decode(cfg, params, last)
    np.testing.assert_allclose(x[:, :-4], z[:, :-4], rtol=1e-6, atol=1e-6)


def test_both_copies_stand_at_the_same_positions(tiny):
    """The rotary tables are made for L and read twice: the same tokens in
    both copies of a one-block row (a block sees itself either way) give
    the same hidden states in both."""
    cfg, _, params, _, _, _ = tiny
    ids = jax.random.randint(jax.random.key(5), (1, 4), 4, cfg.vocab_size,
                             jnp.int32)
    x = mellum.decode(cfg, params, jnp.concatenate([ids, ids], axis=1))
    np.testing.assert_allclose(x[:, :4], x[:, 4:], rtol=1e-6, atol=1e-6)
    cos, sin = mellum._twice_rope_tables(cfg, mellum.FULL, 16)
    np.testing.assert_array_equal(cos[:8], cos[8:])
    np.testing.assert_array_equal(
        cos[:8], mellum._rope_tables(cfg, mellum.FULL, 8)[0])
    assert mellum.rope_inv_freq(cfg, mellum.FULL)[1] == 1.0     # no YaRN


def test_what_the_halves_keep_is_counted_over_both_copies(monkeypatch):
    """A row of L tokens is 2 L positions through every layer: the room
    the halves' kept products get is reckoned over them, and a memory that
    has room for one layer's q, k and v at 2 L keeps one."""
    cfg = mellum.sdar_tiny()
    tokens = jnp.zeros((2, _SEQ), jnp.int32)
    both = mellum.diffusion_noise(cfg, tokens, jax.random.key(0))[0]
    assert both.shape == (2, 2 * _SEQ)
    one_layer = mellum.in_projections_bytes(cfg, 0, both.size)
    assert one_layer == both.size * (4 + 2 + 2) * 16 * 2
    rest = mellum.STEP_ROWS_A_TOKEN * both.size * cfg.hidden_size * 2
    memory = (16 * (rest + one_layer + one_layer // 2) // 15 + 16, 0)
    room = mellum.keep_room(cfg, both.size, memory)
    assert one_layer <= room < 2 * one_layer
    assert mellum.first_halves_kept(cfg, both.size, room) == (True, False)
    monkeypatch.setattr(mellum, "_device_memory", lambda mesh: memory)
    kept = lambda: getattr(metrics.get(                   # noqa: E731
        "rsdl_lm_proj_kept_total", {"kind": mellum.FULL}), "value", 0)
    before = kept()
    jax.eval_shape(lambda p: mellum.decode(cfg, p, both),
                   mellum.init(cfg, jax.random.key(0)))
    assert kept() - before == 1
    assert metrics.get("rsdl_lm_mlp_keep_room_bytes").value == room


# -- the four shares against the whole layer ----------------------------------


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The test that ties the share to the model: at ``sdar_tiny``'s
    sizes the routed sums of the four chips that share a layer, each over
    its own two of the router's eight experts through the program's expert
    layer, add up to what the reference gives for the whole layer, every
    expert held (nothing else of the MLP half is computed on every chip
    alike: SDAR has no shared expert)."""
    cfg = mellum.sdar_tiny()
    hidden, width = cfg.hidden_size, cfg.expert_width
    experts, held, top_k = cfg.num_experts, cfg.experts_held[1], cfg.top_k
    assert (experts, held, top_k) == (8, 2, 2)
    keys = jax.random.split(jax.random.key(11), 5)
    x = jax.random.normal(keys[0], (64, hidden))
    p = {"router": jax.random.normal(keys[1], (hidden, experts))}
    gate, up = (0.3 * jax.random.normal(k, (experts, hidden, width))
                for k in keys[2:4])
    down = 0.3 * jax.random.normal(keys[4], (experts, width, hidden))
    sizes = {"num_experts_per_tok": top_k, "norm_topk_prob": True}
    whole = ref.routed(sizes, x, p, 0, (gate, up, down))
    shares = []
    for chip in range(experts // held):
        mine = slice(chip * held, (chip + 1) * held)
        shares.append(moe.moe(x, p["router"], gate[mine], up[mine],
                              down[mine], (chip * held, held), top_k, 8))
        # the reference's share is the program's
        np.testing.assert_allclose(
            shares[-1], ref.routed(sizes, x, p, chip * held,
                                   (gate[mine], up[mine], down[mine])),
            rtol=1e-4, atol=1e-5)
    assert float(jnp.max(jnp.abs(shares[0]))) > 0
    np.testing.assert_allclose(sum(shares), whole, rtol=1e-4, atol=1e-5)
    ids, _ = moe.route(x @ p["router"], top_k)
    assert sorted(np.unique(np.asarray(ids) // held)) == list(range(4))


# -- configurations -----------------------------------------------------------


def test_the_share_holds_the_published_widths():
    cfg = mellum.sdar_30b_a3b_ep8_share()
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim) == (2048, 32, 4, 128)
    assert (cfg.num_experts, cfg.experts_held, cfg.top_k,
            cfg.expert_width) == (128, (0, 16), 8, 768)
    assert cfg.layer_types == 6 * (mellum.FULL,)
    assert cfg.mlp_layer_types is None and not cfg.shared_expert_width
    assert cfg.vocab_size == 19_072 == 149 * 128 == 152_576 // 8
    assert (cfg.diffusion_block, cfg.mask_token_id,
            cfg.diffusion_eps) == (4, 3, 1e-3)
    assert cfg.qk_norm and cfg.yarn is None and not cfg.tie_embeddings
    assert cfg.rope_theta == 1e6 and not cfg.router_trains
    shapes = jax.eval_shape(lambda k: mellum.init(cfg, k),
                            jax.random.key(0))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert count == 645_950_976             # 10.34 GB at 16 bytes each
    assert 16 * count < 0.7e9 * 16


def test_the_mask_tokens_row_is_seeded_small():
    cfg = mellum.sdar_tiny()
    embed = mellum.init(cfg, jax.random.key(0))["embed"]
    assert float(jnp.std(embed[cfg.mask_token_id])) < 3 * mellum.MASK_ROW_STD
    assert 0.8 < float(jnp.std(embed[4:])) < 1.2
    assert ref.MASK_ROW_STD == mellum.MASK_ROW_STD == 1e-4
    sizes = _sizes(cfg)
    assert float(jnp.std(ref.init_params(sizes, jax.random.key(0))[
        "embed"][cfg.mask_token_id])) < 3 * ref.MASK_ROW_STD


@pytest.mark.parametrize("change, said", [
    (dict(layer_types=(mellum.FULL, mellum.SLIDING)), "full attention only"),
    (dict(layer_types=(mellum.FULL, mellum.CONV)), "full attention only"),
    (dict(layer_types=(mellum.MAMBA, mellum.FULL), mamba_heads=4),
     "full attention only"),
    (dict(diffusion_eps=0.0), "probability above 0"),
], ids=["window", "convolution", "scan", "eps"])
def test_a_configuration_the_mask_is_not_defined_for_is_refused(change,
                                                                said):
    cfg = dataclasses.replace(mellum.sdar_tiny(), **change)
    with pytest.raises(ValueError, match=said):
        mellum.decode(cfg, {}, jnp.zeros((1, 8), jnp.int32))


def test_differential_attention_under_the_mask_is_refused():
    cfg = dataclasses.replace(mellum.phi4flash_tiny(), diffusion_block=4,
                              layer_types=6 * (mellum.FULL,))
    with pytest.raises(ValueError, match="full attention only"):
        mellum.decode(cfg, {}, jnp.zeros((1, 8), jnp.int32))


# -- the q and k heads placed by ops/rope.py's kernels ---------------------------


def test_where_the_chip_would_the_kernels_place_the_heads(placings,
                                                          monkeypatch):
    """Heads of 128 as the cell's, normed and rotated at a doubled row's
    positions: with ``ops.rope.on_tpu`` true the two kernels (interpreted)
    place every layer's q and k, and the loss and every gradient are the
    XLA passes' to float32's rounding."""
    cfg = dataclasses.replace(mellum.sdar_tiny(), compute_dtype=jnp.float32,
                              head_dim=128)
    params = _seeded(_sizes(cfg), jax.random.key(3))
    tokens = jax.random.randint(jax.random.key(4), (2, _SEQ), 4,
                                cfg.vocab_size, jnp.int32)
    step = jax.value_and_grad(lambda p: mellum.loss_fn(
        cfg, p, tokens, None, jax.random.key(7)))
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
    assert float(jnp.max(jnp.abs(grads["layer_0"]["q_layernorm"]))) > 0


# -- names in the registry, in the step's counters and in a compiled step -----


def test_a_trace_counts_the_mask_and_its_tiles(tiny, monkeypatch):
    cfg, _, params, tokens, seed_key, _ = tiny

    def count(kind):
        metric = metrics.get("rsdl_lm_attention_total",
                             {"kind": kind, "values": "same"})
        return 0 if metric is None else metric.value

    before = {kind: count(kind) for kind in ("inline", "diffusion", "full")}
    trace = lambda: jax.make_jaxpr(lambda p: mellum.loss_fn(  # noqa: E731
        cfg, p, tokens, None, seed_key))(params)
    trace()
    assert count("inline") - before["inline"] == cfg.num_layers
    monkeypatch.setattr(fa, "beats_inline", lambda seq_len: True)
    trace()
    assert count("diffusion") - before["diffusion"] == cfg.num_layers
    assert count("full") == before["full"]
    # 32 tokens in tiles of 32: a tile a copy's side, three of four live
    for direction in ("forward", "backward"):
        read = lambda name: metrics.get(                 # noqa: E731
            name, {"direction": direction}).value
        assert read("rsdl_lm_attention_tiles_visited") == 3
        assert read("rsdl_lm_attention_tiles_compared") == 3
        assert read("rsdl_lm_attention_tile_pairs") == 3 * 32 * 32
        assert read("rsdl_lm_attention_live_pairs") == 32 * 32 + 4 * 32


def test_the_noise_rides_out_of_the_step_and_its_scope_reaches_it():
    """Through ``SpmdTrainer``: the step takes ``(step, seed_key)`` as
    arguments, compiles once, and each step's ``lm_noise`` (masked
    positions, the sum of their weights) reaches the ring; the compiled
    step's text names the draw's operations under ``rsdl.lm.noise``."""
    from chipbench import xplane
    from ray_shuffling_data_loader_tpu.workloads import mellum_lm
    cfg = mellum.sdar_tiny()
    tokens = jax.random.randint(jax.random.key(1), (2, _SEQ), 4,
                                cfg.vocab_size, jnp.int32)
    label = jnp.zeros((2,), jnp.int32)
    tracing.reset_step_stats()
    trainer = trainer_mod.SpmdTrainer(
        mesh_mod.make_mesh(num_devices=1), mellum_lm.make_loss(cfg),
        mellum.init(cfg, jax.random.key(0)), optax.adam(1e-4))
    key = jax.random.key(6)
    losses = [float(trainer.train_step([tokens], label, np.int32(i), key))
              for i in range(3)]
    trainer.block_until_ready()
    assert trainer.step_fn._cache_size() == 1
    assert len(set(losses)) == 3, "each step draws its own noise"
    entries = tracing.step_stats()
    assert [e["step"] for e in entries] == [0, 1, 2]
    for step, entry in enumerate(entries):
        (row,) = entry["stats"]["lm_noise"]
        _, masked, weights = mellum.diffusion_noise(
            cfg, tokens, jax.random.fold_in(key, step))
        assert row["masked"] == int(masked.sum())
        np.testing.assert_allclose(
            row["weight_sum"], float(jnp.sum(jnp.where(masked, weights, 0))),
            rtol=1e-5)
        assert len(entry["stats"]["moe_walk"]) == cfg.num_layers
    assert metrics.get("rsdl_lm_noise_masked_positions").value \
        == entries[-1]["stats"]["lm_noise"][0]["masked"]
    names = xplane.hlo_op_names(trainer.step_fn.lower(
        trainer.params, trainer.opt_state, [tokens], label, np.int32(0),
        key).compile().as_text())
    assert mellum.NOISE_SCOPE == "rsdl.lm.noise"
    assert any(xplane.under_scope(n, mellum.NOISE_SCOPE)
               for n in names.values())
    # and the passes between the products and the kernels under theirs
    assert (mellum.NORM_SCOPE, mellum.ROPE_SCOPE) == ("rsdl.lm.norm",
                                                      "rsdl.lm.rope")
    for scope in (mellum.NORM_SCOPE, mellum.ROPE_SCOPE):
        assert any(xplane.under_scope(n, scope) for n in names.values())
    # a next-token configuration's loss takes the same call and draws
    # nothing
    plain = mellum_lm.make_loss(mellum.mellum_tiny())
    assert float(plain(mellum.init(mellum.mellum_tiny(), jax.random.key(0)),
                       [tokens], label, np.int32(0), key)) > 0


# -- the older configurations' programs ---------------------------------------

# sha256 (first 16 hex digits) of the text of ``jax.make_jaxpr`` of a train
# step (``make_train_step`` over ``loss_fn`` and Adam, two rows of 32
# tokens), addresses and source paths taken out, at PR 46's commit
# (cce7e01): ``diffusion_block`` 0 leaves every older program as it was,
# with XLA's inline attention and with the blocked kernels (interpreted),
# whose walk this PR rewrote. A PR that means to change one of these
# programs puts the new digest here (/root/scratch/digests.py's way:
# ``jax.clear_caches()``, then ``str(jax.make_jaxpr(step)(...))``).
_PARENT_PROGRAMS = {
    ("mellum_tiny", "inline"): "902ed7b410107205",
    ("laguna_tiny", "inline"): "bef26c60b4c8392f",
    ("granite_tiny", "inline"): "35c4d69d13bfdc3f",
    ("phi4flash_tiny", "inline"): "f7044936b57f8fe2",
    ("lfm2_tiny", "inline"): "65554b596c490cce",
    ("mellum_tiny", "kernels"): "f2ed2d6d911cfa02",
    ("laguna_tiny", "kernels"): "7d6df7f51d2d8676",
    ("granite_tiny", "kernels"): "95bae15b5070fab2",
    ("phi4flash_tiny", "kernels"): "70e34be82913c73d",
    ("lfm2_tiny", "kernels"): "4c4930093f3d76fd",
}


@pytest.mark.parametrize("builder, attention", sorted(_PARENT_PROGRAMS))
def test_the_older_configurations_trace_to_the_parents_programs(
        builder, attention, monkeypatch):
    if attention == "kernels":
        monkeypatch.setattr(fa, "beats_inline", lambda seq_len: True)
    cfg = getattr(mellum, builder)()
    assert cfg.diffusion_block == 0
    optimizer = optax.adam(1e-4)
    params = jax.eval_shape(lambda k: mellum.init(cfg, k), jax.random.key(0))
    step = trainer_mod.make_train_step(
        functools.partial(mellum.loss_fn, cfg), optimizer)
    jax.clear_caches()
    text = str(jax.make_jaxpr(step)(
        params, jax.eval_shape(optimizer.init, params),
        jax.ShapeDtypeStruct((2, 32), jnp.int32)))
    text = re.sub(r" at (/root/\S+|0x[0-9a-f]+)", "", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == _PARENT_PROGRAMS[builder, attention]


@pytest.mark.parametrize("builder", ["granite_tiny", "phi4flash_tiny"])
def test_a_decoder_without_positions_places_nothing(builder, placings,
                                                    monkeypatch):
    """No rotary, no norm on the heads: with ``ops.rope.on_tpu`` true as
    on the chip the step traces to the parent's program all the same and
    counts no placing of either kind."""
    monkeypatch.setattr(rope, "on_tpu", lambda: True)
    test_the_older_configurations_trace_to_the_parents_programs(
        builder, "inline", monkeypatch)
    assert placings() == {"vmem": 0, "xla": 0}
