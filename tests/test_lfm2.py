"""The decoder (models/mellum.py) at its LFM2 configuration against the
plain reference (chipbench/references/lfm2.py) at ``lfm2_tiny`` on the CPU:
gated short convolutions (ops/sconv.py), attention over normed q and k
heads under plain rotary, a
router scored by sigmoids and picked under a selection bias
(ops/moe.py:route), the tied head over a slice of the vocabulary; the
eight shares of an expert layer against the uncut layer; and the names the
new layers leave in the registry and in a compiled step."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chipbench.references import lfm2 as ref
from ray_shuffling_data_loader_tpu.models import mellum
from ray_shuffling_data_loader_tpu.ops import flash_attention as fa
from ray_shuffling_data_loader_tpu.ops import moe, rope, sconv
from ray_shuffling_data_loader_tpu.parallel import mesh as mesh_mod
from ray_shuffling_data_loader_tpu.parallel import trainer as trainer_mod
from ray_shuffling_data_loader_tpu.runtime import metric_names, metrics

_SEQ = 32


def _sizes(cfg: mellum.DecoderConfig, seq_len: int = _SEQ):
    """The reference's view of a program configuration."""
    dense = [cfg.mlp_type(i) == mellum.DENSE for i in range(cfg.num_layers)]
    return {
        "hidden_size": cfg.hidden_size, "vocab_size": cfg.vocab_size,
        "intermediate_size": cfg.intermediate_size,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "num_hidden_layers": cfg.num_layers,
        "layer_types": list(cfg.layer_types),
        "num_dense_layers": sum(dense), "conv_L_cache": cfg.conv_taps,
        "num_experts_routed": cfg.num_experts,
        "experts_held_first": cfg.experts_held[0],
        "num_experts": cfg.experts_held[1],
        "num_experts_per_tok": cfg.top_k,
        "moe_intermediate_size": cfg.expert_width,
        "routed_scaling_factor": cfg.routed_scale, "norm_topk_prob": True,
        "rope_parameters": {"rope_type": "default",
                            "rope_theta": cfg.rope_theta},
        "tie_word_embeddings": cfg.tie_embeddings,
        "router_trains": cfg.router_trains,
        "norm_eps": cfg.rms_norm_eps, "seq_len": seq_len,
        "published": {"num_hidden_layers": cfg.published_layers},
    }


def _tiny_f32() -> mellum.DecoderConfig:
    return dataclasses.replace(mellum.lfm2_tiny(), compute_dtype=jnp.float32)


def _seeded(cfg, sizes, key):
    """The reference's seeded weights with every norm's scale moved off 1
    (the q and k heads' too): at 1 a norm after the rotation would equal
    the one before it."""
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
    cfg = _tiny_f32()
    sizes = _sizes(cfg)
    params = _seeded(cfg, sizes, jax.random.key(3))
    tokens = jax.random.randint(jax.random.key(4), (2, _SEQ), 4,
                                cfg.vocab_size, jnp.int32)
    return cfg, sizes, params, tokens, ref.value_and_grad(
        sizes, params, [tokens], None, 0)


def _assert_matches(loss, grads, want_loss, want_grads):
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), want in zip(flat, jax.tree.leaves(want_grads)):
        # a leaf's values against its own largest (a norm scale's gradient
        # is 1e-5); the selection bias's is zero on both sides
        scale = max(float(jnp.max(jnp.abs(want))), 1e-30)
        np.testing.assert_allclose(
            got / scale, want / scale, rtol=2e-3, atol=2e-5,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("flash", [False, True], ids=["inline", "kernels"])
def test_loss_and_every_gradient_match_the_reference(tiny, flash,
                                                     monkeypatch):
    """Seeded weights from the reference's own initialiser, the program's
    tree: the loss and every leaf's gradient in float32, with XLA's inline
    attention and with the Pallas kernels (interpreted). 2e-3 of a leaf's
    largest value: both sides are float32 and differ by the order of
    their sums alone (the walk's tiles against a scan over the experts,
    the head's blocks); a routing that flipped a pick would move an
    expert's gradient by tens of per cent."""
    cfg, sizes, params, tokens, (want_loss, want_grads) = tiny
    assert jax.tree.structure(params) == jax.tree.structure(
        mellum.init(cfg, jax.random.key(0)))
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(
        jnp.shape, mellum.init(cfg, jax.random.key(0)))
    if flash:
        monkeypatch.setattr(fa, "beats_inline", lambda seq_len: True)
    loss, grads = jax.value_and_grad(
        lambda p: mellum.loss_fn(cfg, p, tokens))(params)
    _assert_matches(loss, grads, want_loss, want_grads)
    assert ref.param_count(sizes) == sum(
        x.size for x in jax.tree.leaves(params))
    for layer in (1, 2, 3, 4):
        for leaf in ("expert_bias", "router"):      # both held here
            assert float(jnp.max(jnp.abs(
                grads[f"layer_{layer}"][leaf]))) == 0.0


def test_a_router_that_trains_matches_the_reference_too(tiny):
    """``router_trains`` on, as a chip that held every expert would have
    it: the routers' gradients are there on both sides and agree (the
    tolerance of the test above), and every other leaf's is what it was
    with the router held: the switch stops one leaf's gradient and changes
    nothing of the forward pass."""
    cfg, sizes, params, tokens, (want_loss, held_grads) = tiny
    on = dataclasses.replace(cfg, router_trains=True)
    want_loss_on, want_grads = ref.value_and_grad(
        dict(sizes, router_trains=True), params, [tokens], None, 0)
    assert float(want_loss_on) == float(want_loss)
    loss, grads = jax.value_and_grad(
        lambda p: mellum.loss_fn(on, p, tokens))(params)
    _assert_matches(loss, grads, want_loss, want_grads)
    for layer in (1, 2, 3, 4):
        name = f"layer_{layer}"
        assert float(jnp.max(jnp.abs(grads[name]["router"]))) > 0
        assert float(jnp.max(jnp.abs(held_grads[name]["router"]))) == 0.0
        np.testing.assert_array_equal(want_grads[name]["gate"],
                                      held_grads[name]["gate"])


def test_bfloat16_compute_stays_by_the_reference(tiny):
    """The configuration's precision, bfloat16 compute on float32
    parameters: the loss to 1e-3 (bf16 carries 8 bits, 4e-3 a value; the
    mean over 62 positions of logits summed in float32 takes most of it
    out) and every leaf's gradient NORM to 10 % of the reference's, or of
    the median leaf's where a leaf's own is smaller (the comparison's own
    measure, ``check.leaf_gaps``): at 64 channels a rounded activation
    moves a router's pick for a token or two of 64, and a held expert of
    the eight sees sixteen tokens, so one pick is a sixteenth of its
    leaves' gradient; the chip's limits, at 2,048 channels and 16,384
    tokens, are set from chip readings (the configuration file)."""
    from chipbench import check
    cfg, _, params, tokens, (want_loss, want_grads) = tiny
    low = dataclasses.replace(cfg, compute_dtype=jnp.bfloat16)
    loss, grads = jax.value_and_grad(
        lambda p: mellum.loss_fn(low, p, tokens))(params)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-3)
    gap, leaf = check.worst_leaf_gap(check.leaf_norms(grads),
                                     check.leaf_norms(want_grads))
    assert gap < 0.1, (gap, leaf)


def test_the_conv_operator_from_first_principles():
    """``C * conv(B * u)`` by a loop over positions: position t reads
    ``v_(t-2) .. v_t`` under taps 0 .. 2 and zeros before the row's first
    position (so position 0 reads one product and position 1 two), no
    bias, no activation; the program's plain form, its wrapper under the
    scope (float32 and bfloat16 operands), and the reference's."""
    keys = jax.random.split(jax.random.key(5), 2)
    bcu = jax.random.normal(keys[0], (2, 9, 3 * 6))
    weight = jax.random.normal(keys[1], (3, 6))
    b, c, u = (np.asarray(part) for part in jnp.split(bcu, 3, axis=-1))
    w = np.asarray(weight)
    want = np.zeros_like(b)
    for t in range(9):
        for k in range(3):
            if t - 2 + k >= 0:
                want[:, t] += w[k] * (b * u)[:, t - 2 + k]
    want = c * want
    np.testing.assert_allclose(want[:, 0], c[:, 0] * w[2] * (b * u)[:, 0],
                               rtol=1e-6)
    np.testing.assert_allclose(
        want[:, 1], c[:, 1] * (w[1] * (b * u)[:, 0] + w[2] * (b * u)[:, 1]),
        rtol=1e-6)
    for got in (sconv.gated_conv(bcu, weight),
                sconv.causal_gated_conv(bcu, weight),
                jnp.stack([ref.gated_conv(*jnp.split(row, 3, axis=-1),
                                          weight) for row in bcu])):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # bfloat16 operands: float32 inside, rounded once on the way out
    low = sconv.causal_gated_conv(bcu.astype(jnp.bfloat16), weight)
    assert low.dtype == jnp.bfloat16
    np.testing.assert_array_equal(low, sconv.gated_conv(
        bcu.astype(jnp.bfloat16).astype(jnp.float32),
        weight).astype(jnp.bfloat16))
    # position t reads nothing after t
    later = bcu.at[:, 5:].set(7.0)
    np.testing.assert_array_equal(
        sconv.gated_conv(later, weight)[:, :5],
        sconv.gated_conv(bcu, weight)[:, :5])


# -- the operator's kernels, interpreted, against the plain form ------------------


def _gated_operands(shape, dtype, taps: int = 3, seed: int = 12):
    batch, seq, channels = shape
    keys = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(keys[0], (batch, seq, 3 * channels), dtype),
            jax.random.uniform(keys[1], (taps, channels), jnp.float32,
                               -taps ** -0.5, taps ** -0.5),
            jax.random.normal(keys[2], shape))


def _gated_with_grads(conv, bcu, weight, mix):
    """``(y, d bcu, d w)`` of ``sum(conv(bcu, w) * mix)``."""
    def loss(bcu, weight):
        y = conv(bcu, weight)
        return jnp.sum(y.astype(jnp.float32) * mix), y

    grads, y = jax.grad(loss, (0, 1), has_aux=True)(bcu, weight)
    return (y, *grads)


@pytest.fixture
def small_gated_blocks(monkeypatch):
    """Blocks of 64 positions of a width of 3 x 256 float32 (128 of
    bfloat16's), columns of 128 lanes, so that a small array is several of
    each."""
    from ray_shuffling_data_loader_tpu.ops import ssd
    monkeypatch.setattr(sconv, "_BLOCK_BYTES", 64 * 3 * 256 * 4)
    monkeypatch.setattr(ssd, "_CONV_LANES", 128)


#: (B, S, C) by what the grid and the kernel's columns are over.
_GATED_SHAPES = {"one_block": (1, 64, 256),
                 "sequence_blocks": (1, 256, 256),
                 "one_column": (1, 128, 128),
                 "batch_2": (2, 128, 256)}


@pytest.mark.parametrize("shape", list(_GATED_SHAPES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_operators_kernels_are_the_plain_one_and_autodiff(
        dtype, shape, small_gated_blocks):
    """The output, ``d (B | C | u)`` and the taps' gradient by the two
    kernels, interpreted, at three taps: float32 equal to rounding,
    bfloat16 the same values to its step (``d w`` is a float32 sum either
    way)."""
    dims = _GATED_SHAPES[shape]
    rows, lanes = sconv._block(dims[1], dims[2], dtype)
    assert lanes == 128 and rows in (64, 128) and dims[1] % rows == 0
    bcu, weight, mix = _gated_operands(dims, dtype)
    got = _gated_with_grads(sconv._gated_in_vmem, bcu, weight, mix)
    want = _gated_with_grads(sconv.gated_conv, bcu, weight, mix)
    step = {jnp.float32: 1e-6, jnp.bfloat16: 2.0 ** -8}[dtype]
    for name, g, w, tol in zip(("y", "d bcu", "d w"), got, want,
                               (step, step, 2e-6)):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.abs(g - w).max() <= tol * np.abs(w).max(), name


@pytest.mark.parametrize("taps", [1, 2, 8])
def test_the_operators_kernels_take_other_taps(taps, small_gated_blocks):
    bcu, weight, mix = _gated_operands((1, 128, 128), jnp.float32, taps)
    for g, w in zip(
            _gated_with_grads(sconv._gated_in_vmem, bcu, weight, mix),
            _gated_with_grads(sconv.gated_conv, bcu, weight, mix)):
        np.testing.assert_allclose(g, w, rtol=0, atol=2e-6 * float(
            jnp.max(jnp.abs(w))))


def test_a_gated_impulse_crosses_the_seam_between_blocks_and_comes_back(
        small_gated_blocks):
    """``B * u`` of one in a block's last two rows reaches the next
    block's first two positions and no further; the cotangent of those
    positions comes back across the seam to the rows that fed them; the
    first positions of a row see zeros, whatever stands in the tile the
    first block fetches in that place."""
    bcu, weight, _ = _gated_operands((2, 128, 128), jnp.float32)
    b, c, u = jnp.split(bcu, 3, axis=-1)
    quiet = jnp.concatenate([jnp.zeros_like(b).at[:, 62:64].set(1.0),
                             jnp.ones_like(c),
                             jnp.ones_like(u)], axis=-1)
    got = sconv._gated_in_vmem(quiet, weight)
    np.testing.assert_allclose(got, sconv.gated_conv(quiet, weight),
                               rtol=1e-6, atol=1e-7)
    for t in (64, 65):
        assert float(jnp.min(jnp.max(jnp.abs(got[:, t]), axis=-1))) > 1e-3
    assert float(jnp.max(jnp.abs(got[:, 66:]))) == 0.0
    # a loss that sees the second block alone
    mix = jnp.zeros((2, 128, 128)).at[:, 64:66].set(1.0)

    def d_b(conv):
        return jax.grad(lambda a: jnp.sum(conv(a, weight) * mix))(
            quiet)[..., :128]

    back = d_b(sconv._gated_in_vmem)
    np.testing.assert_allclose(back, d_b(sconv.gated_conv), rtol=1e-5,
                               atol=1e-7)
    for t in (62, 63):
        assert float(jnp.min(jnp.max(jnp.abs(back[:, t]), axis=-1))) > 1e-3
    assert float(jnp.max(jnp.abs(back[:, :62]))) == 0.0
    assert float(jnp.max(jnp.abs(back[:, 66:]))) == 0.0
    # the first two positions: one product, then two
    y = sconv._gated_in_vmem(bcu, weight)
    v = b * u
    np.testing.assert_allclose(y[:, 0], c[:, 0] * weight[2] * v[:, 0],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        y[:, 1], c[:, 1] * (weight[1] * v[:, 0] + weight[2] * v[:, 1]),
        rtol=1e-5, atol=1e-6)
    loud = bcu.at[:, 8:16].multiply(100.0)
    np.testing.assert_array_equal(
        sconv._gated_in_vmem(loud, weight)[:, :5], y[:, :5])


def test_which_shapes_the_operators_kernels_take(monkeypatch):
    """On the chip, bfloat16 or float32, channels of whole lanes, a
    sequence of whole blocks, at most 8 taps; XLA's pad and slices
    otherwise, and everywhere off the chip."""
    cell = (8192, 2048, 3, jnp.bfloat16)       # lfm2_train_8k's B | C | u
    assert not sconv.convs_in_vmem(*cell)      # the CPU
    assert sconv.conv_takes(*cell)
    assert sconv._block(8192, 2048, jnp.bfloat16) == (256, 512)
    monkeypatch.setattr(sconv, "on_tpu", lambda: True)
    assert sconv.convs_in_vmem(*cell)
    assert sconv.convs_in_vmem(64, 128, 8, jnp.float32)
    assert not sconv.convs_in_vmem(32, 64, 3, jnp.bfloat16)  # lfm2_tiny
    assert not sconv.convs_in_vmem(8192, 2048 + 64, 3, jnp.bfloat16)
    assert not sconv.convs_in_vmem(8192, 2048, 9, jnp.bfloat16)
    assert not sconv.convs_in_vmem(8192, 2048, 3, jnp.float16)


# -- the router: sigmoid scores picked under a selection bias ---------------------


def _logits_and_bias(tokens=64, experts=16, seed=7):
    keys = jax.random.split(jax.random.key(seed), 2)
    return (jax.random.normal(keys[0], (tokens, experts)),
            0.5 * jax.random.normal(keys[1], (experts,)))


def test_the_picks_follow_the_bias_and_the_weights_do_not():
    logits, bias = _logits_and_bias()
    ids, weights = moe.route(logits, 4, 1.0, bias)
    scores = jax.nn.sigmoid(logits)
    _, want_ids = jax.lax.top_k(scores + bias, 4)
    np.testing.assert_array_equal(ids, want_ids)
    # the bias moves picks: without it other experts are picked
    _, plain_ids = jax.lax.top_k(scores, 4)
    assert np.any(np.sort(np.asarray(ids)) != np.sort(np.asarray(plain_ids)))
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    total = picked.sum(axis=-1, keepdims=True)
    np.testing.assert_allclose(weights, picked / (total + 1e-6), rtol=1e-6)
    # a token's weights sum to sum s / (sum s + 1e-6), not to 1, and a
    # weight that took the bias in would be another number
    np.testing.assert_allclose(weights.sum(axis=-1),
                               (total / (total + 1e-6))[:, 0], rtol=1e-6)
    with_bias = jnp.take_along_axis(scores + bias, ids, axis=-1)
    assert float(jnp.max(jnp.abs(
        weights - with_bias / with_bias.sum(axis=-1, keepdims=True)))) > 0.01
    # times the model's scaling factor
    np.testing.assert_allclose(moe.route(logits, 4, 2.5, bias)[1],
                               2.5 * weights, rtol=1e-6)
    # the reference routes alike
    sizes = {"num_experts_per_tok": 4, "norm_topk_prob": True,
             "routed_scaling_factor": 1.0}
    eye = {"router": jnp.eye(16), "expert_bias": bias}
    ref_ids, ref_weights = ref.route(sizes, logits, eye)
    np.testing.assert_array_equal(ref_ids, ids)
    np.testing.assert_allclose(ref_weights, weights, rtol=1e-6)


def test_the_bias_takes_no_gradient_through_the_layers_backward():
    """Through ``route`` and through the expert layer's written backward
    (``_moe_bwd`` makes the weights again with the bias): the router's
    gradient is there, the bias's is exactly zero."""
    logits, bias = _logits_and_bias()
    d_bias = jax.grad(lambda b: jnp.sum(
        moe.route(logits, 4, 1.0, b)[1] ** 2))(bias)
    assert float(jnp.max(jnp.abs(d_bias))) == 0.0
    keys = jax.random.split(jax.random.key(8), 5)
    x = jax.random.normal(keys[0], (64, 32))
    router = jax.random.normal(keys[1], (32, 16))
    gate, up = (0.2 * jax.random.normal(k, (4, 32, 8)) for k in keys[2:4])
    down = 0.2 * jax.random.normal(keys[4], (4, 8, 32))

    def loss(router, bias):
        return jnp.sum(moe.moe(x, router, gate, up, down, (4, 4), 4, 8, 1.0,
                               bias) ** 2)

    d_router, d_bias = jax.grad(loss, (0, 1))(router, bias)
    assert float(jnp.max(jnp.abs(d_router))) > 0
    assert d_bias.shape == bias.shape
    assert float(jnp.max(jnp.abs(d_bias))) == 0.0


@pytest.mark.parametrize("trains", [False, True],
                         ids=["router_held", "router_trains"])
def test_adam_leaves_the_bias_where_it_was(tiny, trains):
    """``expert_bias`` is a leaf of the parameter tree under
    ``stop_gradient``: through ``SpmdTrainer`` and plain Adam, with no
    balancing update, its moments stay zero and its values stay what they
    were, bit for bit. The router beside it moves where ``router_trains``
    and is left alike where not (a share's configuration: ``lfm2_tiny``'s
    own), while the layer's other leaves move either way."""
    cfg, _, params, tokens, _ = tiny
    assert not cfg.router_trains and cfg.expert_bias_speed == 1e-4
    cfg = dataclasses.replace(cfg, router_trains=trains,
                              expert_bias_speed=0.0)
    trainer = trainer_mod.SpmdTrainer(
        mesh_mod.make_mesh(num_devices=1),
        lambda p, features, label: mellum.loss_fn(cfg, p, features),
        jax.tree.map(jnp.copy, params), optax.adam(1e-2))
    for _ in range(3):
        trainer.train_step(tokens, jnp.zeros((2,), jnp.int32))
    for layer in (1, 2, 3, 4):
        name = f"layer_{layer}"
        np.testing.assert_array_equal(trainer.params[name]["expert_bias"],
                                      params[name]["expert_bias"])
        adam = trainer.opt_state[0]
        assert float(jnp.max(jnp.abs(adam.mu[name]["expert_bias"]))) == 0.0
        assert float(jnp.max(jnp.abs(adam.nu[name]["expert_bias"]))) == 0.0
        moved = float(jnp.max(jnp.abs(trainer.params[name]["router"]
                                      - params[name]["router"])))
        assert (moved > 0) == trains
        assert (float(jnp.max(jnp.abs(adam.nu[name]["router"]))) > 0) \
            == trains
        assert float(jnp.max(jnp.abs(trainer.params[name]["gate"]
                                     - params[name]["gate"]))) > 0


def test_the_balancing_update_follows_the_loads(tiny):
    """With ``expert_bias_speed`` the train step moves each sparse layer's
    bias after Adam's update by the rule: by the speed times the share of
    the mean load that the expert fell short of it, so down for an expert
    that more than the mean of the step's tokens picked and up for one
    that fewer did; the moves of a layer sum to nothing (the picks are
    ``top_k`` a token); Adam's moments of the leaf stay zero. The loads
    are of all the router's experts, held here or not, under the bias the
    step began with; and the reference's step, which moves its caller's
    tree in place, moves the leaf alike."""
    cfg, sizes, params, tokens, _ = tiny
    speed = 0.25          # far over the seeded values: a move shows whole
    cfg = dataclasses.replace(cfg, expert_bias_speed=speed)
    trainer = trainer_mod.SpmdTrainer(
        mesh_mod.make_mesh(num_devices=1),
        lambda p, features, label: mellum.loss_fn(cfg, p, features),
        jax.tree.map(jnp.copy, params), optax.adam(1e-2))
    trainer.train_step(tokens, jnp.zeros((2,), jnp.int32))
    moved = {k: dict(v) if isinstance(v, dict) else v
             for k, v in params.items()}
    ref.value_and_grad(dict(sizes, expert_bias_update_speed=speed), moved,
                       [tokens], None, 0)
    even = tokens.size * cfg.top_k / cfg.num_experts
    x = params["embed"][tokens]
    for layer in range(cfg.num_layers):
        name = f"layer_{layer}"
        p = params[name]
        if layer:
            picked = sum(ref._layer_loads(ref._Sizes(sizes), layer, p, row)
                         for row in x)
            assert int(picked.sum()) == tokens.size * cfg.top_k
            assert int(picked.max()) > even > int(picked.min())
            move = speed * (1.0 - picked / even)
            assert abs(float(move.sum())) < 1e-6
            for got in (trainer.params[name]["expert_bias"],
                        moved[name]["expert_bias"]):
                np.testing.assert_allclose(got - p["expert_bias"], move,
                                           rtol=1e-5, atol=1e-7)
            adam = trainer.opt_state[0]
            assert float(jnp.max(jnp.abs(
                adam.mu[name]["expert_bias"]))) == 0.0
            # the caller's own tree is as it was
            assert params[name]["expert_bias"] is p["expert_bias"]
        x = jnp.stack([ref.layer(ref._Sizes(sizes), layer, p, row)
                       for row in x])


def test_a_leafs_move_leaves_the_loss_beside_the_stats():
    """``tracing.leaf_move`` through ``make_train_step``: the move is
    added to the named leaf after the optimizer's update (which a zero
    gradient leaves at nothing), every other leaf is the optimizer's
    alone, and the step's counters go on as they did."""
    from ray_shuffling_data_loader_tpu.utils import tracing

    def loss(params, x):
        tracing.leaf_move(("inner", "buffer"),
                          2.0 * jnp.ones_like(params["inner"]["buffer"]))
        tracing.step_stat("diff_attention", jnp.float32(7.0), layer=0)
        return jnp.sum((params["w"] * x) ** 2) + 0.0 * jnp.sum(
            jax.lax.stop_gradient(params["inner"]["buffer"]))

    params = {"w": jnp.ones((3,)), "inner": {"buffer": jnp.arange(3.0)}}
    opt = optax.sgd(0.1)
    new, _, _, stats = jax.jit(trainer_mod.make_train_step(loss, opt))(
        params, opt.init(params), jnp.ones((3,)))
    np.testing.assert_array_equal(new["inner"]["buffer"],
                                  jnp.arange(3.0) + 2.0)
    np.testing.assert_allclose(new["w"], 0.8 * jnp.ones((3,)))
    assert [key[0] for key in stats] == ["diff_attention"]
    # outside a train step the move is dropped and the loss is the loss
    assert float(loss(params, jnp.ones((3,)))) == 3.0


def _softmax_route_as_it_was(logits, top_k: int, scale: float = 1.0):
    """``ops/moe.py:route`` of the parent commit, letter for letter."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top, ids = jax.lax.top_k(probs, top_k)
    return (ids.astype(jnp.int32),
            scale * top / top.sum(axis=-1, keepdims=True))


@pytest.mark.parametrize("scale", [1.0, 2.5])
def test_softmax_routing_is_bit_for_bit_what_it_was(scale):
    """Without a bias ``route`` is the softmax router of ``mellum_train_8k``
    and ``laguna_train_8k``: the same picks and weights to the last bit,
    and the same program (the jaxprs' text)."""
    logits, _ = _logits_and_bias(tokens=128, experts=64, seed=9)
    for dtype in (jnp.float32, jnp.bfloat16):
        got = moe.route(logits.astype(dtype), 8, scale)
        want = _softmax_route_as_it_was(logits.astype(dtype), 8, scale)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(g, w)
    assert str(jax.make_jaxpr(lambda x: moe.route(x, 8, scale))(logits)) \
        == str(jax.make_jaxpr(
            lambda x: _softmax_route_as_it_was(x, 8, scale))(logits))


def test_the_seeded_bias_moves_picks_and_no_load_by_much():
    """What the configuration file says of the seeded ``expert_bias``,
    N(0, 0.002) beside scores of a router N(0, 0.02) over a normed stream
    of 2,048 (a deviation of 0.19): it changes a pick of about one token
    in seventeen, and every expert's load stays within a fifth of even."""
    keys = jax.random.split(jax.random.key(10), 3)
    x = jax.random.normal(keys[0], (4096, 2048))
    scores = jax.nn.sigmoid(x @ (0.02 * jax.random.normal(keys[1],
                                                          (2048, 64))))
    assert ref.EXPERT_BIAS_STD == mellum.EXPERT_BIAS_STD == 0.002
    bias = ref.EXPERT_BIAS_STD * jax.random.normal(keys[2], (64,))
    with_bias = np.sort(np.asarray(jax.lax.top_k(scores + bias, 4)[1]))
    without = np.sort(np.asarray(jax.lax.top_k(scores, 4)[1]))
    moved = np.mean(np.any(with_bias != without, axis=-1))
    assert 0.03 < moved < 0.10, moved
    load = np.bincount(with_bias.reshape(-1), minlength=64) / (4096 * 4 / 64)
    assert 0.8 < load.min() and load.max() < 1.2, (load.min(), load.max())
    assert 0.15 < float(jnp.std(scores)) < 0.25
    # the program's initialiser draws it as wide
    drawn = mellum.init(mellum.lfm2_tiny(), jax.random.key(1))
    assert 0.001 < float(jnp.std(jnp.concatenate(
        [drawn[f"layer_{i}"]["expert_bias"] for i in (1, 2, 3, 4)]))) < 0.004


# -- the eight shares against the whole layer --------------------------------------


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The test that ties the share to the model: at a small size the
    routed sums of the eight chips that share a layer, each over its own
    two of the router's sixteen experts through the program's expert
    layer, add up to what the reference gives for the whole layer, every
    expert held (nothing else of a sparse layer's MLP half is computed on
    every chip alike: LFM2 has no shared expert)."""
    hidden, width, experts, held, top_k = 32, 8, 16, 2, 4
    keys = jax.random.split(jax.random.key(11), 6)
    x = jax.random.normal(keys[0], (48, hidden))
    p = {"router": jax.random.normal(keys[1], (hidden, experts)),
         "expert_bias": 0.3 * jax.random.normal(keys[2], (experts,))}
    gate, up = (0.3 * jax.random.normal(k, (experts, hidden, width))
                for k in keys[3:5])
    down = 0.3 * jax.random.normal(keys[5], (experts, width, hidden))
    sizes = {"num_experts_per_tok": top_k, "norm_topk_prob": True,
             "routed_scaling_factor": 1.0}
    whole = ref.routed(sizes, x, p, 0, (gate, up, down))
    shares = []
    for chip in range(experts // held):
        mine = slice(chip * held, (chip + 1) * held)
        shares.append(moe.moe(
            x, p["router"], gate[mine], up[mine], down[mine],
            (chip * held, held), top_k, 8, 1.0, p["expert_bias"]))
        # the reference's share is the program's
        np.testing.assert_allclose(
            shares[-1], ref.routed(sizes, x, p, chip * held,
                                   (gate[mine], up[mine], down[mine])),
            rtol=1e-4, atol=1e-5)
    assert float(jnp.max(jnp.abs(shares[0]))) > 0
    np.testing.assert_allclose(sum(shares), whole, rtol=1e-4, atol=1e-5)
    # every token's four picks are each held by exactly one chip
    ids, _ = moe.route(x @ p["router"], top_k, 1.0, p["expert_bias"])
    assert sorted(np.unique(np.asarray(ids) // held)) == list(range(8))


# -- attention over normed heads under plain rotary -----------------------------


def test_the_heads_are_normed_before_they_are_rotated(tiny):
    """Layer 1's attention half against the same written out: RMSNorm
    over each q head and each k head under its one scale of ``head_dim``,
    THEN the rotation. Under scales off 1 the other order is another
    model (a rotation mixes lanes d and d + D / 2, which the scale then
    weighs differently)."""
    cfg, _, params, tokens, _ = tiny
    lp = params["layer_1"]
    x = 0.5 * jax.random.normal(jax.random.key(12), (2, _SEQ,
                                                     cfg.hidden_size))
    got = mellum._attention_half(cfg, 1, x, lp)
    cos, sin = mellum._rope_tables(cfg, mellum.FULL, _SEQ)
    a = mellum._rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)

    def heads_of(w, count, scale, norm_first: bool):
        h = (a @ w).reshape(2, _SEQ, count, cfg.head_dim)

        def normed(h):
            return h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True)
                                     + cfg.rms_norm_eps) * scale

        def rotated(h):
            half = cfg.head_dim // 2
            turned = jnp.concatenate([-h[..., half:], h[..., :half]], -1)
            return h * cos[:, None] + turned * sin[:, None]

        h = rotated(normed(h)) if norm_first else normed(rotated(h))
        return h.reshape(2, _SEQ, -1)

    def half(norm_first: bool):
        q = heads_of(lp["wq"], cfg.num_heads, lp["q_layernorm"], norm_first)
        k = heads_of(lp["wk"], cfg.num_kv_heads, lp["k_layernorm"],
                     norm_first)
        out = mellum._inline_attention(q, k, a @ lp["wv"], None,
                                       cfg.num_heads, cfg.num_kv_heads, None)
        return x + out @ lp["wo"]

    np.testing.assert_allclose(got, half(True), rtol=1e-4, atol=1e-6)
    assert float(jnp.max(jnp.abs(got - half(False)))) > 1e-3 * float(
        jnp.max(jnp.abs(got - x)))
    # plain rotary in a full layer: theta 1e6 over the whole head, cos and
    # sin unscaled
    assert cfg.yarn is None and cfg.rope_theta == 1e6
    inv_freq, scale = mellum.rope_inv_freq(cfg, mellum.FULL)
    np.testing.assert_allclose(
        inv_freq, 1e6 ** (-jnp.arange(0, cfg.head_dim, 2) / cfg.head_dim),
        rtol=1e-6)
    assert scale == 1.0 and mellum.rotated_dims(cfg, mellum.FULL) == 16
    # and YaRN's where a configuration has it, as before
    assert mellum.rope_inv_freq(mellum.laguna_tiny(), mellum.FULL)[1] > 1.0


def test_where_the_chip_would_the_kernels_place_the_heads(placings,
                                                          monkeypatch):
    """Heads of 64 as the cell's, two a register, normed and rotated: with
    ``ops.rope.on_tpu`` true the two kernels (interpreted) place the
    attention layer's q and k, and the loss and every gradient are the
    XLA passes' to float32's rounding."""
    cfg = dataclasses.replace(_tiny_f32(), head_dim=64)
    params = _seeded(cfg, _sizes(cfg), jax.random.key(3))
    tokens = jax.random.randint(jax.random.key(4), (2, _SEQ), 4,
                                cfg.vocab_size, jnp.int32)
    attention = sum(kind == mellum.FULL for kind in cfg.layer_types)
    step = jax.value_and_grad(lambda p: mellum.loss_fn(cfg, p, tokens))
    want_loss, want_grads = step(params)
    assert placings() == {"vmem": 0, "xla": 2 * attention}
    monkeypatch.setattr(rope, "on_tpu", lambda: True)
    loss, grads = step(params)
    assert attention == 1
    assert placings() == {"vmem": 2 * attention, "xla": 2 * attention}
    _assert_matches(loss, grads, want_loss, want_grads)
    assert metric_names.METRIC_NAMES["rsdl_lm_place_total"] == (
        "counter", ("kind",))


# -- names in the registry and in a compiled step ----------------------------------


def test_a_trace_counts_the_new_layers_by_what_computes_them(tiny):
    cfg, _, params, tokens, _ = tiny

    def count(name, **labels):
        if name == "rsdl_lm_attention_total":
            labels["values"] = "same"   # values shaped as the keys
        metric = metrics.get(name, labels)
        return 0 if metric is None else metric.value

    before = {key: count(*key[:1], kind=key[1]) for key in (
        ("rsdl_lm_conv_total", "xla"), ("rsdl_lm_conv_total", "vmem"),
        ("rsdl_moe_router_total", "sigmoid_bias"),
        ("rsdl_moe_router_total", "softmax"),
        ("rsdl_lm_attention_total", "inline"))}
    jax.make_jaxpr(lambda p: mellum.loss_fn(cfg, p, tokens))(params)

    def gained(name, kind):
        return count(name, kind=kind) - before[(name, kind)]

    # the gated convolutions count with Mamba's, and are XLA's everywhere
    assert gained("rsdl_lm_conv_total", "xla") == 4
    assert gained("rsdl_lm_conv_total", "vmem") == 0
    assert gained("rsdl_moe_router_total", "sigmoid_bias") == 4
    assert gained("rsdl_moe_router_total", "softmax") == 0
    assert gained("rsdl_lm_attention_total", "inline") == 1
    softmax = mellum.mellum_tiny()
    jax.make_jaxpr(lambda p, t: mellum.loss_fn(softmax, p, t))(
        mellum.init(softmax, jax.random.key(0)), tokens)
    assert gained("rsdl_moe_router_total", "softmax") == 4
    assert gained("rsdl_moe_router_total", "sigmoid_bias") == 4
    for name in ("rsdl_lm_conv_total", "rsdl_moe_router_total"):
        assert metric_names.METRIC_NAMES[name] == ("counter", ("kind",))


def test_the_scope_reaches_the_compiled_step():
    """``rsdl.lm.sconv`` names the gates' and the convolution's
    operations in the step's text, forward and backward; the operator's
    two projections stay under ``rsdl.lm.proj``."""
    from chipbench import xplane
    cfg = mellum.lfm2_tiny()
    params = mellum.init(cfg, jax.random.key(0))
    tokens = jnp.zeros((2, _SEQ), jnp.int32)
    step = jax.jit(trainer_mod.make_train_step(
        functools.partial(mellum.loss_fn, cfg), optax.adam(1e-3)))
    names = xplane.hlo_op_names(step.lower(
        params, optax.adam(1e-3).init(params), tokens).compile().as_text())
    assert mellum.SCONV_SCOPE == sconv.SCOPE == "rsdl.lm.sconv"
    under = [n for n in names.values()
             if xplane.under_scope(n, mellum.SCONV_SCOPE)]
    assert any("transpose" in n for n in under), "the backward's"
    assert any("transpose" not in n for n in under), "the forward's"
    assert not any("dot_general" in n for n in under)
    assert any(xplane.under_scope(n, mellum.PROJ_SCOPE)
               for n in names.values())


def test_a_configuration_the_decoder_does_not_know_is_refused():
    tokens = jnp.zeros((1, 8), jnp.int32)
    cfg = dataclasses.replace(mellum.lfm2_tiny(), conv_taps=0)
    with pytest.raises(ValueError, match="conv_taps"):
        mellum.decode(cfg, mellum.init(mellum.lfm2_tiny(),
                                       jax.random.key(0)), tokens)
    both = dataclasses.replace(mellum.phi4flash_tiny(), qk_norm=True)
    with pytest.raises(ValueError, match="head norms"):
        mellum._checked(both)
