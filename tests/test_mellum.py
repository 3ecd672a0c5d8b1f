"""The sparse-expert decoder (models/mellum.py), its expert layer
(ops/moe.py) and the masked, grouped attention kernels
(ops/flash_attention.py) against the plain reference
(chipbench/references/mellum.py), at tiny sizes on the CPU."""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import xplane
from chipbench.references import mellum as ref
from ray_shuffling_data_loader_tpu.models import mellum
from ray_shuffling_data_loader_tpu.ops import flash_attention as fa
from ray_shuffling_data_loader_tpu.ops import moe
from ray_shuffling_data_loader_tpu.runtime import metrics


def _sizes(cfg: mellum.MellumConfig, seq_len: int, held=None):
    """The reference's view of a program configuration."""
    first, count = cfg.experts_held if held is None else held
    yarn = cfg.yarn
    return {
        "hidden_size": cfg.hidden_size, "head_dim": cfg.head_dim,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "num_hidden_layers": cfg.num_layers,
        "layer_types": list(cfg.layer_types),
        "sliding_window": cfg.sliding_window,
        "moe_intermediate_size": cfg.expert_width,
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
                "attention_factor": yarn.attention_factor},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": cfg.rope_theta}},
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


def test_yarn_frequencies_are_the_formula_written_out():
    """Peng et al. 2023 as the source's ``rope_parameters`` state it: below
    ``beta_slow`` turns over the original context a frequency is
    interpolated (divided by ``factor``), above ``beta_fast`` it is kept,
    between the two a linear ramp over the dimensions."""
    cfg = mellum.mellum2_ep4_share()
    dim, base, factor, original = 128, 500_000.0, 16.0, 8192
    want = []
    for i in range(dim // 2):
        plain = base ** (-2.0 * i / dim)

        def where(turns):
            return dim * math.log(original / (turns * 2 * math.pi)) / (
                2 * math.log(base))

        low, high = math.floor(where(32.0)), math.ceil(where(1.0))
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(plain / factor * ramp + plain * (1.0 - ramp))
    assert 0 < low < high < dim // 2      # all three regimes are present
    sizes = _sizes(cfg, 8192)
    for got, scale in (mellum.rope_inv_freq(cfg, mellum.FULL),
                       ref.inv_freq(sizes, ref.FULL)):
        np.testing.assert_allclose(got, want, rtol=1e-5)
        assert scale == 1.2772588722239782
        assert abs(scale - (0.1 * math.log(factor) + 1.0)) < 1e-12
    for got, scale in (mellum.rope_inv_freq(cfg, mellum.SLIDING),
                       ref.inv_freq(sizes, ref.SLIDING)):
        np.testing.assert_allclose(
            got, [base ** (-2.0 * i / dim) for i in range(dim // 2)],
            rtol=1e-5)
        assert scale == 1.0


# -- the expert layer ----------------------------------------------------------------

_HIDDEN, _WIDTH, _EXPERTS, _TOP_K = 16, 8, 8, 2


def _expert_weights(key, held):
    ks = jax.random.split(key, 3)
    return {"gate": jax.random.normal(ks[0], (held, _HIDDEN, _WIDTH)),
            "up": jax.random.normal(ks[1], (held, _HIDDEN, _WIDTH)),
            "down": jax.random.normal(ks[2], (held, _WIDTH, _HIDDEN))}


def _moe_sizes(first, count):
    return {"num_experts": count, "experts_held_first": first,
            "num_experts_per_tok": _TOP_K}


def _favouring(experts):
    """A router under which every token of positive features picks
    ``experts`` (and only them, where there are ``_TOP_K``)."""
    router = jnp.zeros((_HIDDEN, _EXPERTS))
    for rank, e in enumerate(experts):
        router = router.at[:, e].set(1.0 + 0.1 * rank)
    return router


def _tied(experts):
    router = jnp.zeros((_HIDDEN, _EXPERTS))
    return router.at[:, jnp.asarray(experts)].set(1.0)


@pytest.mark.parametrize("held,router", [
    ((2, 2), _favouring([2, 3])),         # every pick is held
    ((2, 2), _favouring([0, 7])),         # none is
    ((2, 2), _favouring([7, 2])),         # one held expert takes every token
    ((2, 2), _tied([2, 3, 4])),           # ties: three equal, two picked
    ((2, 2), None),                       # any routing, a share held
    ((0, 8), None),                       # any routing, all held
], ids=["every_pick_held", "none_held", "one_expert_takes_all", "ties",
        "random_share", "random_all_held"])
def test_the_expert_layer_is_exact_for_any_routing(held, router):
    """No capacity, no dropped token: output and all five gradients equal
    the plain loop of dense products under masks, walked in tiles of 8
    rows (several tiles an expert, the last part empty)."""
    key = jax.random.key(held[1])
    x = jnp.abs(jax.random.normal(key, (24, _HIDDEN)))
    if router is None:
        router = jax.random.normal(jax.random.fold_in(key, 1),
                                   (_HIDDEN, _EXPERTS))
    weights = _expert_weights(jax.random.fold_in(key, 2), held[1])
    mix = jax.random.normal(jax.random.fold_in(key, 3), x.shape)

    def program(x, router, w):
        return moe.moe(x, router, w["gate"], w["up"], w["down"], held,
                       _TOP_K, 8)

    def plain(x, router, w):
        return ref._experts(_moe_sizes(*held), x, dict(w, router=router))

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


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Eight experts over four chips, two each: the shares' outputs
    summed are what the uncut reference gives for the whole layer (the
    router, which every chip computes alike, counted once)."""
    key = jax.random.key(7)
    x = jax.random.normal(key, (24, _HIDDEN))
    router = jax.random.normal(jax.random.fold_in(key, 1),
                               (_HIDDEN, _EXPERTS))
    whole = _expert_weights(jax.random.fold_in(key, 2), _EXPERTS)
    total = sum(
        moe.moe(x, router, *(whole[n][first:first + 2]
                             for n in ("gate", "up", "down")),
                (first, 2), _TOP_K, 8)
        for first in range(0, _EXPERTS, 2))
    uncut = ref._experts(_moe_sizes(0, _EXPERTS), x,
                         dict(whole, router=router))
    np.testing.assert_allclose(total, uncut, rtol=2e-5, atol=2e-5)


# -- the decoder against the reference ----------------------------------------------

_SEQ = 32


@pytest.fixture(scope="module")
def tiny_f32():
    # one window layer and one full one: both kinds, half the compiling
    cfg = mellum.MellumConfig(**{
        **mellum.mellum_tiny().__dict__, "compute_dtype": jnp.float32,
        "layer_types": (mellum.SLIDING, mellum.FULL)})
    sizes = _sizes(cfg, _SEQ)
    params = ref.init_params(sizes, jax.random.key(3))
    tokens = jax.random.randint(jax.random.key(4), (2, _SEQ), 4,
                                cfg.vocab_size, jnp.int32)
    return cfg, sizes, params, tokens, ref.value_and_grad(
        sizes, params, [tokens], None, 0)


def test_loss_and_every_gradient_match_the_reference(tiny_f32, monkeypatch):
    """Seeded weights from the reference's own initialiser, the program's
    tree: the loss and every leaf's gradient, the Pallas kernels
    (interpreted, the one-kernel backward) under the model's own
    custom_vjp. XLA's inline attention, which a CPU run takes, is held to the same reference by
    the cell's rehearsal (tests/chipbench/test_chipbench_mellum.py)."""
    cfg, sizes, params, tokens, (want_loss, want_grads) = tiny_f32
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
    assert ref.param_count(sizes) == sum(
        x.size for x in jax.tree.leaves(params))


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


def test_rotary_positions_are_the_rotate_half_formula():
    """The rotation as a product with a signed permutation equals
    ``x cos + concat(-x2, x1) sin``, exactly."""
    cfg = mellum.mellum_tiny()
    x = jax.random.normal(jax.random.key(0), (2, 8, 4 * cfg.head_dim))
    cos, sin = mellum._rope_tables(cfg, mellum.FULL, 8)
    heads = x.reshape(2, 8, 4, cfg.head_dim)
    half = cfg.head_dim // 2
    want = (heads * cos[:, None] + jnp.concatenate(
        [-heads[..., half:], heads[..., :half]], -1) * sin[:, None])
    np.testing.assert_allclose(mellum._rope(x, 4, cos, sin),
                               want.reshape(x.shape), rtol=1e-6)


def test_scopes_and_counters_reach_the_compiled_step(tiny_f32):
    """The three scopes name operations of the compiled gradient as
    written (forward and backward, not ``jvp(scope)``), never a ``while``
    (a reader that sums under a scope counts each operation once), and
    the trace counted what it compiled."""
    cfg, _, params, tokens, _ = tiny_f32

    def count(name, **labels):
        metric = metrics.get(name, labels or None)
        return 0 if metric is None else metric.value

    before = {kind: count("rsdl_lm_attention_total", kind=kind)
              for kind in ("inline", "window", "full")}
    layers_before = count("rsdl_moe_layer_total", kind="share")
    text = jax.jit(jax.grad(lambda p, t: mellum.loss_fn(cfg, p, t))).lower(
        params, tokens).compile().as_text()
    names = xplane.hlo_op_names(text)
    for scope in (mellum.ATTENTION_SCOPE, mellum.MOE_SCOPE,
                  mellum.HEAD_SCOPE):
        under = {name: op_name for name, op_name in names.items()
                 if xplane.under_scope(op_name, scope)}
        assert any(name.startswith(("dot", "fusion")) for name in under), (
            scope, sorted(under))
        assert not any(name.startswith("while") for name in under), scope
        assert not re.search(rf"jvp\({re.escape(scope)}\)", text)
    # CPU: every layer's attention is XLA's inline one
    assert count("rsdl_lm_attention_total", kind="inline") == (
        before["inline"] + cfg.num_layers)
    assert count("rsdl_lm_attention_total", kind="window") == before["window"]
    assert count("rsdl_moe_layer_total", kind="share") == (
        layers_before + cfg.num_layers)
    assert count("rsdl_moe_experts_held") == 2
    assert count("rsdl_moe_experts_routed") == 8
    assert count("rsdl_moe_top_k") == 2


def test_the_attention_counter_tells_window_from_full(monkeypatch):
    """On the chip the kernels take both kinds; a window that covers the
    row is the triangle."""
    monkeypatch.setattr(fa, "beats_inline", lambda seq_len: True)
    cfg = mellum.mellum_tiny()

    def count(kind):
        metric = metrics.get("rsdl_lm_attention_total", {"kind": kind})
        return 0 if metric is None else metric.value

    before = count("window"), count("full")
    jax.eval_shape(lambda p, t: mellum.loss_fn(cfg, p, t),
                   mellum.init(cfg, jax.random.key(0)),
                   jnp.zeros((1, 16), jnp.int32))
    assert (count("window"), count("full")) == (before[0] + 3, before[1] + 1)
    jax.eval_shape(lambda p, t: mellum.loss_fn(cfg, p, t),
                   mellum.init(cfg, jax.random.key(0)),
                   jnp.zeros((1, 8), jnp.int32))
    assert (count("window"), count("full")) == (before[0] + 3, before[1] + 5)


def test_a_mesh_of_several_devices_is_refused():
    """Nothing stands in for the absent chips: the decoder runs one chip's
    share and says so when handed more."""
    from ray_shuffling_data_loader_tpu.parallel import mesh as mesh_mod
    cfg = mellum.mellum_tiny()
    assert mesh_mod.EXPERT_AXIS == "expert"
    with pytest.raises(NotImplementedError, match="exchange"):
        mellum.loss_fn(cfg, mellum.init(cfg, jax.random.key(0)),
                       jnp.zeros((2, 16), jnp.int32),
                       mesh_mod.make_mesh(num_devices=2))
