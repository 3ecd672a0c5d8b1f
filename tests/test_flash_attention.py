"""Pallas flash attention vs full attention (interpreter mode on CPU)."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_shuffling_data_loader_tpu.models import bert
from ray_shuffling_data_loader_tpu.ops import flash_attention as fa
from ray_shuffling_data_loader_tpu.ops import ring_attention as ra

B, H, S, D = 2, 4, 64, 16


def _qkv(rng, s=S, dtype=jnp.float32):
    return tuple(jnp.asarray(rng.standard_normal((B, H, s, D)), dtype)
                 for _ in range(3))


def test_flash_matches_full(rng):
    q, k, v = _qkv(rng)
    got = fa.flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)
    want = ra._full_attention(q, k, v, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_flash_with_bias(rng):
    q, k, v = _qkv(rng)
    mask = jnp.asarray(rng.integers(0, 2, (B, S)))
    bias = jnp.where(mask[:, None, None, :] > 0, 0.0, ra.NEG_INF).astype(
        jnp.float32)
    got = fa.flash_attention(q, k, v, bias, block_q=16, block_k=16,
                             interpret=True)
    want = ra._full_attention(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_flash_odd_sequence_autoshrinks_blocks(rng):
    q, k, v = _qkv(rng, s=48)  # 48 not divisible by default 128
    got = fa.flash_attention(q, k, v, interpret=True)
    want = ra._full_attention(q, k, v, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_flash_gradients_match(rng):
    q, k, v = _qkv(rng)
    mask = jnp.asarray(rng.integers(0, 2, (B, S)))
    bias = jnp.where(mask[:, None, None, :] > 0, 0.0, ra.NEG_INF).astype(
        jnp.float32)

    def flash_loss(q, k, v, bias):
        return jnp.sum(fa.flash_attention(q, k, v, bias, 16, 16, True) ** 2)

    def full_loss(q, k, v, bias):
        return jnp.sum(ra._full_attention(q, k, v, bias) ** 2)

    g_flash = jax.grad(flash_loss, argnums=(0, 1, 2, 3))(q, k, v, bias)
    g_full = jax.grad(full_loss, argnums=(0, 1, 2, 3))(q, k, v, bias)
    for gf, gr in zip(g_flash, g_full):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=1e-4, atol=1e-4)


def test_flash_bf16_inputs(rng):
    q, k, v = _qkv(rng, dtype=jnp.bfloat16)
    got = fa.flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)
    want = ra._full_attention(q, k, v, None)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(want, dtype=np.float32),
                               rtol=2e-2, atol=2e-2)


def test_bert_with_flash_attention(rng):
    config = bert.BertConfig(vocab_size=128, hidden_dim=32, num_layers=1,
                             num_heads=4, ffn_dim=64, max_seq_len=S,
                             compute_dtype=jnp.float32)
    params = bert.init(config, jax.random.key(0))
    token_ids = jnp.asarray(rng.integers(0, 128, (B, S)), jnp.int32)
    attention_mask = jnp.asarray(rng.integers(0, 2, (B, S)), jnp.int32)
    attention_fn = fa.make_flash_attention_fn(block_q=16, block_k=16)
    want = bert.apply(config, params, token_ids, attention_mask)
    got = bert.apply(config, params, token_ids, attention_mask,
                     attention_fn=attention_fn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_bert_flash_train_step_under_jit(rng):
    """loss+grads through the flash kernel under jit stay finite/close."""
    config = bert.BertConfig(vocab_size=64, hidden_dim=32, num_layers=1,
                             num_heads=4, ffn_dim=64, max_seq_len=S,
                             compute_dtype=jnp.float32)
    params = bert.init(config, jax.random.key(1))
    token_ids = jnp.asarray(rng.integers(0, 64, (B, S)), jnp.int32)
    targets = jnp.where(jnp.asarray(rng.random((B, S)) < 0.15),
                        token_ids, bert.IGNORE_ID)
    attention_fn = fa.make_flash_attention_fn(block_q=16, block_k=16)

    @jax.jit
    def flash_step(p):
        return jax.value_and_grad(
            lambda p_: bert.loss_fn(config, p_, token_ids, targets,
                                    attention_fn=attention_fn))(p)

    loss_flash, grads_flash = flash_step(params)
    loss_full, grads_full = jax.value_and_grad(
        lambda p_: bert.loss_fn(config, p_, token_ids, targets))(params)
    np.testing.assert_allclose(float(loss_flash), float(loss_full),
                               rtol=1e-4)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-4),
        grads_flash, grads_full)


@pytest.mark.parametrize("seq,preferred,expect", [(64, 128, 64),
                                                  (64, 16, 16),
                                                  (48, 32, 24),
                                                  (7, 128, 7)])
def test_pick_block(seq, preferred, expect):
    assert fa._pick_block(seq, preferred) == expect


def test_rejects_non_keyside_bias(rng):
    """A full (.., S, S) bias (e.g. a causal mask) must fail loudly, not
    silently read row 0 for every query."""
    q, k, v = _qkv(rng)
    pos = jnp.arange(S)
    causal = ra.causal_bias(pos, pos)  # (1, 1, S, S)
    with pytest.raises(ValueError, match="key-side"):
        fa.flash_attention(q, k, v, causal, 16, 16, True)


@pytest.mark.parametrize("sq,sk,block_q,block_k,exp", [
    (512, 512, 128, 128, (128, 128, 512, 512)),     # aligned, no padding
    (127, 127, 128, 128, (128, 128, 128, 128)),     # prime S -> pad up
    (48, 48, 16, 16, (16, 128, 48, 128)),           # small S, K padded
    # 520 = 8*65: the largest 8-aligned divisor (104) beats padding to
    # a multiple of the preferred 128 (640 rows -> 520 rows).
    (520, 200, 128, 128, (104, 128, 520, 256)),
    # 768 with 512-preferred blocks must shrink to 384, not pad to 1024
    # (fixed-512 blocks added ~33% masked FLOPs here).
    (768, 768, 512, 512, (384, 384, 768, 768)),
])
def test_tpu_block_plan_is_tile_aligned(sq, sk, block_q, block_k, exp):
    bq, bk, sq_pad, sk_pad = fa._plan(sq, sk, block_q, block_k,
                                      interpret=False)
    assert (bq, bk, sq_pad, sk_pad) == exp
    assert bq % 8 == 0 and bk % 128 == 0
    assert sq_pad % bq == 0 and sk_pad % bk == 0


def test_prep_bias_masks_padded_keys(rng):
    bias = jnp.zeros((2, 1, 1, 48), jnp.float32)
    padded = fa._prep_bias(bias, 2, 48, 128)
    assert padded.shape == (2, 1, 1, 128)
    assert float(padded[..., :48].max()) == 0.0
    assert float(padded[..., 48:].max()) == fa._MASK
    # no bias + no padding -> stays None (fast path)
    assert fa._prep_bias(None, 2, 48, 48) is None
    # no bias + padding -> synthetic mask bias
    synth = fa._prep_bias(None, 2, 48, 128)
    assert synth is not None and float(synth[..., 48:].max()) == fa._MASK


@pytest.mark.parametrize("seq,preferred,align,exp", [
    (768, 512, 8, 384),     # largest aligned divisor wins over padding
    (520, 512, 8, 104),     # 104 >= floor: no padding needed
    (1016, 512, 8, 512),    # 8*127: only degenerate divisors -> pad w/ cap
    (2032, 512, 8, 512),    # 16*127: 16 < floor -> pad w/ cap
    (768, 512, 128, 384),
    (200, 512, 128, 256),   # cap clamped to round_up(seq, align)
])
def test_pick_aligned_block_floor(seq, preferred, align, exp):
    assert fa._pick_aligned_block(seq, preferred, align) == exp


def test_auto_attention_fn_dispatch(monkeypatch):
    """The rule a model chooses by (``beats_inline``): off the chip never
    (the kernels would run interpreted), on it from the sequence length
    PR 31 measured the crossover at, head width 64."""
    assert not fa.beats_inline(4096)  # tests pin the cpu backend
    assert fa.FLASH_MIN_SEQ_LEN == 192
    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    assert fa.beats_inline(fa.FLASH_MIN_SEQ_LEN)
    assert not fa.beats_inline(fa.FLASH_MIN_SEQ_LEN - 1)
    fn = fa.make_flash_attention_fn(interpret=True)
    assert callable(fn)


# -- PR 31: operands in their own dtype, the one-block kernels, the model's
# -- choice ---------------------------------------------------------------


def _f32_attention(qkv, bias, heads):
    """The float32 answer: ``bert._inline_attention`` on float32."""
    return bert._inline_attention(qkv.astype(jnp.float32), bias, heads)


@pytest.mark.parametrize("seq", [512, 200])
def test_flash_bf16_operands_against_the_inline_path(rng, seq):
    """One row, two heads of 64 (a full 128-lane block of the fused
    projection), bf16: the kernels' loss and gradients (q, k, v and a
    key-side bias) are the inline attention's to bf16's resolution, and
    no further from the float32 answer than the inline path is."""
    heads, d = 2, 64
    qkv = jnp.asarray(rng.standard_normal((1, seq, 3 * heads * d)),
                      jnp.bfloat16)
    keep = rng.integers(0, 4, (1, seq)) > 0
    bias = jnp.where(jnp.asarray(keep)[:, None, None, :], 0.0,
                     -1e9).astype(jnp.float32)
    weight = jnp.asarray(rng.standard_normal((1, seq, heads * d)),
                         jnp.float32)
    assert fa._packs(qkv, heads) == 2

    def graded(attend):
        return jax.value_and_grad(
            lambda x, b: jnp.sum(attend(x, b, heads).astype(jnp.float32)
                                 * weight) / seq, argnums=(0, 1))(qkv, bias)

    flash_loss, flash_grads = graded(bert._flash_attention)
    inline_loss, inline_grads = graded(bert._inline_attention)
    exact_loss, exact_grads = graded(_f32_attention)
    assert flash_grads[0].dtype == jnp.bfloat16

    def gap(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))

    assert abs(float(flash_loss) - float(inline_loss)) < 2e-3
    assert abs(float(flash_loss) - float(exact_loss)) <= max(
        1e-3, 2 * abs(float(inline_loss) - float(exact_loss)))
    for got, inline, exact in zip(flash_grads, inline_grads, exact_grads):
        scale = float(jnp.max(jnp.abs(exact)))
        assert gap(got, inline) < 2e-2 * scale
        assert gap(got, exact) <= max(1e-2 * scale, 1.5 * gap(inline, exact))


@pytest.fixture(params=["fused", "split"])
def blocked_kernels(request, monkeypatch):
    """The blocked family's backward, both ways: the one kernel, which
    every shape of these tests fits, and the dq + dk/dv pair that a
    sequence too long for VMEM takes."""
    if request.param == "split":
        monkeypatch.setattr(fa, "_fused_fits", lambda *sizes: False)
    return request.param


@pytest.mark.parametrize("lse_covers", ["these_keys", "more_keys"])
def test_one_block_backward_equals_the_blocked_backward(rng, lse_covers,
                                                        blocked_kernels):
    """The same inputs through the one-block backward kernel and through
    the blocked family's (blocks of 16) give the same gradients, also
    with a global lse over keys this call does not hold (a ring hop)."""
    q, k, v = _qkv(rng)
    more_k, more_v = _qkv(rng)[:2]
    bias = jnp.where(jnp.asarray(rng.integers(0, 2, (B, S)))[:, None, None, :]
                     > 0, 0.0, ra.NEG_INF).astype(jnp.float32)
    do = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    if lse_covers == "these_keys":
        out, lse = fa.flash_forward(q, k, v, bias, interpret=True)
    else:
        all_k = jnp.concatenate([k, more_k], axis=2)
        all_v = jnp.concatenate([v, more_v], axis=2)
        all_bias = jnp.concatenate([bias, jnp.zeros_like(bias)], axis=3)
        out, lse = fa.flash_forward(q, all_k, all_v, all_bias,
                                    interpret=True)
    assert fa._one_block(S, S, fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K)
    one = fa.flash_backward(q, k, v, bias, out, lse, do, interpret=True)
    two = fa.flash_backward(q, k, v, bias, out, lse, do, 16, 16, True)
    for got, want in zip(one, two):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def _attention_counts():
    from ray_shuffling_data_loader_tpu.runtime import metrics
    counts = {}
    for kind in ("flash", "inline"):
        traced = metrics.get("rsdl_bert_attention_total", {"kind": kind})
        counts[kind] = 0 if traced is None else traced.value
    return counts


def _two_head_bert(seq, layers=2, dtype=jnp.float32):
    config = bert.BertConfig(vocab_size=64, hidden_dim=128, num_layers=layers,
                             num_heads=2, ffn_dim=64, max_seq_len=seq,
                             compute_dtype=dtype)
    return config, bert.init(config, jax.random.key(0))


def _pallas_eqns(jaxpr):
    """The ``pallas_call`` equations of ``jaxpr`` and the programs it
    calls, a kernel's own body left out."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        else:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _pallas_eqns(sub)


def _pallas_calls(jaxpr) -> int:
    return sum(1 for _ in _pallas_eqns(jaxpr))


@pytest.mark.parametrize("chip,seq,masked,hook,want", [
    (False, 512, False, False, "inline"),   # every CPU run
    (True, 128, False, False, "inline"),    # under the crossover
    (True, 512, False, False, "flash"),
    (True, 512, True, False, "flash"),      # a key-side bias
    (True, 512, False, True, None),         # a caller's attention_fn wins
])
def test_encode_chooses_its_attention(monkeypatch, chip, seq, masked, hook,
                                      want):
    """``attention_fn=None``: the kernels on the chip from the measured
    sequence length up, XLA's inline attention elsewhere; one count a
    layer traced, by kind."""
    config, params = _two_head_bert(seq)
    monkeypatch.setattr(fa, "on_tpu", lambda: chip)
    tokens = jnp.zeros((2, seq), jnp.int32)
    mask = jnp.ones((2, seq), jnp.int32) if masked else None
    calls = []

    def attention_fn(q, k, v, bias):
        calls.append(q.shape)
        return q

    before = _attention_counts()
    jaxpr = jax.make_jaxpr(lambda p: bert.encode(
        config, p, tokens, mask, attention_fn if hook else None))(params)
    after = _attention_counts()
    traced = {kind: after[kind] - before[kind] for kind in after}
    assert traced == {kind: config.num_layers * (kind == want)
                      for kind in traced}
    assert len(calls) == config.num_layers * hook
    assert _pallas_calls(jaxpr.jaxpr) == config.num_layers * (
        want == "flash")


def _shapes_outside_kernels(jaxpr, found):
    """Every array shape in ``jaxpr`` and the programs it calls, a
    ``pallas_call``'s own body left out."""
    for eqn in jaxpr.eqns:
        for var in (*eqn.invars, *eqn.outvars):
            found.add(getattr(var.aval, "shape", ()))
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _shapes_outside_kernels(sub, found)
    return found


def test_the_losses_gradient_holds_no_scores_outside_the_kernels(
        monkeypatch):
    """With the kernels taken nothing (B, H, S, S) is left in the loss's
    gradient but inside the ``pallas_call``s; with the inline path it is."""
    seq, batch = 512, 2
    config, params = _two_head_bert(seq, layers=1, dtype=jnp.bfloat16)
    tokens = jnp.zeros((batch, seq), jnp.int32)

    def shapes():
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda p: bert.loss_fn(config, p, tokens, tokens)))(params)
        return _shapes_outside_kernels(jaxpr.jaxpr, set())

    scores = (batch, config.num_heads, seq, seq)
    assert scores in shapes()
    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    with_kernels = shapes()
    assert not [s for s in with_kernels if s[-2:] == (seq, seq)]
    assert (batch, seq, 3 * config.hidden_dim) in with_kernels


def test_flash_bert_loss_on_a_data_mesh_equals_one_device(rng, monkeypatch):
    """A step jitted over four devices tells ``loss_fn`` its mesh: the
    kernels run once a shard of the batch under ``shard_map`` and the loss
    and gradients are the one-device ones (and the inline path's)."""
    from ray_shuffling_data_loader_tpu.parallel import mesh as mesh_mod
    seq = 128
    config, params = _two_head_bert(seq, layers=1)
    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    monkeypatch.setattr(fa, "FLASH_MIN_SEQ_LEN", seq)
    tokens = jnp.asarray(rng.integers(0, 64, (8, seq)), jnp.int32)
    targets = jnp.where(jnp.asarray(rng.random((8, seq)) < 0.15), tokens,
                        bert.IGNORE_ID)
    mask = jnp.asarray(rng.integers(0, 4, (8, seq)) > 0, jnp.int32)

    def graded(mesh, attention_fn=None):
        return jax.jit(jax.value_and_grad(lambda p, t, y, m: bert.loss_fn(
            config, p, t, y, m, attention_fn, mesh)))

    before = _attention_counts()
    want_loss, want_grads = graded(None)(params, tokens, targets, mask)
    mesh = mesh_mod.make_mesh(num_devices=4)
    sharded = mesh_mod.batch_sharding(mesh)
    got_loss, got_grads = graded(mesh)(
        params, *(jax.device_put(a, sharded) for a in (tokens, targets, mask)))
    assert _attention_counts()["flash"] - before["flash"] == 2
    monkeypatch.setattr(fa, "on_tpu", lambda: False)
    inline_loss, inline_grads = graded(None)(params, tokens, targets, mask)
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-6)
    np.testing.assert_allclose(float(got_loss), float(inline_loss),
                               rtol=1e-5)
    for got, want, inline in zip(*(jax.tree.leaves(g) for g in (
            got_grads, want_grads, inline_grads))):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got), np.asarray(inline),
                                   rtol=5e-3, atol=2e-5)


# -- the blocked family's backward: one kernel, or the pair past VMEM ----------


def _plain_attention(q, k, v, bias, causal, window):
    """(B, H, S, D) float32 softmax attention as XLA has it inline, k and
    v of fewer heads (v of fewer again, at a width of its own), a key-side
    bias, the structural mask."""
    s = q.shape[2]
    k, v = (jnp.repeat(x, q.shape[1] // x.shape[1], axis=1) for x in (k, v))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if bias is not None:
        scores = scores + bias
    ahead = jnp.arange(s)[:, None] - jnp.arange(k.shape[2])[None, :]
    seen = jnp.ones(ahead.shape, bool)
    if causal:
        seen &= ahead >= 0
    if window is not None:
        seen &= ahead < window
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", weights, v)


def _packed(x):
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


@pytest.mark.parametrize(
    "seq,heads,kv_heads,causal,window,biased,block,layout,wide", [
        (64, 2, 2, True, None, False, 16, "bhsd", False),
        (64, 2, 2, True, None, False, 16, "packed", False),
        (64, 2, 2, True, 5, False, 16, "packed", False),
        (64, 2, 2, True, 32, False, 16, "bhsd", False),
        (64, 2, 2, True, 23, False, 16, "packed", False),
        (48, 8, 2, True, None, False, 16, "bhsd", False),
        (48, 8, 2, True, 20, False, 16, "packed", False),
        (64, 2, 2, False, None, True, 16, "bhsd", False),
        (1000, 2, 1, True, None, False, 256, "packed", False),
        (1000, 1, 1, False, None, True, 256, "bhsd", False),
        (64, 2, 1, True, 23, False, 16, "own_delta", False),
        (64, 8, 4, True, None, False, 16, "packed", True),
        (64, 8, 4, True, 23, False, 16, "packed", True),
        (48, 4, 2, True, None, False, 16, "bhsd", True),
        (48, 4, 2, True, 20, False, 16, "bhsd", True),
        (64, 8, 4, True, 23, False, 16, "values_in_place", True),
        (48, 8, 4, True, None, False, 16, "copied", True),
    ], ids=["causal", "causal_in_place", "window_under_a_tile",
            "window_of_two_tiles", "window_of_no_whole_tiles", "heads_8_to_2",
            "heads_8_to_2_in_place_window", "key_bias_and_dbias",
            "padded_1000_in_place", "padded_1000_key_bias",
            "the_callers_delta", "wide_values_in_place",
            "wide_values_in_place_window", "wide_values",
            "wide_values_window", "wide_values_alone_in_place",
            "wide_values_copied_head_major"])
def test_blocked_backward_is_the_float32_vjp(
        seq, heads, kv_heads, causal, window, biased, block, layout, wide,
        blocked_kernels, monkeypatch):
    """dq, dk, dv (and dbias) of the blocked family against ``jax.vjp`` of
    the inline float32 attention: under each mask, with grouped heads, a
    key bias, a length the chip pads (its plan under the interpreter),
    both layouts, with a delta the caller brings (``out`` unread), and
    with ``wide`` values: half as many heads as the keys at twice the
    width, two key heads' maps over one value head (differential
    attention's), read in place beside q and k, in place beside q and k
    copied head-major (the chip's layouts at D = 64, Dv = 128), or copied
    too. The one kernel gathers dv over a value head's query heads; the
    dq + dk/dv pair refuses what is not shaped as the keys."""
    if layout in ("values_in_place", "copied"):
        monkeypatch.setattr(
            fa, "_reads_in_place",
            lambda d, interpret: layout == "values_in_place" and d == 32)
        layout = "packed"
    if seq % block:
        plan = fa._plan
        monkeypatch.setattr(
            fa, "_plan", lambda sq, sk, bq, bk, interpret: plan(
                sq, sk, bq, bk, False))
        assert fa._plan(seq, seq, block, block, True)[3] > seq
    key = jax.random.key(seq + heads + (window or 0))
    v_heads, dv = (kv_heads // 2, 32) if wide else (kv_heads, 16)
    q, k, v, do = (jax.random.normal(jax.random.fold_in(key, i), shape)
                   for i, shape in enumerate([
                       (2, heads, seq, 16), (2, kv_heads, seq, 16),
                       (2, v_heads, seq, dv), (2, heads, seq, dv)]))
    bias = None
    if biased:
        bias = jnp.where(jax.random.bernoulli(
            jax.random.fold_in(key, 4), 0.7, (2, 1, 1, seq)), 0.0,
            ra.NEG_INF).astype(jnp.float32)
    want_out, vjp = jax.vjp(
        lambda *a: _plain_attention(*a, causal, window), q, k, v, bias)
    want = vjp(do)
    mask = fa._Mask(causal, window)
    refused = (pytest.raises(ValueError, match="the keys' width and head")
               if wide and blocked_kernels == "split"
               else contextlib.nullcontext())
    if layout == "bhsd":
        out, lse = fa._flash_forward(q, k, v, bias, block, block, True, mask)
        with refused:
            got = fa.flash_backward(q, k, v, bias, out, lse, do, block, block,
                                    True, causal, window)
    else:
        operands = [_packed(x) for x in (q, k, v)]
        values = dict(num_v_heads=v_heads) if wide else {}
        out, lse = fa.grouped_forward(*operands, heads, kv_heads, causal,
                                      window, block, block, True, **values)
        if layout == "packed":
            with refused:
                got = fa.grouped_backward(
                    *operands, out, lse, _packed(do), heads, kv_heads, causal,
                    window, block, block, True, **values)
        else:
            delta = fa._delta(do, want_out)[..., None]
            got = fa._blocked_backward(
                *operands, None, None, lse, _packed(do), mask, block, block,
                True, True, heads, kv_heads, delta=delta)[:3]
        want = [_packed(x) for x in want[:3]]
        want_out = _packed(want_out)
    np.testing.assert_allclose(out, want_out, atol=2e-5)
    if wide and blocked_kernels == "split":
        return
    assert len(got) == 3 or (got[3] is None) == (bias is None)
    for grad, plain in zip(got, want):
        if grad is not None:
            np.testing.assert_allclose(grad, plain, atol=5e-5)


def _launch(eqn):
    """(grid, each block's sizes, the scratch's shapes, conditionals in
    the kernel's body) of a ``pallas_call``."""
    mapping, body = eqn.params["grid_mapping"], eqn.params["jaxpr"]
    blocks = [tuple(getattr(size, "block_size", None)
                    for size in block.block_shape)
              for block in mapping.block_mappings]
    scratch = [var.aval.shape
               for var in body.invars[-mapping.num_scratch_operands:]]
    return (mapping.grid, blocks, scratch,
            sum(e.primitive.name == "cond" for e in body.eqns))


@pytest.mark.parametrize("which", ["forward", "backward"])
def test_values_shaped_as_the_keys_launch_what_they_always_did(which):
    """With Dv = D and no value head count the kernels' launch does not
    know that values may be wide: the program is the one an explicit
    ``num_v_heads = Hkv`` gives, every block and scratch is D wide (sizes
    written out: PR 44's launch), and the backward's body holds the six
    conditionals it had. Wide values change the values' side alone, and
    the backward opens and closes dv's scratch on a run of its own."""
    q = jnp.zeros((1, 64, 4 * 16))
    kv = jnp.zeros((1, 64, 2 * 16))
    lse = jnp.zeros((1, 4, 64, 1))

    def traced(v, out, **values):
        if which == "forward":
            return jax.make_jaxpr(lambda *a: fa.grouped_forward(
                *a, 4, 2, True, 20, 16, 16, True, **values))(q, kv, v)
        return jax.make_jaxpr(lambda *a: fa.grouped_backward(
            *a, 4, 2, True, 20, 16, 16, True, **values))(
                q, kv, v, out, lse, out)

    plain = traced(kv, q)
    assert str(plain) == str(traced(kv, q, num_v_heads=2))
    (eqn,) = _pallas_eqns(plain.jaxpr)
    row, column, whole = (None, 16, 16), (None, None, 16, 1), (None, 64, 16)
    assert _launch(eqn) == {
        "forward": ((1, 4, 4, 3), [row] * 4 + [column],
                    [(16, 1), (16, 1), (16, 16)], 4),
        "backward": ((1, 4, 4, 3), [row] * 4 + [column] + [row] * 2
                     + [whole] * 2,
                     [(16, 16), (16, 1), (64, 16), (64, 16)], 6)}[which]
    (eqn,) = _pallas_eqns(traced(jnp.zeros((1, 64, 32)),
                                 jnp.zeros((1, 64, 4 * 32)),
                                 num_v_heads=1).jaxpr)
    wide_row, wide_whole = (None, 16, 32), (None, 64, 32)
    assert _launch(eqn) == {
        "forward": ((1, 4, 4, 3), [row] * 2 + [wide_row] * 2 + [column],
                    [(16, 1), (16, 1), (16, 32)], 4),
        "backward": ((1, 4, 4, 3), [row] * 2 + [wide_row] * 2 + [column]
                     + [wide_row, row, whole, wide_whole],
                     [(16, 16), (16, 1), (64, 16), (64, 32)], 8)}[which]


def test_the_blocked_backward_is_one_kernel_where_dk_and_dv_fit(
        blocked_kernels):
    """One ``pallas_call`` in the backward's program, two past VMEM; and
    which it is follows from the shape alone."""
    q = jnp.zeros((1, 64, 4 * 16))
    kv = jnp.zeros((1, 64, 2 * 16))
    lse = jnp.zeros((1, 4, 64, 1))
    jaxpr = jax.make_jaxpr(lambda *a: fa.grouped_backward(
        *a, 4, 2, True, 20, 16, 16, True))(q, kv, kv, q, lse, q)
    assert _pallas_calls(jaxpr.jaxpr) == {"fused": 1, "split": 2}[
        blocked_kernels]
    if blocked_kernels == "fused":
        bf16, f32 = jnp.bfloat16, jnp.float32
        # mellum_train_8k's layers, and what no longer fits
        for seq, dtype, tile, kind in [
                (8192, bf16, 1024, "fused"), (8192, bf16, 512, "fused"),
                (8192, f32, 1024, "fused"), (16384, bf16, 1024, "fused"),
                (16384, f32, 1024, "split"), (32768, bf16, 1024, "split")]:
            assert fa._blocked_kind(seq, seq, 128, dtype, tile, tile,
                                    False) == kind, (seq, dtype, tile)
        # phi4flash_train_8k's layers: keys of 64 (a tile pads them to its
        # 128 lanes), values of 128, the triangle's tiles and the window's
        for tile in (1024, 512):
            assert fa._blocked_kind(8192, 8192, 64, bf16, tile, tile, False,
                                    128) == "fused"
            assert fa._fused_fits(tile, tile, 8192, 64, bf16, 128)
        assert fa._fused_fits(1024, 1024, 16384, 64, bf16, 128)
        assert not fa._fused_fits(1024, 1024, 16384, 64, f32, 128)
        assert not fa._fused_fits(1024, 1024, 16384, 128, bf16, 512)


def _backward_counts():
    from ray_shuffling_data_loader_tpu.runtime import metrics
    counts = {}
    for kind in ("one_block", "fused", "split"):
        traced = metrics.get("rsdl_attention_backward_total", {"kind": kind})
        counts[kind] = 0 if traced is None else traced.value
    return counts


def test_the_backward_counter_names_each_cells_kernel(monkeypatch):
    """Traced at the cells' own shapes (no kernel runs): every layer of
    ``mellum_train_8k`` takes the one blocked kernel, every layer of
    ``bert_train`` the one-block kernel; one count a layer."""
    from ray_shuffling_data_loader_tpu.models import mellum
    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    cfg = mellum.mellum2_ep4_share()
    q, kv = (jax.ShapeDtypeStruct((4, 8192, n * cfg.head_dim), jnp.bfloat16)
             for n in (cfg.num_heads, cfg.num_kv_heads))

    def traced(graded, *shapes):
        before = _backward_counts()
        jax.eval_shape(jax.grad(graded), *shapes)
        return {kind: count - before[kind]
                for kind, count in _backward_counts().items()}

    def decoder(q, k, v):
        return sum(mellum._attention(cfg, q, k, v, None, layer_type,
                                     cfg.num_heads).astype(
            jnp.float32).sum() for layer_type in cfg.layer_types)

    assert traced(decoder, q, kv, kv) == {
        "one_block": 0, "fused": len(cfg.layer_types), "split": 0}
    base = bert.bert_base()
    qkv = jax.ShapeDtypeStruct((32, 512, 3 * base.hidden_dim), jnp.bfloat16)

    def encoder(qkv):
        return sum(bert._attention(qkv, None, base.num_heads, None).astype(
            jnp.float32).sum() for _ in range(base.num_layers))

    assert traced(encoder, qkv) == {
        "one_block": base.num_layers, "fused": 0, "split": 0}
