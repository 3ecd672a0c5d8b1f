"""Tests for ops/embedding.py: all lookup modes agree bit-for-bit, grads
match, and the DLRM flagship is invariant to the lookup strategy."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_shuffling_data_loader_tpu.models import dlrm
from ray_shuffling_data_loader_tpu.ops import embedding

MODES = ["take", "one_hot", "pallas"]


@pytest.fixture
def table_and_indices(rng):
    table = jnp.asarray(rng.standard_normal((96, 32)), jnp.float32)
    indices = jnp.asarray(rng.integers(0, 96, 64), jnp.int32)
    return table, indices


@pytest.mark.parametrize("mode", MODES)
def test_lookup_matches_take_f32(table_and_indices, mode):
    table, indices = table_and_indices
    want = np.asarray(table)[np.asarray(indices)]
    got = embedding.lookup(table, indices, jnp.float32, mode=mode)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("mode", MODES)
def test_lookup_matches_take_bf16(table_and_indices, mode):
    """A one-hot row selects exactly one table row, so even bf16 results
    are bit-identical to the gather."""
    table, indices = table_and_indices
    want = np.asarray(embedding.take_lookup(table, indices, jnp.bfloat16))
    got = np.asarray(embedding.lookup(table, indices, jnp.bfloat16,
                                      mode=mode))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("mode", MODES)
def test_lookup_clips_out_of_range(table_and_indices, mode):
    table, _ = table_and_indices
    indices = jnp.asarray([-5, 0, 95, 96, 1000], jnp.int32)
    got = np.asarray(embedding.lookup(table, indices, jnp.float32,
                                      mode=mode))
    want = np.asarray(table)[[0, 0, 95, 95, 95]]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("mode", MODES)
def test_lookup_grad_is_scatter_add(table_and_indices, mode):
    table, _ = table_and_indices
    # Repeated indices: the table grad must accumulate.
    indices = jnp.asarray([3, 3, 7, 0, 3], jnp.int32)

    def loss(t):
        out = embedding.lookup(t, indices, jnp.float32, mode=mode)
        return (out * out).sum()

    got = np.asarray(jax.grad(loss)(table))
    want = np.zeros_like(got)
    t = np.asarray(table)
    for i in np.asarray(indices):
        want[i] += 2 * t[i]
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_auto_mode_dispatch():
    small = jnp.zeros((16, 8), jnp.float32)
    large = jnp.zeros((embedding.ONE_HOT_MAX_VOCAB + 1, 8), jnp.float32)
    idx = jnp.zeros((4,), jnp.int32)
    # Both paths produce the right shape; dispatch itself is exercised by
    # jit-compiling each (one_hot would OOM-scale with the large table if
    # auto mis-dispatched, but correctness is shape/value-checked here).
    assert embedding.lookup(small, idx, jnp.float32).shape == (4, 8)
    assert embedding.lookup(large, idx, jnp.float32).shape == (4, 8)


def test_unknown_mode_raises():
    with pytest.raises(ValueError, match="unknown lookup mode"):
        embedding.lookup(jnp.zeros((4, 4)), jnp.zeros((2,), jnp.int32),
                         jnp.float32, mode="nope")


@pytest.mark.parametrize("mode", MODES + ["auto"])
def test_dlrm_forward_invariant_to_lookup_mode(rng, mode):
    base = dlrm.DLRMConfig(vocab_sizes=(40, 7, 3000), embed_dim=16,
                           top_hidden=(32,), compute_dtype=jnp.float32)
    params = dlrm.init(base, jax.random.key(0))
    sparse = jnp.asarray(
        np.stack([rng.integers(0, v, 8) for v in base.vocab_sizes], axis=1),
        jnp.int32)
    want = dlrm.apply(
        dlrm.DLRMConfig(**{**base.__dict__, "lookup_mode": "take"}),
        params, None, sparse)
    got = dlrm.apply(
        dlrm.DLRMConfig(**{**base.__dict__, "lookup_mode": mode}),
        params, None, sparse)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6)


def test_dlrm_train_step_with_pallas_lookup(rng):
    """End-to-end grad step through the Pallas kernel's custom VJP."""
    import optax
    cfg = dlrm.DLRMConfig(vocab_sizes=(50, 20), embed_dim=8,
                          top_hidden=(16,), compute_dtype=jnp.float32,
                          lookup_mode="pallas")
    params = dlrm.init(cfg, jax.random.key(0))
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)
    sparse = jnp.asarray(
        np.stack([rng.integers(0, v, 16) for v in cfg.vocab_sizes], axis=1),
        jnp.int32)
    labels = jnp.asarray(rng.random((16, 1)), jnp.float32)

    @jax.jit
    def step(params, opt_state):
        loss, grads = jax.value_and_grad(
            lambda p: dlrm.loss_fn(cfg, p, None, sparse, labels))(params)
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_auto_mode_dispatch_rules(monkeypatch):
    small_v = embedding.ONE_HOT_MAX_VOCAB
    large_v = embedding.ONE_HOT_MAX_VOCAB + 1
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert embedding._auto_mode(small_v, 128) == "one_hot"
    assert embedding._auto_mode(large_v, 128) == "take"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert embedding._auto_mode(small_v, 128) == "one_hot"
    assert embedding._auto_mode(large_v, 128) == "pallas"
    assert embedding._auto_mode(large_v, 32) == "take"  # unaligned rows


# (vocab, how the table's gradient crosses the data axis) at a global
# batch of 64: rows while the batch is below the vocab, dense from there.
EXCHANGES = [(640, "rows"), (65, "rows"), (64, "dense"), (48, "dense")]
_BATCH, _EMBED = 64, 128


def _mesh_table_indices(rng, vocab=640):
    """A replicated table, and batch-sharded indices that repeat within
    a shard and across shards and leave the table at both ends."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_shuffling_data_loader_tpu.parallel import mesh as mesh_mod
    mesh = mesh_mod.make_mesh()
    table = jax.device_put(
        jnp.asarray(rng.standard_normal((vocab, _EMBED)), jnp.float32),
        NamedSharding(mesh, P()))
    indices = rng.integers(0, vocab, _BATCH)
    per_shard = _BATCH // mesh.size
    indices[1] = indices[0]                      # within the first shard
    indices[per_shard::per_shard] = indices[0]   # once in every other shard
    indices[2], indices[-1] = -7, vocab + 1000   # clipped to rows 0, vocab-1
    indices[3], indices[-2] = 0, vocab - 1
    indices = jax.device_put(jnp.asarray(indices, jnp.int32),
                             NamedSharding(mesh, P("data")))
    return mesh, table, indices


def _value_and_table_grad(mode, mesh, weights):
    def fn(table, indices):
        out = embedding.lookup(table, indices, jnp.float32, mode=mode,
                               mesh=mesh)
        return jnp.sum(out * weights), out
    return jax.jit(jax.value_and_grad(fn, has_aux=True))


def _collectives_holding(hlo, op, rows, embed=_EMBED):
    """How many ``op``s of a compiled program's text have a result (alone
    or in a combined tuple) that holds an ``f32[rows, embed]``; XLA may
    carry a unit axis between the two."""
    import re
    results = re.findall(rf"= (.*?) {op}(?:-start)?\(", hlo)
    return sum(bool(re.search(rf"f32\[{rows},(?:1,)?{embed}\]", r))
               for r in results)


def _exchanges_counted():
    from ray_shuffling_data_loader_tpu.runtime import metrics
    counts = {}
    for kind in ("rows", "dense"):
        counter = metrics.get("rsdl_embedding_grad_exchange_total",
                              {"kind": kind})
        counts[kind] = 0 if counter is None else int(counter.value)
    return counts


@pytest.mark.parametrize("vocab", [vocab for vocab, _ in EXCHANGES])
def test_pallas_lookup_under_a_mesh_matches_take(rng, vocab):
    """Batch-sharded indices, replicated table: one gather per data shard
    under shard_map, same rows and same table gradient as XLA take on one
    device, whichever way the gradient is exchanged."""
    mesh, table, indices = _mesh_table_indices(rng, vocab)
    weights = jnp.asarray(rng.standard_normal((_BATCH, _EMBED)), jnp.float32)
    (_, got), got_grad = _value_and_table_grad(
        "pallas", mesh, weights)(table, indices)
    one = jax.devices()[0]
    (_, want), want_grad = _value_and_table_grad("take", None, weights)(
        jax.device_put(table, one), jax.device_put(indices, one))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_allclose(np.asarray(got_grad), np.asarray(want_grad),
                               atol=1e-6)
    assert got.sharding.shard_shape(got.shape) == (_BATCH // mesh.size,
                                                   _EMBED)
    assert got_grad.sharding.is_fully_replicated


@pytest.mark.parametrize("vocab,kind", EXCHANGES)
def test_mesh_lookup_gradient_exchanges_rows_below_the_vocab(
        rng, vocab, kind):
    """What crosses the data axis in the compiled gradient: the looked-up
    rows (one all-gather of the cotangent, none of the table's size) while
    the global batch is below the vocab, the dense all-reduce from there.
    The counter says which was traced."""
    mesh, table, indices = _mesh_table_indices(rng, vocab)
    weights = jnp.asarray(rng.standard_normal((_BATCH, _EMBED)), jnp.float32)
    before = _exchanges_counted()
    hlo = _value_and_table_grad("pallas", mesh, weights).lower(
        table, indices).compile().as_text()
    after = _exchanges_counted()
    assert {k: after[k] - before[k] for k in after} == {
        "rows": int(kind == "rows"), "dense": int(kind == "dense")}

    if kind == "rows":
        assert _collectives_holding(hlo, "all-reduce", vocab) == 0
        assert _collectives_holding(hlo, "all-gather", _BATCH) == 1
        assert "rsdl.embedding.grad_exchange" in hlo
    else:
        assert _collectives_holding(hlo, "all-reduce", vocab) == 1
        assert _collectives_holding(hlo, "all-gather", _BATCH) == 0


@pytest.mark.parametrize("vocab_sizes", [(40, 300, 3000), (3000, 65)])
def test_dlrm_trainer_steps_over_the_mesh_match_one_device(rng, vocab_sizes):
    """Three SpmdTrainer steps of a small DLRM through the Pallas lookup
    over the 8-device mesh (vocabs above the global batch exchange rows,
    40 the dense gradient): the losses and parameters of the same three
    steps on one device over the same global batches."""
    import optax

    from ray_shuffling_data_loader_tpu.parallel import mesh as mesh_mod
    from ray_shuffling_data_loader_tpu.parallel.trainer import (
        SpmdTrainer, batch_shardings)
    cfg = dlrm.DLRMConfig(vocab_sizes=vocab_sizes, embed_dim=16,
                          top_hidden=(32, 16), compute_dtype=jnp.float32,
                          lookup_mode="pallas")
    # On the host: each trainer donates its own device copy.
    params = jax.device_get(dlrm.init(cfg, jax.random.key(1)))
    batches = [
        (np.stack([rng.integers(0, v, _BATCH) for v in vocab_sizes],
                  axis=1).astype(np.int32),
         rng.random((_BATCH, 1)).astype(np.float32)) for _ in range(3)]

    def run(mesh):
        multi = mesh.size > 1

        def loss(params, sparse, labels):
            return dlrm.loss_fn(cfg, params, None, sparse, labels,
                                mesh if multi else None)

        trainer = SpmdTrainer(mesh, loss, params, optax.adam(1e-2))
        shardings = batch_shardings(mesh, batches[0])
        losses = [float(trainer.train_step(*jax.device_put(b, shardings)))
                  for b in batches]
        return losses, jax.device_get(trainer.params)

    before = _exchanges_counted()
    got_losses, got_params = run(mesh_mod.make_mesh())
    traced = _exchanges_counted()
    want_losses, want_params = run(mesh_mod.make_mesh(num_devices=1))
    assert _exchanges_counted() == traced      # one device counts nothing
    assert traced["rows"] - before["rows"] == sum(
        v > _BATCH for v in vocab_sizes)
    assert traced["dense"] - before["dense"] == sum(
        v <= _BATCH for v in vocab_sizes)
    np.testing.assert_allclose(got_losses, want_losses, rtol=1e-5)
    jax.tree.map(
        lambda got, want: np.testing.assert_allclose(got, want, atol=2e-5),
        got_params, want_params)


def test_pallas_lookup_lowers_for_the_chip_only_with_the_mesh(
        rng, monkeypatch):
    """GSPMD cannot partition a Mosaic kernel: lowering a multi-device
    step for the TPU fails unless the gather was told the mesh."""
    mesh, table, indices = _mesh_table_indices(rng)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def lower(mesh):
        fn = jax.jit(lambda t, i: embedding.lookup(
            t, i, jnp.float32, mode="pallas", mesh=mesh))
        return fn.trace(table, indices).lower(
            lowering_platforms=("tpu",)).as_text()

    assert lower(mesh).count("tpu_custom_call") == 1
    with pytest.raises(NotImplementedError, match="shard_map"):
        lower(None)


@pytest.fixture(scope="module")
def four_chips():
    """A described v5e 2x2 (the TPU's compiler is installed here; no chip
    is attached), as a data mesh."""
    from jax.experimental import topologies

    from ray_shuffling_data_loader_tpu.parallel import mesh as mesh_mod
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return mesh_mod.make_mesh(devices=list(topo.devices))


def test_mesh_lookup_gradient_compiles_for_four_chips(four_chips,
                                                      monkeypatch):
    """dlrm-mlperf's largest table at the four-chip cell's batch, through
    the TPU's own compiler: the Mosaic gather once a chip in the forward,
    8,192 cotangent rows all-gathered in the backward, and no collective
    of the table's 484 MB."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    vocab, embed, batch = 945195, 128, 8192
    table = jax.ShapeDtypeStruct((vocab, embed), jnp.float32,
                                 sharding=NamedSharding(four_chips, P()))
    indices = jax.ShapeDtypeStruct(
        (batch,), jnp.int32, sharding=NamedSharding(four_chips, P("data")))

    def loss(table, indices):
        out = embedding.lookup(table, indices, jnp.bfloat16, mode="pallas",
                               mesh=four_chips)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    hlo = jax.jit(jax.grad(loss)).lower(table, indices).compile().as_text()
    assert hlo.count("tpu_custom_call") == 1
    for op in ("all-reduce", "all-gather", "reduce-scatter"):
        assert _collectives_holding(hlo, op, vocab, embed) == 0
    assert _collectives_holding(hlo, "all-gather", batch, embed) == 1


def test_bert_mlm_head_gradient_compiles_for_the_chip_in_blocks(four_chips):
    """``bert-base-mlm``'s head at the cell's batch through the TPU's own
    compiler (kept in this file: one process may load that compiler): the
    loss's gradient holds logits of one block of 64 positions a row and
    nothing of ``[32,512,30522]``."""
    import re

    from jax.sharding import SingleDeviceSharding

    from ray_shuffling_data_loader_tpu.models import bert
    cfg = bert.bert_base()
    batch, seq = 32, cfg.max_seq_len
    one_chip = SingleDeviceSharding(four_chips.devices.flat[0])

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    hlo = jax.jit(jax.grad(bert._masked_nll, argnums=(0, 1, 2))).lower(
        on_chip((batch, seq, cfg.hidden_dim), cfg.compute_dtype),
        on_chip((cfg.vocab_size, cfg.hidden_dim), jnp.float32),
        on_chip((cfg.vocab_size,), jnp.float32),
        on_chip((batch, seq), jnp.int32)).compile().as_text()
    block = bert.mlm_block_size(seq)
    assert block == 64
    assert re.search(rf"f32\[{batch},{block},{cfg.vocab_size}\]", hlo)
    assert not re.search(rf"\[{batch},{seq},{cfg.vocab_size}\]", hlo)
    assert not re.search(rf"\[{batch * seq},{cfg.vocab_size}\]", hlo)


def _bert_base_step(four_chips, monkeypatch, mesh, sharding):
    """The loss and gradients of ``bert-base-mlm`` (one of its twelve
    layers: the widths are what the compiler may refuse) at
    ``bert_train``'s batch a chip, lowered for described chips with the
    model's choice of attention as on the chip."""
    import dataclasses

    from ray_shuffling_data_loader_tpu.models import bert
    from ray_shuffling_data_loader_tpu.ops import flash_attention
    monkeypatch.setattr(flash_attention, "on_tpu", lambda: True)
    monkeypatch.setattr(bert, "on_tpu", lambda: True)
    cfg = dataclasses.replace(bert.bert_base(), num_layers=1)
    replicated = sharding()
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=replicated),
        jax.eval_shape(lambda: bert.init(cfg, jax.random.key(0))))
    chips = 1 if mesh is None else mesh.size
    tokens = jax.ShapeDtypeStruct((32 * chips, cfg.max_seq_len), jnp.int32,
                                  sharding=sharding("data"))
    return jax.jit(jax.value_and_grad(
        lambda p, t, y: bert.loss_fn(cfg, p, t, y, mesh=mesh))).lower(
            params, tokens, tokens)


def test_bert_attention_compiles_for_the_chip_without_scores(four_chips,
                                                            monkeypatch):
    """``bert_train``'s loss and gradients through the TPU's own compiler:
    a forward and a backward Mosaic kernel a layer, reading the fused
    projection in place, and no array over (512, 512) in the program."""
    import re

    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(four_chips.devices.flat[0])
    hlo = _bert_base_step(four_chips, monkeypatch, None,
                          lambda *axes: one_chip).compile().as_text()
    assert hlo.count("tpu_custom_call") == 2
    assert not re.search(r"\[32,12,512,512\]", hlo)
    assert not re.search(r"bf16\[32,12,512,64\]", hlo)   # no head-major copy
    assert re.search(r"custom-call\(.*bf16\[32,512,2304\]", hlo)


def test_bert_attention_lowers_for_four_chips_only_with_the_mesh(
        four_chips, monkeypatch):
    """GSPMD cannot partition a Mosaic kernel: a step over four chips
    lowers once ``loss_fn`` is told the mesh (the kernels then run a
    shard of the batch a chip), and not without."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def on_mesh(*axes):
        return NamedSharding(four_chips, P(*axes))

    lowered = _bert_base_step(four_chips, monkeypatch, four_chips, on_mesh)
    # One forward and one backward program.
    assert lowered.as_text().count("tpu_custom_call") == 2
    with pytest.raises(NotImplementedError, match="shard_map"):
        _bert_base_step(four_chips, monkeypatch, None, on_mesh)


@pytest.mark.parametrize("build,layer", [
    ("mellum2_ep4_share", 0), ("mellum2_ep4_share", 3),
    ("laguna_xs2_ep8_share", 1), ("laguna_xs2_ep8_share", 4)],
    ids=["sliding_attention", "full_attention", "laguna_sliding_64_to_8",
         "laguna_full_48_to_8"])
def test_the_decoders_attention_compiles_for_the_chip(four_chips, build,
                                                      layer):
    """A layer of ``mellum_train_8k`` or ``laguna_train_8k`` of either
    kind through the TPU's own compiler, a row of 8,192 tokens at the
    published widths: two Mosaic kernels (forward, and one backward that
    holds a key/value head's dk and dv in VMEM) that read the layer's
    query heads of 128 (32 over 4 key/value heads; 64 or 48 over 8, from
    rows 8,192 or 6,144 wide) where the projections left them, with a
    grid as long as the band (``tests/test_mellum.py``) and no (S, S)
    array, nor delta's (B, H, S, 1) column."""
    import re

    from jax.sharding import SingleDeviceSharding

    from ray_shuffling_data_loader_tpu.models import mellum
    from ray_shuffling_data_loader_tpu.ops import flash_attention as fa
    cfg = getattr(mellum, build)()
    heads = cfg.heads(layer)
    window = (cfg.sliding_window
              if cfg.layer_types[layer] == mellum.SLIDING else None)
    one_chip = SingleDeviceSharding(four_chips.devices.flat[0])
    q, kv = (jax.ShapeDtypeStruct((1, 8192, n * cfg.head_dim),
                                  jnp.bfloat16, sharding=one_chip)
             for n in (heads, cfg.num_kv_heads))

    def both(q, k, v, do):
        args = (heads, cfg.num_kv_heads, True, window)
        out, lse = fa.grouped_forward(
            q, k, v, *args, *mellum._blocks(window, False))
        return fa.grouped_backward(
            q, k, v, out, lse, do, *args, *mellum._blocks(window, True))

    hlo = jax.jit(both).lower(q, kv, kv, q).compile().as_text()
    assert hlo.count("tpu_custom_call") == 2
    # no scores: (B, S, 64 x 128) is 8,192 wide itself, a head's scores
    # would be (B, H, S, S)
    assert not re.search(r"\[\d+,\d+(,\d+)*,8192,8192\]", hlo)
    # no head-major copy
    assert not re.search(rf"bf16\[1,{heads},8192,128\]", hlo)
    assert len(re.findall(rf"f32\[1,{heads},8192,1\]",
                          hlo.split("ENTRY")[1])) \
        <= 3  # lse: the forward's result and the backward's operand


def test_the_decoders_step_names_one_attention_backward_a_layer(
        four_chips, monkeypatch):
    """The decoder's loss and gradients through the TPU's own compiler, a
    row of 8,192 tokens at the published attention widths (a window layer
    and a full one; the experts, the vocabulary and the hidden size cut:
    they are not what is counted): under ``rsdl.lm.attention`` a layer
    has two Mosaic calls, its forward and one backward (the dq and dk/dv
    pair made two of that, and a checkpoint that kept only the half's
    input ran the forward a second time), and what waits from the one to
    the other of lse is (B, H, S): the kernels' (B, H, S, 1) column, a
    128-lane tile a value on the chip, is squeezed before the forward
    pass goes on and widened where the backward kernel takes it."""
    import re

    from jax.sharding import SingleDeviceSharding

    from chipbench import xplane
    from ray_shuffling_data_loader_tpu.models import mellum
    from ray_shuffling_data_loader_tpu.ops import flash_attention as fa
    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    monkeypatch.setattr(mellum, "on_tpu", lambda: True)
    cfg = mellum.DecoderConfig(
        vocab_size=2048, hidden_size=256, layer_types=(mellum.SLIDING,
                                                       mellum.FULL),
        num_experts=8, experts_held=(0, 2), top_k=2, expert_width=128)
    one_chip = SingleDeviceSharding(four_chips.devices.flat[0])
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: mellum.init(cfg, jax.random.key(0))))
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)
    hlo = jax.jit(jax.grad(lambda p, t: mellum.loss_fn(cfg, p, t))).lower(
        params, tokens).compile().as_text()
    calls = re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', hlo)
    assert len(calls) == 2 * cfg.num_layers, calls
    assert all(xplane.under_scope(c, mellum.ATTENTION_SCOPE) for c in calls)
    for rule in ("_flash_attention_fwd", "_flash_attention_bwd"):
        assert len([c for c in calls if rule in c]) == cfg.num_layers, calls
    # The compiled step's instructions stand in the order they run. No
    # forward kernel's column is read from the first backward kernel on;
    # a squeezed lse (XLA drops the row's axis of 1 too) is.
    assert "is_scheduled=true" in hlo
    step = hlo.split("ENTRY")[1].splitlines()
    backward = next(i for i, line in enumerate(step)
                    if "tpu_custom_call" in line
                    and "_flash_attention_bwd" in line)

    def made_forward(shape):
        return [m.group(1) for m in (
            re.match(rf"\s*(%[\w.-]+) = f32\[{shape}\]\{{.*"
                     "_flash_attention_fwd", line)
            for line in step[:backward]) if m]

    columns = made_forward(f"1,{cfg.num_heads},8192,1")
    kept = made_forward(f"(?:1,)?{cfg.num_heads},8192")
    assert len(columns) == len(kept) == cfg.num_layers, (columns, kept)
    later = "\n".join(step[backward:])
    for names, read in ((columns, False), (kept, True)):
        assert any(re.search(re.escape(name) + r"\b", later)
                   for name in names) == read, names


def test_the_hybrid_decoders_attention_layer_compiles_for_the_chip(
        four_chips):
    """``granite_train_8k``'s one attention layer through the TPU's own
    compiler, a row of 8,192 tokens: 32 query heads over 8 key/value
    heads of 64 under the configuration's scale (1/64). Heads of 64 are
    not whole lanes, so the blocked family takes them head-major
    (``_reads_in_place``): still two Mosaic kernels, a forward and one
    backward, and no (S, S) array."""
    import re

    from jax.sharding import SingleDeviceSharding

    from ray_shuffling_data_loader_tpu.models import mellum
    from ray_shuffling_data_loader_tpu.ops import flash_attention as fa
    cfg = mellum.granite4_h_micro_period()
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.attention_multiplier) == (32, 8, 64, 1 / 64)
    assert not fa._reads_in_place(cfg.head_dim, False)
    one_chip = SingleDeviceSharding(four_chips.devices.flat[0])
    q, kv = (jax.ShapeDtypeStruct((1, 8192, n * cfg.head_dim),
                                  jnp.bfloat16, sharding=one_chip)
             for n in (cfg.num_heads, cfg.num_kv_heads))

    def both(q, k, v, do):
        args = (cfg.num_heads, cfg.num_kv_heads, True, None)
        out, lse = fa.grouped_forward(
            q, k, v, *args, *mellum._blocks(None, False),
            scale=cfg.attention_multiplier)
        return fa.grouped_backward(
            q, k, v, out, lse, do, *args, *mellum._blocks(None, True),
            scale=cfg.attention_multiplier)

    hlo = jax.jit(both).lower(q, kv, kv, q).compile().as_text()
    assert hlo.count("tpu_custom_call") == 2
    assert not re.search(r"\[\d+,\d+(,\d+)*,8192,8192\]", hlo)
    assert fa.grouped_backward_kind(q, kv, cfg.num_heads) == "fused"


@pytest.mark.parametrize("layer", [1, 3], ids=["window_512", "triangle"])
def test_the_junctions_differential_layers_compile_for_the_chip(four_chips,
                                                                layer):
    """A differential layer of ``phi4flash_train_8k`` through the TPU's
    own compiler, a row of 8,192 tokens: one attention head a map, 40
    query heads over 20 key heads of 64 and 10 value heads of 128. Two
    Mosaic kernels, a forward and one backward (a key head's dk and a
    value head's dv fit in VMEM), no (S, S) array; q and k are copied
    head-major (64 is not whole lanes), the values, the float32 maps and
    their cotangent are read and written where they lie."""
    import re

    from jax.sharding import SingleDeviceSharding

    from ray_shuffling_data_loader_tpu.models import mellum
    from ray_shuffling_data_loader_tpu.ops import flash_attention as fa
    cfg = mellum.phi4_mini_flash_junction()
    heads, kv_heads, v_heads = (cfg.num_heads, cfg.num_kv_heads,
                                cfg.num_kv_heads // 2)
    assert (heads, kv_heads, cfg.head_dim) == (40, 20, 64)
    window = (cfg.sliding_window
              if cfg.layer_types[layer] == mellum.SLIDING else None)
    assert window == (512 if layer == 1 else None)
    one_chip = SingleDeviceSharding(four_chips.devices.flat[0])
    q, k, v, do = (jax.ShapeDtypeStruct((1, 8192, width), jnp.bfloat16,
                                        sharding=one_chip)
                   for width in (heads * 64, kv_heads * 64, v_heads * 128,
                                 heads * 128))

    def both(q, k, v, do):
        args = (heads, kv_heads, True, window)
        out, lse = fa.grouped_forward(
            q, k, v, *args, *mellum._blocks(window, False),
            out_dtype=jnp.float32, num_v_heads=v_heads)
        return out, fa.grouped_backward(
            q, k, v, out, lse, do, *args, *mellum._blocks(window, True),
            num_v_heads=v_heads)

    hlo = jax.jit(both).lower(q, k, v, do).compile().as_text()
    assert hlo.count("tpu_custom_call") == 2
    assert not re.search(r"\[\d+,\d+(,\d+)*,8192,8192\]", hlo)
    assert re.search(rf"bf16\[1,{heads},8192,64\]", hlo)
    assert not re.search(r"\[1,\d+,8192,128\]", hlo)
    assert fa.grouped_backward_kind(
        q, k, heads, *mellum._blocks(window, True),
        value_dim=128) == "fused"


def test_attention_under_block_diffusions_mask_compiles_for_the_chip(
        four_chips):
    """A layer's attention of ``sdar_train_8k`` through the TPU's own
    compiler: one row of 16,384 positions (8,192 tokens twice), 32 query
    heads over 4 key/value heads of 128, bf16, under block diffusion's
    mask in blocks of 4, at the decoder's own tiles. Two Mosaic kernels, a
    forward and one backward (16,384 keys' dk and dv fit in VMEM), no
    (S, S) array, q, k, v and the output read and written where the
    projections leave them; the mask's scalar walk, its shifts and its
    comparisons are what interpret mode lets through unasked."""
    import re

    from jax.sharding import SingleDeviceSharding

    from ray_shuffling_data_loader_tpu.models import mellum
    from ray_shuffling_data_loader_tpu.ops import flash_attention as fa
    cfg = mellum.sdar_30b_a3b_ep8_share()
    heads, kv_heads, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    assert (heads, kv_heads, d, cfg.diffusion_block) == (32, 4, 128, 4)
    length = 8192
    diffusion = (cfg.diffusion_block, length)
    one_chip = SingleDeviceSharding(four_chips.devices.flat[0])
    q, k, v, do = (jax.ShapeDtypeStruct((1, 2 * length, n * d),
                                        jnp.bfloat16, sharding=one_chip)
                   for n in (heads, kv_heads, kv_heads, heads))

    def both(q, k, v, do):
        args = (heads, kv_heads, False, None)
        out, lse = fa.grouped_forward(
            q, k, v, *args, *mellum._blocks(None, False, diffusion),
            diffusion=diffusion)
        return out, fa.grouped_backward(
            q, k, v, out, lse, do, *args,
            *mellum._blocks(None, True, diffusion), diffusion=diffusion)

    hlo = jax.jit(both).lower(q, k, v, do).compile().as_text()
    assert hlo.count("tpu_custom_call") == 2
    assert not re.search(r"\[\d+,\d+(,\d+)*,16384,16384\]", hlo)
    assert not re.search(rf"\[1,\d+,16384,{d}\]", hlo), "no head-major copy"
    assert fa.grouped_backward_kind(
        q, k, heads, *mellum._blocks(None, True, diffusion)) == "fused"
    bq, bk = fa.planned_blocks(2 * length,
                               *mellum._blocks(None, False, diffusion))
    visited, compared, live = fa.diffusion_tiles(4, length, bq, bk)
    assert 100 * live / (visited * bq * bk) > 60, "no dead tile is walked"
    assert compared < visited


@pytest.mark.parametrize("in_vmem", [False, True],
                         ids=["einsums", "in_vmem"])
def test_the_state_space_scan_compiles_for_the_chip_in_chunks(
        four_chips, in_vmem, monkeypatch):
    """A Mamba layer's scan of ``granite_train_8k`` (one row of 8,192
    positions, 64 heads of 64, a state of 128, chunks of 256), forward and
    backward, through the TPU's own compiler, nothing of length S x S
    either way. Where the code believes itself on the chip: six Mosaic
    calls (the chunks' end states, the carry across them and their
    outputs, and the same backwards) and no array of every head's
    chunk-by-chunk matrix. Off it
    (the fallback, and the kernels' oracle): XLA's products over chunks,
    no Mosaic call, the largest array that matrix (32 x 64 x 256 x 256).
    Temporaries that leave the step its room."""
    import re

    from jax.sharding import SingleDeviceSharding

    from ray_shuffling_data_loader_tpu.models import mellum
    from ray_shuffling_data_loader_tpu.ops import ssd
    cfg = mellum.granite4_h_micro_period()
    one_chip = SingleDeviceSharding(four_chips.devices.flat[0])
    if in_vmem:
        monkeypatch.setattr(ssd, "on_tpu", lambda: True)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    s, h = 8192, cfg.mamba_heads
    x = shape((1, s, h, cfg.mamba_head_dim), jnp.bfloat16)
    bc = shape((1, s, cfg.mamba_state), jnp.bfloat16)
    head = shape((h,), jnp.float32)

    def both(x, dt, a_log, b, c, d, dy):
        (y, crossed), vjp = jax.vjp(
            lambda *operands: ssd.ssd_counted(*operands, cfg.mamba_chunk),
            x, dt, a_log, b, c, d)
        return y, crossed, vjp((dy, jnp.zeros_like(crossed)))

    compiled = jax.jit(both).lower(x, shape((1, s, h), jnp.float32), head,
                                   bc, bc, head, x).compile()
    hlo = compiled.as_text()
    assert not re.search(r"\[(\d+,)*8192,8192\]", hlo)
    # (XLA folds the row's axis of 1 and may fold chunks and heads)
    every_heads = re.search(r"\[(1,)?(32,64|2048),256,256\]", hlo)
    if in_vmem:
        assert hlo.count("tpu_custom_call") == 6
        assert "while" not in hlo
        assert not every_heads
        assert compiled.memory_analysis().temp_size_in_bytes < 1e9
    else:
        assert "tpu_custom_call" not in hlo
        assert every_heads
        assert compiled.memory_analysis().temp_size_in_bytes < 4e9


def test_the_selective_scan_compiles_for_the_chip_with_its_state_in_vmem(
        four_chips, monkeypatch):
    """A Mamba-1 layer's scan of ``phi4flash_train_8k`` (one row of 8,192
    positions, 5,120 channels, a state of 16, chunks of 64), forward and
    backward, through the TPU's own compiler (kept in this file: one
    process may load that compiler): one Mosaic call each way, no array of
    every position's state (8,192 x 5,120 x 16) and temporaries that leave
    the step its room."""
    import re

    from jax.sharding import SingleDeviceSharding

    from ray_shuffling_data_loader_tpu.models import mellum
    from ray_shuffling_data_loader_tpu.ops import selective_scan as sscan
    cfg = mellum.phi4_mini_flash_junction()
    one_chip = SingleDeviceSharding(four_chips.devices.flat[0])
    monkeypatch.setattr(sscan, "on_tpu", lambda: True)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    s, width, state = 8192, cfg.mamba1_width, cfg.mamba1_state
    u = shape((1, s, width), jnp.bfloat16)
    bc = shape((1, s, state), jnp.bfloat16)

    def both(u, dt, a_log, b, c, d, dy):
        (y, crossed), vjp = jax.vjp(
            lambda *operands: sscan.selective_scan_counted(
                *operands, cfg.mamba_chunk), u, dt, a_log, b, c, d)
        return y, crossed, vjp((dy, jnp.zeros_like(crossed)))

    compiled = jax.jit(both).lower(
        u, shape((1, s, width), jnp.float32),
        shape((width, state), jnp.float32), bc, bc,
        shape((width,), jnp.float32), u).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 2
    assert "while" not in hlo
    assert not re.search(r"\[(1,)?8192,(5120,16|16,5120|16,40,128)\]", hlo)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


@pytest.mark.parametrize("channels", [4352, 5120],
                         ids=["granite_xbc", "phi4flash_u"])
def test_the_mixers_convolution_compiles_for_the_chip_as_two_kernels(
        four_chips, channels, monkeypatch):
    """The depthwise convolution of ``granite_train_8k``'s Mamba-2 layers
    (``xBC``: 4,352 channels) and of ``phi4flash_train_8k``'s Mamba-1
    layers (``u``: 5,120), one row of 8,192 positions in bf16, four taps,
    forward and backward, through the TPU's own compiler: one Mosaic call
    each way and no float32 array of the row's shape (the padded copy,
    the shifted slices, ``pre``) outside them."""
    import re

    from jax.sharding import SingleDeviceSharding

    from ray_shuffling_data_loader_tpu.ops import ssd
    one_chip = SingleDeviceSharding(four_chips.devices.flat[0])
    monkeypatch.setattr(ssd, "on_tpu", lambda: True)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    x = shape((1, 8192, channels), jnp.bfloat16)
    assert ssd.convs_in_vmem(8192, channels, 4, jnp.bfloat16)

    def both(x, weight, bias, dy):
        y, vjp = jax.vjp(ssd.causal_conv_silu, x, weight, bias)
        return y, vjp(dy)

    compiled = jax.jit(both).lower(
        x, shape((4, channels), jnp.float32),
        shape((channels,), jnp.float32), x).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 2
    assert not re.search(rf"f32\[(1,)?819\d,{channels}\]", hlo)
    assert compiled.memory_analysis().temp_size_in_bytes < 1e7


@pytest.mark.parametrize("batch,seq,heads,dim,rotated,normed", [
    (1, 16384, 32, 128, 128, True), (1, 16384, 4, 128, 128, True),
    (4, 8192, 32, 128, 128, False), (2, 8192, 48, 128, 64, False),
    (2, 8192, 32, 64, 64, True),
], ids=["sdar_q", "sdar_k", "mellum_q", "laguna_full_q", "lfm2_q"])
def test_the_placing_of_the_heads_compiles_for_the_chip_as_two_kernels(
        four_chips, batch, seq, heads, dim, rotated, normed, monkeypatch):
    """The rotary cells' q and k projections in bf16 through the TPU's own
    compiler, placed forward and backward: one Mosaic call each way and no
    float32 array of the projection's shape outside them (the widened
    copy, the normed one, the turned one); heads of 64 ride two a
    register."""
    import re

    from jax.sharding import SingleDeviceSharding

    from ray_shuffling_data_loader_tpu.ops import rope
    one_chip = SingleDeviceSharding(four_chips.devices.flat[0])
    monkeypatch.setattr(rope, "on_tpu", lambda: True)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    width = heads * dim
    x = shape((batch, seq, width), jnp.bfloat16)
    table = shape((seq, dim), jnp.float32)
    assert rope.places_in_vmem(seq, width, dim, rotated, jnp.bfloat16)

    def both(x, scale, cos, sin, dy):
        out, vjp = jax.vjp(
            lambda x, scale: rope.placed_in_vmem(x, scale, cos, sin, rotated,
                                                 1e-6, False), x, scale)
        return out, vjp(dy)

    compiled = jax.jit(both).lower(
        x, shape((dim,), jnp.float32) if normed else None, table, table,
        x).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 2
    assert not re.search(rf"f32\[({batch},)?{seq},({width}|{heads},{dim})\]",
                         hlo)
    assert compiled.memory_analysis().temp_size_in_bytes < 6e7


@pytest.mark.parametrize("tokens,hidden,experts,count,top_k", [
    (4 * 8192, 2304, 64, 16, 8), (2 * 8192, 2048, 128, 16, 8),
    (2 * 8192, 2048, 256, 32, 8), (2 * 8192, 2048, 64, 8, 4),
], ids=["mellum", "sdar", "laguna", "lfm2"])
def test_the_combine_by_runs_compiles_for_the_chip(
        four_chips, tokens, hidden, experts, count, top_k, monkeypatch):
    """A round's combine at the four sparse cells' shapes through the
    TPU's own compiler: one Mosaic call, the sums in the place of the
    sums it is given, and both halves of the landing slab inside the VMEM
    it asks for."""
    from jax.sharding import SingleDeviceSharding

    from ray_shuffling_data_loader_tpu.ops import moe
    one_chip = SingleDeviceSharding(four_chips.devices.flat[0])
    monkeypatch.setattr(moe, "on_tpu", lambda: True)
    tile = moe.tile_rows(tokens, top_k, experts)
    rows = moe.round_rows(tokens, top_k, count, experts, tile)
    blocks = tokens // moe._block(tokens)
    assert moe.sums_by_runs(tokens, hidden, tile, jnp.bfloat16, top_k, count)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def combined(acc, buffer, index, starts, counts, expert, first_row):
        return moe._combined(acc, buffer, index, rows,
                             (starts, counts, expert), first_row)

    picks, runs = (tokens, top_k), (blocks, count)
    compiled = jax.jit(combined, donate_argnums=0).lower(
        shape((tokens, hidden), jnp.float32),
        shape((rows + tile, hidden), jnp.bfloat16), shape(picks, jnp.int32),
        shape(runs, jnp.int32), shape(runs, jnp.int32),
        shape(picks, jnp.int32), shape((), jnp.int32)).compile()
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert "output_to_operand_aliasing" in hlo
    # nothing of the sums' size beside the sums
    assert compiled.memory_analysis().temp_size_in_bytes < 2e7


@pytest.mark.parametrize("layer", [1, 3], ids=["window_512", "full"])
def test_differential_attention_compiles_for_the_chip(four_chips, layer):
    """A differential layer of ``phi4flash_train_8k`` through the TPU's
    own compiler, a row of 8,192 tokens at the published widths: each map
    over each half of its values as one call of the blocked family over
    80 query heads and 40 key/value heads of 64: two Mosaic kernels
    (forward, and the one-kernel backward) and no (S, S) array."""
    import re

    from jax.sharding import SingleDeviceSharding

    from ray_shuffling_data_loader_tpu.models import mellum
    from ray_shuffling_data_loader_tpu.ops import flash_attention as fa
    cfg = mellum.phi4_mini_flash_junction()
    heads, kv_heads = 2 * cfg.num_heads, 2 * cfg.num_kv_heads
    window = (cfg.sliding_window
              if cfg.layer_types[layer] == mellum.SLIDING else None)
    one_chip = SingleDeviceSharding(four_chips.devices.flat[0])
    q, kv = (jax.ShapeDtypeStruct((1, 8192, n * cfg.head_dim),
                                  jnp.bfloat16, sharding=one_chip)
             for n in (heads, kv_heads))

    def both(q, k, v, do):
        args = (heads, kv_heads, True, window)
        out, lse = fa.grouped_forward(
            q, k, v, *args, *mellum._blocks(window, False))
        return fa.grouped_backward(
            q, k, v, out, lse, do, *args, *mellum._blocks(window, True))

    hlo = jax.jit(both).lower(q, kv, kv, q).compile().as_text()
    assert hlo.count("tpu_custom_call") == 2
    assert not re.search(r"\[\d+,\d+(,\d+)*,8192,8192\]", hlo)
    assert fa.grouped_backward_kind(q, kv, heads) == "fused"


@pytest.mark.parametrize("hidden,width", [(2048, 8192), (2560, 10240)],
                         ids=["granite", "phi4flash"])
def test_the_mlp_half_keeps_two_products_on_the_chip(four_chips, hidden,
                                                     width, monkeypatch):
    """Two layers with ``granite_train_8k``'s or ``phi4flash_train_8k``'s
    dense SwiGLU through the TPU's own compiler, a row of 8,192 tokens:
    nine products a layer under ``rsdl.lm.mlp`` where the half's
    checkpoint keeps ``x G`` and ``x U``, eleven where there is no room
    for them, and what is kept (those two and, where there is room for
    them too, the attention half's q, k and v) holds its own bytes of the
    step's temporaries and no more (no copy of it waits through the step,
    as a kept log-sum-exp's once did)."""
    from jax.sharding import SingleDeviceSharding

    from ray_shuffling_data_loader_tpu.models import mellum
    from ray_shuffling_data_loader_tpu.ops import flash_attention as fa
    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    monkeypatch.setattr(mellum, "on_tpu", lambda: True)
    cfg = mellum.DecoderConfig(
        vocab_size=2048, hidden_size=hidden, num_heads=32, num_kv_heads=8,
        head_dim=64, layer_types=(mellum.FULL, mellum.FULL),
        mlp_layer_types=(mellum.DENSE, mellum.DENSE),
        intermediate_size=width, rotary=False)
    one_chip = SingleDeviceSharding(four_chips.devices.flat[0])
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: mellum.init(cfg, jax.random.key(0))))
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)

    def compiled(memory):
        monkeypatch.setattr(mellum, "_device_memory", lambda mesh: memory)
        program = jax.jit(jax.grad(
            lambda p, t: mellum.loss_fn(cfg, p, t))).lower(
                params, tokens).compile()
        products = [line for line in program.as_text().splitlines()
                    if mellum.MLP_SCOPE in line
                    and (" convolution(" in line or " dot(" in line)]
        return len(products), program.memory_analysis().temp_size_in_bytes

    # the CPU's devices keep no statistics: every layer keeps
    kept, kept_temporaries = compiled(None)
    plain, plain_temporaries = compiled((1 << 30, 1 << 30))
    assert (kept, plain) == (9 * cfg.num_layers, 11 * cfg.num_layers)
    a_layer = mellum.kept_products_bytes(cfg, 0, 8192)
    assert a_layer == 2 * 8192 * width * 2
    # 32 : 8 heads of 64: q, k, v
    q_k_v = mellum.in_projections_bytes(cfg, 0, 8192)
    assert q_k_v == 8192 * (2048 + 2 * 512) * 2
    assert kept_temporaries - plain_temporaries <= cfg.num_layers * (
        a_layer + q_k_v)
