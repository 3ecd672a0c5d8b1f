"""Tests for the ResNet and BERT model families (models/resnet.py, bert.py)."""

import dataclasses
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_shuffling_data_loader_tpu.models import bert, mellum, resnet
from ray_shuffling_data_loader_tpu.parallel import mesh as mesh_mod
from ray_shuffling_data_loader_tpu.parallel.trainer import SpmdTrainer
from ray_shuffling_data_loader_tpu.runtime import metrics
from ray_shuffling_data_loader_tpu.workloads import bert_mlm


def test_resnet_forward_shape():
    cfg = resnet.resnet18_cifar()
    params = resnet.init(cfg, jax.random.key(0))
    images = jnp.ones((2, 32, 32, 3), jnp.float32)
    logits = resnet.apply(cfg, params, images)
    assert logits.shape == (2, 10)
    assert logits.dtype == jnp.float32


def test_resnet_specs_match_tree():
    cfg = resnet.resnet18_cifar()
    params = resnet.init(cfg, jax.random.key(0))
    specs = resnet.param_specs(cfg)
    jax.tree.map(lambda a, b: None, params, specs,
                 is_leaf=lambda x: isinstance(x, P))


def test_resnet_loss_and_grad_finite():
    cfg = resnet.resnet18_cifar()
    params = resnet.init(cfg, jax.random.key(0))
    images = jnp.asarray(
        np.random.default_rng(0).normal(size=(4, 32, 32, 3)),
        jnp.float32)
    labels = jnp.asarray([0, 1, 2, 3], jnp.int32)
    loss, grads = jax.value_and_grad(
        lambda p: resnet.loss_fn(cfg, p, images, labels))(params)
    assert np.isfinite(float(loss))
    flat = jax.tree.leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in flat)


def test_resnet_learns_tiny():
    cfg = resnet.resnet18_cifar(num_classes=2)
    params = resnet.init(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    # Class 0 = dark images, class 1 = bright images.
    images = np.concatenate([
        rng.normal(-1, 0.1, (8, 16, 16, 3)),
        rng.normal(1, 0.1, (8, 16, 16, 3))]).astype(np.float32)
    labels = np.array([0] * 8 + [1] * 8, np.int32)
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)
    step = jax.jit(lambda p, o: _step(cfg, p, o, opt, images, labels))
    first = None
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state)
        if first is None:
            first = float(loss)
    assert float(loss) < first


def _step(cfg, params, opt_state, opt, images, labels):
    loss, grads = jax.value_and_grad(
        lambda p: resnet.loss_fn(cfg, p, images, labels))(params)
    updates, opt_state = opt.update(grads, opt_state, params)
    params = optax.apply_updates(params, updates)
    return params, opt_state, loss


def test_resnet50_config():
    cfg = resnet.resnet50()
    assert cfg.stage_sizes == (3, 4, 6, 3)
    assert cfg.num_classes == 1000


def test_bert_forward_shape_and_mask():
    cfg = bert.bert_tiny()
    params = bert.init(cfg, jax.random.key(0))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16)),
        jnp.int32)
    logits = bert.apply(cfg, params, tokens)
    assert logits.shape == (2, 16, cfg.vocab_size)
    mask = jnp.ones((2, 16), jnp.int32).at[:, 8:].set(0)
    logits_masked = bert.apply(cfg, params, tokens, mask)
    assert logits_masked.shape == (2, 16, cfg.vocab_size)
    assert not np.allclose(np.asarray(logits), np.asarray(logits_masked))


def test_bert_mlm_loss_ignores_unmasked():
    cfg = bert.bert_tiny()
    params = bert.init(cfg, jax.random.key(0))
    tokens = jnp.zeros((2, 8), jnp.int32)
    # Only one position per row is a target.
    targets = jnp.full((2, 8), bert.IGNORE_ID, jnp.int32)
    targets = targets.at[:, 3].set(7)
    loss = bert.loss_fn(cfg, params, tokens, targets)
    assert np.isfinite(float(loss))
    # All-ignored targets: loss must not NaN (count clamps to 1).
    loss0 = bert.loss_fn(cfg, params, tokens,
                         jnp.full((2, 8), bert.IGNORE_ID, jnp.int32))
    assert float(loss0) == 0.0


_MLM_BATCH, _MLM_SEQ = 8, 64


def _dense_mlm_loss(cfg, params, tokens, targets):
    """The head as it was before the blocked walk: every position
    projected, one float32 log-softmax over the whole, then the mask."""
    logits = bert.apply(cfg, params, tokens)
    mask = targets != bert.IGNORE_ID
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.where(mask, targets, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(mask, -picked, 0.0)) / jnp.maximum(
        jnp.sum(mask), 1)


@pytest.fixture(scope="module")
def mlm_f32():
    """``bert_tiny`` in float32 with a bias that is not zero, and the
    value-and-gradient of the model's loss and of the dense reference,
    each compiled once for every mask."""
    cfg = dataclasses.replace(bert.bert_tiny(), compute_dtype=jnp.float32)
    params = bert.init(cfg, jax.random.key(0))
    params["mlm_bias"] = 0.1 * jax.random.normal(
        jax.random.key(5), params["mlm_bias"].shape)
    tokens = jax.random.randint(
        jax.random.key(1), (_MLM_BATCH, _MLM_SEQ),
        bert_mlm.NUM_SPECIAL_TOKENS, cfg.vocab_size)
    got = jax.jit(jax.value_and_grad(
        lambda p, t, y: bert.loss_fn(cfg, p, t, y)))
    want = jax.jit(jax.value_and_grad(
        lambda p, t, y: _dense_mlm_loss(cfg, p, t, y)))
    return cfg, params, tokens, got, want


def _mlm_targets(case, cfg, tokens):
    none = jnp.full(tokens.shape, bert.IGNORE_ID, jnp.int32)
    block = bert.mlm_block_size(_MLM_SEQ)
    if case == "none":
        return none
    if case == "one_position":
        return none.at[3, 17].set(tokens[3, 17])
    if case == "draw_15_pct":
        return bert_mlm.mlm_mask(tokens, jax.random.key(2),
                                 cfg.vocab_size)[1]
    if case == "row_at_block_edge":
        # one row fills the first block exactly, another spills one over
        return (none.at[2, 5:5 + block].set(tokens[2, 5:5 + block])
                .at[6, 40:41 + block].set(tokens[6, 40:41 + block]))
    if case == "one_row_full":
        return none.at[4].set(tokens[4])
    assert case == "all"
    return tokens


@pytest.mark.parametrize("case", ["none", "one_position", "draw_15_pct",
                                  "row_at_block_edge", "one_row_full",
                                  "all"])
def test_bert_mlm_loss_matches_the_dense_head_for_every_mask(mlm_f32, case):
    """The blocked walk over the masked positions gives the loss and every
    leaf's gradient that the dense head gives, whatever the mask."""
    cfg, params, tokens, got, want = mlm_f32
    assert 1 < bert.mlm_block_size(_MLM_SEQ) < _MLM_SEQ
    targets = _mlm_targets(case, cfg, tokens)
    got_loss, got_grads = got(params, tokens, targets)
    want_loss, want_grads = want(params, tokens, targets)
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-6)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    for path, leaf in jax.tree_util.tree_leaves_with_path(got_grads):
        np.testing.assert_allclose(
            np.asarray(leaf), np.asarray(flat_want[path]), rtol=1e-4,
            atol=1e-6, err_msg=jax.tree_util.keystr(path))
    if case == "none":
        assert float(got_loss) == 0.0


def test_bert_mlm_loss_pads_a_row_to_whole_blocks(mlm_f32):
    """A sequence length that is no whole number of blocks (20 = 8 + 8 +
    4): the walk's last block is padded with ignored positions."""
    cfg, params, tokens, _, _ = mlm_f32
    tokens = tokens[:4, :20]
    assert tokens.shape[1] % bert.mlm_block_size(tokens.shape[1])
    targets = jnp.where(tokens % 2 == 0, tokens, bert.IGNORE_ID)
    got_loss, got_grads = jax.value_and_grad(
        lambda p: bert.loss_fn(cfg, p, tokens, targets))(params)
    want_loss, want_grads = jax.value_and_grad(
        lambda p: _dense_mlm_loss(cfg, p, tokens, targets))(params)
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-6)
    for got, want in zip(jax.tree.leaves(got_grads),
                         jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-6)


def test_bert_mlm_compiled_gradient_holds_no_full_logits(mlm_f32):
    """The compiled gradient keeps nothing of (batch, seq, vocab), runs
    the head under the scope the device trace's reader looks for, and the
    trace counted the walk it compiled."""
    cfg, params, tokens, _, _ = mlm_f32
    traced = metrics.get("rsdl_mlm_head_total", {"kind": "blocked"})
    before = 0 if traced is None else traced.value
    text = jax.jit(jax.grad(
        lambda p, t, y: bert.loss_fn(cfg, p, t, y))).lower(
            params, tokens, tokens).compile().as_text()
    block = bert.mlm_block_size(_MLM_SEQ)
    vocab = cfg.vocab_size
    assert re.search(rf"\[{_MLM_BATCH},{block},{vocab}\]", text)
    assert not re.search(rf"\[{_MLM_BATCH},{_MLM_SEQ},{vocab}\]", text)
    assert not re.search(rf"\[{_MLM_BATCH * _MLM_SEQ},{vocab}\]", text)
    # The scope names what runs inside the walk's loops and not the loops:
    # a reader that sums operations under it counts each once.
    from chipbench import xplane
    under = {name for name, op_name in xplane.hlo_op_names(text).items()
             if xplane.under_scope(op_name, bert.MLM_HEAD_SCOPE)}
    assert any(name.startswith(("dot", "fusion")) for name in under), under
    assert not any(name.startswith("while") for name in under), under
    assert metrics.get("rsdl_mlm_head_total",
                       {"kind": "blocked"}).value == before + 1
    assert metrics.get("rsdl_mlm_head_block_positions").value == block
    assert metrics.get("rsdl_mlm_head_blocks_per_row").value == (
        _MLM_SEQ // block)


def test_bert_mlm_loss_on_a_data_mesh_equals_one_device(mlm_f32):
    """Through ``SpmdTrainer`` on four devices with the batch sharded, the
    first loss is the one-device loss: the compaction is per row."""
    cfg, params, tokens, got, _ = mlm_f32
    targets = _mlm_targets("draw_15_pct", cfg, tokens)
    want_loss, _ = got(params, tokens, targets)
    mesh = mesh_mod.make_mesh(num_devices=4)
    trainer = SpmdTrainer(
        mesh, lambda p, t, y: bert.loss_fn(cfg, p, t, y), params,
        optax.adam(1e-3))
    sharded = mesh_mod.batch_sharding(mesh)
    loss = trainer.train_step(jax.device_put(tokens, sharded),
                              jax.device_put(targets, sharded))
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-6)


_SPARSE_CELLS = ["mellum_train_8k", "laguna_train_8k", "lfm2_train_8k",
                 "sdar_train_8k"]
_DECODER_CELLS = ["mellum_train_8k", "laguna_train_8k", "granite_train_8k",
                  "phi4flash_train_8k", "lfm2_train_8k", "sdar_train_8k"]
_DENSE_MLP_CELLS = ["laguna_train_8k", "granite_train_8k",
                    "phi4flash_train_8k", "lfm2_train_8k"]
# ``mellum_train_8k``'s reference has no ``proj_work`` and lists neither
_PROJ_CELLS = _DENSE_MLP_CELLS + ["sdar_train_8k"]


@pytest.mark.parametrize("name,scope,layer,cells", [
    ("mlm_head_pct", bert.MLM_HEAD_SCOPE, "model", ["bert_train"]),
    ("attention_pct", bert.ATTENTION_SCOPE, "kernels", ["bert_train"]),
    ("moe_pct", mellum.MOE_SCOPE, "kernels", _SPARSE_CELLS),
    ("lm_attention_pct", mellum.ATTENTION_SCOPE, "kernels", _DECODER_CELLS),
    ("lm_head_pct", mellum.HEAD_SCOPE, "model", _DECODER_CELLS),
    ("lm_mlp_pct", mellum.MLP_SCOPE, "model", _DENSE_MLP_CELLS),
    ("lm_proj_pct", mellum.PROJ_SCOPE, "model", _PROJ_CELLS),
    ("lm_ssm_pct", mellum.SSM_SCOPE, "kernels", ["granite_train_8k"]),
    ("lm_sscan_pct", mellum.SSCAN_SCOPE, "kernels", ["phi4flash_train_8k"]),
    ("lm_gmu_pct", mellum.GMU_SCOPE, "model", ["phi4flash_train_8k"]),
    ("lm_sconv_pct", mellum.SCONV_SCOPE, "kernels", ["lfm2_train_8k"]),
    ("lm_noise_pct", mellum.NOISE_SCOPE, "model", ["sdar_train_8k"]),
])
def test_the_benchmarks_scope_shares_read_the_models_scopes(name, scope,
                                                            layer, cells):
    """``mlm_head_pct`` (PR 29), ``attention_pct`` (PR 31), the decoder's
    three (PR 32), its dense SwiGLUs' and its projections' (PR 34), its
    state-space mixers' (PR 38), its selective scans' and its memory
    units' (PR 40), its gated short convolutions' (PR 44), block
    diffusion's noise (PR 47) are data: the scope
    reader ``grad_exchange_pct`` uses, pointed at a scope the model
    names, in the cells of that model's configurations that run the
    scope and in no other; a program without the scope (the parent's
    side) gives it nothing."""
    from chipbench import manifest
    bench = manifest.load_manifest()
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": "%", "better": "lower",
                     "source": "device_trace", "layer": layer,
                     "moves": "train_rows_per_s", "workloads": cells}
    with open(os.path.join(manifest.BENCH_DIR, "layers",
                           f"{name}.json")) as f:
        reads = json.load(f)
    with open(os.path.join(manifest.BENCH_DIR, "layers",
                           "grad_exchange_pct.json")) as f:
        exchange = json.load(f)
    assert reads["args"].pop("scope") == scope
    assert exchange["args"].pop("scope") != scope
    assert reads == exchange
    for other in bench["workloads"]:
        reported = {m["name"]
                    for m in manifest.resolve_cell(other["name"]).per_layer}
        assert (name in reported) == (other["name"] in cells)
    reader = manifest.layer_reader(name)
    assert reader({"trace": None}) is None
    assert reader({"trace": object(), "step_op_names": {}}) is None


@pytest.mark.parametrize("family", ["mellum", "laguna", "granite",
                                    "phi4flash", "lfm2", "sdar"])
def test_the_decoders_builders_come_in_pairs(family):
    """Each decoder configuration has a builder at its published widths
    and a tiny one of the same pattern for the CPU: the same kinds of
    layer and of MLP in the same order (the tiny one may be a shorter
    run of them), the same switches, and both pass the decoder's own
    checks and give a parameter tree a layer."""
    full, tiny = {
        "mellum": (mellum.mellum2_ep4_share, mellum.mellum_tiny),
        "laguna": (mellum.laguna_xs2_ep8_share, mellum.laguna_tiny),
        "granite": (mellum.granite4_h_micro_period, mellum.granite_tiny),
        "phi4flash": (mellum.phi4_mini_flash_junction,
                      mellum.phi4flash_tiny),
        "lfm2": (mellum.lfm2_24b_a2b_ep8_share, mellum.lfm2_tiny),
        "sdar": (mellum.sdar_30b_a3b_ep8_share, mellum.sdar_tiny),
    }[family]
    full, tiny = full(), tiny()
    for cfg in (full, tiny):
        mellum._checked(cfg)
        shapes = jax.eval_shape(lambda k, cfg=cfg: mellum.init(cfg, k),
                                jax.random.key(0))
        assert sum(name.startswith("layer_") for name in shapes) \
            == cfg.num_layers
    assert set(tiny.layer_types) == set(full.layer_types)
    assert (tiny.mlp_layer_types is None) == (full.mlp_layer_types is None)
    for switch in ("qk_norm", "expert_bias", "router_trains",
                   "tie_embeddings", "rotary",
                   "differential", "attention_gate", "norm", "conv_taps",
                   "published_layers", "rope_theta", "diffusion_block",
                   "mask_token_id", "diffusion_eps"):
        assert getattr(tiny, switch) == getattr(full, switch), switch
    assert (tiny.yarn is None) == (full.yarn is None)
    assert tiny.hidden_size < full.hidden_size
    assert bool(full.diffusion_block) == (family == "sdar")
    if family == "lfm2":
        assert full.layer_types == tiny.layer_types == (
            mellum.CONV, mellum.FULL, mellum.CONV, mellum.CONV, mellum.CONV)
        assert (full.hidden_size, full.num_heads, full.num_kv_heads,
                full.head_dim, full.intermediate_size, full.expert_width,
                full.num_experts, full.experts_held, full.top_k,
                full.vocab_size) == (2048, 32, 8, 64, 11776, 1536, 64,
                                     (0, 8), 4, 8192)


def test_bert_attention_runs_under_its_scope():
    """Both attentions are programs of their own, so the scope reaches a
    compiled step as written, forward and backward, where a reader of the
    device trace finds it (entered straight under ``grad`` it would read
    ``jvp(rsdl.bert.attention)``)."""
    from chipbench import xplane
    cfg = bert.BertConfig(vocab_size=64, hidden_dim=128, num_layers=1,
                          num_heads=2, ffn_dim=64, max_seq_len=128,
                          compute_dtype=jnp.float32)
    params = bert.init(cfg, jax.random.key(0))
    tokens = jnp.zeros((2, 128), jnp.int32)
    qkv = jnp.zeros((2, 128, 3 * 128), jnp.float32)
    inline = jax.jit(jax.grad(
        lambda p: bert.loss_fn(cfg, p, tokens, tokens))).lower(
            params).compile().as_text()
    flash = jax.jit(jax.grad(lambda x: jnp.sum(
        bert._flash_attention(x, None, 2)))).lower(qkv).compile().as_text()
    for text in (inline, flash):
        under = [op_name for op_name in xplane.hlo_op_names(text).values()
                 if xplane.under_scope(op_name, bert.ATTENTION_SCOPE)]
        assert any("transpose(" in op_name for op_name in under), under
        assert any("transpose(" not in op_name for op_name in under), under


def test_bert_specs_match_tree():
    cfg = bert.bert_tiny()
    params = bert.init(cfg, jax.random.key(0))
    specs = bert.param_specs(cfg)
    jax.tree.map(lambda a, b: None, params, specs,
                 is_leaf=lambda x: isinstance(x, P))


def test_bert_tp_train_step_on_mesh():
    mesh = mesh_mod.make_mesh(model_parallel=2)
    cfg = bert.bert_tiny()
    params = bert.init(cfg, jax.random.key(0))
    trainer = SpmdTrainer(
        mesh,
        lambda p, t, y: bert.loss_fn(cfg, p, t, y),
        params, optax.adam(1e-3), param_specs=bert.param_specs(cfg))
    qkv = trainer.params["layer_0"]["qkv_w"]
    assert qkv.sharding.is_equivalent_to(
        NamedSharding(mesh, P(None, "model")), qkv.ndim)
    rng = np.random.default_rng(0)
    tokens = jax.device_put(
        jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)), jnp.int32),
        mesh_mod.batch_sharding(mesh))
    targets = jnp.full((8, 16), bert.IGNORE_ID, jnp.int32).at[:, 2].set(5)
    targets = jax.device_put(targets, mesh_mod.batch_sharding(mesh))
    loss = trainer.train_step(tokens, targets)
    assert np.isfinite(float(loss))


def test_bert_base_config():
    cfg = bert.bert_base()
    assert cfg.hidden_dim == 768 and cfg.num_layers == 12
    assert cfg.head_dim == 64


def _assert_grads_match(g0, g1):
    """remat re-runs the identical XLA program, so its grads equal the
    exact grads bit for bit."""
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bert_remat_matches_exact_grads():
    """remat=True changes memory behavior only: loss and grads are
    identical to the non-remat graph."""
    import optax  # noqa: F401 - parity with sibling tests
    rng = np.random.default_rng(0)
    base = dict(vocab_size=64, hidden_dim=32, num_layers=2, num_heads=4,
                ffn_dim=64, max_seq_len=16, compute_dtype=jnp.float32)
    cfg = bert.BertConfig(**base)
    cfg_remat = bert.BertConfig(**base, remat=True)
    params = bert.init(cfg, jax.random.key(0))
    tokens = jnp.asarray(rng.integers(4, 64, (2, 16)), jnp.int32)
    targets = jnp.where(jnp.asarray(rng.random((2, 16))) < 0.2, tokens,
                        bert.IGNORE_ID).astype(jnp.int32)

    def loss(cfg, p):
        return bert.loss_fn(cfg, p, tokens, targets)

    l0, g0 = jax.value_and_grad(lambda p: loss(cfg, p))(params)
    l1, g1 = jax.value_and_grad(lambda p: loss(cfg_remat, p))(params)
    assert float(l0) == float(l1)
    _assert_grads_match(g0, g1)


def test_resnet_remat_matches_exact_grads():
    rng = np.random.default_rng(0)
    base = dict(stage_sizes=(1, 1), width=8, num_classes=2, num_groups=4,
                compute_dtype=jnp.float32)
    cfg = resnet.ResNetConfig(**base)
    cfg_remat = resnet.ResNetConfig(**base, remat=True)
    params = resnet.init(cfg, jax.random.key(0))
    images = jnp.asarray(rng.random((2, 16, 16, 3)), jnp.float32)
    labels = jnp.asarray([0, 1], jnp.int32)

    def loss(cfg, p):
        return resnet.loss_fn(cfg, p, images, labels)

    l0, g0 = jax.value_and_grad(lambda p: loss(cfg, p))(params)
    l1, g1 = jax.value_and_grad(lambda p: loss(cfg_remat, p))(params)
    assert float(l0) == float(l1)
    _assert_grads_match(g0, g1)
