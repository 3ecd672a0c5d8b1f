"""The decoder (models/mellum.py) at its hybrid state-space configuration
against the plain reference (chipbench/references/granite.py) at
``granite_tiny`` on the CPU: Mamba-2 layers through the chunked scan
(ops/ssd.py), an attention layer without positions under
``attention_multiplier``, the four scalars off 1, the tied head over a
slice of the vocabulary; and the scan's statistics riding out of the
jitted step."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chipbench.references import granite as ref
from ray_shuffling_data_loader_tpu.models import mellum
from ray_shuffling_data_loader_tpu.ops import flash_attention as fa
from ray_shuffling_data_loader_tpu.parallel import mesh as mesh_mod
from ray_shuffling_data_loader_tpu.parallel import trainer as trainer_mod
from ray_shuffling_data_loader_tpu.runtime import (metric_names, metrics,
                                                   telemetry)
from ray_shuffling_data_loader_tpu.utils import tracing
from tests.test_step_stats import _Late

_SEQ = 32
_WORDS = {mellum.MAMBA: "mamba", mellum.FULL: "attention"}


def _sizes(cfg: mellum.DecoderConfig, seq_len: int = _SEQ):
    """The reference's view of a program configuration."""
    return {
        "hidden_size": cfg.hidden_size, "vocab_size": cfg.vocab_size,
        "shared_intermediate_size": cfg.intermediate_size,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "num_hidden_layers": cfg.num_layers,
        "layer_types": [_WORDS[kind] for kind in cfg.layer_types],
        "attention_multiplier": cfg.attention_multiplier,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "logits_scaling": cfg.logits_scaling,
        "position_embedding_type": "rope" if cfg.rotary else "nope",
        "tie_word_embeddings": cfg.tie_embeddings,
        "mamba_n_heads": cfg.mamba_heads, "mamba_d_head": cfg.mamba_head_dim,
        "mamba_d_state": cfg.mamba_state, "mamba_d_conv": cfg.mamba_conv,
        "mamba_chunk_size": cfg.mamba_chunk, "mamba_n_groups": 1,
        "rms_norm_eps": cfg.rms_norm_eps, "seq_len": seq_len,
        "published": {"num_hidden_layers": cfg.published_layers},
    }


def _tiny_f32() -> mellum.DecoderConfig:
    return dataclasses.replace(mellum.granite_tiny(),
                               compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def tiny():
    cfg = _tiny_f32()
    sizes = _sizes(cfg)
    params = ref.init_params(sizes, jax.random.key(3))
    tokens = jax.random.randint(jax.random.key(4), (2, _SEQ), 4,
                                cfg.vocab_size, jnp.int32)
    return cfg, sizes, params, tokens, ref.value_and_grad(
        sizes, params, [tokens], None, 0)


def _assert_matches(loss, grads, want_loss, want_grads):
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), want in zip(flat, jax.tree.leaves(want_grads)):
        # a leaf's values against its own largest: a_log's and dt_bias's
        # gradients are 1e-8
        scale = max(float(jnp.max(jnp.abs(want))), 1e-30)
        np.testing.assert_allclose(
            got / scale, want / scale, rtol=2e-3, atol=2e-5,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("flash", [False, True], ids=["inline", "kernels"])
def test_loss_and_every_gradient_match_the_reference(tiny, flash,
                                                     monkeypatch):
    """Seeded weights from the reference's own initialiser, the program's
    tree: the loss and every leaf's gradient, with XLA's inline attention
    and with the Pallas kernels (interpreted) under the configuration's
    scale. All four multipliers are off 1 at the tiny size, the chunk is
    8 of 32 positions, and the vocabulary's 512 are a slice."""
    cfg, sizes, params, tokens, (want_loss, want_grads) = tiny
    assert jax.tree.structure(params) == jax.tree.structure(
        mellum.init(cfg, jax.random.key(0)))
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (12.0, 0.22, 8.0)
    assert cfg.attention_multiplier not in (1.0, cfg.head_dim ** -0.5)
    if flash:
        monkeypatch.setattr(fa, "beats_inline", lambda seq_len: True)
    loss, grads = jax.value_and_grad(
        lambda p: mellum.loss_fn(cfg, p, tokens))(params)
    _assert_matches(loss, grads, want_loss, want_grads)
    assert ref.param_count(sizes) == sum(
        x.size for x in jax.tree.leaves(params))


@pytest.mark.parametrize("flash", [False, True], ids=["inline", "kernels"])
def test_the_usual_scale_in_place_of_the_multiplier_fails(tiny, flash,
                                                          monkeypatch):
    """1 / sqrt(head dimension) where the configuration says
    ``attention_multiplier`` (1/8 for 1/64 at the published size, 1/4 for
    1/16 here) is another model: the attention layer's gradients leave
    the reference's by far more than the comparison's room."""
    cfg, _, params, tokens, (want_loss, want_grads) = tiny
    if flash:
        monkeypatch.setattr(fa, "beats_inline", lambda seq_len: True)
    usual = dataclasses.replace(cfg, attention_multiplier=None)
    loss, grads = jax.value_and_grad(
        lambda p: mellum.loss_fn(usual, p, tokens))(params)
    with pytest.raises(AssertionError):
        _assert_matches(loss, grads, want_loss, want_grads)
    got, want = grads["layer_2"]["wq"], want_grads["layer_2"]["wq"]
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) > 0.5


def test_the_tied_leaf_takes_the_gradient_of_both_uses(tiny):
    """The embedding's gradient is the lookup's plus the head's: the loss
    with the head handed in as a leaf of its own (the same values) gives
    the two apart, and they add up."""
    cfg, _, params, tokens, (_, want_grads) = tiny
    tied = jax.grad(lambda p: mellum.loss_fn(cfg, p, tokens))(params)
    untied_cfg = dataclasses.replace(cfg, tie_embeddings=False)
    apart = jax.grad(lambda p: mellum.loss_fn(untied_cfg, p, tokens))(
        {**params, "head": params["embed"].T})
    assert float(jnp.linalg.norm(apart["head"])) > 0
    assert float(jnp.linalg.norm(apart["embed"])) > 0
    np.testing.assert_allclose(tied["embed"],
                               apart["embed"] + apart["head"].T,
                               rtol=1e-5, atol=1e-9)
    np.testing.assert_allclose(tied["embed"], want_grads["embed"],
                               rtol=2e-3, atol=1e-8)


def _logits(cfg, params, tokens):
    x = mellum._rms_norm(mellum.decode(cfg, params, tokens),
                         params["final_norm"], cfg.rms_norm_eps)
    return x @ params["embed"].T / cfg.logits_scaling


def test_the_sliced_logits_are_the_uncut_models_at_the_slices_rows(tiny):
    """An eighth of the vocabulary is an eighth of the tied matrix's rows:
    with ids drawn from the slice, the slice's model gives the logits the
    uncut model gives at those rows."""
    cfg, sizes, _, _, _ = tiny
    whole_cfg = dataclasses.replace(cfg, vocab_size=8 * cfg.vocab_size)
    whole = ref.init_params({**sizes, "vocab_size": whole_cfg.vocab_size},
                            jax.random.key(5))
    first = 3 * cfg.vocab_size
    sliced = {**whole, "embed": whole["embed"][first:first + cfg.vocab_size]}
    tokens = jax.random.randint(jax.random.key(6), (2, _SEQ), 0,
                                cfg.vocab_size, jnp.int32)
    np.testing.assert_allclose(
        _logits(cfg, sliced, tokens),
        _logits(whole_cfg, whole, tokens + first)[
            ..., first:first + cfg.vocab_size], rtol=1e-5, atol=1e-6)


def test_the_published_model_counts_3_19_b():
    from chipbench import manifest
    config = manifest.resolve_cell("granite_train_8k").config
    assert ref.param_count(config) == 772_160_448          # 12.37 GB
    published = {**config, "num_hidden_layers": 40,
                 "layer_types": 4 * config["layer_types"],
                 "vocab_size": config["published"]["vocab_size"]}
    assert ref.param_count(published) == 3_191_396_096
    parts = ref._forward_flops_per_token(config)
    total = sum(parts.values())
    assert ref.train_flops_per_row(config) == pytest.approx(39.706e12,
                                                            rel=1e-4)
    shares = {k: round(100 * v / total, 1) for k, v in parts.items()}
    assert shares == {"mamba_projections": 28.8, "ssm": 2.4,
                      "projections": 1.3, "attention": 2.1, "mlp": 62.3,
                      "head": 3.2}
    # a layer and token forward, as the issue counts it
    assert ref._ssm_flops_per_token(config) == (
        2 * 256 * 128 + 2 * 256 * 4096 + 2 * 2 * 128 * 4096)
    flops, hbm = ref.ssm_work(config, 1)
    assert flops == 3 * 8192 * 9 * ref._ssm_flops_per_token(config)
    assert hbm == 9 * 2 * 2 * 8192 * (4096 + 4352 + 64 + 4096)


def test_the_neutral_values_leave_the_other_decoders_alone():
    """The new layer type and the four scalars at 1 (and the softmax's
    usual scale) are not in the other configurations' graphs: nothing of
    the scan, no multiplication by a constant 1."""
    for build in (mellum.mellum_tiny, mellum.laguna_tiny):
        cfg = build()
        assert (cfg.embedding_multiplier, cfg.residual_multiplier,
                cfg.logits_scaling, cfg.attention_multiplier, cfg.rotary,
                cfg.tie_embeddings) == (1.0, 1.0, 1.0, None, True, False)
        params = mellum.init(cfg, jax.random.key(0))
        assert "head" in params
        tokens = jnp.zeros((1, _SEQ), jnp.int32)
        text = jax.jit(jax.grad(
            lambda p: mellum.loss_fn(cfg, p, tokens))).lower(params).as_text(
                debug_info=True)
        assert mellum.SSM_SCOPE not in text


def test_a_mamba_layer_needs_its_heads_and_whole_chunks():
    cfg = _tiny_f32()
    params = mellum.init(cfg, jax.random.key(0))
    with pytest.raises(ValueError, match="not whole chunks of 8"):
        mellum.loss_fn(cfg, params, jnp.zeros((1, 20), jnp.int32))
    with pytest.raises(ValueError, match="mamba_heads"):
        mellum.loss_fn(dataclasses.replace(cfg, mamba_heads=0), params,
                       jnp.zeros((1, _SEQ), jnp.int32))


# -- the scan's statistics ----------------------------------------------------------


@pytest.fixture
def empty_ring():
    tracing.reset_step_stats()
    yield
    tracing.reset_step_stats()


def test_the_step_reports_every_mamba_layers_scan(empty_ring):
    """Through ``SpmdTrainer``: the loss comes back alone, the jitted step
    has a fourth output, and each step's entry holds one ``ssm_scan`` a
    Mamba layer; the registry holds each layer's last values."""
    cfg = mellum.granite_tiny()
    params = mellum.init(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, _SEQ), 0,
                                cfg.vocab_size)
    trainer = trainer_mod.SpmdTrainer(
        mesh_mod.make_mesh(devices=jax.devices()[:1]),
        functools.partial(mellum.loss_fn, cfg), params, optax.adam(1e-3))
    losses = [trainer.train_step(tokens) for _ in range(3)]
    assert all(loss.shape == () for loss in losses)
    assert len(jax.eval_shape(trainer.step_fn, trainer.params,
                              trainer.opt_state, tokens)) == 4
    trainer.block_until_ready()
    entries = tracing.step_stats()
    assert [e["step"] for e in entries] == [0, 1, 2]
    for entry in entries:
        scans = entry["stats"]["ssm_scan"]
        assert "moe_walk" not in entry["stats"]
        assert [s["layer"] for s in scans] == ["0", "1", "3"]
        for scan in scans:
            assert 0.0 < scan["end_decay_mean"] < 1.0
            assert scan["carry_abs_max"] > 0.0
    for layer in ("0", "1", "3"):
        last = entries[-1]["stats"]["ssm_scan"][("0", "1", "3").index(layer)]
        assert metrics.get("rsdl_ssm_end_decay_mean",
                           {"layer": layer}).value == pytest.approx(
                               last["end_decay_mean"])
        assert metrics.get("rsdl_ssm_carry_abs_max", {"layer": layer}
                           ).value == pytest.approx(last["carry_abs_max"])
    traced = metrics.get("rsdl_lm_ssm_total", {"kind": "chunked_xla"})
    assert traced is not None and traced.value >= 3
    assert metrics.get("rsdl_lm_ssm_chunk").value == cfg.mamba_chunk
    # the CPU, and chunks of 8: XLA's einsums
    assert metrics.get("rsdl_lm_ssm_in_vmem").value == 0
    # and XLA's pad and slices for the mixers' convolution (24 channels)
    convs = metrics.get("rsdl_lm_conv_total", {"kind": "xla"})
    assert convs is not None and convs.value >= 3


def test_the_scans_statistics_fold_without_waiting_for_the_device(
        empty_ring):
    """``ssm_scan`` entries whose arrays are not ready stay in the ring;
    the step path folds them only once they are."""
    key = ("ssm_scan", (("layer", "0"),))
    late = _Late([0.25, 3.5])
    tracing.keep_step_stats(0, {key: late})
    tracing.keep_step_stats(1, {key: np.asarray([0.5, 1.5], np.float32)})
    assert tracing.step_stats() == []
    late.ready = True
    tracing.keep_step_stats(2, {key: np.asarray([0.75, 2.5], np.float32)})
    entries = tracing.step_stats()
    assert [e["step"] for e in entries] == [0, 1, 2]
    assert [e["stats"]["ssm_scan"][0] for e in entries] == [
        {"layer": "0", "end_decay_mean": 0.25, "carry_abs_max": 3.5},
        {"layer": "0", "end_decay_mean": 0.5, "carry_abs_max": 1.5},
        {"layer": "0", "end_decay_mean": 0.75, "carry_abs_max": 2.5}]
    assert telemetry.STEP_STAT_FIELDS["ssm_scan"] == ("end_decay_mean",
                                                      "carry_abs_max")
    for name, entry in {"rsdl_ssm_end_decay_mean": ("gauge", ("layer",)),
                        "rsdl_ssm_carry_abs_max": ("gauge", ("layer",)),
                        "rsdl_lm_ssm_total": ("counter", ("kind",)),
                        "rsdl_lm_conv_total": ("counter", ("kind",)),
                        "rsdl_lm_ssm_chunk": ("gauge", ()),
                        "rsdl_lm_ssm_in_vmem": ("gauge", ())}.items():
        assert metric_names.METRIC_NAMES[name] == entry


def test_the_scope_reaches_the_compiled_step():
    """``rsdl.lm.ssm`` names the convolution's, the scan's and the gated
    norm's operations in the step's text, forward and backward; the
    mixers' projections stay under ``rsdl.lm.proj``."""
    from chipbench import xplane
    cfg = mellum.granite_tiny()
    params = mellum.init(cfg, jax.random.key(0))
    tokens = jnp.zeros((2, _SEQ), jnp.int32)
    step = jax.jit(trainer_mod.make_train_step(
        functools.partial(mellum.loss_fn, cfg), optax.adam(1e-3)))
    opt_state = optax.adam(1e-3).init(params)
    names = xplane.hlo_op_names(
        step.lower(params, opt_state, tokens).compile().as_text())
    under = [n for n in names.values()
             if xplane.under_scope(n, mellum.SSM_SCOPE)]
    assert any("transpose" in n for n in under), "the backward's"
    assert any("transpose" not in n for n in under), "the forward's"
    assert any(n.endswith("/exp") or "/exp" in n for n in under)
    assert any(xplane.under_scope(n, mellum.PROJ_SCOPE)
               for n in names.values())
