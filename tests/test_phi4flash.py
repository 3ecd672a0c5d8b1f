"""The decoder (models/mellum.py) at its SambaY configuration against the
plain reference (chipbench/references/phi4flash.py) at ``phi4flash_tiny``
on the CPU: Mamba-1 layers through the selective scan
(ops/selective_scan.py), window and full differential attention, a Gated
Memory Unit over one layer's scan output and cross-attention over one
layer's keys and values, LayerNorm, the tied head; the three controls
(no carry, ``lambda`` 0, one reader's cotangent dropped); the statistics
that ride out of the jitted step; and the other decoders left alone."""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chipbench.references import phi4flash as ref
from ray_shuffling_data_loader_tpu.models import mellum
from ray_shuffling_data_loader_tpu.ops import flash_attention as fa
from ray_shuffling_data_loader_tpu.ops import selective_scan as sscan
from ray_shuffling_data_loader_tpu.parallel import mesh as mesh_mod
from ray_shuffling_data_loader_tpu.parallel import trainer as trainer_mod
from ray_shuffling_data_loader_tpu.runtime import (metric_names, metrics,
                                                   telemetry)
from ray_shuffling_data_loader_tpu.utils import tracing

_SEQ = 32


def _sizes(cfg: mellum.DecoderConfig, seq_len: int = _SEQ):
    """The reference's view of a program configuration."""
    return {
        "hidden_size": cfg.hidden_size, "vocab_size": cfg.vocab_size,
        "intermediate_size": cfg.intermediate_size,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "num_hidden_layers": cfg.num_layers,
        "layer_types": list(cfg.layer_types),
        "published_layer_indices": list(cfg.published_indices),
        "sliding_window": cfg.sliding_window,
        "layer_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
        "mamba_expand": cfg.mamba1_width // cfg.hidden_size,
        "mamba_d_state": cfg.mamba1_state,
        "mamba_dt_rank": cfg.mamba1_dt_rank, "mamba_d_conv": cfg.mamba_conv,
        "seq_len": seq_len,
        "published": {"num_hidden_layers": cfg.published_layers},
    }


def _tiny_f32() -> mellum.DecoderConfig:
    return dataclasses.replace(mellum.phi4flash_tiny(),
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


def _program(cfg, params, tokens):
    return jax.value_and_grad(
        lambda p: mellum.loss_fn(cfg, p, tokens))(params)


def _assert_matches(loss, grads, want_loss, want_grads):
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), want in zip(flat, jax.tree.leaves(want_grads)):
        # a leaf's values against its own largest: a_log's and dt_proj's
        # gradients are 1e-8
        scale = max(float(jnp.max(jnp.abs(want))), 1e-30)
        np.testing.assert_allclose(
            got / scale, want / scale, rtol=2e-3, atol=1e-4,
            err_msg=jax.tree_util.keystr(path))


def _gap(got, want) -> float:
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.mark.parametrize("flash", [False, True], ids=["inline", "kernels"])
def test_loss_and_every_gradient_match_the_reference(tiny, flash,
                                                     monkeypatch):
    """Seeded weights from the reference's own initialiser, the program's
    tree: the loss and every leaf's gradient, with XLA's inline attention
    and with the Pallas kernels (interpreted). All six kinds of layer are
    there, the window is 8 of 32 positions and the scan's chunk 8 of
    them."""
    cfg, sizes, params, tokens, (want_loss, want_grads) = tiny
    assert jax.tree.structure(params) == jax.tree.structure(
        mellum.init(cfg, jax.random.key(0)))
    assert cfg.layer_types == (mellum.MAMBA1, mellum.SLIDING, mellum.MAMBA1,
                               mellum.FULL, mellum.GMU, mellum.CROSS)
    assert cfg.sliding_window < _SEQ and cfg.mamba_chunk < _SEQ
    if flash:
        monkeypatch.setattr(fa, "beats_inline", lambda seq_len: True)
    loss, grads = _program(cfg, params, tokens)
    _assert_matches(loss, grads, want_loss, want_grads)
    assert ref.param_count(sizes) == sum(
        x.size for x in jax.tree.leaves(params))


def test_without_the_scans_carry_the_comparison_fails(tiny, monkeypatch):
    """The control of the carry between the scan's chunks: with every
    chunk started from zero the Mamba-1 layers' gradients leave the
    reference's by far more than the comparison's room."""
    cfg, _, params, tokens, (want_loss, want_grads) = tiny
    monkeypatch.setattr(sscan, "_handed_on", jnp.zeros_like)
    jax.clear_caches()      # the scan was traced with its carry
    try:
        loss, grads = _program(cfg, params, tokens)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    with pytest.raises(AssertionError):
        _assert_matches(loss, grads, want_loss, want_grads)
    for layer in ("layer_0", "layer_2"):
        assert _gap(grads[layer]["a_log"], want_grads[layer]["a_log"]) > 0.1


@pytest.mark.parametrize("flash", [False, True], ids=["inline", "kernels"])
def test_differential_attention_is_the_written_out_form(tiny, flash,
                                                        monkeypatch):
    """One layer's differential attention alone, the program's (one call
    of the attention, one head a map over its pair's values at their own
    width, then the subtraction and the pairs' norm) against the
    reference's (both maps written out a head pair), under the window and
    over the whole row; with ``lambda`` 0, the control, they differ."""
    cfg, sizes, params, _, _ = tiny
    if flash:
        monkeypatch.setattr(fa, "beats_inline", lambda seq_len: True)
    keys = jax.random.split(jax.random.key(7), 3)
    width = cfg.num_heads * cfg.head_dim
    kv_width = cfg.num_kv_heads * cfg.head_dim
    q = jax.random.normal(keys[0], (1, _SEQ, width))
    k = jax.random.normal(keys[1], (1, _SEQ, kv_width))
    v = jax.random.normal(keys[2], (1, _SEQ, kv_width))
    for layer, kind in ((1, mellum.SLIDING), (3, mellum.FULL)):
        lp = params[f"layer_{layer}"]
        want = ref._differential(
            ref._Sizes(sizes), layer, kind, lp,
            q[0].reshape(_SEQ, cfg.num_heads, -1),
            k[0].reshape(_SEQ, cfg.num_kv_heads, -1),
            v[0].reshape(_SEQ, cfg.num_kv_heads, -1)).reshape(_SEQ, -1)
        got = mellum._differential(cfg, layer, q, k, v, lp, kind)[0]
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        lam = float(mellum._diff_lambda(cfg, layer, lp))
        start = 0.8 - 0.6 * math.exp(-0.3 * cfg.published_indices[layer])
        assert abs(lam - start) < 0.1 and lam != start
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(mellum, "_diff_lambda",
                          lambda config, layer, lp: jnp.float32(0.0))
            without = mellum._differential(cfg, layer, q, k, v, lp, kind)[0]
        assert _gap(without, want) > 0.1


def test_with_lambda_zero_the_comparison_fails(tiny, monkeypatch):
    cfg, _, params, tokens, (want_loss, want_grads) = tiny
    monkeypatch.setattr(mellum, "_diff_lambda",
                        lambda config, layer, lp: jnp.float32(0.0))
    loss, grads = _program(cfg, params, tokens)
    with pytest.raises(AssertionError):
        _assert_matches(loss, grads, want_loss, want_grads)
    assert _gap(grads["layer_1"]["wq"], want_grads["layer_1"]["wq"]) > 0.1


@pytest.mark.parametrize("reader,leaves", [
    (4, [("layer_2", "in_proj"), ("layer_2", "a_log")]),
    (5, [("layer_3", "wk"), ("layer_3", "wv")])],
    ids=["memory", "kv"])
def test_a_dropped_readers_cotangent_fails_the_comparison(tiny, reader,
                                                          leaves):
    """``M``'s cotangent is its own layer's plus the memory unit's, ``K,
    V``'s their own layer's plus the cross layer's: with one reader's
    share left out of the sum (the reference's ``dropped``) the gradients
    of the layer that made the tensor no longer match, and those of every
    later layer still do."""
    cfg, sizes, params, tokens, _ = tiny
    loss, grads = _program(cfg, params, tokens)
    want_loss, dropped = ref.value_and_grad(sizes, params, [tokens], None,
                                            0, dropped=(reader,))
    with pytest.raises(AssertionError):
        _assert_matches(loss, grads, want_loss, dropped)
    for layer, leaf in leaves:
        assert _gap(grads[layer][leaf], dropped[layer][leaf]) > 0.02
    for layer in ("layer_4", "layer_5"):
        later = jax.tree.map(_gap, grads[layer], dropped[layer])
        assert max(jax.tree.leaves(later)) < 1e-3
    assert ref.sources(sizes["layer_types"]) == {4: 2, 5: 3}


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

    def logits(cfg, params, tokens):
        x = mellum._norm(cfg, mellum.decode(cfg, params, tokens), params,
                         "final_norm")
        return x @ params["embed"].T

    np.testing.assert_allclose(
        logits(cfg, sliced, tokens),
        logits(whole_cfg, whole, tokens + first)[
            ..., first:first + cfg.vocab_size], rtol=1e-5, atol=1e-6)


def test_the_counts_of_the_cut_and_of_the_published_model():
    from chipbench import manifest
    config = manifest.resolve_cell("phi4flash_train_8k").config
    assert ref.param_count(config) == 697_073_792          # 11.15 GB
    full = mellum.phi4_mini_flash_junction()
    shapes = jax.eval_shape(lambda k: mellum.init(full, k),
                            jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 697_073_792
    published = {
        **config, "num_hidden_layers": 32,
        "vocab_size": config["published"]["vocab_size"],
        "layer_types": 8 * ["mamba1", "sliding_attention"]
        + ["mamba1", "full_attention"] + 7 * ["gmu", "cross"]}
    assert ref.param_count(published) == 3_852_457_984
    parts = ref._forward_flops_per_token(config)
    total = sum(parts.values())
    assert ref.train_flops_per_row(config) == pytest.approx(37.553e12,
                                                            rel=1e-4)
    shares = {k: round(100 * v / total, 1) for k, v in parts.items()}
    assert shares == {"mamba_projections": 10.8, "scan": 0.1,
                      "gmu_projections": 3.4, "projections": 6.9,
                      "attention": 8.7, "mlp": 61.8, "head": 8.4}
    # a token and layer forward, as the issue counts it
    assert ref._scan_ops_per_token(config) == 6 * 5120 * 16 + 2 * 4 * 5120
    ops, hbm = ref.sscan_work(config, 1)
    assert ops == 3 * 8192 * 2 * ref._scan_ops_per_token(config)
    assert hbm == 2 * 2 * 2 * 8192 * (4 * 5120 + 2 * 16)
    # 40 maps' scores at 64 and 40 maps' products with values at 128, over
    # the triangle twice (the full and the cross layer) and the band once
    flops, _ = ref.attention_work(config, 1)
    keys = 2 * (8192 + 1) / 2 + (512 * 513 / 2 + (8192 - 512) * 512) / 8192
    assert flops == pytest.approx(
        3 * 8192 * (2 * 64 * 40 + 2 * 128 * 40) * keys)


def test_the_neutral_values_leave_the_other_decoders_alone(monkeypatch):
    """The three new layer types, LayerNorm and the differential switch
    are not in the other configurations' graphs: nothing of the selective
    scan, of a memory unit or of a bias. Nor of values wider than the
    keys: with the kernels taken, the step's text is letter for letter
    the one whose every attention names its value heads (as many as its
    key heads), which no layer of these does."""
    named = []

    def naming(launch, kv_heads_at):
        def launched(*operands, num_v_heads, **options):
            named.append(num_v_heads)
            return launch(*operands, num_v_heads=operands[kv_heads_at],
                          **options)
        return launched

    for build in (mellum.mellum_tiny, mellum.laguna_tiny,
                  mellum.granite_tiny, mellum.lfm2_tiny):
        cfg = build()
        assert (cfg.norm, cfg.differential, cfg.published_indices,
                cfg.mamba1_width) == (mellum.RMS_NORM, False, None, 0)
        params = mellum.init(cfg, jax.random.key(0))
        assert not any(name.endswith("_bias") and "norm" in name
                       for name in params)
        tokens = jnp.zeros((1, _SEQ), jnp.int32)

        def text(debug_info=False):
            return jax.jit(jax.grad(lambda p: mellum.loss_fn(
                cfg, p, tokens))).lower(params).as_text(
                    debug_info=debug_info)

        inline = text(debug_info=True)
        assert mellum.SSCAN_SCOPE not in inline
        assert mellum.GMU_SCOPE not in inline
        assert "_diff_combine" not in inline and "_gmu_gated" not in inline
        if build not in (mellum.laguna_tiny, mellum.lfm2_tiny):
            continue    # windows and a gate; q and k normed, plain rotary
        with monkeypatch.context() as patch:
            patch.setattr(fa, "beats_inline", lambda seq_len: True)
            kernels = text()
            assert "_diff_combine" not in kernels and not named
            for launch, at in (("grouped_forward", 4),
                               ("grouped_backward", 7)):
                patch.setattr(fa, launch, naming(getattr(fa, launch), at))
            jax.clear_caches()      # the attentions' jits: traced again
            assert text() == kernels
            # forward and backward, once a shape the layers' jits see
            assert len(named) >= 2 and set(named) == {None}
            named.clear()


def test_the_attention_counter_tells_wide_values_from_the_keys_own(
        monkeypatch):
    """One count a layer traced: the junction's three differential layers
    hand the kernels (or the inline path) values of their own width and
    head count, ``values="wide"``; the attention layer of a Granite cut
    hands them values shaped as its keys, ``"same"``."""
    def counts():
        found = {}
        for kind in ("inline", "window", "full"):
            for values in ("wide", "same"):
                metric = metrics.get("rsdl_lm_attention_total",
                                     {"kind": kind, "values": values})
                found[kind, values] = 0 if metric is None else metric.value
        return found

    def traced(cfg):
        before = counts()
        jax.eval_shape(lambda p, t: mellum.loss_fn(cfg, p, t),
                       mellum.init(cfg, jax.random.key(0)),
                       jnp.zeros((1, _SEQ), jnp.int32))
        return {key: count - before[key] for key, count in counts().items()
                if count != before[key]}

    assert traced(mellum.phi4flash_tiny()) == {("inline", "wide"): 3}
    assert traced(mellum.granite_tiny()) == {("inline", "same"): 1}
    monkeypatch.setattr(fa, "beats_inline", lambda seq_len: True)
    assert traced(mellum.phi4flash_tiny()) == {("window", "wide"): 1,
                                               ("full", "wide"): 2}
    assert traced(mellum.granite_tiny()) == {("full", "same"): 1}
    assert metric_names.METRIC_NAMES["rsdl_lm_attention_total"] == (
        "counter", ("kind", "values"))


def test_what_the_decoder_refuses():
    cfg = _tiny_f32()
    params = mellum.init(cfg, jax.random.key(0))
    tokens = jnp.zeros((1, _SEQ), jnp.int32)

    def with_types(*types):
        return dataclasses.replace(
            cfg, layer_types=types, mlp_layer_types=len(types) * ("dense",),
            published_indices=tuple(range(len(types))))

    with pytest.raises(ValueError, match="gmu with no mamba1"):
        mellum.loss_fn(with_types(mellum.FULL, mellum.GMU), params, tokens)
    with pytest.raises(ValueError, match="cross layer with no"):
        mellum.loss_fn(with_types(mellum.MAMBA1, mellum.SLIDING,
                                  mellum.CROSS), params, tokens)
    with pytest.raises(ValueError, match="published index"):
        mellum.loss_fn(dataclasses.replace(cfg, published_indices=(0, 1)),
                       params, tokens)
    with pytest.raises(ValueError, match="mamba1_width"):
        mellum.loss_fn(dataclasses.replace(cfg, mamba1_dt_rank=0), params,
                       tokens)
    with pytest.raises(ValueError, match="without positions"):
        mellum.loss_fn(dataclasses.replace(cfg, rotary=True), params, tokens)
    with pytest.raises(ValueError, match="unknown norm"):
        mellum.loss_fn(dataclasses.replace(cfg, norm="batch"), params,
                       tokens)
    with pytest.raises(ValueError, match="not whole chunks of 8"):
        mellum.loss_fn(cfg, params, jnp.zeros((1, 20), jnp.int32))
    from jax.sharding import Mesh
    two = Mesh(np.array(jax.devices()[:2]), ("data",))
    with pytest.raises(NotImplementedError, match="mesh of 2 devices"):
        mellum.decode(cfg, params, tokens, two)


# -- what rides out of the jitted step ------------------------------------------------


@pytest.fixture
def empty_ring():
    tracing.reset_step_stats()
    yield
    tracing.reset_step_stats()


def test_the_step_reports_both_scans_and_the_three_lambdas(empty_ring):
    """Through ``SpmdTrainer``: each step's entry holds one ``ssm_scan`` a
    Mamba-1 layer and one ``diff_attention`` a differential layer; the
    registry holds each layer's last values, and the counters of what was
    traced."""
    cfg = mellum.phi4flash_tiny()
    params = mellum.init(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, _SEQ), 0,
                                cfg.vocab_size)
    shared = {kind: getattr(metrics.get("rsdl_lm_shared_total",
                                        {"kind": kind}), "value", 0)
              for kind in ("memory", "kv")}
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
        assert [s["layer"] for s in scans] == ["0", "2"]
        for scan in scans:
            assert 0.0 < scan["end_decay_mean"] < 1.0
            assert scan["carry_abs_max"] > 0.0
        lambdas = entry["stats"]["diff_attention"]
        assert [row["layer"] for row in lambdas] == ["1", "3", "5"]
        for row, index in zip(lambdas, (1, 17, 19)):
            assert row["lambda"] == pytest.approx(
                mellum.lambda_init(index), abs=0.1)
    last = {row["layer"]: row["lambda"]
            for row in entries[-1]["stats"]["diff_attention"]}
    for layer, value in last.items():
        assert metrics.get("rsdl_lm_diff_lambda",
                           {"layer": layer}).value == pytest.approx(value)
    # a compile of the step traces each reader once, forward and again
    for kind in ("memory", "kv"):
        traced = metrics.get("rsdl_lm_shared_total", {"kind": kind}).value
        assert traced > shared[kind]
    traced = metrics.get("rsdl_lm_ssm_total", {"kind": "selective_xla"})
    assert traced is not None and traced.value >= 2
    convs = metrics.get("rsdl_lm_conv_total", {"kind": "xla"})
    assert convs is not None and convs.value >= 2
    assert telemetry.STEP_STAT_FIELDS["diff_attention"] == ("lambda",)
    for name, entry in {"rsdl_lm_diff_lambda": ("gauge", ("layer",)),
                        "rsdl_lm_shared_total": ("counter", ("kind",)),
                        "rsdl_lm_ssm_total": ("counter", ("kind",))}.items():
        assert metric_names.METRIC_NAMES[name] == entry


def test_the_scopes_reach_the_compiled_step():
    """``rsdl.lm.sscan`` names the convolution's, the softplus's, the
    scan's and the gate's operations in the step's text, forward and
    backward, ``rsdl.lm.gmu`` the memory unit's; the projections stay
    under ``rsdl.lm.proj``, attention under ``rsdl.lm.attention``, and
    nothing is under Mamba-2's ``rsdl.lm.ssm``."""
    from chipbench import xplane
    cfg = mellum.phi4flash_tiny()
    params = mellum.init(cfg, jax.random.key(0))
    tokens = jnp.zeros((2, _SEQ), jnp.int32)
    step = jax.jit(trainer_mod.make_train_step(
        functools.partial(mellum.loss_fn, cfg), optax.adam(1e-3)))
    opt_state = optax.adam(1e-3).init(params)
    names = xplane.hlo_op_names(
        step.lower(params, opt_state, tokens).compile().as_text())
    for scope in (mellum.SSCAN_SCOPE, mellum.GMU_SCOPE):
        under = [n for n in names.values() if xplane.under_scope(n, scope)]
        assert any("transpose" in n for n in under), f"{scope}: backward"
        assert any("transpose" not in n for n in under), f"{scope}: forward"
    for scope in (mellum.PROJ_SCOPE, mellum.ATTENTION_SCOPE,
                  mellum.MLP_SCOPE, mellum.HEAD_SCOPE):
        assert any(xplane.under_scope(n, scope) for n in names.values())
    assert not any(xplane.under_scope(n, mellum.SSM_SCOPE)
                   for n in names.values())
