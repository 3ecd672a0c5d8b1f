"""BERT-style masked-LM — sequence model family (BASELINE.json config 4:
BERT-base MLM on pre-tokenized Wikipedia Parquet).

The reference treats sequence workloads purely as "batch pre-tokenized
fixed-length rows" (SURVEY.md §5: no sequence-parallel machinery exists or
is needed); the loader delivers (batch, seq_len) int token tables and this
model consumes them. Same functional API as the other families: ``init``,
``apply``, ``loss_fn``, ``param_specs``.

TPU-first choices:
- Megatron TP sharding spec: QKV and FFN-in split column-wise over
  "model", attention-out and FFN-out split row-wise, so each transformer
  block needs exactly two psums; embeddings column-sharded.
- bf16 compute / f32 params & softmax accumulation; static seq_len, fused
  QKV projection. Attention reads that projection in place through the
  Pallas flash kernels (ops/flash_attention.py: scores and weights stay in
  VMEM) on the chip from the measured crossover up, and is two batched
  matmuls around a materialized softmax below it and off the chip.
- MLM loss masks with a -100 ignore-id convention (positions to predict
  carry their target id, others -100) and projects only those positions
  onto the vocabulary, in blocks (``_masked_nll``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ray_shuffling_data_loader_tpu.ops import flash_attention, on_tpu
from ray_shuffling_data_loader_tpu.parallel.mesh import DATA_AXIS
from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics
from ray_shuffling_data_loader_tpu.runtime import telemetry

IGNORE_ID = -100

# The names a device trace shows the step's operations under: the head's
# (with the loss's count and division) and a layer's attention's; the
# token and position embeddings with their layer norm and the mask's bias;
# a layer's qkv and output products, and its FFN's two products and
# activation, each with the residual add and layer norm that close its
# half of the layer. The three below are entered outside any ``jit`` of
# their own, so a forward operation's name holds them inside the
# transform's, ``jvp(rsdl.bert.mlp)`` (``chipbench/readers/
# wrapped_scopes.py`` reads both forms).
MLM_HEAD_SCOPE = telemetry.step_scope("rsdl.bert.mlm_head")
ATTENTION_SCOPE = telemetry.step_scope("rsdl.bert.attention")
EMBED_SCOPE = telemetry.step_scope("rsdl.bert.embed")
PROJ_SCOPE = telemetry.step_scope("rsdl.bert.proj")
MLP_SCOPE = telemetry.step_scope("rsdl.bert.mlp")
# The loss walks a row's masked positions in this many blocks at most: at
# the paper's 15 % the fullest row of a batch ends in the second.
_MLM_BLOCKS_PER_ROW = 8


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30_522
    hidden_dim: int = 768
    num_layers: int = 12
    num_heads: int = 12
    ffn_dim: int = 3072
    max_seq_len: int = 512
    compute_dtype: Any = jnp.bfloat16
    # Rematerialize each transformer layer in the backward pass instead of
    # saving its activations — trades ~30% more FLOPs for O(num_layers)
    # less activation HBM, the standard long-context/large-batch knob
    # (jax.checkpoint; composes with ring-attention SP, whose custom VJP
    # already recomputes per hop).
    remat: bool = False

    @property
    def head_dim(self) -> int:
        return self.hidden_dim // self.num_heads


def bert_base() -> BertConfig:
    return BertConfig()


def bert_tiny() -> BertConfig:
    """For tests/CPU smoke runs."""
    return BertConfig(vocab_size=1000, hidden_dim=64, num_layers=2,
                      num_heads=4, ffn_dim=128, max_seq_len=64)


def init(config: BertConfig, key: jax.Array) -> Dict[str, Any]:
    h, f = config.hidden_dim, config.ffn_dim
    keys = iter(jax.random.split(key, 3 + config.num_layers * 6))
    scale = 0.02
    params: Dict[str, Any] = {
        "token_emb": scale * jax.random.normal(
            next(keys), (config.vocab_size, h), jnp.float32),
        "pos_emb": scale * jax.random.normal(
            next(keys), (config.max_seq_len, h), jnp.float32),
        "emb_ln": {"scale": jnp.ones((h,), jnp.float32),
                   "bias": jnp.zeros((h,), jnp.float32)},
    }
    for layer in range(config.num_layers):
        lp = {
            "qkv_w": scale * jax.random.normal(next(keys), (h, 3 * h),
                                               jnp.float32),
            "qkv_b": jnp.zeros((3 * h,), jnp.float32),
            "attn_out_w": scale * jax.random.normal(next(keys), (h, h),
                                                    jnp.float32),
            "attn_out_b": jnp.zeros((h,), jnp.float32),
            "ln1": {"scale": jnp.ones((h,), jnp.float32),
                    "bias": jnp.zeros((h,), jnp.float32)},
            "ffn_in_w": scale * jax.random.normal(next(keys), (h, f),
                                                  jnp.float32),
            "ffn_in_b": jnp.zeros((f,), jnp.float32),
            "ffn_out_w": scale * jax.random.normal(next(keys), (f, h),
                                                   jnp.float32),
            "ffn_out_b": jnp.zeros((h,), jnp.float32),
            "ln2": {"scale": jnp.ones((h,), jnp.float32),
                    "bias": jnp.zeros((h,), jnp.float32)},
        }
        params[f"layer_{layer}"] = lp
    params["mlm_bias"] = jnp.zeros((config.vocab_size,), jnp.float32)
    return params


def param_specs(config: BertConfig, model_axis: str = "model"
                ) -> Dict[str, Any]:
    ln = {"scale": P(None), "bias": P(None)}
    specs: Dict[str, Any] = {
        "token_emb": P(None, model_axis),
        "pos_emb": P(None, model_axis),
        "emb_ln": dict(ln),
        "mlm_bias": P(None),
    }
    for layer in range(config.num_layers):
        specs[f"layer_{layer}"] = {
            "qkv_w": P(None, model_axis),
            "qkv_b": P(model_axis),
            "attn_out_w": P(model_axis, None),
            "attn_out_b": P(None),
            "ln1": dict(ln),
            "ffn_in_w": P(None, model_axis),
            "ffn_in_b": P(model_axis),
            "ffn_out_w": P(model_axis, None),
            "ffn_out_b": P(None),
            "ln2": dict(ln),
        }
    return specs


def _layer_norm(x, scale, bias, eps=1e-12):
    xf = x.astype(jnp.float32)
    mean = xf.mean(axis=-1, keepdims=True)
    var = xf.var(axis=-1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps) * scale + bias
    return out.astype(x.dtype)


def _split_heads(qkv, num_heads: int):
    """(B, S, 3 x hidden) -> q, k, v, each (B, H, S, D)."""
    b, s, width = qkv.shape
    return [x.reshape(b, s, num_heads, -1).transpose(0, 2, 1, 3)
            for x in jnp.split(qkv, 3, axis=-1)]


def _merge_heads(x):
    """(B, H, S, D) -> (B, S, hidden)."""
    b, _, s, _ = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, -1)


# The attentions are jitted for the scope's sake, as ``_masked_nll_fwd``
# below: inside a program of its own the name stays as written.
@functools.partial(jax.jit, static_argnums=(2,))
def _inline_attention(qkv, bias, num_heads: int):
    """Attention of a fused projection ``qkv`` (B, S, 3 x hidden) as XLA
    has it: two batched matmuls around a float32 softmax of materialized
    (B, H, S, S) scores; the backward is autodiff's."""
    with jax.named_scope(ATTENTION_SCOPE):
        q, k, v = _split_heads(qkv, num_heads)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
        scores = scores / jnp.sqrt(q.shape[-1])
        if bias is not None:
            scores = scores + bias
        weights = jax.nn.softmax(scores, axis=-1).astype(qkv.dtype)
        return _merge_heads(jnp.einsum("bhqk,bhkd->bhqd", weights, v))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _flash_attention(qkv, bias, num_heads: int):
    """``_inline_attention``'s result from the Pallas flash kernels: they
    read q, k and v where the projection left them and keep a block's
    scores and weights in VMEM, forward and backward."""
    return _flash_attention_fwd(qkv, bias, num_heads)[0]


@functools.partial(jax.jit, static_argnums=(2,))
def _flash_attention_fwd(qkv, bias, num_heads):
    with jax.named_scope(ATTENTION_SCOPE):
        out, lse = flash_attention.qkv_forward(qkv, bias, num_heads,
                                               interpret=not on_tpu())
    saved = out if flash_attention.saves_out(qkv, num_heads) else None
    return out, (qkv, bias, saved, lse)


@functools.partial(jax.jit, static_argnums=(0,))
def _flash_attention_bwd(num_heads, residuals, cotangent):
    qkv, bias, out, lse = residuals
    with jax.named_scope(ATTENTION_SCOPE):
        return flash_attention.qkv_backward(qkv, bias, out, lse, cotangent,
                                            num_heads, interpret=not on_tpu())


def _counted_flash_attention_bwd(num_heads, residuals, cotangent):
    # Counted here, once a layer: the program under it is traced once.
    flash_attention.count_backward(flash_attention.qkv_backward_kind(
        residuals[0], num_heads, interpret=not on_tpu()))
    return _flash_attention_bwd(num_heads, residuals, cotangent)


_flash_attention.defvjp(_flash_attention_fwd, _counted_flash_attention_bwd)


def _attention(qkv, bias, num_heads: int, mesh: Optional[Mesh]):
    """A layer's attention, (B, S, 3 x hidden) -> (B, S, hidden), by what
    the trace can observe: the flash kernels where they beat the inline
    path (``flash_attention.beats_inline``: on the chip, from the measured
    sequence length up) and the bias is none or key-side, else inline.
    GSPMD cannot partition a Mosaic kernel, so under a ``mesh`` of more
    than one device the kernels run once per shard of its data axis (rows
    are independent: nothing is exchanged)."""
    key_side = bias is None or bias.shape == (qkv.shape[0], 1, 1,
                                               qkv.shape[1])
    flash = key_side and flash_attention.beats_inline(qkv.shape[1])
    # Counted when a layer is traced, not when it runs.
    rt_metrics.counter(
        "rsdl_bert_attention_total",
        "BERT layers' attentions traced, by implementation: the Pallas "
        "flash kernels or XLA's inline softmax over materialized scores",
        kind="flash" if flash else "inline").inc()
    if not flash:
        return _inline_attention(qkv, bias, num_heads)
    attend = functools.partial(_flash_attention, num_heads=num_heads)
    if mesh is not None and mesh.size > 1:
        attend = jax.shard_map(
            attend, mesh=mesh, in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=P(DATA_AXIS), check_vma=False)
    return attend(qkv, bias)


def encode(config: BertConfig, params: Dict[str, Any],
           token_ids: jax.Array,
           attention_mask: jax.Array = None,
           attention_fn=None,
           mesh: Optional[Mesh] = None) -> jax.Array:
    """token_ids (B, S) int32 -> hidden states (B, S, hidden_dim) in the
    compute dtype, after the last transformer layer.

    ``attention_mask`` (B, S) with 1 = attend, 0 = padding; None = all 1.

    ``attention_fn(q, k, v, bias) -> (B, H, S, D)`` swaps the attention
    implementation — e.g. ``ops.ring_attention.make_attention_fn(mesh,
    seq_axis)`` for sequence-parallel long-context runs. None = the model
    chooses (:func:`_attention`): the Pallas flash kernels on the chip
    from ``flash_attention.FLASH_MIN_SEQ_LEN`` up, XLA's inline attention
    below it and on every other backend.

    ``mesh``: the mesh the calling step is jitted over, when it spans more
    than one device (``ops/embedding.py:lookup``'s convention): a trace
    cannot see it, and the kernels must be told.
    """
    dtype = config.compute_dtype
    b, s = token_ids.shape
    nh = config.num_heads

    with jax.named_scope(EMBED_SCOPE):
        x = (jnp.take(params["token_emb"], token_ids, axis=0, mode="clip")
             + params["pos_emb"][:s][None, :, :]).astype(dtype)
        x = _layer_norm(x, params["emb_ln"]["scale"],
                        params["emb_ln"]["bias"])

        if attention_mask is None:
            bias = None  # no mask: skip the zero-add (and any SP rotation)
        else:
            bias = jnp.where(attention_mask[:, None, None, :] > 0, 0.0,
                             -1e9).astype(jnp.float32)

    def layer_fn(x, lp, bias):
        with jax.named_scope(PROJ_SCOPE):
            qkv = x @ lp["qkv_w"].astype(dtype) + lp["qkv_b"].astype(dtype)
        if attention_fn is not None:
            with jax.named_scope(ATTENTION_SCOPE):
                attended = _merge_heads(
                    attention_fn(*_split_heads(qkv, nh), bias))
        else:
            attended = _attention(qkv, bias, nh, mesh)
        with jax.named_scope(PROJ_SCOPE):
            attn_out = (attended @ lp["attn_out_w"].astype(dtype)
                        + lp["attn_out_b"].astype(dtype))
            x = _layer_norm(x + attn_out, lp["ln1"]["scale"],
                            lp["ln1"]["bias"])
        with jax.named_scope(MLP_SCOPE):
            ffn = jax.nn.gelu(x @ lp["ffn_in_w"].astype(dtype)
                              + lp["ffn_in_b"].astype(dtype))
            ffn = (ffn @ lp["ffn_out_w"].astype(dtype)
                   + lp["ffn_out_b"].astype(dtype))
            return _layer_norm(x + ffn, lp["ln2"]["scale"],
                               lp["ln2"]["bias"])

    if config.remat:
        layer_fn = jax.checkpoint(layer_fn)
    for layer in range(config.num_layers):
        x = layer_fn(x, params[f"layer_{layer}"], bias)
    return x


def _head(x: jax.Array, token_emb: jax.Array, mlm_bias: jax.Array
          ) -> jax.Array:
    """Hidden states (B, n, h) -> float32 logits (B, n, vocab): the MLM
    head, tied to the token embedding (standard BERT)."""
    with jax.named_scope(MLM_HEAD_SCOPE):
        logits = jnp.einsum("bsh,vh->bsv", x, token_emb.astype(x.dtype))
        return logits.astype(jnp.float32) + mlm_bias


def apply(config: BertConfig, params: Dict[str, Any],
          token_ids: jax.Array,
          attention_mask: jax.Array = None,
          attention_fn=None,
          mesh: Optional[Mesh] = None) -> jax.Array:
    """token_ids (B, S) int32 -> logits (B, S, vocab); the arguments are
    :func:`encode`'s."""
    x = encode(config, params, token_ids, attention_mask, attention_fn, mesh)
    return _head(x, params["token_emb"], params["mlm_bias"])


def _block_nll(x, token_emb, mlm_bias, targets):
    """Summed cross-entropy of the positions of ``x`` (B, n, h) whose
    ``targets`` (B, n) are not ``IGNORE_ID``."""
    mask = targets != IGNORE_ID
    logp = jax.nn.log_softmax(_head(x, token_emb, mlm_bias), axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.where(mask, targets, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(mask, -picked, 0.0))


def mlm_block_size(seq_len: int) -> int:
    """Positions of a row in one block of the loss's walk: an eighth of
    the row, rounded up to the TPU's sublane tile."""
    return 8 * -(-seq_len // (8 * _MLM_BLOCKS_PER_ROW))


def _compact(x, mlm_targets, block):
    """Each row's positions that carry a target moved to its front (the
    batch axis stays leading: a batch sharded over a data mesh exchanges
    nothing), padded to whole blocks of ``block`` positions: hidden
    states, targets, the order taken, and how many leading blocks hold a
    target in some row."""
    with jax.named_scope(MLM_HEAD_SCOPE):
        pad = -x.shape[1] % block
        order = jnp.argsort(mlm_targets == IGNORE_ID, axis=1, stable=True)
        xs = jnp.take_along_axis(x, order[..., None], axis=1)
        ts = jnp.take_along_axis(mlm_targets, order, axis=1)
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
        ts = jnp.pad(ts, ((0, 0), (0, pad)), constant_values=IGNORE_ID)
        fullest = jnp.max(jnp.sum(ts != IGNORE_ID, axis=1))
        return xs, ts, order, (fullest + block - 1) // block


def _block_of(a, k, block):
    return jax.lax.dynamic_slice_in_dim(a, k * block, block, axis=1)


@jax.custom_vjp
def _masked_nll(x, token_emb, mlm_bias, mlm_targets):
    """Summed cross-entropy over the positions of ``x`` (B, S, h) whose
    ``mlm_targets`` (B, S) are not ``IGNORE_ID``: what
    ``_block_nll`` gives over all of them at once, walked a block of each
    row at a time and only as far as the fullest row's targets reach.
    The walk stops on the device, from the mask alone, so every mask gets
    the dense head's loss and gradients up to summation order: none
    projects nothing, all masked walks every block. The backward makes a
    block's logits again, so nothing (positions, vocab) outlives a block.
    """
    return _masked_nll_fwd(x, token_emb, mlm_bias, mlm_targets)[0]


# The forward and the backward are jitted for their names' sake: a scope
# entered straight under a transformation reaches the compiled step as
# ``jvp(rsdl.bert.mlm_head)``, which no reader of a trace looks for; inside
# a program of its own (as inside a loop's body) it stays as written.
@jax.jit
def _masked_nll_fwd(x, token_emb, mlm_bias, mlm_targets):
    block = mlm_block_size(x.shape[1])
    xs, ts, order, blocks = _compact(x, mlm_targets, block)
    with jax.named_scope(MLM_HEAD_SCOPE):
        emb = token_emb.astype(x.dtype)

    def add_block(k, total):
        with jax.named_scope(MLM_HEAD_SCOPE):
            return total + _block_nll(_block_of(xs, k, block), emb,
                                      mlm_bias, _block_of(ts, k, block))

    total = jax.lax.fori_loop(0, blocks, add_block, jnp.float32(0))
    return total, (xs, ts, order, blocks, emb, mlm_bias)


@jax.jit
def _masked_nll_bwd(residuals, cotangent):
    xs, ts, order, blocks, emb, mlm_bias = residuals
    block = mlm_block_size(order.shape[1])

    def add_block(k, grads):
        d_xs, d_emb, d_bias = grads
        with jax.named_scope(MLM_HEAD_SCOPE):
            targets = _block_of(ts, k, block)
            _, vjp = jax.vjp(
                lambda x, e, b: _block_nll(x, e, b, targets),
                _block_of(xs, k, block), emb, mlm_bias)
            dx, de, db = vjp(cotangent)
            return (jax.lax.dynamic_update_slice_in_dim(
                        d_xs, dx, k * block, axis=1),
                    d_emb + de.astype(jnp.float32), d_bias + db)

    with jax.named_scope(MLM_HEAD_SCOPE):
        zeros = (jnp.zeros_like(xs), jnp.zeros(emb.shape, jnp.float32),
                 jnp.zeros_like(mlm_bias))
    d_xs, d_emb, d_bias = jax.lax.fori_loop(0, blocks, add_block, zeros)
    with jax.named_scope(MLM_HEAD_SCOPE):
        back = jnp.argsort(order, axis=1)
        d_x = jnp.take_along_axis(d_xs[:, :order.shape[1]],
                                  back[..., None], axis=1)
    return d_x, d_emb, d_bias, None


_masked_nll.defvjp(_masked_nll_fwd, _masked_nll_bwd)


def loss_fn(config: BertConfig, params: Dict[str, Any],
            token_ids: jax.Array, mlm_targets: jax.Array,
            attention_mask: jax.Array = None,
            attention_fn=None,
            mesh: Optional[Mesh] = None) -> jax.Array:
    """Masked-LM cross-entropy over positions where targets != IGNORE_ID.
    Only those positions are projected onto the vocabulary
    (:func:`_masked_nll`). ``attention_fn`` and ``mesh`` are
    :func:`encode`'s."""
    seq_len = token_ids.shape[1]
    block = mlm_block_size(seq_len)
    # Counted when a step is traced, not when it runs.
    rt_metrics.counter(
        "rsdl_mlm_head_total",
        "Masked-LM losses traced, by how the head walks the vocabulary "
        "projection", kind="blocked").inc()
    rt_metrics.gauge(
        "rsdl_mlm_head_block_positions",
        "Positions of a row in one block of the masked-LM head's walk, "
        "last loss traced").set(block)
    rt_metrics.gauge(
        "rsdl_mlm_head_blocks_per_row",
        "Blocks the masked-LM head's walk takes over a fully masked row, "
        "last loss traced").set(-(-seq_len // block))
    x = encode(config, params, token_ids, attention_mask, attention_fn, mesh)
    total = _masked_nll(x, params["token_emb"], params["mlm_bias"],
                        mlm_targets.astype(jnp.int32))
    with jax.named_scope(MLM_HEAD_SCOPE):
        count = jnp.maximum(jnp.sum(mlm_targets != IGNORE_ID), 1)
        return total / count
