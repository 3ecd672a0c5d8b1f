"""A sparse-expert causal decoder with window and full attention mixed:
one chip's share of a Mellum-2-style language model.

Per layer, on the residual stream: RMSNorm, grouped-query attention
(``num_heads`` query heads over ``num_kv_heads`` key/value heads, no
biases) with rotary positions, then RMSNorm and a sparse-expert SwiGLU
layer with no shared expert. ``layer_types`` gives each layer's attention:
``sliding_attention`` is causal within the last ``sliding_window`` keys
under plain rotary positions, ``full_attention`` is causal over the whole
row under YaRN's (interpolated and extrapolated frequencies blended once,
whatever the sequence length; cos and sin scaled by its attention factor).
After the last layer RMSNorm and an untied head; the loss is the mean
next-token negative log-likelihood. Same functional API as the other
families: ``init``, ``loss_fn``.

Under expert parallelism a chip holds ``experts_held = (first, count)`` of
the router's ``num_experts`` and a slice of the vocabulary: the expert
layer routes over all the experts and adds what the held ones give
(``ops/moe.py``), and the logits, ids and loss are over the slice. On one
chip the layer runs without its exchange.

TPU-first choices:
- bf16 compute, float32 parameters; the router, every softmax, the norms
  and the rotary tables in float32.
- Attention through the blocked Pallas kernels (``ops/flash_attention.py``)
  on the chip: they read q, k and v where the projections left them, fetch
  a key/value head once for its eight query heads, and visit only the key
  blocks a query block can see (a window layer's walk is as long as its
  band). Off the chip, and below the measured crossover, XLA's masked
  softmax over materialized scores.
- Every layer is made again in the backward pass (``jax.checkpoint``), a
  half at a time: a half keeps its bf16 input and nothing else.
- The head's loss walks blocks of tokens, forward and backward, so that no
  (tokens, vocabulary) float32 array outlives a block.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_shuffling_data_loader_tpu.ops import flash_attention, moe, on_tpu
from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics

IGNORE_ID = -100
SLIDING, FULL = "sliding_attention", "full_attention"

# The names a device trace shows a layer's attention's, its expert
# layer's (``ops/moe.py``) and the head's operations under.
ATTENTION_SCOPE = "rsdl.lm.attention"
MOE_SCOPE = moe.SCOPE
HEAD_SCOPE = "rsdl.lm.head"


@dataclasses.dataclass(frozen=True)
class YarnConfig:
    """YaRN's rotary parameters (Peng et al. 2023), as a model's
    ``rope_parameters`` states them."""
    factor: float = 16.0
    original_max_position_embeddings: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.2772588722239782


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 24_576
    hidden_size: int = 2304
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL)
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    num_experts: int = 64                    # the router's width
    experts_held: Tuple[int, int] = (0, 16)  # (first, count) held here
    top_k: int = 8
    expert_width: int = 896
    rms_norm_eps: float = 1e-6
    rope_theta: float = 500_000.0
    yarn: YarnConfig = YarnConfig()
    compute_dtype: Any = jnp.bfloat16
    published_layers: int = 28    # the uncut depth: scales ``init`` only

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)


def mellum2_ep4_share() -> MellumConfig:
    """Mellum2-12B-A2.5B at its published widths, cut to one chip of four
    that share each layer by expert parallelism: 16 of the 64 experts, a
    quarter of the 98,304-id vocabulary, and one period of the layer
    pattern (three window layers, one full) of the 28 layers."""
    return MellumConfig()


def mellum_tiny() -> MellumConfig:
    """For tests/CPU smoke runs: the same pattern, 8 experts of which
    the first two are held, top-2, window 8."""
    return MellumConfig(vocab_size=512, hidden_size=64, num_heads=4,
                        num_kv_heads=2, head_dim=16, sliding_window=8,
                        num_experts=8, experts_held=(0, 2), top_k=2,
                        expert_width=32)


def init(config: MellumConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded float32 weights: the embedding N(0, 1), matrices N(0, 0.02),
    the two projections that write into the residual stream (``wo``,
    ``down``) N(0, 0.02 / sqrt(2 x ``published_layers``)) (Megatron's
    scaled init), unit norm scales. At 0.02 everywhere the mean of a
    thousand values that uniform attention over random tokens makes
    outweighs a token's own embedding, and every token of a row routes
    alike."""
    h, f = config.hidden_size, config.expert_width
    q_width = config.num_heads * config.head_dim
    kv_width = config.num_kv_heads * config.head_dim
    held = config.experts_held[1]
    residual = 0.02 / math.sqrt(2 * config.published_layers)
    keys = iter(jax.random.split(key, 2 + 8 * config.num_layers))

    def normal(shape, std=0.02):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    params: Dict[str, Any] = {"embed": normal((config.vocab_size, h), 1.0),
                              "head": normal((h, config.vocab_size)),
                              "final_norm": jnp.ones((h,), jnp.float32)}
    for layer in range(config.num_layers):
        params[f"layer_{layer}"] = {
            "attn_norm": jnp.ones((h,), jnp.float32),
            "wq": normal((h, q_width)),
            "wk": normal((h, kv_width)),
            "wv": normal((h, kv_width)),
            "wo": normal((q_width, h), residual),
            "moe_norm": jnp.ones((h,), jnp.float32),
            "router": normal((h, config.num_experts)),
            "gate": normal((held, h, f)),
            "up": normal((held, h, f)),
            "down": normal((held, f, h), residual),
        }
    return params


# -- rotary positions ----------------------------------------------------------


def rope_inv_freq(config: MellumConfig, layer_type: str):
    """``(inverse frequencies (head_dim / 2,), scale of cos and sin)`` of
    a layer's rotary positions: plain for a window layer, YaRN's for a
    full one: the interpolated frequencies (divided by ``factor``) below
    ``beta_slow`` rotations over the original context, the extrapolated
    ones above ``beta_fast``, a linear ramp between."""
    dim = config.head_dim
    pos_freqs = config.rope_theta ** (
        jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if layer_type != FULL:
        return 1.0 / pos_freqs, 1.0
    yarn = config.yarn

    def correction_dim(rotations: float) -> float:
        return (dim * math.log(yarn.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(config.rope_theta)))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    extrapolated = 1.0 - ramp
    inv_freq = ((1.0 / (yarn.factor * pos_freqs)) * (1.0 - extrapolated)
                + (1.0 / pos_freqs) * extrapolated)
    return inv_freq, yarn.attention_factor


def _rope_tables(config: MellumConfig, layer_type: str, seq_len: int):
    """cos and sin, (S, head_dim) float32, a frequency at lanes d and
    d + head_dim / 2 (the rotate-half convention)."""
    inv_freq, scale = rope_inv_freq(config, layer_type)
    angles = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def _rotate_half(dim: int, dtype):
    """(D, D) of 0 and +-1: ``x @ it`` is ``concat(-x2, x1)`` for
    ``x = concat(x1, x2)``. As a product on the MXU (exact: a signed
    permutation) the rotation fuses into one pass over q; as slices and a
    concatenate XLA made a dozen float32 passes of it, 150 ms of the
    8k-token cell's step on a v5e (PERF.md section 6, PR 32)."""
    half = dim // 2
    swap = jnp.zeros((dim, dim), dtype)
    swap = swap.at[jnp.arange(half) + half, jnp.arange(half)].set(-1)
    return swap.at[jnp.arange(half), jnp.arange(half) + half].set(1)


def _rope(x, heads: int, cos, sin):
    """(B, S, heads x D) -> the same, each head's D rotated by position."""
    b, s, width = x.shape
    x = x.reshape(b, s, heads, width // heads)
    turned = jnp.einsum("bshd,de->bshe", x,
                        _rotate_half(x.shape[-1], x.dtype))
    out = (x.astype(jnp.float32) * cos[:, None, :]
           + turned.astype(jnp.float32) * sin[:, None, :])
    return out.astype(x.dtype).reshape(b, s, width)


# -- attention -----------------------------------------------------------------


# The attentions are jitted for the scope's sake, as models/bert.py's: inside
# a program of its own the name reaches the compiled step as written.
@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _inline_attention(q, k, v, heads: int, kv_heads: int,
                      window: Optional[int]):
    """Causal grouped-query attention as XLA has it: float32 softmax over
    materialized (B, H, S, S) scores; the backward is autodiff's."""
    with jax.named_scope(ATTENTION_SCOPE):
        b, s, _ = q.shape
        q = q.reshape(b, s, kv_heads, heads // kv_heads, -1)
        k, v = (x.reshape(b, s, kv_heads, -1) for x in (k, v))
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k).astype(jnp.float32)
        scores = scores / jnp.sqrt(q.shape[-1])
        ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
        seen = ahead >= 0
        if window is not None:
            seen &= ahead < window
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", weights.astype(v.dtype), v)
        return out.reshape(b, s, -1)


def _blocks(window: Optional[int], backward: bool) -> Tuple[int, int]:
    """The kernels' tiles: the defaults, but half a window in a window
    layer's backward, whole lanes at least. A band of 1,024 keys is two
    live tiles of 1024 x 1024 a query block, both cut by the mask, or
    three of 512 x 512 with one whole. Measured on a v5e (4 rows of
    8,192, 32 : 4 heads of 128, ms). The one-kernel backward (PR 33;
    the dq + dk/dv pair it replaced in brackets), under a window of
    1,024: tiles of 256 22.2 (47.5), 512 15.5 (27.5), 1024 17.7 (29.0),
    512 x 1024 18.3, 1024 x 512 18.4; the whole triangle: 1024 40.6
    (61.5), 512 46.2 (76.7), 512 x 1024 43.2, 1024 x 512 42.5. The
    forward (PR 32), window / triangle: 256 23.6, 512 12.9 / 38.2, 1024
    10.1 / 22.1."""
    side = flash_attention.DEFAULT_BLOCK_Q
    if window is not None and backward:
        side = min(side, max(128, window // 2))
    return side, side


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_attention(q, k, v, heads: int, kv_heads: int,
                     window: Optional[int]):
    """``_inline_attention``'s result from the blocked Pallas kernels."""
    return _flash_attention_fwd(q, k, v, heads, kv_heads, window)[0]


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _flash_attention_fwd(q, k, v, heads, kv_heads, window):
    with jax.named_scope(ATTENTION_SCOPE):
        out, lse = flash_attention.grouped_forward(
            q, k, v, heads, kv_heads, True, window, *_blocks(window, False),
            interpret=not on_tpu())
    return out, (q, k, v, out, lse)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _flash_attention_bwd(heads, kv_heads, window, residuals, cotangent):
    q, k, v, out, lse = residuals
    with jax.named_scope(ATTENTION_SCOPE):
        return flash_attention.grouped_backward(
            q, k, v, out, lse, cotangent, heads, kv_heads, True, window,
            *_blocks(window, True), interpret=not on_tpu())


def _counted_flash_attention_bwd(heads, kv_heads, window, residuals,
                                 cotangent):
    # Counted here, once a layer: the program under it is traced once.
    q, k = residuals[:2]
    flash_attention.count_backward(flash_attention.grouped_backward_kind(
        q, k, heads, *_blocks(window, True), interpret=not on_tpu()))
    return _flash_attention_bwd(heads, kv_heads, window, residuals, cotangent)


_flash_attention.defvjp(_flash_attention_fwd, _counted_flash_attention_bwd)


def _attention(config: MellumConfig, q, k, v, layer_type: str):
    """A layer's attention over rotated q (B, S, H x D) and k, v
    (B, S, Hkv x D), by what the trace can observe: the kernels where they
    beat the inline path (``flash_attention.beats_inline``)."""
    seq_len = q.shape[1]
    window = config.sliding_window if layer_type == SLIDING else None
    if window is not None and window >= seq_len:
        window = None       # the band covers the triangle: nothing to cut
    flash = flash_attention.beats_inline(seq_len)
    # Counted when a layer is traced, not when it runs.
    rt_metrics.counter(
        "rsdl_lm_attention_total",
        "Decoder layers' attentions traced, by what computes them: the "
        "Pallas kernels over a window's band or the whole triangle, or "
        "XLA's inline softmax over materialized scores",
        kind=("inline" if not flash else
              "window" if window is not None else "full")).inc()
    attend = _flash_attention if flash else _inline_attention
    return attend(q, k, v, config.num_heads, config.num_kv_heads, window)


# -- the decoder ---------------------------------------------------------------


def _rms_norm(x, scale, eps: float):
    xf = x.astype(jnp.float32)
    normed = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                                + eps)
    return (normed * scale).astype(x.dtype)


def _experts(config: MellumConfig, x, lp):
    """The held experts' part of a layer's sparse-expert sum, (B, S, h)."""
    first, count = config.experts_held
    # Counted when a layer is traced, not when it runs.
    rt_metrics.counter(
        "rsdl_moe_layer_total",
        "Sparse-expert layers traced, by whether the chip holds all of the "
        "router's experts or a share of them",
        kind="all" if count == config.num_experts else "share").inc()
    rt_metrics.gauge("rsdl_moe_experts_held",
                     "Experts this chip holds, last layer traced").set(count)
    rt_metrics.gauge("rsdl_moe_experts_routed",
                     "Experts the router routes over, last layer traced"
                     ).set(config.num_experts)
    rt_metrics.gauge("rsdl_moe_top_k",
                     "Experts a token picks, last layer traced"
                     ).set(config.top_k)
    b, s, h = x.shape
    out = moe.moe(x.reshape(b * s, h), lp["router"], lp["gate"], lp["up"],
                  lp["down"], config.experts_held, config.top_k)
    return out.reshape(b, s, h)


def _attention_half(config: MellumConfig, layer_type: str, x, lp):
    """x + attention(RMSNorm(x)), the first half of a layer."""
    dtype = config.compute_dtype
    cos, sin = _rope_tables(config, layer_type, x.shape[1])
    a = _rms_norm(x, lp["attn_norm"], config.rms_norm_eps)
    q = _rope(a @ lp["wq"].astype(dtype), config.num_heads, cos, sin)
    k = _rope(a @ lp["wk"].astype(dtype), config.num_kv_heads, cos, sin)
    v = a @ lp["wv"].astype(dtype)
    return x + _attention(config, q, k, v, layer_type) @ lp["wo"].astype(
        dtype)


def _expert_half(config: MellumConfig, x, lp):
    """x + experts(RMSNorm(x)), the second half of a layer."""
    return x + _experts(
        config, _rms_norm(x, lp["moe_norm"], config.rms_norm_eps), lp)


def decode(config: MellumConfig, params: Dict[str, Any],
           token_ids: jax.Array, mesh: Optional[Mesh] = None) -> jax.Array:
    """token_ids (B, S) int32 -> hidden states (B, S, hidden) in the
    compute dtype, after the last layer's residual (before the final
    norm). Every layer is made again in the backward pass.

    ``mesh``: the mesh the calling step is jitted over (``ops/embedding.py:
    lookup``'s convention). One device only: the expert layer's exchange
    across chips does not exist, and nothing here stands in for it."""
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "the decoder runs one chip's share of an expert-parallel "
            f"deployment; a mesh of {mesh.size} devices needs the expert "
            "layer's exchange across chips, which does not exist yet")
    if config.experts_held[0] + config.experts_held[1] > config.num_experts:
        raise ValueError(f"experts_held {config.experts_held} reaches past "
                         f"the router's {config.num_experts} experts")
    x = jnp.take(params["embed"], token_ids, axis=0,
                 mode="clip").astype(config.compute_dtype)
    for layer, layer_type in enumerate(config.layer_types):
        if layer_type not in (SLIDING, FULL):
            raise ValueError(f"unknown layer type {layer_type!r}")
        # Each half is made again on its own in the backward pass: the
        # expert half's backward runs before the attention half's q, k, v
        # and lse exist again, so the two halves' activations never sit
        # on the chip together (a half keeps its bf16 input).
        lp = params[f"layer_{layer}"]
        x = jax.checkpoint(functools.partial(
            _attention_half, config, layer_type))(x, lp)
        x = jax.checkpoint(functools.partial(_expert_half, config))(x, lp)
    return x


# -- the head's loss, in blocks of positions -------------------------------------


#: Tokens in one block of the loss's walk: 2,048 x 24,576 float32 logits
#: are 0.2 GB.
HEAD_BLOCK_TOKENS = 2048


def head_block_size(tokens: int) -> int:
    """Tokens in one block of the loss's walk: ``HEAD_BLOCK_TOKENS``, or
    all of them rounded up to the TPU's sublane tile where they are
    fewer."""
    return min(HEAD_BLOCK_TOKENS, 8 * -(-tokens // 8))


def _block_nll(x, head, targets):
    """Summed cross-entropy of the tokens of ``x`` (n, h) whose
    ``targets`` (n,) are not ``IGNORE_ID``."""
    mask = targets != IGNORE_ID
    logits = jnp.dot(x, head, preferred_element_type=jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.where(mask, targets, 0)[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(mask, -picked, 0.0))


def _block_of(a, k, block):
    return jax.lax.dynamic_slice_in_dim(a, k * block, block, axis=0)


def _flat_padded(x, targets, block):
    """(B, S, h) and (B, S) as tokens (N, h) and (N,), padded with
    ignored tokens to whole blocks: a block is then a run of rows, which a
    loop reads and writes in place."""
    xs, ts = x.reshape(-1, x.shape[-1]), targets.reshape(-1)
    pad = -xs.shape[0] % block
    return (jnp.pad(xs, ((0, pad), (0, 0))),
            jnp.pad(ts, (0, pad), constant_values=IGNORE_ID))


@jax.custom_vjp
def _nll(x, head, targets):
    """Summed cross-entropy over the positions of ``x`` (B, S, h) whose
    ``targets`` (B, S) are not ``IGNORE_ID``, against the untied ``head``
    (h, vocab): what ``_block_nll`` gives over all of them at once, walked
    a block of tokens at a time. The backward makes a block's logits
    again, so nothing (tokens, vocab) outlives a block.
    """
    return _nll_fwd(x, head, targets)[0]


# Jitted for their names' sake (models/bert.py:_masked_nll_fwd).
@jax.jit
def _nll_fwd(x, head, targets):
    block = head_block_size(x.shape[0] * x.shape[1])
    with jax.named_scope(HEAD_SCOPE):
        xs, ts = _flat_padded(x, targets, block)
        head16 = head.astype(x.dtype)

    def add_block(k, total):
        with jax.named_scope(HEAD_SCOPE):
            return total + _block_nll(_block_of(xs, k, block), head16,
                                      _block_of(ts, k, block))

    total = jax.lax.fori_loop(0, xs.shape[0] // block, add_block,
                              jnp.float32(0))
    return total, (x, targets, head16)


@jax.jit
def _nll_bwd(residuals, cotangent):
    x, targets, head16 = residuals
    block = head_block_size(x.shape[0] * x.shape[1])
    with jax.named_scope(HEAD_SCOPE):
        xs, ts = _flat_padded(x, targets, block)
        zeros = (jnp.zeros_like(xs), jnp.zeros(head16.shape, jnp.float32))

    def add_block(k, grads):
        d_xs, d_head = grads
        with jax.named_scope(HEAD_SCOPE):
            block_targets = _block_of(ts, k, block)
            _, vjp = jax.vjp(lambda x, w: _block_nll(x, w, block_targets),
                             _block_of(xs, k, block), head16)
            dx, dw = vjp(cotangent)
            return (jax.lax.dynamic_update_slice_in_dim(
                        d_xs, dx, k * block, axis=0),
                    d_head + dw.astype(jnp.float32))

    d_xs, d_head = jax.lax.fori_loop(0, xs.shape[0] // block, add_block,
                                     zeros)
    with jax.named_scope(HEAD_SCOPE):
        d_x = d_xs[:x.shape[0] * x.shape[1]].reshape(x.shape)
    return d_x, d_head, None


_nll.defvjp(_nll_fwd, _nll_bwd)


def next_token_targets(token_ids: jax.Array) -> jax.Array:
    """(B, S): each position's next token, ``IGNORE_ID`` at the last."""
    return jnp.concatenate(
        [token_ids[:, 1:],
         jnp.full((token_ids.shape[0], 1), IGNORE_ID, token_ids.dtype)],
        axis=1)


def loss_fn(config: MellumConfig, params: Dict[str, Any],
            token_ids: jax.Array, mesh: Optional[Mesh] = None) -> jax.Array:
    """Mean next-token cross-entropy over the ``S - 1`` shifted positions
    of each row of ``token_ids`` (B, S), over this chip's slice of the
    vocabulary. ``mesh`` is :func:`decode`'s."""
    x = _rms_norm(decode(config, params, token_ids, mesh),
                  params["final_norm"], config.rms_norm_eps)
    targets = next_token_targets(token_ids.astype(jnp.int32))
    total = _nll(x, params["head"], targets)
    return total / jnp.maximum(jnp.sum(targets != IGNORE_ID), 1)
