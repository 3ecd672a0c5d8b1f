"""A causal decoder whose layers mix window attention, full attention,
Mamba-2 and Mamba-1 state-space mixers, gated short convolutions, Gated
Memory Units and cross-attention over an earlier layer's keys and values,
with dense or sparse-expert MLPs: one chip's share of a language model, by
its configuration (``mellum2_ep4_share``, ``laguna_xs2_ep8_share``,
``granite4_h_micro_period``, ``phi4_mini_flash_junction``,
``lfm2_24b_a2b_ep8_share``, ``sdar_30b_a3b_ep8_share``).

Per layer, on the residual stream: RMSNorm, grouped-query attention (a
layer's own count of query heads over ``num_kv_heads`` key/value heads,
no biases) with rotary positions, optionally a sigmoid gate a query head
on the attention's output (``attention_gate``), then RMSNorm and the
layer's MLP: a dense SwiGLU (``mlp_layer_types``) or a sparse-expert
SwiGLU layer whose routed sum is scaled (``routed_scale``) and may have a
shared expert beside it (``shared_expert_width``). ``layer_types`` gives
each layer's attention: ``sliding_attention`` is causal within the last
``sliding_window`` keys under plain rotary positions, ``full_attention``
is causal over the whole row under YaRN's (interpolated and extrapolated
frequencies blended once, whatever the sequence length; cos and sin
scaled by its attention factor) over the first ``full_rotary_factor`` of
a head's dimensions; with ``rotary`` off (``position_embedding_type:
"nope"``) an attention layer rotates nothing. A ``mamba`` layer's mixer is
Mamba-2's: one projection to ``z | x B C | dt``, a depthwise causal
convolution and ``silu`` over ``x B C``, the state-space scan
(``ops/ssd.py``: ``mamba_heads`` heads of ``mamba_head_dim``, a state of
``mamba_state``, B and C in one group, in chunks of ``mamba_chunk``: on the
chip, at shapes of whole lanes, Pallas kernels that keep a chunk's
head-by-head tiles in VMEM; elsewhere XLA's einsums over the chunks), an
RMSNorm gated by ``silu(z)`` and the projection back. Four scalars, all 1
unless a configuration says otherwise: ``embedding_multiplier`` on the
embedding, ``residual_multiplier`` on each half's output before it is added,
``attention_multiplier`` as the softmax's scale in place of 1 / sqrt(head
dimension), ``logits_scaling`` dividing the logits. After the last layer
RMSNorm and the head, untied or (``tie_embeddings``) the embedding's own
matrix; the loss is the mean next-token negative log-likelihood. Same
functional API as the other families: ``init``, ``loss_fn``.

Three more ``layer_types`` (SambaY, Ren et al. 2025). A ``mamba1`` layer's
mixer is Mamba-1's: ``W_in`` to ``u | z``, the convolution and ``silu``
over ``u``, ``W_x`` to ``r | B | C``, ``dt = softplus(r W_dt + b_dt)``, the
selective scan (``ops/selective_scan.py``: ``mamba1_width`` channels, a
state of ``mamba1_state`` a channel, a decay a channel and state), ``y *
silu(z)`` and ``W_out``; the last one before a ``gmu`` layer also gives
out its scan's output ``M = y``. A ``gmu`` layer's mixer is a Gated
Memory Unit, ``(silu(n W_1) * M) W_2``. A ``cross`` layer's attention has
a ``W_q`` and a ``W_o`` only: its keys and values are the tensors the
last ``full_attention`` layer before it made. ``M``, ``K`` and ``V`` leave
the half that makes them as results and enter the halves that read them
as arguments (:func:`decode`): kept once, never made again by a reader,
their cotangents summed by autodiff before the maker's backward runs.
With ``differential`` every attention layer is differential attention (Ye
et al. 2024): query heads ``(2i, 2i + 1)`` and key heads ``(2j, 2j + 1)``
are two softmax maps, value heads ``(2j, 2j + 1)`` side by side their
values, the second map subtracted under a learned scalar ``lambda``, an
RMSNorm a head pair, times ``1 - lambda_init`` (``published_indices``
gives each layer's ``lambda_init``). ``norm`` ``"layer"`` is LayerNorm
with a bias in place of every RMSNorm on the residual stream.

A seventh ``layer_types`` entry and two switches (LFM2, Liquid AI 2025). A
``conv`` layer's operator is a gated short convolution (``ops/sconv.py``):
``W_in`` to ``B | C | u``, ``B * u``, a depthwise causal convolution of
``conv_taps`` taps with neither bias nor activation, ``C *`` the result,
``W_out``; no state and no scan. With ``qk_norm`` each q head and each k
head passes an RMSNorm (one scale of ``head_dim`` for q, one for k) before
it is rotated. With ``expert_bias`` the router scores by a sigmoid an
expert, PICKS by score plus the leaf ``expert_bias`` (under
``stop_gradient``: Adam leaves it where it finds it) and WEIGHS by the
picks' scores alone over their sum (``ops/moe.py:route``); with an
``expert_bias_speed`` the train step moves that leaf after the
optimizer's update by its balancing update, the speed times the share of
the mean load an expert fell short of it this step: down where more than
the mean of the tokens picked it, up where fewer did
(``utils/tracing.leaf_move``). ``router_trains``
``False`` holds the router's matrix under ``stop_gradient`` too: a chip
that holds a share of the experts sees the router's gradient through its
own experts alone, and that part applied alone draws every token's picks
onto them. ``yarn`` ``None`` gives a full layer plain rotary, as a window
layer's.

A second objective (SDAR, arXiv 2510.06303, after BD3-LMs, Arriola et al.
2025). With ``diffusion_block`` B the decoder trains by block diffusion: a
row ``x_0`` of L tokens is cut into blocks of B, each block draws a noise
level ``t ~ U[0, 1)`` and each of its tokens becomes ``mask_token_id``
with probability ``p = (1 - diffusion_eps) t + diffusion_eps``
(:func:`diffusion_noise`, from a key that is an argument of the step);
the decoder reads the clean row and the noised one as ONE row of 2 L
positions, clean first, both copies at rotary positions 0..L-1, under one
mask (``ops/flash_attention.py:_Mask``): a clean query sees the clean
blocks up to and with its own, a noised query its own noised block and the
clean blocks strictly before it, nothing clean sees anything noised. The
final norm and the head run on the noised copy alone and the loss is
``sum over the masked positions of -log p(x_0[i]) / p_block(i)`` at the
same position (no shift), over ``rows x L``. Full attention layers only.

Under expert parallelism a chip holds ``experts_held = (first, count)`` of
the router's ``num_experts`` and a slice of the vocabulary: the expert
layer routes over all the experts and adds what the held ones give
(``ops/moe.py``), and the logits, ids and loss are over the slice. What
every chip computes alike (attention, a dense layer, the shared expert) is
whole here. On one chip the layer runs without its exchange.

TPU-first choices:
- bf16 compute, float32 parameters; the router, every softmax, the norms,
  the gate's sigmoid and the rotary tables in float32.
- Attention through the blocked Pallas kernels (``ops/flash_attention.py``)
  on the chip: they read q, k and v where the projections left them, fetch
  a key/value head once for its group of query heads, and visit only the
  key blocks a query block can see (a window layer's walk is as long as
  its band). Off the chip, and below the measured crossover, XLA's masked
  softmax over materialized scores. The head gate multiplies the kernel's
  output under the same scope.
- The q and k heads are placed, each head's RMSNorm where the
  configuration has one and the rotary, by one Pallas kernel each way on
  the chip (``ops/rope.py``: the projection read once and written once,
  the rotation two rolls of the lanes; heads of 128 or of 64), and by
  XLA's float32 passes (``_head_norm``, ``_rope``) everywhere else.
- Every layer is made again in the backward pass (``jax.checkpoint``), a
  half at a time: a half keeps its bf16 input, and an attention half
  whose attention the kernels compute also their two results, the ungated
  output and the log-sum-exp a row, so that the backward pass runs no
  forward kernel twice. An MLP half keeps its SwiGLU's ``x G`` and ``x U``
  too (a dense layer's MLP or a sparse layer's shared expert; not the
  routed experts), so that the backward pass makes the norm again and
  runs nine products a SwiGLU, not eleven; and a first half of any kind
  keeps what its in-projections give (q, k, v and the head gate; a Mamba
  mixer's ``z | x B C | dt``; a Mamba-1 mixer's ``u | z``, ``r | B | C``
  and ``r W_dt``; a memory unit's ``n W_1``; a ``conv`` operator's ``B |
  C | u``), so that the backward pass makes the norms, head norms,
  rotary, convolution and scan again but runs three products a projection
  weight, not four. Both in as many layers as the device's memory has
  room for by the allocator's own count when the step is traced, out of
  one running sum (``keep_room``): the SwiGLUs take theirs first, in
  layer order (``mlp_halves_kept``: every layer of the five 8,192-token
  configurations that has one, on a v5e), then the first halves theirs of
  what is left (``first_halves_kept``: on a v5e every layer of LFM2's and
  the junction's cuts, five of granite's ten, one of Laguna's five, none
  of Mellum's, whose room is negative); a half there is no room for
  keeps its input alone.
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
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh

from ray_shuffling_data_loader_tpu.ops import (flash_attention, moe, on_tpu,
                                               rope, sconv, selective_scan,
                                               ssd)
from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics
from ray_shuffling_data_loader_tpu.runtime import telemetry
from ray_shuffling_data_loader_tpu.utils import tracing

IGNORE_ID = -100
#: The standard deviation of a seeded ``expert_bias`` (``init``).
EXPERT_BIAS_STD = 0.002
SLIDING, FULL, MAMBA = "sliding_attention", "full_attention", "mamba"
MAMBA1, GMU, CROSS, CONV = "mamba1", "gmu", "cross", "conv"
DENSE, SPARSE = "dense", "sparse"
RMS_NORM, LAYER_NORM = "rms", "layer"

# The names a device trace shows a layer's projections' (q, k, v, the gate
# and ``wo``), its attention's, its expert layer's (``ops/moe.py``), its
# dense MLP's or shared expert's and the head's operations under; a Mamba
# mixer's two projections are under the first, what lies between them (the
# convolution, the scan, the gated norm: ``ops/ssd.py``) under ``SSM_SCOPE``.
# A Mamba-1 mixer's four projections and a Gated Memory Unit's two are under
# the first too; what lies between a Mamba-1 mixer's (the convolution, the
# softplus, the selective scan, the gate: ``ops/selective_scan.py``) under
# ``SSCAN_SCOPE``, a memory unit's ``silu(n W_1) * M`` under ``GMU_SCOPE``. A
# ``conv`` operator's two projections are under the first as well, its two
# gates and the convolution between them (``ops/sconv.py``) under
# ``SCONV_SCOPE``.
PROJ_SCOPE = telemetry.step_scope("rsdl.lm.proj")
ATTENTION_SCOPE = telemetry.step_scope("rsdl.lm.attention")
SSM_SCOPE = ssd.SCOPE
SSCAN_SCOPE = selective_scan.SCOPE
GMU_SCOPE = telemetry.step_scope("rsdl.lm.gmu")
SCONV_SCOPE = sconv.SCOPE
MOE_SCOPE = moe.SCOPE
MLP_SCOPE = telemetry.step_scope("rsdl.lm.mlp")
HEAD_SCOPE = telemetry.step_scope("rsdl.lm.head")
# Block diffusion's draw of the noise, the masking and the joining of the
# clean and the noised copy into one row (``_noised_beside_clean``).
NOISE_SCOPE = telemetry.step_scope("rsdl.lm.noise")
# The element-wise passes between the products and the kernels (PR 47: 30 %
# of ``sdar_train_8k``'s step ran under no scope, PERF.md section 5): a
# norm of the residual stream or of the q and k heads, float32 inside
# (``_norm``, ``_head_norm``), and the rotary over the q and k heads
# (``_rope``), with the casts, copies and reshapes XLA makes for them. Where
# the q and k heads are placed by ``ops/rope.py``'s kernels (PR 48) their
# norms are in the kernels, under the second.
NORM_SCOPE = telemetry.step_scope("rsdl.lm.norm")
ROPE_SCOPE = rope.SCOPE
# What the step runs outside the parts named above, so that every operation
# of it has a name (``telemetry.STEP_SCOPES``; a trace bills an operation to
# the innermost scope on its path): the token gather with its multiplier,
# its cast and its scatter-add backward; around each half of a layer, what
# the half runs between its named parts (the residual add and its
# multiplier, the splits and reshapes between a projection and its
# operator, a head gate's sigmoid, the rotary tables, the cotangents' sums);
# around the loss, the targets, the noised half's slice, the count and the
# division. The last two hold no product, kernel or loop of their own
# (``tests/test_step_scopes.py``): a part added without a name of its own
# turns that test red.
EMBED_SCOPE = telemetry.step_scope("rsdl.lm.embed")
LAYER_SCOPE = telemetry.step_scope("rsdl.lm.layer")
LOSS_SCOPE = telemetry.step_scope("rsdl.lm.loss")

# What an attention half's checkpoint keeps of the forward kernel
# (``_flash_attention_fwd`` names them, ``decode``'s policy saves them).
KEPT_OUT, KEPT_LSE = "rsdl.lm.attention.out", "rsdl.lm.attention.lse"
# What an MLP half's checkpoint keeps of a SwiGLU's forward, ``x G`` and
# ``x U`` (``_swiglu_fwd`` names them), in the layers ``mlp_halves_kept``
# finds room for.
KEPT_GATE, KEPT_UP = "rsdl.lm.mlp.gate", "rsdl.lm.mlp.up"
# What a first half's checkpoint keeps of its in-projections, the products
# that feed the half's operator and not the residual stream (``_project_in``
# names them), in the layers ``first_halves_kept`` finds room for once the
# SwiGLUs have taken theirs.
KEPT_PROJ = "rsdl.lm.proj.in"


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
class DecoderConfig:
    vocab_size: int = 24_576
    hidden_size: int = 2304
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL)
    # each layer's MLP, DENSE or SPARSE; None: every layer sparse
    mlp_layer_types: Optional[Tuple[str, ...]] = None
    num_heads: int = 32
    # each layer's query heads; None: ``num_heads`` in every layer
    heads_per_layer: Optional[Tuple[int, ...]] = None
    num_kv_heads: int = 4
    head_dim: int = 128
    attention_gate: bool = False  # a sigmoid gate a query head on the output
    # RMSNorm over each q head and each k head before rotary, one scale of
    # ``head_dim`` for q and one for k
    qk_norm: bool = False
    sliding_window: int = 1024
    intermediate_size: int = 0    # a dense layer's SwiGLU width
    num_experts: int = 64                    # the router's width
    experts_held: Tuple[int, int] = (0, 16)  # (first, count) held here
    top_k: int = 8
    expert_width: int = 896
    shared_expert_width: int = 0  # 0: no shared expert
    routed_scale: float = 1.0     # what a token's routing weights sum to
    # the router scores by sigmoids and picks under the leaf
    # ``expert_bias`` (``ops/moe.py:route``); False: softmax, top-k
    expert_bias: bool = False
    # the selection bias's balancing update (auxiliary-loss-free load
    # balancing, Wang et al. 2024, in its proportional form): after each
    # step an expert's bias moves by this times (1 - its picks over the
    # mean picks an expert), this step's tokens; 0: it stays where it was
    expert_bias_speed: float = 0.0
    # False: the router's matrix takes no gradient. Its gradient is a sum
    # over every chip that shares the layer; one chip's part alone, which
    # reaches it through the held experts only, moves the picks onto them.
    router_trains: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 500_000.0
    sliding_rope_theta: Optional[float] = None   # None: ``rope_theta``
    # the share of a head's dimensions a full layer rotates (the first)
    full_rotary_factor: float = 1.0
    # a full layer's rotary; None: plain, as a window layer's
    yarn: Optional[YarnConfig] = YarnConfig()
    rotary: bool = True           # False: no positions at all ("nope")
    # the softmax's scale; None: 1 / sqrt(head_dim)
    attention_multiplier: Optional[float] = None
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    tie_embeddings: bool = False  # the head is the embedding's matrix
    # a ``mamba`` layer's mixer (Mamba-2; B and C in one group)
    mamba_heads: int = 0
    mamba_head_dim: int = 64
    mamba_state: int = 128
    mamba_conv: int = 4
    mamba_chunk: int = 256
    # a ``mamba1`` layer's mixer (Mamba-1; ``mamba_conv`` taps, the scan in
    # chunks of ``mamba_chunk``)
    mamba1_width: int = 0
    mamba1_state: int = 16
    mamba1_dt_rank: int = 0
    conv_taps: int = 3            # a ``conv`` layer's depthwise taps
    norm: str = RMS_NORM          # LAYER_NORM: LayerNorm with a bias
    # every attention layer differential; then each layer's index in the
    # published model, which sets its ``lambda_init``
    differential: bool = False
    published_indices: Optional[Tuple[int, ...]] = None
    # block diffusion (module docstring): the block length, 0 for the
    # next-token objective; the id a masked token becomes; the least
    # masking probability of a block
    diffusion_block: int = 0
    mask_token_id: int = 3
    diffusion_eps: float = 1e-3
    compute_dtype: Any = jnp.bfloat16
    published_layers: int = 28    # the uncut depth: scales ``init`` only

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    def heads(self, layer: int) -> int:
        """Query heads of layer ``layer``."""
        return (self.num_heads if self.heads_per_layer is None
                else self.heads_per_layer[layer])

    def mlp_type(self, layer: int) -> str:
        return (SPARSE if self.mlp_layer_types is None
                else self.mlp_layer_types[layer])

    @property
    def mamba_width(self) -> int:
        """Channels of a Mamba mixer's ``x`` (and of its gate ``z``)."""
        return self.mamba_heads * self.mamba_head_dim


def mellum2_ep4_share() -> DecoderConfig:
    """Mellum2-12B-A2.5B at its published widths, cut to one chip of four
    that share each layer by expert parallelism: 16 of the 64 experts, a
    quarter of the 98,304-id vocabulary, and one period of the layer
    pattern (three window layers, one full) of the 28 layers."""
    return DecoderConfig()


def mellum_tiny() -> DecoderConfig:
    """For tests/CPU smoke runs: the same pattern, 8 experts of which
    the first two are held, top-2, window 8."""
    return DecoderConfig(vocab_size=512, hidden_size=64, num_heads=4,
                         num_kv_heads=2, head_dim=16, sliding_window=8,
                         num_experts=8, experts_held=(0, 2), top_k=2,
                         expert_width=32)


_LAGUNA_PATTERN = dict(
    layer_types=(FULL, SLIDING, SLIDING, SLIDING, FULL),
    mlp_layer_types=(DENSE, SPARSE, SPARSE, SPARSE, SPARSE),
    attention_gate=True, routed_scale=2.5, sliding_rope_theta=10_000.0,
    full_rotary_factor=0.5, published_layers=40,
    yarn=YarnConfig(factor=64.0, original_max_position_embeddings=4096,
                    beta_fast=64.0, beta_slow=1.0,
                    attention_factor=1.4158883083359672))


def laguna_xs2_ep8_share() -> DecoderConfig:
    """Laguna-XS.2 at its published widths, cut to one chip of eight that
    share each layer by expert parallelism: 32 of the 256 experts, an
    eighth of the 100,352-id vocabulary, and the published layers 0-4 of
    40: the dense leading layer (full attention) and one period after it
    (three window layers of 64 query heads, one full of 48)."""
    return DecoderConfig(
        vocab_size=12_544, hidden_size=2048, num_heads=48,
        heads_per_layer=(48, 64, 64, 64, 48), num_kv_heads=8, head_dim=128,
        sliding_window=512, intermediate_size=8192, num_experts=256,
        experts_held=(0, 32), top_k=8, expert_width=512,
        shared_expert_width=512, **_LAGUNA_PATTERN)


def laguna_tiny() -> DecoderConfig:
    """For tests/CPU smoke runs: Laguna's pattern (a dense first layer,
    heads by layer, a head gate, a shared expert, half a head rotated in
    the full layers), 8 experts of which the first two are held, top-2,
    window 8."""
    return DecoderConfig(
        vocab_size=512, hidden_size=64, num_heads=6,
        heads_per_layer=(6, 8, 8, 8, 6), num_kv_heads=2, head_dim=16,
        sliding_window=8, intermediate_size=128, num_experts=8,
        experts_held=(0, 2), top_k=2, expert_width=32,
        shared_expert_width=32, **_LAGUNA_PATTERN)


_GRANITE_PERIOD = dict(
    layer_types=5 * (MAMBA,) + (FULL,) + 4 * (MAMBA,),
    mlp_layer_types=10 * (DENSE,), rotary=False, tie_embeddings=True,
    attention_multiplier=0.015625, embedding_multiplier=12.0,
    residual_multiplier=0.22, logits_scaling=8.0, rms_norm_eps=1e-5,
    rope_theta=10_000.0, published_layers=40)


def granite4_h_micro_period() -> DecoderConfig:
    """granite-4.0-h-micro at its published widths, cut to one period of
    its layer pattern, the published layers 0-9 of 40 (five Mamba-2
    layers, one attention layer of 32:8 heads of 64 without positions,
    four more Mamba-2 layers; every MLP the dense SwiGLU of 8,192), and
    an eighth of the 100,352-id vocabulary under the tied embedding: one
    pipeline stage of four."""
    return DecoderConfig(
        vocab_size=12_544, hidden_size=2048, num_heads=32, num_kv_heads=8,
        head_dim=64, intermediate_size=8192, mamba_heads=64,
        mamba_head_dim=64, mamba_state=128, mamba_conv=4, mamba_chunk=256,
        **_GRANITE_PERIOD)


def granite_tiny() -> DecoderConfig:
    """For tests/CPU smoke runs: granite's pattern at a third of a period
    (two Mamba-2 layers, one attention layer, one more Mamba-2 layer), all
    four multipliers off 1, 4 state-space heads of 8 with a state of 16
    in chunks of 8."""
    return DecoderConfig(
        vocab_size=512, hidden_size=64, num_heads=4, num_kv_heads=2,
        head_dim=16, intermediate_size=128, mamba_heads=4, mamba_head_dim=8,
        mamba_state=16, mamba_conv=4, mamba_chunk=8,
        **{**_GRANITE_PERIOD,
           "layer_types": (MAMBA, MAMBA, FULL, MAMBA),
           "mlp_layer_types": 4 * (DENSE,),
           "attention_multiplier": 0.0625})


_PHI4FLASH_JUNCTION = dict(
    layer_types=(MAMBA1, SLIDING, MAMBA1, FULL, GMU, CROSS),
    mlp_layer_types=6 * (DENSE,), published_indices=(0, 1, 16, 17, 18, 19),
    differential=True, norm=LAYER_NORM, rotary=False, tie_embeddings=True,
    rms_norm_eps=1e-5, published_layers=32)


def phi4_mini_flash_junction() -> DecoderConfig:
    """Phi-4-mini-flash-reasoning (SambaY) at its published widths, cut to
    the junction of its two decoders, the published layers 0, 1, 16, 17,
    18 and 19 of 32: one period of the self-decoder (Mamba-1, window-512
    differential attention), the Mamba-1 layer that gives out ``M`` and
    the full attention layer that gives out ``K`` and ``V``, one period
    of the cross-decoder (a Gated Memory Unit over ``M``, cross-attention
    over ``K`` and ``V``); 40:20 heads of 64, every MLP the dense SwiGLU
    of 10,240, LayerNorm, no positions, and an eighth of the 200,064-id
    vocabulary under the tied embedding."""
    return DecoderConfig(
        vocab_size=25_008, hidden_size=2560, num_heads=40, num_kv_heads=20,
        head_dim=64, sliding_window=512, intermediate_size=10_240,
        mamba1_width=5120, mamba1_state=16, mamba1_dt_rank=160,
        mamba_conv=4, mamba_chunk=64, **_PHI4FLASH_JUNCTION)


def phi4flash_tiny() -> DecoderConfig:
    """For tests/CPU smoke runs: the junction's six kinds of layer at 8:4
    heads of 8, a window of 8 and Mamba-1 mixers of 128 channels with a
    state of 4 in chunks of 8."""
    return DecoderConfig(
        vocab_size=512, hidden_size=64, num_heads=8, num_kv_heads=4,
        head_dim=8, sliding_window=8, intermediate_size=128,
        mamba1_width=128, mamba1_state=4, mamba1_dt_rank=4, mamba_conv=4,
        mamba_chunk=8, **_PHI4FLASH_JUNCTION)


_LFM2_LAYERS = dict(
    layer_types=(CONV, FULL, CONV, CONV, CONV),
    mlp_layer_types=(DENSE, SPARSE, SPARSE, SPARSE, SPARSE), qk_norm=True,
    expert_bias=True, expert_bias_speed=0.02, router_trains=False,
    yarn=None, tie_embeddings=True,
    rms_norm_eps=1e-5, rope_theta=1_000_000.0, conv_taps=3,
    published_layers=40)


def lfm2_24b_a2b_ep8_share() -> DecoderConfig:
    """LFM2-24B-A2B at its published widths, cut to one chip of eight that
    share each layer by expert parallelism: 8 of the 64 experts of 1,536
    (top 4, sigmoid scores picked under a selection bias), an eighth of
    the 65,536-id vocabulary under the tied embedding, and the published
    layers 1-5 of 40: the second dense layer (a gated short convolution,
    SwiGLU of 11,776) and one period after it (full attention of 32:8
    normed heads of 64 under plain rotary, three more convolutions, all
    sparse)."""
    return DecoderConfig(
        vocab_size=8192, hidden_size=2048, num_heads=32, num_kv_heads=8,
        head_dim=64, intermediate_size=11_776, num_experts=64,
        experts_held=(0, 8), top_k=4, expert_width=1536, **_LFM2_LAYERS)


def lfm2_tiny() -> DecoderConfig:
    """For tests/CPU smoke runs: LFM2's five layers at 4:2 heads of 16, 8
    experts of which the first two are held, top-2. The bias's balancing
    update at a two-hundredth of the share's speed: at a few dozen picks
    an expert one pick is 3 % of a load."""
    return DecoderConfig(**{
        **_LFM2_LAYERS, **dict(
            vocab_size=512, hidden_size=64, num_heads=4, num_kv_heads=2,
            head_dim=16, intermediate_size=128, num_experts=8,
            experts_held=(0, 2), top_k=2, expert_width=32,
            expert_bias_speed=1e-4)})


_SDAR_LAYERS = dict(
    qk_norm=True, router_trains=False, yarn=None, rope_theta=1_000_000.0,
    rms_norm_eps=1e-6, diffusion_block=4, mask_token_id=3,
    diffusion_eps=1e-3, published_layers=48)


def sdar_30b_a3b_ep8_share() -> DecoderConfig:
    """SDAR-30B-A3B-Chat at its published widths, cut to one chip of eight
    that share each layer by expert parallelism: 16 of the 128 experts of
    768 (softmax over all 128, top 8, renormalised), an eighth of the
    vocabulary padded to 152,576 under an untied head, and six of the 48
    layers, all alike (full attention of 32:4 normed heads of 128 under
    plain rotary at theta 1e6, every MLP sparse): one pipeline stage of
    eight. It trains by block diffusion in blocks of 4."""
    return DecoderConfig(
        vocab_size=19_072, hidden_size=2048, layer_types=6 * (FULL,),
        num_heads=32, num_kv_heads=4, head_dim=128, num_experts=128,
        experts_held=(0, 16), top_k=8, expert_width=768, **_SDAR_LAYERS)


def sdar_tiny() -> DecoderConfig:
    """For tests/CPU smoke runs: SDAR's layer twice at 4:2 heads of 16, 8
    experts of 32 of which the first two are held, top-2, blocks of 4
    (rows of 32 tokens are 64 positions)."""
    return DecoderConfig(**{
        **_SDAR_LAYERS, **dict(
            vocab_size=512, hidden_size=64, layer_types=2 * (FULL,),
            num_heads=4, num_kv_heads=2, head_dim=16, num_experts=8,
            experts_held=(0, 2), top_k=2, expert_width=32)})


#: The standard deviation of the seeded embedding row of a block-diffusion
#: configuration's mask token (``init``).
MASK_ROW_STD = 1e-4


def lambda_init(published_index: int) -> float:
    """A differential attention layer's ``lambda_init`` at its depth in
    the published model (Ye et al. 2024)."""
    return 0.8 - 0.6 * math.exp(-0.3 * published_index)


def init(config: DecoderConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded float32 weights: the embedding N(0, 1), matrices N(0, 0.02),
    the projections that write into the residual stream (``wo``, every
    ``down``) N(0, 0.02 / sqrt(2 x ``published_layers``)) (Megatron's
    scaled init), unit norm scales. At 0.02 everywhere the mean of a
    thousand values that uniform attention over random tokens makes
    outweighs a token's own embedding, and every token of a row routes
    alike. A tied embedding is the head too and is drawn as one, N(0,
    0.02), and there is no ``head`` leaf. A Mamba mixer (state-spaces/
    mamba's ``Mamba2``): the step ``dt`` log-uniform in [0.001, 0.1] as
    ``dt_bias`` (its inverse softplus), ``A`` uniform in [1, 16] as
    ``a_log``, ``D`` 1, the convolution's taps and bias uniform in
    +-1 / sqrt(taps). A Mamba-1 mixer (state-spaces/mamba's ``Mamba``):
    ``dt`` the same through ``dt_bias``, ``A[c, n] = n + 1``, ``D`` 1. A
    differential layer's four ``lambda`` vectors N(0, 0.1) and its head
    pairs' norm scale 1. LayerNorm's biases 0. A ``conv`` operator's taps
    uniform in +-1 / sqrt(taps) (``Conv1d``'s default), the q and k heads'
    norm scales 1. A sigmoid router's ``expert_bias`` N(0,
    ``EXPERT_BIAS_STD``): a hundredth of the spread of the seeded scores,
    which changes a pick of one token in seventeen and leaves the experts'
    loads near even until the balancing update has them (at zero ``score
    + bias`` and ``score`` pick alike, and nothing would show a pick that
    left the bias out). A block-diffusion configuration's mask token's
    row N(0, ``MASK_ROW_STD``): a token the checkpoint the diffusion
    training starts from never saw. At the other rows' size every masked
    position, a quarter of the 2 L, carries the one row and routes to the
    one set of experts, which no chip of a trained model sees; this small,
    a masked position's stream is what attention writes into it, and it
    routes by its context."""
    h, f = config.hidden_size, config.expert_width
    kv_width = config.num_kv_heads * config.head_dim
    held = config.experts_held[1]
    residual = 0.02 / math.sqrt(2 * config.published_layers)
    keys = iter(jax.random.split(key, 2 + 12 * config.num_layers))

    def normal(shape, std=0.02):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    def swiglu(prefix, shape, width):
        return {f"{prefix}gate": normal((*shape, h, width)),
                f"{prefix}up": normal((*shape, h, width)),
                f"{prefix}down": normal((*shape, width, h), residual)}

    def uniform(shape, low, high):
        return jax.random.uniform(next(keys), shape, jnp.float32, low, high)

    def mamba_mixer():
        width, taps = config.mamba_width, config.mamba_conv
        conved = width + 2 * config.mamba_state
        dt = jnp.exp(uniform((config.mamba_heads,), math.log(0.001),
                             math.log(0.1)))
        edge = 1.0 / math.sqrt(taps)
        return {"mamba_norm": jnp.ones((h,), jnp.float32),
                "in_proj": normal((h, width + conved + config.mamba_heads)),
                "conv_w": uniform((taps, conved), -edge, edge),
                "conv_b": uniform((conved,), -edge, edge),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "a_log": jnp.log(uniform((config.mamba_heads,), 1.0, 16.0)),
                "d": jnp.ones((config.mamba_heads,), jnp.float32),
                "ssm_norm": jnp.ones((width,), jnp.float32),
                "out_proj": normal((width, h), residual)}

    def mamba1_mixer():
        width, state = config.mamba1_width, config.mamba1_state
        rank, taps = config.mamba1_dt_rank, config.mamba_conv
        dt = jnp.exp(uniform((width,), math.log(0.001), math.log(0.1)))
        edge = 1.0 / math.sqrt(taps)
        return {"mamba_norm": jnp.ones((h,), jnp.float32),
                "in_proj": normal((h, 2 * width)),
                "conv_w": uniform((taps, width), -edge, edge),
                "conv_b": uniform((width,), -edge, edge),
                "x_proj": normal((width, rank + 2 * state)),
                "dt_proj": normal((rank, width)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "a_log": jnp.log(jnp.broadcast_to(
                    jnp.arange(1, state + 1, dtype=jnp.float32),
                    (width, state))),
                "d": jnp.ones((width,), jnp.float32),
                "out_proj": normal((width, h), residual)}

    def conv_operator():
        edge = 1.0 / math.sqrt(config.conv_taps)
        return {"conv_norm": jnp.ones((h,), jnp.float32),
                "in_proj": normal((h, 3 * h)),
                "conv_w": uniform((config.conv_taps, h), -edge, edge),
                "out_proj": normal((h, h), residual)}

    def attention(layer_type, q_width):
        lp = {"attn_norm": jnp.ones((h,), jnp.float32),
              "wq": normal((h, q_width))}
        if layer_type != CROSS:
            lp.update(wk=normal((h, kv_width)), wv=normal((h, kv_width)))
        lp["wo"] = normal((q_width, h), residual)
        if config.qk_norm:
            lp["q_layernorm"] = jnp.ones((config.head_dim,), jnp.float32)
            lp["k_layernorm"] = jnp.ones((config.head_dim,), jnp.float32)
        if config.differential:
            for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
                lp[name] = normal((config.head_dim,), 0.1)
            lp["subln"] = jnp.ones((2 * config.head_dim,), jnp.float32)
        return lp

    params: Dict[str, Any] = {"final_norm": jnp.ones((h,), jnp.float32)}
    if config.tie_embeddings:
        params["embed"] = normal((config.vocab_size, h))
    else:
        params["embed"] = normal((config.vocab_size, h), 1.0)
        params["head"] = normal((h, config.vocab_size))
    if config.diffusion_block:
        params["embed"] = params["embed"].at[config.mask_token_id].multiply(
            MASK_ROW_STD / (0.02 if config.tie_embeddings else 1.0))
    for layer in range(config.num_layers):
        heads = config.heads(layer)
        q_width = heads * config.head_dim
        layer_type = config.layer_types[layer]
        if layer_type == MAMBA:
            lp = mamba_mixer()
        elif layer_type == MAMBA1:
            lp = mamba1_mixer()
        elif layer_type == GMU:
            lp = {"gmu_norm": jnp.ones((h,), jnp.float32),
                  "w1": normal((h, config.mamba1_width)),
                  "w2": normal((config.mamba1_width, h), residual)}
        elif layer_type == CONV:
            lp = conv_operator()
        else:
            lp = attention(layer_type, q_width)
            if config.attention_gate:
                lp["wg"] = normal((h, heads))
        if config.mlp_type(layer) == DENSE:
            lp["mlp_norm"] = jnp.ones((h,), jnp.float32)
            lp.update(swiglu("", (), config.intermediate_size))
        else:
            lp["moe_norm"] = jnp.ones((h,), jnp.float32)
            lp["router"] = normal((h, config.num_experts))
            if config.expert_bias:
                lp["expert_bias"] = normal((config.num_experts,),
                                           EXPERT_BIAS_STD)
            lp.update(swiglu("", (held,), f))
            if config.shared_expert_width:
                lp.update(swiglu("shared_", (), config.shared_expert_width))
        params[f"layer_{layer}"] = lp
    if config.norm == LAYER_NORM:
        for tree in (params, *(params[f"layer_{layer}"]
                               for layer in range(config.num_layers))):
            for name in [n for n in tree if n.endswith("_norm")]:
                tree[f"{name}_bias"] = jnp.zeros((h,), jnp.float32)
    return params


# -- rotary positions ----------------------------------------------------------


def rotated_dims(config: DecoderConfig, layer_type: str) -> int:
    """How many of a head's dimensions (the first) a layer rotates."""
    share = config.full_rotary_factor if layer_type == FULL else 1.0
    return int(config.head_dim * share)


def rope_inv_freq(config: DecoderConfig, layer_type: str):
    """``(inverse frequencies (rotated dims / 2,), scale of cos and sin)``
    of a layer's rotary positions: plain for a window layer (and for a
    full one of a configuration without ``yarn``), YaRN's for a full one:
    the interpolated frequencies (divided by ``factor``) below
    ``beta_slow`` rotations over the original context, the extrapolated
    ones above ``beta_fast``, a linear ramp between."""
    dim = rotated_dims(config, layer_type)
    theta = config.rope_theta
    if layer_type != FULL and config.sliding_rope_theta is not None:
        theta = config.sliding_rope_theta
    pos_freqs = theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    yarn = config.yarn
    if layer_type != FULL or yarn is None:
        return 1.0 / pos_freqs, 1.0

    def correction_dim(rotations: float) -> float:
        return (dim * math.log(yarn.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    extrapolated = 1.0 - ramp
    inv_freq = ((1.0 / (yarn.factor * pos_freqs)) * (1.0 - extrapolated)
                + (1.0 / pos_freqs) * extrapolated)
    return inv_freq, yarn.attention_factor


def _rope_tables(config: DecoderConfig, layer_type: str, seq_len: int):
    """cos and sin, (S, head_dim) float32: over the rotated dimensions a
    frequency at lanes d and d + rotated / 2 (the rotate-half convention),
    over the rest 1 and 0 (they pass)."""
    inv_freq, scale = rope_inv_freq(config, layer_type)
    angles = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)
    passed = ((0, 0), (0, config.head_dim - angles.shape[1]))
    return (jnp.pad(jnp.cos(angles) * scale, passed, constant_values=1.0),
            jnp.pad(jnp.sin(angles) * scale, passed))


def _twice_rope_tables(config: DecoderConfig, layer_type: str,
                       seq_len: int):
    """``_rope_tables`` for a row of ``seq_len`` = 2 L positions that holds
    a sequence twice (block diffusion's clean and noised copy): both
    copies stand at positions 0..L-1, so the tables are made for L and
    read twice."""
    return tuple(jnp.concatenate([table, table]) for table in
                 _rope_tables(config, layer_type, seq_len // 2))


def _rotate_half(dim: int, rotated: int, dtype):
    """(D, D) of 0 and +-1: ``x @ it`` is ``concat(-x2, x1, 0)`` for
    ``x = concat(x1, x2, rest)``, x1 and x2 the halves of the first
    ``rotated`` dimensions. As a product on the MXU (exact: a signed
    permutation) the rotation fuses into one pass over q; as slices and a
    concatenate XLA made a dozen float32 passes of it, 150 ms of the
    8k-token cell's step on a v5e (PERF.md section 6, PR 32)."""
    half = rotated // 2
    swap = jnp.zeros((dim, dim), dtype)
    swap = swap.at[jnp.arange(half) + half, jnp.arange(half)].set(-1)
    return swap.at[jnp.arange(half), jnp.arange(half) + half].set(1)


def _rope(x, heads: int, cos, sin, rotated: int):
    """(B, S, heads x D) -> the same, the first ``rotated`` of each head's
    D rotated by position."""
    b, s, width = x.shape
    with jax.named_scope(ROPE_SCOPE):
        x = x.reshape(b, s, heads, width // heads)
        turned = jnp.einsum("bshd,de->bshe", x,
                            _rotate_half(x.shape[-1], rotated, x.dtype))
        out = (x.astype(jnp.float32) * cos[:, None, :]
               + turned.astype(jnp.float32) * sin[:, None, :])
        return out.astype(x.dtype).reshape(b, s, width)


# -- attention -----------------------------------------------------------------


def _gated(out, gate, heads: int):
    """``out`` (B, S, H x D) with each head's D times its ``gate``
    (B, S, H) float32; ``out`` itself where there is no gate."""
    if gate is None:
        return out
    b, s, _ = out.shape
    return (out.reshape(b, s, heads, -1).astype(jnp.float32)
            * gate[..., None]).astype(out.dtype).reshape(out.shape)


# The attentions are jitted for the scope's sake, as models/bert.py's: inside
# a program of its own the name reaches the compiled step as written.
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9))
def _inline_attention(q, k, v, gate, heads: int, kv_heads: int,
                      window: Optional[int], scale: Optional[float] = None,
                      out_dtype=None, v_heads: Optional[int] = None,
                      seen=None):
    """Causal grouped-query attention as XLA has it: float32 softmax over
    materialized (B, H, S, S) scores, times ``scale`` (``None``: over the
    root of a head's dimensions), the output in ``out_dtype`` (``None``:
    v's); ``v`` of ``v_heads`` heads (``None``: ``kv_heads``) at a width
    of their own, each read by the query heads of ``kv_heads / v_heads``
    key heads; the backward is autodiff's. ``seen`` (S, S) booleans, query
    by key, take the causal mask's place where the mask is another
    (block diffusion's: ``flash_attention.diffusion_seen``)."""
    with jax.named_scope(ATTENTION_SCOPE):
        b, s, _ = q.shape
        q = q.reshape(b, s, kv_heads, heads // kv_heads, -1)
        k = k.reshape(b, s, kv_heads, -1)
        v = v.reshape(b, s, kv_heads if v_heads is None else v_heads, -1)
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k).astype(jnp.float32)
        scores = (scores / jnp.sqrt(q.shape[-1]) if scale is None
                  else scores * scale)
        if seen is None:
            ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
            seen = ahead >= 0
            if window is not None:
                seen &= ahead < window
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        # a value head's maps: its key heads' query heads, side by side
        weights = weights.reshape(b, v.shape[2], -1, s, s)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", weights.astype(v.dtype), v,
                         preferred_element_type=out_dtype)
        return _gated(out.reshape(b, s, -1), gate, heads)


def _blocks(window: Optional[int], backward: bool,
            diffusion: Optional[flash_attention.Diffusion] = None
            ) -> Tuple[int, int]:
    """The kernels' tiles: the defaults over the whole triangle; in a
    window layer the window's width in the forward and half of it in the
    backward, neither under 512 nor over the default. A band of 1,024
    keys is two live tiles of 1024 x 1024 a query block, both cut by the
    mask, or three of 512 x 512 with one whole; a band of 512 is two live
    tiles of 512 x 512, both cut, or three of 256 x 256 with one whole,
    and a tile of 256 costs more in steps than it saves in area.

    Measured on a v5e, bf16, heads of 128, ms. **At ``mellum_train_8k``'s
    shapes** (4 rows of 8,192, 32 : 4 heads). The one-kernel backward
    (PR 33; the dq + dk/dv pair it replaced in brackets), under a window
    of 1,024: tiles of 256 22.2 (47.5), 512 15.5 (27.5), 1024 17.7
    (29.0), 512 x 1024 18.3, 1024 x 512 18.4; the whole triangle: 1024
    40.6 (61.5), 512 46.2 (76.7), 512 x 1024 43.2, 1024 x 512 42.5. The
    forward (PR 32), window / triangle: 256 23.6, 512 12.9 / 38.2, 1024
    10.1 / 22.1. **At ``laguna_train_8k``'s shapes** (PR 34; 2 rows of
    8,192; forward / backward). 64 : 8 heads under a window of 512: tiles
    of 128 29.5 / 34.0, 256 15.6 / 14.8, 512 9.6 / 11.3, 1024 9.9 / 17.7,
    256 x 512 11.2 / 14.7, 512 x 256 16.2 / 14.2, 128 x 512 16.9 / 19.0,
    512 x 128 31.4 / 24.4. 48 : 8 heads over the whole triangle: 1024
    16.9 / 30.9, 512 28.9 / 35.2, 512 x 1024 19.4 / 32.9, 1024 x 512
    30.2 / 32.4, 2048 x 1024 17.6 / 34.4.

    **Under block diffusion's mask** (``diffusion``; PR 47) the defaults,
    no larger than a copy. At ``sdar_train_8k``'s shape (one row of 8,192
    tokens twice, 32 : 4 heads, blocks of 4; ``python3 -m
    chipbench.probes.sdar_kernels`` on a v5e; forward / backward; tiles
    visited a head): 1024 11.9 / 22.3 (80), 512 20.4 / 24.6 (288), 512 x
    1024 14.0 / 23.7 (160), 1024 x 512 21.7 / 23.5 (160), 256 47.5 / 46.1
    (1,088); 2048 x 1024 does not fit the one-kernel backward's VMEM and
    the dq + dk/dv pair refuses the mask. The plainly causal walk over the
    same 16,384 positions, for scale: 1024 18.7 / 35.9. How the walk
    reaches a noised query block's own noised key block: the index map
    sends the step after the run's last there
    (``flash_attention._diffusion_visit``), which costs a tile of 1,024 x
    1,024 for 4 live keys a query, 8 of the 80 tiles; two calls joined by
    their lse (the ring's way) would read q twice and write out and lse
    twice, and a kernel of its own for the in-block part (4 keys a query)
    would save those 8 tiles, a tenth of the kernels' time: neither was
    built (``PERF.md`` section 7)."""
    side = flash_attention.DEFAULT_BLOCK_Q
    if window is not None:
        side = min(side, max(512, window // 2 if backward else window))
    if diffusion is not None:
        side = min(side, diffusion[1])      # a tile lies in one copy
    return side, side


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash_attention(q, k, v, gate, heads: int, kv_heads: int,
                     window: Optional[int], scale: Optional[float] = None,
                     out_dtype=None, v_heads: Optional[int] = None,
                     diffusion: Optional[flash_attention.Diffusion] = None):
    """``_inline_attention``'s result from the blocked Pallas kernels;
    ``diffusion`` = ``(block length, clean length)`` names block
    diffusion's mask where ``_inline_attention`` takes its booleans."""
    return _flash_attention_fwd(q, k, v, gate, heads, kv_heads, window,
                                scale, out_dtype, v_heads, diffusion)[0]


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash_attention_fwd(q, k, v, gate, heads, kv_heads, window, scale,
                         out_dtype=None, v_heads=None, diffusion=None):
    with jax.named_scope(ATTENTION_SCOPE):
        out, lse = flash_attention.grouped_forward(
            q, k, v, heads, kv_heads, diffusion is None, window,
            *_blocks(window, False, diffusion),
            interpret=not on_tpu(), scale=scale, out_dtype=out_dtype,
            num_v_heads=v_heads, diffusion=diffusion)
        # The two residuals the half's checkpoint keeps (``decode``), so
        # that the backward pass has them without this kernel run again;
        # q, k, v and the gate it makes again. lse without the column's
        # last axis: the chip stores (B, H, S, 1) float32 a 128-lane tile
        # a value, 537 MB a layer of 4 x 32 x 8,192 where (B, H, S) is 4.
        # The barrier has the column squeezed before the forward pass goes
        # on with ``out``: left alone, XLA's schedule for the 8k-token
        # cells squeezes it where the backward kernel wants it, and every
        # layer's column waits till then (the compiled step's temporaries
        # 8.10 GB against 6.46; PERF.md section 6, PR 35).
        out, lse = jax.lax.optimization_barrier((out, lse[..., 0]))
        out = checkpoint_name(out, KEPT_OUT)
        lse = checkpoint_name(lse, KEPT_LSE)
        return _gated(out, gate, heads), (q, k, v, gate, out, lse)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6))
def _flash_attention_bwd(heads, kv_heads, window, scale, out_dtype, v_heads,
                         diffusion, residuals, cotangent):
    q, k, v, gate, out, lse = residuals
    with jax.named_scope(ATTENTION_SCOPE):
        if out_dtype is not None:
            # the kernel's products take the operands' dtype
            cotangent = cotangent.astype(q.dtype)
        d_gate = None
        if gate is not None:
            # gated = gate x out: the kernels' cotangent is gate x d gated,
            # the gate's is sum over a head's D of d gated x out
            b, s, _ = out.shape
            by_head = cotangent.reshape(b, s, heads, -1).astype(jnp.float32)
            d_gate = jnp.sum(
                by_head * out.reshape(by_head.shape).astype(jnp.float32),
                axis=-1)
            cotangent = _gated(cotangent, gate, heads)
        return (*flash_attention.grouped_backward(
            q, k, v, out, lse[..., None], cotangent, heads, kv_heads,
            diffusion is None, window, *_blocks(window, True, diffusion),
            interpret=not on_tpu(), scale=scale, num_v_heads=v_heads,
            diffusion=diffusion), d_gate)


def _counted_flash_attention_bwd(heads, kv_heads, window, scale, out_dtype,
                                 v_heads, diffusion, residuals, cotangent):
    # Counted here, once a layer: the program under it is traced once.
    q, k, v = residuals[:3]
    flash_attention.count_backward(flash_attention.grouped_backward_kind(
        q, k, heads, *_blocks(window, True, diffusion),
        interpret=not on_tpu(),
        value_dim=v.shape[-1] // (v_heads or kv_heads)))
    return _flash_attention_bwd(heads, kv_heads, window, scale, out_dtype,
                                v_heads, diffusion, residuals, cotangent)


_flash_attention.defvjp(_flash_attention_fwd, _counted_flash_attention_bwd)


def _attention(config: DecoderConfig, q, k, v, gate, layer_type: str,
               heads: int, kv_heads: Optional[int] = None, out_dtype=None,
               v_heads: Optional[int] = None):
    """A layer's attention over rotated q (B, S, H x D) and k, v
    (B, S, Hkv x D; ``kv_heads`` of them, ``None``: the configuration's;
    or v of ``v_heads`` heads, a divisor of ``kv_heads``, at a width of
    their own, where key heads share their values), each query head's
    output times its ``gate`` (B, S, H) where there is one, the softmax
    scaled by ``attention_multiplier`` where the configuration has one,
    the output in ``out_dtype`` (``None``: the operands'), by what the
    trace can observe: the kernels where they beat the inline path
    (``flash_attention.beats_inline``)."""
    if kv_heads is None:
        kv_heads = config.num_kv_heads
    seq_len = q.shape[1]
    window = config.sliding_window if layer_type == SLIDING else None
    if window is not None and window >= seq_len:
        window = None       # the band covers the triangle: nothing to cut
    flash = flash_attention.beats_inline(seq_len)
    # block diffusion: the row is the sequence twice, clean copy first
    diffusion = ((config.diffusion_block, seq_len // 2)
                 if config.diffusion_block else None)
    kind = ("inline" if not flash else "window" if window is not None
            else "full" if diffusion is None else "diffusion")
    # Counted when a layer is traced, not when it runs.
    rt_metrics.counter(
        "rsdl_lm_attention_total",
        "Decoder layers' attentions traced, by what computes them (the "
        "Pallas kernels over a window's band, the whole triangle or block "
        "diffusion's mask, or "
        "XLA's inline softmax over materialized scores) and by their "
        "values: shaped as the keys, or wide, key heads sharing a value "
        "head of a width of its own so that a map's scores are made once",
        kind=kind, values="same" if v_heads is None else "wide").inc()
    if flash:
        rt_metrics.counter(
            "rsdl_lm_attention_kept_total",
            "Decoder layers' attentions traced whose forward kernel's "
            "output and log-sum-exp the layer's checkpoint keeps for the "
            "backward pass: every layer the kernels compute",
            kind=kind).inc()
    attend = _flash_attention if flash else _inline_attention
    if flash and diffusion is not None:
        _count_diffusion_tiles(diffusion)
    # the kernels take block diffusion's mask by its three numbers, XLA as
    # booleans; neither takes anything where the mask is causal
    return attend(q, k, v, gate, heads, kv_heads, window,
                  config.attention_multiplier, out_dtype, v_heads,
                  diffusion if flash or diffusion is None
                  else flash_attention.diffusion_seen(*diffusion))


def _count_diffusion_tiles(diffusion: flash_attention.Diffusion) -> None:
    """What block diffusion's mask lets through and what the kernels walk
    to cover it, a head of a layer, forward and backward."""
    block, clean_len = diffusion
    # Set when a layer is traced, not when it runs.
    for direction, backward in (("forward", False), ("backward", True)):
        bq, bk = flash_attention.planned_blocks(
            2 * clean_len, *_blocks(None, backward, diffusion),
            interpret=not on_tpu())
        visited, compared, live = flash_attention.diffusion_tiles(
            block, clean_len, bq, bk)
        rt_metrics.gauge(
            "rsdl_lm_attention_tiles_visited",
            "Tiles the attention kernels visit for one head of one layer "
            "under block diffusion's mask, those that hold a live pair, "
            "last layer traced", direction=direction).set(visited)
        rt_metrics.gauge(
            "rsdl_lm_attention_tiles_compared",
            "Of the tiles visited, those that compare positions (the mask "
            "cuts them), last layer traced",
            direction=direction).set(compared)
        rt_metrics.gauge(
            "rsdl_lm_attention_tile_pairs",
            "Query-key pairs in the tiles visited, what the kernels "
            "compute for one head of one layer, last layer traced",
            direction=direction).set(visited * bq * bk)
        rt_metrics.gauge(
            "rsdl_lm_attention_live_pairs",
            "Query-key pairs block diffusion's mask lets through for one "
            "head of one layer, what the attention needs, last layer "
            "traced", direction=direction).set(live)


# -- differential attention ----------------------------------------------------------


def _diff_lambda(config: DecoderConfig, layer: int, lp):
    """A differential layer's ``lambda``, a float32 scalar: ``exp(lq1 .
    lk1) - exp(lq2 . lk2) + lambda_init``."""
    return (jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"]))
            - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"]))
            + lambda_init(config.published_indices[layer]))


# Jitted for the scope's sake, as the attentions.
@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _diff_combine(maps, lam, scale, after: float, eps: float, dtype):
    """``after x RMSNorm(A1 V - lam x A2 V) x scale`` a head pair: ``maps``
    (B, S, Hkv / 2, 2, H / Hkv, 2 D) float32 holds ``A1 V`` and ``A2 V``
    of the H / Hkv head pairs that read a key/value pair, as the
    attention's heads lie; (B, S, pairs x 2 D) out, in ``dtype``."""
    with jax.named_scope(ATTENTION_SCOPE):
        diff = maps[:, :, :, 0] - lam * maps[:, :, :, 1]
        normed = diff * jax.lax.rsqrt(
            jnp.mean(diff * diff, axis=-1, keepdims=True) + eps)
        return (after * (normed * scale)).astype(dtype).reshape(
            *maps.shape[:2], -1)


def _differential(config: DecoderConfig, layer: int, q, k, v, lp,
                  layer_type: str):
    """Differential attention over q (B, S, H x D), k and v (B, S, Hkv x
    D). Query heads ``(2i, 2i + 1)`` are pair ``i``'s two maps' queries,
    key heads ``(2j, 2j + 1)`` their keys, value heads ``(2j, 2j + 1)``
    side by side the values ``V_j`` (2 D wide) of key/value pair ``j``,
    which the ``H / Hkv`` query pairs ``i`` with ``i // (H / Hkv) = j``
    read. One attention head a map, in one call of :func:`_attention`: H
    query heads ordered ``(j, map, pair of j)`` so that the two query
    heads of a key head and the four of a value pair are neighbours, the
    Hkv key heads of D and the Hkv / 2 value heads ``V_j`` of 2 D as the
    projections left them, so a map's scores are made once and its
    product with the values is 2 D wide (the released form's four
    attentions a pair, each map over each half of ``V_j``, made every
    map's scores twice); then :func:`_diff_combine`, which reads the maps
    as the heads lie. The attention's output stays float32 until the
    subtraction: at the seeded weights both maps are near the running
    mean of the values, ``lambda``'s gradient is what is left once the
    pairs' norm has taken their common part out, and outputs rounded to
    bf16 put 2-15 % on it (PERF.md section 6, PR 40). ``lambda`` goes out
    as the step's ``diff_attention`` of this layer."""
    b, s, _ = q.shape
    d = config.head_dim
    groups, per = config.num_kv_heads // 2, config.num_heads \
        // config.num_kv_heads
    # one attention head a (j, map, query pair of j)
    q = q.reshape(b, s, groups, per, 2, d).swapaxes(3, 4).reshape(b, s, -1)
    maps = _attention(config, q, k, v, None, layer_type, config.num_heads,
                      out_dtype=jnp.float32, v_heads=groups)
    lam = _diff_lambda(config, layer, lp)
    tracing.step_stat("diff_attention", jnp.reshape(lam, (1,)), layer=layer)
    return _diff_combine(
        maps.reshape(b, s, groups, 2, per, 2 * d), lam, lp["subln"],
        1.0 - lambda_init(config.published_indices[layer]),
        config.rms_norm_eps, q.dtype)


# -- the dense MLP and the shared expert -----------------------------------------


@jax.custom_vjp
def _swiglu(x, gate, up, down):
    """``(silu(x G) * (x U)) D`` for x (B, S, h) in the compute dtype and
    float32 weights G, U (h, f), D (f, h), cast to x's dtype for the
    products. The backward is written out: it keeps ``x G`` and ``x U``
    and makes nothing again, six products to the forward's three. Under a
    ``jax.checkpoint`` those two outlive the forward pass only if its
    policy saves their names (``KEPT_GATE``, ``KEPT_UP``: ``decode``'s
    does, where there is room): a plain one runs both products a second
    time to have them, eleven a step."""
    return _swiglu_fwd(x, gate, up, down)[0]


# Jitted for their names' sake (models/bert.py:_masked_nll_fwd).
@jax.jit
def _swiglu_fwd(x, gate, up, down):
    with jax.named_scope(MLP_SCOPE):
        # The two residuals an MLP half's checkpoint keeps where there is
        # room (``decode``): the backward pass then has them without these
        # two products run again. x, the half's norm, it makes again.
        g = checkpoint_name(x @ gate.astype(x.dtype), KEPT_GATE)
        u = checkpoint_name(x @ up.astype(x.dtype), KEPT_UP)
        h = (jax.nn.silu(g.astype(jnp.float32))
             * u.astype(jnp.float32)).astype(x.dtype)
        return h @ down.astype(x.dtype), (x, gate, up, down, g, u)


@jax.jit
def _swiglu_bwd(residuals, dy):
    x, gate, up, down, g, u = residuals
    with jax.named_scope(MLP_SCOPE):
        tokens = (((0, 1), (0, 1)), ((), ()))    # a.T @ b over (B, S)

        def weight_grad(a, b):
            return jax.lax.dot_general(a, b, tokens,
                                       preferred_element_type=jnp.float32)

        dy = dy.astype(x.dtype)
        g32, u32 = g.astype(jnp.float32), u.astype(jnp.float32)
        sig = jax.nn.sigmoid(g32)
        act = g32 * sig
        dh = (dy @ down.astype(x.dtype).T).astype(jnp.float32)
        du = (dh * act).astype(x.dtype)
        dg = (dh * u32 * sig * (1.0 + g32 * (1.0 - sig))).astype(x.dtype)
        dx = dg @ gate.astype(x.dtype).T + du @ up.astype(x.dtype).T
        # float32 sums, handed back as the weights are held (float32; the
        # benchmark's bf16_params control holds them in bfloat16)
        return (dx, weight_grad(x, dg).astype(gate.dtype),
                weight_grad(x, du).astype(up.dtype),
                weight_grad((act * u32).astype(x.dtype),
                            dy).astype(down.dtype))


_swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


def _mlp(kind: str, kept: bool, x, gate, up, down):
    # Counted when a layer is traced, not when it runs.
    rt_metrics.counter(
        "rsdl_lm_mlp_total",
        "Dense SwiGLUs traced, by what they are: a dense layer's MLP or a "
        "sparse-expert layer's shared expert", kind=kind).inc()
    if kept:
        rt_metrics.counter(
            "rsdl_lm_mlp_kept_total",
            "Dense SwiGLUs traced whose two first products, x G and x U, "
            "the layer's checkpoint keeps for the backward pass: every "
            "layer the device's memory has room for", kind=kind).inc()
    return _swiglu(x, gate, up, down)


# -- the decoder ---------------------------------------------------------------


def _in_layer(half):
    """``half`` (a layer's first or second half) under ``LAYER_SCOPE``:
    what it runs between its named parts is the layer's."""

    @functools.wraps(half)
    def scoped(*args, **kwargs):
        with jax.named_scope(LAYER_SCOPE):
            return half(*args, **kwargs)

    return scoped


def _rms_norm(x, scale, eps: float):
    xf = x.astype(jnp.float32)
    normed = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                                + eps)
    return (normed * scale).astype(x.dtype)


def _layer_norm(x, scale, bias, eps: float):
    xf = x.astype(jnp.float32)
    centered = xf - jnp.mean(xf, axis=-1, keepdims=True)
    normed = centered * jax.lax.rsqrt(
        jnp.mean(centered * centered, axis=-1, keepdims=True) + eps)
    return (normed * scale + bias).astype(x.dtype)


def _norm(config: DecoderConfig, x, p, name: str):
    """The configuration's norm of the residual stream under ``p``'s
    scale ``name`` (and, LayerNorm, its bias ``name_bias``)."""
    with jax.named_scope(NORM_SCOPE):
        if config.norm == LAYER_NORM:
            return _layer_norm(x, p[name], p[f"{name}_bias"],
                               config.rms_norm_eps)
        return _rms_norm(x, p[name], config.rms_norm_eps)


def _experts(config: DecoderConfig, layer: int, x, lp):
    """The held experts' part of a layer's sparse-expert sum, (B, S, h).
    What its walk did this step (held pairs, tiles, rounds) goes out as
    the step's ``moe_walk`` of this layer."""
    first, count = config.experts_held
    b, s, h = x.shape
    tile = moe.tile_rows(b * s, config.top_k, config.num_experts)
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
    rt_metrics.gauge("rsdl_moe_tile_rows",
                     "Rows of one expert in a tile of the expert layer's "
                     "walk, last layer traced").set(tile)
    # A sigmoid router's selection bias is a buffer in the parameter tree:
    # no gradient reaches it, Adam leaves it where it finds it, and its
    # balancing update (below) is the train step's to add.
    bias = (jax.lax.stop_gradient(lp["expert_bias"])
            if config.expert_bias else None)
    router = (lp["router"] if config.router_trains
              else jax.lax.stop_gradient(lp["router"]))
    if config.expert_bias_speed:
        even = b * s * config.top_k / config.num_experts
        tracing.leaf_move(
            (f"layer_{layer}", "expert_bias"),
            config.expert_bias_speed * (1.0 - moe.loads(
                jax.lax.stop_gradient(x.reshape(b * s, h)), router, bias,
                config.top_k) / even))
    out, walk = moe.moe_counted(
        x.reshape(b * s, h), router, lp["gate"], lp["up"], lp["down"],
        config.experts_held, config.top_k, tile, config.routed_scale, bias)
    tracing.step_stat("moe_walk", walk, layer=layer)
    return out.reshape(b, s, h)


# Jitted for the scope's sake, as the attentions: the products of an
# attention half that are no kernel's (q, k, v, the gate, ``wo``), their
# weight gradients and the forward made again. The backward is autodiff's.
@jax.jit
def _project(x, weight):
    """``x @ weight``, the float32 ``weight`` cast to x's dtype."""
    with jax.named_scope(PROJ_SCOPE):
        return x @ weight.astype(x.dtype)


def _project_in(x, weight, kept: bool):
    """:func:`_project` of a first half's in-projection, a product that
    feeds the half's operator. ``kept``: the half's checkpoint keeps it,
    so the backward pass has it without the product run again (three
    products a weight a step, not four); named only then, so that a half
    that keeps nothing traces to what it always did."""
    out = _project(x, weight)
    return checkpoint_name(out, KEPT_PROJ) if kept else out


def _added(config: DecoderConfig, x, out):
    """``x + residual_multiplier x out``, a half's output joining the
    residual stream."""
    if config.residual_multiplier == 1.0:
        return x + out
    return x + (out.astype(jnp.float32)
                * config.residual_multiplier).astype(x.dtype)


def _head_norm(x, heads: int, scale, eps: float):
    """(B, S, heads x D) -> the same, RMSNorm over each head's D under
    the one ``scale`` (D,), float32 inside."""
    b, s, width = x.shape
    with jax.named_scope(NORM_SCOPE):
        return _rms_norm(x.reshape(b, s, heads, width // heads), scale,
                         eps).reshape(b, s, width)


def _count_place(in_vmem: bool) -> None:
    # Counted when a layer is traced, not when it runs.
    rt_metrics.counter(
        "rsdl_lm_place_total",
        "Rotary attention layers' q and k projections traced (two a layer), "
        "by what places their heads, the heads' RMSNorm where there is one "
        "and the rotary: a Pallas kernel each way that reads the projection "
        "once (vmem) or XLA's float32 passes and autodiff (xla)",
        kind="vmem" if in_vmem else "xla").inc()


@_in_layer
def _attention_half(config: DecoderConfig, layer: int, x, lp,
                    kept: bool = False):
    """x + attention(RMSNorm(x)), the first half of a layer; with
    ``qk_norm`` each q head and each k head is normed before it is
    rotated. ``kept``: the half's checkpoint keeps q, k, v and the head
    gate as their products left them (what the caller's policy does)."""
    layer_type, heads = config.layer_types[layer], config.heads(layer)
    if config.rotary:
        cos, sin = (_twice_rope_tables if config.diffusion_block
                    else _rope_tables)(config, layer_type, x.shape[1])
        rotated = rotated_dims(config, layer_type)

    def placed(projected, count, scale):
        scale = lp[scale] if config.qk_norm else None
        if config.rotary:
            in_vmem = rope.places_in_vmem(
                projected.shape[1], projected.shape[2], config.head_dim,
                rotated, projected.dtype)
            _count_place(in_vmem)
            if in_vmem:
                return rope.placed_in_vmem(projected, scale, cos, sin,
                                           rotated, config.rms_norm_eps,
                                           not on_tpu())
        if scale is not None:
            projected = _head_norm(projected, count, scale,
                                   config.rms_norm_eps)
        return (_rope(projected, count, cos, sin, rotated) if config.rotary
                else projected)

    a = _norm(config, x, lp, "attn_norm")
    q = placed(_project_in(a, lp["wq"], kept), heads, "q_layernorm")
    k = placed(_project_in(a, lp["wk"], kept), config.num_kv_heads,
               "k_layernorm")
    v = _project_in(a, lp["wv"], kept)
    gate = (jax.nn.sigmoid(
        _project_in(a, lp["wg"], kept).astype(jnp.float32))
            if config.attention_gate else None)
    return _added(config, x, _project(
        _attention(config, q, k, v, gate, layer_type, heads), lp["wo"]))


def _count_ssm(kind: str) -> None:
    # Counted when a layer is traced, not when it runs.
    rt_metrics.counter(
        "rsdl_lm_ssm_total",
        "Decoder layers' state-space mixers traced, by what computes the "
        "scan: Mamba-2's chunked scan by Pallas kernels that keep a head's "
        "chunk-by-chunk tile in VMEM or by XLA's products over chunks "
        "(chunked_*), Mamba-1's selective scan by Pallas kernels that keep "
        "the state in VMEM or by XLA's loops (selective_*)", kind=kind).inc()


def _count_conv(in_vmem: bool) -> None:
    # Counted when a layer is traced, not when it runs.
    rt_metrics.counter(
        "rsdl_lm_conv_total",
        "Decoder layers' state-space mixers and gated short convolutions "
        "traced, by what computes their depthwise convolution: a Pallas "
        "kernel each way that reads a block once (vmem) or XLA's pad, "
        "shifted slices and autodiff (xla)",
        kind="vmem" if in_vmem else "xla").inc()


def _conv_silu(conv, x, lp):
    """A mixer's convolution (``conv``: its ``causal_conv_silu``) over
    ``x`` (B, S, C), counted by what computes it."""
    _count_conv(ssd.convs_in_vmem(x.shape[1], x.shape[2],
                                  lp["conv_w"].shape[0], x.dtype))
    return conv(x, lp["conv_w"], lp["conv_b"])


@_in_layer
def _conv_half(config: DecoderConfig, layer: int, x, lp,
               kept: bool = False):
    """x + ``(C * conv(B * u)) W_out`` with ``B | C | u = norm(x) W_in``,
    a ``conv`` layer's first half (LFM2's gated short convolution): the
    two projections under ``PROJ_SCOPE``, the gates and the convolution
    between them under ``SCONV_SCOPE``. ``kept``: the half's checkpoint
    keeps ``B | C | u``."""
    n = _norm(config, x, lp, "conv_norm")
    bcu = _project_in(n, lp["in_proj"], kept)
    _count_conv(sconv.convs_in_vmem(bcu.shape[1], bcu.shape[2] // 3,
                                    lp["conv_w"].shape[0], bcu.dtype))
    return _added(config, x, _project(
        sconv.causal_gated_conv(bcu, lp["conv_w"]), lp["out_proj"]))


@_in_layer
def _mamba_half(config: DecoderConfig, layer: int, x, lp,
                kept: bool = False):
    """x + Mamba-2(RMSNorm(x)), a ``mamba`` layer's first half: the two
    projections under ``PROJ_SCOPE``, what lies between them under
    ``SSM_SCOPE``. How much state crossed the scan's chunks goes out as
    the step's ``ssm_scan`` of this layer. ``kept``: the half's
    checkpoint keeps ``z | x B C | dt``."""
    b, s, _ = x.shape
    width, state = config.mamba_width, config.mamba_state
    in_vmem = ssd.scans_in_vmem(
        config.mamba_chunk, config.mamba_heads, config.mamba_head_dim, state,
        x.dtype)
    _count_ssm("chunked_vmem" if in_vmem else "chunked_xla")
    rt_metrics.gauge("rsdl_lm_ssm_chunk",
                     "Positions in a chunk of the state-space scan, last "
                     "layer traced").set(config.mamba_chunk)
    rt_metrics.gauge("rsdl_lm_ssm_in_vmem",
                     "Whether the last state-space scan traced keeps its "
                     "chunk-by-chunk tiles in VMEM (1) or sends them "
                     "through HBM (0)").set(int(in_vmem))
    n = _norm(config, x, lp, "mamba_norm")
    z, xbc, dt = jnp.split(_project_in(n, lp["in_proj"], kept),
                           [width, 2 * width + 2 * state], axis=-1)
    xbc = _conv_silu(ssd.causal_conv_silu, xbc, lp)
    xs, b_in, c_in = jnp.split(xbc, [width, width + state], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
    y, crossed = ssd.ssd_counted(
        xs.reshape(b, s, config.mamba_heads, config.mamba_head_dim), dt,
        lp["a_log"], b_in, c_in, lp["d"], config.mamba_chunk)
    tracing.step_stat("ssm_scan", crossed, layer=layer)
    y = ssd.gated_rms_norm(y.reshape(b, s, width), z, lp["ssm_norm"],
                           config.rms_norm_eps)
    return _added(config, x, _project(y, lp["out_proj"]))


def _shared(tensors, kind: str):
    """What an earlier layer made, as a later layer reads it."""
    # Counted when a reader is traced, not when it runs.
    rt_metrics.counter(
        "rsdl_lm_shared_total",
        "Decoder layers traced that read what an earlier layer made: a "
        "Gated Memory Unit the selective scan's output (memory), a "
        "cross-attention layer the full attention layer's keys and values "
        "(kv)", kind=kind).inc()
    return tensors


@_in_layer
def _differential_half(config: DecoderConfig, layer: int, x, lp, kv=None,
                       kept: bool = False):
    """``(x + differential attention(norm(x)), (k, v))``, a differential
    layer's first half, without positions. ``kv``: an earlier layer's keys
    and values, which a ``cross`` layer (no ``wk``, no ``wv``) attends
    over; they come back as they were. ``kept``: the half's checkpoint
    keeps q, and k and v where the layer makes them: a ``full_attention``
    layer's, which leave the half as results too, are the one array
    either way."""
    layer_type = config.layer_types[layer]
    a = _norm(config, x, lp, "attn_norm")
    q = _project_in(a, lp["wq"], kept)
    if layer_type == CROSS:
        k, v = _shared(kv, "kv")
        layer_type = FULL       # causal over the whole row
    else:
        k = _project_in(a, lp["wk"], kept)
        v = _project_in(a, lp["wv"], kept)
    out = _differential(config, layer, q, k, v, lp, layer_type)
    return _added(config, x, _project(out, lp["wo"])), (k, v)


@_in_layer
def _mamba1_half(config: DecoderConfig, layer: int, x, lp,
                 kept: bool = False):
    """``(x + Mamba-1(norm(x)), y)``, a ``mamba1`` layer's first half and
    its scan's output before the gate: the four projections under
    ``PROJ_SCOPE``, what lies between them under ``SSCAN_SCOPE``. How much
    state crossed the scan's chunks goes out as the step's ``ssm_scan`` of
    this layer. ``kept``: the half's checkpoint keeps ``u | z``, ``r | B |
    C`` and ``r W_dt``."""
    width, state = config.mamba1_width, config.mamba1_state
    rank = config.mamba1_dt_rank
    in_vmem = selective_scan.scans_in_vmem(width, state, config.mamba_chunk)
    _count_ssm("selective_vmem" if in_vmem else "selective_xla")
    n = _norm(config, x, lp, "mamba_norm")
    u, z = jnp.split(_project_in(n, lp["in_proj"], kept), 2, axis=-1)
    u = _conv_silu(selective_scan.causal_conv_silu, u, lp)
    r, b_in, c_in = jnp.split(_project_in(u, lp["x_proj"], kept),
                              [rank, rank + state], axis=-1)
    dt = selective_scan.softplus_step(
        _project_in(r, lp["dt_proj"], kept), lp["dt_bias"])
    y, crossed = selective_scan.selective_scan_counted(
        u, dt, lp["a_log"], b_in, c_in, lp["d"], config.mamba_chunk)
    tracing.step_stat("ssm_scan", crossed, layer=layer)
    return _added(config, x, _project(selective_scan.gated(y, z),
                                      lp["out_proj"])), y


@jax.custom_vjp
def _gmu_gated(gate, memory):
    """``silu(gate) * memory``, float32 inside, in ``gate``'s dtype. The
    backward is written out. Both passes sit between barriers: left to
    XLA they become the epilogue of the product before them (``W_1``'s;
    ``W_2``'s gradient's) or the prologue of the one after, under that
    product's name, and a trace shows nothing under ``GMU_SCOPE``; what
    the barriers cost is one more pass over (S, 5,120) bf16 each way."""
    return _gmu_gated_fwd(gate, memory)[0]


# Jitted for their names' sake (``_swiglu_fwd``).
@jax.jit
def _gmu_gated_fwd(gate, memory):
    with jax.named_scope(GMU_SCOPE):
        gate, memory = jax.lax.optimization_barrier((gate, memory))
        out = (jax.nn.silu(gate.astype(jnp.float32))
               * memory.astype(jnp.float32)).astype(gate.dtype)
        return jax.lax.optimization_barrier(out), (gate, memory)


@jax.jit
def _gmu_gated_bwd(residuals, d_out):
    gate, memory = residuals
    with jax.named_scope(GMU_SCOPE):
        d_out = jax.lax.optimization_barrier(d_out)
        g32, d32 = gate.astype(jnp.float32), d_out.astype(jnp.float32)
        sig = jax.nn.sigmoid(g32)
        d_gate = (d32 * memory.astype(jnp.float32) * sig
                  * (1.0 + g32 * (1.0 - sig))).astype(gate.dtype)
        d_memory = (d32 * g32 * sig).astype(memory.dtype)
        return jax.lax.optimization_barrier((d_gate, d_memory))


_gmu_gated.defvjp(_gmu_gated_fwd, _gmu_gated_bwd)


@_in_layer
def _gmu_half(config: DecoderConfig, layer: int, x, lp, memory,
              kept: bool = False):
    """x + ``(silu(norm(x) W_1) * M) W_2``, a ``gmu`` layer's first half:
    a Gated Memory Unit over ``memory``, an earlier Mamba-1 layer's scan
    output. ``kept``: the half's checkpoint keeps ``norm(x) W_1``."""
    n = _norm(config, x, lp, "gmu_norm")
    mixed = _gmu_gated(_project_in(n, lp["w1"], kept),
                       _shared(memory, "memory"))
    return _added(config, x, _project(mixed, lp["w2"]))


@_in_layer
def _mlp_half(config: DecoderConfig, layer: int, x, lp, kept: bool = False):
    """x + MLP(RMSNorm(x)), the second half of a layer: the dense SwiGLU,
    or the held experts' part of the routed sum and the shared expert.
    ``kept``: the half's checkpoint keeps the SwiGLU's ``x G`` and ``x U``
    (what the caller's policy does; counted here)."""
    if config.mlp_type(layer) == DENSE:
        n = _norm(config, x, lp, "mlp_norm")
        return _added(config, x, _mlp("dense", kept, n, lp["gate"],
                                      lp["up"], lp["down"]))
    n = _norm(config, x, lp, "moe_norm")
    out = _added(config, x, _experts(config, layer, n, lp))
    if config.shared_expert_width:
        out = _added(config, out, _mlp(
            "shared", kept, n, lp["shared_gate"], lp["shared_up"],
            lp["shared_down"]))
    return out


# -- what the halves' checkpoints keep ------------------------------------------

#: What the rest of a step is taken to hold in temporaries when the kept
#: products are given their room: rows of ``hidden_size`` in the compute
#: dtype a token. The compiled steps of the four 8,192-token cells hold 37
#: to 90 without the kept products (a described v5e's memory analysis, PR
#: 41: 5.63 GB at 32,768 tokens of 2,304, 1.75 GB at 8,192 of 2,048, 5.90
#: GB at 16,384 of 2,048, 3.76 GB at 8,192 of 2,560).
STEP_ROWS_A_TOKEN = 96


def kept_products_bytes(config: DecoderConfig, layer: int,
                        tokens: int) -> int:
    """Bytes of ``x G`` and ``x U`` of the SwiGLU layer ``layer``'s MLP
    half runs through ``_swiglu``, a dense layer's MLP or a sparse layer's
    shared expert, over ``tokens`` tokens: 0 where it has neither."""
    width = (config.intermediate_size if config.mlp_type(layer) == DENSE
             else config.shared_expert_width)
    return 2 * tokens * width * jnp.dtype(config.compute_dtype).itemsize


def in_projections_bytes(config: DecoderConfig, layer: int,
                         tokens: int) -> int:
    """Bytes of what layer ``layer``'s first half's in-projections give
    over ``tokens`` tokens, the products ``_project_in`` names: an
    attention layer's q, k, v and head gate (a ``cross`` layer's q alone:
    its keys and values are the ``full_attention`` layer's, counted
    there and nowhere else), a ``mamba`` layer's ``z | x B C | dt``, a
    ``mamba1`` layer's ``u | z``, ``r | B | C`` and ``r W_dt``, a ``gmu``
    layer's ``norm(x) W_1``, a ``conv`` layer's ``B | C | u``."""
    kind = config.layer_types[layer]
    if kind == MAMBA:
        width = (2 * config.mamba_width + 2 * config.mamba_state
                 + config.mamba_heads)
    elif kind == MAMBA1:
        width = (3 * config.mamba1_width + config.mamba1_dt_rank
                 + 2 * config.mamba1_state)
    elif kind == GMU:
        width = config.mamba1_width
    elif kind == CONV:
        width = 3 * config.hidden_size
    else:
        heads = config.heads(layer)
        width = heads * config.head_dim
        if kind != CROSS:
            width += 2 * config.num_kv_heads * config.head_dim
        if config.attention_gate:
            width += heads
    return tokens * width * jnp.dtype(config.compute_dtype).itemsize


def _device_memory(mesh: Optional[Mesh]) -> Optional[Tuple[int, int]]:
    """The allocator's ``(bytes_limit, bytes_in_use)`` on the device the
    step is traced for, as it stands now: while a training step is traced
    its parameters and optimizer state are what is in use. ``None`` where
    the backend keeps no such statistics (the CPU's)."""
    device = jax.devices()[0] if mesh is None else mesh.devices.flat[0]
    stats = device.memory_stats()
    if not stats or "bytes_limit" not in stats:
        return None
    return int(stats["bytes_limit"]), int(stats.get("bytes_in_use", 0))


def keep_room(config: DecoderConfig, tokens: int,
              memory: Optional[Tuple[int, int]]) -> Optional[int]:
    """Bytes the halves' checkpoints may keep between them in a step
    over ``tokens`` tokens traced with ``memory`` = ``(bytes_limit,
    bytes_in_use)`` on its device: the limit, less a sixteenth of it, less
    what is in use, less ``STEP_ROWS_A_TOKEN`` rows of ``hidden_size`` a
    token for the rest of the step. ``None`` (no allocator to ask): no
    bound."""
    if memory is None:
        return None
    limit, in_use = memory
    return (limit - limit // 16 - in_use - STEP_ROWS_A_TOKEN * tokens
            * config.hidden_size * jnp.dtype(config.compute_dtype).itemsize)


def _taken_in_order(needs, room: Optional[int]):
    """``(which of the layers' ``needs`` bytes ``room`` lasts for, what is
    left of it)``: layers take theirs in order, and one it does not last
    for (or that needs none) takes nothing. ``None``: no bound."""
    kept = []
    for need in needs:
        keeps = need > 0 and (room is None or need <= room)
        if keeps and room is not None:
            room -= need
        kept.append(keeps)
    return tuple(kept), room


def _mlp_halves_taken(config: DecoderConfig, tokens: int,
                      room: Optional[int]):
    """:func:`_taken_in_order` of the layers' SwiGLUs' kept products."""
    return _taken_in_order(
        [kept_products_bytes(config, layer, tokens)
         for layer in range(config.num_layers)], room)


def mlp_halves_kept(config: DecoderConfig, tokens: int,
                    room: Optional[int]) -> Tuple[bool, ...]:
    """Which layers' MLP halves keep their SwiGLU's ``x G`` and ``x U``
    across the checkpoint: layers take their bytes of ``room`` in order
    while it lasts, and a layer it does not last for is made again whole,
    as every layer was. ``None``: every SwiGLU keeps."""
    return _mlp_halves_taken(config, tokens, room)[0]


def first_halves_kept(config: DecoderConfig, tokens: int,
                      room: Optional[int]) -> Tuple[bool, ...]:
    """Which layers' first halves keep their in-projections across the
    checkpoint, out of the same ``room``: the SwiGLUs take theirs first
    (:func:`mlp_halves_kept`, so none of them goes without for a first
    half's sake), then the first halves take theirs of what is left, in
    layer order while it lasts. ``None``: every first half keeps."""
    _, left = _mlp_halves_taken(config, tokens, room)
    return _taken_in_order(
        [in_projections_bytes(config, layer, tokens)
         for layer in range(config.num_layers)], left)[0]


@functools.lru_cache(maxsize=None)
def _keeping(*names: str):
    """The checkpoint policy that keeps the arrays named ``names`` and a
    half's input; ``None``, a plain checkpoint, for no name. One object a
    set of names: JAX caches what it derives from a half by its policy's
    identity, and layers alike then share one program."""
    return (jax.checkpoint_policies.save_only_these_names(*names)
            if names else None)


def _checked(config: DecoderConfig) -> None:
    first, count = config.experts_held
    if first + count > config.num_experts:
        raise ValueError(f"experts_held {config.experts_held} reaches past "
                         f"the router's {config.num_experts} experts")
    for name, kinds, known in (
            ("layer_types", config.layer_types,
             (SLIDING, FULL, MAMBA, MAMBA1, GMU, CROSS, CONV)),
            ("mlp_layer_types", config.mlp_layer_types, (DENSE, SPARSE))):
        for kind in kinds or ():
            if kind not in known:
                raise ValueError(f"unknown {name} entry {kind!r}")
    for name in ("mlp_layer_types", "heads_per_layer"):
        per_layer = getattr(config, name)
        if per_layer is not None and len(per_layer) != config.num_layers:
            raise ValueError(f"{name} names {len(per_layer)} layers, "
                             f"layer_types {config.num_layers}")
    if MAMBA in config.layer_types and config.mamba_heads < 1:
        raise ValueError("a mamba layer needs mamba_heads")
    if config.norm not in (RMS_NORM, LAYER_NORM):
        raise ValueError(f"unknown norm {config.norm!r}")
    if config.expert_bias_speed and not config.expert_bias:
        raise ValueError("expert_bias_speed moves an expert_bias")
    if CONV in config.layer_types and config.conv_taps < 1:
        raise ValueError("a conv layer needs conv_taps")
    kinds = config.layer_types
    if MAMBA1 in kinds and (config.mamba1_width < 1
                            or config.mamba1_dt_rank < 1):
        raise ValueError("a mamba1 layer needs mamba1_width and "
                         "mamba1_dt_rank")
    for layer, kind in enumerate(kinds):
        if kind == GMU and MAMBA1 not in kinds[:layer]:
            raise ValueError(f"layer {layer} is a gmu with no mamba1 layer "
                             "before it to give out its memory")
        if kind == CROSS and FULL not in kinds[:layer]:
            raise ValueError(f"layer {layer} is a cross layer with no "
                             "full_attention layer before it to give out "
                             "its keys and values")
    if CROSS in kinds and not config.differential:
        raise ValueError("a cross layer is differential attention")
    if config.diffusion_block and (set(kinds) != {FULL}
                                   or config.differential):
        raise ValueError(
            "block diffusion's mask is defined for full attention only: "
            "not beside a window, a convolution, a scan, a memory unit, "
            "cross-attention or differential attention (got layer_types "
            f"{sorted(set(kinds))}, differential={config.differential})")
    if config.diffusion_block < 0 or not 0.0 < config.diffusion_eps <= 1.0:
        raise ValueError("diffusion_block counts tokens and diffusion_eps "
                         "is a probability above 0")
    if config.differential:
        if (config.published_indices is None
                or len(config.published_indices) != config.num_layers):
            raise ValueError("differential attention needs each layer's "
                             "published index")
        if (config.rotary or config.attention_gate or config.qk_norm
                or config.heads_per_layer is not None
                or config.num_kv_heads % 2
                or config.num_heads % config.num_kv_heads):
            raise ValueError(
                "differential attention pairs adjacent heads (an even "
                "count of key/value heads that divides the query heads'), "
                "without positions, head gate, head norms or heads by "
                "layer")


def decode(config: DecoderConfig, params: Dict[str, Any],
           token_ids: jax.Array, mesh: Optional[Mesh] = None) -> jax.Array:
    """token_ids (B, S) int32 -> hidden states (B, S, hidden) in the
    compute dtype, after the last layer's residual (before the final
    norm). Every layer is made again in the backward pass, but for its
    attention kernel's two results and, where the device's memory has
    room, its SwiGLU's two first products and then its first half's
    in-projections. What later layers read of an earlier one (a
    ``mamba1`` layer's scan output, a ``full_attention`` layer's keys and
    values) leaves its half's checkpoint as a result and enters theirs as
    an argument: kept once (a full layer that keeps its in-projections
    keeps those keys and values as the one array, and makes neither
    again), and autodiff sums what its readers and its own layer hand
    back before its half's backward runs.

    With ``diffusion_block`` a row of ``token_ids`` is a sequence twice,
    the clean copy and then the noised one (:func:`diffusion_noise`), S =
    2 L positions that every layer's attention reads under block
    diffusion's mask, both copies at rotary positions 0..L-1; what the
    halves keep is counted over the 2 L.

    ``mesh``: the mesh the calling step is jitted over (``ops/embedding.py:
    lookup``'s convention). One device only: the expert layer's exchange
    across chips does not exist, and nothing here stands in for it."""
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "the decoder runs one chip's share of an expert-parallel "
            f"deployment; a mesh of {mesh.size} devices needs the expert "
            "layer's exchange across chips, which does not exist yet")
    _checked(config)
    with jax.named_scope(EMBED_SCOPE):
        x = jnp.take(params["embed"], token_ids, axis=0, mode="clip")
        if config.embedding_multiplier != 1.0:
            x = x * config.embedding_multiplier
        x = x.astype(config.compute_dtype)
    # Of an attention half, its bf16 input and the forward kernel's two
    # results are kept, where the kernels run: 272 MB a layer of 4 rows of
    # 8,192 tokens and 32 heads, against 19.5 ms (the whole triangle) or
    # 8.4 ms (a window) of kernel run a second time. The inline attention
    # names neither, and its half keeps its input only.
    kernel_results = (KEPT_OUT, KEPT_LSE)
    # Of an MLP half, its bf16 input and its SwiGLU's ``x G`` and ``x U``,
    # in the layers there is room for: 268 MB a layer of one row of 8,192
    # tokens and a width of 8,192 (2.68 GB over ten such layers, 2.01 GB
    # over six of 10,240; 0.67 GB for a dense layer and four shared
    # experts of 512 at two rows), against 2.8 ms a layer under
    # ``rsdl.lm.mlp`` with the two products run a second time (7.0 ms at
    # 10,240; PERF.md section 6, PR 41). The half's norm, the routed
    # experts and ``silu(x G) * (x U)`` are made again as they were.
    keep_products = _keeping(KEPT_GATE, KEPT_UP)
    room = keep_room(config, token_ids.size, _device_memory(mesh))
    mlp_kept = mlp_halves_kept(config, token_ids.size, room)
    # Of any first half, its in-projections too, in the layers there is
    # room for once the SwiGLUs have theirs: 201 MB a ``conv`` layer's
    # ``B | C | u`` at two rows of 8,192 tokens of 2,048 and 101 MB an
    # attention layer's q, k, v there, 139 MB a Mamba layer's ``z | x B C
    # | dt`` at one row, 270-338 MB a layer of 48 or 64 gated heads of 128
    # at two, against one product a projection weight a step (PERF.md
    # section 6, PR 46). The half's norm, head norms, rotary, convolution
    # and scan are made again as they were.
    first_kept = first_halves_kept(config, token_ids.size, room)
    if room is not None:
        # Set when a step is traced, as the counters beside it.
        rt_metrics.gauge(
            "rsdl_lm_mlp_keep_room_bytes",
            "Bytes the device's memory had for the halves' kept products "
            "(the SwiGLUs' first, then the first halves' in-projections), "
            "last decoder traced").set(room)

    def first_half(layer, half, *names, stats=False):
        """``half`` of layer ``layer`` under its checkpoint, which keeps
        ``names`` and, where there is room, the half's in-projections."""
        kept = first_kept[layer]
        if kept:
            names += (KEPT_PROJ,)
            # Counted when a half is traced, not when it runs.
            rt_metrics.counter(
                "rsdl_lm_proj_kept_total",
                "Decoder layers' first halves traced whose checkpoint keeps "
                "the outputs of their in-projections for the backward pass, "
                "by the layer's kind: every layer the device's memory has "
                "room for once the SwiGLUs have taken theirs",
                kind=config.layer_types[layer]).inc()
        half = functools.partial(half, config, layer, kept=kept)
        # What a half records of the step's own counters leaves its
        # checkpoint as an output (counted in the forward pass, not again
        # when the half is made again).
        made = jax.checkpoint(
            tracing.with_step_stats(half) if stats else half,
            policy=_keeping(*names))
        return tracing.step_stats_of(made) if stats else made

    memory = kv = None      # the last mamba1 layer's y, full layer's k, v
    for layer in range(config.num_layers):
        # Each half is made again on its own in the backward pass: the
        # MLP half's backward runs before the attention half's q, k and v
        # exist again (or are read again, where they are kept), so the two
        # halves' activations never sit on the chip together.
        lp = params[f"layer_{layer}"]
        layer_type = config.layer_types[layer]
        if layer_type == MAMBA:
            x = first_half(layer, _mamba_half, stats=True)(x, lp)
        elif layer_type == MAMBA1:
            x, memory = first_half(layer, _mamba1_half, stats=True)(x, lp)
        elif layer_type == GMU:
            x = first_half(layer, _gmu_half)(x, lp, memory)
        elif layer_type == CONV:
            x = first_half(layer, _conv_half)(x, lp)
        elif config.differential:
            x, made = first_half(layer, _differential_half, *kernel_results,
                                 stats=True)(x, lp, kv)
            if layer_type == FULL:
                kv = made
        else:
            x = first_half(layer, _attention_half, *kernel_results)(x, lp)
        x = tracing.step_stats_of(jax.checkpoint(
            tracing.with_step_stats(functools.partial(
                _mlp_half, config, layer, kept=mlp_kept[layer])),
            policy=keep_products if mlp_kept[layer] else None))(x, lp)
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


def _block_nll(x, head, targets, logits_scaling: float = 1.0,
               weights=None):
    """Summed cross-entropy of the tokens of ``x`` (n, h) whose
    ``targets`` (n,) are not ``IGNORE_ID``, the logits divided by
    ``logits_scaling``, each token's times its ``weights`` (n,) where
    there are any."""
    mask = targets != IGNORE_ID
    logits = jnp.dot(x, head, preferred_element_type=jnp.float32)
    if logits_scaling != 1.0:
        logits = logits / logits_scaling
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.where(mask, targets, 0)[:, None], axis=-1)[:, 0]
    if weights is not None:
        picked = picked * weights
    return jnp.sum(jnp.where(mask, -picked, 0.0))


def _block_of(a, k, block):
    return jax.lax.dynamic_slice_in_dim(a, k * block, block, axis=0)


def _flat_padded(x, targets, block, weights=None):
    """(B, S, h) and (B, S) as tokens (N, h) and (N,), padded with
    ignored tokens to whole blocks: a block is then a run of rows, which a
    loop reads and writes in place. With ``weights`` (B, S), those (N,)
    as a third."""
    xs, ts = x.reshape(-1, x.shape[-1]), targets.reshape(-1)
    pad = -xs.shape[0] % block
    flat = (jnp.pad(xs, ((0, pad), (0, 0))),
            jnp.pad(ts, (0, pad), constant_values=IGNORE_ID))
    if weights is None:
        return flat
    return (*flat, jnp.pad(weights.reshape(-1), (0, pad)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _nll(x, head, targets, logits_scaling: float = 1.0, weights=None):
    """Summed cross-entropy over the positions of ``x`` (B, S, h) whose
    ``targets`` (B, S) are not ``IGNORE_ID``, against the ``head``
    (h, vocab), its logits divided by ``logits_scaling``, each position's
    times its float32 ``weights`` (B, S) where there are any (they take
    no gradient): what ``_block_nll`` gives over all of them at once,
    walked a block of tokens at a time. The backward makes a block's
    logits again, so nothing (tokens, vocab) outlives a block.
    """
    return _nll_fwd(x, head, targets, logits_scaling, weights)[0]


# Jitted for their names' sake (models/bert.py:_masked_nll_fwd).
@functools.partial(jax.jit, static_argnums=(3,))
def _nll_fwd(x, head, targets, logits_scaling, weights=None):
    block = head_block_size(x.shape[0] * x.shape[1])
    with jax.named_scope(HEAD_SCOPE):
        xs, ts, *ws = _flat_padded(x, targets, block, weights)
        head16 = head.astype(x.dtype)

    def add_block(k, total):
        with jax.named_scope(HEAD_SCOPE):
            return total + _block_nll(_block_of(xs, k, block), head16,
                                      _block_of(ts, k, block),
                                      logits_scaling,
                                      *(_block_of(w, k, block) for w in ws))

    total = jax.lax.fori_loop(0, xs.shape[0] // block, add_block,
                              jnp.float32(0))
    return total, (x, targets, head16, weights)


@functools.partial(jax.jit, static_argnums=(0,))
def _nll_bwd(logits_scaling, residuals, cotangent):
    x, targets, head16, weights = residuals
    block = head_block_size(x.shape[0] * x.shape[1])
    with jax.named_scope(HEAD_SCOPE):
        xs, ts, *ws = _flat_padded(x, targets, block, weights)
        zeros = (jnp.zeros_like(xs), jnp.zeros(head16.shape, jnp.float32))

    def add_block(k, grads):
        d_xs, d_head = grads
        with jax.named_scope(HEAD_SCOPE):
            block_targets = _block_of(ts, k, block)
            block_weights = [_block_of(w, k, block) for w in ws]
            _, vjp = jax.vjp(
                lambda x, w: _block_nll(x, w, block_targets, logits_scaling,
                                        *block_weights),
                _block_of(xs, k, block), head16)
            dx, dw = vjp(cotangent)
            return (jax.lax.dynamic_update_slice_in_dim(
                        d_xs, dx, k * block, axis=0),
                    d_head + dw.astype(jnp.float32))

    d_xs, d_head = jax.lax.fori_loop(0, xs.shape[0] // block, add_block,
                                     zeros)
    with jax.named_scope(HEAD_SCOPE):
        d_x = d_xs[:x.shape[0] * x.shape[1]].reshape(x.shape)
    return d_x, d_head, None, None


_nll.defvjp(_nll_fwd, _nll_bwd)


def next_token_targets(token_ids: jax.Array) -> jax.Array:
    """(B, S): each position's next token, ``IGNORE_ID`` at the last."""
    return jnp.concatenate(
        [token_ids[:, 1:],
         jnp.full((token_ids.shape[0], 1), IGNORE_ID, token_ids.dtype)],
        axis=1)


# Jitted for the scope's sake, as the attentions.
@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _noised_beside_clean(token_ids, key, block: int, mask_id: int,
                         eps: float):
    with jax.named_scope(NOISE_SCOPE):
        rows, length = token_ids.shape
        level_key, mask_key = jax.random.split(key)
        level = jax.random.uniform(level_key, (rows, length // block),
                                   jnp.float32)
        prob = jnp.repeat((1.0 - eps) * level + eps, block, axis=1)
        masked = jax.random.uniform(mask_key, (rows, length),
                                    jnp.float32) < prob
        noised = jnp.where(masked, jnp.asarray(mask_id, token_ids.dtype),
                           token_ids)
        return (jnp.concatenate([token_ids, noised], axis=1), masked,
                1.0 / prob)


def diffusion_noise(config: DecoderConfig, token_ids: jax.Array,
                    key: jax.Array):
    """Block diffusion's forward process on rows ``token_ids`` (B, L):
    ``(both (B, 2 L), masked (B, L), weights (B, L))``. Each block of
    ``diffusion_block`` tokens draws a level ``t ~ U[0, 1)`` and each of
    its tokens is masked independently with probability ``p = (1 - eps) t
    + eps``; ``both`` is the clean row and then the row with its masked
    tokens replaced by ``mask_token_id``, ``weights`` the float32 ``1 /
    p`` of each position's block."""
    if token_ids.shape[1] % config.diffusion_block:
        raise ValueError(
            f"rows of {token_ids.shape[1]} tokens are not whole blocks of "
            f"{config.diffusion_block}")
    return _noised_beside_clean(
        token_ids, key, config.diffusion_block, config.mask_token_id,
        config.diffusion_eps)


def _diffusion_loss(config: DecoderConfig, params: Dict[str, Any],
                    token_ids: jax.Array, mesh: Optional[Mesh],
                    key: Optional[jax.Array]) -> jax.Array:
    """Block diffusion's loss (module docstring) over the rows
    ``token_ids`` (B, L), the noise drawn from ``key``. Every noised
    position goes through the head and the unmasked ones are ignored;
    the clean copy's last hidden states feed nothing and are not
    projected. How many positions were masked and what their weights sum
    to go out as the step's ``lm_noise``."""
    if key is None:
        raise ValueError("block diffusion draws its noise from a key: "
                         "loss_fn(config, params, token_ids, mesh, key)")
    with jax.named_scope(LOSS_SCOPE):
        token_ids = token_ids.astype(jnp.int32)
        rows, length = token_ids.shape
        both, masked, weights = diffusion_noise(config, token_ids, key)
    x = decode(config, params, both, mesh)
    with jax.named_scope(LOSS_SCOPE):
        x = _norm(config, x[:, length:], params, "final_norm")
        tracing.step_stat("lm_noise", jnp.stack(
            [jnp.sum(masked, dtype=jnp.float32),
             jnp.sum(jnp.where(masked, weights, 0.0))]))
        head = (params["embed"].T if config.tie_embeddings
                else params["head"])
        total = _nll(x, head.astype(jnp.float32),
                     jnp.where(masked, token_ids, IGNORE_ID),
                     config.logits_scaling, weights)
        return total / (rows * length)


def loss_fn(config: DecoderConfig, params: Dict[str, Any],
            token_ids: jax.Array, mesh: Optional[Mesh] = None,
            key: Optional[jax.Array] = None) -> jax.Array:
    """Mean next-token cross-entropy over the ``S - 1`` shifted positions
    of each row of ``token_ids`` (B, S), over this chip's slice of the
    vocabulary; with ``diffusion_block``, block diffusion's loss over the
    rows, its noise drawn from ``key`` (a step's own: an argument of the
    program, not a constant of it). ``mesh`` is :func:`decode`'s."""
    if config.diffusion_block:
        return _diffusion_loss(config, params, token_ids, mesh, key)
    x = decode(config, params, token_ids, mesh)
    with jax.named_scope(LOSS_SCOPE):
        x = _norm(config, x, params, "final_norm")
        targets = next_token_targets(token_ids.astype(jnp.int32))
        # A tied head is the embedding's own matrix: the one leaf takes the
        # gradient of both uses.
        head = (params["embed"].T if config.tie_embeddings
                else params["head"])
        # The loss's backward sums the head's gradient in float32 and hands
        # it back so: float32 weights pass as they are.
        total = _nll(x, head.astype(jnp.float32), targets,
                     config.logits_scaling)
        return total / jnp.maximum(jnp.sum(targets != IGNORE_ID), 1)
