"""Plain reference of the step the ``granite-4.0-h-micro-p1`` configuration
trains: one period of a granite-4.0-h-style hybrid decoder (Mamba-2 layers
and one attention layer, every MLP a dense SwiGLU), in ``jax.numpy`` and
float32, every part written out, no kernels and no chunks. It imports
nothing of the program and makes its own weights from the seed; what it
shares with the other decoders' references is
``chipbench/references/mellum.py``'s plain helpers (RMSNorm, the gradient
tree's in-place sum, the hashable sizes).

The equations (HF ``GraniteMoeHybrid`` with ``num_local_experts`` 0; each
assumption is under ``assumed`` in the configuration file). The stream
starts as ``embedding_multiplier x E[token]``. ``n = RMSNorm(x)``, eps
``rms_norm_eps``. **A ``mamba`` layer's mixer** (Mamba-2, Dao & Gu 2024):
``[z | xBC | dt] = n W_in`` of widths ``mamba_n_heads x mamba_d_head`` |
that + 2 ``mamba_n_groups x mamba_d_state`` | ``mamba_n_heads``, no bias;
``xBC = silu(conv1d(xBC))``, depthwise, causal, ``mamba_d_conv`` taps,
with bias; split into ``x`` (heads of ``mamba_d_head``), ``B`` and ``C``
(``mamba_d_state`` each, one group: every head's); ``dt = softplus(dt +
dt_bias)``, ``A = -exp(A_log)``, one scalar a head; a head's state
(``mamba_d_head`` x ``mamba_d_state``) follows ``h_t = exp(dt_t A) h_(t-1)
+ dt_t x_t (x) B_t`` and gives ``y_t = h_t . C_t + D x_t``: computed here
as that recurrence, a position at a time (:func:`_recurrence`); ``y =
RMSNorm(y * silu(z)) * w`` over the whole width; ``x += residual_multiplier
x (y W_out)``. **The ``attention`` layer**: q, k, v, o without bias,
``num_attention_heads`` over ``num_key_value_heads`` heads, causal over the
whole row, no positions (``position_embedding_type: "nope"``), scores times
``attention_multiplier`` (not 1 / sqrt(head dimension)); ``x +=
residual_multiplier x (a W_o)``. **Every layer's MLP**: ``x +=
residual_multiplier x (silu(n G) * (n U)) D`` at ``shared_intermediate_size``.
After the last layer RMSNorm and the head, which is the embedding's own
matrix (``tie_word_embeddings``), its logits divided by ``logits_scaling``;
the loss is the mean next-token negative log-likelihood over the ``seq_len
- 1`` shifted positions of each row, over this chip's slice of the
vocabulary.

``value_and_grad`` goes a row at a time and a layer at a time (each
layer's input kept, its activations made again in the backward pass; the
recurrence in blocks of positions, a block's states made again; attention
a head at a time; the head's logits in blocks of positions), so that its
float32 activations fit beside 16 bytes a parameter.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from chipbench.references.mellum import HEAD_BLOCK, _Sizes, _add, _rms_norm
# The whole model is followed, nothing to cut: the harness finds these here.
from chipbench.references.mellum import (  # noqa: F401
    remap, take_rows, touched_rows)

MAMBA, ATTENTION = "mamba", "attention"
SCAN_BLOCK = 128     # positions whose states one block of the scan keeps


def _mamba_widths(sizes) -> Tuple[int, int, int]:
    """(x's and z's channels, B's or C's, what the convolution runs over)."""
    width = sizes["mamba_n_heads"] * sizes["mamba_d_head"]
    state = sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    return width, state, width + 2 * state


def _head_dim(sizes) -> int:
    return sizes["hidden_size"] // sizes["num_attention_heads"]


def init_params(sizes: Dict[str, Any], key) -> Dict[str, Any]:
    """Seeded float32 weights in the layout the trainer takes. Matrices
    N(0, 0.02); what writes into the residual stream (``out_proj``, ``wo``,
    every ``down``) N(0, 0.02 / sqrt(2 x the published depth)); unit norm
    scales; the tied embedding N(0, 0.02): it is the head too, and at the
    other decoders' N(0, 1) a token's own logit would be hidden_size /
    logits_scaling = 256. A Mamba mixer as state-spaces/mamba's ``Mamba2``
    draws it: ``dt`` log-uniform in [0.001, 0.1] through ``dt_bias`` (its
    inverse softplus), ``A`` uniform in [1, 16] as ``a_log``, ``D`` 1, the
    convolution's taps and bias uniform in +-1 / sqrt(taps) (``Conv1d``'s
    default at a fan-in of ``mamba_d_conv``)."""
    if sizes["mamba_n_groups"] != 1:
        raise ValueError("B and C in one group only")
    h, f = sizes["hidden_size"], sizes["shared_intermediate_size"]
    d = _head_dim(sizes)
    q_width = sizes["num_attention_heads"] * d
    kv_width = sizes["num_key_value_heads"] * d
    width, _, conved = _mamba_widths(sizes)
    heads, taps = sizes["mamba_n_heads"], sizes["mamba_d_conv"]
    residual = 0.02 / math.sqrt(2 * sizes["published"]["num_hidden_layers"])
    keys = iter(jax.random.split(key, 1 + 12 * sizes["num_hidden_layers"]))

    def normal(shape, std=0.02):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    def uniform(shape, low, high):
        return jax.random.uniform(next(keys), shape, jnp.float32, low, high)

    params: Dict[str, Any] = {"embed": normal((sizes["vocab_size"], h)),
                              "final_norm": jnp.ones((h,), jnp.float32)}
    for i, kind in enumerate(sizes["layer_types"]):
        if kind == MAMBA:
            dt = jnp.exp(uniform((heads,), math.log(0.001), math.log(0.1)))
            edge = 1.0 / math.sqrt(taps)
            p = {"mamba_norm": jnp.ones((h,), jnp.float32),
                 "in_proj": normal((h, width + conved + heads)),
                 "conv_w": uniform((taps, conved), -edge, edge),
                 "conv_b": uniform((conved,), -edge, edge),
                 "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                 "a_log": jnp.log(uniform((heads,), 1.0, 16.0)),
                 "d": jnp.ones((heads,), jnp.float32),
                 "ssm_norm": jnp.ones((width,), jnp.float32),
                 "out_proj": normal((width, h), residual)}
        elif kind == ATTENTION:
            p = {"attn_norm": jnp.ones((h,), jnp.float32),
                 "wq": normal((h, q_width)), "wk": normal((h, kv_width)),
                 "wv": normal((h, kv_width)),
                 "wo": normal((q_width, h), residual)}
        else:
            raise ValueError(f"unknown layer_types entry {kind!r}")
        p.update(mlp_norm=jnp.ones((h,), jnp.float32), gate=normal((h, f)),
                 up=normal((h, f)), down=normal((f, h), residual))
        params[f"layer_{i}"] = p
    return params


# -- one row through one layer -----------------------------------------------------


def _recurrence(xs, dt, a, b, c, d):
    """``h_t = exp(dt_t A) h_(t-1) + dt_t x_t (x) B_t``, ``y_t = h_t . C_t
    + D x_t`` for xs (S, H, P), dt (S, H), a and d (H,), b and c (S, N): a
    position at a time, in blocks of ``SCAN_BLOCK`` positions whose states
    the backward pass makes again (a head's states of a whole row of 8,192
    would be 17 GB)."""
    s, heads, width = xs.shape
    block = math.gcd(s, SCAN_BLOCK)

    def position(h, at):
        x_t, dt_t, b_t, c_t = at
        h = (jnp.exp(dt_t * a)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t)
        return h, jnp.sum(h * c_t, axis=-1) + d[:, None] * x_t

    @jax.checkpoint
    def run(h, block_of):
        return jax.lax.scan(position, h, block_of)

    blocks = tuple(m.reshape(s // block, block, *m.shape[1:])
                   for m in (xs, dt, b, c))
    start = jnp.zeros((heads, width, b.shape[-1]), xs.dtype)
    return jax.lax.scan(run, start, blocks)[1].reshape(s, heads, width)


def _conv_silu(x, weight, bias):
    """x (S, C): position t sees x_(t-K+1) .. x_t under weight[0] ..
    weight[K-1] (K, C), plus bias; then silu."""
    taps, s = weight.shape[0], x.shape[0]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return jax.nn.silu(bias + sum(weight[k] * padded[k:k + s]
                                  for k in range(taps)))


def mamba_half(sizes, p, x):
    """x (S, h) -> x + residual_multiplier x Mamba-2(RMSNorm(x))."""
    s = x.shape[0]
    width, state, _ = _mamba_widths(sizes)
    n = _rms_norm(x, p["mamba_norm"], sizes["rms_norm_eps"])
    z, xbc, dt = jnp.split(n @ p["in_proj"], [width, 2 * width + 2 * state],
                           axis=-1)
    xs, b, c = jnp.split(_conv_silu(xbc, p["conv_w"], p["conv_b"]),
                         [width, width + state], axis=-1)
    y = _recurrence(xs.reshape(s, sizes["mamba_n_heads"], -1),
                    jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["a_log"]),
                    b, c, p["d"])
    y = _rms_norm(y.reshape(s, width) * jax.nn.silu(z), p["ssm_norm"],
                  sizes["rms_norm_eps"])
    return x + sizes["residual_multiplier"] * (y @ p["out_proj"])


def _attention(sizes, q, k, v):
    """q (S, H, D), k and v (S, Hkv, D) -> (S, H, D): causal over the whole
    row, scores times ``attention_multiplier``, a head at a time (each
    made again in the backward pass: a head's (S, S) float32 scores are
    268 MB at 8,192)."""
    s, heads, _ = q.shape
    group = heads // k.shape[1]
    seen = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    @jax.checkpoint
    def one_head(head):
        kv = head // group
        scores = q[:, head] @ k[:, kv].T * sizes["attention_multiplier"]
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return weights @ v[:, kv]

    return jax.lax.map(one_head, jnp.arange(heads)).transpose(1, 0, 2)


def attention_half(sizes, p, x):
    """x (S, h) -> x + residual_multiplier x attention(RMSNorm(x)); no
    positions."""
    if sizes["position_embedding_type"] != "nope":
        raise ValueError("the reference knows no positions but none")
    s = x.shape[0]
    n = _rms_norm(x, p["attn_norm"], sizes["rms_norm_eps"])
    q = (n @ p["wq"]).reshape(s, sizes["num_attention_heads"], -1)
    k = (n @ p["wk"]).reshape(s, sizes["num_key_value_heads"], -1)
    v = (n @ p["wv"]).reshape(s, sizes["num_key_value_heads"], -1)
    return x + sizes["residual_multiplier"] * (
        _attention(sizes, q, k, v).reshape(s, -1) @ p["wo"])


def mlp_half(sizes, p, x):
    n = _rms_norm(x, p["mlp_norm"], sizes["rms_norm_eps"])
    return x + sizes["residual_multiplier"] * (
        (jax.nn.silu(n @ p["gate"]) * (n @ p["up"])) @ p["down"])


def layer(sizes, kind: str, p, x):
    """x (S, h) -> x (S, h)."""
    mixer = mamba_half if kind == MAMBA else attention_half
    return mlp_half(sizes, p, mixer(sizes, p, x))


def _head_nll(sizes, scale, embed, x, targets):
    """Summed next-token negative log-likelihood of positions x (n, h)
    under the tied head."""
    logits = (_rms_norm(x, scale, sizes["rms_norm_eps"]) @ embed.T
              / sizes["logits_scaling"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_forward(sizes, kind, p, x):
    return layer(sizes, kind, p, x)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_backward(sizes, kind, p, x, dy):
    _, vjp = jax.vjp(functools.partial(layer, sizes, kind), p, x)
    return vjp(dy)


@functools.partial(jax.jit, static_argnums=(0,))
def _head_block(sizes, scale, embed, x, targets):
    return jax.value_and_grad(functools.partial(_head_nll, sizes),
                              argnums=(0, 1, 2))(scale, embed, x, targets)


def add_row(sizes, params, tokens, total, grads):
    """One row ``tokens`` (S,): its summed loss added to ``total`` and its
    gradient to the tree ``grads``, a layer's share at a time. The
    embedding's leaf takes both of its uses: the head's blocks, then the
    lookup's rows."""
    kinds = sizes["layer_types"]
    if not sizes["tie_word_embeddings"]:
        raise ValueError("the reference knows the tied head only")
    x = sizes["embedding_multiplier"] * params["embed"][tokens]
    inputs = []
    for i, kind in enumerate(kinds):
        inputs.append(x)
        x = _layer_forward(sizes, kind, params[f"layer_{i}"], x)
    targets = tokens[1:]
    d_x = []
    for lo in range(0, targets.shape[0], HEAD_BLOCK):
        value, (ds, de, dx) = _head_block(
            sizes, params["final_norm"], params["embed"],
            x[:-1][lo:lo + HEAD_BLOCK], targets[lo:lo + HEAD_BLOCK])
        total = _add(total, value)
        grads["final_norm"] = _add(grads.get("final_norm"), ds)
        grads["embed"] = _add(grads.get("embed"), de)
        d_x.append(dx)
    dy = jnp.concatenate(d_x + [jnp.zeros_like(x[-1:])], axis=0)
    for i in reversed(range(len(kinds))):
        d_layer, dy = _layer_backward(
            sizes, kinds[i], params[f"layer_{i}"], inputs.pop(), dy)
        grads[f"layer_{i}"] = _add(grads.get(f"layer_{i}"), d_layer)
    grads["embed"] = _add(
        grads["embed"], jnp.zeros_like(params["embed"]).at[tokens].add(
            sizes["embedding_multiplier"] * dy))
    return total


def value_and_grad(sizes: Dict[str, Any], params: Dict[str, Any],
                   features: Sequence[Any], labels: Any, step: int,
                   seed_key=None) -> Tuple[jax.Array, Dict[str, Any]]:
    """Loss (mean over the batch's shifted positions) and its gradient, a
    row at a time; one gradient tree is held, added to in place."""
    tokens = jnp.asarray(features[0], jnp.int32)
    sizes = _Sizes(sizes)
    total, grads = None, {}
    for row in tokens:
        total = add_row(sizes, params, row, total, grads)
    count = tokens.shape[0] * (tokens.shape[1] - 1)
    scale = jax.jit(lambda g: jax.tree.map(lambda x: x / count, g),
                    donate_argnums=(0,))
    return total / count, scale({k: grads[k] for k in params})


# -- operations and bytes of one train step, from the shapes ----------------------


def _layers(sizes, kind: str) -> int:
    return sum(k == kind for k in sizes["layer_types"])


def param_count(sizes: Dict[str, Any]) -> int:
    """Of the layers ``layer_types`` names and ``vocab_size`` rows of the
    tied embedding: this chip's cut, or the published model given its
    forty layers and 100,352 rows."""
    h, f = sizes["hidden_size"], sizes["shared_intermediate_size"]
    d = _head_dim(sizes)
    width, _, conved = _mamba_widths(sizes)
    heads = sizes["mamba_n_heads"]
    mlp = 3 * h * f + h
    mamba = (h + h * (width + conved + heads)
             + (sizes["mamba_d_conv"] + 1) * conved + 3 * heads + width
             + width * h)
    attention = h + 2 * h * d * (sizes["num_attention_heads"]
                                 + sizes["num_key_value_heads"])
    return (sizes["vocab_size"] * h + h
            + _layers(sizes, MAMBA) * (mamba + mlp)
            + _layers(sizes, ATTENTION) * (attention + mlp))


def _ssm_flops_per_token(sizes) -> float:
    """Forward FLOPs of one Mamba layer's scan a token, as the published
    ``mamba_chunk_size`` lays the mathematics out, whatever implements it:
    a chunk's ``C B^T`` (2 x chunk x state), its masked product with every
    head's ``dt x`` (2 x chunk x width), and the states written at a
    chunk's end and read at the next one's start (2 x 2 x state x
    width)."""
    width, state, _ = _mamba_widths(sizes)
    chunk = sizes["mamba_chunk_size"]
    return 2.0 * chunk * state + 2.0 * chunk * width + 4.0 * state * width


def _forward_flops_per_token(sizes) -> Dict[str, float]:
    """Forward matrix-multiply FLOPs a token, by part of the model."""
    h, f = sizes["hidden_size"], sizes["shared_intermediate_size"]
    d = _head_dim(sizes)
    heads, kv_heads = (sizes["num_attention_heads"],
                       sizes["num_key_value_heads"])
    width, _, conved = _mamba_widths(sizes)
    s = sizes["seq_len"]
    mamba, attention = _layers(sizes, MAMBA), _layers(sizes, ATTENTION)
    return {
        "mamba_projections": mamba * 2.0 * h * (
            2 * width + conved + sizes["mamba_n_heads"]),
        "ssm": mamba * _ssm_flops_per_token(sizes),
        "projections": attention * 2.0 * h * d * (2 * heads + 2 * kv_heads),
        # two products over the keys a query sees: the triangle
        "attention": attention * 2 * 2.0 * d * heads * (s + 1) / 2,
        "mlp": (mamba + attention) * 3 * 2.0 * h * f,
        "head": 2.0 * h * sizes["vocab_size"] * (s - 1) / s,
    }


def train_flops_per_row(sizes: Dict[str, Any]) -> float:
    """Matrix-multiply FLOPs the forward and backward passes need for one
    row of ``seq_len`` tokens, times three (forward, and two products per
    matmul backward): the Mamba mixers' two projections and their scans,
    the attention layer's projections and its two products over the
    triangle, every layer's dense MLP, the tied head. Recomputation is not
    counted."""
    return 3.0 * sizes["seq_len"] * sum(
        _forward_flops_per_token(sizes).values())


def _stream_bytes(sizes, rows: int, width: int, passes: float) -> float:
    """bf16 bytes of ``passes`` passes over ``rows`` rows' tokens at
    ``width`` values a token."""
    return passes * 2.0 * rows * sizes["seq_len"] * width


def train_step_bytes(sizes: Dict[str, Any], rows: int) -> float:
    """HBM bytes one step cannot avoid: dense Adam's 28 bytes a float32
    parameter, plus each row's bf16 residual stream written and read once
    per layer forward and backward. A floor: the step is bound by FLOPs."""
    return 28.0 * param_count(sizes) + sizes["num_hidden_layers"] \
        * _stream_bytes(sizes, rows, sizes["hidden_size"], 4)


def ssm_work(sizes: Dict[str, Any], rows: int) -> Tuple[float, float]:
    """(FLOPs, least HBM bytes) of the Mamba layers' convolution, scan and
    gated norm of one step of ``rows`` rows, forward and backward,
    whatever implements them: the scan's products at the published chunk
    (``_ssm_flops_per_token``), times three; the convolution and the norm
    count by their bytes alone: bf16 ``z``, ``xBC`` and ``dt`` read and
    ``y`` written once forward, their gradients once more backward.
    Nothing made again is counted."""
    width, _, conved = _mamba_widths(sizes)
    layers = _layers(sizes, MAMBA)
    flops = 3.0 * rows * sizes["seq_len"] * layers \
        * _ssm_flops_per_token(sizes)
    ends = 2 * width + conved + sizes["mamba_n_heads"]
    return flops, layers * _stream_bytes(sizes, rows, ends, 2)


def proj_work(sizes: Dict[str, Any], rows: int) -> Tuple[float, float]:
    """(FLOPs, least HBM bytes) of the mixers' projections (a Mamba
    layer's ``W_in`` and ``W_out``, the attention layer's q, k, v and
    ``W_o``) of one step, forward and backward, whatever implements them:
    a product each, times three; their float32 weights read forward and
    backward and their gradients written; per product the bf16 input read
    and the output written forward, and as much again in gradients
    backward with both inputs read once more."""
    parts = _forward_flops_per_token(sizes)
    flops = 3.0 * rows * sizes["seq_len"] * (parts["mamba_projections"]
                                             + parts["projections"])
    h, d = sizes["hidden_size"], _head_dim(sizes)
    heads, kv_heads = (sizes["num_attention_heads"],
                       sizes["num_key_value_heads"])
    width, _, conved = _mamba_widths(sizes)
    fan_out = width + conved + sizes["mamba_n_heads"]
    mamba, attention = _layers(sizes, MAMBA), _layers(sizes, ATTENTION)
    weights = (mamba * (h * fan_out + width * h)
               + attention * h * d * (2 * heads + 2 * kv_heads))
    # what the products read and write forward, and of that what they read
    ends = (mamba * (2 * h + fan_out + width)
            + attention * (2 * h + d * (2 * heads + 2 * kv_heads)))
    inputs = mamba * (h + width) + attention * (h + d * heads)
    return flops, 3 * 4.0 * weights + _stream_bytes(
        sizes, rows, 2 * ends + inputs, 1)


def mlp_work(sizes: Dict[str, Any], rows: int) -> Tuple[float, float]:
    """(FLOPs, least HBM bytes) of the layers' dense MLPs of one step,
    forward and backward, whatever implements them: three products each,
    times three; their float32 weights read forward and backward and
    their gradients written; per SwiGLU the bf16 tokens read and the
    result written forward, both read and the tokens' gradient written
    backward."""
    flops = 3.0 * rows * sizes["seq_len"] \
        * _forward_flops_per_token(sizes)["mlp"]
    layers = sizes["num_hidden_layers"]
    weights = layers * 3 * sizes["hidden_size"] \
        * sizes["shared_intermediate_size"]
    return flops, 3 * 4.0 * weights + layers * _stream_bytes(
        sizes, rows, sizes["hidden_size"], 5)


def attention_work(sizes: Dict[str, Any], rows: int) -> Tuple[float, float]:
    """(FLOPs, least HBM bytes) of the attention layer's attention
    (scores, softmax, weighted values; not its projections) of one step,
    forward and backward: two products over the triangle, times three;
    bf16 q, k, v read and the output written forward, those and the
    output's gradient read and three gradients written backward."""
    flops = 3.0 * rows * sizes["seq_len"] \
        * _forward_flops_per_token(sizes)["attention"]
    widths = _layers(sizes, ATTENTION) * _head_dim(sizes) * (
        sizes["num_attention_heads"] + sizes["num_key_value_heads"])
    return flops, _stream_bytes(sizes, rows, widths, 2 + 4)
