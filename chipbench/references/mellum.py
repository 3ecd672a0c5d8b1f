"""Plain reference of the step the ``mellum2-12b-a2.5b-ep4`` configuration
trains: one chip's share of a Mellum-2-style sparse-expert decoder, in
``jax.numpy`` and float32, attention and the expert layer written out, no
kernels. It imports nothing of the program and makes its own weights from
the seed.

The equations, from the source's ``config.json`` (each assumption is under
``assumed`` in the configuration file). Per layer, on the residual stream
``x``: ``a = RMSNorm(x)``; ``q = a W_q`` (``num_attention_heads`` heads of
``head_dim``), ``k = a W_k``, ``v = a W_v`` (``num_key_value_heads``
heads), no biases; rotary positions on q and k (the rotate-half
convention): a ``sliding_attention`` layer's are plain (theta), a
``full_attention`` layer's are YaRN's (the inverse frequencies a fixed
blend of interpolated and extrapolated ones, cos and sin scaled by
``attention_factor``); query head h reads key/value head h // group;
scores ``q k^T / sqrt(head_dim)`` masked to j <= i, in a window layer
also to j > i - ``sliding_window``; softmax; ``x += (P v) W_o``. Then
``b = RMSNorm(x)``; ``p = softmax(b W_r)`` over all
``num_experts_routed`` experts; S = the ``num_experts_per_tok`` largest;
``w_e = p_e / sum_S p``; ``x += sum over e in S that are HELD of
w_e (silu(b G_e) * (b U_e)) D_e``. **This chip's share**: the sum runs
over the ``num_experts`` experts held from ``experts_held_first`` on;
what the absent experts would add is left out, and that partial result
goes on to the next layer. After the last layer RMSNorm and an untied
head onto the vocabulary slice; the loss is the mean next-token negative
log-likelihood over the ``seq_len - 1`` shifted positions of each row.

The held experts are a plain loop of dense products over every token
under the routing's weights (zero where a token did not pick the
expert). ``value_and_grad`` goes a row at a time and a layer at a time
(each layer's input kept, its activations made again in the backward
pass; attention a head at a time; the head's logits in blocks of
positions), so that its float32 activations fit beside 16 bytes a
parameter.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

SLIDING, FULL = "sliding_attention", "full_attention"
HEAD_BLOCK = 2048    # positions per block of the head's logits


def init_params(sizes: Dict[str, Any], key) -> Dict[str, Any]:
    """Seeded float32 weights in the layout the trainer takes: the
    embedding N(0, 1) (``torch.nn.Embedding``'s default), matrices
    N(0, 0.02), the two projections that write into the residual stream
    (``wo``, ``down``) N(0, 0.02 / sqrt(2 x the published depth)) as
    Megatron's scaled init, unit norm scales. Why not 0.02 everywhere
    (PERF.md section 6, PR 32): uniform attention over random tokens makes
    the mean of a thousand values, a component common to a row's tokens
    and larger than a token's own 0.02-wide embedding; every token then
    routes alike, an expert's load ranges from 0 to 16,000 tokens where a
    trained, balanced router gives 4,096, and the step's time follows the
    seed."""
    h, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    d = sizes["head_dim"]
    q_width = sizes["num_attention_heads"] * d
    kv_width = sizes["num_key_value_heads"] * d
    held, layers = sizes["num_experts"], sizes["num_hidden_layers"]
    residual = 0.02 / math.sqrt(2 * sizes["published"]["num_hidden_layers"])
    keys = iter(jax.random.split(key, 2 + 8 * layers))

    def normal(shape, std=0.02):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    params: Dict[str, Any] = {
        "embed": normal((sizes["vocab_size"], h), 1.0),
        "head": normal((h, sizes["vocab_size"])),
        "final_norm": jnp.ones((h,), jnp.float32),
    }
    for layer in range(layers):
        params[f"layer_{layer}"] = {
            "attn_norm": jnp.ones((h,), jnp.float32),
            "wq": normal((h, q_width)),
            "wk": normal((h, kv_width)),
            "wv": normal((h, kv_width)),
            "wo": normal((q_width, h), residual),
            "moe_norm": jnp.ones((h,), jnp.float32),
            "router": normal((h, sizes["num_experts_routed"])),
            "gate": normal((held, h, f)),
            "up": normal((held, h, f)),
            "down": normal((held, f, h), residual),
        }
    return params


# -- one row through one layer -----------------------------------------------------


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def inv_freq(sizes: Dict[str, Any], layer_type: str):
    """(inverse frequencies (head_dim / 2,), the scale of cos and sin)."""
    dim = sizes["head_dim"]
    rope = sizes["rope_parameters"][layer_type]
    base = rope["rope_theta"]
    extrapolated = 1.0 / base ** (jnp.arange(0, dim, 2) / dim)
    if rope["rope_type"] == "default":
        return extrapolated, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {rope['rope_type']!r}")
    interpolated = extrapolated / rope["factor"]

    def dim_of(rotations):
        # the dimension whose wavelength makes ``rotations`` turns over
        # the original context
        return dim * math.log(rope["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) / (
                                  2 * math.log(base))

    low = max(math.floor(dim_of(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2) - low) / (high - low), 0, 1)
    return (interpolated * ramp + extrapolated * (1 - ramp),
            rope["attention_factor"])


def _rotate(x, cos, sin):
    """x (S, heads, D): each head's D rotated by position, rotate-half."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + turned * sin[:, None, :]


def _attention(sizes, layer_type: str, q, k, v):
    """q (S, H, D), k and v (S, Hkv, D) -> (S, H, D), a head at a time
    (each made again in the backward pass: a head's (S, S) float32 scores
    are 268 MB at 8,192)."""
    s, heads, d = q.shape
    group = heads // k.shape[1]
    ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    seen = ahead >= 0
    if layer_type == SLIDING:
        seen &= ahead < sizes["sliding_window"]

    @jax.checkpoint
    def one_head(head):
        kv = head // group
        scores = q[:, head] @ k[:, kv].T / math.sqrt(d)
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return weights @ v[:, kv]

    return jax.lax.map(one_head, jnp.arange(heads)).transpose(1, 0, 2)


def _experts(sizes, x, p):
    """The held experts' part of the sparse-expert sum for x (S, h)."""
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    top, ids = jax.lax.top_k(probs, sizes["num_experts_per_tok"])
    top = top / top.sum(axis=-1, keepdims=True)
    held = sizes["experts_held_first"] + jnp.arange(sizes["num_experts"])

    @jax.checkpoint      # an expert's activations are made again, not kept
    def add_expert(out, expert):
        e, gate, up, down = expert
        weight = jnp.sum(jnp.where(ids == e, top, 0.0), axis=-1,
                         keepdims=True)
        return out + weight * ((jax.nn.silu(x @ gate) * (x @ up)) @ down), None

    return jax.lax.scan(add_expert, jnp.zeros_like(x),
                        (held, p["gate"], p["up"], p["down"]))[0]


def layer(sizes, layer_type: str, p, x):
    """x (S, h) -> x (S, h)."""
    s = x.shape[0]
    heads, kv_heads = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    freqs, scale = inv_freq(sizes, layer_type)
    angles = jnp.arange(s)[:, None] * freqs
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(angles) * scale, jnp.sin(angles) * scale
    a = _rms_norm(x, p["attn_norm"], sizes["rms_norm_eps"])
    q = _rotate((a @ p["wq"]).reshape(s, heads, -1), cos, sin)
    k = _rotate((a @ p["wk"]).reshape(s, kv_heads, -1), cos, sin)
    v = (a @ p["wv"]).reshape(s, kv_heads, -1)
    x = x + _attention(sizes, layer_type, q, k, v).reshape(s, -1) @ p["wo"]
    return x + _experts(
        sizes, _rms_norm(x, p["moe_norm"], sizes["rms_norm_eps"]), p)


def _head_nll(sizes, scale, head, x, targets):
    """Summed next-token negative log-likelihood of positions x (n, h)."""
    logits = _rms_norm(x, scale, sizes["rms_norm_eps"]) @ head
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1))


class _Sizes(dict):
    """``sizes`` as a jitted function's static argument: equal by what it
    holds, so that every step of a run meets the same compiled layers."""

    def _text(self) -> str:
        return json.dumps(self, sort_keys=True, default=str)

    def __hash__(self):
        return hash(self._text())

    def __eq__(self, other):
        return isinstance(other, _Sizes) and self._text() == other._text()


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_forward(sizes, layer_type, p, x):
    return layer(sizes, layer_type, p, x)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_backward(sizes, layer_type, p, x, dy):
    _, vjp = jax.vjp(functools.partial(layer, sizes, layer_type), p, x)
    return vjp(dy)


@functools.partial(jax.jit, static_argnums=(0,))
def _head_block(sizes, scale, head, x, targets):
    return jax.value_and_grad(functools.partial(_head_nll, sizes),
                              argnums=(0, 1, 2))(scale, head, x, targets)


@functools.partial(jax.jit, donate_argnums=(0,))
def _add_in_place(total, more):
    return jax.tree.map(jnp.add, total, more)


def _add(total, more):
    """``total + more`` in ``total``'s buffers; ``more`` where there is
    no total yet."""
    return more if total is None else _add_in_place(total, more)


def add_row(sizes, params, tokens, total, grads):
    """One row ``tokens`` (S,): its summed loss added to ``total`` and its
    gradient to the tree ``grads``, a layer's share at a time."""
    types = sizes["layer_types"]
    x = params["embed"][tokens]
    inputs = []
    for i, layer_type in enumerate(types):
        inputs.append(x)
        x = _layer_forward(sizes, layer_type, params[f"layer_{i}"], x)
    targets = tokens[1:]
    d_x = []
    for lo in range(0, targets.shape[0], HEAD_BLOCK):
        value, (ds, dh, dx) = _head_block(
            sizes, params["final_norm"], params["head"],
            x[:-1][lo:lo + HEAD_BLOCK], targets[lo:lo + HEAD_BLOCK])
        total = _add(total, value)
        grads["final_norm"] = _add(grads.get("final_norm"), ds)
        grads["head"] = _add(grads.get("head"), dh)
        d_x.append(dx)
    dy = jnp.concatenate(d_x + [jnp.zeros_like(x[-1:])], axis=0)
    for i in reversed(range(len(types))):
        d_layer, dy = _layer_backward(
            sizes, types[i], params[f"layer_{i}"], inputs.pop(), dy)
        grads[f"layer_{i}"] = _add(grads.get(f"layer_{i}"), d_layer)
    grads["embed"] = _add(
        grads.get("embed"),
        jnp.zeros_like(params["embed"]).at[tokens].add(dy))
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


# -- the whole model is followed: nothing to cut ----------------------------------

def touched_rows(sizes, batches):
    return None


def take_rows(params, rows):
    return params


def remap(features, rows):
    return list(features)


# -- operations and bytes of one train step, from the shapes ----------------------

def param_count(sizes: Dict[str, Any]) -> int:
    h, f, v = (sizes["hidden_size"], sizes["moe_intermediate_size"],
               sizes["vocab_size"])
    q_width = sizes["num_attention_heads"] * sizes["head_dim"]
    kv_width = sizes["num_key_value_heads"] * sizes["head_dim"]
    per_layer = (2 * h * q_width + 2 * h * kv_width + 2 * h
                 + h * sizes["num_experts_routed"]
                 + sizes["num_experts"] * 3 * h * f)
    return 2 * v * h + h + sizes["num_hidden_layers"] * per_layer


def _keys_per_query(sizes, layer_type: str) -> float:
    """Keys a query sees, averaged over a row's positions: the triangle,
    or the band."""
    s = sizes["seq_len"]
    w = min(sizes["sliding_window"], s) if layer_type == SLIDING else s
    return (w * (w + 1) / 2 + (s - w) * w) / s


def _forward_flops_per_token(sizes) -> Dict[str, float]:
    """Forward matrix-multiply FLOPs a token, by part of the model."""
    h, f, d = (sizes["hidden_size"], sizes["moe_intermediate_size"],
               sizes["head_dim"])
    heads, kv_heads = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    layers = sizes["num_hidden_layers"]
    # of a token's picks, the share that falls on held experts
    held_picks = (sizes["num_experts_per_tok"] * sizes["num_experts"]
                  / sizes["num_experts_routed"])
    return {
        "projections": layers * 2.0 * h * d * (2 * heads + 2 * kv_heads),
        "attention": sum(2 * 2.0 * d * heads * _keys_per_query(sizes, t)
                         for t in sizes["layer_types"]),
        "experts": layers * (held_picks * 3 * 2.0 * h * f
                             + 2.0 * h * sizes["num_experts_routed"]),
        "head": 2.0 * h * sizes["vocab_size"] * (sizes["seq_len"] - 1)
        / sizes["seq_len"],
    }


def train_flops_per_row(sizes: Dict[str, Any]) -> float:
    """Matrix-multiply FLOPs the forward and backward passes need for one
    row of ``seq_len`` tokens, times three (forward, and two products per
    matmul backward): the projections, attention's two products over the
    keys a query sees (the triangle, or a window layer's band), the
    experts at the expected share of a token's picks that is held, the
    router, the head. Recomputation is not counted."""
    return 3.0 * sizes["seq_len"] * sum(
        _forward_flops_per_token(sizes).values())


def train_step_bytes(sizes: Dict[str, Any], rows: int) -> float:
    """HBM bytes one step cannot avoid: dense Adam's 28 bytes a float32
    parameter, plus each row's bf16 residual stream written and read once
    per layer forward and backward. A floor: the step is bound by FLOPs."""
    stream = 4.0 * 2.0 * rows * sizes["seq_len"] * sizes["hidden_size"] \
        * sizes["num_hidden_layers"]
    return 28.0 * param_count(sizes) + stream


def _stream_bytes(sizes, rows: int, width: int, passes: float) -> float:
    return passes * 2.0 * rows * sizes["seq_len"] * width \
        * sizes["num_hidden_layers"]


def moe_work(sizes: Dict[str, Any], rows: int) -> Tuple[float, float]:
    """(FLOPs, least HBM bytes) of the expert layers of one step of
    ``rows`` rows, forward and backward, whatever implements them: the
    held picks' three products and the router, times three; the held
    experts' float32 weights read forward and backward and their
    gradients written, the bf16 tokens read and the sum written forward,
    both read and the tokens' gradient written backward."""
    flops = 3.0 * rows * sizes["seq_len"] \
        * _forward_flops_per_token(sizes)["experts"]
    weights = sizes["num_hidden_layers"] * (
        sizes["num_experts"] * 3 * sizes["hidden_size"]
        * sizes["moe_intermediate_size"]
        + sizes["hidden_size"] * sizes["num_experts_routed"])
    return flops, 3 * 4.0 * weights + _stream_bytes(
        sizes, rows, sizes["hidden_size"], 5)


def attention_work(sizes: Dict[str, Any], rows: int) -> Tuple[float, float]:
    """(FLOPs, least HBM bytes) of the layers' attention (scores, softmax,
    weighted values; not the projections) of one step, forward and
    backward: two products over the keys a query sees, times three; bf16
    q, k, v read and the output written forward, those and the output's
    gradient read and three gradients written backward."""
    flops = 3.0 * rows * sizes["seq_len"] \
        * _forward_flops_per_token(sizes)["attention"]
    d = sizes["head_dim"]
    q_width = sizes["num_attention_heads"] * d
    kv_width = sizes["num_key_value_heads"] * d
    return flops, (_stream_bytes(sizes, rows, q_width, 2 + 4)
                   + _stream_bytes(sizes, rows, kv_width, 2 + 4))
