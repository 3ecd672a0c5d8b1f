"""Plain reference of the step the ``laguna-xs.2-ep8`` configuration
trains: one chip's share of a Laguna-XS.2-style decoder, in ``jax.numpy``
and float32, every part written out, no kernels. It imports nothing of the
program and makes its own weights from the seed; what it shares with the
other decoder's reference (``chipbench/references/mellum.py``) is that
file's plain helpers: RMSNorm, a head's masked softmax, the head's block.

The equations, from the source's ``config.json`` (each assumption is under
``assumed`` in the configuration file); ``n = RMSNorm(x)``, eps
``rms_norm_eps``. **Attention half** of layer i, of type t =
``layer_types[i]`` with H = ``num_attention_heads_per_layer[i]`` query
heads over ``num_key_value_heads`` key/value heads of ``head_dim``:
``q = n W_q``, ``k = n W_k``, ``v = n W_v``, no biases; rotary positions
on q and k over the first ``partial_rotary_factor`` of a head's dimensions
(rotate-half within them, the rest pass): a ``full_attention`` layer's
are YaRN's (inverse frequencies a fixed blend of interpolated and
extrapolated ones, cos and sin scaled by ``attention_factor``), a
``sliding_attention`` layer's plain; query head h reads key/value head
h // group; scores ``q k^T / sqrt(head_dim)`` masked to j <= i, in a
sliding layer also to j > i - ``sliding_window``; softmax; ``a = P v``;
with ``gating``, ``g = sigmoid(n W_g)``, one value a query head, and
``x += (g_h a_h)_h W_o``. **MLP half**, by ``mlp_layer_types[i]``:
``dense``: ``x += (silu(n G) * (n U)) D`` at ``intermediate_size``;
``sparse``: ``p = softmax(n W_r)`` over all ``num_experts_routed``
experts; S = the ``num_experts_per_tok`` largest; ``w_e = p_e / sum_S p``;
``x += moe_routed_scaling_factor x sum over e in S that are HELD of
w_e E_e(n) + Sh(n)``, E_e and the shared expert Sh SwiGLUs of
``moe_intermediate_size`` and ``shared_expert_intermediate_size``. **This
chip's share**: the routed sum runs over the ``num_experts`` experts held
from ``experts_held_first`` on; what the absent experts would add is left
out, and that partial result goes on to the next layer; attention, a dense
layer and the shared expert are whole (every chip computes them alike).
After the last layer RMSNorm and an untied head onto the vocabulary slice;
the loss is the mean next-token negative log-likelihood over the
``seq_len - 1`` shifted positions of each row.

``value_and_grad`` goes a row at a time and a layer at a time (each
layer's input kept, its activations made again in the backward pass;
attention a head at a time; the held experts a scan of dense products
over every token under the routing's weights; the head's logits in blocks
of positions), so that its float32 activations fit beside 16 bytes a
parameter.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from chipbench.references.mellum import (
    FULL, HEAD_BLOCK, SLIDING, _Sizes, _add, _attention, _head_block,
    _rms_norm)
# The whole model is followed, nothing to cut: the harness finds these here.
from chipbench.references.mellum import (  # noqa: F401
    remap, take_rows, touched_rows)

DENSE, SPARSE = "dense", "sparse"


def _heads(sizes, i: int) -> int:
    return sizes["num_attention_heads_per_layer"][i]


def init_params(sizes: Dict[str, Any], key) -> Dict[str, Any]:
    """Seeded float32 weights in the layout the trainer takes: the
    embedding N(0, 1), matrices N(0, 0.02), the projections that write
    into the residual stream (``wo``, every ``down``: a dense layer's, the
    held experts', the shared expert's) N(0, 0.02 / sqrt(2 x the published
    depth)), unit norm scales (the other decoder's init and its reasons:
    ``chipbench/references/mellum.py``)."""
    h, d = sizes["hidden_size"], sizes["head_dim"]
    kv_width = sizes["num_key_value_heads"] * d
    held = sizes["num_experts"]
    residual = 0.02 / math.sqrt(2 * sizes["published"]["num_hidden_layers"])
    keys = iter(jax.random.split(key, 2 + 12 * sizes["num_hidden_layers"]))

    def normal(shape, std=0.02):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    params: Dict[str, Any] = {
        "embed": normal((sizes["vocab_size"], h), 1.0),
        "head": normal((h, sizes["vocab_size"])),
        "final_norm": jnp.ones((h,), jnp.float32),
    }
    for i in range(sizes["num_hidden_layers"]):
        q_width = _heads(sizes, i) * d
        p = {"attn_norm": jnp.ones((h,), jnp.float32),
             "wq": normal((h, q_width)),
             "wk": normal((h, kv_width)),
             "wv": normal((h, kv_width)),
             "wo": normal((q_width, h), residual)}
        if sizes["gating"]:
            p["wg"] = normal((h, _heads(sizes, i)))
        if sizes["mlp_layer_types"][i] == DENSE:
            f = sizes["intermediate_size"]
            p.update(mlp_norm=jnp.ones((h,), jnp.float32),
                     gate=normal((h, f)), up=normal((h, f)),
                     down=normal((f, h), residual))
        else:
            f, fs = (sizes["moe_intermediate_size"],
                     sizes["shared_expert_intermediate_size"])
            p.update(moe_norm=jnp.ones((h,), jnp.float32),
                     router=normal((h, sizes["num_experts_routed"])),
                     gate=normal((held, h, f)), up=normal((held, h, f)),
                     down=normal((held, f, h), residual),
                     shared_gate=normal((h, fs)), shared_up=normal((h, fs)),
                     shared_down=normal((fs, h), residual))
        params[f"layer_{i}"] = p
    return params


# -- one row through one layer -----------------------------------------------------


def rotated_dims(sizes, layer_type: str) -> int:
    """How many of a head's dimensions, the first, a layer type rotates."""
    rope = sizes["rope_parameters"][layer_type]
    return int(sizes["head_dim"] * rope.get("partial_rotary_factor", 1))


def inv_freq(sizes: Dict[str, Any], layer_type: str):
    """(inverse frequencies (rotated dims / 2,), the scale of cos and sin),
    the rotated dimensions taking a whole head's place in the formulas."""
    dim = rotated_dims(sizes, layer_type)
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
    """x (S, heads, D): the first ``cos.shape[-1]`` of each head's D
    rotated by position, rotate-half within them; the rest pass."""
    r = cos.shape[-1]
    turn, rest = x[..., :r], x[..., r:]
    turned = jnp.concatenate([-turn[..., r // 2:], turn[..., :r // 2]],
                             axis=-1)
    return jnp.concatenate(
        [turn * cos[:, None, :] + turned * sin[:, None, :], rest], axis=-1)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _routed(sizes, x, p):
    """The held experts' part of the scaled routed sum for x (S, h)."""
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    top, ids = jax.lax.top_k(probs, sizes["num_experts_per_tok"])
    top = sizes["moe_routed_scaling_factor"] * top / top.sum(
        axis=-1, keepdims=True)
    held = sizes["experts_held_first"] + jnp.arange(sizes["num_experts"])

    @jax.checkpoint      # an expert's activations are made again, not kept
    def add_expert(out, expert):
        e, gate, up, down = expert
        weight = jnp.sum(jnp.where(ids == e, top, 0.0), axis=-1,
                         keepdims=True)
        return out + weight * _swiglu(x, gate, up, down), None

    return jax.lax.scan(add_expert, jnp.zeros_like(x),
                        (held, p["gate"], p["up"], p["down"]))[0]


def attention_half(sizes, i: int, p, x):
    """x (S, h) -> x + gated attention of RMSNorm(x), layer ``i``'s."""
    s = x.shape[0]
    layer_type, heads = sizes["layer_types"][i], _heads(sizes, i)
    kv_heads = sizes["num_key_value_heads"]
    freqs, scale = inv_freq(sizes, layer_type)
    angles = jnp.arange(s)[:, None] * freqs
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(angles) * scale, jnp.sin(angles) * scale
    n = _rms_norm(x, p["attn_norm"], sizes["rms_norm_eps"])
    q = _rotate((n @ p["wq"]).reshape(s, heads, -1), cos, sin)
    k = _rotate((n @ p["wk"]).reshape(s, kv_heads, -1), cos, sin)
    v = (n @ p["wv"]).reshape(s, kv_heads, -1)
    a = _attention(sizes, layer_type, q, k, v)
    if sizes["gating"]:
        a = a * jax.nn.sigmoid(n @ p["wg"])[:, :, None]
    return x + a.reshape(s, -1) @ p["wo"]


def mlp_half(sizes, i: int, p, x):
    """x (S, h) -> x + the MLP of RMSNorm(x), layer ``i``'s."""
    if sizes["mlp_layer_types"][i] == DENSE:
        n = _rms_norm(x, p["mlp_norm"], sizes["rms_norm_eps"])
        return x + _swiglu(n, p["gate"], p["up"], p["down"])
    n = _rms_norm(x, p["moe_norm"], sizes["rms_norm_eps"])
    return x + _routed(sizes, n, p) + _swiglu(
        n, p["shared_gate"], p["shared_up"], p["shared_down"])


def layer(sizes, i: int, p, x):
    """x (S, h) -> x (S, h)."""
    return mlp_half(sizes, i, p, attention_half(sizes, i, p, x))


def _like(sizes, i: int) -> int:
    """The first layer with layer ``i``'s shapes: its compiled programs
    serve layer ``i`` too."""
    kind = list(zip(sizes["layer_types"],
                    sizes["num_attention_heads_per_layer"],
                    sizes["mlp_layer_types"]))
    return kind.index(kind[i])


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_forward(sizes, i, p, x):
    return layer(sizes, i, p, x)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_backward(sizes, i, p, x, dy):
    _, vjp = jax.vjp(functools.partial(layer, sizes, i), p, x)
    return vjp(dy)


def add_row(sizes, params, tokens, total, grads):
    """One row ``tokens`` (S,): its summed loss added to ``total`` and its
    gradient to the tree ``grads``, a layer's share at a time."""
    layers = range(sizes["num_hidden_layers"])
    x = params["embed"][tokens]
    inputs = []
    for i in layers:
        inputs.append(x)
        x = _layer_forward(sizes, _like(sizes, i), params[f"layer_{i}"], x)
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
    for i in reversed(layers):
        d_layer, dy = _layer_backward(
            sizes, _like(sizes, i), params[f"layer_{i}"], inputs.pop(), dy)
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


# -- operations and bytes of one train step, from the shapes ----------------------


def param_count(sizes: Dict[str, Any]) -> int:
    h, d, v = sizes["hidden_size"], sizes["head_dim"], sizes["vocab_size"]
    kv_width = sizes["num_key_value_heads"] * d
    total = 2 * v * h + h
    for i in range(sizes["num_hidden_layers"]):
        heads = _heads(sizes, i)
        total += 2 * h * heads * d + 2 * h * kv_width + 2 * h
        if sizes["gating"]:
            total += h * heads
        if sizes["mlp_layer_types"][i] == DENSE:
            total += 3 * h * sizes["intermediate_size"]
        else:
            total += (h * sizes["num_experts_routed"]
                      + sizes["num_experts"] * 3 * h
                      * sizes["moe_intermediate_size"]
                      + 3 * h * sizes["shared_expert_intermediate_size"])
    return total


def _keys_per_query(sizes, layer_type: str) -> float:
    """Keys a query sees, averaged over a row's positions: the triangle,
    or the band."""
    s = sizes["seq_len"]
    w = min(sizes["sliding_window"], s) if layer_type == SLIDING else s
    return (w * (w + 1) / 2 + (s - w) * w) / s


def _sparse_layers(sizes) -> int:
    return sum(kind == SPARSE for kind in sizes["mlp_layer_types"])


def _forward_flops_per_token(sizes) -> Dict[str, float]:
    """Forward matrix-multiply FLOPs a token, by part of the model."""
    h, d = sizes["hidden_size"], sizes["head_dim"]
    kv_heads = sizes["num_key_value_heads"]
    heads = sizes["num_attention_heads_per_layer"]
    sparse = _sparse_layers(sizes)
    # of a token's picks, the share that falls on held experts
    held_picks = (sizes["num_experts_per_tok"] * sizes["num_experts"]
                  / sizes["num_experts_routed"])
    gate = 1 if sizes["gating"] else 0
    return {
        "projections": sum(2.0 * h * (d * (2 * n + 2 * kv_heads) + gate * n)
                           for n in heads),
        "attention": sum(2 * 2.0 * d * n * _keys_per_query(sizes, t)
                         for n, t in zip(heads, sizes["layer_types"])),
        "dense": (len(heads) - sparse) * 3 * 2.0 * h
        * sizes["intermediate_size"],
        "shared": sparse * 3 * 2.0 * h
        * sizes["shared_expert_intermediate_size"],
        "experts": sparse * held_picks * 3 * 2.0 * h
        * sizes["moe_intermediate_size"],
        "router": sparse * 2.0 * h * sizes["num_experts_routed"],
        "head": 2.0 * h * sizes["vocab_size"] * (sizes["seq_len"] - 1)
        / sizes["seq_len"],
    }


def train_flops_per_row(sizes: Dict[str, Any]) -> float:
    """Matrix-multiply FLOPs the forward and backward passes need for one
    row of ``seq_len`` tokens, times three (forward, and two products per
    matmul backward): the projections (the gate's among them),
    attention's two products over the keys a query sees (the triangle, or
    a sliding layer's band), the dense layer, the shared experts, the
    routed experts at the expected share of a token's picks that is held,
    the router, the head. Recomputation is not counted."""
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


def moe_work(sizes: Dict[str, Any], rows: int) -> Tuple[float, float]:
    """(FLOPs, least HBM bytes) of the routed experts of one step of
    ``rows`` rows, forward and backward, whatever implements them: the
    held picks' three products and the router, times three; the held
    experts' and the router's float32 weights read forward and backward
    and their gradients written, the bf16 tokens read and the sum written
    forward, both read and the tokens' gradient written backward."""
    parts = _forward_flops_per_token(sizes)
    flops = 3.0 * rows * sizes["seq_len"] * (parts["experts"]
                                             + parts["router"])
    weights = _sparse_layers(sizes) * (
        sizes["num_experts"] * 3 * sizes["hidden_size"]
        * sizes["moe_intermediate_size"]
        + sizes["hidden_size"] * sizes["num_experts_routed"])
    return flops, 3 * 4.0 * weights + _sparse_layers(sizes) * _stream_bytes(
        sizes, rows, sizes["hidden_size"], 5)


def mlp_work(sizes: Dict[str, Any], rows: int) -> Tuple[float, float]:
    """(FLOPs, least HBM bytes) of the dense MLP and the shared experts of
    one step, forward and backward, whatever implements them: three
    products each, times three; their float32 weights read forward and
    backward and their gradients written; per SwiGLU the bf16 tokens read
    and the result written forward, both read and the tokens' gradient
    written backward."""
    parts = _forward_flops_per_token(sizes)
    flops = 3.0 * rows * sizes["seq_len"] * (parts["dense"]
                                             + parts["shared"])
    sparse = _sparse_layers(sizes)
    dense = sizes["num_hidden_layers"] - sparse
    weights = 3 * sizes["hidden_size"] * (
        dense * sizes["intermediate_size"]
        + sparse * sizes["shared_expert_intermediate_size"])
    return flops, 3 * 4.0 * weights + (dense + sparse) * _stream_bytes(
        sizes, rows, sizes["hidden_size"], 5)


def proj_work(sizes: Dict[str, Any], rows: int) -> Tuple[float, float]:
    """(FLOPs, least HBM bytes) of the attention halves' projections (q,
    k, v, the gate and the output's) of one step, forward and backward,
    whatever implements them: a product each, times three; their float32
    weights read forward and backward and their gradients written; per
    layer the bf16 tokens read and q, k, v written, the attention's output
    read and the projected one written forward, and as much again in
    gradients backward with both inputs read once more."""
    flops = 3.0 * rows * sizes["seq_len"] \
        * _forward_flops_per_token(sizes)["projections"]
    h, d = sizes["hidden_size"], sizes["head_dim"]
    kv_heads = sizes["num_key_value_heads"]
    heads = sizes["num_attention_heads_per_layer"]
    gate = 1 if sizes["gating"] else 0
    weights = sum(h * (d * (2 * n + 2 * kv_heads) + gate * n) for n in heads)
    # what the products read and write forward (a gate's head-wide values
    # are left out), and of that what they read
    ends = sum(2 * h + d * (2 * n + 2 * kv_heads) for n in heads)
    inputs = sum(h + d * n for n in heads)
    return flops, 3 * 4.0 * weights + _stream_bytes(
        sizes, rows, 2 * ends + inputs, 1)


def attention_work(sizes: Dict[str, Any], rows: int) -> Tuple[float, float]:
    """(FLOPs, least HBM bytes) of the layers' attention (scores, softmax,
    weighted values; not the projections, nor the gate's product) of one
    step, forward and backward: two products over the keys a query sees,
    times three; bf16 q, k, v read and the output written forward, those
    and the output's gradient read and three gradients written
    backward."""
    flops = 3.0 * rows * sizes["seq_len"] \
        * _forward_flops_per_token(sizes)["attention"]
    d = sizes["head_dim"]
    widths = sum(d * (n + sizes["num_key_value_heads"])
                 for n in sizes["num_attention_heads_per_layer"])
    return flops, _stream_bytes(sizes, rows, widths, 2 + 4)
