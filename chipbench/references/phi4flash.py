"""Plain reference of the step the ``phi-4-mini-flash-j6`` configuration
trains: the junction of a Phi-4-mini-flash-reasoning-style decoder (SambaY,
Ren et al. 2025, arXiv:2507.06607) in ``jax.numpy`` and float32, every
part written out, no kernels and no chunks. It imports nothing of the
program and makes its own weights from the seed; what it shares with the
other decoders' references is their plain helpers (``mellum.py``'s
gradient tree's in-place sum, hashable sizes and band's keys a query;
``granite.py``'s depthwise convolution and bf16 stream's bytes).

The equations (each assumption is under ``assumed`` in the configuration
file). ``n = LayerNorm(x)`` with scale and bias, eps ``layer_norm_eps``.
Every layer: ``h = x + Mixer(LN_1(x))``, then ``h + W_down (silu(W_gate
n') * (W_up n'))`` with ``n' = LN_2(h)``, no biases. By ``layer_types``:

**``mamba1``** (Mamba-1, Gu & Dao 2023). ``[u | z] = n W_in``; ``u =
silu(conv(u))``, depthwise, causal, ``mamba_d_conv`` taps, with bias; ``[r
| B | C] = u W_x`` of ``mamba_dt_rank | mamba_d_state | mamba_d_state``;
``dt = softplus(r W_dt + b_dt)``; ``A = -exp(A_log)``, a number a channel
and state; ``h_t[c, n] = exp(dt_t[c] A[c, n]) h_(t-1)[c, n] + dt_t[c]
B_t[n] u_t[c]``, ``y_t[c] = sum_n C_t[n] h_t[c, n] + D[c] u_t[c]``,
computed as that recurrence, a position at a time (:func:`_recurrence`);
the mixer gives ``(y * silu(z)) W_out``. The layer also **gives out ``M =
y``**, before the gate: the last ``mamba1`` layer before a ``gmu`` layer
is the one it reads.

**``sliding_attention``, ``full_attention``**: differential attention (Ye
et al. 2024). ``q = n W_q`` (``num_attention_heads`` heads of
``head_dim``), ``k = n W_k``, ``v = n W_v`` (``num_key_value_heads``),
no biases, no positions. Query heads ``(2i, 2i + 1)`` are pair ``i``'s
``q1, q2``; key heads ``(2j, 2j + 1)`` are ``k1, k2`` and value heads
``(2j, 2j + 1)`` side by side ``V_j`` of key/value pair ``j``; query pair
``i`` reads pair ``j = i // (heads / kv heads)``. ``A1 = softmax(mask(q1
k1^T / sqrt(head_dim)))``, ``A2`` the same of ``q2, k2``; the mask is
causal, in a window layer also ``i - j < sliding_window``. ``lambda =
exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6
exp(-0.3 l)`` at the layer's published index ``l``. ``o_i = (1 -
lambda_init) x RMSNorm((A1 - lambda A2) V_j) x g`` over the pair's ``2 x
head_dim``; the mixer gives ``concat_i(o_i) W_o``. A ``full_attention``
layer also **gives out its ``K, V``**.

**``gmu``** (a Gated Memory Unit): ``(silu(n W_1) * M) W_2``. **``cross``**:
differential attention with this layer's ``W_q``, ``lambda`` vectors, ``g``
and ``W_o`` over the ``K, V`` the last ``full_attention`` layer before it
gave out, causal over the whole row; no ``W_k``, no ``W_v``.

After the last layer ``LN_f`` and the head, which is the embedding's own
matrix (``tie_word_embeddings``); the loss is the mean next-token negative
log-likelihood over the ``seq_len - 1`` shifted positions of each row,
over this chip's slice of the vocabulary.

``value_and_grad`` goes a row at a time and a layer at a time (each
layer's input kept, its activations made again in the backward pass; the
recurrence in blocks of positions, a block's states made again; attention
a head pair at a time; the head's logits in blocks of positions), so that
its float32 activations fit beside 16 bytes a parameter. ``M`` and ``K,
V`` are kept across its layers, and what their readers hand back is
summed with their own layer's before that layer runs backward
(:func:`add_row`).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from chipbench.references.granite import _conv_silu, _stream_bytes
from chipbench.references.mellum import (HEAD_BLOCK, _Sizes, _add,
                                         _keys_per_query)
# The whole model is followed, nothing to cut: the harness finds these here.
from chipbench.references.mellum import (  # noqa: F401
    remap, take_rows, touched_rows)

MAMBA1, SLIDING, FULL = "mamba1", "sliding_attention", "full_attention"
GMU, CROSS = "gmu", "cross"
ATTENTIONS = (SLIDING, FULL, CROSS)
SCAN_BLOCK = 128     # positions whose states one block of the scan keeps


def _mamba_width(sizes) -> int:
    return sizes["mamba_expand"] * sizes["hidden_size"]


def lambda_init(published_index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * published_index)


def init_params(sizes: Dict[str, Any], key) -> Dict[str, Any]:
    """Seeded float32 weights in the layout the trainer takes. Matrices
    N(0, 0.02); what writes into the residual stream (``out_proj``, ``w2``,
    ``wo``, every ``down``) N(0, 0.02 / sqrt(2 x the published depth));
    unit norm scales, zero norm biases; the tied embedding N(0, 0.02). A
    Mamba-1 mixer as state-spaces/mamba's ``Mamba`` draws it: ``dt``
    log-uniform in [0.001, 0.1] through ``dt_bias`` (its inverse
    softplus), ``A[c, n] = n + 1`` as ``a_log``, ``D`` 1, the
    convolution's taps and bias uniform in +-1 / sqrt(taps). A
    differential layer's four ``lambda`` vectors N(0, 0.1), its pairs'
    norm scale 1."""
    h, f, d = (sizes["hidden_size"], sizes["intermediate_size"],
               sizes["head_dim"])
    q_width = sizes["num_attention_heads"] * d
    kv_width = sizes["num_key_value_heads"] * d
    width, state = _mamba_width(sizes), sizes["mamba_d_state"]
    rank, taps = sizes["mamba_dt_rank"], sizes["mamba_d_conv"]
    residual = 0.02 / math.sqrt(2 * sizes["published"]["num_hidden_layers"])
    keys = iter(jax.random.split(key, 1 + 12 * sizes["num_hidden_layers"]))

    def normal(shape, std=0.02):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    def uniform(shape, low, high):
        return jax.random.uniform(next(keys), shape, jnp.float32, low, high)

    def norm(name):
        return {name: jnp.ones((h,), jnp.float32),
                f"{name}_bias": jnp.zeros((h,), jnp.float32)}

    params: Dict[str, Any] = {"embed": normal((sizes["vocab_size"], h)),
                              **norm("final_norm")}
    for i, kind in enumerate(sizes["layer_types"]):
        if kind == MAMBA1:
            dt = jnp.exp(uniform((width,), math.log(0.001), math.log(0.1)))
            edge = 1.0 / math.sqrt(taps)
            p = {**norm("mamba_norm"),
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
        elif kind == GMU:
            p = {**norm("gmu_norm"), "w1": normal((h, width)),
                 "w2": normal((width, h), residual)}
        elif kind in ATTENTIONS:
            p = {**norm("attn_norm"), "wq": normal((h, q_width))}
            if kind != CROSS:
                p.update(wk=normal((h, kv_width)), wv=normal((h, kv_width)))
            p["wo"] = normal((q_width, h), residual)
            for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
                p[name] = normal((d,), 0.1)
            p["subln"] = jnp.ones((2 * d,), jnp.float32)
        else:
            raise ValueError(f"unknown layer_types entry {kind!r}")
        p.update(**norm("mlp_norm"), gate=normal((h, f)), up=normal((h, f)),
                 down=normal((f, h), residual))
        params[f"layer_{i}"] = p
    return params


# -- one row through one layer -----------------------------------------------------


def _layer_norm(x, scale, bias, eps):
    centered = x - jnp.mean(x, axis=-1, keepdims=True)
    return (centered * jax.lax.rsqrt(
        jnp.mean(centered * centered, axis=-1, keepdims=True) + eps)
        * scale + bias)


def _normed(sizes, p, name, x):
    return _layer_norm(x, p[name], p[f"{name}_bias"],
                       sizes["layer_norm_eps"])


def _recurrence(u, dt, a, b, c, d):
    """``h_t = exp(dt_t A) h_(t-1) + dt_t u_t B_t``, ``y_t = h_t . C_t + D
    u_t`` for u and dt (S, C), a (C, N), b and c (S, N), d (C,): a
    position at a time, in blocks of ``SCAN_BLOCK`` positions whose states
    the backward pass makes again (a row's states would be 2.7 GB)."""
    s, channels = u.shape
    block = math.gcd(s, SCAN_BLOCK)

    def position(h, at):
        u_t, dt_t, b_t, c_t = at
        h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * u_t)[:, None] * b_t
        return h, jnp.sum(h * c_t, axis=-1) + d * u_t

    @jax.checkpoint
    def run(h, block_of):
        return jax.lax.scan(position, h, block_of)

    blocks = tuple(m.reshape(s // block, block, *m.shape[1:])
                   for m in (u, dt, b, c))
    start = jnp.zeros((channels, b.shape[-1]), u.dtype)
    return jax.lax.scan(run, start, blocks)[1].reshape(s, channels)


def mamba1_mixer(sizes, p, n):
    """n (S, h) -> (Mamba-1(n) (S, h), the scan's output y (S, C))."""
    rank, state = sizes["mamba_dt_rank"], sizes["mamba_d_state"]
    u, z = jnp.split(n @ p["in_proj"], 2, axis=-1)
    u = _conv_silu(u, p["conv_w"], p["conv_b"])
    r, b, c = jnp.split(u @ p["x_proj"], [rank, rank + state], axis=-1)
    y = _recurrence(u, jax.nn.softplus(r @ p["dt_proj"] + p["dt_bias"]),
                    -jnp.exp(p["a_log"]), b, c, p["d"])
    return (y * jax.nn.silu(z)) @ p["out_proj"], y


def _differential(sizes, index: int, kind: str, p, q, k, v):
    """q (S, H, D), k and v (S, Hkv, D) -> (S, H / 2, 2 D): differential
    attention a head pair at a time (each made again in the backward pass:
    a map's (S, S) float32 scores are 268 MB at 8,192)."""
    s, heads, d = q.shape
    per = heads // k.shape[1]      # query pairs a key/value pair
    ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    seen = ahead >= 0
    if kind == SLIDING:
        seen &= ahead < sizes["sliding_window"]
    start = lambda_init(sizes["published_layer_indices"][index])
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + start)

    def weights(q_, k_):
        return jax.nn.softmax(
            jnp.where(seen, q_ @ k_.T / math.sqrt(d), -jnp.inf), axis=-1)

    @jax.checkpoint
    def one_pair(i):
        j = i // per
        values = jnp.concatenate([v[:, 2 * j], v[:, 2 * j + 1]], axis=-1)
        diff = (weights(q[:, 2 * i], k[:, 2 * j])
                - lam * weights(q[:, 2 * i + 1], k[:, 2 * j + 1])) @ values
        normed = diff * jax.lax.rsqrt(
            jnp.mean(diff * diff, axis=-1, keepdims=True)
            + sizes["layer_norm_eps"])
        return (1.0 - start) * normed * p["subln"]

    return jax.lax.map(one_pair, jnp.arange(heads // 2)).transpose(1, 0, 2)


def attention_mixer(sizes, index: int, kind: str, p, n, kv):
    """n (S, h) -> (differential attention(n) (S, h), (K, V)): a
    ``cross`` layer over the ``kv`` it is given, the others over their
    own."""
    s = n.shape[0]
    q = (n @ p["wq"]).reshape(s, sizes["num_attention_heads"], -1)
    if kind == CROSS:
        k, v = kv
    else:
        k = (n @ p["wk"]).reshape(s, sizes["num_key_value_heads"], -1)
        v = (n @ p["wv"]).reshape(s, sizes["num_key_value_heads"], -1)
    out = _differential(sizes, index, kind, p, q, k, v)
    return out.reshape(s, -1) @ p["wo"], (k, v)


def layer(sizes, index: int, p, x, taken):
    """x (S, h) -> (x (S, h), what the layer gives out): ``taken`` is
    ``M`` for a ``gmu`` layer and ``(K, V)`` for a ``cross`` layer, else
    ``None``; a ``mamba1`` layer gives out ``M``, a ``full_attention``
    layer ``(K, V)``, the others ``None``."""
    kind = sizes["layer_types"][index]
    gives = None
    if kind == MAMBA1:
        mixed, gives = mamba1_mixer(sizes, p, _normed(sizes, p, "mamba_norm",
                                                      x))
    elif kind == GMU:
        n = _normed(sizes, p, "gmu_norm", x)
        mixed = (jax.nn.silu(n @ p["w1"]) * taken) @ p["w2"]
    else:
        mixed, made = attention_mixer(
            sizes, index, kind, p, _normed(sizes, p, "attn_norm", x), taken)
        if kind == FULL:
            gives = made
    x = x + mixed
    n = _normed(sizes, p, "mlp_norm", x)
    return x + (jax.nn.silu(n @ p["gate"]) * (n @ p["up"])) @ p["down"], gives


def _head_nll(sizes, scale, bias, embed, x, targets):
    """Summed next-token negative log-likelihood of positions x (n, h)
    under the tied head."""
    logits = _layer_norm(x, scale, bias, sizes["layer_norm_eps"]) @ embed.T
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_forward(sizes, index, p, x, taken):
    return layer(sizes, index, p, x, taken)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_backward(sizes, index, p, x, taken, dy, d_gives):
    """``(d p, d x, d taken)`` from what the stream and the readers of
    what the layer gave out hand back."""
    _, vjp = jax.vjp(functools.partial(layer, sizes, index), p, x, taken)
    return vjp((dy, d_gives))


@functools.partial(jax.jit, static_argnums=(0,))
def _head_block(sizes, scale, bias, embed, x, targets):
    return jax.value_and_grad(functools.partial(_head_nll, sizes),
                              argnums=(0, 1, 2, 3))(scale, bias, embed, x,
                                                    targets)


def sources(kinds: Sequence[str]) -> Dict[int, int]:
    """Reader layer -> the layer whose tensors it takes: a ``gmu`` the
    last ``mamba1`` before it, a ``cross`` the last ``full_attention``."""
    last, out = {}, {}
    for i, kind in enumerate(kinds):
        if kind in (GMU, CROSS):
            out[i] = last[MAMBA1 if kind == GMU else FULL]
        last[kind] = i
    return out


def add_row(sizes, params, tokens, total, grads, dropped=()):
    """One row ``tokens`` (S,): its summed loss added to ``total`` and its
    gradient to the tree ``grads``, a layer's share at a time. What a
    layer gives out is kept for its readers; what they hand back for it
    is summed and waits until the backward pass reaches the layer
    (``dropped``: reader layers whose share is left out, the control of
    that sum). The embedding's leaf takes both of its uses: the head's
    blocks, then the lookup's rows."""
    kinds = sizes["layer_types"]
    if not sizes["tie_word_embeddings"]:
        raise ValueError("the reference knows the tied head only")
    source = sources(kinds)
    x = params["embed"][tokens]
    inputs, given = [], {}
    for i in range(len(kinds)):
        inputs.append(x)
        x, given[i] = _layer_forward(sizes, i, params[f"layer_{i}"], x,
                                     given.get(source.get(i)))
    targets = tokens[1:]
    d_x = []
    for lo in range(0, targets.shape[0], HEAD_BLOCK):
        value, (ds, db, de, dx) = _head_block(
            sizes, params["final_norm"], params["final_norm_bias"],
            params["embed"], x[:-1][lo:lo + HEAD_BLOCK],
            targets[lo:lo + HEAD_BLOCK])
        total = _add(total, value)
        grads["final_norm"] = _add(grads.get("final_norm"), ds)
        grads["final_norm_bias"] = _add(grads.get("final_norm_bias"), db)
        grads["embed"] = _add(grads.get("embed"), de)
        d_x.append(dx)
    dy = jnp.concatenate(d_x + [jnp.zeros_like(x[-1:])], axis=0)
    handed_back: Dict[int, Any] = {}
    for i in reversed(range(len(kinds))):
        d_gives = handed_back.pop(i, None)
        if d_gives is None:     # no reader: nothing comes back but zeros
            d_gives = jax.tree.map(jnp.zeros_like, given[i])
        d_layer, dy, d_taken = _layer_backward(
            sizes, i, params[f"layer_{i}"], inputs.pop(),
            given.get(source.get(i)), dy, d_gives)
        if i in source and i not in dropped:
            handed_back[source[i]] = _add(handed_back.get(source[i]),
                                          d_taken)
        grads[f"layer_{i}"] = _add(grads.get(f"layer_{i}"), d_layer)
        del given[i]
    grads["embed"] = _add(
        grads["embed"], jnp.zeros_like(params["embed"]).at[tokens].add(dy))
    return total


def value_and_grad(sizes: Dict[str, Any], params: Dict[str, Any],
                   features: Sequence[Any], labels: Any, step: int,
                   seed_key=None, dropped=()
                   ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Loss (mean over the batch's shifted positions) and its gradient, a
    row at a time; one gradient tree is held, added to in place."""
    tokens = jnp.asarray(features[0], jnp.int32)
    sizes = _Sizes(sizes)
    total, grads = None, {}
    for row in tokens:
        total = add_row(sizes, params, row, total, grads, dropped)
    count = tokens.shape[0] * (tokens.shape[1] - 1)
    scale = jax.jit(lambda g: jax.tree.map(lambda x: x / count, g),
                    donate_argnums=(0,))
    return total / count, scale({k: grads[k] for k in params})


# -- operations and bytes of one train step, from the shapes ----------------------


def _layers(sizes, *kinds: str) -> int:
    return sum(k in kinds for k in sizes["layer_types"])


def param_count(sizes: Dict[str, Any]) -> int:
    """Of the layers ``layer_types`` names and ``vocab_size`` rows of the
    tied embedding: this chip's cut, or the published model given its 32
    layers and 200,064 rows."""
    h, f, d = (sizes["hidden_size"], sizes["intermediate_size"],
               sizes["head_dim"])
    heads, kv_heads = (sizes["num_attention_heads"],
                       sizes["num_key_value_heads"])
    width, state = _mamba_width(sizes), sizes["mamba_d_state"]
    rank, taps = sizes["mamba_dt_rank"], sizes["mamba_d_conv"]
    mlp = 3 * h * f + 2 * h + 2 * h        # with both of a layer's norms
    mamba1 = (h * 2 * width + (taps + 1) * width
              + width * (rank + 2 * state) + rank * width + width
              + width * state + width + width * h)
    differential = 4 * d + 2 * d
    attention = 2 * h * d * (heads + kv_heads) + differential
    cross = 2 * h * d * heads + differential
    return (sizes["vocab_size"] * h + 2 * h
            + _layers(sizes, MAMBA1) * (mamba1 + mlp)
            + _layers(sizes, SLIDING, FULL) * (attention + mlp)
            + _layers(sizes, GMU) * (2 * h * width + mlp)
            + _layers(sizes, CROSS) * (cross + mlp))


def _scan_ops_per_token(sizes) -> float:
    """Forward operations of one Mamba-1 layer's scan a token, whatever
    implements it: a channel and state's decay, its multiply-add into the
    state, ``dt u B``'s and the readout's (6), and the convolution's
    multiply-adds."""
    width = _mamba_width(sizes)
    return (6.0 * width * sizes["mamba_d_state"]
            + 2.0 * sizes["mamba_d_conv"] * width)


def _forward_flops_per_token(sizes) -> Dict[str, float]:
    """Forward FLOPs a token, by part of the model: matrix products, and
    the selective scan's elementwise operations."""
    h, f, d = (sizes["hidden_size"], sizes["intermediate_size"],
               sizes["head_dim"])
    heads, kv_heads = (sizes["num_attention_heads"],
                       sizes["num_key_value_heads"])
    width, state = _mamba_width(sizes), sizes["mamba_d_state"]
    rank, s = sizes["mamba_dt_rank"], sizes["seq_len"]
    # a map's scores at head_dim and its product with values twice as wide
    a_key = 2.0 * d * heads + 2.0 * 2 * d * heads
    return {
        "mamba_projections": _layers(sizes, MAMBA1) * 2.0 * (
            h * 2 * width + width * (rank + 2 * state) + rank * width
            + width * h),
        "scan": _layers(sizes, MAMBA1) * _scan_ops_per_token(sizes),
        "gmu_projections": _layers(sizes, GMU) * 2.0 * 2 * h * width,
        "projections": 2.0 * h * d * (
            _layers(sizes, SLIDING, FULL) * (2 * heads + 2 * kv_heads)
            + _layers(sizes, CROSS) * 2 * heads),
        "attention": a_key * (
            _layers(sizes, SLIDING) * _keys_per_query(sizes, SLIDING)
            + _layers(sizes, FULL, CROSS) * _keys_per_query(sizes, FULL)),
        "mlp": sizes["num_hidden_layers"] * 3 * 2.0 * h * f,
        "head": 2.0 * h * sizes["vocab_size"] * (s - 1) / s,
    }


def train_flops_per_row(sizes: Dict[str, Any]) -> float:
    """FLOPs the forward and backward passes need for one row of
    ``seq_len`` tokens, times three (forward, and two products per matmul
    backward): the mixers' projections, the selective scans, the
    differential attentions' maps over the band or the triangle, every
    layer's dense MLP, the tied head. Recomputation is not counted."""
    return 3.0 * sizes["seq_len"] * sum(
        _forward_flops_per_token(sizes).values())


def train_step_bytes(sizes: Dict[str, Any], rows: int) -> float:
    """HBM bytes one step cannot avoid: dense Adam's 28 bytes a float32
    parameter, plus each row's bf16 residual stream written and read once
    per layer forward and backward. A floor: the step is bound by FLOPs."""
    return 28.0 * param_count(sizes) + sizes["num_hidden_layers"] \
        * _stream_bytes(sizes, rows, sizes["hidden_size"], 4)


def sscan_work(sizes: Dict[str, Any], rows: int) -> Tuple[float, float]:
    """(operations, least HBM bytes) of the Mamba-1 layers' convolution,
    softplus, scan and gate of one step of ``rows`` rows, forward and
    backward, whatever implements them: the scan's and the convolution's
    operations (``_scan_ops_per_token``), times three; bf16 ``u``, ``z``,
    ``dt``, ``B`` and ``C`` read and ``y`` written once forward, their
    gradients once more backward. Nothing made again is counted."""
    layers = _layers(sizes, MAMBA1)
    ops = 3.0 * rows * sizes["seq_len"] * layers * _scan_ops_per_token(sizes)
    ends = 4 * _mamba_width(sizes) + 2 * sizes["mamba_d_state"]
    return ops, layers * _stream_bytes(sizes, rows, ends, 2)


def proj_work(sizes: Dict[str, Any], rows: int) -> Tuple[float, float]:
    """(FLOPs, least HBM bytes) of the mixers' projections (a Mamba-1
    layer's ``W_in``, ``W_x``, ``W_dt`` and ``W_out``, a memory unit's
    ``W_1`` and ``W_2``, an attention layer's q, k, v and ``W_o``, a cross
    layer's q and ``W_o``) of one step, forward and backward, whatever
    implements them: a product each, times three; their float32 weights
    read forward and backward and their gradients written; per product
    the bf16 input read and the output written forward, and as much again
    in gradients backward with both inputs read once more."""
    parts = _forward_flops_per_token(sizes)
    flops = 3.0 * rows * sizes["seq_len"] * (
        parts["mamba_projections"] + parts["gmu_projections"]
        + parts["projections"])
    h, d = sizes["hidden_size"], sizes["head_dim"]
    q_width = sizes["num_attention_heads"] * d
    kv_width = sizes["num_key_value_heads"] * d
    width, state = _mamba_width(sizes), sizes["mamba_d_state"]
    rank = sizes["mamba_dt_rank"]
    # (input width, output width) of each product, by kind of layer
    products = (
        _layers(sizes, MAMBA1) * [(h, 2 * width), (width, rank + 2 * state),
                                  (rank, width), (width, h)]
        + _layers(sizes, GMU) * [(h, width), (width, h)]
        + _layers(sizes, SLIDING, FULL) * [(h, q_width), (h, kv_width),
                                           (h, kv_width), (q_width, h)]
        + _layers(sizes, CROSS) * [(h, q_width), (q_width, h)])
    weights = sum(fan_in * fan_out for fan_in, fan_out in products)
    ends = sum(fan_in + fan_out for fan_in, fan_out in products)
    inputs = sum(fan_in for fan_in, _ in products)
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
    weights = layers * 3 * sizes["hidden_size"] * sizes["intermediate_size"]
    return flops, 3 * 4.0 * weights + layers * _stream_bytes(
        sizes, rows, sizes["hidden_size"], 5)


def attention_work(sizes: Dict[str, Any], rows: int) -> Tuple[float, float]:
    """(FLOPs, least HBM bytes) of the differential attentions (both
    maps' scores and softmaxes, their products with the values, the
    subtraction and the pairs' norm; not the projections) of one step,
    forward and backward, by the mathematics, whatever implements it:
    ``num_attention_heads`` maps' ``q k^T`` at ``head_dim`` and as many
    products with values twice as wide, over the band or the triangle,
    times three; bf16 q, k, v read and the output written forward, those
    and the output's gradient read and three gradients written
    backward."""
    flops = 3.0 * rows * sizes["seq_len"] \
        * _forward_flops_per_token(sizes)["attention"]
    widths = _layers(sizes, *ATTENTIONS) * sizes["head_dim"] * (
        sizes["num_attention_heads"] + sizes["num_key_value_heads"])
    return flops, _stream_bytes(sizes, rows, widths, 2 + 4)
