"""Plain reference of the step the ``lfm2-24b-a2b-ep8`` configuration
trains: one chip's share of an LFM2-24B-A2B-style hybrid decoder (gated
short convolutions three layers in four, attention over normed q and k
heads, sparse experts picked by sigmoid scores under a selection bias), in
``jax.numpy`` and float32, every part written out, no kernels. It imports
nothing of the program and makes its own weights from the seed; what it
shares with the other decoders' references is
``chipbench/references/mellum.py``'s plain helpers (RMSNorm, rotate-half, a
head's masked softmax, the gradient tree's in-place sum, the hashable
sizes).

The equations (HF ``Lfm2Moe``; each assumption is under ``assumed`` in the
configuration file). Per layer ``i`` on the residual stream ``x``, ``n =
RMSNorm(x)``, eps ``norm_eps``. **A ``conv`` operator**: ``[B | C | u] = n
W_in`` (hidden -> 3 x hidden, no bias); ``v = B * u``; ``c_t = sum_k w[k]
v_(t-K+1+k)`` a channel, K = ``conv_L_cache`` taps, zeros before the row's
first position (``Conv1d(h, h, K, groups=h, padding=K-1, bias=False)`` cut
to S); ``y = C * c``; ``x += y W_out``. No activation anywhere in it. **A
``full_attention`` operator**: ``q = n W_q`` (``num_attention_heads`` heads
of ``head_dim``), ``k = n W_k``, ``v = n W_v`` (``num_key_value_heads``),
no biases; RMSNorm over each q head and each k head, one scale of
``head_dim`` for q and one for k; then plain rotate-half rotary over the
whole head at ``rope_theta``; causal softmax at ``1 / sqrt(head_dim)`` over
the whole row, each key/value head for its group of query heads; ``x += o
W_o``. Then ``m = RMSNorm(x)``. **A dense layer** (``i <
num_dense_layers``): ``x += (silu(m W_1) * (m W_3)) W_2`` at
``intermediate_size``. **A sparse layer**: ``s = sigmoid(m W_r)`` over all
``num_experts_routed`` experts; the picks are the ``num_experts_per_tok``
largest of ``s + b`` (``b`` = ``expert_bias``: it chooses and takes no
gradient; its balancing update moves it after the step:
:func:`value_and_grad`); ``w_e = s_e / (sum over the picks of s + 1e-6)``
(``norm_topk_prob``), times ``routed_scaling_factor``; ``x += sum over the
picks HELD here of w_e SwiGLU_e(m)`` at ``moe_intermediate_size``. **This
chip's share**: the routed sum runs over the ``num_experts`` experts held
from ``experts_held_first`` on; what the absent experts would add is left
out, and that partial result goes on to the next layer; the operators and
the dense layer are whole (every chip computes them alike). After the last
layer RMSNorm and the head, which is the embedding's own matrix
(``tie_word_embeddings``), over the vocabulary slice; the loss is the mean
next-token negative log-likelihood over the ``seq_len - 1`` shifted
positions of each row.

``value_and_grad`` goes a row at a time and a layer at a time (each
layer's input kept, its activations made again in the backward pass;
attention a head at a time; the held experts a scan of dense products over
every token under the routing's weights; the head's logits in blocks of
positions), so that its float32 activations fit beside 16 bytes a
parameter.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from chipbench.references.mellum import (FULL, HEAD_BLOCK, _Sizes, _add,
                                         _attention, _rms_norm, _rotate)
# The whole model is followed, nothing to cut: the harness finds these here.
from chipbench.references.mellum import (  # noqa: F401
    remap, take_rows, touched_rows)

CONV = "conv"
#: What the router adds to the sum of a token's picked scores before it
#: divides by it.
ROUTER_SUM_EPS = 1e-6
#: The standard deviation of the seeded ``expert_bias`` (the configuration
#: file's ``assumed.expert_bias`` says why it is not zero).
EXPERT_BIAS_STD = 0.002


def _dense(sizes, i: int) -> bool:
    return i < sizes["num_dense_layers"]


def init_params(sizes: Dict[str, Any], key) -> Dict[str, Any]:
    """Seeded float32 weights in the layout the trainer takes. Matrices
    N(0, 0.02); what writes into the residual stream (``out_proj``, ``wo``,
    every ``down``) N(0, 0.02 / sqrt(2 x the published depth)); unit norm
    scales, the q and k heads' among them; the tied embedding N(0, 0.02)
    (it is the head too: granite's reason); a ``conv`` operator's taps
    uniform in +-1 / sqrt(taps) (``Conv1d``'s default at a fan-in of
    ``conv_L_cache``); ``expert_bias`` N(0, ``EXPERT_BIAS_STD``)."""
    h, d = sizes["hidden_size"], sizes["head_dim"]
    q_width = sizes["num_attention_heads"] * d
    kv_width = sizes["num_key_value_heads"] * d
    held, taps = sizes["num_experts"], sizes["conv_L_cache"]
    residual = 0.02 / math.sqrt(2 * sizes["published"]["num_hidden_layers"])
    keys = iter(jax.random.split(key, 1 + 12 * sizes["num_hidden_layers"]))

    def normal(shape, std=0.02):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    params: Dict[str, Any] = {"embed": normal((sizes["vocab_size"], h)),
                              "final_norm": jnp.ones((h,), jnp.float32)}
    for i, kind in enumerate(sizes["layer_types"]):
        if kind == CONV:
            edge = 1.0 / math.sqrt(taps)
            p = {"conv_norm": jnp.ones((h,), jnp.float32),
                 "in_proj": normal((h, 3 * h)),
                 "conv_w": jax.random.uniform(next(keys), (taps, h),
                                              jnp.float32, -edge, edge),
                 "out_proj": normal((h, h), residual)}
        elif kind == FULL:
            p = {"attn_norm": jnp.ones((h,), jnp.float32),
                 "wq": normal((h, q_width)), "wk": normal((h, kv_width)),
                 "wv": normal((h, kv_width)),
                 "wo": normal((q_width, h), residual),
                 "q_layernorm": jnp.ones((d,), jnp.float32),
                 "k_layernorm": jnp.ones((d,), jnp.float32)}
        else:
            raise ValueError(f"unknown layer_types entry {kind!r}")
        if _dense(sizes, i):
            f = sizes["intermediate_size"]
            p.update(mlp_norm=jnp.ones((h,), jnp.float32),
                     gate=normal((h, f)), up=normal((h, f)),
                     down=normal((f, h), residual))
        else:
            f = sizes["moe_intermediate_size"]
            p.update(moe_norm=jnp.ones((h,), jnp.float32),
                     router=normal((h, sizes["num_experts_routed"])),
                     expert_bias=normal((sizes["num_experts_routed"],),
                                        EXPERT_BIAS_STD),
                     gate=normal((held, h, f)), up=normal((held, h, f)),
                     down=normal((held, f, h), residual))
        params[f"layer_{i}"] = p
    return params


# -- one row through one layer -----------------------------------------------------


def gated_conv(b, c, u, weight):
    """``C * conv(B * u)`` for b, c, u (S, C) and ``weight`` (K, C):
    position t sees ``v_(t-K+1) .. v_t`` under ``weight[0] ..
    weight[K-1]``, zeros before the first position."""
    taps, s = weight.shape[0], b.shape[0]
    padded = jnp.pad(b * u, ((taps - 1, 0), (0, 0)))
    return c * sum(weight[k] * padded[k:k + s] for k in range(taps))


def conv_half(sizes, p, x):
    """x (S, h) -> x + the gated short convolution of RMSNorm(x)."""
    n = _rms_norm(x, p["conv_norm"], sizes["norm_eps"])
    b, c, u = jnp.split(n @ p["in_proj"], 3, axis=-1)
    return x + gated_conv(b, c, u, p["conv_w"]) @ p["out_proj"]


def attention_half(sizes, p, x):
    """x (S, h) -> x + attention of RMSNorm(x) over normed, rotated q and
    k heads."""
    rope = sizes["rope_parameters"]
    if rope["rope_type"] != "default":
        raise ValueError("the reference knows plain rotary only")
    s, d = x.shape[0], sizes["head_dim"]
    heads, kv_heads = (sizes["num_attention_heads"],
                       sizes["num_key_value_heads"])
    freqs = 1.0 / rope["rope_theta"] ** (jnp.arange(0, d, 2) / d)
    angles = jnp.arange(s)[:, None] * freqs
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    n = _rms_norm(x, p["attn_norm"], sizes["norm_eps"])
    q = _rms_norm((n @ p["wq"]).reshape(s, heads, d), p["q_layernorm"],
                  sizes["norm_eps"])
    k = _rms_norm((n @ p["wk"]).reshape(s, kv_heads, d), p["k_layernorm"],
                  sizes["norm_eps"])
    v = (n @ p["wv"]).reshape(s, kv_heads, d)
    a = _attention(sizes, FULL, _rotate(q, cos, sin), _rotate(k, cos, sin),
                   v)
    return x + a.reshape(s, -1) @ p["wo"]


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(sizes, x, p):
    """``(ids (S, top_k), weights (S, top_k))`` of x (S, h): sigmoid
    scores, the picks by ``score + expert_bias``, the weights the picks'
    scores over their sum plus 1e-6, times ``routed_scaling_factor``.
    Where ``router_trains`` is false the router's matrix takes no
    gradient (the configuration file's ``assumed.router_trains``)."""
    router = (p["router"] if sizes.get("router_trains", True)
              else jax.lax.stop_gradient(p["router"]))
    scores = jax.nn.sigmoid(x @ router)
    _, ids = jax.lax.top_k(scores + p["expert_bias"],
                           sizes["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, ids, axis=-1)
    if sizes["norm_topk_prob"]:
        top = top / (top.sum(axis=-1, keepdims=True) + ROUTER_SUM_EPS)
    return ids, sizes["routed_scaling_factor"] * top


def routed(sizes, x, p, first: int, experts):
    """The part of the routed sum for x (S, h) that the experts
    ``experts`` = (gate, up, down), numbered from ``first`` on, give."""
    ids, top = route(sizes, x, p)
    gates, ups, downs = experts

    @jax.checkpoint      # an expert's activations are made again, not kept
    def add_expert(out, expert):
        e, gate, up, down = expert
        weight = jnp.sum(jnp.where(ids == e, top, 0.0), axis=-1,
                         keepdims=True)
        return out + weight * _swiglu(x, gate, up, down), None

    return jax.lax.scan(
        add_expert, jnp.zeros_like(x),
        (first + jnp.arange(gates.shape[0]), gates, ups, downs))[0]


def mlp_half(sizes, i: int, p, x):
    """x (S, h) -> x + the MLP of RMSNorm(x), layer ``i``'s."""
    if _dense(sizes, i):
        n = _rms_norm(x, p["mlp_norm"], sizes["norm_eps"])
        return x + _swiglu(n, p["gate"], p["up"], p["down"])
    n = _rms_norm(x, p["moe_norm"], sizes["norm_eps"])
    return x + routed(sizes, n, p, sizes["experts_held_first"],
                      (p["gate"], p["up"], p["down"]))


def layer(sizes, i: int, p, x):
    """x (S, h) -> x (S, h)."""
    operator = conv_half if sizes["layer_types"][i] == CONV \
        else attention_half
    return mlp_half(sizes, i, p, operator(sizes, p, x))


def _like(sizes, i: int) -> int:
    """The first layer with layer ``i``'s shapes: its compiled programs
    serve layer ``i`` too."""
    kind = [(t, _dense(sizes, n)) for n, t in enumerate(sizes["layer_types"])]
    return kind.index(kind[i])


def _head_nll(sizes, scale, embed, x, targets):
    """Summed next-token negative log-likelihood of positions x (n, h)
    under the tied head."""
    logits = _rms_norm(x, scale, sizes["norm_eps"]) @ embed.T
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_forward(sizes, i, p, x):
    return layer(sizes, i, p, x)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_loads(sizes, i, p, x):
    """(experts,) int32: how many of the positions of x (S, h), sparse
    layer ``i``'s input, pick each of the router's experts."""
    operator = conv_half if sizes["layer_types"][i] == CONV \
        else attention_half
    n = _rms_norm(operator(sizes, p, x), p["moe_norm"], sizes["norm_eps"])
    ids, _ = route(sizes, n, p)
    return jnp.sum(ids.reshape(-1, 1) == jnp.arange(
        sizes["num_experts_routed"]), axis=0, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_backward(sizes, i, p, x, dy):
    _, vjp = jax.vjp(functools.partial(layer, sizes, i), p, x)
    return vjp(dy)


@functools.partial(jax.jit, static_argnums=(0,))
def _head_block(sizes, scale, embed, x, targets):
    return jax.value_and_grad(functools.partial(_head_nll, sizes),
                              argnums=(0, 1, 2))(scale, embed, x, targets)


def add_row(sizes, params, tokens, total, grads, loads=None):
    """One row ``tokens`` (S,): its summed loss added to ``total`` and its
    gradient to the tree ``grads``, a layer's share at a time. The
    embedding's leaf takes both of its uses: the head's blocks, then the
    lookup's rows. Given ``loads``, each sparse layer's count of picks an
    expert is added to it under the layer's index."""
    if not sizes["tie_word_embeddings"]:
        raise ValueError("the reference knows the tied head only")
    layers = range(sizes["num_hidden_layers"])
    x = params["embed"][tokens]
    inputs = []
    for i in layers:
        inputs.append(x)
        if loads is not None and not _dense(sizes, i):
            loads[i] = _add(loads.get(i), _layer_loads(
                sizes, _like(sizes, i), params[f"layer_{i}"], x))
        x = _layer_forward(sizes, _like(sizes, i), params[f"layer_{i}"], x)
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
    for i in reversed(layers):
        d_layer, dy = _layer_backward(
            sizes, _like(sizes, i), params[f"layer_{i}"], inputs.pop(), dy)
        grads[f"layer_{i}"] = _add(grads.get(f"layer_{i}"), d_layer)
    grads["embed"] = _add(
        grads["embed"], jnp.zeros_like(params["embed"]).at[tokens].add(dy))
    return total


def value_and_grad(sizes: Dict[str, Any], params: Dict[str, Any],
                   features: Sequence[Any], labels: Any, step: int,
                   seed_key=None) -> Tuple[jax.Array, Dict[str, Any]]:
    """Loss (mean over the batch's shifted positions) and its gradient, a
    row at a time; one gradient tree is held, added to in place.

    **The balancing update moves ``params`` in place.** Where the
    configuration states an ``expert_bias_update_speed``, this step's one
    move that is no gradient's is made here, after the gradient: every
    sparse layer's ``expert_bias`` in the caller's tree moves by the speed
    times (1 - the expert's picks over the mean picks an expert) of the
    batch's positions, down where more than the mean picked it and up
    where fewer did (auxiliary-loss-free load balancing, Wang et al. 2024,
    arXiv 2408.15664, the proportional form). The harness's trajectory
    (``chipbench/check.py``) applies plain Adam to what this returns and
    knows no other update; the leaf's gradient is zero, so Adam leaves the
    moved leaf as it finds it."""
    tokens = jnp.asarray(features[0], jnp.int32)
    sizes = _Sizes(sizes)
    speed = sizes.get("expert_bias_update_speed", 0.0)
    total, grads, loads = None, {}, ({} if speed else None)
    for row in tokens:
        total = add_row(sizes, params, row, total, grads, loads)
    for i, picked in (loads or {}).items():
        even = tokens.size * sizes["num_experts_per_tok"] \
            / sizes["num_experts_routed"]
        layer = params[f"layer_{i}"]
        params[f"layer_{i}"] = dict(
            layer, expert_bias=layer["expert_bias"] + (
                speed * (1.0 - picked / even)).astype(
                    layer["expert_bias"].dtype))
    count = tokens.shape[0] * (tokens.shape[1] - 1)
    scale = jax.jit(lambda g: jax.tree.map(lambda x: x / count, g),
                    donate_argnums=(0,))
    return total / count, scale({k: grads[k] for k in params})


# -- operations and bytes of one train step, from the shapes ----------------------


def _layers(sizes, kind: str) -> int:
    return sum(k == kind for k in sizes["layer_types"])


def _sparse_layers(sizes) -> int:
    return sizes["num_hidden_layers"] - sizes["num_dense_layers"]


def param_count(sizes: Dict[str, Any]) -> int:
    """Of the layers ``layer_types`` names, the experts held and
    ``vocab_size`` rows of the tied embedding: this chip's cut, or the
    published model given its forty layers, 64 experts and 65,536 rows."""
    h, d = sizes["hidden_size"], sizes["head_dim"]
    conv = h + h * 3 * h + sizes["conv_L_cache"] * h + h * h
    attention = h + 2 * d + 2 * h * d * (sizes["num_attention_heads"]
                                         + sizes["num_key_value_heads"])
    dense = h + 3 * h * sizes["intermediate_size"]
    routed = sizes["num_experts_routed"]
    sparse = (h + h * routed + routed
              + sizes["num_experts"] * 3 * h * sizes["moe_intermediate_size"])
    return (sizes["vocab_size"] * h + h
            + _layers(sizes, CONV) * conv + _layers(sizes, FULL) * attention
            + sizes["num_dense_layers"] * dense
            + _sparse_layers(sizes) * sparse)


def _forward_flops_per_token(sizes) -> Dict[str, float]:
    """Forward matrix-multiply FLOPs a token, by part of the model."""
    h, d, s = sizes["hidden_size"], sizes["head_dim"], sizes["seq_len"]
    heads, kv_heads = (sizes["num_attention_heads"],
                       sizes["num_key_value_heads"])
    # of a token's picks, the share that falls on held experts
    held_picks = (sizes["num_experts_per_tok"] * sizes["num_experts"]
                  / sizes["num_experts_routed"])
    sparse = _sparse_layers(sizes)
    return {
        "conv_projections": _layers(sizes, CONV) * 2.0 * h * (3 * h + h),
        "projections": _layers(sizes, FULL) * 2.0 * h * d
        * (2 * heads + 2 * kv_heads),
        # two products over the keys a query sees: the triangle
        "attention": _layers(sizes, FULL) * 2 * 2.0 * d * heads
        * (s + 1) / 2,
        "dense": sizes["num_dense_layers"] * 3 * 2.0 * h
        * sizes["intermediate_size"],
        "experts": sparse * held_picks * 3 * 2.0 * h
        * sizes["moe_intermediate_size"],
        "router": sparse * 2.0 * h * sizes["num_experts_routed"],
        "head": 2.0 * h * sizes["vocab_size"] * (s - 1) / s,
    }


def train_flops_per_row(sizes: Dict[str, Any]) -> float:
    """Matrix-multiply FLOPs the forward and backward passes need for one
    row of ``seq_len`` tokens, times three (forward, and two products per
    matmul backward): the ``conv`` operators' two projections, the
    attention operator's projections and its two products over the
    triangle, the dense layer, the routed experts at the expected share of
    a token's picks that is held, the router, the tied head. The
    convolution itself and its gates are no product and are not counted;
    nor is recomputation."""
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


def sconv_work(sizes: Dict[str, Any], rows: int) -> Tuple[float, float]:
    """(FLOPs, least HBM bytes) of the ``conv`` operators' gates and
    convolution (not their projections) of one step of ``rows`` rows,
    forward and backward, whatever implements them: a token-channel's
    gate, taps and gate (``2 x taps + 1`` multiplies and adds), times
    three; by their bytes, which bound them: bf16 ``B | C | u`` read once
    and ``y`` written forward; those and ``dy`` read and ``d(B | C | u)``
    written backward; the taps' float32 sums. Nothing made again is
    counted (``ssm_work``'s convention)."""
    h, taps = sizes["hidden_size"], sizes["conv_L_cache"]
    layers = _layers(sizes, CONV)
    flops = 3.0 * rows * sizes["seq_len"] * layers * h * (2 * taps + 1)
    return flops, layers * (_stream_bytes(sizes, rows, h, 3 + 1 + 3 + 1 + 3)
                            + 4.0 * taps * h)


def proj_work(sizes: Dict[str, Any], rows: int) -> Tuple[float, float]:
    """(FLOPs, least HBM bytes) of the operators' projections (a ``conv``
    operator's ``W_in`` and ``W_out``, the attention operator's q, k, v
    and ``W_o``) of one step, forward and backward, whatever implements
    them: a product each, times three; their float32 weights read forward
    and backward and their gradients written; per product the bf16 input
    read and the output written forward (q, k and v read the one input),
    and as much again in gradients backward with both inputs read once
    more."""
    parts = _forward_flops_per_token(sizes)
    flops = 3.0 * rows * sizes["seq_len"] * (parts["conv_projections"]
                                             + parts["projections"])
    h, d = sizes["hidden_size"], sizes["head_dim"]
    heads, kv_heads = (sizes["num_attention_heads"],
                       sizes["num_key_value_heads"])
    conv, attention = _layers(sizes, CONV), _layers(sizes, FULL)
    weights = (conv * (h * 3 * h + h * h)
               + attention * h * d * (2 * heads + 2 * kv_heads))
    # what the products read and write forward, and of that what they read
    ends = (conv * (h + 3 * h + h + h)
            + attention * (2 * h + d * (2 * heads + 2 * kv_heads)))
    inputs = conv * (h + h) + attention * (h + d * heads)
    return flops, 3 * 4.0 * weights + _stream_bytes(
        sizes, rows, 2 * ends + inputs, 1)


def mlp_work(sizes: Dict[str, Any], rows: int) -> Tuple[float, float]:
    """(FLOPs, least HBM bytes) of the dense layer's MLP of one step,
    forward and backward, whatever implements it: three products, times
    three; its float32 weights read forward and backward and their
    gradients written; the bf16 tokens read and the result written
    forward, both read and the tokens' gradient written backward."""
    flops = 3.0 * rows * sizes["seq_len"] \
        * _forward_flops_per_token(sizes)["dense"]
    dense = sizes["num_dense_layers"]
    weights = dense * 3 * sizes["hidden_size"] * sizes["intermediate_size"]
    return flops, 3 * 4.0 * weights + dense * _stream_bytes(
        sizes, rows, sizes["hidden_size"], 5)


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
    sparse = _sparse_layers(sizes)
    weights = sparse * (
        sizes["num_experts"] * 3 * sizes["hidden_size"]
        * sizes["moe_intermediate_size"]
        + sizes["hidden_size"] * sizes["num_experts_routed"])
    return flops, 3 * 4.0 * weights + sparse * _stream_bytes(
        sizes, rows, sizes["hidden_size"], 5)


def attention_work(sizes: Dict[str, Any], rows: int) -> Tuple[float, float]:
    """(FLOPs, least HBM bytes) of the attention operator's attention
    (scores, softmax, weighted values; not its projections, nor the heads'
    norms and rotary) of one step, forward and backward: two products over
    the triangle, times three; bf16 q, k, v read and the output written
    forward, those and the output's gradient read and three gradients
    written backward."""
    flops = 3.0 * rows * sizes["seq_len"] \
        * _forward_flops_per_token(sizes)["attention"]
    widths = _layers(sizes, FULL) * sizes["head_dim"] * (
        sizes["num_attention_heads"] + sizes["num_key_value_heads"])
    return flops, _stream_bytes(sizes, rows, widths, 2 + 4)
