"""Plain reference of the step the ``sdar-30b-a3b-ep8`` configuration
trains: one chip's share of an SDAR-30B-A3B-style sparse-expert decoder
under the block-diffusion objective, in ``jax.numpy`` and float32, the
mask, the attention and the expert layer written out, no kernels. It
imports nothing of the program and makes its own weights and its own noise
from the seed; what it shares with the other decoders' references is
``chipbench/references/mellum.py``'s plain helpers (RMSNorm, rotate-half,
the gradient tree's in-place sum, the hashable sizes).

**The layer** is the Qwen3-MoE layer ``sdar_moe`` is derived from (each
assumption is under ``assumed`` in the configuration file). On the
residual stream ``x``: ``a = RMSNorm(x)``; ``q = a W_q``
(``num_attention_heads`` heads of ``head_dim``), ``k = a W_k``, ``v = a
W_v`` (``num_key_value_heads``), no biases; RMSNorm over each q head and
each k head, one scale of ``head_dim`` for q and one for k; plain
rotate-half rotary over the whole head at ``rope_theta``, no scaling;
softmax at ``1 / sqrt(head_dim)`` under the mask below, query head h
reading key/value head h // group; ``x += (P v) W_o``. Then ``b =
RMSNorm(x)``; ``p = softmax(b W_r)`` over all ``num_experts_routed``
experts; S = the ``num_experts_per_tok`` largest; ``w_e = p_e / sum_S p``
(``norm_topk_prob``); ``x += sum over e in S that are HELD of w_e (silu(b
G_e) * (b U_e)) D_e``. **This chip's share**: the sum runs over the
``num_experts`` experts held from ``experts_held_first`` on; what the
absent experts would add is left out, and that partial result goes on to
the next layer; attention, norms and router are whole. Where
``router_trains`` is false the router's matrix takes no gradient.

**The objective** is block diffusion's (Arriola et al. 2025, BD3-LMs,
arXiv 2503.09573, whose vectorised training SDAR, arXiv 2510.06303, takes
over). A row ``x_0`` of L = ``seq_len`` tokens is cut into blocks of
``block_length``; each block b draws a noise level ``t_b ~ U[0, 1)`` and
each of its tokens is replaced by ``mask_token_id`` independently with
probability ``p_b = (1 - noise_eps) t_b + noise_eps`` (:func:`noise`, from
``fold_in(seed_key, step)``). The model reads the 2 L positions ``[x_t ;
x_0]``, the noised copy first as published, both copies at rotary
positions 0..L-1, under one mask (:func:`block_diffusion_mask`): a noised
query sees its own noised block, both directions, and the clean blocks
strictly before it; a clean query sees the clean blocks up to and with its
own; nothing clean sees anything noised. After the last layer RMSNorm and
the untied head run on the noised copy; the loss is ``sum over the masked
positions i of -log p(x_0[i] | .) / p_block(i)`` from the logits at the
same position (no shift), over the vocabulary slice, divided by the batch's
``rows x L`` tokens.

**Departures.** The program puts the clean copy first; the result does
not depend on the order, and this file keeps the published one. The mask
token's embedding row is drawn at ``MASK_ROW_STD`` (the configuration
file's ``assumed.mask_row``).

``value_and_grad`` goes a row at a time and a layer at a time (each
layer's input kept, its activations made again in the backward pass),
attention a head and ``QUERY_BLOCK`` query positions at a time (a head's
whole 16,384 x 16,384 float32 scores are 1.07 GB; 4,096 queries' are 268
MB), the held experts a scan of dense products over every position under
the routing's weights, the head's logits in blocks of positions, so that
its float32 activations fit beside 16 bytes a parameter. On a TPU a
float32 product runs in bfloat16 passes unless
``jax.default_matmul_precision("highest")`` is set: the harness's
trajectory (``chipbench/check.py:reference_trajectory``) sets it around
every call it makes here, and a caller of its own has to.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from chipbench.references.mellum import (HEAD_BLOCK, _Sizes, _add,
                                         _rms_norm, _rotate)
# The whole model is followed, nothing to cut: the harness finds these here.
from chipbench.references.mellum import (  # noqa: F401
    remap, take_rows, touched_rows)

#: Query positions in one block of a head's attention.
QUERY_BLOCK = 4096
#: The standard deviation of the seeded embedding row of the mask token
#: (the configuration file's ``assumed.mask_row`` says why it is small).
MASK_ROW_STD = 1e-4


def init_params(sizes: Dict[str, Any], key) -> Dict[str, Any]:
    """Seeded float32 weights in the layout the trainer takes: the
    embedding N(0, 1) (``mellum2-12b-a2.5b-ep4``'s untied one: a token's
    own row outweighs what attention writes, so unmasked tokens route by
    what they are) but for the mask token's row, N(0, ``MASK_ROW_STD``);
    matrices, the head and the router among them, N(0, 0.02); what writes
    into the residual stream (``wo``, every ``down``) N(0, 0.02 / sqrt(2 x
    the published depth)); unit norm scales, the q and k heads' among
    them."""
    h, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    d = sizes["head_dim"]
    q_width = sizes["num_attention_heads"] * d
    kv_width = sizes["num_key_value_heads"] * d
    held, layers = sizes["num_experts"], sizes["num_hidden_layers"]
    residual = 0.02 / math.sqrt(2 * sizes["published"]["num_hidden_layers"])
    keys = iter(jax.random.split(key, 2 + 8 * layers))

    def normal(shape, std=0.02):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    embed = normal((sizes["vocab_size"], h), 1.0)
    params: Dict[str, Any] = {
        "embed": embed.at[sizes["mask_token_id"]].multiply(MASK_ROW_STD),
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
            "q_layernorm": jnp.ones((d,), jnp.float32),
            "k_layernorm": jnp.ones((d,), jnp.float32),
            "moe_norm": jnp.ones((h,), jnp.float32),
            "router": normal((h, sizes["num_experts_routed"])),
            "gate": normal((held, h, f)),
            "up": normal((held, h, f)),
            "down": normal((held, f, h), residual),
        }
    return params


# -- the forward process and the mask -----------------------------------------


def step_key(seed_key, step):
    return jax.random.fold_in(seed_key, step)


def noise(tokens, key, block: int, mask_id: int, eps: float):
    """``(noised, masked, weights)`` of the rows ``tokens`` (B, L): per row
    and per block of ``block`` tokens a level ``t ~ U[0, 1)``, ``p = (1 -
    eps) t + eps``; each token masked independently with its block's
    ``p``; ``noised`` the rows with the masked tokens replaced by
    ``mask_id``, ``weights`` (B, L) float32 each position's ``1 / p``. The
    same draws, in the same order, as the program's
    ``models/mellum.py:diffusion_noise`` makes."""
    rows, length = tokens.shape
    level_key, mask_key = jax.random.split(key)
    level = jax.random.uniform(level_key, (rows, length // block),
                               jnp.float32)
    prob = jnp.repeat((1.0 - eps) * level + eps, block, axis=1)
    masked = jax.random.uniform(mask_key, (rows, length), jnp.float32) < prob
    return jnp.where(masked, mask_id, tokens), masked, 1.0 / prob


# Jitted, as the program's draw is: the two then round alike.
_noise = jax.jit(noise, static_argnums=(2, 3, 4))


def block_diffusion_mask(length: int, block: int):
    """(2 L, 2 L) booleans, ``[i, j]``: query i sees key j, over the
    published row ``[x_t ; x_0]``, from BD3-LMs' three parts: the
    block-diagonal mask (a position sees its own block of its own copy),
    the offset block-causal mask (a noised query sees the clean blocks
    strictly before its own) and the block-causal mask (a clean query sees
    the clean blocks up to and with its own)."""
    index = jnp.arange(2 * length)
    clean = index >= length
    blk = jnp.where(clean, index - length, index) // block
    q_clean, k_clean = clean[:, None], clean[None, :]
    q_blk, k_blk = blk[:, None], blk[None, :]
    block_diagonal = (q_blk == k_blk) & (q_clean == k_clean)
    offset_block_causal = (q_blk > k_blk) & k_clean & ~q_clean
    block_causal = (q_blk >= k_blk) & k_clean & q_clean
    return block_diagonal | offset_block_causal | block_causal


# -- one row through one layer ------------------------------------------------


def _attention(q, k, v, seen):
    """q (S, H, D), k and v (S, Hkv, D), ``seen`` (S, S) -> (S, H, D), a
    head and a block of query positions at a time, each made again in the
    backward pass."""
    s, heads, d = q.shape
    group = heads // k.shape[1]
    rows = min(QUERY_BLOCK, s)
    if s % rows:
        raise ValueError(f"{s} positions are not whole blocks of {rows}")
    blocks = s // rows

    @jax.checkpoint
    def one_block(index):
        head, start = index // blocks, (index % blocks) * rows
        queries = jax.lax.dynamic_slice_in_dim(q[:, head], start, rows)
        scores = queries @ k[:, head // group].T / math.sqrt(d)
        weights = jax.nn.softmax(jnp.where(
            jax.lax.dynamic_slice_in_dim(seen, start, rows), scores,
            -jnp.inf), axis=-1)
        return weights @ v[:, head // group]

    out = jax.lax.map(one_block, jnp.arange(heads * blocks))
    return out.reshape(heads, s, d).transpose(1, 0, 2)


def route(sizes, x, p):
    """``(ids (S, top_k), weights (S, top_k))`` of x (S, h): softmax over
    all the router's experts, the ``num_experts_per_tok`` largest, their
    probabilities over their sum (``norm_topk_prob``)."""
    router = (p["router"] if sizes.get("router_trains", True)
              else jax.lax.stop_gradient(p["router"]))
    probs = jax.nn.softmax(x @ router, axis=-1)
    top, ids = jax.lax.top_k(probs, sizes["num_experts_per_tok"])
    if sizes["norm_topk_prob"]:
        top = top / top.sum(axis=-1, keepdims=True)
    return ids, top


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
        return out + weight * ((jax.nn.silu(x @ gate) * (x @ up)) @ down), \
            None

    return jax.lax.scan(
        add_expert, jnp.zeros_like(x),
        (first + jnp.arange(gates.shape[0]), gates, ups, downs))[0]


def layer(sizes, p, x, seen):
    """x (2 L, h) -> x (2 L, h) under the mask ``seen``; position i of
    either copy stands at rotary position ``i mod L``."""
    if sizes.get("rope_scaling") is not None:
        raise ValueError("the reference knows plain rotary only")
    s, d = x.shape[0], sizes["head_dim"]
    heads, kv_heads = (sizes["num_attention_heads"],
                       sizes["num_key_value_heads"])
    freqs = 1.0 / sizes["rope_theta"] ** (jnp.arange(0, d, 2) / d)
    angles = (jnp.arange(s) % (s // 2))[:, None] * freqs
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    eps = sizes["rms_norm_eps"]
    a = _rms_norm(x, p["attn_norm"], eps)
    q = _rms_norm((a @ p["wq"]).reshape(s, heads, d), p["q_layernorm"], eps)
    k = _rms_norm((a @ p["wk"]).reshape(s, kv_heads, d), p["k_layernorm"],
                  eps)
    v = (a @ p["wv"]).reshape(s, kv_heads, d)
    x = x + _attention(_rotate(q, cos, sin), _rotate(k, cos, sin), v,
                       seen).reshape(s, -1) @ p["wo"]
    return x + routed(sizes, _rms_norm(x, p["moe_norm"], eps), p,
                      sizes["experts_held_first"],
                      (p["gate"], p["up"], p["down"]))


def _head_nll(sizes, scale, head, x, targets, weights):
    """``sum_i weights_i x -log p(targets_i)`` of the positions x (n, h):
    ``weights`` is ``1 / p`` at a masked position and 0 elsewhere."""
    logits = _rms_norm(x, scale, sizes["rms_norm_eps"]) @ head
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(weights * jnp.take_along_axis(
        logp, targets[:, None], axis=-1)[:, 0])


@functools.partial(jax.jit, static_argnums=(0,))
def _layer_forward(sizes, p, x, seen):
    return layer(sizes, p, x, seen)


@functools.partial(jax.jit, static_argnums=(0,))
def _layer_backward(sizes, p, x, seen, dy):
    _, vjp = jax.vjp(lambda p, x: layer(sizes, p, x, seen), p, x)
    return vjp(dy)


@functools.partial(jax.jit, static_argnums=(0,))
def _head_block(sizes, scale, head, x, targets, weights):
    return jax.value_and_grad(functools.partial(_head_nll, sizes),
                              argnums=(0, 1, 2))(scale, head, x, targets,
                                                 weights)


_mask = functools.lru_cache(maxsize=2)(jax.jit(
    block_diffusion_mask, static_argnums=(0, 1)))


def add_row(sizes, params, clean, noised, weights, total, grads):
    """One row: ``clean`` and ``noised`` (L,) token ids, ``weights`` (L,)
    ``1 / p`` at its masked positions and 0 elsewhere. Its summed loss is
    added to ``total`` and its gradient to the tree ``grads``, a layer's
    share at a time."""
    length = clean.shape[0]
    seen = _mask(length, sizes["block_length"])
    both = jnp.concatenate([noised, clean])
    x = params["embed"][both]
    inputs = []
    for i in range(sizes["num_hidden_layers"]):
        inputs.append(x)
        x = _layer_forward(sizes, params[f"layer_{i}"], x, seen)
    d_x = []
    for lo in range(0, length, HEAD_BLOCK):
        hi = min(lo + HEAD_BLOCK, length)
        value, (ds, dh, dx) = _head_block(
            sizes, params["final_norm"], params["head"], x[lo:hi],
            clean[lo:hi], weights[lo:hi])
        total = _add(total, value)
        grads["final_norm"] = _add(grads.get("final_norm"), ds)
        grads["head"] = _add(grads.get("head"), dh)
        d_x.append(dx)
    # the clean copy's last hidden states feed nothing
    dy = jnp.concatenate(d_x + [jnp.zeros_like(x[length:])], axis=0)
    for i in reversed(range(sizes["num_hidden_layers"])):
        d_layer, dy = _layer_backward(
            sizes, params[f"layer_{i}"], inputs.pop(), seen, dy)
        grads[f"layer_{i}"] = _add(grads.get(f"layer_{i}"), d_layer)
    grads["embed"] = _add(
        grads.get("embed"),
        jnp.zeros_like(params["embed"]).at[both].add(dy))
    return total


def value_and_grad(sizes: Dict[str, Any], params: Dict[str, Any],
                   features: Sequence[Any], labels: Any, step: int,
                   seed_key=None) -> Tuple[jax.Array, Dict[str, Any]]:
    """Block diffusion's loss over the batch's rows and its gradient, the
    noise drawn from ``fold_in(seed_key, step)`` for the whole batch as
    the program draws it; then a row at a time, one gradient tree held
    and added to in place."""
    tokens = jnp.asarray(features[0], jnp.int32)
    sizes = _Sizes(sizes)
    noised, masked, weights = _noise(
        tokens, step_key(seed_key, step), sizes["block_length"],
        sizes["mask_token_id"], sizes["noise_eps"])
    weights = jnp.where(masked, weights, 0.0)
    total, grads = None, {}
    for row in range(tokens.shape[0]):
        total = add_row(sizes, params, tokens[row], noised[row],
                        weights[row], total, grads)
    count = tokens.shape[0] * tokens.shape[1]
    scale = jax.jit(lambda g: jax.tree.map(lambda x: x / count, g),
                    donate_argnums=(0,))
    return total / count, scale({k: grads[k] for k in params})


# -- operations and bytes of one train step, from the shapes ------------------


def param_count(sizes: Dict[str, Any]) -> int:
    """Of ``num_hidden_layers`` layers, the experts held and ``vocab_size``
    rows of the embedding and of the untied head: this chip's cut, or the
    published model given its 48 layers, 128 experts and 151,936 rows."""
    h, d = sizes["hidden_size"], sizes["head_dim"]
    attention = 2 * h * d * (sizes["num_attention_heads"]
                             + sizes["num_key_value_heads"])
    per_layer = (attention + 2 * h + 2 * d
                 + h * sizes["num_experts_routed"]
                 + sizes["num_experts"] * 3 * h
                 * sizes["moe_intermediate_size"])
    return (2 * sizes["vocab_size"] * h + h
            + sizes["num_hidden_layers"] * per_layer)


def live_pairs(sizes: Dict[str, Any]) -> int:
    """Query-key pairs the mask lets through, a head a row: the clean
    copy's blocks up to and with a clean query's own (``(L^2 + B L) / 2``),
    the clean blocks before a noised query's (``(L^2 - B L) / 2``) and the
    noised copy's own blocks (``B L``): ``L^2 + B L``."""
    length = sizes["seq_len"]
    return length * (length + sizes["block_length"])


def _forward_flops_per_row(sizes) -> Dict[str, float]:
    """Forward matrix-multiply FLOPs one row of ``seq_len`` tokens needs,
    by part of the model: everything but the head runs over the row's 2 L
    positions, the head over its masked positions, L x (1 + noise_eps) / 2
    in expectation (the mean of p over ``t ~ U[0, 1)``)."""
    h, f, d = (sizes["hidden_size"], sizes["moe_intermediate_size"],
               sizes["head_dim"])
    heads, kv_heads = (sizes["num_attention_heads"],
                       sizes["num_key_value_heads"])
    layers, positions = sizes["num_hidden_layers"], 2 * sizes["seq_len"]
    # of a position's picks, the share that falls on held experts
    held_picks = (sizes["num_experts_per_tok"] * sizes["num_experts"]
                  / sizes["num_experts_routed"])
    return {
        "projections": layers * positions * 2.0 * h * d
        * (2 * heads + 2 * kv_heads),
        # two products a live pair
        "attention": layers * 2 * 2.0 * d * heads * live_pairs(sizes),
        "experts": layers * positions * held_picks * 3 * 2.0 * h * f,
        "router": layers * positions * 2.0 * h * sizes["num_experts_routed"],
        "head": sizes["seq_len"] * (1.0 + sizes["noise_eps"]) / 2
        * 2.0 * h * sizes["vocab_size"],
    }


def train_flops_per_row(sizes: Dict[str, Any]) -> float:
    """Matrix-multiply FLOPs the forward and backward passes need for one
    row of ``seq_len`` tokens, times three (forward, and two products per
    matmul backward): the projections, the router and the held experts (at
    the expected share of a position's picks that is held) over the row's
    2 L positions, attention's two products over the pairs the mask lets
    through, the head over the masked positions alone, L x (1 +
    noise_eps) / 2 in expectation (what the loss needs, whatever the
    program projects).
    Recomputation is not counted."""
    return 3.0 * sum(_forward_flops_per_row(sizes).values())


def _stream_bytes(sizes, rows: int, width: int, passes: float) -> float:
    """bf16 bytes of ``passes`` passes over ``rows`` rows' 2 L positions at
    ``width`` values a position, every layer."""
    return passes * 2.0 * rows * 2 * sizes["seq_len"] * width \
        * sizes["num_hidden_layers"]


def train_step_bytes(sizes: Dict[str, Any], rows: int) -> float:
    """HBM bytes one step cannot avoid: dense Adam's 28 bytes a float32
    parameter, plus each row's bf16 residual stream written and read once
    per layer forward and backward. A floor: the step is bound by FLOPs."""
    return 28.0 * param_count(sizes) + _stream_bytes(
        sizes, rows, sizes["hidden_size"], 4)


def attention_work(sizes: Dict[str, Any], rows: int) -> Tuple[float, float]:
    """(FLOPs, least HBM bytes) of the layers' attention (scores, softmax,
    weighted values; not the projections, nor the heads' norms and rotary)
    of one step, forward and backward, whatever tiles or kernels cover the
    mask: two products over the pairs it lets through (:func:`live_pairs`),
    times three; bf16 q, k, v read and the output written forward, those
    and the output's gradient read and three gradients written backward."""
    flops = 3.0 * rows * _forward_flops_per_row(sizes)["attention"]
    d = sizes["head_dim"]
    return flops, _stream_bytes(
        sizes, rows, d * (sizes["num_attention_heads"]
                          + sizes["num_key_value_heads"]), 2 + 4)


def moe_work(sizes: Dict[str, Any], rows: int) -> Tuple[float, float]:
    """(FLOPs, least HBM bytes) of the expert layers of one step of
    ``rows`` rows, forward and backward, whatever implements them: the
    held picks' three products and the router, times three; the held
    experts' and the router's float32 weights read forward and backward
    and their gradients written, the bf16 positions read and the sum
    written forward, both read and the positions' gradient written
    backward."""
    parts = _forward_flops_per_row(sizes)
    flops = 3.0 * rows * (parts["experts"] + parts["router"])
    weights = sizes["num_hidden_layers"] * (
        sizes["num_experts"] * 3 * sizes["hidden_size"]
        * sizes["moe_intermediate_size"]
        + sizes["hidden_size"] * sizes["num_experts_routed"])
    return flops, 3 * 4.0 * weights + _stream_bytes(
        sizes, rows, sizes["hidden_size"], 5)


def proj_work(sizes: Dict[str, Any], rows: int) -> Tuple[float, float]:
    """(FLOPs, least HBM bytes) of the layers' projections (q, k, v and
    ``W_o``) of one step, forward and backward, whatever implements them:
    a product each, times three; their float32 weights read forward and
    backward and their gradients written; per product the bf16 input read
    and the output written forward (q, k and v read the one input), and as
    much again in gradients backward with both inputs read once more."""
    flops = 3.0 * rows * _forward_flops_per_row(sizes)["projections"]
    h, d = sizes["hidden_size"], sizes["head_dim"]
    heads, kv_heads = (sizes["num_attention_heads"],
                       sizes["num_key_value_heads"])
    weights = sizes["num_hidden_layers"] * h * d * (2 * heads + 2 * kv_heads)
    # what the products read and write forward, and of that what they read
    ends = 2 * h + d * (2 * heads + 2 * kv_heads)
    inputs = h + d * heads
    return flops, 3 * 4.0 * weights + _stream_bytes(
        sizes, rows, 2 * ends + inputs, 1)
