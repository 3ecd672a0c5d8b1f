"""Plain reference of the BERT-base masked-LM step the ``bert-base-mlm``
configuration trains: ``jax.numpy`` in float32, full attention written
out, no kernels. It imports nothing of the program and makes its own
weights from the seed.

Follows Devlin et al. 2018 as ``models/bert.py`` does, with that model's
departures: no segment embeddings and no next-sentence head (the rows are
single pre-tokenized sequences), post-layer-norm blocks with eps 1e-12,
the tanh form of GELU (as Google's released BERT code), an output
projection tied to the token embedding plus a bias, no padding mask (the
rows have no padding). Masking is dynamic: 15 % of the non-special
positions, of which 80 % become [MASK], 10 % a random token and 10 % stay,
drawn on the device from a key folded from the seed and the step number.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

IGNORE_ID = -100
MASK_ID = 3
NUM_SPECIAL = 4
ROW_BLOCK = 8      # rows per block of the reference's gradient


def init_params(sizes: Dict[str, Any], key) -> Dict[str, Any]:
    """Seeded float32 weights in the layout the trainer takes
    (N(0, 0.02) matrices, unit layer-norm scales, zero biases)."""
    h, f = sizes["hidden_dim"], sizes["ffn_dim"]
    layers = sizes["num_layers"]
    keys = iter(jax.random.split(key, 2 + 4 * layers))

    def normal(shape):
        return 0.02 * jax.random.normal(next(keys), shape, jnp.float32)

    def ln():
        return {"scale": jnp.ones((h,), jnp.float32),
                "bias": jnp.zeros((h,), jnp.float32)}

    params: Dict[str, Any] = {
        "token_emb": normal((sizes["vocab_size"], h)),
        "pos_emb": normal((sizes["max_seq_len"], h)),
        "emb_ln": ln(),
        "mlm_bias": jnp.zeros((sizes["vocab_size"],), jnp.float32),
    }
    for layer in range(layers):
        params[f"layer_{layer}"] = {
            "qkv_w": normal((h, 3 * h)),
            "qkv_b": jnp.zeros((3 * h,), jnp.float32),
            "attn_out_w": normal((h, h)),
            "attn_out_b": jnp.zeros((h,), jnp.float32),
            "ln1": ln(),
            "ffn_in_w": normal((h, f)),
            "ffn_in_b": jnp.zeros((f,), jnp.float32),
            "ffn_out_w": normal((f, h)),
            "ffn_out_b": jnp.zeros((h,), jnp.float32),
            "ln2": ln(),
        }
    return params


def step_key(seed_key, step):
    return jax.random.fold_in(seed_key, step)


def mlm_mask(tokens, key, vocab_size: int, mask_prob: float):
    """(inputs, targets): the BERT 80/10/10 rule, ``targets`` holding the
    original id at selected positions and ``IGNORE_ID`` elsewhere. The same
    draws, in the same order, as ``workloads/bert_mlm.mlm_mask`` makes."""
    select_key, action_key, random_key = jax.random.split(key, 3)
    selected = ((jax.random.uniform(select_key, tokens.shape) < mask_prob)
                & (tokens >= NUM_SPECIAL))
    action = jax.random.uniform(action_key, tokens.shape)
    random_tokens = jax.random.randint(random_key, tokens.shape, NUM_SPECIAL,
                                       vocab_size, dtype=tokens.dtype)
    inputs = jnp.where(selected & (action < 0.8), MASK_ID,
                       jnp.where(selected & (action >= 0.9), random_tokens,
                                 tokens))
    return inputs, jnp.where(selected, tokens, IGNORE_ID)


def _layer_norm(x, p):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + 1e-12) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def logits(sizes: Dict[str, Any], params: Dict[str, Any], token_ids):
    b, s = token_ids.shape
    heads = sizes["num_heads"]
    hd = sizes["hidden_dim"] // heads
    x = jnp.take(params["token_emb"], token_ids, axis=0) \
        + params["pos_emb"][:s][None]
    x = _layer_norm(x, params["emb_ln"])
    for layer in range(sizes["num_layers"]):
        p = params[f"layer_{layer}"]
        q, k, v = jnp.split(x @ p["qkv_w"] + p["qkv_b"], 3, axis=-1)
        q, k, v = (t.reshape(b, s, heads, hd).transpose(0, 2, 1, 3)
                   for t in (q, k, v))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
            jnp.float32(hd))
        attended = jnp.einsum("bhqk,bhkd->bhqd",
                              jax.nn.softmax(scores, axis=-1), v)
        attended = attended.transpose(0, 2, 1, 3).reshape(b, s, -1)
        x = _layer_norm(x + attended @ p["attn_out_w"] + p["attn_out_b"],
                        p["ln1"])
        ffn = _gelu(x @ p["ffn_in_w"] + p["ffn_in_b"]) @ p["ffn_out_w"] \
            + p["ffn_out_b"]
        x = _layer_norm(x + ffn, p["ln2"])
    return jnp.einsum("bsh,vh->bsv", x, params["token_emb"]) \
        + params["mlm_bias"]


def _nll_sum(sizes, params, inputs, targets):
    """Sum over masked positions of -log p(target)."""
    mask = targets != IGNORE_ID
    logp = jax.nn.log_softmax(logits(sizes, params, inputs), axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.where(mask, targets, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(mask, -picked, 0.0))


def value_and_grad(sizes: Dict[str, Any], params: Dict[str, Any],
                   features: Sequence[Any], labels: Any, step: int,
                   seed_key=None) -> Tuple[jax.Array, Dict[str, Any]]:
    """Loss (mean over the batch's masked positions) and its gradient,
    gathered in blocks of ``ROW_BLOCK`` rows so that float32 activations
    of the whole batch never sit on the device at once."""
    tokens = jnp.asarray(features[0], jnp.int32)
    inputs, targets = mlm_mask(tokens, step_key(seed_key, step),
                               sizes["vocab_size"], sizes["mask_prob"])
    count = jnp.maximum(jnp.sum(targets != IGNORE_ID), 1)
    block = jax.jit(jax.value_and_grad(
        lambda p, i, t: _nll_sum(sizes, p, i, t)))
    total, grads = None, None
    for lo in range(0, tokens.shape[0], ROW_BLOCK):
        value, g = block(params, inputs[lo:lo + ROW_BLOCK],
                         targets[lo:lo + ROW_BLOCK])
        total = value if total is None else total + value
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return total / count, jax.tree.map(lambda g: g / count, grads)


# -- the whole model is followed: nothing to cut ----------------------------------

def touched_rows(sizes, batches):
    return None


def take_rows(params, rows):
    return params


def remap(features, rows):
    return list(features)


# -- operations and bytes of one train step, from the shapes ----------------------

def param_count(sizes: Dict[str, Any]) -> int:
    h, f, v = sizes["hidden_dim"], sizes["ffn_dim"], sizes["vocab_size"]
    per_layer = (h * 3 * h + 3 * h) + (h * h + h) + (h * f + f) \
        + (f * h + h) + 4 * h
    return v * h + sizes["max_seq_len"] * h + 2 * h + v \
        + sizes["num_layers"] * per_layer


def train_flops_per_row(sizes: Dict[str, Any]) -> float:
    """Matrix-multiply FLOPs the forward and backward passes need for one
    row of ``seq_len`` tokens: the four projections and two FFN matmuls of
    every layer, attention's two batched products, and the vocabulary
    projection, times three (forward, and two products per matmul
    backward). Recomputation is not counted; the model does none."""
    h, f, s = sizes["hidden_dim"], sizes["ffn_dim"], sizes["seq_len"]
    per_token_layer = 2.0 * (h * 3 * h + h * h + 2 * h * f) + 2.0 * 2 * s * h
    per_token = sizes["num_layers"] * per_token_layer \
        + 2.0 * h * sizes["vocab_size"]
    return 3.0 * s * per_token


def train_step_bytes(sizes: Dict[str, Any], rows: int) -> float:
    """HBM bytes one step cannot avoid: dense Adam's 28 bytes a float32
    parameter, plus each row's bf16 residual stream written and read once
    per layer forward and backward. A floor: the step is bound by FLOPs."""
    adam = 28.0 * param_count(sizes)
    stream = 4.0 * 2.0 * rows * sizes["seq_len"] * sizes["hidden_dim"] \
        * sizes["num_layers"]
    return adam + stream
