"""Plain reference of the DLRM the ``dlrm-mlperf`` configuration trains:
``jax.numpy`` in float32, ``take`` lookups, no kernels, no sharding. It
imports nothing of the program and makes its own weights from the seed.

The model, as the repo defines it (``models/dlrm.py``), and where that
departs from MLPerf's DLRM-v2: the schema has no dense features, so there
is no bottom MLP; the top MLP sees the upper triangle of the embeddings'
Gram matrix and, in place of the bottom MLP's output, the mean embedding.
The loss is sigmoid cross-entropy against the label column, averaged over
the batch.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _top_dims(sizes: Dict[str, Any]) -> Tuple[int, ...]:
    n = len(sizes["vocab_sizes"])
    top_in = n * (n - 1) // 2 + sizes["embed_dim"]
    return (top_in, *sizes["top_hidden"], 1)


def init_params(sizes: Dict[str, Any], key) -> Dict[str, Any]:
    """Seeded float32 weights in the layout the trainer takes: embedding
    rows N(0, 1/embed_dim), MLP weights He-normal, biases zero. Traceable:
    the harness makes all of them on the device in one jitted call."""
    vocab, d = sizes["vocab_sizes"], sizes["embed_dim"]
    keys = jax.random.split(key, len(vocab) + 1)
    params: Dict[str, Any] = {"embeddings": {}, "top": {}}
    for i, rows in enumerate(vocab):
        params["embeddings"][f"table_{i}"] = (
            jax.random.normal(keys[i], (rows, d), jnp.float32)
            / jnp.sqrt(jnp.float32(d)))
    dims = _top_dims(sizes)
    mlp_keys = jax.random.split(keys[-1], len(dims) - 1)
    for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
        params["top"][f"w{i}"] = (
            jax.random.normal(mlp_keys[i], (d_in, d_out), jnp.float32)
            * jnp.sqrt(jnp.float32(2.0 / d_in)))
        params["top"][f"b{i}"] = jnp.zeros((d_out,), jnp.float32)
    return params


def loss(sizes: Dict[str, Any], params: Dict[str, Any],
         features: Sequence[Any], labels: Any, step: int = 0) -> jax.Array:
    vectors = [jnp.take(params["embeddings"][f"table_{i}"],
                        jnp.asarray(col).reshape(-1).astype(jnp.int32),
                        axis=0)
               for i, col in enumerate(features)]
    stacked = jnp.stack(vectors, axis=1)                 # (rows, F, d)
    gram = jnp.einsum("bfe,bge->bfg", stacked, stacked)
    iu, ju = np.triu_indices(stacked.shape[1], k=1)
    x = jnp.concatenate([gram[:, iu, ju], stacked.mean(axis=1)], axis=1)
    layers = len(_top_dims(sizes)) - 1
    for i in range(layers):
        x = x @ params["top"][f"w{i}"] + params["top"][f"b{i}"]
        if i < layers - 1:
            x = jnp.maximum(x, 0.0)
    y = jnp.asarray(labels, jnp.float32).reshape(-1, 1)
    return jnp.mean(jnp.maximum(x, 0) - x * y
                    + jnp.log1p(jnp.exp(-jnp.abs(x))))


def value_and_grad(sizes, params, features, labels, step: int = 0,
                   seed_key=None):
    """Loss and gradient of one batch (``step`` and ``seed_key`` are for
    models that draw per step; this one does not)."""
    return jax.jit(jax.value_and_grad(
        lambda p, f, y: loss(sizes, p, f, y)))(params, list(features), labels)


# -- the share of the tables three steps can touch -----------------------------
#
# Dense Adam leaves a row whose gradient has always been zero exactly where
# it was (its moments stay zero, so its update is 0 / (0 + eps)). The
# reference therefore follows only the rows that the compared steps look
# up: same losses, same gradient norms, same norms of the parameters'
# change, at a few MB instead of four copies of 1.49 GB, which would raise
# the device's memory peak above the program's own.

def touched_rows(sizes: Dict[str, Any],
                 batches: Sequence[Tuple[Sequence[Any], Any]]
                 ) -> List[np.ndarray]:
    """Per table, sorted distinct rows that cover every row the batches
    look up, always ``min(vocab, rows in the batches)`` of them: where
    fewer are looked up, rows nobody looks up fill the list (their
    gradient and their change are zero on both sides, so no norm moves).
    The fixed length keeps the reference's programs the same from seed to
    seed, so that they are compiled once and found in the cache after."""
    looked_up = sum(np.asarray(labels).shape[0] for _, labels in batches)
    out = []
    for i, vocab in enumerate(sizes["vocab_sizes"]):
        rows = np.unique(np.concatenate(
            [np.asarray(features[i]).reshape(-1) for features, _ in batches]))
        want = min(vocab, looked_up)
        if len(rows) < want:
            spare = np.setdiff1d(np.arange(min(vocab, 2 * want)), rows)
            rows = np.sort(np.concatenate([rows, spare[:want - len(rows)]]))
        out.append(rows.astype(np.int32))
    return out


def take_rows(params: Dict[str, Any], rows: Sequence[np.ndarray]
              ) -> Dict[str, Any]:
    """``params`` with every table cut to ``rows``; traceable."""
    small = {"embeddings": {}, "top": dict(params["top"])}
    for i, idx in enumerate(rows):
        small["embeddings"][f"table_{i}"] = jnp.take(
            params["embeddings"][f"table_{i}"], jnp.asarray(idx), axis=0)
    return small


def remap(features: Sequence[Any], rows: Sequence[np.ndarray]
          ) -> List[np.ndarray]:
    """Lookup indices into the cut tables."""
    return [np.searchsorted(idx, np.asarray(col).reshape(-1))
            .astype(np.int32) for col, idx in zip(features, rows)]


# -- operations and bytes of one train step, from the shapes ---------------------

def train_flops_per_row(sizes: Dict[str, Any]) -> float:
    """Matrix-multiply FLOPs the forward and backward passes need per row:
    the pairwise interaction and the top MLP, times three (forward, and
    two matmuls per layer backward). Lookups and the optimizer move bytes
    and are not counted, and neither are one-hot-matmul lookups, which are
    a way of doing a lookup. Copied from ``bench._train_flops_per_row``."""
    f, d = len(sizes["vocab_sizes"]), sizes["embed_dim"]
    dims = _top_dims(sizes)
    mlp = sum(2.0 * a * b for a, b in zip(dims[:-1], dims[1:]))
    return 3.0 * (2.0 * f * f * d + mlp)


def param_count(sizes: Dict[str, Any]) -> int:
    dims = _top_dims(sizes)
    return (sum(sizes["vocab_sizes"]) * sizes["embed_dim"]
            + sum(a * b + b for a, b in zip(dims[:-1], dims[1:])))


def train_step_bytes(sizes: Dict[str, Any], rows: int) -> float:
    """HBM bytes one step cannot avoid with the optimizer the
    configuration states. Dense Adam over float32 parameters reads the
    parameter, both moments and the gradient and writes the parameter and
    both moments: 7 x 4 bytes a parameter, whatever the batch. The batch's
    own traffic (``rows`` lookups of ``embed_dim`` floats per table, read
    forward and added backward) is counted too and is small beside it."""
    dense_adam = 28.0 * param_count(sizes)
    lookups = 3.0 * 4.0 * rows * len(sizes["vocab_sizes"]) * sizes["embed_dim"]
    return dense_adam + lookups
