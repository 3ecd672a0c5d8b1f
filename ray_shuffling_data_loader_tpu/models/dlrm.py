"""DLRM-style recommendation model — the flagship (BASELINE.json config 5).

The reference's data is DLRM-shaped (17 embedding-index columns with
cardinalities up to ~945k, 2 small categorical columns, a float label —
reference: data_generation.py:74-95) but it never ships a real model; its
example trainer mocks the step entirely (reference:
ray_torch_shuffle.py:199-204). We provide the real thing, TPU-first:

- per-feature embedding tables with the embedding dim sharded over the
  "model" mesh axis (Megatron column-parallel embeddings: every device
  holds ``embed_dim / model_parallel`` of each table, lookups are local,
  XLA all-gathers the slices — divisibility only constrains embed_dim,
  never the ragged vocab sizes);
- dot-product feature interaction (upper triangle), the DLRM signature;
- bottom/top MLPs with the same alternating column/row TP sharding as
  models/mlp.py;
- bf16 compute, f32 params, one static XLA graph.

Functional API: ``init(config, key)``, ``apply(config, params, dense,
sparse)``, ``loss_fn``, ``param_specs(config)``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ray_shuffling_data_loader_tpu.models import mlp as mlp_mod
from ray_shuffling_data_loader_tpu.ops import embedding
from ray_shuffling_data_loader_tpu.runtime import telemetry

# The names a device trace shows the step's operations under: the loop of
# embedding lookups, one a sparse column, with their backward (a table's
# scatter-add; under a mesh the shards' exchange, which keeps its own name
# inside this one: ``ops/embedding.py``); the Gram product, the triangle's
# gather and the concatenation; the bottom and the top MLP, and the loss,
# which fuses into the top MLP's last layer.
LOOKUP_SCOPE = telemetry.step_scope("rsdl.dlrm.lookup")
INTERACTION_SCOPE = telemetry.step_scope("rsdl.dlrm.interaction")
MLP_SCOPE = telemetry.step_scope("rsdl.dlrm.mlp")

# The reference DATA_SPEC's categorical cardinalities
# (reference: data_generation.py:74-95): 17 embedding columns + 2 one-hots.
DATA_SPEC_VOCAB_SIZES: Tuple[int, ...] = (
    2385, 201, 201, 6, 19, 1441, 201, 22, 156, 1216, 9216, 88999, 941792,
    9405, 83332, 828767, 945195, 3, 50)


@dataclasses.dataclass(frozen=True)
class DLRMConfig:
    vocab_sizes: Tuple[int, ...] = DATA_SPEC_VOCAB_SIZES
    embed_dim: int = 32
    dense_dim: int = 0  # the reference schema has no dense features
    bottom_hidden: Tuple[int, ...] = (64,)
    top_hidden: Tuple[int, ...] = (512, 256)
    compute_dtype: Any = jnp.bfloat16
    # Embedding lookup strategy (ops/embedding.py). "auto" sends tables
    # of <= 2048 rows through a one-hot MXU matmul; larger ones go through
    # the scalar-prefetch Pallas gather on the chip when embed_dim is a
    # multiple of 128, and through XLA gather otherwise (narrower embed
    # dims, and every backend that is not a TPU).
    lookup_mode: str = "auto"

    @property
    def num_sparse(self) -> int:
        return len(self.vocab_sizes)

    @property
    def num_interacting(self) -> int:
        # Dense branch contributes one embed_dim vector when present.
        return self.num_sparse + (1 if self.dense_dim > 0 else 0)

    @property
    def interaction_dim(self) -> int:
        n = self.num_interacting
        return n * (n - 1) // 2

    @property
    def top_in_dim(self) -> int:
        base = self.interaction_dim
        if self.dense_dim > 0:
            base += self.embed_dim
        else:
            # Without a dense branch, also feed the mean embedding so the
            # top MLP sees first-order signal, not only interactions.
            base += self.embed_dim
        return base


def mlperf_config() -> DLRMConfig:
    """MLPerf-DLRM-v2-like widths on the reference's 19-column schema:
    full ``DATA_SPEC_VOCAB_SIZES``, embed 128, top MLP 1024-1024-512-256.
    The tables alone are 2,912,607 x 128 float32 = 1.49 GB, so dense Adam
    holds 4.5 GB of state — the configuration chip_smoke.py and the
    benchmark's ``dlrm-mlperf`` run at full width on one chip."""
    return DLRMConfig(embed_dim=128, top_hidden=(1024, 1024, 512, 256))


def _mlp_cfg(in_dim: int, hidden: Tuple[int, ...], out_dim: int,
             dtype) -> mlp_mod.MLPConfig:
    return mlp_mod.MLPConfig(in_dim=in_dim, hidden_dims=hidden,
                             out_dim=out_dim, compute_dtype=dtype)


def init(config: DLRMConfig, key: jax.Array) -> Dict[str, Any]:
    keys = jax.random.split(key, config.num_sparse + 2)
    params: Dict[str, Any] = {"embeddings": {}}
    for i, vocab in enumerate(config.vocab_sizes):
        params["embeddings"][f"table_{i}"] = (
            jax.random.normal(keys[i], (vocab, config.embed_dim),
                              jnp.float32) / jnp.sqrt(config.embed_dim))
    if config.dense_dim > 0:
        params["bottom"] = mlp_mod.init(
            _mlp_cfg(config.dense_dim, config.bottom_hidden,
                     config.embed_dim, config.compute_dtype),
            keys[config.num_sparse])
    params["top"] = mlp_mod.init(
        _mlp_cfg(config.top_in_dim, config.top_hidden, 1,
                 config.compute_dtype),
        keys[config.num_sparse + 1])
    return params


def param_specs(config: DLRMConfig, model_axis: str = "model"
                ) -> Dict[str, Any]:
    """Embedding dim column-sharded over the model axis; MLPs Megatron-TP."""
    specs: Dict[str, Any] = {
        "embeddings": {
            f"table_{i}": P(None, model_axis)
            for i in range(config.num_sparse)
        }
    }
    if config.dense_dim > 0:
        specs["bottom"] = mlp_mod.param_specs(
            _mlp_cfg(config.dense_dim, config.bottom_hidden,
                     config.embed_dim, config.compute_dtype), model_axis)
    specs["top"] = mlp_mod.param_specs(
        _mlp_cfg(config.top_in_dim, config.top_hidden, 1,
                 config.compute_dtype), model_axis)
    return specs


def apply(config: DLRMConfig, params: Dict[str, Any],
          dense: Optional[jax.Array], sparse,
          mesh: Optional[Mesh] = None) -> jax.Array:
    """Forward: sparse is a (batch, num_sparse) int index array OR a list
    of per-feature (batch,)/(batch, 1) index arrays — the latter is what
    ``JaxShufflingDataset`` yields with per-column narrow dtypes
    (workloads/dlrm_criteo.py). dense (batch, dense_dim) or None.
    ``mesh``: the mesh the step is jitted over when it spans more than
    one device, batch on its "data" axis — the Pallas gather needs it
    (ops/embedding.py). Returns (batch, 1) f32 logits."""
    dtype = config.compute_dtype
    is_columns = isinstance(sparse, (list, tuple))
    if is_columns and len(sparse) != config.num_sparse:
        raise ValueError(
            f"expected {config.num_sparse} sparse columns, got "
            f"{len(sparse)}")
    # One embedding lookup per feature (ops/embedding.py picks the hardware
    # path per table size). Tables are stacked feature-wise afterwards.
    vectors = []
    with jax.named_scope(LOOKUP_SCOPE):
        for i in range(config.num_sparse):
            idx = sparse[i].reshape(-1) if is_columns else sparse[:, i]
            vectors.append(
                embedding.lookup(params["embeddings"][f"table_{i}"], idx,
                                 dtype, mode=config.lookup_mode, mesh=mesh))
    if config.dense_dim > 0:
        bottom_cfg = _mlp_cfg(config.dense_dim, config.bottom_hidden,
                              config.embed_dim, dtype)
        with jax.named_scope(MLP_SCOPE):
            vectors.append(
                mlp_mod.apply(bottom_cfg, params["bottom"],
                              dense).astype(dtype))
    with jax.named_scope(INTERACTION_SCOPE):
        stacked = jnp.stack(vectors, axis=1)  # (batch, F, embed_dim)
        # Dot interaction: upper triangle of the F x F Gram matrix — one
        # batched matmul on the MXU (the DLRM signature op).
        gram = jnp.einsum("bfe,bge->bfg", stacked, stacked)
        f = stacked.shape[1]
        iu, ju = jnp.triu_indices(f, k=1)
        interactions = gram[:, iu, ju]  # (batch, F*(F-1)/2)
        first_order = jnp.mean(stacked, axis=1)  # (batch, embed_dim)
        top_in = jnp.concatenate(
            [interactions, first_order], axis=1).astype(dtype)
    top_cfg = _mlp_cfg(config.top_in_dim, config.top_hidden, 1, dtype)
    with jax.named_scope(MLP_SCOPE):
        return mlp_mod.apply(top_cfg, params["top"], top_in)


def validate_sparse_batch(config: DLRMConfig, sparse) -> None:
    """Host-side bounds check for a sparse index batch.

    ``apply`` clips out-of-range indices on device (a stray bad index must
    not NaN a step), which also means a *systematically* broken pipeline
    would train silently on edge rows — run this on the host batch (e.g.
    every N steps or in a debug mode) to surface corruption loudly.
    Accepts both batch layouts ``apply`` does: one (batch, num_sparse)
    array or a list of per-feature (batch,)/(batch, 1) columns.
    """
    import numpy as np
    if isinstance(sparse, (list, tuple)):
        if len(sparse) != config.num_sparse:
            raise ValueError(
                f"expected {config.num_sparse} sparse columns, got "
                f"{len(sparse)}")
        columns = [np.asarray(c).reshape(-1) for c in sparse]
    else:
        arr = np.asarray(sparse)
        if arr.shape[-1] != config.num_sparse:
            raise ValueError(
                f"expected {config.num_sparse} sparse features, got "
                f"{arr.shape[-1]}")
        columns = [arr[:, i] for i in range(config.num_sparse)]
    for i, (col, vocab) in enumerate(zip(columns, config.vocab_sizes)):
        lo, hi = col.min(), col.max()
        if lo < 0 or hi >= vocab:
            raise ValueError(
                f"sparse feature {i} has indices in [{lo}, {hi}] "
                f"outside vocab [0, {vocab})")


def loss_fn(config: DLRMConfig, params: Dict[str, Any],
            dense: Optional[jax.Array], sparse,
            labels: jax.Array, mesh: Optional[Mesh] = None) -> jax.Array:
    """Sigmoid BCE-with-logits, mean over the batch. ``mesh`` as in
    :func:`apply`."""
    logits = apply(config, params, dense, sparse, mesh)
    with jax.named_scope(MLP_SCOPE):
        return jnp.mean(
            jnp.maximum(logits, 0) - logits * labels
            + jnp.log1p(jnp.exp(-jnp.abs(logits))))
