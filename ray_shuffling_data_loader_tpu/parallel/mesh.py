"""Device mesh construction helpers.

The reference's gradient plane is Horovod/NCCL allreduce and its control
plane is Ray GCS (SURVEY.md §2.4). TPU-native, both collapse into the XLA
device mesh: ``jax.sharding.Mesh`` over the slice's chips, gradients
synced by XLA collectives over ICI (inserted automatically under jit from
sharding annotations; the one written by hand is the row exchange of the
Pallas embedding lookup's backward, ``ops/embedding.py``), multi-host
coordination via ``jax.distributed.initialize``.

Axis convention used across the framework:
- ``"data"``  — batch-dim sharding (DP). One trainer rank per data-axis
  host group replaces the reference's Horovod ranks.
- ``"model"`` — tensor-parallel sharding of params (TP / column-parallel
  embeddings in models/).
- ``"expert"`` — the chips that share a sparse-expert layer, each holding
  a share of its experts. A name only: no mesh here has the axis yet. A
  chip's share is told to the layer as ``held = (first, count)``
  (``ops/moe.py``), and one chip runs it without its exchange.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"
EXPERT_AXIS = "expert"


def make_mesh(num_devices: Optional[int] = None,
              model_parallel: int = 1,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a ("data", "model") mesh.

    ``model_parallel`` chips per model group; the rest is the data axis.
    With the default ``model_parallel=1`` this is pure DP — the
    configuration that matches the reference's Horovod example
    (reference: ray_torch_shuffle.py:161-177).
    """
    if devices is None:
        devices = jax.devices()
    if num_devices is not None:
        devices = devices[:num_devices]
    n = len(devices)
    if n % model_parallel != 0:
        raise ValueError(
            f"model_parallel={model_parallel} must divide device count {n}")
    grid = np.asarray(devices).reshape(n // model_parallel, model_parallel)
    return Mesh(grid, (DATA_AXIS, MODEL_AXIS))


def batch_sharding(mesh: Mesh, ndim: int = 2,
                   data_axis: str = DATA_AXIS) -> NamedSharding:
    """Leading-axis (batch) sharding for an ndim-rank array."""
    return NamedSharding(mesh, P(data_axis, *([None] * (ndim - 1))))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def local_data_shard_info():
    """(rank, world) for per-host loader sharding — the multi-host analog
    of the reference's (hvd.rank(), hvd.size()).

    One loader process runs per host (jax.distributed), each feeding all
    of its local chips, so trainer rank = process index and world =
    process count — independent of chips-per-host or mesh layout.
    """
    return jax.process_index(), jax.process_count()
