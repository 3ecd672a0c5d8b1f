"""TPU embedding lookup strategies for DLRM-style sparse features.

The reference never runs a real model (its train step is mocked,
reference: ray_torch_shuffle.py:199-204); our DLRM flagship does 19 table
lookups per step (models/dlrm.py), so the lookup is the model's hot
non-matmul op. Three strategies, dispatched by :func:`lookup`:

- ``take``: ``jnp.take(..., mode="clip")`` — XLA's native gather.
- ``one_hot``: encode indices as a ``(batch, vocab)`` one-hot and matmul
  with the table. Random-access gathers underuse the TPU (they issue from
  the scalar/vector units against 512-byte HBM granules); a one-hot matmul
  rides the MXU's systolic array instead. For small vocabularies the
  (batch x vocab) FLOP waste is far cheaper than the gather's latency —
  the standard TPU trick for small embedding tables. Exact: each output
  row is 1.0 times one table row, so even bf16 results match the gather
  bit-for-bit.
- ``pallas``: a Pallas kernel using ``PrefetchScalarGridSpec`` — indices
  are scalar-prefetched into SMEM so each grid step's BlockSpec index_map
  selects the table row to DMA HBM->VMEM, overlapping row fetches with the
  pipeline. Backward is an XLA scatter-add via ``custom_vjp``. Under a
  multi-device mesh the gather runs once per data shard against the
  replicated table, and the backward moves the looked-up rows' gradients
  between the shards, not the table's dense gradient
  (:func:`pallas_lookup`).

``auto`` picks ``one_hot`` for vocab <= ONE_HOT_MAX_VOCAB; above it, the
Pallas gather on a real TPU when the embed dim is 128-lane aligned,
else XLA ``take``. (Whether the gather beats ``take`` on the chip is not
measured by the benchmark: ROADMAP A7.)
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ray_shuffling_data_loader_tpu.ops import on_tpu
from ray_shuffling_data_loader_tpu.parallel.mesh import DATA_AXIS
from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics
from ray_shuffling_data_loader_tpu.runtime import telemetry

#: The name a device trace shows the four-chip lookup's backward under: the
#: shards' exchange of indices and cotangent rows and the scatter-add of
#: all of them.
GRAD_EXCHANGE_SCOPE = telemetry.step_scope("rsdl.embedding.grad_exchange")

# Above this vocab size the one-hot matmul's wasted FLOPs and VMEM
# pressure outgrow the gather's latency; 2048 keeps the one-hot tile
# within a few MXU passes at typical batch sizes.
ONE_HOT_MAX_VOCAB = 2048


def take_lookup(table: jax.Array, indices: jax.Array,
                dtype: Any) -> jax.Array:
    """XLA gather. mode="clip" so a stray bad index cannot NaN the step
    (models/dlrm.py validates batches host-side instead)."""
    return jnp.take(table.astype(dtype), indices, axis=0, mode="clip")


def one_hot_lookup(table: jax.Array, indices: jax.Array,
                   dtype: Any) -> jax.Array:
    """(batch, vocab) one-hot @ (vocab, embed) on the MXU."""
    vocab = table.shape[0]
    indices = jnp.clip(indices, 0, vocab - 1)
    one_hot = jax.nn.one_hot(indices, vocab, dtype=dtype)
    return one_hot @ table.astype(dtype)


# Output rows gathered per grid step. 8 = the float32 sublane tile, the
# minimum legal block height; it also bounds in-flight row DMAs per step.
_GATHER_BLOCK = 8


def _pallas_gather_impl(table: jax.Array, indices: jax.Array,
                        interpret: bool) -> jax.Array:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, embed_dim = table.shape
    batch = indices.shape[0]
    padded = ((batch + _GATHER_BLOCK - 1) // _GATHER_BLOCK) * _GATHER_BLOCK
    if padded != batch:
        indices = jnp.pad(indices, (0, padded - batch))

    def kernel(idx_ref, table_ref, out_ref, sems):
        i = pl.program_id(0)
        # Issue all row DMAs of this block back-to-back (HBM -> this
        # step's VMEM output block), then wait — the copies overlap.
        dmas = []
        for j in range(_GATHER_BLOCK):
            row = idx_ref[i * _GATHER_BLOCK + j]
            dma = pltpu.make_async_copy(
                table_ref.at[pl.ds(row, 1), :],
                out_ref.at[pl.ds(j, 1), :],
                sems.at[j])
            dma.start()
            dmas.append(dma)
        for dma in dmas:
            dma.wait()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(padded // _GATHER_BLOCK,),
        in_specs=[
            # The table never enters VMEM wholesale; rows are DMA'd on
            # demand straight out of HBM.
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((_GATHER_BLOCK, embed_dim),
                               lambda i, idx_ref: (i, 0)),
        scratch_shapes=[pltpu.SemaphoreType.DMA((_GATHER_BLOCK,))],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((padded, embed_dim), table.dtype),
        interpret=interpret,
    )(indices, table)
    return out[:batch] if padded != batch else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _pallas_gather(table: jax.Array, indices: jax.Array,
                   interpret: bool) -> jax.Array:
    return _pallas_gather_impl(table, indices, interpret)


def _pallas_gather_fwd(table, indices, interpret):
    return _pallas_gather_impl(table, indices, interpret), (
        indices, table.shape[0])


def _pallas_gather_bwd(interpret, residual, cotangent):
    indices, vocab = residual
    d_table = jnp.zeros((vocab, cotangent.shape[-1]),
                        cotangent.dtype).at[indices].add(cotangent)
    return d_table, None


_pallas_gather.defvjp(_pallas_gather_fwd, _pallas_gather_bwd)


def _shard_gather(mesh: Mesh, interpret: bool):
    """The Pallas gather once per shard of the mesh's "data" axis: table
    replicated, indices and the rows they select split over that axis."""
    return jax.shard_map(
        lambda table, indices: _pallas_gather(table, indices, interpret),
        mesh=mesh, in_specs=(P(), P(DATA_AXIS)), out_specs=P(DATA_AXIS),
        check_vma=False)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _mesh_gather_rows(table: jax.Array, indices: jax.Array, mesh: Mesh,
                      interpret: bool) -> jax.Array:
    """:func:`_shard_gather` with a backward of its own. Left to
    ``shard_map``'s transpose, the replicated table's cotangent is each
    shard's dense ``f32[vocab, embed]`` scatter, summed over the data
    axis by an all-reduce of the table's size. Here the shards exchange
    what they looked up instead (indices and cotangent rows, all-gathered)
    and every one scatter-adds all ``global_batch`` rows itself: the same
    replicated sum, and nothing of the table's size crosses the ICI."""
    return _shard_gather(mesh, interpret)(table, indices)


def _mesh_gather_rows_fwd(table, indices, mesh, interpret):
    return _shard_gather(mesh, interpret)(table, indices), (
        indices, table.shape[0])


def _mesh_gather_rows_bwd(mesh, interpret, residual, cotangent):
    indices, vocab = residual

    def exchange(indices, cotangent):
        with jax.named_scope(GRAD_EXCHANGE_SCOPE):
            indices = jax.lax.all_gather(indices, DATA_AXIS, tiled=True)
            cotangent = jax.lax.all_gather(cotangent, DATA_AXIS, tiled=True)
            return jnp.zeros((vocab, cotangent.shape[-1]),
                             cotangent.dtype).at[indices].add(cotangent)

    d_table = jax.shard_map(
        exchange, mesh=mesh, in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=P(), check_vma=False)(indices, cotangent)
    return d_table, None


_mesh_gather_rows.defvjp(_mesh_gather_rows_fwd, _mesh_gather_rows_bwd)


def pallas_lookup(table: jax.Array, indices: jax.Array, dtype: Any,
                  mesh: Optional[Mesh] = None) -> jax.Array:
    """Pallas scalar-prefetch row gather (interpret mode off-TPU).

    On real TPUs Mosaic requires HBM row-slice DMAs to be 128-lane
    aligned, so tables whose embed dim is not a multiple of 128 fall back
    to the XLA gather (numerically identical).

    GSPMD cannot partition a Mosaic kernel, so a step jitted over more
    than one device must say so: with ``mesh``, the gather runs once per
    shard of the mesh's "data" axis under ``shard_map`` — the table
    replicated, the indices and the rows they select split over that
    axis. The table's gradient comes back replicated, and how the shards
    agree on it follows the shapes: with fewer rows looked up than the
    table holds (``global_batch < vocab``) they all-gather the indices and
    the cotangent rows and each scatter-adds all of them
    (:func:`_mesh_gather_rows`); otherwise each scatters its own rows and
    the dense gradients are all-reduced (``shard_map``'s own transpose),
    which then moves fewer bytes. ``rsdl_embedding_grad_exchange_total``
    counts the lookups traced either way.
    """
    vocab, embed_dim = table.shape
    interpret = not on_tpu()
    if not interpret and embed_dim % 128 != 0:
        return take_lookup(table, indices, dtype)
    indices = jnp.clip(indices.astype(jnp.int32), 0, vocab - 1)
    # Gather in the table's storage dtype and cast afterwards: Mosaic
    # supports single-row HBM DMAs for 4-byte types but not 2-byte ones,
    # and cast-then-gather == gather-then-cast elementwise.
    if mesh is None or mesh.size == 1:
        return _pallas_gather(table, indices, interpret).astype(dtype)
    exchange = "rows" if indices.shape[0] < vocab else "dense"
    rt_metrics.counter(
        "rsdl_embedding_grad_exchange_total",
        "Pallas lookups traced under a mesh, by how the replicated "
        "table's gradient crosses the data axis: looked-up rows "
        "all-gathered, or the dense gradient all-reduced",
        kind=exchange).inc()
    if exchange == "rows":
        rows = _mesh_gather_rows(table, indices, mesh, interpret)
    else:
        rows = _shard_gather(mesh, interpret)(table, indices)
    return rows.astype(dtype)


def _auto_mode(vocab: int, embed_dim: int) -> str:
    if vocab <= ONE_HOT_MAX_VOCAB:
        return "one_hot"
    if on_tpu() and embed_dim % 128 == 0:
        return "pallas"
    return "take"


def lookup(table: jax.Array,
           indices: jax.Array,
           dtype: Any,
           mode: str = "auto",
           mesh: Optional[Mesh] = None) -> jax.Array:
    """Embedding lookup: ``table (vocab, embed)``, ``indices (batch,)`` ->
    ``(batch, embed)`` in ``dtype``. All modes clip out-of-range indices
    and return bit-identical results; they differ only in which hardware
    unit does the work. ``mesh``: the mesh the calling step is jitted
    over, when it spans more than one device (see :func:`pallas_lookup`;
    the XLA modes partition themselves and ignore it)."""
    if mode == "auto":
        mode = _auto_mode(table.shape[0], table.shape[1])
    if mode == "take":
        return take_lookup(table, indices, dtype)
    if mode == "one_hot":
        return one_hot_lookup(table, indices, dtype)
    if mode == "pallas":
        return pallas_lookup(table, indices, dtype, mesh)
    raise ValueError(
        f"unknown lookup mode {mode!r}; expected auto/take/one_hot/pallas")
