"""SPMD trainer: jitted train step over a device mesh.

Replaces the reference's Horovod training harness (reference:
examples/horovod/ray_torch_shuffle.py:126-243): instead of
``hvd.DistributedOptimizer`` wrapping a torch optimizer with NCCL allreduce
hooks (:173-177) and explicit parameter broadcast (:165-166), the whole
train step — forward, backward, optimizer update — is one ``jax.jit``
program over a ``Mesh``. The trainer writes no gradient synchronization:
batches arrive sharded along the "data" axis, params are replicated (or TP-
sharded along "model"), and XLA inserts the ``psum``/``all_gather``
collectives over ICI that the sharding layout implies. The one exchange
written by hand is in the loss it is given: the Pallas embedding lookup
under a mesh (``ops/embedding.py``) all-gathers the looked-up rows'
gradients rather than let the replicated table's dense gradient be
all-reduced. fp16 compression / Adasum knobs (:80-87) map to bf16 compute
in the models and optax transforms here.

The trainer owns sharded params + optimizer state and exposes
``train_step(batch) -> loss``; donation keeps params/opt-state in place in
HBM across steps (no host round-trips).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_shuffling_data_loader_tpu.parallel.mesh import DATA_AXIS
from ray_shuffling_data_loader_tpu.runtime import telemetry
from ray_shuffling_data_loader_tpu.utils import tracing
from ray_shuffling_data_loader_tpu.utils.logger import setup_custom_logger

logger = setup_custom_logger(__name__)


#: The name a device trace shows the optimizer's operations under: the
#: transformation's update (Adam's moments) and its addition to the
#: parameters.
OPTIMIZER_SCOPE = telemetry.step_scope("rsdl.train.optimizer")


def _moved(tree: Any, path: Tuple[str, ...], delta: Any) -> Any:
    """``tree`` with the leaf at ``path`` (nested dictionaries' keys) moved
    by ``delta``; nothing else is copied."""
    if not path:
        return tree + delta.astype(tree.dtype)
    return {**tree, path[0]: _moved(tree[path[0]], path[1:], delta)}


def make_train_step(loss_fn: Callable,
                    optimizer: optax.GradientTransformation) -> Callable:
    """Pure train-step function: (params, opt_state, *batch) ->
    (params, opt_state, loss), and a fourth output where the loss
    recorded any of the step's own counters while it was traced
    (``tracing.step_stat``): the ``{key: device value}`` they were
    recorded under. A loss that records none gives the three-output
    program it always gave. A leaf the loss moves by a rule of its own
    (``tracing.leaf_move``: it takes no gradient, so the optimizer leaves
    it) is moved after the optimizer's update."""
    counted_loss = tracing.with_step_stats(loss_fn)

    def train_step(params, opt_state, *batch):
        (loss, stats), grads = jax.value_and_grad(
            counted_loss, has_aux=True)(params, *batch)
        moves, stats = tracing.leaf_moves(stats)
        with jax.named_scope(OPTIMIZER_SCOPE):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            for path, delta in moves.items():
                params = _moved(params, path, delta)
        if stats:
            return params, opt_state, loss, stats
        return params, opt_state, loss

    return train_step


class SpmdTrainer:
    """Owns mesh-sharded training state and the compiled step.

    Args:
        mesh: the device mesh ("data" [, "model"]).
        loss_fn: ``loss_fn(params, *batch) -> scalar``.
        params: initial parameter pytree (host or device).
        param_specs: pytree of ``PartitionSpec`` matching ``params``
            (e.g. ``models.dlrm.param_specs(cfg)``); ``None`` = replicate
            everything (pure DP).
        optimizer: an optax ``GradientTransformation``.
    """

    def __init__(self,
                 mesh: Mesh,
                 loss_fn: Callable,
                 params: Any,
                 optimizer: optax.GradientTransformation,
                 param_specs: Optional[Any] = None,
                 donate: bool = True):
        self.mesh = mesh
        if param_specs is None:
            param_specs = jax.tree.map(lambda _: P(), params)
        self._param_shardings = jax.tree.map(
            lambda spec: NamedSharding(mesh, spec), param_specs,
            is_leaf=lambda x: isinstance(x, P))
        self.params = jax.device_put(params, self._param_shardings)
        # Optimizer state sharding is inferred by XLA from the param
        # shardings (mu/nu mirror params). Scalars such as Adam's step
        # count come back off the mesh, while the step returns them on
        # it — and a step whose input types changed between the first
        # call and the second compiles twice. Replicate them over the
        # mesh up front.
        replicated = NamedSharding(mesh, P())
        self.opt_state = jax.tree.map(
            lambda x: x if (isinstance(x.sharding, NamedSharding)
                            and x.sharding.mesh == mesh)
            else jax.device_put(x, replicated),
            jax.jit(optimizer.init)(self.params))
        step = make_train_step(loss_fn, optimizer)
        self._step = jax.jit(
            step, donate_argnums=(0, 1) if donate else ())
        self._step_count = 0

    def train_step(self, *batch) -> jax.Array:
        """One optimizer step; returns the (lazy) scalar loss. The step's
        own counters, where the loss records any, go to ``tracing``'s ring
        under this step's number, still on the device: nothing here waits
        for them."""
        with tracing.step_span(self._step_count):
            self.params, self.opt_state, loss, *stats = self._step(
                self.params, self.opt_state, *batch)
        if stats:
            tracing.keep_step_stats(self._step_count, stats[0])
        self._step_count += 1
        return loss

    @property
    def step_fn(self):
        """The jitted step, for inspection: ``.lower(...)`` shows what it
        compiles to and ``._cache_size()`` how often it compiled."""
        return self._step

    def block_until_ready(self) -> None:
        jax.block_until_ready((self.params, self.opt_state))
        tracing.fold_step_stats(wait=True)


def batch_shardings(mesh: Mesh, batch_example: Tuple,
                    data_axis: str = DATA_AXIS):
    """NamedShardings for a batch pytree: leading axis over ``data_axis``."""
    return jax.tree.map(
        lambda a: NamedSharding(
            mesh, P(data_axis, *([None] * (a.ndim - 1)))),
        batch_example)
