"""The reference's trajectory as ``chipbench/check.py`` had it before PR 28
(one un-donated jit of Adam over the whole tree, the starting parameters
kept beside the moving ones: eight copies of the parameters at its peak),
kept word for word as the oracle of the one that took its place: the
arithmetic may not change, so every number has to come out equal. The one
addition is ``observe``, called while an update's inputs and outputs are
both alive, for the test of the footprint.
"""

import time

import jax
import jax.numpy as jnp

from chipbench import check


def old_reference_trajectory(ref, sizes, params0, batches, opt, seed_key,
                             lower_precision=False, observe=None):
    if opt["name"] != "adam":
        raise ValueError(f"the reference knows Adam, not {opt['name']!r}")
    if lower_precision:
        params0 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params0)
    with jax.default_matmul_precision(
            "default" if lower_precision else "highest"):
        params = params0
        mu = jax.tree.map(jnp.zeros_like, params0)
        nu = jax.tree.map(jnp.zeros_like, params0)
        losses = []
        grad_norms = {}
        for i, (features, labels) in enumerate(batches):
            t0 = time.perf_counter()
            value, grads = ref.value_and_grad(sizes, params, features,
                                              labels, i, seed_key)
            losses.append(float(value))
            print(f"# reference step {i}: loss and gradient "
                  f"{time.perf_counter() - t0:.2f} s", flush=True)
            if i == 0:
                grad_norms = check.leaf_norms(grads)
            updated = jax.jit(
                lambda p, g, m, v, c=i + 1: check.adam_update(
                    p, g, m, v, c, opt))(params, grads, mu, nu)
            if observe is not None:
                observe()
            params, mu, nu = updated
        return {"losses": losses, "grad_norms": grad_norms,
                "change_norms": check.diff_norms(params, params0)}


class BothTrajectories:
    """In ``check.reference_trajectory``'s place for the length of a
    rehearsal: runs the new function and the old one on the same
    arguments, keeps both results, and hands the new one on."""

    def __init__(self):
        self.new = check.reference_trajectory
        self.pairs = []

    def __call__(self, ref, sizes, make_params0, batches, opt, seed_key,
                 lower_precision=False):
        new = self.new(ref, sizes, make_params0, batches, opt, seed_key,
                       lower_precision=lower_precision)
        old = old_reference_trajectory(ref, sizes, make_params0(), batches,
                                       opt, seed_key,
                                       lower_precision=lower_precision)
        self.pairs.append((lower_precision, new, old))
        return new
