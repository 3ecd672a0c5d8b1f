"""The comparison that decides ``correct`` for a train cell: the program's
first steps against the plain reference's.

The program's numbers are read off the very trainer the window then
drives: each step's loss, the norm of the first gradient as the optimizer
got it (Adam's first moment after one step is ``(1 - b1) * g``), and the
norm of the parameters' change after the compared steps. The reference
follows the same batches from the same seeded weights in float32 with
``jax.default_matmul_precision("highest")`` and a plain Adam written here.
Norms are compared leaf by leaf, as the gap between the two norms over the
reference's norm of that leaf or of the median leaf, whichever is larger.

Which numbers decide follows the names in the configuration's ``limits``
(``compare``): a loss is held over all the compared steps (``loss_gap``) or
at the seeded weights alone (``first_loss_gap``), the parameters' change
by its worst leaf (``param_change_norm_gap``) or by its median leaf
(``param_change_median_leaf_gap``). The second of each pair is for a model
whose later steps are ill-conditioned at some seeds (``dlrm-mlperf``:
where the first batch's residuals happen to average to nothing, Adam
normalises a gradient that is all noise, and the top MLP's path after the
first step swings by several per cent between float32 and bfloat16
arithmetic that agree on every gradient).
"""

from __future__ import annotations

import dataclasses
import math
import statistics
import time
from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

STEPS = 3


@dataclasses.dataclass
class Compared:
    """One number of the comparison beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit

    def line(self) -> str:
        verdict = "ok" if self.ok else "FAILED"
        return (f"# compared {self.name}: {self.value:.6g} "
                f"(limit {self.limit:g}) {verdict}")


def leaf_norms(tree: Any) -> Dict[str, float]:
    """Euclidean norm of every leaf, by its path, as host floats."""
    norms = jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))(
            tree)
    flat, _ = jax.tree_util.tree_flatten_with_path(norms)
    return {jax.tree_util.keystr(path): float(value) for path, value in flat}


def diff_norms(after: Any, before: Any) -> Dict[str, float]:
    return leaf_norms(jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))(
            after, before))


def leaf_gaps(program: Dict[str, float], reference: Dict[str, float]
              ) -> Dict[str, float]:
    """Per leaf, ``|program - reference|`` over the larger of the
    reference's norm of that leaf and of its median leaf."""
    if program.keys() != reference.keys():
        raise ValueError("the program's and the reference's parameters have "
                         f"different leaves: {sorted(program)[:3]}... vs "
                         f"{sorted(reference)[:3]}...")
    floor = statistics.median(reference.values())
    gaps = {}
    for leaf, ref in reference.items():
        scale = max(ref, floor)
        gap = abs(program[leaf] - ref) / scale if scale > 0 else (
            0.0 if program[leaf] == 0 else math.inf)
        gaps[leaf] = gap if math.isfinite(gap) else math.inf
    return gaps


def worst_leaf_gap(program: Dict[str, float], reference: Dict[str, float]
                   ) -> Tuple[float, str]:
    """(largest gap, its leaf)."""
    worst, where = 0.0, ""
    for leaf, gap in leaf_gaps(program, reference).items():
        if gap == math.inf:
            return math.inf, leaf
        if gap >= worst:
            worst, where = gap, leaf
    return worst, where


def median_leaf_gap(program: Dict[str, float], reference: Dict[str, float]
                    ) -> float:
    """The median of the leaves' gaps: what every leaf's change is off by
    when the arithmetic is (a lower precision moves them all), blind to the
    few leaves whose path is ill-conditioned at a seed."""
    return statistics.median(leaf_gaps(program, reference).values())


def adam_update(params, grads, mu, nu, count: int, opt: Dict[str, Any]):
    """One step of Adam (Kingma & Ba 2015, bias-corrected, no decay)."""
    b1, b2, eps, lr = opt["b1"], opt["b2"], opt["eps"], opt["learning_rate"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        params, mu, nu)
    return params, mu, nu


def copy_of(tree: Any) -> Callable[[], Any]:
    """A ``make_params0`` for ``reference_trajectory`` over a tree the
    caller goes on using: every call hands out a fresh copy."""
    return lambda: jax.tree.map(jnp.copy, tree)


def _adam_in_place(count: int, opt: Dict[str, Any]):
    """``adam_update`` with the parameters' and both moments' buffers given
    to its outputs: the update costs no memory of its own (the TPU and the
    CPU both honour the donation; three copies where a backend does not)."""
    return jax.jit(lambda p, g, m, v: adam_update(p, g, m, v, count, opt),
                   donate_argnums=(0, 2, 3))


def reference_trajectory(ref, sizes: Dict[str, Any],
                         make_params0: Callable[[], Any],
                         batches: Sequence[Tuple[Sequence[Any], Any]],
                         opt: Dict[str, Any], seed_key,
                         lower_precision: bool = False) -> Dict[str, Any]:
    """Losses, first-gradient norms and parameter-change norms of the
    plain reference over ``batches`` from the parameters that
    ``make_params0`` returns.

    The footprint is the program's own, 16 bytes a float32 parameter (the
    parameters, Adam's two moments, one gradient) plus the reference's
    activations. So no copy of the starting parameters is kept while the
    steps run: the trajectory owns what ``make_params0`` returns and
    updates it in place, drops each gradient once Adam has read it, and
    calls ``make_params0`` again at the end, the moments freed, for the
    norm of the change (three copies: both parameters and their
    difference). A caller that goes on using its starting tree passes
    ``copy_of(tree)``.

    ``lower_precision`` is the control: the same reference with its
    parameters, moments and arithmetic in bfloat16, the precision below
    the float32 parameters the configurations state. Put in the program's
    place, it has to come out as not correct."""
    if opt["name"] != "adam":
        raise ValueError(f"the reference knows Adam, not {opt['name']!r}")

    def start():
        params0 = make_params0()
        if lower_precision:
            params0 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params0)
        return params0

    with jax.default_matmul_precision(
            "default" if lower_precision else "highest"):
        params = start()
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        losses: List[float] = []
        grad_norms: Dict[str, float] = {}
        for i, (features, labels) in enumerate(batches):
            t0 = time.perf_counter()
            value, grads = ref.value_and_grad(sizes, params, features,
                                              labels, i, seed_key)
            losses.append(float(value))
            print(f"# reference step {i}: loss and gradient "
                  f"{time.perf_counter() - t0:.2f} s", flush=True)
            if i == 0:
                grad_norms = leaf_norms(grads)
            params, mu, nu = _adam_in_place(i + 1, opt)(params, grads, mu, nu)
            # Dispatch is asynchronous: a gradient dropped while its reader
            # still runs is freed after the next one has been allocated.
            jax.block_until_ready(nu)
            del grads
        del mu, nu
        return {"losses": losses, "grad_norms": grad_norms,
                "change_norms": diff_norms(params, start())}


def step_loss_gaps(program: Dict[str, Any], reference: Dict[str, Any]
                   ) -> List[float]:
    return [abs(p - r) / max(abs(r), 1e-12)
            for p, r in zip(program["losses"], reference["losses"])]


def compare(program: Dict[str, Any], reference: Dict[str, Any],
            limits: Dict[str, float]) -> List[Compared]:
    """The numbers of the comparison, each beside its limit: one for every
    name in ``limits``, in this order."""
    losses = step_loss_gaps(program, reference)
    grad_gap, grad_leaf = worst_leaf_gap(program["grad_norms"],
                                         reference["grad_norms"])
    change_gap, change_leaf = worst_leaf_gap(program["change_norms"],
                                             reference["change_norms"])
    numbers = {
        "loss_gap": ("loss_gap", max(losses)),
        "first_loss_gap": ("first_loss_gap", losses[0]),
        "first_grad_norm_gap": (f"first_grad_norm_gap[{grad_leaf}]",
                                grad_gap),
        "param_change_norm_gap": (f"param_change_norm_gap[{change_leaf}]",
                                  change_gap),
        "param_change_median_leaf_gap": (
            "param_change_median_leaf_gap",
            median_leaf_gap(program["change_norms"],
                            reference["change_norms"])),
    }
    unknown = sorted(set(limits) - set(numbers))
    if unknown:
        raise ValueError(f"limits name {unknown}; the comparison knows "
                         f"{sorted(numbers)}")
    if not ({"loss_gap", "first_loss_gap"} & set(limits)
            and "first_grad_norm_gap" in limits
            and {"param_change_norm_gap",
                 "param_change_median_leaf_gap"} & set(limits)):
        raise ValueError("limits hold a loss, first_grad_norm_gap and a "
                         f"change of the parameters; got {sorted(limits)}")
    return [Compared(*numbers[name], limits[name])
            for name in numbers if name in limits]


def not_compared(program: Dict[str, Any], reference: Dict[str, Any],
                 limits: Dict[str, float]) -> str:
    """The numbers that ``limits`` leaves out, for the reader of a run."""
    losses = step_loss_gaps(program, reference)
    change_gap, change_leaf = worst_leaf_gap(program["change_norms"],
                                             reference["change_norms"])
    said = {
        "loss_gap": "loss gap by step " + " / ".join(
            f"{g:.6g}" for g in losses),
        "param_change_norm_gap": (f"worst leaf's change {change_gap:.6g} "
                                  f"at {change_leaf}"),
        "param_change_median_leaf_gap": "median leaf's change "
        f"{median_leaf_gap(program['change_norms'], reference['change_norms']):.6g}",
    }
    return "; ".join(v for k, v in said.items() if k not in limits)
