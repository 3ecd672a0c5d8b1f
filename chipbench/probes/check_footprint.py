"""How much of the chip the first-steps comparison takes beside a
configuration of N parameters: ``check.reference_trajectory`` over three
steps of a synthetic reference, and the allocator's peak.

    python3 -m chipbench.probes.check_footprint --params 640e6

The reference is made here: N float32 parameters in a dozen leaves of
unequal size (the largest a quarter of them), a loss whose gradient
reaches every element and that keeps no activation to speak of, so what
the peak holds is the trajectory's own copies of the parameters. The
budget it is held to is 20 bytes a parameter (the parameters, Adam's two
moments, one gradient, one copy to spare; it reads 16). Exit code 0:
finished within it. 1: finished over it. 2: no TPU, or no allocator
statistics. A trajectory that does not fit dies of the runtime's
``RESOURCE_EXHAUSTED``; before PR 28 it held 32 bytes a parameter.
"""

from __future__ import annotations

import argparse
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench import check

#: Shares of the parameters by leaf: unequal, as a model's are.
SHARES = (0.25, 0.18, 0.14, 0.11, 0.09, 0.07, 0.05, 0.04, 0.03, 0.02, 0.015,
          0.005)
BUDGET_BYTES_PER_PARAM = 20
ADAM = {"name": "adam", "learning_rate": 1e-3, "b1": 0.9, "b2": 0.999,
        "eps": 1e-8}


def leaf_sizes(params: int) -> List[int]:
    """Elements per leaf, in whole rows of 1024; they sum to ``params`` to
    within a leaf's rounding."""
    return [max(1024, int(params * share) // 1024 * 1024)
            for share in SHARES]


class Reference:
    """The plain reference's interface over the synthetic leaves: the loss
    is each leaf's mean squared distance from the batch's label, summed
    over the leaves."""

    def __init__(self, params: int):
        import jax
        import jax.numpy as jnp

        sizes = leaf_sizes(params)
        self.params = sum(sizes)

        def init(key):
            keys = jax.random.split(key, len(sizes))
            return {f"leaf_{i:02d}": jax.random.normal(k, (n // 1024, 1024),
                                                       jnp.float32)
                    for i, (k, n) in enumerate(zip(keys, sizes))}

        def loss(tree, label):
            return sum(jnp.mean(jnp.square(x - label))
                       for x in jax.tree.leaves(tree)) / 2

        self.init = jax.jit(init)
        self._value_and_grad = jax.jit(jax.value_and_grad(loss))

    def value_and_grad(self, sizes, params, features, labels, step: int = 0,
                       seed_key=None):
        return self._value_and_grad(params, labels)


def verdict(peak_bytes: int, params: int) -> bool:
    """Within 20 bytes a parameter?"""
    return peak_bytes <= BUDGET_BYTES_PER_PARAM * params


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(prog="chipbench.probes.check_footprint")
    parser.add_argument("--params", type=float, required=True,
                        help="parameters of the synthetic reference, 640e6")
    parser.add_argument("--seed", type=int, default=28)
    args = parser.parse_args(argv)

    import jax

    device = jax.devices()[0]
    stats: Dict[str, Any] = device.memory_stats() or {}
    if device.platform != "tpu" or "peak_bytes_in_use" not in stats:
        print(f"check_footprint: JAX reports {device.platform!r} and "
              f"{'no ' if 'peak_bytes_in_use' not in stats else ''}allocator "
              "statistics; the peak is the chip's to give", file=sys.stderr)
        return 2
    ref = Reference(int(args.params))
    key = jax.random.key(args.seed & 0x7FFFFFFF)
    batches = [([], np.float32(0.25 * (i + 1))) for i in range(check.STEPS)]
    before = int(stats["peak_bytes_in_use"])
    print(f"# {device.device_kind}: {ref.params} float32 parameters in "
          f"{len(SHARES)} leaves of {SHARES[-1]:.1%} to {SHARES[0]:.0%}; "
          f"one copy {4 * ref.params} B; allocator peak before "
          f"{before} B, limit {stats.get('bytes_limit')} B", flush=True)
    t0 = time.perf_counter()
    out = check.reference_trajectory(ref, {}, lambda: ref.init(key), batches,
                                     ADAM, None)
    took = time.perf_counter() - t0
    after = int(device.memory_stats()["peak_bytes_in_use"])
    ok = verdict(after, ref.params)
    print(f"# losses {out['losses']}; change norms "
          f"{sorted(out['change_norms'].values())[:2]} ...")
    print(f"# trajectory of {check.STEPS} steps in {took:.2f} s; allocator "
          f"peak after {after} B = {after / ref.params:.3f} bytes a "
          f"parameter, {after / (4 * ref.params):.3f} copies: "
          + ("within" if ok else "OVER") + f" {BUDGET_BYTES_PER_PARAM} bytes "
          "a parameter")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
