"""What block diffusion's noise draws for ``sdar_train_8k``'s compared
steps at a seed, off the chip: the draw is a pure function of the step's
key and the batch's shape (threefry gives the same numbers on every
platform), so which seeds hold a token that weighs hundreds is known
before a run.

    python3 -m chipbench.probes.sdar_noise --seeds 4700002104 4700005003

One line a seed and a step (``--steps``, the comparison's three by
default): the masked tokens, the heaviest weight ``1 / p`` among them,
its share of the step's sum of squared weights (what a one-row step's
gradient is made of) and the weights' sum a token. The key is the
harness's: ``fold_in(fold_in(seed_key(seed), 1), step)``
(``loops/train.py``, ``adapters/sdar.py``); the draw is the reference's
(``references/sdar.py:noise``), which the program's equals to the bit
(``tests/test_sdar.py``).
"""

from __future__ import annotations

import argparse
import sys
from typing import Dict, List, Optional

from chipbench import check, harness, manifest


def step_weights(sizes: Dict, seed: int, step: int, rows: int) -> Dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.references import sdar as ref
    key = jax.random.fold_in(
        jax.random.fold_in(harness.seed_key(seed), 1), step)
    tokens = jnp.zeros((rows, sizes["seq_len"]), jnp.int32)
    _, masked, weights = ref.noise(tokens, key, sizes["block_length"],
                                   sizes["mask_token_id"],
                                   sizes["noise_eps"])
    weights = np.where(np.asarray(masked), np.asarray(weights), 0.0)
    return {"masked": int(np.asarray(masked).sum()),
            "heaviest": float(weights.max()),
            "share_of_squares": float(weights.max() ** 2
                                      / np.sum(weights ** 2)),
            "sum_a_token": float(weights.sum() / weights.size)}


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(prog="chipbench.probes.sdar_noise")
    parser.add_argument("--workload", default="sdar_train_8k")
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--steps", type=int, default=check.STEPS)
    args = parser.parse_args(argv)
    cell = manifest.resolve_cell(args.workload)
    sizes, rows = cell.config, cell.config["batching"]["batch_per_device"]
    for seed in args.seeds:
        for step in range(args.steps):
            got = step_weights(sizes, seed, step, rows)
            print(f"# noise seed {seed} step {step}: masked "
                  f"{got['masked']} of {rows * sizes['seq_len']}, heaviest "
                  f"weight {got['heaviest']:.1f}, "
                  f"{100 * got['share_of_squares']:.1f} % of the squares' "
                  f"sum, weights sum to {got['sum_a_token']:.4f} a token",
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
