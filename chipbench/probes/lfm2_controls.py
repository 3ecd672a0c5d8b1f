"""The controls of ``lfm2_train_8k``'s limits that are faults of the
program: ``probes/decoder_steps.py`` with the fault put in before anything
is traced.

    python3 -m chipbench.probes.lfm2_controls --without half-the-batch \
        --workload lfm2_train_8k --first-seed 4400006001 --seeds 1
    python3 -m chipbench.probes.lfm2_controls --without bias-in-the-pick ...

``half-the-batch``: the loss sees the first half of a step's rows (what
``first_loss_gap`` and ``first_grad_norm_gap`` are held against).
``bias-in-the-pick``: the router picks by its scores alone, the selection
bias left out (``ops/moe.py:route`` given zeros in its place). The
comparison has to refuse each; neither says anything about a sound
program. Every other argument is ``decoder_steps``'s.
"""

from __future__ import annotations

import argparse
import sys
from typing import List, Optional


def _half_the_batch() -> None:
    from chipbench.adapters import lfm2

    whole = lfm2.make_loss

    def make_loss(model_cfg, sizes, mesh):
        loss = whole(model_cfg, sizes, mesh)
        return lambda params, features, *rest: loss(
            params, [f[:f.shape[0] // 2] for f in features], *rest)

    lfm2.make_loss = make_loss


def _bias_out_of_the_pick() -> None:
    import jax.numpy as jnp

    from ray_shuffling_data_loader_tpu.ops import moe

    route = moe.route
    moe.route = lambda logits, top_k, scale=1.0, bias=None: route(
        logits, top_k, scale, None if bias is None else jnp.zeros_like(bias))


FAULTS = {"half-the-batch": _half_the_batch,
          "bias-in-the-pick": _bias_out_of_the_pick}


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(prog="chipbench.probes.lfm2_controls")
    parser.add_argument("--without", required=True, choices=sorted(FAULTS))
    args, rest = parser.parse_known_args(argv)
    FAULTS[args.without]()
    print(f"# control: the program without {args.without}", flush=True)
    from chipbench.probes import decoder_steps
    return decoder_steps.main(rest)


if __name__ == "__main__":
    sys.exit(main())
