"""The controls of ``sdar_train_8k``'s limits that are faults of the
mechanism: ``probes/decoder_steps.py`` with the fault put in before
anything is traced.

    python3 -m chipbench.probes.sdar_controls --without block-mask \
        --workload sdar_train_8k --first-seed 4700003001 --seeds 1
    python3 -m chipbench.probes.sdar_controls --without restarted-positions ...
    python3 -m chipbench.probes.sdar_controls --without loss-weights ...

``block-mask``: attention plainly causal over the row's 2 L positions (a
noised query then sees every clean position, its own among them, and the
noised ones before it). ``restarted-positions``: the rotary positions run
on from L through the second copy, 0..2 L - 1, where both copies stand at
0..L - 1. ``loss-weights``: a masked position's ``1 / p`` left out of the
loss (every weight 1). The comparison has to refuse each; none says
anything about a sound program, and the program gains no switch for them.
The two precision controls are the harness's and ``decoder_steps``'s own:
``python3 -m chipbench.run --workload sdar_train_8k ... --control
ref_bf16`` (or ``decoder_steps --control-seeds``) and ``--control
bf16_params``. Every other argument is ``decoder_steps``'s.
"""

from __future__ import annotations

import argparse
import sys
from typing import List, Optional


def _causal_in_the_masks_place() -> None:
    from ray_shuffling_data_loader_tpu.models import mellum
    from ray_shuffling_data_loader_tpu.ops import flash_attention as fa

    def causal(kernels, at: int):
        def launched(*args, diffusion=None, **kwargs):
            args = list(args)
            args[at] = True
            return kernels(*args, **kwargs)
        return launched

    # grouped_forward(q, k, v, heads, kv_heads, causal, ...) and
    # grouped_backward(q, k, v, out, lse, do, heads, kv_heads, causal, ...)
    fa.grouped_forward = causal(fa.grouped_forward, 5)
    fa.grouped_backward = causal(fa.grouped_backward, 8)
    # XLA's inline attention (off the chip): its own causal mask
    fa.diffusion_seen = lambda *diffusion: None
    mellum._count_diffusion_tiles = lambda diffusion: None


def _positions_run_on() -> None:
    from ray_shuffling_data_loader_tpu.models import mellum
    mellum._twice_rope_tables = mellum._rope_tables


def _weights_left_out() -> None:
    import jax.numpy as jnp

    from ray_shuffling_data_loader_tpu.models import mellum

    drawn = mellum._noised_beside_clean

    def unweighted(*args):
        both, masked, weights = drawn(*args)
        return both, masked, jnp.ones_like(weights)

    mellum._noised_beside_clean = unweighted


FAULTS = {"block-mask": _causal_in_the_masks_place,
          "restarted-positions": _positions_run_on,
          "loss-weights": _weights_left_out}


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(prog="chipbench.probes.sdar_controls")
    parser.add_argument("--without", required=True, choices=sorted(FAULTS))
    args, rest = parser.parse_known_args(argv)
    FAULTS[args.without]()
    print(f"# control: the program without {args.without}", flush=True)
    from chipbench.probes import decoder_steps
    return decoder_steps.main(rest)


if __name__ == "__main__":
    sys.exit(main())
