"""The two controls of ``phi4flash_train_8k``'s limits that are faults of
the program: ``probes/decoder_steps.py`` with the fault put in before
anything is traced.

    python3 -m chipbench.probes.phi4flash_controls --without scan-carry \
        --workload phi4flash_train_8k --first-seed 4000002001 --seeds 3
    python3 -m chipbench.probes.phi4flash_controls --without lambda ...

``scan-carry``: every chunk of the selective scan starts from a state of
zero, and hands none back (``ops/selective_scan.py:_handed_on``).
``lambda``: a differential layer's second softmax map is left out
(``models/mellum.py:_diff_lambda`` gives 0). The comparison has to refuse
either; neither says anything about a sound program. Every other argument
is ``decoder_steps``'s (its ``--without-carry`` is the Mamba-2 scan's and
does nothing here).
"""

from __future__ import annotations

import argparse
import sys
from typing import List, Optional


def _drop_the_scan_carry() -> None:
    import jax.numpy as jnp

    from ray_shuffling_data_loader_tpu.ops import selective_scan
    selective_scan._handed_on = jnp.zeros_like


def _drop_lambda() -> None:
    import jax.numpy as jnp

    from ray_shuffling_data_loader_tpu.models import mellum
    mellum._diff_lambda = lambda config, layer, lp: jnp.float32(0.0)


FAULTS = {"scan-carry": _drop_the_scan_carry, "lambda": _drop_lambda}


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(prog="chipbench.probes.phi4flash_controls")
    parser.add_argument("--without", required=True, choices=sorted(FAULTS))
    args, rest = parser.parse_known_args(argv)
    FAULTS[args.without]()
    print(f"# control: the program without {args.without}", flush=True)
    from chipbench.probes import decoder_steps
    return decoder_steps.main(rest)


if __name__ == "__main__":
    sys.exit(main())
