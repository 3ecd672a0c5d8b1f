"""The attention kernels under block diffusion's mask at ``sdar_train_8k``'s
shape (one row of 8,192 tokens twice = 16,384 positions, 32 query heads
over 4 key/value heads of 128, bf16, blocks of 4), timed by tile, forward
and backward, beside the plainly causal walk over the same 2 L positions:
the readings ``models/mellum.py:_blocks``'s docstring quotes.

    python3 -m chipbench.probes.sdar_kernels [--tiles 1024x1024 512x512 ...]

Prints one ``# tiles`` line a tiling and a mask: milliseconds of the
forward and of the backward (the mean of ``--calls`` calls after one that
compiles) and, under the mask, ``(tiles visited, tiles that compare
positions, live pairs)`` a head (``flash_attention.diffusion_tiles``). A
tiling the kernels refuse prints the refusal. Whether the kernels are
right is ``chip_smoke.py``'s to say (it checks them against the inline
form at this shape and times nothing). Exit code 2 without a TPU
(``--allow-cpu``: interpreted, 64 tokens twice, for the tests).
"""

from __future__ import annotations

import argparse
import sys
import time
from typing import List, Optional

TILINGS = ("1024x1024", "512x512", "512x1024", "1024x512", "2048x1024",
           "256x256")


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(prog="chipbench.probes.sdar_kernels")
    parser.add_argument("--tiles", nargs="+", default=list(TILINGS))
    parser.add_argument("--calls", type=int, default=6)
    parser.add_argument("--allow-cpu", action="store_true")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from ray_shuffling_data_loader_tpu.ops import flash_attention as fa
    from ray_shuffling_data_loader_tpu.utils.compile_cache import (
        enable_compile_cache)

    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip and not args.allow_cpu:
        print("sdar_kernels: a TPU; JAX reports "
              f"{jax.devices()[0].platform!r}", file=sys.stderr)
        return 2
    if on_chip:
        enable_compile_cache()
    length, heads, kv, d, block = ((8192, 32, 4, 128, 4) if on_chip
                                   else (64, 4, 2, 16, 4))
    keys = jax.random.split(jax.random.key(0), 4)
    q, k, v, do = (jax.random.normal(
        key, (1, 2 * length, n * d),
        jnp.bfloat16 if on_chip else jnp.float32)
        for key, n in zip(keys, (heads, kv, kv, heads)))

    def timed(fn, *operands):
        out = jax.block_until_ready(fn(*operands))
        start = time.perf_counter()
        for _ in range(args.calls):
            out = fn(*operands)
        jax.block_until_ready(out)
        return 1e3 * (time.perf_counter() - start) / args.calls, out

    for name, causal, diffusion in (("diffusion", False, (block, length)),
                                    ("causal over 2L", True, None)):
        for tiling in args.tiles:
            bq, bk = (int(side) for side in tiling.split("x"))
            common = dict(interpret=not on_chip, diffusion=diffusion)
            try:
                f_ms, (out, lse) = timed(jax.jit(
                    lambda q, k, v: fa.grouped_forward(
                        q, k, v, heads, kv, causal, None, bq, bk,
                        **common)), q, k, v)
                b_ms, _ = timed(jax.jit(
                    lambda q, k, v, out, lse, do: fa.grouped_backward(
                        q, k, v, out, lse, do, heads, kv, causal, None, bq,
                        bk, **common)), q, k, v, out, lse, do)
            except ValueError as refused:
                print(f"# tiles {bq} x {bk} {name}: refused: "
                      f"{str(refused)[:300]}", flush=True)
                continue
            tiles = (fa.diffusion_tiles(block, length, bq, bk)
                     if diffusion else None)
            print(f"# tiles {bq} x {bk} {name}: forward {f_ms:.2f} ms, "
                  f"backward {b_ms:.2f} ms; tiles {tiles}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
