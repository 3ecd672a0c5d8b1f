"""Micro-benchmark: embedding lookup strategies (ops/embedding.py).

Times take / one_hot / pallas lookups across table sizes on the current
backend (TPU if available), fwd and fwd+bwd. This is the measurement that
justifies ops/embedding.py's ``auto`` dispatch threshold; re-run on-chip
when tuning ONE_HOT_MAX_VOCAB.

Usage: python benchmarks/bench_embedding.py [--batch 65536] [--embed 32]
"""

from __future__ import annotations

import argparse
import os
import sys
import timeit

sys.path.insert(0,
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from ray_shuffling_data_loader_tpu.ops import embedding
from ray_shuffling_data_loader_tpu.utils.compile_cache import (
    enable_compile_cache)

VOCABS = [64, 512, 2048, 8192, 131072, 1048576]
MODES = ["take", "one_hot", "pallas"]


def _time(fn, *args, iters=20):
    jax.block_until_ready(fn(*args))  # compile + warm
    start = timeit.default_timer()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (timeit.default_timer() - start) / iters


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--batch", type=int, default=65_536)
    parser.add_argument("--embed", type=int, default=32)
    parser.add_argument("--iters", type=int, default=20)
    args = parser.parse_args()
    enable_compile_cache()

    print(f"backend={jax.default_backend()} batch={args.batch} "
          f"embed={args.embed}")
    rng = np.random.default_rng(0)
    for vocab in VOCABS:
        table = jnp.asarray(
            rng.standard_normal((vocab, args.embed)), jnp.float32)
        idx = jnp.asarray(rng.integers(0, vocab, args.batch), jnp.int32)
        row = [f"vocab {vocab:>8}"]
        for mode in MODES:
            if mode == "one_hot" and vocab > 65536:
                row.append(f"{mode}: skip")
                continue

            fwd = jax.jit(lambda t, i, m=mode: embedding.lookup(
                t, i, jnp.bfloat16, mode=m))
            grad = jax.jit(jax.grad(lambda t, i, m=mode: embedding.lookup(
                t, i, jnp.float32, mode=m).sum()))
            try:
                t_fwd = _time(fwd, table, idx, iters=args.iters)
                t_bwd = _time(grad, table, idx, iters=args.iters)
                row.append(f"{mode}: {t_fwd*1e3:7.3f}ms fwd "
                           f"{t_bwd*1e3:7.3f}ms bwd")
            except Exception as e:  # noqa: BLE001 - report and continue
                row.append(f"{mode}: failed ({type(e).__name__})")
        print(" | ".join(row))


if __name__ == "__main__":
    main()
