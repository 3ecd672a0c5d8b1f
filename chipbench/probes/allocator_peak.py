"""Does the runtime's ``peak_bytes_in_use`` count what a running program
holds in temporaries? The train loop's ``memory_peak_bytes`` adds the
compiled step's temporaries to the allocator's peak, which is right only
if the allocator leaves them out. This probe shows it on the chip:

    python3 -m chipbench.probes.allocator_peak

It runs the gradient of a chain of ``LAYERS`` matmuls over one shared
weight: three small buffers go in and out (weight, input, gradient), and
the backward pass keeps every layer's activation alive, about a gigabyte
of temporaries by the compiled program's own memory analysis. Then it
reads the allocator's peak. Exit code 0: the peak rose by less than a
quarter of the temporaries (they are left out, the sum does not count them
twice). 1: the peak holds them (the train loop must stop adding them).
2: no TPU, or no allocator statistics.
"""

from __future__ import annotations

import sys
from typing import Tuple

WIDTH = 4096          # float32 [4096, 4096]: 67 MB a buffer
LAYERS = 16


def verdict(temp_bytes: int, peak_before: int, peak_after: int
            ) -> Tuple[bool, float]:
    """(temporaries left out of the allocator's peak?, the share of them
    by which the peak rose)."""
    share = (peak_after - peak_before) / temp_bytes
    return share < 0.25, share


def main() -> int:
    import jax
    import jax.numpy as jnp

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"allocator_peak: JAX reports {device.platform!r}, not 'tpu'",
              file=sys.stderr)
        return 2

    def loss(w, x):
        h = x
        for _ in range(LAYERS):
            h = jnp.tanh(h @ w)
        return jnp.sum(h)

    grad = jax.jit(jax.grad(loss))
    key_w, key_x = jax.random.split(jax.random.key(0))
    w = jax.random.normal(key_w, (WIDTH, WIDTH), jnp.float32) / WIDTH ** 0.5
    x = jax.random.normal(key_x, (WIDTH, WIDTH), jnp.float32)
    jax.block_until_ready((w, x))
    memory = grad.lower(w, x).compile().memory_analysis()
    stats = device.memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        print("allocator_peak: the device gives no allocator statistics",
              file=sys.stderr)
        return 2
    before = int(stats["peak_bytes_in_use"])
    jax.block_until_ready(grad(w, x))
    after = int(device.memory_stats()["peak_bytes_in_use"])
    left_out, share = verdict(memory.temp_size_in_bytes, before, after)
    print(f"# {device.device_kind}: program arguments "
          f"{memory.argument_size_in_bytes} B, output "
          f"{memory.output_size_in_bytes} B, temporaries "
          f"{memory.temp_size_in_bytes} B (compiled program's memory "
          "analysis)")
    print(f"# allocator peak_bytes_in_use before the program ran {before} B, "
          f"after {after} B: rose by {after - before} B, "
          f"{100 * share:.2f} % of the temporaries")
    print("# the allocator's peak " + (
        "leaves a running program's temporaries out" if left_out
        else "holds a running program's temporaries"))
    return 0 if left_out else 1


if __name__ == "__main__":
    sys.exit(main())
