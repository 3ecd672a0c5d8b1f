"""Device kernels and their host-side twins."""

import jax


def on_tpu() -> bool:
    """The one test for "running on the chip": Pallas kernels compile
    through Mosaic there and run under the interpreter everywhere else."""
    return jax.default_backend() == "tpu"
