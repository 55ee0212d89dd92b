"""The one test for "this process runs on the TPU".

Every branch between the chip's programs (Pallas kernels, fused stage
programs, buffer donation, the armed wave watchdog, the device tiers)
and the host's asks `on_tpu()` when it is called, never while a module
is imported: importing the kernels must not bind the chip, and a test
steers the branch by monkeypatching this function.
"""

from __future__ import annotations


def on_tpu() -> bool:
    """Whether JAX's default backend is the TPU (binds the backend)."""
    import jax

    return jax.default_backend() == "tpu"
