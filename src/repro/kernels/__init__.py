"""Pallas kernels for the HAIL hot path (``ops`` wraps them, ``ref`` holds
their pure-jnp oracles)."""
import jax


def interpret_default() -> bool:
    """Pallas kernels run in the interpreter exactly where JAX's default
    backend is the CPU; on a TPU they lower through Mosaic."""
    return jax.default_backend() == "cpu"
