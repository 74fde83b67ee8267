"""Pallas kernels for the model's hot spots. Each has kernel.py (the
Pallas body), ops.py (the jitted public wrapper) and ref.py (the pure-jnp
oracle)."""
import jax


def default_interpret() -> bool:
    """The one platform rule for every kernel wrapper: compile on a TPU,
    run the kernel body in the Pallas interpreter anywhere else."""
    return jax.default_backend() != "tpu"
