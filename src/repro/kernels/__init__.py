"""Pallas TPU kernels.  Each subpackage: kernel.py (pl.pallas_call +
BlockSpec), ops.py (jit'd public wrapper), ref.py (pure-jnp oracle)."""
import jax


def interpret_mode() -> bool:
    """Whether ``pl.pallas_call`` runs in the Pallas interpreter: on every
    platform but the TPU.  The platform decides, never the caller — a
    kernel the TPU compiler refuses raises there instead of quietly falling
    back to the interpreter.  Read at trace time by every kernel."""
    return jax.default_backend() != "tpu"
