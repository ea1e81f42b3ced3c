"""TPU Pallas kernels + jnp paths; ``ops.py`` picks between them."""
import jax


def interpret_on_this_platform() -> bool:
    """Whether a Pallas kernel must run through the interpreter: only on
    the CPU backend (tests).  On an accelerator kernels compile natively
    and never fall back to the interpreter."""
    return jax.default_backend() == "cpu"
