"""Pallas TPU kernels.  Each kernel package holds ``kernel.py`` (the
``pallas_call``), ``ops.py`` (jitted public wrappers) and ``ref.py`` (a
pure-jnp oracle)."""
import jax


def default_interpret() -> bool:
    """Whether a Pallas kernel runs in the interpreter: on a TPU backend the
    compiled kernel runs, anywhere else interpret mode keeps it testable.
    Every ops wrapper resolves ``interpret=None`` through this."""
    return jax.default_backend() != "tpu"
