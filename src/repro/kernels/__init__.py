"""Pallas TPU kernels: flash attention, paged attention, the chunked
GLA scan and GreedyTL scoring.  Each package holds the kernel, a jitted
model-layout wrapper (``ops.py``) and a pure-jnp oracle (``ref.py``)."""
import jax


def on_tpu() -> bool:
    """True when the default backend is a TPU: the ops wrappers lower their
    kernels to Mosaic there and run the Pallas interpreter everywhere else
    (the CPU test suite)."""
    return jax.default_backend() == "tpu"
