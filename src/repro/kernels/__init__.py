"""Pallas kernels for the compute hot-spots the paper optimizes.

Every kernel decides its execution mode in one place, :func:`interpret_mode`:
the Pallas interpreter on the CPU backend (how the tests run them), the
Mosaic compiler on every other backend.  No caller passes the flag.
"""
import jax


def interpret_mode() -> bool:
    """True when Pallas kernels must run in the interpreter (CPU backend)."""
    return jax.default_backend() == "cpu"
