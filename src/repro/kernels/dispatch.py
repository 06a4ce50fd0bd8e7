"""One source of truth for Pallas execution mode.

Every kernel wrapper in this package takes ``interpret: bool | None``
and resolves it here: compiled where the kernel actually lowers (a TPU
default backend), the Pallas interpreter everywhere else (CPU test
runs).  ``cg_fused``'s ``use_pallas=None`` auto-dispatch keys off the
same predicate — interpret-mode Pallas would only add per-block overhead
where XLA already fuses the pure-jnp reference.

Keeping the predicate in one place is what the kernel sanitizer
(``repro.analysis.sanitize_kernels``) audits against, and what the TPU
compile tests (``tests/test_tpu_compile.py``) steer to lower the kernels
for a described chip from a CPU-only process.
"""
from __future__ import annotations

import jax


def compiled_backend() -> bool:
    """True when Pallas kernels should lower for real instead of running
    in the interpreter: the default JAX backend is a TPU."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve a kernel wrapper's ``interpret`` argument: an explicit
    bool wins; ``None`` auto-detects via :func:`compiled_backend`."""
    if interpret is not None:
        return interpret
    return not compiled_backend()
