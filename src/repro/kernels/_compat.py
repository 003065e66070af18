"""Interpret-mode resolution shared by the Pallas kernel wrappers."""

from __future__ import annotations

import functools


@functools.cache
def default_interpret() -> bool:
    """True when the default JAX backend is CPU.

    The Pallas kernels target TPU; off-TPU they run in interpret mode so
    the whole suite is testable anywhere. The backend probe touches the
    platform registry, so it is memoized here once per process instead
    of being re-evaluated on every kernel call (it was previously inlined
    in each wrapper).
    """
    import jax

    return jax.default_backend() == "cpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret=None`` means "interpret iff running on CPU"."""
    return default_interpret() if interpret is None else bool(interpret)
