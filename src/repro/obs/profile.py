"""``jax.profiler`` bracketing for engine steps.

:func:`jax_profile` wraps a region in a ``jax.profiler.TraceAnnotation``
so serving-engine steps show up named inside a JAX/XLA profiler capture
(``jax.profiler.trace(...)`` → TensorBoard/Perfetto).  JAX is imported
at first use, so importing :mod:`repro.obs` (cluster parents, sleep
workers) never loads it.
"""
from __future__ import annotations

import contextlib
from typing import Any, Iterator


@contextlib.contextmanager
def jax_profile(name: str, **kwargs: Any) -> Iterator[None]:
    """Annotate the enclosed region in any active JAX profiler capture."""
    from jax.profiler import TraceAnnotation
    with TraceAnnotation(name, **kwargs):
        yield
